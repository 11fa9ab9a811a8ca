"""Plain references for the benchmark's comparisons, and their controls.

Built on numpy and scipy alone: nothing here imports the program under
test or takes anything it made.  Each ``*_control`` is the same reference
computed one precision step below what the configuration states — the
step a later change might be tempted to take — and must fail the cell's
comparison.
"""
from __future__ import annotations

import numpy as np
import scipy.linalg as sla
import scipy.sparse as sp


def csr(n: int, indptr, indices, data, dtype=np.float64) -> sp.csr_matrix:
    return sp.csr_matrix((np.asarray(data, dtype), indices, indptr),
                         shape=(n, n))


def bf16_round(x: np.ndarray) -> np.ndarray:
    """float32 → nearest bfloat16 (ties to even), returned as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + (np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1)))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def bf16x3_split(x: np.ndarray):
    """``x ≈ hi + lo`` with both parts bfloat16: the operand split of a
    three-pass (``Precision.HIGH``) float32 product on the MXU."""
    x = np.asarray(x, np.float32)
    hi = bf16_round(x)
    lo = bf16_round(x - hi)
    return hi, lo


# -- SpGEMM ----------------------------------------------------------------

def spgemm_pattern(a: sp.csr_matrix) -> sp.csr_matrix:
    """Structural pattern of A·A: ones where some product lands."""
    ones = a.copy()
    ones.data = np.ones_like(ones.data, np.float64)
    s = ones @ ones
    s.data[:] = 1.0
    return s


def spgemm_ref(a: sp.csr_matrix):
    """``(A·A, |A|·|A|)`` in float64; the second scales each entry's
    rounding error."""
    a = a.astype(np.float64)
    return a @ a, abs(a) @ abs(a)


def spgemm_control(a: sp.csr_matrix) -> sp.csr_matrix:
    """A·A with float32 operands split into bfloat16 halves, three passes."""
    hi, lo = bf16x3_split(a.data)
    ah = sp.csr_matrix((hi.astype(np.float64), a.indices, a.indptr),
                       shape=a.shape)
    al = sp.csr_matrix((lo.astype(np.float64), a.indices, a.indptr),
                       shape=a.shape)
    c = (ah @ ah + ah @ al + al @ ah).tocsr()
    c.sum_duplicates()
    c.data = c.data.astype(np.float32)
    return c


def spgemm_errors(c_indptr, c_indices, c_data, n: int,
                  pattern: sp.csr_matrix, ref: sp.csr_matrix,
                  scale: sp.csr_matrix) -> dict:
    """Compare a product as the user receives it with the reference.

    ``extra_entries``: returned entries outside A·A's structural pattern.
    ``max_scaled_err``: the largest ``|C − R| / (|A|·|A|)`` over that
    pattern, each entry's error in units of the magnitudes its sum is
    built from.  An entry missing from the returned pattern reads as zero
    there, so a dropped exact zero passes and a dropped value does not.
    """
    c = csr(n, c_indptr, c_indices, c_data)
    c.sum_duplicates()                 # sorts; merges duplicates
    if c.nnz != len(c_indices):        # a duplicate entry is a fault
        return dict(extra_entries=float("inf"), max_scaled_err=float("inf"))
    got = c.copy()
    got.data = np.ones_like(got.data)
    extra = int((got - got.multiply(pattern)).count_nonzero())
    inv = scale.copy()
    inv.data = 1.0 / inv.data
    ratio = abs(c - ref).multiply(inv)
    err = float(ratio.max()) if ratio.nnz else 0.0
    return dict(extra_entries=extra, max_scaled_err=err)


# -- Cholesky --------------------------------------------------------------

def lower_band(a: sp.csr_matrix, dtype=np.float64):
    """A's lower triangle in LAPACK lower band storage ``ab[i-j, j]``."""
    coo = sp.tril(a).tocoo()
    w = int(np.max(coo.row - coo.col)) if coo.nnz else 0
    ab = np.zeros((w + 1, a.shape[0]), dtype)
    ab[coo.row - coo.col, coo.col] = coo.data
    return ab


def cholesky_ref(a: sp.csr_matrix, dtype=np.float64) -> np.ndarray:
    """L of A = L·Lᵀ in lower band storage (LAPACK ``pbtrf``)."""
    return sla.cholesky_banded(lower_band(a, dtype), lower=True)


def cholesky_error(col_ptr, row_idx, vals, l_band: np.ndarray) -> float:
    """``max |L − L_ref| / max |L_ref|`` over every entry of either
    factor: a value outside the returned pattern counts as zero."""
    n = l_band.shape[1]
    col = np.repeat(np.arange(n), np.diff(np.asarray(col_ptr)))
    off = np.asarray(row_idx) - col
    vals = np.asarray(vals, np.float64)
    if np.any(off < 0):
        return float("inf")
    w = l_band.shape[0]
    inside = off < w
    got = np.zeros_like(l_band, np.float64)
    np.add.at(got, (off[inside], col[inside]), vals[inside])
    err = np.max(np.abs(got - l_band))
    if np.any(~inside):
        err = max(err, float(np.max(np.abs(vals[~inside]))))
    return float(err / np.max(np.abs(l_band)))


def band_to_csc(l_band: np.ndarray):
    """Lower band storage → ``(col_ptr, row_idx, vals)`` of L in CSC."""
    w, n = l_band.shape
    k, j = (g.ravel() for g in np.indices(l_band.shape))
    i = j + k
    ok = i < n
    order = np.lexsort((i[ok], j[ok]))
    rows, cols = i[ok][order], j[ok][order]
    vals = l_band[k[ok][order], cols]
    col_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(cols, minlength=n), out=col_ptr[1:])
    return col_ptr, rows, vals


# -- CG --------------------------------------------------------------------

def cg_plain(matvec, b: np.ndarray, tol: float, maxiter: int):
    """Textbook conjugate gradient from x = 0; stops when the updated
    residual's norm falls below ``tol·‖b‖`` or after ``maxiter``
    iterations.  ``matvec`` supplies A·p.  Returns ``(x, iterations,
    updated relative residual)``."""
    b = np.asarray(b, np.float64)
    x = np.zeros_like(b)
    r = b.copy()
    p = r.copy()
    rr = float(r @ r)
    bnorm = float(np.linalg.norm(b))
    k = 0
    while k < maxiter and np.sqrt(rr) >= tol * bnorm:
        q = matvec(p)
        alpha = rr / float(p @ q)
        x += alpha * p
        r -= alpha * q
        rr, rr_old = float(r @ r), rr
        p = r + (rr / rr_old) * p
        k += 1
    return x, k, float(np.sqrt(rr) / bnorm)


def cg_errors(a: sp.csr_matrix, b: np.ndarray, x: np.ndarray,
              iterations: int, relres: float, converged: bool,
              tol: float) -> dict:
    """Compare a CG answer with exact CG in float64.

    ``x_err``: ‖x − x_k‖/‖x_k‖, where x_k is float64 CG after the same
    number of iterations as the answer reports, so the number measures
    rounding alone and not where the stopping test fell.
    ``residual_gap``: |‖b − A x‖/‖b‖ − reported residual| / tol, how far
    the reported convergence is from the true one.  ``unconverged``: 1
    when the solve reports no convergence to ``tol``.
    """
    x = np.asarray(x, np.float64)
    x_k, _, _ = cg_plain(lambda p: a @ p, b, 0.0, int(iterations))
    true_res = float(np.linalg.norm(b - a @ x) / np.linalg.norm(b))
    return dict(
        x_err=float(np.linalg.norm(x - x_k) / np.linalg.norm(x_k)),
        residual_gap=abs(true_res - relres) / tol,
        unconverged=float(not converged or relres >= tol))


def cg_control(a: sp.csr_matrix, b: np.ndarray, tol: float, maxiter: int):
    """CG whose float32 matvecs take three bfloat16 passes."""
    hi, lo = bf16x3_split(a.data)
    ah = sp.csr_matrix((hi.astype(np.float64), a.indices, a.indptr),
                       shape=a.shape)
    al = sp.csr_matrix((lo.astype(np.float64), a.indices, a.indptr),
                       shape=a.shape)

    def matvec(p):
        ph, pl = bf16x3_split(p.astype(np.float32))
        ph, pl = ph.astype(np.float64), pl.astype(np.float64)
        return (ah @ ph + ah @ pl + al @ ph).astype(np.float32) \
            .astype(np.float64)

    return cg_plain(matvec, b, tol, maxiter)
