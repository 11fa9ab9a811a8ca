"""Sparse Cholesky (left-looking, simplicial LL^T) — REAP split.

Host (core.etree.inspect_cholesky) has already produced a CholeskyPlan:
L's symbolic pattern, etree level sets, and per-level update triples.  This
module is the device-side numeric executor:

  per level ℓ (all columns independent — the paper's parallel pipelines):
    1. cmod:   vals[dst] -= vals[src1] * vals[src2]     (dot-product PEs)
    2. cdiv:   vals[diag] = sqrt(vals[diag])            (Div/SqRoot PEs)
               vals[offd] /= vals[diag of column]

The level loop is the only host interaction; within a level everything is a
single jitted step over padded (bucketed) index vectors — the RIR padding
discipline keeps compiled shapes static, exactly like bundle capacity in the
paper.  Matching the paper, the numeric phase is all fp32/fp64 FLOPs with no
symbolic work on the device.

The index bundles (bundle-emit) depend only on the pattern, so they are
packed once per plan into six flat index arrays and kept on the device
(``CholeskyPlan.device_bundles``); the one executor,
``runtime.pipeline.cholesky_execute_overlapped``, then sends only the value
array per factor, and each level's step cuts its vectors from the packed
arrays on the device.
"""
from __future__ import annotations

import functools
import time
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.runtime import spans

from .etree import CholeskyPlan, cholesky_values, inspect_cholesky
from .formats import CSR


@functools.partial(jax.jit, static_argnums=(8, 9, 10), donate_argnums=(0,))
def _level_program(vals, at, src1, src2, dst, diag, off, off_diag,
                   upd_n, diag_n, off_n):
    def cut(flat, k, n):
        return lax.dynamic_slice(flat, (at[k],), (n,))

    s1, s2 = cut(src1, 0, upd_n), cut(src2, 0, upd_n)
    contrib = vals[s1] * vals[s2]
    vals = vals.at[cut(dst, 0, upd_n)].add(-contrib)    # dead: scratch
    diag_idx = cut(diag, 1, diag_n)
    d = jnp.sqrt(vals[diag_idx])
    vals = vals.at[diag_idx].set(d)
    off_idx, off_diag_idx = cut(off, 2, off_n), cut(off_diag, 2, off_n)
    vals = vals.at[off_idx].set(vals[off_idx] / vals[off_diag_idx])
    return vals, at + jnp.array((upd_n, diag_n, off_n), at.dtype)


@functools.partial(jax.jit, static_argnums=(0,))
def _first_starts(dtype):
    """Level 0's three slice starts, made on the device: a launch and no
    put (an eager ``jnp.zeros`` transfers its fill value)."""
    return jnp.zeros(3, dtype)


def _level_step(state, src1, src2, dst, diag, off, off_diag,
                upd_n, diag_n, off_n):
    """One etree level: cmod (gather–multiply–scatter-sub) then cdiv.

    ``state`` is (L values, the level's three slice starts).  The six index
    arrays are a plan's packed ``LevelBundles``; the level's vectors are
    their slices at the starts (cmod, diagonal, off-diagonal), of the
    static bucket lengths ``*_n``, so one program serves every level with
    the same buckets.  Levels lie back to back, so the step returns the
    next level's starts — its own plus its bucket lengths — and they never
    leave the device.  The values are donated, the starts are not: the
    first level's come from the plan's memo and serve every factor.
    """
    return _level_program(*state, src1, src2, dst, diag, off, off_diag,
                          upd_n, diag_n, off_n)


def init_values(plan: CholeskyPlan, a_vals: np.ndarray, dtype=jnp.float64):
    """Scatter A's lower-triangle values into the L value array (+scratch)."""
    vals = np.zeros(plan.nnz + 1, dtype=np.float64 if dtype == jnp.float64
                    else np.float32)
    vals[plan.a_scatter_pos] = a_vals
    return jnp.asarray(vals, dtype=dtype)


def cholesky(a: CSR, dtype=jnp.float64):
    """Full REAP sparse Cholesky, planned and run once: A = L L^T.
    Returns (plan, L values, stats).

    The plan is built here and run through the op's one executor,
    ``runtime.pipeline.cholesky_execute_overlapped`` (looked up at call
    time); ``ReapRuntime.run("cholesky", a)`` runs the same executor with
    the plan cache in front, for same-pattern refactors.
    """
    from repro.runtime import pipeline
    with spans.span("reap.inspect") as ins:
        plan = inspect_cholesky(a)
    vals, stats = pipeline.cholesky_execute_overlapped(
        plan, cholesky_values(a), dtype)
    stats["inspect_s"] = ins.seconds
    return plan, vals, stats


def plan_to_dense_l(plan: CholeskyPlan, vals: np.ndarray) -> np.ndarray:
    out = np.zeros((plan.n, plan.n), dtype=vals.dtype)
    col_of_slot = np.repeat(np.arange(plan.n), np.diff(plan.col_ptr))
    out[plan.row_idx, col_of_slot] = vals
    return out


# ---------------------------------------------------------------------------
# CPU baseline (CHOLMOD simplicial-LL^T stand-in): same plan, numpy loops
# ---------------------------------------------------------------------------

def cholesky_baseline_numpy(plan: CholeskyPlan, a_vals: np.ndarray
                            ) -> Tuple[np.ndarray, float]:
    """Column-at-a-time numpy left-looking factorization (numeric only)."""
    vals = np.zeros(plan.nnz + 1, dtype=np.float64)
    vals[plan.a_scatter_pos] = a_vals
    col_of_slot = plan.col_of_slot()
    t0 = time.perf_counter()
    for ell in range(plan.n_levels):
        s1, s2, d = plan.upd_src1[ell], plan.upd_src2[ell], plan.upd_dst[ell]
        np.subtract.at(vals, d, vals[s1] * vals[s2])
        cols = plan.cols_per_level[ell]
        diag = plan.diag_pos[cols]
        vals[diag] = np.sqrt(vals[diag])
        from .inspector import _ranges
        starts = plan.col_ptr[cols] + 1
        counts = plan.col_ptr[cols + 1] - starts
        off = _ranges(starts, counts)
        vals[off] /= vals[plan.diag_pos[col_of_slot[off]]]
    return vals[:plan.nnz], time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Op registry: sparse Cholesky as a planned op (runtime.ops protocol)
# ---------------------------------------------------------------------------
#
# One fingerprint per pattern (dtype is a value-level choice and stays out
# of the key).  The runtime builds the plan, then execute_sync runs it
# through ``cholesky_execute_overlapped``: one launch per etree level over
# the plan's packed bundles, kept on the device, so the host dispatches
# ahead of the device with no chunked hook.

from .inspector import fingerprint_pattern  # noqa: E402
from repro.runtime.ops import (OpCapabilities, OpSpec,  # noqa: E402
                               register_op)


def _fp_cholesky(operands, cfg, *, chunked, **kw):
    (a,) = operands
    return fingerprint_pattern("cholesky", (a,))


def _inspect_cholesky(operands, cfg, fp, **kw):
    (a,) = operands
    return inspect_cholesky(a, fp)


def _exec_cholesky(plan, operands, cfg, *, dtype=jnp.float64, **kw):
    from repro.runtime import pipeline
    (a,) = operands
    with spans.span("reap.values"):
        a_vals = plan.a_values(a)
    vals, stats = pipeline.cholesky_execute_overlapped(plan, a_vals, dtype)
    return (plan, vals), stats


register_op(OpSpec(
    tag="cholesky",
    fingerprint=_fp_cholesky,
    inspect=_inspect_cholesky,
    execute_sync=_exec_cholesky,
    plan_types={"cholesky": CholeskyPlan},
    allowed_kw=("dtype",),
    capabilities=OpCapabilities(dtypes=("float32", "float64"),
                                routing="host"),
))
