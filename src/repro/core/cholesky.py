"""Sparse Cholesky (left-looking, simplicial LL^T) — REAP split.

Host (core.etree.inspect_cholesky) has already produced a CholeskyPlan:
L's symbolic pattern, etree level sets, and per-level update triples.  This
module is the device-side numeric executor:

  per level ℓ (all columns independent — the paper's parallel pipelines):
    1. cmod:   vals[dst] -= vals[src1] * vals[src2]     (dot-product PEs)
    2. cdiv:   vals[diag] = sqrt(vals[diag])            (Div/SqRoot PEs)
               vals[offd] /= vals[diag of column]

The level loop is the only host interaction; within a level everything is a
single jitted step over padded (bucketed) index arrays — the RIR padding
discipline keeps compiled shapes static, exactly like bundle capacity in the
paper.  Matching the paper, the numeric phase is all fp32/fp64 FLOPs with no
symbolic work on the device.

The per-level host work (bundle-emit: building the padded cmod/cdiv index
arrays) is factored into ``emit_level_bundle`` so the one executor,
``runtime.pipeline.cholesky_execute_overlapped``, can prepare level group
g+1 on a worker thread while the device executes group g — the software
analogue of the paper's CPU/FPGA overlap.
"""
from __future__ import annotations

import functools
import time
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.runtime import spans

from .etree import CholeskyPlan, cholesky_values, inspect_cholesky
from .formats import CSR
from .inspector import next_pow2


def _pad(arr: np.ndarray, size: int, fill: int) -> np.ndarray:
    # stays numpy: bundle-emit may run on a worker thread, and host→device
    # transfer belongs to the executor step (avoids jax dispatch contention)
    out = np.full(size, fill, dtype=np.int64)
    out[:arr.shape[0]] = arr
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _level_step(vals, src1, src2, dst, diag_idx, off_idx, off_diag):
    """One etree level: cmod (gather–multiply–scatter-sub) then cdiv."""
    contrib = vals[src1] * vals[src2]
    vals = vals.at[dst].add(-contrib)            # dead slots hit scratch
    d = jnp.sqrt(vals[diag_idx])
    vals = vals.at[diag_idx].set(d)
    vals = vals.at[off_idx].set(vals[off_idx] / vals[off_diag])
    return vals


def emit_level_bundle(plan: CholeskyPlan, ell: int) -> tuple:
    """Bundle-emit stage for level ``ell``: padded device index arrays.

    Pure host work with no dependence on numeric values, so it can run on a
    worker thread one level ahead of the executor.
    """
    scratch = plan.nnz                           # dead-op slot
    col_of_slot = plan.col_of_slot()
    s1, s2, d = plan.upd_src1[ell], plan.upd_src2[ell], plan.upd_dst[ell]
    cols = plan.cols_per_level[ell]
    diag = plan.diag_pos[cols]
    # off-diagonal slots of this level's columns + their diag slot
    seg_starts = plan.col_ptr[cols] + 1          # skip the diagonal
    seg_ends = plan.col_ptr[cols + 1]
    counts = seg_ends - seg_starts
    from .inspector import _ranges
    off = _ranges(seg_starts, counts)
    off_diag = plan.diag_pos[col_of_slot[off]]

    bu = next_pow2(max(1, s1.shape[0]))
    bc = next_pow2(max(1, diag.shape[0]))
    bo = next_pow2(max(1, off.shape[0]))
    return (_pad(s1, bu, scratch), _pad(s2, bu, scratch),
            _pad(d, bu, scratch), _pad(diag, bc, scratch),
            _pad(off, bo, scratch), _pad(off_diag, bo, scratch))


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
# through the level-group pipeline: the etree level schedule is the chunk
# stream, so the overlap lives inside the executor rather than in a
# chunked hook.

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
