"""Pallas TPU kernel: BSR sparse-weight × dense-activation matmul (SpMM).

The paper's technique applied to *weight* sparsity in the LM stack
(DESIGN.md §4): a host inspector prunes/blocks the weight matrix into BSR
tiles and emits a job schedule (one job per nonzero weight block, sorted
by output column-block); the kernel streams activation tiles through the
MXU against only the stored weight blocks, consuming the schedule via
scalar prefetch.  FLOPs scale with the *stored* blocks — weight sparsity
becomes wall-clock savings instead of masked waste.

Two entry points:

* ``inspect_bsr_weight`` — the original magnitude-pruning inspector for a
  *dense* weight matrix (used by ``sparse_swiglu``).
* ``inspect_spmm`` / ``SpmmPlan`` — the planned-op form for an already
  *sparse* CSR operand: ``Y = X @ W`` with W's sparsity pattern
  fingerprinted under the ``spmm`` op tag.  This op is admitted to the
  plan cache, the runtime, and the persistent store purely
  through ``runtime.ops.register_op`` at the bottom of this file — no
  edits to ``runtime/{api,plan_cache,plan_store}.py`` — which is the
  registry's worked "admit your own op" example (docs/architecture.md).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import BsrPattern, CSR, bsr_pattern_from_csr
from repro.runtime import spans
from repro.runtime.exec_store import persistent_jit
from repro.core.inspector import (PatternFingerprint, fingerprint_pattern,
                                  next_pow2)
from repro.core.rir import ScheduleBundle

from . import I0, dot_precision, resolve_interpret


def _sorted_job_schedule(kk: np.ndarray, jj: np.ndarray, carry: np.ndarray,
                         carry_fill, n_k_blocks: int, n_j_blocks: int):
    """Shared RIR job-schedule construction for the SpMM kernels.

    Appends a coverage job for every output block-column with no stored
    block (its tile must still be zeroed; ``carry_fill`` marks the job's
    per-caller payload — a dead/zero operand), sorts jobs by (output
    block, input block), and derives the ``is_first``/``is_last`` group
    flags.  Returns ``(kk, jj, carry, is_first, is_last)``.
    """
    missing = np.setdiff1d(np.arange(n_j_blocks), np.unique(jj))
    if missing.size:
        kk = np.concatenate([kk, np.zeros(missing.size, kk.dtype)])
        jj = np.concatenate([jj, missing])
        carry = np.concatenate(
            [carry, np.full(missing.size, carry_fill, carry.dtype)])
    order = np.argsort(jj * np.int64(max(1, n_k_blocks)) + kk,
                       kind="stable")
    kk, jj, carry = kk[order], jj[order], carry[order]
    n_jobs = int(kk.shape[0])
    is_first = np.ones(n_jobs, bool)
    is_first[1:] = jj[1:] != jj[:-1]
    is_last = np.ones(n_jobs, bool)
    is_last[:-1] = jj[1:] != jj[:-1]
    return kk, jj, carry, is_first, is_last


def inspect_bsr_weight(w_dense: np.ndarray, block: int,
                       keep_fraction: float):
    """Host inspector: magnitude-prune W into BSR blocks + job schedule.

    Returns (blocks (nb, block, block), schedule dict) where the schedule
    has, per job: the weight-block id, its k (input) block and j (output)
    block, sorted by j with first/last group flags — the same RIR bundle
    discipline as the SpGEMM executor.
    """
    d_in, d_out = w_dense.shape
    assert d_in % block == 0 and d_out % block == 0
    nk, nj = d_in // block, d_out // block
    tiles = w_dense.reshape(nk, block, nj, block).transpose(0, 2, 1, 3)
    # reaplint: disable=REAP001 this inspector CREATES the sparsity
    # pattern (magnitude pruning of a dense weight); value-dependence is
    # its purpose. Downstream spmm plans consume only the pattern.
    energy = np.abs(tiles).sum(axis=(2, 3)).reshape(-1)      # (nk*nj,)
    n_keep = max(nj, int(round(keep_fraction * nk * nj)))
    keep_ids = np.argsort(-energy)[:n_keep]
    kk, jj = keep_ids // nj, keep_ids % nj
    # coverage jobs (carry=live False) multiply by a ZERO block
    kk, jj, live, is_first, is_last = _sorted_job_schedule(
        kk, jj, np.ones(kk.shape[0], bool), False, nk, nj)
    blocks = tiles[kk, jj].copy()
    blocks[~live] = 0.0
    n_jobs = kk.shape[0]
    sched = dict(w_id=np.arange(n_jobs, dtype=np.int32),
                 k_blk=kk.astype(np.int32), j_blk=jj.astype(np.int32),
                 is_first=is_first.astype(np.int32),
                 is_last=is_last.astype(np.int32))
    mask = np.zeros((nk, nj), bool)
    mask[kk[live], jj[live]] = True
    return blocks.astype(w_dense.dtype), sched, mask


def _kernel(w_id, k_blk, j_blk, is_first, is_last, x_ref, w_ref, o_ref):
    del w_id, k_blk, j_blk, is_last
    t = pl.program_id(1)

    @pl.when(is_first[t] == 1)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                          precision=dot_precision(x_ref.dtype),
                          preferred_element_type=jnp.float32
                          ).astype(o_ref.dtype)


@persistent_jit(static_argnames=("n_j_blocks", "bt", "interpret"))
def bsr_spmm(x, w_blocks, w_id, k_blk, j_blk, is_first, is_last, *,
             n_j_blocks: int, bt: int = 128,
             interpret: Optional[bool] = None):
    """out = x @ W_bsr.  x: (T, d_in); w_blocks: (n_jobs, bs, bs).

    Schedule arrays (n_jobs,) are sorted by output block column with
    group-boundary flags.  Returns (T, n_j_blocks*bs).
    """
    t_total, d_in = x.shape
    bs = w_blocks.shape[-1]
    n_jobs = w_id.shape[0]
    bt = min(bt, t_total)
    assert t_total % bt == 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(t_total // bt, n_jobs),
        in_specs=[
            pl.BlockSpec((bt, bs),
                         lambda ti, t, wid, kb, jb, fi, la: (ti, kb[t])),
            pl.BlockSpec((1, bs, bs),
                         lambda ti, t, wid, kb, jb, fi, la: (wid[t], I0, I0)),
        ],
        out_specs=pl.BlockSpec((bt, bs),
                               lambda ti, t, wid, kb, jb, fi, la:
                               (ti, jb[t])),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_total, n_j_blocks * bs), x.dtype),
        interpret=resolve_interpret(interpret),
        cost_estimate=pl.CostEstimate(
            flops=2 * (t_total // bt) * n_jobs * bt * bs * bs,
            bytes_accessed=(t_total * d_in + n_jobs * bs * bs) * 2,
            transcendentals=0),
    )(w_id, k_blk, j_blk, is_first, is_last, x, w_blocks)


# ---------------------------------------------------------------------------
# Planned SpMM: Y = X @ W with a sparse CSR W (pattern-pure plan)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class SpmmPlan:
    """Pattern-pure plan for ``Y = X @ W`` with W sparse (CSR → BSR tiles).

    The job schedule has one entry per stored W block (plus zero-tile
    coverage jobs for all-pruned output block-columns, so every output
    tile is written), sorted by output block-column with
    ``is_first``/``is_last`` group flags — the same RIR schedule
    discipline as the SpGEMM block path.  ``w_id == pat.n_blocks`` marks a
    coverage job; :meth:`scatter` appends the zero tile it multiplies.

    Only W's sparsity pattern (and ``block``) enters the fingerprint: the
    dense activations X are values, so every same-weight-pattern call —
    each microbatch through a frozen sparse layer — replays a warm plan.
    """

    block: int
    n_rows: int                      # W rows (d_in), unpadded
    n_cols: int                      # W cols (d_out), unpadded
    pat: BsrPattern                  # W's block structure + value scatter
    w_id: np.ndarray                 # (n_jobs,) W tile per job
    k_blk: np.ndarray                # (n_jobs,) X block-column per job
    j_blk: np.ndarray                # (n_jobs,) output block-column per job
    is_first: np.ndarray             # (n_jobs,) first job of its j group
    is_last: np.ndarray              # (n_jobs,) last job of its j group
    n_jobs: int
    fingerprint: Optional[PatternFingerprint] = None

    @property
    def n_j_blocks(self) -> int:
        return self.pat.n_block_cols

    @property
    def n_k_blocks(self) -> int:
        return self.pat.n_block_rows

    @property
    def schedule(self) -> ScheduleBundle:
        return ScheduleBundle("spmm", {
            "w_id": self.w_id.astype(np.int32),
            "k_blk": self.k_blk.astype(np.int32),
            "j_blk": self.j_blk.astype(np.int32),
            "is_first": self.is_first.astype(np.int32),
            "is_last": self.is_last.astype(np.int32)})

    def scatter(self, w_data: np.ndarray, dtype=np.float32) -> np.ndarray:
        """Value pass: W's CSR values → (n_blocks + 1, bs, bs) MXU tiles
        (the trailing tile is the zero operand of coverage jobs)."""
        tiles = self.pat.scatter(w_data, dtype=dtype)
        return np.concatenate(
            [tiles, np.zeros((1, self.block, self.block), tiles.dtype)])

    def flops(self, n_tokens: int) -> int:
        return 2 * n_tokens * self.n_jobs * self.block * self.block


def inspect_spmm(w: CSR, block: int = 128,
                 fingerprint: Optional[PatternFingerprint] = None
                 ) -> SpmmPlan:
    """Stage-2 plan-build for SpMM: W's block schedule, sorted by output."""
    pat = bsr_pattern_from_csr(w, block)
    # coverage jobs (carry=wid n_blocks) multiply the appended zero tile
    kk, jj, wid, is_first, is_last = _sorted_job_schedule(
        pat.block_rows(), pat.indices.copy(),
        np.arange(pat.n_blocks, dtype=np.int64), pat.n_blocks,
        pat.n_block_rows, pat.n_block_cols)
    return SpmmPlan(block, w.n_rows, w.n_cols, pat, wid,
                    kk.astype(np.int64), jj.astype(np.int64),
                    is_first, is_last, int(kk.shape[0]), fingerprint)


def _spmm_math(x_tiles, w_tiles, w_id, k_blk, j_blk, n_j: int):
    """Per-job tile dots + segment-sum over output block-columns (jobs are
    sorted by ``j_blk``).  Shared by the jnp fallback executor and the
    sharded (shard_map) executor in ``runtime/shard.py`` — one definition
    keeps the two paths bit-for-bit interchangeable."""
    prods = jnp.einsum("tij,tjk->tik", x_tiles[k_blk], w_tiles[w_id],
                       precision=dot_precision(x_tiles.dtype),
                       preferred_element_type=x_tiles.dtype)
    return jax.ops.segment_sum(prods, j_blk, num_segments=n_j,
                               indices_are_sorted=True)


@persistent_jit(static_argnames=("n_j",))
def _spmm_execute_jnp(x_tiles, w_tiles, w_id, k_blk, j_blk, n_j: int):
    """jnp fallback executor (see ``_spmm_math``)."""
    return _spmm_math(x_tiles, w_tiles, w_id, k_blk, j_blk, n_j)


def spmm_execute(plan: SpmmPlan, x: np.ndarray, w_data: np.ndarray,
                 use_pallas: bool = True, dtype=np.float32) -> np.ndarray:
    """Y = X @ W from a plan + this call's values.  Returns (T, d_out).

    T is bucketed to a power of two (and X zero-padded to W's padded
    row count) so a stream of differently sized activation batches costs
    O(log) executor compiles — the RIR static-shape discipline.

    ``dtype`` picks the value dtype of the whole pass (plans are
    value-free, so it never touches the fingerprint).  The Pallas MXU
    path accumulates in float32 by design; wider dtypes (the planned
    solver's float64 matvecs) route through the jnp executor.
    """
    dtype = np.dtype(dtype)
    x = np.asarray(x, dtype)
    t, d_in = x.shape
    if d_in != plan.n_rows:
        raise ValueError(f"x has {d_in} features, W has {plan.n_rows} rows")
    bs = plan.block
    t_pad = next_pow2(max(1, t))
    bt = min(128, t_pad)
    pallas = use_pallas and dtype == np.float32
    with spans.span("reap.values"):
        xp = np.zeros((t_pad, plan.pat.n_rows), dtype)
        xp[:t, :d_in] = x
        w_tiles = plan.scatter(w_data, dtype=dtype)
    with spans.span("reap.h2d"):
        if pallas:
            args = (jnp.asarray(xp), jnp.asarray(w_tiles),
                    jnp.asarray(plan.w_id, jnp.int32),
                    jnp.asarray(plan.k_blk, jnp.int32),
                    jnp.asarray(plan.j_blk, jnp.int32),
                    jnp.asarray(plan.is_first, jnp.int32),
                    jnp.asarray(plan.is_last, jnp.int32))
        else:
            x_tiles = xp.reshape(t_pad, plan.n_k_blocks, bs).swapaxes(0, 1)
            args = (jnp.asarray(x_tiles), jnp.asarray(w_tiles),
                    jnp.asarray(plan.w_id), jnp.asarray(plan.k_blk),
                    jnp.asarray(plan.j_blk))
        spans.count("h2d_bytes", sum(arg.nbytes for arg in args))
    with spans.span("reap.launch"):
        if pallas:
            # reaplint: disable=REAP004 plan-static shape: the output block
            # count is fixed per cached plan (bt, the streamed axis, IS
            # pow-2-bucketed)
            out = bsr_spmm(*args, n_j_blocks=plan.n_j_blocks, bt=bt)
        else:
            # reaplint: disable=REAP004 plan-static shape: fixed per cached
            # plan (jnp fallback path)
            out_j = _spmm_execute_jnp(*args, n_j=plan.n_j_blocks)
            out = jnp.swapaxes(out_j, 0, 1).reshape(
                t_pad, plan.n_j_blocks * bs)
    return spans.to_host(out)[:t, :plan.n_cols]


def spmm_ref_numpy(x: np.ndarray, w: CSR) -> np.ndarray:
    """Dense-product oracle for tests/benchmarks."""
    return np.asarray(x, np.float32) @ w.to_dense().astype(np.float32)


# ---------------------------------------------------------------------------
# Op registry: SpMM admitted as a planned op — this block is the *entire*
# integration with the runtime, cache, store, serve and benchmarks.
# ---------------------------------------------------------------------------

from repro.runtime.ops import OpCapabilities, OpSpec, register_op  # noqa: E402


def _fp_spmm(operands, cfg, *, chunked, **kw):
    _, w = operands
    return fingerprint_pattern("spmm", (w,), block=cfg.block)


def _inspect_spmm(operands, cfg, fp, **kw):
    return inspect_spmm(operands[1], cfg.block, fp)


def _exec_spmm(plan, operands, cfg, *, dtype=np.float32, **kw):
    x, w = operands
    with spans.span("reap.execute") as ex:
        y = spmm_execute(plan, x, w.data, use_pallas=cfg.use_pallas,
                         dtype=dtype)
    stats = dict(method="spmm", execute_s=ex.seconds, n_jobs=plan.n_jobs,
                 fill=plan.pat.fill, flops=plan.flops(np.asarray(x).shape[0]))
    return y, stats


def _shard_spmm(cached, operands, cfg, *, mesh, dtype=np.float32, **kw):
    from repro.runtime.shard import sharded_spmm
    x, w = operands
    return sharded_spmm(x, w, mesh, cfg.block, plan=cached, dtype=dtype)


register_op(OpSpec(
    tag="spmm",
    fingerprint=_fp_spmm,
    inspect=_inspect_spmm,
    execute_sync=_exec_spmm,
    shard_plan=_shard_spmm,
    plan_types={"spmm": SpmmPlan, "bsr_pattern": BsrPattern},
    allowed_kw=("dtype",),
    capabilities=OpCapabilities(dtypes=("float32", "float64"),
                                routing="host", shardable=True),
))
