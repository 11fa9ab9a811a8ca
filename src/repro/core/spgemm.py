"""SpGEMM: row-by-row (Gustavson) formulation, REAP-split into
host inspection (core.inspector) + device execution (this module).

Two executors mirror the DESIGN.md adaptation:

* ``gather`` (VPU path)  — element bundles; device does gather → multiply →
  segment-sum.  Matches the paper's element pipelines most literally.
* ``block`` (MXU path)   — BSR bundles; device streams 128×128 tile dots
  driven by the inspector's schedule (Pallas kernel in kernels/bsr_spgemm.py,
  jnp fallback here).

Plans are pattern-pure (core.inspector); executors take the numeric values
separately, so a cached plan serves any number of same-pattern calls
(runtime.plan_cache / runtime.api build on this).

The numpy reference ``spgemm_ref_numpy`` doubles as the CPU-library baseline
(MKL stand-in) for the paper's figures.
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.runtime import spans
from repro.runtime.exec_store import persistent_jit

from .formats import BsrPattern, CSR
from .inspector import (SpGemmBlockPlan, SpGemmGatherPlan, choose_spgemm_path,
                        csr_pattern_digest, fingerprint_pattern,
                        inspect_spgemm_block, inspect_spgemm_gather)


# ---------------------------------------------------------------------------
# Reference / CPU baseline
# ---------------------------------------------------------------------------

def spgemm_ref_numpy(a: CSR, b: CSR) -> CSR:
    """Vectorized numpy Gustavson SpGEMM — the CPU library stand-in."""
    from .inspector import _ranges
    b_row_len = b.row_lengths
    k = a.indices
    counts = b_row_len[k]
    a_idx = np.repeat(np.arange(a.nnz, dtype=np.int64), counts)
    b_idx = _ranges(b.indptr[k], counts)
    out_row = np.repeat(a.nnz_rows(), counts)
    out_col = b.indices[b_idx]
    vals = a.data[a_idx] * b.data[b_idx]
    key = out_row * np.int64(b.n_cols) + out_col
    uniq, inv = np.unique(key, return_inverse=True)
    acc = np.zeros(uniq.shape[0], dtype=a.data.dtype)
    np.add.at(acc, inv, vals)
    indptr = np.zeros(a.n_rows + 1, dtype=np.int64)
    rows = (uniq // b.n_cols).astype(np.int64)
    np.add.at(indptr, rows + 1, 1)
    np.cumsum(indptr, out=indptr)
    return CSR(a.n_rows, b.n_cols, indptr, (uniq % b.n_cols).astype(np.int64), acc)


# ---------------------------------------------------------------------------
# Gather (VPU) executor
# ---------------------------------------------------------------------------

@persistent_jit(static_argnames=("c_nnz",))
def _gather_execute(a_data, b_data, a_idx, b_idx, out_idx, c_nnz: int):
    # trailing zero slot keeps padded (dead) gathers in bounds
    a_data = jnp.concatenate([a_data, jnp.zeros(1, a_data.dtype)])
    b_data = jnp.concatenate([b_data, jnp.zeros(1, b_data.dtype)])
    pp = a_data[a_idx] * b_data[b_idx]          # multiply units
    c = jax.ops.segment_sum(pp, out_idx, num_segments=c_nnz + 1,
                            indices_are_sorted=True)  # merge units
    return c[:c_nnz]


def spgemm_gather_execute(plan: SpGemmGatherPlan, a_data: np.ndarray,
                          b_data: np.ndarray) -> np.ndarray:
    return np.asarray(_gather_execute(
        jnp.asarray(a_data), jnp.asarray(b_data),
        jnp.asarray(plan.a_idx), jnp.asarray(plan.b_idx),
        # reaplint: disable=REAP004 plan-static shape: the sync path
        # compiles once per cached plan; bucketing lives on the chunked
        # path (_gather_execute_capped)
        jnp.asarray(plan.out_idx), c_nnz=plan.c_nnz))


def _gather_math(a_data, b_data, a_idx, b_idx, out_idx, c_cap: int):
    """Capped gather→multiply→merge math, shared by the chunked executor
    and the sharded (shard_map) executor in ``runtime/shard.py`` — one
    definition keeps the two paths bit-for-bit interchangeable.

    Dead (padding) gathers must index the appended zero slot
    (``len(a_data)`` / ``len(b_data)``) and dead outputs the ``c_cap``
    segment, which is dropped by the trailing slice.
    """
    a_data = jnp.concatenate([a_data, jnp.zeros(1, a_data.dtype)])
    b_data = jnp.concatenate([b_data, jnp.zeros(1, b_data.dtype)])
    pp = a_data[a_idx] * b_data[b_idx]
    return jax.ops.segment_sum(pp, out_idx, num_segments=c_cap + 1,
                               indices_are_sorted=True)[:c_cap]


@persistent_jit(static_argnames=("c_cap",))
def _gather_execute_capped(a_data, b_data, a_idx, b_idx, out_idx, c_cap: int):
    """Shape-bucketed gather executor for the chunked/overlapped runtime.

    ``c_cap`` is a power-of-two ≥ the chunk's c_nnz, and the index arrays
    are padded to power-of-two tile counts, so streaming many differently
    sized chunks triggers only O(log) recompilations.
    """
    return _gather_math(a_data, b_data, a_idx, b_idx, out_idx, c_cap)


def _put_gather_indices(host):
    """Uploads a chunk plan's padded index arrays (a memo miss): under
    ``reap.h2d``, their bytes counted as ``h2d_bytes``, and one
    ``gather_index_builds``."""
    with spans.span("reap.h2d"):
        dev = [jnp.asarray(x) for x in host]
        spans.count("h2d_bytes", sum(x.nbytes for x in dev))
    spans.count("gather_index_builds")
    return dev


def spgemm_gather_execute_chunk(plan: SpGemmGatherPlan, a_data: np.ndarray,
                                b_data: np.ndarray) -> np.ndarray:
    """Execute one chunk plan with bucketed shapes; returns (c_nnz,) values.

    The plan's index arrays, padded to the bucketed length, stay on the
    device (``plan.device_indices``: pattern-pure, built on the plan's
    first product and freed with the plan; 3.22 GB over cop20k_A's four
    chunk plans), so a product sends only ``a_data`` and ``b_data``.

    Spans: ``reap.values`` (the index memo: a lookup on a warm plan; on the
    plan's first product the host padding, with the indices' upload nested
    in it under ``reap.h2d``), ``reap.h2d`` (the values to the device; with
    the indices' upload, counted as ``h2d_bytes``), ``reap.launch`` and
    ``reap.fetch``.  Counters: ``gather_index_builds``, the memos built
    (1 on a plan's first product, else 0); ``gather_products``, the plan's
    live partial products, and ``gather_slots``, the padded length run.
    """
    spans.count("gather_index_builds", 0)
    with spans.span("reap.values"):
        index = plan.device_indices(len(a_data), len(b_data),
                                    _put_gather_indices)
    with spans.span("reap.h2d"):
        values = [jnp.asarray(a_data), jnp.asarray(b_data)]
        spans.count("h2d_bytes", sum(x.nbytes for x in values))
    spans.count("gather_products", plan.n_pp)
    spans.count("gather_slots", index.cap)
    with spans.span("reap.launch"):
        c = _gather_execute_capped(*values, index.a_idx, index.b_idx,
                                   index.out_idx,
                                   c_cap=index.c_cap)[:plan.c_nnz]
    return spans.to_host(c)


# ---------------------------------------------------------------------------
# Block (MXU) executor — jnp fallback; Pallas kernel lives in kernels/
# ---------------------------------------------------------------------------

@persistent_jit(static_argnames=("n_out",))
def _block_execute_jnp(a_blocks, b_blocks, a_id, b_id, out_id, n_out: int):
    prods = jnp.einsum("tij,tjk->tik", a_blocks[a_id], b_blocks[b_id],
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    return jax.ops.segment_sum(prods, out_id, num_segments=n_out,
                               indices_are_sorted=True)


def spgemm_block_execute(plan: SpGemmBlockPlan, a_data: np.ndarray,
                         b_data: np.ndarray, use_pallas: bool = True
                         ) -> np.ndarray:
    """Returns the dense (n_out_blocks, block, block) output tiles.

    ``a_data``/``b_data`` are the operands' CSR value arrays; the plan's
    BsrPattern scatters them into MXU tiles (the per-call value pass).
    """
    if plan.n_pairs == 0:
        return np.zeros((plan.n_out_blocks, plan.block, plan.block), np.float32)
    a_blocks = plan.a_pat.scatter(a_data)
    b_blocks = plan.b_pat.scatter(b_data)
    if use_pallas:
        # replay the emitted schedule bundle through the Pallas kernel —
        # the single entry point runtime.api also uses
        from repro.kernels import ops as kops
        return np.asarray(kops.bsr_spgemm_schedule(
            plan.schedule,
            jnp.asarray(a_blocks, jnp.float32),
            jnp.asarray(b_blocks, jnp.float32),
            # reaplint: disable=REAP004 plan-static shape: one compile
            # per cached plan; the chunked path buckets via
            # bucket_block_schedule
            n_out_blocks=plan.n_out_blocks))
    return np.asarray(_block_execute_jnp(
        jnp.asarray(a_blocks, jnp.float32),
        jnp.asarray(b_blocks, jnp.float32),
        jnp.asarray(plan.a_id), jnp.asarray(plan.b_id),
        # reaplint: disable=REAP004 plan-static shape: one compile per
        # cached plan (sync fallback path)
        jnp.asarray(plan.out_id), n_out=plan.n_out_blocks))


def block_result_to_dense(plan: SpGemmBlockPlan, c_blocks: np.ndarray
                          ) -> np.ndarray:
    bs = plan.block
    out = np.zeros((plan.a_pat.n_rows, plan.b_pat.n_cols), np.float32)
    for t in range(plan.n_out_blocks):
        r0, c0 = plan.out_brow[t] * bs, plan.out_bcol[t] * bs
        out[r0:r0 + bs, c0:c0 + bs] = c_blocks[t]
    return out


def block_result_to_csr(plan: SpGemmBlockPlan, c_blocks: np.ndarray,
                        n_rows: int, n_cols: int) -> CSR:
    """Output tiles → CSR: one gather over A·B's structural entries.

    Bit-for-bit ``CSR.from_dense(block_result_to_dense(...))`` for finite
    values: the same entries in row-major order, the same dtypes, exact
    zeros dropped.  Entries outside the structural pattern are exact zeros
    in every tile, so only the pattern's entries are read.

    Pattern-pure, built once per plan (``plan.out_csr_index``, through the
    synchronous executor unless the caller built it already): which tile
    entries are read, in what order, and ``indptr``/``indices``, which every
    result of the plan shares read-only.  Per call: the gather of the
    values and a check for exact zeros.  Only zeros inside the pattern
    (cancellation, stored zeros in A or B) cost more: they are dropped,
    ``indices`` compacted, ``indptr`` recounted, and their number is
    counted as ``extract_zeros_dropped``.
    """
    index = plan.out_csr_index(functools.partial(spgemm_block_execute, plan))
    vals = c_blocks.reshape(-1)[index.sel]
    zeros = np.flatnonzero(vals == 0)
    spans.count("extract_zeros_dropped", zeros.size)
    if not zeros.size:
        return CSR(n_rows, n_cols, index.indptr, index.indices, vals)
    keep = vals != 0
    # each row start moves back by the zeros dropped before it
    indptr = index.indptr - np.searchsorted(zeros, index.indptr)
    return CSR(n_rows, n_cols, indptr, index.indices[keep], vals[keep])


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def spgemm(a: CSR, b: CSR, method: str = "auto", block: int = 128,
           use_pallas: bool = True, tile: int = 1024,
           plan=None) -> Tuple[CSR, dict]:
    """C = A @ B with the REAP split. Returns (C, stats).

    stats records the inspector/executor time split (paper Fig 7).  This is
    the plain synchronous path; runtime.api.ReapRuntime adds plan caching
    and inspector/executor overlap on top of the same stages.

    ``plan`` accepts a pre-built ``SpGemmGatherPlan`` or ``SpGemmBlockPlan``
    (e.g. from ``runtime.PlanCache``): inspection is skipped, the executor
    path is chosen by the plan's type, and ``method``/``block``/``tile`` are
    ignored — the plan already fixed them.  This is the single planned-
    execution entry point every layer (runtime, benchmarks, examples) shares.
    """
    inspect_s = 0.0
    if plan is None:
        if method == "auto":
            method = choose_spgemm_path(a, b, block)
        if method not in ("gather", "block"):
            raise ValueError(f"unknown method {method!r}")
        with spans.span("reap.inspect") as ins:
            if method == "gather":
                plan = inspect_spgemm_gather(a, b, tile)
            else:
                plan = inspect_spgemm_block(a, b, block)
        inspect_s = ins.seconds

    if isinstance(plan, SpGemmGatherPlan):
        with spans.span("reap.execute") as ex:
            c_data = spgemm_gather_execute(plan, a.data, b.data)
        c = CSR(a.n_rows, b.n_cols, plan.c_indptr, plan.c_indices, c_data)
        stats = dict(method="gather", inspect_s=inspect_s,
                     execute_s=ex.seconds, flops=plan.flops(),
                     n_pp=plan.n_pp)
        return c, stats
    if isinstance(plan, SpGemmBlockPlan):
        with spans.span("reap.execute") as ex:
            c_blocks = spgemm_block_execute(plan, a.data, b.data,
                                            use_pallas=use_pallas)
        with spans.span("reap.extract"):
            plan.out_csr_index(functools.partial(
                spgemm_block_execute, plan, use_pallas=use_pallas))
            c = block_result_to_csr(plan, c_blocks, a.n_rows, b.n_cols)
        stats = dict(method="block", inspect_s=inspect_s,
                     execute_s=ex.seconds, flops=plan.flops(),
                     n_pairs=plan.n_pairs, fill=plan.a_pat.fill)
        return c, stats
    raise TypeError(f"unsupported plan type {type(plan).__name__}")


# ---------------------------------------------------------------------------
# Op registry: SpGEMM as planned ops (runtime.ops protocol)
# ---------------------------------------------------------------------------
#
# "spgemm" is a pure router: it resolves method="auto" (caching the
# heuristic's decision per pattern in the runtime's route cache) and
# forwards to the concrete "spgemm_gather" / "spgemm_block" ops.  The
# concrete specs keep the exact fingerprint op strings and params the
# runtime has always used, so persisted stores stay warm across this
# refactor.

from repro.runtime.ops import (OpCapabilities, OpSpec,  # noqa: E402
                               register_op)


def _spgemm_digests(a: CSR, b: CSR, digests):
    # each operand pattern is hashed exactly once per call; the routing key
    # and the plan key share these digests
    return digests if digests is not None else (csr_pattern_digest(a),
                                                csr_pattern_digest(b))


def _route_spgemm(operands, cfg, routes, *, method: str = "auto",
                  digests=None, **kw):
    a, b = operands
    digests = _spgemm_digests(a, b, digests)
    if method == "auto":
        # the routing heuristic builds A's block structure (O(nnz log nnz));
        # cache the decision per pattern like any other plan
        route_fp = fingerprint_pattern("route", (a, b), digests,
                                       block=cfg.block)
        method, _ = routes.get_or_build(
            route_fp, lambda: choose_spgemm_path(a, b, cfg.block))
    if method not in ("gather", "block"):
        raise ValueError(f"unknown method {method!r}")
    return f"spgemm_{method}", dict(kw, digests=digests)


def _fp_spgemm_gather(operands, cfg, *, chunked, digests=None, **kw):
    a, b = operands
    digests = _spgemm_digests(a, b, digests)
    if chunked:
        return fingerprint_pattern("spgemm_gather_chunked", (a, b), digests,
                                   tile=cfg.tile, n_chunks=cfg.n_chunks)
    return fingerprint_pattern("spgemm_gather", (a, b), digests,
                               tile=cfg.tile)


def _inspect_spgemm_gather(operands, cfg, fp, **kw):
    a, b = operands
    return inspect_spgemm_gather(a, b, cfg.tile, fp)


def _exec_spgemm_gather(plan, operands, cfg, *, overlap, **kw):
    a, b = operands
    c, stats = spgemm(a, b, plan=plan)
    stats["overlap"] = False
    return c, stats


def _exec_spgemm_gather_chunked(cached, operands, cfg, *, overlap, **kw):
    from repro.runtime.pipeline import spgemm_gather_chunked
    a, b = operands
    c, stats, chunkset = spgemm_gather_chunked(
        a, b, n_chunks=cfg.n_chunks, tile=cfg.tile, overlap=overlap,
        chunkset=cached)
    return c, stats, chunkset


def _shard_spgemm_gather(cached, operands, cfg, *, mesh, **kw):
    from repro.runtime.shard import sharded_spgemm_gather
    a, b = operands
    return sharded_spgemm_gather(a, b, mesh, tile=cfg.tile, plan=cached)


def _fp_spgemm_block(operands, cfg, *, chunked, digests=None, **kw):
    a, b = operands
    digests = _spgemm_digests(a, b, digests)
    if chunked:
        return fingerprint_pattern("spgemm_block_chunked", (a, b), digests,
                                   block=cfg.block, n_chunks=cfg.n_chunks)
    return fingerprint_pattern("spgemm_block", (a, b), digests,
                               block=cfg.block)


def _inspect_spgemm_block(operands, cfg, fp, **kw):
    a, b = operands
    return inspect_spgemm_block(a, b, cfg.block, fp)


def _exec_spgemm_block(plan, operands, cfg, *, overlap, **kw):
    a, b = operands
    c, stats = spgemm(a, b, plan=plan, use_pallas=cfg.use_pallas)
    stats["overlap"] = False
    return c, stats


def _exec_spgemm_block_chunked(cached, operands, cfg, *, overlap, **kw):
    from repro.runtime.pipeline import spgemm_block_chunked
    a, b = operands
    c, stats, chunkset = spgemm_block_chunked(
        a, b, block=cfg.block, n_chunks=cfg.n_chunks, overlap=overlap,
        use_pallas=cfg.use_pallas, chunkset=cached)
    return c, stats, chunkset


register_op(OpSpec(tag="spgemm", route=_route_spgemm))

register_op(OpSpec(
    tag="spgemm_gather",
    fingerprint=_fp_spgemm_gather,
    inspect=_inspect_spgemm_gather,
    execute_sync=_exec_spgemm_gather,
    execute_chunked=_exec_spgemm_gather_chunked,
    shard_plan=_shard_spgemm_gather,
    plan_types={"spgemm_gather": SpGemmGatherPlan},
    fingerprint_ops=("spgemm_gather", "spgemm_gather_chunked"),
    allowed_kw=("digests",),
    capabilities=OpCapabilities(shardable=True),
))

register_op(OpSpec(
    tag="spgemm_block",
    fingerprint=_fp_spgemm_block,
    inspect=_inspect_spgemm_block,
    execute_sync=_exec_spgemm_block,
    execute_chunked=_exec_spgemm_block_chunked,
    plan_types={"spgemm_block": SpGemmBlockPlan, "bsr_pattern": BsrPattern},
    fingerprint_ops=("spgemm_block", "spgemm_block_chunked"),
    allowed_kw=("digests",),
))
