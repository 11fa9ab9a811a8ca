"""SpGEMM: row-by-row (Gustavson) formulation, REAP-split into
host inspection (core.inspector) + device execution (this module).

Two routes mirror the DESIGN.md adaptation, each with one executor — a
pipeline in ``runtime.pipeline`` over the device programs defined here:

* ``gather`` (VPU path)  — element bundles; device does gather → multiply →
  segment-sum (``spgemm_gather_execute_chunk``).  Matches the paper's
  element pipelines most literally.
* ``block`` (MXU path)   — BSR bundles; device streams 128×128 tile dots
  driven by the inspector's schedule (Pallas kernel in kernels/bsr_spgemm.py,
  jnp fallback ``_block_execute_jnp`` here).

Plans are pattern-pure (core.inspector); executors take the numeric values
separately, so a cached plan serves any number of same-pattern calls
(runtime.plan_cache / runtime.api build on this).

The numpy reference ``spgemm_ref_numpy`` doubles as the CPU-library baseline
(MKL stand-in) for the paper's figures.
"""
from __future__ import annotations

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

def _gather_math(a_data, b_data, a_idx, b_idx, out_idx, c_cap: int):
    """Capped gather→multiply→merge math, shared by the gather executor
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
    """The gather executor's device program, at bucketed shapes.

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
    """Execute one gather plan with bucketed shapes; returns (c_nnz,) values.

    The plan is a chunk's (``runtime.pipeline.spgemm_gather_chunked``) or
    a whole matrix's; ``a_data``/``b_data`` are the CSR values it was built
    for.

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

    Pattern-pure, built once per plan by the block pipeline
    (``plan.out_csr_index``, which ``runtime.pipeline.spgemm_block_chunked``
    fills on the plan's first product): which tile entries are read, in
    what order, and ``indptr``/``indices``, which every result of the plan
    shares read-only.  Per call: the gather of the
    values and a check for exact zeros.  Only zeros inside the pattern
    (cancellation, stored zeros in A or B) cost more: they are dropped,
    ``indices`` compacted, ``indptr`` recounted, and their number is
    counted as ``extract_zeros_dropped``.
    """
    index = plan.out_csr_index(None)
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
           use_pallas: bool = True, tile: int = 1024) -> Tuple[CSR, dict]:
    """C = A @ B with the REAP split, planned and run once. Returns (C, stats).

    One chunk through the route's pipelined executor
    (``runtime.pipeline.spgemm_gather_chunked`` or ``spgemm_block_chunked``),
    the same executor ``ReapRuntime.run("spgemm", ...)`` runs; the plan is
    dropped with the call, so same-pattern calls belong on the runtime,
    whose plan cache keeps it.  ``stats`` are the executor's: ``plan_s``
    the plan build, ``inspect_s`` the emit stage (the plan build too on the
    gather route), ``execute_s`` the device stage.
    """
    from repro.runtime import pipeline
    if method == "auto":
        method = choose_spgemm_path(a, b, block)
    if method == "gather":
        c, stats, _ = pipeline.spgemm_gather_chunked(a, b, n_chunks=1,
                                                     tile=tile)
    elif method == "block":
        c, stats, _ = pipeline.spgemm_block_chunked(
            a, b, block=block, n_chunks=1, use_pallas=use_pallas)
    else:
        raise ValueError(f"unknown method {method!r}")
    return c, stats


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
    # the sharded path's whole-matrix plan
    return fingerprint_pattern("spgemm_gather", (a, b), digests,
                               tile=cfg.tile)


def _inspect_spgemm_gather(operands, cfg, fp, **kw):
    a, b = operands
    return inspect_spgemm_gather(a, b, cfg.tile, fp)


def _exec_spgemm_gather_chunked(cached, operands, cfg, **kw):
    from repro.runtime.pipeline import spgemm_gather_chunked
    a, b = operands
    c, stats, chunkset = spgemm_gather_chunked(
        a, b, n_chunks=cfg.n_chunks, tile=cfg.tile, chunkset=cached)
    return c, stats, chunkset


def _shard_spgemm_gather(cached, operands, cfg, *, mesh, **kw):
    from repro.runtime.shard import sharded_spgemm_gather
    a, b = operands
    return sharded_spgemm_gather(a, b, mesh, tile=cfg.tile, plan=cached)


def _fp_spgemm_block(operands, cfg, *, chunked, digests=None, **kw):
    a, b = operands
    digests = _spgemm_digests(a, b, digests)
    return fingerprint_pattern("spgemm_block_chunked", (a, b), digests,
                               block=cfg.block, n_chunks=cfg.n_chunks)


def _inspect_spgemm_block(operands, cfg, fp, **kw):
    a, b = operands
    return inspect_spgemm_block(a, b, cfg.block, fp)


def _exec_spgemm_block_chunked(cached, operands, cfg, **kw):
    from repro.runtime.pipeline import spgemm_block_chunked
    a, b = operands
    c, stats, chunkset = spgemm_block_chunked(
        a, b, block=cfg.block, n_chunks=cfg.n_chunks,
        use_pallas=cfg.use_pallas, chunkset=cached)
    return c, stats, chunkset


register_op(OpSpec(tag="spgemm", route=_route_spgemm))

register_op(OpSpec(
    tag="spgemm_gather",
    fingerprint=_fp_spgemm_gather,
    inspect=_inspect_spgemm_gather,
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
    execute_chunked=_exec_spgemm_block_chunked,
    plan_types={"spgemm_block": SpGemmBlockPlan, "bsr_pattern": BsrPattern},
    fingerprint_ops=("spgemm_block_chunked",),
    allowed_kw=("digests",),
))
