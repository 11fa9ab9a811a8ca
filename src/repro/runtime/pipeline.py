"""Pipelined inspector/executor: the one executor of each planned op.

REAP's input controller keeps the FPGA pipelines busy while the CPU keeps
producing RIR bundles; here the same overlap is software: the schedule-bundle
stream is chunked, and while the device executes chunk *k* a worker thread
emits chunk *k+1* (double-buffering).  Two pipelines, each the only
executor of its op at any chunk count (one chunk runs inline), and the
Cholesky executor:

  * ``spgemm_gather_chunked`` — A's rows are partitioned into nnz-balanced
    chunks; each chunk is an independent Gustavson sub-problem whose output
    rows are disjoint, so results concatenate exactly.
  * ``spgemm_block_chunked`` — the block plan's pair schedule is cut at
    output-group starts; each chunk's operand tiles are scattered on the
    worker while the device runs the previous chunk's tile dots.
  * ``cholesky_execute_overlapped`` — one launch per etree level.  The
    levels' index bundles are pattern-pure, packed once per plan and kept
    on the device, so nothing is left to emit: the host dispatches level
    ℓ+1 while the device runs level ℓ, and a factor sends only its values.

``run_overlapped`` is the shared engine of the two SpGEMM pipelines.
"""
from __future__ import annotations

import dataclasses
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from repro.core.cholesky import _first_starts, _level_step, init_values
from repro.core.etree import CholeskyPlan
from repro.core.formats import CSR
from repro.core.inspector import (PatternFingerprint, SpGemmBlockPlan,
                                  SpGemmGatherPlan, inspect_spgemm_block,
                                  inspect_spgemm_gather, next_pow2)
from repro.core.spgemm import (block_result_to_csr, _block_execute_jnp,
                               spgemm_gather_execute_chunk)

from . import spans


@dataclasses.dataclass
class OverlapStats:
    """Timing split of one pipelined run.

    ``inspect_s``/``execute_s`` are summed per-chunk stage times;
    ``wall_s`` is end-to-end.  With more than one chunk the emit stage runs
    on the worker, and wall_s < inspect_s + execute_s measures how much
    host work the device time hid.
    """

    n_chunks: int
    inspect_s: float
    execute_s: float
    wall_s: float

    @property
    def overlap(self) -> bool:
        """Whether the emit stage ran on the worker (more than one chunk)."""
        return self.n_chunks > 1

    @property
    def hidden_s(self) -> float:
        return max(0.0, self.inspect_s + self.execute_s - self.wall_s)


_EMIT_POOL: Optional[ThreadPoolExecutor] = None
_EMIT_POOL_LOCK = threading.Lock()


def _emit_pool() -> ThreadPoolExecutor:
    """Process-wide single worker for bundle emission.

    Created once (under a lock) and reused so a pipelined call does not pay
    OS thread spawn.  One worker deliberately serializes emission across
    concurrent pipelines in the same process, exactly like the paper's
    single CPU feeding the input controller — concurrent ReapRuntime calls
    share the emission core rather than oversubscribing the host.
    """
    global _EMIT_POOL
    with _EMIT_POOL_LOCK:
        if _EMIT_POOL is None:
            _EMIT_POOL = ThreadPoolExecutor(max_workers=1,
                                            thread_name_prefix="reap-emit")
    return _EMIT_POOL


def run_overlapped(n_chunks: int,
                   inspect_fn: Callable[[int], object],
                   execute_fn: Callable[[int, object], object],
                   execute_span: str = "reap.execute"
                   ) -> Tuple[List[object], OverlapStats]:
    """Double-buffered inspector/executor driver.

    ``inspect_fn(k)`` must be independent of execution results (pure host
    pattern work); ``execute_fn(k, artifact)`` may carry sequential state.
    While chunk *k* executes, chunk *k+1* is inspected on a worker thread;
    a single chunk runs inline.

    Spans: ``reap.pipeline`` (the whole call: ``wall_s``), ``reap.emit``
    (each ``inspect_fn`` call, on the worker when overlapped, counted into
    the caller's run record: ``inspect_s``), ``reap.emit_wait`` (the caller
    blocked on the worker) and ``execute_span`` (each ``execute_fn`` call:
    ``execute_s``).
    """
    inspect_s = 0.0
    execute_s = 0.0
    results: List[object] = []
    rec = spans.current()

    def timed_inspect(k: int):
        with spans.bind(rec), spans.span("reap.emit") as emit:
            art = inspect_fn(k)
        return art, emit.seconds

    def timed_execute(k: int, art) -> float:
        with spans.span(execute_span) as ex:
            results.append(execute_fn(k, art))
        return ex.seconds

    with spans.span("reap.pipeline") as wall:
        if n_chunks <= 1:
            for k in range(n_chunks):
                art, dt = timed_inspect(k)
                inspect_s += dt
                execute_s += timed_execute(k, art)
        else:
            pool = _emit_pool()
            fut = pool.submit(timed_inspect, 0)
            try:
                for k in range(n_chunks):
                    with spans.span("reap.emit_wait"):
                        art, dt = fut.result()
                    inspect_s += dt
                    if k + 1 < n_chunks:
                        fut = pool.submit(timed_inspect, k + 1)  # prefetch
                    execute_s += timed_execute(k, art)
            finally:
                # on an execute_fn error, settle the in-flight prefetch so
                # the shared worker is idle (and its exception consumed)
                # before the caller unwinds — the per-call-pool join this
                # pool replaced
                fut.cancel()
                try:
                    fut.exception()
                except BaseException:   # CancelledError is a BaseException
                    pass
    stats = OverlapStats(n_chunks, inspect_s, execute_s, wall.seconds)
    return results, stats


# ---------------------------------------------------------------------------
# Chunked SpGEMM (gather path)
# ---------------------------------------------------------------------------

def chunk_row_bounds(a: CSR, n_chunks: int) -> np.ndarray:
    """Partition A's rows into ≤ n_chunks contiguous, nnz-balanced ranges.

    Each cut is the first row boundary at or past its share of A's nnz;
    rows are never split and empty ranges are merged.  A chunk's device
    cost follows its live partial products padded to a power-of-two bucket
    (``SpGemmGatherPlan.device_indices``), not its nnz, so on a skewed
    pattern the chunks' products differ (``spgemm_gather_chunked`` counts
    the largest as ``gather_chunk_products_max``).  Cutting by products
    instead is not better by itself: on the webbase-1M stand-in it evens
    the products (largest chunk 20.0 % over the mean → 0.01 %) but lifts
    the three chunks that sit just under 2**24 products over that bucket
    edge, 83.9 M → 134.2 M padded slots.
    """
    n_chunks = max(1, min(n_chunks, a.n_rows))
    targets = a.nnz * np.arange(1, n_chunks) / n_chunks
    cuts = np.searchsorted(a.indptr, targets, side="left")
    return np.unique(np.concatenate(
        [[0], np.minimum(cuts, a.n_rows), [a.n_rows]])).astype(np.int64)


@dataclasses.dataclass(eq=False)
class GatherChunkSet:
    """Cached artifact of a chunked gather inspection: one plan per chunk.

    Plans use chunk-local row/nnz indexing; ``row_bounds[k]`` maps chunk k
    back to A's global rows.  Pattern-pure, so one chunk set serves every
    same-pattern call.
    """

    n_rows: int
    n_cols: int
    tile: int
    row_bounds: np.ndarray
    plans: List[SpGemmGatherPlan]
    fingerprint: Optional[PatternFingerprint] = None

    @property
    def n_chunks(self) -> int:
        return len(self.plans)


def spgemm_gather_chunked(a: CSR, b: CSR, n_chunks: int = 4,
                          tile: int = 1024,
                          chunkset: Optional[GatherChunkSet] = None
                          ) -> Tuple[CSR, dict, GatherChunkSet]:
    """C = A @ B, chunked over A's rows with inspect/execute overlap.

    With a warm ``chunkset`` (plan-cache hit) inspection degenerates to a
    list lookup and the pipeline is pure execution.  Returns
    (C, stats, chunkset) so callers can cache the chunk set.

    A's rows are cut into nnz-balanced chunks (``chunk_row_bounds``); a
    warm ``chunkset`` keeps the bounds it was built with.

    Spans, besides ``run_overlapped``'s: ``reap.inspect`` around each chunk
    plan built on a miss (inside that chunk's ``reap.emit``; their sum is
    ``stats["plan_s"]``, 0 on a warm call), the executor's own
    (``spgemm_gather_execute_chunk``), and ``reap.extract`` around the
    stitch of the chunk results into one CSR.  Counter, besides the
    executor's: ``gather_chunk_products_max``, the largest chunk plan's
    live partial products, once per call.
    """
    bounds = (chunkset.row_bounds if chunkset is not None
              else chunk_row_bounds(a, n_chunks))
    nk = len(bounds) - 1
    plans: List[Optional[SpGemmGatherPlan]] = (
        list(chunkset.plans) if chunkset is not None else [None] * nk)
    plan_s = [0.0] * nk

    def inspect_fn(k: int) -> SpGemmGatherPlan:
        if plans[k] is None:
            with spans.span("reap.inspect") as ins:
                plans[k] = inspect_spgemm_gather(
                    a.row_slice(int(bounds[k]), int(bounds[k + 1])), b,
                    tile)
            plan_s[k] = ins.seconds
        return plans[k]

    def execute_fn(k: int, plan: SpGemmGatherPlan) -> np.ndarray:
        s, e = int(a.indptr[bounds[k]]), int(a.indptr[bounds[k + 1]])
        return spgemm_gather_execute_chunk(plan, a.data[s:e], b.data)

    chunks, ostats = run_overlapped(nk, inspect_fn, execute_fn)
    spans.count("gather_chunk_products_max",
                max((p.n_pp for p in plans), default=0))

    # stitch: chunk output rows are disjoint, contiguous, and ordered
    with spans.span("reap.extract"):
        c_indptr = np.zeros(a.n_rows + 1, dtype=np.int64)
        row_nnz = np.concatenate([np.diff(p.c_indptr) for p in plans]) \
            if nk else np.zeros(0, np.int64)
        c_indptr[1:] = np.cumsum(row_nnz)
        c_indices = (np.concatenate([p.c_indices for p in plans])
                     if nk else np.zeros(0, np.int64))
        c_data = (np.concatenate(chunks) if nk
                  else np.zeros(0, a.data.dtype))
        c = CSR(a.n_rows, b.n_cols, c_indptr, c_indices, c_data)
    out_set = chunkset if chunkset is not None else GatherChunkSet(
        a.n_rows, b.n_cols, tile, bounds, plans)  # type: ignore[arg-type]
    stats = dict(method="gather_chunked", n_chunks=nk, plan_s=sum(plan_s),
                 overlap=ostats.overlap, inspect_s=ostats.inspect_s,
                 execute_s=ostats.execute_s, wall_s=ostats.wall_s,
                 hidden_s=ostats.hidden_s,
                 n_pp=sum(p.n_pp for p in plans),
                 flops=sum(p.flops() for p in plans))
    return c, stats, out_set


# ---------------------------------------------------------------------------
# Chunked SpGEMM (block/MXU path) — schedule groups as chunk boundaries
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class BlockChunk:
    """One output-group-aligned slice of a block plan's pair schedule.

    Ids are chunk-local: ``a_id``/``b_id`` index the chunk's compact operand
    tile arrays, ``out_id`` is 0-based within the chunk.  The ``*_sel`` /
    ``*_eblk``/``*_erow``/``*_ecol`` arrays are the chunk-local scatter maps
    (which source CSR elements land where in the chunk's operand tiles) —
    the per-call value pass the pipeline overlaps with device execution.
    """

    a_id: np.ndarray
    b_id: np.ndarray
    out_id: np.ndarray
    is_first: np.ndarray
    is_last: np.ndarray
    n_out_blocks: int
    n_a_blocks: int
    n_b_blocks: int
    a_sel: np.ndarray
    a_eblk: np.ndarray
    a_erow: np.ndarray
    a_ecol: np.ndarray
    b_sel: np.ndarray
    b_eblk: np.ndarray
    b_erow: np.ndarray
    b_ecol: np.ndarray

    @property
    def n_pairs(self) -> int:
        return int(self.a_id.shape[0])


@dataclasses.dataclass(eq=False)
class BlockChunkSet:
    """Cached artifact of a chunked block inspection: the full plan plus its
    output-group-aligned chunk slices.  Pattern-pure like every plan.

    Chunk slices are built lazily — ``chunk(k)`` materializes on first use,
    so the overlapped pipeline constructs chunk *k+1*'s slice on the worker
    thread while the device executes chunk *k* (the gather path builds its
    per-chunk plans the same way).  A cached (warm) chunk set is fully
    materialized and ``chunk(k)`` degenerates to a list lookup.
    """

    plan: SpGemmBlockPlan
    out_bounds: np.ndarray          # (n_chunks+1,) out-block index bounds
    pair_bounds: np.ndarray         # (n_chunks+1,) pair index bounds
    chunks: List[Optional[BlockChunk]]
    fingerprint: Optional[PatternFingerprint] = None

    @property
    def n_chunks(self) -> int:
        return len(self.chunks)

    def chunk(self, k: int) -> BlockChunk:
        if self.chunks[k] is None:
            self.chunks[k] = _build_block_chunk(
                self.plan, int(self.out_bounds[k]),
                int(self.pair_bounds[k]), int(self.pair_bounds[k + 1]))
        return self.chunks[k]

    def materialize(self) -> None:
        """Force every lazy chunk slice (serialize_plan calls this)."""
        for k in range(self.n_chunks):
            self.chunk(k)


def _chunk_scatter_maps(pat, blk_ids: np.ndarray):
    """Restrict a BsrPattern's element scatter to the given (sorted, unique)
    block ids, re-indexed to the chunk's compact tile array."""
    mask = np.isin(pat.elem_block, blk_ids)
    sel = np.flatnonzero(mask)
    local = np.searchsorted(blk_ids, pat.elem_block[sel])
    return sel, local, pat.elem_row[sel], pat.elem_col[sel]


def _build_block_chunk(plan: SpGemmBlockPlan, out0: int, s: int, e: int
                       ) -> BlockChunk:
    """Materialize one chunk slice: local schedule + operand scatter maps."""
    a_uniq, a_local = np.unique(plan.a_id[s:e], return_inverse=True)
    b_uniq, b_local = np.unique(plan.b_id[s:e], return_inverse=True)
    a_sel, a_eblk, a_erow, a_ecol = _chunk_scatter_maps(plan.a_pat, a_uniq)
    b_sel, b_eblk, b_erow, b_ecol = _chunk_scatter_maps(plan.b_pat, b_uniq)
    n_out = int(plan.out_id[e - 1]) - out0 + 1
    return BlockChunk(
        a_local.astype(np.int64), b_local.astype(np.int64),
        (plan.out_id[s:e] - out0).astype(np.int64),
        plan.is_first[s:e].copy(), plan.is_last[s:e].copy(),
        n_out, int(a_uniq.shape[0]), int(b_uniq.shape[0]),
        a_sel, a_eblk, a_erow, a_ecol, b_sel, b_eblk, b_erow, b_ecol)


def bucket_block_schedule(ch: BlockChunk) -> dict:
    """Pow-2-bucketed executor operands for one block chunk (memoized).

    Without bucketing every distinct chunk shape — pair count, operand tile
    counts, output block count — is a fresh XLA compile, so a mixed-pattern
    workload replaying persisted plans triggers a recompile storm.  This
    pads all four executor dimensions to power-of-two buckets, mirroring
    ``spgemm_gather_execute_chunk`` on the gather path: compiled shapes are
    ``(pair_cap,)`` schedules over ``(a_cap, bs, bs)``/``(b_cap, bs, bs)``
    operand tiles with ``out_cap + 1`` output tiles, O(log) distinct shapes
    across any stream of chunks.

    Dead schedule slots form one trailing ``is_first``/``is_last`` group
    whose products (of real operand tiles, so indices stay in bounds)
    accumulate into the dummy output tile at index ``out_cap``; callers
    slice the result back to the chunk's true ``n_out_blocks``.  Memoized
    as a plain attribute — pattern-pure, rebuilt after deserialization,
    skipped by serialization.
    """
    cached = getattr(ch, "_bucketed", None)
    if cached is not None:
        return cached
    n = ch.n_pairs
    pair_cap = next_pow2(max(1, n))
    out_cap = next_pow2(max(1, ch.n_out_blocks))
    pad = pair_cap - n

    def sched(arr, fill, pad_first=0, pad_last=0):
        out = arr.astype(np.int32)
        if pad:
            tail = np.full(pad, fill, np.int32)
            tail[0], tail[-1] = tail[0] + pad_first, tail[-1] + pad_last
            out = np.concatenate([out, tail])
        return out

    cached = dict(a_id=sched(ch.a_id, 0), b_id=sched(ch.b_id, 0),
                  out_id=sched(ch.out_id, out_cap),
                  is_first=sched(ch.is_first, 0, pad_first=1),
                  is_last=sched(ch.is_last, 0, pad_last=1),
                  pair_cap=pair_cap, out_cap=out_cap,
                  a_cap=next_pow2(max(1, ch.n_a_blocks)),
                  b_cap=next_pow2(max(1, ch.n_b_blocks)))
    ch._bucketed = cached
    return cached


def build_block_chunkset(plan: SpGemmBlockPlan, n_chunks: int,
                         lazy: bool = False) -> BlockChunkSet:
    """Split a block plan's pair schedule into ≤ n_chunks chunks.

    The schedule is sorted by output block with ``is_first``/``is_last``
    marking group runs, so cutting only at group starts keeps every output
    block whole within one chunk — per-chunk results are disjoint slices of
    the output tile array and concatenate exactly.

    With ``lazy=True`` only the (cheap) bounds are computed; chunk slices
    materialize on first ``chunk(k)`` — inside the overlapped pipeline's
    emit stage, where their cost hides under device execution.
    """
    n_out = plan.n_out_blocks
    if n_out == 0 or plan.n_pairs == 0:
        return BlockChunkSet(plan, np.zeros(1, np.int64),
                             np.zeros(1, np.int64), [])
    n_chunks = max(1, min(n_chunks, n_out))
    group_starts = np.flatnonzero(plan.is_first)        # (n_out,)
    # pair-balanced cuts, snapped to group boundaries
    targets = plan.n_pairs * np.arange(1, n_chunks) / n_chunks
    cuts = np.searchsorted(group_starts, targets, side="left")
    ob = np.unique(np.concatenate([[0], cuts, [n_out]])).astype(np.int64)
    pair_bounds = np.concatenate([group_starts[ob[:-1]], [plan.n_pairs]])
    chunkset = BlockChunkSet(plan, ob, pair_bounds,
                             [None] * (len(ob) - 1))
    if not lazy:
        for k in range(chunkset.n_chunks):
            chunkset.chunk(k)
    return chunkset


def _run_block_chunks(chunkset: BlockChunkSet, a_data: np.ndarray,
                      b_data: np.ndarray, use_pallas: bool
                      ) -> Tuple[List[np.ndarray], OverlapStats]:
    """Each chunk of a block chunk set through the emit stage (the host
    scatter into bucketed tiles, on the worker) and the execute stage
    (transfer, launch, fetch); returns each chunk's output tiles and the
    pipeline's stats."""
    bs = chunkset.plan.block
    # the schedule arrays each executor takes (int32 already: bucketed)
    sched_keys = (("a_id", "b_id", "out_id", "is_first", "is_last")
                  if use_pallas else ("a_id", "b_id", "out_id"))

    def emit_fn(k: int):
        # host-side *emit* stage (not inspection — it scatters operand
        # values into RIR tiles, so it must not carry an inspect_* name):
        # pow-2-bucketed tile arrays (bucket_block_schedule) keep the
        # executor at O(log) distinct shapes across a chunk stream
        ch = chunkset.chunk(k)
        sched = bucket_block_schedule(ch)
        a_blocks = np.zeros((sched["a_cap"], bs, bs), np.float32)
        a_blocks[ch.a_eblk, ch.a_erow, ch.a_ecol] = a_data[ch.a_sel]
        b_blocks = np.zeros((sched["b_cap"], bs, bs), np.float32)
        b_blocks[ch.b_eblk, ch.b_erow, ch.b_ecol] = b_data[ch.b_sel]
        return ch, sched, a_blocks, b_blocks

    def execute_fn(k: int, emitted) -> np.ndarray:
        ch, sched, a_blocks, b_blocks = emitted
        n_out_cap = sched["out_cap"] + 1    # +1: dummy tile for dead slots
        with spans.span("reap.h2d"):
            a_dev, b_dev = jnp.asarray(a_blocks), jnp.asarray(b_blocks)
            ids = {key: jnp.asarray(sched[key]) for key in sched_keys}
            spans.count("h2d_bytes", a_dev.nbytes + b_dev.nbytes
                        + sum(v.nbytes for v in ids.values()))
        with spans.span("reap.launch"):
            if use_pallas:
                from repro.kernels import ops as kops
                out = kops.bsr_spgemm_schedule(ids, a_dev, b_dev,
                                               n_out_blocks=n_out_cap)
            else:
                out = _block_execute_jnp(a_dev, b_dev, ids["a_id"],
                                         ids["b_id"], ids["out_id"],
                                         n_out=n_out_cap)
        return spans.to_host(out)[:ch.n_out_blocks]

    return run_overlapped(chunkset.n_chunks, emit_fn, execute_fn)


def block_chunk_tiles(chunkset: BlockChunkSet, a_data: np.ndarray,
                      b_data: np.ndarray, use_pallas: bool = True
                      ) -> np.ndarray:
    """The (n_out_blocks, block, block) output tiles of a block chunk set
    for CSR value arrays of A and B, through the pipeline's stages (zero
    tiles for an empty schedule)."""
    plan = chunkset.plan
    if not chunkset.chunks:
        return np.zeros((plan.n_out_blocks, plan.block, plan.block),
                        np.float32)
    tiles, _ = _run_block_chunks(chunkset, a_data, b_data, use_pallas)
    return np.concatenate(tiles, axis=0)


def spgemm_block_chunked(a: CSR, b: CSR, block: int = 128, n_chunks: int = 4,
                         use_pallas: bool = True,
                         chunkset: Optional[BlockChunkSet] = None
                         ) -> Tuple[CSR, dict, BlockChunkSet]:
    """C = A @ B on the MXU path with per-chunk emit/execute overlap.

    The bundle-emit stage per chunk — scattering the chunk's operand CSR
    values into compact MXU tiles — runs on the worker thread while the
    device executes the previous chunk's tile dots (the gather path's
    pipeline, applied to the block executor).  Returns (C, stats, chunkset)
    so callers can cache the chunk set; a warm chunkset skips plan-build
    entirely and the pipeline is scatter+execute only.

    The output tiles go to CSR through ``block_result_to_csr``, whose
    extraction index (``plan.out_csr_index``) this pipeline builds on the
    plan's first product by running its own chunk programs on indicator
    values.
    """
    plan_s = 0.0
    if chunkset is None:
        with spans.span("reap.inspect") as ins:
            plan = inspect_spgemm_block(a, b, block)
            # bounds only: chunk slices materialize inside the emit stage,
            # one chunk ahead of the device (hidden under execution when
            # overlapped)
            chunkset = build_block_chunkset(plan, n_chunks, lazy=True)
        plan_s = ins.seconds
    plan = chunkset.plan

    def indicator_tiles(a_data: np.ndarray, b_data: np.ndarray):
        # the plan's extraction index, on a miss: the same chunk programs
        # (no compile), kept out of this product's run record
        with spans.bind(None):
            return block_chunk_tiles(chunkset, a_data, b_data, use_pallas)

    base = dict(method="block_chunked", n_chunks=chunkset.n_chunks,
                plan_s=plan_s, flops=plan.flops(), n_pairs=plan.n_pairs,
                fill=plan.a_pat.fill)
    if not chunkset.chunks:
        zero = block_chunk_tiles(chunkset, a.data, b.data)
        plan.out_csr_index(indicator_tiles)
        c = block_result_to_csr(plan, zero, a.n_rows, b.n_cols)
        base.update(overlap=False, inspect_s=0.0, execute_s=0.0,
                    wall_s=plan_s, hidden_s=0.0)
        return c, base, chunkset

    results, ostats = _run_block_chunks(chunkset, a.data, b.data, use_pallas)
    with spans.span("reap.extract"):
        c_blocks = np.concatenate(results, axis=0)
        plan.out_csr_index(indicator_tiles)
        c = block_result_to_csr(plan, c_blocks, a.n_rows, b.n_cols)
    base.update(overlap=ostats.overlap, inspect_s=ostats.inspect_s,
                execute_s=ostats.execute_s, wall_s=ostats.wall_s,
                hidden_s=ostats.hidden_s)
    return c, base, chunkset


# ---------------------------------------------------------------------------
# Cholesky (one launch per etree level, bundles kept on the device)
# ---------------------------------------------------------------------------

def _put_level_bundles(host):
    """Uploads a plan's packed level bundles (a memo miss): under
    ``reap.h2d``, the six index arrays, their puts and bytes counted, and
    one ``cholesky_bundle_builds``; the first level's starts (zeros) are
    made on the device, with no put."""
    with spans.span("reap.h2d"):
        dev = [jnp.asarray(x) for x in host[:6]]
        spans.count("h2d_puts", len(dev))
        spans.count("h2d_bytes", sum(x.nbytes for x in dev))
    spans.count("cholesky_bundle_builds")
    return dev + [_first_starts(host[6].dtype)]


def cholesky_execute_overlapped(plan: CholeskyPlan, a_vals: np.ndarray,
                                dtype=jnp.float64
                                ) -> Tuple[np.ndarray, dict]:
    """Numeric phase: one ``_level_step`` launch per etree level.

    The levels' index bundles live on the device, packed into six flat
    arrays (``plan.device_bundles``: pattern-pure, built on the plan's
    first factor and freed with the plan), with the first level's slice
    starts, so a factor sends only the value array; each step hands the
    next level its starts on the device, so a launch sends nothing.
    Dispatch is asynchronous, so the host issues level ℓ+1 while the
    device runs level ℓ.  ``a_vals`` are A's lower-triangle values in the
    plan's order (``plan.a_values(a)`` or ``cholesky_values(a)``); returns
    (L values in CSC order, stats).

    Spans: ``reap.values`` (the bundle memo — a lookup on a warm plan; on
    the plan's first factor the packing, with the upload nested under
    ``reap.h2d`` — and the put of the values), ``reap.dispatch`` (the
    launch loop) and ``reap.drain`` (the wait for the device).  Counters:
    ``launches``, ``h2d_puts``/``h2d_bytes`` (the values; the memo's
    arrays too on the factor that builds it) and
    ``cholesky_bundle_builds`` (1 on that factor, else 0).
    """
    spans.count("cholesky_bundle_builds", 0)
    with spans.span("reap.values"):
        bundles = plan.device_bundles(_put_level_bundles)
        vals = init_values(plan, a_vals, dtype)
        spans.count("h2d_puts", 1)
        spans.count("h2d_bytes", vals.nbytes)
    state = (vals, bundles.start)
    index = bundles[:6]
    with spans.span("reap.dispatch") as dispatch:
        for n in bundles.buckets.tolist():
            state = _level_step(state, *index, *n)
        spans.count("launches", plan.n_levels)
    vals = state[0]
    with spans.span("reap.drain") as drain:
        # reaplint: disable=REAP003 deliberate drain: queued device work is
        # blocked on inside the timed region so execute_s measures device
        # completion, not dispatch
        vals.block_until_ready()
    stats = dict(execute_s=dispatch.seconds + drain.seconds,
                 n_levels=plan.n_levels, nnz_l=plan.nnz, flops=plan.flops())
    return spans.to_host(vals[:plan.nnz]), stats


# ---------------------------------------------------------------------------
# Op-registry plan types: chunk sets serialize through the generic
# serializer, so their names live in the registry's type table next to
# their definitions (the per-op plan dataclasses register via OpSpec).
# ---------------------------------------------------------------------------

from .ops import register_plan_type  # noqa: E402

register_plan_type("gather_chunkset", GatherChunkSet)
register_plan_type("block_chunkset", BlockChunkSet)
register_plan_type("block_chunk", BlockChunk)
