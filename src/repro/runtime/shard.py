"""Sharded planned execution: ``shard_plan`` hooks over a device mesh.

The REAP split scaled out: the CPU inspector still builds pattern-pure
plans, but the executor side becomes a *fleet* — each device in the data
axis of a mesh owns a contiguous row range of the computation and streams
only its shard's FLOPs.  Plans are partitioned on the host (index
manipulation stays adjacent to the data that describes it), values are
sharded or replicated per operand, and the device math runs under
``shard_map`` using the *same* math bodies as the single-host executors
(``core.spgemm._gather_math``, ``kernels.bsr_spmm._spmm_math``) — one
definition, so sharded and single-host results are bit-for-bit identical:

* gather-SpGEMM — Gustavson is row-local: every output nonzero is a sum
  over one A-row's partial products, and row-range sharding never splits
  a row, so each per-element summation order is unchanged.
* SpMM — each token row's tile dots are independent of the batch split.
* moe_dispatch — bundling is a pure gather; experts are sharded over the
  data axis and each bundle row is gathered from replicated tokens.

Ops opt in through the registry (``OpSpec.shard_plan`` +
``OpCapabilities.shardable``); ``ReapRuntime.run(..., mesh=...)`` routes
through the hook generically and namespaces the fingerprint with the
shard count, so this module — like the runtime — contains zero op-tag
branches (reaplint REAP002).

Per-mesh ``shard_map`` programs are built once and wrapped in
``persistent_jit`` with the mesh topology folded into the executable key
(``key_extra``), so warm fleet restarts skip XLA and executables never
cross device counts.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.formats import CSR
from repro.core.inspector import (MoeDispatchPlan, PatternFingerprint,
                                  SpGemmGatherPlan, inspect_moe_dispatch,
                                  inspect_spgemm_gather, next_pow2)
from repro.core.spgemm import _gather_math
from repro.kernels.bsr_spmm import SpmmPlan, _spmm_math, inspect_spmm
from repro.parallel.sharding import axis_size, dp_axes
from repro.runtime import spans
from repro.runtime.exec_store import persistent_jit
from repro.runtime.ops import register_plan_type


def data_shard_count(mesh) -> int:
    """Number of shards the mesh's data-parallel axes provide."""
    return axis_size(mesh, dp_axes(mesh))


def shard_bounds(n: int, n_shards: int) -> np.ndarray:
    """Even partition of ``[0, n)`` into exactly ``n_shards`` contiguous
    ranges (shards may be empty when ``n < n_shards``) — ``shard_map``
    needs one fixed-extent operand slice per device, so unlike
    ``pipeline.chunk_row_bounds`` this never merges ranges."""
    return np.linspace(0, n, n_shards + 1).astype(np.int64)


@dataclasses.dataclass(eq=False)
class ShardedPlan:
    """Row-range partition of a gather-SpGEMM inspection across a mesh.

    Shard ``k`` owns A rows ``[bounds[k], bounds[k+1])`` and a chunk-local
    ``SpGemmGatherPlan`` for them (the same row-slice inspection the
    chunked pipeline uses, so per-shard plans are pattern-pure and the
    whole artifact round-trips through the generic serializer).  Ops whose
    single plan is already global (SpMM's weight schedule, MoE's slot
    map) keep their native plan type and derive the value partition at
    execute time instead — only a pattern-pure partition belongs in the
    cache.
    """

    n_shards: int
    n_rows: int
    n_cols: int
    tile: int
    bounds: np.ndarray                  # (n_shards + 1,) A-row ranges
    plans: List[SpGemmGatherPlan]       # one per shard, chunk-local indexing
    fingerprint: Optional[PatternFingerprint] = None


register_plan_type("sharded_plan", ShardedPlan)


# ---------------------------------------------------------------------------
# Per-mesh shard_map programs (memoized; persistent via the exec store)
# ---------------------------------------------------------------------------

_FN_CACHE: Dict[tuple, object] = {}


def _mesh_key(mesh) -> tuple:
    return tuple(zip(tuple(mesh.axis_names), tuple(mesh.devices.shape)))


def _shard_fn(kind: str, mesh, build):
    """Memoize one compiled program per (program kind, mesh topology).

    The key doubles as ``persistent_jit``'s ``key_extra`` so persisted
    executables are scoped to the exact device layout they were built
    for — a warm store never serves an 8-device program to a 4-device
    fleet member.
    """
    key = (kind, _mesh_key(mesh))
    fn = _FN_CACHE.get(key)
    if fn is None:
        fn = _FN_CACHE[key] = build(key)
    return fn


def _gather_shard_fn(mesh):
    axes = dp_axes(mesh)

    def build(key):
        sh = P(axes)

        def impl(a_vals, b_vals, a_idx, b_idx, out_idx, *, c_cap: int):
            def body(av, bv, ai, bi, oi):
                return _gather_math(av[0], bv, ai[0], bi[0], oi[0],
                                    c_cap)[None]
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(sh, P(), sh, sh, sh),
                                 out_specs=sh, check_vma=False)(
                a_vals, b_vals, a_idx, b_idx, out_idx)

        return persistent_jit(impl, static_argnames=("c_cap",),
                              key_extra=key)

    return _shard_fn("gather_pp", mesh, build)


def _spmm_shard_fn(mesh):
    axes = dp_axes(mesh)

    def build(key):
        sh = P(axes)

        def impl(x_tiles, w_tiles, w_id, k_blk, j_blk, *, n_j: int):
            def body(xt, wt, wi, kb, jb):
                return _spmm_math(xt[0], wt, wi, kb, jb, n_j)[None]
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(sh, P(), P(), P(), P()),
                                 out_specs=sh, check_vma=False)(
                x_tiles, w_tiles, w_id, k_blk, j_blk)

        return persistent_jit(impl, static_argnames=("n_j",),
                              key_extra=key)

    return _shard_fn("xw_tiles", mesh, build)


def _moe_shard_fn(mesh):
    axes = dp_axes(mesh)

    def build(key):
        sh = P(axes)

        def impl(slot_token, padded):
            def body(st, pad):
                return pad[st[0]][None]
            return jax.shard_map(body, mesh=mesh, in_specs=(sh, P()),
                                 out_specs=sh, check_vma=False)(
                slot_token, padded)

        return persistent_jit(impl, key_extra=key)

    return _shard_fn("bundle_gather", mesh, build)


# ---------------------------------------------------------------------------
# Sharded gather-SpGEMM
# ---------------------------------------------------------------------------

def sharded_spgemm_gather(a: CSR, b: CSR, mesh, *, tile: int = 1024,
                          plan: Optional[ShardedPlan] = None):
    """C = A @ B across the mesh's data axis.  Returns (C, stats, plan).

    A's rows are range-partitioned (``ShardedPlan``); each shard runs the
    capped gather math on its row slice with B's values replicated.  All
    shards share common pow-2 caps (stacked ``shard_map`` operands need
    one shape), dead slots follow the chunked executor's conventions
    (operand pads gather the appended zero, output pads land in the
    dropped ``c_cap`` segment), and shard outputs are disjoint contiguous
    ordered row ranges — the stitch is an exact concatenation.
    """
    n_shards = data_shard_count(mesh)
    inspect_s = 0.0
    if plan is None:
        with spans.span("reap.inspect") as ins:
            bounds = shard_bounds(a.n_rows, n_shards)
            plans = [inspect_spgemm_gather(
                a.row_slice(int(bounds[k]), int(bounds[k + 1])), b, tile)
                for k in range(n_shards)]
            plan = ShardedPlan(n_shards, a.n_rows, b.n_cols, tile, bounds,
                               plans)
        inspect_s = ins.seconds
    bounds, plans = plan.bounds, plan.plans

    pp_cap = max(next_pow2(max(1, p.a_idx.shape[0] // max(1, plan.tile)))
                 * plan.tile for p in plans)
    vals_cap = next_pow2(max(1, max(
        int(a.indptr[bounds[k + 1]] - a.indptr[bounds[k]])
        for k in range(n_shards))))
    c_cap = max(next_pow2(max(1, p.c_nnz)) for p in plans)

    a_vals = np.zeros((n_shards, vals_cap), a.data.dtype)
    a_idx = np.full((n_shards, pp_cap), vals_cap, np.int64)
    b_idx = np.full((n_shards, pp_cap), len(b.data), np.int64)
    out_idx = np.full((n_shards, pp_cap), c_cap, np.int64)
    for k, p in enumerate(plans):
        s, e = int(a.indptr[bounds[k]]), int(a.indptr[bounds[k + 1]])
        a_vals[k, :e - s] = a.data[s:e]
        n = p.a_idx.shape[0]
        # the plan's own dead slots index its chunk-local data length /
        # c_nnz; remap them to the common caps' zero slot / drop segment
        a_idx[k, :n] = np.where(p.a_idx >= e - s, vals_cap, p.a_idx)
        b_idx[k, :n] = p.b_idx
        out_idx[k, :n] = np.where(p.out_idx >= p.c_nnz, c_cap, p.out_idx)

    with spans.span("reap.execute") as ex:
        fn = _gather_shard_fn(mesh)
        c_sh = np.asarray(fn(
            jnp.asarray(a_vals), jnp.asarray(b.data), jnp.asarray(a_idx),
            jnp.asarray(b_idx), jnp.asarray(out_idx), c_cap=int(c_cap)))
        c_data = np.concatenate(
            [c_sh[k, :p.c_nnz] for k, p in enumerate(plans)])
        c_indptr = np.zeros(plan.n_rows + 1, np.int64)
        c_indptr[1:] = np.cumsum(
            np.concatenate([np.diff(p.c_indptr) for p in plans]))
        c_indices = np.concatenate([p.c_indices for p in plans])
        c = CSR(plan.n_rows, plan.n_cols, c_indptr, c_indices, c_data)
    stats = dict(method="gather_sharded", n_shards=n_shards,
                 inspect_s=inspect_s, execute_s=ex.seconds,
                 n_pp=sum(p.n_pp for p in plans),
                 flops=sum(p.flops() for p in plans))
    return c, stats, plan


# ---------------------------------------------------------------------------
# Sharded SpMM
# ---------------------------------------------------------------------------

def sharded_spmm(x: np.ndarray, w: CSR, mesh, block: int, *,
                 plan: Optional[SpmmPlan] = None, dtype=np.float32):
    """Y = X @ W across the mesh's data axis.  Returns (Y, stats, plan).

    W's plan is global (the schedule depends only on W's pattern); the
    *token* rows of X are range-partitioned per call, every shard padded
    to one common pow-2 token cap, with W's tiles and schedule replicated.
    Always runs the jnp tile math (``_spmm_math``) — the Pallas kernel
    streams a single host-local grid and has no shard_map form.
    """
    n_shards = data_shard_count(mesh)
    inspect_s = 0.0
    if plan is None:
        with spans.span("reap.inspect") as ins:
            plan = inspect_spmm(w, block)
        inspect_s = ins.seconds
    dtype = np.dtype(dtype)
    x = np.asarray(x, dtype)
    t, d_in = x.shape
    if d_in != plan.n_rows:
        raise ValueError(f"x has {d_in} features, W has {plan.n_rows} rows")
    bs = plan.block
    bounds = shard_bounds(t, n_shards)
    t_cap = next_pow2(max(1, int(np.max(np.diff(bounds)))))
    xp = np.zeros((n_shards, t_cap, plan.pat.n_rows), dtype)
    for k in range(n_shards):
        s, e = int(bounds[k]), int(bounds[k + 1])
        xp[k, :e - s, :d_in] = x[s:e]
    x_tiles = xp.reshape(n_shards, t_cap, plan.n_k_blocks, bs
                         ).transpose(0, 2, 1, 3)
    w_tiles = plan.scatter(w.data, dtype=dtype)

    with spans.span("reap.execute") as ex:
        fn = _spmm_shard_fn(mesh)
        out_j = np.asarray(fn(
            jnp.asarray(x_tiles), jnp.asarray(w_tiles),
            jnp.asarray(plan.w_id), jnp.asarray(plan.k_blk),
            jnp.asarray(plan.j_blk),
            n_j=plan.n_j_blocks))       # (n_shards, n_j, t_cap, bs)
        pieces = []
        for k in range(n_shards):
            s, e = int(bounds[k]), int(bounds[k + 1])
            y_k = out_j[k].swapaxes(0, 1).reshape(t_cap,
                                                  plan.n_j_blocks * bs)
            pieces.append(y_k[:e - s])
        y = np.concatenate(pieces)[:, :plan.n_cols]
    stats = dict(method="spmm_sharded", n_shards=n_shards,
                 inspect_s=inspect_s, execute_s=ex.seconds,
                 n_jobs=plan.n_jobs,
                 fill=plan.pat.fill, flops=plan.flops(t))
    return y, stats, plan


# ---------------------------------------------------------------------------
# Expert-parallel MoE dispatch
# ---------------------------------------------------------------------------

def sharded_moe_dispatch(tokens: np.ndarray, routing: CSR, capacity: int,
                         mesh, *, plan: Optional[MoeDispatchPlan] = None):
    """Expert-parallel bundling across the mesh's data axis.

    The dispatch plan is global (slot map over all experts); each shard
    gathers its expert block's ``(experts/n_shards, capacity, d)`` bundles
    from the replicated padded token table — a pure gather, so results
    are trivially identical to ``plan.bundle``.  When ``n_experts`` does
    not divide evenly, falls back to the host gather (the plan is still
    built, cached, and returned).  Returns ((x_bundles, plan), stats,
    plan) — the result shape of the single-host executor.
    """
    n_shards = data_shard_count(mesh)
    inspect_s = 0.0
    if plan is None:
        with spans.span("reap.inspect") as ins:
            plan = inspect_moe_dispatch(routing, capacity)
        inspect_s = ins.seconds
    tokens = np.asarray(tokens)
    with spans.span("reap.bundle"):
        if plan.n_experts % n_shards:
            x_bundles = plan.bundle(tokens)
            sharded = False
        else:
            d = tokens.shape[-1]
            pad = np.concatenate([tokens, np.zeros((1, d), tokens.dtype)])
            st = plan.slot_token.reshape(
                n_shards, plan.n_experts // n_shards, plan.capacity)
            fn = _moe_shard_fn(mesh)
            x_bundles = np.asarray(fn(jnp.asarray(st), jnp.asarray(pad))
                                   ).reshape(plan.n_experts, plan.capacity,
                                             d)
            sharded = True
    stats = dict(method="dispatch_sharded", n_shards=n_shards,
                 sharded=sharded, inspect_s=inspect_s,
                 capacity=plan.capacity, dropped=plan.dropped_frac)
    return (x_bundles, plan), stats, plan
