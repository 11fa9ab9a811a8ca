"""Pallas TPU kernel: schedule-driven block SpGEMM (REAP's SpGEMM executor).

The inspector's schedule bundle (a_id, b_id, out_id, is_first) is passed
as **scalar prefetch** operands; the BlockSpec index maps consult it to
route operand tiles — the TPU analogue of REAP's input controller reading
RIR metadata and routing bundles to pipelines (DESIGN.md §2).

The schedule is sorted by output block, so each output tile stays resident
in VMEM across its group of (A-block @ B-block) MXU dots and is flushed to
HBM exactly once — the paper's "partial results maintained in bundles,
merged before write-back" property.

Scalar-prefetch operands live in SMEM (1 MiB on a TPU v5e), so one
``pallas_call`` holds at most ``LAUNCH_PAIRS`` pairs.  Longer schedules run
as a sequence of launches over one aliased output buffer: a launch that
starts inside an output group resumes that tile from the previous launch's
partial sum, so launch boundaries may fall anywhere.

Grid: one step per scheduled block pair.  Block shapes: (1, bs, bs) tiles of
the (n_blocks, bs, bs) bundle arrays; bs should be an MXU-aligned 128 on
real hardware (the CPU tests also sweep smaller bs in interpret mode).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import I0, dot_precision, resolve_interpret

#: Pairs per launch.  The four int32 schedule arrays of a launch take
#: 16 bytes per pair of SMEM: 2**15 pairs use 512 KiB of the 1 MiB.
LAUNCH_PAIRS = 1 << 15


def _kernel(a_id, b_id, out_id, is_first, a_ref, b_ref, *refs):
    """Grid step t: ``o[out_id[t]] (+)= a[a_id[t]] @ b[b_id[t]]``.

    ``refs`` is ``(o_ref,)`` for a schedule's first launch and
    ``(carry_ref, o_ref)`` for later ones, where ``carry_ref`` is the
    previous launches' output tile of this launch's first group.
    """
    del a_id, b_id, out_id
    o_ref = refs[-1]
    t = pl.program_id(0)

    @pl.when(is_first[t] == 1)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    if len(refs) == 2:
        carry_ref = refs[0]

        @pl.when((t == 0) & (is_first[t] == 0))
        def _resume():
            o_ref[...] = carry_ref[...]

    o_ref[0] += jnp.dot(a_ref[0], b_ref[0],
                        precision=dot_precision(a_ref.dtype),
                        preferred_element_type=jnp.float32)


def _launch(a_blocks, b_blocks, a_id, b_id, out_id, is_first, carry,
            n_out_blocks: int, interpret: bool):
    """One ``pallas_call`` over a schedule slice; ``carry`` (the output of
    the previous launches, or None) is aliased to this launch's output."""
    n_pairs = a_id.shape[0]
    bs = a_blocks.shape[-1]
    tile = (1, bs, bs)
    in_specs = [
        pl.BlockSpec(tile, lambda t, aid, bid, oid, fi: (aid[t], I0, I0)),
        pl.BlockSpec(tile, lambda t, aid, bid, oid, fi: (bid[t], I0, I0))]
    operands = [a_blocks, b_blocks]
    aliases = {}
    if carry is not None:
        # fetched once per launch: the block index never changes
        in_specs.append(pl.BlockSpec(
            tile, lambda t, aid, bid, oid, fi: (oid[0], I0, I0)))
        operands.append(carry)
        aliases = {6: 0}        # 4 scalar-prefetch operands + a + b
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_pairs,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(tile, lambda t, aid, bid, oid, fi:
                               (oid[t], I0, I0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out_blocks, bs, bs), jnp.float32),
        input_output_aliases=aliases,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * int(n_pairs) * bs ** 3,
            bytes_accessed=3 * int(n_pairs) * bs * bs * 4,
            transcendentals=0),
    )(a_id, b_id, out_id, is_first, *operands)


@functools.partial(jax.jit, static_argnames=("n_out_blocks", "launch_pairs",
                                             "interpret"))
def bsr_spgemm(a_blocks, b_blocks, a_id, b_id, out_id, is_first, is_last,
               *, n_out_blocks: int, launch_pairs: int = LAUNCH_PAIRS,
               interpret: Optional[bool] = None):
    """C_blocks[out_id[t]] += A_blocks[a_id[t]] @ B_blocks[b_id[t]].

    a_blocks: (na, bs, bs) f32; b_blocks: (nb, bs, bs) f32.
    Schedule arrays: (n_pairs,) int32, sorted by out_id, with group-boundary
    flags (``is_last`` is part of the bundle format; the kernel does not
    need it).  Runs ``ceil(n_pairs / launch_pairs)`` launches.  Returns
    (n_out_blocks, bs, bs) f32; tiles no pair writes are undefined.
    """
    del is_last
    interpret = resolve_interpret(interpret)
    n_pairs = a_id.shape[0]
    if n_pairs == 0:
        return jnp.zeros((n_out_blocks,) + a_blocks.shape[1:], jnp.float32)
    out = None
    for s in range(0, n_pairs, launch_pairs):
        e = min(s + launch_pairs, n_pairs)
        out = _launch(a_blocks, b_blocks, a_id[s:e], b_id[s:e], out_id[s:e],
                      is_first[s:e], out, n_out_blocks, interpret)
    return out


def bsr_spgemm_schedule(schedule, a_blocks, b_blocks, *, n_out_blocks: int,
                        interpret: Optional[bool] = None):
    """Runtime entry point: drive the kernel from an RIR ScheduleBundle.

    ``schedule`` is a plan's metadata-only bundle (``plan.schedule`` for a
    ``SpGemmBlockPlan``) — the arrays the inspector emitted become the
    scalar-prefetch operands directly, so a cached plan replays onto fresh
    operand tiles with zero re-inspection.
    """
    return bsr_spgemm(
        a_blocks, b_blocks,
        jnp.asarray(schedule["a_id"], jnp.int32),
        jnp.asarray(schedule["b_id"], jnp.int32),
        jnp.asarray(schedule["out_id"], jnp.int32),
        jnp.asarray(schedule["is_first"], jnp.int32),
        jnp.asarray(schedule["is_last"], jnp.int32),
        n_out_blocks=n_out_blocks, interpret=interpret)
