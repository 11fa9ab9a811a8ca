"""Pallas TPU kernel: capacity-bundled expert GEMM (MoE RIR dispatch executor).

The beyond-paper generalization (DESIGN.md §4): token→expert routing is an
irregular sparse pattern; the host/router packs tokens into fixed-capacity
bundles per expert (RIR discipline: padded, contiguous, metadata-carrying),
and this kernel streams them through the MXU as dense tiles.  The
bundle→expert map is the schedule bundle, consumed via scalar prefetch so
only the needed expert tile is DMA'd per bundle — experts the bundle does
not touch are never read (the paper's "only stream those rows of B that
match").

Grid: (n_bundles, d_out tiles, d_in tiles), k innermost so the output tile
stays VMEM-resident across the contraction.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import I0, resolve_interpret


def _kernel(expert_of_bundle, x_ref, w_ref, o_ref, acc_ref):
    del expert_of_bundle
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "bf", "interpret"))
def moe_gemm(x_bundles, w, bundle_expert, *, bk: int = 512, bf: int = 512,
             interpret: Optional[bool] = None):
    """out[b] = x_bundles[b] @ w[bundle_expert[b]].

    x_bundles: (nb, cap, d_in); w: (E, d_in, d_out);
    bundle_expert: (nb,) int32.  Returns (nb, cap, d_out), x dtype.
    """
    nb, cap, d_in = x_bundles.shape
    _, _, d_out = w.shape
    bk = min(bk, d_in)
    bf = min(bf, d_out)
    assert d_in % bk == 0 and d_out % bf == 0, (d_in, bk, d_out, bf)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, d_out // bf, d_in // bk),
        in_specs=[
            pl.BlockSpec((1, cap, bk), lambda b, f, k, e: (b, I0, k)),
            pl.BlockSpec((1, bk, bf), lambda b, f, k, e: (e[b], k, f)),
        ],
        out_specs=pl.BlockSpec((1, cap, bf), lambda b, f, k, e: (b, I0, f)),
        scratch_shapes=[pltpu.VMEM((cap, bf), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, cap, d_out), x_bundles.dtype),
        interpret=resolve_interpret(interpret),
        cost_estimate=pl.CostEstimate(
            flops=2 * int(nb) * cap * d_in * d_out,
            bytes_accessed=int(nb) * cap * (d_in + d_out) * 2
            + int(nb) * d_in * d_out * 2,
            transcendentals=0),
    )(bundle_expert, x_bundles, w)


def moe_gemm_schedule(schedule, x_bundles, w, *, bk: int = 512, bf: int = 512,
                      interpret: Optional[bool] = None):
    """Runtime entry point: drive the kernel from a ``MoeDispatchPlan``'s RIR
    ScheduleBundle (mirrors ``bsr_spgemm_schedule``).

    The plan's ``bundle_expert`` metadata becomes the scalar-prefetch operand
    directly, so a cached dispatch plan replays onto fresh token bundles with
    zero re-routing.
    """
    return moe_gemm(x_bundles, w,
                    jnp.asarray(schedule["bundle_expert"], jnp.int32),
                    bk=bk, bf=bf, interpret=interpret)
