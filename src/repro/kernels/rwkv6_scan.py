"""Pallas TPU kernel: chunked RWKV6 (Finch) linear recurrence.

RWKV6's WKV computation is a linear recurrence with *data-dependent
per-channel decay* — sequential if computed per token.  The REAP treatment
(DESIGN.md §5): regularize time into fixed chunks (the bundle), compute the
intra-chunk part with dense tile ops, and carry the (K, V) state across
chunks in VMEM scratch — "organize the data so the accelerator streams it".

Stability: all cross-step decay factors are exponentials of *non-positive*
log-decay sums (no 1/cumprod anywhere), so no overflow for any w ∈ (0, 1).

Grid: (B, H, T/C), chunk axis innermost & sequential; state scratch persists
across chunk steps and is reset at c == 0.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import I0, resolve_interpret


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state, *, chunk):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    r = r_ref[0, 0].astype(jnp.float32)          # (C, K)
    k = k_ref[0, 0].astype(jnp.float32)          # (C, K)
    v = v_ref[0, 0].astype(jnp.float32)          # (C, V)
    w = w_ref[0, 0].astype(jnp.float32)          # (C, K)
    u = u_ref[0].astype(jnp.float32)             # (K,)

    logw = jnp.log(w)
    cum = jnp.cumsum(logw, axis=0)               # inclusive  (C, K)
    ecum = cum - logw                            # exclusive  (C, K)

    # inter-chunk: o_t += (r_t ⊙ Π_{i<t} w_i) @ S0
    o = jnp.dot(r * jnp.exp(ecum), state[...],
                preferred_element_type=jnp.float32)          # (C, V)

    # intra-chunk (strict lower triangle): A[t,s] = Σ_k r[t,k] k[s,k] e^{ecum[t,k]-cum[s,k]}
    expo = ecum[:, None, :] - cum[None, :, :]                # (C, C, K) ≤ 0 for s<t
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    expo = jnp.where(tri[:, :, None], expo, -jnp.inf)
    a = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(expo), axis=-1)
    o += jnp.dot(a, v, preferred_element_type=jnp.float32)

    # bonus diagonal: o_t += (r_t · (u ⊙ k_t)) v_t
    diag = jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True)  # (C, 1)
    o += diag * v
    o_ref[0, 0] = o.astype(o_ref.dtype)

    # state carry: S' = e^{cum[-1]} ⊙ S0 + Σ_s (k_s ⊙ e^{cum[-1]-cum[s]})^T v_s
    decay_all = jnp.exp(cum[-1])[:, None]                    # (K, 1)
    kd = k * jnp.exp(cum[-1][None, :] - cum)                 # (C, K), ≤ 1
    state[...] = decay_all * state[...] + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6(r, k, v, w, u, *, chunk: int = 32,
          interpret: Optional[bool] = None):
    """Chunked WKV. r,k,w: (B,H,T,K); v: (B,H,T,V); u: (H,K). T % chunk == 0.

    Returns o: (B,H,T,V) float32.
    """
    b, h, t, kk = r.shape
    vv = v.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(b, h, t // chunk),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, kk), lambda bi, hi, c: (bi, hi, c, I0)),
            pl.BlockSpec((1, 1, chunk, kk), lambda bi, hi, c: (bi, hi, c, I0)),
            pl.BlockSpec((1, 1, chunk, vv), lambda bi, hi, c: (bi, hi, c, I0)),
            pl.BlockSpec((1, 1, chunk, kk), lambda bi, hi, c: (bi, hi, c, I0)),
            pl.BlockSpec((1, kk), lambda bi, hi, c: (hi, I0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, vv),
                               lambda bi, hi, c: (bi, hi, c, I0)),
        scratch_shapes=[pltpu.VMEM((kk, vv), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, vv), jnp.float32),
        interpret=resolve_interpret(interpret),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * t * kk * vv + 2 * b * h * t * chunk * (kk + vv),
            bytes_accessed=(3 * b * h * t * kk + 2 * b * h * t * vv) * 4,
            transcendentals=b * h * t * kk * (2 + chunk)),
    )(r, k, v, w, u)
