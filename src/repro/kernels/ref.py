"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are tested against
(interpret mode on CPU, shape/dtype sweeps in tests/test_kernels.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# bsr_spgemm: schedule-driven block SpGEMM (the paper's SpGEMM executor)
# ---------------------------------------------------------------------------

def bsr_spgemm_ref(a_blocks, b_blocks, a_id, b_id, out_id, is_first, is_last,
                   n_out_blocks: int):
    del is_first, is_last
    prods = jnp.einsum("tij,tjk->tik", a_blocks[a_id], b_blocks[b_id],
                       preferred_element_type=jnp.float32)
    return jax.ops.segment_sum(prods, out_id, num_segments=n_out_blocks,
                               indices_are_sorted=True)


# ---------------------------------------------------------------------------
# moe_gemm: capacity-bundled grouped expert GEMM (RIR dispatch executor)
# ---------------------------------------------------------------------------

def moe_gemm_ref(x_bundles, w, bundle_expert):
    """x_bundles: (nb, cap, d_in), w: (E, d_in, d_out), bundle_expert: (nb,).

    out[b] = x_bundles[b] @ w[bundle_expert[b]]
    """
    return jnp.einsum("bcd,bdf->bcf", x_bundles, w[bundle_expert],
                      preferred_element_type=jnp.float32
                      ).astype(x_bundles.dtype)


# ---------------------------------------------------------------------------
# flash_attention: blockwise attention w/ causal, sliding window, softcap
# ---------------------------------------------------------------------------

def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, scale: float | None = None):
    """q,k,v: (B, H, S, D) (H = q heads; k/v may have fewer heads → GQA
    replication is done by the caller). fp32 reference.

    window > 0 ⇒ token t attends to [t-window+1, t] (sliding window, causal).
    softcap > 0 ⇒ logits = softcap * tanh(logits / softcap)  (gemma-2).
    """
    b, h, s, d = q.shape
    scale = (d ** -0.5) if scale is None else scale
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# rwkv6: data-dependent-decay linear recurrence (Finch), per-step oracle
# ---------------------------------------------------------------------------

def rwkv6_ref(r, k, v, w, u):
    """Naive per-step scan (the semantic definition).

    r,k,w: (B, H, T, K); v: (B, H, T, V); u: (H, K). w ∈ (0,1) is the
    per-channel data-dependent decay. Recurrence, per (batch, head):

        o_t = r_t @ (S_{t-1} + (u ⊙ k_t)^T v_t)
        S_t = diag(w_t) S_{t-1} + k_t^T v_t

    Returns o: (B, H, T, V) in fp32.
    """
    b, h, t, kk = r.shape
    vv = v.shape[-1]
    r32, k32, v32, w32 = (x.astype(jnp.float32) for x in (r, k, v, w))
    u32 = u.astype(jnp.float32)

    def head_scan(r_h, k_h, v_h, w_h, u_h):
        def step(s, inp):
            r_t, k_t, v_t, w_t = inp
            kv = jnp.outer(k_t, v_t)
            o_t = r_t @ (s + u_h[:, None] * kv)
            s_new = w_t[:, None] * s + kv
            return s_new, o_t
        s0 = jnp.zeros((kk, vv), jnp.float32)
        _, o = jax.lax.scan(step, s0, (r_h, k_h, v_h, w_h))
        return o

    fn = jax.vmap(jax.vmap(head_scan, in_axes=(0, 0, 0, 0, 0)),
                  in_axes=(0, 0, 0, 0, None))
    return fn(r32, k32, v32, w32, u32)


# ---------------------------------------------------------------------------
# bsr_spmm: BSR sparse-weight × dense-activation matmul
# ---------------------------------------------------------------------------

def bsr_spmm_ref(x, w_dense, mask, block: int):
    """Oracle: dense matmul against the block-masked weight."""
    d_in, d_out = w_dense.shape
    nk, nj = d_in // block, d_out // block
    m = jnp.repeat(jnp.repeat(jnp.asarray(mask), block, 0), block, 1)
    return x @ (w_dense * m.astype(w_dense.dtype))
