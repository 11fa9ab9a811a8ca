"""Pallas TPU kernel: blockwise (flash) attention with an RIR-style
host-computed block schedule.

REAP connection (DESIGN.md §4): causal and sliding-window masks make the
attention score matrix *block-sparse with a statically known pattern*.  The
host inspector (``attention_block_schedule``) enumerates, per query block,
the visible KV block range — a metadata-only RIR bundle.  The kernel
consumes it via scalar prefetch, so invisible KV blocks are never read from
HBM (paper: "only stream those rows of B that match").

Supports: causal, sliding window (gemma local layers), logit softcap
(gemma-2), GQA via zero-copy KV head index mapping.

Two schedule sources:

* ``attention_block_schedule`` — closed-form causal/sliding-window
  ranges (contiguous kv block intervals per q block).
* ``inspect_block_attention`` / ``BlockAttentionPlan`` — the planned-op
  form for an *arbitrary* block-sparse mask given as a CSR matrix:
  ``bsr_pattern_from_csr`` (the same ``BsrPattern`` machinery the SpMM
  plan uses) turns the mask into a per-q-block list of visible kv block
  ids, fingerprinted under the ``block_attention`` op tag.  Admitted to
  the plan cache / runtime / persistent store purely through
  ``runtime.ops.register_op`` at the bottom of this file — the second
  worked example (after SpMM) that ``runtime/{api,plan_cache,
  plan_store}.py`` need zero edits per op.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import CSR, bsr_pattern_from_csr
from repro.core.inspector import (PatternFingerprint, fingerprint_pattern,
                                  next_pow2)
from repro.runtime import spans

from . import I0, resolve_interpret

NEG_INF = -1e30


def attention_block_schedule(seq: int, bq: int, bk: int, *, causal: bool,
                             window: int = 0):
    """Host inspector: per q-block, the [lo, hi) range of visible kv blocks.

    Returns (kv_lo, n_kv, nk_max) — int32 arrays of shape (seq//bq,).
    """
    nq = seq // bq
    kv_lo = np.zeros(nq, dtype=np.int32)
    n_kv = np.zeros(nq, dtype=np.int32)
    for qi in range(nq):
        q_first, q_last = qi * bq, qi * bq + bq - 1
        hi = (q_last // bk + 1) if causal else (seq // bk)
        lo = 0
        if window > 0:
            lo = max(0, (q_first - window + 1) // bk)
        kv_lo[qi], n_kv[qi] = lo, hi - lo
    return kv_lo, n_kv, int(n_kv.max())


def _kernel(kv_lo, n_kv, q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
            scale, causal, window, softcap, bq, bk):
    qi, j = pl.program_id(2), pl.program_id(3)
    nk_max = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(j < n_kv[qi])
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = (kv_lo[qi] + j) * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * alpha + p.sum(-1, keepdims=True)
        acc[...] = acc[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(j == nk_max - 1)
    def _finish():
        lsum = l_s[:, :1]
        o_ref[0, 0] = jnp.where(lsum > 0, acc[...] / lsum,
                                0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                              "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: float | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B, H, S, D); k, v: (B, Hkv, S, D) with H % Hkv == 0 (GQA).

    The GQA mapping is zero-copy: the KV BlockSpec index map folds the
    q-head → kv-head division, so kv tiles are DMA'd once per group.
    """
    b, h, s, d = q.shape
    _, hkv, _, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0
    scale = (d ** -0.5) if scale is None else scale

    kv_lo, n_kv, nk_max = attention_block_schedule(
        s, bq, bk, causal=causal, window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, s // bq, nk_max),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, qi, j, lo, nk: (bi, hi, qi, I0)),
            pl.BlockSpec(
                (1, 1, bk, d),
                lambda bi, hi, qi, j, lo, nk:
                (bi, hi // group, jnp.minimum(lo[qi] + j, lo[qi] + nk[qi] - 1),
                 0)),
            pl.BlockSpec(
                (1, 1, bk, d),
                lambda bi, hi, qi, j, lo, nk:
                (bi, hi // group, jnp.minimum(lo[qi] + j, lo[qi] + nk[qi] - 1),
                 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, j, lo, nk: (bi, hi, qi, I0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, softcap=softcap, bq=bq, bk=bk)
    visible = int(n_kv.sum())
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * visible * bq * bk * d,
            bytes_accessed=q.size * q.dtype.itemsize * 4,
            transcendentals=b * h * visible * bq * bk),
    )(jnp.asarray(kv_lo), jnp.asarray(n_kv), q, k, v)


# ---------------------------------------------------------------------------
# Planned block-sparse attention: arbitrary CSR mask → per-q-block kv lists
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class BlockAttentionPlan:
    """Pattern-pure plan for attention under a block-sparse CSR mask.

    Semantics are *block granular*: q block ``qi`` attends kv block ``kj``
    iff the mask has at least one stored element in that ``block x block``
    tile (positions past the unpadded ``seq`` are always masked).  The
    mask's values never enter the plan — only its sparsity pattern — so
    every same-mask call (each decode step / layer sharing a document
    mask) replays a warm plan.

    ``kv_ids[qi, s]`` is the s-th visible kv block of q block ``qi``;
    slots past ``n_kv[qi]`` are padded with block 0 and skipped by both
    executors.  ``nk_cap`` is the pow-2 bucketed max visible count, so a
    stream of same-shape masks with slightly different fill costs O(log)
    kernel compiles (RIR static-shape discipline).
    """

    block: int
    seq: int                 # unpadded q/kv sequence length (mask dims)
    n_q_blocks: int
    nk_cap: int              # pow-2 bucketed max visible kv blocks/q block
    kv_ids: np.ndarray       # (n_q_blocks, nk_cap) int32, slot-padded with 0
    n_kv: np.ndarray         # (n_q_blocks,) int32 visible count per q block
    n_visible: int           # total stored mask blocks (schedule size)
    fingerprint: Optional[PatternFingerprint] = None

    def flops(self, batch: int, heads: int, head_dim: int) -> int:
        return 4 * batch * heads * self.n_visible * self.block \
            * self.block * head_dim


def inspect_block_attention(mask: CSR, block: int = 128,
                            fingerprint: Optional[PatternFingerprint] = None
                            ) -> BlockAttentionPlan:
    """Stage-2 plan-build: the mask's BSR structure → visible-kv lists."""
    if mask.n_rows != mask.n_cols:
        raise ValueError(f"attention mask must be square, got "
                         f"{mask.n_rows}x{mask.n_cols}")
    pat = bsr_pattern_from_csr(mask, block)
    n_kv = np.diff(pat.indptr).astype(np.int32)
    nq = pat.n_block_rows
    nk_cap = next_pow2(max(1, int(n_kv.max(initial=0))))
    kv_ids = np.zeros((nq, nk_cap), np.int32)
    slots = np.arange(pat.n_blocks, dtype=np.int64) \
        - np.repeat(pat.indptr[:-1], n_kv)
    kv_ids[pat.block_rows(), slots] = pat.indices
    return BlockAttentionPlan(block, mask.n_rows, nq, nk_cap, kv_ids, n_kv,
                              pat.n_blocks, fingerprint)


def _block_attn_kernel(kv_ids, n_kv, q_ref, k_ref, v_ref, o_ref, acc, m_s,
                       l_s, *, scale, softcap, seq, bs):
    qi, j = pl.program_id(2), pl.program_id(3)
    nk_cap = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(j < n_kv[qi])
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        # the only mask inside the kernel is the padded tail: block
        # visibility is entirely encoded by the prefetched schedule
        kpos = kv_ids[qi, j] * bs + jax.lax.broadcasted_iota(
            jnp.int32, (bs, bs), 1)
        s = jnp.where(kpos < seq, s, NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * alpha + p.sum(-1, keepdims=True)
        acc[...] = acc[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(j == nk_cap - 1)
    def _finish():
        lsum = l_s[:, :1]
        o_ref[0, 0] = jnp.where(lsum > 0, acc[...] / lsum,
                                0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("softcap", "scale", "seq", "interpret"))
def block_sparse_attention(q, k, v, kv_ids, n_kv, *, softcap: float = 0.0,
                           scale: float | None = None, seq: int | None = None,
                           interpret: Optional[bool] = None):
    """q: (B, H, S_pad, D); kv_ids: (S_pad//bs, nk_cap) visible kv blocks.

    Gathered flash attention: the grid's kv axis walks each q block's
    *schedule slots*, and the KV BlockSpec index map dereferences
    ``kv_ids`` so invisible kv blocks are never DMA'd.  Padded slots
    alias block 0 but are skipped by ``pl.when(j < n_kv[qi])``.
    """
    b, h, s_pad, d = q.shape
    _, hkv, _, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    nq, nk_cap = kv_ids.shape
    assert s_pad % nq == 0
    bs = s_pad // nq
    scale = (d ** -0.5) if scale is None else scale
    seq = s_pad if seq is None else seq

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, nq, nk_cap),
        in_specs=[
            pl.BlockSpec((1, 1, bs, d),
                         lambda bi, hi, qi, j, ids, nk: (bi, hi, qi, I0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda bi, hi, qi, j, ids, nk:
                         (bi, hi // group, ids[qi, j], I0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda bi, hi, qi, j, ids, nk:
                         (bi, hi // group, ids[qi, j], I0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bs, d),
                               lambda bi, hi, qi, j, ids, nk:
                               (bi, hi, qi, I0)),
        scratch_shapes=[
            pltpu.VMEM((bs, d), jnp.float32),
            pltpu.VMEM((bs, 128), jnp.float32),
            pltpu.VMEM((bs, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_block_attn_kernel, scale=scale,
                               softcap=softcap, seq=seq, bs=bs)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * nq * nk_cap * bs * bs * d,
            bytes_accessed=q.size * q.dtype.itemsize * 4,
            transcendentals=b * h * nq * nk_cap * bs * bs),
    )(jnp.asarray(kv_ids), jnp.asarray(n_kv), q, k, v)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "seq"))
def _block_attention_jnp(q, k, v, kv_ids, n_kv, *, softcap: float,
                         scale: float, seq: int):
    """jnp fallback executor: gather visible kv blocks, masked softmax."""
    b, h, s_pad, d = q.shape
    _, hkv, _, _ = k.shape
    nq, nk_cap = kv_ids.shape
    bs = s_pad // nq
    group = h // hkv
    qb = q.reshape(b, h, nq, bs, d).astype(jnp.float32)
    kb = k.reshape(b, hkv, nq, bs, d).astype(jnp.float32)
    vb = v.reshape(b, hkv, nq, bs, d).astype(jnp.float32)
    kg = kb[:, :, kv_ids]                      # (b, hkv, nq, nk_cap, bs, d)
    vg = vb[:, :, kv_ids]
    if group > 1:
        kg = jnp.repeat(kg, group, axis=1)
        vg = jnp.repeat(vg, group, axis=1)
    s = jnp.einsum("bhqid,bhqsjd->bhqisj", qb, kg,
                   preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    live = jnp.arange(nk_cap)[None, :] < n_kv[:, None]          # (nq, nk_cap)
    kpos = kv_ids[:, :, None] * bs + jnp.arange(bs)       # (nq, nk_cap, bs)
    mask = live[:, :, None] & (kpos < seq)
    mask6 = mask[None, None, :, None, :, :]
    s = jnp.where(mask6, s, NEG_INF)
    m = s.max(axis=(-2, -1), keepdims=True)
    # fully-masked q rows: exp(NEG_INF - NEG_INF) would be 1, so zero the
    # masked probabilities explicitly and divide under an lsum>0 guard
    p = jnp.where(mask6, jnp.exp(s - m), 0.0)
    lsum = p.sum(axis=(-2, -1))[..., None]                # (b, h, nq, bs, 1)
    out = jnp.einsum("bhqisj,bhqsjd->bhqid", p, vg,
                     preferred_element_type=jnp.float32)
    out = jnp.where(lsum > 0, out / jnp.maximum(lsum, 1e-30), 0.0)
    return out.reshape(b, h, s_pad, d).astype(q.dtype)


def block_attention_execute(plan: BlockAttentionPlan, q, k, v,
                            use_pallas: bool = True, *,
                            softcap: float = 0.0,
                            scale: float | None = None) -> np.ndarray:
    """Attention output from a plan + this call's q/k/v values.

    q: (B, H, S, D); k, v: (B, Hkv, S, D) with H % Hkv == 0 (GQA).  S is
    zero-padded up to the plan's block multiple; padded kv positions are
    masked by the executors and padded q rows are sliced off the result.
    """
    q, k, v = np.asarray(q), np.asarray(k), np.asarray(v)
    b, h, s, d = q.shape
    if s != plan.seq:
        raise ValueError(f"q has seq {s}, plan was built for {plan.seq}")
    s_pad = plan.n_q_blocks * plan.block
    if s_pad != s:
        qp = np.zeros((b, h, s_pad, d), q.dtype)
        qp[:, :, :s] = q
        kp = np.zeros((b, k.shape[1], s_pad, d), k.dtype)
        kp[:, :, :s] = k
        vp = np.zeros((b, v.shape[1], s_pad, d), v.dtype)
        vp[:, :, :s] = v
        q, k, v = qp, kp, vp
    d_scale = float(d ** -0.5) if scale is None else float(scale)
    if use_pallas:
        out = block_sparse_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(plan.kv_ids), jnp.asarray(plan.n_kv),
            softcap=softcap, scale=d_scale, seq=plan.seq)
    else:
        out = _block_attention_jnp(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(plan.kv_ids), jnp.asarray(plan.n_kv),
            softcap=softcap, scale=d_scale, seq=plan.seq)
    return np.asarray(out)[:, :, :plan.seq]


def block_attention_ref(q, k, v, mask: CSR, block: int, *,
                        softcap: float = 0.0,
                        scale: float | None = None) -> np.ndarray:
    """Dense numpy oracle with the same block-granular mask semantics."""
    q = np.asarray(q, np.float64)
    k = np.asarray(k, np.float64)
    v = np.asarray(v, np.float64)
    b, h, s, d = q.shape
    group = h // k.shape[1]
    kf = np.repeat(k, group, axis=1)
    vf = np.repeat(v, group, axis=1)
    blk = mask.to_dense() != 0
    nq, nk = -(-s // block), -(-s // block)
    allowed = np.zeros((s, s), bool)
    for qi in range(nq):
        for kj in range(nk):
            tile = blk[qi * block:(qi + 1) * block,
                       kj * block:(kj + 1) * block]
            if tile.any():
                allowed[qi * block:(qi + 1) * block,
                        kj * block:(kj + 1) * block] = True
    scl = (d ** -0.5) if scale is None else scale
    s_mat = np.einsum("bhid,bhjd->bhij", q, kf) * scl
    if softcap > 0.0:
        s_mat = softcap * np.tanh(s_mat / softcap)
    s_mat = np.where(allowed[None, None], s_mat, -np.inf)
    m = s_mat.max(axis=-1, keepdims=True)
    p = np.where(np.isfinite(s_mat), np.exp(s_mat - np.where(
        np.isfinite(m), m, 0.0)), 0.0)
    lsum = p.sum(axis=-1, keepdims=True)
    out = np.einsum("bhij,bhjd->bhid", p, vf)
    return np.where(lsum > 0, out / np.maximum(lsum, 1e-30), 0.0)


# ---------------------------------------------------------------------------
# Op registry: block-sparse attention admitted as a planned op — like SpMM,
# this block is the entire integration with runtime, cache, store, serve.
# ---------------------------------------------------------------------------

from repro.runtime.ops import OpCapabilities, OpSpec, register_op  # noqa: E402


def _fp_block_attention(operands, cfg, *, chunked, **kw):
    mask = operands[3]
    return fingerprint_pattern("block_attention", (mask,), block=cfg.block)


def _inspect_block_attention(operands, cfg, fp, **kw):
    return inspect_block_attention(operands[3], cfg.block, fp)


def _exec_block_attention(plan, operands, cfg, *, softcap=0.0, scale=None,
                          **kw):
    q, k, v = operands[0], operands[1], operands[2]
    with spans.span("reap.execute") as ex:
        o = block_attention_execute(plan, q, k, v,
                                    use_pallas=cfg.use_pallas,
                                    softcap=softcap, scale=scale)
    stats = dict(method="block_attention", execute_s=ex.seconds,
                 n_visible_blocks=plan.n_visible, nk_cap=plan.nk_cap,
                 flops=plan.flops(np.asarray(q).shape[0],
                                  np.asarray(q).shape[1],
                                  np.asarray(q).shape[3]))
    return o, stats


register_op(OpSpec(
    tag="block_attention",
    fingerprint=_fp_block_attention,
    inspect=_inspect_block_attention,
    execute_sync=_exec_block_attention,
    plan_types={"block_attention": BlockAttentionPlan},
    allowed_kw=("softcap", "scale"),
    capabilities=OpCapabilities(dtypes=("float32", "bfloat16"),
                                routing="host"),
))
