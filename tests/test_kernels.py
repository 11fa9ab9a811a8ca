"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle,
sweeping shapes and dtypes (ref.py is the ground truth)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import inspect_spgemm_block, random_csr
from repro.core.spgemm import block_result_to_dense
from repro.kernels import ops, ref
from repro.kernels.flash_attention import attention_block_schedule


# ---------------------------------------------------------------------------
# bsr_spgemm
# ---------------------------------------------------------------------------

class TestBsrSpgemm:
    @pytest.mark.parametrize("block", [8, 16, 128])
    @pytest.mark.parametrize("pattern", ["blocky", "uniform"])
    def test_vs_ref(self, block, pattern):
        rng = np.random.default_rng(block)
        a = random_csr(200, 160, 0.05, rng, pattern)
        b = random_csr(160, 140, 0.05, rng, pattern)
        plan = inspect_spgemm_block(a, b, block)
        args = (jnp.asarray(plan.a_pat.scatter(a.data), jnp.float32),
                jnp.asarray(plan.b_pat.scatter(b.data), jnp.float32),
                jnp.asarray(plan.a_id, jnp.int32),
                jnp.asarray(plan.b_id, jnp.int32),
                jnp.asarray(plan.out_id, jnp.int32),
                jnp.asarray(plan.is_first, jnp.int32),
                jnp.asarray(plan.is_last, jnp.int32))
        out = ops.bsr_spgemm(*args, n_out_blocks=plan.n_out_blocks)
        expect = ref.bsr_spgemm_ref(*args, n_out_blocks=plan.n_out_blocks)
        np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("launch_pairs", [3, 10, 64])
    def test_split_launches(self, launch_pairs):
        """A schedule run as several launches, cut inside output groups,
        equals the one-launch result."""
        rng = np.random.default_rng(3)
        a = random_csr(64, 64, 0.1, rng, "uniform")
        b = random_csr(64, 64, 0.1, rng, "uniform")
        plan = inspect_spgemm_block(a, b, 16)       # 16 groups of 4 pairs
        assert plan.n_pairs == 64 and plan.n_out_blocks == 16
        args = (jnp.asarray(plan.a_pat.scatter(a.data), jnp.float32),
                jnp.asarray(plan.b_pat.scatter(b.data), jnp.float32),
                jnp.asarray(plan.a_id, jnp.int32),
                jnp.asarray(plan.b_id, jnp.int32),
                jnp.asarray(plan.out_id, jnp.int32),
                jnp.asarray(plan.is_first, jnp.int32),
                jnp.asarray(plan.is_last, jnp.int32))
        out = ops.bsr_spgemm(*args, n_out_blocks=plan.n_out_blocks,
                             launch_pairs=launch_pairs)
        expect = ref.bsr_spgemm_ref(*args, n_out_blocks=plan.n_out_blocks)
        np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)

    def test_end_to_end_dense_oracle(self):
        rng = np.random.default_rng(7)
        a = random_csr(100, 100, 0.1, rng, "blocky")
        plan = inspect_spgemm_block(a, a, 32)
        # drive the kernel the way the runtime does: from the schedule bundle
        out = ops.bsr_spgemm_schedule(
            plan.schedule,
            jnp.asarray(plan.a_pat.scatter(a.data), jnp.float32),
            jnp.asarray(plan.b_pat.scatter(a.data), jnp.float32),
            n_out_blocks=plan.n_out_blocks)
        dense = block_result_to_dense(plan, np.asarray(out))
        oracle = a.to_dense().astype(np.float64) @ a.to_dense()
        np.testing.assert_allclose(dense[:100, :100], oracle, rtol=1e-4,
                                   atol=1e-4)


# ---------------------------------------------------------------------------
# moe_gemm
# ---------------------------------------------------------------------------

class TestMoeGemm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("nb,cap,din,dout,e", [
        (4, 8, 32, 64, 3), (7, 16, 128, 128, 8), (2, 128, 256, 512, 2)])
    def test_vs_ref(self, dtype, nb, cap, din, dout, e):
        key = jax.random.PRNGKey(nb)
        k1, k2, k3 = jax.random.split(key, 3)
        x = jax.random.normal(k1, (nb, cap, din), dtype)
        w = jax.random.normal(k2, (e, din, dout), dtype)
        be = jax.random.randint(k3, (nb,), 0, e, jnp.int32)
        out = ops.moe_gemm(x, w, be, bk=min(128, din), bf=min(128, dout))
        expect = ref.moe_gemm_ref(x, w, be)
        # kernel tiles K → different accumulation order than the ref einsum
        tol = 1e-3 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_basic(self, dtype, causal):
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        b, h, s, d = 2, 4, 256, 64
        q = jax.random.normal(kq, (b, h, s, d), dtype)
        k = jax.random.normal(kk, (b, h, s, d), dtype)
        v = jax.random.normal(kv, (b, h, s, d), dtype)
        out = ops.flash_attention(q, k, v, causal=causal, bq=64, bk=64)
        expect = ref.flash_attention_ref(q, k, v, causal=causal)
        tol = 2e-3 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("window", [64, 128])
    def test_sliding_window(self, window):
        key = jax.random.PRNGKey(1)
        kq, kk, kv = jax.random.split(key, 3)
        b, h, s, d = 1, 2, 512, 32
        q = jax.random.normal(kq, (b, h, s, d), jnp.float32)
        k = jax.random.normal(kk, (b, h, s, d), jnp.float32)
        v = jax.random.normal(kv, (b, h, s, d), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True, window=window,
                                  bq=64, bk=64)
        expect = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-3, atol=2e-3)

    def test_softcap_gemma2(self):
        key = jax.random.PRNGKey(2)
        kq, kk, kv = jax.random.split(key, 3)
        b, h, s, d = 1, 2, 128, 32
        q = 3 * jax.random.normal(kq, (b, h, s, d), jnp.float32)
        k = 3 * jax.random.normal(kk, (b, h, s, d), jnp.float32)
        v = jax.random.normal(kv, (b, h, s, d), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True, softcap=50.0,
                                  bq=64, bk=64)
        expect = ref.flash_attention_ref(q, k, v, causal=True, softcap=50.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-3, atol=2e-3)

    def test_gqa_zero_copy(self):
        key = jax.random.PRNGKey(3)
        kq, kk, kv = jax.random.split(key, 3)
        b, h, hkv, s, d = 1, 8, 2, 128, 32
        q = jax.random.normal(kq, (b, h, s, d), jnp.float32)
        k = jax.random.normal(kk, (b, hkv, s, d), jnp.float32)
        v = jax.random.normal(kv, (b, hkv, s, d), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
        k_rep = jnp.repeat(k, h // hkv, axis=1)
        v_rep = jnp.repeat(v, h // hkv, axis=1)
        expect = ref.flash_attention_ref(q, k_rep, v_rep, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-3, atol=2e-3)

    def test_schedule_skips_invisible_blocks(self):
        lo, n, nmax = attention_block_schedule(512, 64, 64, causal=True)
        assert list(n) == list(range(1, 9))       # causal ramp
        lo2, n2, _ = attention_block_schedule(512, 64, 64, causal=True,
                                              window=128)
        assert n2.max() <= 3                      # window bounds the range
        # schedule saves > 40% of blocks vs dense for causal
        assert n.sum() < 0.6 * 8 * 8


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------

class TestRwkv6:
    @pytest.mark.parametrize("t,chunk", [(64, 16), (128, 32), (96, 32)])
    def test_vs_naive_scan(self, t, chunk):
        if t % chunk:
            pytest.skip("t % chunk != 0")
        key = jax.random.PRNGKey(t)
        ks = jax.random.split(key, 5)
        b, h, kk, vv = 2, 3, 16, 24
        r = jax.random.normal(ks[0], (b, h, t, kk), jnp.float32)
        k = jax.random.normal(ks[1], (b, h, t, kk), jnp.float32)
        v = jax.random.normal(ks[2], (b, h, t, vv), jnp.float32)
        # realistic decay range incl. strong decay (stability stressor)
        w = jax.nn.sigmoid(4 * jax.random.normal(ks[3], (b, h, t, kk)))
        w = jnp.clip(w, 1e-4, 1 - 1e-4).astype(jnp.float32)
        u = jax.random.normal(ks[4], (h, kk), jnp.float32)
        out = ops.rwkv6(r, k, v, w, u, chunk=chunk)
        expect = ref.rwkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-4)

    def test_chunk_size_invariance(self):
        key = jax.random.PRNGKey(9)
        ks = jax.random.split(key, 5)
        b, h, t, kk, vv = 1, 2, 64, 8, 8
        r = jax.random.normal(ks[0], (b, h, t, kk), jnp.float32)
        k = jax.random.normal(ks[1], (b, h, t, kk), jnp.float32)
        v = jax.random.normal(ks[2], (b, h, t, vv), jnp.float32)
        w = jnp.clip(jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, t, kk))),
                     1e-4, 1 - 1e-4).astype(jnp.float32)
        u = jax.random.normal(ks[4], (h, kk), jnp.float32)
        o16 = ops.rwkv6(r, k, v, w, u, chunk=16)
        o32 = ops.rwkv6(r, k, v, w, u, chunk=32)
        o64 = ops.rwkv6(r, k, v, w, u, chunk=64)
        np.testing.assert_allclose(np.asarray(o16), np.asarray(o32),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(o32), np.asarray(o64),
                                   rtol=1e-4, atol=1e-5)

    def test_extreme_decay_stable(self):
        # w → 0 (instant forget) and w → 1 (no decay) must not NaN/overflow
        b, h, t, kk, vv = 1, 1, 32, 4, 4
        key = jax.random.PRNGKey(11)
        ks = jax.random.split(key, 4)
        r = jax.random.normal(ks[0], (b, h, t, kk), jnp.float32)
        k = jax.random.normal(ks[1], (b, h, t, kk), jnp.float32)
        v = jax.random.normal(ks[2], (b, h, t, vv), jnp.float32)
        u = jax.random.normal(ks[3], (h, kk), jnp.float32)
        for wval in (1e-6, 1 - 1e-6):
            w = jnp.full((b, h, t, kk), wval, jnp.float32)
            out = ops.rwkv6(r, k, v, w, u, chunk=16)
            assert np.isfinite(np.asarray(out)).all()
            expect = ref.rwkv6_ref(r, k, v, w, u)
            np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                       rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# bsr_spmm (structured-sparse weights)
# ---------------------------------------------------------------------------

class TestBsrSpmm:
    @pytest.mark.parametrize("keep", [0.25, 0.5, 1.0])
    @pytest.mark.parametrize("block", [8, 16])
    def test_vs_masked_dense(self, keep, block):
        from repro.kernels.bsr_spmm import inspect_bsr_weight
        rng = np.random.default_rng(int(keep * 100) + block)
        t, d_in, d_out = 64, 64, 96
        x = jnp.asarray(rng.standard_normal((t, d_in)), jnp.float32)
        w = rng.standard_normal((d_in, d_out)).astype(np.float32)
        blocks, sched, mask = inspect_bsr_weight(w, block, keep)
        out = ops.bsr_spmm(x, jnp.asarray(blocks), sched,
                           n_j_blocks=d_out // block, bt=32)
        expect = ref.bsr_spmm_ref(x, jnp.asarray(w), mask, block)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)

    def test_flops_scale_with_kept_blocks(self):
        from repro.kernels.bsr_spmm import inspect_bsr_weight
        rng = np.random.default_rng(0)
        w = rng.standard_normal((64, 64)).astype(np.float32)
        _, s25, _ = inspect_bsr_weight(w, 8, 0.25)
        _, s100, _ = inspect_bsr_weight(w, 8, 1.0)
        # job count (→ MXU work) scales with density, modulo coverage jobs
        assert s25["w_id"].shape[0] < 0.45 * s100["w_id"].shape[0]

    def test_full_keep_equals_dense(self):
        from repro.kernels.bsr_spmm import inspect_bsr_weight
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
        w = rng.standard_normal((32, 48)).astype(np.float32)
        blocks, sched, mask = inspect_bsr_weight(w, 8, 1.0)
        out = ops.bsr_spmm(x, jnp.asarray(blocks), sched, n_j_blocks=6,
                           bt=32)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(x @ jnp.asarray(w)),
                                   rtol=1e-4, atol=1e-4)


class TestBlockAttention:
    """Planned block-sparse attention (arbitrary CSR mask)."""

    def _problem(self, s=200, block=64, h=4, hkv=2, d=32, seed=1,
                 rows_hi=None):
        from repro.core import CSR
        from repro.core.formats import COO
        rng = np.random.default_rng(seed)
        rows_hi = s if rows_hi is None else rows_hi
        row = rng.integers(0, rows_hi, 6 * s)
        col = rng.integers(0, s, 6 * s)
        mask = CSR.from_coo(COO(s, s, row, col, np.ones(row.size, np.float32)))
        q = rng.standard_normal((2, h, s, d)).astype(np.float32)
        k = rng.standard_normal((2, hkv, s, d)).astype(np.float32)
        v = rng.standard_normal((2, hkv, s, d)).astype(np.float32)
        return mask, q, k, v

    @pytest.mark.parametrize("use_pallas", [False, True])
    @pytest.mark.parametrize("s,block", [(256, 64), (200, 64)])
    def test_vs_dense_reference(self, use_pallas, s, block):
        from repro.kernels.flash_attention import (
            block_attention_execute, block_attention_ref,
            inspect_block_attention)
        mask, q, k, v = self._problem(s=s, block=block)
        plan = inspect_block_attention(mask, block)
        out = block_attention_execute(plan, q, k, v, use_pallas=use_pallas)
        ref = block_attention_ref(q, k, v, mask, block)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_masked_out_rows_and_softcap(self, use_pallas):
        """q blocks with no visible kv must produce exact zeros, and the
        softcap/scale kwargs flow through both executors."""
        from repro.kernels.flash_attention import (
            block_attention_execute, block_attention_ref,
            inspect_block_attention)
        # mask rows confined to blocks 0-1: q rows 128+ see nothing
        mask, q, k, v = self._problem(s=200, rows_hi=128)
        plan = inspect_block_attention(mask, 64)
        assert plan.n_kv[2:].max(initial=0) == 0
        out = block_attention_execute(plan, q, k, v, use_pallas=use_pallas,
                                      softcap=5.0, scale=0.2)
        ref = block_attention_ref(q, k, v, mask, 64, softcap=5.0, scale=0.2)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
        assert np.abs(out[:, :, 128:]).max() == 0.0

    def test_registered_op_end_to_end(self):
        from repro.kernels.flash_attention import block_attention_ref
        from repro.runtime import ReapRuntime
        mask, q, k, v = self._problem(s=256)
        rt = ReapRuntime(n_chunks=1, use_pallas=False,
                         block=64)
        o1, s1 = rt.run("block_attention", q, k, v, mask)
        o2, s2 = rt.run("block_attention", q, k, v, mask)
        assert not s1["cache_hit"] and s2["cache_hit"]
        ref = block_attention_ref(q, k, v, mask, 64)
        np.testing.assert_allclose(o1, ref, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(o2, ref, rtol=1e-4, atol=1e-4)


class TestPlannedSpmv:
    """Planned y = A @ x (the CG solver's matvec op)."""

    def test_vs_dense_and_dtypes(self):
        from repro.core import random_spd_csr
        from repro.core.solver import (inspect_spmv, spmv_execute,
                                       spmv_ref_numpy)
        rng = np.random.default_rng(3)
        a = random_spd_csr(300, 0.02, rng)
        x = rng.standard_normal(300)
        plan = inspect_spmv(a, 64)
        ref = spmv_ref_numpy(a, x)
        scale = np.abs(ref).max()
        for use_pallas in (False, True):
            y = spmv_execute(plan, a.data, x, use_pallas=use_pallas)
            assert np.abs(y - ref).max() / scale < 1e-5

    def test_cg_solves_planned(self):
        from repro.core import random_spd_csr
        from repro.core.solver import cg_solve
        from repro.runtime import ReapRuntime
        rng = np.random.default_rng(4)
        n = 300
        a = random_spd_csr(n, 0.02, rng)
        b = rng.standard_normal(n)
        rt = ReapRuntime(n_chunks=1, use_pallas=False,
                         block=64)
        # float32 matvecs (x64 is off in the test process)
        x, info = cg_solve(a, b, rt, tol=1e-5, dtype=np.float32,
                           precond="cholesky", precond_block=32)
        assert info["converged"], info
        x_ref = np.linalg.solve(a.to_dense().astype(np.float64), b)
        err = np.linalg.norm(x - x_ref) / np.linalg.norm(x_ref)
        assert err < 1e-4, (err, info)
        # all iterations after the first replayed the warm spmv plan
        assert info["spmv_cache_hits"] == info["iterations"] - 1, info
        per_op = rt.cache_stats()["per_op"]
        assert per_op["spmv"]["misses"] == 1, per_op
        assert per_op["cholesky"]["misses"] == 1, per_op
