"""Op registry: registration rules, generic dispatch, wrapper equivalence,
and the SpMM proof-of-design (a new op admitted to cache + store purely via
register_op)."""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import CSR, random_csr, random_spd_csr
from repro.core.inspector import fingerprint_pattern
from repro.kernels.bsr_spmm import SpmmPlan, inspect_spmm, spmm_ref_numpy
from repro.runtime import (OpSpec, ReapRuntime, deserialize_plan, get_op,
                           list_ops, register_op, register_plan_type,
                           serialize_plan, unregister_op)


def _rand(n, m, density, seed=0, pattern="uniform"):
    return random_csr(n, m, density, np.random.default_rng(seed), pattern)


def _revalue(a: CSR, seed: int) -> CSR:
    rng = np.random.default_rng(seed)
    return CSR(a.n_rows, a.n_cols, a.indptr, a.indices,
               rng.standard_normal(a.nnz).astype(a.data.dtype))


class TestRegistry:
    def test_builtin_ops_registered(self):
        tags = list_ops()
        for tag in ("spgemm", "spgemm_gather", "spgemm_block", "cholesky",
                    "moe_dispatch", "spmm"):
            assert tag in tags, tags

    def test_duplicate_tag_registration_errors(self):
        spec = get_op("spgemm_gather")
        with pytest.raises(ValueError, match="already registered"):
            register_op(dataclasses.replace(spec))
        # explicit override is allowed and restores the original cleanly
        register_op(spec, allow_override=True)
        assert get_op("spgemm_gather") is spec

    def test_unknown_tag_run_errors(self):
        rt = ReapRuntime(use_pallas=False)
        with pytest.raises(KeyError, match="unknown op tag"):
            rt.run("no_such_op", _rand(10, 10, 0.3))
        with pytest.raises(KeyError, match="registered ops"):
            get_op("also_missing")

    def test_unknown_kwargs_rejected(self):
        """Typo'd kwargs must raise, not silently fall into **kw sinks
        (the strictness the per-op methods had before the registry)."""
        rt = ReapRuntime(use_pallas=False)
        a = _rand(20, 20, 0.2, 1)
        with pytest.raises(TypeError, match="unexpected keyword"):
            rt.run("cholesky", a, dtyp="nope")
        with pytest.raises(TypeError, match="unexpected keyword"):
            rt.run("spmm", np.zeros((4, 20), np.float32), a,
                   use_palas=True)
        with pytest.raises(TypeError, match="unexpected keyword"):
            rt.run("spgemm", a, a, method="gather", overlp=False)

    def test_register_unregister_custom_op(self):
        def fp_hook(operands, cfg, *, chunked, **kw):
            return fingerprint_pattern("test_noop", operands)

        def inspect_hook(operands, cfg, fp, **kw):
            return {"fp": fp}

        def exec_hook(plan, operands, cfg, **kw):
            return operands[0].nnz, dict(method="test_noop")

        spec = OpSpec(tag="test_noop", fingerprint=fp_hook,
                      inspect=inspect_hook, execute_sync=exec_hook)
        register_op(spec)
        try:
            assert "test_noop" in list_ops()
            rt = ReapRuntime()
            a = _rand(12, 12, 0.3, 1)
            result, stats = rt.run("test_noop", a)
            assert result == a.nnz and not stats["cache_hit"]
            _, stats = rt.run("test_noop", a)
            assert stats["cache_hit"]
        finally:
            unregister_op("test_noop")
        assert "test_noop" not in list_ops()

    def test_incomplete_spec_rejected(self):
        with pytest.raises(ValueError, match="must define"):
            OpSpec(tag="broken", fingerprint=lambda *a, **k: None)

    @pytest.mark.parametrize("executors", [(), ("execute_sync",
                                                "execute_chunked")])
    def test_spec_needs_exactly_one_executor(self, executors):
        hook = lambda *a, **k: None                      # noqa: E731
        with pytest.raises(ValueError, match="exactly one"):
            OpSpec(tag="broken", fingerprint=hook, inspect=hook,
                   **{name: hook for name in executors})

    def test_plan_type_name_collision_errors(self):
        class Impostor:
            pass
        with pytest.raises(ValueError, match="already registered"):
            register_plan_type("spmm", Impostor)


class TestWrapperEquivalence:
    """Back-compat wrappers are thin adapters: rt.spgemm(...) ≡
    rt.run("spgemm", ...) bit-for-bit (fresh runtimes on each side, so
    both go cold → warm identically)."""

    def test_spgemm_gather_sync(self):
        a, b = _rand(90, 90, 0.06, 1), _rand(90, 90, 0.06, 2)
        rt1 = ReapRuntime(n_chunks=1, use_pallas=False)
        rt2 = ReapRuntime(n_chunks=1, use_pallas=False)
        for seed in (10, 11):       # cold call, then warm call
            a2, b2 = _revalue(a, seed), _revalue(b, seed + 50)
            c1, st1 = rt1.spgemm(a2, b2, method="gather")
            c2, st2 = rt2.run("spgemm", a2, b2, method="gather")
            np.testing.assert_array_equal(c1.to_dense(), c2.to_dense())
            np.testing.assert_array_equal(c1.data, c2.data)
            for key in ("cache_hit", "method", "fingerprint", "overlap"):
                assert st1[key] == st2[key]

    def test_spgemm_block_chunked(self):
        a = _rand(128, 128, 0.05, 3, "blocky")
        rt1 = ReapRuntime(n_chunks=3, block=32, use_pallas=False)
        rt2 = ReapRuntime(n_chunks=3, block=32, use_pallas=False)
        for seed in (20, 21):
            a2 = _revalue(a, seed)
            c1, st1 = rt1.spgemm(a2, a2, method="block")
            c2, st2 = rt2.run("spgemm", a2, a2, method="block")
            np.testing.assert_array_equal(c1.to_dense(), c2.to_dense())
            assert st1["cache_hit"] == st2["cache_hit"]
            assert st1["fingerprint"] == st2["fingerprint"]

    def test_spgemm_auto_routes_identically(self):
        a = _rand(100, 100, 0.05, 4)
        rt1 = ReapRuntime(n_chunks=1, use_pallas=False)
        rt2 = ReapRuntime(n_chunks=1, use_pallas=False)
        c1, st1 = rt1.spgemm(a, a)
        c2, st2 = rt2.run("spgemm", a, a)
        assert st1["method"] == st2["method"]
        np.testing.assert_array_equal(c1.to_dense(), c2.to_dense())

    def test_cholesky(self):
        a = random_spd_csr(50, 0.08, np.random.default_rng(5))
        rt1 = ReapRuntime(use_pallas=False)
        rt2 = ReapRuntime(use_pallas=False)
        p1, v1, st1 = rt1.cholesky(a, dtype=jnp.float32)
        (p2, v2), st2 = rt2.run("cholesky", a, dtype=jnp.float32)
        np.testing.assert_array_equal(v1, v2)
        np.testing.assert_array_equal(p1.row_idx, p2.row_idx)
        assert st1["cache_hit"] == st2["cache_hit"]
        assert st1["fingerprint"] == st2["fingerprint"]

    def test_moe_dispatch(self):
        rng = np.random.default_rng(6)
        tokens = rng.standard_normal((48, 16)).astype(np.float32)
        eids = rng.integers(0, 8, (48, 2))
        rt1, rt2 = ReapRuntime(), ReapRuntime()
        xb1, plan1, st1 = rt1.moe_dispatch(tokens, eids, n_experts=8)
        (xb2, plan2), st2 = rt2.run("moe_dispatch", tokens, eids,
                                    n_experts=8)
        np.testing.assert_array_equal(xb1, xb2)
        np.testing.assert_array_equal(plan1.dest, plan2.dest)
        assert st1["fingerprint"] == st2["fingerprint"]
        assert st1["capacity"] == st2["capacity"]


class TestSpmmThroughRegistry:
    """The brand-new op is fully served by the generic machinery."""

    def _wx(self, seed=7, n=192, m=160, t=40):
        rng = np.random.default_rng(seed)
        w = random_csr(n, m, 0.06, rng, "blocky")
        x = rng.standard_normal((t, n)).astype(np.float32)
        return w, x

    def test_correct_and_cached(self):
        w, x = self._wx()
        rt = ReapRuntime(use_pallas=False, block=32)
        y, st = rt.run("spmm", x, w)
        assert not st["cache_hit"] and st["method"] == "spmm"
        np.testing.assert_allclose(y, spmm_ref_numpy(x, w),
                                   rtol=1e-4, atol=1e-4)
        x2 = np.random.default_rng(8).standard_normal(x.shape).astype(
            np.float32)
        y2, st2 = rt.run("spmm", x2, w)
        assert st2["cache_hit"]          # same W pattern, fresh X values
        np.testing.assert_allclose(y2, spmm_ref_numpy(x2, w),
                                   rtol=1e-4, atol=1e-4)
        # different W pattern misses
        w3, x3 = self._wx(seed=9)
        _, st3 = rt.run("spmm", x3, w3)
        assert not st3["cache_hit"]

    def test_pallas_matches_jnp(self):
        w, x = self._wx(t=32)
        y_jnp, _ = ReapRuntime(use_pallas=False, block=32).run("spmm", x, w)
        y_pl, _ = ReapRuntime(use_pallas=True, block=32).run("spmm", x, w)
        np.testing.assert_allclose(y_pl, y_jnp, rtol=1e-3, atol=1e-3)

    def test_serialize_roundtrip(self):
        w, _ = self._wx()
        plan = inspect_spmm(w, 32)
        back = deserialize_plan(serialize_plan(plan))
        assert isinstance(back, SpmmPlan)
        for name in ("w_id", "k_blk", "j_blk", "is_first", "is_last"):
            np.testing.assert_array_equal(getattr(back, name),
                                          getattr(plan, name))
        np.testing.assert_array_equal(back.pat.elem_block,
                                      plan.pat.elem_block)

    def test_store_roundtrip_via_registry_only(self, tmp_path):
        """Cold process → store-warm process, all through run("spmm")."""
        w, x = self._wx()
        rt1 = ReapRuntime(use_pallas=False, block=32,
                          store_dir=str(tmp_path))
        y1, st1 = rt1.run("spmm", x, w)
        assert not st1["cache_hit"]
        assert rt1.store.summary()["saves"] == 1
        rt2 = ReapRuntime(use_pallas=False, block=32,
                          store_dir=str(tmp_path))
        y2, st2 = rt2.run("spmm", x, w)
        assert st2["cache_hit"]
        assert rt2.cache_stats()["per_op"]["spmm"]["store_hits"] == 1
        np.testing.assert_array_equal(y1, y2)

    def test_coverage_jobs_zero_pruned_columns(self):
        # W with an entirely empty block-column range: output must be zero
        # there, which requires the coverage jobs' zero tile
        w = CSR(64, 96, np.arange(0, 65, 1, dtype=np.int64),
                np.zeros(64, dtype=np.int64),
                np.ones(64, dtype=np.float32))          # only column 0
        x = np.random.default_rng(1).standard_normal((16, 64)).astype(
            np.float32)
        y, _ = ReapRuntime(use_pallas=False, block=32).run("spmm", x, w)
        np.testing.assert_allclose(y, spmm_ref_numpy(x, w),
                                   rtol=1e-4, atol=1e-4)
        assert np.all(y[:, 32:] == 0)
