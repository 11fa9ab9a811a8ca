"""Plan cache: fingerprint keying, hit/miss semantics, eviction, serialization."""
import numpy as np
import pytest

from repro.core import (CSR, cholesky_values, fingerprint_pattern,
                        inspect_cholesky, inspect_spgemm_block,
                        inspect_spgemm_gather, random_csr, random_spd_csr,
                        spgemm_ref_numpy)
from repro.runtime import (BlockChunkSet, GatherChunkSet, PlanCache,
                           ReapRuntime, deserialize_plan, serialize_plan,
                           spgemm_block_chunked, spgemm_gather_chunked)


def _rand(n, m, density, seed=0, pattern="uniform"):
    return random_csr(n, m, density, np.random.default_rng(seed), pattern)


def _revalue(a: CSR, seed: int) -> CSR:
    rng = np.random.default_rng(seed)
    return CSR(a.n_rows, a.n_cols, a.indptr, a.indices,
               rng.standard_normal(a.nnz).astype(a.data.dtype))


class TestFingerprint:
    def test_same_pattern_different_values_collide(self):
        a = _rand(50, 60, 0.1, 1)
        a2 = _revalue(a, 99)
        fp1 = fingerprint_pattern("spgemm_gather", (a,), tile=1024)
        fp2 = fingerprint_pattern("spgemm_gather", (a2,), tile=1024)
        assert fp1 == fp2 and hash(fp1) == hash(fp2)

    def test_miss_on_any_component(self):
        a = _rand(50, 50, 0.1, 1)
        base = fingerprint_pattern("spgemm_gather", (a,), tile=1024)
        # different shape
        wide = _rand(50, 60, 0.1, 1)
        assert fingerprint_pattern("spgemm_gather", (wide,), tile=1024) != base
        # different indices (same shape/nnz): shift one column id
        idx = a.indices.copy()
        idx[0] = (idx[0] + 1) % a.n_cols
        if idx[0] == a.indices[0]:
            idx[0] = (idx[0] + 1) % a.n_cols
        perturbed = CSR(a.n_rows, a.n_cols, a.indptr, idx, a.data)
        assert fingerprint_pattern("spgemm_gather", (perturbed,),
                                   tile=1024) != base
        # different indptr (move an element between rows)
        ip = a.indptr.copy()
        ip[1] += 1
        ip2 = CSR(a.n_rows, a.n_cols, ip, a.indices, a.data)
        assert fingerprint_pattern("spgemm_gather", (ip2,), tile=1024) != base
        # different params (tile/capacity/block) and different op
        assert fingerprint_pattern("spgemm_gather", (a,), tile=512) != base
        assert fingerprint_pattern("spgemm_block", (a,), tile=1024) != base


class TestPlanCache:
    def test_hit_returns_identical_plan(self):
        cache = PlanCache(capacity=4)
        a, b = _rand(40, 40, 0.1, 1), _rand(40, 40, 0.1, 2)
        fp = fingerprint_pattern("spgemm_gather", (a, b), tile=1024)
        p1, hit1 = cache.get_or_build(fp, lambda: inspect_spgemm_gather(a, b))
        p2, hit2 = cache.get_or_build(fp, lambda: inspect_spgemm_gather(a, b))
        assert not hit1 and hit2
        assert p1 is p2                      # the exact cached object
        assert cache.stats.hits == 1 and cache.stats.misses == 1

    def test_hit_schedule_bundles_bit_identical(self):
        """Same pattern + different values ⇒ bit-identical schedule bundles."""
        a, b = _rand(60, 60, 0.08, 3), _rand(60, 60, 0.08, 4)
        a2, b2 = _revalue(a, 11), _revalue(b, 12)
        p1 = inspect_spgemm_gather(a, b)
        p2 = inspect_spgemm_gather(a2, b2)
        for key in ("a_idx", "b_idx", "out_idx"):
            np.testing.assert_array_equal(p1.schedule[key], p2.schedule[key])
        pb1 = inspect_spgemm_block(a, b, 16)
        pb2 = inspect_spgemm_block(a2, b2, 16)
        for key in ("a_id", "b_id", "out_id", "is_first", "is_last"):
            np.testing.assert_array_equal(pb1.schedule[key], pb2.schedule[key])

    def test_eviction_respects_capacity(self):
        cache = PlanCache(capacity=2)
        mats = [_rand(20 + i, 20 + i, 0.2, i) for i in range(4)]
        fps = [fingerprint_pattern("spgemm_gather", (m,), tile=64)
               for m in mats]
        for m, fp in zip(mats, fps):
            cache.put(fp, inspect_spgemm_gather(m, m, tile=64))
        assert len(cache) == 2
        assert cache.stats.evictions == 2
        # LRU order: the two most recent survive
        assert fps[2] in cache and fps[3] in cache
        assert fps[0] not in cache and fps[1] not in cache

    def test_lru_touch_on_get(self):
        cache = PlanCache(capacity=2)
        fps = [fingerprint_pattern("op", (_rand(10 + i, 10, 0.3, i),))
               for i in range(3)]
        cache.put(fps[0], "p0")
        cache.put(fps[1], "p1")
        assert cache.get(fps[0]) == "p0"     # touch 0 → 1 becomes LRU
        cache.put(fps[2], "p2")
        assert fps[0] in cache and fps[2] in cache and fps[1] not in cache

    def test_capacity_zero_disables(self):
        cache = PlanCache(capacity=0)
        fp = fingerprint_pattern("op", (_rand(10, 10, 0.3, 0),))
        cache.put(fp, "plan")
        assert len(cache) == 0 and cache.get(fp) is None

    def test_max_entry_bytes_guard(self):
        """Oversized entries are refused at put (the route-cache guard)."""
        cache = PlanCache(capacity=8, max_entry_bytes=1024)
        fps = [fingerprint_pattern("op", (_rand(10 + i, 10, 0.3, i),))
               for i in range(2)]
        cache.put(fps[0], "gather")                       # tiny: admitted
        assert cache.get(fps[0]) == "gather"
        big = inspect_spgemm_gather(_rand(60, 60, 0.1, 3),
                                    _rand(60, 60, 0.1, 4))
        cache.put(fps[1], big)                            # plan-sized: no
        assert fps[1] not in cache
        assert cache.stats.rejected == 1

    def test_route_cache_guard_wired_in_runtime(self):
        rt = ReapRuntime(use_pallas=False)
        assert rt._routes.max_entry_bytes is not None
        a = _rand(80, 80, 0.05, 5)
        rt.spgemm(a, a)                  # auto-routing populates _routes
        assert len(rt._routes) == 1 and rt._routes.stats.rejected == 0


class TestCacheStats:
    def test_clear_resets_all_counters(self, tmp_path):
        """clear() must reset stats — store_hits included — so a cleared
        cache reports like a fresh one."""
        from repro.runtime import PlanStore
        store = PlanStore(tmp_path)
        cache = PlanCache(capacity=4, store=store)
        a = _rand(40, 40, 0.1, 1)
        fp = fingerprint_pattern("spgemm_gather", (a, a), tile=1024)
        cache.put(fp, inspect_spgemm_gather(a, a))
        fresh = PlanCache(capacity=4, store=store)
        assert fresh.get(fp) is not None            # answered by the store
        fresh.get(fingerprint_pattern("spgemm_gather", (a, a), tile=512))
        fresh.get(fp)
        s = fresh.stats
        assert (s.hits, s.store_hits, s.misses) == (1, 1, 1)
        fresh.clear()
        s = fresh.stats
        assert (s.hits, s.store_hits, s.misses, s.evictions,
                s.rejected) == (0, 0, 0, 0, 0)
        assert len(fresh) == 0 and s.hit_rate == 0.0

    def test_runtime_cache_stats_reflect_clear(self, tmp_path):
        rt = ReapRuntime(n_chunks=1, use_pallas=False,
                         store_dir=str(tmp_path))
        a = _rand(50, 50, 0.1, 2)
        rt.spgemm(a, a, method="gather")
        rt2 = ReapRuntime(n_chunks=1, use_pallas=False,
                          store_dir=str(tmp_path))
        rt2.spgemm(a, a, method="gather")
        assert rt2.cache_stats()["store_hits"] == 1
        rt2.cache.clear()
        cs = rt2.cache_stats()
        assert cs["store_hits"] == 0 and cs["hits"] == 0 \
            and cs["misses"] == 0
        # the per-op split resets with the aggregates (cache.on_clear)
        assert all(not any(rec.values()) for rec in cs["per_op"].values())

    def test_per_op_breakdown_present(self):
        rt = ReapRuntime(n_chunks=1, use_pallas=False)
        a = _rand(50, 50, 0.1, 3)
        rt.spgemm(a, a, method="gather")
        rt.spgemm(_revalue(a, 9), _revalue(a, 9), method="gather")
        per_op = rt.cache_stats()["per_op"]
        from repro.runtime import list_ops
        assert set(list_ops()) <= set(per_op)
        assert per_op["spgemm_gather"]["misses"] == 1
        assert per_op["spgemm_gather"]["hits"] == 1


class TestRuntimeCaching:
    def test_warm_spgemm_matches_and_skips_inspection(self):
        rt = ReapRuntime(n_chunks=1, use_pallas=False)
        a, b = _rand(80, 80, 0.08, 5), _rand(80, 80, 0.08, 6)
        _, st_cold = rt.spgemm(a, b, method="gather")
        a2, b2 = _revalue(a, 21), _revalue(b, 22)
        c, st_warm = rt.spgemm(a2, b2, method="gather")
        assert not st_cold["cache_hit"] and st_warm["cache_hit"]
        np.testing.assert_allclose(c.to_dense(),
                                   spgemm_ref_numpy(a2, b2).to_dense(),
                                   rtol=1e-4, atol=1e-5)

    def test_chunked_warm_hit(self):
        rt = ReapRuntime(n_chunks=4, use_pallas=False)
        a, b = _rand(100, 100, 0.05, 7), _rand(100, 100, 0.05, 8)
        _, st0 = rt.spgemm(a, b, method="gather")
        _, st1 = rt.spgemm(_revalue(a, 31), _revalue(b, 32), method="gather")
        assert not st0["cache_hit"] and st1["cache_hit"]

    def test_cholesky_warm_reuses_plan(self):
        rt = ReapRuntime(use_pallas=False)
        a = random_spd_csr(60, 0.08, np.random.default_rng(9))
        p0, _, st0 = rt.cholesky(a)
        scaled = CSR(a.n_rows, a.n_cols, a.indptr, a.indices, a.data * 2.0)
        p1, vals, st1 = rt.cholesky(scaled)
        assert not st0["cache_hit"] and st1["cache_hit"]
        assert p0 is p1
        # correctness on the new values
        from repro.core import plan_to_dense_l
        l = plan_to_dense_l(p1, vals)
        np.testing.assert_allclose(l @ l.T, scaled.to_dense(),
                                   rtol=1e-8, atol=1e-9)

    def test_block_path_cached(self):
        rt = ReapRuntime(use_pallas=False)
        a = _rand(64, 64, 0.1, 10, "blocky")
        _, st0 = rt.spgemm(a, a, method="block")
        c, st1 = rt.spgemm(_revalue(a, 41), _revalue(a, 41), method="block")
        assert not st0["cache_hit"] and st1["cache_hit"]
        a2 = _revalue(a, 41)
        np.testing.assert_allclose(c.to_dense(),
                                   spgemm_ref_numpy(a2, a2).to_dense(),
                                   rtol=1e-3, atol=1e-3)


class TestSerialization:
    @pytest.mark.parametrize("maker", [
        lambda: inspect_spgemm_gather(_rand(40, 50, 0.1, 1),
                                      _rand(50, 30, 0.1, 2)),
        lambda: inspect_spgemm_block(_rand(40, 50, 0.1, 3),
                                     _rand(50, 30, 0.1, 4), 16),
        lambda: inspect_cholesky(
            random_spd_csr(40, 0.1, np.random.default_rng(5))),
    ])
    def test_roundtrip(self, maker, tmp_path):
        plan = maker()
        # in-memory round trip
        back = deserialize_plan(serialize_plan(plan))
        assert type(back) is type(plan)
        # through npz on disk
        path = tmp_path / "plan.npz"
        np.savez(path, **serialize_plan(plan))
        with np.load(path, allow_pickle=False) as data:
            back2 = deserialize_plan(data)
        for p in (back, back2):
            for name in ("c_indptr", "out_idx", "out_id", "row_idx"):
                if hasattr(plan, name):
                    np.testing.assert_array_equal(getattr(plan, name),
                                                  getattr(p, name))

    def test_cholesky_roundtrip_executes(self):
        a = random_spd_csr(30, 0.1, np.random.default_rng(6))
        plan = inspect_cholesky(a)
        back = deserialize_plan(serialize_plan(plan))
        from repro.runtime import cholesky_execute_overlapped
        v1, _ = cholesky_execute_overlapped(plan, cholesky_values(a))
        v2, _ = cholesky_execute_overlapped(back, cholesky_values(a))
        np.testing.assert_array_equal(v1, v2)


class TestChunkSetSerialization:
    """Overlapped (chunked) plans must survive a save/load round-trip."""

    def test_gather_chunkset_roundtrip_executes(self, tmp_path):
        a, b = _rand(90, 90, 0.06, 11), _rand(90, 90, 0.06, 12)
        c_ref, _, chunkset = spgemm_gather_chunked(a, b, n_chunks=3)
        path = tmp_path / "chunkset.npz"
        np.savez(path, **serialize_plan(chunkset))
        with np.load(path, allow_pickle=False) as data:
            back = deserialize_plan(data)
        assert isinstance(back, GatherChunkSet)
        assert back.n_chunks == chunkset.n_chunks
        np.testing.assert_array_equal(back.row_bounds, chunkset.row_bounds)
        for p, q in zip(back.plans, chunkset.plans):
            np.testing.assert_array_equal(p.a_idx, q.a_idx)
            np.testing.assert_array_equal(p.out_idx, q.out_idx)
        # the deserialized chunk set drives a warm pipelined run exactly
        c, stats, _ = spgemm_gather_chunked(a, b, n_chunks=3, chunkset=back)
        np.testing.assert_array_equal(c.to_dense(), c_ref.to_dense())

    def test_block_chunkset_roundtrip_executes(self, tmp_path):
        a = _rand(96, 96, 0.08, 13, "blocky")
        c_ref, _, chunkset = spgemm_block_chunked(a, a, block=16, n_chunks=3,
                                                  use_pallas=False)
        path = tmp_path / "block_chunkset.npz"
        np.savez(path, **serialize_plan(chunkset))
        with np.load(path, allow_pickle=False) as data:
            back = deserialize_plan(data)
        assert isinstance(back, BlockChunkSet)
        assert back.n_chunks == chunkset.n_chunks
        c, stats, out_set = spgemm_block_chunked(a, a, block=16, n_chunks=3,
                                                 use_pallas=False,
                                                 chunkset=back)
        assert out_set is back           # warm: no rebuild
        np.testing.assert_array_equal(c.to_dense(), c_ref.to_dense())
