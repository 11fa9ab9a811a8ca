"""Persistent plan store: round-trips per op tag, integrity failure modes
(truncation, digest mismatch, schema bumps) falling back to clean rebuilds,
disk LRU gc, and the end-to-end warm-restart path through ReapRuntime."""
import json

import numpy as np

import jax.numpy as jnp

from repro.core import (CSR, cholesky_values, inspect_cholesky,
                        inspect_spgemm_block, inspect_spgemm_gather,
                        random_csr, random_spd_csr, spgemm_ref_numpy)
from repro.core.inspector import (fingerprint_pattern, inspect_moe_dispatch,
                                  routing_csr)
from repro.runtime import (PlanCache, PlanStore, ReapRuntime,
                           cholesky_execute_overlapped,
                           fingerprint_from_json, fingerprint_to_json,
                           spgemm_block_chunked, spgemm_gather_chunked,
                           store_key)
from repro.runtime.plan_store import MANIFEST, SCHEMA_VERSION


def _rand(n, m, density, seed=0, pattern="uniform"):
    return random_csr(n, m, density, np.random.default_rng(seed), pattern)


def _payloads(store_dir):
    return sorted(p for p in (store_dir / "plans").iterdir()
                  if not p.name.startswith("."))


class TestFingerprintJson:
    def test_roundtrip_hash_equal(self):
        a = _rand(30, 40, 0.1, 1)
        fp = fingerprint_pattern("spgemm_gather", (a,), tile=1024, block=16)
        back = fingerprint_from_json(
            json.loads(json.dumps(fingerprint_to_json(fp))))
        assert back == fp and hash(back) == hash(fp)
        assert store_key(back) == store_key(fp)

    def test_distinct_fingerprints_distinct_keys(self):
        a, b = _rand(30, 30, 0.1, 1), _rand(30, 30, 0.1, 2)
        k1 = store_key(fingerprint_pattern("op", (a,)))
        k2 = store_key(fingerprint_pattern("op", (b,)))
        k3 = store_key(fingerprint_pattern("other", (a,)))
        assert len({k1, k2, k3}) == 3


class TestRoundTripPerOpTag:
    """put → fresh store → get must reproduce each op tag's plan."""

    def test_gather(self, tmp_path):
        a, b = _rand(40, 50, 0.1, 1), _rand(50, 30, 0.1, 2)
        plan = inspect_spgemm_gather(a, b)
        fp = fingerprint_pattern("spgemm_gather", (a, b), tile=1024)
        PlanStore(tmp_path).put(fp, plan)
        back = PlanStore(tmp_path).get(fp)          # fresh manifest read
        for name in ("a_idx", "b_idx", "out_idx", "c_indptr", "c_indices"):
            np.testing.assert_array_equal(getattr(back, name),
                                          getattr(plan, name))
        assert back.fingerprint == fp

    def test_block(self, tmp_path):
        a, b = _rand(40, 50, 0.1, 3), _rand(50, 30, 0.1, 4)
        plan = inspect_spgemm_block(a, b, 16)
        fp = fingerprint_pattern("spgemm_block", (a, b), block=16)
        PlanStore(tmp_path).put(fp, plan)
        back = PlanStore(tmp_path).get(fp)
        for name in ("a_id", "b_id", "out_id", "is_first", "is_last"):
            np.testing.assert_array_equal(getattr(back, name),
                                          getattr(plan, name))
        assert back.a_id.dtype == plan.a_id.dtype   # downcast is lossless

    def test_cholesky_executes(self, tmp_path):
        a = random_spd_csr(30, 0.1, np.random.default_rng(5))
        plan = inspect_cholesky(a)
        fp = fingerprint_pattern("cholesky", (a,))
        PlanStore(tmp_path).put(fp, plan)
        back = PlanStore(tmp_path).get(fp)
        v1, _ = cholesky_execute_overlapped(plan, cholesky_values(a))
        v2, _ = cholesky_execute_overlapped(back, cholesky_values(a))
        np.testing.assert_array_equal(v1, v2)

    def test_moe_dispatch(self, tmp_path):
        rng = np.random.default_rng(6)
        eids = rng.integers(0, 8, (32, 2))
        routing = routing_csr(eids, 8)
        plan = inspect_moe_dispatch(routing, capacity=10)
        fp = fingerprint_pattern("moe_dispatch", (routing,), capacity=10)
        PlanStore(tmp_path).put(fp, plan)
        back = PlanStore(tmp_path).get(fp)
        np.testing.assert_array_equal(back.dest, plan.dest)
        np.testing.assert_array_equal(back.slot_token, plan.slot_token)
        tokens = rng.standard_normal((32, 8)).astype(np.float32)
        np.testing.assert_array_equal(back.bundle(tokens),
                                      plan.bundle(tokens))

    def test_gather_chunkset_executes(self, tmp_path):
        a, b = _rand(90, 90, 0.06, 11), _rand(90, 90, 0.06, 12)
        c_ref, _, chunkset = spgemm_gather_chunked(a, b, n_chunks=3)
        fp = fingerprint_pattern("spgemm_gather_chunked", (a, b),
                                 tile=1024, n_chunks=3)
        PlanStore(tmp_path).put(fp, chunkset)
        back = PlanStore(tmp_path).get(fp)
        c, _, _ = spgemm_gather_chunked(a, b, n_chunks=3, chunkset=back)
        np.testing.assert_array_equal(c.to_dense(), c_ref.to_dense())

    def test_block_chunkset_executes(self, tmp_path):
        a = _rand(96, 96, 0.08, 13, "blocky")
        c_ref, _, chunkset = spgemm_block_chunked(a, a, block=16, n_chunks=3,
                                                  use_pallas=False)
        fp = fingerprint_pattern("spgemm_block_chunked", (a, a),
                                 block=16, n_chunks=3)
        PlanStore(tmp_path).put(fp, chunkset)
        back = PlanStore(tmp_path).get(fp)
        c, _, out_set = spgemm_block_chunked(a, a, block=16, n_chunks=3,
                                             use_pallas=False, chunkset=back)
        assert out_set is back                      # warm: no rebuild
        np.testing.assert_array_equal(c.to_dense(), c_ref.to_dense())


class TestFailureModes:
    """Every corruption falls back to a clean rebuild — never a crash."""

    def _populated(self, tmp_path):
        a, b = _rand(60, 60, 0.08, 21), _rand(60, 60, 0.08, 22)
        plan = inspect_spgemm_gather(a, b)
        fp = fingerprint_pattern("spgemm_gather", (a, b), tile=1024)
        store = PlanStore(tmp_path)
        store.put(fp, plan)
        return fp, plan

    def test_truncated_payload_rebuilds(self, tmp_path):
        fp, _ = self._populated(tmp_path)
        payload = _payloads(tmp_path)[0]
        payload.write_bytes(payload.read_bytes()[:64])
        store = PlanStore(tmp_path)
        assert store.get(fp) is None                # miss, not crash
        assert store.stats.corrupt == 1
        assert len(store) == 0                      # entry dropped

    def test_digest_mismatch_rebuilds(self, tmp_path):
        fp, _ = self._populated(tmp_path)
        payload = _payloads(tmp_path)[0]
        blob = bytearray(payload.read_bytes())
        blob[len(blob) // 2] ^= 0xFF
        payload.write_bytes(bytes(blob))
        store = PlanStore(tmp_path)
        assert store.get(fp) is None
        assert store.stats.corrupt == 1

    def test_schema_version_bump_rebuilds(self, tmp_path):
        fp, plan = self._populated(tmp_path)
        manifest = tmp_path / MANIFEST
        data = json.loads(manifest.read_text())
        data["schema"] = SCHEMA_VERSION + 1
        manifest.write_text(json.dumps(data))
        store = PlanStore(tmp_path)
        assert store.get(fp) is None and len(store) == 0
        store.put(fp, plan)                         # store is still usable
        assert PlanStore(tmp_path).get(fp) is not None

    def test_garbage_manifest_rebuilds(self, tmp_path):
        fp, plan = self._populated(tmp_path)
        (tmp_path / MANIFEST).write_text("{not json")
        store = PlanStore(tmp_path)
        assert store.get(fp) is None
        store.put(fp, plan)
        assert PlanStore(tmp_path).get(fp) is not None

    def test_cache_still_functional_after_corruption(self, tmp_path):
        """Runtime-level: a damaged store never breaks results, and the
        write-through heals it."""
        a = _rand(80, 80, 0.08, 23)
        rt = ReapRuntime(store_dir=str(tmp_path), n_chunks=1,
                         use_pallas=False)
        rt.spgemm(a, a, method="gather")
        for payload in _payloads(tmp_path):
            payload.write_bytes(payload.read_bytes()[:32])
        rt2 = ReapRuntime(store_dir=str(tmp_path), n_chunks=1,
                          use_pallas=False)
        c, st = rt2.spgemm(a, a, method="gather")
        assert not st["cache_hit"]                  # rebuilt transparently
        np.testing.assert_allclose(c.to_dense(),
                                   spgemm_ref_numpy(a, a).to_dense(),
                                   rtol=1e-4, atol=1e-5)
        report = rt2.store.verify()
        assert report["ok"] and not report["corrupt"]   # healed

    def test_verify_prune_drops_corrupt(self, tmp_path):
        fp, _ = self._populated(tmp_path)
        payload = _payloads(tmp_path)[0]
        payload.write_bytes(b"garbage")
        store = PlanStore(tmp_path)
        report = store.verify(prune=True)
        assert report["corrupt"] and len(store) == 0


class TestCrossProcessLocking:
    """Manifest mutations take manifest.lock and merge the on-disk state,
    so concurrent writers sharing one store_dir stop being
    last-writer-wins (they still fall through to best-effort writes on
    lock contention)."""

    def _plan_fp(self, seed):
        a = _rand(40 + seed, 40 + seed, 0.1, seed)
        return (inspect_spgemm_gather(a, a),
                fingerprint_pattern("spgemm_gather", (a, a), tile=1024))

    def test_stale_writer_merges_not_clobbers(self, tmp_path):
        p1, fp1 = self._plan_fp(1)
        p2, fp2 = self._plan_fp(2)
        s1, s2 = PlanStore(tmp_path), PlanStore(tmp_path)
        assert len(s2) == 0          # s2 caches an (empty) manifest view
        s1.put(fp1, p1)
        s2.put(fp2, p2)              # stale view: must merge under lock
        s3 = PlanStore(tmp_path)
        assert len(s3) == 2
        assert s3.get(fp1) is not None and s3.get(fp2) is not None

    def test_contention_falls_through(self, tmp_path):
        import repro.runtime.plan_store as ps
        if ps.fcntl is None:
            import pytest
            pytest.skip("no fcntl on this platform")
        p1, fp1 = self._plan_fp(3)
        holder = open(tmp_path / ps.LOCKFILE, "a+")
        ps.fcntl.flock(holder, ps.fcntl.LOCK_EX)
        try:
            store = PlanStore(tmp_path)
            store.lock_timeout = 0.1
            store.put(fp1, p1)       # contended: no hang, best-effort write
        finally:
            ps.fcntl.flock(holder, ps.fcntl.LOCK_UN)
            holder.close()
        assert store.stats.errors == 0
        assert PlanStore(tmp_path).get(fp1) is not None

    def test_stale_reader_mismatch_spares_fresh_entry(self, tmp_path):
        """A sha mismatch caused by the reader's own stale manifest view
        must not delete a concurrent writer's re-persisted valid entry."""
        p1, fp1 = self._plan_fp(5)
        s_writer = PlanStore(tmp_path)
        s_writer.put(fp1, p1)
        s_reader = PlanStore(tmp_path)
        assert len(s_reader) == 1            # reader caches this view
        # concurrent writer re-persists the same key with different bytes
        s_writer2 = PlanStore(tmp_path, compress=True)
        s_writer2.put(fp1, p1)
        # reader's cached sha no longer matches the new payload → its get
        # misses, but it must leave the writer's fresh entry intact
        assert s_reader.get(fp1) is None
        assert s_reader.stats.corrupt == 1
        fresh = PlanStore(tmp_path)
        assert fresh.get(fp1) is not None    # survived the stale reader

    def test_custom_plan_without_fingerprint_slot(self, tmp_path):
        """Custom serialize/deserialize hooks may persist plan objects that
        don't accept attribute assignment (e.g. plain dicts)."""
        import numpy as np_
        from repro.runtime import OpSpec, register_op, unregister_op

        def ser(plan):
            return {k: np_.asarray(v) for k, v in plan.items()}

        def deser(flat):
            return {k: np_.asarray(v) for k, v in flat.items()
                    if not k.endswith("__type")}

        spec = OpSpec(
            tag="dict_plan_op",
            fingerprint=lambda operands, cfg, *, chunked, **kw:
                fingerprint_pattern("dict_plan_op", operands),
            inspect=lambda operands, cfg, fp, **kw:
                {"ids": operands[0].indices.copy()},
            execute_sync=lambda plan, operands, cfg, **kw:
                (int(plan["ids"].sum()), dict(method="dict_plan_op")),
            serialize=ser, deserialize=deser)
        register_op(spec)
        try:
            a = _rand(20, 20, 0.2, 6)
            rt1 = ReapRuntime(store_dir=str(tmp_path))
            r1, st1 = rt1.run("dict_plan_op", a)
            assert not st1["cache_hit"]
            assert rt1.store.summary()["saves"] == 1
            rt2 = ReapRuntime(store_dir=str(tmp_path))   # fresh process
            r2, st2 = rt2.run("dict_plan_op", a)
            assert st2["cache_hit"] and r1 == r2         # no crash, warm
        finally:
            unregister_op("dict_plan_op")

    def test_lockfile_not_treated_as_orphan(self, tmp_path):
        p1, fp1 = self._plan_fp(4)
        store = PlanStore(tmp_path)
        store.put(fp1, p1)
        report = store.verify()
        assert not report["orphans"]     # lock lives outside plans/
        store.gc()
        assert store.get(fp1) is not None


class TestDiskLru:
    def test_byte_budget_evicts_lru(self, tmp_path):
        store = PlanStore(tmp_path, byte_budget=None)
        fps = []
        for i in range(4):
            a = _rand(50 + i, 50 + i, 0.1, 30 + i)
            fp = fingerprint_pattern("spgemm_gather", (a, a), tile=1024)
            store.put(fp, inspect_spgemm_gather(a, a))
            fps.append(fp)
        total = store.summary()["bytes"]
        assert len(store) == 4
        store.get(fps[0])                           # touch: 0 becomes MRU
        evicted = store.gc(byte_budget=total // 2)
        assert evicted and store.summary()["bytes"] <= total // 2
        assert fps[0] in store                      # MRU survived
        assert fps[1] not in store                  # LRU went first
        # evicted payload files are gone from disk too
        assert len(_payloads(tmp_path)) == len(store)

    def test_put_never_sweeps_other_writers_payloads(self, tmp_path):
        """Write-through puts must not delete payloads committed by a
        concurrent writer whose entries our manifest view predates
        (last-writer-wins may drop them from the *index*; the bytes and
        any already-loaded view must survive)."""
        store_b = PlanStore(tmp_path)
        assert len(store_b) == 0                    # B snapshots empty view
        store_a = PlanStore(tmp_path)
        a = _rand(40, 40, 0.1, 41)
        fpa = fingerprint_pattern("spgemm_gather", (a, a), tile=1024)
        store_a.put(fpa, inspect_spgemm_gather(a, a))   # A commits
        m = _rand(44, 44, 0.1, 42)
        fpb = fingerprint_pattern("spgemm_gather", (m, m), tile=1024)
        store_b.put(fpb, inspect_spgemm_gather(m, m))   # B's stale-view put
        assert store_a.get(fpa) is not None         # A's payload survived

    def test_orphan_payloads_swept(self, tmp_path):
        self_dir = tmp_path / "plans"
        store = PlanStore(tmp_path)
        a = _rand(40, 40, 0.1, 40)
        store.put(fingerprint_pattern("spgemm_gather", (a, a), tile=1024),
                  inspect_spgemm_gather(a, a))
        (self_dir / "deadbeef.npz").write_bytes(b"orphan")
        store.gc()
        assert not (self_dir / "deadbeef.npz").exists()


class TestRuntimeWarmRestart:
    def test_all_op_tags_restart_warm(self, tmp_path):
        rng = np.random.default_rng(50)
        ga = _rand(70, 70, 0.08, 51)
        ba = _rand(64, 64, 0.1, 52, "blocky")
        spd = random_spd_csr(50, 0.08, rng)
        eids = rng.integers(0, 8, (48, 2))
        tokens = rng.standard_normal((48, 16)).astype(np.float32)

        def run(rt):
            return [rt.spgemm(ga, ga, method="gather")[1],
                    rt.spgemm(ba, ba, method="block")[1],
                    rt.cholesky(spd, dtype=jnp.float32)[2],
                    rt.moe_dispatch(tokens, eids, n_experts=8)[2]]

        rt1 = ReapRuntime(store_dir=str(tmp_path), n_chunks=3, block=16,
                          use_pallas=False)
        cold = run(rt1)
        assert not any(st["cache_hit"] for st in cold)
        assert rt1.store.stats.saves >= 4

        rt2 = ReapRuntime(store_dir=str(tmp_path), n_chunks=3, block=16,
                          use_pallas=False)       # simulated process restart
        warm = run(rt2)
        assert all(st["cache_hit"] for st in warm)
        assert rt2.store.stats.loads >= 4
        assert rt2.cache.stats.store_hits >= 4
        stats = rt2.cache_stats()
        assert stats["store"]["entries"] >= 4

    def test_store_loaded_result_matches(self, tmp_path):
        a = _rand(90, 90, 0.06, 53)
        rt1 = ReapRuntime(store_dir=str(tmp_path), n_chunks=3,
                          use_pallas=False)
        rt1.spgemm(a, a, method="gather")
        rt2 = ReapRuntime(store_dir=str(tmp_path), n_chunks=3,
                          use_pallas=False)
        a2 = CSR(a.n_rows, a.n_cols, a.indptr, a.indices,
                 np.random.default_rng(54).standard_normal(a.nnz)
                 .astype(a.data.dtype))           # same pattern, new values
        c, st = rt2.spgemm(a2, a2, method="gather")
        assert st["cache_hit"]
        np.testing.assert_allclose(c.to_dense(),
                                   spgemm_ref_numpy(a2, a2).to_dense(),
                                   rtol=1e-4, atol=1e-5)

    def test_no_store_by_default(self):
        rt = ReapRuntime()
        assert rt.store is None and "store" not in rt.cache_stats()

    def test_capacity_zero_skips_store(self, tmp_path):
        a = _rand(40, 40, 0.1, 55)
        fp = fingerprint_pattern("spgemm_gather", (a, a), tile=1024)
        PlanStore(tmp_path).put(fp, inspect_spgemm_gather(a, a))
        cache = PlanCache(capacity=0, store=PlanStore(tmp_path))
        assert cache.get(fp) is None                # disabled cache: no disk
        assert cache.store.stats.loads == 0
