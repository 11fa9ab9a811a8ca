"""Executable-persistence store: failure modes, keys, and the typed stats.

The contract under test (runtime/exec_store.py + the RunStats surface in
runtime/api.py):

* a corrupted payload is a silent miss — the caller recompiles, the store
  re-persists a good copy, and results stay bit-for-bit equal;
* an environment mismatch (jaxlib/device/backend/x64) is a miss, never a
  crash;
* the disk LRU respects its byte budget;
* a *fresh process* over a populated store reaches results with zero XLA
  compilations (the warm-restart claim, e2e);
* ``persistent_jit`` with no exec cache in effect is exactly ``jax.jit``;
* host-callback executables are detected and kept process-local;
* ``RunStats`` keeps dict-style back-compat and ``RuntimeConfig.from_args``
  is the one flag→config path.
"""
import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

from repro.runtime.api import RunStats, RuntimeConfig, add_runtime_args
from repro.runtime.exec_store import (ExecCache, ExecStore, EXE_DIR,
                                      persistent_jit, use_exec_cache)
from repro.runtime.exec_store import main as exec_store_cli

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _f(x, *, n):
    return jnp.cumsum(x * 2.0)[:n]


def _pj():
    # a fresh wrapper per test: PersistentJitFn instances memoize through
    # the *cache*, and tests want isolated compile counters
    return persistent_jit(_f, static_argnames=("n",))


def _x():
    return jnp.arange(8, dtype=jnp.float32)


class TestPersistentJit:
    def test_no_cache_is_plain_jit(self):
        fn = _pj()
        out = fn(_x(), n=4)
        np.testing.assert_allclose(np.asarray(out),
                                   np.cumsum(np.arange(8) * 2.0)[:4])
        assert fn._aot_compiles == 0          # never took the AOT path

    def test_cache_resolves_and_dedups(self, tmp_path):
        cache = ExecCache(ExecStore(tmp_path / "exec"))
        fn = _pj()
        with use_exec_cache(cache):
            a = fn(_x(), n=4)
            b = fn(_x(), n=4)                 # same key: memory hit
            c = fn(_x() + 1.0, n=4)           # same shapes: still one key
        assert cache.stats.compiles == 1
        assert cache.stats.mem_hits >= 2
        assert cache.stats.saves == 1
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_distinct_statics_distinct_keys(self, tmp_path):
        cache = ExecCache(ExecStore(tmp_path / "exec"))
        fn = _pj()
        with use_exec_cache(cache):
            fn(_x(), n=4)
            fn(_x(), n=6)
        assert cache.stats.compiles == 2

    def test_weak_type_does_not_collide(self, tmp_path):
        # avals differing only in weak_type lower differently; sharing one
        # executable between them would return wrongly-typed results
        cache = ExecCache(ExecStore(tmp_path / "exec"))

        @persistent_jit
        def ident(x):
            return x + 1

        strong = jnp.array(1.0, dtype=jnp.float64)
        weak = jnp.asarray(1.0)               # python float: weak f64
        assert weak.weak_type and not strong.weak_type
        assert weak.shape == strong.shape and weak.dtype == strong.dtype
        with use_exec_cache(cache):
            ident(strong)
            ident(jnp.array(2.0, dtype=jnp.float64))
            n0 = cache.stats.compiles
            ident(weak)
        assert n0 == 1                        # same strong sig shared
        assert cache.stats.compiles == 2      # weak sig got its own

    def test_host_callback_stays_process_local(self, tmp_path):
        cache = ExecCache(ExecStore(tmp_path / "exec"))

        @persistent_jit
        def hop(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v) * 2.0,
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return y + 1.0

        with use_exec_cache(cache):
            out = hop(jnp.arange(4, dtype=jnp.float32))
        np.testing.assert_allclose(np.asarray(out), [1.0, 3.0, 5.0, 7.0])
        assert cache.stats.unserializable == 1
        assert cache.stats.saves == 0         # nothing persisted
        assert len(cache.store) == 0


class TestFailureModes:
    def _populate(self, root):
        cache = ExecCache(ExecStore(root))
        fn = _pj()
        with use_exec_cache(cache):
            ref = np.asarray(fn(_x(), n=4))
        assert cache.stats.saves == 1
        return ref

    def test_corrupt_payload_silently_recompiles(self, tmp_path):
        root = tmp_path / "exec"
        ref = self._populate(root)
        payloads = list((root / EXE_DIR).glob("*.bin"))
        assert len(payloads) == 1
        blob = bytearray(payloads[0].read_bytes())
        blob[len(blob) // 2] ^= 0xFF          # single-bit-ish flip
        payloads[0].write_bytes(bytes(blob))

        store = ExecStore(root)
        cache = ExecCache(store)
        fn = _pj()
        with use_exec_cache(cache):
            out = np.asarray(fn(_x(), n=4))
        np.testing.assert_array_equal(out, ref)          # bit-for-bit heal
        assert store.stats.corrupt == 1
        assert cache.stats.compiles == 1                 # recompiled
        assert cache.stats.saves == 1                    # re-persisted
        # and the healed store loads cleanly in yet another "process"
        cache3 = ExecCache(ExecStore(root))
        with use_exec_cache(cache3):
            np.testing.assert_array_equal(np.asarray(_pj()(_x(), n=4)), ref)
        assert cache3.stats.compiles == 0
        assert cache3.stats.loads == 1

    def test_env_mismatch_is_a_miss_not_a_crash(self, tmp_path):
        root = tmp_path / "exec"
        ref = self._populate(root)
        # manifest layer: probing a stored key from a different environment
        # is a counted miss, never an exception (the belt)
        store = ExecStore(root)
        stored_key = next(iter(store._load_manifest_locked()))
        store.env = dict(store.env, jaxlib="0.0.0-other")
        assert store.get(stored_key) is None
        assert store.stats.env_miss == 1
        # cache layer: the env is also folded into the exec key (the
        # suspenders), so the mismatched process compiles fresh under its
        # own key and both entries coexist
        cache = ExecCache(store)
        fn = _pj()
        with use_exec_cache(cache):
            out = np.asarray(fn(_x(), n=4))
        np.testing.assert_array_equal(out, ref)
        assert cache.stats.compiles == 1
        fresh = ExecStore(root)
        assert len(fresh) == 2
        report = fresh.verify()
        assert not report["corrupt"]
        assert len(report["stale_env"]) == 1    # the fake-env entry

    def test_disk_lru_respects_byte_budget(self, tmp_path):
        root = tmp_path / "exec"
        store = ExecStore(root, byte_budget=None)
        cache = ExecCache(store)
        fn = _pj()
        with use_exec_cache(cache):
            for n in (1, 2, 3, 4, 5, 6):
                fn(_x(), n=n)
        assert cache.stats.saves == 6
        sizes = [int(e["bytes"])
                 for e in store._load_manifest_locked().values()]
        budget = sum(sorted(sizes)[:2]) + max(sizes) // 2
        evicted = store.gc(budget)
        assert evicted                       # something had to go
        s = store.summary()
        assert s["bytes"] <= budget
        assert s["entries"] == 6 - len(evicted)
        # evicted payload files are gone from disk too
        assert len(list((root / EXE_DIR).glob("*.bin"))) == s["entries"]

    def test_put_time_gc_under_tiny_budget(self, tmp_path):
        # a budget smaller than two entries: every put evicts the LRU
        root = tmp_path / "exec"
        probe = ExecStore(root, byte_budget=None)
        cache0 = ExecCache(probe)
        fn = _pj()
        with use_exec_cache(cache0):
            fn(_x(), n=1)
        one = probe.summary()["bytes"]
        probe.clear()

        store = ExecStore(root, byte_budget=int(one * 1.5))
        cache = ExecCache(store)
        fn = _pj()
        with use_exec_cache(cache):
            for n in (1, 2, 3):
                fn(_x(), n=n)
        assert store.stats.evicted >= 2
        assert store.summary()["entries"] == 1

    def test_corrupt_manifest_restarts_empty(self, tmp_path):
        root = tmp_path / "exec"
        self._populate(root)
        (root / "manifest.json").write_text("{not json")
        store = ExecStore(root)
        assert len(store) == 0               # moved aside, not crashed
        assert store.stats.corrupt == 1
        assert (root / "manifest.corrupt").exists()


class TestWarmRestartE2E:
    SCRIPT = r"""
import sys
import numpy as np
import jax.numpy as jnp
from repro.runtime.exec_store import (ExecCache, ExecStore, persistent_jit,
                                      use_exec_cache)

@persistent_jit(static_argnames=("n",))
def f(x, *, n):
    return jnp.cumsum(x * 2.0)[:n]

cache = ExecCache(ExecStore(sys.argv[1]))
with use_exec_cache(cache):
    out = np.asarray(f(jnp.arange(8, dtype=jnp.float32), n=4))
print("RESULT", out.tolist())
print("COMPILES", cache.stats.compiles)
print("LOADS", cache.stats.loads)
"""

    def test_fresh_process_skips_xla(self, tmp_path):
        """The tentpole claim, end to end: run 2 in a *fresh interpreter*
        over the same store pays zero XLA compiles and agrees bitwise."""
        script = tmp_path / "warm.py"
        script.write_text(self.SCRIPT)
        env = dict(os.environ, PYTHONPATH=SRC)

        def run():
            out = subprocess.run(
                [sys.executable, str(script), str(tmp_path / "exec")],
                capture_output=True, text=True, env=env, timeout=300)
            assert out.returncode == 0, out.stderr
            lines = dict(line.split(" ", 1)
                         for line in out.stdout.splitlines()
                         if " " in line)
            return (json.loads(lines["RESULT"]), int(lines["COMPILES"]),
                    int(lines["LOADS"]))

        res1, compiles1, loads1 = run()
        assert compiles1 == 1 and loads1 == 0
        res2, compiles2, loads2 = run()
        assert compiles2 == 0, "warm restart must skip XLA entirely"
        assert loads2 == 1
        assert res1 == res2                   # bit-for-bit across processes


class TestCLI:
    def _store_with_entry(self, tmp_path):
        root = tmp_path / "exec"
        cache = ExecCache(ExecStore(root))
        fn = _pj()
        with use_exec_cache(cache):
            fn(_x(), n=4)
        return root

    def test_ls_verify_gc(self, tmp_path, capsys):
        root = self._store_with_entry(tmp_path)
        assert exec_store_cli(["ls", str(root)]) == 0
        out = capsys.readouterr().out
        assert "1 executables" in out and "ok" in out

        assert exec_store_cli(["verify", str(root)]) == 0
        assert "1 ok, 0 corrupt" in capsys.readouterr().out

        # corrupt it: verify reports (nonzero), --prune heals to empty
        payload = next((root / EXE_DIR).glob("*.bin"))
        payload.write_bytes(b"garbage")
        assert exec_store_cli(["verify", str(root)]) == 1
        assert "1 corrupt" in capsys.readouterr().out
        assert exec_store_cli(["verify", str(root), "--prune"]) == 0
        capsys.readouterr()
        assert exec_store_cli(["ls", str(root)]) == 0
        assert "empty" in capsys.readouterr().out

    def test_gc_budget(self, tmp_path, capsys):
        root = self._store_with_entry(tmp_path)
        assert exec_store_cli(["gc", str(root), "--budget-mb", "0"]) == 0
        assert "evicted 1 entries" in capsys.readouterr().out


class TestRunStatsAndFromArgs:
    def test_dict_style_back_compat(self):
        st = RunStats(cache_hit=True, fingerprint="abc",
                      extra={"method": "gather", "plan_s": 0.25})
        assert st["cache_hit"] is True
        assert st["method"] == "gather"
        assert st.get("plan_s", 0.0) == 0.25
        assert st.get("missing", 7) == 7
        assert "fingerprint" in st and "store_hit" not in st   # None=absent
        assert set(st.keys()) >= {"cache_hit", "fingerprint", "method"}
        assert dict(st.items()) == st.asdict()
        assert len(st) == len(list(iter(st)))

    def test_declared_fields_win_over_extra(self):
        st = RunStats(cache_hit=False, extra={"cache_hit": True})
        assert st["cache_hit"] is False

    def test_fields_mirror_registry_declaration(self):
        from repro.runtime import ops
        import dataclasses as dc
        declared = tuple(f.name for f in dc.fields(RunStats)
                         if f.name != "extra")
        assert declared == ops.RUNSTATS_FIELDS

    def _args(self, argv):
        ap = argparse.ArgumentParser()
        add_runtime_args(ap)
        return ap.parse_args(argv)

    def test_from_args_defaults_match_config_defaults(self):
        assert RuntimeConfig.from_args(self._args([])) == RuntimeConfig()

    def test_from_args_full_flag_set(self):
        cfg = RuntimeConfig.from_args(self._args(
            ["--plan-store", "/p", "--plan-store-budget-mb", "2",
             "--exec-store", "/e", "--exec-store-budget-mb", "3",
             "--cache-entries", "9", "--n-chunks", "2",
             "--no-pallas"]))
        assert cfg.store_dir == "/p" and cfg.store_budget_bytes == 2_000_000
        assert cfg.exec_store_dir == "/e"
        assert cfg.exec_budget_bytes == 3_000_000
        assert cfg.cache_entries == 9 and cfg.n_chunks == 2
        assert cfg.use_pallas is False

    def test_overrides_win_last(self):
        cfg = RuntimeConfig.from_args(self._args(["--n-chunks", "2"]),
                                      n_chunks=1, block=64)
        assert cfg.n_chunks == 1 and cfg.block == 64

    def test_partial_namespace_tolerated(self):
        # a parser that opted into none of the flags still works
        cfg = RuntimeConfig.from_args(argparse.Namespace())
        assert cfg == RuntimeConfig()

    def test_set_default_runtime_installs_and_clears(self):
        from repro.runtime import api
        rt = api.set_default_runtime(
            api.ReapRuntime(RuntimeConfig(n_chunks=1)))
        assert api.default_runtime() is rt
        assert rt.config.n_chunks == 1
        api.set_default_runtime(None)
        assert api.default_runtime() is not rt     # cleared → fresh lazy
        api.set_default_runtime(None)



class TestRuntimeIntegration:
    def test_run_reports_exec_cache_hit(self, tmp_path):
        from repro.core import random_csr
        from repro.runtime import ReapRuntime
        rng = np.random.default_rng(0)
        a = random_csr(96, 96, 0.05, rng, "blocky")
        b = random_csr(96, 96, 0.05, rng, "blocky")
        cfg = RuntimeConfig(use_pallas=False, block=32, n_chunks=1,
                            exec_store_dir=str(tmp_path / "exec"))
        rt = ReapRuntime(cfg)
        _, st1 = rt.spgemm(a, b, method="gather")
        assert st1["exec_cache_hit"] is False           # paid XLA
        _, st2 = rt.spgemm(a, b, method="gather")
        assert st2["exec_cache_hit"] is True            # fully warm
        # a runtime with no exec store reports None (absent from mapping)
        rt2 = ReapRuntime(RuntimeConfig(use_pallas=False, block=32,
                                        n_chunks=1))
        _, st3 = rt2.spgemm(a, b, method="gather")
        assert st3.exec_cache_hit is None
        assert "exec_cache_hit" not in st3

    def test_cross_runtime_warm(self, tmp_path):
        from repro.core import random_csr
        from repro.runtime import ReapRuntime
        rng = np.random.default_rng(1)
        a = random_csr(96, 96, 0.05, rng, "blocky")
        b = random_csr(96, 96, 0.05, rng, "blocky")
        base = RuntimeConfig(use_pallas=False, block=32, n_chunks=1,
                             exec_store_dir=str(tmp_path / "exec"))
        c1, _ = ReapRuntime(base).spgemm(a, b, method="gather")
        rt2 = ReapRuntime(base)                         # fresh caches
        c2, st = rt2.spgemm(a, b, method="gather")
        assert rt2.exec.stats.compiles == 0
        assert rt2.exec.stats.loads >= 1
        np.testing.assert_array_equal(np.asarray(c1.data),
                                      np.asarray(c2.data))
