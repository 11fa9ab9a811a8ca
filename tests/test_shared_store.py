"""Fleet store tests: one content-addressed payload namespace, N processes.

The claims of ``runtime/shared_store.py``, proven at three levels:

* **refcount semantics** — blobs dedup by content, manifest entries hold
  ``blob:<sha>`` refs, dropping a ref never unlinks, and ``gc`` removes a
  blob only when *no* manifest references it (the documented safety
  argument, exercised against hand-written manifests and real stores);
* **fleet e2e** — N fresh interpreters pointed at one ``--shared-store``
  root: only the first inspects and compiles; every later process answers
  its plans from the store and its executables with zero XLA compiles,
  bit-for-bit equal results;
* **concurrent writers** — simultaneous processes racing the same
  patterns leave the store consistent (no corrupt blobs, no dangling
  refs) and agree on results.
"""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import random_csr
from repro.runtime import ReapRuntime
from repro.runtime.api import RuntimeConfig, parse_mesh_shape
from repro.runtime.shared_store import (MANIFEST, SCHEMA_VERSION,
                                        SharedBlobs)
from repro.runtime.shared_store import main as shared_store_cli

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _write_manifest(root: Path, shas) -> None:
    """A minimal store manifest referencing the given blobs (the documented
    schema the refcounter reads)."""
    root.mkdir(parents=True, exist_ok=True)
    entries = {f"k{i}": dict(payload=f"blob:{sha}", bytes=1, last_used=0.0)
               for i, sha in enumerate(shas)}
    (root / MANIFEST).write_text(json.dumps(
        dict(schema=SCHEMA_VERSION, entries=entries)))


class TestRefcounts:
    def test_add_dedups_and_refreshes_mtime(self, tmp_path):
        blobs = SharedBlobs(tmp_path / "s")
        sha = blobs.add(b"payload")
        assert blobs.add(b"payload") == sha
        assert len(list(blobs.blob_dir.iterdir())) == 1
        # a dedup hit must refresh mtime so the GC grace window re-covers
        # the caller's write→manifest-commit gap
        os.utime(blobs.path(sha), (1.0, 1.0))
        blobs.add(b"payload")
        assert blobs.path(sha).stat().st_mtime > 1.0

    def test_gc_removes_only_unreferenced(self, tmp_path):
        blobs = SharedBlobs(tmp_path / "s")
        live = blobs.add(b"live")
        dead = blobs.add(b"dead")
        _write_manifest(blobs.store_root("plans"), [live])
        _write_manifest(blobs.store_root("exec"), [live])
        assert blobs.refcounts() == {live: 2}
        assert blobs.gc(grace_s=0.0) == [dead]
        assert blobs.path(live).exists()
        # one ref dropped: the other manifest still holds it → spared
        _write_manifest(blobs.store_root("plans"), [])
        assert blobs.gc(grace_s=0.0) == []
        assert blobs.path(live).exists()
        # last ref dropped → reclaimed
        _write_manifest(blobs.store_root("exec"), [])
        assert blobs.gc(grace_s=0.0) == [live]

    def test_grace_window_spares_fresh_unreferenced_blobs(self, tmp_path):
        """The lockless-fallback safety net: a blob written moments ago may
        be mid-publish (manifest commit pending), so default-grace gc must
        not touch it even with zero refs."""
        blobs = SharedBlobs(tmp_path / "s")
        sha = blobs.add(b"mid-publish")
        assert blobs.gc() == []
        assert blobs.path(sha).exists()

    def test_unparseable_manifest_contributes_no_refs(self, tmp_path):
        blobs = SharedBlobs(tmp_path / "s")
        sha = blobs.add(b"orphaned by corruption")
        _write_manifest(blobs.store_root("plans"), [sha])
        (blobs.store_root("plans") / MANIFEST).write_text("{not json")
        assert blobs.refcounts() == {}
        assert blobs.gc(grace_s=0.0) == [sha]

    def test_verify_reports(self, tmp_path):
        blobs = SharedBlobs(tmp_path / "s")
        ok = blobs.add(b"referenced")
        unref = blobs.add(b"unreferenced")
        bad = blobs.add(b"will be corrupted")
        blobs.path(bad).write_bytes(b"mutated in place")
        _write_manifest(blobs.store_root("plans"), [ok, "0" * 64])
        report = blobs.verify()
        assert report["ok"] == [ok]
        assert bad in report["corrupt"]
        assert unref in report["unreferenced"]
        assert report["dangling"] == ["0" * 64]


class TestRuntimeSharedStore:
    def _workload(self):
        rng = np.random.default_rng(7)
        return (random_csr(160, 160, 0.04, rng),
                random_csr(160, 160, 0.04, rng))

    def _runtime(self, shared_root) -> ReapRuntime:
        return ReapRuntime(RuntimeConfig(n_chunks=1,
                                         shared_store_dir=str(shared_root)))

    def test_manifests_hold_blob_refs(self, tmp_path):
        rt = self._runtime(tmp_path / "fleet")
        a, b = self._workload()
        rt.spgemm(a, b, method="gather")
        for store in (rt.store, rt.exec.store):
            entries = store._entries or {}
            assert entries, "store must have committed entries"
            assert all(str(e["payload"]).startswith("blob:")
                       for e in entries.values())
        # every ref resolves to a content-addressed blob
        assert not rt.shared.verify()["dangling"]
        assert not rt.shared.verify()["corrupt"]

    def test_gc_with_live_manifests_keeps_store_warm(self, tmp_path):
        root = tmp_path / "fleet"
        rt = self._runtime(root)
        a, b = self._workload()
        c0, _ = rt.spgemm(a, b, method="gather")
        junk = rt.shared.add(b"no manifest references this")
        live = set(rt.shared.refcounts())
        removed = rt.shared.gc(grace_s=0.0)
        assert junk in removed
        assert not set(removed) & live, "gc dropped a live-referenced blob"
        # the swept store still answers a fresh runtime from disk
        rt2 = self._runtime(root)
        c2, st2 = rt2.spgemm(a, b, method="gather")
        assert st2["cache_hit"]
        assert rt2.cache_stats()["store_hits"] >= 1
        np.testing.assert_array_equal(np.asarray(c0.data),
                                      np.asarray(c2.data))

    def test_ref_drop_then_gc_reclaims_exactly_those(self, tmp_path):
        rt = self._runtime(tmp_path / "fleet")
        a, b = self._workload()
        rt.spgemm(a, b, method="gather")
        before = set(rt.shared.refcounts())
        rt.store.gc(byte_budget=0)          # evict every plan *ref*
        after = set(rt.shared.refcounts())
        dropped = before - after
        assert dropped, "plan eviction must drop refs"
        for sha in dropped:                 # ref drop never unlinks
            assert rt.shared.path(sha).exists()
        removed = set(rt.shared.gc(grace_s=0.0))
        assert removed == dropped
        for sha in after:                   # exec refs survive untouched
            assert rt.shared.path(sha).exists()

    def test_cli_ls_verify_gc(self, tmp_path, capsys):
        root = tmp_path / "fleet"
        rt = self._runtime(root)
        a, b = self._workload()
        rt.spgemm(a, b, method="gather")
        assert shared_store_cli(["ls", str(root)]) == 0
        assert "blobs" in capsys.readouterr().out
        assert shared_store_cli(["verify", str(root)]) == 0
        out = capsys.readouterr().out
        assert "0 corrupt" in out and "0 dangling" in out
        assert shared_store_cli(["gc", str(root), "--grace-s", "0"]) == 0


def test_parse_mesh_shape():
    assert parse_mesh_shape("8") == (8,)
    assert parse_mesh_shape("2x4") == (2, 4)
    assert parse_mesh_shape("2,4") == (2, 4)
    assert parse_mesh_shape((2, 4)) == (2, 4)
    assert parse_mesh_shape(None) is None
    with pytest.raises(ValueError):
        parse_mesh_shape("0x4")


class TestFleetE2E:
    """N interpreters, one shared store: the many-inspectors/one-namespace
    claim end to end."""

    SCRIPT = r"""
import hashlib
import sys

import numpy as np

from repro.core import random_csr
from repro.runtime import ReapRuntime
from repro.runtime.api import RuntimeConfig

rng = np.random.default_rng(7)
a = random_csr(160, 160, 0.04, rng)
b = random_csr(160, 160, 0.04, rng)
rt = ReapRuntime(RuntimeConfig(n_chunks=1,
                               shared_store_dir=sys.argv[1]))
c, st = rt.spgemm(a, b, method="gather")
cs = rt.cache_stats()
print("STORE_HITS", cs["store_hits"])
print("MISSES", cs["misses"])
print("COMPILES", rt.exec.stats.compiles)
print("LOADS", rt.exec.stats.loads)
print("DIGEST", hashlib.sha256(
    np.ascontiguousarray(np.asarray(c.data)).tobytes()).hexdigest())
"""

    def _spawn(self, script: Path, root: Path):
        env = dict(os.environ, PYTHONPATH=SRC)
        return subprocess.Popen(
            [sys.executable, str(script), str(root)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)

    def _collect(self, proc) -> dict:
        out, err = proc.communicate(timeout=300)
        assert proc.returncode == 0, err
        return dict(line.split(" ", 1) for line in out.splitlines()
                    if " " in line)

    def test_only_first_process_plans_and_compiles(self, tmp_path):
        script = tmp_path / "worker.py"
        script.write_text(self.SCRIPT)
        root = tmp_path / "fleet"

        runs = []
        for _ in range(3):                  # sequential: strict expectations
            runs.append(self._collect(self._spawn(script, root)))

        first, rest = runs[0], runs[1:]
        assert int(first["MISSES"]) == 1 and int(first["STORE_HITS"]) == 0
        assert int(first["COMPILES"]) >= 1 and int(first["LOADS"]) == 0
        for r in rest:
            assert int(r["MISSES"]) == 0, "later processes must not inspect"
            assert int(r["STORE_HITS"]) == 1
            assert int(r["COMPILES"]) == 0, \
                "later processes must not pay XLA"
            assert int(r["LOADS"]) >= 1
            assert r["DIGEST"] == first["DIGEST"]   # bit-for-bit

    def test_concurrent_writers_leave_store_consistent(self, tmp_path):
        script = tmp_path / "worker.py"
        script.write_text(self.SCRIPT)
        root = tmp_path / "fleet"

        procs = [self._spawn(script, root) for _ in range(3)]
        runs = [self._collect(p) for p in procs]
        digests = {r["DIGEST"] for r in runs}
        assert len(digests) == 1, "racing writers must agree bit-for-bit"

        blobs = SharedBlobs(root)
        report = blobs.verify()
        assert not report["corrupt"], report
        assert not report["dangling"], report
        # the store the race left behind still warms a fresh process
        follower = self._collect(self._spawn(script, root))
        assert int(follower["MISSES"]) == 0
        assert int(follower["COMPILES"]) == 0
        assert follower["DIGEST"] in digests

    def test_gc_between_processes_never_drops_live_payloads(self, tmp_path):
        script = tmp_path / "worker.py"
        script.write_text(self.SCRIPT)
        root = tmp_path / "fleet"
        first = self._collect(self._spawn(script, root))

        removed = SharedBlobs(root).gc(grace_s=0.0)
        assert removed == [], "all blobs are manifest-referenced"
        warm = self._collect(self._spawn(script, root))
        assert int(warm["MISSES"]) == 0 and int(warm["COMPILES"]) == 0
        assert warm["DIGEST"] == first["DIGEST"]


def test_fleet_bench_refuses_a_parent_holding_the_device():
    """The fleet bench's workers each need the device, which belongs to
    one process: a parent that has initialised a backend is refused before
    any worker starts."""
    import jax

    from benchmarks.bench_plan_store import bench_fleet_warm
    jax.devices()
    with pytest.raises(RuntimeError, match="already holds a JAX backend"):
        bench_fleet_warm(reduced=True, verbose=False)
