"""Plan-store benchmark: the claims of persistent warm restarts.

1. **warm restart beats cold build** — a process-fresh ``ReapRuntime`` whose
   plan cache is empty but whose plan *store* is populated must answer every
   op (gather SpGEMM, block SpGEMM, Cholesky, MoE dispatch) from disk — no
   inspection, ``cache_hit`` on the very first call — and acquire its plans
   at least ``MIN_SPEEDUP``× faster than rebuilding them.  The gated ratio
   is *plan acquisition* (summed cold ``inspect_s`` vs the store's summed
   load time): execution is identical on both sides, and on this CPU-only
   container its jax dispatch cost would only dilute the quantity the store
   actually changes.  End-to-end walls are reported alongside,
   informationally.
2. **corruption rebuilds transparently** — truncating one payload and
   bit-flipping another must not crash anything: the affected ops re-inspect,
   results stay correct, and write-through re-persists good copies (the
   store verifies clean afterwards).
3. **chunk-shape bucketing bounds compiles** — a mixed-pattern block
   workload replayed through ``BlockChunkSet`` must trigger at most one XLA
   compile per distinct pow-2 bucket tuple (``bucket_block_schedule``), not
   one per distinct raw chunk shape.
4. **exec-store warm restart skips XLA** (time-to-first-result) — a
   process-fresh runtime over a populated plan *and* executable store must
   reach its first op results with **zero XLA compilations** (every
   executor program deserialized from disk) and acquire plans+executables
   ``MIN_SPEEDUP``× faster than inspecting+compiling them, with bit-for-bit
   identical results.
5. **corrupt executables heal by recompiling** — bit-flipping every
   serialized executable must not crash or change results: affected keys
   recompile silently, write-through re-persists good copies, values stay
   bit-for-bit equal.

Prints ``plan_store,...`` CSV lines with a PASS/FAIL verdict per claim and
exits non-zero on failure (the gate ``.github/workflows/bench.yml`` relies
on).  ``--store-dir``/``--plan-store`` and ``--exec-store`` point at
persistent directories: the first call the benchmark makes against them
reports ``prior_store_hits`` / ``prior_exec_loads`` — on a machine that
restored the directories from a previous run (CI's ``actions/cache``),
those counts must be positive, which ``--expect-store-hits`` /
``--expect-exec-hits`` turn into gated claims (warm restart works across
machines, not just processes).

    PYTHONPATH=src python -m benchmarks.bench_plan_store [--reduced]
        [--plan-store DIR] [--exec-store DIR] [--expect-store-hits]
        [--expect-exec-hits] [--json OUT]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import List, Optional

import numpy as np

import jax.numpy as jnp

from repro.core import random_csr, random_spd_csr, spgemm_ref_numpy
from repro.core.spgemm import _block_execute_jnp
from repro.launch.compile_cache import init_compile_cache
from repro.runtime import (BlockChunkSet, ExecCache, ReapRuntime,
                           RuntimeConfig, bucket_block_schedule)
from repro.runtime.exec_store import EXE_DIR

#: documented tolerance: acquiring every plan of the mixed workload from the
#: store (load + integrity check + deserialize) must be at least this much
#: faster than rebuilding the plans via inspection.  bench.yml fails the
#: nightly run below this.
MIN_SPEEDUP = 1.5


class _Workload:
    """One mixed repeated-pattern workload covering every op tag.

    Gather-weighted on purpose: the gather inspector (partial-product sort +
    merge scheduling) is the paper's dominant one-time cost, so it carries
    the timing claim; block/Cholesky/MoE are in the loop to pin hit/round-
    trip behaviour for every op tag.
    """

    def __init__(self, reduced: bool):
        rng = np.random.default_rng(7)
        if reduced:
            gn, gd, bn, bd, cn, t, d = 900, 0.03, 512, 0.02, 300, 4096, 32
        else:
            gn, gd, bn, bd, cn, t, d = 1500, 0.03, 1024, 0.03, 550, 16384, 64
        self.ga = random_csr(gn, gn, gd, rng)
        self.gb = random_csr(gn, gn, gd, rng)
        self.ga2 = random_csr(gn, gn, gd, rng)
        self.gb2 = random_csr(gn, gn, gd, rng)
        self.ba = random_csr(bn, bn, bd, rng, "blocky")
        self.bb = random_csr(bn, bn, bd, rng, "blocky")
        self.chol = random_spd_csr(cn, 0.01, rng)
        self.tokens = rng.standard_normal((t, d)).astype(np.float32)
        self.expert_ids = rng.integers(0, 64, (t, 4))

    #: the benchmark's fixed non-store knobs; store directories vary per
    #: phase via dataclasses.replace (the one RuntimeConfig construction
    #: path — see runtime.api.RuntimeConfig)
    BASE_CFG = RuntimeConfig(use_pallas=False, block=64, n_chunks=4)

    @classmethod
    def runtime(cls, store_dir: Optional[str],
                exec_dir: Optional[str] = None) -> ReapRuntime:
        return ReapRuntime(dataclasses.replace(
            cls.BASE_CFG, store_dir=store_dir, exec_store_dir=exec_dir))

    def run(self, rt: ReapRuntime) -> dict:
        _, sg = rt.spgemm(self.ga, self.gb, method="gather")
        _, sg2 = rt.spgemm(self.ga2, self.gb2, method="gather")
        _, sb = rt.spgemm(self.ba, self.bb, method="block")
        _, _, sc = rt.cholesky(self.chol, dtype=jnp.float32)
        _, _, sm = rt.moe_dispatch(self.tokens, self.expert_ids, n_experts=64)
        return dict(gather=sg, gather2=sg2, block=sb, cholesky=sc,
                    moe_dispatch=sm)


def _stage_time(stats: dict) -> float:
    """Summed host-stage seconds of one workload pass (``inspect_s`` +
    ``plan_s``).  On a cold pass this is plan-build plus per-call value
    work (chunk scatter, bundling); on a warm pass plan-build is gone and
    only the value work remains — the cold−warm difference isolates the
    plan-build cost the store is meant to replace."""
    return sum(st.get("inspect_s", 0.0) + st.get("plan_s", 0.0)
               for st in stats.values())


def bench_warm_restart(store_dir: str, reduced: bool, repeats: int = 3,
                       verbose: bool = True) -> dict:
    wl = _Workload(reduced)

    # first touch of the (possibly pre-populated) store: on a restored CI
    # directory this is the cross-machine warm restart; it also populates
    # the store and warms the jit caches for the timed phases below
    rt0 = wl.runtime(store_dir)
    t0 = time.perf_counter()
    wl.run(rt0)
    first_s = time.perf_counter() - t0
    prior_hits = rt0.store.stats.loads

    cold_s: List[float] = []
    cold_stage: List[float] = []
    for _ in range(repeats):
        rt = wl.runtime(None)               # no store: full inspection
        t0 = time.perf_counter()
        stats = wl.run(rt)
        cold_s.append(time.perf_counter() - t0)
        cold_stage.append(_stage_time(stats))

    warm_s: List[float] = []
    warm_stage: List[float] = []
    load_s: List[float] = []
    for _ in range(repeats):
        rt = wl.runtime(store_dir)          # process-fresh cache, warm store
        t0 = time.perf_counter()
        stats = wl.run(rt)
        warm_s.append(time.perf_counter() - t0)
        warm_stage.append(_stage_time(stats))
        load_s.append(rt.store.stats.load_s)
        for op, st in stats.items():
            assert st["cache_hit"], f"{op}: store hit must skip inspection"
        assert rt.store.stats.loads > 0, "warm run must load from the store"

    cold, warm = float(np.min(cold_s)), float(np.min(warm_s))
    build = max(0.0, float(np.min(cold_stage)) - float(np.min(warm_stage)))
    load = float(np.min(load_s))
    speedup = build / max(load, 1e-9)
    all_hit = all(st["cache_hit"] for st in stats.values())
    row = dict(bench="warm_restart_vs_cold",
               cold_build_s=build, warm_load_s=load, speedup=speedup,
               cold_wall_s=cold, warm_wall_s=warm,
               wall_ratio=cold / max(warm, 1e-9), first_run_s=first_s,
               prior_store_hits=int(prior_hits),
               store_entries=len(rt0.store), all_ops_hit=all_hit, gate=True,
               ok=bool(speedup >= MIN_SPEEDUP and all_hit))
    if verbose:
        print(f"plan_store,warm_restart,cold_build_ms={build * 1e3:.1f},"
              f"warm_load_ms={load * 1e3:.1f},speedup={speedup:.2f},"
              f"cold_wall_ms={cold * 1e3:.1f},warm_wall_ms={warm * 1e3:.1f},"
              f"all_ops_hit={all_hit},prior_store_hits={prior_hits},"
              f"{'PASS' if row['ok'] else 'FAIL'}(>={MIN_SPEEDUP}x)")
    return row


def bench_corruption(reduced: bool, verbose: bool = True) -> dict:
    with tempfile.TemporaryDirectory() as d:
        wl = _Workload(True)                # corruption claim: small is fine
        rt = wl.runtime(d)
        wl.run(rt)
        plans = sorted(Path(d, "plans").iterdir())
        assert len(plans) >= 4, "expected one payload per op tag"
        # truncated npz payload + bit-flipped payload
        blob = plans[0].read_bytes()
        plans[0].write_bytes(blob[:max(1, len(blob) // 3)])
        blob = bytearray(plans[1].read_bytes())
        blob[len(blob) // 2] ^= 0xFF
        plans[1].write_bytes(bytes(blob))

        rt2 = wl.runtime(d)                 # fresh process, damaged store
        stats = wl.run(rt2)
        c, _ = rt2.spgemm(wl.ga, wl.gb, method="gather")
        dense_ok = np.allclose(c.to_dense(),
                               spgemm_ref_numpy(wl.ga, wl.gb).to_dense(),
                               rtol=1e-4, atol=1e-5)
        corrupt_seen = rt2.store.stats.corrupt
        rebuilt = sum(0 if st["cache_hit"] else 1 for st in stats.values())
        report = rt2.store.verify()         # write-through healed the store
        healed = not report["corrupt"] and len(report["ok"]) >= 4
        row = dict(bench="corruption_rebuild", corrupt_seen=int(corrupt_seen),
                   rebuilt_ops=rebuilt, healed=healed, values_ok=dense_ok,
                   gate=True,
                   ok=bool(corrupt_seen == 2 and rebuilt == 2 and healed
                           and dense_ok))
    if verbose:
        print(f"plan_store,corruption,corrupt_seen={corrupt_seen},"
              f"rebuilt_ops={rebuilt},healed={healed},values_ok={dense_ok},"
              f"{'PASS' if row['ok'] else 'FAIL'}")
    return row


def bench_bucketing(reduced: bool, verbose: bool = True) -> dict:
    """Mixed-pattern block workload: compiles ≤ distinct pow-2 buckets."""
    sizes = [368, 400, 432, 464] if reduced else [368, 400, 432, 464, 528,
                                                  592, 656, 720]
    rng = np.random.default_rng(11)
    rt = ReapRuntime(use_pallas=False, block=32, n_chunks=4)
    before = _block_execute_jnp._cache_size()
    for i, n in enumerate(sizes):
        a = random_csr(n, n, 0.02, rng, "blocky")
        b = random_csr(n, n, 0.02, rng, "blocky")
        c, _ = rt.spgemm(a, b, method="block")
        if i == 0:
            ok_vals = np.allclose(c.to_dense(),
                                  spgemm_ref_numpy(a, b).to_dense(),
                                  rtol=1e-3, atol=1e-3)
    compiles = _block_execute_jnp._cache_size() - before

    raw, bucketed, total_chunks = set(), set(), 0
    for plan in rt.cache._entries.values():     # benchmark-only introspection
        if not isinstance(plan, BlockChunkSet):
            continue
        for k in range(plan.n_chunks):
            ch = plan.chunk(k)
            sched = bucket_block_schedule(ch)
            raw.add((ch.n_pairs, ch.n_a_blocks, ch.n_b_blocks,
                     ch.n_out_blocks))
            bucketed.add((sched["pair_cap"], sched["a_cap"], sched["b_cap"],
                          sched["out_cap"]))
            total_chunks += 1
    row = dict(bench="chunk_shape_bucketing", patterns=len(sizes),
               total_chunks=total_chunks, raw_shapes=len(raw),
               bucketed_shapes=len(bucketed), compiles=int(compiles),
               values_ok=ok_vals, gate=True,
               ok=bool(compiles <= len(bucketed) < len(raw) and ok_vals))
    if verbose:
        print(f"plan_store,bucketing,chunks={total_chunks},"
              f"raw_shapes={len(raw)},bucketed_shapes={len(bucketed)},"
              f"compiles={compiles},{'PASS' if row['ok'] else 'FAIL'}"
              f"(compiles<=buckets<raw)")
    return row


def bench_exec_restart(store_dir: str, exec_dir: str, reduced: bool,
                       repeats: int = 3, verbose: bool = True) -> dict:
    """Claim 4: a restarted process reaches first results with zero XLA
    compiles and ≥ MIN_SPEEDUP× faster plan+compile acquisition.

    Cold side: fresh runtime, no stores, a memory-only ExecCache installed
    so every compilation is paid *and measured* through the same AOT path
    the store uses (``persistent_jit`` bypasses jax's per-process jit
    cache whenever an exec cache is active, so repeats stay honest).
    Warm side: process-fresh runtime over the populated plan + exec
    stores — acquisition is pure deserialization.
    """
    wl = _Workload(reduced)

    # first touch: populates both stores; on a CI-restored directory this
    # measures the cross-machine restart (prior_exec_loads > 0)
    rt0 = wl.runtime(store_dir, exec_dir)
    wl.run(rt0)
    prior_exec_loads = rt0.exec.stats.loads

    cold_acq: List[float] = []
    cold_ref = None
    for _ in range(repeats):
        rt = wl.runtime(None)           # no stores: inspect + compile
        rt.exec = ExecCache(store=None)  # count + time the compiles
        stats = wl.run(rt)
        cold_ref, _ = rt.spgemm(wl.ga, wl.gb, method="gather")
        assert rt.exec.stats.compiles > 0, \
            "cold side must pay XLA compilation"
        cold_acq.append(_stage_time(stats) + rt.exec.stats.compile_s)

    warm_acq: List[float] = []
    warm_compiles: List[int] = []
    warm_loads: List[int] = []
    exec_hits = True
    warm_ref = None
    for _ in range(repeats):
        rt = wl.runtime(store_dir, exec_dir)    # process-fresh, warm disks
        stats = wl.run(rt)
        warm_ref, st = rt.spgemm(wl.ga, wl.gb, method="gather")
        exec_hits &= all(s["exec_cache_hit"] for s in stats.values())
        exec_hits &= bool(st["exec_cache_hit"])
        warm_compiles.append(rt.exec.stats.compiles)
        warm_loads.append(rt.exec.stats.loads)
        warm_acq.append(rt.store.stats.load_s + rt.exec.stats.load_s)

    cold = float(np.min(cold_acq))
    warm = float(np.min(warm_acq))
    speedup = cold / max(warm, 1e-9)
    zero_compiles = max(warm_compiles) == 0
    loaded = min(warm_loads) >= 1
    bitwise = bool(np.array_equal(np.asarray(cold_ref.data),
                                  np.asarray(warm_ref.data)))
    row = dict(bench="exec_warm_restart_ttfr",
               cold_acquire_s=cold, warm_acquire_s=warm, speedup=speedup,
               warm_xla_compiles=int(max(warm_compiles)),
               warm_exec_loads=int(min(warm_loads)),
               prior_exec_loads=int(prior_exec_loads),
               exec_store_entries=len(rt0.exec.store),
               exec_hits=exec_hits, bitwise_equal=bitwise, gate=True,
               ok=bool(speedup >= MIN_SPEEDUP and zero_compiles and loaded
                       and exec_hits and bitwise))
    if verbose:
        print(f"plan_store,exec_restart,"
              f"cold_acquire_ms={cold * 1e3:.1f},"
              f"warm_acquire_ms={warm * 1e3:.1f},speedup={speedup:.2f},"
              f"warm_compiles={max(warm_compiles)},"
              f"exec_loads={min(warm_loads)},exec_hits={exec_hits},"
              f"bitwise={bitwise},prior_exec_loads={prior_exec_loads},"
              f"{'PASS' if row['ok'] else 'FAIL'}"
              f"(>={MIN_SPEEDUP}x, 0 compiles)")
    return row


def bench_exec_corruption(reduced: bool, verbose: bool = True) -> dict:
    """Claim 5: corrupt executable payloads recompile silently, results
    bit-for-bit equal, write-through re-persists good copies."""
    with tempfile.TemporaryDirectory() as d:
        plan_d, exec_d = str(Path(d, "plans")), str(Path(d, "exe"))
        wl = _Workload(True)               # corruption claim: small is fine
        rt = wl.runtime(plan_d, exec_d)
        wl.run(rt)
        ref, _ = rt.spgemm(wl.ga, wl.gb, method="gather")
        payloads = sorted(Path(exec_d, EXE_DIR).glob("*.bin"))
        assert payloads, "expected persisted executables"
        for p in payloads:                  # flip one byte in every payload
            blob = bytearray(p.read_bytes())
            blob[len(blob) // 2] ^= 0xFF
            p.write_bytes(bytes(blob))

        rt2 = wl.runtime(plan_d, exec_d)    # fresh process, damaged store
        wl.run(rt2)
        got, _ = rt2.spgemm(wl.ga, wl.gb, method="gather")
        corrupt_seen = rt2.exec.store.stats.corrupt
        recompiled = rt2.exec.stats.compiles
        repersisted = rt2.exec.stats.saves
        bitwise = bool(np.array_equal(np.asarray(ref.data),
                                      np.asarray(got.data)))
        report = rt2.exec.store.verify()    # write-through healed the store
        healed = not report["corrupt"] and len(report["ok"]) >= 1
        row = dict(bench="exec_corruption_recompile",
                   payloads=len(payloads), corrupt_seen=int(corrupt_seen),
                   recompiled=int(recompiled), repersisted=int(repersisted),
                   healed=healed, bitwise_equal=bitwise, gate=True,
                   ok=bool(corrupt_seen == len(payloads)
                           and recompiled >= len(payloads)
                           and repersisted >= len(payloads)
                           and healed and bitwise))
    if verbose:
        print(f"plan_store,exec_corruption,payloads={len(payloads)},"
              f"corrupt_seen={corrupt_seen},recompiled={recompiled},"
              f"repersisted={repersisted},healed={healed},bitwise={bitwise},"
              f"{'PASS' if row['ok'] else 'FAIL'}")
    return row


def _fleet_acquire_time(stats: dict, rt: ReapRuntime) -> float:
    """One process's plan+exec *acquisition* cost: inspection (plan build
    or digest-only when warm) + XLA compile time + store load time.
    Execution is excluded — it is identical on both sides of the fleet
    comparison."""
    return (sum(st.get("inspect_s", 0.0) for st in stats.values())
            + rt.exec.stats.compile_s + rt.store.stats.load_s
            + rt.exec.stats.load_s)


def _fleet_worker(shared_dir: str, reduced: bool) -> int:
    """Child process of :func:`bench_fleet_warm`: one workload pass
    against the shared content-addressed store; prints one
    ``FLEET {json}`` line the parent parses."""
    import hashlib
    wl = _Workload(reduced)
    rt = ReapRuntime(dataclasses.replace(
        wl.BASE_CFG, shared_store_dir=shared_dir))
    t0 = time.perf_counter()
    stats = wl.run(rt)
    wall = time.perf_counter() - t0
    c, _ = rt.spgemm(wl.ga, wl.gb, method="gather")
    cs = rt.cache_stats()
    print("FLEET " + json.dumps(dict(
        acquire_s=_fleet_acquire_time(stats, rt), wall_s=wall,
        compiles=rt.exec.stats.compiles, exec_loads=rt.exec.stats.loads,
        store_hits=cs["store_hits"], misses=cs["misses"],
        digest=hashlib.sha256(np.ascontiguousarray(
            np.asarray(c.data)).tobytes()).hexdigest())))
    return 0


def bench_fleet_warm(reduced: bool, verbose: bool = True) -> dict:
    """Fleet warm start: two fresh interpreters, one ``--shared-store``.

    Process 1 inspects, compiles and populates the content-addressed
    store; process 2 must build NOTHING — zero inspections, zero XLA
    compiles, every plan and executable loaded from process 1's writes —
    and acquire them at least ``MIN_SPEEDUP``× faster than process 1
    built them, with bit-for-bit identical results.  This is the gate for
    the sharded-runtime PR's "many inspectors, one plan namespace" claim
    (``bench.yml`` fleet step).

    Each worker needs the device, and a device belongs to one process: the
    parent must not have initialised a JAX backend, and the workers run
    one after the other.
    """
    import subprocess

    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise RuntimeError("bench_fleet_warm: this process already holds a "
                           "JAX backend, so its workers could not reach the "
                           "device; run it through --fleet-only")
    rows: List[dict] = []
    with tempfile.TemporaryDirectory(prefix="fleet-bench-") as d:
        for _ in range(2):
            cmd = [sys.executable, "-m", "benchmarks.bench_plan_store",
                   "--fleet-worker", "--shared-store", d]
            if reduced:
                cmd.append("--reduced")
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=900)
            if p.returncode != 0:
                raise RuntimeError(f"fleet worker failed:\n{p.stderr[-4000:]}")
            line = [ln for ln in p.stdout.splitlines()
                    if ln.startswith("FLEET ")][-1]
            rows.append(json.loads(line[len("FLEET "):]))
    a, b = rows
    speedup = a["acquire_s"] / max(b["acquire_s"], 1e-9)
    bitwise = a["digest"] == b["digest"]
    row = dict(bench="fleet_warm_start",
               first_acquire_s=a["acquire_s"],
               second_acquire_s=b["acquire_s"], speedup=speedup,
               second_compiles=int(b["compiles"]),
               second_misses=int(b["misses"]),
               second_exec_loads=int(b["exec_loads"]),
               second_store_hits=int(b["store_hits"]),
               bitwise_equal=bitwise, gate=True,
               ok=bool(speedup >= MIN_SPEEDUP and b["compiles"] == 0
                       and b["misses"] == 0 and b["exec_loads"] >= 1
                       and b["store_hits"] >= 1 and bitwise))
    if verbose:
        print(f"plan_store,fleet_warm,"
              f"first_acquire_ms={a['acquire_s'] * 1e3:.1f},"
              f"second_acquire_ms={b['acquire_s'] * 1e3:.1f},"
              f"speedup={speedup:.2f},second_compiles={b['compiles']},"
              f"second_misses={b['misses']},bitwise={bitwise},"
              f"{'PASS' if row['ok'] else 'FAIL'}"
              f"(>={MIN_SPEEDUP}x, 0 compiles)")
    return row


def bench_store_io(reduced: bool, verbose: bool = True) -> dict:
    """Informational: manifest + payload sizes, gc behaviour under budget."""
    with tempfile.TemporaryDirectory() as d:
        wl = _Workload(True)
        rt = wl.runtime(d)
        wl.run(rt)
        s = rt.store.summary()
        evicted = rt.store.gc(byte_budget=s["bytes"] // 2)
        after = rt.store.summary()
        row = dict(bench="store_io", entries=s["entries"], bytes=s["bytes"],
                   evicted_at_half_budget=len(evicted),
                   bytes_after_gc=after["bytes"], gate=False,
                   ok=after["bytes"] <= s["bytes"] // 2 and len(evicted) > 0)
    if verbose:
        print(f"plan_store,store_io,entries={s['entries']},"
              f"kB={s['bytes'] / 1e3:.0f},evicted={len(evicted)},"
              f"kB_after_gc={after['bytes'] / 1e3:.0f},"
              f"{'PASS' if row['ok'] else 'FAIL'}")
    return row


def run(reduced: bool = False, store_dir: Optional[str] = None,
        exec_dir: Optional[str] = None, expect_store_hits: bool = False,
        expect_exec_hits: bool = False, verbose: bool = True) -> List[dict]:
    tmps: List[str] = []
    if store_dir is None:
        store_dir = tempfile.mkdtemp(prefix="plan-store-bench-")
        tmps.append(store_dir)
    if exec_dir is None:
        exec_dir = tempfile.mkdtemp(prefix="exec-store-bench-")
        tmps.append(exec_dir)
    try:
        rows = [bench_warm_restart(store_dir, reduced, verbose=verbose),
                bench_corruption(reduced, verbose=verbose),
                bench_bucketing(reduced, verbose=verbose),
                bench_exec_restart(store_dir, exec_dir, reduced,
                                   verbose=verbose),
                bench_exec_corruption(reduced, verbose=verbose),
                bench_store_io(reduced, verbose=verbose)]
    finally:
        for tmp in tmps:
            shutil.rmtree(tmp, ignore_errors=True)
    if expect_store_hits:
        hits = rows[0]["prior_store_hits"]
        row = dict(bench="cold_machine_restart", prior_store_hits=hits,
                   gate=True, ok=hits > 0)
        if verbose:
            print(f"plan_store,cold_machine_restart,prior_store_hits={hits},"
                  f"{'PASS' if row['ok'] else 'FAIL'}(>0)")
        rows.append(row)
    if expect_exec_hits:
        loads = rows[3]["prior_exec_loads"]
        row = dict(bench="cold_machine_exec_restart", prior_exec_loads=loads,
                   gate=True, ok=loads > 0)
        if verbose:
            print(f"plan_store,cold_machine_exec_restart,"
                  f"prior_exec_loads={loads},"
                  f"{'PASS' if row['ok'] else 'FAIL'}(>0)")
        rows.append(row)
    if verbose:
        ok = all(r["ok"] for r in rows if r.get("gate", True))
        print(f"plan_store,verdict,{'PASS' if ok else 'FAIL'}")
    return rows


def main(argv: Optional[List[str]] = None) -> int:
    from repro.runtime import add_runtime_args
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reduced", action="store_true",
                    help="smaller problem sizes (CI mode)")
    ap.add_argument("--store-dir", dest="plan_store", metavar="DIR",
                    help="alias for --plan-store (original flag name)")
    ap.add_argument("--expect-store-hits", action="store_true",
                    help="fail unless the first touch of the plan store "
                         "hits plans persisted by a previous process/machine")
    ap.add_argument("--expect-exec-hits", action="store_true",
                    help="fail unless the first touch of the exec store "
                         "loads executables persisted by a previous "
                         "process/machine")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write result rows to this JSON file")
    ap.add_argument("--fleet-only", action="store_true",
                    help="run only the fleet warm-start gate: two fresh "
                         "processes over one --shared-store; the second "
                         "must acquire every plan+executable from the "
                         "first's writes with zero compiles")
    ap.add_argument("--fleet-worker", action="store_true",
                    help="internal: run one workload pass against "
                         "--shared-store and print a FLEET result line")
    add_runtime_args(ap)    # --plan-store/--exec-store + shared knobs
    args = ap.parse_args(argv)
    init_compile_cache()
    if args.fleet_worker:
        return _fleet_worker(args.shared_store, args.reduced)
    if args.fleet_only:
        row = bench_fleet_warm(args.reduced)
        if args.json:
            Path(args.json).write_text(json.dumps(
                dict(bench="plan_store_fleet", reduced=args.reduced,
                     min_speedup=MIN_SPEEDUP, rows=[row]), indent=1))
        return 0 if row["ok"] else 1
    rows = run(reduced=args.reduced, store_dir=args.plan_store,
               exec_dir=args.exec_store,
               expect_store_hits=args.expect_store_hits,
               expect_exec_hits=args.expect_exec_hits)
    if args.json:
        Path(args.json).write_text(json.dumps(
            dict(bench="plan_store", reduced=args.reduced,
                 min_speedup=MIN_SPEEDUP, rows=rows), indent=1))
    return 0 if all(r["ok"] for r in rows if r.get("gate", True)) else 1


if __name__ == "__main__":
    sys.exit(main())
