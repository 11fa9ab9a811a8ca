"""Continuous-batching serve benchmark: sustained tokens/sec + warm plans.

The serving claims of PR 8 (ROADMAP open item 1), both gated in bench.yml:

1. **continuous ≥ serial** — replaying the synthetic trace through the
   continuous-batching scheduler (``launch/scheduler.py``, decode slots
   shared across requests) must sustain ≥ ``MIN_CONTINUOUS_SPEEDUP`` ×
   the tokens/sec of the same trace served one-request-at-a-time
   (``max_batch=1``): the decode batch amortizes per-step launch overhead
   across in-flight requests.  Both modes run on pre-warmed jit caches
   (a warmup trace covering every prompt length), so the ratio measures
   the steady serving loop, not compilation.
2. **warm dispatch from inside compiled code** — with ``--host-moe``
   semantics (host runtime installed), decode stays jitted and routes
   expert-dispatch patterns through ``jax.pure_callback`` into the
   registry's ``moe_dispatch`` op.  Per-token routing patterns recur, so
   after warmup ≥ ``MIN_WARM_STEP_FRACTION`` of decode steps must run
   entirely on warm plans (zero fresh inspections), and the overall
   ``cache_stats()`` warm rate must clear ``MIN_OVERALL_WARM_RATE``.

Prints ``serve,...`` CSV lines and a PASS/FAIL verdict per claim, exits
non-zero when a gated claim fails, and writes JSON rows with ``--json``.

    PYTHONPATH=src python -m benchmarks.bench_serve [--reduced]
        [--arch dbrx-132b] [--json OUT]
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

import jax

from repro.configs import get_config, reduced_config
from repro.launch.compile_cache import init_compile_cache
from repro.launch.scheduler import Request, ServeScheduler, synthetic_trace
from repro.models import model as M
from repro.models import moe
from repro.runtime import ReapRuntime, RuntimeConfig, add_runtime_args

MIN_CONTINUOUS_SPEEDUP = 1.2     # continuous vs serial tokens/sec
MIN_WARM_STEP_FRACTION = 0.9     # decode steps with zero fresh inspections
MIN_OVERALL_WARM_RATE = 0.8      # cache_stats moe_dispatch warm_rate
MAX_SEQ = 32


def _warmup_trace(trace):
    """One request per distinct prompt length — compiles every prefill
    shape (and the decode step) before timing starts."""
    seen, reqs = set(), []
    for r in trace:
        n = len(r.prompt)
        if n not in seen:
            seen.add(n)
            reqs.append(Request(rid=10_000 + n, prompt=r.prompt, gen=6,
                                arrival=0))
    return reqs


def _timed_run(sch, trace):
    """Replay ``trace`` on a pre-warmed scheduler; returns (tok/s, tokens,
    decode_steps, seconds)."""
    done_before = len(sch.completions)
    steps_before = sch.stats["decode_steps"]
    t0 = time.time()
    sch.run(trace)
    dt = time.time() - t0
    new = sch.completions[done_before:]
    tokens = sum(len(c.tokens) for c in new)
    return tokens / dt, tokens, sch.stats["decode_steps"] - steps_before, dt


def _instrumented_run(sch, trace, rt):
    """Replay ``trace`` stepwise, classifying each decode step as warm
    (zero moe_dispatch misses) or cold."""
    pending = collections.deque(sorted(trace, key=lambda r: (r.arrival,
                                                             r.rid)))
    warm = cold = 0
    while pending or not sch.drained():
        while pending and pending[0].arrival <= sch.step_idx:
            sch.submit(pending.popleft())
        decoding = bool(sch.active_slots())
        before = rt.cache_stats()["per_op"]["moe_dispatch"]
        sch.step()
        after = rt.cache_stats()["per_op"]["moe_dispatch"]
        if decoding:
            if after["misses"] == before["misses"]:
                warm += 1
            else:
                cold += 1
    return warm, cold


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dbrx-132b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="tiny same-family config (default); "
                         "--no-reduced uses the published widths")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, metavar="OUT")
    add_runtime_args(ap)
    args = ap.parse_args(argv)
    init_compile_cache()
    base_cfg = RuntimeConfig.from_args(args)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    if cfg.ffn != "moe":
        print(f"bench_serve: {args.arch} has no MoE layers; the warm-"
              "dispatch gate needs one (default: dbrx-132b)", file=sys.stderr)
        return 2
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    trace = synthetic_trace(args.requests, seed=args.seed,
                            vocab=cfg.vocab_size, prompt_lens=(4, 6, 8),
                            gen_lens=(2, 4, 6, 8), max_gap=1)
    total_gen = sum(r.gen for r in trace)
    rows, failures = [], []

    rt = ReapRuntime(base_cfg)
    moe.set_host_dispatch_runtime(rt)
    try:
        # -- claim 1: continuous vs serial tokens/sec --------------------
        results = {}
        for mode, batch in (("serial", 1), ("continuous", args.max_batch)):
            sch = ServeScheduler(cfg, params, max_batch=batch,
                                 max_seq=MAX_SEQ)
            sch.run(_warmup_trace(trace))          # compile, then time
            tps, tokens, steps, dt = _timed_run(sch, trace)
            assert tokens == total_gen, (mode, tokens, total_gen)
            occupancy = M.cache_slot_occupancy(sch.cache)
            assert not occupancy.any(), f"orphaned slots: {occupancy}"
            results[mode] = tps
            rows.append(dict(row="serve", mode=mode, arch=args.arch,
                             batch=batch, tokens=tokens, decode_steps=steps,
                             seconds=round(dt, 4), tok_per_s=round(tps, 2)))
            print(f"serve,{mode},batch={batch},tokens={tokens},"
                  f"steps={steps},sec={dt:.3f},tok/s={tps:.1f}")
            lat = sch.latency_summary()
            rows.append(dict(row="latency", mode=mode, **{
                f"{kind}_{k}": (round(v, 6) if isinstance(v, float) else v)
                for kind, p in lat.items() for k, v in p.items()}))
            print(f"serve,latency,{mode},"
                  f"ttft_p50_ms={lat['ttft']['p50_s'] * 1e3:.1f},"
                  f"ttft_p99_ms={lat['ttft']['p99_s'] * 1e3:.1f},"
                  f"decode_p50_ms={lat['decode_step']['p50_s'] * 1e3:.1f},"
                  f"decode_p99_ms={lat['decode_step']['p99_s'] * 1e3:.1f}")
        speedup = results["continuous"] / results["serial"]
        ok1 = speedup >= MIN_CONTINUOUS_SPEEDUP
        rows.append(dict(row="gate", gate="continuous_speedup",
                         value=round(speedup, 3),
                         threshold=MIN_CONTINUOUS_SPEEDUP,
                         passed=bool(ok1)))
        print(f"{'PASS' if ok1 else 'FAIL'}: continuous/serial = "
              f"{speedup:.2f}x (need >= {MIN_CONTINUOUS_SPEEDUP}x)")
        if not ok1:
            failures.append("continuous_speedup")

        # -- claim 2: warm dispatch plans inside the jitted decode -------
        warm_rt = ReapRuntime(base_cfg)
        moe.set_host_dispatch_runtime(warm_rt)
        sch = ServeScheduler(cfg, params, max_batch=args.max_batch,
                             max_seq=MAX_SEQ)
        sch.run(_warmup_trace(trace))              # plan + jit warmup
        warm, cold = _instrumented_run(sch, trace, warm_rt)
        frac = warm / max(1, warm + cold)
        rec = warm_rt.cache_stats()["per_op"]["moe_dispatch"]
        ok2 = frac >= MIN_WARM_STEP_FRACTION
        ok3 = rec["warm_rate"] >= MIN_OVERALL_WARM_RATE
        rows.append(dict(row="gate", gate="warm_decode_steps",
                         warm_steps=warm, cold_steps=cold,
                         value=round(frac, 3),
                         threshold=MIN_WARM_STEP_FRACTION,
                         passed=bool(ok2)))
        rows.append(dict(row="gate", gate="overall_warm_rate",
                         hits=rec["hits"], store_hits=rec["store_hits"],
                         misses=rec["misses"],
                         value=round(rec["warm_rate"], 3),
                         threshold=MIN_OVERALL_WARM_RATE,
                         passed=bool(ok3)))
        print(f"serve,warm,steps_warm={warm},steps_cold={cold},"
              f"hits={rec['hits']},misses={rec['misses']},"
              f"warm_rate={rec['warm_rate']:.3f}")
        print(f"{'PASS' if ok2 else 'FAIL'}: {frac:.1%} of decode steps "
              f"fully warm after warmup (need >= "
              f"{MIN_WARM_STEP_FRACTION:.0%})")
        print(f"{'PASS' if ok3 else 'FAIL'}: moe_dispatch warm_rate = "
              f"{rec['warm_rate']:.2f} (need >= {MIN_OVERALL_WARM_RATE})")
        if not ok2:
            failures.append("warm_decode_steps")
        if not ok3:
            failures.append("overall_warm_rate")
    finally:
        moe.set_host_dispatch_runtime(None)

    if args.json:
        Path(args.json).write_text(json.dumps(rows, indent=2))
        print(f"wrote {args.json}")
    if failures:
        print(f"bench_serve: FAILED gates: {', '.join(failures)}",
              file=sys.stderr)
        return 1
    print("bench_serve: all gates passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
