"""One cell of the benchmark: set-up, a measured window, the comparison.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration file and traffic mix, the mix names its op driver
(``bench/ops/<op>.py``), and each per-layer metric is read by
``bench/metrics/<metric>.py``.  The loop is closed: one caller issues
operations back to back, and the window ends with the first operation
that completes after ``seconds``.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import statistics
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = Path(__file__).resolve().parent


# -- finding things by name ------------------------------------------------

def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell_spec(bench: dict, workload: str):
    """``(workload, config, traffic, end_to_end, per_layer)`` of a cell."""
    wl = next((w for w in bench["workloads"] if w["name"] == workload),
              None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == wl["config"])
    with open(ROOT / cfg["file"]) as f:
        config = json.load(f)
    traffic = load_traffic(wl["traffic"])

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    per_layer = [m for m in bench["per_layer"] if mine(m)]
    return wl, config, traffic, e2e, per_layer


def load_traffic(name: str) -> dict:
    with open(BENCH_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


def load_op(name: str):
    return importlib.import_module(f"bench.ops.{name}")


def load_metric(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str) -> dict:
    """Peaks of ``kind`` from ``peaks.json``; an unknown device is an
    error, never a default."""
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# -- the run -----------------------------------------------------------------

def _annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def init_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached,
    so that only a checkout's first run of a cell compiles."""
    import jax
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or str(ROOT / ".jax-cache"))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


_JAX_EVENTS = {"programs": 0, "compile_s": 0.0, "cache_load_s": 0.0,
               "listening": False}


def _jax_events() -> dict:
    """Programs obtained (JAX reports a persistent-cache hit as a backend
    compile too), the seconds that took, and the part of them spent loading
    from the persistent cache, so far in this process."""
    if not _JAX_EVENTS["listening"]:
        import jax

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _JAX_EVENTS["programs"] += 1
                _JAX_EVENTS["compile_s"] += duration
            elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
                _JAX_EVENTS["cache_load_s"] += duration

        jax.monitoring.register_event_duration_secs_listener(listener)
        _JAX_EVENTS["listening"] = True
    return {k: v for k, v in _JAX_EVENTS.items() if k != "listening"}


def _reservoir(rng, k: int, i: int, kept: dict, item) -> None:
    """Keep a uniform sample of ``k`` items of a stream; ``k == 0`` keeps
    every item."""
    if k == 0 or len(kept) < k:
        kept[i] = item
        return
    j = int(rng.integers(0, i + 1))
    if j < k:
        del kept[sorted(kept)[j]]
        kept[i] = item


def run_cell(config: dict, traffic: dict, *, seed: int, seconds: float,
             trace: bool, end_to_end, per_layer, peaks=None,
             t_start: float | None = None, trace_dir: str | None = None
             ) -> dict:
    """Run one cell on whatever device JAX has.

    Returns ``(line, info)``: the result line, and what goes to the log:
    set-up phases, programs obtained inside the window (there should be
    none), answers compared, and the cost of reading the trace.

    ``end_to_end``/``per_layer`` are the metric entries of
    ``BENCHMARK.json`` that this cell reports.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    jax.config.update("jax_enable_x64", True)    # Cholesky's float64
    ev0 = _jax_events()
    phases = {"start_s": time.perf_counter() - t_start}
    t = time.perf_counter()
    op = load_op(traffic["op"])
    state = op.prepare(config, traffic, seed)
    phases["generate_s"] = time.perf_counter() - t

    # set-up: plan miss, then every shape the window uses
    inspect_s = 0.0
    for w in range(int(traffic["warmup_ops"])):
        t = time.perf_counter()
        operands = op.operands(state, -1 - w)
        out, counters = op.call(state, operands)
        dt = time.perf_counter() - t
        if w == 0:
            phases["first_op_s"], inspect_s = dt, counters["inspect_s"]
        else:
            phases["warmup_s"] = phases.get("warmup_s", 0.0) + dt
    del out
    setup_s = time.perf_counter() - t_start
    ev1 = _jax_events()
    phases.update({k: ev1[k] - ev0[k] for k in ev0})

    sample_rng = np.random.default_rng([seed % 2**64, 7])
    trace_ops = int(traffic.get("trace_ops", 0))   # 0: the whole window
    kept: dict = {}
    durations, counters_log = [], []
    if trace:
        from jax import profiler
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        trace_dir = trace_dir or str(ROOT / ".bench-trace")
        profiler.start_trace(trace_dir, profiler_options=opts)
    with _annotate("bench.window", trace):
        t0 = time.perf_counter()
        i = 0
        while True:
            a = time.perf_counter()
            with _annotate("bench.values", trace):
                operands = op.operands(state, i)
            with _annotate("bench.run", trace):
                out, counters = op.call(state, operands)
            b = time.perf_counter()
            with _annotate("bench.result", trace):
                durations.append(b - a)
                counters_log.append(counters)
                _reservoir(sample_rng, int(traffic["check_sample"]), i,
                           kept, op.keep(out))
                del out
            i += 1
            if b - t0 >= seconds or (trace and i == trace_ops):
                break
        window_s = b - t0
    t = time.perf_counter()
    if trace:
        profiler.stop_trace()
    trace_s = {"stop_s": time.perf_counter() - t}
    programs_window = _jax_events()["programs"] - ev1["programs"]

    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}

    reduced = None
    if trace:
        from bench import tracereduce
        pb = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
        t = time.perf_counter()
        loaded = tracereduce.load(str(pb[-1]))
        trace_s["load_s"] = time.perf_counter() - t
        reduced = tracereduce.reduce(loaded)
        trace_s["reduce_s"] = time.perf_counter() - t - trace_s["load_s"]
        trace_s["bytes"] = pb[-1].stat().st_size
        del loaded
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        if not reduced["chips"]:        # no device plane: nothing to read
            reduced = None

    n_ops = len(durations)
    e2e = {"setup_s": setup_s,
           traffic["per_op_metric"]: window_s / n_ops}
    for name, q in traffic.get("percentiles", {}).items():
        e2e[name] = float(np.percentile(durations, q))
    units = {m["name"]: m["unit"] for m in list(end_to_end) + list(per_layer)}
    metrics = {}
    if not trace:
        for m in end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        ctx = SimpleNamespace(
            trace=reduced, n_ops=n_ops, window_s=window_s,
            counters={k: [c[k] for c in counters_log]
                      for k in counters_log[0]},
            setup={"inspect_s": inspect_s}, peaks=peaks,
            work=lambda: op.work(state))
        for m in per_layer:
            value = load_metric(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": units[m["name"]]}

    # the comparison, once the window has closed and memory has been read
    state.release()
    limits = traffic["limits"]
    worst = {k: 0.0 for k in limits}
    failed = 0
    for i, item in sorted(kept.items()):
        numbers = op.check(state, i, item)
        failed += any(numbers[k] > limits[k] for k in limits)
        for k in limits:
            worst[k] = max(worst[k], numbers[k])
    checks = {k: {"value": worst[k], "limit": limits[k]} for k in limits}
    correct = bool(kept) and failed == 0

    line = {"correct": correct, "attempted": n_ops, "failed": failed,
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = {
            "device_ops": reduced["device_ops"] if reduced else [],
            "idle_gaps": reduced["idle_gaps"] if reduced else []}
    line["checks"] = checks
    info = {"setup": dict(phases, inspect_s=inspect_s),
            "programs_in_window": programs_window, "compared": len(kept),
            "trace": trace_s if trace else None,
            "op_s_median": statistics.median(durations)}
    return line, info
