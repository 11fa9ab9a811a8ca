"""The benchmark's cells, and its ready mixes, at a size a CPU test run
holds.

Each keeps its traffic mix and limits and its configuration's generator;
only the sizes shrink.  A cell of ``BENCHMARK.json`` keeps its metrics
too; a mix that no cell uses yet reports its set-up and per-op times.
"""
from __future__ import annotations

import json

from bench import harness

SIZES = {
    "cant": dict(rows=108, nnz=4000, grid=[3, 3, 4]),
    "bcsstk17": dict(rows=300, nnz=2300, half_bandwidth=8),
}
MIXES = ("cant.spgemm", "bcsstk17.cholesky", "bcsstk17.cg")
CELLS = tuple(w["name"] for w in harness.load_benchmark()["workloads"])
SEED = 2**31 + 12345


def cell(workload: str):
    """``(config, traffic, end_to_end, per_layer)`` of a shrunk cell or
    mix (``<config>.<traffic>``)."""
    if workload in CELLS:
        wl, config, traffic, e2e, per_layer = harness.cell_spec(
            harness.load_benchmark(), workload)
        name = wl["config"]
    else:
        name, mix = workload.split(".", 1)
        with open(harness.BENCH_DIR / "configs" / f"{name}.json") as f:
            config = json.load(f)
        traffic = harness.load_traffic(mix)
        e2e = [{"name": n, "unit": "s"} for n in
               ["setup_s", traffic["per_op_metric"],
                *traffic.get("percentiles", {})]]
        per_layer = []
    return dict(config, **SIZES[name]), traffic, e2e, per_layer


def run(workload: str, tmp_path, *, trace: bool = False,
        seconds: float = 0.2):
    """Run the shrunk cell or mix on the CPU; returns the result line."""
    config, traffic, e2e, per_layer = cell(workload)
    line, _ = harness.run_cell(
        config, traffic, seed=SEED, seconds=seconds, trace=trace,
        end_to_end=e2e, per_layer=per_layer,
        peaks=harness.load_peaks("TPU v5 lite"),
        trace_dir=str(tmp_path / "trace"))
    return line
