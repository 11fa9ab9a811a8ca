"""The harness finds every cell's parts by name, prints the line the
contract asks for, and refuses to measure anything but a TPU."""
from __future__ import annotations

import json
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness, run
from bench.tests import tiny

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"] and 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for wl in m["workloads"]:
            reports = e2e[m["moves"]].get("workloads")
            assert reports is None or wl in reports, (m["name"], wl)


@pytest.mark.parametrize("workload", tiny.CELLS)
def test_cell_parts_found_by_name(workload):
    """Each cell's configuration, mix, op driver and metric readers."""
    wl, config, traffic, e2e, per_layer = harness.cell_spec(BENCH, workload)
    assert config["name"] == wl["config"] and wl["chips"] == 1
    op = harness.load_op(traffic["op"])
    for hook in ("prepare", "operands", "call", "keep", "check", "control",
                 "work"):
        assert callable(getattr(op, hook))
    assert {m["name"] for m in e2e} >= {"setup_s", traffic["per_op_metric"]}
    assert per_layer
    for m in per_layer:
        assert callable(harness.load_metric(m["name"]))


def test_unknown_device_has_no_peaks():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] > 0
    with pytest.raises(KeyError):
        harness.load_peaks("cpu")


@pytest.mark.parametrize("workload", tiny.MIXES)
@pytest.mark.parametrize("trace", [False, True])
def test_result_line(workload, trace, tmp_path):
    line = tiny.run(workload, tmp_path, trace=trace)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if trace else []
    assert list(line) == keys + ["checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    _, _, e2e, per_layer = tiny.cell(workload)
    if trace:
        # the CPU trace has no device plane: only host readings appear
        assert set(line["metrics"]) <= {m["name"] for m in per_layer}
        assert ("inspect_s" in line["metrics"]) == bool(per_layer)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == {m["name"] for m in e2e}
        assert all(v["value"] > 0 for v in line["metrics"].values())
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    json.dumps(line)


def test_run_refuses_a_cpu(capsys):
    assert run.main(["--workload", "cant.spgemm", "--seed", "1",
                     "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "TPU" in out.err


def test_run_needs_the_program(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "cant.spgemm", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""
