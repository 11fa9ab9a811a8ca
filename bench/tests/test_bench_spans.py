"""The metrics read from the program's run records: reported by the shrunk
traced cells on the CPU, and None, without raising, wherever the records
cannot be the window's operations."""
from __future__ import annotations

import sys
from types import SimpleNamespace

import pytest

from bench import harness
from bench.tests import tiny

SPAN_METRICS = {
    "cant.spgemm": ("acquire_ms.spgemm", "emit_wait_ms.spgemm",
                    "transfer_ms.spgemm", "csr_extract_ms.spgemm",
                    "h2d_mb.spgemm"),
    "bcsstk17.cholesky": ("acquire_ms.cholesky", "dispatch_ms.cholesky",
                          "drain_ms.cholesky", "h2d_puts_per_factor",
                          "h2d_mb.cholesky"),
}
ALL = [m for names in SPAN_METRICS.values() for m in names]
# a cell whose product fits one chunk never waits on the emit worker
MAY_BE_ZERO = {"emit_wait_ms.spgemm"}


def _ctx(n_ops):
    return SimpleNamespace(n_ops=n_ops, trace=None, counters={}, setup={})


def test_every_span_metric_is_declared_for_its_cell():
    per_layer = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    for cell, names in SPAN_METRICS.items():
        for name in names:
            m = per_layer[name]
            assert m["workloads"] == [cell]
            assert m["source"] in ("program_span", "program_counter")


@pytest.mark.parametrize("workload", sorted(SPAN_METRICS))
def test_traced_cell_reports_its_span_metrics(workload, tmp_path):
    line = tiny.run(workload, tmp_path, trace=True)
    assert line["correct"] is True
    for name in SPAN_METRICS[workload]:
        value = line["metrics"][name]["value"]
        assert value >= 0 if name in MAY_BE_ZERO else value > 0, name


def test_chunked_spgemm_waits_on_the_emit_worker(tmp_path):
    """Smaller tiles give the shrunk product several chunks, so the caller
    waits on the emit worker and the wait is read."""
    config, traffic, e2e, per_layer = tiny.cell("cant.spgemm")
    traffic = dict(traffic, runtime={"block": 32})
    line, _ = harness.run_cell(
        config, traffic, seed=tiny.SEED, seconds=0.2, trace=True,
        end_to_end=e2e, per_layer=per_layer,
        peaks=harness.load_peaks("TPU v5 lite"),
        trace_dir=str(tmp_path / "trace"))
    assert line["correct"] is True
    for name in SPAN_METRICS["cant.spgemm"]:
        assert line["metrics"][name]["value"] > 0, name


@pytest.mark.parametrize("name", ALL)
def test_reader_is_none_with_too_few_records(name):
    from repro.runtime import spans
    read = harness.load_metric(name)
    assert read(_ctx(spans.RING_SIZE + 1)) is None
    spans.clear()
    assert read(_ctx(1)) is None


@pytest.mark.parametrize("name", ALL)
def test_reader_is_none_for_another_op(name):
    from repro.runtime import spans
    spans.clear()
    with spans.record("reap.run", op="spmv"):
        spans.count("h2d_bytes", 10)
    assert harness.load_metric(name)(_ctx(1)) is None


@pytest.mark.parametrize("name", ALL)
def test_reader_is_none_without_the_span_module(name, monkeypatch):
    """As on a checkout of the program from before the run records."""
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    monkeypatch.delattr("repro.runtime.spans", raising=False)
    assert harness.load_metric(name)(_ctx(1)) is None
