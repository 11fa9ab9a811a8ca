"""The ``webbase1M`` stand-in and its cell: the web-graph generator at a
shrunk size and at full size against the published counts, the router's
choice, a shrunk run of the cell on the CPU, its control, and the reader of
``gather_chunk_skew``."""
from __future__ import annotations

import sys
from types import SimpleNamespace

import numpy as np
import pytest
import scipy.sparse as sp

from bench import harness, webgraph
from bench.control import control_readings
from bench.ops import spgemm_web
from bench.tests import tiny

BENCH = harness.load_benchmark()
CELL = "webbase1M.spgemm"
# Liu & Vinter, IPDPS 2014: A² of Williams/webbase-1M
PUBLISHED = {"products": 69.5e6, "nnz_c": 51.1e6}
SMALL = dict(rows=3000, nnz=9317)     # the published mean row length
HOST_METRICS = ("inspect_s", "gather_chunk_skew")


def _cell(**size):
    _, config, traffic, e2e, per_layer = harness.cell_spec(BENCH, CELL)
    return dict(config, **size), traffic, e2e, per_layer


def _matrix(n, indptr, indices):
    return sp.csr_matrix((np.ones(indices.shape[0], np.float32), indices,
                          indptr), shape=(n, n))


def _row_products(n, indptr, indices):
    rows = np.diff(indptr)
    return np.bincount(np.repeat(np.arange(n), rows), rows[indices],
                       minlength=n).astype(np.int64)


# -- the generator ------------------------------------------------------------

def test_shrunk_exact_sorted_directed():
    config = _cell(**SMALL)[0]
    n = config["rows"]
    indptr, indices = webgraph.pattern_of(config)
    assert indptr.shape == (n + 1,) and indptr[-1] == config["nnz"]
    assert indices.shape == (config["nnz"],) and indices.dtype == np.int64
    row = np.repeat(np.arange(n), np.diff(indptr))
    # strictly increasing columns within each row: sorted and distinct
    assert np.all((np.diff(indices) > 0) | (np.diff(row) > 0))
    assert not np.any(row == indices)             # no self-links
    a = _matrix(n, indptr, indices)
    assert (a != a.T).nnz > 0                     # directed
    rows = np.diff(indptr)
    assert (rows == 0).sum() > 0 and rows.max() > 20 * rows.mean()


def test_pattern_is_the_configurations_values_are_the_runs():
    config, traffic, _, _ = _cell(**SMALL)
    s1 = spgemm_web.prepare(config, traffic, tiny.SEED)
    s2 = spgemm_web.prepare(config, traffic, tiny.SEED + 1)
    assert np.array_equal(s1.indptr, s2.indptr)
    assert np.array_equal(s1.indices, s2.indices)
    assert not np.array_equal(s1.ring[0], s2.ring[0])
    other = webgraph.pattern_of(dict(config, pattern_seed=1))[1]
    assert not np.array_equal(s1.indices, other)


def test_generator_refuses_fewer_links_than_the_nnz():
    config = _cell(rows=400, nnz=1242, oversample=0.5)[0]
    with pytest.raises(ValueError, match="distinct pairs"):
        webgraph.pattern_of(config)


@pytest.fixture(scope="module")
def full():
    config = _cell()[0]
    return config, *webgraph.pattern_of(config)


def test_full_size_exact_and_a_squared_near_published(full):
    config, indptr, indices = full
    n = config["rows"]
    assert (n, config["nnz"], config["reduced"]) == (1000005, 3105536, [])
    assert indptr[-1] == indices.shape[0] == config["nnz"]
    rows = np.diff(indptr)
    products = int(_row_products(n, indptr, indices).sum())
    nnz_c = _matrix(n, indptr, indices) @ _matrix(n, indptr, indices)
    got = {"products": products, "nnz_c": nnz_c.nnz}
    del nnz_c
    recorded = config["standin_spgemm"]
    for name, value in got.items():
        assert abs(value / PUBLISHED[name] - 1) < 0.02, (name, value)
        assert value == recorded[name]
    assert rows.max() == recorded["row_nnz_max"]
    assert (rows == 0).sum() == recorded["empty_rows"]
    assert rows.mean() == pytest.approx(recorded["row_nnz_mean"], abs=1e-4)
    assert rows.std() == pytest.approx(recorded["row_nnz_sd"], abs=1e-3)


def test_full_size_routes_gather(full):
    from repro.core import CSR, choose_spgemm_path
    from repro.core.inspector import bsr_pattern_from_csr
    config, indptr, indices = full
    n = config["rows"]
    a = CSR(n, n, indptr, indices, np.ones(indices.shape[0], np.float32))
    fill = bsr_pattern_from_csr(a, 128).fill
    assert fill == pytest.approx(config["standin_spgemm"]["tile128_fill"],
                                 rel=1e-4)
    assert choose_spgemm_path(a, a) == "gather"


# -- the shrunk cell ----------------------------------------------------------

@pytest.mark.parametrize("trace", [False, True])
def test_shrunk_cell_is_correct_and_reads_host_metrics(trace, tmp_path):
    config, traffic, e2e, per_layer = _cell(**SMALL)
    line, _ = harness.run_cell(
        config, traffic, seed=tiny.SEED, seconds=0.2, trace=trace,
        end_to_end=e2e, per_layer=per_layer,
        peaks=harness.load_peaks("TPU v5 lite"),
        trace_dir=str(tmp_path / "trace"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["extra_entries"]["value"] == 0
    if not trace:
        assert set(line["metrics"]) == {"setup_s", "spgemm_s"}
        return
    # the CPU trace has no device plane: only host readings appear
    assert set(line["metrics"]) == set(HOST_METRICS)
    assert line["metrics"]["inspect_s"]["value"] > 0
    assert line["metrics"]["gather_chunk_skew"]["value"] >= 0


def test_metrics_declared_for_the_cell():
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    skew = per_layer["gather_chunk_skew"]
    assert skew["workloads"] == [CELL] and skew["moves"] == "spgemm_s"
    assert skew["unit"] == "%" and skew["better"] == "lower"
    assert CELL in per_layer["inspect_s"]["workloads"]
    spgemm_s = next(m for m in BENCH["end_to_end"] if m["name"] == "spgemm_s")
    assert CELL in spgemm_s["workloads"]
    wl = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "webbase1M", "spgemm_web", 1)


def test_control_fails():
    config, traffic, _, _ = _cell(**SMALL)
    limits = traffic["limits"]
    assert limits == {"extra_entries": 0, "max_scaled_err": 3e-06}
    for seed in (tiny.SEED, tiny.SEED + 1, tiny.SEED + 2):
        worst = control_readings(config, traffic, seed, 2)
        assert any(worst[k] > limits[k] for k in limits), worst


def test_reference_passes():
    config, traffic, _, _ = _cell(**SMALL)
    state = spgemm_web.prepare(config, traffic, tiny.SEED)
    for i in range(3):
        a = sp.csr_matrix((state.ring[i].astype(np.float64), state.indices,
                           state.indptr), shape=(state.n, state.n))
        c = (a @ a).tocsr()
        kept = (c.indptr, c.indices, c.data.astype(np.float32))
        numbers = spgemm_web.check(state, i, kept)
        assert all(numbers[k] <= v for k, v in traffic["limits"].items())


# -- the reader of gather_chunk_skew ------------------------------------------

def _ctx(n_ops):
    return SimpleNamespace(n_ops=n_ops, trace=None, counters={}, setup={})


def _gather_record(chunk_products, with_max=True):
    from repro.runtime import spans
    with spans.record("reap.run", op="spgemm_gather"):
        for n_pp in chunk_products:
            with spans.span("reap.launch"):
                spans.count("gather_products", n_pp)
        if with_max:
            spans.count("gather_chunk_products_max", max(chunk_products))


def test_skew_reads_the_counter_of_recorded_runs():
    from repro.runtime import spans
    spans.clear()
    for _ in range(3):
        _gather_record([10, 30, 20, 20])
    read = harness.load_metric("gather_chunk_skew")
    assert read(_ctx(3)) == pytest.approx(100.0 * (30 * 4 / 80 - 1))
    spans.clear()
    _gather_record([5, 5])
    assert read(_ctx(1)) == 0.0


def test_skew_reads_a_recorded_run_of_the_program():
    from repro.core import CSR
    from repro.runtime import ReapRuntime, spans
    from repro.runtime.pipeline import chunk_row_bounds
    config = _cell(**SMALL)[0]
    n = config["rows"]
    indptr, indices = webgraph.pattern_of(config)
    a = CSR(n, n, indptr, indices, np.ones(indices.shape[0], np.float32))
    spans.clear()
    ReapRuntime(n_chunks=4).run("spgemm", a, a, method="gather")
    bounds = chunk_row_bounds(a, 4)
    per_chunk = np.add.reduceat(_row_products(n, indptr, indices),
                                bounds[:-1])
    want = 100.0 * (per_chunk.max() * len(per_chunk) / per_chunk.sum() - 1)
    got = harness.load_metric("gather_chunk_skew")(_ctx(1))
    assert got == pytest.approx(want) and got > 0


def test_skew_is_none_without_the_counter():
    """As on a program whose gather path counts no chunk maximum."""
    from repro.runtime import spans
    spans.clear()
    _gather_record([10, 30], with_max=False)
    assert harness.load_metric("gather_chunk_skew")(_ctx(1)) is None


def test_skew_is_none_for_another_op_or_too_few_records():
    from repro.runtime import spans
    read = harness.load_metric("gather_chunk_skew")
    spans.clear()
    assert read(_ctx(1)) is None
    with spans.record("reap.run", op="spgemm_block"):
        spans.count("gather_products", 10)
        spans.count("gather_chunk_products_max", 10)
    assert read(_ctx(1)) is None
    assert read(_ctx(spans.RING_SIZE + 1)) is None


def test_skew_is_none_without_the_span_module(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    monkeypatch.delattr("repro.runtime.spans", raising=False)
    assert harness.load_metric("gather_chunk_skew")(_ctx(1)) is None
