"""The ``cop20K`` stand-in and its cell: the mesh generator at full size
against the published counts, the router's choice at full size, a shrunk
run of the cell on the CPU, its control, and the gather path's readers."""
from __future__ import annotations

import sys
from types import SimpleNamespace

import numpy as np
import pytest
import scipy.sparse as sp

from bench import generators, harness, meshes
from bench.control import control_readings
from bench.ops import spgemm_mesh
from bench.tests import tiny

BENCH = harness.load_benchmark()
CELL = "cop20K.spgemm"
# Liu & Vinter, IPDPS 2014: A² of Williams/cop20k_A
PUBLISHED = {"products": 79.9e6, "nnz_c": 18.7e6}
SMALL = dict(rows=3000, nnz=64961)    # the published mean row length; gather
HOST_METRICS = ("transfer_ms.gather", "h2d_mb.gather", "pad_ms.gather",
                "csr_extract_ms.gather", "gather_pad_pct", "inspect_s")
DEVICE_METRICS = ("device_idle.gather", "gather_roofline")
GATHER_READERS = HOST_METRICS[:-1]


def _cell(**size):
    _, config, traffic, e2e, per_layer = harness.cell_spec(BENCH, CELL)
    return dict(config, **size), traffic, e2e, per_layer


def _matrix(n, indptr, indices):
    return sp.csr_matrix((np.ones(indices.shape[0]), indices, indptr),
                         shape=(n, n))


@pytest.fixture(scope="module")
def full():
    config = _cell()[0]
    return config, *meshes.pattern_of(config)


def test_full_size_exact_sorted_symmetric(full):
    config, indptr, indices = full
    n = config["rows"]
    assert (n, config["nnz"]) == (121192, 2624331)
    assert indptr.shape == (n + 1,) and indptr[-1] == indices.shape[0]
    assert indices.shape == (config["nnz"],) and indices.dtype == np.int64
    row = np.repeat(np.arange(n), np.diff(indptr))
    # strictly increasing columns within each row: sorted and distinct
    assert np.all((np.diff(indices) > 0) | (np.diff(row) > 0))
    a = _matrix(n, indptr, indices)
    assert (a != a.T).nnz == 0
    assert int(np.sum(row == indices)) == config["diagonal_entries"]


def test_full_size_a_squared_near_published(full):
    config, indptr, indices = full
    n = config["rows"]
    row_nnz = np.diff(indptr).astype(np.int64)
    products = int(np.sum(row_nnz * row_nnz))     # symmetric: Σ r_i²
    nnz_c = (_matrix(n, indptr, indices) @ _matrix(n, indptr, indices)).nnz
    for name, got in (("products", products), ("nnz_c", nnz_c)):
        assert abs(got / PUBLISHED[name] - 1) < 0.10, (name, got)
        assert got == config["standin_spgemm"][name]


def test_full_size_routes_gather_and_cant_block(full):
    from repro.core import CSR, choose_spgemm_path

    def route(n, indptr, indices):
        a = CSR(n, n, indptr, indices, np.ones(indices.shape[0], np.float32))
        return choose_spgemm_path(a, a)

    config, indptr, indices = full
    assert route(config["rows"], indptr, indices) == "gather"
    cant = harness.cell_spec(BENCH, "cant.spgemm")[1]
    assert route(cant["rows"], *generators.pattern_of(cant)) == "block"


def test_pattern_is_the_configurations_values_are_the_runs():
    config, traffic, _, _ = _cell(**SMALL)
    s1 = spgemm_mesh.prepare(config, traffic, tiny.SEED)
    s2 = spgemm_mesh.prepare(config, traffic, tiny.SEED + 1)
    assert np.array_equal(s1.indices, s2.indices)
    assert not np.array_equal(s1.ring[0], s2.ring[0])
    other = meshes.pattern_of(dict(config, pattern_seed=1))[1]
    assert not np.array_equal(s1.indices, other)


@pytest.mark.parametrize("rows,nnz", [(1000, 21651), (1000, 21000)])
def test_small_mesh_exact_with_its_diagonal(rows, nnz):
    config = _cell(rows=rows, nnz=nnz)[0]
    indptr, indices = meshes.pattern_of(config)
    assert indices.shape[0] == nnz
    a = _matrix(rows, indptr, indices)
    assert (a != a.T).nnz == 0
    # whole when nnz − rows is even, else one node short
    assert a.diagonal().astype(bool).sum() == rows - (nnz - rows) % 2


def test_generator_refuses_more_entries_than_the_couplings_give():
    config = _cell(rows=200, nnz=199 * 200 - 1, max_neighbours=4)[0]
    with pytest.raises(ValueError, match="node pairs"):
        meshes.pattern_of(config)


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
    for name in HOST_METRICS:
        value = line["metrics"][name]["value"]
        assert value >= 0 if name == "gather_pad_pct" else value > 0, name


def test_metrics_declared_for_the_cell():
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in HOST_METRICS[:-1] + DEVICE_METRICS:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "spgemm_s"
    assert CELL in per_layer["inspect_s"]["workloads"]
    spgemm_s = next(m for m in BENCH["end_to_end"] if m["name"] == "spgemm_s")
    assert CELL in spgemm_s["workloads"]


def test_control_fails():
    config, traffic, _, _ = _cell(**SMALL)
    limits = traffic["limits"]
    for seed in (tiny.SEED, tiny.SEED + 1, tiny.SEED + 2):
        worst = control_readings(config, traffic, seed, 2)
        assert any(worst[k] > limits[k] for k in limits), worst


def test_reference_passes():
    config, traffic, _, _ = _cell(**SMALL)
    state = spgemm_mesh.prepare(config, traffic, tiny.SEED)
    for i in range(3):
        a = sp.csr_matrix((state.ring[i].astype(np.float64), state.indices,
                           state.indptr), shape=(state.n, state.n))
        c = (a @ a).tocsr()
        kept = (c.indptr, c.indices, c.data.astype(np.float32))
        numbers = spgemm_mesh.check(state, i, kept)
        assert all(numbers[k] <= v for k, v in traffic["limits"].items())


# -- the gather readers where the records cannot be read ---------------------

def _ctx(n_ops):
    return SimpleNamespace(n_ops=n_ops, trace=None, counters={}, setup={})


@pytest.mark.parametrize("name", GATHER_READERS)
def test_reader_is_none_with_too_few_records(name):
    from repro.runtime import spans
    read = harness.load_metric(name)
    assert read(_ctx(spans.RING_SIZE + 1)) is None
    spans.clear()
    assert read(_ctx(1)) is None


@pytest.mark.parametrize("name", GATHER_READERS)
def test_reader_is_none_for_another_op(name):
    from repro.runtime import spans
    spans.clear()
    with spans.record("reap.run", op="spgemm_block"):
        with spans.span("reap.h2d"), spans.span("reap.fetch"):
            spans.count("h2d_bytes", 10)
    assert harness.load_metric(name)(_ctx(1)) is None


@pytest.mark.parametrize("name", GATHER_READERS)
def test_reader_is_none_where_the_gather_path_keeps_no_such_record(name):
    """As on a program whose gather path has none of these spans or
    counters: its records name the op and hold only the pipeline's."""
    from repro.runtime import spans
    spans.clear()
    with spans.record("reap.run", op="spgemm_gather"):
        with spans.span("reap.pipeline"), spans.span("reap.execute"):
            pass
    assert harness.load_metric(name)(_ctx(1)) is None


@pytest.mark.parametrize("name", GATHER_READERS)
def test_reader_is_none_without_the_span_module(name, monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    monkeypatch.delattr("repro.runtime.spans", raising=False)
    assert harness.load_metric(name)(_ctx(1)) is None
