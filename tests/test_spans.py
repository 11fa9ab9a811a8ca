"""Run records of ``runtime.spans``: nesting, self time, worker threads,
the ring, counters; and the records ``ReapRuntime.run`` returns as
``RunStats.spans``/``counters``, from which its timing keys derive."""
from __future__ import annotations

import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import random_csr, random_spd_csr
from repro.core.inspector import next_pow2
from repro.core.solver import cg_solve
from repro.runtime import ReapRuntime, spans


class FakeClock:
    """A clock that moves only when told to."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(spans, "_clock", c)
    spans.clear()
    return c


def test_nesting_and_per_name_totals(clock):
    with spans.record("reap.run", op="x") as rec:
        clock.tick(1.0)
        with spans.span("a"):
            clock.tick(2.0)
            for _ in range(2):
                with spans.span("b"):
                    clock.tick(0.5)
        with spans.span("b"):
            clock.tick(0.25)
    assert rec.op == "x"
    assert rec.seconds == {"a": 3.0, "b": 1.25, "reap.run": 4.25}
    assert rec.calls == {"a": 1, "b": 3, "reap.run": 1}
    assert spans.recent(1) == [rec]


def test_self_time_is_parent_minus_children(clock):
    with spans.record("reap.run") as rec:
        clock.tick(1.0)
        with spans.span("a"):
            clock.tick(2.0)
            with spans.span("b"):
                clock.tick(0.5)
        with spans.span("c"):
            clock.tick(0.25)
    assert rec.self_seconds("reap.run") == pytest.approx(1.0)
    assert rec.self_seconds("a") == pytest.approx(2.0)
    assert rec.self_seconds("b") == pytest.approx(0.5)
    assert rec.self_seconds("missing") == 0.0


def test_span_handle_carries_its_duration(clock):
    with spans.span("alone") as s:
        clock.tick(0.75)
    assert s.seconds == 0.75
    assert spans.recent(5) == []        # outside any record: not kept


def test_worker_thread_spans_count_into_the_submitting_record(clock):
    with spans.record("reap.run") as rec:
        handed = spans.current()

        def work():
            with spans.bind(handed), spans.span("reap.emit"):
                spans.count("rows", 3)

        t = threading.Thread(target=work)
        t.start()
        t.join()
        with spans.span("reap.emit_wait"):
            clock.tick(1.0)
    assert rec.calls["reap.emit"] == 1 and rec.counters == {"rows": 3}
    # the worker's span is no child of the submitting thread's root
    assert rec.self_seconds("reap.run") == pytest.approx(0.0)
    assert rec.child_seconds["reap.run"] == pytest.approx(1.0)


def test_unbound_worker_spans_are_dropped(clock):
    with spans.record("reap.run") as rec:
        def work():
            with spans.span("orphan"):
                spans.count("rows", 1)

        t = threading.Thread(target=work)
        t.start()
        t.join()
    assert "orphan" not in rec.seconds and rec.counters == {}


def test_nested_record_adds_to_the_outer_one(clock):
    with spans.record("reap.solve", op="cg") as outer:
        for _ in range(3):
            with spans.record("reap.run", op="spmv") as inner:
                with spans.span("reap.acquire"):
                    clock.tick(0.5)
                spans.count("h2d_bytes", 8)
            assert inner.seconds == {"reap.acquire": 0.5, "reap.run": 0.5}
        with spans.span("reap.cg_host"):
            clock.tick(0.25)
    assert spans.recent(10) == [outer]
    assert outer.op == "cg"
    assert outer.calls["reap.run"] == 3
    assert outer.seconds["reap.acquire"] == pytest.approx(1.5)
    assert outer.counters == {"h2d_bytes": 24}
    assert outer.self_seconds("reap.solve") == pytest.approx(0.0)


def test_ring_keeps_the_newest_records(clock):
    for i in range(spans.RING_SIZE + 10):
        with spans.record("reap.run", op=str(i)):
            pass
    kept = spans.recent(10 ** 6)
    assert len(kept) == spans.RING_SIZE
    assert [r.op for r in spans.recent(2)] == [
        str(spans.RING_SIZE + 8), str(spans.RING_SIZE + 9)]
    assert spans.recent(0) == []


def test_counters_add_and_need_a_record(clock):
    spans.count("lost", 5)                      # no record open
    with spans.record("reap.run") as rec:
        spans.count("launches")
        spans.count("launches", 2)
        spans.count("h2d_bytes", 1024)
    assert rec.counters == {"launches": 3, "h2d_bytes": 1024}


def test_to_host_counts_bytes_under_fetch(clock):
    import jax.numpy as jnp
    with spans.record("reap.run") as rec:
        out = spans.to_host(jnp.arange(6, dtype=jnp.float32))
    assert isinstance(out, np.ndarray) and out.shape == (6,)
    assert rec.counters == {"d2h_bytes": 24} and rec.calls["reap.fetch"] == 1


# -- the records ReapRuntime.run returns ---------------------------------

def _pair(seed, n=96, density=0.08):
    a = random_csr(n, n, density, np.random.default_rng(seed))
    return a, a


@pytest.mark.parametrize("method", ["gather", "block"])
@pytest.mark.parametrize("n_chunks", [1, 3])
def test_runstats_carry_the_call_record_for_spgemm(method, n_chunks):
    rt = ReapRuntime(n_chunks=n_chunks, block=32, tile=64)
    a, b = _pair(3)
    spans.clear()
    _, st = rt.run("spgemm", a, b, method=method)
    rec = spans.recent(1)[0]
    assert rec.op == f"spgemm_{method}"
    assert st.spans == rec.seconds and st.counters == rec.counters
    assert st["spans"] is st.spans
    assert st.spans["reap.run"] > 0 and st.spans["reap.acquire"] > 0
    assert set(st.spans) <= set(rec.calls)
    # the children of reap.run cover it but for a little bookkeeping
    assert 0 <= rec.self_seconds("reap.run") < st.spans["reap.run"]


def test_runstats_carry_the_call_record_for_cholesky():
    rt = ReapRuntime()
    a = random_spd_csr(80, 0.08, np.random.default_rng(5))
    (plan, _), st = rt.run("cholesky", a)
    rec = spans.recent(1)[0]
    assert rec.op == "cholesky"
    assert st.spans == rec.seconds and st.counters == rec.counters
    c = st.counters
    assert c["launches"] == plan.n_levels
    # the values, and once the plan's six packed bundle arrays
    bundles = plan.device_bundles(put=None)
    assert c["h2d_puts"] == 1 + 6 and c["cholesky_bundle_builds"] == 1
    assert c["h2d_bytes"] == plan.nnz * 8 + 8 + sum(
        x.nbytes for x in bundles[:6])
    assert c["d2h_bytes"] == plan.nnz * 8
    assert rec.calls["reap.dispatch"] == 1 and "reap.emit" not in rec.calls
    assert rec.calls["reap.values"] == 2 and rec.calls["reap.drain"] == 1
    assert rec.calls["reap.h2d"] == 1


def test_block_chunked_keys_derive_from_spans():
    rt = ReapRuntime(n_chunks=3, block=32)
    a, b = _pair(7)
    _, cold = rt.run("spgemm", a, b, method="block")
    _, warm = rt.run("spgemm", a, b, method="block")
    for st in (cold, warm):
        sp = st.spans
        assert st["method"] == "block_chunked" and st["n_chunks"] > 1
        assert st["inspect_s"] == pytest.approx(sp["reap.emit"], rel=1e-12)
        assert st["execute_s"] == pytest.approx(sp["reap.execute"],
                                                rel=1e-12)
        assert st["wall_s"] == pytest.approx(sp["reap.pipeline"], rel=1e-12)
        assert st["hidden_s"] == pytest.approx(
            max(0.0, sp["reap.emit"] + sp["reap.execute"]
                - sp["reap.pipeline"]), abs=1e-12)
        assert st.counters["h2d_bytes"] > 0 and st.counters["d2h_bytes"] > 0
    assert cold["plan_s"] == pytest.approx(cold.spans["reap.inspect"],
                                           rel=1e-12)
    assert warm["plan_s"] == 0.0 and "reap.inspect" not in warm.spans


def test_block_chunked_product_counts_extraction_zeros():
    rt = ReapRuntime(n_chunks=3, block=32)
    a, b = _pair(11)
    rt.run("spgemm", a, b, method="block")       # plan miss: index built
    spans.clear()
    c, st = rt.run("spgemm", a, b, method="block")
    rec = spans.recent(1)[0]
    assert st["method"] == "block_chunked"
    # random values: no exact zero inside A·B's structural pattern
    assert rec.counters["extract_zeros_dropped"] == 0
    assert rec.calls["reap.extract"] == 1 and c.nnz > 0

def test_cholesky_keys_derive_from_spans():
    rt = ReapRuntime()
    a = random_spd_csr(80, 0.08, np.random.default_rng(6))
    _, st = rt.run("cholesky", a)
    sp = st.spans
    assert st["execute_s"] == pytest.approx(
        sp["reap.dispatch"] + sp["reap.drain"], rel=1e-12)
    assert st.inspect_s == pytest.approx(sp["reap.inspect"], rel=1e-12)
    _, warm = rt.run("cholesky", a)
    assert warm["execute_s"] == pytest.approx(
        warm.spans["reap.dispatch"] + warm.spans["reap.drain"], rel=1e-12)
    assert warm.inspect_s == 0.0 and "reap.inspect" not in warm.spans


def test_gather_chunked_keys_derive_from_spans():
    rt = ReapRuntime(n_chunks=3, tile=64)
    a, b = _pair(8)
    _, cold = rt.run("spgemm", a, b, method="gather")
    _, warm = rt.run("spgemm", a, b, method="gather")
    for st in (cold, warm):
        assert st["method"] == "gather_chunked" and st["n_chunks"] == 3
        assert st["inspect_s"] == pytest.approx(st.spans["reap.emit"],
                                                rel=1e-12)
        assert st["execute_s"] == pytest.approx(st.spans["reap.execute"],
                                                rel=1e-12)
    # each chunk plan of the miss is built under its own reap.inspect
    assert cold["plan_s"] > 0
    assert cold["plan_s"] == pytest.approx(cold.spans["reap.inspect"],
                                           rel=1e-12)
    assert warm["plan_s"] == 0.0 and "reap.inspect" not in warm.spans


def test_gather_chunked_record_times_and_counts_each_stage():
    from repro.runtime.pipeline import spgemm_gather_chunked
    rt = ReapRuntime(n_chunks=3, tile=64)
    a, b = _pair(12)
    spans.clear()
    rt.run("spgemm", a, b, method="gather")                 # plan miss
    rt.run("spgemm", a, b, method="gather")
    miss, rec = spans.recent(2)
    assert rec.op == miss.op == "spgemm_gather"
    for name in ("reap.values", "reap.h2d", "reap.launch", "reap.fetch"):
        assert rec.calls[name] == 3, name
    assert rec.calls["reap.extract"] == 1
    # the miss uploads each chunk's padded indices once, nested in its
    # reap.values; a warm product only looks them up
    assert miss.calls["reap.h2d"] == 6 and miss.calls["reap.values"] == 3
    assert miss.counters["gather_index_builds"] == 3
    assert rec.counters["gather_index_builds"] == 0
    # the same plans, built outside the runtime: what each chunk sends
    _, _, chunkset = spgemm_gather_chunked(a, b, n_chunks=3, tile=64)
    index_bytes = jnp.asarray(np.zeros(1, np.int64)).dtype.itemsize
    values = indices = products = slots = 0
    for k, plan in enumerate(chunkset.plans):
        rows = slice(chunkset.row_bounds[k], chunkset.row_bounds[k + 1] + 1)
        cap = next_pow2(plan.a_idx.shape[0] // plan.tile) * plan.tile
        nnz = int(np.diff(a.indptr[rows][[0, -1]])[0])
        values += (nnz + b.nnz) * a.data.itemsize
        indices += 3 * cap * index_bytes
        products += plan.n_pp
        slots += cap
    c = rec.counters
    assert c["h2d_bytes"] == values
    assert miss.counters["h2d_bytes"] == values + indices
    # the plans' live products are A·B's scalar products
    assert c["gather_products"] == products == int(
        np.diff(b.indptr)[a.indices].sum()) > 0
    assert c["gather_slots"] == slots >= products
    assert miss.counters["gather_slots"] == slots
    assert c["d2h_bytes"] == sum(p.c_nnz for p in chunkset.plans) * 4


def test_sync_spgemm_execute_s_is_its_span():
    # one chunk: execute_s is the sum of the pipeline's reap.execute spans
    rt = ReapRuntime(n_chunks=1, tile=64)
    a, b = _pair(9)
    spans.clear()
    _, st = rt.run("spgemm", a, b, method="gather")
    rec = spans.recent(1)[0]
    assert st["method"] == "gather_chunked" and st["n_chunks"] == 1
    assert rec.calls["reap.execute"] == 1
    assert st["execute_s"] == pytest.approx(st.spans["reap.execute"],
                                            rel=1e-12)


@pytest.mark.parametrize("method", ["gather", "block"])
def test_one_chunk_spgemm_runs_the_pipelined_executor(method):
    rt = ReapRuntime(n_chunks=1, block=32, tile=64)
    a, _ = _pair(13)
    spans.clear()
    rt.run("spgemm", a, a, method=method)                   # plan miss
    rt.run("spgemm", a, a, method=method)
    miss, warm = spans.recent(2)
    for rec in (miss, warm):
        assert rec.op == f"spgemm_{method}"
        for name in ("reap.emit", "reap.h2d", "reap.launch", "reap.fetch",
                     "reap.extract"):
            assert rec.calls.get(name, 0) >= 1, name
        assert rec.calls["reap.emit"] == rec.calls["reap.launch"] == 1
    if method == "gather":
        # the device index memo: built on the miss, looked up when warm
        assert miss.counters["gather_index_builds"] == 1
        assert warm.counters["gather_index_builds"] == 0


def test_cg_solve_is_one_record_with_its_matvecs():
    a = random_spd_csr(64, 0.1, np.random.default_rng(11))
    b = np.random.default_rng(0).standard_normal(64)
    rt = ReapRuntime(n_chunks=1)
    spans.clear()
    _, info = cg_solve(a, b, rt, tol=1e-6, dtype=np.float32)
    (rec,) = spans.recent(5)
    assert rec.op == "cg"
    assert rec.counters["iterations"] == info["iterations"] > 0
    assert rec.calls["reap.run"] == info["iterations"]
    assert rec.calls["reap.cg_host"] == info["iterations"]
    assert rec.counters["h2d_bytes"] > 0
    assert rec.self_seconds("reap.solve") >= 0


def test_moe_dispatch_bundles_under_a_span():
    rt = ReapRuntime()
    rng = np.random.default_rng(2)
    tokens = rng.standard_normal((16, 8)).astype(np.float32)
    ids = rng.integers(0, 4, size=(16, 2))
    _, _, st = rt.moe_dispatch(tokens, ids, n_experts=4)
    assert "bundle_s" not in st
    assert st.spans["reap.bundle"] > 0


def test_concurrent_counts_into_one_record_are_not_lost():
    """Workers bound to one record add to it at once; a lost update would
    leave the sums short."""
    n_workers, n_adds = 16, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with spans.record("reap.run") as rec:
            def work(r=spans.current()):
                with spans.bind(r):
                    for _ in range(n_adds):
                        with spans.span("w"):
                            spans.count("n")

            threads = [threading.Thread(target=work)
                       for _ in range(n_workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert rec.counters["n"] == n_workers * n_adds
    assert rec.calls["w"] == n_workers * n_adds
