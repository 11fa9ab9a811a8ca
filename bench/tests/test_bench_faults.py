"""A run whose timed path is broken underneath comes out not correct.

Each cell is run shrunk on the CPU past the harness's look for a chip, with
one fault planted in the program where the answer is produced."""
from __future__ import annotations

import numpy as np
import pytest

from bench.tests import tiny


def _alter_csr(monkeypatch):
    import repro.runtime.pipeline as pl
    real = pl.block_result_to_csr

    def altered(plan, c_blocks, n_rows, n_cols):
        c = real(plan, c_blocks, n_rows, n_cols)
        c.data[c.data.shape[0] // 2] *= 1.5
        return c
    monkeypatch.setattr(pl, "block_result_to_csr", altered)


def _half_tiles(monkeypatch):
    import repro.runtime.pipeline as pl
    real = pl.block_result_to_csr

    def half(plan, c_blocks, n_rows, n_cols):
        c_blocks = np.array(c_blocks)
        c_blocks[: c_blocks.shape[0] // 2 + 1] = 0
        return real(plan, c_blocks, n_rows, n_cols)
    monkeypatch.setattr(pl, "block_result_to_csr", half)


def _alter_factor(monkeypatch):
    import repro.runtime.pipeline as pl
    real = pl.cholesky_execute_overlapped

    def altered(*args, **kw):
        vals, stats = real(*args, **kw)
        vals = np.array(vals)
        vals[vals.shape[0] // 2] *= 1.001
        return vals, stats
    monkeypatch.setattr(pl, "cholesky_execute_overlapped", altered)


def _level_unchanged(monkeypatch):
    import repro.runtime.pipeline as pl
    monkeypatch.setattr(pl, "_level_step", lambda vals, *bundle: vals)


def _alter_x(monkeypatch):
    import repro.core.solver as solver
    real = solver.cg_solve

    def altered(*args, **kw):
        x, info = real(*args, **kw)
        x = x.copy()
        x[0] += 1e-3 * np.abs(x).max()
        return x, info
    monkeypatch.setattr(solver, "cg_solve", altered)


def _matvec_unchanged(monkeypatch):
    import repro.core.solver as solver
    monkeypatch.setattr(solver, "spmv_execute",
                        lambda plan, a_data, x, **kw: np.asarray(x))


FAULTS = [("cant.spgemm", _alter_csr), ("cant.spgemm", _half_tiles),
          ("bcsstk17.cholesky", _alter_factor),
          ("bcsstk17.cholesky", _level_unchanged),
          ("bcsstk17.cg", _alter_x), ("bcsstk17.cg", _matvec_unchanged)]


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__.strip('_')}"
                              for w, f in FAULTS])
def test_fault_is_not_correct(workload, fault, monkeypatch, tmp_path):
    fault(monkeypatch)
    line = tiny.run(workload, tmp_path)
    assert line["correct"] is False
    assert line["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
