"""The control, the plain reference one precision step below the
configuration's, fails each cell's comparison, and the reference at the
stated precision passes it.  Shrunk sizes, on the CPU; ``bench/control.py``
reads the same at the cells' own sizes."""
from __future__ import annotations

import numpy as np
import pytest

from bench import harness, reference
from bench.control import control_readings
from bench.tests import tiny


@pytest.mark.parametrize("workload", tiny.MIXES)
def test_control_fails(workload):
    config, traffic, _, _ = tiny.cell(workload)
    limits = traffic["limits"]
    for seed in (tiny.SEED, tiny.SEED + 1, tiny.SEED + 2):
        worst = control_readings(config, traffic, seed, 2)
        assert any(worst[k] > limits[k] for k in limits), worst


def _exact(workload, state, i):
    """The reference at the configuration's precision, in the program's
    place."""
    if workload == "cant.spgemm":
        a = reference.csr(state.n, state.indptr, state.indices,
                          state.ring[i % len(state.ring)])
        c = (a @ a).tocsr()
        return c.indptr, c.indices, c.data.astype(np.float32)
    if workload == "bcsstk17.cholesky":
        vals = state.ring[i % len(state.ring)]
        a = reference.csr(state.n, state.indptr, state.indices, vals)
        return reference.band_to_csc(reference.cholesky_ref(a))
    vals, b = state.ring[i % len(state.ring)]
    a = reference.csr(state.n, state.indptr, state.indices, vals)
    x, k, relres = reference.cg_plain(lambda p: a @ p, b,
                                      state.kwargs["tol"], 10 * state.n)
    return x, k, relres, True


@pytest.mark.parametrize("workload", tiny.MIXES)
def test_reference_passes(workload):
    config, traffic, _, _ = tiny.cell(workload)
    op = harness.load_op(traffic["op"])
    state = op.prepare(config, traffic, tiny.SEED)
    for i in range(3):
        numbers = op.check(state, i, _exact(workload, state, i))
        assert all(numbers[k] <= v for k, v in traffic["limits"].items()), \
            numbers
