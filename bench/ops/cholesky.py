"""A = L·Lᵀ through ``ReapRuntime.run("cholesky", a)``, fresh SPD values on
one pattern each factor; the comparison covers the factor values returned,
located by the pattern returned with them."""
from __future__ import annotations


import numpy as np

from bench import generators, reference
from bench.ops import RecordingRuntime, State
from repro.core import CSR


def prepare(config, traffic, seed):
    indptr, indices = generators.pattern_of(config)
    rng = generators.value_rng(seed)
    dtype = np.dtype(config["value_dtype"])
    ring = [generators.spd_values(rng, indptr, indices, dtype)
            for _ in range(int(traffic["value_ring"]))]
    return State(n=int(config["rows"]), indptr=indptr, indices=indices,
                 ring=ring, kwargs=dict(traffic.get("kwargs", {})),
                 rt=RecordingRuntime(**traffic.get("runtime", {})))


def operands(state, i):
    return (CSR(state.n, state.n, state.indptr, state.indices,
                state.ring[i % len(state.ring)]),)


def call(state, ops):
    state.rt.log.clear()
    (plan, vals), stats = state.rt.run("cholesky", *ops, **state.kwargs)
    return (plan, vals), {"inspect_s": stats.inspect_s or 0.0}


def keep(out):
    plan, vals = out
    return (plan.col_ptr, plan.row_idx, vals)


def _l_ref(state, i, dtype=np.float64):
    key = ("l", i % len(state.ring), np.dtype(dtype).name)
    if key not in state.cache:
        a = reference.csr(state.n, state.indptr, state.indices,
                          state.ring[i % len(state.ring)])
        state.cache[key] = reference.cholesky_ref(a, dtype)
    return state.cache[key]


def check(state, i, kept):
    return {"max_rel_err": reference.cholesky_error(*kept, _l_ref(state, i))}


def control(state, i):
    return reference.band_to_csc(_l_ref(state, i, np.float32))


def work(state):
    return None
