"""Planned CG solves through ``repro.core.solver.cg_solve``, fresh SPD
values and right-hand side each solve; the comparison covers x as
returned."""
from __future__ import annotations

import numpy as np

from bench import generators, reference
from bench.ops import RecordingRuntime, State
from repro.core import CSR

VALUE_BYTES = INDEX_BYTES = 4


def prepare(config, traffic, seed):
    indptr, indices = generators.pattern_of(config)
    rng = generators.value_rng(seed)
    dtype = np.dtype(config["value_dtype"])
    n = int(config["rows"])
    ring = [(generators.spd_values(rng, indptr, indices, dtype),
             rng.standard_normal(n)) for _ in range(int(traffic["value_ring"]))]
    kw = dict(traffic["kwargs"])
    kw["dtype"] = np.dtype(kw["dtype"])
    return State(n=n, indptr=indptr, indices=indices, ring=ring, kwargs=kw,
                 rt=RecordingRuntime(**traffic.get("runtime", {})))


def operands(state, i):
    vals, b = state.ring[i % len(state.ring)]
    return (CSR(state.n, state.n, state.indptr, state.indices, vals), b)


def call(state, ops):
    from repro.core import solver
    state.rt.log.clear()
    x, info = solver.cg_solve(*ops, state.rt, **state.kwargs)
    inspect_s = sum(s.inspect_s or 0.0 for _, s in state.rt.log
                    if not s.cache_hit)
    return (x, info), {"inspect_s": inspect_s,
                       "iterations": info["iterations"]}


def keep(out):
    x, info = out
    return (x, info["iterations"], info["relres"], info["converged"])


def _system(state, i):
    vals, b = state.ring[i % len(state.ring)]
    return reference.csr(state.n, state.indptr, state.indices, vals), b


def check(state, i, kept):
    a, b = _system(state, i)
    return reference.cg_errors(a, b, *kept, tol=state.kwargs["tol"])


def control(state, i):
    a, b = _system(state, i)
    x, k, relres = reference.cg_control(a, b, state.kwargs["tol"],
                                        10 * state.n)
    return (x, k, relres, True)


def work(state):
    """Per matvec: two flops per nonzero; bytes of A's values, indices and
    row pointers, and of the two vectors."""
    nnz = int(state.indices.shape[0])
    return {"flops": 2.0 * nnz,
            "bytes": float(nnz * (VALUE_BYTES + INDEX_BYTES)
                           + (state.n + 1) * INDEX_BYTES
                           + 2 * state.n * VALUE_BYTES)}
