"""C = A·A through ``ReapRuntime.run("spgemm", a, a)``, fresh values on one
pattern each product; the comparison covers C as the user receives it."""
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
    ring = [generators.normal_values(rng, indices.shape[0], dtype)
            for _ in range(int(traffic["value_ring"]))]
    return State(n=int(config["rows"]), indptr=indptr, indices=indices,
                 ring=ring, kwargs=dict(traffic.get("kwargs", {})),
                 rt=RecordingRuntime(**traffic.get("runtime", {})))


def operands(state, i):
    a = CSR(state.n, state.n, state.indptr, state.indices,
            state.ring[i % len(state.ring)])
    return (a, a)


def call(state, ops):
    state.rt.log.clear()
    c, stats = state.rt.run("spgemm", *ops, **state.kwargs)
    return c, {"inspect_s": stats.extra.get("plan_s", 0.0),
               "emit_s": stats.extra.get("inspect_s", 0.0)}


def keep(c):
    return (c.indptr, c.indices, c.data)


def _matrix(state, i):
    return reference.csr(state.n, state.indptr, state.indices,
                         state.ring[i % len(state.ring)])


def _pattern(state):
    if "pattern" not in state.cache:
        state.cache["pattern"] = reference.spgemm_pattern(_matrix(state, 0))
    return state.cache["pattern"]


def check(state, i, kept):
    ref, scale = reference.spgemm_ref(_matrix(state, i))
    return reference.spgemm_errors(*kept, state.n, _pattern(state), ref,
                                   scale)


def control(state, i):
    c = reference.spgemm_control(
        _matrix(state, i).astype(np.float32).astype(np.float64))
    return (c.indptr, c.indices, c.data)


def work(state):
    """Flops: two per scalar partial product of A·A.  Bytes: values and
    indices of A, B and C, and their row pointers."""
    row_nnz = np.diff(state.indptr)
    col_nnz = np.bincount(state.indices, minlength=state.n)
    products = int(np.sum(col_nnz.astype(np.int64) * row_nnz))
    nnz_a = int(state.indices.shape[0])
    nnz_c = int(_pattern(state).nnz)
    entries = 2 * nnz_a + nnz_c
    return {"flops": 2.0 * products,
            "bytes": float(entries * (VALUE_BYTES + INDEX_BYTES)
                           + 3 * (state.n + 1) * INDEX_BYTES)}
