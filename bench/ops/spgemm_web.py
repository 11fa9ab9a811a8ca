"""C = A·A on a crawled web graph's pattern (``bench/webgraph.py``), through
``ReapRuntime.run("spgemm", a, a)``: the ``spgemm`` driver with another
pattern source.  Fresh values on one pattern each product; the comparison
covers C as the user receives it."""
from __future__ import annotations

import numpy as np

from bench import generators, webgraph
from bench.ops import RecordingRuntime, State
from bench.ops.spgemm import (call, check, control, keep,  # noqa: F401
                              operands, work)


def prepare(config, traffic, seed):
    indptr, indices = webgraph.pattern_of(config)
    rng = generators.value_rng(seed)
    dtype = np.dtype(config["value_dtype"])
    ring = [generators.normal_values(rng, indices.shape[0], dtype)
            for _ in range(int(traffic["value_ring"]))]
    return State(n=int(config["rows"]), indptr=indptr, indices=indices,
                 ring=ring, kwargs=dict(traffic.get("kwargs", {})),
                 rt=RecordingRuntime(**traffic.get("runtime", {})))
