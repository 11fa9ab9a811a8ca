"""Fig 6: SpGEMM speedup of REAP designs vs Intel MKL single-core.

Protocol (paper §V): C = A², 20 matrices (S1–S20).  Three result sets:
  * simulated — the paper's own methodology: analytic REAP-32/64/128 and
    CPU-1/16 models over the true workload statistics of each matrix.
  * measured  — our actual CPU library stand-in (vectorized numpy
    Gustavson) vs the REAP inspector+executor (jit), on this container.
    This is the paper's cold-split protocol: every call pays inspection.
  * warm      — the same REAP split through ``runtime.ReapRuntime``'s plan
    cache (same pattern, fresh values): the steady state of a repeated-
    pattern workload, where the inspector cost is amortized away.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from repro.core import CSR, spgemm, spgemm_ref_numpy
from repro.core.simulator import (REAP_32, REAP_64, REAP_128,
                                  simulate_spgemm_cpu, simulate_spgemm_reap,
                                  spgemm_workload)
from repro.runtime import ReapRuntime

from .op_coverage import per_op_warm_rows
from .table1 import SPGEMM_SET, make_spgemm_matrix


def _revalue(a: CSR, rng: np.random.Generator) -> CSR:
    """Same pattern, fresh values — one step of a repeated-pattern workload."""
    return CSR(a.n_rows, a.n_cols, a.indptr, a.indices,
               rng.standard_normal(a.nnz).astype(a.data.dtype))


def run(verbose: bool = True) -> List[dict]:
    rows = []
    geo = {"REAP-32": [], "REAP-64": [], "REAP-128": [], "CPU-16": [],
           "measured": [], "warm": []}
    rng = np.random.default_rng(0)
    rt = ReapRuntime(n_chunks=1)
    for spec in SPGEMM_SET:
        a, scale = make_spgemm_matrix(spec)
        stats = spgemm_workload(a, a)
        stats["density"] = spec.density          # original operating point
        cpu1 = simulate_spgemm_cpu(stats, threads=1)
        cpu16 = simulate_spgemm_cpu(stats, threads=16)
        sims = {hw.name: simulate_spgemm_reap(stats, hw)
                for hw in (REAP_32, REAP_64, REAP_128)}

        # measured on this container: numpy library baseline vs REAP split
        t0 = time.perf_counter()
        spgemm_ref_numpy(a, a)
        t_lib = time.perf_counter() - t0
        c, st = spgemm(a, a, method="gather")
        t_reap = st["inspect_s"] + st["execute_s"]

        # warm-cache column: populate the plan cache, then time a same-
        # pattern-fresh-values call through the runtime (steady state)
        rt.spgemm(a, a, method="gather")
        a2 = _revalue(a, rng)
        t0 = time.perf_counter()
        _, st_warm = rt.spgemm(a2, a2, method="gather")
        t_warm = time.perf_counter() - t0
        assert st_warm["cache_hit"], "same pattern must hit the plan cache"

        row = dict(id=spec.spgemm_id, name=spec.name, scale=scale,
                   pp=stats["pp"], density=spec.density,
                   cpu1_s=cpu1, cpu16_s=cpu16,
                   speedup_reap32=cpu1 / sims["REAP-32"]["total_s"],
                   speedup_reap64=cpu1 / sims["REAP-64"]["total_s"],
                   speedup_reap128=cpu1 / sims["REAP-128"]["total_s"],
                   speedup_cpu16=cpu1 / cpu16,
                   measured_lib_s=t_lib, measured_reap_s=t_reap,
                   measured_speedup=t_lib / t_reap,
                   measured_warm_s=t_warm,
                   warm_speedup=t_lib / max(t_warm, 1e-9),
                   reap32_bound=sims["REAP-32"]["bound"])
        rows.append(row)
        geo["REAP-32"].append(row["speedup_reap32"])
        geo["REAP-64"].append(row["speedup_reap64"])
        geo["REAP-128"].append(row["speedup_reap128"])
        geo["CPU-16"].append(row["speedup_cpu16"])
        geo["measured"].append(row["measured_speedup"])
        geo["warm"].append(row["warm_speedup"])
        if verbose:
            print(f"fig6,{spec.spgemm_id},{spec.name},"
                  f"{row['speedup_reap32']:.2f},{row['speedup_reap64']:.2f},"
                  f"{row['speedup_reap128']:.2f},{row['measured_speedup']:.2f},"
                  f"warm={row['warm_speedup']:.2f}",
                  flush=True)
    gm = {k: float(np.exp(np.mean(np.log(np.maximum(v, 1e-9)))))
          for k, v in geo.items()}
    if verbose:
        print(f"fig6_geomean,REAP-32,{gm['REAP-32']:.2f},(paper: 3.2)")
        print(f"fig6_geomean,REAP-64,{gm['REAP-64']:.2f}")
        print(f"fig6_geomean,REAP-128,{gm['REAP-128']:.2f}")
        print(f"fig6_geomean,measured_reap_vs_numpy,{gm['measured']:.2f}")
        print(f"fig6_geomean,warm_cache_vs_numpy,{gm['warm']:.2f}")
    # registry-driven coda: the same cold-vs-warm amortization, but for
    # EVERY registered op (list_ops()), so a newly admitted op appears in
    # the fig6 output with no edits here
    per_op = per_op_warm_rows(n=384, verbose=verbose, prefix="fig6")
    return rows + [dict(id="GEOMEAN", **{f"speedup_{k}": v
                                         for k, v in gm.items()})] + per_op


if __name__ == "__main__":
    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()
    run()
