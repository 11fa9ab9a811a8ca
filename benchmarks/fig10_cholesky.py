"""Fig 10: sparse Cholesky speedup of REAP vs CHOLMOD (simplicial LL^T,
numeric phase only — paper protocol; etree construction excluded).

Also reproduces the §V-B finding that idle cycles grow with pipeline count
(dependency-limited parallelism)."""
from __future__ import annotations

from typing import List

import numpy as np

from repro.core import cholesky_baseline_numpy, cholesky_values, inspect_cholesky
from repro.core.simulator import (REAP_32C, REAP_64C,
                                  simulate_cholesky_cpu,
                                  simulate_cholesky_reap)
from repro.runtime import cholesky_execute_overlapped

from .op_coverage import per_op_warm_rows
from .table1 import CHOLESKY_SET, make_chol_matrix


def run(verbose: bool = True) -> List[dict]:
    rows = []
    geo32, geo64, geom = [], [], []
    for spec in CHOLESKY_SET:
        a, scale = make_chol_matrix(spec)
        plan = inspect_cholesky(a)
        cpu_s = simulate_cholesky_cpu(plan)
        r32 = simulate_cholesky_reap(plan, REAP_32C)
        r64 = simulate_cholesky_reap(plan, REAP_64C)

        # measured: numpy numeric baseline vs jitted level executor
        a_vals = cholesky_values(a)
        base_vals, t_base = cholesky_baseline_numpy(plan, a_vals)
        _, st = cholesky_execute_overlapped(plan, a_vals)
        t_reap = st["execute_s"]

        row = dict(id=spec.chol_id, name=spec.name, scale=scale,
                   n_levels=plan.n_levels, nnz_l=plan.nnz,
                   flops=plan.flops(),
                   speedup_reap32=cpu_s / r32["fpga_s"],
                   speedup_reap64=cpu_s / r64["fpga_s"],
                   idle32=r32["idle_frac"], idle64=r64["idle_frac"],
                   measured_base_s=t_base, measured_reap_s=t_reap,
                   measured_speedup=t_base / max(t_reap, 1e-9))
        rows.append(row)
        geo32.append(row["speedup_reap32"])
        geo64.append(row["speedup_reap64"])
        geom.append(row["measured_speedup"])
        if verbose:
            print(f"fig10,{spec.chol_id},{spec.name},"
                  f"{row['speedup_reap32']:.2f},{row['speedup_reap64']:.2f},"
                  f"idle32={row['idle32']:.2f},idle64={row['idle64']:.2f}",
                  flush=True)
    gm32 = float(np.exp(np.mean(np.log(geo32))))
    gm64 = float(np.exp(np.mean(np.log(geo64))))
    if verbose:
        print(f"fig10_geomean,REAP-32,{gm32:.2f},(paper: 1.18)")
        print(f"fig10_geomean,REAP-64,{gm64:.2f},(paper: 1.85)")
        # paper §V-B: idle grows ~linearly with pipelines
        mean_idle32 = float(np.mean([r['idle32'] for r in rows]))
        mean_idle64 = float(np.mean([r['idle64'] for r in rows]))
        print(f"fig10_idle,mean_idle_32p,{mean_idle32:.2f},"
              f"mean_idle_64p,{mean_idle64:.2f}")
    # registry-driven coda: warm-plan amortization for every registered
    # op (list_ops()) — new ops appear here with no edits to this script
    rows += per_op_warm_rows(n=384, verbose=verbose, prefix="fig10")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()
    run()
