"""Fig 9: sensitivity of REAP speedup to matrix density.

Paper finding: REAP favors sparse matrices; the CPU wins only on the
densest inputs (the dashed cross-over line).  Swept on synthetic uniform
matrices, density 1e-5 → 0.2."""
from __future__ import annotations

from typing import List

import numpy as np

from repro.core import random_csr
from repro.core.simulator import (REAP_32, REAP_64, simulate_spgemm_cpu,
                                  simulate_spgemm_reap, spgemm_workload)


def run(verbose: bool = True, n: int = 4096) -> List[dict]:
    rows = []
    for density in (1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1,
                    2e-1):
        # match the paper's matrices: ≥4 nnz/row at every density (Table I
        # spans 4-100 nnz/row) — low densities therefore need larger n —
        # while capping pp ≈ density²·n³ for container memory
        n_eff = max(256, min(int(4 / density), 262_144,
                             int((2.5e7 / density ** 2) ** (1 / 3))))
        rng = np.random.default_rng(int(1 / density))
        a = random_csr(n_eff, n_eff, density, rng, "uniform")
        stats = spgemm_workload(a, a)
        stats["density"] = density
        cpu1 = simulate_spgemm_cpu(stats, threads=1)
        s32 = cpu1 / simulate_spgemm_reap(stats, REAP_32)["total_s"]
        s64 = cpu1 / simulate_spgemm_reap(stats, REAP_64)["total_s"]
        rows.append(dict(density=density, speedup_reap32=s32,
                         speedup_reap64=s64))
        if verbose:
            print(f"fig9,density={density:.0e},reap32={s32:.2f},"
                  f"reap64={s64:.2f}", flush=True)
    if verbose:
        s = rows
        sparse_wins = all(r["speedup_reap32"] > 1 for r in s
                          if r["density"] <= 1e-3)
        lo = np.mean([r["speedup_reap32"] for r in s if r["density"] <= 1e-4])
        hi = np.mean([r["speedup_reap32"] for r in s if r["density"] >= 1e-1])
        print(f"fig9_finding,reap_wins_below_1e-3_density,{sparse_wins},"
              f"speedup_falls_with_density,{hi < 0.6 * lo}")
        print("fig9_paper_claim,speedup_whenever_density_under_1:1000,"
              f"{sparse_wins}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()
    run()
