"""Fig 8: (left) GFLOPS normalized per floating-point unit, REAP vs CPU;
(right) frequency + logic utilization vs pipeline count.

Right panel constants are the paper's synthesis results (Quartus 16.1,
Arria 10) — they are RTL facts with no TPU analogue (DESIGN.md §2) and are
reproduced as published to keep the figure complete."""
from __future__ import annotations

from typing import List

import numpy as np

from repro.core.simulator import (ReapVariant, simulate_spgemm_cpu,
                                  simulate_spgemm_reap, spgemm_workload)

from .table1 import SPGEMM_SET, make_spgemm_matrix

# paper Fig 8 (right): pipelines → (freq MHz, logic %)
SYNTHESIS = {2: (280, 5), 4: (278, 7), 8: (272, 10), 16: (264, 14),
             32: (250, 19), 64: (239, 26), 128: (220, 40)}


def run(verbose: bool = True) -> List[dict]:
    per_matrix = []
    for spec in SPGEMM_SET:
        a, _ = make_spgemm_matrix(spec)
        stats = spgemm_workload(a, a)
        stats["density"] = spec.density
        per_matrix.append(stats)

    rows = []
    for n_pipe, (freq, logic) in SYNTHESIS.items():
        hw = ReapVariant(f"REAP-{n_pipe}", n_pipe, freq * 1e6, 147e9, 73e9)
        gfl = []
        for stats in per_matrix:
            sim = simulate_spgemm_reap(stats, hw)
            gfl.append(2 * stats["pp"] / sim["fpga_s"] / 1e9 / n_pipe)
        # CPU with matching FPU count (paper: CPU-2 ≈ 32 FPUs w/ AVX)
        cpu_fpus = max(1, n_pipe // 16)
        cpu_g = []
        for stats in per_matrix:
            t = simulate_spgemm_cpu(stats, threads=cpu_fpus)
            cpu_g.append(2 * stats["pp"] / t / 1e9 / (cpu_fpus * 16))
        row = dict(pipelines=n_pipe, freq_mhz=freq, logic_pct=logic,
                   reap_gflops_per_fpu_median=float(np.median(gfl)),
                   reap_gflops_per_fpu_geomean=float(
                       np.exp(np.mean(np.log(np.maximum(gfl, 1e-12))))),
                   reap_p25=float(np.percentile(gfl, 25)),
                   reap_p75=float(np.percentile(gfl, 75)),
                   cpu_gflops_per_fpu_median=float(np.median(cpu_g)))
        rows.append(row)
        if verbose:
            print(f"fig8,{n_pipe},freq={freq}MHz,logic={logic}%,"
                  f"reap_gflops/fpu={row['reap_gflops_per_fpu_median']:.3f},"
                  f"cpu_gflops/fpu={row['cpu_gflops_per_fpu_median']:.3f}",
                  flush=True)
    if verbose:
        r2, r128 = rows[0], rows[-1]
        print(f"fig8_scaling,logic_growth,"
              f"{r128['logic_pct'] / r2['logic_pct']:.1f}x,for,64x,pipelines"
              f",freq_drop,{r2['freq_mhz']}->{r128['freq_mhz']}MHz")
        better = all(r["reap_gflops_per_fpu_median"]
                     > r["cpu_gflops_per_fpu_median"] for r in rows)
        print(f"fig8_finding,reap_higher_gflops_per_fpu_everywhere,{better}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()
    run()
