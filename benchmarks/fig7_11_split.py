"""Figs 7 & 11: fraction of time in CPU preprocessing vs FPGA computation
(REAP-32).  Paper finding: FPGA dominates except for very sparse matrices,
where extracting/organizing nonzeros costs more than computing on them."""
from __future__ import annotations

from typing import List


from repro.core import inspect_cholesky
from repro.core.simulator import (REAP_32, REAP_32C, simulate_cholesky_reap,
                                  simulate_spgemm_reap, spgemm_workload)

from .table1 import CHOLESKY_SET, SPGEMM_SET, make_chol_matrix, \
    make_spgemm_matrix


def run(verbose: bool = True) -> List[dict]:
    rows = []
    for spec in SPGEMM_SET:
        a, _ = make_spgemm_matrix(spec)
        stats = spgemm_workload(a, a)
        stats["density"] = spec.density
        sim = simulate_spgemm_reap(stats, REAP_32)
        tot = sim["preprocess_s"] + sim["fpga_s"]
        row = dict(kind="spgemm", id=spec.spgemm_id, name=spec.name,
                   cpu_pct=100 * sim["preprocess_s"] / tot,
                   fpga_pct=100 * sim["fpga_s"] / tot,
                   density=spec.density)
        rows.append(row)
        if verbose:
            print(f"fig7,{spec.spgemm_id},{spec.name},"
                  f"cpu%={row['cpu_pct']:.1f},fpga%={row['fpga_pct']:.1f}",
                  flush=True)
    for spec in CHOLESKY_SET:
        a, _ = make_chol_matrix(spec)
        plan = inspect_cholesky(a)
        sim = simulate_cholesky_reap(plan, REAP_32C)
        # symbolic pass: linear walk over |L| (no flops — paper Fig 11)
        pre_s = plan.nnz * 4 / 2.1e9
        tot = pre_s + sim["fpga_s"]
        row = dict(kind="cholesky", id=spec.chol_id, name=spec.name,
                   cpu_pct=100 * pre_s / tot,
                   fpga_pct=100 * sim["fpga_s"] / tot)
        rows.append(row)
        if verbose:
            print(f"fig11,{spec.chol_id},{spec.name},"
                  f"cpu%={row['cpu_pct']:.1f},fpga%={row['fpga_pct']:.1f}",
                  flush=True)
    if verbose:
        sp = [r for r in rows if r["kind"] == "spgemm"]
        sparse_heavy = [r for r in sp if r["cpu_pct"] > 45]
        print(f"fig7_finding,cpu_preprocessing_ge45pct_on,"
              f"{len(sparse_heavy)}/{len(sp)},matrices"
              f",all_low_density={all(r['density'] < 3e-4 for r in sparse_heavy)}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()
    run()
