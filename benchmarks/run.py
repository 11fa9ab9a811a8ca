"""Benchmark entry point: one module per paper table/figure + the roofline
table from the dry-run artifacts.  Prints ``name,...`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--only fig6,fig10]
"""
from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import init_compile_cache


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset: fig6,fig7_11,fig8,fig9,"
                         "fig10,roofline,plan_cache")
    args = ap.parse_args(argv)
    init_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    t0 = time.time()
    if want("fig6"):
        from . import fig6_spgemm
        fig6_spgemm.run()
    if want("fig7_11"):
        from . import fig7_11_split
        fig7_11_split.run()
    if want("fig8"):
        from . import fig8_gflops
        fig8_gflops.run()
    if want("fig9"):
        from . import fig9_density
        fig9_density.run()
    if want("fig10"):
        from . import fig10_cholesky
        fig10_cholesky.run()
    if want("roofline"):
        from . import roofline_table
        roofline_table.summary()
    if want("plan_cache"):
        from . import bench_plan_cache
        bench_plan_cache.run()
    print(f"benchmarks_total_seconds,{time.time() - t0:.1f}")


if __name__ == "__main__":
    main()
