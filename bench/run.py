"""Run one cell of the benchmark on the TPU this process is started on.

    python3 bench/run.py --workload cant.spgemm --seed 7 --seconds 51 --trace 0

Prints one JSON object as the last line of standard output (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``; ``breakdown`` with
``--trace 1``; the numbers compared, each with its limit, last under
``checks``) and the same comparison as the last lines of standard error.
Exits non-zero, with no result, without a TPU or with fewer chips than the
cell asks for, and outside a checkout of the repository.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs to /tmp
    from bench import harness

    bench = harness.load_benchmark()
    wl, config, traffic, e2e, per_layer = harness.cell_spec(
        bench, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(wl["chips"]):
        print(f"bench: {args.workload} needs {wl['chips']} TPU chip(s); "
              f"JAX sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    peaks = harness.load_peaks(devices[0].device_kind)
    harness.init_compile_cache()
    trace_dir = ROOT / ".bench-trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        line, info = harness.run_cell(
            config, traffic, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), end_to_end=e2e, per_layer=per_layer,
            peaks=peaks, t_start=T_START, trace_dir=str(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print("info " + json.dumps(info), file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    here = Path(__file__).resolve().parent    # bench/ must not shadow
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != here]
    sys.exit(main())
