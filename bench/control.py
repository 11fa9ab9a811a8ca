"""Read a cell's comparison with the control in the program's place.

    python3 bench/control.py --workload bcsstk17.cholesky --seeds 11 12 13

For each seed, builds the cell's pattern and values as a run does, puts the
control (the plain reference one precision step below the configuration's,
see ``bench/reference.py``) where the program's answers would be, and
prints the worst reading of each compared number beside the cell's limit.
Host-only: it touches no accelerator.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_readings(config: dict, traffic: dict, seed: int,
                     n_ops: int) -> dict:
    """Worst reading of each compared number over ``n_ops`` operations."""
    from bench import harness
    op = harness.load_op(traffic["op"])
    state = op.prepare(config, traffic, seed)
    state.release()
    worst: dict = {}
    for i in range(n_ops):
        for k, v in op.check(state, i, op.control(state, i)).items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--ops", type=int, default=2,
                    help="operations read per seed")
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness
    _, config, traffic, _, _ = harness.cell_spec(harness.load_benchmark(),
                                                 args.workload)
    for seed in args.seeds:
        worst = control_readings(config, traffic, seed, args.ops)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": worst, "limits": traffic["limits"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
