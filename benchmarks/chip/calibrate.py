#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program's and the
control's, seed by seed, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 11 12 13 --seconds 20 [--control-seeds 11 12 13]

For each seed the cell is served for ``--seconds`` at its own load,
then the check's sample is run through the float32 reference; for the
control seeds the same positions are also run through the float8
reference (``check.py``). One JSON line per seed on standard output.
The benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness
    cell, jax = harness.prepare(ROOT, args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        window = harness.serve_window(cell, jax, seed, args.seconds, False,
                                      t)
        readings = harness.verify(cell, window, seed,
                                  control=seed in args.control_seeds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **readings, "metrics": window.metrics,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
