#!/usr/bin/env python3
"""The benchmark of the AoPI edge-analytics control loop, one cell a run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips. It builds the cell's inputs from
``--seed``, warms up every shape the cell uses (set-up), runs the cell's
closed loop for ``--seconds``, checks a seeded sample of what the timed
path produced against the plain reference, and prints one JSON line.
With ``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. Without a TPU it exits 1 and prints no result.
"""
import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import harness
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
