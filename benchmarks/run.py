"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME[,NAME...]]

Emits CSV blocks per figure and persists JSON under results/bench/ —
every table (fig reproductions and BENCH_* trajectory benches alike)
carries the ``common.run_metadata()`` provenance stamp, including the
``repro.obs`` metric snapshot accumulated by the run.

``--only`` takes one or more comma-separated names; each is matched as a
substring against the table keys, and a token that matches nothing
aborts with the list of valid keys.
"""
import argparse
import json
import sys
import time
from pathlib import Path

from . import (bench_bandwidth, bench_cameras, bench_compute,
               bench_dataplane, bench_energy, bench_engine, bench_faults,
               bench_frontier, bench_hyperparams, bench_overhead,
               bench_policy, bench_rollout, bench_scenarios,
               bench_slot_solver, bench_validation, common)

ALL = {
    "fig14_15_validation": bench_validation.run,
    "fig6_policy_phase": bench_policy.run,
    "fig3_5_frontier": bench_frontier.run,
    "fig7_8_hyperparams": bench_hyperparams.run,
    "fig9_bandwidth": bench_bandwidth.run,
    "fig10_compute": bench_compute.run,
    "fig11_cameras": bench_cameras.run,
    "fig12_overhead": bench_overhead.run,
    "beyond_energy": bench_energy.run,
    "BENCH_rollout": bench_rollout.run,
    "BENCH_scenarios": bench_scenarios.run,
    "BENCH_slot_solver": bench_slot_solver.run,
    "BENCH_dataplane": bench_dataplane.run,
    "BENCH_engine": bench_engine.run,
    "BENCH_faults": bench_faults.run,
}


def select(only: str | None) -> list[str]:
    """Resolve ``--only`` (comma-separated substrings) to table keys,
    erroring per-token so a typo names itself AND the valid keys."""
    if not only:
        return list(ALL)
    selected: list[str] = []
    for token in (t.strip() for t in only.split(",")):
        if not token:
            continue
        hits = [name for name in ALL if token in name]
        if not hits:
            sys.exit(f"--only token {token!r} matched no benchmark; "
                     f"known: {', '.join(ALL)}")
        selected += [h for h in hits if h not in selected]
    return selected


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark name substrings")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])
    t0 = time.time()
    print(f"# meta: {json.dumps(common.run_metadata(), default=float)}\n",
          flush=True)
    for name in select(args.only):
        t = time.time()
        ALL[name](full=args.full)
        print(f"[{name}: {time.time()-t:.1f}s]\n", flush=True)
    print(f"total {time.time()-t0:.1f}s")


if __name__ == '__main__':
    main()
