"""Fault-storm demo: every fault kind at once, graceful degradation on.

1. Build the two fault scenario families (``camera_churn`` — a fleet
   mask threaded through every rollout engine, so churned-out cameras
   get exactly zero allocation — and ``correlated_fade`` — correlated
   multi-server backhaul fades), plus the steady AR(1) anchor.
2. Replay each through ``AnalyticsService`` under
   ``repro.faults.storm_plan``: camera churn, a server crash, a
   correlated fade, telemetry drop/delay/corruption, and solver faults
   staged to engage every rung of the graceful-degradation ladder
   (retry with backoff -> stale plan re-projected on the surviving
   fleet -> MIN fallback).
3. Verify the run the way CI does: measured AoPI finite everywhere, the
   fallback / degraded-epoch / retry / telemetry-gap counters nonzero,
   and each ``repro.obs`` counter exactly equal to its legacy service
   list (the reconciliation contract).
4. Print the degradation report: AoPI under faults vs fault-free, with
   recovery epochs, per (policy, fault kind).

    PYTHONPATH=src python examples/fault_storm.py [--smoke] [--policies lbcd,min]
"""
import argparse
from pathlib import Path

import numpy as np

from repro import obs, scenarios
from repro.faults import storm_plan
from repro.serving.replay import replay_tables

COUNTERS = (
    ("service.fallback", lambda s: s.fallbacks),
    ("service.degraded_epoch", lambda s: s.degraded_epochs),
    ("service.plan_retry", lambda s: s.plan_failures),
    ("service.telemetry_gap", lambda s: s.telemetry_gaps),
)


def main(smoke: bool = False, policies: tuple = ("lbcd", "min")):
    obs.configure(enabled=True)
    dims = (dict(n_cameras=6, n_slots=16, n_servers=2,
                 mean_bandwidth_hz=15e6, mean_compute_flops=20e12)
            if smoke else dict(n_cameras=16, n_slots=32, n_servers=3))
    plan = storm_plan(dims["n_slots"], seed=0)
    print(f"storm plan: {len(plan.specs)} specs -> "
          f"{', '.join(s.kind for s in plan.specs)}\n")

    names = ["camera_churn", "correlated_fade", "steady_ar1"]
    totals = {name: 0 for name, _ in COUNTERS}
    for scen in names:
        tables = scenarios.build(scen, **dims)
        for policy in policies:
            rep = replay_tables(tables, policy, plan_window=4,
                                telemetry_gain=0.2, faults=plan)
            svc = rep.service
            assert np.isfinite(rep.measured).all(), \
                f"{scen}/{policy}: non-finite measured AoPI"
            counts = {name: len(get(svc)) for name, get in COUNTERS}
            for name in totals:
                totals[name] += counts[name]
            print(f"{scen:<16s} {policy:<5s} "
                  f"mean AoPI {float(rep.measured.mean()):.4f} | "
                  + " ".join(f"{n.split('.')[1]}={c}"
                             for n, c in counts.items()))

    # The reconciliation contract: every obs counter equals the summed
    # legacy lists, and the storm actually engaged the ladder.
    evs = obs.events()
    for name, total in totals.items():
        n_ev = sum(1 for e in evs if e.get("name") == name)
        n_ctr = sum(m.value for m in obs.registry()
                    if m.name == name + ".count")
        assert n_ev == n_ctr == total, \
            f"{name}: events={n_ev} counter={n_ctr} lists={total}"
    assert totals["service.fallback"] > 0, "storm engaged no fallback"
    assert totals["service.degraded_epoch"] > 0
    assert totals["service.telemetry_gap"] > 0
    print(f"\nreconciled: " + ", ".join(f"{n}={c}"
                                        for n, c in totals.items()))

    suite = scenarios.suite(names, **dims)
    n_epochs = 8 if smoke else 16
    print("\ndegradation report (faulted vs clean replay per kind):")
    print(scenarios.degradation(suite, policies=policies,
                                n_epochs=n_epochs, plan_window=4))


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny dimensions for CI smoke runs")
    ap.add_argument("--policies", default="lbcd,min",
                    help="comma-separated policies to storm")
    args = ap.parse_args()
    main(args.smoke, tuple(p for p in args.policies.split(",") if p))
