"""Quickstart: the paper's core loop in ~60 lines.

1. Validate the AoPI closed forms (Theorems 1-2) against the discrete-event
   oracle for one configuration.
2. Run the LBCD controller (device-resident scan rollout engine) on a small
   edge system and compare against the DOS / JCAB / MIN baselines.
3. Sweep the whole (V, P_min) hyperparameter grid as one vmapped call.

    PYTHONPATH=src python examples/quickstart.py
"""
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from repro.core import aopi, baselines, lbcd, profiles, queues


def main():
    # --- 1. AoPI theory vs simulation --------------------------------
    lam, mu, p = 5.0, 10.0, 0.8
    print("Theorem 1 (FCFS):   A_F =",
          f"{float(aopi.aopi_fcfs(lam, mu, p)):.4f} s "
          f"(sim: {queues.simulate_fcfs(lam, mu, p, 200_000).mean_aopi:.4f})")
    print("Theorem 2 (LCFSP):  A_L =",
          f"{float(aopi.aopi_lcfsp(lam, mu, p)):.4f} s "
          f"(sim: {queues.simulate_lcfsp(lam, mu, p, 200_000).mean_aopi:.4f})")
    rho = lam / mu
    print(f"Theorem 3 threshold at rho={rho}: p* ="
          f" {float(aopi.policy_threshold(rho)):.3f} -> optimal policy for"
          f" p={p}: {'LCFSP' if aopi.optimal_policy(lam, mu, p) else 'FCFS'}")

    # --- 2. LBCD vs baselines ----------------------------------------
    def system():
        return profiles.EdgeSystem(n_cameras=20, n_servers=3, n_slots=25,
                                   mean_bandwidth_hz=15e6,
                                   mean_compute_flops=25e12, seed=0)

    print("\ncontroller     mean AoPI   mean accuracy")
    s = lbcd.LBCDController(system(), v=10.0, p_min=0.7).run(25)
    print(f"LBCD           {s.mean_aopi:9.4f}   {s.mean_acc:.3f}")
    for name in ("MIN", "DOS", "JCAB"):
        b = baselines.make(name, system()).run(25)
        print(f"{name:<14s} {b.mean_aopi:9.4f}   {b.mean_acc:.3f}")

    # --- 3. (V, P_min) grid: one vmapped device-resident rollout ------
    tables = system().horizon(25)
    vs = jnp.asarray([1.0, 10.0, 100.0])
    p_mins = jnp.asarray([0.7, 0.7, 0.7])
    grid = lbcd.rollout_grid(tables, vs, p_mins)
    print("\nV sweep (one vmapped rollout_grid call):")
    for g, v in enumerate(vs):
        print(f"  V={float(v):6.1f}  mean AoPI {float(grid.aopi[g].mean()):.4f}"
              f"  mean acc {float(grid.acc[g].mean()):.3f}")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])
    main()
