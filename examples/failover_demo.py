"""Failover demo: an island (model-parallel subgroup) dies mid-service and
LBCD's server-selection subproblem re-places its streams on the next epoch
(the paper's Algorithm 2 doubling as the fault-tolerance mechanism).

    PYTHONPATH=src python examples/failover_demo.py
"""
from pathlib import Path

import numpy as np

from repro.core import lbcd, profiles
from repro.training.failure import failover_assignment


def main():
    system = profiles.EdgeSystem(n_cameras=16, n_servers=4, n_slots=12,
                                 seed=0)
    ctrl = lbcd.LBCDController(system, v=10.0, p_min=0.7)

    print("epoch 0-2: healthy islands")
    for t in range(3):
        rec = ctrl.step(t)
        load = np.bincount(rec.assign, minlength=4)
        print(f"  t={t} AoPI={rec.mean_aopi:.4f} island-load={load}")

    print("\nepoch 3: island 1 fails -> LBCD re-solves placement")
    dead = np.array([False, True, False, False])
    rec = failover_assignment(ctrl, 3, dead)
    load = np.bincount(rec.assign, minlength=4)
    print(f"  t=3 AoPI={rec.mean_aopi:.4f} island-load={load} "
          f"(island 1 drained)")
    assert load[1] == 0

    print("\nepoch 4: island restored")
    rec = ctrl.step(4)
    load = np.bincount(rec.assign, minlength=4)
    print(f"  t=4 AoPI={rec.mean_aopi:.4f} island-load={load}")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])
    main()
