#!/usr/bin/env python3
"""Run a cell with its control, or a planted fault, in the program's place.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> \
        [--fault <name>]

Without ``--fault`` it runs the control, what the check must refuse: the
tables go in at bfloat16 precision and, in the serve cells, the data
plane runs in float32 (the program's own path, its float64 switch point
raised). ``--fault`` plants one of ``FAULTS`` in the timed path instead,
and ``--fault none`` runs the program as it is. Every seed runs in this
one process, each printing the same result line as ``run.py``
(``correct`` should read false but for ``none``). It exits 1 without a
TPU. The benchmark's own runs never run it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def _bcd_edit(edit):
    """Edit every slot decision ``bcd.solve_slot`` returns."""
    from repro.core import bcd
    solve = bcd.solve_slot

    def faulty(*args, **kwargs):
        return edit(solve(*args, **kwargs))
    return [(bcd, "solve_slot", faulty)]


def _state_unchanged():
    """The virtual queue (Eq. 44) returns its state unchanged."""
    import jax.numpy as jnp
    from repro.core import lyapunov
    return [(lyapunov, "queue_update",
             lambda q, p_bar, p_min: jnp.asarray(q, jnp.float32))]


def _half_left_out():
    """Half of the cameras get no bandwidth."""
    def edit(dec):
        half = dec.b.shape[-1] // 2
        return dataclasses.replace(dec, b=dec.b.at[half:].set(0.0))
    return _bcd_edit(edit)


def _answer_altered():
    """One camera's AoPI is altered where the solver produces it."""
    return _bcd_edit(lambda dec: dataclasses.replace(
        dec, aopi=dec.aopi.at[0].multiply(1.01)))


def _bandwidth_short():
    """The bandwidth water-fill hands out 90% of what it solved for,
    before compute is allocated and the AoPI evaluated."""
    from repro.core import allocate
    fill = allocate.waterfill_bandwidth
    return [(allocate, "waterfill_bandwidth",
             lambda *a, **k: 0.9 * fill(*a, **k))]


def _argmin_shift():
    """The config search returns the flat (model, resolution, policy)
    index one past its argmin."""
    from repro.kernels import slot_solver
    search = slot_solver.config_argmin

    def shifted(b, c, acc, xi, *args, **kwargs):
        r, m, pol = search(b, c, acc, xi, *args, **kwargs)
        n_m, n_r = xi.shape
        flat = ((m * n_r + r) * 2 + pol + 1) % (n_m * n_r * 2)
        return ((flat // 2) % n_r).astype(r.dtype), \
            (flat // (2 * n_r)).astype(m.dtype), (flat % 2).astype(pol.dtype)
    return [(slot_solver, "config_argmin", shifted)]


def _plane_edit(edit):
    """Edit the measured AoPI where the data plane's program returns it."""
    from repro.core import queues
    sim = queues._window_sim

    def faulty(*args, **kwargs):
        out = dict(sim(*args, **kwargs))
        out["aopi"] = edit(out["aopi"])
        return out
    return [(queues, "_window_sim", faulty)]


#: name -> (the fault's patches, the check that must catch it, where).
FAULTS = {
    "state_unchanged": (_state_unchanged, "queue_gap", "planner"),
    "half_left_out": (_half_left_out, "plan_aopi_gap", "planner"),
    "answer_altered": (_answer_altered, "plan_aopi_gap", "planner"),
    "bandwidth_short": (_bandwidth_short, "budget_gap", "planner"),
    "argmin_shift": (_argmin_shift, "config_miss", "planner"),
    "plane_half_left_out": (lambda: _plane_edit(
        lambda a: a.at[:, a.shape[1] // 2:].set(0.0)),
        "measured_aopi_gap", "data plane"),
    "plane_answer_altered": (lambda: _plane_edit(
        lambda a: a.at[0, 0].multiply(1.01)),
        "measured_aopi_gap", "data plane"),
}


def plant(name: str):
    """Plant fault ``name``; returns the function that removes it. Both
    drop JAX's traced programs, so no faulty program outlives it."""
    import jax
    patches = FAULTS[name][0]()
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    for obj, attr, new in patches:
        setattr(obj, attr, new)
    jax.clear_caches()

    def undo():
        for obj, attr, old in saved:
            setattr(obj, attr, old)
        jax.clear_caches()
    return undo


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, run in this one process")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=["none", *FAULTS], default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness
    if args.fault not in (None, "none"):
        plant(args.fault)
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        rc = rc or harness.run(args.workload, seed, args.seconds, False,
                               t_start=time.perf_counter(),
                               control=args.fault is None,
                               memory_report=False)
    return rc


if __name__ == "__main__":
    sys.exit(main())
