"""Device time and least HBM bytes of the slot-solver kernels in the
replan cells, for their per-layer metrics.

Bytes are the least each call must move, from its operands' shapes
(float32 and int32 are 4 bytes); the kernels do VPU work only, so the
bound is HBM bandwidth (``peaks.json``'s ``hbm_bytes_per_s``):

* ``slot_solver.config_argmin`` (Algorithm 1 line 3): the ``[N, M, R]``
  accuracy block, the per-camera ``b``, ``c`` and link efficiency, and
  the three index rows out, with the ``[M, R]`` FLOPs table, the ``[R]``
  frame sizes and the two scalars ``q`` and ``V``.
* ``slot_solver.waterfill_pair`` (lines 4 and 5 in one call): the eight
  packed per-camera vectors in and the two allocations out, each
  lane-padded to ``Np`` (a multiple of 128 cameras), and the ``[S, Np]``
  server-membership matrix, each read or written once.

A plan of ``plan_window`` slots solves each slot twice (Algorithm 2: the
virtual server, ``S = 1``, then the real servers, ``S = n_servers``);
each solve runs ``bcd_iters`` passes of a config search and a water-fill,
then one more water-fill at full precision for the final configuration.
"""
from __future__ import annotations

WORD = 4
LANES = 128
#: The rollout program whose runs count the plans (``planner.device_ms``).
PROGRAM = "rollout"
#: Op-name prefixes of the kernels' ``pallas_call`` names.
OPS = {"config_argmin": "slot_solver.config_argmin",
       "waterfill_pair": "slot_solver.waterfill_pair"}


def config_argmin_bytes(n: int, n_models: int, n_res: int) -> int:
    return WORD * (n * n_models * n_res + 3 * n + 3 * n
                   + n_models * n_res + n_res + 2)


def waterfill_pair_bytes(n: int, n_servers: int) -> int:
    n_pad = max(LANES, -(-n // LANES) * LANES)
    return WORD * ((8 + 2 + n_servers) * n_pad + 1)


def plan_bytes(cfg: dict, traffic: dict) -> dict:
    """Least HBM bytes of each kernel over one plan of the cell."""
    n, s = cfg["n_cameras"], cfg["n_servers"]
    slots, passes = traffic["plan_window"], cfg["bcd_iters"]
    n_res = len(cfg["resolutions"])
    argmin = config_argmin_bytes(n, cfg["models"], n_res)
    return {"config_argmin": slots * 2 * passes * argmin,
            "waterfill_pair": slots * (passes + 1) * (
                waterfill_pair_bytes(n, 1) + waterfill_pair_bytes(n, s))}


def kernel_s_per_plan(run, kernel: str) -> float | None:
    """Device seconds of ``kernel``'s ops over the ``rollout`` programs
    run wholly inside the traced window, per such program; ``None``
    where the trace holds no such op (a kernel without its name)."""
    if run.trace is None:
        return None
    plans = [s for name, s in run.trace.programs if PROGRAM in name]
    ops = sum(s for name, s in run.trace.op_s.items() if OPS[kernel] in name)
    if not plans or not ops or len(plans) > run.record.get("plans", 0):
        return None
    return ops / len(plans)


def roofline_pct(run, kernel: str) -> float | None:
    """``kernel``'s share of the HBM roofline, %: its least bytes over
    peak bandwidth, over its device time."""
    sec = kernel_s_per_plan(run, kernel)
    if sec is None or run.peaks is None:
        return None
    least = plan_bytes(run.cfg, run.traffic)[kernel]
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / sec
