"""Device time of the first-fit kernel (``slot_solver.first_fit``,
Algorithm 2 lines 3-9, one call a slot) per plan in the EUA replan cell,
ms: the time of the ops so named over the ``rollout`` programs run wholly
inside the traced window, per such program. ``None`` without a trace, and
where no op carries the name (a program that places cameras in an XLA
loop)."""
OP = "slot_solver.first_fit"
PROGRAM = "rollout"


def read(run):
    if run.trace is None:
        return None
    plans = [s for name, s in run.trace.programs if PROGRAM in name]
    sec = sum(s for name, s in run.trace.op_s.items() if OP in name)
    if not plans or not sec or len(plans) > run.record.get("plans", 0):
        return None
    return 1e3 * sec / len(plans)
