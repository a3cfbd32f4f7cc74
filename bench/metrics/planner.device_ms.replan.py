"""Device time of the planner's program per plan in the replan cell, ms:
the ``rollout`` programs that ran wholly inside the traced window, over
as many plans. Beside ``plan_p50_ms`` it splits a plan's latency into
device work and the host's dispatch and copies."""
PROGRAM = "rollout"


def read(run):
    if run.trace is None:
        return None
    runs = [s for name, s in run.trace.programs if PROGRAM in name]
    if not runs or len(runs) > run.record.get("plans", 0):
        return None
    return 1e3 * sum(runs) / len(runs)
