"""Planner time per epoch, ms: the ``service.plan_window`` spans (one
``lbcd.rollout`` per plan window, dispatch through host copy) over the
epochs the window ran."""
from bench.spans import count, total_s


def read(run):
    epochs = count(run.spans, "service.run_epoch")
    if not epochs:
        return None
    return 1e3 * total_s(run.spans, "service.plan_window") / epochs
