"""Service loop's own time per epoch, ms: the ``service.run_epoch`` spans
less the planner (``service.plan_window``) and data-plane
(``service.measure_window``) spans inside them."""
from bench.spans import count, total_s


def read(run):
    epochs = count(run.spans, "service.run_epoch")
    if not epochs:
        return None
    own = (total_s(run.spans, "service.run_epoch")
           - total_s(run.spans, "service.plan_window")
           - total_s(run.spans, "service.measure_window"))
    return 1e3 * own / epochs
