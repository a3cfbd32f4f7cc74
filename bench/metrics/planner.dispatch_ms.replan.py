"""Host time before a plan can start on the device in the replan cell,
ms: the ``planner.tables`` spans (the horizon window sliced from the
device tables) and the ``planner.dispatch`` spans (the ``rollout``
program enqueued), per plan. Beside ``plan_p50_ms`` and
``planner.device_ms.replan`` it leaves the wait and the copy-back of a
plan. A program without the spans reads nothing."""
from bench.spans import count, total_s


def read(run):
    plans = count(run.spans, "planner.dispatch")
    if not plans:
        return None
    host = (total_s(run.spans, "planner.tables")
            + total_s(run.spans, "planner.dispatch"))
    return 1e3 * host / plans
