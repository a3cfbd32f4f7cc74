"""Copy-back of the plan per epoch in the serve cell, ms: the
``planner.fetch`` spans (every leaf of the plan copied to the host, once
the device has finished it) over the epochs the window ran. Part of
``planner.ms.serve``; a program without the span reads nothing."""
from bench.spans import count, total_s


def read(run):
    epochs = count(run.spans, "service.run_epoch")
    if not epochs or not count(run.spans, "planner.fetch"):
        return None
    return 1e3 * total_s(run.spans, "planner.fetch") / epochs
