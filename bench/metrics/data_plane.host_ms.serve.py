"""The data plane's host time per epoch in the serve cell, ms: the
``data_plane.inputs`` spans (the plane's rates formed from the plan and
the device tables) and the ``service.measure_window`` spans, less the
``data_plane.wait`` spans inside them (the host waiting on the
``_window_sim`` program), over the epochs the window ran: the part of
the data plane that does not overlap its device work. A program without
those spans reads nothing."""
from bench.spans import count, total_s


def read(run):
    epochs = count(run.spans, "service.run_epoch")
    if (not epochs or not count(run.spans, "data_plane.inputs")
            or not count(run.spans, "data_plane.wait")):
        return None
    host = (total_s(run.spans, "data_plane.inputs")
            + total_s(run.spans, "service.measure_window")
            - total_s(run.spans, "data_plane.wait"))
    return 1e3 * host / epochs
