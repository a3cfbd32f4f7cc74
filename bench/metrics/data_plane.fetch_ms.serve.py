"""Copy-back of the data plane's outputs per epoch in the serve cell, ms:
the ``data_plane.fetch`` spans (the ``_window_sim`` outputs copied to the
host as float64, once the device has finished them) over the epochs the
window ran. A program without the span reads nothing."""
from bench.spans import count, total_s


def read(run):
    epochs = count(run.spans, "service.run_epoch")
    if not epochs or not count(run.spans, "data_plane.fetch"):
        return None
    return 1e3 * total_s(run.spans, "data_plane.fetch") / epochs
