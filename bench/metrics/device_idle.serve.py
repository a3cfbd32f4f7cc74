"""Idle share of the device in the traced window, %: 1 - busy/window,
busy being the union of the device's op intervals."""


def read(run):
    red = run.trace
    if red is None or red.window_s <= 0 or not red.busy_s:
        return None
    return 100.0 * (1.0 - red.mean_busy_s / red.window_s)
