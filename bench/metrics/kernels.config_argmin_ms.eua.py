"""Device time of the config-search kernel (``slot_solver.config_argmin``,
Algorithm 1 line 3) per plan in the EUA replan cell, ms."""
from bench import roofline


def read(run):
    sec = roofline.kernel_s_per_plan(run, "config_argmin")
    return None if sec is None else 1e3 * sec
