"""Device time of the fused water-fill kernel
(``slot_solver.waterfill_pair``, Algorithm 1 lines 4 and 5) per plan in
the EUA replan cell, ms."""
from bench import roofline


def read(run):
    sec = roofline.kernel_s_per_plan(run, "waterfill_pair")
    return None if sec is None else 1e3 * sec
