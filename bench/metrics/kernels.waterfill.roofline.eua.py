"""The fused water-fill kernel's share of the HBM roofline in the EUA
replan cell, % (``bench/roofline.py``)."""
from bench import roofline


def read(run):
    return roofline.roofline_pct(run, "waterfill_pair")
