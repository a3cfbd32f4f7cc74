"""``kernels.first_fit_ms.eua``: the first-fit kernel's device time per
plan, read from a synthetic trace of the EUA replan cell, and nothing
where the trace has no such op or no trace was taken."""
from types import SimpleNamespace

import pytest

from bench import harness

METRIC = "kernels.first_fit_ms.eua"


def _run(op_s, programs, plans):
    return SimpleNamespace(trace=SimpleNamespace(op_s=op_s,
                                                 programs=programs),
                           record={"plans": plans})


#: Four plans traced, each rollout program 9 ms; its 8 first-fit calls
#: (op names as the compiled HLO has them) 1.5 ms together.
OPS = {"%slot_solver.first_fit.31": 0.004,
       "%slot_solver.first_fit.32": 0.002,
       "%slot_solver.waterfill_pair.22": 0.009,
       "%slot_solver.config_argmin.14": 0.001, "%fusion.7": 0.02}
PROGRAMS = [("jit_rollout", 0.009)] * 4


def test_reads_the_kernel_time_per_plan():
    assert harness.reader(METRIC)(_run(OPS, PROGRAMS, 4)) == pytest.approx(
        1.5)


def test_counts_only_rollouts_wholly_in_the_window():
    """Programs cut by the window's edges are not in ``programs``; a
    trace that lists more rollouts than the window planned reads
    nothing."""
    assert harness.reader(METRIC)(_run(OPS, PROGRAMS, 3)) is None
    programs = PROGRAMS + [("jit__window_sim", 0.3)]
    assert harness.reader(METRIC)(_run(OPS, programs, 6)) == pytest.approx(
        1.5)


@pytest.mark.parametrize("op_s,programs", [
    ({"%while.194": 0.07, "%fusion.7": 0.02}, PROGRAMS),
    (OPS, [("jit__window_sim", 0.3)])])
def test_reads_nothing_without_the_kernel_or_a_rollout(op_s, programs):
    """The parent's program places cameras in an XLA loop: no op is
    named ``slot_solver.first_fit``, and the metric is left out."""
    assert harness.reader(METRIC)(_run(op_s, programs, 4)) is None


def test_reads_nothing_without_a_trace():
    run = _run(OPS, PROGRAMS, 4)
    run.trace = None
    assert harness.reader(METRIC)(run) is None
