"""The check fails what it must, at a toy size on the CPU.

* The control: each cell's lower-precision control in the program's
  place (tables at bfloat16; in the serve cells the program's own float32
  data plane too) comes out not correct.
* Faults planted in the timed path (``bench/control.py``), each of those
  a cell can have (it runs on one chip, so no exchange between chips can
  be left out): a step that returns its state unchanged, half of the
  batch left out, an answer altered where it is produced, bandwidth
  handed out short of what the water-fill solved for, and the config
  search's index shifted past its argmin.
"""
import pytest

from bench import control
from test_harness import SPEC, run_toy

CELLS = [w["name"] for w in SPEC["workloads"]]
SERVE = [w["name"] for w in SPEC["workloads"] if w["traffic"] == "serve"]


def failed_checks(line) -> set:
    return {k for k, c in line["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_is_not_correct(workload, monkeypatch):
    from repro.core import queues
    monkeypatch.setattr(queues, "F32_MAX_FRAMES", queues.F32_MAX_FRAMES)
    line = run_toy(workload, control=True)
    assert line["correct"] is False
    assert failed_checks(line)


def _run_with(fault, workload):
    undo = control.plant(fault)
    try:
        return run_toy(workload)
    finally:
        undo()


PLANNER = sorted(k for k, v in control.FAULTS.items() if v[2] == "planner")
DATA_PLANE = sorted(k for k, v in control.FAULTS.items()
                    if v[2] == "data plane")


@pytest.mark.parametrize("fault", PLANNER)
@pytest.mark.parametrize("workload", CELLS)
def test_a_planner_fault_is_not_correct(workload, fault):
    line = _run_with(fault, workload)
    assert line["correct"] is False
    assert control.FAULTS[fault][1] in failed_checks(line)


@pytest.mark.parametrize("fault", DATA_PLANE)
@pytest.mark.parametrize("workload", SERVE)
def test_a_data_plane_fault_is_not_correct(workload, fault):
    line = _run_with(fault, workload)
    assert line["correct"] is False
    assert "measured_aopi_gap" in failed_checks(line)
