"""The readers of the planner's and the data plane's phase spans on
synthetic span lists with known answers, and on the spans of a program
that lacks the phase spans, where they read nothing."""
from types import SimpleNamespace

import pytest

from bench import harness


def span(name, dur):
    return {"name": name, "dur": dur}


#: Two epochs of one plan window in the serve cell, times in seconds.
SERVE = ([span("service.run_epoch", 0.020), span("service.run_epoch", 0.001),
          span("service.plan_window", 0.006),
          span("planner.tables", 0.0004), span("planner.dispatch", 0.0002),
          span("planner.fetch", 0.0014),
          span("data_plane.inputs", 0.0003),
          span("service.measure_window", 0.012),
          span("queues.gi_g1_window", 0.0118),
          span("data_plane.wait", 0.0110),
          span("data_plane.fetch", 0.0002)])

#: Three plans in the replan cell.
REPLAN = [span(n, d) for _ in range(3)
          for n, d in (("planner.tables", 0.0003),
                       ("planner.dispatch", 0.0001))]

EXPECTED = {
    # 1.4 ms of copy-back over 2 epochs.
    "planner.fetch_ms.serve": (SERVE, 0.7),
    # 0.2 ms over 2 epochs.
    "data_plane.fetch_ms.serve": (SERVE, 0.1),
    # (0.3 + 12.0 - 11.0) ms over 2 epochs.
    "data_plane.host_ms.serve": (SERVE, 0.65),
    # (0.9 + 0.3) ms over 3 plans.
    "planner.dispatch_ms.replan": (REPLAN, 0.4),
}

#: The spans of a program without the phase spans.
WITHOUT = {"service.run_epoch", "service.plan_window",
           "service.measure_window", "queues.gi_g1_window"}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_its_spans(metric):
    spans, want = EXPECTED[metric]
    run = SimpleNamespace(spans=spans, record={})
    assert harness.reader(metric)(run) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_nothing_without_the_phase_spans(metric):
    spans, _ = EXPECTED[metric]
    run = SimpleNamespace(spans=[e for e in spans if e["name"] in WITHOUT],
                          record={"plans": 3, "plan_p50_ms": 23.0})
    assert harness.reader(metric)(run) is None
