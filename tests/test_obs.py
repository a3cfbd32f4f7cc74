"""``repro.obs``: registry/span/exporter units + the reconciliation
contract — ``early_replans``/``divergences`` emitted through the obs
registry must match the trace-event stream AND the legacy list
attributes across a forced-replan replay of every scenario family."""
import json

import jax
import numpy as np
import pytest

from repro import obs, scenarios
from repro.obs import export, metrics, report
from repro.serving import replay

DIMS = dict(n_cameras=4, n_slots=6, n_servers=2,
            mean_bandwidth_hz=15e6, mean_compute_flops=20e12)


@pytest.fixture(autouse=True)
def _isolated_obs():
    """Each test gets an empty registry/buffer and leaves none behind."""
    obs.reset()
    obs.configure(enabled=True)
    yield
    obs.configure(run_dir="")
    obs.reset()


# ---------------------------------------------------------------------------
# Registry + metric primitives
# ---------------------------------------------------------------------------

def test_registry_label_sets_are_distinct_series():
    r = metrics.Registry()
    r.counter("plans", policy="lbcd").inc()
    r.counter("plans", policy="lbcd").inc(2)
    r.counter("plans", policy="min").inc()
    assert r.counter("plans", policy="lbcd").value == 3.0
    assert r.counter("plans", policy="min").value == 1.0
    assert len(r.collect("plans")) == 2
    assert r.total("plans") == 4.0
    assert r.get("plans", policy="dos") is None
    assert len(r) == 2


def test_registry_rejects_kind_conflicts():
    r = metrics.Registry()
    r.counter("x", a="1")
    with pytest.raises(TypeError, match="already registered as counter"):
        r.gauge("x", a="1")
    # Same name under a different kind is still a conflict per-series
    # only — a different label set is a fresh key.
    with pytest.raises(TypeError):
        r.histogram("x", a="1")


def test_histogram_quantiles_within_bucket_resolution():
    h = metrics.Histogram("lat", {})
    rng = np.random.default_rng(0)
    vals = np.exp(rng.normal(size=5000))
    h.observe_many(vals)
    assert h.count == 5000
    assert h.total == pytest.approx(float(vals.sum()))
    for q in (0.5, 0.95, 0.99, 1.0):
        exact = float(np.quantile(vals, q))
        # Geometric buckets with base 2**0.25 -> estimate within half a
        # bucket (~10%) of the true quantile.
        assert h.quantile(q) == pytest.approx(exact, rel=0.12)
    assert h.vmin <= h.quantile(0.0) <= h.quantile(1.0) <= h.vmax


def test_histogram_underflow_bucket_and_empty():
    h = metrics.Histogram("d", {})
    assert h.quantile(0.5) == 0.0
    h.observe(0.0)
    h.observe(-1.0)
    h.observe(4.0)
    assert h.count == 3 and h.zero_count == 2
    assert h.quantile(0.5) == 0.0          # 2/3 of mass at <= 0
    assert h.quantile(1.0) == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# Disabled fast path
# ---------------------------------------------------------------------------

def test_disabled_path_is_shared_noop_singletons():
    obs.configure(enabled=False)
    assert obs.counter("c") is metrics.NOOP_METRIC
    assert obs.gauge("g") is metrics.NOOP_METRIC
    assert obs.histogram("h") is metrics.NOOP_METRIC
    assert obs.span("s") is obs.NOOP_SPAN
    with obs.span("s", policy="lbcd"):
        obs.counter("c").inc()
        obs.event("e", t=3)
        obs.count_dispatch("k")
    assert len(obs.registry()) == 0
    assert obs.events() == []
    obs.configure(enabled=True)
    obs.counter("c").inc()
    assert obs.registry().total("c") == 1.0


# ---------------------------------------------------------------------------
# Spans, nesting, label context
# ---------------------------------------------------------------------------

def test_span_nesting_builds_parent_tree_and_inherits_labels():
    with obs.label_context(policy="lbcd", family="steady_ar1"):
        with obs.span("outer", k=2) as outer:
            with obs.span("inner"):
                obs.event("tick", t=7)
    evs = {e["name"]: e for e in obs.events()}
    assert set(evs) == {"outer", "inner", "tick"}
    assert evs["outer"]["parent"] == 0
    assert evs["inner"]["parent"] == outer.sid
    assert evs["tick"]["parent"] == evs["inner"]["id"]
    assert evs["tick"]["ph"] == "i"
    for e in evs.values():
        assert e["args"]["policy"] == "lbcd"
        assert e["args"]["family"] == "steady_ar1"
    assert evs["outer"]["args"]["k"] == 2
    assert evs["outer"]["dur"] >= evs["inner"]["dur"] >= 0.0


def test_span_exception_closes_records_and_flags_error():
    with pytest.raises(RuntimeError, match="boom"):
        with obs.span("outer"):
            with obs.span("inner"):
                raise RuntimeError("boom")
    evs = {e["name"]: e for e in obs.events()}
    # Both spans recorded despite the raise, error attr on each, and the
    # parent tree stayed intact.
    assert set(evs) == {"outer", "inner"}
    assert evs["inner"]["args"]["error"] == 1
    assert evs["outer"]["args"]["error"] == 1
    assert evs["inner"]["parent"] != 0 and evs["outer"]["parent"] == 0
    # The per-thread stack fully unwound: a fresh span is a root again.
    with obs.span("after"):
        pass
    assert obs.events()[-1]["parent"] == 0
    # The latency histogram still observed the failed spans.
    h = obs.registry().get("inner.seconds")
    assert h is not None and h.count == 1


def test_label_context_restored_after_exception():
    from repro.obs.trace import current_labels
    with pytest.raises(ValueError):
        with obs.label_context(policy="lbcd"):
            with obs.label_context(family="storm"):
                assert current_labels() == {"policy": "lbcd",
                                            "family": "storm"}
                raise ValueError("x")
    assert current_labels() == {}
    obs.event("clean")
    assert "policy" not in obs.events()[-1]["args"]


def test_span_success_has_no_error_attr():
    with obs.span("fine"):
        pass
    assert "error" not in obs.events()[0]["args"]


def test_span_duration_feeds_latency_histogram_with_string_labels_only():
    with obs.span("plan", policy="lbcd", t0=3):
        pass
    h = obs.registry().get("plan.seconds", policy="lbcd")  # t0 not a label
    assert h is not None and h.count == 1
    assert obs.events()[0]["args"] == {"policy": "lbcd", "t0": 3}


def test_event_bumps_count_counter():
    with obs.label_context(family="outage"):
        obs.event("service.early_replan", policy="lbcd", t=4)
        obs.event("service.early_replan", policy="lbcd", t=5)
    c = obs.registry().get("service.early_replan.count",
                           policy="lbcd", family="outage")
    assert c is not None and c.value == 2.0


# ---------------------------------------------------------------------------
# Exporters + artifacts + report round trip
# ---------------------------------------------------------------------------

def test_prometheus_text_exposition():
    obs.counter("plan.count", policy="lbcd").inc(3)
    obs.gauge("service.divergence", policy="lbcd").set(-0.25)
    obs.histogram("plan.seconds", policy="lbcd").observe_many(
        [0.01, 0.02, 0.04])
    txt = obs.prometheus_text()
    assert 'repro_plan_count_total{policy="lbcd"} 3' in txt
    assert 'repro_service_divergence{policy="lbcd"} -0.25' in txt
    assert 'repro_plan_seconds_count{policy="lbcd"} 3' in txt
    assert 'quantile="0.99"' in txt
    assert "# TYPE repro_plan_seconds summary" in txt
    # Every line is `# ...` or `name{labels} value`.
    for line in txt.strip().splitlines():
        if not line.startswith("#"):
            name_part, val = line.rsplit(" ", 1)
            float(val)
            assert name_part.startswith("repro_")


def test_artifacts_and_report_round_trip(tmp_path):
    run_dir = str(tmp_path / "run0")
    obs.configure(run_dir=run_dir)
    with obs.label_context(policy="lbcd", family="steady_ar1"):
        for reason in ("boundary", "early"):
            with obs.span("service.plan_window", reason=reason):
                pass
        with obs.span("service.run_epoch"):
            pass
        obs.event("service.early_replan", t=1)
        obs.gauge("service.divergence").set(0.1)
    paths = obs.write_artifacts()
    # Streamed JSONL and the snapshot artifacts agree.
    streamed = [json.loads(line)
                for line in open(paths["trace_jsonl"]) if line.strip()]
    assert [e["name"] for e in streamed] == \
        [e["name"] for e in obs.events()]
    chrome = json.load(open(paths["chrome_trace"]))
    assert len(chrome["traceEvents"]) == len(streamed)
    assert all(ev["ts"] >= 0 for ev in chrome["traceEvents"])
    for line in open(paths["metrics_jsonl"]):
        json.loads(line)
    assert "repro_service_plan_window_seconds" in \
        open(paths["prometheus"]).read()
    # The module dashboard renders from the files alone.
    txt = report.build_report(report.load_events(run_dir),
                              report.load_metrics(run_dir))
    assert "lbcd" in txt and "steady_ar1" in txt
    assert "plans/s" in txt and "p99 replan" in txt
    assert "COUNTER MISMATCH" not in txt


def test_report_flags_counter_mismatch():
    events = [{"ph": "i", "name": "service.early_replan", "ts": 0.0,
               "dur": 0.0, "args": {"policy": "lbcd", "family": "f"}}]
    mets = [{"name": "service.early_replan.count", "type": "counter",
             "labels": {"policy": "lbcd", "family": "f"}, "value": 3.0}]
    assert "[COUNTER MISMATCH]" in report.build_report(events, mets)
    mets[0]["value"] = 1.0
    assert "MISMATCH" not in report.build_report(events, mets)


# ---------------------------------------------------------------------------
# Hot-path instrumentation: solve_slot host dispatches
# ---------------------------------------------------------------------------

def test_solve_slot_concrete_dispatch_records_timed_span():
    from repro.core import lbcd, profiles
    system = profiles.EdgeSystem(n_cameras=3, n_servers=2, n_slots=4,
                                 seed=0)
    ctrl = lbcd.LBCDController(system, v=10.0, p_min=0.6)
    ctrl.step(0)                    # virtual + per-server solve: 2 calls
    h = obs.registry().get("bcd.solve_slot.seconds", solver_backend="jnp")
    assert h is not None and h.count == 2
    spans = [e for e in obs.events() if e["name"] == "bcd.solve_slot"]
    assert len(spans) == 2
    assert all(e["args"]["n_cameras"] == 3 for e in spans)


# ---------------------------------------------------------------------------
# The reconciliation contract (tentpole acceptance)
# ---------------------------------------------------------------------------

def test_forced_replan_reconciles_obs_with_legacy_lists_all_families():
    """Forced-replan replay (hair-trigger ``replan_threshold``) over every
    registered family: the ``service.early_replan`` counter, the instant
    trace events, the ``reason="early"`` plan spans, and the legacy
    ``AnalyticsService.early_replans`` list must agree exactly — and the
    divergence series through the registry must match ``svc.divergences``.
    """
    s = scenarios.suite(**DIMS)
    fams = sorted(set(s.families))
    assert len(fams) >= 6
    n_epochs = 4
    reps = {}
    for i in range(s.n_scenarios):
        one = jax.tree.map(lambda x, i=i: x[i], s.tables)
        with obs.label_context(family=s.families[i], scenario=s.names[i]):
            reps[(s.families[i], s.names[i])] = replay.replay_tables(
                one, "lbcd", n_epochs=n_epochs, plan_window=2,
                replan_threshold=1e-9, epoch_duration=300.0)

    events = obs.events()
    reg = obs.registry()
    total_replans = 0
    for (fam, name), rep in reps.items():
        svc = rep.service
        n = len(svc.early_replans)
        assert n > 0, f"{name}: threshold 1e-9 must force replans"
        total_replans += n
        labels = dict(policy="lbcd", delay_model="mm1",
                      family=fam, scenario=name)
        evs = [e for e in events if e["args"].get("scenario") == name]

        # 1. instant events == legacy list (same epochs, same order)
        replan_evs = [e for e in evs
                      if e["name"] == report.REPLAN_EVENT]
        assert [e["args"]["t"] for e in replan_evs] == svc.early_replans

        # 2. registry counter == trace stream == legacy list
        c = reg.get(report.REPLAN_EVENT + ".count", **labels)
        assert c is not None and c.value == len(replan_evs) == n

        # 3. the NEXT plan span after each trigger carries reason="early"
        plan_spans = [e for e in evs if e["name"] == report.PLAN_SPAN]
        early = [e for e in plan_spans
                 if e["args"].get("reason") == "early"]
        assert len(early) == n
        assert plan_spans[0]["args"]["reason"] == "boundary"

        # 4. divergence series through the registry matches the list
        divs = svc.divergences
        assert reg.get("service.epochs", **labels).value == len(divs) \
            == n_epochs
        assert len([e for e in evs
                    if e["name"] == report.EPOCH_SPAN]) == n_epochs
        g = reg.get("service.divergence", **labels)
        assert g.value == pytest.approx(float(divs[-1]))

    assert reg.total(report.REPLAN_EVENT + ".count") == total_replans

    # The dashboard renders this run with per policy x family rows and no
    # reconciliation flag (the acceptance criterion's report source).
    txt = report.build_report(events, reg.snapshot())
    assert "COUNTER MISMATCH" not in txt
    for fam in fams:
        assert fam in txt
    row = [ln for ln in txt.splitlines() if fams[0] in ln][0]
    assert "ms" in row                     # plan latency columns rendered


def test_run_metadata_carries_obs_snapshot():
    import benchmarks.common as common
    obs.counter("queues.batch_dispatches", delay_model="mm1").inc(4)
    meta = common.run_metadata()
    assert meta["obs"]["enabled"] is True
    m = meta["obs"]["metrics"]["queues.batch_dispatches"]
    assert m["total"] == 4.0
    assert json.dumps(meta, default=float)   # JSON-serializable stamp


# ---------------------------------------------------------------------------
# Phase spans of the planner and the data plane
# ---------------------------------------------------------------------------

PHASES = ("planner.tables", "planner.dispatch", "planner.fetch",
          "data_plane.inputs", "data_plane.wait", "data_plane.fetch")


def _serve_two_plan_windows():
    """Four epochs of the scan planner at plan window 2: two plan windows,
    each planned once and measured in one data-plane dispatch."""
    from repro.core import lbcd, profiles
    from repro.serving import AnalyticsService
    system = profiles.EdgeSystem(n_cameras=4, n_servers=2, n_slots=8,
                                 seed=0)
    svc = AnalyticsService(lbcd.LBCDController(system, v=10.0, p_min=0.6),
                           mode="mm1", plan_window=2, frames_cap=2000)
    svc.run(4)
    return svc


@pytest.fixture(scope="module")
def served():
    obs.reset()
    obs.configure(enabled=True)
    svc = _serve_two_plan_windows()
    return svc, obs.events()


def _by_id(events):
    return {e["id"]: e for e in events}


def test_each_plan_window_records_each_phase_span_once(served):
    _, events = served
    windows = [e for e in events if e["name"] == report.PLAN_SPAN]
    assert len(windows) == 2
    for name in PHASES:
        assert sum(e["name"] == name for e in events) == 2, name
    by_id = _by_id(events)
    for w in windows:
        kids = [e["name"] for e in events if e["parent"] == w["id"]]
        assert sorted(kids) == ["planner.dispatch", "planner.fetch",
                                "planner.tables"]
        assert by_id[w["parent"]]["name"] == report.EPOCH_SPAN


def test_phase_spans_lie_inside_their_parents(served):
    _, events = served
    by_id = _by_id(events)
    parent_of = {"planner.tables": report.PLAN_SPAN,
                 "planner.dispatch": report.PLAN_SPAN,
                 "planner.fetch": report.PLAN_SPAN,
                 "data_plane.inputs": report.EPOCH_SPAN,
                 report.MEASURE_SPAN: report.EPOCH_SPAN,
                 "queues.gi_g1_window": report.MEASURE_SPAN,
                 "data_plane.wait": "queues.gi_g1_window",
                 "data_plane.fetch": "queues.gi_g1_window"}
    for e in events:
        if e["name"] not in parent_of:
            continue
        p = by_id[e["parent"]]
        assert p["name"] == parent_of[e["name"]], e["name"]
        assert p["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= p["ts"] + p["dur"]
    # The plane's inputs and its dispatch are siblings, in that order.
    for e in events:
        if e["name"] == "data_plane.inputs":
            sib = [s for s in events if s["parent"] == e["parent"]
                   and s["name"] == report.MEASURE_SPAN]
            assert len(sib) == 1 and sib[0]["ts"] >= e["ts"] + e["dur"]


def test_fetch_spans_count_the_leaves_and_bytes_copied(served):
    svc, events = served
    leaves = jax.tree.leaves(svc._plan)
    assert len(leaves) == 14
    fetch = [e for e in events if e["name"] == "planner.fetch"]
    for e in fetch:
        assert e["args"]["leaves"] == len(leaves)
        assert e["args"]["bytes"] == sum(x.nbytes for x in leaves)
    plane = [e for e in events if e["name"] == "data_plane.fetch"]
    for e in plane:
        # aopi, horizon, n_frames, n_completed, n_accurate: [2, 4] each,
        # float64 at 2000 frames (past the float32 switch point).
        assert e["args"] == {"leaves": 5, "bytes": 5 * 2 * 4 * 8}


def _dispatches_per_window(events):
    """``(data_plane.inputs dispatches, queues.gi_g1_window calls)`` of
    each epoch that measured a plan window."""
    by_id = _by_id(events)
    out = []
    for e in events:
        if e["name"] != "data_plane.inputs":
            continue
        calls = sum(1 for g in events if g["name"] == "queues.gi_g1_window"
                    and by_id[g["parent"]]["parent"] == e["parent"])
        out.append((e["args"]["dispatches"], calls))
    return out


def test_dispatch_spans_name_the_fleet_and_its_resolved_solver(served):
    _, events = served
    dispatch = [e for e in events if e["name"] == "planner.dispatch"]
    assert len(dispatch) == 2
    for e in dispatch:
        assert e["args"] == {"k": 2, "backend": "jnp", "first_fit": "jnp",
                             "n_cameras": 4, "n_servers": 2}


@pytest.mark.parametrize("n_cameras,first_fit",
                         [(30, "jnp"), (128, "pallas")])
def test_dispatch_spans_name_the_first_fit_the_plan_runs(n_cameras,
                                                         first_fit):
    """Under ``auto`` the rollout places cameras with the first-fit
    kernel from ``bcd.AUTO_PALLAS_MIN_CAMERAS`` cameras, with the XLA
    loop below; the span says which."""
    from repro.core import lbcd, profiles
    from repro.serving import AnalyticsService
    system = profiles.EdgeSystem(n_cameras=n_cameras, n_servers=3,
                                 n_slots=2, seed=0)
    svc = AnalyticsService(
        lbcd.LBCDController(system, v=10.0, p_min=0.6,
                            solver_backend="auto"),
        mode="mm1", plan_window=1)
    svc.plan_horizon(1)
    (e,) = [e for e in obs.events() if e["name"] == "planner.dispatch"]
    assert e["args"]["first_fit"] == first_fit
    assert e["args"]["backend"] == first_fit


def test_inputs_spans_count_the_data_plane_dispatches(served):
    _, events = served
    assert _dispatches_per_window(events) == [(1, 1), (1, 1)]


def test_inputs_spans_count_a_window_split_into_dispatches(monkeypatch):
    from repro.serving import service
    # Room for one epoch of 4 streams at the 2000-frame cap: each plan
    # window of 2 epochs takes two dispatches.
    monkeypatch.setattr(service, "MAX_BATCH_ELEMS", 4 * 2000)
    _serve_two_plan_windows()
    assert _dispatches_per_window(obs.events()) == [(2, 2), (2, 2)]


def test_phase_spans_are_not_recorded_when_disabled():
    obs.configure(enabled=False)
    _serve_two_plan_windows()
    assert obs.events() == []
    assert len(obs.registry()) == 0


def test_a_compile_is_an_event_under_the_open_span():
    f = jax.jit(lambda x: 3.0 * x + 1.0)
    x = np.arange(4.0, dtype=np.float32)
    with obs.span("step") as step:
        f(x)
    with obs.span("again"):
        f(x)
    compiles = [e for e in obs.events() if e["name"] == "jax.compile"]
    assert len(compiles) == 1
    assert compiles[0]["parent"] == step.sid
    assert compiles[0]["args"]["seconds"] > 0.0
    assert obs.registry().total("jax.compile.count") == 1.0
    txt = report.build_report(obs.events(), obs.registry().snapshot())
    assert "compiles: 1," in txt
    assert "under step: 1," in txt


def test_span_annotations_share_the_profiler_clock(tmp_path):
    """Each span is a host-plane annotation of the same name, nesting and
    duration as its obs event, on a clock that differs from obs's by one
    constant: an idle gap put down to a span's name lies in the interval
    the span timed."""
    import time

    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("clock.outer"):
            time.sleep(0.002)
            with obs.span("clock.inner"):
                time.sleep(0.003)
            time.sleep(0.001)
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("clock.outer", "clock.inner"):
                    found[ev.name] = (line.name, ev.start_ns * 1e-9,
                                      ev.duration_ns * 1e-9)
    evs = {e["name"]: e for e in obs.events()}
    assert set(found) == set(evs) == {"clock.outer", "clock.inner"}
    assert evs["clock.inner"]["parent"] == evs["clock.outer"]["id"]
    (line_o, t_o, d_o), (line_i, t_i, d_i) = (found["clock.outer"],
                                              found["clock.inner"])
    assert line_o == line_i
    assert t_o <= t_i and t_i + d_i <= t_o + d_o
    for name, (_, _, dur) in found.items():
        assert abs(dur - evs[name]["dur"]) <= max(50e-6,
                                                  0.05 * evs[name]["dur"])
    lead = evs["clock.inner"]["ts"] - evs["clock.outer"]["ts"]
    assert abs((t_i - t_o) - lead) <= max(50e-6, 0.05 * lead)
