"""Data-plane replay: measured AoPI vs Theorems 1-2, determinism, and the
scan-engine serving planner (``AnalyticsService.plan_horizon``)."""
import jax
import numpy as np
import pytest

from repro import scenarios
from repro.core import aopi, binpack, lbcd, profiles
from repro.serving import replay, service
from repro.serving.service import AnalyticsService

DIMS = dict(n_cameras=5, n_slots=12, n_servers=2,
            mean_bandwidth_hz=15e6, mean_compute_flops=20e12)


# ---------------------------------------------------------------------------
# Statistical parity: the M/M/1 data plane converges to Theorems 1-2
# ---------------------------------------------------------------------------

def _measure_one(lam, mu, p, pol, seed, epoch_duration=40_000.0):
    meas, tel = service.measure_mm1(
        np.array([lam]), np.array([mu]), np.array([p]),
        np.array([pol], np.int32), epoch_duration=epoch_duration,
        frames_cap=400_000, seed=seed)
    return float(meas[0]), tel


@pytest.mark.parametrize("rho,pol,p", [
    (0.5, aopi.FCFS, 0.8), (0.5, aopi.LCFSP, 0.8),
    (0.75, aopi.FCFS, 0.6), (0.25, aopi.LCFSP, 0.9)])
def test_mm1_measurement_matches_closed_forms(rho, pol, p):
    """Always-run anchor points of the hypothesis sweep below (both
    policies, low/mid/high load)."""
    mu = 10.0
    meas, _ = _measure_one(rho * mu, mu, p, pol, seed=11)
    assert meas == pytest.approx(float(aopi.aopi(rho * mu, mu, p, pol)),
                                 rel=0.1)


def test_mm1_measurement_matches_closed_forms_hypothesis():
    """Measured AoPI from the event-driven plane == Theorem 1 (FCFS) /
    Theorem 2 (LCFSP) within CI bounds, over load factors and policies."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(st.sampled_from([0.25, 0.5, 0.75]),
           st.sampled_from([aopi.FCFS, aopi.LCFSP]),
           st.sampled_from([0.45, 0.7, 0.9]),
           st.integers(0, 10_000))
    def inner(rho, pol, p, seed):
        mu = 10.0
        lam = rho * mu
        th = float(aopi.aopi(lam, mu, p, pol))
        meas, tel = _measure_one(lam, mu, p, pol, seed)
        # ~100-300k frames per draw: the sample mean's CI is a few percent.
        assert meas == pytest.approx(th, rel=0.1)
        # Telemetry sanity: unbiased plane, so measured rates track inputs.
        assert tel.acc_hat[0] == pytest.approx(p, abs=0.05)
        assert tel.lam_hat[0] == pytest.approx(lam, rel=0.05)

    inner()


def test_steady_replay_statistical_parity():
    """Fig. 14/15 at suite scale: replaying the steady AR(1) family, the
    plane's measured AoPI converges to the planner's closed form."""
    tab = scenarios.build("steady_ar1", DIMS)
    rep = replay.replay_tables(tab, "lbcd", epoch_duration=2000.0, seed=0)
    assert rep.measured.shape == rep.predicted.shape == (DIMS["n_slots"],)
    # Horizon mean within CI; every epoch individually close.
    assert rep.measured.mean() == pytest.approx(rep.predicted.mean(),
                                                rel=0.1)
    np.testing.assert_allclose(rep.measured, rep.predicted, rtol=0.3)
    # Per-stream agreement on average across epochs.
    ratio = np.concatenate(
        [r.per_stream_measured / np.maximum(r.per_stream_predicted, 1e-9)
         for r in rep.service.reports])
    assert np.median(ratio) == pytest.approx(1.0, abs=0.15)


# ---------------------------------------------------------------------------
# Determinism
# ---------------------------------------------------------------------------

def test_replay_is_bitwise_deterministic():
    tab = scenarios.build("gilbert_elliott", DIMS)
    kw = dict(n_epochs=6, epoch_duration=600.0, seed=3)
    a = replay.replay_tables(tab, "lbcd", **kw)
    b = replay.replay_tables(tab, "lbcd", **kw)
    np.testing.assert_array_equal(a.measured, b.measured)
    np.testing.assert_array_equal(a.predicted, b.predicted)
    c = replay.replay_tables(tab, "lbcd", n_epochs=6,
                             epoch_duration=600.0, seed=4)
    assert not np.array_equal(a.measured, c.measured)


# ---------------------------------------------------------------------------
# Scan-engine planner
# ---------------------------------------------------------------------------

def _service(plan_window=6, **kw):
    system = profiles.EdgeSystem(n_cameras=4, n_servers=2, n_slots=12,
                                 seed=7)
    ctrl = lbcd.LBCDController(system, v=10.0, p_min=0.6)
    return AnalyticsService(ctrl, mode="mm1", epoch_duration=400.0,
                            plan_window=plan_window, **kw), system, ctrl


def test_plan_horizon_matches_rollout():
    """The planner window IS one ``lbcd.rollout`` call on the horizon."""
    svc, system, ctrl = _service()
    res = svc.plan_horizon(6)
    direct = lbcd.rollout(system.horizon(6), ctrl.v, ctrl.queue.p_min,
                          q0=0.0)
    for got, want in zip(jax.tree.leaves(res), jax.tree.leaves(direct)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)


def _top_level_eqns(jaxpr):
    """Descend through single-eqn jit wrappers to the body jaxpr."""
    while len(jaxpr.eqns) == 1 and jaxpr.eqns[0].primitive.name == "jit":
        jaxpr = jaxpr.eqns[0].params["jaxpr"].jaxpr
    return jaxpr.eqns


def test_planner_is_single_scan_no_python_loop():
    """Jaxpr structure of the planner path: ONE lax.scan over the epochs
    at the top level, and an eqn count independent of the window length
    (a per-epoch Python loop would grow it linearly)."""
    system = profiles.EdgeSystem(n_cameras=4, n_servers=2, n_slots=12,
                                 seed=7)

    def plan(tables):
        return lbcd.rollout(tables, 10.0, 0.6)

    short = jax.make_jaxpr(plan)(system.horizon(4))
    long = jax.make_jaxpr(plan)(system.horizon(8))
    for jaxpr in (short.jaxpr, long.jaxpr):
        eqns = _top_level_eqns(jaxpr)
        scans = [e for e in eqns if e.primitive.name == "scan"]
        assert len(scans) == 1, [e.primitive.name for e in eqns]
    assert len(_top_level_eqns(short.jaxpr)) == \
        len(_top_level_eqns(long.jaxpr))


def test_service_scan_planner_commits_queue_and_windows():
    """Window boundaries replan; the virtual queue follows Eq. 44 from the
    consumed plan epochs; scan and step planners see the same horizon."""
    svc, system, ctrl = _service(plan_window=3)
    assert svc.planner == "scan"
    reps = svc.run(5)                      # spans two plan windows
    assert svc._plan_t0 == 3               # second window started at t=3
    assert ctrl.queue.q == pytest.approx(reps[-1].q)
    # Custom assignment functions are not scan-able -> legacy fallback.
    ctrl2 = lbcd.LBCDController(system, v=10.0, p_min=0.6,
                                assign_fn=lambda *a: binpack.first_fit(*a))
    svc2 = AnalyticsService(ctrl2, mode="mm1")
    assert svc2.planner == "step"


def test_step_only_controller_falls_back_to_step_planner():
    """A controller that only implements step(t) (no _rollout override)
    must get the legacy planner, not a NotImplementedError mid-run."""
    from repro.core import baselines
    system = profiles.EdgeSystem(n_cameras=3, n_servers=2, n_slots=6,
                                 seed=1)

    class StepOnly(baselines.BaselineController):
        def step(self, t, tables=None):
            return baselines.MINController(self.system).step(t, tables)

    svc = AnalyticsService(StepOnly(system), mode="mm1",
                           epoch_duration=300.0)
    assert svc.planner == "step"
    rep = svc.run_epoch(0)
    assert rep.measured_aopi > 0


def test_plane_rates_use_truth_on_short_bounded_horizons():
    """A bounded horizon shorter than the default plan window must still
    serve the data plane the unscaled truth (not silently degrade to the
    planner's beliefs), and epochs past it must fail loudly."""
    tab = scenarios.build("steady_ar1", {**DIMS, "n_slots": 4})
    system = replay.TableSystem(tab)
    ctrl = lbcd.LBCDController(system, v=10.0, p_min=0.6,
                               assign_fn=lambda *a: binpack.first_fit(*a))
    svc = AnalyticsService(ctrl, mode="mm1", epoch_duration=300.0)
    assert svc.planner == "step"          # custom assign_fn
    svc.run_epoch(0)
    assert svc._base_cache is not None    # truth horizon was built
    with pytest.raises(ValueError, match="exceeds"):
        svc.run_epoch(9)


def test_horizonless_system_falls_back_to_step_planner():
    """Duck-typed systems exposing only capacities/tables (the historical
    AnalyticsService contract) must keep the legacy planner, not crash
    mid-run inside the horizon cache."""
    base = profiles.EdgeSystem(n_cameras=3, n_servers=2, n_slots=6, seed=2)

    class NoHorizon:
        n_cameras = base.n_cameras
        capacities = base.capacities
        tables = base.tables

    svc = AnalyticsService(lbcd.LBCDController(NoHorizon(), v=10.0,
                                               p_min=0.6),
                           mode="mm1", epoch_duration=300.0)
    assert svc.planner == "step"
    assert svc.run_epoch(0).measured_aopi > 0


def test_sweep_rejects_unknown_dataplane_params():
    s = scenarios.suite(["steady_ar1"], **{**DIMS, "n_slots": 4})
    with pytest.raises(ValueError, match="unknown dataplane_params.*epochs"):
        scenarios.sweep(s, dataplane=True,
                        dataplane_params=dict(epochs=2),
                        devices=jax.devices()[:1])


def test_baseline_controllers_ride_the_scan_planner():
    tab = scenarios.build("steady_ar1", DIMS)
    for policy in ("min", "dos", "jcab"):
        rep = replay.replay_tables(tab, policy, n_epochs=4,
                                   epoch_duration=400.0)
        svc = rep.service
        assert svc.planner == "scan"
        assert np.isfinite(rep.measured).all() and (rep.measured > 0).all()


# ---------------------------------------------------------------------------
# Telemetry feedback into the next planning window
# ---------------------------------------------------------------------------

def test_telemetry_scales_are_applied_to_window():
    svc, system, ctrl = _service(telemetry_gain=0.5)
    svc._acc_scale[:] = 0.8
    base = svc._base_window(0, 4)
    win = svc._window_tables(0, 4)
    np.testing.assert_allclose(
        np.asarray(win.acc),
        np.clip(np.asarray(base.acc) * 0.8, 1e-3, 1.0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(win.eff), np.asarray(base.eff),
                               rtol=1e-6)


def test_telemetry_pulls_biased_belief_back_to_truth():
    """Start the planner with a wrong link-efficiency belief; measured
    arrival rates must drag the scale back toward 1 (the truth)."""
    svc, system, ctrl = _service(plan_window=2, telemetry_gain=0.5)
    svc._eff_scale[:] = 0.6
    svc.run(8)
    assert (svc._eff_scale > 0.75).all()
    assert (svc._eff_scale < 1.4).all()
    # Gain 0 keeps beliefs frozen.
    svc0, *_ = _service(plan_window=2, telemetry_gain=0.0)
    svc0.run(4)
    np.testing.assert_array_equal(svc0._acc_scale, 1.0)


def test_replay_with_telemetry_replans_in_windows():
    """A feedback replay must replan so telemetry can re-enter: the
    default plan window shrinks below the horizon when gain > 0."""
    tab = scenarios.build("steady_ar1", DIMS)
    rep = replay.replay_tables(tab, "lbcd", epoch_duration=400.0,
                               telemetry_gain=0.5)
    assert rep.service.plan_window == min(8, DIMS["n_slots"])
    assert not np.array_equal(rep.service._acc_scale,
                              np.ones(DIMS["n_cameras"]))
    # Without feedback the whole horizon is one dispatch.
    rep0 = replay.replay_tables(tab, "lbcd", epoch_duration=400.0)
    assert rep0.service.plan_window == DIMS["n_slots"]


# ---------------------------------------------------------------------------
# Suite-level replay + the dataplane sweep (acceptance criterion)
# ---------------------------------------------------------------------------

def test_sweep_dataplane_reports_all_families():
    """sweep(dataplane=True) -> measured-vs-predicted robustness for every
    registered family."""
    s = scenarios.suite(**DIMS)
    n_replay = 4
    res = scenarios.sweep(
        s, v=10.0, p_min=0.7, devices=jax.devices()[:1], dataplane=True,
        dataplane_params=dict(n_epochs=n_replay, epoch_duration=400.0))
    k = s.n_scenarios
    for p in res.policies:
        assert res.measured_aopi[p].shape == (k, n_replay)
        assert res.predicted_aopi[p].shape == (k, n_replay)
        assert np.isfinite(res.measured_aopi[p]).all()
        assert (res.measured_aopi[p] > 0).all()
        assert np.isfinite(res.divergence(p)).all()
    rep = scenarios.robustness(res)
    assert rep.has_measured
    assert set(rep.families) == set(s.families)
    assert len(set(rep.families)) >= 6
    for p in res.policies:
        for f in rep.families:
            st = rep.table[p][f]
            assert st.measured_mean is not None and st.measured_mean > 0
            assert st.divergence is not None
        fam, div = rep.worst_divergence(p)
        assert fam in rep.families and np.isfinite(div)
    assert len(rep.rows()[0]) == 10
    txt = str(rep)
    assert "measured" in txt and "diverge" in txt
    # Truncated replay (4 of 12 slots) is flagged so the side-by-side
    # blocks are not read as covering the same epochs.
    assert rep.replay_slots == n_replay and rep.total_slots == 12
    assert f"first {n_replay}/12 slots" in txt


def test_sweep_without_dataplane_has_no_measured_columns():
    s = scenarios.suite(["steady_ar1"], **{**DIMS, "n_slots": 4})
    res = scenarios.sweep(s, devices=jax.devices()[:1])
    assert res.measured_aopi is None
    with pytest.raises(ValueError, match="dataplane"):
        res.divergence("lbcd")
    rep = scenarios.robustness(res)
    assert not rep.has_measured
    assert len(rep.rows()[0]) == 6
    with pytest.raises(ValueError, match="measured"):
        rep.worst_divergence("lbcd")


# ---------------------------------------------------------------------------
# Batched data plane on the hot path (acceptance criterion)
# ---------------------------------------------------------------------------

def test_replay_suite_hot_path_is_batched(monkeypatch):
    """``replay_suite`` over ALL registered families must never fall into
    per-stream Python-loop simulation: the numpy oracle is monkeypatched
    to explode, and the batched engine's dispatch counter must show
    exactly ONE device dispatch per (policy, scenario) plan window."""
    from repro.core import queues

    def _boom(*a, **k):
        raise AssertionError("per-stream loop simulation on the hot path")

    monkeypatch.setattr(queues, "simulate", _boom)
    monkeypatch.setattr(queues, "simulate_fcfs", _boom)
    monkeypatch.setattr(queues, "simulate_lcfsp", _boom)
    s = scenarios.suite(**DIMS)
    assert len(set(s.families)) >= 6
    before = queues.BATCH_DISPATCHES
    res = replay.replay_suite(s, n_epochs=3, epoch_duration=300.0)
    dispatches = queues.BATCH_DISPATCHES - before
    # telemetry_gain=0 -> one plan window per (policy, scenario), each
    # measured as one [E, N, F] dispatch.
    assert dispatches == s.n_scenarios * len(res.policies)
    for p in res.policies:
        assert np.isfinite(res.measured[p]).all()
        assert (res.measured[p] > 0).all()


# ---------------------------------------------------------------------------
# Non-exponential delay models: drift + telemetry closing the gap
# ---------------------------------------------------------------------------

def test_uniform_delays_drift_from_theorems_and_telemetry_closes_gap():
    """§III-B regime: uniform delays with M/M/1 means make measured AoPI
    diverge from the Theorem 1/2 predictions; with ``telemetry_gain > 0``
    the AoPI residual scale calibrates the next windows' predictions and
    shrinks the gap."""
    tab = scenarios.build("steady_ar1", DIMS)
    rep0 = replay.replay_tables(tab, "lbcd", epoch_duration=600.0, seed=0,
                                delay_model="uniform")
    div0 = rep0.service.divergences
    # mm1 replay of the same scenario stays unbiased...
    rep_mm1 = replay.replay_tables(tab, "lbcd", epoch_duration=600.0,
                                   seed=0)
    assert abs(np.mean(rep_mm1.service.divergences)) < 0.05
    # ...while the uniform plane visibly drifts from the closed forms.
    assert abs(np.mean(div0)) > 0.05
    # Telemetry feedback (replanning windows) calibrates the gap away.
    rep1 = replay.replay_tables(tab, "lbcd", epoch_duration=600.0, seed=0,
                                delay_model="uniform", telemetry_gain=0.7,
                                plan_window=2)
    tail0 = np.abs(div0[-4:]).mean()
    tail1 = np.abs(rep1.service.divergences[-4:]).mean()
    assert tail1 < tail0 * 0.6
    assert rep1.delay_model == "uniform"


def test_gamma_delays_drift_check():
    tab = scenarios.build("steady_ar1", {**DIMS, "n_slots": 6})
    rep = replay.replay_tables(tab, "lbcd", epoch_duration=600.0, seed=0,
                               delay_model="gamma")
    assert abs(np.mean(rep.service.divergences)) > 0.03
    assert np.isfinite(rep.measured).all()


def test_service_rejects_unknown_delay_model():
    system = profiles.EdgeSystem(n_cameras=3, n_servers=2, n_slots=6,
                                 seed=0)
    ctrl = lbcd.LBCDController(system, v=10.0, p_min=0.6)
    with pytest.raises(ValueError, match="delay_model"):
        AnalyticsService(ctrl, delay_model="pareto")
    # "auto" is a service-level sentinel, not a plane family: the service
    # accepts it (fitted selector), the plane does not.
    with pytest.raises(ValueError, match="delay_model"):
        service.measure_mm1(np.ones(1), np.ones(1), np.ones(1) * 0.5,
                            np.zeros(1), delay_model="auto")


# ---------------------------------------------------------------------------
# Divergence-triggered replanning
# ---------------------------------------------------------------------------

def test_divergence_triggered_replanning_cuts_windows():
    """With a hair-trigger threshold every epoch's (nonzero) divergence
    cuts the rest of the plan window, so the planner re-runs each epoch;
    without a threshold (or without remaining epochs) windows never cut."""
    svc, system, ctrl = _service(plan_window=6, telemetry_gain=0.3,
                                 replan_threshold=1e-9)
    svc.run(5)
    assert svc.early_replans == [1, 2, 3, 4, 5]
    assert svc._plan_t0 == 4               # replanned at every epoch
    # No threshold -> fixed windows (the PR-4 behaviour).
    svc0, *_ = _service(plan_window=6, telemetry_gain=0.3)
    svc0.run(5)
    assert svc0.early_replans == [] and svc0._plan_t0 == 0
    # A loose threshold on a well-modeled plane never triggers.
    svc1, *_ = _service(plan_window=6, replan_threshold=5.0)
    svc1.run(5)
    assert svc1.early_replans == []
    # A one-epoch window has nothing left to cut.
    svc2, *_ = _service(plan_window=1, replan_threshold=1e-9)
    svc2.run(3)
    assert svc2.early_replans == []


def test_replay_threads_replan_threshold():
    tab = scenarios.build("steady_ar1", {**DIMS, "n_slots": 6})
    rep = replay.replay_tables(tab, "lbcd", epoch_duration=400.0,
                               telemetry_gain=0.5, plan_window=4,
                               replan_threshold=1e-9)
    assert rep.service.early_replans != []
    assert np.isfinite(rep.measured).all()


# ---------------------------------------------------------------------------
# Per-delay-model divergence columns in the sweep/report
# ---------------------------------------------------------------------------

def test_sweep_dataplane_multi_delay_model():
    s = scenarios.suite(["steady_ar1", "server_outage"],
                        **{**DIMS, "n_slots": 4})
    res = scenarios.sweep(
        s, devices=jax.devices()[:1], dataplane=True,
        dataplane_params=dict(n_epochs=2, epoch_duration=300.0,
                              delay_model=("mm1", "uniform")))
    assert res.delay_models == ("mm1", "uniform")
    assert set(res.measured_by_model) == {"mm1", "uniform"}
    for p in res.policies:
        np.testing.assert_array_equal(res.measured_aopi[p],
                                      res.measured_by_model["mm1"][p])
        assert np.isfinite(res.divergence(p, "uniform")).all()
    with pytest.raises(ValueError, match="not replayed"):
        res.divergence("lbcd", "gamma")
    rep = scenarios.robustness(res)
    assert rep.delay_models == ("mm1", "uniform")
    # 6 closed-form + 4 measured + 1 extra divergence column.
    assert len(rep.rows()[0]) == 11
    for p in res.policies:
        for f in rep.families:
            dm = rep.table[p][f].divergence_models
            assert set(dm) == {"mm1", "uniform"}
            assert dm["mm1"] == pytest.approx(rep.table[p][f].divergence)
    txt = str(rep)
    assert "div:uniform" in txt and "delay model" in txt


def test_sweep_dataplane_single_uniform_model():
    s = scenarios.suite(["steady_ar1"], **{**DIMS, "n_slots": 4})
    res = scenarios.sweep(
        s, devices=jax.devices()[:1], dataplane=True,
        dataplane_params=dict(n_epochs=2, epoch_duration=300.0,
                              delay_model="uniform"))
    assert res.delay_models == ("uniform",)
    rep = scenarios.robustness(res)
    assert len(rep.rows()[0]) == 10        # no extra columns
    assert "delay model(s): uniform" in str(rep)


# ---------------------------------------------------------------------------
# TableSystem guard rails
# ---------------------------------------------------------------------------

def test_table_system_rejects_stacked_and_overlong():
    s = scenarios.suite(["steady_ar1", "server_outage"],
                        **{**DIMS, "n_slots": 4})
    with pytest.raises(ValueError, match="ONE scenario"):
        replay.TableSystem(s.tables)
    tab = scenarios.build("steady_ar1", {**DIMS, "n_slots": 4})
    sys_ = replay.TableSystem(tab)
    with pytest.raises(ValueError, match="exceeds"):
        sys_.horizon(9)
    with pytest.raises(ValueError, match="exceeds"):
        replay.replay_tables(tab, "lbcd", n_epochs=9)
    with pytest.raises(ValueError, match="unknown policy"):
        replay.replay_tables(tab, "nope")


def test_horizon_window_slices_time_axes():
    tab = scenarios.build("snr_mobility", DIMS)      # time-varying eff
    win = tab.window(3, 7)
    assert win.n_slots == 4
    np.testing.assert_array_equal(np.asarray(win.acc),
                                  np.asarray(tab.acc[3:7]))
    np.testing.assert_array_equal(np.asarray(win.eff),
                                  np.asarray(tab.eff[3:7]))
    np.testing.assert_array_equal(np.asarray(win.xi), np.asarray(tab.xi))
    static = scenarios.build("steady_ar1", DIMS)
    assert static.window(0, 5).eff.ndim == static.eff.ndim
    with pytest.raises(ValueError, match="window"):
        tab.window(8, 20)
