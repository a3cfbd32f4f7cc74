"""``repro.faults``: the declarative fault-injection plane.

Covers the tentpole acceptance criteria: per-kind deterministic RNG
streams, churn masks threaded exactly-zero through every rollout engine
and the water-fill, the ``faults=None`` bitwise no-op pin, the
graceful-degradation ladder (retry -> stale plan -> MIN fallback) with
obs counters reconciling against the legacy lists, telemetry-fault
gating, zero-rate guards in the queue/AoPI layers, and the suite-level
failure isolation of ``sweep``/``replay_suite``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, scenarios
from repro.core import allocate, aopi, baselines, lbcd, queues
from repro.faults import (FaultPlan, FaultSpec, InjectedSolverFault,
                          SOLVER_KINDS, apply_plan, storm_plan)
from repro.serving import AnalyticsService, replay
from repro.serving.replay import TableSystem, replay_suite, replay_tables

DIMS = dict(n_cameras=4, n_slots=12, n_servers=2,
            mean_bandwidth_hz=15e6, mean_compute_flops=20e12)


def _tables(name="steady_ar1", **kw):
    return scenarios.build(name, **{**DIMS, **kw})


@pytest.fixture(autouse=True)
def _isolated_obs():
    obs.reset()
    yield
    obs.reset()


# ---------------------------------------------------------------------------
# FaultSpec / FaultPlan units
# ---------------------------------------------------------------------------

def test_fault_spec_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("cosmic_ray")


def test_fault_spec_window_clamps_to_horizon():
    assert FaultSpec("server_crash", t0=3, duration=4).window(5) == (3, 5)
    assert FaultSpec("server_crash", t0=2).window(10) == (2, 10)  # open end
    assert FaultSpec("server_crash", t0=2, duration=3).active_at(4)
    assert not FaultSpec("server_crash", t0=2, duration=3).active_at(5)


def test_per_kind_rng_streams_are_independent():
    churn_only = FaultPlan((FaultSpec("camera_churn", params={
        "fraction": 0.5}),), seed=7)
    with_fade = FaultPlan((FaultSpec("camera_churn", params={
        "fraction": 0.5}),
        FaultSpec("correlated_fade", params={"depth": 0.5}),), seed=7)
    a = churn_only.camera_active(20, 6)
    b = with_fade.camera_active(20, 6)
    # Adding a fade spec must not perturb the churn trajectory.
    np.testing.assert_array_equal(a, b)
    # Same (specs, seed) -> bitwise identical; different seed -> different.
    np.testing.assert_array_equal(a, churn_only.camera_active(20, 6))
    c = dataclasses.replace(churn_only, seed=8).camera_active(20, 6)
    assert not np.array_equal(a, c)


def test_camera_active_mask_shape_and_survivor_guarantee():
    plan = FaultPlan((FaultSpec("camera_churn", t0=2, params={
        "fraction": 0.9, "leave_prob": 0.5, "join_prob": 0.0}),), seed=0)
    act = plan.camera_active(30, 5)
    assert act.shape == (30, 5)
    assert set(np.unique(act)) <= {0.0, 1.0}
    np.testing.assert_array_equal(act[:2], 1.0)     # before t0: all live
    assert (act.sum(axis=1) >= 1.0).all()           # never an empty fleet
    assert act.min() == 0.0                         # churn actually bites


def test_camera_active_none_without_churn_specs():
    plan = FaultPlan((FaultSpec("server_crash"),), seed=0)
    assert plan.camera_active(10, 4) is None
    assert FaultPlan().camera_active(10, 4) is None


def test_capacity_factor_crash_and_fade():
    plan = FaultPlan((
        FaultSpec("server_crash", t0=3, duration=4,
                  params={"server": 1, "depth": 1.0}),
        FaultSpec("correlated_fade", t0=0, duration=None,
                  params={"fraction": 1.0, "depth": 0.6, "corr": 0.9}),
    ), seed=1)
    f = plan.capacity_factor(10, 2)
    assert f.shape == (10, 2)
    assert (f[3:7, 1] == 0.0).all()                 # crash zeroes server 1
    assert (f[:3, 1] > 0.0).all() and (f[7:, 1] > 0.0).all()
    # The fade squashes into (1 - depth, 1]; never negative, never > 1.
    assert (f >= 0.0).all() and (f <= 1.0).all()
    assert (f[:, 0] >= 1.0 - 0.6 - 1e-6).all()      # fade-only server


# ---------------------------------------------------------------------------
# apply_plan + the faults=None bitwise no-op pin
# ---------------------------------------------------------------------------

def test_apply_plan_none_returns_same_object():
    t = _tables()
    assert apply_plan(None, t) is t


def test_tables_without_active_carry_no_extra_leaf():
    # The parity mechanism: active=None adds NO pytree leaf, so every
    # maskless trace/jaxpr is structurally identical to a pre-fault-plane
    # build (6 leaves: acc, xi, size, eff, budgets_b, budgets_c).
    t = _tables()
    assert t.active is None
    assert len(jax.tree.leaves(t)) == 6
    assert len(jax.tree.leaves(_tables("camera_churn"))) == 7


def test_apply_plan_attaches_mask_and_floors_budgets():
    t = _tables()
    plan = FaultPlan((
        FaultSpec("camera_churn", t0=1, params={"fraction": 0.5}),
        FaultSpec("server_crash", t0=2, duration=4,
                  params={"server": 0, "depth": 1.0}),
    ), seed=0)
    out = apply_plan(plan, t)
    assert out is not t and t.active is None        # input untouched
    assert out.active is not None
    assert out.active.shape == (t.n_slots, t.n_cameras)
    # Crash scales budgets but the floor keeps every solver input finite
    # and positive.
    bb = np.asarray(out.budgets_b)
    assert (bb > 0.0).all()
    assert (bb[2:6, 0] < np.asarray(t.budgets_b)[2:6, 0]).all()


def test_apply_plan_intersects_existing_mask():
    t = _tables("camera_churn")
    assert t.active is not None
    plan = FaultPlan((FaultSpec("camera_churn", t0=0, params={
        "fraction": 0.5, "leave_prob": 0.3, "join_prob": 0.0}),), seed=3)
    out = apply_plan(plan, t)
    a0, a1 = np.asarray(t.active), np.asarray(out.active)
    assert (a1 <= a0 + 1e-9).all()                  # only ever removes


def test_replay_faults_none_bitwise_equals_omitted_kwarg():
    t = _tables()
    a = replay_tables(t, "lbcd", plan_window=4)
    b = replay_tables(t, "lbcd", plan_window=4, faults=None)
    np.testing.assert_array_equal(a.measured, b.measured)
    np.testing.assert_array_equal(a.predicted, b.predicted)
    np.testing.assert_array_equal(a.acc, b.acc)
    assert b.service.fallbacks == [] and b.service.degraded_epochs == []
    assert b.service.telemetry_gaps == [] and b.service.plan_failures == []


# ---------------------------------------------------------------------------
# Churn mask through the rollout engines and the water-fill
# ---------------------------------------------------------------------------

ROLLOUTS = {
    "lbcd": lambda t: lbcd.rollout(t, 10.0, 0.7),
    "min": baselines.rollout_min,
    "dos": baselines.rollout_dos,
    "jcab": baselines.rollout_jcab,
}


@pytest.mark.parametrize("policy", sorted(ROLLOUTS))
def test_rollouts_zero_inactive_cameras_exactly(policy):
    t = _tables("camera_churn", params={"churn_fraction": 0.5,
                                        "leave_prob": 0.2})
    res = ROLLOUTS[policy](t)
    dead = np.asarray(t.active) == 0.0
    assert dead.any(), "scenario must actually churn cameras out"
    for name in ("aopi", "acc"):
        arr = np.asarray(getattr(res, name))
        assert np.isfinite(arr).all()
        np.testing.assert_array_equal(arr[dead], 0.0)
    for name in ("b", "c", "lam"):
        arr = np.asarray(getattr(res.decision, name))
        assert np.isfinite(arr).all()
        np.testing.assert_array_equal(arr[dead], 0.0)


def test_waterfill_redistributes_churned_budget_to_survivors():
    n = 6
    k = jnp.full(n, 2e-7)
    p = jnp.full(n, 0.8)
    pol = jnp.full(n, aopi.LCFSP, jnp.int32)
    mu = jnp.full(n, 20.0)
    sid = jnp.zeros(n, jnp.int32)
    budgets = jnp.array([30e6])
    b_all = allocate.waterfill_bandwidth(k, p, pol, mu, sid, budgets, 1)
    act = jnp.array([1.0, 1.0, 1.0, 0.0, 0.0, 0.0])
    b_half = allocate.waterfill_bandwidth(k, p, pol, mu, sid, budgets, 1,
                                          active=act)
    b_all, b_half = np.asarray(b_all), np.asarray(b_half)
    np.testing.assert_array_equal(b_half[3:], 0.0)  # exact zero, not tiny
    # The whole budget still gets used: the survivors' share grows to
    # absorb what the churned cameras forfeited.
    assert b_half[:3].sum() == pytest.approx(float(budgets[0]), rel=5e-2)
    assert (b_half[:3] > b_all[:3]).all()


def test_waterfill_compute_masks_fcfs_floor():
    n = 4
    inv_xi = jnp.full(n, 1e-12)
    p = jnp.full(n, 0.8)
    pol = jnp.full(n, aopi.FCFS, jnp.int32)
    lam = jnp.full(n, 10.0)
    sid = jnp.zeros(n, jnp.int32)
    budgets = jnp.array([40e12])
    act = jnp.array([1.0, 0.0, 1.0, 0.0])
    c = np.asarray(allocate.waterfill_compute(inv_xi, p, pol, lam, sid,
                                              budgets, 1, active=act))
    np.testing.assert_array_equal(c[[1, 3]], 0.0)
    assert (c[[0, 2]] > 0.0).all()                  # FCFS floor survives


def test_masked_aopi_closed_form():
    lam = jnp.array([0.0, 5.0, 5.0])
    mu = jnp.array([0.0, 10.0, 10.0])
    p = jnp.array([0.9, 0.9, 0.9])
    pol = jnp.array([1, 1, 1], jnp.int32)
    out = np.asarray(aopi.aopi_masked(lam, mu, p, pol))
    ref = np.asarray(aopi.aopi(lam[1:], mu[1:], p[1:], pol[1:]))
    assert out[0] == 0.0                            # dead lane: exact zero
    np.testing.assert_array_equal(out[1:], ref)     # live lanes: bit-exact
    # Explicit active mask kills an otherwise-live lane too.
    out2 = np.asarray(aopi.aopi_masked(lam, mu, p, pol,
                                       active=jnp.array([1.0, 0.0, 1.0])))
    assert out2[1] == 0.0 and out2[2] == ref[1]


# ---------------------------------------------------------------------------
# Zero-rate guards in the queue layer
# ---------------------------------------------------------------------------

def test_simulate_zero_rate_returns_finite_empty_result():
    for lam, mu in ((0.0, 5.0), (5.0, 0.0), (0.0, 0.0)):
        s = queues.simulate(lam, mu, 0.9, 0, n_frames=64)
        assert s.mean_aopi == 0.0 and s.n_frames == 0


def test_gi_g1_window_masks_dead_streams_bitwise():
    lam = np.array([[4.0, 5.0, 0.0]])
    mu = np.array([[8.0, 0.0, 9.0]])
    p = np.full((1, 3), 0.9)
    pol = np.array([[1, 1, 1]])
    out = queues.gi_g1_window(lam, mu, p, pol, n_frames=128, horizon=30.0)
    for v in out.values():
        assert np.isfinite(v).all()
        np.testing.assert_array_equal(v[0, 1:], 0.0)
    # Live lanes are bitwise unchanged vs an all-live call on the same
    # rates (masking happens on output only).
    solo = queues.gi_g1_window(lam[:, :1], mu[:, :1], p[:, :1], pol[:, :1],
                               n_frames=128, horizon=30.0)
    assert out["aopi"][0, 0] == solo["aopi"][0, 0]
    # An explicit active mask zeroes an otherwise-live stream.
    out2 = queues.gi_g1_window(lam, mu, p, pol, n_frames=128, horizon=30.0,
                               active=np.array([[0.0, 1.0, 1.0]]))
    assert out2["aopi"][0, 0] == 0.0


# ---------------------------------------------------------------------------
# Graceful-degradation ladder + exact obs reconciliation (tentpole)
# ---------------------------------------------------------------------------

def _ladder_replay(plan, **kw):
    t = _tables("camera_churn", n_slots=16)
    return replay_tables(t, "lbcd", plan_window=4, faults=plan, **kw)


def test_storm_engages_every_ladder_rung_and_reconciles():
    obs.configure(enabled=True)
    rep = _ladder_replay(storm_plan(16, seed=3))
    svc = rep.service
    assert np.isfinite(rep.measured).all()
    reasons = [r for _, r in svc.fallbacks]
    assert "min_fallback" in reasons                # t=0: no good plan yet
    assert "stale_plan" in reasons                  # later: tile last plan
    assert len(svc.plan_failures) > len(svc.fallbacks)  # retries happened
    assert svc.degraded_epochs and svc.telemetry_gaps
    # Every degraded epoch belongs to a window opened by some fallback.
    assert set(t for t, _ in svc.fallbacks) <= set(svc.degraded_epochs)

    evs = obs.events()

    def count(name):
        return sum(1 for e in evs if e.get("name") == name)

    def ctr(name):
        c = 0.0
        for m in obs.registry():
            if m.name == name:
                c += m.value
        return c

    for name, lst in (("service.fallback", svc.fallbacks),
                      ("service.degraded_epoch", svc.degraded_epochs),
                      ("service.plan_retry", svc.plan_failures),
                      ("service.telemetry_gap", svc.telemetry_gaps)):
        assert count(name) == len(lst)
        assert ctr(name + ".count") == len(lst)
    # Event epochs match the lists in order.
    assert [e["args"]["t"] for e in evs
            if e["name"] == "service.fallback"] == \
        [t for t, _ in svc.fallbacks]


def test_solver_nonconverge_single_attempt_recovers_by_retry():
    plan = FaultPlan((FaultSpec("solver_nonconverge", t0=0, duration=1),),
                     seed=0)
    rep = _ladder_replay(plan)
    svc = rep.service
    assert svc.plan_failures and svc.fallbacks == []
    assert svc.plan_failures[0][2].startswith("InjectedSolverFault")
    assert svc.degraded_epochs == []
    assert np.isfinite(rep.measured).all()


def test_retry_exhaustion_without_prior_plan_hits_min_fallback():
    plan = FaultPlan((FaultSpec("solver_nan", t0=0, duration=1,
                                params={"attempts": 64}),), seed=0)
    rep = _ladder_replay(plan, plan_retries=1)
    svc = rep.service
    assert svc.fallbacks[0] == (0, "min_fallback")
    assert len([f for f in svc.plan_failures if f[0] == 0]) == 2  # retries+1
    assert np.isfinite(rep.measured).all()


def test_stale_plan_rung_masks_churned_cameras():
    # Fail every attempt in the SECOND plan window only: the service tiles
    # the first window's last slot and re-projects it on the live fleet.
    plan = FaultPlan((FaultSpec("solver_nonconverge", t0=4, duration=4,
                                params={"attempts": 64}),), seed=0)
    rep = _ladder_replay(plan)
    svc = rep.service
    assert (4, "stale_plan") in svc.fallbacks
    assert set(range(4, 8)) <= set(svc.degraded_epochs)
    assert np.isfinite(rep.measured).all()


def test_plan_deadline_watchdog_trips_ladder():
    rep = _ladder_replay(None, plan_deadline=0.0)
    svc = rep.service
    assert svc.fallbacks and all(f[0] is not None for f in svc.fallbacks)
    assert all("TimeoutError" in err for _, _, err in svc.plan_failures)
    assert np.isfinite(rep.measured).all()


def _ladder_service(plan):
    t = _tables("camera_churn", n_slots=16)
    system = TableSystem(t)
    ctrl = replay.make_controller("lbcd", system)
    return AnalyticsService(ctrl, tables=system.horizon(16), plan_window=4,
                            faults=plan)


def test_unmodelled_planner_error_propagates(monkeypatch):
    """Only the modelled failures engage the ladder: any other exception
    out of ``plan_horizon`` (a device program that fails to lower,
    compile or run) leaves ``run_epoch`` and no fallback plan is served."""
    svc = _ladder_service(None)

    def broken(k, t0=0):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(svc, "plan_horizon", broken)
    with pytest.raises(RuntimeError, match="Mosaic"):
        svc.run_epoch(0)
    assert svc.plan_failures == [] and svc.fallbacks == []


def test_injected_solver_fault_walks_retry_stale_min():
    """An injected fault on every attempt walks the whole ladder: retries
    first, MIN with no good plan yet, the stale plan once one exists."""
    plan = FaultPlan((FaultSpec("solver_nonconverge", t0=0, duration=1,
                                params={"attempts": 64}),
                      FaultSpec("solver_nonconverge", t0=8, duration=4,
                                params={"attempts": 64})), seed=0)
    svc = _ladder_service(plan)
    svc.run(12)
    assert [f[1] for f in svc.plan_failures if f[0] == 0] == [0, 1, 2]
    assert svc.fallbacks == [(0, "min_fallback"), (8, "stale_plan")]
    assert all(err.startswith("InjectedSolverFault")
               for _, _, err in svc.plan_failures)


# ---------------------------------------------------------------------------
# Telemetry faults: drop / delay / corrupt + threshold widening
# ---------------------------------------------------------------------------

def test_telemetry_drop_holds_ewma_and_records_gap():
    plan = FaultPlan((FaultSpec("telemetry_drop", t0=2, duration=3),),
                     seed=0)
    t = _tables(n_slots=10)
    clean = replay_tables(t, "lbcd", plan_window=5, telemetry_gain=0.3)
    rep = replay_tables(t, "lbcd", plan_window=5, telemetry_gain=0.3,
                        faults=plan)
    assert rep.service.telemetry_gaps == [2, 3, 4]
    assert clean.service.telemetry_gaps == []
    assert np.isfinite(rep.measured).all()


def test_telemetry_corrupt_is_rejected_not_ingested():
    plan = FaultPlan((FaultSpec("telemetry_corrupt", t0=1, duration=2),),
                     seed=0)
    rep = replay_tables(_tables(n_slots=8), "lbcd", plan_window=4,
                        telemetry_gain=0.5, faults=plan)
    assert rep.service.telemetry_gaps == [1, 2]
    # NaN never reached the filter: all downstream plans stayed finite.
    assert np.isfinite(rep.measured).all()
    assert rep.service.fallbacks == []


def test_telemetry_delay_arrives_later():
    plan = FaultPlan((FaultSpec("telemetry_delay", t0=2, duration=1,
                                params={"delay": 2}),), seed=0)
    rep = replay_tables(_tables(n_slots=8), "lbcd", plan_window=4,
                        telemetry_gain=0.5, faults=plan)
    assert rep.service.telemetry_gaps == [2]        # gap at origin epoch
    assert np.isfinite(rep.measured).all()


def test_gap_streak_widens_replan_threshold():
    svc = replay_tables(_tables(n_slots=6), "lbcd", plan_window=3,
                        telemetry_gain=0.3, replan_threshold=0.2).service
    base = svc.replan_threshold
    svc._gap_streak = 4
    assert svc._effective_replan_threshold() == pytest.approx(base * 3.0)
    svc._gap_streak = 0
    assert svc._effective_replan_threshold() == pytest.approx(base)


# ---------------------------------------------------------------------------
# Suite-level failure isolation (satellite: sweep / replay_suite)
# ---------------------------------------------------------------------------

def test_replay_suite_isolates_failing_cell(monkeypatch):
    suite = scenarios.suite(["steady_ar1", "server_outage"], **DIMS)
    real = replay.replay_tables
    calls = []

    def boom(tables, policy="lbcd", **kw):
        calls.append(policy)
        if len(calls) == 1:
            raise RuntimeError("injected cell failure")
        return real(tables, policy, **kw)

    monkeypatch.setattr(replay, "replay_tables", boom)
    res = replay.replay_suite(suite, policies=("lbcd", "min"), n_epochs=4)
    assert len(res.errors) == 1
    (key, msg), = res.errors.items()
    assert msg == "RuntimeError: injected cell failure"
    bad_name, bad_policy = key
    assert np.isnan(
        res.measured[bad_policy][res.names.index(bad_name)]).all()
    # Every other cell replayed fine.
    for p in ("lbcd", "min"):
        ok = [i for i in range(len(res.names))
              if (res.names[i], p) not in res.errors]
        assert np.isfinite(res.measured[p][ok]).all()


def test_sweep_isolates_failing_policy(monkeypatch):
    from repro.scenarios import runner
    suite = scenarios.suite(["steady_ar1"], **DIMS)
    real = runner._run_vmap

    def boom(name, *a, **kw):
        if name == "jcab":
            raise RuntimeError("solver exploded")
        return real(name, *a, **kw)

    monkeypatch.setattr(runner, "_run_vmap", boom)
    res = scenarios.sweep(suite, backend="vmap")
    assert "jcab" in res.errors
    assert "solver exploded" in res.errors["jcab"]
    assert np.isnan(res.aopi["jcab"]).all()
    for p in ("lbcd", "min", "dos"):
        assert np.isfinite(res.aopi[p]).all()


# ---------------------------------------------------------------------------
# Window / TableSystem edge cases (satellite)
# ---------------------------------------------------------------------------

def test_horizon_window_rejects_out_of_range_and_empty():
    t = _tables()
    with pytest.raises(ValueError, match="outside horizon"):
        t.window(0, t.n_slots + 1)
    with pytest.raises(ValueError, match="outside horizon"):
        t.window(-1, 2)
    with pytest.raises(ValueError, match="outside horizon"):
        t.window(3, 3)                              # empty window
    w = t.window(2, 5)
    assert w.n_slots == 3


def test_table_system_rejects_stacked_suite_and_long_horizon():
    suite = scenarios.suite(["steady_ar1"], **DIMS)
    with pytest.raises(ValueError, match="ONE scenario"):
        TableSystem(suite.tables)
    sys1 = TableSystem(_tables())
    with pytest.raises(ValueError, match="exceeds the scenario"):
        sys1.horizon(DIMS["n_slots"] + 1)


def test_replay_tables_short_n_epochs_and_overrun():
    t = _tables()
    rep = replay_tables(t, "lbcd", n_epochs=3, plan_window=8)
    assert rep.measured.shape == (3,)               # window clamps to 3
    with pytest.raises(ValueError, match="exceeds the scenario"):
        replay_tables(t, "lbcd", n_epochs=DIMS["n_slots"] + 1)


# ---------------------------------------------------------------------------
# New scenario families + degradation report
# ---------------------------------------------------------------------------

def test_churn_and_fade_families_registered():
    fams = scenarios.families()
    assert "camera_churn" in fams and "correlated_fade" in fams
    t = _tables("camera_churn")
    assert t.active is not None and 0.0 < float(t.active.mean()) < 1.0
    t2 = _tables("correlated_fade")
    assert t2.active is None                        # fades touch budgets
    ref = _tables("steady_ar1")
    assert float(t2.budgets_b.mean()) < float(ref.budgets_b.mean())
    assert (np.asarray(t2.budgets_b) > 0.0).all()


def test_degradation_report_rows_and_recovery():
    suite = scenarios.suite(["steady_ar1"], **DIMS)
    rep = scenarios.degradation(
        suite, fault_kinds=("camera_churn", "solver_nonconverge"),
        policies=("min",), n_epochs=8, plan_window=4)
    rows = rep.rows()
    assert len(rows) == 2
    for row in rows:
        policy, kind, clean, faulted, ratio, recov, fb, degr, errs = row
        assert policy == "min" and np.isfinite(clean) and clean > 0
        assert np.isfinite(faulted) and errs == 0
        assert 0.0 <= recov <= 8
    by_kind = {r[1]: r for r in rows}
    assert by_kind["solver_nonconverge"][6] > 0     # fallbacks engaged
    txt = str(rep)
    assert "camera_churn" in txt and "ratio" in txt


def test_storm_plan_covers_every_kind():
    plan = storm_plan(18)
    kinds = {s.kind for s in plan.specs}
    from repro.faults import FAULT_KINDS
    assert kinds == set(FAULT_KINDS)
    assert {s.kind for s in storm_plan(18, solver=False).specs} == \
        set(FAULT_KINDS) - set(SOLVER_KINDS)
