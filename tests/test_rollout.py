"""Scan rollout engine: first-fit parity, legacy reproduction, vmap grids."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, binpack, lbcd, profiles


def _system(**kw):
    kw.setdefault("n_cameras", 12)
    kw.setdefault("n_servers", 3)
    kw.setdefault("n_slots", 40)
    kw.setdefault("mean_bandwidth_hz", 15e6)
    kw.setdefault("mean_compute_flops", 20e12)
    return profiles.EdgeSystem(**kw)


# ---------------------------------------------------------------------------
# first_fit_jax == first_fit
# ---------------------------------------------------------------------------

def test_first_fit_jax_matches_numpy_random_instances():
    """Property: the jit-safe first-fit reproduces the numpy assignment on
    random instances (feasible and overflowing)."""
    for seed in range(40):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(3, 25))
        s = int(rng.integers(2, 5))
        b_hat = rng.uniform(0.1, 2.0, n)
        c_hat = rng.uniform(0.1, 2.0, n)
        # Mix of roomy and tight instances (tight ones hit the overflow
        # branch, lines 6-8 of Algorithm 2).
        scale = rng.uniform(0.3, 1.5)
        B = rng.uniform(0.5, 1.0, s) * b_hat.sum() * scale
        C = rng.uniform(0.5, 1.0, s) * c_hat.sum() * scale
        ref = binpack.first_fit(b_hat, c_hat, B, C)
        jit = np.asarray(binpack.first_fit_jax(
            jnp.asarray(b_hat), jnp.asarray(c_hat), jnp.asarray(B),
            jnp.asarray(C)))
        np.testing.assert_array_equal(ref, jit, err_msg=f"seed={seed}")


def test_first_fit_jax_under_jit_and_float32():
    rng = np.random.default_rng(7)
    b_hat = rng.uniform(0.5, 2.0, 16).astype(np.float32)
    c_hat = rng.uniform(0.5, 2.0, 16).astype(np.float32)
    B = np.full(2, 12.0, np.float32)
    C = np.full(2, 12.0, np.float32)
    a = np.asarray(jax.jit(binpack.first_fit_jax)(b_hat, c_hat, B, C))
    for s in range(2):
        m = a == s
        assert b_hat[m].sum() <= B[s] + 1e-5
        assert c_hat[m].sum() <= C[s] + 1e-5


# ---------------------------------------------------------------------------
# rollout() reproduces LBCDController.run()
# ---------------------------------------------------------------------------

def test_rollout_reproduces_legacy_run():
    """The scan engine must reproduce the per-slot python loop's records
    (AoPI / accuracy / q series) to float tolerance."""
    slots = 25
    legacy = lbcd.LBCDController(_system(), v=10.0, p_min=0.7)
    s_legacy = legacy.run(slots, engine="legacy")

    scan = lbcd.LBCDController(_system(), v=10.0, p_min=0.7)
    s_scan = scan.run(slots)                      # engine="scan"

    np.testing.assert_allclose(s_scan.acc_series, s_legacy.acc_series,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s_scan.aopi_series, s_legacy.aopi_series,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(s_scan.q_series, s_legacy.q_series,
                               rtol=1e-4, atol=1e-4)
    # Same server placements, slot by slot.
    for a, b in zip(s_legacy.records, s_scan.records):
        np.testing.assert_array_equal(a.assign, b.assign)
    # The stateful wrapper carries the queue across run() calls identically.
    assert scan.queue.q == pytest.approx(legacy.queue.q, abs=1e-4)


def test_rollout_result_summary_consistency():
    tables = _system().horizon(10)
    res = lbcd.rollout(tables, 10.0, 0.7)
    summary = lbcd.summarize(res, 10.0, 0.7)
    assert len(summary.records) == 10
    assert summary.mean_aopi == pytest.approx(res.mean_aopi, rel=1e-6)
    # Records expose full decisions (serving/energy consumers rely on it).
    dec = summary.records[0].decision
    assert dec.b.shape == (tables.n_cameras,)


def test_baseline_rollouts_match_legacy_steps():
    for name in ("MIN", "DOS", "JCAB"):
        legacy = baselines.make(name, _system(seed=2)).run(
            12, engine="legacy")
        scan = baselines.make(name, _system(seed=2)).run(12)
        np.testing.assert_allclose(scan.aopi_series, legacy.aopi_series,
                                   rtol=2e-4, atol=1e-6, err_msg=name)
        np.testing.assert_allclose(scan.acc_series, legacy.acc_series,
                                   rtol=2e-4, atol=1e-6, err_msg=name)


# ---------------------------------------------------------------------------
# vmap
# ---------------------------------------------------------------------------

def test_rollout_grid_matches_individual_rollouts():
    """One vmapped grid call == per-point rollouts."""
    tables = _system().horizon(8)
    vs = jnp.asarray([1.0, 10.0, 100.0])
    p_mins = jnp.asarray([0.5, 0.7, 0.9])
    grid = lbcd.rollout_grid(tables, vs, p_mins)
    assert grid.aopi.shape == (3, 8, tables.n_cameras)
    for g in range(3):
        single = lbcd.rollout(tables, float(vs[g]), float(p_mins[g]))
        np.testing.assert_allclose(np.asarray(grid.q[g]),
                                   np.asarray(single.q), rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(grid.aopi[g]),
                                   np.asarray(single.aopi), rtol=1e-4,
                                   atol=1e-6)


def test_rollout_scenarios_over_stacked_horizons():
    stacked = profiles.stack_horizons(
        [_system(seed=i).horizon(6) for i in range(3)])
    res = lbcd.rollout_scenarios(stacked, 10.0, 0.7)
    assert res.acc.shape[0] == 3
    # Scenarios differ (different seeds) but each meets basic sanity.
    assert np.isfinite(np.asarray(res.aopi)).all()
    assert (np.asarray(res.acc) > 0).all()


def test_time_varying_eff_matches_static_when_constant():
    """A broadcast eff[T, N] must reproduce the static eff[N] rollout
    exactly, for the LBCD engine and every baseline scan."""
    import dataclasses

    from repro.core import baselines as bl

    tables = _system().horizon(6)
    tv = dataclasses.replace(
        tables, eff=jnp.broadcast_to(tables.eff[None, :],
                                     (6, tables.n_cameras)))
    for name, fn in [("lbcd", lambda t: lbcd.rollout(t, 10.0, 0.7)),
                     ("min", bl.rollout_min),
                     ("dos", bl.rollout_dos),
                     ("jcab", bl.rollout_jcab)]:
        a, b = fn(tables), fn(tv)
        np.testing.assert_array_equal(np.asarray(a.aopi),
                                      np.asarray(b.aopi), err_msg=name)
        np.testing.assert_array_equal(np.asarray(a.assign),
                                      np.asarray(b.assign), err_msg=name)


def test_horizon_tables_match_legacy_tables():
    """horizon() pregenerates exactly what sequential tables(t) would."""
    sys_a = _system(seed=5)
    sys_b = _system(seed=5)
    hor = sys_a.horizon(4)
    for t in range(4):
        legacy = sys_b.tables(t)
        np.testing.assert_allclose(np.asarray(hor.acc[t]), legacy.acc,
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(hor.eff), legacy.eff,
                                   rtol=1e-6)
        bb, bc = sys_b.capacities(t)
        np.testing.assert_allclose(np.asarray(hor.budgets_b[t]), bb,
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(hor.budgets_c[t]), bc,
                                   rtol=1e-6)


@pytest.mark.parametrize("short,long", [(8, 24), (24, 100)],
                         ids=["inside", "past-n_slots"])
@pytest.mark.parametrize("n_cameras,n_servers", [(30, 3), (12, 2)])
@pytest.mark.parametrize("seed", range(3))
def test_edge_system_horizon_is_prefix_stable(seed, n_cameras, n_servers,
                                              short, long):
    """horizon(short) is bitwise the first ``short`` slots of horizon(long),
    also past the system's own n_slots, where the capacity traces wrap.
    The service's source-horizon cache (``_base_window``) grows on this."""
    system = _system(n_cameras=n_cameras, n_servers=n_servers, n_slots=24,
                     seed=seed)
    a, b = system.horizon(short), system.horizon(long)
    assert a.n_slots == short and b.n_slots == long
    assert a.active is None and b.active is None
    for name in ("acc", "budgets_b", "budgets_c"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name))[:short],
                                      err_msg=name)
    for name in ("xi", "size", "eff"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=name)
