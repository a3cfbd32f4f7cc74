"""repro.scenarios: registry, determinism, generator properties, sweeps."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro import scenarios
from repro.core import profiles

DIMS = dict(n_cameras=5, n_slots=16, n_servers=2,
            mean_bandwidth_hz=15e6, mean_compute_flops=20e12)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_has_at_least_five_families():
    fams = scenarios.families()
    assert len(fams) >= 5
    assert len(scenarios.names()) >= len(fams)
    for name in scenarios.names():
        assert scenarios.family_of(name) in fams


def test_unknown_scenario_raises_with_known_names():
    with pytest.raises(KeyError, match="steady_ar1"):
        scenarios.build("no_such_scenario")


def test_overrides_reach_spec_fields_and_params():
    spec = scenarios.spec_for("server_outage",
                              {"n_cameras": 3, "degrade": 0.5})
    assert spec.n_cameras == 3
    assert spec.param("degrade", None) == 0.5
    assert spec.family == "server_outage"


# ---------------------------------------------------------------------------
# Determinism (satellite: same name + seed -> bitwise-identical tables)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["steady_ar1", "gilbert_elliott",
                                  "snr_mobility", "content_burst"])
def test_build_is_bitwise_deterministic(name):
    a = scenarios.build(name, DIMS)
    b = scenarios.build(name, DIMS)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_different_seed_changes_tables():
    a = scenarios.build("steady_ar1", DIMS)
    b = scenarios.build("steady_ar1", DIMS, seed=1)
    assert not np.array_equal(np.asarray(a.budgets_b),
                              np.asarray(b.budgets_b))


def test_horizon_is_deterministic_and_reset_replays():
    sys_a = profiles.EdgeSystem(n_cameras=4, n_servers=2, n_slots=10)
    h1 = sys_a.horizon(6)
    sys_a.advance_drift()              # perturb the stateful legacy RNG
    h2 = sys_a.horizon(6)              # horizon() must not care
    for la, lb in zip(jax.tree.leaves(h1), jax.tree.leaves(h2)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # reset() replays the legacy per-slot drift stream from the top.
    sys_b = profiles.EdgeSystem(n_cameras=4, n_servers=2, n_slots=10)
    first = sys_b.advance_drift().copy()
    again = sys_b.reset().advance_drift()
    np.testing.assert_array_equal(first, again)


def test_vectorized_trace_matches_reference_loop():
    """ar1_scan path == the historical per-slot python recursion."""
    rho, sigma, mean, shape = 0.85, 0.25, 5e6, (300, 3)
    ref_rng = np.random.default_rng(9)
    x = np.zeros(shape)
    x[0] = ref_rng.normal(0, sigma, shape[1])
    for t in range(1, shape[0]):
        x[t] = rho * x[t - 1] + np.sqrt(1 - rho**2) * ref_rng.normal(
            0, sigma, shape[1])
    ref = mean * np.exp(x - 0.5 * sigma**2)
    got = profiles.lognormal_ar1_trace(np.random.default_rng(9), mean,
                                       shape, rho=rho, sigma=sigma)
    np.testing.assert_allclose(got, ref, rtol=1e-12)


# ---------------------------------------------------------------------------
# stack_horizons (satellite: error quality + slot round-trip)
# ---------------------------------------------------------------------------

def test_stack_horizons_shape_mismatch_raises_clear_error():
    a = profiles.EdgeSystem(n_cameras=4, n_servers=2, n_slots=8).horizon(6)
    b = profiles.EdgeSystem(n_cameras=5, n_servers=2, n_slots=8).horizon(6)
    with pytest.raises(ValueError, match="shape mismatch on field 'acc'"):
        profiles.stack_horizons([a, b])
    with pytest.raises(ValueError, match="at least one"):
        profiles.stack_horizons([])


def test_stack_horizons_slot_roundtrip():
    systems = [profiles.EdgeSystem(n_cameras=4, n_servers=2, n_slots=8,
                                   seed=s) for s in range(3)]
    horizons = [s.horizon(5) for s in systems]
    stacked = profiles.stack_horizons(horizons)
    for k, hor in enumerate(horizons):
        unstacked = jax.tree.map(lambda x: x[k], stacked)
        for t in range(5):
            want, got = hor.slot(t), unstacked.slot(t)
            np.testing.assert_array_equal(want.acc, got.acc)
            np.testing.assert_array_equal(want.eff, got.eff)


def test_slot_view_handles_time_varying_eff():
    tab = scenarios.build("snr_mobility", DIMS)
    assert tab.eff.ndim == 2
    s0, s5 = tab.slot(0), tab.slot(5)
    assert s0.eff.shape == (DIMS["n_cameras"],)
    assert not np.array_equal(s0.eff, s5.eff)


# ---------------------------------------------------------------------------
# Generator family properties
# ---------------------------------------------------------------------------

def test_gilbert_elliott_is_bimodal():
    tab = scenarios.build("gilbert_elliott", DIMS, n_slots=200)
    bw = np.asarray(tab.budgets_b)
    mean = DIMS["mean_bandwidth_hz"]
    assert bw.min() < 0.5 * mean          # deep-fade state visited
    assert bw.max() > 0.9 * mean          # good state visited


def test_gilbert_elliott_sojourn_lengths_match_transition_probs():
    """Mean bad-state sojourn must be ~1/p_bg (geometric), not 1/(1-p_bg) —
    guards against inverted transition logic."""
    from repro.scenarios.generators import _gilbert_elliott_states
    p_gb, p_bg = 0.05, 0.25
    spec = scenarios.spec_for("gilbert_elliott",
                              {**DIMS, "n_slots": 20000, "n_servers": 1})
    states = _gilbert_elliott_states(spec, p_gb, p_bg)[:, 0]
    bad = ~states
    # runs of consecutive bad slots
    edges = np.diff(bad.astype(int))
    starts = np.where(edges == 1)[0]
    ends = np.where(edges == -1)[0]
    n = min(len(starts), len(ends))
    lengths = ends[:n] - starts[:n]
    assert abs(lengths.mean() - 1.0 / p_bg) < 1.0    # ~4 +- sampling noise


def test_server_outage_degrades_one_server():
    tab = scenarios.build("server_outage",
                          {**DIMS, "degrade": 0.01, "n_outages": 2})
    steady = scenarios.build("steady_ar1", DIMS)
    mean = DIMS["mean_bandwidth_hz"]
    assert np.asarray(tab.budgets_b).min() < 0.02 * mean
    assert np.asarray(steady.budgets_b).min() > 0.1 * mean
    assert np.asarray(tab.budgets_b).min() > 0.0   # floored, never zero


def test_diurnal_flash_swings_more_than_steady():
    tab = scenarios.build("diurnal_flash", DIMS, n_slots=96)
    steady = scenarios.build("steady_ar1", DIMS, n_slots=96)
    swing = lambda x: float(np.asarray(x).max() / np.asarray(x).min())
    assert swing(tab.budgets_b) > swing(steady.budgets_b)


def test_snr_mobility_varies_eff_over_time():
    tab = scenarios.build("snr_mobility", DIMS)
    steady = scenarios.build("steady_ar1", DIMS)
    assert np.asarray(tab.eff).std(axis=0).min() > 1e-3
    # steady eff is constant per camera (up to f32 rounding in std)
    assert np.asarray(steady.eff).std(axis=0).max() < 1e-4


def test_content_burst_crushes_accuracy_below_steady():
    tab = scenarios.build("content_burst",
                          {**DIMS, "n_bursts": 10, "burst_depth": 0.6})
    steady = scenarios.build("steady_ar1", DIMS)
    assert float(np.asarray(tab.acc).min()) < \
        float(np.asarray(steady.acc).min())


# ---------------------------------------------------------------------------
# Suite + sweep (vmap fallback path, single device)
# ---------------------------------------------------------------------------

def test_suite_stacks_all_registered_scenarios():
    s = scenarios.suite(**DIMS)
    assert s.n_scenarios == len(scenarios.names())
    assert len(set(s.families)) >= 5
    assert s.tables.acc.shape[0] == s.n_scenarios
    assert s.tables.acc.shape[1] == DIMS["n_slots"]


def test_sweep_runs_all_policies_and_reports():
    s = scenarios.suite(["steady_ar1", "gilbert_elliott", "server_outage"],
                        n_cameras=4, n_slots=6, n_servers=2,
                        mean_bandwidth_hz=15e6, mean_compute_flops=20e12)
    # Pin one device: the suite may run with many virtual devices in the
    # process (e.g. after launch/dryrun forces 512), and this test is about
    # the vmap fallback semantics, not backend selection.
    res = scenarios.sweep(s, v=10.0, p_min=0.7, devices=jax.devices()[:1])
    assert res.backend == "vmap"
    assert set(res.policies) == set(scenarios.POLICIES)
    for p in res.policies:
        assert res.aopi[p].shape == (3, 6)
        assert np.isfinite(res.aopi[p]).all()
        assert (res.acc[p] > 0).all()
    rep = scenarios.robustness(res)
    assert set(rep.families) == set(s.families)
    fam, stats = rep.worst_family("lbcd")
    assert stats.worst_aopi >= rep.table["lbcd"][fam].mean_aopi - 1e-9
    assert "lbcd" in str(rep)
    assert len(rep.rows()) == len(rep.policies) * len(rep.families)


def test_sweep_unknown_policy_or_backend_raises():
    s = scenarios.suite(["steady_ar1"], n_cameras=3, n_slots=4,
                        n_servers=2)
    with pytest.raises(ValueError, match="unknown policy"):
        scenarios.sweep(s, policies=("nope",))
    with pytest.raises(ValueError, match="unknown backend"):
        scenarios.sweep(s, backend="nope")
    # An unstacked horizon (the thing rollout() takes) is rejected at the
    # API boundary instead of dying inside a jitted scan.
    single = profiles.EdgeSystem(n_cameras=3, n_servers=2,
                                 n_slots=4).horizon(4)
    with pytest.raises(ValueError, match="stacked"):
        scenarios.sweep(single)


# ---------------------------------------------------------------------------
# Sharded execution (4 virtual CPU devices in a subprocess — XLA_FLAGS must
# be set before jax initializes, hence the subprocess)
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = textwrap.dedent("""
    import jax
    import numpy as np
    from repro import scenarios

    assert len(jax.devices()) == 4, jax.devices()
    s = scenarios.suite(n_cameras=4, n_slots=6, n_servers=2,
                        mean_bandwidth_hz=15e6, mean_compute_flops=20e12)
    vmap_ = scenarios.sweep(s, backend="vmap", devices=jax.devices()[:1])
    fleet = scenarios.sweep(s, backend="fleet")
    shard = scenarios.sweep(s, backend="shard_map")
    assert vmap_.backend == "vmap" and fleet.backend == "fleet[4]" \\
        and shard.backend == "shard_map[4]", \\
        (vmap_.backend, fleet.backend, shard.backend)
    for p in scenarios.POLICIES:
        # fleet runs the identical per-block executables as the vmap
        # fallback, fleet means in their own program: bitwise equal.
        for k in ("aopi", "acc", "q"):
            np.testing.assert_array_equal(getattr(fleet, k)[p],
                                          getattr(vmap_, k)[p],
                                          err_msg=f"{p} {k}")
        # shard_map compiles a distinct num_partitions>1 XLA module; fp
        # rounding may flip knife-edge discrete allocations, so parity is
        # statistical: per-scenario horizon means.
        np.testing.assert_allclose(shard.mean_aopi(p), vmap_.mean_aopi(p),
                                   rtol=0.08, atol=1e-6, err_msg=p)
        np.testing.assert_allclose(shard.mean_acc(p), vmap_.mean_acc(p),
                                   rtol=0.05, atol=1e-6, err_msg=p)
    # sharded runs are themselves deterministic.
    shard2 = scenarios.sweep(s, backend="shard_map")
    for p in scenarios.POLICIES:
        np.testing.assert_array_equal(shard.aopi[p], shard2.aopi[p])
    print("SHARD-OK")
""")


def test_shard_map_and_fleet_match_vmap_on_four_virtual_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SHARD-OK" in proc.stdout


# ---------------------------------------------------------------------------
# report.robustness aggregation math (hand-built inputs)
# ---------------------------------------------------------------------------

def _hand_sweep(measured=False):
    """Two families ('a' x2 scenarios, 'b' x1), one policy, known values."""
    aopi_ = np.array([[1.0, 3.0],     # family a, scenario 0
                      [2.0, 4.0],     # family a, scenario 1
                      [10.0, 30.0]])  # family b
    acc = np.array([[0.5, 0.7], [0.6, 0.8], [0.9, 0.9]])
    kw = {}
    if measured:
        kw = dict(measured_aopi={"lbcd": aopi_ * 1.5},
                  predicted_aopi={"lbcd": aopi_})
    from repro.scenarios.runner import SweepResult
    return SweepResult(
        names=["a0", "a1", "b0"], families=["a", "a", "b"],
        policies=["lbcd"], v=10.0, p_min=0.7, backend="vmap",
        aopi={"lbcd": aopi_}, acc={"lbcd": acc},
        q={"lbcd": np.zeros((3, 2))}, **kw)


def test_robustness_aggregation_math():
    rep = scenarios.robustness(_hand_sweep(), pct=50.0)
    a = rep.table["lbcd"]["a"]
    assert a.mean_aopi == pytest.approx(2.5)          # mean of 1,3,2,4
    assert a.pct_aopi == pytest.approx(2.5)           # median of 1,2,3,4
    assert a.worst_aopi == pytest.approx(4.0)
    assert a.mean_acc == pytest.approx(0.65)
    b = rep.table["lbcd"]["b"]
    assert b.mean_aopi == pytest.approx(20.0)
    assert b.worst_aopi == pytest.approx(30.0)
    assert rep.worst_family("lbcd")[0] == "b"
    assert a.measured_mean is None and a.divergence is None
    assert not rep.has_measured


def test_robustness_divergence_columns():
    rep = scenarios.robustness(_hand_sweep(measured=True), pct=50.0)
    assert rep.has_measured
    for fam, base_mean, base_worst in (("a", 2.5, 4.0), ("b", 20.0, 30.0)):
        s = rep.table["lbcd"][fam]
        assert s.measured_mean == pytest.approx(base_mean * 1.5)
        assert s.measured_worst == pytest.approx(base_worst * 1.5)
        assert s.mean_predicted == pytest.approx(base_mean)
        assert s.divergence == pytest.approx(0.5)     # measured = 1.5x
    fam, div = rep.worst_divergence("lbcd")
    assert div == pytest.approx(0.5)
    rows = rep.rows()
    assert len(rows) == 2 and len(rows[0]) == 10
    assert rows[0][:2] == ["lbcd", "a"]
    assert rows[0][9] == pytest.approx(0.5)           # divergence column
    txt = str(rep)
    assert "measured" in txt and "+50.00%" in txt
