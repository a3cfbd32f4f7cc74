"""LBCD controller: constraint satisfaction, optimality gap, bin packing."""
import numpy as np
import pytest

from repro.core import baselines, binpack, lbcd, lyapunov, profiles


def _system(**kw):
    kw.setdefault("n_cameras", 18)
    kw.setdefault("n_servers", 3)
    kw.setdefault("n_slots", 60)
    return profiles.EdgeSystem(**kw)


# P_min values in the paper's Fig. 8 range (benchmarks/bench_hyperparams
# sweeps 0.3-0.9) that _system() can meet: its best reachable mean accuracy
# is about 0.78, so floors at 0.8 and above are infeasible.
FIG8_P_MINS = (0.3, 0.5, 0.6, 0.65, 0.7)


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("p_min", FIG8_P_MINS)
def test_long_term_accuracy_constraint(p_min, seed):
    # v=2: accuracy converges within ~40 slots (Fig. 8 regime); the large-V
    # transient is exercised by test_v_tradeoff below.
    ctrl = lbcd.LBCDController(_system(seed=seed), v=2.0, p_min=p_min)
    summary = ctrl.run(100)
    tail = summary.acc_series[40:]
    assert tail.mean() >= p_min - 0.01
    # Virtual queue stays bounded (stability).
    assert summary.q_series[-1] < 5.0


@pytest.mark.parametrize("p_min", (0.6, 0.75, 0.85))
@pytest.mark.parametrize("v", (1.0, 10.0, 100.0))
def test_q_dynamics_match_eq44(v, p_min):
    ctrl = lbcd.LBCDController(_system(seed=4), v=v, p_min=p_min)
    q_prev = 0.0
    for t in range(5):
        rec = ctrl.step(t)
        expect = max(q_prev - rec.mean_acc + p_min, 0.0)
        assert rec.q == pytest.approx(expect, abs=1e-6)
        q_prev = rec.q


def test_v_tradeoff():
    """Theorem 4: larger V -> lower AoPI (we check the drift-plus-penalty
    score improves), slower accuracy convergence."""
    base = dict(n_cameras=12, n_servers=2, n_slots=40,
                mean_bandwidth_hz=8e6, mean_compute_flops=8e12)
    lo = lbcd.LBCDController(_system(**base), v=1.0, p_min=0.7).run(40)
    hi = lbcd.LBCDController(_system(**base), v=100.0, p_min=0.7).run(40)
    assert hi.mean_aopi <= lo.mean_aopi * 1.05
    assert lo.acc_series[:10].mean() >= hi.acc_series[:10].mean() - 0.02


def test_min_is_lower_bound():
    sysk = dict(n_cameras=12, n_servers=3, n_slots=30, seed=2,
                mean_bandwidth_hz=10e6, mean_compute_flops=10e12)
    mn = baselines.MINController(_system(**sysk)).run(30)
    lb = lbcd.LBCDController(_system(**sysk), v=10.0, p_min=0.7).run(30)
    # MIN ignores the accuracy constraint on a pooled server: lower AoPI.
    assert mn.mean_aopi <= lb.mean_aopi * 1.02


def test_lbcd_beats_baselines_when_constrained():
    """Fig. 9-11 regime: resource-limited, LBCD wins on AoPI while meeting
    the accuracy floor."""
    sysk = dict(n_cameras=24, n_servers=3, n_slots=30, seed=1,
                mean_bandwidth_hz=10e6, mean_compute_flops=12e12)
    lb = lbcd.LBCDController(_system(**sysk), v=10.0, p_min=0.7).run(30)
    for name in ("DOS", "JCAB"):
        bl = baselines.make(name, _system(**sysk)).run(30)
        assert lb.mean_aopi < bl.mean_aopi, name


# (cameras, servers) of the two benchmark configurations: paper-30 and
# eua-816.
FIT_SHAPES = ((30, 3), (816, 125))


def _fit_instance(n_cameras, n_servers, seed, headroom):
    """Random Algorithm-2 demands and per-server capacities. Each capacity
    is ``headroom`` x (2 x the mean per-server demand + the largest single
    demand). At headroom >= 1 first-fit places every camera without
    overflow: a camera that fits nowhere would need more than half the
    servers full on bandwidth and more than half full on compute."""
    rng = np.random.default_rng(seed)
    b_hat = rng.uniform(0.2e6, 2e6, n_cameras)
    c_hat = rng.uniform(0.5e12, 5e12, n_cameras)
    B = (2 * b_hat.sum() / n_servers + b_hat.max()) * headroom * \
        rng.uniform(1.0, 1.5, n_servers)
    C = (2 * c_hat.sum() / n_servers + c_hat.max()) * headroom * \
        rng.uniform(1.0, 1.5, n_servers)
    return b_hat, c_hat, B, C


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("n_cameras,n_servers", FIT_SHAPES)
def test_first_fit_respects_capacity_when_feasible(n_cameras, n_servers,
                                                   seed):
    b_hat, c_hat, B, C = _fit_instance(n_cameras, n_servers, seed, 1.0)
    a = binpack.first_fit(b_hat, c_hat, B, C)
    assert a.min() >= 0 and a.max() < n_servers
    for s in range(n_servers):
        m = a == s
        assert b_hat[m].sum() <= B[s] * (1 + 1e-12)
        assert c_hat[m].sum() <= C[s] * (1 + 1e-12)


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("n_cameras,n_servers", FIT_SHAPES)
def test_first_fit_overflow_goes_to_largest_remaining(n_cameras, n_servers,
                                                      seed):
    """With capacities too small for the fleet, each camera that fits on no
    server goes to the server with the most remaining volume (Algorithm 2
    lines 6-8); each camera that fits somewhere goes where it fits."""
    b_hat, c_hat, B, C = _fit_instance(n_cameras, n_servers, seed, 0.2)
    a = binpack.first_fit(b_hat, c_hat, B, C)
    assert a.min() >= 0 and a.max() < n_servers
    tot_b, tot_c = B.sum(), C.sum()
    rem_b, rem_c = B.copy(), C.copy()
    n_overflow = 0
    for n in np.argsort(-(b_hat / tot_b + c_hat / tot_c)):
        s = a[n]
        fits = (rem_b >= b_hat[n]) & (rem_c >= c_hat[n])
        if fits.any():
            assert fits[s]
        else:
            n_overflow += 1
            assert s == np.argmax(rem_b / tot_b + rem_c / tot_c)
        rem_b[s] = max(rem_b[s] - b_hat[n], 0.0)
        rem_c[s] = max(rem_c[s] - c_hat[n], 0.0)
    assert n_overflow > 0


def test_hierarchical_first_fit():
    rng = np.random.default_rng(0)
    b_hat = rng.uniform(0.5, 2.0, 16)
    c_hat = rng.uniform(0.5, 2.0, 16)
    a = binpack.hierarchical_first_fit(b_hat, c_hat, [20.0, 20.0],
                                       [20.0, 20.0], islands_per_pod=4)
    assert a.min() >= 0 and a.max() < 8


@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize("p_min", FIG8_P_MINS)
def test_drift_lemma1_bound(p_min, seed):
    """Empirical drift never exceeds the Lemma-1 bound."""
    rng = np.random.default_rng(seed)
    q = 0.0
    for _ in range(200):
        p_bar = rng.uniform(0.0, 1.0)
        q_next = lyapunov.queue_update(q, p_bar, p_min)
        drift = 0.5 * (float(q_next)**2 - q**2)
        assert drift <= lyapunov.drift_bound(q, p_bar, p_min) + 1e-9
        q = float(q_next)


def test_interior_point_method_end_to_end():
    """The paper-faithful Algorithm-1 path (interior point) also satisfies
    the constraint and achieves similar score."""
    sysk = dict(n_cameras=10, n_servers=2, n_slots=12, seed=6)
    wf = lbcd.LBCDController(_system(**sysk), v=10.0, p_min=0.7,
                             method="waterfill").run(12)
    ip = lbcd.LBCDController(_system(**sysk), v=10.0, p_min=0.7,
                             method="interior").run(12)
    assert ip.mean_aopi == pytest.approx(wf.mean_aopi, rel=0.15)


def test_first_fit_property_never_overflows_when_feasible():
    """Property: whenever a feasible packing exists for first-fit's greedy
    order, no server exceeds capacity."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=30, deadline=None)
    @given(st.integers(0, 10_000))
    def inner(seed):
        rng = np.random.default_rng(seed)
        n, s = rng.integers(3, 12), rng.integers(2, 4)
        b_hat = rng.uniform(0.1, 1.0, n)
        c_hat = rng.uniform(0.1, 1.0, n)
        # generous capacity -> must fit without overflow
        B = np.full(s, b_hat.sum())
        C = np.full(s, c_hat.sum())
        a = binpack.first_fit(b_hat, c_hat, B, C)
        for j in range(s):
            m = a == j
            assert b_hat[m].sum() <= B[j] + 1e-9
            assert c_hat[m].sum() <= C[j] + 1e-9
    inner()
