"""Water-filling vs interior-point allocators: KKT + properties."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import allocate, aopi


# Cameras per server: paper-30 runs 10, eua-816 about 7 (816 on 125).
# ORIGINAL stands for each test's first instance: random server ids and
# the budgets it has always had.
LOADS = (1, 3, 7, 10, 64)
ORIGINAL = pytest.param(None, id="original")


def _setup(n, s, seed=0, lcfsp_frac=0.5, even=False):
    """Random bandwidth-allocation instance; ``even`` puts exactly n / s
    cameras on each server."""
    rng = np.random.default_rng(seed)
    k = rng.uniform(1e-6, 5e-6, n)          # lam per Hz
    p = rng.uniform(0.3, 0.95, n)
    pol = (rng.random(n) < lcfsp_frac).astype(np.int32)
    mu = rng.uniform(5.0, 40.0, n)
    server_id = rng.integers(0, s, n).astype(np.int32)
    budgets = rng.uniform(2e7, 5e7, s)
    if even:
        server_id = rng.permutation(np.arange(n) % s).astype(np.int32)
    return (jnp.asarray(k, jnp.float32), jnp.asarray(p, jnp.float32),
            jnp.asarray(pol), jnp.asarray(mu, jnp.float32),
            jnp.asarray(server_id), jnp.asarray(budgets, jnp.float32))


def _obj_bandwidth(b, k, p, pol, mu):
    lam = np.maximum(np.asarray(b) * np.asarray(k), 1e-9)
    a = np.where(np.asarray(pol) == 1,
                 np.asarray(aopi.aopi_lcfsp(lam, mu, p)),
                 np.asarray(aopi.aopi_fcfs(
                     jnp.minimum(jnp.asarray(lam), 0.999 * mu), mu, p)))
    return a.sum()


@pytest.mark.parametrize("load", (ORIGINAL,) + LOADS)
def test_bandwidth_budget_respected(load):
    k, p, pol, mu, sid, B = (_setup(12, 3) if load is None
                             else _setup(3 * load, 3, even=True))
    b = allocate.waterfill_bandwidth(k, p, pol, mu, sid, B, n_servers=3)
    b = np.asarray(b)
    assert (b > 0).all()
    for s in range(3):
        assert b[np.asarray(sid) == s].sum() <= float(B[s]) * 1.001


def _compute_setup(seed, load, lam_hi, n_original):
    """Random compute-allocation instance on two servers. ``load=None`` is
    the original instance: ``n_original`` cameras on random servers with
    budgets of 3e13-8e13 FLOPS, several times the FCFS stability floors.
    Otherwise ``load`` cameras sit on each server, and a server's budget is
    1.05-1.5 times the compute that would serve each of its cameras at
    mu = lam, so the floors leave little slack."""
    rng = np.random.default_rng(seed)
    s = 2
    n = n_original if load is None else s * load
    inv_xi = rng.uniform(1e-12, 5e-12, n)
    p = rng.uniform(0.3, 0.95, n)
    pol = (rng.random(n) < 0.5).astype(np.int32)
    lam = rng.uniform(1.0, lam_hi, n)
    if load is None:
        sid = rng.integers(0, s, n).astype(np.int32)
        C = rng.uniform(3e13, 8e13, s)
    else:
        sid = rng.permutation(np.arange(n) % s).astype(np.int32)
        floor = np.bincount(sid, weights=lam / inv_xi, minlength=s)
        C = floor * rng.uniform(1.05, 1.5, s)
    return (jnp.asarray(inv_xi, jnp.float32), jnp.asarray(p, jnp.float32),
            jnp.asarray(pol), jnp.asarray(lam, jnp.float32),
            jnp.asarray(sid), jnp.asarray(C, jnp.float32), s)


@pytest.mark.parametrize("load", (ORIGINAL,) + LOADS)
def test_compute_budget_respected_and_stability(load):
    # Budgets large enough that the FCFS stability floors are feasible
    # (the config-selection step guarantees this in the full controller;
    # infeasible instances get documented best-effort scaling instead).
    inv_xi, p, pol, lam, sid, C, s = _compute_setup(1, load, lam_hi=10.0,
                                                n_original=10)
    c = np.asarray(allocate.waterfill_compute(inv_xi, p, pol, lam, sid, C,
                                              n_servers=s))
    assert (c > 0).all()
    for j in range(s):
        assert c[np.asarray(sid) == j].sum() <= float(C[j]) * 1.001
    mu = c * np.asarray(inv_xi)
    fcfs = np.asarray(pol) == 0
    assert (mu[fcfs] > np.asarray(lam)[fcfs]).all()   # constraint (10)


@pytest.mark.parametrize("load", (1, 3, 7, 9, 10, 64))
def test_waterfill_kkt_equal_marginals(load):
    """At the optimum, active (uncapped) cameras on one server share the
    same marginal -dA/db (the dual nu_s). Load 9 is the original
    instance."""
    k, p, pol, mu, sid, B = _setup(load, 1, seed=3, lcfsp_frac=1.0)
    b = allocate.waterfill_bandwidth(k, p, pol, mu, sid, B, n_servers=1)
    lam = np.asarray(b) * np.asarray(k)
    h = (1.0 + 1.0 / np.asarray(p)) / lam**2 * np.asarray(k)  # -dA/db
    assert h.std() / h.mean() < 0.02


@pytest.mark.parametrize("load", (ORIGINAL,) + LOADS)
def test_interior_point_matches_waterfill_bandwidth(load):
    k, p, pol, mu, sid, B = (_setup(8, 2, seed=5) if load is None
                             else _setup(2 * load, 2, seed=5, even=True))
    b_wf = np.asarray(allocate.waterfill_bandwidth(
        k, p, pol, mu, sid, B, n_servers=2))
    b_ip = np.asarray(allocate.interior_point_bandwidth(
        k, p, pol, mu, sid, B, n_servers=2))
    f_wf = _obj_bandwidth(b_wf, k, p, pol, mu)
    f_ip = _obj_bandwidth(b_ip, k, p, pol, mu)
    # Same optimum to <0.5% in objective value.
    assert f_ip == pytest.approx(f_wf, rel=5e-3)


@pytest.mark.parametrize("load", (ORIGINAL,) + LOADS)
def test_interior_point_matches_waterfill_compute(load):
    inv_xi, p, pol, lam, sid, C, s = _compute_setup(7, load, lam_hi=8.0,
                                                n_original=8)

    def obj(c):
        mu = np.maximum(np.asarray(c) * np.asarray(inv_xi), 1e-9)
        a = np.where(np.asarray(pol) == 1,
                     np.asarray(aopi.aopi_lcfsp(lam, mu, p)),
                     np.asarray(aopi.aopi_fcfs(
                         lam, jnp.maximum(jnp.asarray(mu),
                                          np.asarray(lam) / 0.999), p)))
        return a.sum()

    c_wf = allocate.waterfill_compute(inv_xi, p, pol, lam, sid, C,
                                      n_servers=s)
    c_ip = allocate.interior_point_compute(inv_xi, p, pol, lam, sid, C,
                                           n_servers=s)
    assert obj(c_ip) == pytest.approx(obj(c_wf), rel=5e-3)


def test_waterfill_beats_equal_split():
    k, p, pol, mu, sid, B = _setup(10, 2, seed=11)
    b = allocate.waterfill_bandwidth(k, p, pol, mu, sid, B, n_servers=2)
    counts = np.bincount(np.asarray(sid), minlength=2)
    eq = np.asarray(B)[np.asarray(sid)] / counts[np.asarray(sid)]
    assert _obj_bandwidth(b, k, p, pol, mu) <= \
        _obj_bandwidth(eq, k, p, pol, mu) + 1e-6


def test_property_budget_and_positivity():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 12), st.integers(0, 10_000))
    def inner(n, seed):
        k, p, pol, mu, sid, B = _setup(n, 2, seed=seed)
        b = np.asarray(allocate.waterfill_bandwidth(
            k, p, pol, mu, sid, B, n_servers=2))
        assert np.isfinite(b).all() and (b >= 0).all()
        for s in range(2):
            m = np.asarray(sid) == s
            if m.any():
                assert b[m].sum() <= float(B[s]) * 1.005
    inner()


def test_property_more_budget_never_hurts():
    """Objective is monotone non-increasing in the budget."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 10_000))
    def inner(seed):
        k, p, pol, mu, sid, B = _setup(6, 1, seed=seed, lcfsp_frac=1.0)
        b1 = allocate.waterfill_bandwidth(k, p, pol, mu, sid, B, n_servers=1)
        b2 = allocate.waterfill_bandwidth(k, p, pol, mu, sid, B * 2.0,
                                          n_servers=1)
        assert _obj_bandwidth(b2, k, p, pol, mu) <= \
            _obj_bandwidth(b1, k, p, pol, mu) * 1.001
    inner()
