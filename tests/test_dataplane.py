"""Batched device-resident GI/G/1 data plane (``queues.gi_g1_window`` /
``service.measure_window``): parity with the numpy oracle and Theorems 1-2,
collision-free key streams, epoch-horizon truncation, determinism, and
the blocked frame scan."""
import functools
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro import obs
from repro.core import aopi, queues
from repro.serving import service


def _measure(lam, mu, p, pol, *, seed=0, t=0, horizon=20_000.0,
             delay_model="mm1", frames_cap=400_000):
    n_frames = queues.frames_budget(lam, horizon, frames_cap)
    out = queues.gi_g1_window([lam], [mu], [p], [pol], seed=seed, t0=t,
                              n_frames=n_frames, horizon=horizon,
                              delay_model=delay_model)
    return {k: v[0, 0] for k, v in out.items()}


# ---------------------------------------------------------------------------
# Parity: batched engine == Theorems 1-2 (mm1) == numpy oracle (all models)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rho,pol,p", [
    (0.5, aopi.FCFS, 0.8), (0.5, aopi.LCFSP, 0.8),
    (0.75, aopi.FCFS, 0.6), (0.25, aopi.LCFSP, 0.9)])
def test_batched_engine_matches_closed_forms(rho, pol, p):
    mu = 10.0
    out = _measure(rho * mu, mu, p, pol, seed=11)
    assert out["aopi"] == pytest.approx(
        float(aopi.aopi(rho * mu, mu, p, pol)), rel=0.1)


@pytest.mark.parametrize("delay_model", queues.DELAY_MODELS)
@pytest.mark.parametrize("pol", [aopi.FCFS, aopi.LCFSP])
def test_batched_engine_matches_numpy_oracle(delay_model, pol):
    """Same delay family, independent draws: the batched engine and the
    per-stream numpy oracle estimate the same steady-state mean AoPI."""
    lam, mu, p = 5.0, 10.0, 0.8
    out = _measure(lam, mu, p, pol, seed=2, delay_model=delay_model)
    sim = queues.simulate(lam, mu, p, pol, n_frames=150_000, seed=7,
                          **queues.oracle_samplers(delay_model, lam, mu))
    assert out["aopi"] == pytest.approx(sim.mean_aopi, rel=0.1)


def test_batched_engine_matches_oracle_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=8, deadline=None)
    @given(st.sampled_from([0.25, 0.5, 0.75]),
           st.sampled_from([aopi.FCFS, aopi.LCFSP]),
           st.sampled_from(queues.DELAY_MODELS),
           st.integers(0, 10_000))
    def inner(rho, pol, delay_model, seed):
        mu, p = 10.0, 0.7
        lam = rho * mu
        out = _measure(lam, mu, p, pol, seed=seed, horizon=15_000.0,
                       delay_model=delay_model)
        sim = queues.simulate(
            lam, mu, p, pol, n_frames=120_000, seed=seed + 1,
            **queues.oracle_samplers(delay_model, lam, mu))
        assert out["aopi"] == pytest.approx(sim.mean_aopi, rel=0.12)

    inner()


def test_non_exponential_models_drift_from_theorems():
    """The §III-B regime: same means, different shape -> Theorems 1-2 are
    biased (less delay variance means less waiting, so measured < theory
    under FCFS; heavy tails push the other way)."""
    lam, mu, p = 5.0, 10.0, 0.8
    th = float(aopi.aopi(lam, mu, p, aopi.FCFS))
    for dm in ("uniform", "gamma"):
        out = _measure(lam, mu, p, aopi.FCFS, seed=4, delay_model=dm)
        assert out["aopi"] < th * 0.95
    for dm in queues.HEAVY_TAIL_MODELS:
        out = _measure(lam, mu, p, aopi.FCFS, seed=4, delay_model=dm)
        assert out["aopi"] > th * 1.05


def test_heavy_tail_samplers_match_target_mean_and_shape():
    """Mean-matched heavy tails: sampler mean == 1/rate for lognormal and
    weibull, with the coefficient of variation the family's parameters
    imply (sigma=1 lognormal: CV = sqrt(e - 1); k=0.7 weibull:
    CV ~ 1.46) — well above exponential's CV = 1."""
    import math
    rng = np.random.default_rng(3)
    mean = 0.4
    ln = queues.lognormal_sampler(mean)(rng, 400_000)
    assert ln.mean() == pytest.approx(mean, rel=0.02)
    assert ln.std() / ln.mean() == pytest.approx(
        np.sqrt(np.e - 1.0), rel=0.05)
    wb = queues.weibull_sampler(mean)(rng, 400_000)
    assert wb.mean() == pytest.approx(mean, rel=0.02)
    k = queues.WEIBULL_SHAPE
    cv = math.sqrt(math.gamma(1 + 2 / k) / math.gamma(1 + 1 / k) ** 2 - 1)
    assert wb.std() / wb.mean() == pytest.approx(cv, rel=0.05)
    assert (ln > 0).all() and (wb > 0).all()


def test_heavy_tail_samplers_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(st.floats(0.05, 5.0), st.integers(0, 10_000),
           st.sampled_from(sorted(queues.HEAVY_TAIL_MODELS)))
    def inner(mean, seed, dm):
        rng = np.random.default_rng(seed)
        maker = (queues.lognormal_sampler if dm == "lognormal"
                 else queues.weibull_sampler)
        x = maker(mean)(rng, 200_000)
        assert x.mean() == pytest.approx(mean, rel=0.05)
        assert (x > 0).all()
        assert x.std() > x.mean()      # heavier-tailed than exponential

    inner()


# ---------------------------------------------------------------------------
# Telemetry-fitted delay-model selector
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dm", queues.DELAY_MODELS)
def test_fit_delay_model_round_trips_every_family(dm):
    rng = np.random.default_rng(17)
    mean = 0.4
    if dm == "mm1":
        samples = rng.exponential(mean, 4096)
    else:
        samples = queues.oracle_samplers(
            dm, 1.0 / mean, 10.0)["t_sampler"](rng, 4096)
    fit = queues.fit_delay_model(samples)
    assert fit.model == dm, fit
    assert fit.n_samples == 4096
    assert fit.residuals[dm] == min(fit.residuals.values())


def test_fit_delay_model_falls_back_below_min_samples():
    fit = queues.fit_delay_model(np.array([1.0, 2.0]))
    assert fit.model == "mm1" and fit.residuals == {}
    assert queues.fit_delay_model(np.zeros(64)).model == "mm1"


def test_validate_delay_model_lists_auto_sentinel():
    queues.validate_delay_model("auto", allow_auto=True)
    with pytest.raises(ValueError, match="auto"):
        queues.validate_delay_model("pareto", allow_auto=True)
    with pytest.raises(ValueError, match="delay_model"):
        queues.validate_delay_model("auto")


@pytest.mark.parametrize("dm,pname,truth", [
    ("lognormal", "sigma", 1.25), ("weibull", "k", 0.5)])
def test_fit_delay_model_estimates_shape_parameters(dm, pname, truth):
    """The fitted selector also estimates the family's shape parameter
    from the CvM grid — off-default shapes are recovered exactly (the
    grid contains the truth)."""
    rng = np.random.default_rng(23)
    mean = 0.4
    if dm == "lognormal":
        samples = rng.lognormal(np.log(mean) - truth ** 2 / 2.0, truth,
                                8192)
    else:
        from math import gamma as _g
        samples = mean / _g(1.0 + 1.0 / truth) * rng.weibull(truth, 8192)
    fit = queues.fit_delay_model(samples)
    assert fit.model == dm
    assert fit.params == {pname: truth}


def test_fit_delay_model_default_shapes_and_mm1_have_params():
    rng = np.random.default_rng(5)
    fit = queues.fit_delay_model(rng.exponential(0.3, 4096))
    assert fit.model == "mm1" and fit.params == {}
    ln = queues.fit_delay_model(
        queues.oracle_samplers("lognormal", 2.5, 10.0)["t_sampler"](
            rng, 4096))
    assert ln.model == "lognormal" and "sigma" in ln.params


def test_family_cv2_and_residual_prior():
    """Squared CoV per family and the Kingman-style residual prior
    ``(1 + cv^2) / 2`` the planner seeds its AoPI scale from."""
    assert queues.family_cv2("mm1") == pytest.approx(1.0)
    assert queues.residual_prior("mm1") == pytest.approx(1.0)
    # uniform on [0.5m, 1.5m]: cv^2 = spread^2 / 3 < 1 -> prior < 1.
    assert queues.residual_prior("uniform") < 1.0
    # heavy tails: cv^2 > 1 -> prior > 1, monotone in sigma.
    assert queues.residual_prior("weibull", {"k": 0.5}) > \
        queues.residual_prior("weibull", {"k": 0.9})
    # lognormal cv^2 = expm1(sigma^2): monotone, crosses 1 at sigma ~ 0.83.
    assert queues.family_cv2("lognormal", {"sigma": 1.5}) > 1.0 > \
        queues.family_cv2("lognormal", {"sigma": 0.5})


# ---------------------------------------------------------------------------
# Determinism + key streams
# ---------------------------------------------------------------------------

def test_batched_window_is_bitwise_deterministic():
    lam = np.array([[4.0, 6.0], [5.0, 3.0]])
    mu = np.full((2, 2), 12.0)
    p = np.full((2, 2), 0.8)
    pol = np.array([[0, 1], [1, 0]])
    kw = dict(n_frames=4096, horizon=300.0)
    a = queues.gi_g1_window(lam, mu, p, pol, seed=5, t0=3, **kw)
    b = queues.gi_g1_window(lam, mu, p, pol, seed=5, t0=3, **kw)
    np.testing.assert_array_equal(a["aopi"], b["aopi"])
    c = queues.gi_g1_window(lam, mu, p, pol, seed=6, t0=3, **kw)
    d = queues.gi_g1_window(lam, mu, p, pol, seed=5, t0=4, **kw)
    assert not np.array_equal(a["aopi"], c["aopi"])
    assert not np.array_equal(a["aopi"], d["aopi"])


def test_epoch_stream_keys_never_collide():
    """Regression for the old ``seed + 7919*t + i`` scheme, which collided
    (t=0, i=7919) with (t=1, i=0). Folded jax keys and SeedSequence spawn
    keys are pairwise distinct for N up to 10k across epochs."""
    import jax
    import jax.numpy as jnp

    n = 10_000
    seen = set()
    for t in (0, 1, 2):
        keys = jax.vmap(jax.random.fold_in, (None, 0))(
            queues.epoch_key(seed=0, t=t), jnp.arange(n))
        for kd in np.asarray(jax.random.key_data(keys)):
            seen.add(tuple(int(x) for x in kd))
    assert len(seen) == 3 * n
    # The numpy loop oracle's streams: the historic collision pair plus a
    # broad uniqueness sweep.
    s_old = queues.stream_seed_sequence(0, t=0, i=7919).generate_state(4)
    s_new = queues.stream_seed_sequence(0, t=1, i=0).generate_state(4)
    assert not np.array_equal(s_old, s_new)
    states = {
        tuple(queues.stream_seed_sequence(0, t, i).generate_state(2))
        for t in (0, 1) for i in range(2000)}
    assert len(states) == 2 * 2000


def test_window_batching_invariance():
    """One [E, N] window dispatch == E single-epoch dispatches at the same
    frame budget: per-(epoch, stream) keys depend only on (seed, t, i),
    not on how the window was batched."""
    rng = np.random.default_rng(0)
    lam = rng.uniform(3, 8, size=(3, 4))
    mu = np.full((3, 4), 15.0)
    p = np.full((3, 4), 0.8)
    pol = rng.integers(0, 2, size=(3, 4))
    kw = dict(n_frames=2048, horizon=200.0, seed=9)
    win = queues.gi_g1_window(lam, mu, p, pol, t0=2, **kw)
    for e in range(3):
        one = queues.gi_g1_window(lam[e], mu[e], p[e], pol[e], t0=2 + e,
                                  **kw)
        np.testing.assert_allclose(win["aopi"][e], one["aopi"][0],
                                   rtol=1e-9)
        np.testing.assert_array_equal(win["n_frames"][e],
                                      one["n_frames"][0])
    # The service-level window shares ONE budget across its epochs (from
    # the window's max rate), so its telemetry is per-epoch complete.
    meas, tels = service.measure_window(lam, mu, p, pol,
                                        epoch_duration=200.0, seed=9, t0=2)
    assert meas.shape == (3, 4) and len(tels) == 3
    assert all(np.isfinite(t.aopi_hat).all() for t in tels)


# ---------------------------------------------------------------------------
# Epoch-horizon truncation (frames_floor overshoot fix)
# ---------------------------------------------------------------------------

def test_frames_floor_no_longer_overshoots_epoch():
    """A low-rate stream (floor >> lam * epoch) must be measured over the
    epoch, not the floor's ~200,000 s simulated horizon: with ~no frames
    arriving in the epoch, AoPI -> epoch/2 (age of the virtual frame at
    t=0). The old loop reported the steady-state mean ~2/lam instead —
    a 40x overshoot of anything observable within the epoch."""
    epoch = 100.0
    meas, tel = service.measure_mm1(
        np.array([1e-3]), np.array([50.0]), np.array([1.0]),
        np.array([0]), epoch_duration=epoch, frames_floor=200, seed=0)
    assert meas[0] == pytest.approx(epoch / 2, rel=0.15)
    # The loop oracle keeps the historical (simulated-horizon) semantics:
    # its answer cannot even be seen within the 100 s epoch.
    loop, _ = service.measure_mm1_loop(
        np.array([1e-3]), np.array([50.0]), np.array([1.0]),
        np.array([0]), epoch_duration=epoch, frames_floor=200, seed=0)
    assert loop[0] > epoch


def test_frames_cap_shrinks_horizon_instead_of_inflating_age():
    """When frames_cap cuts coverage short of the epoch, the engine
    measures over the covered window (unbiased) instead of counting the
    uncovered tail as pure age growth."""
    lam, mu, p = 500.0, 1500.0, 0.6
    meas, tel = service.measure_mm1(
        np.array([lam]), np.array([mu]), np.array([p]), np.array([0]),
        epoch_duration=400.0, frames_cap=100_000, seed=1)
    assert meas[0] == pytest.approx(
        float(aopi.aopi(lam, mu, p, 0)), rel=0.1)
    assert tel.lam_hat[0] == pytest.approx(lam, rel=0.05)


def test_telemetry_derives_from_batched_outputs():
    lam, mu, p = 6.0, 15.0, 0.7
    meas, tel = service.measure_mm1(
        np.array([lam, lam]), np.array([mu, mu]), np.array([p, p]),
        np.array([0, 1]), epoch_duration=5000.0, seed=3)
    assert tel.lam_hat == pytest.approx([lam, lam], rel=0.05)
    assert tel.acc_hat == pytest.approx([p, p], abs=0.03)
    np.testing.assert_allclose(tel.aopi_hat, meas)
    # LCFSP discards preempted frames: completion rate < arrival rate.
    assert tel.mu_hat[1] < tel.lam_hat[1]
    assert tel.mu_hat[0] == pytest.approx(lam, rel=0.05)


def test_unknown_delay_model_raises():
    with pytest.raises(ValueError, match="delay_model"):
        queues.gi_g1_window([1.0], [2.0], [0.5], [0], n_frames=256,
                            horizon=10.0, delay_model="pareto")
    with pytest.raises(ValueError, match="delay_model"):
        service.measure_mm1_loop(
            np.ones(1), np.ones(1), np.ones(1) * 0.5, np.zeros(1),
            delay_model="pareto")


# ---------------------------------------------------------------------------
# Blocked frame scan: FRAME_BLOCK frames per scan iteration, then a tail
# ---------------------------------------------------------------------------

FB = queues.FRAME_BLOCK


def _per_frame_window_sim(monkeypatch, *args):
    """``_window_sim`` with no block scan: every frame in the tail, i.e.
    one plain per-frame ``lax.scan``. A fresh jit of a fresh function,
    so it shares no trace with ``queues._window_sim``."""
    def per_frame(*a):
        return queues._window_sim.__wrapped__(*a)
    with monkeypatch.context() as m:
        m.setattr(queues, "FRAME_BLOCK", 1 << 40)
        return jax.jit(per_frame, static_argnums=(6, 7, 8))(*args)


@pytest.mark.parametrize("delay_model", ["mm1", "weibull"])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("n_frames", [4 * FB, 3 * FB + 5, FB - 7],
                         ids=["blocks", "blocks+tail", "tail"])
def test_blocked_frame_scan_is_bitwise_the_per_frame_scan(
        n_frames, dtype, delay_model, monkeypatch):
    """The block scan plus tail steps every frame in order with the same
    arithmetic: its carries equal a plain per-frame ``lax.scan`` of
    ``_frame_step`` bit for bit, and so do ``_window_sim``'s outputs,
    on lanes of both policies, with the horizon cutting some lanes and
    the frame budget others."""
    rng = np.random.default_rng(n_frames)
    e, n = 2, 6
    lam = rng.uniform(2.0, 8.0, (e, n))
    mu = rng.uniform(4.0, 12.0, (e, n))
    p = rng.uniform(0.3, 1.0, (e, n))
    pol = np.tile([0, 1], (e, n // 2))
    horizon = 0.25 * n_frames
    with jax.enable_x64(True):
        lanes = e * n
        u = jnp.asarray(rng.uniform(size=(3, n_frames, lanes)), dtype)
        T = queues._delays_from_uniforms(
            u[:1], jnp.asarray(1.0 / lam.ravel(), dtype), delay_model)
        O = queues._delays_from_uniforms(
            u[1:2], jnp.asarray(1.0 / mu.ravel(), dtype), delay_model)
        T_next = jnp.concatenate([T[1:], jnp.full((1, lanes), jnp.inf, dtype)])
        xs = (T, T_next, O, u[2])
        zero = jnp.zeros(lanes, dtype)
        step = functools.partial(
            queues._frame_step, is_lcfsp=jnp.asarray(pol.ravel() == 1),
            h_eff=jnp.minimum(jnp.asarray(horizon, dtype), T.sum(axis=0)),
            zero=zero, p=jnp.asarray(p.ravel(), dtype))
        init = (zero, zero, jnp.full(lanes, -jnp.inf, dtype)) + (zero,) * 6
        blocked = jax.jit(lambda c, x: queues._scan_frames(step, c, x))(
            init, xs)
        plain = jax.jit(lambda c, x: lax.scan(
            lambda ci, xi: (step(ci, xi), None), c, x)[0])(init, xs)
        for got, want in zip(blocked, plain):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

        keys = jax.vmap(jax.random.fold_in, (None, 0))(
            jax.random.key(n_frames), jnp.arange(e))
        args = (jnp.asarray(lam, dtype), jnp.asarray(mu, dtype),
                jnp.asarray(p, dtype), jnp.asarray(pol, jnp.int32), keys,
                horizon, n_frames, delay_model, 8)
        got = queues._window_sim(*args)
        want = _per_frame_window_sim(monkeypatch, *args)
    assert sorted(got) == sorted(want)
    assert got["delay_samples"].shape == (e, n, min(8, n_frames))
    for k in got:
        assert got[k].dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_one_block_and_a_tail_differ_only_by_cpu_fma_contraction():
    """With one block and a short tail (FRAME_BLOCK + 4 or + 8 frames),
    XLA:CPU fuses the program differently from the per-frame scan and
    contracts a multiply-add into an FMA there, moving ``aopi`` by 1-2
    ulp. With the CPU's instruction set capped below FMA, in a process of
    its own, both dtypes are bitwise equal again: the blocked scan does
    the same arithmetic in the same order."""
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_cpu_max_isa=SSE4_2"
        sys.path.insert(0, {str(Path(queues.__file__).parents[2])!r})
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import queues

        def per_frame(*a):
            return queues._window_sim.__wrapped__(*a)

        rng = np.random.default_rng(0)
        e, n = 8, 30
        ins = [rng.uniform(lo, hi, (e, n)) for lo, hi in
               ((0.5, 2.0), (2.0, 4.0), (0.3, 1.0))]
        pol = jnp.asarray(rng.integers(0, 2, (e, n)), jnp.int32)
        fb = queues.FRAME_BLOCK
        with jax.enable_x64(True):
            keys = jax.vmap(jax.random.fold_in, (None, 0))(
                jax.random.key(3), jnp.arange(e))
            for dtype in ("float32", "float64"):
                for f in (fb + 4, fb + 8):
                    args = (*(jnp.asarray(x, dtype) for x in ins), pol,
                            keys, 0.5 * f, f, "mm1", 0)
                    got = queues._window_sim(*args)
                    queues.FRAME_BLOCK = 1 << 40
                    want = jax.jit(per_frame, static_argnums=(6, 7, 8))(*args)
                    queues.FRAME_BLOCK = fb
                    for k in got:
                        assert np.array_equal(got[k], want[k]), (dtype, f, k)
        print("bitwise")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == ["bitwise"]


def _scans(jaxpr):
    """Every ``scan`` eqn in ``jaxpr`` and in the jaxprs it calls."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    out += _scans(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    out += _scans(sub)
    return out


@pytest.mark.parametrize("n_frames", [32768, 20480 + 12, FB - 7])
def test_window_sim_scans_blocks_then_one_tail(n_frames):
    """The serve cell's largest budget is whole blocks, with no tail
    scan; a budget with a remainder adds exactly one per-frame scan of
    it; a budget under one block is the tail alone. The span of each
    call says so."""
    e, n = 8, 30
    with jax.enable_x64(True):
        x = jnp.ones((e, n), jnp.float64)
        keys = jax.vmap(jax.random.fold_in, (None, 0))(
            jax.random.key(0), jnp.arange(e))
        jaxpr = jax.make_jaxpr(queues._window_sim, static_argnums=(6, 7, 8))(
            x, x, x, jnp.zeros((e, n), jnp.int32), keys, 300.0, n_frames,
            "mm1", 0)
    blocks, tail = divmod(n_frames, FB)
    scans = _scans(jaxpr.jaxpr)
    assert [s.params["length"] for s in scans] == \
        [blocks] * (blocks > 0) + [tail] * (tail > 0)
    if n_frames == 32768:
        assert scans[0].params["length"] == 32768 // FB
        # Each iteration takes one [FRAME_BLOCK, E*N] block of T, T_next,
        # O and the coin.
        xs = scans[0].params["jaxpr"].in_avals[-4:]
        assert [a.shape for a in xs] == [(FB, e * n)] * 4

    obs.reset()
    try:
        queues.gi_g1_window([2.0], [5.0], [0.9], [1], n_frames=n_frames,
                            horizon=50.0)
        (span,) = [ev["args"] for ev in obs.events()
                   if ev["name"] == "queues.gi_g1_window"]
    finally:
        obs.reset()
    assert (span["block"], span["tail"], span["n_frames"]) == \
        (FB, tail, n_frames)
