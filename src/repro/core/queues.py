"""Discrete-event AoPI simulators — the oracle for Theorems 1-3.

These reproduce the paper's frame-uploading model exactly (§III-A): the
camera uploads a new frame the instant the previous frame's transmission
finishes, so server inter-arrival times equal the (exponential) transmission
times. The edge server runs either an FCFS queue or an LCFS-with-preemption
(LCFSP) single server with exponential service. Each *completed* frame is
accurately recognized with independent probability ``p``.

AoPI(t) = t - generation time of the newest accurately recognized frame
whose result has been delivered by time t. We integrate the piecewise-linear
age curve and return its time average — the quantity Theorems 1 and 2 predict
in closed form. The simulators are fully vectorized numpy (no Python loop
over frames) so multi-million-frame runs used by the validation tests and
``benchmarks/bench_validation.py`` finish in milliseconds.

Generalized (non-exponential) delay draws are supported via the ``t_sampler``
/ ``o_sampler`` hooks, mirroring the paper's testbed observation (§III-B)
that real delays are "more evenly distributed than exponential".

Two implementations live here:

  * the per-stream **numpy oracle** (``simulate_fcfs`` / ``simulate_lcfsp``)
    — the reference the validation tests trust;
  * the **batched device-resident GI/G/1 engine** (``gi_g1_window``) — both
    closed-form recurrences as one jitted JAX program shaped
    ``[n_epochs, n_streams, n_frames]``, with pluggable delay families
    (``DELAY_MODELS``) keyed by collision-free folded ``jax.random`` keys
    and exact age integration truncated at the epoch horizon. One dispatch
    simulates a whole replay window; this is the serving data plane's hot
    path (``serving.service.measure_window``).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import obs

Sampler = Callable[[np.random.Generator, int], np.ndarray]


def _exp_sampler(rate: float) -> Sampler:
    return lambda rng, n: rng.exponential(1.0 / rate, size=n)


@dataclass
class SimResult:
    mean_aopi: float
    horizon: float
    n_frames: int
    n_completed: int
    n_accurate: int

    @property
    def completion_rate(self) -> float:
        return self.n_completed / max(self.horizon, 1e-12)


def _integrate_age(gen_times: np.ndarray, done_times: np.ndarray,
                   accurate: np.ndarray, horizon: float) -> float:
    """Time-average of the age curve.

    ``gen_times[i]``/``done_times[i]``: generation & result-delivery instants
    of completed frames (done_times strictly increasing). Age resets to
    ``done - gen`` at each *accurate* completion and grows at slope 1
    otherwise. Age starts at 0 at t=0 (virtual accurate frame at the origin —
    a vanishing O(1/horizon) bias, identical to the paper's Fig. 2 setup).
    """
    d = done_times[accurate]
    g = gen_times[accurate]
    # Event boundaries: 0, accurate completions, horizon.
    t0 = np.concatenate(([0.0], d))          # segment starts
    age0 = np.concatenate(([0.0], d - g))    # age immediately after reset
    t1 = np.concatenate((d, [horizon]))      # segment ends
    seg = t1 - t0
    # Integral of (age0 + s) ds over each segment.
    area = np.sum(age0 * seg + 0.5 * seg * seg)
    return float(area / horizon)


def simulate_fcfs(lam: float, mu: float, p: float, n_frames: int = 1_000_000,
                  seed: int = 0, t_sampler: Optional[Sampler] = None,
                  o_sampler: Optional[Sampler] = None) -> SimResult:
    """FCFS (x=0) policy simulator.

    Service-start recurrence ``start_i = max(arrive_i, finish_{i-1})`` is
    solved in closed vectorized form: with S_i = cumsum(O)_i,
    finish_i = S_i + running_max_j(arrive_j - S_{j-1}).
    """
    rng = np.random.default_rng(seed)
    T = (t_sampler or _exp_sampler(lam))(rng, n_frames)
    O = (o_sampler or _exp_sampler(mu))(rng, n_frames)
    gen = np.concatenate(([0.0], np.cumsum(T)))[:-1]   # tau_i
    arrive = gen + T                                    # a_i = tau_{i+1}
    S = np.cumsum(O)
    slack = arrive - np.concatenate(([0.0], S[:-1]))
    finish = S + np.maximum.accumulate(slack)
    acc = rng.random(n_frames) < p
    horizon = float(finish[-1])
    mean_age = _integrate_age(gen, finish, acc, horizon)
    return SimResult(mean_age, horizon, n_frames, n_frames, int(acc.sum()))


def simulate_lcfsp(lam: float, mu: float, p: float, n_frames: int = 1_000_000,
                   seed: int = 0, t_sampler: Optional[Sampler] = None,
                   o_sampler: Optional[Sampler] = None) -> SimResult:
    """LCFSP (x=1) policy simulator.

    Every arriving frame immediately seizes the server, preempting (and
    discarding) any frame in service. Frame i (arriving at a_i = tau_{i+1})
    completes iff its service time O_i is shorter than the next frame's
    transmission time T_{i+1}.
    """
    rng = np.random.default_rng(seed)
    T = (t_sampler or _exp_sampler(lam))(rng, n_frames)
    O = (o_sampler or _exp_sampler(mu))(rng, n_frames)
    gen = np.concatenate(([0.0], np.cumsum(T)))[:-1]
    arrive = gen + T
    nxt = np.concatenate((T[1:], [np.inf]))  # T_{i+1}
    completed = O < nxt
    finish = arrive + O
    acc = completed & (rng.random(n_frames) < p)
    horizon = float(arrive[-1] + O[-1] * completed[-1])
    mean_age = _integrate_age(gen[completed], finish[completed],
                              acc[completed], horizon)
    return SimResult(mean_age, horizon, n_frames, int(completed.sum()),
                     int(acc.sum()))


def simulate(lam: float, mu: float, p: float, policy: int, **kw) -> SimResult:
    if lam <= 0.0 or mu <= 0.0:
        # Zero-rate stream (churned-out camera): no frames ever arrive or
        # complete. The samplers would divide by the rate, so short-circuit
        # with an exactly-zero masked result instead of inf/NaN.
        return SimResult(0.0, 0.0, 0, 0, 0)
    return (simulate_lcfsp if policy == 1 else simulate_fcfs)(lam, mu, p, **kw)


def uniform_sampler(mean: float, spread: float = 0.9) -> Sampler:
    """Uniform on [mean*(1-spread), mean*(1+spread)] — the 'more evenly
    distributed than exponential' testbed regime (§III-B / §VI-C1)."""
    lo, hi = mean * (1 - spread), mean * (1 + spread)
    return lambda rng, n: rng.uniform(lo, hi, size=n)


def gamma_sampler(mean: float, shape: float = 2.0) -> Sampler:
    return lambda rng, n: rng.gamma(shape, mean / shape, size=n)


def lognormal_sampler(mean: float, sigma: float | None = None) -> Sampler:
    """Heavy-tailed lognormal with the given mean: ``exp(N(m, sigma^2))``
    with ``m = ln(mean) - sigma^2/2`` so the mean matches the exponential
    model exactly while the tail is fatter (CV ~ 1.31 at sigma = 1)."""
    sigma = LOGNORMAL_SIGMA if sigma is None else sigma
    m = np.log(mean) - 0.5 * sigma * sigma
    return lambda rng, n: rng.lognormal(m, sigma, size=n)


def weibull_sampler(mean: float, shape: float | None = None) -> Sampler:
    """Heavy-tailed Weibull (shape < 1) with the given mean:
    ``scale * W(k)`` with ``scale = mean / Gamma(1 + 1/k)`` (CV ~ 1.46 at
    k = 0.7) — the sub-exponential tail regime where the §III-B testbed
    diverged hardest from M/M/1."""
    shape = WEIBULL_SHAPE if shape is None else shape
    scale = mean / math.gamma(1.0 + 1.0 / shape)
    return lambda rng, n: scale * rng.weibull(shape, size=n)


def oracle_samplers(delay_model: str, lam: float, mu: float) -> dict:
    """``t_sampler``/``o_sampler`` kwargs for :func:`simulate` matching a
    batched-engine ``delay_model`` — the single mapping the loop oracle,
    the engine-rung data plane, and the parity tests share (empty for
    "mm1": the simulators default to exponential draws)."""
    validate_delay_model(delay_model)
    if delay_model == "mm1":
        return {}
    makers = {"uniform": uniform_sampler, "gamma": gamma_sampler,
              "lognormal": lognormal_sampler, "weibull": weibull_sampler}
    make = makers[delay_model]
    return dict(t_sampler=make(1.0 / lam), o_sampler=make(1.0 / mu))


# ---------------------------------------------------------------------------
# Batched device-resident GI/G/1 engine (JAX)
# ---------------------------------------------------------------------------

#: Delay families of the batched engine. Means always match the numpy
#: ``Sampler`` helpers: "mm1" is exponential with mean 1/rate; the rest
#: keep that mean but change the shape — "uniform"/"gamma" are the
#: lighter-than-exponential §III-B testbed regime where Theorems 1-2
#: drift low, "lognormal"/"weibull" are the heavy-tail regime where
#: they drift high.
DELAY_MODELS = ("mm1", "uniform", "gamma", "lognormal", "weibull")
UNIFORM_SPREAD = 0.9     # matches uniform_sampler's default
GAMMA_SHAPE = 2.0        # matches gamma_sampler's default
LOGNORMAL_SIGMA = 1.0    # matches lognormal_sampler's default
WEIBULL_SHAPE = 0.7      # matches weibull_sampler's default (k < 1)

#: Families whose tails overflow the f32 fast path: a single 6-sigma
#: lognormal draw is ~1e2 x the mean, and the running age *area* squares
#: it, so heavy-tail windows always take the float64 branch regardless
#: of frame budget.
HEAVY_TAIL_MODELS = frozenset({"lognormal", "weibull"})

#: Sentinel accepted by the serving layer (`AnalyticsService`,
#: `replay_tables`): fit the family from observed delay telemetry via
#: :func:`fit_delay_model` instead of trusting a flag. The batched
#: engine itself never sees it — `gi_g1_window` requires a concrete
#: family.
AUTO_DELAY_MODEL = "auto"


def validate_delay_model(delay_model: str, *, allow_auto: bool = False) -> str:
    """The single gate every delay-model flag passes through (batched
    engine, oracle samplers, serving layer). Returns the validated name;
    raises ``ValueError`` listing the known families — and the ``"auto"``
    selector sentinel where the caller accepts it."""
    known = DELAY_MODELS + ((AUTO_DELAY_MODEL,) if allow_auto else ())
    if delay_model not in known:
        raise ValueError(
            f"unknown delay_model {delay_model!r}; known: {known}")
    return delay_model

#: Host-side dispatch counter: +1 per batched device call. The hot-path
#: tests assert the replay suite runs entirely through here (no per-stream
#: Python-loop simulation).
BATCH_DISPATCHES = 0


def stream_seed_sequence(seed: int, t: int, i: int) -> np.random.SeedSequence:
    """Collision-free numpy RNG stream for (epoch ``t``, stream ``i``).

    ``SeedSequence(entropy=seed, spawn_key=(t, i))`` hashes the pair into
    the stream key, so distinct ``(t, i)`` never collide — unlike the old
    ``seed + 7919 * t + i`` arithmetic (t=0,i=7919 == t=1,i=0)."""
    return np.random.SeedSequence(entropy=seed, spawn_key=(t, i))


def epoch_key(seed: int, t: int):
    """Folded jax.random key for epoch ``t``; streams fold in their index
    on top (``_window_sim``), so (epoch, stream) keys never collide."""
    return jax.random.fold_in(jax.random.key(seed), t)


def frames_budget(max_lam: float, horizon: float, frames_cap: int,
                  frames_floor: int = 200) -> int:
    """Frames to simulate so arrivals cover ``[0, horizon]`` w.h.p. for
    the fastest stream: ``lam*H`` plus a 2-sigma margin (a rare shortfall
    only shrinks the *measured* window ``h_eff`` — unbiased — instead of
    skewing the estimate), rounded up to a quarter-power-of-two bucket
    (bounds jit recompiles across windows at <= 25% overshoot), capped at
    ``frames_cap``. The floor keeps tiny epochs statistically meaningful;
    age integration truncates at the horizon regardless, so the floor
    never inflates measured AoPI past the epoch."""
    need = float(max_lam) * float(horizon)
    need = max(need + 2.0 * np.sqrt(max(need, 1.0)) + 16.0,
               float(frames_floor), 2.0)
    p2 = 2.0 ** np.floor(np.log2(need))
    for m in (1.0, 1.25, 1.5, 1.75, 2.0):
        if p2 * m >= need:
            return int(min(np.ceil(p2 * m), frames_cap))
    raise AssertionError("unreachable")


#: Compute dtype switch: short per-stream frame budgets run the whole
#: engine in float32 (sequential-sum error ~ n_frames^1.5 * eps stays
#: below 1e-2 of a mean delay up to ~1k frames), longer horizons switch
#: to float64 so multi-hour epochs keep sub-millisecond age resolution
#: (matching the numpy oracle). Deterministic per workload: the dtype is
#: a pure function of the frame budget.
F32_MAX_FRAMES = 1024


def _n_uniforms(delay_model: str) -> int:
    """Uniform variates consumed per frame: T + O + the accuracy coin.
    The Erlang-``k`` gamma family needs ``k`` uniforms per delay."""
    if delay_model == "gamma" and float(GAMMA_SHAPE) == int(GAMMA_SHAPE):
        return 2 * int(GAMMA_SHAPE) + 1
    return 3


def _delays_from_uniforms(u, mean, delay_model: str):
    """``u`` is ``[k, n]`` uniforms -> ``[n]`` positive delays with mean
    ``mean`` (matching the numpy ``Sampler`` helpers)."""
    if delay_model == "mm1":
        return -jnp.log1p(-u[0]) * mean
    if delay_model == "uniform":
        lo = mean * (1.0 - UNIFORM_SPREAD)
        return lo + u[0] * (2.0 * UNIFORM_SPREAD * mean)
    if delay_model == "gamma":
        # Integer shape -> Erlang: an exact sum of k exponentials. Orders
        # of magnitude faster than jax.random.gamma's rejection sampler
        # (a vmapped while_loop) on CPU at data-plane frame counts.
        k = int(GAMMA_SHAPE)
        if float(GAMMA_SHAPE) == k:
            return -jnp.log1p(-u).sum(axis=0) * (mean / GAMMA_SHAPE)
    if delay_model == "lognormal":
        # Inverse-CDF: exp(m + sigma * Phi^-1(u)) with the mean-matching
        # log-location m = ln(mean) - sigma^2/2. Clip u away from {0, 1}
        # so ndtri stays finite (u=0 would give a literal zero delay).
        uc = jnp.clip(u[0], 1e-7, 1.0 - 1e-7)
        m = jnp.log(mean) - 0.5 * LOGNORMAL_SIGMA * LOGNORMAL_SIGMA
        return jnp.exp(m + LOGNORMAL_SIGMA * jax.scipy.special.ndtri(uc))
    if delay_model == "weibull":
        # Inverse-CDF: scale * (-ln(1-u))^(1/k), mean-matched via
        # scale = mean / Gamma(1 + 1/k). k < 1 => sub-exponential tail.
        scale = mean / math.gamma(1.0 + 1.0 / WEIBULL_SHAPE)
        return scale * jnp.power(-jnp.log1p(-u[0]), 1.0 / WEIBULL_SHAPE)
    raise ValueError(
        f"unknown delay_model {delay_model!r}; known: {DELAY_MODELS}")


#: Streams per epoch whose raw transmission delays are surfaced when
#: ``collect_samples`` is set — enough for the CvM selector to pool a
#: few thousand draws without shipping the whole [E, N, F] tensor host-side.
SAMPLE_STREAM_CAP = 32


@functools.partial(jax.jit, static_argnames=(
    "n_frames", "delay_model", "collect_samples"))
def _window_sim(lam, mu, p, pol, keys, horizon, n_frames: int,
                delay_model: str, collect_samples: int = 0):
    """The fused data-plane program: ONE ``lax.scan`` over the frame axis
    with ``[E * N]``-wide vector carries.

    Single-pass recurrences (like the numpy oracle's cumsums, unlike
    XLA's O(n log n) associative cumulative ops) batched across every
    (epoch, stream) pair of the window, with the exact piecewise-linear
    age integral accumulated forward in the same pass — so the whole
    window is one dispatch whose per-step body is a handful of fused
    elementwise ops on the flattened stream vector.
    """
    e, n = lam.shape
    dtype = lam.dtype
    flat = lambda x: x.reshape(e * n)
    lam, mu, p = flat(lam), flat(mu), flat(p)
    is_lcfsp = flat(pol) == 1

    # Collision-free per-(epoch, stream) keys; all of a stream's variates
    # come from one bulk uniform draw under its own key.
    stream_keys = jax.vmap(
        lambda ke: jax.vmap(jax.random.fold_in, (None, 0))(
            ke, jnp.arange(n)))(keys)
    k = _n_uniforms(delay_model)
    ku, ko = k // 2, (k - 1) - k // 2

    def draw(key):
        u = jax.random.uniform(key, (k, n_frames), dtype)
        return u

    u = jax.vmap(draw)(stream_keys.reshape(e * n))       # [EN, k, F]
    T = _delays_from_uniforms(
        jnp.moveaxis(u[:, :ku], 0, -1), 1.0 / lam, delay_model)
    O = _delays_from_uniforms(
        jnp.moveaxis(u[:, ku:ku + ko], 0, -1), 1.0 / mu, delay_model)
    coin = jnp.moveaxis(u[:, -1], 0, -1)                 # [F, EN]
    # LCFSP completion needs the NEXT transmission time at each step.
    T_next = jnp.concatenate(
        [T[1:], jnp.full((1, e * n), jnp.inf, dtype)])
    # Effective horizon: the epoch, unless the frame budget (frames_cap)
    # ran out of arrivals first — then measure over the simulated window
    # instead of counting the uncovered tail as pure age growth.
    h_eff = jnp.minimum(jnp.asarray(horizon, dtype), T.sum(axis=0))
    zero = jnp.zeros(e * n, dtype)

    def step(carry, xs):
        a, s, m, last_t, age0, area, n_arr, n_done, n_acc = carry
        t_f, t_nxt, o_f, u_f = xs
        a = a + t_f                            # arrival a_i = tau_{i+1}
        gen = a - t_f                          # generation tau_i
        s = s + o_f                            # cumsum of service times
        m = jnp.maximum(m, a - (s - o_f))      # running max idle slack
        finish = jnp.where(is_lcfsp, a + o_f, s + m)
        completed = jnp.where(is_lcfsp, o_f < t_nxt, True)
        done = completed & (finish <= h_eff)
        valid = done & (u_f < p)
        # Age resets to finish - gen at each valid event; events are
        # nondecreasing in time, so accumulate the closed segment.
        seg = jnp.where(valid, finish - last_t, zero)
        area = area + age0 * seg + 0.5 * seg * seg
        last_t = jnp.where(valid, finish, last_t)
        age0 = jnp.where(valid, finish - gen, age0)
        n_arr = n_arr + (a <= h_eff)
        n_done = n_done + done
        n_acc = n_acc + valid
        return (a, s, m, last_t, age0, area, n_arr, n_done, n_acc), None

    init = (zero, zero, jnp.full(e * n, -jnp.inf, dtype), zero, zero,
            zero, zero, zero, zero)
    (a, s, m, last_t, age0, area, n_arr, n_done, n_acc), _ = lax.scan(
        step, init, (T, T_next, O, coin))
    # Final open segment up to the effective horizon.
    seg = jnp.maximum(h_eff - last_t, zero)
    area = area + age0 * seg + 0.5 * seg * seg
    shape = lambda x: x.reshape(e, n)
    out = {
        "aopi": shape(area / h_eff),
        "horizon": shape(h_eff),
        "n_frames": shape(n_arr),
        "n_completed": shape(n_done),
        "n_accurate": shape(n_acc),
    }
    if collect_samples:
        # Raw transmission delays for the fitted selector: the camera
        # uploads back-to-back (§III-A), so inter-arrival == transmission
        # times, i.e. the T draws ARE family-distributed observations.
        capf = min(int(collect_samples), n_frames)
        ns = min(n, SAMPLE_STREAM_CAP)
        samp = T[:capf].reshape(capf, e, n)[:, :, :ns]
        out["delay_samples"] = jnp.moveaxis(samp, 0, -1)   # [E, ns, capf]
    return out


def gi_g1_window(lam, mu, p, pol, *, seed: int = 0, t0: int = 0,
                 n_frames: int, horizon: float,
                 delay_model: str = "mm1", active=None,
                 collect_samples: int = 0) -> dict:
    """Simulate ``[E, N]`` GI/G/1 streams (E epochs x N streams) in ONE
    jitted device dispatch.

    Per (epoch ``t0+e``, stream ``i``): ``n_frames`` transmission/service
    delays are drawn from ``delay_model`` with means ``1/lam``/``1/mu``
    under the collision-free key ``fold_in(fold_in(key(seed), t), i)``,
    both queueing recurrences are solved in closed vectorized form, and
    the exact age integral is truncated at ``horizon`` seconds — measured
    AoPI reflects the epoch even when ``n_frames`` extends past it. If a
    stream's frame budget runs out *before* the horizon (``frames_cap``),
    the integral covers the simulated window instead (the per-stream
    effective horizon is returned).

    Dead streams — ``lam <= 0`` or ``mu <= 0``, or masked out by the
    optional ``active`` ``[E, N]`` fleet-churn mask — are simulated on
    rate-clamped stand-ins and then zeroed in every output array, so the
    window stays one fused dispatch and fleet reductions stay finite.
    Live lanes are bitwise identical to an unmasked call.

    ``collect_samples > 0`` additionally returns ``delay_samples``
    ``[E, min(N, SAMPLE_STREAM_CAP), collect_samples]`` — the raw
    transmission-delay draws (exactly family-distributed, since uploads
    are back-to-back) for the telemetry-fitted :func:`fit_delay_model`
    selector. Dead-lane samples are zeroed.

    One ``lax.scan`` over the frame axis carries every (epoch, stream)
    recurrence as an ``[E*N]`` vector — single-pass like the numpy
    oracle's cumsums, but batched across the whole window. Short frame
    budgets (<= ``F32_MAX_FRAMES``) run in float32; longer horizons
    switch to float64 (scoped ``jax.enable_x64``) so multi-hour epochs keep
    sub-millisecond age resolution, matching the oracle. Returns host
    numpy: ``aopi``/``horizon``/``n_frames``/``n_completed``/
    ``n_accurate``, each ``[E, N]``.
    """
    validate_delay_model(delay_model)
    global BATCH_DISPATCHES
    n_frames = int(n_frames)
    # Heavy tails force the f64 branch: the f32 <= 1024-frames fast path
    # relies on delays staying within a few means of each other, which a
    # sub-exponential tail violates (see HEAVY_TAIL_MODELS).
    use_f64 = n_frames > F32_MAX_FRAMES or delay_model in HEAVY_TAIL_MODELS
    dtype = np.float64 if use_f64 else np.float32
    lam = np.atleast_2d(np.asarray(lam, dtype))
    mu_h = np.atleast_2d(np.asarray(mu, dtype))
    live = (lam > 0.0) & (mu_h > 0.0)
    if active is not None:
        live = live & (np.atleast_2d(np.asarray(active)) > 0.0)
    e, n = lam.shape
    obs.histogram("queues.batch_elems",
                  delay_model=delay_model).observe(e * n * n_frames)
    with obs.span("queues.gi_g1_window", delay_model=delay_model,
                  epochs=e, streams=n, n_frames=n_frames), jax.enable_x64(True):
        keys = jax.vmap(jax.random.fold_in, (None, 0))(
            jax.random.key(int(seed)), jnp.arange(t0, t0 + e))
        out = _window_sim(
            jnp.asarray(np.maximum(lam, dtype(1e-6))),
            jnp.asarray(np.maximum(mu_h, dtype(1e-6))),
            jnp.asarray(np.clip(
                np.atleast_2d(np.asarray(p, dtype)), 1e-3, 1.0)),
            jnp.asarray(np.atleast_2d(np.asarray(pol, np.int32))),
            keys, float(horizon), n_frames, str(delay_model),
            int(collect_samples))
        out = {k: np.asarray(v, np.float64) for k, v in out.items()}
        if not live.all():
            # Dead lanes ran on clamped stand-in rates — zero them out.
            samples = out.pop("delay_samples", None)
            out = {k: np.where(live, v, 0.0) for k, v in out.items()}
            if samples is not None:
                ns = samples.shape[1]
                out["delay_samples"] = np.where(
                    live[:, :ns, None], samples, 0.0)
    BATCH_DISPATCHES += 1
    obs.counter("queues.batch_dispatches", delay_model=delay_model).inc()
    return out


# ---------------------------------------------------------------------------
# Telemetry-fitted delay-model selector
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DelayFit:
    """Result of :func:`fit_delay_model`: the winning family plus the
    per-family Cramér–von Mises residuals it beat (smaller = closer)
    and the winner's fitted shape parameters (``{"sigma": ...}`` for
    lognormal, ``{"k": ...}`` for weibull, empty for the shape-free
    families)."""
    model: str
    residuals: dict
    n_samples: int
    params: dict = field(default_factory=dict)


#: CvM estimation grids for the shape-parameterized families: the fit
#: is a joint (family, shape) minimization, not just family selection.
#: The defaults (LOGNORMAL_SIGMA=1.0, WEIBULL_SHAPE=0.7) are grid
#: members, so default-parameter worlds round-trip exactly; the weibull
#: grid stays strictly below k=1 (k=1 IS the exponential — it belongs
#: to "mm1").
LOGNORMAL_SIGMA_GRID = (0.5, 0.75, 1.0, 1.25, 1.5)
WEIBULL_SHAPE_GRID = (0.5, 0.6, 0.7, 0.8, 0.9)

_FAMILY_GRIDS = {"lognormal": ("sigma", LOGNORMAL_SIGMA_GRID),
                 "weibull": ("k", WEIBULL_SHAPE_GRID)}


def _family_cdf(x: np.ndarray, delay_model: str,
                params: dict | None = None) -> np.ndarray:
    """CDF of the unit-mean member of ``delay_model`` evaluated at ``x``
    (x >= 0). Each family is parameterized exactly as the samplers /
    ``_delays_from_uniforms`` are, with the mean pinned to 1; ``params``
    overrides the shape (``sigma`` for lognormal, ``k`` for weibull),
    defaulting to the sampler constants."""
    params = params or {}
    if delay_model == "mm1":
        return -np.expm1(-x)
    if delay_model == "uniform":
        lo, width = 1.0 - UNIFORM_SPREAD, 2.0 * UNIFORM_SPREAD
        return np.clip((x - lo) / width, 0.0, 1.0)
    if delay_model == "gamma":
        # Erlang-k with mean 1 => rate k. Closed form for integer k.
        k = int(GAMMA_SHAPE)
        terms = sum((k * x) ** j / math.factorial(j) for j in range(k))
        return -np.expm1(-k * x) - np.exp(-k * x) * (terms - 1.0)
    if delay_model == "lognormal":
        from scipy.special import ndtr
        s = float(params.get("sigma", LOGNORMAL_SIGMA))
        m = -0.5 * s * s
        safe = np.maximum(x, 1e-300)
        return np.where(x > 0.0, ndtr((np.log(safe) - m) / s), 0.0)
    if delay_model == "weibull":
        k = float(params.get("k", WEIBULL_SHAPE))
        scale = 1.0 / math.gamma(1.0 + 1.0 / k)
        return -np.expm1(-np.power(np.maximum(x, 0.0) / scale, k))
    raise ValueError(
        f"unknown delay_model {delay_model!r}; known: {DELAY_MODELS}")


def family_cv2(delay_model: str, params: dict | None = None) -> float:
    """Squared coefficient of variation of a delay family (optionally at
    fitted shape ``params``) — the tail statistic that drives how far
    the exponential closed forms drift: 1 for mm1, < 1 for the light
    §III-B families, > 1 for the heavy tails."""
    validate_delay_model(delay_model)
    params = params or {}
    if delay_model == "mm1":
        return 1.0
    if delay_model == "uniform":
        return UNIFORM_SPREAD ** 2 / 3.0
    if delay_model == "gamma":
        return 1.0 / float(GAMMA_SHAPE)
    if delay_model == "lognormal":
        s = float(params.get("sigma", LOGNORMAL_SIGMA))
        return float(np.expm1(s * s))
    k = float(params.get("k", WEIBULL_SHAPE))
    g1 = math.gamma(1.0 + 1.0 / k)
    return math.gamma(1.0 + 2.0 / k) / (g1 * g1) - 1.0


def residual_prior(delay_model: str, params: dict | None = None) -> float:
    """Kingman-style residual scale prior for the planner: GI/G/1
    waiting time scales like ``(C_a^2 + C_s^2) / 2`` relative to M/M/1,
    so a fitted family's ``(1 + cv^2) / 2`` (both T and O drawn from the
    family) is the first-order correction to the exponential closed
    forms — exactly 1 for mm1, so seeding with it is a no-op when the
    world matches the paper's model."""
    return 0.5 * (1.0 + family_cv2(delay_model, params))


def fit_delay_model(samples, models: Sequence[str] = DELAY_MODELS,
                    min_samples: int = 8) -> DelayFit:
    """Pick the (delay family, shape parameters) with the smallest
    Cramér–von Mises residual against observed delay samples.

    ``samples`` is any array of positive delay observations (pooled
    inter-completion / transmission times from telemetry; zeros — masked
    dead-lane fill — are dropped). Each candidate family is mean-matched
    to the sample mean, its CDF evaluated at the sorted samples, and the
    mean squared distance to the empirical CDF ``(i - 0.5)/n`` taken as
    the residual; the shape-parameterized families (lognormal sigma,
    weibull k) additionally minimize over their estimation grids, and
    the winner's fitted shape is returned on ``DelayFit.params``. Falls
    back to "mm1" (the paper's modeling assumption) below
    ``min_samples`` observations.
    """
    x = np.asarray(samples, np.float64).ravel()
    x = x[np.isfinite(x) & (x > 0.0)]
    n = x.size
    if n < min_samples:
        return DelayFit("mm1", {}, n)
    x = np.sort(x) / x.mean()                 # mean-matched, unit scale
    ecdf = (np.arange(1, n + 1) - 0.5) / n
    cvm = lambda m, prm: float(np.mean((_family_cdf(x, m, prm) - ecdf) ** 2))
    residuals: dict = {}
    params: dict = {}
    for m in models:
        grid = _FAMILY_GRIDS.get(m)
        if grid is None:
            residuals[m], params[m] = cvm(m, None), {}
        else:
            pname, values = grid
            cand = {v: cvm(m, {pname: v}) for v in values}
            v = min(cand, key=cand.get)
            residuals[m], params[m] = cand[v], {pname: float(v)}
    best = min(residuals, key=residuals.get)
    return DelayFit(best, residuals, n, params[best])
