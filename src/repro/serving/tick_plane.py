"""Tick-scan engine plane: the engine rung as ONE jitted ``lax.scan``.

PR 9's ``engine_plane`` closed the truth ladder's third rung with a
host-side discrete-event replay of the real continuous-batching
:class:`~.engine.Engine` — correct, but ~3 orders of magnitude slower
per frame than the batched GI/G/1 plane, so ``mode="engine"`` was capped
at smoke-sized frame budgets. This module is the batched, device-resident
equivalent: because the replay plane pins **one lane per stream**
(``n_lanes >= n_streams``), lanes never contend, and the whole DES —
admit/prefill, batched decode ticks, LCFSP preemption with version
invalidation, FCFS backlog, epoch-end drain, ``h_eff`` truncation —
collapses to per-lane recurrences that a single ``lax.scan`` over decode
ticks (one tick per frame index, all ``E*N`` lanes advanced together)
replays **bitwise-compatibly** with the DES:

  * identical pre-drawn T/O/coin streams (``stream_seed_sequence`` +
    ``oracle_samplers`` — shared via ``engine_plane.draw_streams``);
  * FCFS service start is the sequential ``max(a_k, fin_{k-1})``
    recurrence in float64 — the same op-for-op float chain the DES heap
    produces (NOT the cumsum/running-max algebraic form ``gi_g1_window``
    uses, which is only algebraically equal);
  * LCFSP completion wins time ties with the next arrival
    (``fin <= a_next``): the DES pushes the completion event before the
    arrival that could preempt it, so equal timestamps pop completion
    first. A preemption is counted iff the next arrival was actually
    scheduled (``a_k <= h_eff``) and strictly beats the finish;
  * the carried lane state (service-finish front, last-update time,
    sampled age, arrival/completion/accuracy/preempt counts, busy time)
    is exactly the DES bookkeeping, vectorized ``[E*N]``-wide; the scan
    *is* the version counter — a preempted finish simply never updates
    the carry, which is what invalidation does in the DES;
  * the age-area polynomial terms (``age0*seg`` and ``0.5*seg*seg``) are
    *emitted* per tick and summed on the host in DES event order rather
    than accumulated in the carry: XLA's CPU codegen contracts any
    multiply-feeding-add into an FMA inside a fused loop (1-2 ulp drift
    the DES's numpy arithmetic never sees, immune to
    ``optimization_barrier``), while a bare multiply rounds identically
    everywhere. Pure products on device, order-preserving sums on host
    => bitwise-identical ``aopi``.

Everything the DES counts inside the effective horizon is reproduced
bitwise (``aopi``/``n_frames``/``n_completed``/``n_accurate``/
``preempts`` and the (stream, frame, completion-time) trace — pinned by
``tests/test_engine_plane.py`` for all five delay families). What is
*not* replayed is the stub model's token arithmetic: the DES drives real
admits and decode dispatches, the scan reproduces their timing algebra.
Use the DES when lane bookkeeping itself is under test; use the scan
when the engine rung must run at full-suite scale.

``delay_samples`` for the fitted selector come straight off the host-side
pre-draws — zero extra device transfers; all per-stream outputs leave the
device in one ``device_get``.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs
from ..core import queues
from . import engine_plane

#: Engine-rung backend grammar (``AnalyticsService``, ``replay_tables``,
#: ``sweep`` ``engine_params={"backend": ...}``). "des" is the PR-9
#: host-side discrete-event replay of the real Engine; "scan" is this
#: module's batched device-resident replay; "auto" keeps the DES at
#: small scale (the real engine's lane bookkeeping stays exercised) and
#: switches to the scan once the epoch's frame volume would make the DES
#: the bottleneck.
ENGINE_BACKENDS = ("des", "scan", "auto")

#: "auto" keeps the DES while ``n_streams * frames_cap`` is at most this
#: many frame events per epoch (~a few hundred ms of host DES), and
#: switches to the tick-scan above it.
AUTO_DES_MAX_FRAMES = 4096


def resolve_engine_backend(backend: str, *, n_streams: int,
                           frames_cap: int) -> str:
    """Validate ``backend`` and resolve ``"auto"`` by epoch frame volume."""
    if backend not in ENGINE_BACKENDS:
        raise ValueError(
            f"unknown engine_backend {backend!r}; known: {ENGINE_BACKENDS}")
    if backend != "auto":
        return backend
    return ("des" if int(n_streams) * int(frames_cap) <= AUTO_DES_MAX_FRAMES
            else "scan")


def _tick_scan_impl(t_f, o_f, u_f, a, a_nxt, p, is_lcfsp, h_eff, live,
                    collect_trace=False):
    """One epoch of every lane as a single scan over decode ticks.

    All array args are float64 (bools for ``is_lcfsp``/``live``); the
    tick axis is leading on the ``[F, S]`` inputs, ``S = E*N`` lanes.
    Returns (out dict of ``[S]`` stats, optional ``[F, S]`` trace ys).
    """
    zero = jnp.zeros((), t_f.dtype)
    init = tuple(jnp.zeros(p.shape[0], t_f.dtype) for _ in range(8))

    def step(carry, x):
        fin_prev, last_t, age0, n_arr, n_done, n_acc, n_pre, busy = carry
        tk, ok, uk, ak, nk = x
        gen = ak - tk
        # FCFS seizes at arrival or queues behind the finish front;
        # LCFSP always seizes at arrival (preempting the front).
        start = jnp.where(is_lcfsp, ak, jnp.maximum(ak, fin_prev))
        fin = start + ok
        arrived = ak <= h_eff
        # LCFSP completion survives iff it beats the next arrival;
        # ties go to the completion (DES heap pushes it first). The
        # preempting arrival only exists if it was scheduled, i.e. the
        # current arrival was still inside the effective horizon.
        completed = jnp.where(is_lcfsp, fin <= nk, True)
        preempted = is_lcfsp & (fin > nk) & arrived
        done = completed & (fin <= h_eff) & live
        valid = done & (uk < p)
        seg = jnp.where(valid, fin - last_t, zero)
        # Age-area polynomial terms. Emitted as scan outputs — NOT
        # summed in the carry — so the device only performs the bare
        # multiplies (which round identically to numpy); the host sums
        # them in event order. An in-carry ``age0*seg + 0.5*seg*seg``
        # gets FMA-contracted by the CPU codegen and drifts 1-2 ulp off
        # the DES.
        t1 = age0 * seg
        t2 = 0.5 * seg * seg
        # Busy time (batch occupancy): service runs from its start to
        # finish — or to the preempting arrival under LCFSP — clipped
        # to the effective horizon.
        nxt_gate = jnp.where(arrived, nk, jnp.inf)
        end_s = jnp.where(is_lcfsp, jnp.minimum(fin, nxt_gate), fin)
        busy_seg = jnp.maximum(
            jnp.minimum(end_s, h_eff) - jnp.minimum(start, h_eff), zero)
        carry = (fin,
                 jnp.where(valid, fin, last_t),
                 jnp.where(valid, fin - gen, age0),
                 n_arr + arrived,
                 n_done + done,
                 n_acc + valid,
                 n_pre + preempted,
                 busy + busy_seg)
        ys = ((t1, t2, fin, done) if collect_trace else (t1, t2))
        return carry, ys

    carry, ys = lax.scan(step, init, (t_f, o_f, u_f, a, a_nxt))
    _, last_t, age0, n_arr, n_done, n_acc, n_pre, busy = carry
    safe_h = jnp.maximum(h_eff, 1e-12)
    out = {
        "n_frames": jnp.where(live, n_arr, zero),
        "n_completed": jnp.where(live, n_done, zero),
        "n_accurate": jnp.where(live, n_acc, zero),
        "preempts": jnp.where(live, n_pre, zero),
        "occupancy": jnp.where(live, busy / safe_h, zero),
    }
    return out, (last_t, age0), ys


_tick_scan = jax.jit(_tick_scan_impl, static_argnames=("collect_trace",))


def measure_engine_window_scan(lam, mu, p, pol, *, epoch_duration: float,
                               seed: int = 0, t0: int = 0,
                               delay_model: str = "mm1", active=None,
                               frames_cap: int =
                               engine_plane.ENGINE_FRAMES_CAP,
                               collect_samples: int = 0,
                               collect_trace: bool = False) -> dict:
    """Replay ``[E, N]`` engine epochs in ONE jitted scan dispatch.

    Each (epoch ``t0+e``, stream ``i``) lane replays the exact stochastic
    process the DES would run for that epoch (same
    ``stream_seed_sequence(seed, t0+e, i)`` pre-draws), all ``E*N`` lanes
    carried together. Returns the ``gi_g1_window``-shaped stat dict
    (``[E, N]`` values) plus ``preempts``/``occupancy`` ``[E, N]``,
    scalar ``engine_steps`` (scan ticks), optional ``delay_samples``
    ``[E, N, collect_samples]`` and, under ``collect_trace``, ``trace``:
    a list of ``(epoch, stream, frame, t_done)`` completion events in
    canonical ``(t_done, stream, frame)`` order per epoch.
    """
    queues.validate_delay_model(delay_model)
    lam = np.atleast_2d(np.asarray(lam, np.float64))
    mu = np.atleast_2d(np.asarray(mu, np.float64))
    p = np.clip(np.atleast_2d(np.asarray(p, np.float64)), 1e-3, 1.0)
    pol = np.atleast_2d(np.asarray(pol, np.int64))
    e, n = lam.shape
    live = (lam > 0.0) & (mu > 0.0)
    if active is not None:
        live = live & (np.atleast_2d(np.asarray(active, np.float64)) > 0.0)
    f = int(frames_cap)
    s = e * n
    T = np.zeros((s, f))
    O = np.zeros((s, f))
    coin = np.ones((s, f))
    for ei in range(e):
        Te, Oe, Ce = engine_plane.draw_streams(
            lam[ei], mu[ei], live[ei], delay_model=delay_model,
            seed=seed, t=t0 + ei, frames_cap=f)
        T[ei * n:(ei + 1) * n] = Te
        O[ei * n:(ei + 1) * n] = Oe
        coin[ei * n:(ei + 1) * n] = Ce
    arrive = np.cumsum(T, axis=1)                 # a_k; gen_k = a_k - T_k
    live_f = live.ravel()
    h_eff = np.where(live_f, np.minimum(float(epoch_duration),
                                        arrive[:, -1]), 0.0)
    a_nxt = np.concatenate([arrive[:, 1:], np.full((s, 1), np.inf)], axis=1)

    with obs.span("tick_plane.window", delay_model=delay_model,
                  epochs=e, streams=n, n_frames=f), jax.enable_x64(True):
        out, fin_state, ys = _tick_scan(
            jnp.asarray(T.T), jnp.asarray(O.T), jnp.asarray(coin.T),
            jnp.asarray(arrive.T), jnp.asarray(a_nxt.T),
            jnp.asarray(p.ravel()), jnp.asarray(pol.ravel() == 1),
            jnp.asarray(h_eff), jnp.asarray(live_f),
            collect_trace=collect_trace)
        # One transfer per window: stats + final lane state + tick ys.
        out, (last_t, age0), ys = jax.device_get((out, fin_state, ys))

    # Order-preserving age-area reduction (see module docstring): the
    # device emits the exact products per tick, the host adds them in
    # the DES's event order — bitwise identical to the heap replay.
    t1, t2 = np.asarray(ys[0]), np.asarray(ys[1])     # [F, S]
    area = np.zeros(s)
    for k in range(f):
        area += t1[k] + t2[k]
    seg = np.maximum(h_eff - last_t, 0.0)             # DES drain point
    area += age0 * seg + 0.5 * seg * seg
    safe_h = np.maximum(h_eff, 1e-12)
    out["aopi"] = np.where(live_f, area / safe_h, 0.0)

    occ = out["occupancy"][live_f]
    out = {k: np.asarray(v, np.float64).reshape(e, n)
           for k, v in out.items()}
    out["horizon"] = h_eff.reshape(e, n)
    out["engine_steps"] = float(f)
    if collect_samples:
        cap = min(int(collect_samples), f)
        out["delay_samples"] = np.where(
            live_f[:, None], T[:, :cap], 0.0).reshape(e, n, cap)
    if collect_trace:
        fin, done = np.asarray(ys[2]), np.asarray(ys[3])   # [F, S]
        kk, ss = np.nonzero(done)
        ev = zip((ss // n).tolist(), (ss % n).tolist(), kk.tolist(),
                 fin[kk, ss].tolist())
        out["trace"] = sorted(ev, key=lambda r: (r[0], r[3], r[1], r[2]))
    obs.counter("engine.ticks", backend="scan",
                delay_model=delay_model).inc(float(f))
    obs.counter("engine.preempts", backend="scan").inc(
        float(out["preempts"].sum()))
    if occ.size:
        obs.histogram("engine.occupancy", backend="scan").observe_many(occ)
    return out


def measure_engine_epoch_scan(lam, mu, p, pol, *, epoch_duration: float,
                              seed: int = 0, t: int = 0,
                              delay_model: str = "mm1", active=None,
                              frames_cap: int =
                              engine_plane.ENGINE_FRAMES_CAP,
                              collect_samples: int = 0,
                              collect_trace: bool = False) -> dict:
    """Single-epoch tick-scan replay: the drop-in batched equivalent of
    ``engine_plane.measure_engine_epoch`` (same ``[N]`` stat dict, same
    draws, bitwise-identical counted statistics — no Engine instance
    required)."""
    out = measure_engine_window_scan(
        np.asarray(lam, np.float64).ravel()[None, :],
        np.asarray(mu, np.float64).ravel()[None, :],
        np.asarray(p, np.float64).ravel()[None, :],
        np.asarray(pol, np.int64).ravel()[None, :],
        epoch_duration=epoch_duration, seed=seed, t0=t,
        delay_model=delay_model,
        active=None if active is None
        else np.asarray(active, np.float64).ravel()[None, :],
        frames_cap=frames_cap, collect_samples=collect_samples,
        collect_trace=collect_trace)
    trace = out.pop("trace", None)
    steps = out.pop("engine_steps")
    out = {k: v[0] for k, v in out.items()}
    out["engine_steps"] = steps
    if trace is not None:
        out["trace"] = [(i, k, td) for _, i, k, td in trace]
    return out


def measure_epoch(lam, mu, p, pol, *, backend: str = "auto", engine=None,
                  frames_cap: int = engine_plane.ENGINE_FRAMES_CAP,
                  **kw) -> dict:
    """Backend-dispatching engine-rung epoch measurement.

    Resolves ``backend`` (``ENGINE_BACKENDS``) against the epoch's frame
    volume and runs either the DES replay on ``engine`` (required for
    ``"des"``) or the tick-scan. Both return the same stat dict over the
    same pre-drawn stochastic process.
    """
    n = np.asarray(lam).ravel().size
    resolved = resolve_engine_backend(backend, n_streams=n,
                                      frames_cap=frames_cap)
    if resolved == "scan":
        return measure_engine_epoch_scan(lam, mu, p, pol,
                                         frames_cap=frames_cap, **kw)
    if engine is None:
        raise ValueError("engine_backend 'des' needs an Engine instance "
                         "(make_replay_engine)")
    return engine_plane.measure_engine_epoch(engine, lam, mu, p, pol,
                                             frames_cap=frames_cap, **kw)
