"""The AoPI-tracked analytics service: LBCD in the serving control plane.

Per controller epoch (= the paper's 5-minute slot):
  1. the *planner* decides per-stream (model candidate, fidelity/resolution,
     FCFS/LCFSP policy, island assignment, ingest + compute-share
     allocation) by solving (P2);
  2. the data plane runs: frames arrive per the transmission model, are
     queued per-policy, and processed with the allocated compute rate;
  3. measured AoPI (exact age integration) and per-stream telemetry
     (accurate fraction, arrival/completion rates) feed the virtual queue
     and the next planning window's profiles.

Two planners:
  * ``planner="scan"`` (default) — lookahead windows of ``plan_window``
    epochs are solved as ONE jitted ``lax.scan`` (``lbcd.rollout`` for the
    LBCD controller, the ``baselines.rollout_*`` engines for MIN/DOS/JCAB)
    over a ``profiles.HorizonTables`` window; ``plan_horizon(k)`` exposes
    the same call for what-if queries. ``solver_backend`` (including
    ``"auto"``/``"pallas"`` and spec strings like ``"pallas:tile=4096"``
    or ``"pallas:nofuse"``) threads through from the controller, so
    kernel-backed replay rides the fused — and, at large N, camera-tiled
    — slot solver.
  * ``planner="step"`` — the legacy per-slot ``controller.step(t)`` path
    (kept for custom ``assign_fn`` controllers and failover experiments).

Two data planes ship:
  * ``mode="mm1"``  — the batched device-resident GI/G/1 engine
    (``queues.gi_g1_window``): every stream of a whole plan window is
    simulated in ONE jitted dispatch shaped ``[E, N, F]``, with
    ``delay_model`` selecting exponential ("mm1", the paper's model that
    validates Theorems 1-2 at scale), uniform, or gamma delays (the
    §III-B testbed regime where the closed forms drift). The plane
    executes against the *unscaled* scenario truth: measured accuracy
    uses the raw profile table and the true link efficiency, while the
    planner sees the telemetry-corrected beliefs — exactly the
    model-vs-measurement split where config-adaptation policies break.
    ``replan_threshold`` arms divergence-triggered replanning: a mid-
    window drift past the threshold cuts the window and replans early.
  * ``mode="engine"`` — the engine rung: frames replayed through the
    continuous-batching Engine over its stub model
    (``make_replay_engine``), with LCFSP preemption at step boundaries.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults as fault_plane
from .. import obs
from ..core import baselines, bcd, binpack, lbcd, queues
from ..core.lbcd import LBCDController
from ..core.profiles import HorizonTables
from .scheduler import AoPITracker, Frame, StreamQueue, StreamTelemetry


def _policy_label(controller) -> str:
    """Metric/span ``policy`` label for a controller (the sweep names
    where recognizable, the class name otherwise)."""
    names = {"LBCDController": "lbcd", "MINController": "min",
             "DOSController": "dos", "JCABController": "jcab"}
    cls = type(controller).__name__
    return names.get(cls, cls.lower())


#: Planning failures the degradation ladder absorbs (see
#: ``AnalyticsService._plan_with_ladder``).
_LADDER_FAILURES = (fault_plane.InjectedSolverFault, FloatingPointError,
                    TimeoutError)

#: Element budget (epochs x streams x frames) of one batched data-plane
#: dispatch; larger windows are chunked along the epoch axis so peak
#: device memory stays bounded (~a few hundred MB of f64 intermediates).
MAX_BATCH_ELEMS = 1 << 25


def _window_dispatch(lam, horizon: float, frames_cap: int,
                     frames_floor: int = 200) -> tuple[int, int]:
    """``(n_frames, epochs per dispatch)`` of :func:`measure_window` for
    ``[E, N]`` arrival rates ``lam``: the fastest stream's frame budget,
    and as many epochs a dispatch as ``MAX_BATCH_ELEMS`` holds."""
    lam = np.atleast_2d(lam)
    n_frames = queues.frames_budget(max(lam.max(), 1e-6), horizon,
                                    frames_cap, frames_floor)
    n = lam.shape[1]
    return n_frames, max(int(MAX_BATCH_ELEMS // max(n * n_frames, 1)), 1)


def measure_window(lam, mu, p, pol, *, epoch_duration: float = 300.0,
                   frames_cap: int = 200_000, frames_floor: int = 200,
                   seed: int = 0, t0: int = 0, delay_model: str = "mm1",
                   collect_samples: int = 0
                   ) -> tuple[np.ndarray, list[StreamTelemetry]]:
    """Measure epochs ``[t0, t0+E)`` of an N-stream data plane in ONE
    batched device dispatch (``queues.gi_g1_window``; chunked along the
    epoch axis only past ``MAX_BATCH_ELEMS``).

    ``lam``/``mu``/``p``/``pol`` are ``[E, N]``: per stream, ``delay_model``
    transmissions with mean ``1/lam[e, i]``, service with mean
    ``1/mu[e, i]``, Bernoulli(``p[e, i]``) recognition, FCFS/LCFSP per
    ``pol[e, i]`` — the frame-uploading model of §III-A, generalized to
    the GI/G/1 delay families of ``queues.DELAY_MODELS``. Deterministic in
    ``(seed, t, i)`` via collision-free folded keys; age integration is
    truncated at ``epoch_duration`` so measured AoPI reflects the epoch
    even for low-rate streams padded up to the frame floor.

    Returns ``(measured_aopi[E, N], [StreamTelemetry] * E)``.
    """
    lam = np.atleast_2d(np.asarray(lam, np.float64))
    mu = np.atleast_2d(np.asarray(mu, np.float64))
    p = np.atleast_2d(np.asarray(p, np.float64))
    pol = np.atleast_2d(np.asarray(pol))
    n_epochs, n = lam.shape
    horizon = float(epoch_duration)
    n_frames, e_chunk = _window_dispatch(lam, horizon, frames_cap,
                                         frames_floor)
    measured = np.zeros((n_epochs, n))
    tels: list[StreamTelemetry] = []
    for e0 in range(0, n_epochs, e_chunk):
        e1 = min(e0 + e_chunk, n_epochs)
        out = queues.gi_g1_window(
            lam[e0:e1], mu[e0:e1], p[e0:e1], pol[e0:e1],
            seed=seed, t0=t0 + e0, n_frames=n_frames, horizon=horizon,
            delay_model=delay_model, collect_samples=collect_samples)
        measured[e0:e1] = out["aopi"]
        samples = out.get("delay_samples")
        for j in range(e1 - e0):
            h_eff = np.maximum(out["horizon"][j], 1e-9)
            tels.append(StreamTelemetry(
                acc_hat=out["n_accurate"][j] /
                np.maximum(out["n_completed"][j], 1),
                lam_hat=out["n_frames"][j] / h_eff,
                mu_hat=out["n_completed"][j] / h_eff,
                n_frames=out["n_frames"][j].astype(np.float64),
                n_completed=out["n_completed"][j].astype(np.float64),
                aopi_hat=out["aopi"][j].copy(),
                delay_samples=(None if samples is None
                               else samples[j])))
    return measured, tels


def measure_mm1(lam, mu, p, pol, *, epoch_duration: float = 300.0,
                frames_cap: int = 200_000, frames_floor: int = 200,
                seed: int = 0, t: int = 0, delay_model: str = "mm1"
                ) -> tuple[np.ndarray, StreamTelemetry]:
    """One epoch of the event-driven data plane for N streams — a single
    batched device dispatch (see :func:`measure_window`; the historical
    name survives because "mm1" is still the default delay family).

    Returns ``(measured_aopi[N], StreamTelemetry)``.
    """
    lam = np.asarray(lam, np.float64)
    measured, tels = measure_window(
        lam[None], np.asarray(mu, np.float64)[None],
        np.asarray(p, np.float64)[None], np.asarray(pol)[None],
        epoch_duration=epoch_duration, frames_cap=frames_cap,
        frames_floor=frames_floor, seed=seed, t0=t,
        delay_model=delay_model)
    return measured[0], tels[0]


def measure_mm1_loop(lam, mu, p, pol, *, epoch_duration: float = 300.0,
                     frames_cap: int = 200_000, frames_floor: int = 200,
                     seed: int = 0, t: int = 0, delay_model: str = "mm1"
                     ) -> tuple[np.ndarray, StreamTelemetry]:
    """The PR-4 per-stream numpy loop — kept as the parity reference for
    the batched engine (``tests/test_dataplane.py``) and the baseline of
    ``benchmarks/bench_dataplane.py``. Seeded with collision-free
    ``SeedSequence(entropy=seed, spawn_key=(t, i))`` streams (the old
    ``seed + 7919*t + i`` arithmetic collided across (t, i) pairs). Note
    the loop integrates age over the *simulated* horizon (the historical
    semantics), not the truncated epoch."""
    lam = np.asarray(lam, np.float64)
    mu = np.asarray(mu, np.float64)
    p = np.asarray(p, np.float64)
    pol = np.asarray(pol)
    n = len(lam)
    measured = np.zeros(n)
    tel = StreamTelemetry.empty(n)
    for i in range(n):
        lam_i = max(float(lam[i]), 1e-6)
        mu_i = max(float(mu[i]), 1e-6)
        n_frames = int(min(lam_i * epoch_duration, frames_cap))
        n_frames = max(n_frames, frames_floor)
        samplers = queues.oracle_samplers(delay_model, lam_i, mu_i)
        sim = queues.simulate(
            lam_i, mu_i, float(np.clip(p[i], 1e-3, 1.0)),
            int(pol[i]), n_frames=n_frames,
            seed=queues.stream_seed_sequence(seed, t, i), **samplers)
        measured[i] = sim.mean_aopi
        horizon = max(sim.horizon, 1e-9)
        tel.acc_hat[i] = sim.n_accurate / max(sim.n_completed, 1)
        tel.lam_hat[i] = sim.n_frames / horizon
        tel.mu_hat[i] = sim.n_completed / horizon
        tel.n_frames[i] = sim.n_frames
        tel.n_completed[i] = sim.n_completed
        tel.aopi_hat[i] = sim.mean_aopi
    return measured, tel


@dataclasses.dataclass
class EpochReport:
    t: int
    predicted_aopi: float       # closed-form, from the planner
    measured_aopi: float        # data-plane measurement
    accuracy: float
    q: float
    per_stream_measured: np.ndarray
    per_stream_predicted: np.ndarray
    telemetry: Optional[StreamTelemetry] = None
    # Engine mode only: the rung-2 GI/G/1 measurement of the same epoch
    # (measured_aopi is then the rung-3 engine measurement), so one run
    # yields all three truth-ladder rungs.
    model_aopi: Optional[float] = None
    per_stream_model: Optional[np.ndarray] = None
    #: Family the fitted selector chose for this epoch (delay_model="auto").
    fitted_model: Optional[str] = None
    #: Its fitted shape parameters (sigma/k), when the winner has any.
    fitted_params: Optional[dict] = None


class AnalyticsService:
    def __init__(self, controller, *, mode: str = "mm1",
                 epoch_duration: float = 300.0, engine=None,
                 frames_cap: int = 200_000, seed: int = 0,
                 planner: str = "scan", plan_window: int = 8,
                 tables: HorizonTables | None = None,
                 telemetry_gain: float = 0.0,
                 delay_model: str = "mm1",
                 true_delay_model: str | None = None,
                 engine_frames_cap: int | None = None,
                 engine_backend: str = "auto",
                 replan_threshold: float | None = None,
                 faults: "fault_plane.FaultPlan | None" = None,
                 plan_retries: int = 2,
                 retry_backoff: float = 0.0,
                 plan_deadline: float | None = None):
        """``controller`` is an ``LBCDController`` or one of the
        ``baselines`` controllers (anything with ``step(t)`` and either
        ``plan(tables)`` or ``_rollout(tables)``).

        ``tables`` replays a prebuilt horizon (e.g. a ``repro.scenarios``
        build) instead of the controller's live ``EdgeSystem``;
        ``telemetry_gain`` > 0 lets measured accuracy / arrival rates /
        AoPI correct the next planning window's beliefs (EWMA weight).
        ``delay_model`` selects the data plane's delay family
        (``queues.DELAY_MODELS``; "mm1" keeps the paper's exponential
        model, "uniform"/"gamma" the lighter-tailed §III-B testbed
        regime, "lognormal"/"weibull" the heavy-tail regime) — or
        ``"auto"``, which fits the family from observed transmission
        delays each epoch (``queues.fit_delay_model``) and uses the
        fitted label for observability and, in engine mode, for the
        GI/G/1 model rung. ``true_delay_model`` pins the *generating*
        family of the plane (the world); it defaults to ``delay_model``
        when that is concrete, to "mm1" under "auto". ``replan_threshold``
        (relative
        measured-vs-predicted divergence, e.g. 0.1) arms
        divergence-triggered replanning: when an epoch's divergence
        crosses it mid-window, the remaining plan window is cut and
        ``plan_horizon`` re-runs from the next epoch with fresh telemetry
        instead of waiting for the fixed ``plan_window`` boundary.

        ``engine_backend`` selects the engine-rung measurement plane in
        ``mode="engine"`` (``tick_plane.ENGINE_BACKENDS``): "des" replays
        the real continuous-batching Engine event by event, "scan" runs
        the bitwise-compatible batched tick-scan (no Engine instance
        needed, full-suite frame budgets), "auto" — the default — keeps
        the DES at smoke scale and switches to the scan above
        ``tick_plane.AUTO_DES_MAX_FRAMES`` frame events per epoch.
        ``engine_frames_cap`` defaults per backend: the DES keeps the
        smoke-sized ``engine_plane.ENGINE_FRAMES_CAP``; the scan gets
        ``frames_cap`` (GI/G/1-rung parity) — either way the effective
        per-epoch budget passes through ``queues.frames_budget``.

        ``faults`` (a :class:`repro.faults.FaultPlan`) arms the service's
        *behavioral* fault injections — telemetry drops/delays/corruption
        gate the EWMA filter, and ``solver_*`` kinds drive the graceful-
        degradation ladder on the scan planner: each planning attempt gets
        ``plan_retries`` retries (exponential ``retry_backoff`` sleep, a
        ``plan_deadline``-second watchdog); exhausted retries fall back to
        the last good plan re-projected onto the surviving fleet, then to
        a MIN-baseline plan. Structural faults (churn, capacity) must be
        baked into ``tables`` first via ``faults.apply_plan``.
        ``faults=None`` is the bitwise no-op path.
        """
        if planner not in ("scan", "step"):
            raise ValueError(f"unknown planner {planner!r}; "
                             "known: ('scan', 'step')")
        if mode not in ("mm1", "engine"):
            raise ValueError(f"unknown mode {mode!r}; "
                             "known: ('mm1', 'engine')")
        queues.validate_delay_model(delay_model, allow_auto=True)
        if true_delay_model is None:
            true_delay_model = (delay_model
                                if delay_model != queues.AUTO_DELAY_MODEL
                                else "mm1")
        queues.validate_delay_model(true_delay_model)
        # Scan planning needs a whole-horizon engine on the controller AND
        # a horizon source (replay tables, or a system that can pregenerate
        # one); duck-typed systems exposing only capacities(t)/tables(t)
        # keep the legacy per-slot path.
        if planner == "scan" and not (
                self._supports_scan(controller) and
                (tables is not None or
                 hasattr(controller.system, "horizon"))):
            planner = "step"
        self.controller = controller
        self.mode = mode
        self.engine = engine
        self.epoch_duration = epoch_duration
        self.frames_cap = frames_cap
        self.seed = seed
        self.planner = planner
        self.plan_window = max(int(plan_window), 1)
        self.tables = tables
        self.telemetry_gain = float(telemetry_gain)
        self.delay_model = delay_model
        self.true_delay_model = true_delay_model
        self._auto = delay_model == queues.AUTO_DELAY_MODEL
        self._fitted_model: str | None = None
        self._fitted_params: dict = {}       # winner's shape, e.g. sigma/k
        self.fitted_models: list[tuple[int, str]] = []  # (t, fitted family)
        self._delay_buf: list[np.ndarray] = []  # unit-mean pooled samples
        self.replan_threshold = (None if replan_threshold is None
                                 else float(replan_threshold))
        self.reports: list = []
        # Legacy list attributes (kept for API compatibility); the same
        # series also flow through the obs registry/trace stream — the
        # counters and the lists are written by the same statements, so
        # they reconcile exactly (tests/test_obs.py pins this).
        self.divergences: list[float] = []   # per-epoch measured/pred - 1
        self.early_replans: list[int] = []   # epochs where a window was cut
        self.fallbacks: list[tuple[int, str]] = []   # (t, ladder rung)
        self.degraded_epochs: list[int] = []  # epochs run on a fallback plan
        self.telemetry_gaps: list[int] = []   # epochs whose telemetry held
        self.plan_failures: list[tuple[int, int, str]] = []  # (t, attempt, err)
        self.faults = faults
        self.plan_retries = max(int(plan_retries), 0)
        self.retry_backoff = float(retry_backoff)
        self.plan_deadline = (None if plan_deadline is None
                              else float(plan_deadline))
        self._policy = _policy_label(controller)
        self._replan_pending = False         # next plan is an early replan
        self._plan_degraded: str | None = None  # ladder rung of current plan
        self._last_plan = None               # last validated plan (stale src)
        self._gap_streak = 0                 # consecutive telemetry gaps
        self._delayed_tel: dict = {}         # arrival epoch -> [(dec, tel)]
        n = self._n_streams()
        self._acc_scale = np.ones(n)
        self._eff_scale = np.ones(n)
        self._aopi_scale = np.ones(n)        # measured/closed-form residual
        self._base_cache: HorizonTables | None = tables
        self._plan = None
        self._plan_t0 = 0
        self._plan_meas = None               # window-batched measurements
        from . import engine_plane, tick_plane
        # Resolve "auto" against the DES-sized budget (the question auto
        # answers is "is the event-by-event DES still affordable here?"),
        # then default the cap per backend: DES keeps the smoke-sized
        # ENGINE_FRAMES_CAP, the scan runs at GI/G/1-rung parity.
        des_cap = int(engine_plane.ENGINE_FRAMES_CAP
                      if engine_frames_cap is None else engine_frames_cap)
        self.engine_backend = tick_plane.resolve_engine_backend(
            engine_backend, n_streams=n, frames_cap=des_cap)
        if engine_frames_cap is None and self.engine_backend == "scan":
            self.engine_frames_cap = int(frames_cap)
        else:
            self.engine_frames_cap = des_cap
        if (self.mode == "engine" and self.engine is None
                and self.engine_backend == "des"):
            # Replay-grade default: the deterministic stub-model engine
            # with one lane per stream (see engine_plane). The scan
            # backend needs no Engine instance at all.
            from .engine import make_replay_engine
            self.engine = make_replay_engine(n, seed=seed)

    # ------------------------------------------------------------------
    # Planner: lookahead windows as one jitted scan
    # ------------------------------------------------------------------
    @staticmethod
    def _supports_scan(controller) -> bool:
        if isinstance(controller, LBCDController):
            # The scan engine is specialized to first-fit placement.
            return controller.assign_fn is binpack.first_fit
        # A _rollout *override* — the abstract BaselineController._rollout
        # raises NotImplementedError, so step()-only controllers must fall
        # back to the legacy planner.
        rollout = getattr(type(controller), "_rollout", None)
        return (rollout is not None and
                rollout is not baselines.BaselineController._rollout)

    def _n_streams(self) -> int:
        if self.tables is not None:
            return self.tables.n_cameras
        return self.controller.system.n_cameras

    def _base_window(self, t0: int, t1: int) -> HorizonTables:
        """Slots [t0, t1) of the *uncorrected* source horizon (the truth
        the data plane executes against)."""
        if self._base_cache is None or self._base_cache.n_slots < t1:
            # EdgeSystem.horizon is deterministic and prefix-stable in
            # n_slots, so growing the cache never changes earlier slots;
            # geometric growth keeps total generation work O(T) over a
            # long-running service. Bounded systems (TableSystem) reject
            # the over-request — retry with exactly what is needed.
            cur = 0 if self._base_cache is None else self._base_cache.n_slots
            try:
                self._base_cache = self.controller.system.horizon(
                    max(t1, 2 * cur))
            except ValueError:
                self._base_cache = self.controller.system.horizon(t1)
        return self._base_cache.window(t0, t1)

    def _window_tables(self, t0: int, t1: int) -> HorizonTables:
        """The planner's view: source horizon with the telemetry
        corrections (accuracy / link-efficiency scales) applied."""
        base = self._base_window(t0, t1)
        if self.telemetry_gain <= 0.0:
            return base
        acc = jnp.clip(
            base.acc * self._acc_scale[None, :, None, None], 1e-3, 1.0)
        scale = (self._eff_scale if base.eff.ndim == 1
                 else self._eff_scale[None, :])
        return dataclasses.replace(base, acc=acc, eff=base.eff * scale)

    def plan_horizon(self, k: int, t0: int = 0) -> lbcd.RolloutResult:
        """Plan epochs ``[t0, t0 + k)`` as ONE jitted ``lax.scan`` over the
        (telemetry-corrected) horizon window — no per-epoch Python loop.

        Pure lookahead: neither the controller's virtual queue nor the data
        plane advances; ``run_epoch`` commits epochs as they execute.
        """
        with obs.span("planner.tables", k=k):
            tables = self._window_tables(t0, t0 + k)
        ctrl = self.controller
        # The span times the enqueue: the device work runs on after it.
        with obs.span("planner.dispatch", k=k, **self._solver_attrs(tables)):
            if isinstance(ctrl, LBCDController):
                return ctrl.plan(tables)
            return ctrl._rollout(tables)

    def _solver_attrs(self, tables: HorizonTables) -> dict:
        """The ``planner.dispatch`` span's fleet size, the slot solver it
        resolves to there (``bcd.resolve_spec``) and the first-fit it runs:
        ``lbcd.rollout`` takes the kernel where that solver is pallas; the
        baselines keep the ``fori_loop``."""
        if not obs.enabled():
            return {}
        ctrl = self.controller
        backend = getattr(ctrl, "solver_backend", None)
        if backend is None:           # MIN keeps its solver options apart
            backend = getattr(ctrl, "kw", {}).get("solver_backend", "jnp")
        n = int(tables.acc.shape[1])
        spec = bcd.resolve_spec(backend, n,
                                method=getattr(ctrl, "method", "waterfill"),
                                masked=tables.active is not None)
        first_fit = (spec.backend if isinstance(ctrl, LBCDController)
                     else "jnp")
        return {"backend": str(spec), "first_fit": first_fit,
                "n_cameras": n, "n_servers": int(tables.budgets_b.shape[1])}

    def _slot_record(self, t: int) -> lbcd.SlotRecord:
        if self.planner != "scan":
            with obs.span("service.plan_window", policy=self._policy,
                          reason="boundary", t0=t, k=1):
                return self.controller.step(t)
        if self._plan is None or not (
                self._plan_t0 <= t < self._plan_t0 + self._plan.q.shape[0]):
            k = self.plan_window
            if self.tables is not None:
                k = min(k, self.tables.n_slots - t)
            if k < 1:
                raise ValueError(
                    f"epoch {t} is past the replayed horizon of "
                    f"{self.tables.n_slots} slots")
            # The span covers dispatch AND materialization (np.asarray
            # blocks on the device work), so its duration is the honest
            # end-to-end planning latency; ``reason`` distinguishes
            # divergence-triggered early replans from window boundaries.
            reason = "early" if self._replan_pending else "boundary"
            self._replan_pending = False
            with obs.span("service.plan_window", policy=self._policy,
                          reason=reason, t0=t, k=k):
                self._plan = self._plan_with_ladder(t, k)
            self._plan_t0 = t
            self._plan_meas = None           # re-measure the new window
        j = t - self._plan_t0
        res = self._plan
        q = float(res.q[j])
        if isinstance(self.controller, LBCDController):
            self.controller.queue.q = q      # commit Eq. 44 for this epoch
        return lbcd.SlotRecord(
            t=t, aopi=res.aopi[j], acc=res.acc[j], q=q,
            assign=res.assign[j],
            decision=jax.tree.map(lambda x: x[j], res.decision))

    # ------------------------------------------------------------------
    # Graceful-degradation ladder (scan planner)
    # ------------------------------------------------------------------
    def _plan_attempt(self, t: int, k: int, attempt: int):
        """One planning attempt: consult the fault plan's solver
        injections, run the scan planner under the watchdog deadline, and
        validate the result (NaN anywhere in the plan is a failure — the
        ``solver_nan`` injection and genuine numerical poisoning take the
        same path)."""
        kind = (None if self.faults is None
                else self.faults.solver_fault(t, attempt))
        if kind == "solver_nonconverge":
            raise fault_plane.InjectedSolverFault("solver_nonconverge")
        start = time.perf_counter()
        plan = jax.block_until_ready(self.plan_horizon(k, t))
        leaves, tree = jax.tree.flatten(plan)
        with obs.span("planner.fetch", leaves=len(leaves),
                      bytes=sum(int(x.nbytes) for x in leaves)):
            plan = tree.unflatten([np.asarray(x) for x in leaves])
        elapsed = time.perf_counter() - start
        if kind == "solver_nan":
            plan = dataclasses.replace(
                plan, aopi=np.full_like(np.asarray(plan.aopi, float),
                                        np.nan))
        if kind == "solver_timeout":
            raise fault_plane.InjectedSolverFault("solver_timeout")
        if self.plan_deadline is not None and elapsed > self.plan_deadline:
            raise TimeoutError(
                f"plan window at t={t} took {elapsed:.3f}s "
                f"(deadline {self.plan_deadline:.3f}s)")
        for name in ("aopi", "q"):
            if np.isnan(np.asarray(getattr(plan, name), float)).any():
                raise FloatingPointError(f"plan.{name} contains NaN")
        for name in ("b", "c"):
            if np.isnan(np.asarray(getattr(plan.decision, name),
                                   float)).any():
                raise FloatingPointError(
                    f"plan.decision.{name} contains NaN")
        return plan

    def _plan_with_ladder(self, t: int, k: int):
        """Plan with retries, then degrade gracefully.

        Rungs: (1) up to ``plan_retries`` retries with exponential
        ``retry_backoff``; (2) the last good plan's final slot tiled over
        the window and re-projected onto the surviving fleet; (3) a fresh
        MIN-baseline plan on the current (telemetry-corrected) window.
        Each failed attempt and each fallback appends to the legacy list
        *and* emits the matching ``repro.obs`` event in the same block, so
        counters and lists reconcile exactly.

        Only the failures the ladder models engage it: an injected solver
        fault, a NaN plan and the watchdog deadline. Any other exception
        (a device program that fails to lower, compile or run, a bug)
        propagates, so a broken planner is never served as a fallback.
        """
        for attempt in range(self.plan_retries + 1):
            try:
                plan = self._plan_attempt(t, k, attempt)
                self._plan_degraded = None
                self._last_plan = plan
                return plan
            except _LADDER_FAILURES as e:
                err = f"{type(e).__name__}: {e}"
                self.plan_failures.append((t, attempt, err))
                obs.event("service.plan_retry", policy=self._policy,
                          t=t, attempt=attempt, error=err)
                if self.retry_backoff > 0.0 and attempt < self.plan_retries:
                    time.sleep(self.retry_backoff * (2.0 ** attempt))
        plan = self._stale_plan(t, k)
        reason = "stale_plan"
        if plan is None:
            plan = jax.tree.map(
                np.asarray,
                baselines.rollout_min(self._window_tables(t, t + k),
                                      solver_backend="jnp"))
            reason = "min_fallback"
        self.fallbacks.append((t, reason))
        obs.event("service.fallback", policy=self._policy, t=t,
                  reason=reason)
        self._plan_degraded = reason
        return plan

    def _stale_plan(self, t: int, k: int):
        """Rung 2: tile the last good plan's final slot over ``[t, t+k)``
        and re-project it onto the surviving fleet (zero every per-camera
        quantity of cameras that have since churned out — their bandwidth
        and compute shares are simply forfeited until the next good
        plan). Returns ``None`` when no good plan exists yet."""
        if self._last_plan is None:
            return None
        res = jax.tree.map(
            lambda x: np.repeat(np.asarray(x)[-1:], k, axis=0),
            self._last_plan)
        act = self._active_window(t, t + k)
        if act is not None:
            d = res.decision
            d = dataclasses.replace(
                d, b=d.b * act, c=d.c * act, lam=d.lam * act,
                mu=d.mu * act, acc=d.acc * act, aopi=d.aopi * act)
            res = dataclasses.replace(
                res, aopi=res.aopi * act, acc=res.acc * act, decision=d)
        return res

    def _active_window(self, t0: int, t1: int):
        """``[t1-t0, N]`` numpy fleet mask for the replayed horizon, or
        ``None`` when no churn mask is attached (the no-op path)."""
        if self.tables is None or self.tables.active is None:
            return None
        return np.asarray(self.tables.active[t0:t1], np.float64)

    def _active_at(self, t: int):
        act = self._active_window(t, t + 1)
        return None if act is None else act[0]

    # ------------------------------------------------------------------
    # Data plane
    # ------------------------------------------------------------------
    #: Per-stream delay samples surfaced per epoch / pooled for the fit.
    SAMPLE_CAP = 64
    SAMPLE_POOL = 8192

    def _obs_model(self) -> str:
        """The ``delay_model`` obs label: under "auto" it is the *fitted*
        per-window family (or the bare sentinel until enough samples)."""
        if self._auto:
            return self._fitted_model or queues.AUTO_DELAY_MODEL
        return self.delay_model

    def _measure_model(self) -> str:
        """Family the GI/G/1 *model* rung measures under in engine mode:
        the fitted family when the selector is armed (the EWMA-corrected
        planner then measures under what telemetry says the world is)."""
        if self._auto:
            return self._fitted_model or "mm1"
        return self.delay_model

    def _update_fit(self, t: int, tel: StreamTelemetry | None):
        """Fold this epoch's raw delay samples into the pooled buffer
        (per-stream mean-normalized so streams with different rates share
        one shape) and re-fit the family."""
        if not self._auto or tel is None or tel.delay_samples is None:
            return
        for row in np.asarray(tel.delay_samples, np.float64):
            row = row[row > 0.0]
            if row.size >= 4:
                self._delay_buf.append(row / row.mean())
        while (sum(a.size for a in self._delay_buf) > self.SAMPLE_POOL
               and len(self._delay_buf) > 1):
            self._delay_buf.pop(0)
        pooled = (np.concatenate(self._delay_buf) if self._delay_buf
                  else np.empty(0))
        fit = queues.fit_delay_model(pooled)
        if fit.residuals:                 # enough samples to trust
            changed = (fit.model != self._fitted_model
                       or dict(fit.params) != self._fitted_params)
            self._fitted_model = fit.model
            self._fitted_params = dict(fit.params)
            if changed:
                # Feed the fitted (family, shape) into the planner's
                # residual calibration, not just the labels: seed the
                # AoPI residual scale halfway toward the family's
                # Kingman prior (1 + cv^2)/2. Exactly 1 for mm1 — a
                # no-op when the world matches the paper's model — and
                # the telemetry EWMA keeps refining from there.
                prior = queues.residual_prior(fit.model, fit.params)
                self._aopi_scale = np.clip(
                    0.5 * (self._aopi_scale + prior), 0.25, 4.0)
        self.fitted_models.append((t, self._fitted_model or "mm1"))
        obs.event("service.delay_fit", policy=self._policy, t=t,
                  model=self._fitted_model or "unfit",
                  n_samples=fit.n_samples,
                  **{k: float(v) for k, v in fit.params.items()})

    def _plane_rates(self, t: int, dec) -> tuple[np.ndarray, np.ndarray]:
        """True arrival rate and accuracy of the chosen configs — from the
        *uncorrected* tables (the planner may be acting on telemetry-scaled
        beliefs; the plane executes against the world)."""
        n = len(dec.lam)
        r_idx = np.asarray(dec.r_idx)
        m_idx = np.asarray(dec.m_idx)
        try:
            base = self._base_window(t, t + 1)
        except AttributeError:
            # No horizon source (bare controller on a custom system) —
            # fall back to the planner's own beliefs. A ValueError (epoch
            # past a bounded horizon) propagates: that is a real misuse,
            # not a missing capability.
            return np.asarray(dec.lam), np.asarray(dec.acc)
        eff = np.asarray(base.eff if base.eff.ndim == 1 else base.eff[0])
        size = np.asarray(base.size)
        lam_true = np.asarray(dec.b) * eff / size[r_idx]
        p_true = np.asarray(base.acc[0])[np.arange(n), m_idx, r_idx]
        return lam_true, p_true

    def _plane_rates_window(self, t0: int, n_epochs: int,
                            dec) -> tuple[np.ndarray, np.ndarray]:
        """Vectorized ``_plane_rates`` for a whole plan window: ``dec``
        holds stacked ``[E, N]`` decision arrays."""
        n = dec.lam.shape[-1]
        r_idx = np.asarray(dec.r_idx)
        m_idx = np.asarray(dec.m_idx)
        try:
            base = self._base_window(t0, t0 + n_epochs)
        except AttributeError:
            return np.asarray(dec.lam), np.asarray(dec.acc)
        eff = np.asarray(base.eff)
        if eff.ndim == 1:
            eff = np.broadcast_to(eff, (n_epochs, n))
        size = np.asarray(base.size)
        lam_true = np.asarray(dec.b) * eff / size[r_idx]
        acc = np.asarray(base.acc)                       # [E, N, M, R]
        p_true = acc[np.arange(n_epochs)[:, None],
                     np.arange(n)[None, :], m_idx, r_idx]
        return lam_true, p_true

    def _measure_plan_window(self):
        """Measure every epoch of the current plan window in ONE batched
        device dispatch — the plane's inputs (planned configs + unscaled
        truth tables) are fully known the moment the window is planned."""
        res, t0 = self._plan, self._plan_t0
        n_epochs = int(res.q.shape[0])
        dec = res.decision
        with obs.span("data_plane.inputs", epochs=n_epochs,
                      streams=int(dec.lam.shape[-1])) as inputs:
            lam_true, p_true = self._plane_rates_window(t0, n_epochs, dec)
            if obs.enabled():
                _, e_chunk = _window_dispatch(lam_true, self.epoch_duration,
                                              self.frames_cap)
                inputs.set(dispatches=-(-n_epochs // e_chunk))
        with obs.span("service.measure_window", policy=self._policy,
                      delay_model=self._obs_model(), t0=t0,
                      epochs=n_epochs, streams=int(lam_true.shape[-1])):
            return measure_window(
                lam_true, np.asarray(dec.mu), p_true, np.asarray(dec.pol),
                epoch_duration=self.epoch_duration,
                frames_cap=self.frames_cap, seed=self.seed, t0=t0,
                delay_model=self.true_delay_model,
                collect_samples=self.SAMPLE_CAP if self._auto else 0)

    def _measure_epoch(self, t: int, dec):
        """Measured AoPI + telemetry for epoch ``t``. On the scan path the
        whole plan window is measured in one batched dispatch and cached;
        the step path measures the epoch as one ``[1, N]`` dispatch.
        Armed divergence replanning (``replan_threshold``) also measures
        per epoch: a tripped threshold discards the rest of the window,
        so eagerly simulating it would be wasted work in exactly the
        badly-modeled regime replanning exists for."""
        if (self.planner == "scan" and self._plan is not None
                and self.replan_threshold is None):
            if self._plan_meas is None:
                self._plan_meas = self._measure_plan_window()
            measured_w, tels = self._plan_meas
            j = t - self._plan_t0
            return measured_w[j], tels[j]
        with obs.span("data_plane.inputs", epochs=1,
                      streams=int(np.shape(dec.lam)[-1]), dispatches=1):
            lam_true, p_true = self._plane_rates(t, dec)
        with obs.span("service.measure_window", policy=self._policy,
                      delay_model=self._obs_model(), t0=t, epochs=1,
                      streams=int(np.asarray(lam_true).shape[-1])):
            measured, tels = measure_window(
                lam_true[None], np.asarray(dec.mu)[None], p_true[None],
                np.asarray(dec.pol)[None],
                epoch_duration=self.epoch_duration,
                frames_cap=self.frames_cap, seed=self.seed, t0=t,
                delay_model=self.true_delay_model,
                collect_samples=self.SAMPLE_CAP if self._auto else 0)
            return measured[0], tels[0]

    def _ingest_telemetry(self, t: int, dec, tel: StreamTelemetry):
        """Gate the epoch's measurement through the fault plan before the
        EWMA. Drops and corruption become telemetry *gaps* — the belief
        scales hold their last value and the effective replan threshold
        widens by 50% per consecutive gap — instead of feeding garbage;
        delayed samples are stashed and folded in on arrival."""
        for d_dec, d_tel in self._delayed_tel.pop(t, ()):
            self._apply_telemetry(t, d_dec, d_tel)
        spec = (None if self.faults is None
                else self.faults.telemetry_fault(t))
        if spec is not None:
            if spec.kind == "telemetry_drop":
                self._telemetry_gap(t, "drop")
                return
            if spec.kind == "telemetry_delay":
                d = max(int(spec.params.get("delay", 1)), 1)
                self._delayed_tel.setdefault(t + d, []).append((dec, tel))
                self._telemetry_gap(t, "delay")
                return
            if spec.kind == "telemetry_corrupt":
                tel = dataclasses.replace(
                    tel, acc_hat=np.full_like(
                        np.asarray(tel.acc_hat, np.float64), np.nan))
        self._apply_telemetry(t, dec, tel)

    def _apply_telemetry(self, t: int, dec, tel: StreamTelemetry):
        """Validated EWMA ingest: a non-finite measurement (corruption,
        injected or genuine) is rejected as a gap — garbage never reaches
        the belief scales."""
        finite = all(
            np.isfinite(np.asarray(x, np.float64)).all()
            for x in (tel.acc_hat, tel.lam_hat, tel.mu_hat, tel.aopi_hat))
        if not finite:
            self._telemetry_gap(t, "corrupt")
            return
        self._update_telemetry(dec, tel)
        self._gap_streak = 0

    def _telemetry_gap(self, t: int, why: str):
        self.telemetry_gaps.append(t)
        self._gap_streak += 1
        obs.event("service.telemetry_gap", policy=self._policy, t=t,
                  reason=why)

    def _update_telemetry(self, dec, tel: StreamTelemetry):
        """Fold measured rates back into the planner's belief scales
        (EWMA toward measured/believed, clipped to [0.5, 2]) and the
        AoPI residual scale (measured/closed-form, clipped to [0.25, 4])
        that calibrates predictions under non-exponential delays."""
        g = self.telemetry_gain
        if g <= 0.0:
            return
        seen = tel.n_completed > 0
        ratio_acc = np.where(
            seen, tel.acc_hat / np.maximum(np.asarray(dec.acc), 1e-3), 1.0)
        ratio_lam = np.where(
            tel.n_frames > 0,
            tel.lam_hat / np.maximum(np.asarray(dec.lam), 1e-9), 1.0)
        # Residual of the *calibrated* prediction, so the scale's fixed
        # point is measured == aopi_scale * closed_form.
        pred = self._aopi_scale * np.asarray(dec.aopi)
        ratio_aopi = np.where(
            (tel.aopi_hat > 0) & np.isfinite(pred) & (pred > 0),
            tel.aopi_hat / np.maximum(pred, 1e-9), 1.0)
        self._acc_scale = np.clip(
            (1 - g) * self._acc_scale + g * self._acc_scale * ratio_acc,
            0.5, 2.0)
        self._eff_scale = np.clip(
            (1 - g) * self._eff_scale + g * self._eff_scale * ratio_lam,
            0.5, 2.0)
        self._aopi_scale = np.clip(
            (1 - g) * self._aopi_scale + g * self._aopi_scale * ratio_aopi,
            0.25, 4.0)

    def run_epoch(self, t: int) -> EpochReport:
        with obs.span("service.run_epoch", policy=self._policy, t=t):
            return self._run_epoch(t)

    def _run_epoch(self, t: int) -> EpochReport:
        rec = self._slot_record(t)
        dec = rec.decision
        if self._plan_degraded is not None and self.planner == "scan":
            # This epoch executes a fallback plan — list append and obs
            # event in the same block so they reconcile exactly.
            self.degraded_epochs.append(t)
            obs.event("service.degraded_epoch", policy=self._policy,
                      t=t, reason=self._plan_degraded)
        # The reported prediction is the *calibrated* belief: closed form
        # times the telemetry AoPI residual (identity at gain 0). Taken
        # BEFORE this epoch's telemetry folds in — the scale only carries
        # information from epochs < t, so divergence is out-of-sample.
        predicted = self._aopi_scale * np.asarray(dec.aopi)
        tel = None
        model_meas = None
        if self.mode == "mm1":
            measured, tel = self._measure_epoch(t, dec)
            self._ingest_telemetry(t, dec, tel)
            self._update_fit(t, tel)
        else:
            measured, tel = self._run_engine_epoch(rec)
            self._ingest_telemetry(t, dec, tel)
            self._update_fit(t, tel)
            # Rung 2 of the same epoch, measured under the (possibly
            # fitted) model family — one engine run yields all three
            # truth-ladder columns.
            model_meas = self._measure_model_rung(t, dec)
        act = self._active_at(t)
        if act is None:
            pred_mean = float(np.mean(predicted))
            meas_mean = float(np.mean(measured))
            acc_mean = float(np.mean(dec.acc))
        else:
            # Fleet means over the *surviving* cameras only — churned-out
            # streams carry exact zeros and must not dilute the average.
            n_live = max(float(act.sum()), 1.0)
            pred_mean = float(np.sum(predicted * act) / n_live)
            meas_mean = float(np.sum(measured * act) / n_live)
            acc_mean = float(np.sum(np.asarray(dec.acc) * act) / n_live)
        if act is None:
            model_mean = (None if model_meas is None
                          else float(np.mean(model_meas)))
        else:
            model_mean = (None if model_meas is None else float(
                np.sum(model_meas * act) / max(float(act.sum()), 1.0)))
        rep = EpochReport(
            t=t, predicted_aopi=pred_mean,
            measured_aopi=meas_mean,
            accuracy=acc_mean, q=rec.q,
            per_stream_measured=measured,
            per_stream_predicted=predicted,
            telemetry=tel,
            model_aopi=model_mean,
            per_stream_model=model_meas,
            fitted_model=self._fitted_model if self._auto else None,
            fitted_params=(dict(self._fitted_params)
                           if self._auto and self._fitted_params else None))
        self.reports.append(rep)
        div = rep.measured_aopi / max(rep.predicted_aopi, 1e-12) - 1.0
        self.divergences.append(div)
        obs.gauge("service.divergence", policy=self._policy).set(div)
        obs.counter("service.epochs", policy=self._policy).inc()
        self._maybe_replan(t, div)
        return rep

    def _effective_replan_threshold(self) -> float | None:
        """Consecutive telemetry gaps widen the replan threshold (+50%
        per held epoch): with stale beliefs a large divergence is
        expected, and replanning on it would churn plans on no new
        information. Identity when no gap is open."""
        if self.replan_threshold is None:
            return None
        return self.replan_threshold * (1.0 + 0.5 * self._gap_streak)

    def _maybe_replan(self, t: int, div: float):
        """Divergence-triggered replanning: cut the rest of the plan
        window when the data plane drifted past ``replan_threshold`` from
        the (calibrated) prediction, so ``plan_horizon`` re-runs at
        ``t + 1`` with fresh telemetry instead of waiting for the fixed
        ``plan_window`` boundary."""
        threshold = self._effective_replan_threshold()
        if (threshold is None or self.mode != "mm1"
                or self.planner != "scan" or self._plan is None
                or abs(div) <= threshold):
            return
        remaining = self._plan_t0 + int(self._plan.q.shape[0]) - (t + 1)
        if remaining > 0:
            self._plan = None
            self._plan_meas = None
            self.early_replans.append(t + 1)
            self._replan_pending = True
            # One instant event (and counter bump) per list append — the
            # registry, the trace stream, and the legacy attribute stay
            # reconciled by construction.
            obs.event("service.early_replan", policy=self._policy,
                      t=t + 1, divergence=float(div))

    # ------------------------------------------------------------------
    def _run_engine_epoch(self, rec
                          ) -> tuple[np.ndarray, StreamTelemetry]:
        """Rung 3: the engine-rung measurement plane at the *unscaled*
        truth rates — the same model-vs-measurement split as the batched
        plane. ``engine_backend="des"`` replays the real
        continuous-batching Engine event by event
        (``engine_plane.measure_engine_epoch``: real admits, decode
        ticks, preemptions on the lanes); ``"scan"`` runs the
        bitwise-compatible batched tick-scan
        (``tick_plane.measure_engine_epoch_scan``) so the rung scales to
        full-suite frame budgets."""
        from . import engine_plane, tick_plane
        dec = rec.decision
        t = rec.t
        lam_true, p_true = self._plane_rates(t, dec)
        act = self._active_at(t)
        # Budget the epoch's frame volume against the backend cap: for
        # smoke-sized DES caps this resolves to the cap itself; for the
        # scan's full-suite cap it is the same arrival-coverage budget
        # the GI/G/1 rung runs on.
        max_lam = float(np.max(lam_true)) if np.size(lam_true) else 1.0
        if not np.isfinite(max_lam):
            max_lam = 1.0
        frames = queues.frames_budget(max_lam, self.epoch_duration,
                                      self.engine_frames_cap)
        kw = dict(epoch_duration=self.epoch_duration, seed=self.seed,
                  t=t, delay_model=self.true_delay_model, active=act,
                  frames_cap=frames,
                  collect_samples=self.SAMPLE_CAP if self._auto else 0)
        with obs.span("service.measure_engine", policy=self._policy,
                      delay_model=self._obs_model(), t0=t,
                      backend=self.engine_backend,
                      streams=int(np.asarray(lam_true).shape[-1])):
            if self.engine_backend == "scan":
                out = tick_plane.measure_engine_epoch_scan(
                    lam_true, np.asarray(dec.mu), p_true,
                    np.asarray(dec.pol), **kw)
            else:
                assert self.engine is not None
                out = engine_plane.measure_engine_epoch(
                    self.engine, lam_true, np.asarray(dec.mu), p_true,
                    np.asarray(dec.pol), **kw)
        h_eff = np.maximum(out["horizon"], 1e-9)
        tel = StreamTelemetry(
            acc_hat=out["n_accurate"] / np.maximum(out["n_completed"], 1),
            lam_hat=out["n_frames"] / h_eff,
            mu_hat=out["n_completed"] / h_eff,
            n_frames=out["n_frames"].astype(np.float64),
            n_completed=out["n_completed"].astype(np.float64),
            aopi_hat=out["aopi"].copy(),
            delay_samples=out.get("delay_samples"))
        return out["aopi"], tel

    def _measure_model_rung(self, t: int, dec) -> np.ndarray:
        """Rung 2 in engine mode: the batched GI/G/1 plane at the same
        truth rates, under the measurement family (fitted when
        ``delay_model="auto"``)."""
        lam_true, p_true = self._plane_rates(t, dec)
        with obs.span("service.measure_window", policy=self._policy,
                      delay_model=self._obs_model(), t0=t, epochs=1,
                      streams=int(np.asarray(lam_true).shape[-1])):
            measured, _ = measure_mm1(
                lam_true, np.asarray(dec.mu), p_true, np.asarray(dec.pol),
                epoch_duration=self.epoch_duration,
                frames_cap=self.frames_cap, seed=self.seed, t=t,
                delay_model=self._measure_model())
        return measured

    def run(self, n_epochs: int):
        return [self.run_epoch(t) for t in range(n_epochs)]

    @property
    def mean_measured(self) -> float:
        return float(np.mean([r.measured_aopi for r in self.reports]))

    @property
    def mean_predicted(self) -> float:
        return float(np.mean([r.predicted_aopi for r in self.reports]))
