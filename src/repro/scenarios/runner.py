"""Fleet sweep driver: all policies x all scenarios, sharded over devices.

``sweep`` executes the LBCD controller and the MIN/DOS/JCAB baselines over
a stacked scenario axis (a :class:`registry.Suite` or raw stacked
``HorizonTables``) in one device-resident call per policy. Three backends:

  * ``"shard_map"`` (default on >= 2 devices) — the scenario axis is
    padded to a multiple of the device count and partitioned with
    ``shard_map`` over a 1-D ``("scenario",)`` mesh; each device vmaps the
    scan rollout over its local shard. Embarrassingly parallel — no
    collectives. Caveat: XLA compiles a distinct ``num_partitions > 1``
    module whose floating-point rounding can differ from the single-device
    program by ~1 ulp, and the controller's discrete first-fit can amplify
    a knife-edge tie into a visibly different (equally valid) allocation —
    so cross-backend parity is statistical, not bitwise.
  * ``"fleet"`` — the same padded blocks dispatched asynchronously to each
    device through one shared jitted block function (JAX async dispatch
    keeps all devices busy). Every device runs a plain single-partition
    program, and the fleet means are reduced by a separate program (see
    ``_vmapped``), so results agree bitwise with the vmap fallback
    although the block batch size differs from the full-K vmap call. This
    is the backend the tight parity tests pin.
  * ``"vmap"`` (default on 1 device) — plain ``vmap`` over the scenario
    axis.

Each rollout is reduced on device to per-slot fleet means (AoPI, accuracy,
queue), so the host only ever sees ``[K, T]`` summaries no matter how many
cameras a scenario carries. ``report.robustness`` turns a
:class:`SweepResult` into the per-family worst-case/percentile table.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import obs
from ..core import baselines, lbcd
from ..core.profiles import HorizonTables
from .registry import Suite

POLICIES = ("lbcd", "min", "dos", "jcab")
BACKENDS = ("vmap", "shard_map", "fleet")


def divergence_series(measured: np.ndarray,
                      predicted: np.ndarray) -> np.ndarray:
    """Per-scenario relative divergence of horizon-mean measured vs
    predicted AoPI (``measured/predicted - 1`` over matched epochs) — the
    single definition shared by ``SweepResult`` and
    ``serving.replay.ReplayResult``. [K, T] x [K, T] -> [K]."""
    return (measured.mean(axis=1) /
            np.maximum(predicted.mean(axis=1), 1e-12) - 1.0)


@dataclasses.dataclass
class SweepResult:
    """Per-scenario per-policy slot series (fleet means) + metadata.

    ``aopi``/``acc``/``q`` map policy name -> ``[K, T]`` numpy arrays
    aligned with ``names``/``families``. When the sweep ran with
    ``dataplane=True``, ``measured_aopi`` holds the data-plane
    measurement per epoch (``[K, T_replay]``, possibly fewer slots than
    the closed-form series when the replay was truncated) and
    ``predicted_aopi`` the matching planner prediction — both for the
    *primary* (first) delay model. ``delay_models`` lists every replayed
    delay family; ``measured_by_model``/``predicted_by_model`` map
    model -> policy -> ``[K, T_replay]`` for all of them.
    """
    names: list[str]
    families: list[str]
    policies: list[str]
    v: float
    p_min: float
    backend: str
    aopi: dict[str, np.ndarray]
    acc: dict[str, np.ndarray]
    q: dict[str, np.ndarray]
    measured_aopi: dict[str, np.ndarray] | None = None
    predicted_aopi: dict[str, np.ndarray] | None = None
    delay_models: tuple[str, ...] | None = None
    measured_by_model: dict[str, dict[str, np.ndarray]] | None = None
    predicted_by_model: dict[str, dict[str, np.ndarray]] | None = None
    #: Rung-3 real-engine series for the primary delay model (replay with
    #: ``dataplane_params={"mode": "engine"}``): policy -> [K, T_replay].
    engine_aopi: dict[str, np.ndarray] | None = None
    engine_by_model: dict[str, dict[str, np.ndarray]] | None = None
    #: policy -> repr of the exception that killed its closed-form sweep
    #: (series NaN-filled); merged with the replay's per-cell errors
    #: under ("<scenario>", "<policy>") keys when dataplane=True.
    errors: dict = dataclasses.field(default_factory=dict)
    #: Fault-plane records from the primary-model replay (dataplane=True
    #: with a fault plan): policy -> [K] lists, as on ReplayResult.
    fallbacks: dict | None = None
    degraded: dict | None = None

    def mean_aopi(self, policy: str) -> np.ndarray:
        """Per-scenario mean AoPI over the horizon. [K]"""
        return self.aopi[policy].mean(axis=1)

    def pct_aopi(self, policy: str, pct: float = 95.0) -> np.ndarray:
        """Per-scenario tail (percentile over slots) AoPI. [K]"""
        return np.percentile(self.aopi[policy], pct, axis=1)

    def worst_aopi(self, policy: str) -> np.ndarray:
        """Per-scenario worst slot AoPI. [K]"""
        return self.aopi[policy].max(axis=1)

    def mean_acc(self, policy: str) -> np.ndarray:
        return self.acc[policy].mean(axis=1)

    def divergence(self, policy: str,
                   delay_model: str | None = None) -> np.ndarray:
        """Per-scenario measured/predicted - 1 over the replayed epochs
        (requires ``dataplane=True``). ``delay_model=None`` uses the
        primary model; pass a name from ``delay_models`` for another. [K]
        """
        if self.measured_aopi is None:
            raise ValueError("sweep ran without dataplane=True; no "
                             "measured series to diverge against")
        if delay_model is None:
            return divergence_series(self.measured_aopi[policy],
                                     self.predicted_aopi[policy])
        if (self.measured_by_model is None
                or delay_model not in self.measured_by_model):
            raise ValueError(
                f"delay model {delay_model!r} was not replayed; "
                f"available: {self.delay_models}")
        return divergence_series(self.measured_by_model[delay_model][policy],
                                 self.predicted_by_model[delay_model][policy])


def _policy_rollout(name: str, n_bcd_iters: int, solver_backend: str):
    """One scenario's rollout -> per-camera ``[T, N]`` AoPI and accuracy
    and the ``[T]`` queue, with every policy knob a traced scalar so one
    compiled program serves all knob values."""
    def fn(tables: HorizonTables, v, p_min, dos_weight, jcab_cap):
        if name == "lbcd":
            res = lbcd.rollout(tables, v, p_min, n_bcd_iters=n_bcd_iters,
                               solver_backend=solver_backend)
        elif name == "min":
            res = baselines.rollout_min(tables, v,
                                        n_bcd_iters=n_bcd_iters,
                                        solver_backend=solver_backend)
        elif name == "dos":
            res = baselines.rollout_dos(tables, dos_weight,
                                        solver_backend=solver_backend)
        elif name == "jcab":
            res = baselines.rollout_jcab(tables, jcab_cap,
                                         solver_backend=solver_backend)
        else:
            raise ValueError(
                f"unknown policy {name!r}; known: {POLICIES}")
        return res.aopi, res.acc, res.q
    return fn


def _fleet_means(aopi, acc, q, active) -> dict:
    """Per-camera series -> per-slot fleet means ``{"aopi", "acc", "q"}``."""
    if active is not None:
        # Churn-masked fleet: dead cameras carry exact zeros, so the
        # fleet mean divides by the live count, not N.
        n_live = jnp.maximum(active.sum(axis=-1), 1.0)
        return {"aopi": aopi.sum(axis=-1) / n_live,
                "acc": acc.sum(axis=-1) / n_live, "q": q}
    return {"aopi": aopi.mean(axis=-1), "acc": acc.mean(axis=-1), "q": q}


def _reduced_policy(name: str, n_bcd_iters: int, solver_backend: str):
    """One scenario's rollout reduced to ``[T]`` fleet means."""
    rollout = _policy_rollout(name, n_bcd_iters, solver_backend)

    def fn(tables: HorizonTables, *knobs):
        return _fleet_means(*rollout(tables, *knobs), tables.active)
    return fn


@functools.lru_cache(maxsize=None)
def _vmapped(name: str, n_bcd_iters: int, solver_backend: str):
    """The shared block program: vmap over scenarios, scalars broadcast.
    Cached so repeat sweeps (and the fleet backend's per-device dispatch)
    reuse one compiled executable per (policy, shapes).

    The fleet means are a second program over the per-camera series in
    their default layout. Inside one program XLA lays intermediates out by
    batch size (on a TPU a block of 11 scenarios put the scenario axis on
    lanes, a block of 3 the cameras), which changes the order of the
    camera-axis sum; split, the means of a block agree bitwise with the
    same scenarios' means in any other block size."""
    rollout = jax.jit(jax.vmap(
        _policy_rollout(name, n_bcd_iters, solver_backend),
        in_axes=(0, None, None, None, None)))
    means = jax.jit(_fleet_means)

    def block(tables: HorizonTables, *knobs):
        return means(*rollout(tables, *knobs), tables.active)
    return block


@functools.lru_cache(maxsize=None)
def _sharded(name: str, n_bcd_iters: int, solver_backend: str,
             devices: tuple):
    mesh = Mesh(np.asarray(devices), ("scenario",))
    # check_vma=False: jax has no varying-axes rule for pallas_call; the
    # sweep has no collectives, so the check adds nothing here.
    return jax.jit(jax.shard_map(
        jax.vmap(_reduced_policy(name, n_bcd_iters, solver_backend),
                 in_axes=(0, None, None, None, None)),
        mesh=mesh, in_specs=(P("scenario"), P(), P(), P(), P()),
        out_specs=P("scenario"), check_vma=False))


def _pad_scenarios(tables: HorizonTables, pad: int) -> HorizonTables:
    """Repeat the last scenario ``pad`` times so K divides the mesh."""
    if pad == 0:
        return tables
    return jax.tree.map(
        lambda x: jnp.concatenate(
            [x, jnp.repeat(x[-1:], pad, axis=0)], axis=0), tables)


def _run_shard_map(name, n_bcd_iters, solver_backend, tables, knobs,
                   n_scenarios, devices) -> dict:
    pad = (-n_scenarios) % len(devices)
    fn = _sharded(name, n_bcd_iters, solver_backend, tuple(devices))
    out = fn(_pad_scenarios(tables, pad), *knobs)
    return {k: np.asarray(x)[:n_scenarios] for k, x in out.items()}


def _run_fleet(name, n_bcd_iters, solver_backend, tables, knobs,
               n_scenarios, devices) -> dict:
    """The vmap block program, one async dispatch per device."""
    n_dev = len(devices)
    pad = (-n_scenarios) % n_dev
    padded = _pad_scenarios(tables, pad)
    block_len = (n_scenarios + pad) // n_dev
    block_fn = _vmapped(name, n_bcd_iters, solver_backend)
    futures = []
    for i, dev in enumerate(devices):
        block = jax.tree.map(
            lambda x: jax.device_put(
                x[i * block_len:(i + 1) * block_len], dev), padded)
        futures.append(block_fn(block, *knobs))  # async — all devices busy
    keys = futures[0].keys()
    return {k: np.concatenate([np.asarray(f[k]) for f in futures],
                              axis=0)[:n_scenarios] for k in keys}


def _run_vmap(name, n_bcd_iters, solver_backend, tables, knobs) -> dict:
    out = _vmapped(name, n_bcd_iters, solver_backend)(tables, *knobs)
    return {k: np.asarray(x) for k, x in out.items()}


def sweep(suite_or_tables: Suite | HorizonTables, v: float = 10.0,
          p_min: float = 0.7, policies: Sequence[str] = POLICIES,
          devices: Sequence | None = None, backend: str | None = None,
          policy_params: Mapping | None = None,
          solver_backend: str = "jnp", dataplane: bool = False,
          dataplane_params: Mapping | None = None) -> SweepResult:
    """Run every policy over every stacked scenario; one sharded (or
    vmapped) device-resident call per policy.

    ``backend=None`` picks ``"shard_map"`` on >= 2 devices and ``"vmap"``
    on one; pass ``"fleet"`` for the bitwise-reproducible multi-device
    path (see module docstring). ``solver_backend`` selects the
    Algorithm-1 implementation inside LBCD/MIN and the config-scan engine
    inside DOS/JCAB ("jnp" | "pallas" | "auto" plus tiling/fusion knobs
    like ``"pallas:tile=4096"`` — see ``bcd.parse_backend``).

    ``dataplane=True`` additionally replays every (policy, scenario) pair
    through the batched GI/G/1 data plane
    (``repro.serving.replay_suite``) and attaches *measured* per-epoch
    AoPI (plus the matching planner predictions) to the result —
    ``report.robustness`` then emits the two-column predicted-vs-measured
    table with a divergence column per replayed delay model.
    ``dataplane_params`` forwards replay knobs (``n_epochs``,
    ``epoch_duration``, ``frames_cap``, ``seed``, ``telemetry_gain``,
    ``plan_window``, ``replan_threshold``, ``faults`` — a
    ``repro.faults.FaultPlan`` applied to every cell, with
    ``plan_retries``/``plan_deadline`` tuning the degradation ladder —
    and ``delay_model`` — a name
    from ``queues.DELAY_MODELS`` or a tuple of them; the first is the
    primary model backing ``measured_aopi``/``divergence()``, the rest
    land in ``measured_by_model`` — see ``serving.replay.replay_tables``).
    ``mode="engine"`` climbs to the truth ladder's third rung: every cell
    also replays through the engine rung, and the rung-3 series land in
    ``engine_aopi``/``engine_by_model`` (``engine_params={"backend":
    "des"|"scan"|"auto", "frames_cap": ...}`` picks the rung's plane —
    the event-by-event Engine replay or the batched tick-scan at
    full-suite budgets — and bounds work per epoch; ``true_delay_model``
    picks the plane's generating family when ``delay_model="auto"`` runs
    the fitted selector).
    Each extra delay model is a full extra replay, planner included
    (telemetry feedback couples planning to the plane, and at
    ``telemetry_gain > 0`` the per-model plans genuinely differ);
    compiled planner executables are reused across models, so the
    repeated cost is execution, not compilation.
    """
    if isinstance(suite_or_tables, Suite):
        tables = suite_or_tables.tables
        names = list(suite_or_tables.names)
        fams = list(suite_or_tables.families)
    else:
        tables = suite_or_tables
        if tables.acc.ndim != 5:
            raise ValueError(
                f"sweep() needs a *stacked* scenario axis (acc of rank 5, "
                f"[K, T, N, M, R]); got acc{tuple(tables.acc.shape)}. "
                f"Stack horizons with profiles.stack_horizons or pass a "
                f"scenarios.suite(...)")
        k = int(tables.acc.shape[0])
        names = [f"scenario_{i}" for i in range(k)]
        fams = ["unknown"] * k
    n_scenarios = int(tables.acc.shape[0])
    devices = list(devices) if devices is not None else jax.devices()
    # Never spread K scenarios over more than K devices — a mesh larger
    # than the batch axis just pads (and a num_partitions >> K module,
    # e.g. under --xla_force_host_platform_device_count=512, takes
    # pathologically long to compile for zero parallelism gain).
    devices = devices[:max(n_scenarios, 1)]
    if backend is None:
        backend = "shard_map" if len(devices) > 1 else "vmap"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; known: {BACKENDS}")
    params = dict(policy_params or {})
    n_bcd_iters = int(params.get("n_bcd_iters", 4))
    knobs = (jnp.float32(v), jnp.float32(p_min),
             jnp.float32(params.get("dos_weight", 1.0)),
             jnp.float32(params.get("jcab_latency_cap", 0.5)))

    series = {}
    errors: dict = {}
    n_slots = int(tables.acc.shape[1])
    for name in policies:
        if name not in POLICIES:
            raise ValueError(f"unknown policy {name!r}; known: {POLICIES}")
        sb = solver_backend
        # One span per policy: it wraps the full sharded/vmapped dispatch
        # INCLUDING host materialization (the _run_* helpers np.asarray
        # their outputs), so the duration is honest end-to-end sweep time.
        try:
            with obs.span("sweep.policy", policy=name, backend=backend,
                          solver_backend=str(solver_backend),
                          n_scenarios=n_scenarios, n_devices=len(devices)):
                if backend == "shard_map" and len(devices) > 1:
                    series[name] = _run_shard_map(name, n_bcd_iters, sb,
                                                  tables, knobs,
                                                  n_scenarios, devices)
                elif backend == "fleet" and len(devices) > 1:
                    series[name] = _run_fleet(name, n_bcd_iters, sb, tables,
                                              knobs, n_scenarios, devices)
                else:
                    series[name] = _run_vmap(name, n_bcd_iters, sb, tables,
                                             knobs)
        except Exception as e:  # noqa: BLE001 — isolate the policy cell
            # One failing policy must not abort the whole sweep: record
            # the failure, NaN-fill its series, and keep sweeping.
            errors[name] = f"{type(e).__name__}: {e}"
            obs.event("sweep.policy_failed", policy=name, backend=backend)
            nan = np.full((n_scenarios, n_slots), np.nan)
            series[name] = {"aopi": nan, "acc": nan.copy(),
                            "q": np.full((n_scenarios, n_slots), np.nan)}
            continue
        if obs.enabled():
            # Per-(policy, family) AoPI histograms: the [T] fleet-mean
            # slot series of every scenario, so exporters can quote
            # p50/p95/p99 closed-form AoPI next to the timing series.
            for ki, fam in enumerate(fams):
                obs.histogram("sweep.aopi", policy=name, family=fam
                              ).observe_many(series[name]["aopi"][ki])

    measured = predicted = None
    delay_models = None
    measured_by_model = predicted_by_model = None
    engine_aopi = engine_by_model = None
    fallbacks = degraded = None
    if dataplane:
        # Lazy import: repro.serving pulls the model/engine stack, and
        # importing it here (not at module load) also keeps the
        # scenarios <-> serving dependency one-directional per call.
        from ..serving import replay as _replay
        dp = dict(dataplane_params or {})
        known = {"n_epochs", "epoch_duration", "frames_cap", "seed",
                 "plan_window", "telemetry_gain", "delay_model",
                 "true_delay_model", "mode", "engine_params",
                 "replan_threshold", "faults", "plan_retries",
                 "plan_deadline"}
        unknown = sorted(set(dp) - known)
        if unknown:
            raise ValueError(f"unknown dataplane_params {unknown}; "
                             f"known: {sorted(known)}")
        models = dp.get("delay_model", "mm1")
        if isinstance(models, str):
            models = (models,)
        delay_models = tuple(models)
        mode = str(dp.get("mode", "mm1"))
        measured_by_model, predicted_by_model = {}, {}
        engine_by_model = {}
        for dm in delay_models:
            rres = _replay.replay_suite(
                suite_or_tables, policies=list(policies), v=v, p_min=p_min,
                policy_params=policy_params, solver_backend=solver_backend,
                n_epochs=dp.get("n_epochs"),
                epoch_duration=float(dp.get("epoch_duration", 300.0)),
                frames_cap=int(dp.get("frames_cap", 200_000)),
                seed=int(dp.get("seed", 0)),
                plan_window=dp.get("plan_window"),
                telemetry_gain=float(dp.get("telemetry_gain", 0.0)),
                delay_model=dm,
                true_delay_model=dp.get("true_delay_model"),
                mode=mode, engine_params=dp.get("engine_params"),
                replan_threshold=dp.get("replan_threshold"),
                faults=dp.get("faults"),
                plan_retries=int(dp.get("plan_retries", 2)),
                plan_deadline=dp.get("plan_deadline"))
            measured_by_model[dm] = rres.measured
            predicted_by_model[dm] = rres.predicted
            if rres.engine:
                engine_by_model[dm] = rres.engine
            if dm == delay_models[0]:
                fallbacks, degraded = rres.fallbacks, rres.degraded
                errors.update(rres.errors)
        measured = measured_by_model[delay_models[0]]
        predicted = predicted_by_model[delay_models[0]]
        engine_aopi = engine_by_model.get(delay_models[0])

    tag = backend if len(devices) > 1 or backend == "vmap" else "vmap"
    backend_str = (f"{tag}[{len(devices)}]" if tag != "vmap" else "vmap")
    return SweepResult(
        names=names, families=fams, policies=list(policies),
        v=v, p_min=p_min, backend=backend_str,
        aopi={p: s["aopi"] for p, s in series.items()},
        acc={p: s["acc"] for p, s in series.items()},
        q={p: s["q"] for p, s in series.items()},
        measured_aopi=measured, predicted_aopi=predicted,
        delay_models=delay_models, measured_by_model=measured_by_model,
        predicted_by_model=predicted_by_model,
        engine_aopi=engine_aopi, engine_by_model=engine_by_model or None,
        errors=errors, fallbacks=fallbacks, degraded=degraded)
