"""The timed paths, one per traffic ``entry``, and their checks.

Each loop builds its inputs from the cell's configuration, traffic
mix and ``--seed`` in ``__init__``, warms up every shape its window uses
in ``setup``, runs the closed loop in ``window`` until ``seconds`` have
passed (ending on a whole unit of work), and compares a sample of what
the window produced with ``reference`` in ``check``. ``control=True``
runs the cell's lower-precision control in the program's place.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference
from bench.traffic import horizon


class Reservoir:
    """A uniform sample of ``size`` items from a stream of unknown
    length, drawn from the seed, plus the stream's last item."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng = size, rng
        self.items: list = []
        self.seen = 0
        self.last = None

    def offer(self, item) -> None:
        self.seen += 1
        self.last = item
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.size:
                self.items[j] = item

    def sample(self) -> list:
        if self.last is None or any(i is self.last for i in self.items):
            return list(self.items)
        return self.items + [self.last]


def _device_tables(tables: dict, dtype: str, quantize: str | None = None):
    """The program's ``HorizonTables`` from host truth tables, in the
    dtype the planner is served in. ``quantize`` rounds every leaf through
    a narrower dtype first (the planner's lower-precision control)."""
    import jax.numpy as jnp
    from repro.core.profiles import HorizonTables
    leaves = {}
    for k in ("acc", "xi", "size", "eff", "budgets_b", "budgets_c"):
        x = np.asarray(tables[k])
        if quantize:
            x = np.asarray(jnp.asarray(x, quantize), np.float64)
        leaves[k] = jnp.asarray(x, dtype)
    return HorizonTables(**leaves)


def _host_tables(tables: dict, dtype: str) -> dict:
    """The same tables as host arrays in the served dtype."""
    return {k: np.asarray(v, dtype) for k, v in tables.items()}


def _plan_dict(plan) -> dict:
    d = plan.decision
    return {"r_idx": d.r_idx, "m_idx": d.m_idx, "pol": d.pol, "b": d.b,
            "c": d.c, "mu": d.mu, "aopi": plan.aopi, "q": plan.q,
            "assign": plan.assign}


def _merge_max(into: dict, gaps: dict) -> None:
    for k, v in gaps.items():
        into[k] = max(into.get(k, 0.0), v)


class _Loop:
    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 control: bool = False):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.control = control
        # The planner's lower-precision control: tables at bfloat16.
        self.quantize = "bfloat16" if control else None
        self.window_plan = int(traffic["plan_window"])
        self.rng = np.random.default_rng([self.seed, 0x5EED])

    def _base(self, n_slots: int) -> dict:
        c = self.cfg
        return horizon.build(c["n_cameras"], c["n_servers"], n_slots,
                             c["bandwidth_hz_per_server"],
                             c["compute_flops_per_server"],
                             c["population_seed"])

    def _controller(self):
        from repro.core.lbcd import LBCDController
        c = self.cfg
        return LBCDController(None, v=c["v"], p_min=c["p_min"],
                              n_bcd_iters=c["bcd_iters"],
                              solver_backend=c["solver_backend"])

    def _warm_queue(self, svc, slots: int) -> None:
        """Bring the service's virtual queue to its steady cycle: plan
        ``warm_cycles`` cycles of the horizon ahead, committing each plan
        window's queue as ``run_epoch`` would. From an empty queue the
        fleet's accuracy stays under ``p_min`` and the queue climbs for
        some 900 slots before it settles."""
        queue = svc.controller.queue
        for _ in range(int(self.traffic["warm_cycles"])):
            for t0 in range(0, slots, self.window_plan):
                queue.q = float(np.asarray(
                    svc.plan_horizon(self.window_plan, t0).q[-1]))

    def release(self) -> None:
        """Drop the program's state before the reference runs."""

    def programs(self, spans: list) -> list:
        """``(name, lowered)`` of the compiled programs the window drove
        (``spans``: its ``repro.obs`` events), for their memory analysis."""
        return []


class Serve(_Loop):
    """``AnalyticsService.run_epoch`` back to back on one long-lived
    service: plan a window of ``plan_window`` epochs, measure it on the
    GI/G/1 data plane, fold the telemetry in. Epoch ``t`` cycles over the
    deployment's ``horizon_slots`` slots; the data plane is seeded once
    from ``--seed``.

    With the telemetry gain at 0 the measurements do not feed back, so
    the service's only state between plan windows is the virtual queue,
    and it follows the plans alone. Set-up brings it to its steady cycle
    by planning ``warm_cycles`` cycles ahead, committing each window's
    queue as ``run_epoch`` would, then runs one whole cycle of
    ``run_epoch``: every frame budget the window uses is compiled there.
    The cameras keep the configuration's order, so every seed runs the
    same plans: the data plane simulates as many frames per lane as the
    fastest planned stream needs, and another order tips placements and
    with them that work."""

    #: Lane-frames of the data plane re-simulated per checked window.
    CHECK_LANE_FRAMES = 1 << 22
    CHECK_WINDOWS = 2

    def __init__(self, cfg, traffic, seed, control=False):
        super().__init__(cfg, traffic, seed, control)
        self.slots = int(traffic["horizon_slots"])
        self.base = self._base(self.slots)
        self.dp_seed = int(np.random.SeedSequence([self.seed, 1])
                           .generate_state(1)[0])
        self.kept = Reservoir(self.CHECK_WINDOWS, self.rng)
        self.t = 0
        self.epochs = 0
        self.failed = 0
        if control:
            # The program's own float32 data plane: raise its float64
            # switch point past every frame budget. (The tables go in at
            # bfloat16 precision too.)
            from repro.core import queues
            queues.F32_MAX_FRAMES = 1 << 40

    def setup(self) -> None:
        from repro.serving import AnalyticsService
        c, tr = self.cfg, self.traffic
        self.svc = AnalyticsService(
            self._controller(), mode="mm1", epoch_duration=c["epoch_s"],
            frames_cap=c["frames_cap"], seed=self.dp_seed,
            plan_window=self.window_plan,
            tables=_device_tables(self.base, c["dtypes"]["tables"],
                                  quantize=self.quantize),
            telemetry_gain=tr["telemetry_gain"],
            delay_model=c["delay_model"],
            replan_threshold=tr["replan_threshold"])
        self._warm_queue(self.svc, self.slots)
        for _ in range(0, self.slots, self.window_plan):
            self._run_plan_window(record=False)

    def _run_plan_window(self, record: bool) -> None:
        svc, t0 = self.svc, self.t
        n_ep = min(self.window_plan, self.slots - t0)
        q0 = float(svc.controller.queue.q)
        seen = [len(x) for x in (svc.plan_failures, svc.fallbacks,
                                 svc.degraded_epochs)]
        reps = [svc.run_epoch(t) for t in range(t0, t0 + n_ep)]
        self.t = (t0 + n_ep) % self.slots
        if not record:
            return
        self.epochs += n_ep
        # Failures of this plan window only: t recurs every cycle.
        bad = {t for t, _, _ in svc.plan_failures[seen[0]:]}
        bad |= {t for t, _ in svc.fallbacks[seen[1]:]}
        bad |= set(svc.degraded_epochs[seen[2]:])
        self.failed += sum(
            1 for r in reps if r.t in bad
            or not np.isfinite(r.per_stream_measured).all())
        self.kept.offer({
            "t0": t0, "q0": q0, "plan": _plan_dict(svc._plan),
            "aopi": np.stack([r.per_stream_measured for r in reps]),
            "n_frames": np.stack([r.telemetry.n_frames for r in reps]),
            "n_completed": np.stack([r.telemetry.n_completed
                                     for r in reps])})

    def window(self, seconds: float) -> dict:
        start = time.perf_counter()
        deadline = start + seconds
        while time.perf_counter() < deadline:
            self._run_plan_window(record=True)
        wall = time.perf_counter() - start
        n = self.cfg["n_cameras"]
        return {"wall_s": wall, "attempted": self.epochs,
                "failed": self.failed,
                "epoch_rate": self.epochs * n / wall}

    def programs(self, spans: list) -> list:
        import jax
        import jax.numpy as jnp
        from repro.core import queues
        out = _rollout_program(self.svc, self.window_plan)
        n_ep, n = self.window_plan, self.cfg["n_cameras"]
        budgets = sorted({e["args"]["n_frames"] for e in spans
                          if e["name"] == "queues.gi_g1_window"})
        with jax.enable_x64(True):
            keys = jax.vmap(jax.random.fold_in, (None, 0))(
                jax.random.key(0), jnp.arange(n_ep))
            x = jnp.ones((n_ep, n), jnp.float64)
            pol = jnp.zeros((n_ep, n), jnp.int32)
            for f in budgets:
                dtype = "float64" if f > queues.F32_MAX_FRAMES else "float32"
                xs = x.astype(dtype)
                out.append((f"_window_sim[{n_ep}x{n}x{f}]",
                            queues._window_sim.lower(
                                xs, xs, xs, pol, keys,
                                float(self.cfg["epoch_s"]), f,
                                str(self.cfg["delay_model"]), 0)))
        return out

    def release(self) -> None:
        del self.svc

    def check(self) -> dict:
        c = self.cfg
        served = c["dtypes"]["tables"]
        out: dict = {}
        for item in self.kept.sample():
            plan, t0 = item["plan"], item["t0"]
            n_ep = np.asarray(plan["q"]).shape[0]
            truth = horizon.window(self.base, t0, t0 + n_ep)
            _merge_max(out, reference.planner_gaps(
                plan, truth, item["q0"], c["p_min"], c["v"]))
            _merge_max(out, self._check_data_plane(
                item, _host_tables(truth, served)))
        return out

    def _check_data_plane(self, item: dict, tab: dict) -> dict:
        """Re-simulate a seeded sample of the window's lanes."""
        plan = item["plan"]
        r = np.asarray(plan["r_idx"])
        m = np.asarray(plan["m_idx"])
        n_ep, n = r.shape
        # The plane's inputs, as the service forms them from the plan and
        # the served tables: true arrival rate and accuracy of the chosen
        # configurations, the planned service rate.
        lam = (np.asarray(plan["b"]) * tab["eff"][None, :]
               / tab["size"][r]).astype(np.float64)
        p = tab["acc"][np.arange(n_ep)[:, None], np.arange(n)[None, :],
                       m, r].astype(np.float64)
        mu = np.asarray(plan["mu"], np.float64)
        n_frames = reference.frames_budget(max(lam.max(), 1e-6),
                                           self.cfg["epoch_s"],
                                           self.cfg["frames_cap"])
        lanes = min(n_ep * n, max(8, self.CHECK_LANE_FRAMES // n_frames))
        pick = np.sort(self.rng.choice(n_ep * n, size=lanes, replace=False))
        e, i = np.divmod(pick, n)
        u = reference.lane_uniforms(self.dp_seed, item["t0"] + e, i,
                                    n_frames)
        ref = reference.simulate_lanes(
            u, lam[e, i], mu[e, i], p[e, i], np.asarray(plan["pol"])[e, i],
            self.cfg["epoch_s"])
        got = {k: item[k][e, i] for k in ("aopi", "n_frames", "n_completed")}
        return reference.data_plane_gaps(got, ref)


def _rollout_program(svc, k: int) -> list:
    """The planner's compiled program, lowered as ``plan_horizon(k, 0)``
    calls it."""
    from repro.core import lbcd
    ctrl = svc.controller
    return [("rollout", lbcd.rollout.lower(
        svc._window_tables(0, k), ctrl.v, ctrl.queue.p_min,
        q0=ctrl.queue.q, n_bcd_iters=ctrl.n_bcd_iters, method=ctrl.method,
        solver_effort=ctrl.solver_effort,
        solver_backend=ctrl.solver_backend))]


class Replan(_Loop):
    """``AnalyticsService.plan_horizon(plan_window, t0)`` back to back with
    every leaf copied to the host; t0 advances one slot per plan and
    cycles over ``horizon_slots``; each plan commits its first slot's
    virtual queue, as the service does, from the steady cycle set-up
    brings it to."""

    CHECK_PLANS = 8

    def __init__(self, cfg, traffic, seed, control=False):
        super().__init__(cfg, traffic, seed, control)
        self.slots = int(traffic["horizon_slots"])
        # Each seed lists the deployment's cameras in its own order: the
        # solver's work is fixed by its shapes, so the order changes the
        # inputs and not the work.
        perm = np.random.default_rng([self.seed, 1]).permutation(
            cfg["n_cameras"])
        self.truth = horizon.permute_cameras(
            self._base(self.slots + self.window_plan - 1), perm)
        self.kept = Reservoir(self.CHECK_PLANS, self.rng)
        self.samples: list[float] = []
        self.failed = 0
        self.i = 0

    def setup(self) -> None:
        from repro.serving import AnalyticsService
        tables = _device_tables(self.truth, self.cfg["dtypes"]["tables"],
                                quantize=self.quantize)
        self.svc = AnalyticsService(self._controller(), tables=tables,
                                    plan_window=self.window_plan)
        self._warm_queue(self.svc, self.slots)
        self._plan()

    def _plan(self):
        import jax
        t0 = self.i % self.slots
        self.i += 1
        queue = self.svc.controller.queue
        q0 = float(queue.q)
        start = time.perf_counter()
        plan = jax.tree.map(np.asarray,
                            self.svc.plan_horizon(self.window_plan, t0))
        dt = time.perf_counter() - start
        queue.q = float(plan.q[0])
        return {"t0": t0, "q0": q0, "plan": _plan_dict(plan)}, dt

    def window(self, seconds: float) -> dict:
        start = time.perf_counter()
        deadline = start + seconds
        while time.perf_counter() < deadline:
            try:
                item, dt = self._plan()
            except Exception:  # noqa: BLE001 - a raising plan is a failure
                self.failed += 1
                continue
            self.samples.append(dt)
            p = item["plan"]
            if not all(np.isfinite(np.asarray(p[k], np.float64)).all()
                       for k in ("aopi", "q", "b", "c")):
                self.failed += 1
            self.kept.offer(item)
        wall = time.perf_counter() - start
        if not self.samples:
            raise RuntimeError(f"no plan completed in the window; "
                               f"{self.failed} failed")
        ms = 1e3 * np.asarray(self.samples)
        return {"wall_s": wall, "attempted": len(self.samples) + self.failed,
                "failed": self.failed, "plans": len(self.samples),
                "plan_p95_ms": float(np.percentile(ms, 95)),
                "plan_p50_ms": float(np.percentile(ms, 50))}

    def programs(self, spans: list) -> list:
        return _rollout_program(self.svc, self.window_plan)

    def release(self) -> None:
        del self.svc

    def check(self) -> dict:
        out: dict = {}
        for item in self.kept.sample():
            t0 = item["t0"]
            truth = horizon.window(self.truth, t0, t0 + self.window_plan)
            _merge_max(out, reference.planner_gaps(
                item["plan"], truth, item["q0"], self.cfg["p_min"],
                self.cfg["v"]))
        return out


ENTRIES = {"serve": Serve, "replan": Replan}
