"""Algorithm 3 — the LBCD online controller and its scan rollout engine.

Per slot t (paper §V-D):
  1. observe capacities (B_t^s, C_t^s) and profile zeta_n^t;
  2. solve (P2): Algorithm 2 (virtual server -> Algorithm 1 -> first-fit ->
     Algorithm 1 per real server);
  3. update the virtual accuracy queue q(t+1) (Eq. 44).

Two execution engines share the same per-slot math:

  * ``rollout(tables, v, p_min)`` — the device-resident engine. A full
    T-slot run is **one jitted ``lax.scan``** over a pregenerated
    ``profiles.HorizonTables`` pytree: virtual-server solve -> jit-safe
    first-fit -> per-server solve -> Eq. 44 queue update, all on device,
    with zero per-slot host round trips. Pure in (tables, v, p_min, q0), so
    it vmaps over hyperparameter grids (``rollout_grid``) and over stacked
    same-shape scenarios (``rollout_scenarios``) — the substrate for every
    benchmark sweep and the future pmap/multi-fleet scale-out.

  * ``LBCDController`` — the stateful per-slot wrapper kept for the
    serving/failover control planes (they need ``step(t)`` against live,
    mutable capacities). ``run()`` delegates to the scan engine and
    materializes the legacy ``RunSummary``/``SlotRecord`` views; a custom
    ``assign_fn`` falls back to the per-slot python loop.

``benchmarks/bench_rollout.py`` measures engine vs legacy slots/sec.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import bcd, binpack, lyapunov, profiles
from .lyapunov import VirtualQueue
from .profiles import EdgeSystem, HorizonTables


@dataclasses.dataclass
class SlotRecord:
    t: int
    aopi: np.ndarray          # per-camera closed-form AoPI
    acc: np.ndarray           # per-camera accuracy
    q: float
    assign: np.ndarray        # camera -> server
    decision: bcd.SlotDecision

    @property
    def mean_aopi(self) -> float:
        return float(np.mean(self.aopi))

    @property
    def mean_acc(self) -> float:
        return float(np.mean(self.acc))


@dataclasses.dataclass
class RunSummary:
    records: list
    v: float
    p_min: float

    @property
    def mean_aopi(self) -> float:
        return float(np.mean([r.mean_aopi for r in self.records]))

    @property
    def mean_acc(self) -> float:
        return float(np.mean([r.mean_acc for r in self.records]))

    @property
    def aopi_series(self) -> np.ndarray:
        return np.array([r.mean_aopi for r in self.records])

    @property
    def acc_series(self) -> np.ndarray:
        return np.array([r.mean_acc for r in self.records])

    @property
    def q_series(self) -> np.ndarray:
        return np.array([r.q for r in self.records])


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RolloutResult:
    """Stacked per-slot outputs of one scan rollout (leading axis = slot;
    extra leading axes appear under vmap)."""
    aopi: jnp.ndarray         # [T, N] per-camera closed-form AoPI
    acc: jnp.ndarray          # [T, N] per-camera accuracy
    q: jnp.ndarray            # [T]    virtual queue after the Eq. 44 update
    assign: jnp.ndarray       # [T, N] camera -> server
    decision: bcd.SlotDecision  # all fields stacked [T, ...]

    @property
    def mean_aopi(self) -> float:
        return float(jnp.mean(self.aopi))

    @property
    def mean_acc(self) -> float:
        return float(jnp.mean(self.acc))

    @property
    def aopi_series(self) -> np.ndarray:
        return np.asarray(self.aopi.mean(axis=-1))

    @property
    def acc_series(self) -> np.ndarray:
        return np.asarray(self.acc.mean(axis=-1))

    @property
    def q_series(self) -> np.ndarray:
        return np.asarray(self.q)


@functools.partial(jax.jit, static_argnames=("n_bcd_iters", "method",
                                             "solver_effort",
                                             "solver_backend", "interpret"))
def rollout(tables: HorizonTables, v, p_min, q0=0.0,
            n_bcd_iters: int = 4, method: str = "waterfill",
            solver_effort: str = "fast", solver_backend: str = "jnp",
            interpret: bool | None = None) -> RolloutResult:
    """Run Algorithm 3 for all T slots as one jitted ``lax.scan``.

    Args:
      tables: whole-horizon profiles/capacities (``EdgeSystem.horizon()``).
      v, p_min: Lyapunov penalty weight and accuracy floor (traced scalars,
        so the function vmaps over hyperparameter grids).
      q0: initial virtual-queue value.
      solver_backend: "jnp" | "pallas" | "auto" — Algorithm-1
        implementation (see ``bcd.solve_slot``; "auto" switches on fleet
        size), optionally with tiling/fusion knobs riding the string
        (``"pallas:tile=4096"``, ``"pallas:nofuse"`` — see
        ``bcd.parse_backend``); ``interpret`` is the pallas
        interpret-mode override (None = auto off-TPU).
    Returns a ``RolloutResult`` of device arrays.
    """
    n = tables.acc.shape[1]
    n_servers = tables.budgets_b.shape[1]
    virt_id = jnp.zeros((n,), jnp.int32)
    solve = functools.partial(bcd.solve_slot, n_iters=n_bcd_iters,
                              method=method, solver_effort=solver_effort,
                              solver_backend=solver_backend,
                              interpret=interpret)
    # ``tables.active is None`` is a static (trace-time) branch: the
    # maskless program below is byte-identical to the pre-churn engine.
    has_active = tables.active is not None
    # First-fit runs as a kernel where the slot solver does.
    first_fit = bcd.resolve_spec(solver_backend, n, method=method,
                                 masked=has_active).backend

    def step(q, xs):
        if has_active:
            acc_t, eff_t, act_t, bb, bc = xs
        else:
            acc_t, eff_t, bb, bc = xs
            act_t = None
        # Algorithm 2 lines 1-2: virtual-server ideal demands.
        virt = solve(acc_t, tables.xi, tables.size, eff_t, virt_id,
                     jnp.sum(bb)[None], jnp.sum(bc)[None], q, v, n_servers=1,
                     active=act_t)
        # Algorithm 2 lines 3-9: first-fit placement (jit-safe).
        assign = binpack.first_fit_jax(virt.b, virt.c, bb, bc,
                                       backend=first_fit,
                                       interpret=interpret)
        # Algorithm 2 line 10: re-solve per real server.
        dec = solve(acc_t, tables.xi, tables.size, eff_t, assign,
                    bb, bc, q, v, n_servers=n_servers, active=act_t)
        if has_active:
            # Eq. 44 over the live fleet only — churned-out cameras must
            # not drag the accuracy constraint toward zero.
            acc_mean = jnp.sum(dec.acc) / jnp.maximum(jnp.sum(act_t), 1.0)
        else:
            acc_mean = jnp.mean(dec.acc)
        q_next = lyapunov.queue_update(q, acc_mean, p_min)  # Eq. 44
        return q_next, (dec, assign, q_next)

    xs = ((tables.acc, profiles.eff_sequence(tables), tables.active,
           tables.budgets_b, tables.budgets_c) if has_active else
          (tables.acc, profiles.eff_sequence(tables),
           tables.budgets_b, tables.budgets_c))
    _, (decs, assigns, qs) = jax.lax.scan(
        step, jnp.asarray(q0, jnp.float32), xs)
    return RolloutResult(aopi=decs.aopi, acc=decs.acc, q=qs, assign=assigns,
                         decision=decs)


@functools.partial(jax.jit, static_argnames=("n_bcd_iters", "method",
                                             "solver_backend", "interpret"))
def rollout_grid(tables: HorizonTables, v, p_min, q0=0.0,
                 n_bcd_iters: int = 4, method: str = "waterfill",
                 solver_backend: str = "jnp",
                 interpret: bool | None = None) -> RolloutResult:
    """One vmapped call over a (V, P_min) hyperparameter grid.

    ``v``/``p_min`` are 1-D arrays of equal length G; returns a
    ``RolloutResult`` with leading axis G."""
    fn = functools.partial(rollout, n_bcd_iters=n_bcd_iters, method=method,
                           solver_backend=solver_backend,
                           interpret=interpret)
    return jax.vmap(fn, in_axes=(None, 0, 0, None))(
        tables, jnp.asarray(v), jnp.asarray(p_min), q0)


@functools.partial(jax.jit, static_argnames=("n_bcd_iters", "method",
                                             "solver_backend", "interpret"))
def rollout_scenarios(tables: HorizonTables, v, p_min, q0=0.0,
                      n_bcd_iters: int = 4, method: str = "waterfill",
                      solver_backend: str = "jnp",
                      interpret: bool | None = None) -> RolloutResult:
    """One vmapped call over stacked same-shape scenarios
    (``profiles.stack_horizons``); shared scalar hyperparameters."""
    fn = functools.partial(rollout, n_bcd_iters=n_bcd_iters, method=method,
                           solver_backend=solver_backend,
                           interpret=interpret)
    return jax.vmap(fn, in_axes=(0, None, None, None))(
        tables, v, p_min, q0)


def summarize(res: RolloutResult, v: float, p_min: float) -> RunSummary:
    """Materialize a scan rollout into the legacy RunSummary/SlotRecord
    views (one host transfer for the whole horizon)."""
    res = jax.tree.map(np.asarray, res)
    records = [
        SlotRecord(t=t, aopi=res.aopi[t], acc=res.acc[t],
                   q=float(res.q[t]), assign=res.assign[t],
                   decision=jax.tree.map(lambda x, t=t: x[t], res.decision))
        for t in range(res.aopi.shape[0])
    ]
    return RunSummary(records, v, p_min)


class LBCDController:
    """The paper's controller; also reused as the serving-runtime planner
    (repro.serving.service) and the server-failover mechanism
    (repro.faults.failover_assignment)."""

    def __init__(self, system: EdgeSystem, v: float = 10.0,
                 p_min: float = 0.7, n_bcd_iters: int = 4,
                 method: str = "waterfill",
                 assign_fn: Optional[Callable] = None,
                 solver_effort: str = "fast",
                 solver_backend: str = "jnp"):
        self.system = system
        self.v = v
        self.queue = VirtualQueue(p_min=p_min)
        self.n_bcd_iters = n_bcd_iters
        self.method = method
        self.assign_fn = assign_fn or binpack.first_fit
        self.solver_effort = solver_effort
        self.solver_backend = solver_backend

    def plan(self, tables: HorizonTables, q0: float | None = None
             ) -> RolloutResult:
        """Lookahead / what-if epochs for the serving planner: run the
        controller's hyperparameters over ``tables`` as ONE jitted scan
        (``rollout``) from the live virtual-queue state. Does *not* advance
        ``self.queue`` — the service commits epochs one at a time as the
        data plane actually executes them (``AnalyticsService.run_epoch``).
        """
        return rollout(tables, self.v, self.queue.p_min,
                       q0=self.queue.q if q0 is None else q0,
                       n_bcd_iters=self.n_bcd_iters, method=self.method,
                       solver_effort=self.solver_effort,
                       solver_backend=self.solver_backend)

    def step(self, t: int, tables=None) -> SlotRecord:
        sys = self.system
        budgets_b, budgets_c = sys.capacities(t)          # Alg. 3 line 2
        tables = tables if tables is not None else sys.tables(t)  # line 3
        n = tables.n_cameras

        # --- Algorithm 2 line 1-2: virtual server ideal demands.
        virt = bcd.solve_slot_np(
            tables, np.zeros(n, np.int32),
            np.array([budgets_b.sum()]), np.array([budgets_c.sum()]),
            self.queue.q, self.v, n_servers=1, n_iters=self.n_bcd_iters,
            method=self.method, solver_effort=self.solver_effort,
            solver_backend=self.solver_backend)

        # --- Algorithm 2 lines 3-9: first-fit placement.
        assign = self.assign_fn(virt.b, virt.c, budgets_b, budgets_c)

        # --- Algorithm 2 line 10: re-solve per real server.
        dec = bcd.solve_slot_np(
            tables, assign, budgets_b, budgets_c, self.queue.q, self.v,
            n_servers=len(budgets_b), n_iters=self.n_bcd_iters,
            method=self.method, solver_effort=self.solver_effort,
            solver_backend=self.solver_backend)

        q = self.queue.update(float(np.mean(dec.acc)))    # Alg. 3 line 5
        return SlotRecord(t=t, aopi=dec.aopi, acc=dec.acc, q=q,
                          assign=assign, decision=dec)

    def run(self, n_slots: int, engine: str = "scan") -> RunSummary:
        """Roll the controller forward ``n_slots`` slots.

        ``engine="scan"`` (default) pregenerates the horizon and runs the
        device-resident ``rollout``; ``engine="legacy"`` keeps the per-slot
        python loop. A custom ``assign_fn`` forces the legacy path (the scan
        engine is specialized to first-fit)."""
        if engine == "scan" and self.assign_fn is binpack.first_fit:
            tables = self.system.horizon(n_slots)
            res = rollout(tables, self.v, self.queue.p_min, q0=self.queue.q,
                          n_bcd_iters=self.n_bcd_iters, method=self.method,
                          solver_effort=self.solver_effort,
                          solver_backend=self.solver_backend)
            self.queue.q = float(res.q[-1])
            return summarize(res, self.v, self.queue.p_min)
        records = [self.step(t) for t in range(n_slots)]
        return RunSummary(records, self.v, self.queue.p_min)
