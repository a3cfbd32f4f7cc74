"""State-of-the-art baselines (paper §VI-A): DOS, JCAB, MIN.

All baselines share LBCD's profiling substrate and (per the paper) the
computation policy and model are chosen via Theorem 3 given their own
resolution / allocation decisions; DOS additionally shares LBCD's server
selection. Evaluation (per-camera AoPI/accuracy) uses the same closed forms,
so comparisons isolate the *decision* quality.

Like LBCD, each baseline has two engines: a legacy per-slot ``step(t)`` and
a device-resident whole-horizon rollout (``rollout_min`` / ``rollout_dos`` /
``rollout_jcab`` — one jitted ``lax.scan`` over ``profiles.HorizonTables``,
vmappable over stacked scenarios). ``BaselineController.run`` uses the scan
engine and materializes the legacy ``RunSummary`` view.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import aopi, bcd, binpack, profiles
from .lbcd import RolloutResult, RunSummary, SlotRecord, summarize
from .profiles import EdgeSystem, HorizonTables
from ..kernels import slot_solver


def _evaluate(lam, mu, p, pol):
    lam = np.maximum(lam, 1e-9)
    mu = np.maximum(mu, 1e-9)
    a = np.where(pol == aopi.LCFSP,
                 np.asarray(aopi.aopi_lcfsp(lam, mu, p)),
                 np.asarray(aopi.aopi_fcfs(lam, mu, p)))
    return a


def _thm3_policy(lam, mu, p):
    return np.asarray(aopi.optimal_policy(lam, mu, p))


# ---------------------------------------------------------------------------
# Device-resident rollout engines (one lax.scan per horizon).
# ---------------------------------------------------------------------------

def _eval_decision(acc_t, xi, size, eff, r_idx, m_idx, b, c, active=None):
    """Theorem-3 policy + closed-form AoPI for a fixed configuration (the
    jit twin of ``_thm3_policy`` + ``_evaluate``). With a churn mask the
    dead cameras' outputs are forced to exactly 0 and the score is the
    live-fleet mean (the maskless path is trace-identical to pre-churn)."""
    n = acc_t.shape[0]
    lam = b * eff / size[r_idx]
    mu = c / xi[m_idx, r_idx]
    p = acc_t[jnp.arange(n), m_idx, r_idx]
    if active is not None:
        act = (active > 0).astype(acc_t.dtype)
        lam = lam * act
        mu = mu * act
        pol = aopi.optimal_policy(jnp.maximum(lam, 1e-9),
                                  jnp.maximum(mu, 1e-9), p)
        a = aopi.aopi_masked(lam, mu, p, pol, active=act)
        p = p * act
        b = b * act
        c = c * act
        n_live = jnp.maximum(jnp.sum(act), 1.0)
        return bcd.SlotDecision(r_idx, m_idx, pol, b, c, lam, mu, p, a,
                                jnp.sum(a) / n_live)
    pol = aopi.optimal_policy(lam, mu, p)
    lam_e = jnp.maximum(lam, 1e-9)
    mu_e = jnp.maximum(mu, 1e-9)
    a = jnp.where(pol == aopi.LCFSP, aopi.aopi_lcfsp(lam_e, mu_e, p),
                  aopi.aopi_fcfs(lam_e, mu_e, p))
    return bcd.SlotDecision(r_idx, m_idx, pol, b, c, lam, mu, p, a,
                            jnp.mean(a))


def _scan_result(step, tables: HorizonTables) -> RolloutResult:
    xs = (tables.acc, profiles.eff_sequence(tables),
          tables.budgets_b, tables.budgets_c)
    if tables.active is not None:
        xs = xs + (tables.active,)
    _, (decs, assigns, qs) = jax.lax.scan(step, jnp.float32(0.0), xs)
    return RolloutResult(aopi=decs.aopi, acc=decs.acc, q=qs, assign=assigns,
                         decision=decs)


@functools.partial(jax.jit, static_argnames=("n_bcd_iters", "method",
                                             "solver_effort",
                                             "solver_backend", "interpret"))
def rollout_min(tables: HorizonTables, v=10.0, n_bcd_iters: int = 4,
                method: str = "waterfill", solver_effort: str = "fast",
                solver_backend: str = "jnp",
                interpret: bool | None = None) -> RolloutResult:
    """MIN lower bound over the whole horizon: one pooled virtual server,
    no accuracy queue (q == 0), as a single scan."""
    n = tables.acc.shape[1]
    virt_id = jnp.zeros((n,), jnp.int32)
    has_active = tables.active is not None

    def step(q, xs):
        if has_active:
            acc_t, eff_t, bb, bc, act_t = xs
        else:
            acc_t, eff_t, bb, bc = xs
            act_t = None
        dec = bcd.solve_slot(acc_t, tables.xi, tables.size, eff_t,
                             virt_id, jnp.sum(bb)[None], jnp.sum(bc)[None],
                             jnp.float32(0.0), v, n_servers=1,
                             n_iters=n_bcd_iters, method=method,
                             solver_effort=solver_effort,
                             solver_backend=solver_backend,
                             interpret=interpret, active=act_t)
        return q, (dec, virt_id, q)

    return _scan_result(step, tables)


def _baseline_scan(solver_backend: str, n: int):
    """Resolve the DOS/JCAB config-scan backend and return the scan fn.

    The pallas path streams camera tiles through
    ``slot_solver.baseline_argmax`` so the ``[N, M, R]`` latency/score
    tensors are never materialized; indices are bitwise identical to the
    jnp path. ``"auto"`` follows the same fleet-size switch point as the
    Algorithm-1 solver (jnp below ``AUTO_PALLAS_MIN_CAMERAS``).
    """
    spec = bcd.resolve_spec(solver_backend, n)
    return functools.partial(slot_solver.baseline_argmax,
                             backend=spec.backend)


@functools.partial(jax.jit, static_argnames=("solver_backend",))
def rollout_dos(tables: HorizonTables, weight=1.0,
                solver_backend: str = "jnp") -> RolloutResult:
    """DOS over the whole horizon as a single scan (same per-slot math as
    ``DOSController.step``, with the jit-safe first-fit).

    ``solver_backend`` selects the config-scan engine: "jnp" materializes
    the ``[N, M, R]`` score tensor, "pallas" streams camera tiles through
    the ``slot_solver.baseline_argmax`` kernel (bitwise-identical
    indices); "auto" switches on fleet size like ``bcd.solve_slot``."""
    n = tables.acc.shape[1]
    n_servers = tables.budgets_b.shape[1]
    xi, size = tables.xi, tables.size
    scan = _baseline_scan(solver_backend, n)
    has_active = tables.active is not None

    def step(q, xs):
        if has_active:
            acc_t, eff_t, bb, bc, act_t = xs
        else:
            acc_t, eff_t, bb, bc = xs
        b0 = jnp.sum(bb) / n
        c0 = jnp.sum(bc) / n
        m_idx, r_idx = scan(jnp.full((n,), b0), jnp.full((n,), c0), acc_t,
                            xi, size, eff_t, mode="dos", threshold=weight)

        w_b = jnp.sqrt(size[r_idx] / eff_t)
        w_c = jnp.sqrt(xi[m_idx, r_idx])
        if has_active:
            # Dead cameras carry zero weight, so their proportional share
            # of every server's budget flows to the survivors; the guards
            # keep all-dead servers at 0/eps = 0 instead of 0/0 = NaN.
            act = (act_t > 0).astype(w_b.dtype)
            w_b = w_b * act
            w_c = w_c * act
            eps = jnp.asarray(1e-30, w_b.dtype)
            assign = binpack.first_fit_jax(
                w_b / jnp.maximum(w_b.sum(), eps) * jnp.sum(bb),
                w_c / jnp.maximum(w_c.sum(), eps) * jnp.sum(bc), bb, bc)
            den_b = jnp.maximum(jax.ops.segment_sum(
                w_b, assign, num_segments=n_servers), eps)
            den_c = jnp.maximum(jax.ops.segment_sum(
                w_c, assign, num_segments=n_servers), eps)
            b = bb[assign] * w_b / den_b[assign]
            c = bc[assign] * w_c / den_c[assign]
            dec = _eval_decision(acc_t, xi, size, eff_t, r_idx, m_idx, b, c,
                                 active=act)
        else:
            assign = binpack.first_fit_jax(
                w_b / w_b.sum() * jnp.sum(bb),
                w_c / w_c.sum() * jnp.sum(bc), bb, bc)
            den_b = jax.ops.segment_sum(w_b, assign, num_segments=n_servers)
            den_c = jax.ops.segment_sum(w_c, assign, num_segments=n_servers)
            b = bb[assign] * w_b / den_b[assign]
            c = bc[assign] * w_c / den_c[assign]
            dec = _eval_decision(acc_t, xi, size, eff_t, r_idx, m_idx, b, c)
        return q, (dec, assign, q)

    return _scan_result(step, tables)


@functools.partial(jax.jit, static_argnames=("n_rounds", "solver_backend"))
def rollout_jcab(tables: HorizonTables, latency_cap=0.5,
                 n_rounds: int = 3,
                 solver_backend: str = "jnp") -> RolloutResult:
    """JCAB over the whole horizon as a single scan (same per-slot math as
    ``JCABController.step``; the round-robin assignment is static).

    ``solver_backend`` selects the config-scan engine exactly as in
    :func:`rollout_dos` (the cap check, -inf masking and min-latency
    fallback all run inside the streaming kernel on the pallas path)."""
    n = tables.acc.shape[1]
    n_servers = tables.budgets_b.shape[1]
    xi, size = tables.xi, tables.size
    scan = _baseline_scan(solver_backend, n)
    assign = (jnp.arange(n) % n_servers).astype(jnp.int32)
    counts = jax.ops.segment_sum(jnp.ones((n,)), assign,
                                 num_segments=n_servers)
    share = (1.0 / jnp.maximum(counts, 1.0))[assign]
    has_active = tables.active is not None

    def step(q, xs):
        if has_active:
            acc_t, eff_t, bb, bc, act_t = xs
            act = (act_t > 0).astype(bb.dtype)
            # Per-slot live share (the static round-robin assignment
            # stays, but a server splits its budget over live members).
            counts_t = jax.ops.segment_sum(act, assign,
                                           num_segments=n_servers)
            share_t = act * (1.0 / jnp.maximum(counts_t, 1.0))[assign]
            b = bb[assign] * share_t
            c = bc[assign] * share_t
        else:
            acc_t, eff_t, bb, bc = xs
            act = None
            b = bb[assign] * share
            c = bc[assign] * share
        m_idx = jnp.zeros((n,), jnp.int32)
        r_idx = jnp.zeros((n,), jnp.int32)
        for _ in range(n_rounds):
            m_idx, r_idx = scan(b, c, acc_t, xi, size, eff_t, mode="jcab",
                                threshold=latency_cap)
            size_n = size[r_idx]
            xi_n = xi[m_idx, r_idx]
            if has_active:
                size_n = size_n * act
                xi_n = xi_n * act
                eps = jnp.asarray(1e-30, size_n.dtype)
                den_b = jnp.maximum(jax.ops.segment_sum(
                    size_n, assign, num_segments=n_servers), eps)
                den_c = jnp.maximum(jax.ops.segment_sum(
                    xi_n, assign, num_segments=n_servers), eps)
            else:
                den_b = jax.ops.segment_sum(size_n, assign,
                                            num_segments=n_servers)
                den_c = jax.ops.segment_sum(xi_n, assign,
                                            num_segments=n_servers)
            b = bb[assign] * size_n / den_b[assign]
            c = bc[assign] * xi_n / den_c[assign]
        dec = _eval_decision(acc_t, xi, size, eff_t, r_idx, m_idx, b, c,
                             active=act)
        return q, (dec, assign, q)

    return _scan_result(step, tables)


@dataclasses.dataclass
class BaselineController:
    system: EdgeSystem
    name: str = "base"

    def run(self, n_slots: int, engine: str = "scan") -> RunSummary:
        if engine == "scan":
            res = self._rollout(self.system.horizon(n_slots))
            return summarize(res, v=0.0, p_min=0.0)
        records = [self.step(t) for t in range(n_slots)]
        return RunSummary(records, v=0.0, p_min=0.0)

    def _rollout(self, tables: HorizonTables) -> RolloutResult:
        raise NotImplementedError


class MINController(BaselineController):
    """Lower bound: one virtual server, no accuracy requirement (q == 0)."""

    def __init__(self, system: EdgeSystem, v: float = 10.0, **kw):
        super().__init__(system, name="MIN")
        self.v = v
        self.kw = kw

    def step(self, t: int, tables=None) -> SlotRecord:
        sys = self.system
        budgets_b, budgets_c = sys.capacities(t)
        tables = tables if tables is not None else sys.tables(t)
        n = tables.n_cameras
        dec = bcd.solve_slot_np(
            tables, np.zeros(n, np.int32), np.array([budgets_b.sum()]),
            np.array([budgets_c.sum()]), 0.0, self.v, n_servers=1, **self.kw)
        return SlotRecord(t=t, aopi=dec.aopi, acc=dec.acc, q=0.0,
                          assign=np.zeros(n, np.int32), decision=dec)

    def _rollout(self, tables: HorizonTables) -> RolloutResult:
        known = {"n_iters", "method", "solver_effort", "solver_backend"}
        unknown = set(self.kw) - known
        if unknown:
            raise TypeError(
                f"MIN scan rollout does not support kwargs {sorted(unknown)};"
                " use run(..., engine='legacy')")
        return rollout_min(tables, self.v,
                           n_bcd_iters=self.kw.get("n_iters", 4),
                           method=self.kw.get("method", "waterfill"),
                           solver_effort=self.kw.get("solver_effort",
                                                     "fast"),
                           solver_backend=self.kw.get("solver_backend",
                                                      "jnp"))


class DOSController(BaselineController):
    """DOS [47]: maximize (accuracy - latency).

    Per camera it picks the (r, m) maximizing ``zeta - (1/lam + 1/mu)`` under
    an equal split, then allocates resources to minimize total expected
    latency (sqrt water-filling — latency-optimal but AoPI-blind, which is
    exactly the behaviour §VI-B2 reports: it collapses to the lightest
    configuration). Server selection is shared with LBCD (first-fit on its
    demands), per §VI-A.
    """

    def __init__(self, system: EdgeSystem, weight: float = 1.0,
                 solver_backend: str = "jnp"):
        super().__init__(system, name="DOS")
        self.weight = weight
        self.solver_backend = solver_backend

    def step(self, t: int, tables=None) -> SlotRecord:
        sys = self.system
        budgets_b, budgets_c = sys.capacities(t)
        tables = tables if tables is not None else sys.tables(t)
        n, m_count, r_count = tables.acc.shape

        # Equal-split provisional rates.
        b0 = budgets_b.sum() / n
        c0 = budgets_c.sum() / n
        lam0 = b0 * tables.eff[:, None, None] / tables.size[None, None, :]
        mu0 = c0 / tables.xi[None, :, :]
        latency = 1.0 / np.maximum(lam0, 1e-9) + 1.0 / np.maximum(mu0, 1e-9)
        score = tables.acc - self.weight * latency
        flat = score.reshape(n, -1)
        best = flat.argmax(1)
        m_idx = (best // r_count).astype(np.int32)
        r_idx = (best % r_count).astype(np.int32)

        # Latency-minimizing allocation: b ~ sqrt(size/eff), c ~ sqrt(xi).
        size_n = tables.size[r_idx]
        xi_n = tables.xi[m_idx, r_idx]
        w_b = np.sqrt(size_n / tables.eff)
        w_c = np.sqrt(xi_n)
        assign = binpack.first_fit(w_b / w_b.sum() * budgets_b.sum(),
                                   w_c / w_c.sum() * budgets_c.sum(),
                                   budgets_b, budgets_c)
        b = np.zeros(n)
        c = np.zeros(n)
        for s in range(len(budgets_b)):
            mask = assign == s
            if not mask.any():
                continue
            b[mask] = budgets_b[s] * w_b[mask] / w_b[mask].sum()
            c[mask] = budgets_c[s] * w_c[mask] / w_c[mask].sum()

        lam = b * tables.eff / size_n
        mu = c / xi_n
        p = tables.acc[np.arange(n), m_idx, r_idx]
        pol = _thm3_policy(lam, mu, p)
        a = _evaluate(lam, mu, p, pol)
        dec = bcd.SlotDecision(r_idx, m_idx, pol, b, c, lam, mu, p, a,
                               np.float32(a.mean()))
        return SlotRecord(t=t, aopi=a, acc=p, q=0.0, assign=assign,
                          decision=dec)

    def _rollout(self, tables: HorizonTables) -> RolloutResult:
        return rollout_dos(tables, self.weight,
                           solver_backend=self.solver_backend)


class JCABController(BaselineController):
    """JCAB [3]: maximize accuracy s.t. total latency <= latency_cap, with
    computation allocated proportional to the configuration's xi [48]."""

    def __init__(self, system: EdgeSystem, latency_cap: float = 0.5,
                 n_rounds: int = 3, solver_backend: str = "jnp"):
        super().__init__(system, name="JCAB")
        self.latency_cap = latency_cap
        self.n_rounds = n_rounds
        self.solver_backend = solver_backend

    def step(self, t: int, tables=None) -> SlotRecord:
        sys = self.system
        budgets_b, budgets_c = sys.capacities(t)
        tables = tables if tables is not None else sys.tables(t)
        n, m_count, r_count = tables.acc.shape
        assign = np.asarray([i % len(budgets_b) for i in range(n)], np.int32)

        b = np.zeros(n)
        c = np.zeros(n)
        for s in range(len(budgets_b)):
            mask = assign == s
            b[mask] = budgets_b[s] / max(mask.sum(), 1)
            c[mask] = budgets_c[s] / max(mask.sum(), 1)

        m_idx = np.zeros(n, np.int32)
        r_idx = np.zeros(n, np.int32)
        for _ in range(self.n_rounds):
            # Highest-accuracy config meeting the latency cap.
            lam = b[:, None, None] * tables.eff[:, None, None] / \
                tables.size[None, None, :]
            mu = c[:, None, None] / tables.xi[None, :, :]
            latency = 1.0 / np.maximum(lam, 1e-9) + 1.0 / np.maximum(mu, 1e-9)
            ok = latency <= self.latency_cap
            score = np.where(ok, tables.acc, -np.inf)
            flat = score.reshape(n, -1)
            best = flat.argmax(1)
            none_ok = ~ok.reshape(n, -1).any(1)
            # If nothing meets the cap, take the min-latency config.
            fallback = latency.reshape(n, -1).argmin(1)
            best = np.where(none_ok, fallback, best)
            m_idx = (best // r_count).astype(np.int32)
            r_idx = (best % r_count).astype(np.int32)
            # Re-allocate: bandwidth ~ frame size (equalizes lam), compute
            # ~ xi (per [48]).
            size_n = tables.size[r_idx]
            xi_n = tables.xi[m_idx, r_idx]
            for s in range(len(budgets_b)):
                mask = assign == s
                if not mask.any():
                    continue
                b[mask] = budgets_b[s] * size_n[mask] / size_n[mask].sum()
                c[mask] = budgets_c[s] * xi_n[mask] / xi_n[mask].sum()

        lam = b * tables.eff / tables.size[r_idx]
        mu = c / tables.xi[m_idx, r_idx]
        p = tables.acc[np.arange(n), m_idx, r_idx]
        pol = _thm3_policy(lam, mu, p)
        a = _evaluate(lam, mu, p, pol)
        dec = bcd.SlotDecision(r_idx, m_idx, pol, b, c, lam, mu, p, a,
                               np.float32(a.mean()))
        return SlotRecord(t=t, aopi=a, acc=p, q=0.0, assign=assign,
                          decision=dec)

    def _rollout(self, tables: HorizonTables) -> RolloutResult:
        return rollout_jcab(tables, self.latency_cap,
                            n_rounds=self.n_rounds,
                            solver_backend=self.solver_backend)


def make(name: str, system: EdgeSystem, **kw):
    name = name.upper()
    if name == "MIN":
        return MINController(system, **kw)
    if name == "DOS":
        return DOSController(system, **kw)
    if name == "JCAB":
        return JCABController(system, **kw)
    raise ValueError(f"unknown baseline {name!r}")
