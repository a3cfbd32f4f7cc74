"""Plain float64 reference for what the timed paths produce.

It imports nothing of the program and takes nothing the program made
except the answers it checks. Inputs come from the benchmark's own
generator (``traffic/horizon.py``) and from ``--seed``.

* Planner (Algorithm 3 of arXiv:2406.14820): given the planned
  configuration, placement and b/c allocation, it recomputes every
  camera's rates, accuracy and closed-form AoPI (Theorems 1 and 2) and
  the virtual queue (Eq. 44).
* Data plane: the frame-level GI/G/1 queue of Sec. III-A simulated frame
  by frame in float64 for a sample of (epoch, stream) lanes, from the
  same per-lane variates the data plane's documented key scheme
  ``fold_in(fold_in(key(seed), epoch), stream)`` draws.
"""
from __future__ import annotations

import numpy as np

FCFS, LCFSP = 0, 1
#: Constraint (10), lam < mu under FCFS, as the compute allocator states
#: it: mu >= 1.05 lam.
STABILITY_MARGIN = 1.05


def closed_form_aopi(lam, mu, p, pol) -> np.ndarray:
    """Average AoPI: Theorem 1 (FCFS, infinite where lam >= mu) and
    Theorem 2 (LCFSP), in float64."""
    lam, mu, p = (np.asarray(x, np.float64) for x in (lam, mu, p))
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        fcfs = ((1.0 + 1.0 / p) / lam + 1.0 / mu
                + (2.0 * lam**3 + lam * mu**2 - mu * lam**2)
                / (mu**4 - mu**2 * lam**2))
        fcfs = np.where(lam < mu, fcfs, np.inf)
        lcfsp = (1.0 + 1.0 / p) / lam + 1.0 / (p * mu)
    return np.where(np.asarray(pol) == LCFSP, lcfsp, fcfs)


def aopi_partials(lam, mu, p, pol) -> tuple[np.ndarray, np.ndarray]:
    """dA/dlam and dA/dmu of the closed form (FCFS only where lam < mu),
    in float64."""
    lam, mu, p = (np.asarray(x, np.float64) for x in (lam, mu, p))
    lcfsp = np.asarray(pol) == LCFSP
    a = 1.0 + 1.0 / p
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        num = 2.0 * lam**3 + lam * mu**2 - mu * lam**2
        den = mu**4 - mu**2 * lam**2
        d_lam = -a / lam**2 + ((6.0 * lam**2 + mu**2 - 2.0 * mu * lam) * den
                               + 2.0 * mu**2 * lam * num) / den**2
        d_mu = -1.0 / mu**2 + ((2.0 * lam * mu - lam**2) * den
                               - (4.0 * mu**3 - 2.0 * mu * lam**2) * num
                               ) / den**2
        return (np.where(lcfsp, -a / lam**2, d_lam),
                np.where(lcfsp, -1.0 / (p * mu**2), d_mu))


def waterfill(grad, lo, hi, group, n_groups, outer: int = 64,
              inner: int = 64) -> np.ndarray:
    """Minimise ``sum f_n(x_n)`` subject to ``sum_{n in g} x_n <= 1`` for
    every group g and ``lo_n < x_n <= hi_n``, each ``f_n`` convex with
    derivative ``grad(x)`` (elementwise, increasing) tending to minus
    infinity at ``lo_n``. KKT: ``x_n = hi_n`` where ``grad(hi_n) + nu_g <=
    0``, else ``grad(x_n) = -nu_g``; the group's price ``nu_g >= 0`` fills
    its budget, or is 0 where the budget is slack. Bisection in float64 on
    ``log nu`` (outer) and ``log x`` (inner). A group whose floors ``lo``
    alone exceed its budget has no solution and reads NaN."""
    lo = np.asarray(lo, np.float64)
    hi = np.asarray(hi, np.float64)
    group = np.asarray(group)

    def alloc(nu_n):
        a, b = np.log(lo), np.log(hi)
        with np.errstate(invalid="ignore", over="ignore"):
            top = grad(hi) + nu_n <= 0.0
            for _ in range(inner):
                mid = 0.5 * (a + b)
                up = grad(np.exp(mid)) + nu_n < 0.0
                a, b = np.where(up, mid, a), np.where(up, b, mid)
        return np.where(top, hi, np.exp(0.5 * (a + b)))

    def fill(nu):
        return np.bincount(group, alloc(nu[group]),
                           minlength=n_groups) - 1.0

    slack = fill(np.zeros(n_groups)) <= 0.0
    a = np.full(n_groups, -60.0)
    b = np.full(n_groups, 60.0)
    for _ in range(outer):
        mid = 0.5 * (a + b)
        over = fill(np.exp(mid)) > 0.0
        a, b = np.where(over, mid, a), np.where(over, b, mid)
    nu = np.where(slack, 0.0, np.exp(0.5 * (a + b)))
    infeasible = np.bincount(group, lo, minlength=n_groups) > 1.0
    x = alloc(nu[group])
    return np.where(infeasible[group], np.nan, x)


def _rel_gap(got, want) -> float:
    """Largest ``|got - want| / |want|``; equal infinities count as 0."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    same = (got == want)
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(got - want) / np.maximum(np.abs(want), 1e-300)
    gap = np.where(same, 0.0, gap)
    gap = np.where(np.isnan(gap), np.inf, gap)
    return float(gap.max()) if gap.size else 0.0


def planner_gaps(plan: dict, tables: dict, q0: float, p_min: float,
                 v: float) -> dict:
    """Numbers that compare one plan window with the reference.

    ``plan`` holds host arrays of the planned window: ``r_idx``,
    ``m_idx``, ``pol``, ``b``, ``c``, ``aopi``, ``assign`` (each
    ``[K, N]``) and ``q`` (``[K]``, the queue after each slot). ``tables``
    is the float64 truth of the same K slots; ``q0`` the queue the plan
    started from; ``p_min`` and ``v`` the accuracy floor and the Lyapunov
    weight. Besides the two numbers below, the window's
    :func:`solver_gaps`.

    * ``plan_aopi_gap``: worst relative gap between a camera's planned
      AoPI and the closed form at its planned rates and true accuracy.
    * ``queue_gap``: worst absolute gap between the planned queue and
      Eq. 44 applied to the previous slot's queue and the true mean
      accuracy of the planned configurations.
    """
    acc, xi, size, eff = (tables[k] for k in ("acc", "xi", "size", "eff"))
    r = np.asarray(plan["r_idx"])
    m = np.asarray(plan["m_idx"])
    n_slots, n = r.shape
    cams = np.arange(n)[None, :]
    slots = np.arange(n_slots)[:, None]
    b = np.asarray(plan["b"], np.float64)
    c = np.asarray(plan["c"], np.float64)
    p = acc[slots, cams, m, r]
    lam = b * eff[None, :] / size[r]
    mu = c / xi[m, r]
    aopi = closed_form_aopi(lam, mu, p, plan["pol"])
    q_prev = np.concatenate([[q0], np.asarray(plan["q"], np.float64)[:-1]])
    q_ref = np.maximum(q_prev - p.mean(axis=1) + p_min, 0.0)
    return {"plan_aopi_gap": _rel_gap(plan["aopi"], aopi),
            "queue_gap": float(np.max(np.abs(
                np.asarray(plan["q"], np.float64) - q_ref))),
            **solver_gaps(plan, tables, q_prev, v)}


def solver_gaps(plan: dict, tables: dict, q_prev, v: float) -> dict:
    """Numbers that hold each planned slot to Algorithm 1's own steps at
    the plan's server assignment ``assign`` (``[K, N]``), in float64.

    * ``budget_gap``: problems (53) and (54) each fill their server's
      budget: the most any server's planned compute use departs from its
      budget, or its bandwidth use exceeds it, or falls short of it where
      an LCFSP camera (whose AoPI falls with every hertz) could take the
      rest, as a share of the budget.
    * ``c_gap``: line 5, problem (54): the median camera's ``|c - c*| /
      c*``, ``c*`` minimising the server's summed AoPI over compute at the
      planned configurations and bandwidth, within the compute the plan
      gives that server; FCFS keeps ``mu >= 1.05 lam`` (constraint (10)
      with the allocator's stated margin). The median, not the worst: on
      a few servers the program's water-fill stops short of float32
      precision, by as much as a bfloat16 plan departs everywhere.
    * ``b_gap``: line 4, problem (53), for the LCFSP cameras, whose
      bandwidth optimum does not depend on compute: worst
      ``|b - b*| / b*`` within the bandwidth the plan gives them.
    * ``config_miss``: line 3: the share of cameras whose planned model,
      resolution and policy is not the least drift-plus-penalty ``V *
      AoPI - q * p`` of the whole grid at the planned b and c (relative
      tolerance 1e-5).
    """
    acc, xi, size, eff = (np.asarray(tables[k], np.float64)
                          for k in ("acc", "xi", "size", "eff"))
    r = np.asarray(plan["r_idx"])
    m = np.asarray(plan["m_idx"])
    pol = np.asarray(plan["pol"])
    assign = np.asarray(plan["assign"])
    k_slots, n = r.shape
    n_srv = tables["budgets_b"].shape[1]
    slots = np.arange(k_slots)[:, None]
    cams = np.arange(n)[None, :]
    b = np.asarray(plan["b"], np.float64)
    c = np.asarray(plan["c"], np.float64)
    group = (slots * n_srv + assign).ravel()
    n_groups = k_slots * n_srv
    lcfsp = pol.ravel() == LCFSP
    fcfs = ~lcfsp

    def per_server(x):
        return np.bincount(group, x.ravel(), minlength=n_groups)

    use_b = per_server(b) / np.asarray(tables["budgets_b"]).ravel() - 1.0
    use_c = per_server(c) / np.asarray(tables["budgets_c"]).ravel() - 1.0
    binds_b = per_server(lcfsp) > 0
    budget_gap = max(np.abs(use_c).max(),
                     np.where(binds_b, np.abs(use_b), use_b).max())

    p = acc[slots, cams, m, r].ravel()
    k = (eff[None, :] / size[r]).ravel()         # frames/s per Hz
    inv_xi = (1.0 / xi[m, r]).ravel()            # frames/s per FLOPS
    pf, polf = p, pol.ravel()
    lam, mu = k * b.ravel(), inv_xi * c.ravel()
    tiny = 1e-12
    with np.errstate(divide="ignore", invalid="ignore"):
        # Line 5 at the planned bandwidth, in units of the server's
        # planned compute.
        mu_full = inv_xi * per_server(c)[group]
        lo_c = np.where(fcfs, STABILITY_MARGIN * lam / mu_full, tiny)
        c_ref = waterfill(
            lambda u: mu_full * aopi_partials(lam, mu_full * u, pf,
                                              polf)[1],
            lo_c, np.ones_like(lo_c), group, n_groups)
        c_gap = np.abs(c.ravel() / per_server(c)[group] - c_ref) / c_ref
        # Line 4 for the LCFSP cameras, in units of the bandwidth the plan
        # gives them on their server.
        if lcfsp.any():
            g, x = group[lcfsp], b.ravel()[lcfsp]
            have = np.bincount(g, x, minlength=n_groups)[g]
            lam_full = k[lcfsp] * have
            b_ref = waterfill(
                lambda u: lam_full * aopi_partials(
                    lam_full * u, mu[lcfsp], pf[lcfsp], polf[lcfsp])[0],
                np.full_like(x, tiny), np.ones_like(x), g, n_groups)
            b_gap = np.abs(x / have - b_ref) / b_ref
        else:
            b_gap = np.zeros(0)

    # Line 3: the whole (model, resolution, policy) grid at the planned
    # b and c, against the planned choice.
    q = np.asarray(q_prev, np.float64)[:, None, None, None, None]
    lam_g = (b[..., None] * eff[None, :, None] / size)[:, :, None, :, None]
    mu_g = (c[..., None, None] / xi)[..., None]
    p_g = np.maximum(acc, 1e-3)[..., None]
    score = (v * closed_form_aopi(lam_g, mu_g, p_g, np.array([FCFS, LCFSP]))
             - q * p_g)
    best = score.reshape(k_slots, n, -1).min(axis=-1)
    chosen = score[slots, cams, m, r, pol]
    scale = v * closed_form_aopi(lam, mu, p, polf).reshape(k_slots, n)
    with np.errstate(invalid="ignore"):
        miss = ~(chosen - best <= 1e-5 * scale)
    return {"budget_gap": float(budget_gap), "b_gap": _worst(b_gap),
            "c_gap": float(np.median(np.where(np.isnan(c_gap), np.inf,
                                              c_gap))),
            "config_miss": float(miss.mean())}


def _worst(gap) -> float:
    """Largest entry; a NaN (no answer, or no reference) counts as
    infinite."""
    gap = np.asarray(gap, np.float64)
    return float(np.where(np.isnan(gap), np.inf, gap).max(initial=0.0))


def frames_budget(max_lam: float, horizon: float, frames_cap: int,
                  frames_floor: int = 200) -> int:
    """Frames simulated per lane: the fastest lane's expected arrivals
    over the epoch plus a 2-sigma margin, rounded up to a quarter power
    of two and capped (the data plane's documented budget)."""
    need = float(max_lam) * float(horizon)
    need = max(need + 2.0 * np.sqrt(max(need, 1.0)) + 16.0,
               float(frames_floor), 2.0)
    p2 = 2.0 ** np.floor(np.log2(need))
    for mult in (1.0, 1.25, 1.5, 1.75, 2.0):
        if p2 * mult >= need:
            return int(min(np.ceil(p2 * mult), frames_cap))
    raise AssertionError("unreachable")


def lane_uniforms(seed: int, epochs, streams, n_frames: int) -> np.ndarray:
    """``[L, 3, F]`` float64 uniforms of the lanes ``(epochs[j],
    streams[j])``: transmission, service and accuracy-coin variates under
    ``fold_in(fold_in(key(seed), epoch), stream)``, drawn on the host."""
    import jax
    import jax.numpy as jnp
    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True), jax.default_device(cpu):
        def draw(t, i):
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.key(int(seed)), t), i)
            return jax.random.uniform(key, (3, n_frames), jnp.float64)
        u = jax.jit(jax.vmap(draw))(jnp.asarray(epochs, jnp.int64),
                                    jnp.asarray(streams, jnp.int64))
        return np.asarray(u)


def simulate_lanes(u: np.ndarray, lam, mu, p, pol, horizon: float) -> dict:
    """Frame-by-frame GI/G/1 age of processed information, one lane per
    row of ``u`` (``[L, 3, F]`` uniforms), exponential delays.

    Frame f is uploaded back to back (upload time T_f), so it is
    generated when frame f-1 finishes uploading and arrives at the server
    at a_f = T_0 + ... + T_f. FCFS serves in order: finish_f =
    max(a_f, finish_{f-1}) + O_f. LCFSP preempts: frame f completes at
    a_f + O_f only if that comes before the next arrival. A completion
    inside the epoch is accurate with probability p; each accurate one
    resets the age to finish_f minus the frame's generation time. The age
    starts at 0 and the AoPI is its time average over the effective
    horizon min(epoch, sum of T), as are the counts. A lane whose
    upload or service rate is not positive carries no stream and reads 0.
    """
    live = (np.asarray(lam) > 0) & (np.asarray(mu) > 0)
    lam = np.maximum(np.asarray(lam, np.float64), 1e-6)
    mu = np.maximum(np.asarray(mu, np.float64), 1e-6)
    p = np.clip(np.asarray(p, np.float64), 1e-3, 1.0)
    lcfsp = np.asarray(pol) == LCFSP
    u = np.asarray(u, np.float64)
    t_up = -np.log1p(-u[:, 0]) * (1.0 / lam)[:, None]           # [L, F]
    t_srv = -np.log1p(-u[:, 1]) * (1.0 / mu)[:, None]
    coin = u[:, 2]
    lanes, n_frames = t_up.shape
    h_eff = np.minimum(float(horizon), t_up.sum(axis=1))
    zero = np.zeros(lanes)
    arrival, prev_finish = zero.copy(), zero.copy()
    last, age0, area = zero.copy(), zero.copy(), zero.copy()
    n_arr = np.zeros(lanes, np.int64)
    n_done = np.zeros(lanes, np.int64)
    for f in range(n_frames):
        gen = arrival
        arrival = arrival + t_up[:, f]
        fcfs_finish = np.maximum(arrival, prev_finish) + t_srv[:, f]
        prev_finish = np.where(lcfsp, prev_finish, fcfs_finish)
        finish = np.where(lcfsp, arrival + t_srv[:, f], fcfs_finish)
        nxt = t_up[:, f + 1] if f + 1 < n_frames else np.inf
        done = np.where(lcfsp, t_srv[:, f] < nxt, True) & (finish <= h_eff)
        valid = done & (coin[:, f] < p)
        seg = np.where(valid, finish - last, zero)
        area = area + age0 * seg + 0.5 * seg * seg
        last = np.where(valid, finish, last)
        age0 = np.where(valid, finish - gen, age0)
        n_arr += arrival <= h_eff
        n_done += done
    seg = np.maximum(h_eff - last, zero)
    area = area + age0 * seg + 0.5 * seg * seg
    # A lane with no upload or no compute share has no stream: it reads 0.
    return {k: np.where(live, v, 0) for k, v in (
        ("aopi", area / h_eff), ("n_frames", n_arr),
        ("n_completed", n_done))}


def data_plane_gaps(measured: dict, reference: dict) -> dict:
    """``measured_aopi_gap``: worst relative gap of a lane's measured AoPI;
    ``count_gap``: worst absolute gap in frames arrived or completed."""
    counts = max(
        float(np.max(np.abs(np.asarray(measured[k], np.float64)
                            - reference[k]), initial=0.0))
        for k in ("n_frames", "n_completed"))
    return {"measured_aopi_gap": _rel_gap(measured["aopi"],
                                          reference["aopi"]),
            "count_gap": counts}
