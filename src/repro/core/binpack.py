"""Algorithm 2 — edge-server selection as 2D first-fit bin packing.

Given the *virtual-server* resource demands (Algorithm 2 lines 1-2), cameras
are sized by Eq. (56), servers by Eq. (57), both sorted descending, and each
camera goes to the first server with enough remaining bandwidth AND compute;
if none fits, to the server with most remaining volume (lines 4-9).

Three implementations, semantically equivalent (asserted in tests):

  * ``first_fit``     — host-side numpy reference; O(N S) with tiny
    constants, used by the legacy per-slot controller path;
  * ``first_fit_jax`` — jit-safe (sort + ``fori_loop``) variant traced
    inside the ``lax.scan`` rollout engine so whole-horizon runs never
    leave the device;
  * ``first_fit_jax(..., backend="pallas")`` — the same sorts, with the
    placement loop run as one Pallas kernel
    (``kernels.slot_solver.first_fit``) that keeps the remaining
    capacities in vector registers; the rollout takes it where the slot
    solver resolves to pallas (``bcd.resolve_spec``). Placements equal
    the ``fori_loop``'s exactly: same f32 operations, same tie-breaks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import slot_solver


def first_fit(b_hat: np.ndarray, c_hat: np.ndarray, budgets_b: np.ndarray,
              budgets_c: np.ndarray) -> np.ndarray:
    """Assign cameras to servers. Returns int[N] server ids.

    Args:
      b_hat, c_hat: ideal (virtual-server) per-camera demands, Alg. 2 line 2.
      budgets_b, budgets_c: per-server capacities B_t^s, C_t^s.
    """
    b_hat = np.asarray(b_hat, np.float64)
    c_hat = np.asarray(c_hat, np.float64)
    budgets_b = np.asarray(budgets_b, np.float64)
    budgets_c = np.asarray(budgets_c, np.float64)
    tot_b, tot_c = budgets_b.sum(), budgets_c.sum()

    phi = b_hat / tot_b + c_hat / tot_c                  # Eq. (56)
    psi = budgets_b / tot_b + budgets_c / tot_c          # Eq. (57)

    cam_order = np.argsort(-phi)                         # largest first
    srv_order = np.argsort(-psi)
    rem_b = budgets_b.copy()
    rem_c = budgets_c.copy()
    assign = np.zeros(b_hat.shape[0], np.int32)

    for n in cam_order:
        placed = False
        for s in srv_order:
            if rem_b[s] >= b_hat[n] and rem_c[s] >= c_hat[n]:
                assign[n] = s
                rem_b[s] -= b_hat[n]
                rem_c[s] -= c_hat[n]
                placed = True
                break
        if not placed:                                    # lines 6-8
            rem_vol = rem_b / tot_b + rem_c / tot_c
            s = int(np.argmax(rem_vol))
            assign[n] = s
            rem_b[s] = max(rem_b[s] - b_hat[n], 0.0)
            rem_c[s] = max(rem_c[s] - c_hat[n], 0.0)
    return assign


def first_fit_jax(b_hat: jnp.ndarray, c_hat: jnp.ndarray,
                  budgets_b: jnp.ndarray, budgets_c: jnp.ndarray,
                  backend: str = "jnp",
                  interpret: bool | None = None) -> jnp.ndarray:
    """Jit-safe Algorithm 2 placement, equivalent to ``first_fit``.

    Cameras/servers are sorted by the Eq. (56)/(57) volumes, then a
    ``fori_loop`` places one camera per iteration (vectorized over servers).
    ``backend="pallas"`` runs that loop as one kernel
    (``slot_solver.first_fit``; ``interpret`` as for the other slot-solver
    kernels) and returns the same assignment. Traceable under
    jit/vmap/scan; returns int32[N] server ids.
    """
    b_hat = jnp.asarray(b_hat)
    c_hat = jnp.asarray(c_hat)
    budgets_b = jnp.asarray(budgets_b)
    budgets_c = jnp.asarray(budgets_c)
    tot_b = budgets_b.sum()
    tot_c = budgets_c.sum()

    phi = b_hat / tot_b + c_hat / tot_c                  # Eq. (56)
    psi = budgets_b / tot_b + budgets_c / tot_c          # Eq. (57)
    cam_order = jnp.argsort(-phi)                        # largest first
    srv_order = jnp.argsort(-psi)
    if backend == "pallas":
        pos = slot_solver.first_fit(
            b_hat[cam_order], c_hat[cam_order], budgets_b[srv_order],
            budgets_c[srv_order], srv_order, tot_b, tot_c,
            interpret=interpret)
        return jnp.zeros(b_hat.shape[0], jnp.int32).at[cam_order].set(
            srv_order[pos].astype(jnp.int32))
    if backend != "jnp":
        raise ValueError(f"unknown first-fit backend {backend!r};"
                         " known: ('jnp', 'pallas')")

    def body(i, state):
        rem_b, rem_c, assign = state
        n = cam_order[i]
        bn, cn = b_hat[n], c_hat[n]
        fits = (rem_b[srv_order] >= bn) & (rem_c[srv_order] >= cn)
        s_fit = srv_order[jnp.argmax(fits)]              # first fit in order
        rem_vol = rem_b / tot_b + rem_c / tot_c          # lines 6-8
        s = jnp.where(fits.any(), s_fit, jnp.argmax(rem_vol))
        rem_b = jnp.maximum(rem_b.at[s].add(-bn), 0.0)
        rem_c = jnp.maximum(rem_c.at[s].add(-cn), 0.0)
        return rem_b, rem_c, assign.at[n].set(s.astype(jnp.int32))

    assign0 = jnp.zeros(b_hat.shape[0], jnp.int32)
    _, _, assign = jax.lax.fori_loop(
        0, b_hat.shape[0], body, (budgets_b, budgets_c, assign0))
    return assign


def hierarchical_first_fit(b_hat, c_hat, pod_budgets_b, pod_budgets_c,
                           islands_per_pod: int) -> np.ndarray:
    """Multi-pod variant (beyond paper, §Scale-out): first-fit over pods,
    then over islands inside the chosen pod. Island capacity = pod capacity /
    islands_per_pod. Returns global island ids ``pod * islands_per_pod + i``.
    """
    pod_budgets_b = np.asarray(pod_budgets_b, np.float64)
    pod_budgets_c = np.asarray(pod_budgets_c, np.float64)
    pods = first_fit(b_hat, c_hat, pod_budgets_b, pod_budgets_c)
    out = np.zeros_like(pods)
    for pod in range(pod_budgets_b.shape[0]):
        mask = pods == pod
        if not mask.any():
            continue
        ib = np.full(islands_per_pod, pod_budgets_b[pod] / islands_per_pod)
        ic = np.full(islands_per_pod, pod_budgets_c[pod] / islands_per_pod)
        local = first_fit(b_hat[mask], c_hat[mask], ib, ic)
        out[mask] = pod * islands_per_pod + local
    return out
