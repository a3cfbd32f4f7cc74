"""The benchmark's own copy of the edge deployment's horizon generator.

A copy, not an import, of the arithmetic of ``repro.core.profiles``
(``EdgeSystem.__post_init__`` and ``EdgeSystem.horizon``, with the paper's
model pool and the lognormal AR(1) capacity traces), so that a change to
the program's generator cannot move the yardstick. ``tests/bench/
test_traffic_copy.py`` pins that this copy reproduces the program's
generator exactly at a fixed seed.

Everything here is float64 numpy on the host. ``build`` returns the
deployment's truth tables; the harness casts them to the dtype the
planner is served in and hands the program only the resulting
``HorizonTables``.
"""
from __future__ import annotations

import numpy as np

#: The paper's §VI-A candidate pool (name, params in millions, GFLOPs per
#: frame at 640p, asymptotic accuracy, resolution knee), as in
#: ``profiles.paper_pool``.
PAPER_POOL = (
    ("yolov5n", 1.9, 4.5, 0.62, 190.0),
    ("yolov5s", 7.2, 16.5, 0.72, 200.0),
    ("yolov5m", 21.2, 49.0, 0.80, 210.0),
    ("yolov5l", 46.5, 109.1, 0.85, 220.0),
    ("yolov5x", 86.7, 205.7, 0.88, 230.0),
    ("fpn", 23.0, 90.0, 0.82, 215.0),
    ("unet", 31.0, 120.0, 0.84, 220.0),
    ("yolact", 34.7, 61.6, 0.78, 210.0),
    ("mask_rcnn", 44.2, 134.0, 0.86, 225.0),
)
RESOLUTIONS = (384, 512, 640, 768, 896, 1024)
ALPHA_BITS_PER_PIXEL = 1.2
REF_RESOLUTION = 640


def _ar1_scan(u: np.ndarray, rho: float) -> np.ndarray:
    """x[t] = rho * x[t-1] + u[t] by stride doubling (the program's
    vectorized form, so the traces agree bit for bit)."""
    coef = np.full(u.shape, rho, dtype=np.float64)
    out = np.asarray(u, np.float64).copy()
    d = 1
    while d < u.shape[0]:
        out[d:] = out[d:] + coef[d:] * out[:-d]
        coef[d:] = coef[d:] * coef[:-d]
        d *= 2
    return out


def _capacity_trace(rng, mean: float, shape, rho: float = 0.85,
                    sigma: float = 0.25) -> np.ndarray:
    e = rng.normal(0.0, sigma, shape)
    u = np.concatenate([e[:1], np.sqrt(1 - rho**2) * e[1:]], axis=0)
    return mean * np.exp(_ar1_scan(u, rho) - 0.5 * sigma**2)


def _drift_path(seed: int, n_slots: int, n_cameras: int) -> np.ndarray:
    """Per-camera content drift: clipped AR(1) in [0.75, 1]."""
    noise = np.random.default_rng(seed).normal(0.0, 0.03,
                                               (n_slots, n_cameras))
    state = np.ones(n_cameras)
    out = np.empty((n_slots, n_cameras))
    for t in range(n_slots):
        state = np.clip(0.9 * state + 0.1 + noise[t], 0.75, 1.0)
        out[t] = state
    return out


def build(n_cameras: int, n_servers: int, n_slots: int,
          bandwidth_hz: float, compute_flops: float, seed: int) -> dict:
    """The horizon ``EdgeSystem(n_cameras, n_servers, n_slots,
    bandwidth_hz, compute_flops, seed=seed).horizon(n_slots)`` would
    build, as float64 numpy: ``acc[T, N, M, R]``, ``xi[M, R]``,
    ``size[R]``, ``eff[N]``, ``budgets_b[T, S]``, ``budgets_c[T, S]``."""
    rng = np.random.default_rng(seed)
    snr_db = rng.uniform(12.0, 22.0, size=n_cameras)
    difficulty = rng.uniform(0.88, 1.0, size=n_cameras)
    budgets_b = _capacity_trace(rng, bandwidth_hz, (n_slots, n_servers))
    budgets_c = _capacity_trace(rng, compute_flops, (n_slots, n_servers))
    res = np.asarray(RESOLUTIONS, np.float64)
    zeta = np.stack([np.clip(p_max * (1.0 - np.exp(-res / knee)), 1e-3, 1.0)
                     for _, _, _, p_max, knee in PAPER_POOL])      # [M, R]
    xi = np.stack([g * 1e9 * (res / REF_RESOLUTION) ** 2
                   for _, _, g, _, _ in PAPER_POOL])               # [M, R]
    drift = _drift_path(seed + 1, n_slots, n_cameras)              # [T, N]
    acc = np.clip((difficulty[None, :] * drift)[:, :, None, None]
                  * zeta[None, None], 1e-3, 1.0)
    return {"acc": acc, "xi": xi, "size": ALPHA_BITS_PER_PIXEL * res**2,
            "eff": np.log2(1.0 + 10.0 ** (snr_db / 10.0)),
            "budgets_b": budgets_b, "budgets_c": budgets_c}


def permute_cameras(tables: dict, perm: np.ndarray) -> dict:
    """The same deployment with its cameras listed in another order."""
    return {**tables, "acc": tables["acc"][:, perm], "eff": tables["eff"][perm]}


def window(tables: dict, t0: int, t1: int) -> dict:
    """Slots ``[t0, t1)`` of a horizon."""
    return {**tables, "acc": tables["acc"][t0:t1],
            "budgets_b": tables["budgets_b"][t0:t1],
            "budgets_c": tables["budgets_c"][t0:t1]}
