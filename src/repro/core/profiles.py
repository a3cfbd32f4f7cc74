"""Accuracy / complexity / workload profiles (paper §III + §VI-A).

Two consumption granularities are provided:

  * ``EdgeSystem.tables(t)``   — one slot's profiles as host numpy arrays
    (the legacy per-slot path used by ``LBCDController.step``);
  * ``EdgeSystem.horizon(T)``  — a whole-horizon ``HorizonTables`` pytree
    (acc ``[T, N, M, R]``, capacity traces ``[T, S]``) built once on host
    and moved to device once, consumed by the ``lax.scan`` rollout engine
    (``repro.core.lbcd.rollout``) with zero per-slot host round trips.

Provides the substrate the controller consumes each slot:
  * zeta(r, m)  — concave, monotone-increasing recognition-accuracy profile
                  per (resolution, model), with per-slot content drift
                  (the paper profiles zeta at the start of every slot);
  * xi(r, m)    — convex FLOPs-per-frame profile, proportional to model size;
  * frame size  — alpha * r^2 bits (H.264-style, Eq. before Eq. 2);
  * Shannon-rate link model (Eq. 1) and linear pod-link model;
  * bandwidth / compute capacity traces shaped like the Ghent LTE and
    Bitbrains datacenter traces used in §VI-A (lognormal AR(1) modulation).

The candidate pool is ``paper_pool()``: the paper's own ladder (YOLOv5n..x,
FPN, U-Net, YOLACT, Mask R-CNN) with public FLOPs/params numbers.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

RESOLUTIONS = (384, 512, 640, 768, 896, 1024)
ALPHA_BITS_PER_PIXEL = 1.2          # frame size = alpha * r^2 bits
REF_RESOLUTION = 640


@dataclasses.dataclass(frozen=True)
class ModelCandidate:
    """One selectable recognition model (the paper's m in M)."""
    name: str
    params_m: float          # millions of parameters
    gflops_ref: float        # GFLOPs per frame at REF_RESOLUTION
    p_max: float             # asymptotic accuracy at infinite resolution
    r_knee: float            # resolution scale of the accuracy saturation
    task: str = "detection"

    def xi(self, r: np.ndarray) -> np.ndarray:
        """FLOPs per frame — convex (quadratic) in resolution, proportional
        to model cost (§III-B)."""
        return self.gflops_ref * 1e9 * (np.asarray(r, np.float64) /
                                        REF_RESOLUTION) ** 2

    def zeta(self, r: np.ndarray, drift: float = 1.0) -> np.ndarray:
        """Accuracy — concave, monotone increasing in r, scaled by a content
        drift factor in (0, 1]."""
        r = np.asarray(r, np.float64)
        base = self.p_max * (1.0 - np.exp(-r / self.r_knee))
        return np.clip(base * drift, 1e-3, 1.0)


def paper_pool() -> list[ModelCandidate]:
    """The paper's §VI-A candidates; FLOPs/params from the public model zoo
    (YOLOv5 release table @640, torchvision/paper numbers for the rest).
    The ladder spans ~50x compute, matching §III-B."""
    return [
        ModelCandidate("yolov5n", 1.9, 4.5, 0.62, 190.0),
        ModelCandidate("yolov5s", 7.2, 16.5, 0.72, 200.0),
        ModelCandidate("yolov5m", 21.2, 49.0, 0.80, 210.0),
        ModelCandidate("yolov5l", 46.5, 109.1, 0.85, 220.0),
        ModelCandidate("yolov5x", 86.7, 205.7, 0.88, 230.0),
        ModelCandidate("fpn", 23.0, 90.0, 0.82, 215.0, task="segmentation"),
        ModelCandidate("unet", 31.0, 120.0, 0.84, 220.0, task="segmentation"),
        ModelCandidate("yolact", 34.7, 61.6, 0.78, 210.0, task="instance"),
        ModelCandidate("mask_rcnn", 44.2, 134.0, 0.86, 225.0, task="instance"),
    ]


# ---------------------------------------------------------------------------
# Link models
# ---------------------------------------------------------------------------

def shannon_efficiency(snr_db: np.ndarray) -> np.ndarray:
    """bits/s/Hz from Eq. (1): log2(1 + E*G/sigma)."""
    return np.log2(1.0 + 10.0 ** (np.asarray(snr_db, np.float64) / 10.0))


# ---------------------------------------------------------------------------
# Pure-functional trace machinery (shared with repro.scenarios generators)
# ---------------------------------------------------------------------------

def ar1_scan(u: np.ndarray, rho: float) -> np.ndarray:
    """Vectorized linear recursion x[t] = rho * x[t-1] + u[t], x[-1] = 0.

    Associative prefix scan with stride doubling — O(T log T) numpy work
    instead of a T-step python loop. The recursion composes as affine maps
    (A, B): x_out = A * x_in + B. Matches the sequential recursion up to
    float64 reassociation error (~1e-15 relative), not bitwise.
    """
    t_len = u.shape[0]
    coef = np.full(u.shape, rho, dtype=np.float64)
    out = np.asarray(u, np.float64).copy()
    d = 1
    while d < t_len:
        out[d:] = out[d:] + coef[d:] * out[:-d]
        coef[d:] = coef[d:] * coef[:-d]
        d *= 2
    return out


def lognormal_ar1_trace(rng: np.random.Generator, mean: float,
                        shape: tuple[int, int], rho: float = 0.85,
                        sigma: float = 0.25) -> np.ndarray:
    """Lognormal AR(1) capacity trace (Ghent LTE / Bitbrains shape).

    Pure in ``(rng state, mean, shape, rho, sigma)``; draws all noise in one
    call (same stream as the historical per-slot loop) and runs the AR(1)
    recursion via the vectorized ``ar1_scan`` (values match the loop to
    ~1e-15 relative, not bitwise).
    """
    e = rng.normal(0.0, sigma, shape)
    u = np.concatenate([e[:1], np.sqrt(1 - rho**2) * e[1:]], axis=0)
    x = ar1_scan(u, rho)
    return mean * np.exp(x - 0.5 * sigma**2)


def drift_path(seed: int, n_slots: int, n_cameras: int,
               rho: float = 0.9, pull: float = 0.1, sigma: float = 0.03,
               lo: float = 0.75, hi: float = 1.0,
               init: np.ndarray | None = None) -> np.ndarray:
    """Per-camera clipped-AR(1) content-drift path ``[T, N]``.

    Pure in ``(seed, n_slots, n_cameras, ...)`` — the functional twin of
    ``EdgeSystem.advance_drift`` (the clip makes the recursion nonlinear, so
    this one keeps the short T loop over a pre-drawn noise matrix).
    Matches what ``n_slots`` sequential ``advance_drift()`` calls on a fresh
    ``EdgeSystem(seed=seed - 1)`` would return.
    """
    rng = np.random.default_rng(seed)
    noise = rng.normal(0.0, sigma, (n_slots, n_cameras))
    state = np.ones(n_cameras) if init is None else np.asarray(init, float)
    out = np.empty((n_slots, n_cameras))
    for t in range(n_slots):
        state = np.clip(rho * state + pull * 1.0 + noise[t], lo, hi)
        out[t] = state
    return out


@dataclasses.dataclass
class SlotTables:
    """Everything the per-slot optimizer needs, as dense arrays.

    Shapes: N cameras, M models, R resolutions.
      acc[n, m, r]   accuracy zeta_n^t
      xi[m, r]       FLOPs per frame
      size[r]        bits per frame
      eff[n]         link spectral efficiency (bits/s/Hz); lam = b*eff/size
    """
    acc: np.ndarray
    xi: np.ndarray
    size: np.ndarray
    eff: np.ndarray

    @property
    def n_cameras(self) -> int:
        return self.acc.shape[0]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HorizonTables:
    """Whole-horizon profiles + capacity traces as one device-resident pytree.

    Built once on host (``EdgeSystem.horizon``) and consumed by the
    ``lax.scan`` rollout engine; vmappable over a leading batch axis (e.g. a
    stack of scenarios with identical shapes).

    Shapes: T slots, N cameras, M models, R resolutions, S servers.
      acc[t, n, m, r]   profiled accuracy zeta_n^t (drift applied per slot)
      xi[m, r]          FLOPs per frame
      size[r]           bits per frame
      eff[n]            link spectral efficiency (bits/s/Hz); scenario
                        generators with camera mobility emit a time-varying
                        eff[t, n] instead — every scan engine accepts both
      budgets_b[t, s]   bandwidth capacity trace B_t^s (Hz)
      budgets_c[t, s]   compute capacity trace C_t^s (FLOPS)
      active[t, n]      optional fleet-churn mask (1.0 = camera live).
                        ``None`` (the default) means "all cameras live
                        for the whole horizon" and adds **no pytree
                        leaf**, so every maskless program traces to the
                        same jaxpr as before the field existed — the
                        bitwise ``faults=None`` no-op path.
    """
    acc: jnp.ndarray
    xi: jnp.ndarray
    size: jnp.ndarray
    eff: jnp.ndarray
    budgets_b: jnp.ndarray
    budgets_c: jnp.ndarray
    active: jnp.ndarray | None = None

    @property
    def n_slots(self) -> int:
        return self.acc.shape[-4]

    @property
    def n_cameras(self) -> int:
        return self.acc.shape[-3]

    @property
    def n_servers(self) -> int:
        return self.budgets_b.shape[-1]

    def slot(self, t: int) -> SlotTables:
        """One slot's profiles as host numpy (legacy SlotTables view)."""
        eff = self.eff if self.eff.ndim == 1 else self.eff[t]
        return SlotTables(acc=np.asarray(self.acc[t]),
                          xi=np.asarray(self.xi),
                          size=np.asarray(self.size),
                          eff=np.asarray(eff))

    def window(self, t0: int, t1: int) -> "HorizonTables":
        """Slots ``[t0, t1)`` of an (unbatched) horizon as a new
        ``HorizonTables`` — the serving planner's lookahead view. Static
        profile tables (``xi``/``size``) pass through; time-indexed leaves
        are sliced (``eff`` only when it is the time-varying ``[T, N]``
        form)."""
        if not 0 <= t0 < t1 <= self.n_slots:
            raise ValueError(f"window [{t0}, {t1}) outside horizon of "
                             f"{self.n_slots} slots")
        return HorizonTables(
            acc=self.acc[t0:t1], xi=self.xi, size=self.size,
            eff=self.eff if self.eff.ndim == 1 else self.eff[t0:t1],
            budgets_b=self.budgets_b[t0:t1],
            budgets_c=self.budgets_c[t0:t1],
            active=None if self.active is None else self.active[t0:t1])


def eff_sequence(tables: HorizonTables) -> jnp.ndarray:
    """The per-slot link-efficiency sequence ``[T, N]`` of an (unbatched)
    horizon — broadcasts a static ``eff[n]`` across slots, passes a
    time-varying ``eff[t, n]`` through. The scan engines feed this as a
    scanned input so SNR-mobility scenarios ride the same rollout."""
    n_slots = tables.acc.shape[0]
    if tables.eff.ndim == 1:
        return jnp.broadcast_to(tables.eff[None, :],
                                (n_slots, tables.eff.shape[0]))
    return tables.eff


def stack_horizons(tables: Sequence[HorizonTables]) -> HorizonTables:
    """Stack same-shape horizons along a new leading axis for vmapped /
    sharded rollouts (e.g. one scenario per entry of a suite).

    Raises ``ValueError`` naming the offending field and shapes when the
    horizons disagree (all leaves must match exactly — including whether
    ``eff`` is static ``[N]`` or time-varying ``[T, N]``)."""
    tables = list(tables)
    if not tables:
        raise ValueError("stack_horizons: need at least one horizon")
    # Mixed churn masks: densify the maskless horizons to all-ones so the
    # stacked pytree has a uniform structure. All-None stays None (the
    # maskless fast path is preserved for unperturbed suites).
    if any(t.active is not None for t in tables):
        tables = [
            t if t.active is not None else dataclasses.replace(
                t, active=jnp.ones((t.n_slots, t.n_cameras), t.acc.dtype))
            for t in tables]
    ref = tables[0]
    for i, tab in enumerate(tables[1:], start=1):
        for field in dataclasses.fields(HorizonTables):
            a = getattr(ref, field.name)
            b = getattr(tab, field.name)
            if a is None and b is None:
                continue
            if a.shape != b.shape:
                raise ValueError(
                    f"stack_horizons: shape mismatch on field "
                    f"{field.name!r}: horizons[0] has {a.shape}, "
                    f"horizons[{i}] has {b.shape} — all stacked horizons "
                    f"must share (T, N, M, R, S) and eff rank")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *tables)


@dataclasses.dataclass
class EdgeSystem:
    """Scenario container: cameras, servers, traces, profiles (§VI-A)."""
    n_cameras: int = 30
    n_servers: int = 3
    n_slots: int = 200
    mean_bandwidth_hz: float = 30e6          # per server
    mean_compute_flops: float = 50e12        # per server
    pool: Sequence[ModelCandidate] = dataclasses.field(
        default_factory=paper_pool)
    resolutions: Sequence[int] = RESOLUTIONS
    alpha: float = ALPHA_BITS_PER_PIXEL
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        # Camera SNRs: 12..22 dB (spectral efficiency ~4..7.3 bits/s/Hz).
        self.snr_db = rng.uniform(12.0, 22.0, size=self.n_cameras)
        # Per-camera content difficulty baseline + AR(1) drift (Cityscapes
        # profiling analog: accuracy functions vary per camera and per slot).
        self._difficulty = rng.uniform(0.88, 1.0, size=self.n_cameras)
        self._drift_state = np.ones(self.n_cameras)
        self._drift_rng = np.random.default_rng(self.seed + 1)
        self.bandwidth_trace = self._trace(
            rng, self.mean_bandwidth_hz, (self.n_slots, self.n_servers))
        self.compute_trace = self._trace(
            rng, self.mean_compute_flops, (self.n_slots, self.n_servers))

    @staticmethod
    def _trace(rng: np.random.Generator, mean: float,
               shape: tuple[int, int], rho: float = 0.85,
               sigma: float = 0.25) -> np.ndarray:
        """Lognormal AR(1) capacity trace — vectorized ``ar1_scan`` path
        (same noise stream + values as the historical per-slot loop, so long
        horizons T >= 10k are no longer host-loop bound)."""
        return lognormal_ar1_trace(rng, mean, shape, rho=rho, sigma=sigma)

    def reset(self) -> "EdgeSystem":
        """Restore the stateful drift RNG/state to the post-construction
        point, so the legacy per-slot ``tables(t)`` path replays the exact
        sequence a fresh system would produce."""
        self._drift_state = np.ones(self.n_cameras)
        self._drift_rng = np.random.default_rng(self.seed + 1)
        return self

    def advance_drift(self) -> np.ndarray:
        """One AR(1) step of per-camera content drift in [0.75, 1.0]."""
        noise = self._drift_rng.normal(0.0, 0.03, self.n_cameras)
        self._drift_state = np.clip(
            0.9 * self._drift_state + 0.1 * 1.0 + noise, 0.75, 1.0)
        return self._drift_state

    def tables(self, t: int, drift: np.ndarray | None = None) -> SlotTables:
        """Profile zeta/xi for slot t (Algorithm 3 line 3)."""
        if drift is None:
            drift = self.advance_drift()
        res = np.asarray(self.resolutions, np.float64)
        m_count = len(self.pool)
        acc = np.zeros((self.n_cameras, m_count, len(res)))
        xi = np.zeros((m_count, len(res)))
        for j, m in enumerate(self.pool):
            xi[j] = m.xi(res)
            zr = m.zeta(res)
            acc[:, j, :] = (self._difficulty * drift)[:, None] * zr[None, :]
        size = self.alpha * res**2
        eff = shannon_efficiency(self.snr_db)
        return SlotTables(acc=np.clip(acc, 1e-3, 1.0), xi=xi, size=size,
                          eff=eff)

    def capacities(self, t: int) -> tuple[np.ndarray, np.ndarray]:
        t = t % self.n_slots
        return self.bandwidth_trace[t], self.compute_trace[t]

    def horizon(self, n_slots: int | None = None,
                dtype=jnp.float32) -> HorizonTables:
        """Pregenerate ``n_slots`` of profiles + capacities as one pytree.

        Deterministic in ``(self.seed, n_slots)``: the drift path is
        computed by the pure ``drift_path`` without touching the stateful
        per-slot RNG, so two ``horizon()`` calls on the same system are
        bitwise identical, and a scan rollout reproduces what ``n_slots``
        sequential ``step(t)`` calls on a *fresh* system would have
        observed.
        """
        n_slots = self.n_slots if n_slots is None else n_slots
        drift = drift_path(self.seed + 1, n_slots, self.n_cameras)  # [T, N]
        res = np.asarray(self.resolutions, np.float64)
        zr = np.stack([m.zeta(res) for m in self.pool])        # [M, R]
        xi = np.stack([m.xi(res) for m in self.pool])          # [M, R]
        acc = (self._difficulty[None, :] * drift)[:, :, None, None] * \
            zr[None, None, :, :]                               # [T, N, M, R]
        acc = np.clip(acc, 1e-3, 1.0)
        size = self.alpha * res**2
        eff = shannon_efficiency(self.snr_db)
        idx = np.arange(n_slots) % self.n_slots
        return HorizonTables(
            acc=jnp.asarray(acc, dtype),
            xi=jnp.asarray(xi, dtype),
            size=jnp.asarray(size, dtype),
            eff=jnp.asarray(eff, dtype),
            budgets_b=jnp.asarray(self.bandwidth_trace[idx], dtype),
            budgets_c=jnp.asarray(self.compute_trace[idx], dtype))
