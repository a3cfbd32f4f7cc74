"""Algorithm 1 — block coordinate descent over the one-slot problem (P2).

Three blocks, iterated M times (paper §V-B):

  line 3: video configuration (r, x, m)  — vectorized exhaustive search over
          the (model x resolution x policy) grid, per camera;
  line 4: bandwidth allocation b         — convex, via water-filling or the
          paper's interior-point method (repro.core.allocate);
  line 5: computation allocation c       — same.

Everything is jit-compiled with static (N, M, R, S); the whole solve runs in
a few hundred microseconds for N=30 on CPU (benchmarks/bench_overhead.py).

Both per-camera blocks have two implementations behind
``solver_backend="jnp" | "pallas"``: the pure-jnp reference and the fused
``repro.kernels.slot_solver`` kernels (streaming config argmin, one-dispatch
on-chip water-filling) — float32-tolerance equivalent, benchmarked in
``benchmarks/bench_slot_solver.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from . import allocate, aopi
from .. import obs
from ..kernels import slot_solver

# Fleet size from which ``solver_backend="auto"`` plans with the pallas
# kernels, everywhere the flag goes, including the grid/scenario vmap
# paths; below one 128-lane tile the kernels pad every camera vector up to
# 128 lanes. Measured on one TPU v5e (``plan_horizon(8)`` back to back,
# every leaf copied to the host; p95 of a plan): at 30 cameras on 3
# servers jnp 23.7-24.5 ms, pallas 16.0 ms; at 816 cameras on 125 servers
# pallas 95.2-100.3 ms, jnp 129.9 ms. The pallas path is the faster at
# both sizes there; this value, set from CPU-interpreter timings, has yet
# to be re-derived on the chip.
AUTO_PALLAS_MIN_CAMERAS = 128

# Fleet size at which "auto" switches the water-fills to the camera-tiled
# streaming kernel (default tile below): past this the single-program
# kernel's [S, Np] membership matrix + whole-fleet vectors start crowding
# VMEM, while one [2, 8, tile] double-buffered window always fits. The
# threshold sits where the streaming kernel measurably wins (~1.3x at
# 32k cameras in interpret mode, ~2x at 100k); below it the whole-fleet
# kernel is faster because it pays no per-sweep DMA machinery.
AUTO_TILE_MIN_CAMERAS = 32768
DEFAULT_TILE_N = 16384

SOLVER_BACKENDS = ("jnp", "pallas", "auto")


@dataclasses.dataclass(frozen=True)
class SolverSpec:
    """Parsed ``solver_backend`` spec: backend plus tiling/fusion knobs."""
    backend: str              # "jnp" | "pallas" | "auto" (pre-resolution)
    tile_n: int | None = None  # water-fill camera tile (None = untiled)
    fuse: bool = True          # one fused kernel for both water-fills

    def __str__(self) -> str:
        """The spec string :func:`parse_backend` reads back."""
        return ":".join([self.backend]
                        + ([] if self.tile_n is None
                           else [f"tile={self.tile_n}"])
                        + ([] if self.fuse else ["nofuse"]))


def parse_backend(solver_backend) -> SolverSpec:
    """Parse a ``solver_backend`` string into a :class:`SolverSpec`.

    Grammar: ``<backend>[:<knob>]*`` with knobs ``tile=<int>`` (camera
    tile for the streaming water-fill; ``tile=0`` pins the untiled
    single-program kernel even at auto-tile fleet sizes), ``fuse`` /
    ``nofuse`` (one vs two water-fill dispatches per BCD pass). Examples:
    ``"pallas"``, ``"auto"``, ``"pallas:tile=4096"``,
    ``"pallas:nofuse"``, ``"auto:tile=2048:nofuse"``.
    """
    if isinstance(solver_backend, SolverSpec):
        return solver_backend
    parts = str(solver_backend).split(":")
    if parts[0] not in SOLVER_BACKENDS:
        raise ValueError(f"unknown solver_backend {parts[0]!r}; "
                         f"known: {SOLVER_BACKENDS}")
    tile_n = None
    fuse = True
    for tok in parts[1:]:
        if tok == "fuse":
            fuse = True
        elif tok == "nofuse":
            fuse = False
        elif tok.startswith("tile="):
            tile_n = int(tok[len("tile="):])
        else:
            raise ValueError(f"unknown solver_backend knob {tok!r} in "
                             f"{solver_backend!r}; known: tile=<int>, "
                             "fuse, nofuse")
    return SolverSpec(parts[0], tile_n, fuse)


def resolve_spec(solver_backend, n_cameras: int,
                 method: str = "waterfill",
                 masked: bool = False) -> SolverSpec:
    """Resolve a spec (or spec string) to concrete knobs for a fleet size.

    ``"auto"`` picks jnp below :data:`AUTO_PALLAS_MIN_CAMERAS`
    (lane-padding regime) and pallas at or above it, and — unless the
    spec pins ``tile=``— engages the tiled water-fill with
    :data:`DEFAULT_TILE_N` from :data:`AUTO_TILE_MIN_CAMERAS` cameras.
    ``method="interior"`` and churn-masked solves (``masked=True``; the
    pallas kernels take no mask) are jnp-only, so auto never selects
    pallas for them. Explicit backends pass through unchanged (including
    the pallas+interior and pallas+mask error paths in ``solve_slot``). ``tile=0`` resolves
    to untiled, and so does any tile the whole fleet fits inside
    (``n_cameras <= tile_n``) — streaming a single tile would just be
    the whole-fleet kernel plus DMA overhead, and dropping the tile
    keeps the fused two-water-fill dispatch available. The resolved
    spec never carries backend ``"auto"``.
    """
    spec = parse_backend(solver_backend)
    backend = spec.backend
    if backend == "auto":
        if (method != "waterfill" or masked
                or n_cameras < AUTO_PALLAS_MIN_CAMERAS):
            backend = "jnp"
        else:
            backend = "pallas"
    tile_n = spec.tile_n
    if backend == "pallas":
        if tile_n is None and n_cameras >= AUTO_TILE_MIN_CAMERAS:
            tile_n = DEFAULT_TILE_N
        if tile_n == 0 or (tile_n is not None and n_cameras <= tile_n):
            tile_n = None
    else:
        tile_n = None
    return SolverSpec(backend, tile_n, spec.fuse)


def resolve_backend(solver_backend, n_cameras: int,
                    method: str = "waterfill") -> str:
    """Backend name only (see :func:`resolve_spec` for the full knobs)."""
    return resolve_spec(solver_backend, n_cameras, method=method).backend


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SlotDecision:
    """Output of one Algorithm-1 solve (all per-camera arrays)."""
    r_idx: jnp.ndarray        # resolution index into tables.size
    m_idx: jnp.ndarray        # model index
    pol: jnp.ndarray          # 0 FCFS / 1 LCFSP
    b: jnp.ndarray            # Hz
    c: jnp.ndarray            # FLOPS
    lam: jnp.ndarray          # frames/s
    mu: jnp.ndarray           # frames/s
    acc: jnp.ndarray          # recognition accuracy p_{n,t}
    aopi: jnp.ndarray         # closed-form per-camera AoPI
    score: jnp.ndarray        # scalar drift-plus-penalty value

    def as_numpy(self) -> "SlotDecision":
        return SlotDecision(*(np.asarray(v) for v in dataclasses.astuple(self)))


def _rates(b, c, r_idx, m_idx, eff, size, xi):
    lam = b * eff / size[r_idx]                       # Eqs. (1)-(2)
    mu = c / xi[m_idx, r_idx]                         # Eq. (3)
    return lam, mu


def solve_slot(acc, xi, size, eff, server_id, budgets_b, budgets_c, q, V,
               n_servers: int, n_iters: int = 4,
               method: Literal["waterfill", "interior"] = "waterfill",
               solver_effort: Literal["fast", "seed"] = "fast",
               solver_backend: str = "jnp",
               interpret: bool | None = None, active=None):
    """Run Algorithm 1 and return a SlotDecision (of jnp arrays).

    Args:
      acc:  [N, M, R] profiled accuracy zeta_n^t(r, m).
      xi:   [M, R]    FLOPs per frame.
      size: [R]       bits per frame.
      eff:  [N]       link spectral efficiency (bits/s/Hz).
      server_id: [N]  camera -> server assignment (Algorithm 2's output).
      budgets_b/_c: [n_servers] available Hz / FLOPS.
      q, V: Lyapunov queue value and penalty weight.
      solver_effort: "fast" (default) uses cheap water-filling effort inside
        the BCD loop plus one full-precision re-allocation; "seed"
        reproduces the pre-refactor flat high-iteration effort (kept for
        benchmarks measuring what the rollout-stack rework bought).
      solver_backend: "jnp" (default) runs the pure-jnp config search and
        water-filling; "pallas" fuses both into the
        ``repro.kernels.slot_solver`` kernels (streaming config argmin, by
        default one fused water-fill dispatch per BCD pass); "auto" picks
        per fleet size via :func:`resolve_spec` (jnp below
        ``AUTO_PALLAS_MIN_CAMERAS``, pallas at/above, camera-tiled
        streaming water-fills from ``AUTO_TILE_MIN_CAMERAS``). Knobs ride
        the string — ``"pallas:tile=4096"``, ``"pallas:nofuse"`` (see
        :func:`parse_backend`). Pallas requires ``method="waterfill"``;
        agrees with "jnp" to float32 tolerance.
      interpret: pallas interpret-mode override (None = auto: interpret
        everywhere except on real TPUs — the CPU/CI path).
      active: optional [N] fleet-churn mask (1 = live). Inactive cameras
        get exactly zero bandwidth/compute (their budget share
        redistributes to survivors) and are excluded from the drift-plus-
        penalty means. The masked path runs on the jnp backend: the
        pallas kernels take no mask, so ``"auto"`` resolves to jnp and an
        explicit ``"pallas"`` raises ``ValueError``; ``active=None``
        traces the identical program as before the parameter existed.
    """
    kwargs = dict(n_servers=n_servers, n_iters=n_iters, method=method,
                  solver_effort=solver_effort,
                  solver_backend=solver_backend, interpret=interpret)
    args = (acc, xi, size, eff, server_id, budgets_b, budgets_c, q, V)
    if active is not None:
        kwargs["active"] = active
    if obs.enabled():
        # Per-backend dispatch accounting: concrete (host) calls get a
        # timed span — dispatch through materialization of nothing, i.e.
        # host-side submit latency of the jitted program; traced calls
        # (inside rollout scans / vmaps) bump a per-backend trace counter
        # instead (wall time inside a trace measures tracing, not the
        # solver).
        spec = resolve_spec(solver_backend, acc.shape[0], method=method,
                            masked=active is not None)
        backend = (spec.backend if spec.tile_n is None
                   else f"{spec.backend}:tiled")
        operands = args if active is None else args + (active,)
        if any(isinstance(a, jax.core.Tracer) for a in operands):
            obs.counter("bcd.solve_slot.traces",
                        solver_backend=backend).inc()
        else:
            with obs.span("bcd.solve_slot", solver_backend=backend,
                          n_cameras=int(acc.shape[0])):
                return _solve_slot(*args, **kwargs)
    return _solve_slot(*args, **kwargs)


@functools.partial(jax.jit,
                   static_argnames=("n_servers", "n_iters", "method",
                                    "solver_effort", "solver_backend",
                                    "interpret"))
def _solve_slot(acc, xi, size, eff, server_id, budgets_b, budgets_c, q, V,
                n_servers: int, n_iters: int = 4,
                method: Literal["waterfill", "interior"] = "waterfill",
                solver_effort: Literal["fast", "seed"] = "fast",
                solver_backend: str = "jnp",
                interpret: bool | None = None, active=None):
    spec = resolve_spec(solver_backend, acc.shape[0], method=method,
                        masked=active is not None)
    if active is not None:
        if method == "interior":
            raise ValueError("method='interior' does not support a fleet-"
                             "churn mask; use method='waterfill'")
        if spec.backend == "pallas":
            raise ValueError("solver_backend='pallas' takes no fleet-churn "
                             "mask; use 'auto' (resolves to jnp for masked "
                             "solves) or 'jnp'")
    use_pallas = spec.backend == "pallas"
    if use_pallas and method != "waterfill":
        raise ValueError("solver_backend='pallas' fuses the water-filling "
                         "solver; method='interior' only supports the jnp "
                         "backend")
    n = acc.shape[0]
    if active is not None:
        act = (active > 0).astype(acc.dtype)
        eff = eff * act           # lam = 0 for churned-out cameras
        counts = jax.ops.segment_sum(act, server_id,
                                     num_segments=n_servers)
        share = act * (1.0 / jnp.maximum(counts, 1.0))[server_id]
    else:
        act = None
        counts = jax.ops.segment_sum(jnp.ones((n,)), server_id,
                                     num_segments=n_servers)
        share = (1.0 / jnp.maximum(counts, 1.0))[server_id]
    b = budgets_b[server_id] * share
    c = budgets_c[server_id] * share

    if use_pallas:
        # One static layout per solve: the (possibly traced) assignment is
        # sorted/padded into per-server rows the kernel programs own.
        layout = slot_solver.server_layout(server_id, n_servers)
        config = functools.partial(slot_solver.config_argmin,
                                   backend="pallas", interpret=interpret)
        # The fused pair kernel holds the whole fleet in one program; the
        # camera-tiled water-fills stream it in two (bandwidth, compute).
        if spec.fuse and spec.tile_n is None:
            def make_pair(kw):
                def pair(k, p, pol, mu, inv_xi):
                    return slot_solver.waterfill_pair(
                        k, p, pol, mu, inv_xi, server_id, budgets_b,
                        budgets_c, n_servers, layout=layout,
                        interpret=interpret, **kw)
                return pair
        else:
            def make_pair(kw):
                def pair(k, p, pol, mu, inv_xi):
                    b = slot_solver.waterfill_bandwidth(
                        k, p, pol, mu, server_id, budgets_b, n_servers,
                        layout=layout, tile_n=spec.tile_n,
                        interpret=interpret, **kw)
                    c = slot_solver.waterfill_compute(
                        inv_xi, p, pol, b * k, server_id, budgets_c,
                        n_servers, layout=layout, tile_n=spec.tile_n,
                        interpret=interpret, **kw)
                    return b, c
                return pair
    else:
        config = functools.partial(slot_solver.config_argmin, backend="jnp")

        def make_pair(kw):
            def pair(k, p, pol, mu, inv_xi):
                b = allocate.waterfill_bandwidth(
                    k, p, pol, mu, server_id, budgets_b, n_servers,
                    active=act, **kw)
                c = allocate.waterfill_compute(
                    inv_xi, p, pol, b * k, server_id, budgets_c,
                    n_servers, active=act, **kw)
                return b, c
            return pair

    polish = method == "waterfill" and solver_effort == "fast"
    if polish:
        # Cheap solver effort inside the BCD loop (it only has to steer the
        # discrete config selection); one accurate re-allocation afterwards.
        pair_loop = make_pair(dict(outer_iters=10, inner_iters=3,
                                   final_inner_iters=5))
        pair_full = make_pair({})
    elif method == "waterfill":
        # Pre-refactor effort: flat high-iteration water-filling each pass.
        pair_loop = make_pair(dict(outer_iters=54, inner_iters=40,
                                   final_inner_iters=40))
    else:
        def pair_loop(k, p, pol, mu, inv_xi):
            b = allocate.interior_point_bandwidth(
                k, p, pol, mu, server_id, budgets_b, n_servers)
            c = allocate.interior_point_compute(
                inv_xi, p, pol, b * k, server_id, budgets_c, n_servers)
            return b, c

    def body(_, state):
        b, c, r_idx, m_idx, pol = state
        r_idx, m_idx, pol = config(b, c, acc, xi, size, eff, q, V, n)
        p = acc[jnp.arange(n), m_idx, r_idx]
        # lines 4-5: bandwidth given (r, x, m, c), then compute given the
        # fresh arrival rate lam = b * k.
        k = eff / size[r_idx]
        mu = c / xi[m_idx, r_idx]
        b, c = pair_loop(k, p, pol, mu, 1.0 / xi[m_idx, r_idx])
        return b, c, r_idx, m_idx, pol

    z = jnp.zeros((n,), jnp.int32)
    b, c, r_idx, m_idx, pol = jax.lax.fori_loop(
        0, n_iters, body, (b, c, z, z, z))

    if polish:
        # Lines 4-5 once more at full precision for the final configuration.
        p = acc[jnp.arange(n), m_idx, r_idx]
        k = eff / size[r_idx]
        mu = c / xi[m_idx, r_idx]
        b, c = pair_full(k, p, pol, mu, 1.0 / xi[m_idx, r_idx])

    lam, mu = _rates(b, c, r_idx, m_idx, eff, size, xi)
    p = acc[jnp.arange(n), m_idx, r_idx]
    if act is not None:
        # Masked evaluation: dead cameras contribute exactly 0 to every
        # per-camera array and the means run over the live count only.
        a = aopi.aopi_masked(lam, mu, p, pol, active=act)
        p = p * act
        n_live = jnp.maximum(jnp.sum(act), 1.0)
        score = -q * jnp.sum(p) / n_live + V * jnp.sum(a) / n_live
    else:
        a = aopi.aopi(lam, mu, p, pol)
        score = -q * jnp.mean(p) + V * jnp.mean(a)
    return SlotDecision(r_idx, m_idx, pol, b, c, lam, mu, p, a, score)


def solve_slot_np(tables, server_id, budgets_b, budgets_c, q, V,
                  n_servers, **kw) -> SlotDecision:
    """Convenience wrapper taking a profiles.SlotTables, returning numpy."""
    dec = solve_slot(jnp.asarray(tables.acc, jnp.float32),
                     jnp.asarray(tables.xi, jnp.float32),
                     jnp.asarray(tables.size, jnp.float32),
                     jnp.asarray(tables.eff, jnp.float32),
                     jnp.asarray(server_id, jnp.int32),
                     jnp.asarray(budgets_b, jnp.float32),
                     jnp.asarray(budgets_c, jnp.float32),
                     jnp.float32(q), jnp.float32(V),
                     n_servers=int(n_servers), **kw)
    return dec.as_numpy()
