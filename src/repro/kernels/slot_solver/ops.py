"""Dispatch layer for the fused slot solver: jnp | pallas | interpret.

``ServerLayout`` is the static-shape bridge between the per-camera arrays
Algorithm 1 works with and the sorted per-server blocks the water-filling
kernel owns: cameras are stably sorted by ``server_id`` into contiguous
per-server blocks (plus a ``[S, C]`` row view, ``C`` = per-server
capacity, default N so overflow is impossible) with sentinel-padded
gather tables, so building it is jit-safe even when the assignment is a
traced value (first-fit output inside the rollout scan). ``gather_flat``
is the kernel's single HBM read per operand; ``scatter_flat`` its single
write back to camera order; ``member()`` the static membership matrix the
kernel reduces over per server.

``waterfill_bandwidth`` / ``waterfill_compute`` mirror the signatures of
``repro.core.allocate.waterfill_*`` so ``bcd.solve_slot`` can swap the
backend behind one flag; ``config_argmin`` dispatches Algorithm 1 line 3
between the reference (materialized ``[N, M, R, 2]``) and the streaming
kernel. ``interpret=None`` auto-selects interpret mode off-TPU, which is
the CPU/CI path.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ... import obs
from ...core import aopi
from . import kernel, ref

_EPS = 1e-12
_LANE = 128          # pad per-server rows to the TPU lane width


def _resolve_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ServerLayout:
    """Cameras stably sorted/padded into per-server blocks (static shapes).

    Two views of the same permutation:

      * ``order[s, j]`` — the original index of the j-th camera assigned to
        server s (ascending original order — stable sort), or the sentinel
        ``n_cameras`` on padding slots; ``mask`` is 1.0 on real slots and
        ``counts[s]`` the number of cameras on server s (overflow beyond
        the capacity is dropped — impossible at the default capacity of N).
      * ``flat_order[j]`` — the same cameras as one lane-padded ``[Np]``
        vector of contiguous per-server blocks (``flat_sid`` holding each
        slot's server, ``n_servers`` on padding). ``member`` derives the
        ``[S, Np]`` 0/1 membership matrix the water-filling kernel uses
        for its on-chip per-server reductions.
    """
    order: jnp.ndarray        # [S, C]  int32
    mask: jnp.ndarray         # [S, C]  float32
    counts: jnp.ndarray       # [S]     int32
    flat_order: jnp.ndarray   # [Np]    int32
    flat_sid: jnp.ndarray     # [Np]    int32
    flat_mask: jnp.ndarray    # [Np]    float32

    @property
    def n_servers(self) -> int:
        return self.order.shape[0]

    @property
    def capacity(self) -> int:
        return self.order.shape[1]

    def gather(self, x, fill=0.0):
        """Per-camera ``[N]`` -> per-server rows ``[S, C]`` (one read)."""
        padded = jnp.concatenate(
            [x, jnp.asarray([fill], x.dtype)])
        return padded[self.order]

    def scatter(self, rows, n_cameras: int):
        """Per-server rows ``[S, C]`` -> per-camera ``[N]`` (one write)."""
        vals = (rows * self.mask.astype(rows.dtype)).reshape(-1)
        return jnp.zeros((n_cameras + 1,), rows.dtype).at[
            self.order.reshape(-1)].set(vals)[:n_cameras]

    def gather_flat(self, x, fill=0.0):
        """Per-camera ``[N]`` -> sorted flat ``[Np]`` (one read)."""
        padded = jnp.concatenate([x, jnp.asarray([fill], x.dtype)])
        return padded[self.flat_order]

    def scatter_flat(self, vec, n_cameras: int):
        """Sorted flat ``[Np]`` -> per-camera ``[N]`` (one write)."""
        vals = vec * self.flat_mask.astype(vec.dtype)
        return jnp.zeros((n_cameras + 1,), vec.dtype).at[
            self.flat_order].set(vals)[:n_cameras]

    def member(self):
        """``[S, Np]`` 0/1 server-membership matrix (padding: all-zero)."""
        servers = jnp.arange(self.n_servers, dtype=self.flat_sid.dtype)
        return (self.flat_sid[None, :] == servers[:, None]).astype(
            jnp.float32)


def server_layout(server_id, n_servers: int,
                  capacity: int | None = None) -> ServerLayout:
    """Build a :class:`ServerLayout` from a (possibly traced) assignment.

    ``capacity`` bounds the per-server ``order`` rows only (the flat view
    always holds every camera) and is rounded up to the 128-lane width, so
    values <= 128 are equivalent; a server holding more cameras than the
    rounded capacity silently drops the overflow from its row — only pass
    a sub-N capacity with a known assignment bound. The default (N) makes
    overflow impossible.
    """
    n = server_id.shape[0]
    cap = n if capacity is None else int(capacity)
    cap = max(_LANE, -(-cap // _LANE) * _LANE)
    n_pad = max(_LANE, -(-n // _LANE) * _LANE)
    sort_idx = jnp.argsort(server_id, stable=True).astype(jnp.int32)
    sid_sorted = server_id[sort_idx].astype(jnp.int32)
    counts = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), server_id,
                                 num_segments=n_servers)
    start = jnp.cumsum(counts) - counts
    pos = jnp.arange(n) - start[sid_sorted]
    order = jnp.full((n_servers, cap), n, jnp.int32).at[
        sid_sorted, pos].set(sort_idx, mode="drop")
    mask = (order < n).astype(jnp.float32)
    flat_order = jnp.concatenate(
        [sort_idx, jnp.full((n_pad - n,), n, jnp.int32)])
    flat_sid = jnp.concatenate(
        [sid_sorted, jnp.full((n_pad - n,), n_servers, jnp.int32)])
    return ServerLayout(order=order, mask=mask, counts=counts,
                        flat_order=flat_order, flat_sid=flat_sid,
                        flat_mask=(flat_order < n).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Config selection (Algorithm 1 line 3)
# ---------------------------------------------------------------------------

#: Cameras per grid step of the config scans: the ``[M*R, 32, 128]`` f32
#: accuracy block is 864 KiB at the paper's 9 x 6 grid, double-buffered
#: well inside v5e's 16 MiB scoped VMEM at every fleet size.
CONFIG_BLOCK_N = 4096


def _scan_layout(n: int, block_n: int) -> tuple[int, int]:
    """``(rows, block_rows)`` of the camera-on-lanes scan layout: ``rows``
    128-camera rows, padded to a whole number of grid blocks."""
    rows = -(-n // kernel.LANES)
    block_rows = kernel.block_rows_for(rows, block_n)
    return -(-rows // block_rows) * block_rows, block_rows


def _camera_lanes(x, rows: int):
    """``[N]`` -> ``[rows, 128]`` (padding cameras get a benign 1.0)."""
    x = jnp.asarray(x, jnp.float32)
    pad = rows * kernel.LANES - x.shape[0]
    return jnp.pad(x, (0, pad), constant_values=1.0).reshape(
        rows, kernel.LANES)


def _acc_planes(acc, rows: int):
    """``[N, M, R]`` -> ``[M*R, rows, 128]``: one camera-on-lanes plane per
    (model, resolution) config, flat in (m, r) order."""
    n = acc.shape[0]
    flat = jnp.asarray(acc, jnp.float32).reshape(n, -1).T
    return jnp.pad(flat, ((0, 0), (0, rows * kernel.LANES - n)),
                   constant_values=1.0).reshape(-1, rows, kernel.LANES)


def _scan_operands(b, c, acc, eff, block_n: int):
    rows, block_rows = _scan_layout(acc.shape[0], block_n)
    return ((_camera_lanes(b, rows), _camera_lanes(c, rows),
             _acc_planes(acc, rows), _camera_lanes(eff, rows)), block_rows)


def config_argmin(b, c, acc, xi, size, eff, q, v, n_total: int,
                  backend: str = "jnp", interpret: bool | None = None,
                  block_n: int = CONFIG_BLOCK_N):
    """Per-camera (r_idx, m_idx, pol) minimizing the drift-plus-penalty
    score over the (model x resolution x policy) grid."""
    if backend == "jnp":
        return ref.config_argmin_ref(b, c, acc, xi, size, eff, q, v, n_total)
    if backend != "pallas":
        raise ValueError(f"unknown solver backend {backend!r};"
                         " known: ('jnp', 'pallas')")
    obs.count_dispatch("config_argmin")
    n = acc.shape[0]
    (bl, cl, al, el), block_rows = _scan_operands(b, c, acc, eff, block_n)
    out = kernel.config_argmin(bl, cl, al, xi, size, el, q, v,
                               n_total=n_total, block_rows=block_rows,
                               interpret=_resolve_interpret(interpret))
    out = out.reshape(3, -1)[:, :n]
    return out[0], out[1], out[2]


def baseline_argmax(b, c, acc, xi, size, eff, *, mode: str, threshold,
                    backend: str = "jnp", interpret: bool | None = None,
                    block_n: int = CONFIG_BLOCK_N):
    """Streaming DOS/JCAB config scan; returns per-camera ``(m_idx, r_idx)``.

    The jnp backend materializes the ``[N, M, R]`` latency/score tensors
    (:func:`ref.baseline_argmax_ref`); the pallas backend streams camera
    tiles through :func:`kernel.baseline_argmax` so they never exist.
    Indices are bitwise identical between the two.
    """
    if backend == "jnp":
        return ref.baseline_argmax_ref(b, c, acc, xi, size, eff, mode=mode,
                                       threshold=threshold)
    if backend != "pallas":
        raise ValueError(f"unknown solver backend {backend!r};"
                         " known: ('jnp', 'pallas')")
    obs.count_dispatch("baseline_argmax", mode=str(mode))
    n = acc.shape[0]
    (bl, cl, al, el), block_rows = _scan_operands(b, c, acc, eff, block_n)
    out = kernel.baseline_argmax(bl, cl, al, xi, size, el, mode=mode,
                                 threshold=threshold, block_rows=block_rows,
                                 interpret=_resolve_interpret(interpret))
    out = out.reshape(2, -1)[:, :n]
    return out[0], out[1]


# ---------------------------------------------------------------------------
# Water-filling (Algorithm 1 lines 4/5)
# ---------------------------------------------------------------------------

def _round_tile(tile_n: int) -> int:
    return max(_LANE, -(-int(tile_n) // _LANE) * _LANE)


def _pack_tiled(layout, scale, p, pol, other, lo, hi, cf, tile: int):
    """Gather the water-fill vectors into the packed ``[8, Np]`` block the
    tiled kernel streams (``kernel.TILE_FIELDS`` row order), padding the
    lane-padded layout width up to a multiple of ``tile``."""
    cap = layout.flat_order.shape[0]
    np_to = -(-cap // tile) * tile
    is_l = (layout.gather_flat(pol, fill=jnp.int32(aopi.LCFSP))
            == aopi.LCFSP).astype(jnp.float32)
    rows = [
        (layout.gather_flat(scale, fill=1.0), 1.0),
        (layout.gather_flat(p, fill=0.5), 0.5),
        (is_l, 1.0),
        (layout.gather_flat(other, fill=1.0), 1.0),
        (layout.gather_flat(lo, fill=1e-9), 1e-9),
        (layout.gather_flat(hi, fill=1e-9), 1e-9),
        (layout.gather_flat(cf, fill=1.0), 1.0),
        (layout.flat_sid.astype(jnp.float32), float(layout.n_servers)),
    ]
    pad = np_to - cap
    return jnp.stack([
        jnp.concatenate([v.astype(jnp.float32),
                         jnp.full((pad,), fill, jnp.float32)])
        if pad else v.astype(jnp.float32) for v, fill in rows])


def _run_waterfill(layout, scale, p, pol, other, lo, hi, cf, mode,
                   outer_iters, inner_iters, final_inner_iters, interpret,
                   tile_n=None):
    n = scale.shape[0]
    cap = layout.flat_order.shape[0]
    tile = None if tile_n is None else _round_tile(tile_n)
    if tile is not None and cap > tile:
        obs.count_dispatch("waterfill_tiled", mode=str(mode))
        block = _pack_tiled(layout, scale, p, pol, other, lo, hi, cf, tile)
        vec = kernel.waterfill_tiled(
            block, mode=mode, n_servers=layout.n_servers, tile=tile,
            outer_iters=outer_iters, inner_iters=inner_iters,
            final_inner_iters=final_inner_iters,
            interpret=_resolve_interpret(interpret))
        return layout.scatter_flat(vec[:cap], n)
    obs.count_dispatch("waterfill", mode=str(mode))
    vec = kernel.waterfill(
        layout.gather_flat(scale, fill=1.0),
        layout.gather_flat(p, fill=0.5),
        layout.gather_flat(pol, fill=jnp.int32(aopi.LCFSP)),
        layout.gather_flat(other, fill=1.0),
        layout.gather_flat(lo, fill=1e-9),
        layout.gather_flat(hi, fill=1e-9),
        layout.gather_flat(cf, fill=1.0),
        layout.member(), mode=mode, outer_iters=outer_iters,
        inner_iters=inner_iters, final_inner_iters=final_inner_iters,
        interpret=_resolve_interpret(interpret))
    return layout.scatter_flat(vec, n)


def waterfill_bandwidth(k, p, pol, mu, server_id, budgets, n_servers: int,
                        outer_iters: int = 16, inner_iters: int = 6,
                        final_inner_iters: int = 20, *,
                        layout: ServerLayout | None = None,
                        tile_n: int | None = None,
                        interpret: bool | None = None):
    """Fused twin of ``allocate.waterfill_bandwidth`` (same signature plus
    an optional precomputed layout); returns b[n] in Hz. ``tile_n``
    switches to the camera-tiled streaming kernel when the padded fleet
    exceeds one tile (rounded up to the 128-lane width)."""
    if layout is None:
        layout = server_layout(server_id, n_servers)
    B = budgets[server_id]
    lam_scale = k * B
    lam_star = aopi.argmin_lam_fcfs(mu, p)
    hi = jnp.where(pol == aopi.LCFSP, 1.0,
                   jnp.minimum(lam_star / jnp.maximum(lam_scale, _EPS), 1.0))
    lo = jnp.full_like(hi, 1e-9)
    cf = 1.0 + 1.0 / p       # LCFSP closed form: u = sqrt(cf / (scale * nu))
    u = _run_waterfill(layout, lam_scale, p, pol, mu, lo, hi, cf,
                       "bandwidth", outer_iters, inner_iters,
                       final_inner_iters, interpret, tile_n=tile_n)
    return u * B


def waterfill_compute(inv_xi, p, pol, lam, server_id, budgets,
                      n_servers: int, stability_margin: float = 1.05,
                      outer_iters: int = 16, inner_iters: int = 6,
                      final_inner_iters: int = 20, *,
                      layout: ServerLayout | None = None,
                      tile_n: int | None = None,
                      interpret: bool | None = None):
    """Fused twin of ``allocate.waterfill_compute``; returns c[n] in FLOPS."""
    if layout is None:
        layout = server_layout(server_id, n_servers)
    C = budgets[server_id]
    mu_scale = inv_xi * C
    floor = jnp.where(pol == aopi.FCFS,
                      stability_margin * lam / jnp.maximum(mu_scale, _EPS),
                      1e-9)
    # Best effort if FCFS floors alone exceed a server's budget. This runs
    # in plain XLA outside the kernel, so the O(N) segment_sum (identical
    # to the jnp twin's) beats a dense membership reduction.
    floor_tot = jax.ops.segment_sum(floor, server_id,
                                    num_segments=layout.n_servers)
    scale_fac = jnp.minimum(1.0, 1.0 / jnp.maximum(floor_tot, _EPS))
    lo = jnp.clip(floor * scale_fac[server_id], 1e-9, 1.0)
    hi = jnp.ones_like(lo)
    cf = 1.0 / p             # LCFSP closed form: v = sqrt(cf / (scale * nu))
    v = _run_waterfill(layout, mu_scale, p, pol, lam, lo, hi, cf,
                       "compute", outer_iters, inner_iters,
                       final_inner_iters, interpret, tile_n=tile_n)
    return v * C


def waterfill_pair(k, p, pol, mu, inv_xi, server_id, budgets_b, budgets_c,
                   n_servers: int, stability_margin: float = 1.05,
                   outer_iters: int = 16, inner_iters: int = 6,
                   final_inner_iters: int = 20, *,
                   layout: ServerLayout | None = None,
                   interpret: bool | None = None):
    """Both BCD water-fills (Algorithm 1 lines 4+5) in one kernel dispatch.

    Equivalent (to float32 tolerance) to ``waterfill_bandwidth`` followed
    by ``waterfill_compute`` at ``lam = b * k``: the FCFS floors and the
    intermediate arrival rate are derived on-chip from the in-register
    bandwidth result, so only the packed inputs and the two allocation
    vectors cross HBM. Returns ``(b, c)`` in Hz / FLOPS.
    """
    if layout is None:
        layout = server_layout(server_id, n_servers)
    n = k.shape[0]
    B = budgets_b[server_id]
    C = budgets_c[server_id]
    lam_scale = k * B
    lam_star = aopi.argmin_lam_fcfs(mu, p)
    hi_b = jnp.where(pol == aopi.LCFSP, 1.0,
                     jnp.minimum(lam_star / jnp.maximum(lam_scale, _EPS),
                                 1.0))
    obs.count_dispatch("waterfill_pair")
    u, v = kernel.waterfill_pair(
        layout.gather_flat(lam_scale, fill=1.0),
        layout.gather_flat(p, fill=0.5),
        layout.gather_flat(pol, fill=jnp.int32(aopi.LCFSP)),
        layout.gather_flat(mu, fill=1.0),
        layout.gather_flat(jnp.full_like(hi_b, 1e-9), fill=1e-9),
        layout.gather_flat(hi_b, fill=1e-9),
        layout.gather_flat(1.0 + 1.0 / p, fill=1.0),
        layout.gather_flat(inv_xi * C, fill=1.0),
        layout.member(), stability_margin=stability_margin,
        outer_iters=outer_iters, inner_iters=inner_iters,
        final_inner_iters=final_inner_iters,
        interpret=_resolve_interpret(interpret))
    return (layout.scatter_flat(u, n) * B, layout.scatter_flat(v, n) * C)


# ---------------------------------------------------------------------------
# First-fit server selection (Algorithm 2 lines 3-9)
# ---------------------------------------------------------------------------

#: Sorted cameras per grid step of the first-fit kernel: their demands sit
#: in SMEM, 32 KiB a block, double-buffered well inside v5e's 1 MiB.
FIRST_FIT_BLOCK_N = 4096


def first_fit(b_sorted, c_sorted, rem_b, rem_c, srv_order, tot_b, tot_c,
              interpret: bool | None = None):
    """Kernel twin of ``binpack.first_fit_jax``'s placement loop.

    ``b_sorted``/``c_sorted`` are the demands in Eq. (56) order,
    ``rem_b``/``rem_c`` the budgets in Eq. (57) order, ``srv_order`` the
    server id at each of those positions and ``tot_b``/``tot_c`` the
    fleet's totals (demands and capacities float32). Returns int32[N]:
    the position, in ``srv_order``, of the server each sorted camera is
    placed on. The servers lie on ``ceil(S/128)`` rows of 128 lanes;
    padding lanes hold ``-inf`` capacity, so they never fit and never
    hold the most volume.
    """
    obs.count_dispatch("first_fit")
    n = b_sorted.shape[0]
    s = rem_b.shape[0]
    n_pad = max(_LANE, -(-n // _LANE) * _LANE)
    block = min(n_pad, FIRST_FIT_BLOCK_N)
    n_pad = -(-n_pad // block) * block
    s_pad = -(-s // _LANE) * _LANE

    def lanes(x, fill):
        return jnp.pad(jnp.asarray(x, jnp.float32), (0, s_pad - s),
                       constant_values=fill).reshape(-1, _LANE)

    bc = jnp.pad(jnp.stack([b_sorted, c_sorted]).astype(jnp.float32),
                 ((0, 0), (0, n_pad - n)))
    tot = jnp.stack([tot_b, tot_c]).astype(jnp.float32).reshape(1, 2)
    pos = kernel.first_fit(tot, bc, lanes(rem_b, -jnp.inf),
                           lanes(rem_c, -jnp.inf), lanes(srv_order, s_pad),
                           n=n, block_n=block,
                           interpret=_resolve_interpret(interpret))
    return pos.reshape(-1)[:n].astype(jnp.int32)
