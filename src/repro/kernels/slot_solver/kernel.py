"""Pallas TPU kernels for the slot-solver hot path (Algorithms 1 and 2).

Six kernels, all pure VPU work (no MXU):

  * ``config_argmin`` — Algorithm 1 line 3. The jnp backend materializes the
    ``[N, M, R, 2]`` FCFS/LCFSP score tensor in HBM once per BCD pass (and
    again for every vmap lane of a grid/scenario sweep). Here cameras sit
    on the 128 lanes (``[rows, 128]`` tiles, one accuracy plane per
    (m, r) config) and the grid streams blocks of rows; each program
    loops over models on-chip, folds every (m, r, policy) score into a
    running per-camera ``(best_value, best_flat_index)`` pair, and writes
    only the three index rows back to HBM. Tie breaking matches the
    reference's flat argmin exactly (first index in (m, r, policy) order,
    strict-``<`` fold).

  * ``waterfill`` — Algorithm 1 lines 4/5. The grid program owns the whole
    fleet: cameras arrive stably sorted into contiguous per-server blocks
    and lane-padded to a ``[Np]`` vector (``ops.ServerLayout``), together
    with the layout's static ``[S, Np]`` server-membership matrix. The
    entire Illinois outer loop on the log-duals plus the bracketed inner
    bisection runs on-chip: per-server duals/brackets/fill residuals are
    ``[S, 1]`` registers, the per-camera allocation vectors live in VMEM,
    and the two cross-camera couplings (per-server fill sums, dual
    broadcast back to cameras) are membership-masked reductions — so the
    per-camera h-evaluations stay O(N), not O(S*N). HBM traffic is one
    read of the seven input vectors + membership and one write of the
    allocation vector — the jnp path instead pays ~``outer_iters``
    sequential ``segment_sum``/gather dispatches through HBM per solve.
    The math (h-functions, closed forms, iteration budgets, Illinois
    halving) mirrors ``repro.core.allocate._waterfill`` so the two
    backends agree to float32 tolerance.

  * ``waterfill_pair`` — lines 4 *and* 5 in one dispatch. The bandwidth
    solve, the FCFS stability floors for the compute step, and the compute
    solve share one program, so a BCD pass costs one kernel launch instead
    of two and the intermediate ``lam`` never round-trips through HBM.

  * ``waterfill_tiled`` — the same Illinois search with the camera axis
    streamed through VMEM one tile at a time (double-buffered manual DMA
    out of HBM), for fleets past the single-program VMEM ceiling. The
    per-server Illinois state stays in ``[S, 1]`` registers across tiles;
    per-camera brackets persist in an HBM scratch between dual
    evaluations, and each dual evaluation is one sweep over the tiles.
    The per-tile math is identical to ``waterfill``; only the order of
    the per-server fill-sum accumulation differs (tile partial sums), so
    tiled-vs-untiled agreement is near-bitwise rather than exact.

  * ``baseline_argmax`` — the DOS/JCAB config scans (``core.baselines``).
    Same camera-tiled streaming fold as ``config_argmin`` but maximizing
    the baselines' scores (DOS: ``acc - w * latency``; JCAB: accuracy
    under a latency cap with a min-latency fallback), so the baselines'
    ``[N, M, R]`` score/latency tensors are never materialized. The
    elementwise score math matches the jnp references operation for
    operation, so the returned indices are bitwise identical.

  * ``first_fit`` — Algorithm 2 lines 3-9, the first-fit server
    selection. The placement is one chain of dependent steps (each camera
    sees the capacities its predecessors left), so it cannot be batched;
    what the kernel removes is the per-step launch and HBM round trip of
    an XLA loop. The remaining capacities, in the Eq. (57) server order,
    stay in ``[ceil(S/128), 128]`` registers for the whole slot while a
    ``fori_loop`` reads each sorted camera's demands as SMEM scalars; the
    f32 compares, divisions and ``max(rem - demand, 0)`` update are those
    of ``binpack.first_fit_jax``, and the lines 6-8 fallback breaks
    ``rem_vol`` ties to the lowest server id as its ``argmax`` does, so
    the placements are identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import aopi

_LOG_NU_LO = -34.0   # dual-variable search window (log domain)
_LOG_NU_HI = 34.0
_EPS = 1e-12


# ---------------------------------------------------------------------------
# Camera-on-lanes layout shared by the config scans
# ---------------------------------------------------------------------------

LANES = 128      # TPU vreg lanes
SUBLANES = 8     # TPU vreg sublanes (f32)


def block_rows_for(n_rows: int, block_n: int) -> int:
    """Grid block height (in 128-camera rows) for the config scans.

    A block is either the whole (short) row axis or a multiple of the
    8-sublane tile, so Mosaic accepts it at any fleet size; ``block_n``
    is the requested cameras per grid step. The wrapper pads the row
    axis to a multiple of the result."""
    rows = max(SUBLANES, -(-int(block_n) // (LANES * SUBLANES)) * SUBLANES)
    return n_rows if n_rows <= rows else rows


def _scan_specs(n_in_vecs: int, n_cfg: int, n_out: int, block_rows: int):
    """BlockSpecs of a config scan: 2-D SMEM scalars/tables (2-D so a
    vmapped call's batch block keeps their full trailing dims), ``n_in_vecs``
    per-camera ``[rows, 128]`` vectors, the ``[n_cfg, rows, 128]``
    accuracy planes, and one stacked ``[n_out, rows, 128]`` output."""
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return ([smem] * 3 + [vec] * n_in_vecs +
            [pl.BlockSpec((n_cfg, block_rows, LANES), lambda i: (0, i, 0))],
            pl.BlockSpec((n_out, block_rows, LANES), lambda i: (0, i, 0)))


# ---------------------------------------------------------------------------
# Streaming config argmin (Algorithm 1 line 3)
# ---------------------------------------------------------------------------

def _config_kernel(qv_ref, xi_ref, size_ref, b_ref, c_ref, eff_ref, acc_ref,
                   out_ref, *, n_total: int, n_m: int, n_r: int):
    q = qv_ref[0, 0]
    v = qv_ref[0, 1]
    c = c_ref[...]
    be = b_ref[...] * eff_ref[...]

    def model(m, state):
        best_val, best_flat = state
        for r in range(n_r):                               # static
            lam = be / size_ref[0, r]
            mu = c / xi_ref[m, r]
            acc_mr = acc_ref[m * n_r + r]                  # [rows, 128]
            p = jnp.maximum(acc_mr, 1e-3)
            # Flat order is (m, r, policy), FCFS first; the strict-<
            # fold keeps the first minimal index like the flat argmin.
            for pol, a in enumerate((aopi.aopi_fcfs(lam, mu, p),
                                     aopi.aopi_lcfsp(lam, mu, p))):
                score = (v * a - q * acc_mr) / n_total
                take = score < best_val
                best_val = jnp.where(take, score, best_val)
                best_flat = jnp.where(take, (m * n_r + r) * 2 + pol,
                                      best_flat)
        return best_val, best_flat

    init = (jnp.full(c.shape, jnp.inf, jnp.float32),
            jnp.zeros(c.shape, jnp.int32))
    _, best_flat = jax.lax.fori_loop(0, n_m, model, init)
    out_ref[0] = (best_flat // 2) % n_r
    out_ref[1] = best_flat // (n_r * 2)
    out_ref[2] = best_flat % 2


@functools.partial(jax.jit, static_argnames=("n_total", "block_rows",
                                             "interpret"))
def config_argmin(b, c, acc, xi, size, eff, q, v, *, n_total: int,
                  block_rows: int, interpret: bool = False):
    """Streaming (m, r, policy) argmin in the camera-on-lanes layout.

    ``b``/``c``/``eff`` are ``[rows, 128]`` (camera ``i`` at
    ``(i // 128, i % 128)``), ``acc`` is ``[M*R, rows, 128]`` and ``rows``
    a multiple of ``block_rows``. Returns ``[3, rows, 128]`` int32 rows
    ``(r_idx, m_idx, pol)``. Every intermediate is a dense
    ``[block_rows, 128]`` tile, so VMEM per grid step is the
    ``M*R``-plane accuracy block plus a few vregs, at any fleet size.
    """
    n_m, n_r = xi.shape
    rows = b.shape[0]
    qv = jnp.stack([jnp.asarray(q, jnp.float32),
                    jnp.asarray(v, jnp.float32)]).reshape(1, 2)
    kernel = functools.partial(_config_kernel, n_total=n_total, n_m=n_m,
                               n_r=n_r)
    in_specs, out_spec = _scan_specs(3, n_m * n_r, 3, block_rows)
    return pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((3, rows, LANES), jnp.int32),
        interpret=interpret,
        name="slot_solver.config_argmin",
    )(qv, xi, size.reshape(1, n_r), b, c, eff, acc)


# ---------------------------------------------------------------------------
# Per-server on-chip water-filling (Algorithm 1 lines 4/5)
# ---------------------------------------------------------------------------

#: The water-fills keep the ``[S, Np]`` (tiled: ``[S, tile]``) membership
#: matrix and its products in VMEM at once — 4 MiB each at 10^4 cameras
#: on 100 servers — so they may grow past v5e's 16 MiB default scope, up
#: to 100 MiB of its 128 MiB.
_VMEM_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=100 * 2**20)

def _h_fn(x, scale, p, is_l, other, mode):
    """Marginal-AoPI water-level function h(x) (shared by every variant)."""
    if mode == "bandwidth":
        lam = jnp.maximum(scale * x, _EPS)
        d_l = aopi.d_aopi_lcfsp_dlam(lam, other, p)
        d_f = aopi.d_aopi_fcfs_dlam(jnp.minimum(lam, 0.999 * other),
                                    other, p)
    else:
        mu = jnp.maximum(scale * x, _EPS)
        d_l = aopi.d_aopi_lcfsp_dmu(other, mu, p)
        d_f = aopi.d_aopi_fcfs_dmu(jnp.minimum(other, 0.999 * mu),
                                   mu, p)
    d = jnp.where(is_l, d_l, d_f)
    return jnp.maximum(-d * scale, 0.0)


def _illinois_waterfill(scale, p, is_l, other, lo, hi, cf, member, *,
                        mode: str, outer_iters: int, inner_iters: int,
                        final_inner_iters: int):
    """On-chip Illinois dual search over whole-fleet vectors; returns x.

    This is the body shared by the single-mode ``waterfill`` kernel and
    the fused ``waterfill_pair`` kernel — plain array-in/array-out so it
    can run twice inside one program.
    """

    def h_fn(x):
        return _h_fn(x, scale, p, is_l, other, mode)

    def solve_h_equals_nu(nu, blo, bhi, iters):
        def body(_, state):
            a, b = state
            mid = 0.5 * (a + b)
            go_up = h_fn(mid) >= nu
            return jnp.where(go_up, mid, a), jnp.where(go_up, b, mid)
        a, b = jax.lax.fori_loop(0, iters, body, (blo, bhi))
        return 0.5 * (a + b)

    n_servers = member.shape[0]

    def per_camera(v_s):
        """Broadcast a per-server [S, 1] value to cameras [1, Np] (zero on
        padding slots, whose membership column is all-zero)."""
        return jnp.sum(member * v_s, axis=0, keepdims=True)

    def alloc_at(log_nu_s, blo, bhi, iters):
        nu = per_camera(jnp.exp(log_nu_s))                # [1, Np] duals
        x_cl = jnp.sqrt(cf / jnp.maximum(scale * nu, _EPS))
        x_bi = solve_h_equals_nu(nu, blo, bhi, iters)
        return jnp.clip(jnp.where(is_l, x_cl, x_bi), lo, hi)

    def bracket(xa, xb):
        pad = 0.25 * jnp.maximum(xa - xb, 0.0) + 1e-7
        return jnp.maximum(lo, xb - pad), jnp.minimum(hi, xa + pad)

    def fill_at(log_nu_s, xa, xb, iters):
        blo, bhi = bracket(xa, xb)
        x = alloc_at(log_nu_s, blo, bhi, iters)
        f = jnp.sum(member * x, axis=1, keepdims=True) - 1.0  # [S, 1]
        return x, f

    a0 = jnp.full((n_servers, 1), _LOG_NU_LO, jnp.float32)
    b0 = jnp.full((n_servers, 1), _LOG_NU_HI, jnp.float32)
    xa0, fa0 = fill_at(a0, hi, lo, inner_iters + 4)
    xb0, fb0 = fill_at(b0, hi, lo, inner_iters + 4)

    def body(_, state):
        a, b, fa, fb, xa, xb = state
        denom = fa - fb
        t = jnp.where(jnp.abs(denom) > 1e-12, fa / denom, 0.5)
        t = jnp.clip(t, 0.05, 0.95)
        mid = a + t * (b - a)
        x, f = fill_at(mid, xa, xb, inner_iters)
        over = f > 0.0             # over budget -> raise the price
        over_n = per_camera(over.astype(jnp.float32)) > 0.5
        return (jnp.where(over, mid, a), jnp.where(over, b, mid),
                jnp.where(over, f, 0.5 * fa),    # Illinois halving of the
                jnp.where(over, 0.5 * fb, f),    # retained endpoint
                jnp.where(over_n, x, xa), jnp.where(over_n, xb, x))

    a, b, _, _, xa, xb = jax.lax.fori_loop(
        0, outer_iters, body, (a0, b0, fa0, fb0, xa0, xb0))
    blo, bhi = bracket(xa, xb)
    # If the total cap is below budget the constraint is slack: keep caps.
    return alloc_at(0.5 * (a + b), blo, bhi, final_inner_iters)


def _waterfill_kernel(scale_ref, p_ref, pol_ref, other_ref, lo_ref, hi_ref,
                      cf_ref, member_ref, x_ref, *, mode: str,
                      outer_iters: int, inner_iters: int,
                      final_inner_iters: int):
    x_ref[...] = _illinois_waterfill(
        scale_ref[...], p_ref[...], pol_ref[...] == aopi.LCFSP,
        other_ref[...], lo_ref[...], hi_ref[...], cf_ref[...],
        member_ref[...], mode=mode, outer_iters=outer_iters,
        inner_iters=inner_iters, final_inner_iters=final_inner_iters)


@functools.partial(jax.jit, static_argnames=("mode", "outer_iters",
                                             "inner_iters",
                                             "final_inner_iters",
                                             "interpret"))
def waterfill(scale, p, pol, other, lo, hi, cf, member, *, mode: str,
              outer_iters: int = 16, inner_iters: int = 6,
              final_inner_iters: int = 20, interpret: bool = False):
    """Run the fused water-fill on flat layout vectors.

    The seven per-camera vectors are ``[Np]`` in the layout's sorted
    (contiguous-per-server, lane-padded) order and ``member`` is the
    layout's ``[S, Np]`` membership matrix (``ops.ServerLayout.member``).
    Returns normalized allocations ``[Np]`` in the same order. One grid
    program holds the whole fleet in VMEM (the vectors as lane-dense
    ``[1, Np]`` rows, so the membership reductions have a 2-D layout).
    """
    cap = scale.shape[0]
    kernel = functools.partial(_waterfill_kernel, mode=mode,
                               outer_iters=outer_iters,
                               inner_iters=inner_iters,
                               final_inner_iters=final_inner_iters)
    vecs = [x.reshape(1, cap) for x in (scale, p, pol, other, lo, hi, cf)]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, cap), jnp.float32),
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
        name="slot_solver.waterfill",
    )(*vecs, member).reshape(cap)


# ---------------------------------------------------------------------------
# Fused bandwidth+compute water-fill (Algorithm 1 lines 4 and 5 together)
# ---------------------------------------------------------------------------

def _pair_kernel(margin_ref, scale_b_ref, p_ref, pol_ref, mu_ref, lo_b_ref,
                 hi_b_ref, cf_b_ref, mu_scale_ref, member_ref, u_ref, v_ref,
                 *, outer_iters: int, inner_iters: int,
                 final_inner_iters: int):
    margin = margin_ref[0, 0]                             # FCFS stability
    scale_b = scale_b_ref[...]                            # k * B  [1, Np]
    p = p_ref[...]
    is_l = pol_ref[...] == aopi.LCFSP
    member = member_ref[...]                              # [S, Np] 0/1

    # Line 4: bandwidth water-fill, identical to the single-mode kernel.
    u = _illinois_waterfill(
        scale_b, p, is_l, mu_ref[...], lo_b_ref[...], hi_b_ref[...],
        cf_b_ref[...], member, mode="bandwidth", outer_iters=outer_iters,
        inner_iters=inner_iters, final_inner_iters=final_inner_iters)

    # Line 5 prologue, on-chip: the arrival rate implied by the fresh b and
    # the FCFS stability floors (the jnp twin computes these between the
    # two dispatches; here they never leave VMEM). The floor rescale uses a
    # membership reduction instead of the twin's segment_sum.
    lam = scale_b * u
    mu_scale = mu_scale_ref[...]                          # inv_xi * C
    floor = jnp.where(is_l, 1e-9,
                      margin * lam / jnp.maximum(mu_scale, _EPS))
    floor_tot = jnp.sum(member * floor, axis=1, keepdims=True)  # [S, 1]
    scale_fac = jnp.minimum(1.0, 1.0 / jnp.maximum(floor_tot, _EPS))
    lo_c = jnp.clip(floor * jnp.sum(member * scale_fac, axis=0,
                                    keepdims=True), 1e-9, 1.0)

    v = _illinois_waterfill(
        mu_scale, p, is_l, lam, lo_c, jnp.ones_like(lo_c), 1.0 / p, member,
        mode="compute", outer_iters=outer_iters, inner_iters=inner_iters,
        final_inner_iters=final_inner_iters)
    u_ref[...] = u
    v_ref[...] = v


@functools.partial(jax.jit, static_argnames=("outer_iters", "inner_iters",
                                             "final_inner_iters",
                                             "interpret"))
def waterfill_pair(scale_b, p, pol, mu, lo_b, hi_b, cf_b, mu_scale, member,
                   *, stability_margin: float = 1.05, outer_iters: int = 16,
                   inner_iters: int = 6, final_inner_iters: int = 20,
                   interpret: bool = False):
    """One dispatch for both water-fills of a BCD pass.

    Bandwidth inputs are as for ``waterfill(mode="bandwidth")``;
    ``mu_scale`` is the compute-side scale ``inv_xi * C``. The compute
    bounds/coefficient (FCFS stability floors, unit cap, ``1/p``) are
    derived on-chip from the in-register bandwidth result. Returns
    normalized ``(u, v)`` allocations in layout order.
    """
    cap = scale_b.shape[0]
    kernel = functools.partial(_pair_kernel, outer_iters=outer_iters,
                               inner_iters=inner_iters,
                               final_inner_iters=final_inner_iters)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    mg = jnp.asarray(stability_margin, jnp.float32).reshape(1, 1)
    vecs = [x.reshape(1, cap)
            for x in (scale_b, p, pol, mu, lo_b, hi_b, cf_b, mu_scale)]
    u, v = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [vmem] * 9,
        out_shape=[jax.ShapeDtypeStruct((1, cap), jnp.float32)] * 2,
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
        name="slot_solver.waterfill_pair",
    )(mg, *vecs, member)
    return u.reshape(cap), v.reshape(cap)


# ---------------------------------------------------------------------------
# Camera-tiled streaming water-fill (fleets past the VMEM ceiling)
# ---------------------------------------------------------------------------

# Row order of the packed [8, Np] input block (built by ops._run_waterfill).
TILE_FIELDS = ("scale", "p", "is_l", "other", "lo", "hi", "cf", "sid")
# The bracket scratch holds (xa, xb, x_last); Mosaic tiles a short f32 HBM
# array in 4-row groups and refuses a 3-row DMA slice, so it has a pad row.
_BRACKET_ROWS = 4


def _tiled_waterfill_kernel(in_hbm, x_hbm, st_hbm, *, mode: str,
                            n_servers: int, n_tiles: int, tile: int,
                            outer_iters: int, inner_iters: int,
                            final_inner_iters: int):
    """Illinois dual search with the camera axis streamed tile by tile.

    The whole fleet lives in HBM as one packed ``[8, Np]`` block; VMEM
    holds a double-buffered ``[2, 8, tile]`` window of it. Per-server
    Illinois state (duals, residuals, the deferred bracket decision) stays
    in ``[S, 1]`` registers across the sweep; per-camera brackets
    ``(xa, xb, x_last)`` persist in a ``[4, Np]`` HBM scratch between
    sweeps, so VMEM holds only O(tile) state no matter the fleet size.
    One dual evaluation = one sweep over the tiles accumulating the
    per-server fill sums.

    The bracket update is *deferred*: sweep k applies sweep k-1's
    over/under decision to the stored brackets before allocating — exactly
    the untiled kernel's carried ``(xa, xb)`` update, one evaluation late
    never (the untiled kernel also applies the decision only when the
    *next* evaluation reads the brackets).
    """

    def body(in_scr, st_scr, out_scr, in_sems, st_sem, out_sem):
        srv = jax.lax.broadcasted_iota(jnp.int32, (n_servers, tile),
                                       0).astype(jnp.float32)

        def in_dma(slot, t):
            return pltpu.make_async_copy(
                in_hbm.at[:, pl.ds(t * tile, tile)], in_scr.at[slot],
                in_sems.at[slot])

        def sweep(log_nu, over_prev, iters, phase):
            """One streamed dual evaluation. phase: 0 = init (log_nu is the
            (a0, b0) endpoint pair, brackets seeded from (hi, lo)), 1 =
            Illinois step at log_nu, 2 = final allocation (writes x)."""

            def tile_step(t, fs):
                slot = t % 2

                @pl.when(t + 1 < n_tiles)
                def _():
                    in_dma((t + 1) % 2, t + 1).start()

                in_dma(slot, t).wait()
                blk = in_scr[slot]                        # [8, tile]
                scale, p, is_l, other, lo, hi, cf, sid = (
                    blk[i:i + 1] for i in range(len(TILE_FIELDS)))
                is_l = is_l > 0.5
                member = (sid == srv).astype(jnp.float32)

                def per_camera(v_s):
                    return jnp.sum(member * v_s, axis=0, keepdims=True)

                def alloc_at(log_nu_s, blo, bhi, it):
                    nu = per_camera(jnp.exp(log_nu_s))
                    x_cl = jnp.sqrt(cf / jnp.maximum(scale * nu, _EPS))

                    def bstep(_, state):
                        a_, b_ = state
                        mid = 0.5 * (a_ + b_)
                        go_up = _h_fn(mid, scale, p, is_l, other,
                                      mode) >= nu
                        return (jnp.where(go_up, mid, a_),
                                jnp.where(go_up, b_, mid))

                    a_, b_ = jax.lax.fori_loop(0, it, bstep, (blo, bhi))
                    return jnp.clip(jnp.where(is_l, x_cl, 0.5 * (a_ + b_)),
                                    lo, hi)

                def bracket(xa, xb):
                    pad = 0.25 * jnp.maximum(xa - xb, 0.0) + 1e-7
                    return (jnp.maximum(lo, xb - pad),
                            jnp.minimum(hi, xa + pad))

                def fill_of(x):
                    return jnp.sum(member * x, axis=1,
                                   keepdims=True)          # [S, 1]

                if phase == 0:
                    la, lb = log_nu
                    blo, bhi = bracket(hi, lo)
                    xa = alloc_at(la, blo, bhi, iters)
                    xb = alloc_at(lb, blo, bhi, iters)
                    st_scr[0:1, :] = xa
                    st_scr[1:2, :] = xb
                    st_scr[2:3, :] = xb
                    wr = pltpu.make_async_copy(
                        st_scr, st_hbm.at[:, pl.ds(t * tile, tile)], st_sem)
                    wr.start()
                    wr.wait()
                    return fs[0] + fill_of(xa), fs[1] + fill_of(xb)

                rd = pltpu.make_async_copy(
                    st_hbm.at[:, pl.ds(t * tile, tile)], st_scr, st_sem)
                rd.start()
                rd.wait()
                # Apply the previous evaluation's over/under decision to
                # the stored brackets (same update as the untiled carry).
                ov = per_camera(over_prev) > 0.5
                xa = jnp.where(ov, st_scr[2:3], st_scr[0:1])
                xb = jnp.where(ov, st_scr[1:2], st_scr[2:3])
                blo, bhi = bracket(xa, xb)
                x = alloc_at(log_nu, blo, bhi, iters)
                if phase == 1:
                    st_scr[0:1, :] = xa
                    st_scr[1:2, :] = xb
                    st_scr[2:3, :] = x
                    wr = pltpu.make_async_copy(
                        st_scr, st_hbm.at[:, pl.ds(t * tile, tile)], st_sem)
                    wr.start()
                    wr.wait()
                    return fs[0] + fill_of(x), fs[1]
                out_scr[...] = x
                wr = pltpu.make_async_copy(
                    out_scr, x_hbm.at[:, pl.ds(t * tile, tile)], out_sem)
                wr.start()
                wr.wait()
                return fs

            in_dma(0, 0).start()
            z = jnp.zeros((n_servers, 1), jnp.float32)
            return jax.lax.fori_loop(0, n_tiles, tile_step, (z, z))

        a0 = jnp.full((n_servers, 1), _LOG_NU_LO, jnp.float32)
        b0 = jnp.full((n_servers, 1), _LOG_NU_HI, jnp.float32)
        zero = jnp.zeros((n_servers, 1), jnp.float32)
        fa0, fb0 = sweep((a0, b0), zero, inner_iters + 4, phase=0)
        fa0 = fa0 - 1.0
        fb0 = fb0 - 1.0

        def outer(_, state):
            a, b, fa, fb, over_prev = state
            denom = fa - fb
            t = jnp.where(jnp.abs(denom) > 1e-12, fa / denom, 0.5)
            t = jnp.clip(t, 0.05, 0.95)
            mid = a + t * (b - a)
            f, _ = sweep(mid, over_prev, inner_iters, phase=1)
            f = f - 1.0
            over = f > 0.0
            return (jnp.where(over, mid, a), jnp.where(over, b, mid),
                    jnp.where(over, f, 0.5 * fa),
                    jnp.where(over, 0.5 * fb, f),
                    over.astype(jnp.float32))

        a, b, _, _, over_prev = jax.lax.fori_loop(
            0, outer_iters, outer, (a0, b0, fa0, fb0, zero))
        sweep(0.5 * (a + b), over_prev, final_inner_iters, phase=2)

    return body


@functools.partial(jax.jit, static_argnames=("mode", "n_servers", "tile",
                                             "outer_iters", "inner_iters",
                                             "final_inner_iters",
                                             "interpret"))
def waterfill_tiled(block, *, mode: str, n_servers: int, tile: int,
                    outer_iters: int = 16, inner_iters: int = 6,
                    final_inner_iters: int = 20, interpret: bool = False):
    """Camera-tiled streaming water-fill on a packed ``[8, Np]`` block.

    ``block`` rows follow :data:`TILE_FIELDS` (the seven ``waterfill``
    vectors plus the per-slot server id as f32; ``is_l`` is the 0/1
    LCFSP indicator); ``Np`` must be a multiple of ``tile``. Padding
    slots carry the sentinel sid ``n_servers`` so no membership row
    picks them up. Returns the normalized allocation ``[Np]``.
    """
    f, np_ = block.shape
    assert f == len(TILE_FIELDS) and np_ % tile == 0

    def kernel(in_hbm, x_hbm, st_hbm):
        inner = _tiled_waterfill_kernel(
            in_hbm, x_hbm, st_hbm, mode=mode, n_servers=n_servers,
            n_tiles=np_ // tile, tile=tile, outer_iters=outer_iters,
            inner_iters=inner_iters, final_inner_iters=final_inner_iters)
        pl.run_scoped(
            inner,
            in_scr=pltpu.VMEM((2, f, tile), jnp.float32),
            st_scr=pltpu.VMEM((_BRACKET_ROWS, tile), jnp.float32),
            out_scr=pltpu.VMEM((1, tile), jnp.float32),
            in_sems=pltpu.SemaphoreType.DMA((2,)),
            st_sem=pltpu.SemaphoreType.DMA,
            out_sem=pltpu.SemaphoreType.DMA,
        )

    x, _ = pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, np_), jnp.float32),
                   jax.ShapeDtypeStruct((_BRACKET_ROWS, np_),
                                        jnp.float32)],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
        name="slot_solver.waterfill_tiled",
    )(block)
    return x[0]


# ---------------------------------------------------------------------------
# Streaming DOS/JCAB config scans (core.baselines)
# ---------------------------------------------------------------------------

def _baseline_kernel(th_ref, xi_ref, size_ref, b_ref, c_ref, eff_ref,
                     acc_ref, out_ref, *, mode: str, n_m: int, n_r: int):
    thresh = th_ref[0, 0]           # DOS latency weight / JCAB latency cap
    c = c_ref[...]
    be = b_ref[...] * eff_ref[...]

    def model(m, state):
        best_val, best_flat, lat_best, lat_flat = state
        for r in range(n_r):                               # static
            lam = be / size_ref[0, r]
            mu = c / xi_ref[m, r]
            latency = 1.0 / jnp.maximum(lam, 1e-9) + 1.0 / jnp.maximum(mu,
                                                                       1e-9)
            acc_mr = acc_ref[m * n_r + r]
            if mode == "dos":
                val = acc_mr - thresh * latency
            else:
                val = jnp.where(latency <= thresh, acc_mr, -jnp.inf)
            flat = m * n_r + r
            # Strict-> fold in flat (m-major) order keeps the earliest
            # maximal index, matching jnp.argmax exactly.
            take = val > best_val
            best_val = jnp.where(take, val, best_val)
            best_flat = jnp.where(take, flat, best_flat)
            if mode == "jcab":
                # Min-latency fallback config, tracked alongside.
                lt = latency < lat_best
                lat_best = jnp.where(lt, latency, lat_best)
                lat_flat = jnp.where(lt, flat, lat_flat)
        return best_val, best_flat, lat_best, lat_flat

    zeros = jnp.zeros(c.shape, jnp.int32)
    init = (jnp.full(c.shape, -jnp.inf, jnp.float32), zeros,
            jnp.full(c.shape, jnp.inf, jnp.float32), zeros)
    best_val, best_flat, _, lat_flat = jax.lax.fori_loop(0, n_m, model, init)
    if mode == "jcab":
        # No config met the cap anywhere: min-latency fallback (the jnp
        # twin's argmax over all -inf also lands on flat index 0, so the
        # met-somewhere case needs no special handling).
        best_flat = jnp.where(best_val == -jnp.inf, lat_flat, best_flat)
    out_ref[0] = best_flat // n_r
    out_ref[1] = best_flat % n_r


@functools.partial(jax.jit, static_argnames=("mode", "block_rows",
                                             "interpret"))
def baseline_argmax(b, c, acc, xi, size, eff, *, mode: str, threshold,
                    block_rows: int, interpret: bool = False):
    """Streaming DOS/JCAB config argmax in the camera-on-lanes layout of
    :func:`config_argmin`; returns ``[2, rows, 128]`` int32 rows
    ``(m_idx, r_idx)``.

    ``mode="dos"`` maximizes ``acc - threshold * latency``;
    ``mode="jcab"`` maximizes accuracy among configs with
    ``latency <= threshold`` and falls back to the min-latency config
    when none qualifies. Bitwise-identical indices to the materialized
    jnp scans (same elementwise ops, same first-index tie-breaks).
    """
    n_m, n_r = xi.shape
    rows = b.shape[0]
    th = jnp.asarray(threshold, jnp.float32).reshape(1, 1)
    kernel = functools.partial(_baseline_kernel, mode=mode, n_m=n_m,
                               n_r=n_r)
    in_specs, out_spec = _scan_specs(3, n_m * n_r, 2, block_rows)
    return pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((2, rows, LANES), jnp.int32),
        interpret=interpret,
        name="slot_solver.baseline_argmax",
    )(th, xi, size.reshape(1, n_r), b, c, eff, acc)


# ---------------------------------------------------------------------------
# First-fit server selection (Algorithm 2 lines 3-9)
# ---------------------------------------------------------------------------

def _fold(op, x):
    """``[R, 128]`` -> ``[1, 1]`` reduction by ``op``: lanes, then
    sublanes."""
    x = op(x, axis=1, keepdims=True)
    return x if x.shape[0] == 1 else op(x, axis=0, keepdims=True)


def _first_fit_kernel(tot_ref, bc_ref, rem_b0_ref, rem_c0_ref, sid_ref,
                      pos_ref, rem_b_ref, rem_c_ref, *, n: int,
                      block_n: int):
    """Place one block of sorted cameras; the capacities left carry over
    to the next block in VMEM scratch."""
    blk = pl.program_id(0)

    @pl.when(blk == 0)
    def _():
        rem_b_ref[...] = rem_b0_ref[...]
        rem_c_ref[...] = rem_c0_ref[...]

    tot_b = tot_ref[0, 0]
    tot_c = tot_ref[0, 1]
    sid = sid_ref[...]                  # original server id of each lane
    shape = sid.shape
    flat = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            ).astype(jnp.float32)       # position in the Eq. (57) order
    none = jnp.float32(shape[0] * LANES)   # past every lane and server id
    out = pos_ref.shape
    cam = (jax.lax.broadcasted_iota(jnp.int32, out, 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, out, 1))

    def place(j, state):
        rem_b, rem_c, pos = state
        bn = bc_ref[0, j]
        cn = bc_ref[1, j]
        fits = (rem_b >= bn) & (rem_c >= cn)      # padding lanes hold -inf
        first = _fold(jnp.min, jnp.where(fits, flat, none))
        # Lines 6-8: the most remaining volume, ties to the lowest id.
        rem_vol = rem_b / tot_b + rem_c / tot_c
        top = _fold(jnp.max, rem_vol)
        best = _fold(jnp.min, jnp.where(rem_vol == top, sid, none))
        fit = first < none
        take = jnp.where(fit, flat, sid) == jnp.where(fit, first, best)
        rem_b = jnp.where(take, jnp.maximum(rem_b - bn, 0.0), rem_b)
        rem_c = jnp.where(take, jnp.maximum(rem_c - cn, 0.0), rem_c)
        pos = jnp.where(cam == j, _fold(jnp.min, jnp.where(take, flat, none)),
                        pos)
        return rem_b, rem_c, pos

    count = n if n <= block_n else jnp.minimum(block_n, n - blk * block_n)
    rem_b, rem_c, pos = jax.lax.fori_loop(
        0, count, place, (rem_b_ref[...], rem_c_ref[...],
                          jnp.zeros(out, jnp.float32)))
    rem_b_ref[...] = rem_b
    rem_c_ref[...] = rem_c
    pos_ref[...] = pos


@functools.partial(jax.jit, static_argnames=("n", "block_n", "interpret"))
def first_fit(tot, bc, rem_b, rem_c, sid, *, n: int, block_n: int,
              interpret: bool = False):
    """Algorithm 2's first-fit over cameras already in Eq. (56) order.

    ``tot`` is ``[1, 2]`` (the fleet's total bandwidth and compute),
    ``bc`` the ``[2, Np]`` sorted demands (``Np`` a multiple of
    ``block_n``, itself the whole of ``Np`` or a multiple of 1024),
    ``rem_b``/``rem_c`` the ``[R, 128]`` capacities in Eq. (57) order
    with ``-inf`` on padding lanes and ``sid`` each lane's original server
    id as f32. The grid walks ``block_n`` cameras a step. Returns the
    ``[Np // 128, 128]`` f32 lane position chosen for each camera.
    """
    n_pad = bc.shape[1]
    state = pl.BlockSpec(rem_b.shape, lambda j: (0, 0))
    kernel = functools.partial(_first_fit_kernel, n=n, block_n=block_n)
    return pl.pallas_call(
        kernel,
        grid=(n_pad // block_n,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((2, block_n), lambda j: (0, j),
                               memory_space=pltpu.SMEM),
                  state, state, state],
        out_specs=pl.BlockSpec((block_n // LANES, LANES), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad // LANES, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM(rem_b.shape, jnp.float32)] * 2,
        interpret=interpret,
        name="slot_solver.first_fit",
    )(tot, bc, rem_b, rem_c, sid)
