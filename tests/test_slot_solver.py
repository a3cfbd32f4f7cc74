"""Fused slot-solver kernels vs the jnp backend: parity + dispatch shape.

Pallas runs in interpret mode on CPU (the ops layer auto-selects it
off-TPU), so everything here exercises the exact kernel code paths that
compile on device.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jax_core

from repro.core import allocate, aopi, baselines, bcd, lbcd, profiles
from repro.kernels import slot_solver
from repro.kernels.slot_solver import ops as slot_ops


def _setup(n, s, seed=0, lcfsp_frac=0.5, budget_lo=2e7, budget_hi=5e7,
           server_id=None):
    rng = np.random.default_rng(seed)
    k = rng.uniform(1e-6, 5e-6, n)
    p = rng.uniform(0.3, 0.95, n)
    pol = (rng.random(n) < lcfsp_frac).astype(np.int32)
    mu = rng.uniform(5.0, 40.0, n)
    if server_id is None:
        server_id = rng.integers(0, s, n).astype(np.int32)
    budgets = rng.uniform(budget_lo, budget_hi, s)
    return (jnp.asarray(k, jnp.float32), jnp.asarray(p, jnp.float32),
            jnp.asarray(pol), jnp.asarray(mu, jnp.float32),
            jnp.asarray(server_id), jnp.asarray(budgets, jnp.float32))


# ---------------------------------------------------------------------------
# ServerLayout
# ---------------------------------------------------------------------------

def test_server_layout_roundtrip_and_padding():
    sid = jnp.asarray([2, 0, 2, 1, 0, 2, 0], jnp.int32)
    layout = slot_solver.server_layout(sid, 3)
    n = sid.shape[0]
    assert layout.capacity % 128 == 0 and layout.capacity >= n
    np.testing.assert_array_equal(np.asarray(layout.counts), [3, 1, 3])
    order = np.asarray(layout.order)
    mask = np.asarray(layout.mask)
    # Every camera appears exactly once, on its own server's row, in
    # ascending original order (stable sort); padding slots carry the
    # sentinel and zero mask.
    real = order[mask > 0]
    assert sorted(real.tolist()) == list(range(n))
    for s in range(3):
        row = order[s][mask[s] > 0]
        assert all(np.asarray(sid)[i] == s for i in row)
        assert list(row) == sorted(row)
    assert (order[mask == 0] == n).all()
    # gather -> scatter is the identity on per-camera vectors.
    x = jnp.arange(1.0, n + 1.0)
    np.testing.assert_allclose(
        np.asarray(layout.scatter(layout.gather(x), n)), np.asarray(x))


def test_server_layout_capacity_floor_and_overflow():
    # Sub-lane capacities round up to the 128-lane floor: nothing drops.
    sid = jnp.zeros((5,), jnp.int32)
    layout = slot_solver.server_layout(sid, 1, capacity=2)
    assert layout.capacity == 128
    assert int(layout.mask.sum()) == 5
    # A server loaded past the rounded capacity drops the overflow from
    # its row view; the flat view still carries every camera.
    sid = jnp.zeros((130,), jnp.int32)
    layout = slot_solver.server_layout(sid, 1, capacity=100)
    assert layout.capacity == 128
    assert int(layout.mask.sum()) == 128          # 2 dropped from the row
    assert int(layout.counts[0]) == 130
    assert int(layout.flat_mask.sum()) == 130     # flat view is complete
    x = jnp.arange(130.0)
    np.testing.assert_allclose(
        np.asarray(layout.scatter_flat(layout.gather_flat(x), 130)),
        np.asarray(x))


def test_server_layout_empty_server():
    sid = jnp.asarray([0, 0, 2, 2], jnp.int32)
    layout = slot_solver.server_layout(sid, 3)
    assert int(layout.counts[1]) == 0
    assert float(layout.mask[1].sum()) == 0.0


# ---------------------------------------------------------------------------
# Water-filling kernel vs jnp _waterfill
# ---------------------------------------------------------------------------

def _assert_bandwidth_parity(n, s, seed, lcfsp_frac, budget_lo=2e7,
                             budget_hi=5e7, server_id=None):
    k, p, pol, mu, sid, B = _setup(n, s, seed=seed, lcfsp_frac=lcfsp_frac,
                                   budget_lo=budget_lo, budget_hi=budget_hi,
                                   server_id=server_id)
    b_ref = np.asarray(allocate.waterfill_bandwidth(
        k, p, pol, mu, sid, B, n_servers=s))
    b_pl = np.asarray(slot_solver.waterfill_bandwidth(
        k, p, pol, mu, sid, B, n_servers=s))
    np.testing.assert_allclose(b_pl, b_ref, rtol=2e-4, atol=1e-2)
    return b_pl, np.asarray(sid), np.asarray(B)


def test_waterfill_bandwidth_parity_hypothesis():
    """Random FCFS/LCFSP mixes: pallas-interpret == jnp ``_waterfill``."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 10_000), st.sampled_from([0.0, 0.3, 0.5, 1.0]))
    def inner(seed, frac):
        _assert_bandwidth_parity(10, 2, seed, frac)
    inner()


def test_waterfill_compute_parity_hypothesis():
    """Compute side (FCFS stability floors active) parity."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 10_000), st.sampled_from([0.0, 0.5, 1.0]))
    def inner(seed, frac):
        rng = np.random.default_rng(seed)
        n, s = 10, 2
        inv_xi = jnp.asarray(rng.uniform(1e-12, 5e-12, n), jnp.float32)
        p = jnp.asarray(rng.uniform(0.3, 0.95, n), jnp.float32)
        pol = jnp.asarray((rng.random(n) < frac).astype(np.int32))
        lam = jnp.asarray(rng.uniform(1.0, 10.0, n), jnp.float32)
        sid = jnp.asarray(rng.integers(0, s, n).astype(np.int32))
        C = jnp.asarray(rng.uniform(3e13, 8e13, s), jnp.float32)
        c_ref = np.asarray(allocate.waterfill_compute(
            inv_xi, p, pol, lam, sid, C, n_servers=s))
        c_pl = np.asarray(slot_solver.waterfill_compute(
            inv_xi, p, pol, lam, sid, C, n_servers=s))
        np.testing.assert_allclose(c_pl, c_ref, rtol=2e-4, atol=1e4)
    inner()


def test_waterfill_slack_budget_keeps_caps():
    """When the FCFS caps sum below the budget the constraint is slack:
    both backends return the caps and stay (well) under budget."""
    # All-FCFS + huge budgets -> hi = lam*/(k*B) << 1 per camera.
    b, sid, B = _assert_bandwidth_parity(8, 2, seed=11, lcfsp_frac=0.0,
                                         budget_lo=5e9, budget_hi=9e9)
    for s in range(2):
        m = sid == s
        assert b[m].sum() < 0.9 * B[s]


def test_waterfill_degenerate_single_camera_servers():
    """One camera per server: the dual search degenerates to the
    per-camera cap; backends must still agree."""
    n = 6
    _assert_bandwidth_parity(n, n, seed=3, lcfsp_frac=0.5,
                             server_id=np.arange(n, dtype=np.int32))


def test_waterfill_budget_respected_and_positive():
    b, sid, B = _assert_bandwidth_parity(12, 3, seed=7, lcfsp_frac=0.5)
    assert (b > 0).all() and np.isfinite(b).all()
    for s in range(3):
        assert b[sid == s].sum() <= float(B[s]) * 1.001


# ---------------------------------------------------------------------------
# Streaming config argmin vs materialized reference
# ---------------------------------------------------------------------------

def _config_inputs(n, seed=0, m=5, r=6):
    rng = np.random.default_rng(seed)
    acc = jnp.asarray(rng.uniform(0.2, 0.95, (n, m, r)), jnp.float32)
    xi = jnp.asarray(np.sort(rng.uniform(1e9, 2e11, (m, r)), axis=1),
                     jnp.float32)
    size = jnp.asarray(1.2 * np.asarray(profiles.RESOLUTIONS)[:r] ** 2,
                       jnp.float32)
    eff = jnp.asarray(rng.uniform(4.0, 7.0, n), jnp.float32)
    b = jnp.asarray(rng.uniform(1e6, 1e7, n), jnp.float32)
    c = jnp.asarray(rng.uniform(1e12, 1e13, n), jnp.float32)
    return b, c, acc, xi, size, eff


@pytest.mark.parametrize("n,block_n", [(7, 1024), (1100, 1024),
                                       (2048, 1024)])
def test_config_argmin_matches_ref(n, block_n):
    """Streaming kernel == flat argmin (incl. non-divisible tiling)."""
    for seed in range(3):
        b, c, acc, xi, size, eff = _config_inputs(n, seed=seed)
        ref = slot_solver.config_argmin_ref(b, c, acc, xi, size, eff,
                                            1.3, 10.0, n)
        out = slot_solver.config_argmin(b, c, acc, xi, size, eff,
                                        1.3, 10.0, n, backend="pallas",
                                        block_n=block_n)
        for name, a, o in zip(("r", "m", "pol"), ref, out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(o),
                                          err_msg=f"{name} seed={seed}")


# ---------------------------------------------------------------------------
# Full Algorithm-1 solve + rollout backend parity
# ---------------------------------------------------------------------------

def _slot_instance(seed, n=12, s=3):
    rng = np.random.default_rng(seed)
    sys = profiles.EdgeSystem(n_cameras=n, n_servers=s, n_slots=4,
                              seed=seed)
    tab = sys.horizon(1)
    sid = jnp.asarray(rng.integers(0, s, n).astype(np.int32))
    return (tab.acc[0], tab.xi, tab.size, tab.eff, sid, tab.budgets_b[0],
            tab.budgets_c[0], jnp.float32(rng.uniform(0.0, 3.0)),
            jnp.float32(rng.uniform(1.0, 30.0)))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_solve_slot_pallas_matches_jnp(seed):
    args = _slot_instance(seed)
    d_jnp = bcd.solve_slot(*args, n_servers=3)
    d_pl = bcd.solve_slot(*args, n_servers=3, solver_backend="pallas")
    for f in ("r_idx", "m_idx", "pol"):
        np.testing.assert_array_equal(np.asarray(getattr(d_jnp, f)),
                                      np.asarray(getattr(d_pl, f)),
                                      err_msg=f"{f} seed={seed}")
    for f in ("b", "c", "lam", "mu", "acc", "aopi"):
        np.testing.assert_allclose(np.asarray(getattr(d_pl, f)),
                                   np.asarray(getattr(d_jnp, f)),
                                   rtol=5e-4, err_msg=f"{f} seed={seed}")
    assert float(d_pl.score) == pytest.approx(float(d_jnp.score), rel=1e-4)


def test_solve_slot_pallas_rejects_interior_point():
    args = _slot_instance(5)
    with pytest.raises(ValueError, match="interior"):
        bcd.solve_slot(*args, n_servers=3, method="interior",
                       solver_backend="pallas")
    with pytest.raises(ValueError, match="solver_backend"):
        bcd.solve_slot(*args, n_servers=3, solver_backend="cuda")


def test_churn_mask_resolves_to_jnp_or_raises():
    """The pallas kernels take no churn mask: "auto" resolves a masked
    solve to jnp in ``resolve_spec`` (where the choice is visible), and an
    explicit "pallas" request with a mask raises instead of silently
    running jnp."""
    n = bcd.AUTO_PALLAS_MIN_CAMERAS
    assert bcd.resolve_spec("auto", n).backend == "pallas"
    assert bcd.resolve_spec("auto", n, masked=True).backend == "jnp"
    assert bcd.resolve_spec("pallas", n, masked=True).backend == "pallas"
    args = _slot_instance(0, n=n)
    active = jnp.ones((n,), jnp.float32).at[0].set(0.0)
    with pytest.raises(ValueError, match="churn"):
        bcd.solve_slot(*args, n_servers=3, solver_backend="pallas",
                       active=active)
    dec = bcd.solve_slot(*args, n_servers=3, solver_backend="auto",
                         active=active)
    ref = bcd.solve_slot(*args, n_servers=3, solver_backend="jnp",
                         active=active)
    np.testing.assert_array_equal(np.asarray(dec.b), np.asarray(ref.b))
    assert float(dec.b[0]) == 0.0


def test_rollout_backend_parity():
    """Whole-horizon scan (first-fit assignments traced through the
    layout build) agrees across backends.

    Contract: per-slot parity is float32-tight *given the assignment*
    once the dual search has converged, but the backends' different fp
    summation order can flip a knife-edge first-fit tie into a different
    (equally valid) placement on rare slots — same amplification the
    shard_map caveat documents. The default "fast" effort stops the
    search early, so its per-camera allocations agree only to that
    truncation (a few 1e-3 where the fp order differs); the tight
    per-camera check runs at the converged "seed" effort. Tie-flip
    slots must be rare, and the fleet aggregate must agree closely
    either way."""
    sys = profiles.EdgeSystem(n_cameras=10, n_servers=3, n_slots=8,
                              mean_bandwidth_hz=15e6,
                              mean_compute_flops=20e12)
    tab = sys.horizon(8)
    for effort in ("seed", "fast"):
        r_jnp = lbcd.rollout(tab, 10.0, 0.7, solver_effort=effort)
        r_pl = lbcd.rollout(tab, 10.0, 0.7, solver_backend="pallas",
                            solver_effort=effort)
        same = np.all(np.asarray(r_jnp.assign) == np.asarray(r_pl.assign),
                      axis=1)
        assert same.mean() >= 0.75, f"tie flips on {(~same).sum()}/8 slots"
        if effort == "seed":
            np.testing.assert_allclose(np.asarray(r_pl.aopi)[same],
                                       np.asarray(r_jnp.aopi)[same],
                                       rtol=1e-4)
        np.testing.assert_allclose(np.asarray(r_pl.aopi).mean(axis=1),
                                   np.asarray(r_jnp.aopi).mean(axis=1),
                                   rtol=5e-3)
        np.testing.assert_allclose(np.asarray(r_pl.q), np.asarray(r_jnp.q),
                                   rtol=1e-3, atol=1e-4)


def test_sweep_threads_solver_backend():
    """``scenarios.sweep(..., solver_backend="pallas")`` reproduces the jnp
    sweep. Strict parity is pinned on one device (vmap — no
    ``num_partitions > 1`` rewrite involved); with more devices visible
    (the CI kernel step's 4 virtual ones) the shard_map path must also run
    and agree statistically, per the documented first-fit tie caveat."""
    from repro import scenarios
    from repro.core import profiles as prof

    stacked = prof.stack_horizons(
        [prof.EdgeSystem(n_cameras=6, n_servers=2, n_slots=3,
                         seed=i).horizon(3) for i in range(2)])
    one = jax.devices()[:1]
    r_jnp = scenarios.sweep(stacked, policies=("lbcd", "min"), devices=one)
    r_pl = scenarios.sweep(stacked, policies=("lbcd", "min"), devices=one,
                           solver_backend="pallas")
    for pol in ("lbcd", "min"):
        np.testing.assert_allclose(r_pl.aopi[pol], r_jnp.aopi[pol],
                                   rtol=1e-3, err_msg=pol)
        np.testing.assert_allclose(r_pl.acc[pol], r_jnp.acc[pol],
                                   rtol=1e-3, err_msg=pol)
    if len(jax.devices()) > 1:
        r_sh = scenarios.sweep(stacked, policies=("lbcd",),
                               backend="shard_map",
                               solver_backend="pallas")
        assert r_sh.backend.startswith("shard_map")
        np.testing.assert_allclose(r_sh.aopi["lbcd"].mean(),
                                   r_jnp.aopi["lbcd"].mean(), rtol=0.05)


# ---------------------------------------------------------------------------
# Dispatch structure: one fused call per water-fill, no [N, M, R, 2] HBM
# tensor on the pallas path.
# ---------------------------------------------------------------------------

def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _walk_eqns(sub)


def _subjaxprs(v):
    if isinstance(v, jax_core.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, jax_core.Jaxpr):
        return [v]
    if isinstance(v, (list, tuple)):
        return [j for x in v for j in _subjaxprs(x)]
    return []


def _prim_counts(jaxpr):
    counts = {}
    for eqn in _walk_eqns(jaxpr):
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
    return counts


def _has_aval_shape(jaxpr, shape):
    return any(tuple(getattr(var.aval, "shape", ())) == tuple(shape)
               for eqn in _walk_eqns(jaxpr) for var in eqn.outvars)


def test_waterfill_pallas_is_single_dispatch():
    """The fused allocator is ONE pallas_call; the jnp allocator's outer
    loop re-dispatches segment_sum (scatter-add) every iteration."""
    k, p, pol, mu, sid, B = _setup(12, 3)
    fused = jax.make_jaxpr(functools.partial(
        slot_solver.waterfill_bandwidth, n_servers=3))(k, p, pol, mu, sid, B)
    counts = _prim_counts(fused.jaxpr)
    assert counts.get("pallas_call", 0) == 1
    # The whole dual search runs inside that one call: the only scatter-add
    # is the one-time per-server camera count of the layout build, and the
    # only scatters are the layout's gather table + the single allocation
    # write-back — nothing per outer iteration.
    assert counts.get("scatter-add", 0) <= 1
    assert counts.get("scatter", 0) <= 2

    ref = jax.make_jaxpr(functools.partial(
        allocate.waterfill_bandwidth, n_servers=3))(k, p, pol, mu, sid, B)
    ref_counts = _prim_counts(ref.jaxpr)
    assert ref_counts.get("pallas_call", 0) == 0
    assert ref_counts.get("scatter-add", 0) >= 3   # fill residual per phase


def test_config_argmin_pallas_never_materializes_score_tensor():
    n, m, r = 24, 5, 6
    b, c, acc, xi, size, eff = _config_inputs(n, m=m, r=r)
    args = (b, c, acc, xi, size, eff, 1.0, 10.0)

    ref = jax.make_jaxpr(
        lambda *a: slot_solver.config_argmin(*a, n_total=n,
                                             backend="jnp"))(*args)
    assert _has_aval_shape(ref.jaxpr, (n, m, r, 2))

    fused = jax.make_jaxpr(
        lambda *a: slot_solver.config_argmin(*a, n_total=n,
                                             backend="pallas",
                                             block_n=8))(*args)
    assert not _has_aval_shape(fused.jaxpr, (n, m, r, 2))
    assert _prim_counts(fused.jaxpr).get("pallas_call", 0) == 1


def test_solve_slot_pallas_dispatch_structure():
    """Whole Algorithm-1 solve: every BCD pass is 2 fused dispatches
    (config + one two-water-fill kernel) and the big score tensor never
    hits HBM; ``nofuse`` splits the pair back into two dispatches."""
    args = _slot_instance(0)
    n, n_m, n_r = args[0].shape
    fused = jax.make_jaxpr(functools.partial(
        bcd.solve_slot, n_servers=3, solver_backend="pallas"))(*args)
    counts = _prim_counts(fused.jaxpr)
    # 1 config + 1 fused pair in the BCD body + 1 fused polish pair.
    assert counts.get("pallas_call", 0) == 3
    assert not _has_aval_shape(fused.jaxpr, (n, n_m, n_r, 2))

    seq = jax.make_jaxpr(functools.partial(
        bcd.solve_slot, n_servers=3,
        solver_backend="pallas:nofuse"))(*args)
    # 1 config + 2 water-fills in the BCD body + 2 polish water-fills.
    assert _prim_counts(seq.jaxpr).get("pallas_call", 0) == 5

    ref = jax.make_jaxpr(functools.partial(
        bcd.solve_slot, n_servers=3))(*args)
    assert _has_aval_shape(ref.jaxpr, (n, n_m, n_r, 2))
    assert _prim_counts(ref.jaxpr).get("pallas_call", 0) == 0


# ---------------------------------------------------------------------------
# solver_backend="auto": fleet-size dispatch (BENCH_slot_solver.json shows
# N=30 jnp-favoured under 128-lane padding, N>=300 pallas-favoured).
# ---------------------------------------------------------------------------

def test_resolve_backend_switch_point():
    thr = bcd.AUTO_PALLAS_MIN_CAMERAS
    assert bcd.resolve_backend("auto", thr - 1) == "jnp"
    assert bcd.resolve_backend("auto", thr) == "pallas"
    assert bcd.resolve_backend("auto", 30) == "jnp"        # benched regime
    assert bcd.resolve_backend("auto", 3000) == "pallas"   # benched regime
    # interior-point is jnp-only: auto never hands it to pallas.
    assert bcd.resolve_backend("auto", 10 * thr, method="interior") == "jnp"
    # Explicit backends pass through regardless of fleet size.
    assert bcd.resolve_backend("jnp", 10 * thr) == "jnp"
    assert bcd.resolve_backend("pallas", 2) == "pallas"
    with pytest.raises(ValueError, match="unknown solver_backend"):
        bcd.resolve_backend("nope", 10)


def test_auto_backend_dispatch_choice_pinned():
    """Below the threshold an auto solve traces the pure-jnp program (no
    pallas_call); at the threshold it traces the fused kernels."""
    small = _slot_instance(0, n=bcd.AUTO_PALLAS_MIN_CAMERAS - 108)  # n=20
    jx = jax.make_jaxpr(functools.partial(
        bcd.solve_slot, n_servers=3, solver_backend="auto"))(*small)
    assert _prim_counts(jx.jaxpr).get("pallas_call", 0) == 0

    big = _slot_instance(0, n=bcd.AUTO_PALLAS_MIN_CAMERAS)
    jx = jax.make_jaxpr(functools.partial(
        bcd.solve_slot, n_servers=3, solver_backend="auto"))(*big)
    assert _prim_counts(jx.jaxpr).get("pallas_call", 0) == 3


@pytest.mark.parametrize("n,backend,engaged", [
    (30, "auto", False), (bcd.AUTO_PALLAS_MIN_CAMERAS, "auto", True),
    (30, "pallas", True), (bcd.AUTO_PALLAS_MIN_CAMERAS, "jnp", False)])
def test_rollout_runs_first_fit_as_a_kernel_on_the_pallas_path(n, backend,
                                                               engaged):
    """The rollout places cameras with the ``first_fit`` kernel exactly
    where its slot solver resolves to pallas, once a slot."""
    tab = profiles.EdgeSystem(n_cameras=n, n_servers=3, n_slots=2,
                              seed=0).horizon(2)
    jx = jax.make_jaxpr(functools.partial(
        lbcd.rollout, solver_backend=backend))(tab, 10.0, 0.7)
    names = [e.params["name"] for e in _walk_eqns(jx.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert names.count("slot_solver.first_fit") == int(engaged)


def test_auto_backend_grid_path_switch():
    """The jnp fallback below the switch point also holds on the vmapped
    (V, P_min) grid path: an auto grid over a small fleet traces zero
    pallas_calls, and crosses over with the fleet like ``solve_slot``."""
    vs = jnp.linspace(1.0, 20.0, 2)
    p_mins = jnp.linspace(0.5, 0.8, 2)

    def trace(n):
        tab = profiles.EdgeSystem(n_cameras=n, n_servers=3,
                                  n_slots=2).horizon(2)
        jx = jax.make_jaxpr(lambda t: lbcd.rollout_grid(
            t, vs, p_mins, solver_backend="auto"))(tab)
        return _prim_counts(jx.jaxpr).get("pallas_call", 0)

    assert trace(bcd.AUTO_PALLAS_MIN_CAMERAS - 108) == 0
    assert trace(bcd.AUTO_PALLAS_MIN_CAMERAS) >= 1


# ---------------------------------------------------------------------------
# Spec strings: tiling/fusion knobs and the fleet-size tile policy.
# ---------------------------------------------------------------------------

def test_parse_backend_knobs():
    assert bcd.parse_backend("pallas") == bcd.SolverSpec("pallas", None,
                                                         True)
    assert bcd.parse_backend("pallas:tile=4096") == bcd.SolverSpec(
        "pallas", 4096, True)
    assert bcd.parse_backend("auto:tile=2048:nofuse") == bcd.SolverSpec(
        "auto", 2048, False)
    assert bcd.parse_backend("pallas:nofuse").fuse is False
    assert bcd.parse_backend("jnp:fuse").fuse is True
    # An already-parsed spec passes through untouched.
    spec = bcd.SolverSpec("pallas", 128, False)
    assert bcd.parse_backend(spec) is spec
    with pytest.raises(ValueError, match="unknown solver_backend knob"):
        bcd.parse_backend("pallas:block=4")
    with pytest.raises(ValueError, match="unknown solver_backend"):
        bcd.parse_backend("cuda:tile=2")


@pytest.mark.parametrize("spec", ["jnp", "pallas", "auto:nofuse",
                                  "pallas:tile=4096",
                                  "pallas:tile=2048:nofuse"])
def test_spec_string_reads_back(spec):
    parsed = bcd.parse_backend(spec)
    assert str(parsed) == spec
    assert bcd.parse_backend(str(parsed)) == parsed


def test_resolve_spec_tile_policy():
    thr = bcd.AUTO_TILE_MIN_CAMERAS
    # Auto-tiling engages at the measured streaming-win threshold.
    assert bcd.resolve_spec("auto", thr).tile_n == bcd.DEFAULT_TILE_N
    assert bcd.resolve_spec("pallas", thr).tile_n == bcd.DEFAULT_TILE_N
    assert bcd.resolve_spec("pallas", thr - 1).tile_n is None
    # tile=0 pins the single-program kernel even at scale.
    assert bcd.resolve_spec("pallas:tile=0", 10 * thr).tile_n is None
    # A tile the whole fleet fits inside degenerates to untiled (keeps
    # the fused pair dispatch available).
    assert bcd.resolve_spec(f"pallas:tile={bcd.DEFAULT_TILE_N}",
                            3000).tile_n is None
    assert bcd.resolve_spec("pallas:tile=128", 300).tile_n == 128
    # jnp never tiles; a resolved spec never carries "auto".
    assert bcd.resolve_spec("jnp:tile=4096", 10 * thr).tile_n is None
    assert bcd.resolve_spec("auto", 30) == bcd.SolverSpec("jnp", None, True)
    assert bcd.resolve_spec("auto", 10 * thr).backend == "pallas"


# ---------------------------------------------------------------------------
# Camera-tiled streaming water-fill vs the whole-fleet kernel.
# ---------------------------------------------------------------------------

def _assert_tiled_parity(n, s, seed, lcfsp_frac, tile, budget_lo=2e7,
                         budget_hi=5e7, server_id=None):
    k, p, pol, mu, sid, B = _setup(n, s, seed=seed, lcfsp_frac=lcfsp_frac,
                                   budget_lo=budget_lo, budget_hi=budget_hi,
                                   server_id=server_id)
    b_whole = np.asarray(slot_solver.waterfill_bandwidth(
        k, p, pol, mu, sid, B, n_servers=s))
    b_tiled = np.asarray(slot_solver.waterfill_bandwidth(
        k, p, pol, mu, sid, B, n_servers=s, tile_n=tile))
    # Same Illinois math (deferred bracket update); only the per-server
    # fill-sum accumulation order differs (tile partial sums).
    np.testing.assert_allclose(b_tiled, b_whole, rtol=1e-4, atol=1e-3)
    b_ref = np.asarray(allocate.waterfill_bandwidth(
        k, p, pol, mu, sid, B, n_servers=s))
    np.testing.assert_allclose(b_tiled, b_ref, rtol=2e-4, atol=1e-2)
    return b_tiled, np.asarray(sid), np.asarray(B)


def test_waterfill_tiled_parity_hypothesis():
    """Ragged fleet sizes (not multiples of the tile), mixed policies:
    streamed tiles == whole-fleet kernel == jnp reference."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10_000), st.sampled_from([0.0, 0.5, 1.0]),
           st.sampled_from([(37, 3), (130, 2), (300, 5)]),
           st.sampled_from([128, 256]))
    def inner(seed, frac, ns, tile):
        n, s = ns
        _assert_tiled_parity(n, s, seed, frac, tile)
    inner()


@pytest.mark.parametrize("n,s,tile", [(37, 3, 128), (130, 2, 128),
                                      (300, 5, 256)])
def test_waterfill_tiled_parity_ragged(n, s, tile):
    """Deterministic core of the hypothesis sweep (runs even without
    hypothesis installed): N not a multiple of the tile."""
    for seed in (0, 1):
        _assert_tiled_parity(n, s, seed, lcfsp_frac=0.5, tile=tile)


def test_waterfill_tiled_single_camera_servers():
    n = 6
    _assert_tiled_parity(n, n, seed=3, lcfsp_frac=0.5, tile=128,
                         server_id=np.arange(n, dtype=np.int32))


def test_waterfill_tiled_slack_budget():
    b, sid, B = _assert_tiled_parity(8, 2, seed=11, lcfsp_frac=0.0,
                                     tile=128, budget_lo=5e9,
                                     budget_hi=9e9)
    for s in range(2):
        assert b[sid == s].sum() < 0.9 * B[s]


def _pallas_call_operand_shapes(jaxpr):
    return {tuple(getattr(v.aval, "shape", ()))
            for eqn in _walk_eqns(jaxpr) if eqn.primitive.name ==
            "pallas_call" for v in eqn.invars}


def test_waterfill_tiled_streams_constant_vmem():
    """The whole-fleet kernel takes the f32 ``[S, cap]`` membership
    matrix (and every per-camera vector) as VMEM operands; the tiled
    kernel's only operand is the packed ``[8, Np]`` HBM block —
    membership is recomputed per ``[S, tile]`` window inside the kernel,
    so VMEM holds O(tile), not O(N)."""
    k, p, pol, mu, sid, B = _setup(300, 2)
    cap = slot_solver.server_layout(sid, 2).flat_order.shape[0]
    assert cap > 128
    whole = jax.make_jaxpr(functools.partial(
        slot_solver.waterfill_bandwidth, n_servers=2))(k, p, pol, mu,
                                                       sid, B)
    assert (2, cap) in _pallas_call_operand_shapes(whole.jaxpr)
    tiled = jax.make_jaxpr(functools.partial(
        slot_solver.waterfill_bandwidth, n_servers=2,
        tile_n=128))(k, p, pol, mu, sid, B)
    np_ = -(-cap // 128) * 128
    assert _pallas_call_operand_shapes(tiled.jaxpr) == {(8, np_)}
    assert _prim_counts(tiled.jaxpr).get("pallas_call", 0) == 1


def test_solve_slot_tiled_spec_matches_jnp():
    """A forced-streaming spec string agrees with the jnp solve end to
    end (config indices bitwise, allocations to float32 tolerance)."""
    args = _slot_instance(1, n=40)
    d_jnp = bcd.solve_slot(*args, n_servers=3)
    d_t = bcd.solve_slot(*args, n_servers=3,
                         solver_backend="pallas:tile=128")
    for f in ("r_idx", "m_idx", "pol"):
        np.testing.assert_array_equal(np.asarray(getattr(d_jnp, f)),
                                      np.asarray(getattr(d_t, f)),
                                      err_msg=f)
    for f in ("b", "c", "acc", "aopi"):
        np.testing.assert_allclose(np.asarray(getattr(d_t, f)),
                                   np.asarray(getattr(d_jnp, f)),
                                   rtol=5e-4, err_msg=f)


# ---------------------------------------------------------------------------
# Streaming DOS/JCAB config scans (core.baselines).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,threshold",
                         [("dos", 0.3), ("dos", 3.0),
                          ("jcab", 0.5), ("jcab", 1e-6)])
def test_baseline_argmax_bitwise(mode, threshold):
    """Streaming kernel == materialized argmax, bitwise, incl. a
    non-divisible camera tile and the JCAB all-infeasible fallback
    (threshold=1e-6 makes every config miss the cap)."""
    for seed in range(3):
        b, c, acc, xi, size, eff = _config_inputs(29, seed=seed)
        ref = slot_solver.baseline_argmax_ref(
            b, c, acc, xi, size, eff, mode=mode, threshold=threshold)
        out = slot_solver.baseline_argmax(
            b, c, acc, xi, size, eff, mode=mode, threshold=threshold,
            backend="pallas", block_n=16)
        for name, a, o in zip(("m", "r"), ref, out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(o),
                                          err_msg=f"{name} seed={seed}")


def test_baseline_rollout_backend_parity():
    """Whole-horizon DOS/JCAB rollouts are bitwise identical across the
    scan engines (the kernel reproduces the argmax exactly and everything
    downstream is index arithmetic)."""
    tab = profiles.EdgeSystem(n_cameras=40, n_servers=3,
                              n_slots=4).horizon(4)
    for name, fn in (("dos", baselines.rollout_dos),
                     ("jcab", baselines.rollout_jcab)):
        r_jnp = fn(tab)
        r_pl = fn(tab, solver_backend="pallas")
        for f in ("m_idx", "r_idx"):
            np.testing.assert_array_equal(
                np.asarray(getattr(r_jnp.decision, f)),
                np.asarray(getattr(r_pl.decision, f)),
                err_msg=f"{name} {f}")
        np.testing.assert_array_equal(np.asarray(r_jnp.aopi),
                                      np.asarray(r_pl.aopi),
                                      err_msg=name)


_STRUCTURAL_PRIMS = frozenset({
    "dynamic_slice", "slice", "squeeze", "reshape", "broadcast_in_dim",
    "transpose", "convert_element_type", "copy", "gather", "concatenate",
    "pad", "jit", "scan", "while", "cond", "closed_call", "pallas_call",
    "custom_jvp_call", "custom_vjp_call_jaxpr",
})


def _arith_shape_count(jaxpr, shape):
    """Eqns computing (not merely moving) a value of ``shape``."""
    return sum(1 for eqn in _walk_eqns(jaxpr)
               if eqn.primitive.name not in _STRUCTURAL_PRIMS
               and any(tuple(getattr(v.aval, "shape", ())) == tuple(shape)
                       for v in eqn.outvars))


def test_baseline_rollouts_never_materialize_score_tensor():
    """On the pallas path no [N, M, R] value is ever *computed* — the
    only full-size avals are slices of the input accuracy table. The jnp
    path computes at least five (rates, latency, scores, masks)."""
    tab = profiles.EdgeSystem(n_cameras=24, n_servers=3,
                              n_slots=3).horizon(3)
    n, (n_m, n_r) = 24, tab.xi.shape
    for name, fn in (("dos", baselines.rollout_dos),
                     ("jcab", baselines.rollout_jcab)):
        jx = jax.make_jaxpr(functools.partial(
            fn, solver_backend="jnp"))(tab)
        assert _arith_shape_count(jx.jaxpr, (n, n_m, n_r)) >= 5, name
        px = jax.make_jaxpr(functools.partial(
            fn, solver_backend="pallas"))(tab)
        assert _arith_shape_count(px.jaxpr, (n, n_m, n_r)) == 0, name
        assert _prim_counts(px.jaxpr).get("pallas_call", 0) >= 1, name


# ---------------------------------------------------------------------------
# Large-fleet smoke (CI kernel step runs this with REPRO_SMOKE_10K=1).
# ---------------------------------------------------------------------------

@pytest.mark.skipif(os.environ.get("REPRO_SMOKE_10K") != "1",
                    reason="10^4-camera interpret smoke; set "
                           "REPRO_SMOKE_10K=1 (CI kernel step) to run")
def test_tiled_smoke_10k_cameras():
    """N=10^4 end-to-end solve through the streaming kernel (small tile
    so it actually streams ~5 tiles) against the whole-fleet kernel."""
    n = 10_000
    tab = profiles.EdgeSystem(n_cameras=n, n_servers=3,
                              n_slots=1).horizon(1)
    rng = np.random.default_rng(0)
    sid = jnp.asarray(rng.integers(0, 3, n).astype(np.int32))
    args = (tab.acc[0], tab.xi, tab.size, tab.eff, sid, tab.budgets_b[0],
            tab.budgets_c[0], jnp.float32(1.0), jnp.float32(10.0))
    d_t = bcd.solve_slot(*args, n_servers=3,
                         solver_backend="pallas:tile=2048")
    d_0 = bcd.solve_slot(*args, n_servers=3,
                         solver_backend="pallas:tile=0")
    b = np.asarray(d_t.b)
    assert np.isfinite(b).all() and (b > 0).all()
    B = np.asarray(tab.budgets_b[0])
    sid_np = np.asarray(sid)
    for s in range(3):
        assert b[sid_np == s].sum() <= B[s] * 1.001
    np.testing.assert_array_equal(np.asarray(d_t.m_idx),
                                  np.asarray(d_0.m_idx))
    np.testing.assert_array_equal(np.asarray(d_t.r_idx),
                                  np.asarray(d_0.r_idx))
    np.testing.assert_allclose(b, np.asarray(d_0.b), rtol=1e-3, atol=1e-2)
