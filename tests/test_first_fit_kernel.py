"""Algorithm 2's first-fit as one Pallas kernel (interpret mode off the
TPU) against the XLA ``fori_loop`` and the numpy reference: the same
placement, camera for camera, on roomy and overloaded fleets, with tied
capacities and zero demands, at every fleet size the lane layout has to
handle (one lane, one full row, more than one row)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import binpack, lbcd, profiles


def _instance(n, s, kind, seed=0):
    """float32 demands and budgets; ``kind``: ``roomy`` (every camera
    fits), ``over`` (about 40% of the demand fits, so lines 6-8 place
    the rest), ``equal`` (equal servers: every Eq. (57) volume and every
    untouched ``rem_vol`` ties), ``zero`` (a third of the demands are 0)."""
    rng = np.random.default_rng([n, s, seed])
    b = rng.uniform(0.1, 2.0, n)
    c = rng.uniform(0.1, 2.0, n)
    if kind == "zero":
        b[rng.random(n) < 0.3] = 0.0
        c[rng.random(n) < 0.3] = 0.0
    scale = {"roomy": 1.6, "over": 0.4, "equal": 0.8, "zero": 0.9}[kind]
    if kind == "equal":
        B = np.full(s, b.sum() * scale / s)
        C = np.full(s, c.sum() * scale / s)
    else:
        B = rng.uniform(0.5, 1.0, s) * b.sum() * scale * 2 / s
        C = rng.uniform(0.5, 1.0, s) * c.sum() * scale * 2 / s
    return [np.asarray(x, np.float32) for x in (b, c, B, C)]


@functools.lru_cache(maxsize=None)
def _placer(backend):
    return jax.jit(functools.partial(binpack.first_fit_jax, backend=backend))


def _check(n, s, kind, seed=0):
    args = _instance(n, s, kind, seed)
    kern = np.asarray(_placer("pallas")(*args))
    loop = np.asarray(_placer("jnp")(*args))
    np.testing.assert_array_equal(kern, loop)
    ref = binpack.first_fit(*[x.astype(np.float64) for x in args])
    np.testing.assert_array_equal(kern, ref)
    return kern, args


@pytest.mark.parametrize("n", [1, 30, 129])
@pytest.mark.parametrize("s", [1, 3, 125, 128, 200])
@pytest.mark.parametrize("kind", ["roomy", "over"])
def test_kernel_places_as_the_loop_and_numpy(n, s, kind):
    _check(n, s, kind)


@pytest.mark.parametrize("n,s,kind", [
    (816, 125, "roomy"), (816, 125, "over"), (816, 200, "over"),
    (816, 3, "over"), (4200, 5, "over")])
def test_kernel_places_as_the_loop_and_numpy_at_scale(n, s, kind):
    """816 cameras as in the EUA cell; 4,200 span two grid steps of the
    kernel, the capacities left carrying from one to the next."""
    _check(n, s, kind)


@pytest.mark.parametrize("n,s", [(30, 3), (129, 128), (129, 200),
                                 (816, 125)])
def test_equal_capacities_break_ties_as_argmax_does(n, s):
    """Equal servers tie on ``psi`` (sorted stably) and, while untouched,
    on ``rem_vol``; the fallback takes the lowest server id."""
    kern, args = _check(n, s, "equal")
    bb = args[2]
    assert bb.min() == bb.max()
    # The fleet is overloaded: lines 6-8 placed some cameras.
    assert args[0].sum() > bb.sum()
    assert kern.min() == 0


@pytest.mark.parametrize("n,s", [(30, 3), (129, 125), (816, 128)])
def test_zero_demands(n, s):
    kern, args = _check(n, s, "zero")
    assert (args[0] == 0).any()


def test_fallback_ties_go_to_the_lowest_server_id():
    """Three equal servers, each camera larger than any: every camera
    falls to lines 6-8, and each tie among untouched servers goes to the
    lowest id, whatever their Eq. (57) order."""
    b = np.asarray([5.0, 5.0, 5.0, 5.0], np.float32)
    B = np.asarray([1.0, 1.0, 1.0], np.float32)
    kern = np.asarray(_placer("pallas")(b, b, B, B))
    loop = np.asarray(_placer("jnp")(b, b, B, B))
    np.testing.assert_array_equal(kern, loop)
    np.testing.assert_array_equal(kern, [0, 1, 2, 0])


def test_kernel_under_vmap_matches_the_loop():
    xs = [np.stack(z) for z in zip(*[_instance(40, 130, k, seed=i)
                                      for i, k in enumerate(
                                          ("roomy", "over", "equal"))])]
    kern = jax.vmap(functools.partial(binpack.first_fit_jax,
                                      backend="pallas"))(*xs)
    loop = jax.vmap(binpack.first_fit_jax)(*xs)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(loop))


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="first-fit backend"):
        binpack.first_fit_jax(*_instance(4, 2, "roomy"), backend="nope")


def test_rollout_grid_assignments_equal_the_loops(monkeypatch):
    """``rollout_grid`` on the pallas solver places every slot's cameras
    as it does with first-fit forced back onto the XLA loop."""
    tab = profiles.EdgeSystem(n_cameras=24, n_servers=4, n_slots=6,
                              mean_bandwidth_hz=15e6,
                              mean_compute_flops=20e12, seed=3).horizon(6)
    vs, p_mins = jnp.asarray([5.0, 20.0]), jnp.asarray([0.6, 0.8])
    kern = lbcd.rollout_grid(tab, vs, p_mins, solver_backend="pallas")
    loop_ff = binpack.first_fit_jax

    def loop_only(*args, backend="jnp", interpret=None):
        return loop_ff(*args)
    monkeypatch.setattr(binpack, "first_fit_jax", loop_only)
    jax.clear_caches()
    try:
        loop = lbcd.rollout_grid(tab, vs, p_mins, solver_backend="pallas")
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(kern.assign),
                                  np.asarray(loop.assign))
    np.testing.assert_array_equal(np.asarray(kern.q), np.asarray(loop.q))
