"""Compile-only rehearsal of the main path's device programs for a v5e.

Every program here is lowered with ``interpret=False`` and compiled by the
TPU compiler for a *described* ``v5e:2x2`` topology — no chip is attached
and nothing runs. This catches what interpret mode cannot: Mosaic layout
and alignment refusals, scoped-VMEM overflows, and programs that do not fit
one chip's 16 GB of HBM. All of it stays in this one file, and the topology
is described inside a fixture only, so a test worker that is not given this
file never loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core import bcd, queues
from repro.kernels.slot_solver import kernel, ops
from repro.serving import tick_plane

M, R = 9, 6                     # the paper's model x resolution grid
HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this env
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, mem
    return compiled.as_text()


F32, I32 = jnp.float32, jnp.int32


def _scan_shapes(n):
    return [((n,), F32), ((n,), F32), ((n, M, R), F32), ((M, R), F32),
            ((R,), F32), ((n,), F32)]


def test_config_argmin_compiles_at_1e5_cameras(one_chip):
    n = 100_000
    hlo = _compile(one_chip, lambda b, c, acc, xi, size, eff, q, v:
                   ops.config_argmin(b, c, acc, xi, size, eff, q, v, n,
                                     backend="pallas", interpret=False),
                   *_scan_shapes(n), ((), F32), ((), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("mode", ["dos", "jcab"])
def test_baseline_argmax_compiles_at_1e5_cameras(one_chip, mode):
    n = 100_000
    hlo = _compile(one_chip, lambda b, c, acc, xi, size, eff, th:
                   ops.baseline_argmax(b, c, acc, xi, size, eff, mode=mode,
                                       threshold=th, backend="pallas",
                                       interpret=False),
                   *_scan_shapes(n), ((), F32))
    assert "tpu_custom_call" in hlo


def test_waterfill_pair_compiles_at_1e4_cameras_100_servers(one_chip):
    n_pad, n_servers = 10_112, 100          # 10^4 cameras, 128-lane padded
    vec = ((n_pad,), F32)
    hlo = _compile(one_chip, lambda *a: kernel.waterfill_pair(*a),
                   vec, vec, ((n_pad,), I32), vec, vec, vec, vec, vec,
                   ((n_servers, n_pad), F32))
    assert "tpu_custom_call" in hlo


def test_waterfill_tiled_compiles_at_1e5_cameras(one_chip):
    tile = bcd.DEFAULT_TILE_N
    n_pad = -(-100_000 // tile) * tile
    hlo = _compile(one_chip, lambda blk: kernel.waterfill_tiled(
        blk, mode="bandwidth", n_servers=100, tile=tile),
        ((len(kernel.TILE_FIELDS), n_pad), F32))
    assert "tpu_custom_call" in hlo


def test_window_sim_f64_compiles(one_chip):
    e, n, f = 8, 300, 4096
    key = jax.random.key(0)
    with jax.enable_x64(True):
        hlo = _compile(
            one_chip, lambda lam, mu, p, pol, keys: queues._window_sim(
                lam, mu, p, pol, keys, 300.0, f, "weibull"),
            ((e, n), jnp.float64), ((e, n), jnp.float64),
            ((e, n), jnp.float64), ((e, n), I32), ((e,), key.dtype))
    assert "while" in hlo


def test_tick_scan_compiles(one_chip):
    f, s = 4096, 300
    f64 = jnp.float64
    with jax.enable_x64(True):
        hlo = _compile(one_chip, tick_plane._tick_scan,
                       ((f, s), f64), ((f, s), f64), ((f, s), f64),
                       ((f, s), f64), ((f, s), f64), ((s,), f64),
                       ((s,), jnp.bool_), ((s,), f64), ((s,), jnp.bool_))
    assert "while" in hlo


def test_vmapped_solve_slot_compiles(one_chip):
    """Grid and scenario sweeps vmap the solver: Pallas's batching rule
    then adds a grid axis over the batch, and every block (SMEM tables
    included) must still tile."""
    k, n, s = 2, 3000, 30
    hlo = _compile(
        one_chip, jax.vmap(lambda acc, xi, size, eff, sid, bb, bc, q, v:
                           bcd.solve_slot(acc, xi, size, eff, sid, bb, bc,
                                          q, v, n_servers=s,
                                          solver_backend="pallas",
                                          interpret=False)),
        ((k, n, M, R), F32), ((k, M, R), F32), ((k, R), F32), ((k, n), F32),
        ((k, n), I32), ((k, s), F32), ((k, s), F32), ((k,), F32),
        ((k,), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n,s", [(816, 125), (10_000, 100), (300, 200)])
def test_first_fit_compiles(one_chip, n, s):
    """Algorithm 2's first-fit kernel: one row of servers (125), more
    than one (200), and cameras over several grid steps (10^4)."""
    from repro.core import binpack
    hlo = _compile(one_chip, lambda b, c, bb, bc: binpack.first_fit_jax(
        b, c, bb, bc, backend="pallas", interpret=False),
        ((n,), F32), ((n,), F32), ((s,), F32), ((s,), F32))
    assert "tpu_custom_call" in hlo


def test_vmapped_first_fit_compiles(one_chip):
    from repro.core import binpack
    k, n, s = 3, 816, 125
    hlo = _compile(one_chip, jax.vmap(
        lambda b, c, bb, bc: binpack.first_fit_jax(
            b, c, bb, bc, backend="pallas", interpret=False)),
        ((k, n), F32), ((k, n), F32), ((k, s), F32), ((k, s), F32))
    assert "tpu_custom_call" in hlo
