"""Optimizer, gradient accumulation, compression, end-to-end loss curve."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build
from repro.models.common import init_params
from repro.training import compression, optimizer as opt_mod
from repro.training.train_step import make_train_step, split_microbatches

KEY = jax.random.PRNGKey(0)


def test_adamw_minimizes_quadratic():
    cfg = opt_mod.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                              schedule="constant", grad_clip=0.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = opt_mod.init(params, cfg)
    for _ in range(300):
        g = {"w": 2.0 * params["w"]}
        params, state, _ = opt_mod.update(params, g, state, cfg)
    assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2


def test_grad_clip_bounds_update():
    cfg = opt_mod.AdamWConfig(lr=1.0, grad_clip=1.0, warmup_steps=0,
                              schedule="constant", weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = opt_mod.init(params, cfg)
    g = {"w": jnp.full(4, 1e6)}
    _, state2, metrics = opt_mod.update(params, g, state, cfg)
    assert float(metrics["grad_norm"]) > 1e5
    # m after one step is (1-b1)*clipped_g; clipped norm == 1.
    m_norm = float(jnp.linalg.norm(state2["m"]["w"])) / (1 - cfg.b1)
    assert m_norm == pytest.approx(1.0, rel=1e-3)


def test_bf16_state_dtype():
    cfg = opt_mod.AdamWConfig(state_dtype="bfloat16")
    params = {"w": jnp.zeros((8, 8))}
    state = opt_mod.init(params, cfg)
    assert state["m"]["w"].dtype == jnp.bfloat16


def test_microbatch_accumulation_matches_full_batch():
    cfg = configs.get("qwen2.5-3b").reduced()
    model = build(cfg)
    params = init_params(model.template(), KEY)
    ocfg = opt_mod.AdamWConfig(lr=1e-3)
    opt_state = opt_mod.init(params, ocfg)
    toks = jax.random.randint(KEY, (4, 33), 0, cfg.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    p1, _, m1 = make_train_step(model, ocfg, 1)(params, opt_state, batch)
    p4, _, m4 = make_train_step(model, ocfg, 4)(params, opt_state, batch)
    assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=1e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_split_microbatches_shapes():
    batch = {"tokens": jnp.zeros((8, 16))}
    out = split_microbatches(batch, 4)
    assert out["tokens"].shape == (4, 2, 16)


def test_quantization_error_bound():
    """Blockwise int8: |x - dq(q(x))| <= scale/2 = max|block|/254."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 1000), st.sampled_from([64, 256]))
    def inner(seed, block):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(0, rng.uniform(0.1, 10), size=300),
                        jnp.float32)
        y = compression.roundtrip(x, block=block)
        blocks = np.asarray(x)
        err = np.abs(np.asarray(y) - blocks)
        # per-element bound: half an int8 step of its block scale
        pad = (-len(blocks)) % block
        bl = np.pad(blocks, (0, pad)).reshape(-1, block)
        scale = np.abs(bl).max(1, keepdims=True) / 127.0
        bound = np.repeat(scale / 2 + 1e-7, block, 1).reshape(-1)[:len(blocks)]
        assert (err <= bound + 1e-6).all()
    inner()


def test_compressed_psum_matches_mean():
    """shard_map compressed all-reduce ~= exact mean within int8 error."""
    import os
    from jax.sharding import PartitionSpec as P
    n = len(jax.devices())
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((n,), ("d",))
    x = jax.random.normal(KEY, (n, 64))

    f = jax.shard_map(lambda v: compression.compressed_psum(v[0], "d")[None],
                      mesh=mesh, in_specs=P("d"), out_specs=P("d"))
    out = np.asarray(f(x))
    expect = np.asarray(jnp.mean(x, 0))
    scale = np.abs(np.asarray(x)).max() / 127.0
    np.testing.assert_allclose(out[0], expect, atol=scale)


def test_loss_decreases_end_to_end():
    """A ~100k-param model trains: loss drops over 30 steps."""
    from repro.launch.train import run
    cfg = configs.get("qwen2.5-3b").reduced()
    out = run(cfg, steps=30, batch=4, seq=64, log_every=0)
    first = np.mean(out["losses"][:5])
    last = np.mean(out["losses"][-5:])
    assert last < first - 0.1, (first, last)
