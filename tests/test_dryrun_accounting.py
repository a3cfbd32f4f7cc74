"""Dry-run accounting: scan-depth extrapolation + collective parser."""
import dataclasses

import pytest

from repro.launch.dryrun import collective_bytes


def test_collective_parser_on_synthetic_hlo():
    hlo = """
  ENTRY %main {
  %p0 = bf16[16,4096]{1,0} parameter(0)
  %ag = bf16[256,4096]{1,0} all-gather(bf16[16,4096]{1,0} %p0), dims={0}
  %ar = f32[8,128]{1,0} all-reduce(f32[8,128]{1,0} %x), to_apply=%sum
  %rs = f32[2,128]{1,0} reduce-scatter(f32[32,128]{1,0} %y), dims={0}
  %a2a = bf16[4,64]{1,0} all-to-all(bf16[4,64]{1,0} %z), dims={0}
  %cp = u32[7]{0} collective-permute(u32[7]{0} %w)
  %notacoll = f32[9] add(f32[9] %a, f32[9] %b)
}
"""
    out = collective_bytes(hlo)
    assert out["bytes"]["all-gather"] == 256 * 4096 * 2
    assert out["bytes"]["all-reduce"] == 8 * 128 * 4
    assert out["bytes"]["reduce-scatter"] == 2 * 128 * 4
    assert out["bytes"]["all-to-all"] == 4 * 64 * 2
    assert out["bytes"]["collective-permute"] == 7 * 4
    assert out["counts"]["all-gather"] == 1
    assert out["total_bytes"] == sum(out["bytes"].values())


def test_depth_extrapolation_matches_unrolled():
    """On a tiny config: extrapolated flops from depth 1/2 == actual flops
    of a fully-unrolled depth-4 model (within a small tolerance)."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.models import build
    from repro.models.common import abstract_params

    cfg0 = configs.get("qwen2.5-3b").reduced()

    def flops_at(n_layers, force_unroll):
        cfg = dataclasses.replace(cfg0, n_layers=n_layers)
        model = build(cfg)
        tmpl = model.template()
        params = abstract_params(tmpl, jnp.float32)
        batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((2, 32), jnp.int32)}

        def loss(p, b):
            return model.loss(p, b)

        if force_unroll:
            # monkeypatch threshold: unroll everything by splitting params
            import repro.models.transformer as tr
            orig = tr.jax.lax.scan

            def fake_scan(f, init, xs, **kw):
                n = jax.tree.leaves(xs)[0].shape[0]
                carry = init
                ys = []
                for i in range(n):
                    carry, y = f(carry, jax.tree.map(lambda t: t[i], xs))
                    ys.append(y)
                ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
                return carry, ys
            tr.jax.lax.scan = fake_scan
            try:
                c = jax.jit(loss).lower(params, batch).compile()
            finally:
                tr.jax.lax.scan = orig
        else:
            c = jax.jit(loss).lower(params, batch).compile()
        return c.cost_analysis().get("flops", 0.0)

    f1 = flops_at(1, False)     # <=2 periods auto-unrolls
    f2 = flops_at(2, False)
    extrapolated = f1 + 3 * (f2 - f1)
    actual = flops_at(4, True)
    assert extrapolated == pytest.approx(actual, rel=0.05)


def test_fused_attention_memory_correction_positive():
    from repro.launch.roofline import attention_score_bytes
    from repro import configs
    from repro.configs.base import SHAPES
    cfg = configs.get("yi-6b")
    b = attention_score_bytes(cfg, SHAPES["prefill_32k"], n_devices=256)
    assert b > 0
