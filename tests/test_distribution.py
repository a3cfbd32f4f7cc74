"""Distribution-layer equivalence tests.

These need >1 device, so each runs a subprocess with
--xla_force_host_platform_device_count (the main pytest process keeps the
single real CPU device, per the dry-run isolation rule).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_snippet(body: str, devices: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        import sys
        sys.path.insert(0, {os.path.join(REPO, 'src')!r})
    """) + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_moe_a2a_matches_einsum_path():
    out = run_snippet("""
        import dataclasses
        import jax, jax.numpy as jnp
        from repro import configs
        from repro.models import build
        from repro.models.common import init_params
        from repro.sharding import ctx, rules as rules_mod
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,2), ("data","model"))
        cfg = dataclasses.replace(configs.get("dbrx-132b").reduced(),
                                  n_experts=4, top_k=2,
                                  capacity_factor=2.0)
        model = build(cfg, ep_degree=4)
        params = init_params(model.template(), jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                  cfg.vocab)
        l0, _ = model.forward(params, {"tokens": toks})
        rules = rules_mod.make_rules(cfg, mesh)
        def f(p, b):
            with ctx.activation_rules(rules):
                return model.forward(p, b)
        with mesh:
            l1, _ = jax.jit(f)(params, {"tokens": toks})
        err = float(jnp.max(jnp.abs(l0 - l1)))
        assert err < 2e-3, err
        print("ERR", err)
    """)
    assert "ERR" in out


def test_hoisted_gather_matches_plain_step():
    out = run_snippet("""
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import configs
        from repro.models import build
        from repro.models.common import init_params, pspec_tree
        from repro.sharding import ctx, rules as rules_mod
        from repro.training import optimizer as opt_mod
        from repro.training.train_step import make_train_step
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,2), ("data","model"))
        cfg = configs.get("qwen2.5-3b").reduced()
        model = build(cfg)
        params = init_params(model.template(), jax.random.PRNGKey(0))
        ocfg = opt_mod.AdamWConfig(lr=1e-3)
        opt = opt_mod.init(params, ocfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                  cfg.vocab)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        rules = rules_mod.make_rules(cfg, mesh)
        gr = dict(rules); gr["embed"] = None
        specs = pspec_tree(model.template(), gr)
        def pre(p, _s=specs):
            return jax.tree.map(jax.lax.with_sharding_constraint, p, _s)
        outs = []
        for pc in (None, pre):
            step = make_train_step(model, ocfg, n_microbatches=2,
                                   pre_constrain=pc)
            def f(p, o, b):
                with ctx.activation_rules(rules):
                    return step(p, o, b)
            with mesh:
                p2, _, m = jax.jit(f)(params, opt, batch)
            outs.append((p2, float(m["loss"])))
        assert abs(outs[0][1] - outs[1][1]) < 1e-5
        for a, b in zip(jax.tree.leaves(outs[0][0]),
                        jax.tree.leaves(outs[1][0])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=3e-5)
        print("HOIST-EQ OK")
    """)
    assert "HOIST-EQ OK" in out


def test_plan_cell_compiles_on_small_mesh():
    out = run_snippet("""
        import jax
        from repro import configs
        from repro.configs.base import SHAPES
        from repro.launch.specs import plan_cell
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,4), ("data","model"))
        for shape in ("train_4k", "decode_32k"):
            plan = plan_cell(configs.get("qwen2.5-3b"), SHAPES[shape],
                             mesh)
            c = plan.compile()
            assert c.cost_analysis().get("flops", 0) > 0
        print("PLAN OK")
    """)
    assert "PLAN OK" in out
