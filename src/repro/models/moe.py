"""Mixture-of-experts FFN with GShard-style capacity dispatch.

Layout follows the canonical GSPMD expert-parallel pattern:

  tokens   [b(data), s, d]
  dispatch [b(data), s, E, C]      C = capacity PER SEQUENCE (cf * s * k/E)
  xin      [E(data), b, C, d]      <- all-to-all (batch-shard -> expert-shard)
  expert   [E(data), d, f(model)]  matmuls
  combine  back to [b(data), s, d] <- all-to-all

Capacity is per-sequence, not global: with a global capacity the one-hot
dispatch einsum costs T_global * E * C_global * d per device — the dry-run
FLOP audit showed this inflating jamba's compute 50x (EXPERIMENTS.md §Perf
iteration 0). Per-sequence capacity keeps dispatch at ~3% of expert FLOPs.

Experts are zero-padded to a multiple of the EP degree (qwen2-moe: 60->64);
the router masks padded experts so no token routes there. Shared experts
(qwen2-moe) are a plain always-on SwiGLU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..sharding import ctx
from .common import (EMBED, EXPERTS, EXPERT_MLP, P)
from .layers import swiglu, swiglu_template


def moe_template(cfg, n_experts_padded: int | None = None):
    d = cfg.d_model
    e = n_experts_padded or cfg.n_experts
    eff = cfg.expert_d_ff
    t = {
        "router": P((d, e), (EMBED, EXPERTS), init="normal", scale=0.02),
        "wi_gate": P((e, d, eff), (EXPERTS, EMBED, EXPERT_MLP)),
        "wi_up": P((e, d, eff), (EXPERTS, EMBED, EXPERT_MLP)),
        "wo": P((e, eff, d), (EXPERTS, EXPERT_MLP, EMBED)),
    }
    if cfg.n_shared_experts:
        t["shared"] = swiglu_template(d, cfg.n_shared_experts * eff)
    return t


def _routing(params, x, cfg, capacity):
    """Shared routing math: returns (dispatch, combine, aux) — all local to
    whatever batch shard ``x`` is (capacity is per-sequence, so routing is
    identical under any batch partitioning)."""
    b, s, d = x.shape
    e = params["router"].shape[1]
    k = cfg.top_k
    logits = jnp.einsum("bsd,de->bse", x, params["router"])
    logits = logits.astype(jnp.float32)
    if e > cfg.n_experts:
        pad_mask = jnp.arange(e) < cfg.n_experts
        logits = jnp.where(pad_mask, logits, -1e30)
    gates_all = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(gates_all, k)
    top_vals = top_vals / jnp.maximum(
        jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)
    oh = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)
    ohf = oh.reshape(b, s * k, e)
    pos = jnp.cumsum(ohf, axis=1) - ohf
    pos = jnp.einsum("bfe,bfe->bf", pos, ohf).reshape(b, s, k)
    keep = pos < capacity
    gate_kept = jnp.where(keep, top_vals, 0.0)
    pos_cl = jnp.where(keep, pos, 0).astype(jnp.int32)
    pos_oh = jax.nn.one_hot(pos_cl, capacity, dtype=jnp.float32)
    sel = oh * keep[..., None]
    dispatch = jnp.einsum("bske,bskc->bsec", sel, pos_oh)
    combine = jnp.einsum("bske,bskc,bsk->bsec", oh, pos_oh, gate_kept)
    frac_tokens = jnp.mean(oh[:, :, 0, :], axis=(0, 1))
    mean_prob = jnp.mean(gates_all, axis=(0, 1))
    aux = cfg.n_experts * jnp.sum(frac_tokens * mean_prob)
    return dispatch, combine, aux


def _experts(params, xin, dtype):
    """Expert matmuls on [e, ..., d] buffers (weights [e, d, f])."""
    g = jnp.einsum("e...d,edf->e...f", xin, params["wi_gate"])
    u = jnp.einsum("e...d,edf->e...f", xin, params["wi_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
    return jnp.einsum("e...f,efd->e...d", h, params["wo"])


MOE_GROUP = 2048     # group-limited routing: capacity & dispatch one-hots
#                      are per group of <=2048 tokens, not per sequence —
#                      at 32k the per-seq dispatch tensor is 16x larger in
#                      both bytes and dispatch FLOPs (EXPERIMENTS.md §Perf
#                      iteration MoE-4).


def moe_apply(params, x, cfg, *, capacity_factor: float | None = None):
    """x: [b, s, d] -> ([b, s, d], aux_loss). Dispatches to the explicit
    shard_map all-to-all path when expert parallelism is active."""
    from ..sharding import ctx as shard_ctx
    rules = shard_ctx.current()
    b0, s0, d = x.shape
    if s0 > MOE_GROUP and s0 % MOE_GROUP == 0:
        x = x.reshape(b0 * s0 // MOE_GROUP, MOE_GROUP, d)
    b, s, d = x.shape
    e = params["router"].shape[1]
    k = cfg.top_k
    cap_f = capacity_factor or cfg.capacity_factor
    capacity = max(int(cap_f * s * k / e), 1)
    capacity = min(capacity, s * k)

    def ungroup(out):
        y, aux = out
        return (y.reshape(b0, s0, d), aux) if s != s0 else (y, aux)

    if rules is not None and rules.get("_mesh") is not None:
        ep_ax = rules.get("experts")
        dp = rules.get("batch")
        dp_axes = (dp,) if isinstance(dp, str) else tuple(dp or ())
        mesh = rules["_mesh"]
        ep = mesh.shape.get(ep_ax, 1) if isinstance(ep_ax, str) else 1
        dp_extent = 1
        for a in dp_axes:
            dp_extent *= mesh.shape.get(a, 1)
        if (ep > 1 and e % ep == 0 and b % dp_extent == 0
                and ep_ax in dp_axes):
            return ungroup(_moe_apply_a2a(params, x, cfg, capacity, mesh,
                                          ep_ax, dp_axes, rules))

    dispatch, combine, aux = _routing(params, x, cfg, capacity)
    xin = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(x.dtype), x)
    yout = _experts(params, xin, x.dtype)
    y = jnp.einsum("bsec,ebcd->bsd", combine.astype(x.dtype), yout)
    y = ctx.constrain(y, ("batch", None, None))

    if "shared" in params:
        y = y + swiglu(params["shared"], x)
    return ungroup((y, aux))


def _moe_apply_a2a(params, x, cfg, capacity, mesh, ep_ax, dp_axes, rules):
    """Expert parallelism with explicit all-to-alls (shard_map).

    The pure-einsum GSPMD path resolves the batch-shard -> expert-shard
    layout change by ALL-GATHERING the activations over batch (25.8 GiB
    f32 per device per MoE layer for dbrx train — found by the collective
    audit, EXPERIMENTS.md §Perf iteration MoE-2). Production MoE does a
    local dispatch followed by an all-to-all of the compact expert buffers;
    the compiler's partitioner does not find that form from constraints, so
    it is written explicitly here. Routing math is per-sequence and hence
    bit-identical to the einsum path.
    """
    from jax.sharding import PartitionSpec as Ps

    ep = mesh.shape[ep_ax]
    dtype = x.dtype
    other_dp = tuple(a for a in dp_axes if a != ep_ax)

    w_specs = {
        "router": Ps(None, None),
        "wi_gate": Ps(ep_ax, None, "model"),
        "wi_up": Ps(ep_ax, None, "model"),
        "wo": Ps(ep_ax, "model", None),
    }
    expert_params = {k: params[k] for k in w_specs}

    def local(xl, wl):
        # xl: [b_loc, s, d] (this device's batch shard).
        dispatch, combine, aux = _routing(
            {"router": wl["router"]}, xl, cfg, capacity)
        xin = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(dtype), xl)
        # [E, b_loc, c, d] -> [E/ep, b_loc*ep, c, d]: the EP all-to-all.
        xin = jax.lax.all_to_all(xin, ep_ax, split_axis=0, concat_axis=1,
                                 tiled=True)
        yo = _experts(wl, xin, dtype)
        # Reduce-scatter the TP partial sums over d instead of a full psum:
        # the return all-to-all and the combine then run on d/TP, and only
        # the final (much smaller) y is gathered — measured -41% collective
        # bytes AND -42% HLO flops on qwen2-moe train (EXPERIMENTS.md
        # §Perf iteration MoE-3).
        yo = jax.lax.psum_scatter(yo, "model", scatter_dimension=3,
                                  tiled=True)
        yo = jax.lax.all_to_all(yo, ep_ax, split_axis=1, concat_axis=0,
                                tiled=True)      # back to [E, b_loc, c, d/TP]
        y = jnp.einsum("bsec,ebcd->bsd", combine.astype(dtype), yo)
        y = jax.lax.all_gather(y, "model", axis=2, tiled=True)
        aux = jax.lax.pmean(aux, dp_axes)
        return y, aux

    all_axes = tuple(mesh.axis_names)
    batch_spec = Ps(dp_axes, None, None)
    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(batch_spec, w_specs),
        out_specs=(batch_spec, Ps()),
        check_vma=False)
    y, aux = mapped(x, expert_params)

    if "shared" in params:
        y = y + swiglu(params["shared"], x)
    return y, aux
