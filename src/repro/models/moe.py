"""Mixture-of-Experts FFN, in two layouts that the config selects.

``cfg.experts_held == 0``: the layer holds all ``n_experts`` and is laid
out over a mesh by GSPMD (the dry-run's expert parallelism). GShard-style
top-k routing with per-group capacity; dispatch/combine are one-hot
einsums so the layer is pure SPMD (XLA turns the expert-sharded einsums
into all-to-all / all-gather under pjit — visible in the dry-run HLO and
counted by the roofline's collective term). Tokens past capacity drop.

``cfg.experts_held > 0``: the layer is told it holds the first
``experts_held`` of the router's ``n_experts`` (this chip's share under
expert parallelism, with the router's columns in this chip's order;
``held_moe_ffn``). It routes over all of them, sorts the (token, choice)
rows that fall on its experts and runs the grouped matmul over them,
dropping none; what absent experts would add is not computed here.

Supports:
  - phi3.5-moe: 16 experts, top-2
  - deepseek-v2: 160 routed top-6 + 2 shared experts, expert d_ff 1536
  - deepseek-v2-lite: 64 routed top-6 (8 held), 2 shared, d_ff 1408
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.layers import dense_init, swiglu, swiglu_init
from repro.sharding import shard


def moe_init(key, cfg, dtype=jnp.float32):
    E = cfg.experts_held or cfg.n_experts
    d = cfg.d_model
    ff = cfg.moe_d_ff or cfg.d_ff
    k_r, k_g, k_u, k_d, k_s = jax.random.split(key, 5)
    params = {
        "router": {"w": dense_init(k_r, d, cfg.n_experts, dtype, scale=0.02)},
        "experts": {
            "w_gate": (jax.random.normal(k_g, (E, d, ff)) / jnp.sqrt(d)).astype(dtype),
            "w_up": (jax.random.normal(k_u, (E, d, ff)) / jnp.sqrt(d)).astype(dtype),
            "w_down": (jax.random.normal(k_d, (E, ff, d)) / jnp.sqrt(ff)).astype(dtype),
        },
    }
    if cfg.n_shared_experts:
        params["shared"] = swiglu_init(k_s, d, ff * cfg.n_shared_experts, dtype)
    return params


def _group(x, group_size):
    """(B,S,d) -> (G,g,d) with g | B*S."""
    B, S, d = x.shape
    tokens = B * S
    g = min(group_size, tokens)
    while tokens % g:
        g -= 1
    return x.reshape(tokens // g, g, d), (B, S)


def moe_ffn(params, x, cfg, group_size: int = 0):
    """Returns (out, aux_loss). x: (B,S,d).

    group_size (default cfg.moe_group_size) sets the dispatch granularity:
    capacity c ∝ group tokens, and dispatch/combine einsum cost ∝ E·c·d per
    token — smaller groups cut dispatch flops AND the (G,g,E,c) one-hot
    footprint linearly (§Perf hillclimb #1)."""
    dt = x.dtype
    E, k = cfg.n_experts, cfg.moe_top_k
    xg, (B, S) = _group(x, group_size or cfg.moe_group_size)
    G, g, d = xg.shape
    cap = max(int(k * g / E * cfg.capacity_factor), 1)
    cap = -(-cap // 4) * 4 if cap >= 4 else cap            # pad to multiple of 4

    logits = (xg @ params["router"]["w"].astype(dt)).astype(jnp.float32)  # (G,g,E)
    gates = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(gates, k)            # (G,g,k)
    top_vals = top_vals / (jnp.sum(top_vals, axis=-1, keepdims=True) + 1e-9)

    # load-balance auxiliary loss (Switch/GShard form)
    me = jnp.mean(gates, axis=1)                                   # (G,E)
    onehot_all = jax.nn.one_hot(top_idx[..., 0], E, dtype=jnp.float32)
    ce = jnp.mean(onehot_all, axis=1)                              # (G,E)
    aux = jnp.mean(jnp.sum(me * ce, axis=-1)) * E

    # position of each (token, slot) within its expert's capacity buffer
    slot_onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.int32)      # (G,g,k,E)
    flat = slot_onehot.transpose(0, 2, 1, 3).reshape(G, k * g, E)  # slot-major
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat                # (G,k*g,E)
    pos = jnp.sum(flat * pos_in_expert, axis=-1)                   # (G,k*g)
    pos = pos.reshape(G, k, g).transpose(0, 2, 1)                  # (G,g,k)
    keep = pos < cap

    # dispatch/combine tensors
    cap_onehot = jax.nn.one_hot(pos, cap, dtype=dt) * keep[..., None].astype(dt)  # (G,g,k,c)
    exp_onehot = jax.nn.one_hot(top_idx, E, dtype=dt)                             # (G,g,k,E)
    dispatch = jnp.einsum("gske,gskc->gsec", exp_onehot, cap_onehot)              # (G,g,E,c)
    combine = jnp.einsum("gsk,gske,gskc->gsec", top_vals.astype(dt), exp_onehot, cap_onehot)

    expert_in = jnp.einsum("gsec,gsd->egcd", dispatch, xg)         # (E,G,c,d)
    expert_in = shard(expert_in, "expert", None, None, None)
    w_g = params["experts"]["w_gate"].astype(dt)
    w_u = params["experts"]["w_up"].astype(dt)
    w_d = params["experts"]["w_down"].astype(dt)
    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", expert_in, w_g)) * jnp.einsum(
        "egcd,edf->egcf", expert_in, w_u
    )
    expert_out = jnp.einsum("egcf,efd->egcd", h, w_d)              # (E,G,c,d)
    expert_out = shard(expert_out, "expert", None, None, None)

    out = jnp.einsum("gsec,egcd->gsd", combine, expert_out)        # (G,g,d)
    out = out.reshape(B, S, d)
    if "shared" in params:
        out = out + swiglu(params["shared"], x)
    return out, aux


def route(router_w, x, cfg):
    """Softmax over all ``cfg.n_experts`` router outputs (in float32),
    greedy top-k, renormalised where ``cfg.norm_topk_prob`` else scaled
    by ``cfg.routed_scaling_factor`` (DeepSeek-V2's gate). x: (n, d) ->
    weights (n, k) f32, expert ids (n, k) int32."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.moe_top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    else:
        w = w * cfg.routed_scaling_factor
    return w, idx


def held_moe_ffn(params, x, cfg):
    """The expert layer of a chip that holds ``cfg.experts_held`` of the
    router's experts. x: (B, S, d) -> (out, stats).

    Every token is routed over all experts (``route``). The (token,
    choice) rows whose expert is held here are sorted by expert and run
    through the grouped matmul (``ops.frozen_expert_matmul``) with no
    capacity: a held expert takes every row routed to it. The rows are
    ``n·k`` at most, the static capacity of the sort, so none can drop.
    Each output row is weighted by its gate and added back to its token;
    the shared experts, which every chip computes alike, add once.
    ``stats``: ``moe_rows`` (rows computed on held experts) and
    ``moe_max_expert_rows`` (the most on one held expert)."""
    B, S, d = x.shape
    held, k = cfg.experts_held, cfg.moe_top_k
    xt = x.reshape(B * S, d)
    n = xt.shape[0]
    with jax.named_scope("moe_router"):
        w, idx = route(params["router"]["w"], xt, cfg)
        local = idx.reshape(-1)                              # (n·k,)
        group = jnp.where(local < held, local, held).astype(jnp.int32)
        order = jnp.argsort(group, stable=True)    # held rows first, by expert
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
        tok = (order // k).astype(jnp.int32)
        live = jnp.arange(n * k) < jnp.sum(sizes)
        gate = jnp.where(live, w.reshape(-1)[order], 0.0).astype(x.dtype)
    with jax.named_scope("moe_experts"):
        e = params["experts"]
        # the kernel leaves rows past the held ones unwritten, forward and
        # backward: both masks keep them out of the values and gradients
        rows = jnp.where(live[:, None], jnp.take(xt, tok, axis=0), 0.0)
        h = jax.nn.silu(ops.frozen_expert_matmul(rows, e["w_gate"], sizes)) \
            * ops.frozen_expert_matmul(rows, e["w_up"], sizes)
        y = ops.frozen_expert_matmul(h, e["w_down"], sizes)
        y = jnp.where(live[:, None], y, 0.0) * gate[:, None]
        out = jnp.zeros_like(xt).at[tok].add(y)
    if "shared" in params:
        with jax.named_scope("shared_experts"):
            out = out + swiglu(params["shared"], xt)
    stats = {"moe_rows": jnp.sum(sizes),
             "moe_max_expert_rows": jnp.max(sizes)}
    return out.reshape(B, S, d), stats


def ffn(params, x, cfg):
    """The expert FFN of a decoder layer, (out, aux loss): the held-share
    layer where ``cfg.experts_held`` is set (no routing loss: its router
    is not trained here), else the capacity dispatch."""
    if cfg.experts_held:
        return held_moe_ffn(params, x, cfg)[0], jnp.float32(0.0)
    return moe_ffn(params, x, cfg)
