"""Decoder-only transformer LM: dense (GQA), MoE, MLA variants.

Per-layer parameters are *stacked* along a leading L axis and consumed with
``jax.lax.scan`` so HLO size is depth-independent — critical for compiling
the 512-device dry-run of 60-layer models. ``cfg.remat`` wraps each layer
body in ``jax.checkpoint``.

Caches returned by prefill/decode are pytrees whose leaves carry the same
leading L axis (scanned alongside the layer stack).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.layers import cfg_scan, embed_init, dense_init, rmsnorm, rmsnorm_init, swiglu, swiglu_init
from repro.sharding import shard, unshard_fsdp


def _stack_init(layer_init, key, n, *args):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: layer_init(k, *args))(keys)


def _is_mla(cfg):
    return cfg.kv_lora_rank > 0


def _layer_init(key, cfg, moe: bool, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    attn_p = attn.mla_init(k1, cfg, dtype) if _is_mla(cfg) else attn.gqa_init(k1, cfg, dtype)
    mlp_p = moe_mod.moe_init(k2, cfg, dtype) if moe else swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype)
    return {
        "attn_norm": rmsnorm_init(cfg.d_model, dtype),
        "attn": attn_p,
        "mlp_norm": rmsnorm_init(cfg.d_model, dtype),
        "mlp": mlp_p,
    }


def _layer_counts(cfg):
    n_dense = cfg.moe_layer_start if cfg.n_experts else cfg.n_layers
    return n_dense, (cfg.n_layers - n_dense if cfg.n_experts else 0)


def init(key, cfg):
    dtype = jnp.dtype(cfg.param_dtype)
    ke, kl, kd, kh = jax.random.split(key, 4)
    n_dense, n_moe = _layer_counts(cfg)
    params = {
        "embed": embed_init(ke, cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
        "lm_head": dense_init(kh, cfg.d_model, cfg.vocab_size, dtype, scale=0.02),
    }
    if n_dense:
        params["layers"] = _stack_init(functools.partial(_layer_init, cfg=cfg, moe=False, dtype=dtype), kd, n_dense)
    if n_moe:
        params["moe_layers"] = _stack_init(functools.partial(_layer_init, cfg=cfg, moe=True, dtype=dtype), kl, n_moe)
    return params


# ------------------------------------------------------------- layer bodies
def _layer_train(cfg, moe, h, layer_p):
    layer_p = unshard_fsdp(layer_p)
    dt = h.dtype
    a_in = rmsnorm(layer_p["attn_norm"], h)
    if _is_mla(cfg):
        h = h + attn.mla_train(layer_p["attn"], a_in, cfg)
    else:
        h = h + attn.gqa_train(layer_p["attn"], a_in, cfg)
    m_in = rmsnorm(layer_p["mlp_norm"], h)
    if moe:
        m_out, aux = moe_mod.ffn(layer_p["mlp"], m_in, cfg)
    else:
        m_out, aux = swiglu(layer_p["mlp"], m_in), jnp.float32(0.0)
    h = shard(h + m_out, "batch", None, None)
    return h.astype(dt), aux


def _scan_layers(body, h, stacked, cfg):
    fn = jax.checkpoint(body) if cfg.remat else body

    def step(carry, layer_p):
        h, aux = carry
        h, a = fn(h, layer_p)
        return (h, aux + a), None

    (h, aux), _ = cfg_scan(cfg, step, (h, jnp.float32(0.0)), stacked)
    return h, aux


def apply_stack_train(params, h, cfg):
    """Run the layer stack(s) on hidden states h. Returns (h, aux)."""
    aux = jnp.float32(0.0)
    if "layers" in params:
        h, a = _scan_layers(functools.partial(_layer_train, cfg, False), h, params["layers"], cfg)
        aux += a
    if "moe_layers" in params:
        h, a = _scan_layers(functools.partial(_layer_train, cfg, True), h, params["moe_layers"], cfg)
        aux += a
    return h, aux


def apply_stack_prefill(params, h, cfg):
    caches = {}
    if "layers" in params:
        h, caches["layers"] = _scan_prefill(functools.partial(_layer_prefill, cfg, False), h, params["layers"], cfg)
    if "moe_layers" in params:
        h, caches["moe_layers"] = _scan_prefill(functools.partial(_layer_prefill, cfg, True), h, params["moe_layers"], cfg)
    return h, caches


def forward_train(params, tokens, cfg):
    """tokens: (B,S) int32 -> logits (B,S,V), aux loss."""
    dt = jnp.dtype(cfg.dtype)
    h = params["embed"].astype(dt)[tokens]
    h = shard(h, "batch", None, None)
    h, aux = apply_stack_train(params, h, cfg)
    h = rmsnorm(params["final_norm"], h)
    logits = h @ params["lm_head"].astype(dt)
    return shard(logits, "batch", None, "tp"), aux


def lm_loss(params, batch, cfg, forward=forward_train):
    """Next-token cross-entropy (+ MoE aux). batch: {"tokens": (B,S)}."""
    tokens = batch["tokens"]
    logits, aux = forward(params, tokens, cfg)
    logits = logits[:, :-1].astype(jnp.float32)
    targets = tokens[:, 1:]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.einsum("bsv,bsv->bs", logits, onehot)
    loss = jnp.mean(logz - gold)
    return loss + 0.01 * aux


# ------------------------------------------------------------- LoRA fine-tune
# A frozen backbone (the ``init`` tree, read and never trained) under a
# trained tree of rank-``cfg.lora_rank`` adapters on MLA's four
# projections, stacked per layer as the backbone's layers are. The
# adapters are the params; the backbone is the engine's frozen tree.
def lora_init(key, cfg):
    """The trained tree: one set of MLA adapters per layer."""
    dtype = jnp.dtype(cfg.param_dtype)
    out = {}
    for name, n, k in zip(("layers", "moe_layers"), _layer_counts(cfg),
                          jax.random.split(key)):
        if n:
            out[name] = _stack_init(
                lambda kk: attn.mla_lora_init(kk, cfg, dtype), k, n)
    return out


_NO_STATS = {"moe_rows": 0, "moe_max_expert_rows": 0}


def _layer_lora(cfg, moe, h, layer_p, lora_p):
    with jax.named_scope("mla_attention"):
        a_in = rmsnorm(layer_p["attn_norm"], h)
        h = h + attn.mla_train(layer_p["attn"], a_in, cfg, lora=lora_p)
    m_in = rmsnorm(layer_p["mlp_norm"], h)
    if moe:
        m_out, stats = moe_mod.held_moe_ffn(layer_p["mlp"], m_in, cfg)
    else:
        with jax.named_scope("dense_ffn"):
            m_out = swiglu(layer_p["mlp"], m_in)
        stats = {k: jnp.int32(v) for k, v in _NO_STATS.items()}
    return h + m_out, stats


def lora_forward(adapters, tokens, frozen, cfg):
    """tokens (B, S) int32 -> (logits (B, S, V), stats), computed in the
    backbone's dtype; a float32 backbone's matmuls run at float32
    accuracy (``"high"``: three bf16 passes on a TPU, whose default is
    one). ``stats`` sums ``moe_rows`` over the expert layers and keeps the
    largest ``moe_max_expert_rows``."""
    f32 = frozen["embed"].dtype == jnp.float32
    with jax.default_matmul_precision("high" if f32 else None):
        return _lora_forward(adapters, tokens, frozen, cfg)


def _lora_forward(adapters, tokens, frozen, cfg):
    h = jnp.take(frozen["embed"], tokens, axis=0)
    stats = {k: jnp.int32(v) for k, v in _NO_STATS.items()}
    for name, moe in (("layers", False), ("moe_layers", True)):
        if name not in frozen:
            continue
        body = functools.partial(_layer_lora, cfg, moe)
        body = jax.checkpoint(body) if cfg.remat else body

        def step(carry, xs):
            h, st = carry
            h, s = body(h, *xs)
            return (h, {"moe_rows": st["moe_rows"] + s["moe_rows"],
                        "moe_max_expert_rows": jnp.maximum(
                            st["moe_max_expert_rows"],
                            s["moe_max_expert_rows"])}), None

        (h, stats), _ = cfg_scan(cfg, step, (h, stats),
                                 (frozen[name], adapters[name]))
    h = rmsnorm(frozen["final_norm"], h)
    with jax.named_scope("lm_head"):
        logits = h @ frozen["lm_head"].astype(h.dtype)
    return logits, stats


def lora_loss(adapters, batch, frozen, cfg):
    """Mean next-token cross-entropy of ``batch["x"]`` against
    ``batch["y"]`` (each (B, S) int32), and the forward's stats. No
    routing loss: the router is frozen."""
    logits, stats = lora_forward(adapters, batch["x"], frozen, cfg)
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["y"][..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold), stats


# ------------------------------------------------------------- prefill
def _layer_prefill(cfg, moe, h, layer_p):
    layer_p = unshard_fsdp(layer_p)
    a_in = rmsnorm(layer_p["attn_norm"], h)
    if _is_mla(cfg):
        a_out, cache = attn.mla_prefill(layer_p["attn"], a_in, cfg)
    else:
        a_out, cache = attn.gqa_prefill(layer_p["attn"], a_in, cfg)
    h = h + a_out
    m_in = rmsnorm(layer_p["mlp_norm"], h)
    if moe:
        m_out, _ = moe_mod.ffn(layer_p["mlp"], m_in, cfg)
    else:
        m_out = swiglu(layer_p["mlp"], m_in)
    return shard(h + m_out, "batch", None, None), cache


def _scan_prefill(body, h, stacked, cfg):
    fn = jax.checkpoint(body) if cfg.remat else body

    def step(h, layer_p):
        h, cache = fn(h, layer_p)
        return h, cache

    return cfg_scan(cfg, step, h, stacked)


def prefill(params, tokens, cfg):
    """Returns (last-token logits (B,V), cache pytree)."""
    dt = jnp.dtype(cfg.dtype)
    h = params["embed"].astype(dt)[tokens]
    h = shard(h, "batch", None, None)
    h, caches = apply_stack_prefill(params, h, cfg)
    h = rmsnorm(params["final_norm"], h[:, -1:])
    logits = (h @ params["lm_head"].astype(dt))[:, 0]
    return logits, caches


# ------------------------------------------------------------- decode
def _layer_decode(cfg, moe, carry, inp):
    h, pos = carry
    layer_p, cache = inp
    layer_p = unshard_fsdp(layer_p)
    a_in = rmsnorm(layer_p["attn_norm"], h)
    if _is_mla(cfg):
        a_out, new_cache = attn.mla_decode(layer_p["attn"], a_in, cache, pos, cfg)
    else:
        a_out, new_cache = attn.gqa_decode(layer_p["attn"], a_in, cache, pos, cfg)
    h = h + a_out
    m_in = rmsnorm(layer_p["mlp_norm"], h)
    if moe:
        m_out, _ = moe_mod.ffn(layer_p["mlp"], m_in, cfg)
    else:
        m_out = swiglu(layer_p["mlp"], m_in)
    return (h + m_out, pos), new_cache


def decode_step(params, token, caches, pos, cfg):
    """token: (B,) int32; pos: scalar int32 count of tokens already cached.

    Returns (logits (B,V), new caches)."""
    dt = jnp.dtype(cfg.dtype)
    h = params["embed"].astype(dt)[token][:, None, :]    # (B,1,d)
    new_caches = {}
    for name, moe in (("layers", False), ("moe_layers", True)):
        if name not in params:
            continue
        body = functools.partial(_layer_decode, cfg, moe)

        def step(carry, inp):
            return body(carry, inp)

        (h, _), new_caches[name] = cfg_scan(cfg, step, (h, pos), (params[name], caches[name]))
    h = rmsnorm(params["final_norm"], h)
    logits = (h @ params["lm_head"].astype(dt))[:, 0]
    return logits, new_caches


def make_cache(cfg, batch, seq_len, dtype=None):
    """Allocate (or spec) an empty decode cache for a decoder-only model."""
    dt = dtype or jnp.dtype(cfg.dtype)
    S = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    caches = {}
    n_dense = cfg.moe_layer_start if cfg.n_experts else cfg.n_layers
    n_moe = cfg.n_layers - n_dense if cfg.n_experts else 0
    if _is_mla(cfg):
        def one(L):
            return {
                "c_kv": jnp.zeros((L, batch, S, cfg.kv_lora_rank), dt),
                "k_rope": jnp.zeros((L, batch, S, cfg.qk_rope_dim), dt),
            }
    else:
        hd = cfg.resolved_head_dim

        def one(L):
            return {
                "k": jnp.zeros((L, batch, S, cfg.n_kv_heads, hd), dt),
                "v": jnp.zeros((L, batch, S, cfg.n_kv_heads, hd), dt),
            }
    if n_dense:
        caches["layers"] = one(n_dense)
    if n_moe:
        caches["moe_layers"] = one(n_moe)
    return caches
