"""Attention: GQA (+ optional sliding window), MLA (DeepSeek-V2 style).

Three entry points per variant:
  *_train   — full-sequence causal attention (teacher forcing)
  *_prefill — full sequence, returns the KV cache for decoding
  *_decode  — one new token against an existing cache

Caches:
  GQA full:    {"k","v": (B, S_max, H_kv, hd)}   (k stored already-roped)
  GQA sliding: same shape with S_max = window, ring-buffer writes
  MLA:         {"c_kv": (B, S_max, r), "k_rope": (B, S_max, rope_dim)}
               — the compressed-KV cache that is MLA's raison d'être.

Long sequences use query-chunked attention (flash-style row blocking) so
the S×S logits matrix never materializes above ``_CHUNK`` rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import (apply_rope, cfg_scan, dense, dense_init,
                                 lora_init, rmsnorm, rmsnorm_init,
                                 rope_inv_freq, rope_mscale, yarn_mscale)
from repro.sharding import shard

_CHUNK = 1024          # query-chunk rows for long-sequence attention
_NEG = -1e30


# =========================================================== GQA weights
def gqa_init(key, cfg, dtype=jnp.float32):
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["b_k"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["b_v"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def _qkv(params, x, cfg, positions):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = x @ params["wq"].astype(dt)
    k = x @ params["wk"].astype(dt)
    v = x @ params["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + params["b_q"].astype(dt)
        k = k + params["b_k"].astype(dt)
        v = v + params["b_v"].astype(dt)
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", None, "tp", None)
    k = shard(k, "batch", None, "tp", None)
    v = shard(v, "batch", None, "tp", None)
    return q, k, v


def _repeat_kv(k, n_heads):
    """(B,S,H_kv,hd) -> (B,S,H,hd) by group broadcast."""
    B, S, Hkv, hd = k.shape
    rep = n_heads // Hkv
    if rep == 1:
        return k
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, Hkv, rep, hd)).reshape(B, S, n_heads, hd)


def _attend_rows(q_rows, k, v, mask_rows, scale):
    """q_rows: (B,R,H,hd); k,v: (B,S,H,hd); mask_rows: (R,S) or (B,R,S)."""
    logits = jnp.einsum("brhd,bshd->bhrs", q_rows, k).astype(jnp.float32) * scale
    logits = jnp.where(mask_rows[..., None, :, :] if mask_rows.ndim == 2 else mask_rows[:, None],
                       logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(q_rows.dtype)
    return jnp.einsum("bhrs,bshd->brhd", probs, v)


def causal_attention(q, k, v, cfg, q_offset=0, scale=None):
    """Chunked causal (optionally sliding-window) attention.

    q: (B,Sq,H,hd); k,v: (B,Sk,H_kv,hd). q_offset = absolute position of
    q[0] relative to k[0] (prefill: 0; not used for decode path).
    ``scale`` multiplies the logits (default 1/sqrt(hd)).
    """
    B, Sq, H, hd = q.shape
    hd_v = v.shape[-1]                 # MLA: v head dim ≠ qk head dim
    Sk = k.shape[1]
    k = _repeat_kv(k, H)
    v = _repeat_kv(v, H)
    if scale is None:
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    kpos = jnp.arange(Sk)

    def mask_for(qpos):
        m = kpos[None, :] <= qpos[:, None]
        if cfg.sliding_window:
            m &= kpos[None, :] > (qpos[:, None] - cfg.sliding_window)
        return m

    if Sq <= _CHUNK:
        qpos = jnp.arange(Sq) + q_offset
        return _attend_rows(q, k, v, mask_for(qpos), scale)

    n_chunks = Sq // _CHUNK
    qc = q.reshape(B, n_chunks, _CHUNK, H, hd).transpose(1, 0, 2, 3, 4)

    def body(carry, args):
        i, q_rows = args
        qpos = i * _CHUNK + jnp.arange(_CHUNK) + q_offset
        out = _attend_rows(q_rows, k, v, mask_for(qpos), scale)
        return carry, out

    _, outs = cfg_scan(cfg, body, None, (jnp.arange(n_chunks), qc))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd_v)


def gqa_train(params, x, cfg, positions=None):
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _qkv(params, x, cfg, positions)
    out = causal_attention(q, k, v, cfg)
    out = out.reshape(B, S, -1)
    return out @ params["wo"].astype(x.dtype)


def gqa_prefill(params, x, cfg, positions=None):
    """Returns (out, cache). Cache holds roped keys at absolute positions."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _qkv(params, x, cfg, positions)
    out = causal_attention(q, k, v, cfg)
    out = out.reshape(B, S, -1) @ params["wo"].astype(x.dtype)
    if cfg.sliding_window and S > cfg.sliding_window:
        k = k[:, -cfg.sliding_window:]
        v = v[:, -cfg.sliding_window:]
    return out, {"k": k, "v": v}


def gqa_decode(params, x, cache, pos, cfg):
    """x: (B,1,d); cache k/v: (B,S_max,H_kv,hd); pos: scalar int32 —
    number of tokens already in context (absolute position of new token)."""
    if cfg.flash_decode:
        from repro.sharding import current_ctx
        ctx = current_ctx()
        if (ctx is not None and ctx.mesh is not None
                and ctx.logical_map.get("tp")
                and cache["k"].shape[1] % ctx.mesh.shape[ctx.logical_map["tp"]] == 0):
            return _gqa_decode_flash(params, x, cache, pos, cfg)
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    dt = x.dtype
    positions = jnp.broadcast_to(pos, (B, 1))
    q, k_new, v_new = _qkv(params, x, cfg, positions)

    S_max = cache["k"].shape[1]
    if cfg.sliding_window:
        slot = pos % S_max
        valid_len = jnp.minimum(pos + 1, S_max)
    else:
        slot = pos
        valid_len = pos + 1
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))

    kk = _repeat_kv(k.astype(dt), cfg.n_heads)
    vv = _repeat_kv(v.astype(dt), cfg.n_heads)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bshd->bhqs", q, kk).astype(jnp.float32) * scale
    mask = jnp.arange(S_max)[None, None, None, :] < valid_len
    logits = jnp.where(mask, logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(dt)
    out = jnp.einsum("bhqs,bshd->bqhd", probs, vv).reshape(B, 1, -1)
    out = out @ params["wo"].astype(dt)
    return out, {"k": k, "v": v}


# =========================================================== flash decode
def _flash_decode_core(axis, windowed, q, k, v, k_new, v_new, pos):
    """Per-shard decode attention over a seq-sharded KV cache (shard_map).

    q: (B,1,H,hd) replicated over `axis`; k/v: (B,S_loc,Hkv,hd) = this
    shard's contiguous cache slab. Two-pass-free online softmax: global max
    and normalizer via pmax/psum of (B,H) stats; context psum'd. Per-step
    collectives are O(B·H·hd) instead of all-gathering the cache."""
    B, S_loc = k.shape[0], k.shape[1]
    n_shards = jax.lax.psum(1, axis)
    S_max = S_loc * n_shards
    idx = jax.lax.axis_index(axis)
    start = idx * S_loc

    slot = pos % S_max if windowed else pos
    valid_len = jnp.minimum(pos + 1, S_max) if windowed else pos + 1
    slot_local = jnp.clip(slot - start, 0, S_loc - 1)
    in_range = (slot >= start) & (slot < start + S_loc)

    k_upd = jax.lax.dynamic_update_slice(k, k_new.astype(k.dtype), (0, slot_local, 0, 0))
    v_upd = jax.lax.dynamic_update_slice(v, v_new.astype(v.dtype), (0, slot_local, 0, 0))
    k = jnp.where(in_range, k_upd, k)
    v = jnp.where(in_range, v_upd, v)

    H = q.shape[2]
    kk = _repeat_kv(k.astype(q.dtype), H)
    vv = _repeat_kv(v.astype(q.dtype), H)
    hd = q.shape[-1]
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bshd->bhqs", q, kk).astype(jnp.float32) * scale
    mask = (start + jnp.arange(S_loc))[None, None, None, :] < valid_len
    logits = jnp.where(mask, logits, _NEG)

    local_max = jnp.max(logits, axis=-1)                        # (B,H,1)
    gmax = jax.lax.pmax(local_max, axis)
    p = jnp.exp(logits - gmax[..., None]) * mask
    denom = jax.lax.psum(jnp.sum(p, axis=-1), axis)             # (B,H,1)
    ctx = jnp.einsum("bhqs,bshd->bqhd", p.astype(q.dtype), vv)
    ctx = jax.lax.psum(ctx, axis)
    out = ctx / denom.transpose(0, 2, 1)[..., None].astype(q.dtype)
    return out, k, v


def _gqa_decode_flash(params, x, cache, pos, cfg):
    """shard_map flash-decode path (requires an active mesh ctx with a tp
    axis and a cache whose seq dim divides it)."""
    try:
        from jax import shard_map
    except ImportError:  # jax < 0.5 keeps it under experimental
        from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.sharding import current_ctx

    ctx = current_ctx()
    mesh = ctx.mesh
    tp = ctx.logical_map.get("tp")
    batch_ax = ctx.logical_map.get("batch")
    B = x.shape[0]
    S_max = cache["k"].shape[1]
    n_tp = mesh.shape[tp]

    dt = x.dtype
    positions = jnp.broadcast_to(pos, (B, 1))
    q, k_new, v_new = _qkv(params, x, cfg, positions)

    b_ax = batch_ax if (batch_ax and B % (
        mesh.shape[batch_ax] if not isinstance(batch_ax, tuple)
        else int(np.prod([mesh.shape[a] for a in batch_ax]))) == 0) else None

    cache_spec = P(b_ax, tp, None, None)
    flat_spec = P(b_ax, None, None, None)
    core = functools.partial(_flash_decode_core, tp, bool(cfg.sliding_window))
    try:
        smap = functools.partial(shard_map, check_vma=False)
        smap(lambda: None, mesh=mesh, in_specs=(), out_specs=P())
    except TypeError:  # jax < 0.6 spells it check_rep
        smap = functools.partial(shard_map, check_rep=False)
    out, k2, v2 = smap(
        core, mesh=mesh,
        in_specs=(flat_spec, cache_spec, cache_spec, flat_spec, flat_spec, P()),
        out_specs=(flat_spec, cache_spec, cache_spec),
    )(q, cache["k"], cache["v"], k_new, v_new, pos)
    out = out.reshape(B, 1, -1) @ params["wo"].astype(dt)
    return out, {"k": k2, "v": v2}


# =========================================================== MLA (DeepSeek)
def mla_init(key, cfg, dtype=jnp.float32):
    """Multi-head Latent Attention: compressed KV (rank r) + decoupled RoPE."""
    H, r = cfg.n_heads, cfg.kv_lora_rank
    qk_n, qk_r, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        # query: full-rank (we omit q-lora; it is an orthogonal memory opt)
        "wq": dense_init(ks[0], cfg.d_model, H * (qk_n + qk_r), dtype),
        # kv down-projection to the latent + the shared rope key
        "wkv_a": dense_init(ks[1], cfg.d_model, r + qk_r, dtype),
        # RMSNorm on the latent before the up-projection (kv_a_layernorm)
        "kv_a_norm": rmsnorm_init(r, dtype),
        # latent up-projection to per-head k_nope and v
        "wkv_b": dense_init(ks[2], r, H * (qk_n + dv), dtype),
        "wo": dense_init(ks[3], H * dv, cfg.d_model, dtype),
    }


def mla_lora_init(key, cfg, dtype=jnp.float32):
    """Rank-``cfg.lora_rank`` adapters on MLA's four projections."""
    H, r, rank = cfg.n_heads, cfg.kv_lora_rank, cfg.lora_rank
    qk_n, qk_r, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dims = {"wq": (cfg.d_model, H * (qk_n + qk_r)),
            "wkv_a": (cfg.d_model, r + qk_r),
            "wkv_b": (r, H * (qk_n + dv)),
            "wo": (H * dv, cfg.d_model)}
    return {n: lora_init(k, *dims[n], rank, dtype)
            for n, k in zip(dims, jax.random.split(key, len(dims)))}


def mla_softmax_scale(cfg) -> float:
    """1/sqrt(qk head dim), times mscale² of YaRN's ``mscale_all_dim``
    where the config scales its rope (DeepSeek-V2)."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _mla_rope(x, positions, cfg):
    return apply_rope(x, positions, cfg.rope_theta,
                      inv_freq=rope_inv_freq(cfg, cfg.qk_rope_dim),
                      mscale=rope_mscale(cfg))


def _lora_scale(cfg) -> float:
    return cfg.lora_alpha / cfg.lora_rank if cfg.lora_rank else 1.0


def _mla_qkv_full(params, x, cfg, positions, lora=None):
    """Expanded (train/prefill) path: materialize per-head K,V. ``lora``
    holds adapters of the four projections (or None)."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    qk_n, qk_r, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    lo, ls = lora or {}, _lora_scale(cfg)
    q = dense(x, params["wq"], lo.get("wq"), ls).reshape(B, S, H, qk_n + qk_r)
    q_nope, q_rope = q[..., :qk_n], q[..., qk_n:]
    q_rope = _mla_rope(q_rope, positions, cfg)

    kv_a = dense(x, params["wkv_a"], lo.get("wkv_a"), ls)   # (B,S,r+qk_r)
    c_kv, k_rope = kv_a[..., :r], kv_a[..., r:]
    c_kv = rmsnorm(params["kv_a_norm"], c_kv)
    k_rope = _mla_rope(k_rope[:, :, None, :], positions, cfg)  # (B,S,1,qk_r)
    kv = dense(c_kv, params["wkv_b"], lo.get("wkv_b"), ls).reshape(
        B, S, H, qk_n + dv)
    k_nope, v = kv[..., :qk_n], kv[..., qk_n:]

    k_rope_b = jnp.broadcast_to(k_rope, (B, S, H, qk_r))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    q_full = shard(q_full, "batch", None, "tp", None)
    k_full = shard(k_full, "batch", None, "tp", None)
    v = shard(v, "batch", None, "tp", None)
    return q_full, k_full, v, c_kv, k_rope[:, :, 0, :]


class _MLACfg:
    """Adapter so causal_attention sees head_dim/window of the MLA variant."""
    def __init__(self, cfg):
        self.sliding_window = cfg.sliding_window
        self.scan_unroll = cfg.scan_unroll


def mla_train(params, x, cfg, positions=None, lora=None):
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v, _, _ = _mla_qkv_full(params, x, cfg, positions, lora)
    out = causal_attention(q, k, v, _MLACfg(cfg),
                           scale=mla_softmax_scale(cfg))
    return dense(out.reshape(B, S, -1), params["wo"],
                 (lora or {}).get("wo"), _lora_scale(cfg))


def mla_prefill(params, x, cfg, positions=None):
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v, c_kv, k_rope = _mla_qkv_full(params, x, cfg, positions)
    out = causal_attention(q, k, v, _MLACfg(cfg),
                           scale=mla_softmax_scale(cfg))
    out = out.reshape(B, S, -1) @ params["wo"].astype(x.dtype)
    if cfg.sliding_window and S > cfg.sliding_window:
        c_kv = c_kv[:, -cfg.sliding_window:]
        k_rope = k_rope[:, -cfg.sliding_window:]
    return out, {"c_kv": c_kv, "k_rope": k_rope}


def mla_decode(params, x, cache, pos, cfg):
    """Weight-absorbed MLA decode: attention runs in the r-dim latent space.

    cache: c_kv (B,S_max,r), k_rope (B,S_max,qk_r). Scores =
    (q_nope·W_uk)·c_kv + q_rope·k_rope; output = (probs·c_kv)·W_uv.
    """
    B = x.shape[0]
    H, r = cfg.n_heads, cfg.kv_lora_rank
    qk_n, qk_r, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = x.dtype
    S_max = cache["c_kv"].shape[1]
    positions = jnp.broadcast_to(pos, (B, 1))

    q = (x @ params["wq"].astype(dt)).reshape(B, 1, H, qk_n + qk_r)
    q_nope, q_rope = q[..., :qk_n], q[..., qk_n:]
    q_rope = _mla_rope(q_rope, positions, cfg)[:, 0]               # (B,H,qk_r)
    q_nope = q_nope[:, 0]                                          # (B,H,qk_n)

    kv_a = (x @ params["wkv_a"].astype(dt))[:, 0]                  # (B,r+qk_r)
    c_new, kr_new = kv_a[..., :r], kv_a[..., r:]
    c_new = rmsnorm(params["kv_a_norm"], c_new)
    kr_new = _mla_rope(kr_new[:, None, None, :], positions, cfg)[:, 0, 0]

    if cfg.sliding_window:
        slot = pos % S_max
        valid_len = jnp.minimum(pos + 1, S_max)
    else:
        slot = pos
        valid_len = pos + 1
    c_kv = jax.lax.dynamic_update_slice(cache["c_kv"], c_new[:, None].astype(cache["c_kv"].dtype), (0, slot, 0))
    k_rope = jax.lax.dynamic_update_slice(cache["k_rope"], kr_new[:, None].astype(cache["k_rope"].dtype), (0, slot, 0))

    wkv_b = params["wkv_b"].astype(dt).reshape(r, H, qk_n + dv)
    w_uk, w_uv = wkv_b[..., :qk_n], wkv_b[..., qk_n:]              # (r,H,qk_n), (r,H,dv)

    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk)               # absorbed query
    scores = jnp.einsum("bhr,bsr->bhs", q_lat, c_kv.astype(dt))
    scores = scores + jnp.einsum("bhp,bsp->bhs", q_rope, k_rope.astype(dt))
    scores = scores.astype(jnp.float32) * mla_softmax_scale(cfg)
    mask = jnp.arange(S_max)[None, None, :] < valid_len
    scores = jnp.where(mask, scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    ctx_lat = jnp.einsum("bhs,bsr->bhr", probs, c_kv.astype(dt))   # latent context
    out = jnp.einsum("bhr,rhv->bhv", ctx_lat, w_uv).reshape(B, 1, H * dv)
    out = out @ params["wo"].astype(dt)
    return out, {"c_kv": c_kv, "k_rope": k_rope}


# =========================================================== cross-attn
def cross_attn_init(key, cfg, dtype=jnp.float32):
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype),
        "w_cross_k": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "w_cross_v": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype),
    }


def cross_kv(params, enc_out, cfg):
    B, Se, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    dt = enc_out.dtype
    k = (enc_out @ params["w_cross_k"].astype(dt)).reshape(B, Se, cfg.n_kv_heads, hd)
    v = (enc_out @ params["w_cross_v"].astype(dt)).reshape(B, Se, cfg.n_kv_heads, hd)
    return {"k": k, "v": v}

def cross_attend(params, x, kv, cfg):
    """x: (B,Sq,d) queries over precomputed encoder k/v (no mask)."""
    B, Sq, _ = x.shape
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = (x @ params["wq"].astype(dt)).reshape(B, Sq, cfg.n_heads, hd)
    k = _repeat_kv(kv["k"].astype(dt), cfg.n_heads)
    v = _repeat_kv(kv["v"].astype(dt), cfg.n_heads)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32) * scale
    probs = jax.nn.softmax(logits, axis=-1).astype(dt)
    out = jnp.einsum("bhqs,bshd->bqhd", probs, v).reshape(B, Sq, -1)
    return out @ params["wo"].astype(dt)


def bidir_attention(params, x, cfg):
    """Encoder self-attention (no causal mask), GQA weights."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _qkv(params, x, cfg, positions)
    kk = _repeat_kv(k, cfg.n_heads)
    vv = _repeat_kv(v, cfg.n_heads)
    scale = 1.0 / jnp.sqrt(cfg.resolved_head_dim).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bshd->bhqs", q, kk).astype(jnp.float32) * scale
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", probs, vv).reshape(B, S, -1)
    return out @ params["wo"].astype(x.dtype)
