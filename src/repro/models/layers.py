"""Shared building blocks: inits, norms, RoPE, gated MLPs.

All modules are (init, apply) pure-function pairs over plain dict pytrees.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding import shard


def cfg_scan(cfg, f, init, xs, length=None):
    """lax.scan that fully unrolls when cfg.scan_unroll (cost-probe mode:
    XLA cost_analysis counts while-loop bodies once, so roofline probes
    lower unrolled)."""
    return jax.lax.scan(f, init, xs, length=length,
                        unroll=True if getattr(cfg, "scan_unroll", False) else 1)


# ----------------------------------------------------------------- inits
def dense_init(key, d_in, d_out, dtype=jnp.float32, scale=None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def embed_init(key, vocab, d, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ----------------------------------------------------------------- norms
def rmsnorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps=1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * params["scale"].astype(dt)


def layernorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps=1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * params["scale"].astype(dt) + params["bias"].astype(dt)


# ----------------------------------------------------------------- rope
def rope_freqs(head_dim, theta=10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature factor, 0.1·mscale·ln(scale) + 1."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, base: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's (dim/2,) rotary frequencies (arXiv:2309.00071, as DeepSeek-V2
    computes them): the interpolated ``base^(-2i/dim) / factor`` below
    the correction range, the original frequencies above it, and a
    linear ramp between, the range being where ``beta_fast`` and
    ``beta_slow`` rotations fit in ``original_max`` positions."""
    def corr_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    # in float64, rounded to float32 once
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / float(base) ** (np.arange(0, dim, 2) / dim)
    mask = 1.0 - ramp                      # 1 where the original is kept
    return (extra / factor * (1.0 - mask) + extra * mask).astype(np.float32)


def rope_inv_freq(cfg, dim: int):
    """The (dim/2,) rotary frequencies of a config: YaRN's where
    ``cfg.yarn_factor`` is set, plain RoPE at ``cfg.rope_theta`` else."""
    if cfg.yarn_factor:
        return jnp.asarray(yarn_inv_freq(
            dim, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_max,
            cfg.yarn_beta_fast, cfg.yarn_beta_slow))
    return rope_freqs(dim, cfg.rope_theta)


def rope_mscale(cfg) -> float:
    """The factor on cos and sin under YaRN (1 without it)."""
    if not cfg.yarn_factor:
        return 1.0
    return (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
            / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


def apply_rope(x, positions, theta=10000.0, inv_freq=None, mscale=1.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32.
    ``inv_freq`` (head_dim/2,) replaces the plain frequencies at
    ``theta`` (YaRN), ``mscale`` scales cos and sin."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta) if inv_freq is None else inv_freq
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- lora
def lora_init(key, d_in, d_out, rank, dtype=jnp.float32):
    """A low-rank adapter (arXiv:2106.09685): A (d_in, r) normal with
    variance 1/d_in, B (r, d_out) zero, so a fresh adapter adds nothing."""
    return {"a": dense_init(key, d_in, rank, dtype),
            "b": jnp.zeros((rank, d_out), dtype)}


def dense(x, w, lora=None, scale=1.0):
    """``x @ w``, plus ``scale · (x @ A) @ B`` for an adapter ``lora``."""
    dt = x.dtype
    y = x @ w.astype(dt)
    if lora is not None:
        y = y + scale * ((x @ lora["a"].astype(dt)) @ lora["b"].astype(dt))
    return y


# ----------------------------------------------------------------- mlp
def swiglu_init(key, d_model, d_ff, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


def swiglu(params, x, compute_dtype=None):
    dt = compute_dtype or x.dtype
    g = x @ params["w_gate"].astype(dt)
    u = x @ params["w_up"].astype(dt)
    h = jax.nn.silu(g) * u
    h = shard(h, "batch", None, "tp")
    return h @ params["w_down"].astype(dt)


def gelu_mlp_init(key, d_model, d_ff, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "w_up": dense_init(k1, d_model, d_ff, dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "w_down": dense_init(k2, d_ff, d_model, dtype),
        "b_down": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(params, x, compute_dtype=None):
    dt = compute_dtype or x.dtype
    h = jax.nn.gelu(x @ params["w_up"].astype(dt) + params["b_up"].astype(dt))
    h = shard(h, "batch", None, "tp")
    return h @ params["w_down"].astype(dt) + params["b_down"].astype(dt)
