"""Plain reference of DeepSeek-V2-Lite's decoder, cut to one chip's share
(the JSON file says how), under rank-r LoRA adapters: the equations of
the published ``modeling_deepseek.py`` (V2) in jax.numpy and float32,
with nothing of the program imported by the reference functions.

  layer    h += MLA(RMSNorm(h)); h += FFN(RMSNorm(h)); RMSNorm eps 1e-6
  MLA      q = h Wq; [c, k_pe] = h Wkv_a; k_nope, v = RMSNorm(c) Wkv_b
           (expanded, per head); RoPE on q_pe and k_pe with YaRN's
           frequencies; softmax(q k^T · scale) v, causal; out Wo;
           scale = (128 + 64)^-1/2 · mscale(40, 0.707)^2
  FFN      layer 0: SwiGLU of width 10944. Layers 1-4: the router's
           softmax over all 64 experts, greedy top-6 with no
           renormalisation (routed_scaling_factor 1); for each held
           expert e, gate_e · SwiGLU_e(x) where e is among the token's
           6, plus the shared experts' SwiGLU of width 2 x 1408
  LoRA     y = x W + (alpha / r) (x A) B on wq, wkv_a, wkv_b and wo
  head     RMSNorm, then logits over the 12,800-row vocabulary slice

RoPE rotates the two halves of the rope dims, where DeepSeek-V2 first
permutes interleaved pairs into halves: on random weights a fixed column
permutation of the rope columns of wq and wkv_a (noted in
``repro.configs.deepseek_v2_lite``). Each layer is rematerialised
(``jax.checkpoint``) so that one client's gradient fits at the highest
precision, and the layers and held experts are loops (``lax.scan``) so
that the reference's unrolled local steps compile in reasonable time;
neither changes the mathematics.

``program_init``, ``program_apply`` and ``program_loss`` build the system
under test: ``repro.models.transformer.lora_loss`` through
``engine.init(..., frozen=backbone)``."""
import math

import jax
import jax.numpy as jnp
import numpy as np


def _dims(model):
    g = {k: int(model[k]) for k in (
        "hidden_size", "num_attention_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size",
        "first_k_dense_replace", "num_hidden_layers", "n_routed_experts",
        "experts_held", "num_experts_per_tok", "n_shared_experts",
        "lora_rank")}
    g["vocab"] = int(model["n_classes"])
    g["seq"] = int(model["input_shape"][0])
    return g


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _attn_init(key, g, n):
    d, h, r = g["hidden_size"], g["num_attention_heads"], g["kv_lora_rank"]
    qk = g["qk_nope_head_dim"] + g["qk_rope_head_dim"]
    ks = jax.random.split(key, 4)
    return {"wq": _normal(ks[0], (n, d, h * qk), d ** -0.5),
            "wkv_a": _normal(ks[1], (n, d, r + g["qk_rope_head_dim"]),
                             d ** -0.5),
            "kv_a_norm": {"scale": jnp.ones((n, r))},
            "wkv_b": _normal(ks[2], (n, r, h * (g["qk_nope_head_dim"]
                                                + g["v_head_dim"])),
                             r ** -0.5),
            "wo": _normal(ks[3], (n, h * g["v_head_dim"], d),
                          (h * g["v_head_dim"]) ** -0.5)}


def _swiglu_init(key, lead, d, f):
    ks = jax.random.split(key, 3)
    return {"w_gate": _normal(ks[0], lead + (d, f), d ** -0.5),
            "w_up": _normal(ks[1], lead + (d, f), d ** -0.5),
            "w_down": _normal(ks[2], lead + (f, d), f ** -0.5)}


def frozen_params(key, model):
    """The backbone, laid out as the program reads it: the dense layer(s)
    and the expert layers each stacked on a leading layer axis."""
    g = _dims(model)
    d, v = g["hidden_size"], g["vocab"]
    n_dense = g["first_k_dense_replace"]
    n_moe = g["num_hidden_layers"] - n_dense
    f = g["moe_intermediate_size"]
    ks = jax.random.split(key, 8)
    norms = lambda n: {"scale": jnp.ones((n, d))}  # noqa: E731
    return {
        "embed": _normal(ks[0], (v, d), 0.02),
        "final_norm": {"scale": jnp.ones((d,))},
        "lm_head": _normal(ks[1], (d, v), 0.02),
        "layers": {"attn_norm": norms(n_dense),
                   "attn": _attn_init(ks[2], g, n_dense),
                   "mlp_norm": norms(n_dense),
                   "mlp": _swiglu_init(ks[3], (n_dense,), d,
                                       g["intermediate_size"])},
        "moe_layers": {
            "attn_norm": norms(n_moe),
            "attn": _attn_init(ks[4], g, n_moe),
            "mlp_norm": norms(n_moe),
            "mlp": {"router": {"w": _normal(
                        ks[5], (n_moe, d, g["n_routed_experts"]), 0.02)},
                    "experts": _swiglu_init(
                        ks[6], (n_moe, g["experts_held"]), d, f),
                    "shared": _swiglu_init(
                        ks[7], (n_moe,), d, f * g["n_shared_experts"])}},
    }


def _adapter_dims(g):
    d, h, r = g["hidden_size"], g["num_attention_heads"], g["kv_lora_rank"]
    qk = g["qk_nope_head_dim"] + g["qk_rope_head_dim"]
    return {"wq": (d, h * qk), "wkv_a": (d, r + g["qk_rope_head_dim"]),
            "wkv_b": (r, h * (g["qk_nope_head_dim"] + g["v_head_dim"])),
            "wo": (h * g["v_head_dim"], d)}


def init_params(key, model):
    """The trained tree ω₀: per layer, rank-r adapters A (normal, variance
    1/fan_in) and B (zero) on the four MLA projections."""
    g = _dims(model)
    n_dense = g["first_k_dense_replace"]
    out = {}
    for name, n, k in (("layers", n_dense, jax.random.fold_in(key, 0)),
                       ("moe_layers", g["num_hidden_layers"] - n_dense,
                        jax.random.fold_in(key, 1))):
        stack = {}
        for i, (w, (din, dout)) in enumerate(_adapter_dims(g).items()):
            stack[w] = {"a": _normal(jax.random.fold_in(k, i),
                                     (n, din, g["lora_rank"]), din ** -0.5),
                        "b": jnp.zeros((n, g["lora_rank"], dout))}
        out[name] = stack
    return out


# --------------------------------------------------------------- reference
def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn(model, dim):
    """YaRN's inverse frequencies and attention mscale (modeling_deepseek
    V2: yarn_find_correction_range, yarn_linear_ramp_mask, get_mscale)."""
    rs = model["rope_scaling"]
    base, factor = float(model["rope_theta"]), float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    # on the host in float64, rounded to float32 once
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor
    inv_freq = jnp.asarray(inter * (1.0 - mask) + extra * mask, jnp.float32)

    def get_mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    cos_scale = get_mscale(rs["mscale"]) / get_mscale(rs["mscale_all_dim"])
    return inv_freq, cos_scale, get_mscale(rs["mscale_all_dim"]) ** 2


def _rope(x, inv_freq, cos_scale):
    """x (B, T, heads, dim): rotate the two halves of dim by position."""
    t = jnp.arange(x.shape[1], dtype=jnp.float32)
    ang = t[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * cos_scale)[None, :, None, :]
    sin = (jnp.sin(ang) * cos_scale)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _lora(x, w, ad, scale):
    return x @ w + scale * ((x @ ad["a"]) @ ad["b"])


def _mla(p, ad, x, model, g):
    b, t, _ = x.shape
    h, r = g["num_attention_heads"], g["kv_lora_rank"]
    qn, qr, dv = g["qk_nope_head_dim"], g["qk_rope_head_dim"], g["v_head_dim"]
    s = float(model["lora_alpha"]) / g["lora_rank"]
    eps = float(model["rms_norm_eps"])
    inv_freq, cos_scale, m2 = _yarn(model, qr)
    q = _lora(x, p["wq"], ad["wq"], s).reshape(b, t, h, qn + qr)
    kv_a = _lora(x, p["wkv_a"], ad["wkv_a"], s)
    c = _rmsnorm(kv_a[..., :r], p["kv_a_norm"]["scale"], eps)
    k_pe = _rope(kv_a[..., None, r:], inv_freq, cos_scale)   # (b, t, 1, qr)
    kv = _lora(c, p["wkv_b"], ad["wkv_b"], s).reshape(b, t, h, qn + dv)
    q = jnp.concatenate([q[..., :qn], _rope(q[..., qn:], inv_freq,
                                            cos_scale)], -1)
    k = jnp.concatenate([kv[..., :qn],
                         jnp.broadcast_to(k_pe, (b, t, h, qr))], -1)
    scale = (qn + qr) ** -0.5 * m2
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., qn:]).reshape(b, t, -1)
    return _lora(o, p["wo"], ad["wo"], s)


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def _moe(p, x, model, g):
    """The router over all n_routed_experts outputs; the held experts
    computed densely, each weighted by its gate where it is among the
    token's top k (0 elsewhere); the shared experts added once."""
    gates = jax.nn.softmax(x @ p["router"]["w"], axis=-1)     # (b, t, E)
    top_w, top_i = jax.lax.top_k(gates, g["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    else:
        top_w = top_w * float(model["routed_scaling_factor"])

    def expert(out, e_w):
        e, w = e_w
        w_e = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)  # (b, t)
        return out + w_e[..., None] * _swiglu(w, x), None

    out, _ = jax.lax.scan(expert, _swiglu(p["shared"], x),
                          (jnp.arange(g["experts_held"]), p["experts"]))
    return out


def apply(params, x, model, frozen):
    """(B, T) tokens -> (B, T, V) logits; each stack of layers is a scan
    over its layers."""
    g = _dims(model)
    eps = float(model["rms_norm_eps"])
    h = frozen["embed"][x]
    for name, moe in (("layers", False), ("moe_layers", True)):
        def layer(h, p_ad, moe=moe):
            p, ad = p_ad
            h = h + _mla(p["attn"], ad, _rmsnorm(
                h, p["attn_norm"]["scale"], eps), model, g)
            m_in = _rmsnorm(h, p["mlp_norm"]["scale"], eps)
            return h + (_moe(p["mlp"], m_in, model, g) if moe
                        else _swiglu(p["mlp"], m_in)), None

        h, _ = jax.lax.scan(jax.checkpoint(layer), h,
                            (frozen[name], params[name]))
    h = _rmsnorm(h, frozen["final_norm"]["scale"], eps)
    return h @ frozen["lm_head"]


# ------------------------------------------------------------------- FLOPs
def _per_token(model):
    """Model FLOPs per token, split by how the backward pass treats them:
    (frozen matmuls, attention core, adapters, router)."""
    g = _dims(model)
    d, h, r, t = (g["hidden_size"], g["num_attention_heads"],
                  g["kv_lora_rank"], g["seq"])
    qk = g["qk_nope_head_dim"] + g["qk_rope_head_dim"]
    n_dense = g["first_k_dense_replace"]
    n_moe = g["num_hidden_layers"] - n_dense
    f = g["moe_intermediate_size"]
    proj = 2 * (d * h * qk + d * (r + g["qk_rope_head_dim"])
                + r * h * (g["qk_nope_head_dim"] + g["v_head_dim"])
                + h * g["v_head_dim"] * d)
    lora = 2 * g["lora_rank"] * sum(a + b for a, b in
                                    _adapter_dims(g).values())
    core = 2 * t * h * (qk + g["v_head_dim"])      # q k^T and p v, all keys
    routed = (g["num_experts_per_tok"] * g["experts_held"]
              / g["n_routed_experts"]) * 6 * d * f
    shared = 6 * d * f * g["n_shared_experts"]
    frozen = (g["num_hidden_layers"] * proj
              + n_dense * 6 * d * g["intermediate_size"]
              + n_moe * (routed + shared) + 2 * d * g["vocab"])
    router = n_moe * 2 * d * g["n_routed_experts"]
    nl = g["num_hidden_layers"]
    return frozen, nl * core, nl * lora, router


def forward_flops(model):
    """Model FLOPs of one sequence's forward pass, the routed experts
    counted at k · held / n_routed of a token's rows."""
    g = _dims(model)
    return int(g["seq"] * sum(_per_token(model)))


def gradient_flops(model):
    """Model FLOPs of one sequence's gradient with the backbone frozen:
    the forward; backward, the activation gradient of every matmul (the
    attention core's two operands both) and the adapters' weight
    gradients; rematerialised forwards are not counted."""
    g = _dims(model)
    frozen, core, lora, router = _per_token(model)
    return int(g["seq"] * (2 * frozen + 3 * core + 3 * lora + 2 * router))


# ------------------------------------------------------------- the program
def program_config(model):
    """The program's ``ModelConfig`` at these sizes."""
    from repro.configs.deepseek_v2_lite import from_hf
    c = dict(model, vocab_size=model["n_classes"],
             num_key_value_heads=model["num_attention_heads"])
    return from_hf(c, experts_held=int(model["experts_held"]),
                   lora_rank=int(model["lora_rank"]),
                   lora_alpha=float(model["lora_alpha"]),
                   remat=bool(model["remat"]), dtype="float32",
                   param_dtype="float32")


def program_apply(params, x, model, frozen):
    from repro.models import transformer
    return transformer.lora_forward(params, x, frozen,
                                    program_config(model))[0]


def program_loss(model):
    """The program's loss ``(adapters, batch, frozen) -> (loss, stats)``."""
    from repro.models import transformer
    cfg = program_config(model)
    return lambda p, b, fz: transformer.lora_loss(p, b, fz, cfg)


def program_init(model, frozen, strategy, params0, clients, ecfg, mesh):
    """The program's initial state: the backbone reaches the engine as
    its frozen tree, an argument of every compiled program."""
    import dataclasses

    from repro import engine
    ecfg = dataclasses.replace(ecfg, cohort_chunk=int(model["cohort_chunk"]))
    return engine.init(strategy, program_loss(model), params0, clients, ecfg,
                       mesh=mesh, arena=True, frozen=frozen)
