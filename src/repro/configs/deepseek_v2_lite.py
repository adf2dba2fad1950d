"""deepseek-v2-lite [moe] — MLA kv_lora=512 (no q-LoRA), YaRN rope, 2 shared
+ 64 routed experts top-6 of width 1408, layer 0 dense of width 10944
[huggingface.co/deepseek-ai/DeepSeek-V2-Lite, config.json].

``from_hf`` reads a DeepSeek-V2 ``config.json`` dict (the catalog's keys)
into a ``ModelConfig``. Departure from the published equations: RoPE
rotates the two halves of the 64 rope dims (``layers.apply_rope``) where
DeepSeek-V2 first permutes interleaved pairs into halves; on weights
drawn at random that is a fixed permutation of the rope columns of
``wq`` and ``wkv_a``, so the function computed is the same family."""
from repro.models.config import ModelConfig

HF_CONFIG = {
    "first_k_dense_replace": 1, "hidden_size": 2048,
    "intermediate_size": 10944, "kv_lora_rank": 512,
    "moe_intermediate_size": 1408, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1, "v_head_dim": 128,
    "vocab_size": 102400,
}


def from_hf(c: dict, **overrides) -> ModelConfig:
    """A ``ModelConfig`` from DeepSeek-V2 ``config.json`` keys."""
    rs = c.get("rope_scaling") or {}
    cfg = ModelConfig(
        name="deepseek-v2-lite", arch_type="moe",
        n_layers=int(c["num_hidden_layers"]), d_model=int(c["hidden_size"]),
        vocab_size=int(c["vocab_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        d_ff=int(c["intermediate_size"]),
        n_experts=int(c["n_routed_experts"]),
        n_shared_experts=int(c["n_shared_experts"]),
        moe_top_k=int(c["num_experts_per_tok"]),
        moe_d_ff=int(c["moe_intermediate_size"]),
        moe_layer_start=int(c["first_k_dense_replace"]),
        norm_topk_prob=bool(c["norm_topk_prob"]),
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        kv_lora_rank=int(c["kv_lora_rank"]),
        qk_rope_dim=int(c["qk_rope_head_dim"]),
        qk_nope_dim=int(c["qk_nope_head_dim"]),
        v_head_dim=int(c["v_head_dim"]), rope_theta=float(c["rope_theta"]),
        yarn_factor=float(rs.get("factor", 0.0)),
        yarn_original_max=int(rs.get("original_max_position_embeddings",
                                     4096)),
        yarn_beta_fast=float(rs.get("beta_fast", 32)),
        yarn_beta_slow=float(rs.get("beta_slow", 1)),
        yarn_mscale=float(rs.get("mscale", 1.0)),
        yarn_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        source="huggingface.co/deepseek-ai/DeepSeek-V2-Lite")
    return cfg.with_(**overrides) if overrides else cfg


FULL = from_hf(HF_CONFIG)


def smoke():
    return FULL.with_(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                      d_ff=128, vocab_size=256, n_experts=16,
                      experts_held=4, n_shared_experts=2, moe_d_ff=32,
                      kv_lora_rank=16, qk_rope_dim=8, qk_nope_dim=16,
                      v_head_dim=16, lora_rank=4, lora_alpha=8.0,
                      dtype="float32", remat=False)
