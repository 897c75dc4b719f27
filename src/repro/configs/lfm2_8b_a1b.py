"""LFM2-8B-A1B — gated short convolutions + QK-norm GQA, sigmoid-routed MoE.

[hf:LiquidAI/LFM2-8B-A1B] config.json — 24L (18 conv + 6 full attention in
``layer_types`` order), d_model 2048, 32H / 8KV of 64, RoPE theta 1e6,
conv L=3 without bias; the first 2 layers (``num_dense_layers``) have a
dense SwiGLU FFN of 7168, the other 22 a MoE of 32 experts of 1792, top-4,
sigmoid scores with a selection-only expert bias, normalised top-4
weights, routed scaling 1; vocab 65536; RMSNorm eps 1e-5.  Tied
embeddings are the family's convention (the config lists no key for it).
``repro.models.lfm2`` interprets it.
"""
import dataclasses

from repro.configs.base import ArchConfig

LAYER_TYPES = (
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "full_attention", "conv", "conv",
)

CONFIG = ArchConfig(
    name="lfm2-8b-a1b",
    family="hybrid",
    source="18 conv + 6 GQA layers, MoE 32e top-4 sigmoid [hf:LiquidAI/LFM2-8B-A1B]",
    n_layers=24,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=7168,
    vocab=65536,
    block_kind="lfm2",
    layer_types=LAYER_TYPES,
    n_dense_layers=2,
    conv_width=3,
    n_experts=32,
    top_k=4,
    expert_d_ff=1792,
    norm_eps=1e-5,
    rope_theta=1_000_000.0,
    tie_embeddings=True,
    attn_chunk=256,
    notes="hybrid conv/attention with a dropless held-expert MoE "
    "(repro.models.lfm2); trained per cluster through PACFL's round",
)


# The routing ``repro.models.moe.route`` implements, LFM2-MoE's: sigmoid
# scores, a selection-only expert bias, top-k weights renormalised, unscaled.
ROUTING = {"use_expert_bias": True, "norm_topk_prob": True, "routed_scaling_factor": 1}


def from_hf(c: dict) -> ArchConfig:
    """The :class:`ArchConfig` of a published LFM2-MoE ``config.json``'s
    keys and, where given, a device's share of it: ``n_layers`` (the first
    layers), ``first_expert`` and ``n_experts_held`` (its experts of each
    MoE layer) and ``vocab`` (its rows of the embedding)."""
    for key, want in ROUTING.items():
        if c[key] != want:
            raise ValueError(f"{key}={c[key]!r}: the held-expert MoE implements {want!r} only")
    n_experts = int(c["num_experts"])
    return dataclasses.replace(
        CONFIG,
        n_layers=int(c.get("n_layers", c["num_hidden_layers"])),
        d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["hidden_size"]) // int(c["num_attention_heads"]),
        d_ff=int(c["intermediate_size"]),
        vocab=int(c.get("vocab", c["vocab_size"])),
        layer_types=tuple(c["layer_types"]),
        n_dense_layers=int(c["num_dense_layers"]),
        conv_width=int(c["conv_L_cache"]),
        n_experts=n_experts,
        top_k=int(c["num_experts_per_tok"]),
        expert_d_ff=int(c["moe_intermediate_size"]),
        expert_share=(int(c.get("first_expert", 0)), int(c.get("n_experts_held", n_experts))),
        norm_eps=float(c["norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        attn_chunk=int(c.get("attn_chunk", CONFIG.attn_chunk)),
    )
