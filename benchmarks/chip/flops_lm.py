"""Operations and bytes of the LM round, counted from the shapes of the
configuration (``configs/lfm2-8b-a1b-fed.json``'s keys).

Useful work only: the multiply-adds the layer equations need for this
chip's share (its layers, held experts and vocabulary rows), whatever the
compiled program computes to produce them (rematerialised forwards, the
masked upper triangle of causal attention, grouped-product padding).
"""
from __future__ import annotations


def _dims(cfg: dict):
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return D, H, int(cfg["num_key_value_heads"]), D // H


def forward_macs_per_token(cfg: dict) -> float:
    """Multiply-adds of one token's forward pass through the share, with
    each held expert taking its expected ``top_k * held / n_experts`` pairs
    per token and causal attention reading ``(S + 1) / 2`` keys on average."""
    D, H, Hkv, hd = _dims(cfg)
    S, L = int(cfg["seq_len"]), int(cfg["conv_L_cache"])
    E, Eh, k = int(cfg["num_experts"]), int(cfg["n_experts_held"]), int(cfg["num_experts_per_tok"])
    macs = 0.0
    for i, op in enumerate(cfg["layer_types"][: int(cfg["n_layers"])]):
        if op == "full_attention":
            macs += D * H * hd + 2 * D * Hkv * hd + H * hd * D   # q, k, v, o
            macs += 2 * H * hd * (S + 1) / 2                     # q k^T and p v
        else:
            macs += 3 * D * D + D * D + L * D                   # in_proj, out_proj, conv
        if i < int(cfg["num_dense_layers"]):
            macs += 3 * D * int(cfg["intermediate_size"])
        else:
            macs += D * E + k * Eh / E * 3 * D * int(cfg["moe_intermediate_size"])
    return macs + D * int(cfg["vocab"])                         # tied head


def train_flops_per_token(cfg: dict) -> float:
    """Forward and backward: 3 x the forward's 2 flops per multiply-add."""
    return 3.0 * 2.0 * forward_macs_per_token(cfg)


def expert_flops(cfg: dict, pairs: float) -> float:
    """The held experts' grouped products, forward and backward, for
    ``pairs`` computed (token, expert) pairs: 3 products of 2 D F flops per
    pair, times 3 for the backward's two products each."""
    return 3.0 * 3 * 2.0 * pairs * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def expert_bytes(cfg: dict, pairs: float, calls: float) -> float:
    """float32 bytes the grouped products move: each of the 3 forward
    products reads its rows and the held experts' weights and writes its
    rows; each backward product reads two operands and writes one of the
    same shapes.  ``calls`` counts forward passes through one MoE layer
    (layers x steps)."""
    D, Fe, Eh = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"]), int(cfg["n_experts_held"])
    rows = pairs * (D + Fe)                 # one product's input and output rows
    weights = calls * Eh * D * Fe           # one product's weights
    return 4.0 * 3 * 3 * (rows + weights)
