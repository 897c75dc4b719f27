"""LFM2 hybrid (LiquidAI LFM2 / LFM2-MoE): gated short convolutions and
QK-norm GQA, each followed by a dense SwiGLU FFN (the leading
``n_dense_layers``) or a held-expert MoE (``repro.models.moe``).

With ``y = RMSNorm(x)``, every layer is

    x <- x + Op(y)                    Op: conv or attention, per ``layer_types``
    x <- x + FFN(RMSNorm(x))          FFN: dense SwiGLU or the MoE

    conv (L = conv_width):  [B, C, u] = split3(W_in y);  w = causal_depthwise_conv(B * u);
                            Op = W_out (C * w)                 (no bias, no activation)
    attention:              q = RoPE(RMSNorm_hd(W_q y)), k = RoPE(RMSNorm_hd(W_k y)),
                            v = W_v y;  causal softmax(q k^T / sqrt(hd)) v per GQA group;  W_o
    logits = RMSNorm(x) E^T           (tied embeddings)

RMSNorm is ``x * rsqrt(mean(x^2) + eps) * w`` with ``w`` from ones.  The
model holds ``cfg.vocab`` rows of the embedding and, of each MoE layer, the
experts ``cfg.expert_share``: the device's share under vocabulary and expert
parallelism.  Parameters are float32 and products run at the backend's
default precision; each layer is rematerialised in the backward pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.attention import chunked_attention, rope
from repro.models.layers import dense_init
from repro.models.moe import expert_share, held_moe_apply, init_held_moe

EMBED_STD = 0.02


def layer_kinds(cfg: ArchConfig) -> list[tuple[str, str]]:
    """(operator, FFN) of each layer: ("conv" | "attn", "dense" | "moe")."""
    ops = cfg.layer_types[: cfg.n_layers]
    return [
        ("attn" if op == "full_attention" else "conv", "dense" if i < cfg.n_dense_layers else "moe")
        for i, op in enumerate(ops)
    ]


def _rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _init_layer(key: jax.Array, cfg: ArchConfig, op: str, ffn: str) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    k_op, k_ffn = jax.random.split(key)
    p = {"op_norm": jnp.ones((d,), jnp.float32), "ffn_norm": jnp.ones((d,), jnp.float32)}
    if op == "conv":
        k1, k2, k3 = jax.random.split(k_op, 3)
        p["conv"] = {
            "in_proj": dense_init(k1, d, 3 * d),
            "conv": dense_init(k2, cfg.conv_width, d),
            "out_proj": dense_init(k3, d, d),
        }
    else:
        kq, kk, kv, ko = jax.random.split(k_op, 4)
        p["attn"] = {
            "q": dense_init(kq, d, cfg.n_heads * hd),
            "k": dense_init(kk, d, cfg.n_kv_heads * hd),
            "v": dense_init(kv, d, cfg.n_kv_heads * hd),
            "o": dense_init(ko, cfg.n_heads * hd, d),
            "q_norm": jnp.ones((hd,), jnp.float32),
            "k_norm": jnp.ones((hd,), jnp.float32),
        }
    if ffn == "dense":
        k1, k3, k2 = jax.random.split(k_ffn, 3)
        p["ffn"] = {
            "w1": dense_init(k1, d, cfg.d_ff),
            "w3": dense_init(k3, d, cfg.d_ff),
            "w2": dense_init(k2, cfg.d_ff, d),
        }
    else:
        p["moe"] = init_held_moe(k_ffn, cfg)
    return p


def init_params(key: jax.Array, cfg: ArchConfig) -> dict:
    """Seeded parameters: weights N(0, 1/fan_in), the embedding N(0, 0.02^2),
    norms at one."""
    k_emb, k_layers = jax.random.split(key)
    return {
        "embed": EMBED_STD * jax.random.normal(k_emb, (cfg.vocab, cfg.d_model), jnp.float32),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "layers": [
            _init_layer(jax.random.fold_in(k_layers, i), cfg, op, ffn)
            for i, (op, ffn) in enumerate(layer_kinds(cfg))
        ],
    }


def _qk_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """QK-norm: RMSNorm of each head of q or k, over its head_dim."""
    return _rmsnorm(x, w, eps)


def _conv(p: dict, y: jax.Array, width: int) -> jax.Array:
    S = y.shape[1]
    b, c, u = jnp.split(y @ p["in_proj"], 3, axis=-1)
    bu = jnp.pad(b * u, ((0, 0), (width - 1, 0), (0, 0)))
    w = sum(p["conv"][j] * bu[:, j : j + S] for j in range(width))
    return (c * w) @ p["out_proj"]


def _attention(p: dict, y: jax.Array, cfg: ArchConfig) -> jax.Array:
    B, S, _ = y.shape
    hd = cfg.resolved_head_dim
    pos = jnp.arange(S, dtype=jnp.int32)
    q = (y @ p["q"]).reshape(B, S, cfg.n_heads, hd)
    k = (y @ p["k"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (y @ p["v"]).reshape(B, S, cfg.n_kv_heads, hd)
    q = _qk_norm(q, p["q_norm"], cfg.norm_eps)
    k = _qk_norm(k, p["k_norm"], cfg.norm_eps)
    q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
    o = chunked_attention(q, k, v, pos, pos, causal=True, chunk=min(cfg.attn_chunk, S))
    return o.reshape(B, S, cfg.n_heads * hd) @ p["o"]


def _layer(p: dict, x: jax.Array, cfg: ArchConfig, op: str, ffn: str):
    y = _rmsnorm(x, p["op_norm"], cfg.norm_eps)
    x = x + (_conv(p["conv"], y, cfg.conv_width) if op == "conv" else _attention(p["attn"], y, cfg))
    h = _rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
    if ffn == "dense":
        f = p["ffn"]
        return x + (jax.nn.silu(h @ f["w1"]) * (h @ f["w3"])) @ f["w2"], None
    B, S, D = h.shape
    out, stats = held_moe_apply(p["moe"], h.reshape(B * S, D), cfg)
    return x + out.reshape(B, S, D), stats


def features(params: dict, cfg: ArchConfig, tokens: jax.Array, *, remat: bool = True):
    """The final normed hidden states (B, S, D) of tokens (B, S), the MoE
    layers' counts stacked per layer (``expert_load`` (n_moe, E_h),
    ``routed`` and ``computed`` (n_moe,))."""
    x = params["embed"][tokens]
    loads, routed, computed = [], [], []
    for p, (op, ffn) in zip(params["layers"], layer_kinds(cfg)):
        fn = functools.partial(_layer, cfg=cfg, op=op, ffn=ffn)
        if remat:
            fn = jax.checkpoint(fn)
        x, stats = fn(p, x)
        if stats is not None:
            loads.append(stats["expert_load"])
            routed.append(stats["routed"])
            computed.append(stats["computed"])
    held = expert_share(cfg)[1]
    count = lambda c: jnp.stack(c) if c else jnp.zeros((0,), jnp.int32)
    stats = {
        "expert_load": jnp.stack(loads) if loads else jnp.zeros((0, held), jnp.int32),
        "routed": count(routed),
        "computed": count(computed),
    }
    return _rmsnorm(x, params["final_norm"], cfg.norm_eps), stats


def forward(params: dict, cfg: ArchConfig, tokens: jax.Array):
    """Logits (B, S, V_held) and the MoE counts."""
    h, stats = features(params, cfg, tokens, remat=False)
    return h @ params["embed"].T, stats


def apply(params: dict, tokens: jax.Array, *, cfg: ArchConfig):
    """The FL path's ``apply_fn`` for token models: ``(h, head, stats)``,
    the final hidden states, the output head (tied: the embedding) with
    ``logits = h @ head.T``, and the MoE counts."""
    h, stats = features(params, cfg, tokens)
    return h, params["embed"], stats
