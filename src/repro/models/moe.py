"""Mixture-of-Experts FFN: top-k router, capacity-bounded einsum dispatch.

TPU-idiomatic GShard/Switch-style dense dispatch, *chunked over the sequence*
so the (B, T, E, C) one-hot tensors stay small enough for VMEM/HBM at 32k
sequence lengths.  Shared experts run as dense gated MLPs on every token
(Qwen-MoE: 4 shared; Llama-4: 1 shared).

Sharding: expert weights are (E, D, F).  For E divisible by the model axis
(llama4: 16) we shard E (pure expert parallelism -> all-to-all dispatch);
otherwise (qwen2: 60) we shard F (tensor parallelism inside each expert).
The choice lives in ``repro.sharding``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import COMPUTE_DTYPE, act_fn, dense_init, init_mlp, mlp_apply, mm


def init_moe(key: jax.Array, cfg: ArchConfig) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    kr, ki, kg, ko, ks = jax.random.split(key, 5)
    params = {
        "router": dense_init(kr, d, e),
        "w_in": jax.vmap(lambda k: dense_init(k, d, f))(jax.random.split(ki, e)),
        "w_gate": jax.vmap(lambda k: dense_init(k, d, f))(jax.random.split(kg, e)),
        "w_out": jax.vmap(lambda k: dense_init(k, f, d, scale=f**-0.5))(
            jax.random.split(ko, e)
        ),
    }
    if cfg.n_shared_experts:
        # Shared experts fused into one wide gated MLP (mathematically the sum
        # of n_shared parallel MLPs of width f).
        params["shared"] = init_mlp(ks, d, cfg.n_shared_experts * f)
    return params


def _capacity(tokens: int, cfg: ArchConfig) -> int:
    c = math.ceil(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def moe_apply(params: dict, x: jax.Array, cfg: ArchConfig) -> tuple[jax.Array, jax.Array]:
    """Apply the MoE FFN.  x: (B, S, D) -> (out, aux_loss)."""
    B, S0, D = x.shape
    cs = min(cfg.moe_chunk, S0)
    pad = (-S0) % cs
    S = S0 + pad
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    nc = S // cs
    E, K = cfg.n_experts, cfg.top_k
    C = _capacity(cs, cfg)

    valid = (jnp.arange(S) < S0).astype(jnp.float32)      # padded tokens get no capacity
    vc = jnp.broadcast_to(valid, (B, S)).reshape(B, nc, cs).transpose(1, 0, 2)
    xc = x.reshape(B, nc, cs, D).transpose(1, 0, 2, 3)   # (nc, B, cs, D)

    def chunk_fn(carry, xs_c):                            # x_c: (B, cs, D)
        x_c, v_c = xs_c
        logits = mm(x_c, params["router"]).astype(jnp.float32)       # (B,cs,E)
        gates = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(gates, K)                        # (B,cs,K)
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)

        # Position of each (token, choice) in its expert queue.
        oh = jax.nn.one_hot(top_i, E, dtype=jnp.float32)              # (B,cs,K,E)
        ohf = oh.reshape(B, cs * K, E)
        pos = jnp.cumsum(ohf, axis=1) - ohf                           # (B,cs*K,E)
        pos_in_e = jnp.sum(pos * ohf, axis=-1).reshape(B, cs, K)      # (B,cs,K)
        keep = (pos_in_e < C).astype(jnp.float32) * v_c[..., None]

        slot_oh = jax.nn.one_hot(pos_in_e.astype(jnp.int32), C, dtype=jnp.float32)
        # (B, cs, K, E, C) -> sum over K for token-level tensors
        dis = jnp.einsum("bske,bskc->bsec", oh * keep[..., None], slot_oh)
        com = jnp.einsum(
            "bske,bskc->bsec", oh * (keep * top_w)[..., None], slot_oh
        )

        xd = jnp.einsum(
            "bsec,bsd->becd", dis.astype(COMPUTE_DTYPE), x_c.astype(COMPUTE_DTYPE),
            preferred_element_type=jnp.float32,
        ).astype(COMPUTE_DTYPE)                                        # (B,E,C,D)
        h = jnp.einsum("becd,edf->becf", xd, params["w_in"].astype(COMPUTE_DTYPE),
                       preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)
        g = jnp.einsum("becd,edf->becf", xd, params["w_gate"].astype(COMPUTE_DTYPE),
                       preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)
        h = act_fn(cfg.act)(g) * h
        y = jnp.einsum("becf,efd->becd", h, params["w_out"].astype(COMPUTE_DTYPE),
                       preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)
        out = jnp.einsum("bsec,becd->bsd", com.astype(COMPUTE_DTYPE), y,
                         preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)

        # Switch-style load-balancing aux loss for this chunk.
        me = jnp.mean(gates, axis=(0, 1))                              # (E,)
        ce = jnp.mean(oh[:, :, 0, :], axis=(0, 1))                     # top-1 assignment
        aux = E * jnp.sum(me * ce)
        return carry + aux, out

    # Remat the chunk body: the (B, cs, E, C) dispatch tensors are recomputed
    # in the backward pass instead of being stored for every chunk.
    aux, outs = jax.lax.scan(jax.checkpoint(chunk_fn), jnp.zeros((), jnp.float32), (xc, vc))
    out = outs.transpose(1, 0, 2, 3).reshape(B, S, D)[:, :S0]
    x = x[:, :S0]
    if "shared" in params:
        out = out + mlp_apply(params["shared"], x, cfg.act)
    return out, aux / nc


# ---------------------------------------------------------------------------
# Held-expert MoE (expert parallelism's share on one device), dropless
# ---------------------------------------------------------------------------
#
# The router scores all ``n_experts``; this device holds the experts
# ``cfg.expert_share = (first, count)`` and computes only their part of the
# result, for every (token, choice) pair routed to them: no capacity, no
# dropped pair.  The pairs are sorted by expert and run through grouped
# matrix products (``jax.lax.ragged_dot``) over the held experts; pairs of
# experts held elsewhere sort past the last group and are not computed.

# Spread of the seeded selection-only expert bias (a pre-trained model's is
# learnt outside the gradient; a random one has to be drawn).
EXPERT_BIAS_STD = 0.05


def expert_share(cfg: ArchConfig) -> tuple[int, int]:
    return cfg.expert_share or (0, cfg.n_experts)


def init_held_moe(key: jax.Array, cfg: ArchConfig) -> dict:
    """Router over all experts, the selection bias, and the held experts'
    SwiGLU weights ``w1``, ``w3`` (E_h, D, F) and ``w2`` (E_h, F, D).  Expert
    ``e`` is drawn from its own key, so the shares of one key compose into
    the whole layer."""
    d, f = cfg.d_model, cfg.expert_d_ff
    first, held = expert_share(cfg)
    kr, kb, kw = jax.random.split(key, 3)

    def expert(k):
        k1, k3, k2 = jax.random.split(k, 3)
        return {
            "w1": dense_init(k1, d, f),
            "w3": dense_init(k3, d, f),
            "w2": dense_init(k2, f, d),
        }

    keys = jax.random.split(kw, cfg.n_experts)[first : first + held]
    return {
        "router": dense_init(kr, d, cfg.n_experts),
        "expert_bias": EXPERT_BIAS_STD * jax.random.normal(kb, (cfg.n_experts,), jnp.float32),
        **jax.vmap(expert)(keys),
    }


def route(params: dict, h: jax.Array, cfg: ArchConfig) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(T, D) -> sigmoid scores (T, E), the selected experts (T, k) and
    their weights (T, k).  The bias only selects; the weights are the
    unbiased scores of the selection, renormalised."""
    scores = jax.nn.sigmoid(jnp.dot(h, params["router"]).astype(jnp.float32))
    biased = scores + jax.lax.stop_gradient(params["expert_bias"])
    _, sel = jax.lax.top_k(biased, cfg.top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    return scores, sel, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)


def dispatch_slots(sel: jax.Array, first: int, held: int) -> jax.Array:
    """The held expert each (token, choice) pair goes to, ``held`` (past the
    last group) for a pair whose expert another device holds."""
    local = sel - first
    return jnp.where((local >= 0) & (local < held), local, held)


def held_moe_apply(params: dict, h: jax.Array, cfg: ArchConfig):
    """The held experts' part of the MoE output for tokens ``h`` (T, D).

    Returns ``(out, stats)``: ``stats["expert_load"]`` (E_h,) counts the
    pairs sorted into each held expert's group, ``stats["routed"]`` the
    pairs the router sent to held experts, and ``stats["computed"]`` the
    rows that reach the output with a nonzero product, counted from the
    products' output itself, not from the groups: equal to ``routed`` where
    nothing is dropped.  Rows past the last group are masked before they
    are counted: on a TPU the grouped products leave them unwritten, and
    what they hold is not a result."""
    first, held = expert_share(cfg)
    k = cfg.top_k
    _, sel, w = route(params, h, cfg)
    slot = dispatch_slots(sel, first, held).reshape(-1)            # (T*k,)
    order = jnp.argsort(slot, stable=True)
    slot_sorted = slot[order]
    group_sizes = jnp.bincount(slot, length=held + 1)[:held].astype(jnp.int32)
    tok = order // k
    # Rows past the last group are left uncomputed by the grouped products,
    # forward and backward (on a TPU they hold whatever the buffer held):
    # select them away before any multiply, so that no value of theirs
    # reaches the output, ``h``'s gradient or the router's.
    valid = (slot_sorted < held)[:, None]
    xs = jnp.where(valid, h[tok], 0.0)
    a = jax.lax.ragged_dot(xs, params["w1"], group_sizes, preferred_element_type=jnp.float32)
    b = jax.lax.ragged_dot(xs, params["w3"], group_sizes, preferred_element_type=jnp.float32)
    y = jax.lax.ragged_dot(jax.nn.silu(a) * b, params["w2"], group_sizes,
                           preferred_element_type=jnp.float32)
    y = jnp.where(valid, y, 0.0) * w.reshape(-1)[order][:, None]
    computed = jnp.sum(jnp.any(y != 0, axis=-1)).astype(jnp.int32)
    out = jnp.zeros(h.shape, jnp.float32).at[tok].add(y)
    routed = jnp.sum((sel >= first) & (sel < first + held)).astype(jnp.int32)
    return out.astype(h.dtype), {"expert_load": group_sizes, "routed": routed, "computed": computed}
