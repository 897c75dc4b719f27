"""The plain reference of LFM2-8B-A1B's share on one chip, its local SGD
and the per-cluster mean, and the control.

Nothing here imports the program.  It is written from the published
description (LiquidAI LFM2-8B-A1B, ``config.json`` and the LFM2-MoE
modelling code), reading the configuration's own keys (``hidden_size``,
``layer_types``, ...) plus the share this chip holds (``n_layers``,
``first_expert`` and ``n_experts_held``, ``vocab``).  With ``y = RMSNorm(x)``
each layer is ``x <- x + Op(y)``, then ``x <- x + FFN(RMSNorm(x))``:

* conv (L = ``conv_L_cache``): ``[B, C, u] = split3(y W_in)``,
  ``w[t] = sum_j c_j * (B * u)[t - L + 1 + j]`` per channel (zero before the
  sequence starts; no bias, no activation), ``Op = (C * w) W_out``;
* attention: ``q = RoPE(RMSNorm_64(y W_q))``, ``k = RoPE(RMSNorm_64(y W_k))``,
  ``v = y W_v``, causal ``softmax(q k^T / sqrt(64)) v`` with 4 query heads per
  key-value head, ``W_o``; RoPE rotates the two halves of each head;
* the first ``num_dense_layers`` FFNs: ``(silu(h W_1) * (h W_3)) W_2``;
* the others, a MoE: ``s = sigmoid(h W_r)`` over all ``num_experts``,
  ``E = top4(s + b)`` with the fixed expert bias ``b``, weights
  ``g_e = s_e / (sum_E s + 1e-6) * routed_scaling_factor`` (the modelling
  code's epsilon), and of the held experts ``e`` in ``E`` the sum of
  ``g_e * (silu(h W_1e) * (h W_3e)) W_2e``; experts held on other chips add
  nothing here, as in the program;
* ``logits = RMSNorm(x) E^T`` with the tied embedding rows this chip holds;
  RMSNorm is ``x * rsqrt(mean(x^2) + eps) * w``.

Departures from a whole-model forward, each what the chip's share is: the
first ``n_layers`` layers only, ``n_experts_held`` experts, ``vocab`` rows.

The loss is the mean next-token cross-entropy over all ``B * (S - 1)``
predictions, and ``jax.grad`` gives the gradients.  Local training is plain
SGD, ``theta -= lr * g``, one client and one step at a time, on the
program's batches (its key contract: ``split(round_key, m)[i]`` for the
``i``-th sampled client, ``split(client_key, steps)[t]`` for step ``t``,
which trains on the sequences ``randint(key_t, (batch,), 0, n)``).  The
per-cluster mean is the float64 mean weighted by each client's sequences.

Every product runs in float32 at ``Precision.HIGHEST``.  To fit one chip the
attention is computed in blocks of ``QUERY_BLOCK`` queries and each layer,
each expert and each block is recomputed in the backward pass
(``jax.checkpoint``): the arithmetic is the same.

The initial model (``init_params``): weights ``N(0, 1/fan_in)``, the
embedding ``N(0, 0.02^2)``, norms at one, the expert bias
``N(0, EXPERT_BIAS_STD^2)``, from its own keys.  The control holds the
parameters in bfloat16 between local steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
EXPERT_BIAS_STD = 0.05


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def kinds(cfg: dict) -> list[tuple[str, str]]:
    n = int(cfg["n_layers"])
    return [
        ("attn" if t == "full_attention" else "conv",
         "dense" if i < int(cfg["num_dense_layers"]) else "moe")
        for i, t in enumerate(cfg["layer_types"][:n])
    ]


def _dims(cfg: dict):
    D, H, Hkv = int(cfg["hidden_size"]), int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return D, H, Hkv, D // H


# --- the initial model -------------------------------------------------------


def _init(key, cfg: dict) -> dict:
    D, H, Hkv, hd = _dims(cfg)
    F, Fe = int(cfg["intermediate_size"]), int(cfg["moe_intermediate_size"])
    E, Eh, L = int(cfg["num_experts"]), int(cfg["n_experts_held"]), int(cfg["conv_L_cache"])
    counter = iter(range(1 << 20))
    normal = lambda shape, fan_in: jax.random.normal(
        jax.random.fold_in(key, next(counter)), shape, jnp.float32) / np.float32(np.sqrt(fan_in))
    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for op, ffn in kinds(cfg):
        p = {"op_norm": ones(D), "ffn_norm": ones(D)}
        if op == "conv":
            p["conv"] = {"in_proj": normal((D, 3 * D), D), "conv": normal((L, D), L),
                         "out_proj": normal((D, D), D)}
        else:
            p["attn"] = {"q": normal((D, H * hd), D), "k": normal((D, Hkv * hd), D),
                         "v": normal((D, Hkv * hd), D), "o": normal((H * hd, D), H * hd),
                         "q_norm": ones(hd), "k_norm": ones(hd)}
        if ffn == "dense":
            p["ffn"] = {"w1": normal((D, F), D), "w3": normal((D, F), D), "w2": normal((F, D), F)}
        else:
            p["moe"] = {"router": normal((D, E), D),
                        "expert_bias": EXPERT_BIAS_STD * normal((E,), 1),
                        "w1": normal((Eh, D, Fe), D), "w3": normal((Eh, D, Fe), D),
                        "w2": normal((Eh, Fe, D), Fe)}
        layers.append(p)
    return {"embed": 0.02 * normal((int(cfg["vocab"]), D), 1), "final_norm": ones(D),
            "layers": layers}


def init_params(key: jax.Array, cfg: dict, clusters: int | None = None) -> dict:
    """The federation's initial model (one jitted call from ``key``), stacked
    once per cluster when ``clusters`` is given."""
    init = jax.jit(lambda k: _init(k, cfg))
    if clusters is None:
        return init(key)
    return jax.jit(lambda k: jax.tree.map(
        lambda l: jnp.broadcast_to(l, (clusters,) + l.shape), _init(k, cfg)))(key)


# --- the forward pass --------------------------------------------------------


def _conv(p, y, L):
    S = y.shape[1]
    b, c, u = jnp.split(_mm(y, p["in_proj"]), 3, axis=-1)
    bu = b * u
    w = jnp.zeros_like(bu)
    for j in range(L):
        shift = L - 1 - j   # tap j reads position t - shift
        w = w + p["conv"][j] * jnp.pad(bu, ((0, 0), (shift, 0), (0, 0)))[:, :S]
    return _mm(c * w, p["out_proj"])


def _rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(S, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p, y, cfg):
    B, S, _ = y.shape
    D, H, Hkv, hd = _dims(cfg)
    eps, theta = float(cfg["norm_eps"]), float(cfg["rope_theta"])
    q = _rope(_norm(_mm(y, p["q"]).reshape(B, S, H, hd), p["q_norm"], eps), theta)
    k = _rope(_norm(_mm(y, p["k"]).reshape(B, S, Hkv, hd), p["k_norm"], eps), theta)
    v = _mm(y, p["v"]).reshape(B, S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=2)        # query head h reads key-value head h // 4
    v = jnp.repeat(v, H // Hkv, axis=2)
    qb = min(QUERY_BLOCK, S)
    assert S % qb == 0, "sequences are a whole number of query blocks"

    @jax.checkpoint
    def block(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qs, k, precision=HIGHEST) / np.float32(np.sqrt(hd))
        causal = (start + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(0, S, qb))          # (S / qb, B, qb, H, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * hd)
    return _mm(o, p["o"])


def _route(p, h, cfg):
    """Scores (T, E), biased scores (T, E), the selection (T, k) and its
    weights (T, k)."""
    k = int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(_mm(h, p["router"]))
    biased = s + p["expert_bias"] if cfg["use_expert_bias"] else s
    sel = jnp.argsort(-biased, axis=-1, stable=True)[:, :k]
    g = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
    return s, biased, sel, g * float(cfg["routed_scaling_factor"])


def _moe(p, h, cfg):
    first = int(cfg["first_expert"])
    _, _, sel, g = _route(p, h, cfg)

    @jax.checkpoint
    def expert(out, xs):
        e, w1, w3, w2 = xs
        gate = jnp.sum(jnp.where(sel == first + e, g, 0.0), axis=-1)
        return out + gate[:, None] * _mm(jax.nn.silu(_mm(h, w1)) * _mm(h, w3), w2), None

    held = jnp.arange(p["w1"].shape[0])
    out, _ = jax.lax.scan(expert, jnp.zeros_like(h), (held, p["w1"], p["w3"], p["w2"]))
    return out


def _layer(p, x, cfg, op, ffn):
    eps = float(cfg["norm_eps"])
    y = _norm(x, p["op_norm"], eps)
    x = x + (_conv(p["conv"], y, int(cfg["conv_L_cache"])) if op == "conv" else _attention(p["attn"], y, cfg))
    h = _norm(x, p["ffn_norm"], eps)
    if ffn == "dense":
        f = p["ffn"]
        return x + _mm(jax.nn.silu(_mm(h, f["w1"])) * _mm(h, f["w3"]), f["w2"]), None
    B, S, D = h.shape
    h = h.reshape(B * S, D)
    return x + _moe(p["moe"], h, cfg).reshape(B, S, D), h


def forward(params, tokens, cfg: dict):
    """Logits (B, S, vocab) and each MoE layer's input (T, D), the normed
    hidden states its router scores."""
    x = params["embed"][tokens]
    moe_in = []
    for p, (op, ffn) in zip(params["layers"], kinds(cfg)):
        x, h = jax.checkpoint(functools.partial(_layer, cfg=cfg, op=op, ffn=ffn))(p, x)
        if h is not None:
            moe_in.append(h)
    x = _norm(x, params["final_norm"], float(cfg["norm_eps"]))
    return _mm(x, params["embed"].T), moe_in


def loss(params, tokens, cfg: dict):
    logits, _ = forward(params, tokens, cfg)
    logits = logits[:, :-1]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) - gold)


# --- local SGD and the per-cluster mean --------------------------------------


@functools.lru_cache(maxsize=None)
def _jitted(cfg_items: tuple):
    cfg = dict(cfg_items)

    @functools.partial(jax.jit, static_argnames=("batch", "store"))
    def step(params, x, n, key_t, lr, *, batch, store):
        idx = jax.random.randint(key_t, (batch,), 0, jnp.maximum(n, 1))
        params = jax.tree.map(lambda l: l.astype(jnp.float32), params)
        g = jax.grad(loss)(params, x[idx], cfg)
        return jax.tree.map(lambda p, gi: (p - lr * gi).astype(store), params, g)

    fwd = jax.jit(lambda params, tokens: forward(params, tokens, cfg))
    route = jax.jit(lambda p, h: _route(p, h, cfg)[1:])
    return step, fwd, route


def _key(cfg: dict) -> tuple:
    return tuple((k, tuple(v) if isinstance(v, list) else v) for k, v in sorted(cfg.items())
                 if not isinstance(v, dict))


def reference_forward(params, tokens, cfg: dict):
    """Jitted :func:`forward`."""
    return _jitted(_key(cfg))[1](params, tokens)


def reference_route(moe_params, h, cfg: dict):
    """Jitted routing of a MoE layer's input ``h`` (T, D): the biased scores
    (T, E), the selected experts (T, k) and their weights (T, k)."""
    return _jitted(_key(cfg))[2](moe_params, h)


def local_update(params, x, n, key, *, steps, cfg, store=jnp.float32):
    """One client's plain SGD from ``params`` with its round key."""
    step = _jitted(_key(cfg))[0]
    params = jax.tree.map(lambda l: jnp.asarray(l, jnp.float32).astype(store), params)
    x = jnp.asarray(x, jnp.int32)
    for key_t in jax.random.split(key, steps):
        params = step(params, x, jnp.int32(n), key_t, jnp.float32(cfg["lr"]),
                      batch=int(cfg["batch_size"]), store=store)
    return jax.tree.map(lambda l: l.astype(jnp.float32), params)


def local_steps(cfg: dict, n: np.ndarray) -> int:
    """``local_epochs`` passes of the mean client's batches."""
    return max(1, int(cfg["local_epochs"]) * max(1, int(np.mean(n)) // int(cfg["batch_size"])))


def weighted_mean(trees: list, weights) -> dict:
    """Weighted mean of parameter trees, in float64 on the host."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    return jax.tree.map(
        lambda *leaves: sum(wi * np.asarray(l, np.float64) for wi, l in zip(w, leaves)), *trees
    )


def round_updates(before, labels, sampled, key, x, n, cfg, *, store=jnp.float32):
    """``{cluster: float64 parameters}`` for each cluster with sampled
    clients; ``before`` maps a cluster to its parameters at the round's
    start, ``x`` (K, n_max, S) and ``n`` (K,) are the clients' sequences.
    The weighted sum is kept in float64 as each client's update comes in."""
    steps = local_steps(cfg, n)
    keys = jax.random.split(key, len(sampled))
    out = {}
    for z in np.unique(labels[sampled]):
        mine = [i for i, k in enumerate(sampled) if labels[k] == z]
        w = np.asarray(n[sampled[mine]], np.float64)
        total = None
        for wi, i in zip(w / w.sum(), mine):
            trained = local_update(before[int(z)], x[sampled[i]], n[sampled[i]], keys[i],
                                   steps=steps, cfg=cfg, store=store)
            part = jax.tree.map(lambda l: wi * np.asarray(l, np.float64), trained)
            total = part if total is None else jax.tree.map(np.add, total, part)
        out[int(z)] = total
    return out
