"""Client-side local training: jitted, vmapped across clients.

All clients' data is pre-stacked into fixed-shape arrays (padding by cycling
samples) so one ``vmap(local_sgd)`` call trains every sampled client of a
round — the CPU-friendly *and* TPU-friendly formulation (the client axis maps
onto the mesh data axis in ``launch/fl_train.py``).  A model too large for
one copy per sampled client trains them in sequence instead
(``Strategy._run_sequential``).

Two kinds of client data: rows of float features with integer labels
(classification, :func:`ce_loss`), and integer token sequences (a client's
``x`` is ``(n_sequences, seq_len)`` ids, its labels unused), trained on the
next-token loss (:func:`next_token_loss`).  A token model's ``apply_fn``
returns ``(h, head, stats)``: final hidden states, the output head
(``logits = h @ head.T``) and counts the model keeps (a MoE layer's expert
loads), summed over a local update's steps.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.partition import ClientData

PyTree = Any

# Width of a token client's data matrix for the ``svd`` signature family:
# each sequence is a row of token counts folded into this many buckets by
# ``id mod TOKEN_BUCKETS`` (the feature width of MIX-4's 32x32x3 rows).
TOKEN_BUCKETS = 3072


@dataclass
class StackedClients:
    """Fixed-shape client tensors."""

    x: np.ndarray          # (K, n_max, d)
    y: np.ndarray          # (K, n_max)
    n: np.ndarray          # (K,) true sample counts (aggregation weights)
    x_test: np.ndarray     # (K, t_max, d)
    y_test: np.ndarray     # (K, t_max)
    t: np.ndarray          # (K,) true test counts
    names: list[str]

    @property
    def n_clients(self) -> int:
        return self.x.shape[0]

    @property
    def tokens(self) -> bool:
        """Whether clients hold token sequences (next-token loss)."""
        return is_tokens(self.x)


def is_tokens(x) -> bool:
    return np.issubdtype(x.dtype, np.integer)


def feature_matrix(x: np.ndarray) -> np.ndarray:
    """A client's (features, samples) data matrix for the ``svd`` signature
    family: its rows transposed, or for token sequences each sequence's
    token counts folded into :data:`TOKEN_BUCKETS` buckets."""
    x = np.asarray(x)
    if not is_tokens(x):
        return x.T
    counts = [np.bincount(row % TOKEN_BUCKETS, minlength=TOKEN_BUCKETS) for row in x]
    return np.stack(counts, axis=1).astype(np.float32)


def stack_clients(clients: list[ClientData]) -> StackedClients:
    K = len(clients)
    n_max = max(c.x_train.shape[0] for c in clients)
    t_max = max(c.x_test.shape[0] for c in clients)
    d = clients[0].x_train.shape[1]
    xdt = np.int32 if is_tokens(clients[0].x_train) else np.float32
    x = np.zeros((K, n_max, d), xdt)
    y = np.zeros((K, n_max), np.int64)
    xt = np.zeros((K, t_max, d), xdt)
    yt = np.zeros((K, t_max), np.int64)
    n = np.zeros((K,), np.int64)
    t = np.zeros((K,), np.int64)
    for k, c in enumerate(clients):
        nk, tk = c.x_train.shape[0], c.x_test.shape[0]
        reps = -(-n_max // nk)
        x[k] = np.tile(c.x_train, (reps, 1))[:n_max]
        y[k] = np.tile(c.y_train, reps)[:n_max]
        reps_t = -(-t_max // tk)
        xt[k] = np.tile(c.x_test, (reps_t, 1))[:t_max]
        yt[k] = np.tile(c.y_test, reps_t)[:t_max]
        n[k], t[k] = nk, tk
    return StackedClients(x, y, n, xt, yt, t, [c.dataset_name for c in clients])


def ce_loss(
    apply_fn: Callable,
    params: PyTree,
    xb: jax.Array,
    yb: jax.Array,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Mean cross-entropy; with ``mask`` a weighted mean over masked rows
    (used to restrict probes to a client's real, non-cycled samples)."""
    logits = apply_fn(params, xb)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
    per_example = logz - gold
    if mask is None:
        return jnp.mean(per_example)
    return jnp.sum(per_example * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# Positions per block of the next-token loss: the (B, block, V) logits of
# one block live at a time, recomputed in the backward pass.
LOSS_BLOCK = 1024


def next_token_loss(apply_fn: Callable, params: PyTree, tokens: jax.Array):
    """Mean next-token cross-entropy of token sequences (B, S) over their
    B * (S - 1) predictions, and the model's counts.  A token model's
    ``apply_fn(params, tokens)`` gives ``(h, head, stats)`` with logits
    ``h @ head.T``; they are formed in blocks of :data:`LOSS_BLOCK`
    positions."""
    h, head, stats = apply_fn(params, tokens)
    B, S, D = h.shape
    n = S - 1
    blk = min(LOSS_BLOCK, n)
    pad = (-n) % blk
    hp = jnp.pad(h[:, :-1], ((0, 0), (0, pad), (0, 0)))
    tgt = jnp.pad(tokens[:, 1:], ((0, 0), (0, pad)))
    valid = jnp.arange(n + pad) < n
    nb = (n + pad) // blk
    split = lambda a: jnp.moveaxis(a.reshape((B, nb, blk) + a.shape[2:]), 1, 0)

    @jax.checkpoint
    def block(args):
        hb, tb, vb = args
        logits = (hb @ head.T).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * vb)

    total = jnp.sum(jax.lax.map(block, (split(hp), split(tgt), split(jnp.broadcast_to(valid, (B, n + pad))))))
    return total / (B * n), stats


def make_local_sgd(
    apply_fn: Callable,
    *,
    steps: int,
    batch_size: int,
    lr: float,
    momentum: float = 0.5,
    prox_mu: float = 0.0,
    use_control_variates: bool = False,
    with_stats: bool = False,
):
    """Build local_sgd(params, x, y, n, key, anchor, c_diff) -> new_params.

    * ``anchor``   — global params theta_g (FedProx proximal term); pass params
                     when unused.
    * ``c_diff``   — SCAFFOLD drift correction (c - c_k); zeros when unused.
    Returns plain SGD with heavy-ball momentum (paper setup); with
    ``momentum=0`` plain SGD, which keeps no momentum buffer.  Token data
    trains on :func:`next_token_loss`; ``with_stats`` then also returns the
    model's counts summed over the steps.
    """

    def loss_fn(params, anchor, xb, yb):
        if jnp.issubdtype(xb.dtype, jnp.integer):
            l, stats = next_token_loss(apply_fn, params, xb)
        else:
            l, stats = ce_loss(apply_fn, params, xb, yb), {}
        if prox_mu > 0.0:
            sq = sum(
                jnp.sum(jnp.square(p - a))
                for p, a in zip(jax.tree.leaves(params), jax.tree.leaves(anchor))
            )
            l = l + 0.5 * prox_mu * sq
        return l, stats

    def local_sgd(params, x, y, n, key, anchor, c_diff):
        mu0 = jax.tree.map(jnp.zeros_like, params) if momentum else None

        def step(carry, key_t):
            params, mu = carry
            idx = jax.random.randint(key_t, (batch_size,), 0, jnp.maximum(n, 1))
            xb, yb = x[idx], y[idx]
            g, stats = jax.grad(loss_fn, has_aux=True)(params, anchor, xb, yb)
            if use_control_variates:
                g = jax.tree.map(lambda gi, ci: gi + ci, g, c_diff)
            if momentum:
                mu = jax.tree.map(lambda m, gi: momentum * m + gi, mu, g)
                params = jax.tree.map(lambda p, m: p - lr * m, params, mu)
            else:
                params = jax.tree.map(lambda p, gi: p - lr * gi, params, g)
            return (params, mu), stats

        keys = jax.random.split(key, steps)
        (params, _), stats = jax.lax.scan(step, (params, mu0), keys)
        if with_stats:
            return params, jax.tree.map(lambda s: jnp.sum(s, axis=0), stats)
        return params

    return local_sgd


def make_perfedavg_local(
    apply_fn: Callable, *, steps: int, batch_size: int, alpha: float, beta: float
):
    """Per-FedAvg (FO-MAML): theta' = theta - a*g(B1); theta -= b*g(theta', B2)."""

    def local(params, x, y, n, key, anchor, c_diff):
        del anchor, c_diff

        def step(params, key_t):
            k1, k2 = jax.random.split(key_t)
            i1 = jax.random.randint(k1, (batch_size,), 0, jnp.maximum(n, 1))
            i2 = jax.random.randint(k2, (batch_size,), 0, jnp.maximum(n, 1))
            g1 = jax.grad(lambda p: ce_loss(apply_fn, p, x[i1], y[i1]))(params)
            inner = jax.tree.map(lambda p, g: p - alpha * g, params, g1)
            g2 = jax.grad(lambda p: ce_loss(apply_fn, p, x[i2], y[i2]))(inner)
            params = jax.tree.map(lambda p, g: p - beta * g, params, g2)
            return params, None

        keys = jax.random.split(key, steps)
        params, _ = jax.lax.scan(step, params, keys)
        return params

    return local


@functools.partial(jax.jit, static_argnames=("apply_fn",))
def batch_eval(apply_fn, stacked_params, xt, yt, t):
    """Per-client top-1 accuracy. stacked_params: (K, ...) pytree."""

    def one(params, x, y, tk):
        logits = apply_fn(params, x)
        pred = jnp.argmax(logits, axis=-1)
        mask = jnp.arange(x.shape[0]) < tk
        return jnp.sum((pred == y) * mask) / jnp.maximum(tk, 1)

    return jax.vmap(one)(stacked_params, xt, yt, t)


@functools.partial(jax.jit, static_argnames=("apply_fn",))
def token_eval(apply_fn, cluster_params, labels, xt, t):
    """Per-client next-token top-1 accuracy, each client under its cluster's
    model, one client after another (no per-client model copies)."""

    def one(args):
        z, x, tk = args
        params = jax.tree.map(lambda l: l[z], cluster_params)
        h, head, _ = apply_fn(params, x)
        logits = h @ head.T
        hit = jnp.argmax(logits[:, :-1], axis=-1) == x[:, 1:]
        mask = jnp.arange(x.shape[0]) < tk
        return jnp.sum(jnp.mean(hit, axis=-1) * mask) / jnp.maximum(tk, 1)

    return jax.lax.map(one, (labels, xt, t))


def weighted_average(stacked: PyTree, weights: jax.Array) -> PyTree:
    """Weighted mean over the leading (client) axis."""
    w = weights / jnp.maximum(jnp.sum(weights), 1e-9)

    def avg(leaf):
        return jnp.tensordot(w, leaf, axes=(0, 0))

    return jax.tree.map(avg, stacked)


def tree_size_bytes(tree: PyTree) -> int:
    return int(sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree)))
