"""The plain reference of one PACFL round with LeNet-5, and its control.

Nothing here imports the program; it is written from the published
descriptions:

* LeNet-5 (LeCun et al. 1998, as PACFL's Table 11 uses it): a flat input of
  ``H * W * C`` features read as an ``(H, W, C)`` image; a 5x5 VALID
  convolution to 6 channels, ReLU, 2x2 max pooling; a 5x5 VALID convolution
  to 16 channels, ReLU, 2x2 max pooling; the ``(h, w, c)``-ordered flat
  features through fully connected layers of 120 and 84 units with ReLU
  between them, and one to the classes.  Parameters are the program's
  layout: ``c1``, ``c2`` of shape ``(5, 5, C_in, C_out)`` and ``f1``, ``f2``,
  ``f3`` as ``{"w": (d_in, d_out), "b": (d_out,)}``.  The convolutions are
  written out as sums over the 25 shifted windows, and every product runs at
  ``Precision.HIGHEST``, in float32.
* The local update: mean cross-entropy over a batch, ``jax.grad``, and SGD
  with heavy-ball momentum, ``mu = momentum * mu + g``, ``theta -= lr * mu``,
  from ``mu = 0``, one client at a time and one step at a time.
* The batches follow the program's key contract (``make_local_sgd``): the
  round's key is split into one key per sampled client, in sample order
  (``split(key, m)[i]``), each client's key into one per step
  (``split(key_i, steps)[t]``), and step ``t`` trains on the rows
  ``randint(key_t, (batch,), 0, n_i)`` of the client's own samples.
* Aggregation (PACFL, Algorithm 1): each cluster that has sampled clients
  takes the sample-weighted mean of their updated parameters, in float64
  NumPy; clusters without sampled clients keep theirs.
* The clustering: each client's top-``p`` left singular vectors of its
  ``(features, samples)`` data matrix, in float64; eq2 distances and
  SciPy's hierarchical clustering from ``reference.py``.
* The initial model (``init_params``): every weight drawn from
  ``N(0, 1 / fan_in)``, the fan-in scaling of LeCun et al. 1998, biases
  zero, float32; every cluster starts from the same model (PACFL,
  Algorithm 1).

The control is this round one precision step below what the configuration
states: parameters and momentum held in bfloat16 between steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("hw", "channels", "n_classes", "clusters"))
def _init(key, *, hw, channels, n_classes, clusters):
    flat = ((hw - 4) // 2 - 4) // 2
    shapes = {
        "c1": (5, 5, channels, 6), "c2": (5, 5, 6, 16),
        "f1": (flat * flat * 16, 120), "f2": (120, 84), "f3": (84, n_classes),
    }
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        fan_in = int(np.prod(shape[:-1]))
        w = jax.random.normal(k, shape, jnp.float32) / np.float32(np.sqrt(fan_in))
        out[name] = w if name.startswith("c") else {"w": w, "b": jnp.zeros(shape[-1:], jnp.float32)}
    return jax.tree.map(lambda l: jnp.broadcast_to(l, (clusters,) + l.shape), out)


def init_params(key: jax.Array, cfg: dict, clusters: int) -> dict:
    """The federation's initial LeNet-5, stacked once per cluster: one jitted
    call on the device from ``key``."""
    return _init(
        key, hw=int(cfg["image_hw"]), channels=int(cfg["channels"]),
        n_classes=int(cfg["n_classes"]), clusters=clusters,
    )


def _mm(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _conv5(x: jax.Array, w: jax.Array) -> jax.Array:
    """5x5 VALID convolution, (B, H, W, Ci) x (5, 5, Ci, Co) -> (B, H-4, W-4, Co)."""
    H, W = x.shape[1] - 4, x.shape[2] - 4
    out = 0.0
    for i in range(5):
        for j in range(5):
            out = out + _mm("bhwc,co->bhwo", x[:, i : i + H, j : j + W, :], w[i, j])
    return out


def _pool2(x: jax.Array) -> jax.Array:
    B, H, W, C = x.shape
    return x.reshape(B, H // 2, 2, W // 2, 2, C).max(axis=(2, 4))


def lenet5(params: dict, x: jax.Array, hw: int, channels: int) -> jax.Array:
    """Logits of LeNet-5 for flat inputs ``x`` of shape (B, hw * hw * channels)."""
    h = x.reshape(x.shape[0], hw, hw, channels)
    h = _pool2(jax.nn.relu(_conv5(h, params["c1"])))
    h = _pool2(jax.nn.relu(_conv5(h, params["c2"])))
    h = h.reshape(h.shape[0], -1)
    for name in ("f1", "f2"):
        h = jax.nn.relu(_mm("bi,io->bo", h, params[name]["w"]) + params[name]["b"])
    return _mm("bi,io->bo", h, params["f3"]["w"]) + params["f3"]["b"]


def _loss(params, xb, yb, hw, channels):
    logits = lenet5(params, xb, hw, channels)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.mean(logz - jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0])


@functools.partial(jax.jit, static_argnames=("batch", "hw", "channels", "store"))
def _step(params, mu, x, y, n, key_t, lr, momentum, *, batch, hw, channels, store):
    """One SGD step with momentum on one client; ``store`` is the dtype the
    parameters and momentum are held in between steps."""
    idx = jax.random.randint(key_t, (batch,), 0, jnp.maximum(n, 1))
    up = lambda t: jax.tree.map(lambda l: l.astype(jnp.float32), t)
    params, mu = up(params), up(mu)
    with jax.default_matmul_precision("highest"):
        g = jax.grad(_loss)(params, x[idx], y[idx], hw, channels)
    mu = jax.tree.map(lambda m, gi: momentum * m + gi, mu, g)
    params = jax.tree.map(lambda p, m: p - lr * m, params, mu)
    down = lambda t: jax.tree.map(lambda l: l.astype(store), t)
    return down(params), down(mu)


def local_update(params, x, y, n, key, *, steps, cfg, store=jnp.float32):
    """One client's local training from ``params`` with its round key."""
    hw, channels = int(cfg["image_hw"]), int(cfg["channels"])
    params = jax.tree.map(lambda l: jnp.asarray(l, jnp.float32).astype(store), params)
    mu = jax.tree.map(jnp.zeros_like, params)
    x, y = jnp.asarray(x), jnp.asarray(y, jnp.int32)
    lr, momentum = jnp.float32(cfg["lr"]), jnp.float32(cfg["momentum"])
    for key_t in jax.random.split(key, steps):
        params, mu = _step(
            params, mu, x, y, jnp.int32(n), key_t, lr, momentum,
            batch=int(cfg["batch_size"]), hw=hw, channels=channels, store=store,
        )
    return jax.tree.map(lambda l: np.asarray(l.astype(jnp.float32)), params)


def local_steps(cfg: dict, n: np.ndarray) -> int:
    """Steps of a local update: ``local_epochs`` passes of the mean client's
    batches (the configuration's rule)."""
    per_epoch = max(1, int(np.mean(n)) // int(cfg["batch_size"]))
    return max(1, int(cfg["local_epochs"]) * per_epoch)


def weighted_mean(trees: list, weights: np.ndarray) -> dict:
    """Sample-weighted mean of parameter trees, in float64."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    return jax.tree.map(
        lambda *leaves: sum(wi * np.asarray(l, np.float64) for wi, l in zip(w, leaves)), *trees
    )


def round_updates(before, labels, sampled, key, clients, cfg, *, store=jnp.float32):
    """The reference's round: ``{cluster: float64 parameters}`` for each
    cluster with sampled clients.  ``before`` maps a cluster to its
    parameters at the round's start."""
    steps = local_steps(cfg, clients.n)
    keys = jax.random.split(key, len(sampled))
    trained = [
        local_update(before[int(labels[k])], clients.x[k], clients.y[k], clients.n[k],
                     keys[i], steps=steps, cfg=cfg, store=store)
        for i, k in enumerate(sampled)
    ]
    out = {}
    for z in np.unique(labels[sampled]):
        mine = [i for i, k in enumerate(sampled) if labels[k] == z]
        out[int(z)] = weighted_mean([trained[i] for i in mine], clients.n[sampled[mine]])
    return out


def signatures_f64(clients, p: int) -> np.ndarray:
    """(K, features, p) float64: each client's top-p left singular vectors,
    from the eigenvectors of its float64 (samples, samples) Gram matrix:
    ``D = U S V^T`` gives ``U[:, :p] = D V[:, :p] / S[:p]``."""
    out = []
    for k in range(clients.n_clients):
        D = clients.x[k, : clients.n[k]].astype(np.float64).T
        evals, V = np.linalg.eigh(D.T @ D)
        top = np.argsort(evals)[::-1][:p]
        out.append(D @ V[:, top] / np.sqrt(evals[top]))
    return np.stack(out)


def cluster_labels(clients, cfg: dict) -> np.ndarray:
    pc = cfg["pacfl"]
    U = signatures_f64(clients, int(pc["p"]))
    A = reference.proximity_f64(U, pc["measure"])
    return reference.hc_labels(A, float(pc["beta"]), pc["linkage"])
