"""Paper models for the FL experiments: LeNet-5 and ResNet-9 (Tables 11-12),
plus a small MLP used for CPU-budget experiment runs.

Functional: ``init_<m>(key, ...) -> params``; ``<m>_apply(params, x) -> logits``.
Inputs are flattened feature vectors (the synthetic datasets are flat); the
CNNs reshape to (B, H, W, C) internally.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _conv_init(key, kh, kw, cin, cout):
    scale = 1.0 / math.sqrt(kh * kw * cin)
    return scale * jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)


def _fc_init(key, d_in, d_out):
    scale = 1.0 / math.sqrt(d_in)
    k1, k2 = jax.random.split(key)
    return {
        "w": scale * jax.random.normal(k1, (d_in, d_out), jnp.float32),
        "b": jnp.zeros((d_out,), jnp.float32),
    }


def _conv(x, w, stride=1, padding="VALID"):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _maxpool(x, k=2):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, k, k, 1), "VALID"
    )


def _groupnorm(x, scale, bias, groups=32):
    B, H, W, C = x.shape
    g = min(groups, C)
    xg = x.reshape(B, H, W, g, C // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + 1e-5)
    return xg.reshape(B, H, W, C) * scale + bias


# ---------------------------------------------------------------------------
# LeNet-5 (Table 11): conv(6,5x5) -> pool -> conv(16,5x5) -> pool -> fc 120/84/out
# ---------------------------------------------------------------------------


def init_lenet5(key, *, in_hw=(16, 16), in_ch=3, n_classes=10):
    ks = jax.random.split(key, 5)
    h, w = in_hw
    # spatial dims after conv5/pool/conv5/pool
    h1, w1 = (h - 4) // 2, (w - 4) // 2
    h2, w2 = (h1 - 4) // 2, (w1 - 4) // 2
    flat = h2 * w2 * 16
    return {
        "c1": _conv_init(ks[0], 5, 5, in_ch, 6),
        "c2": _conv_init(ks[1], 5, 5, 6, 16),
        "f1": _fc_init(ks[2], flat, 120),
        "f2": _fc_init(ks[3], 120, 84),
        "f3": _fc_init(ks[4], 84, n_classes),
    }


def lenet5_apply(params, x, *, in_hw=(16, 16), in_ch=3):
    B = x.shape[0]
    x = x.reshape(B, in_hw[0], in_hw[1], in_ch)
    x = _maxpool(jax.nn.relu(_conv(x, params["c1"])))
    x = _maxpool(jax.nn.relu(_conv(x, params["c2"])))
    x = x.reshape(B, -1)
    x = jax.nn.relu(x @ params["f1"]["w"] + params["f1"]["b"])
    x = jax.nn.relu(x @ params["f2"]["w"] + params["f2"]["b"])
    return x @ params["f3"]["w"] + params["f3"]["b"]


# ---------------------------------------------------------------------------
# ResNet-9 (Table 12), GroupNorm(32) as in the paper
# ---------------------------------------------------------------------------


def _init_convgn(key, cin, cout):
    return {
        "w": _conv_init(key, 3, 3, cin, cout),
        "gs": jnp.ones((cout,), jnp.float32),
        "gb": jnp.zeros((cout,), jnp.float32),
    }


def init_resnet9(key, *, in_ch=3, n_classes=100):
    ks = jax.random.split(key, 9)
    return {
        "b1": _init_convgn(ks[0], in_ch, 64),
        "b2": _init_convgn(ks[1], 64, 128),
        "b3a": _init_convgn(ks[2], 128, 128),
        "b3b": _init_convgn(ks[3], 128, 128),
        "b4": _init_convgn(ks[4], 128, 256),
        "b5": _init_convgn(ks[5], 256, 512),
        "b6a": _init_convgn(ks[6], 512, 512),
        "b6b": _init_convgn(ks[7], 512, 512),
        "fc": _fc_init(ks[8], 512, n_classes),
    }


def _convgn(p, x, pool=False):
    x = _conv(x, p["w"], padding="SAME")
    x = jax.nn.relu(_groupnorm(x, p["gs"], p["gb"]))
    return _maxpool(x) if pool else x


def resnet9_apply(params, x, *, in_hw=(16, 16), in_ch=3):
    B = x.shape[0]
    x = x.reshape(B, in_hw[0], in_hw[1], in_ch)
    x = _convgn(params["b1"], x)
    x = _convgn(params["b2"], x, pool=True)
    x = x + _convgn(params["b3b"], _convgn(params["b3a"], x))
    x = _convgn(params["b4"], x, pool=True)
    x = _convgn(params["b5"], x, pool=True)
    x = x + _convgn(params["b6b"], _convgn(params["b6a"], x))
    x = jnp.max(x, axis=(1, 2))
    return x @ params["fc"]["w"] + params["fc"]["b"]


# ---------------------------------------------------------------------------
# MLP (CPU-budget FL runs)
# ---------------------------------------------------------------------------


def init_mlp_clf(key, d_in, n_classes, hidden=(256, 128)):
    dims = (d_in,) + tuple(hidden) + (n_classes,)
    ks = jax.random.split(key, len(dims) - 1)
    return {"layers": [_fc_init(k, a, b) for k, a, b in zip(ks, dims[:-1], dims[1:])]}


def mlp_clf_apply(params, x):
    for i, l in enumerate(params["layers"]):
        x = x @ l["w"] + l["b"]
        if i < len(params["layers"]) - 1:
            x = jax.nn.relu(x)
    return x


MODEL_ZOO = {
    "lenet5": (init_lenet5, lenet5_apply),
    "resnet9": (init_resnet9, resnet9_apply),
    "mlp": (init_mlp_clf, mlp_clf_apply),
}
