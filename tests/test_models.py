"""Model-zoo tests: per-arch smoke (reduced configs), cache consistency,
SSM chunked-vs-recurrent equivalence, flash-attention gradients, CNNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.models import lm, ssm
from repro.models.attention import chunked_attention
from repro.models.cnn import (
    init_lenet5, init_mlp_clf, init_resnet9,
    lenet5_apply, mlp_clf_apply, resnet9_apply,
)

KEY = jax.random.PRNGKey(0)


def _batch_for(cfg, B, S, key):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens}
    if cfg.vision_tokens:
        batch["vision_embeds"] = 0.1 * jax.random.normal(
            key, (B, cfg.vision_tokens, cfg.d_model)
        )
    if cfg.is_enc_dec:
        batch["encoder_frames"] = 0.1 * jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model)
        )
    return batch


# ---------------------------------------------------------------------------
# Per-arch smoke tests (REDUCED variants: 2 layers, d<=512, <=4 experts)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_arch_smoke_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    assert cfg.n_layers == 2 and cfg.d_model <= 512 and cfg.n_experts <= 4
    params = lm.init_params(cfg, KEY)
    B, S = 2, 32
    batch = _batch_for(cfg, B, S, KEY)

    logits, _, _ = jax.jit(
        lambda p, b: lm.forward(p, cfg, b["tokens"], mode="train",
                                vision_embeds=b.get("vision_embeds"),
                                encoder_frames=b.get("encoder_frames"))
    )(params, batch)
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert not bool(jnp.isnan(logits).any())

    # one train step decreases nothing NaN-ish
    from repro.optim import sgd

    opt = sgd(1e-2, momentum=0.9)
    step = jax.jit(lm.make_train_step(cfg, opt))
    opt_state = opt.init(params)
    params2, opt_state, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually changed
    delta = sum(
        float(jnp.abs(a - b).sum())
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2))
    )
    assert delta > 0


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_arch_smoke_decode_consistency(arch):
    """prefill+decode with caches == full teacher-forced forward."""
    cfg = get_config(arch).reduced()
    params = lm.init_params(cfg, KEY)
    B, S = 2, 24
    batch_full = _batch_for(cfg, B, S, KEY)
    tokens = batch_full["tokens"]
    kwargs = {k: v for k, v in batch_full.items() if k != "tokens"}

    logits_full, _, _ = jax.jit(
        lambda p, t: lm.forward(p, cfg, t, mode="train", **kwargs)
    )(params, tokens)

    batch_prefill = dict(batch_full, tokens=tokens[:, : S - 1])
    pre = jax.jit(lm.make_prefill_step(cfg, max_len=S))
    lg, cache = pre(params, batch_prefill)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(logits_full[:, S - 2]), rtol=2e-2, atol=2e-3
    )

    step = jax.jit(lm.make_serve_step(cfg))
    lg2, _ = step(params, cache, tokens[:, S - 1 : S], jnp.int32(S - 1))
    np.testing.assert_allclose(
        np.asarray(lg2), np.asarray(logits_full[:, S - 1]), rtol=2e-2, atol=2e-3
    )


# ---------------------------------------------------------------------------
# SSM internals
# ---------------------------------------------------------------------------


class TestMamba2:
    def test_ssd_matches_recurrence(self):
        cfg = get_config("zamba2-7b").reduced()
        p = ssm.init_mamba(KEY, cfg)
        u = 0.5 * jax.random.normal(KEY, (2, 40, cfg.d_model))
        y_ssd = ssm.mamba_ssd(p, cfg, u)
        y_rec = ssm.mamba_recurrent_ref(p, cfg, u)
        np.testing.assert_allclose(
            np.asarray(y_ssd), np.asarray(y_rec), rtol=5e-2, atol=5e-3
        )

    def test_ssd_state_matches_recurrence_state(self):
        cfg = get_config("zamba2-7b").reduced()
        p = ssm.init_mamba(KEY, cfg)
        u = 0.5 * jax.random.normal(KEY, (1, 24, cfg.d_model))
        _, st = ssm.mamba_ssd(p, cfg, u, return_state=True)
        # continue decoding: compare against recurrence over the full prefix
        st2 = ssm.init_mamba_state(cfg, 1)
        for t in range(24):
            _, st2 = ssm.mamba_decode(p, cfg, u[:, t : t + 1], st2)
        np.testing.assert_allclose(
            np.asarray(st.h), np.asarray(st2.h), rtol=5e-2, atol=5e-3
        )


class TestRWKV6:
    def test_chunked_scan_matches_plain(self):
        """sqrt-T checkpointed two-level scan == semantics of a flat scan."""
        cfg = get_config("rwkv6-1.6b").reduced()
        p = ssm.init_rwkv(KEY, cfg)
        x = 0.5 * jax.random.normal(KEY, (2, 50, cfg.d_model))  # non-multiple of 64
        state = ssm.init_rwkv_state(cfg, 2)
        y, st = ssm.rwkv_time_mix(p, cfg, x, state)
        # reference: token-by-token through the same module
        st_ref = ssm.init_rwkV_state if False else ssm.init_rwkv_state(cfg, 2)
        outs = []
        for t in range(50):
            o, st_ref = ssm.rwkv_time_mix(p, cfg, x[:, t : t + 1], st_ref)
            outs.append(o)
        y_ref = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y_ref), rtol=5e-2, atol=5e-3
        )
        np.testing.assert_allclose(
            np.asarray(st.wkv), np.asarray(st_ref.wkv), rtol=5e-2, atol=5e-3
        )


# ---------------------------------------------------------------------------
# Attention gradients (custom VJP)
# ---------------------------------------------------------------------------


def test_flash_vjp_matches_naive():
    import math

    def naive(q, k, v, q_pos, kv_pos):
        B, Sq, Hq, hd = q.shape
        Hkv = k.shape[2]
        G = Hq // Hkv
        qs = q.reshape(B, Sq, Hkv, G, hd) / math.sqrt(hd)
        s = jnp.einsum("bqhgd,bchd->bqhgc", qs, k)
        valid = (kv_pos[None, :] >= 0) & (kv_pos[None, :] <= q_pos[:, None])
        s = jnp.where(valid[None, :, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bqhgc,bchd->bqhgd", p, v).reshape(B, Sq, Hq, hd)

    q = jax.random.normal(KEY, (2, 16, 8, 16))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 24, 4, 16))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 24, 4, 16))
    q_pos = jnp.arange(8, 24, dtype=jnp.int32)
    kv_pos = jnp.where(jnp.arange(24) < 20, jnp.arange(24), -1).astype(jnp.int32)

    f1 = lambda q, k, v: jnp.sum(
        jnp.cos(chunked_attention(q, k, v, q_pos, kv_pos, causal=True, chunk=8))
    )
    f2 = lambda q, k, v: jnp.sum(jnp.cos(naive(q, k, v, q_pos, kv_pos)))
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ---------------------------------------------------------------------------
# Paper CNNs
# ---------------------------------------------------------------------------


class TestPaperModels:
    def test_lenet5(self):
        p = init_lenet5(KEY, in_hw=(16, 16), in_ch=3, n_classes=10)
        x = jax.random.normal(KEY, (4, 768))
        logits = lenet5_apply(p, x)
        assert logits.shape == (4, 10)
        assert np.isfinite(np.asarray(logits)).all()

    def test_lenet5_trains_at_cifar_width(self):
        """Every param leaf must be differentiable (jax.grad refuses integer
        leaves), and fl_train's model builder must pass the image size to
        apply as well as init: 3072 features = 32x32x3."""
        from repro.fl.client import ce_loss
        from repro.launch.fl_train import build_model

        init_fn, apply_fn = build_model("lenet5", 3072, 10)
        p = init_fn(KEY)
        x = jax.random.normal(KEY, (4, 3072))
        y = jnp.arange(4) % 10
        g = jax.grad(lambda q: ce_loss(apply_fn, q, x, y))(p)
        assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))

    def test_resnet9(self):
        p = init_resnet9(KEY, in_ch=3, n_classes=100)
        x = jax.random.normal(KEY, (2, 768))
        logits = resnet9_apply(p, x)
        assert logits.shape == (2, 100)
        assert np.isfinite(np.asarray(logits)).all()

    def test_mlp(self):
        p = init_mlp_clf(KEY, 64, 10)
        x = jax.random.normal(KEY, (8, 64))
        assert mlp_clf_apply(p, x).shape == (8, 10)


def test_gradient_accumulation_matches_full_batch():
    """microbatches=n with summed grads == single-batch step (same update)."""
    from repro.optim import sgd

    cfg = get_config("tinyllama-1.1b").reduced()
    params = lm.init_params(cfg, KEY)
    batch = {"tokens": jax.random.randint(KEY, (4, 32), 0, cfg.vocab)}
    opt = sgd(1e-2)
    s1 = jax.jit(lm.make_train_step(cfg, opt, microbatches=1))
    s2 = jax.jit(lm.make_train_step(cfg, opt, microbatches=2))
    p1, _, m1 = s1(params, opt.init(params), batch)
    p2, _, m2 = s2(params, opt.init(params), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
