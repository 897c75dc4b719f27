"""LFM2-8B-A1B's per-cluster models through PACFL's round, against the plain
float32 reference (``tests/_lfm2_reference.py``), at a size the CPU holds:
d = 64, the published first six layers' pattern (two dense conv layers,
then attention and three conv layers with MoE FFNs), 8 experts of which a
device holds 2 (4 shares), top-4, 128 vocabulary rows, short sequences.

* the program's logits, next-token loss and gradients match the
  reference's (float32 on the CPU: products agree to rounding);
* one sequential PACFL round and its per-cluster weighted mean match the
  reference's local SGD and float64 mean; untouched clusters keep their
  models bit for bit;
* the four expert shares' outputs sum to the uncut reference layer;
* nothing is dropped under routing skewed onto the held experts;
* LeNet-5's vmapped round is the parent's, bit for bit;
* ``run_federation`` trains the LM for two rounds.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _lfm2_reference as ref
from repro.configs.lfm2_8b_a1b import LAYER_TYPES, from_hf
from repro.core.pacfl import PACFLConfig
from repro.fl import ClientData, FLConfig, run_federation
from repro.fl import strategies
from repro.fl.client import feature_matrix, next_token_loss, stack_clients, weighted_average
from repro.fl.strategies import PACFL
from repro.models import lfm2, moe

TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_dense_layers": 2,
    "layer_types": list(LAYER_TYPES), "conv_L_cache": 3, "norm_eps": 1e-5,
    "rope_theta": 1e6, "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1.0, "vocab_size": 512, "n_layers": 6,
    "first_expert": 2, "n_experts_held": 2, "vocab": 128, "attn_chunk": 8,
    "lr": 0.05, "batch_size": 2, "local_epochs": 1,
}
S = 24


def _arch(**kw):
    return from_hf({**TINY, **kw})


def _tokens(key, shape, vocab=TINY["vocab"]):
    return jax.random.randint(key, shape, 0, vocab, jnp.int32)


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30)) <= tol


def test_forward_logits_match_reference():
    cfg = _arch()
    params = lfm2.init_params(jax.random.PRNGKey(1), cfg)
    toks = _tokens(jax.random.PRNGKey(2), (2, S))
    logits, stats = jax.jit(lambda p, t: lfm2.forward(p, cfg, t))(params, toks)
    want, moe_in = ref.reference_forward(params, toks, TINY)
    assert _close(logits, want, 1e-5)
    moe_layers = [p["moe"] for p in params["layers"] if "moe" in p]
    assert len(moe_in) == len(moe_layers) == stats["expert_load"].shape[0] == 4
    # on the reference's input the program's router selects the reference's
    # top-4 of the biased scores, with the reference's weights
    for p, h in zip(moe_layers, moe_in):
        _, top, g = ref.reference_route(p, h, TINY)
        _, mine, w = moe.route(p, h, cfg)
        assert np.array_equal(np.asarray(mine), np.asarray(top))
        assert _close(w, g, 1e-5)
    assert np.array_equal(np.asarray(stats["expert_load"]).sum(1), np.asarray(stats["routed"]))


def test_loss_and_gradients_match_reference():
    cfg = _arch()
    params = lfm2.init_params(jax.random.PRNGKey(3), cfg)
    toks = _tokens(jax.random.PRNGKey(4), (2, S))
    apply = functools.partial(lfm2.apply, cfg=cfg)
    (loss, _), g = jax.jit(jax.value_and_grad(
        lambda p: next_token_loss(apply, p, toks), has_aux=True))(params)
    want, g_ref = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, toks, TINY)))(params)
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    leaves, ref_leaves = jax.tree.leaves(g), jax.tree.leaves(g_ref)
    assert len(leaves) == len(ref_leaves)
    for a, b in zip(leaves, ref_leaves):
        if np.max(np.abs(np.asarray(b))) > 0:
            assert _close(a, b, 1e-4)
        else:   # the selection-only expert bias has no gradient
            assert np.max(np.abs(np.asarray(a))) == 0


def _clients(n_per=(2, 4), k=8, seed=0):
    """``k`` token clients of two domains (seeded permuted Zipf unigrams),
    alternately ``n_per`` sequences each."""
    rng = np.random.default_rng(seed)
    V = TINY["vocab"]
    probs = 1.0 / np.arange(1, V + 1)
    probs /= probs.sum()
    perms = [np.random.default_rng([seed, d]).permutation(V) for d in range(2)]
    out = []
    for i in range(k):
        n = n_per[(i // 2) % len(n_per)]
        x = perms[i % 2][rng.choice(V, size=(n, S), p=probs)].astype(np.int32)
        out.append(ClientData(x, np.zeros(n, np.int64), x[:1], np.zeros(1, np.int64), f"d{i % 2}"))
    return out


def _fl(**kw):
    base = dict(rounds=2, sample_frac=0.5, local_epochs=1, batch_size=2, lr=TINY["lr"],
                momentum=0.0, pacfl=PACFLConfig(p=2, beta=45.0, measure="eq2"))
    return FLConfig(**{**base, **kw})


@pytest.fixture
def sequential(monkeypatch):
    """Steer the round onto its sequential path, as a model too large for
    one copy per sampled client takes it."""
    monkeypatch.setattr(strategies, "vmapped_fits", lambda *a: False)


def test_round_and_cluster_mean_match_reference(sequential):
    cfg = _arch()
    clients = _clients()
    data = stack_clients(clients)
    strat = PACFL(functools.partial(lfm2.apply, cfg=cfg),
                  functools.partial(lfm2.init_params, cfg=cfg), _fl())
    strat.setup(jax.random.PRNGKey(0), data)
    assert np.array_equal(strat.labels, np.arange(8) % 2)
    init = strat.cluster_params = ref.init_params(jax.random.PRNGKey(7), TINY, clusters=3)
    before = jax.tree.map(np.asarray, init)
    sampled = np.array([0, 2, 3, 4])            # clusters 0, 0, 1, 0; sizes 2, 4, 4, 2
    key = jax.random.PRNGKey(11)
    strat.run_round(1, sampled, key)
    after = strat.cluster_params
    one = jax.tree.map(lambda l: l[0], before)
    want = ref.round_updates({0: one, 1: one}, strat.labels, sampled, key, data.x, data.n, TINY)
    for z in (0, 1):
        got = jax.tree.map(lambda l: np.asarray(l[z]), after)
        for a, b, s in zip(jax.tree.leaves(got), jax.tree.leaves(want[z]), jax.tree.leaves(one)):
            # the change agrees to 1e-4 of its size, above float32's rounding
            # of the parameters themselves (norm weights sit at 1: 6e-8 apart)
            tol = 1e-4 * np.max(np.abs(b - s)) + 4 * np.finfo(np.float32).eps * np.max(np.abs(s))
            assert np.max(np.abs(a - b)) <= tol
    # cluster 2 took no client: bit for bit
    for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before)):
        assert np.array_equal(np.asarray(a)[2], b[2])
    loads = np.asarray(strat.round_stats["expert_load"])
    routed = np.asarray(strat.round_stats["routed"])
    assert loads.shape == (4, 2) and loads.sum() == int(routed.sum())
    assert np.array_equal(np.asarray(strat.round_stats["computed"]), routed)


def test_expert_shares_sum_to_the_uncut_layer():
    key = jax.random.PRNGKey(5)
    h = jax.random.normal(jax.random.PRNGKey(6), (40, TINY["hidden_size"]), jnp.float32)
    whole = _arch(first_expert=0, n_experts_held=8)
    p_whole = moe.init_held_moe(key, whole)
    total = 0.0
    for j in range(4):
        share = _arch(first_expert=2 * j, n_experts_held=2)
        p = moe.init_held_moe(key, share)
        assert np.array_equal(np.asarray(p["w1"]), np.asarray(p_whole["w1"][2 * j : 2 * j + 2]))
        out, stats = moe.held_moe_apply(p, h, share)
        assert int(stats["routed"]) == int(np.asarray(stats["expert_load"]).sum())
        assert int(stats["computed"]) == int(stats["routed"])
        total = total + out
    want = ref._moe(p_whole, h, {**TINY, "first_expert": 0, "n_experts_held": 8})
    assert _close(total, want, 1e-5)


def test_nothing_dropped_under_skewed_routing():
    cfg = _arch()
    p = moe.init_held_moe(jax.random.PRNGKey(8), cfg)
    # every token picks both held experts (2, 3) first
    p["expert_bias"] = p["expert_bias"].at[2:4].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(9), (64, TINY["hidden_size"]), jnp.float32)
    out, stats = moe.held_moe_apply(p, h, cfg)
    load = np.asarray(stats["expert_load"])
    assert load.tolist() == [64, 64] and int(stats["routed"]) == 128
    assert int(stats["computed"]) == 128
    want = ref._moe(p, h, TINY)
    assert _close(out, want, 1e-5)


def _frozen_local_sgd(apply_fn, *, steps, batch_size, lr, momentum):
    """The parent's vmapped local update (``make_local_sgd`` before token
    data), frozen: heavy-ball SGD on the row cross-entropy."""

    def loss_fn(params, xb, yb):
        logits = apply_fn(params, xb)
        gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)

    def local_sgd(params, x, y, n, key):
        mu0 = jax.tree.map(jnp.zeros_like, params)

        def step(carry, key_t):
            params, mu = carry
            idx = jax.random.randint(key_t, (batch_size,), 0, jnp.maximum(n, 1))
            g = jax.grad(loss_fn)(params, x[idx], y[idx])
            mu = jax.tree.map(lambda m, gi: momentum * m + gi, mu, g)
            params = jax.tree.map(lambda p, m: p - lr * m, params, mu)
            return (params, mu), None

        (params, _), _ = jax.lax.scan(step, (params, mu0), jax.random.split(key, steps))
        return params

    return local_sgd


def test_lenet_round_is_the_vmapped_one_bit_for_bit():
    from repro.models.cnn import init_lenet5, lenet5_apply

    rng = np.random.default_rng(3)
    clients = [
        ClientData(rng.normal(size=(n, 3072)).astype(np.float32) + (k % 2),
                   rng.integers(0, 10, n), rng.normal(size=(4, 3072)).astype(np.float32),
                   rng.integers(0, 10, 4), f"d{k % 2}")
        for k, n in enumerate([20, 40] * 3)
    ]
    apply = functools.partial(lenet5_apply, in_hw=(32, 32), in_ch=3)
    fl = FLConfig(sample_frac=0.5, local_epochs=1, batch_size=10, lr=0.05, momentum=0.5,
                  pacfl=PACFLConfig(p=2, beta=45.0, measure="eq2"))
    strat = PACFL(apply, functools.partial(init_lenet5, in_hw=(32, 32), in_ch=3, n_classes=10), fl)
    strat.setup(jax.random.PRNGKey(0), stack_clients(clients))
    start = strat.cluster_params
    sampled, key = np.array([0, 1, 2, 5]), jax.random.PRNGKey(4)
    strat.run_round(1, sampled, key)
    assert not hasattr(strat, "round_stats")

    d = strat.data
    local = jax.jit(jax.vmap(_frozen_local_sgd(apply, steps=strat._steps, batch_size=10,
                                               lr=0.05, momentum=0.5)))
    pick = strat.labels[sampled]
    new = local(jax.tree.map(lambda l: l[pick], start), jnp.asarray(d.x[sampled]),
                jnp.asarray(d.y[sampled]), jnp.asarray(d.n[sampled]),
                jax.random.split(key, len(sampled)))
    w = jnp.asarray(d.n[sampled], jnp.float32)
    want = start
    for z in np.unique(pick):
        idx = np.where(pick == z)[0]
        avg = weighted_average(jax.tree.map(lambda l: l[idx], new), w[idx])
        want = jax.tree.map(lambda all_, a: all_.at[int(z)].set(a), want, avg)
    for a, b in zip(jax.tree.leaves(strat.cluster_params), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_run_federation_trains_the_lm(sequential):
    cfg = _arch()
    res = run_federation(
        "pacfl", _clients(), functools.partial(lfm2.apply, cfg=cfg),
        functools.partial(lfm2.init_params, cfg=cfg), _fl(), eval_every=1,
    )
    strat = res.strategy_obj
    assert np.array_equal(strat.labels, np.arange(8) % 2)
    assert [r.rnd for r in res.records] == [1, 2]
    assert res.final_accs.shape == (8,) and np.all(np.isfinite(res.final_accs))
    init = lfm2.init_params(jax.random.fold_in(jax.random.PRNGKey(0), 0), cfg)
    for z in range(2):
        moved = [float(np.max(np.abs(np.asarray(a)[z] - np.asarray(b))))
                 for a, b in zip(jax.tree.leaves(strat.cluster_params), jax.tree.leaves(init))]
        assert max(moved) > 0


@pytest.mark.parametrize("P, m, fits", [(258_136, 10, True), (2_274_637_824, 4, False)])
def test_round_path_follows_model_bytes_and_device_memory(P, m, fits):
    # LeNet-5 (10 clients) and the cell's LFM2 share (4 clients) on 16 GiB
    assert strategies.vmapped_fits(P, m, 3, 16 * 2**30) is fits


def test_token_feature_matrix_folds_counts():
    x = np.array([[0, 3072, 5, 5], [1, 2, 3, 3075]], np.int32)
    f = feature_matrix(x)
    assert f.shape == (3072, 2) and f.dtype == np.float32
    assert f[0, 0] == 2 and f[5, 0] == 2 and f[3, 1] == 2 and f.sum() == 8
    rows = np.ones((3, 5), np.float32)
    assert np.array_equal(feature_matrix(rows), rows.T)


@pytest.mark.parametrize("key, value", [("use_expert_bias", False), ("norm_topk_prob", False),
                                        ("routed_scaling_factor", 2.5)])
def test_config_with_other_routing_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        _arch(**{key: value})
