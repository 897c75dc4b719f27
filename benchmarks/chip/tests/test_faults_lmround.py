"""A run of the LM round with its timed path broken underneath has to read
``correct: false``, and the same run unbroken ``correct: true``.

Each test drives a whole run of ``lmround.lfm2-8b-a1b-fed`` through the
harness (set-up, window, check) at a size the CPU holds (d = 64, the
published first six layers' pattern, 8 experts of which the chip holds 2,
128 vocabulary rows, 8 clients of 24-token sequences), on the round's
sequential path (the path a model too large for one copy per client takes),
with one fault planted in the program:

* softmax routing instead of sigmoid;
* the selection bias added into the expert weights (``routing_weight_l1``);
* a capacity that drops pairs;
* the short convolution without its ``B`` gate;
* attention without QK-norm;
* a client's update dropped (its cluster's model in its place);
* an unweighted per-cluster mean (on clients of 3 and 12 sequences in one cluster).
"""
import io
import time

import jax
import jax.numpy as jnp
import pytest

import harness

TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "first_expert": 2, "n_experts_held": 2, "vocab": 128, "seq_len": 24, "n_clients": 8,
    "sample_frac": 0.5, "attn_chunk": 8, "lr": 0.05,
}
RAGGED = {"sequences_per_client": [3, 3, 12, 12], "batch_size": 1}
CELL = "lmround.lfm2-8b-a1b-fed"


@pytest.fixture(autouse=True)
def sequential(monkeypatch):
    from repro.fl import strategies

    monkeypatch.setattr(strategies, "vmapped_fits", lambda *a: False)


def _run(seed=2**31 + 17, **overrides):
    return harness.run_cell(
        CELL, seed, 0.5, False, t_start=time.perf_counter(), require_tpu=False,
        config_overrides={**TINY, **overrides}, log=io.StringIO(),
    )


def _failed(line, *names):
    assert not line["correct"], line["checks"]
    return [n for n in names if line["checks"][n]["value"] > line["checks"][n]["limit"]]


@pytest.mark.parametrize("overrides", [{}, RAGGED], ids=["even", "ragged"])
def test_sound_run_is_correct(overrides):
    line = _run(**overrides)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"round_s", "setup_s"}
    assert line["failed"] == 0 and line["attempted"] > 0


def test_control_fails_through_its_gaps():
    """The control (parameters held in bfloat16 between local steps) reads
    ``dropped_pairs`` 0, as the reference drops nothing, and fails through
    the round's gaps."""
    line = harness.run_cell(
        CELL, 2**31 + 17, 0.5, False, t_start=time.perf_counter(), require_tpu=False,
        config_overrides=TINY, control=True, log=io.StringIO(),
    )
    assert line["checks"]["dropped_pairs"]["value"] == 0
    assert "first_round_worst_gap" in _failed(line, "first_round_worst_gap", "first_round_median_gap")


def _patched_route(monkeypatch, scores_fn=None, bias_in_weights=False):
    from repro.models import moe

    def route(params, h, cfg):
        logits = jnp.dot(h, params["router"]).astype(jnp.float32)
        scores = (scores_fn or jax.nn.sigmoid)(logits)
        biased = scores + jax.lax.stop_gradient(params["expert_bias"])
        _, sel = jax.lax.top_k(biased, cfg.top_k)
        w = jnp.take_along_axis(biased if bias_in_weights else scores, sel, axis=-1)
        return scores, sel, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)

    monkeypatch.setattr(moe, "route", route)


def plant_softmax_routing(monkeypatch):
    _patched_route(monkeypatch, scores_fn=lambda l: jax.nn.softmax(l, axis=-1))


def plant_bias_in_the_weights(monkeypatch):
    _patched_route(monkeypatch, bias_in_weights=True)


def plant_capacity(monkeypatch):
    from repro.models import moe

    real = moe.dispatch_slots

    def capped(sel, first, held):
        slot = real(sel, first, held).reshape(-1)
        onehot = jax.nn.one_hot(slot, held + 1, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
        return jnp.where(pos < 4, slot, held).reshape(sel.shape)   # 4 pairs an expert

    monkeypatch.setattr(moe, "dispatch_slots", capped)


def plant_conv_without_its_b_gate(monkeypatch):
    from repro.models import lfm2

    def ungated(p, y, width):
        S = y.shape[1]
        _, c, u = jnp.split(y @ p["in_proj"], 3, axis=-1)
        bu = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
        return (c * sum(p["conv"][j] * bu[:, j : j + S] for j in range(width))) @ p["out_proj"]

    monkeypatch.setattr(lfm2, "_conv", ungated)


def plant_attention_without_qk_norm(monkeypatch):
    from repro.models import lfm2

    monkeypatch.setattr(lfm2, "_qk_norm", lambda x, w, eps: x)


def plant_client_update_dropped(monkeypatch):
    from repro.fl.strategies import Strategy

    real = Strategy._client_update

    def dropping(self):
        update = real(self)
        calls = {"n": 0}

        def fn(stack, x, y, n, key, z):
            trained, stats = update(stack, x, y, n, key, z)
            calls["n"] += 1
            if calls["n"] % 4 == 2:      # the second client of every round
                trained = jax.tree.map(lambda l: l[z], stack)
            return trained, stats

        return fn

    monkeypatch.setattr(Strategy, "_client_update", dropping)


def plant_unweighted_mean(monkeypatch):
    from repro.fl import strategies

    fold, write = strategies._fold_client, strategies._write_mean
    count = {"n": 0}

    def fold_one(acc, trained, w, fresh):
        count["n"] = 1 if fresh else count["n"] + 1
        return fold(acc, trained, jnp.float32(1.0), fresh)

    monkeypatch.setattr(strategies, "_fold_client", fold_one)
    monkeypatch.setattr(strategies, "_write_mean",
                        lambda stack, acc, wsum, z: write(stack, acc, jnp.float32(count["n"]), z))


# Each fault: how it is planted, the clients it needs to show (overrides of
# the configuration, at any size) and the checks through which it fails.
FAULTS = {
    "softmax_routing": (plant_softmax_routing, {},
                        ("routing_mismatch", "logits_rel_err", "first_round_median_gap")),
    "bias_in_the_weights": (plant_bias_in_the_weights, {}, ("routing_weight_l1",)),
    "capacity_drops_pairs": (plant_capacity, {}, ("dropped_pairs",)),
    "conv_without_its_b_gate": (plant_conv_without_its_b_gate, {},
                                ("logits_rel_err", "first_round_worst_gap", "first_round_median_gap")),
    # at the cell's size a random initial model's q and k already have an
    # RMS near one, so the fault shows in the trained change, not the logits
    "attention_without_qk_norm": (plant_attention_without_qk_norm, {},
                                  ("logits_rel_err", "first_round_worst_gap", "first_round_median_gap")),
    "client_update_dropped": (plant_client_update_dropped, {}, ("first_round_worst_gap",)),
    "unweighted_mean": (plant_unweighted_mean, {"sequences_per_client": [3, 3, 12, 12]},
                        ("first_round_worst_gap", "first_round_median_gap")),
}


def _fault_run(monkeypatch, fault):
    plant, overrides, names = FAULTS[fault]
    plant(monkeypatch)
    extra = RAGGED if overrides else {}
    return _failed(_run(**extra), *names)


def test_softmax_routing(monkeypatch):
    assert _fault_run(monkeypatch, "softmax_routing")


def test_bias_in_the_weights(monkeypatch):
    assert "routing_weight_l1" in _fault_run(monkeypatch, "bias_in_the_weights")


def test_capacity_drops_pairs(monkeypatch):
    assert "dropped_pairs" in _fault_run(monkeypatch, "capacity_drops_pairs")


def test_conv_without_its_b_gate(monkeypatch):
    assert _fault_run(monkeypatch, "conv_without_its_b_gate")


def test_attention_without_qk_norm(monkeypatch):
    assert _fault_run(monkeypatch, "attention_without_qk_norm")


def test_client_update_dropped(monkeypatch):
    assert "first_round_worst_gap" in _fault_run(monkeypatch, "client_update_dropped")


def test_unweighted_mean(monkeypatch):
    assert _fault_run(monkeypatch, "unweighted_mean")
