"""The round's plain reference agrees with the program's own local update and
average (``make_local_sgd`` + ``weighted_average``) on the CPU, to float32
rounding, and its bfloat16 control does not.  Its initial model has the
program's layout, and the driver's warm rounds cover every count of sampled
clients a cluster can have."""
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import mix4
import round_reference

CFG = json.loads((Path(round_reference.__file__).parent / "configs" / "mix4-lenet5.json").read_text())
CFG.update({"n_clients": 4, "samples_per_client": [40, 80], "dataset_train": 400,
            "dataset_test": 100, "test_per_client": 10, "local_epochs": 1})
STEPS = 3


def _program_round(before, clients, key):
    from repro.fl.client import make_local_sgd, weighted_average
    from repro.models.cnn import lenet5_apply

    apply_fn = functools.partial(lenet5_apply, in_hw=(32, 32), in_ch=3)
    local = jax.jit(jax.vmap(make_local_sgd(
        apply_fn, steps=STEPS, batch_size=CFG["batch_size"], lr=CFG["lr"], momentum=CFG["momentum"],
    )))
    m = 2
    stacked = jax.tree.map(lambda l: jnp.broadcast_to(l, (m,) + l.shape), before)
    n = jnp.asarray(clients.n[:m])
    new = local(stacked, jnp.asarray(clients.x[:m]), jnp.asarray(clients.y[:m]), n,
                jax.random.split(key, m), stacked, None)
    return jax.tree.map(np.asarray, weighted_average(new, n.astype(jnp.float32)))


@pytest.fixture(scope="module")
def case():
    from repro.models.cnn import init_lenet5

    clients = mix4.clients(CFG, 2**31 + 11)
    before = init_lenet5(jax.random.PRNGKey(3), in_hw=(32, 32), in_ch=3, n_classes=40)
    key = jax.random.PRNGKey(2**31 + 12)
    return clients, jax.tree.map(np.asarray, before), key


def _reference(case, store=jnp.float32):
    clients, before, key = case
    # two clients of one cluster; local_steps of the (40, 80) sizes: 1 epoch of 60 // 20
    assert round_reference.local_steps(CFG, clients.n[:2]) == STEPS
    sub = mix4.Clients(clients.x[:2], clients.y[:2], clients.n[:2], clients.x_test[:2],
                       clients.y_test[:2], clients.names[:2])
    out = round_reference.round_updates({0: before}, np.zeros(2, int), np.arange(2), key, sub, CFG,
                                        store=store)
    return out[0]


def _gaps(got, want, before):
    return [
        (float(np.abs(g - w).max()), float(np.abs(w - b).max()))
        for g, w, b in zip(*map(jax.tree.leaves, (got, want, before)))
    ]


def test_reference_matches_program(case):
    got = _program_round(case[1], case[0], case[2])
    for gap, moved in _gaps(got, _reference(case), case[1]):
        assert moved > 1e-4          # every leaf moved
        assert gap <= 1e-6           # float32 rounding of weights below 0.5


def test_bfloat16_control_departs(case):
    got = _program_round(case[1], case[0], case[2])
    worst = max(gap / moved for gap, moved in _gaps(got, _reference(case, jnp.bfloat16), case[1]))
    assert worst > 0.1


def test_initial_model_has_the_program_layout():
    from repro.models.cnn import init_lenet5

    got = round_reference.init_params(jax.random.PRNGKey(2**31 + 13), CFG, 3)
    want = init_lenet5(jax.random.PRNGKey(0), in_hw=(32, 32), in_ch=3, n_classes=40)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == (3,) + w.shape and g.dtype == w.dtype
        assert np.array_equal(g[0], g[2])                  # one model for every cluster
        if w.ndim > 1:                                     # weights N(0, 1 / fan_in)
            fan_in = int(np.prod(w.shape[:-1]))
            assert abs(float(np.std(g[0])) * np.sqrt(fan_in) - 1.0) < 0.15
        else:
            assert not np.any(g)


@pytest.mark.parametrize("sizes", [(40, 30, 27), (60,), (6, 50, 3, 38)])
def test_warm_rounds_cover_every_count(sizes):
    driver = harness.load_module("drivers", "round")
    cell = driver.Round.__new__(driver.Round)
    cell.labels = np.repeat(np.arange(len(sizes)), sizes)
    cell.m, cell.rng = 10, np.random.default_rng(0)
    samples = cell._warm_samples()
    counts = set()
    for s in samples:
        assert len(s) == cell.m and len(np.unique(s)) == cell.m
        counts |= set(np.bincount(cell.labels[s]).tolist())
    seen = {int(z) for s in samples for z in cell.labels[s]}
    assert seen == set(range(len(sizes)))
    # with one cluster every round gives it all m
    assert set(range(1, cell.m + 1) if len(sizes) > 1 else [cell.m]) <= counts
