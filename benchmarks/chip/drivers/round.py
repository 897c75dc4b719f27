"""Closed loop of PACFL rounds with LeNet-5: ``PACFL.run_round``.

Set-up makes the MIX-4 clients from the seed (``mix4.py``), hands them to
the program as ``ClientData``, stacks them and runs ``PACFL.setup``: the
clients' SVD signatures, then eq2 clustering through the engine.  It then
warms every shape the window uses: the vmapped local update compiles once
for the round's sample size, and the aggregation's eager operations are
shaped by how many sampled clients a cluster has, so m warm rounds give
some cluster each count from 1 to m (``_warm_samples``).

The cluster models then start from the reference's own initial LeNet-5,
made from the seed (``round_reference.init_params``), and set-up drives the
federation through its first ``CHECK_ROUNDS`` rounds, through the window's
own call, on clients that all differ: the check follows those rounds, as a
training check follows a model's first steps.  For each, it keeps the
sample, the key and the cluster models before and after.  After the window,
``round_reference.py`` trains from the same initial model through the same
rounds, carrying its own models from round to round, and each cluster gives
each leaf's gap between the norms of the program's and the reference's
change from the initial model (``leaf_gaps``).  The check compares the
worst leaf's after the first round (``first_round_worst_gap``: the server's
first "gradient") and after the last (``three_round_worst_gap``: the change
after the three), and the median over the first round's leaves of all
touched clusters (``first_round_median_gap``): trained models amplify
rounding, and one leaf's gap swings with it, so the steady median is the
number a lower precision fails.  Clusters a round did not touch keep their
models bit for bit, and the set-up's clusters are checked against the
float64 reference's.

The window is a closed loop of the rounds that follow.  Round ``r`` draws
its distinct clients from the seed and its key, ``fold_in(root, r)``, before
its clock starts; the round is ``PACFL.run_round`` ended by
``jax.block_until_ready`` on the cluster models.  ``round_s`` is the
window's time over the rounds it completed.
"""
from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import mix4
import reference
import round_reference

# Rounds the check follows; its limits were read over this many.
CHECK_ROUNDS = 3
# ``fold_in(root, INIT_FOLD)`` makes the initial model; rounds take 0 (set-up),
# 1, 2, ... and the warm rounds count down from 2**31 - 1.
INIT_FOLD = 2**30


def _cluster(params, z: int) -> dict:
    return jax.tree.map(lambda l: np.asarray(l[z]), params)


def leaf_gaps(got: dict, want: dict, start: dict) -> list[float]:
    """Each leaf's gap between the norms of the program's and the reference's
    change of a cluster model from ``start``, against the reference's norm of
    that leaf or of the median leaf, whichever is larger.  Leaves the
    reference moves by under a thousandth of the median leaf move by
    rounding alone and are left out."""
    norm = lambda a, b: float(np.linalg.norm(np.asarray(a, np.float64) - b))
    moved_got = [norm(g, s) for g, s in zip(jax.tree.leaves(got), jax.tree.leaves(start))]
    moved_ref = [norm(w, s) for w, s in zip(jax.tree.leaves(want), jax.tree.leaves(start))]
    floor = float(np.median(moved_ref))
    return [abs(g - r) / max(r, floor) for g, r in zip(moved_got, moved_ref) if r >= 1e-3 * floor]


class Round:
    def __init__(self, run):
        self.run = run
        self.cfg = run.config

    def _stamp(self, what: str) -> None:
        now = time.perf_counter()
        print(f"[round setup] {what}: {now - self._t:.3f} s", file=sys.stderr, flush=True)
        self._t = now

    def setup(self) -> None:
        from repro.core.pacfl import PACFLConfig
        from repro.fl.client import stack_clients
        from repro.fl.partition import ClientData
        from repro.fl.strategies import PACFL, FLConfig
        from repro.models.cnn import init_lenet5, lenet5_apply

        cfg, seed = self.cfg, self.run.seed
        self._t = time.perf_counter()
        c = self.clients = mix4.clients(cfg, seed)
        given = [
            ClientData(c.x[k, : c.n[k]], c.y[k, : c.n[k]], c.x_test[k], c.y_test[k], c.names[k])
            for k in range(c.n_clients)
        ]
        data = stack_clients(given)
        self._stamp("clients")
        hw, ch = int(cfg["image_hw"]), int(cfg["channels"])
        pc = cfg["pacfl"]
        self.strat = PACFL(
            functools.partial(lenet5_apply, in_hw=(hw, hw), in_ch=ch),
            functools.partial(init_lenet5, in_hw=(hw, hw), in_ch=ch, n_classes=int(cfg["n_classes"])),
            FLConfig(
                sample_frac=float(cfg["sample_frac"]), local_epochs=int(cfg["local_epochs"]),
                batch_size=int(cfg["batch_size"]), lr=float(cfg["lr"]),
                momentum=float(cfg["momentum"]),
                pacfl=PACFLConfig(p=int(pc["p"]), beta=float(pc["beta"]),
                                  measure=pc["measure"], linkage=pc["linkage"]),
            ),
        )
        self.root = jax.random.PRNGKey(seed)
        self.strat.setup(jax.random.fold_in(self.root, 0), data)
        jax.block_until_ready(self.strat.cluster_params)
        self.labels = np.asarray(self.strat.labels)
        self._stamp(f"PACFL.setup ({len(np.unique(self.labels))} clusters)")
        K = c.n_clients
        self.m = max(1, min(K, int(round(float(cfg["sample_frac"]) * K))))
        self.rng = np.random.default_rng([seed, 5])
        self.step = self._control_round if self.run.control else self.strat.run_round
        warm = self._warm_samples()
        for i, sampled in enumerate(warm):
            self.step(-1 - i, sampled, jax.random.fold_in(self.root, 2**31 - 1 - i))
            jax.block_until_ready(self.strat.cluster_params)
        self._stamp(f"{len(warm)} warm rounds")
        self.init = self._initial_models()
        self.strat.cluster_params = self.init
        self.sample = [self._round(r, sampled) for r, sampled in enumerate(self._first_samples(), 1)]
        self._stamp(f"{len(self.sample)} check rounds")

    def _initial_models(self) -> dict:
        """The reference's initial LeNet-5 for every cluster, in the layout
        of the program's models, which it replaces."""
        have = self.strat.cluster_params
        Z = jax.tree.leaves(have)[0].shape[0]
        init = round_reference.init_params(jax.random.fold_in(self.root, INIT_FOLD), self.cfg, Z)
        shapes = lambda t: jax.tree.map(lambda l: (l.shape, l.dtype), t)
        if shapes(init) != shapes(have):
            raise ValueError(f"initial models {shapes(init)} differ from the program's {shapes(have)}")
        return jax.block_until_ready(init)

    def _round(self, rnd: int, sampled: np.ndarray):
        key = jax.random.fold_in(self.root, rnd)
        before = self.strat.cluster_params
        self.step(rnd, sampled, key)
        jax.block_until_ready(self.strat.cluster_params)
        return sampled, key, before, self.strat.cluster_params

    def _first_samples(self) -> list[np.ndarray]:
        """The first rounds' samples: ``CHECK_ROUNDS`` x m distinct clients."""
        K, need = self.clients.n_clients, CHECK_ROUNDS * self.m
        if K < need:
            raise ValueError(f"{CHECK_ROUNDS} check rounds of {self.m} distinct clients need "
                             f"{need} clients; the configuration builds {K}")
        drawn = np.random.default_rng([self.run.seed, 6]).permutation(K)
        return [np.sort(drawn[i * self.m : (i + 1) * self.m]) for i in range(CHECK_ROUNDS)]

    def _warm_samples(self) -> list[np.ndarray]:
        """One sample for each count c from 1 to m: c clients of a cluster
        that holds as many, the least used so far, and the rest drawn from
        the other clusters."""
        groups = [np.flatnonzero(self.labels == z) for z in np.unique(self.labels)]
        used = np.zeros(len(groups), int)
        out = []
        for cnt in range(1, self.m + 1):
            a = min((j for j, g in enumerate(groups) if g.size >= cnt), key=lambda j: used[j])
            used[a] += 1
            mine = self.rng.choice(groups[a], cnt, replace=False)
            rest = np.setdiff1d(np.arange(self.labels.size), groups[a])
            if rest.size < self.m - cnt:
                rest = np.setdiff1d(np.arange(self.labels.size), mine)
            out.append(np.sort(np.concatenate([mine, self.rng.choice(rest, self.m - cnt, replace=False)])))
        return out

    def _control_round(self, rnd: int, sampled: np.ndarray, key) -> None:
        """The reference's round in the program's place, one precision step
        below the configuration's float32: parameters and momentum held in
        bfloat16 between steps."""
        params = self.strat.cluster_params
        touched = np.unique(self.labels[sampled])
        before = {int(z): _cluster(params, int(z)) for z in touched}
        new = round_reference.round_updates(
            before, self.labels, sampled, key, self.clients, self.cfg, store=jnp.bfloat16
        )
        for z, tree in new.items():
            params = jax.tree.map(
                lambda all_, a: all_.at[z].set(jnp.asarray(a, all_.dtype)), params, tree
            )
        self.strat.cluster_params = params

    def window(self, seconds: float) -> None:
        run = self.run
        K = self.clients.n_clients
        times = []
        t_start = time.perf_counter()
        t_end = t_start + seconds
        rnd = len(self.sample)
        while time.perf_counter() < t_end:
            rnd += 1
            sampled = np.sort(self.rng.choice(K, self.m, replace=False))
            key = jax.random.fold_in(self.root, rnd)
            key.block_until_ready()
            t0 = time.perf_counter()
            with run.spans.span("round"):
                self.step(rnd, sampled, key)
                jax.block_until_ready(self.strat.cluster_params)
            times.append(time.perf_counter() - t0)
        elapsed = time.perf_counter() - t_start
        run.steps = run.attempted = len(times)
        run.step_s = times
        run.e2e["round_s"] = elapsed / len(times)

    def check(self) -> list:
        cfg, limits = self.cfg, self.cfg["limits"]
        mismatch = reference.label_mismatch(
            self.labels, round_reference.cluster_labels(self.clients, cfg)
        )
        init = _cluster(self.init, 0)
        ref, first, untouched = {}, [], 0
        for r, (sampled, key, before, after) in enumerate(self.sample):
            touched = np.unique(self.labels[sampled])
            start = {int(z): ref.get(int(z), init) for z in touched}
            ref.update(round_reference.round_updates(
                start, self.labels, sampled, key, self.clients, cfg
            ))
            if r == 0:
                for z, want in ref.items():
                    first += leaf_gaps(_cluster(after, z), want, init)
            for z in range(jax.tree.leaves(after)[0].shape[0]):
                if z not in touched:
                    same = jax.tree.map(np.array_equal, _cluster(after, z), _cluster(before, z))
                    untouched += int(not all(jax.tree.leaves(same)))
        last = self.sample[-1][3]
        worst = max(max(leaf_gaps(_cluster(last, z), want, init)) for z, want in ref.items())
        return [
            ("first_round_worst_gap", max(first), limits["first_round_worst_gap"]),
            ("first_round_median_gap", float(np.median(first)), limits["first_round_median_gap"]),
            ("three_round_worst_gap", worst, limits["three_round_worst_gap"]),
            ("untouched_changed", untouched, limits["untouched_changed"]),
            ("label_mismatch", mismatch, limits["label_mismatch"]),
        ]


def make(run):
    return Round(run)
