"""Closed loop of PACFL rounds with LFM2-8B-A1B's share of one chip as the
per-cluster model: ``PACFL.run_round`` on token clients.

Set-up makes the token clients from the seed (``lmtokens.py``), hands them
to the program as ``ClientData`` and runs ``PACFL.setup``: each client's
SVD signature of its (3,072, sequences) matrix of folded token counts, then
eq2 clustering.  One warm round compiles every program the window runs:
the sequential round's client update (one shape: every client holds the
same sequences) and the per-cluster mean.  The program's own initial models
are then replaced by the reference's (``lfm2_reference.init_params``, from
the seed), and set-up drives two check rounds through the window's own
call: round 1 on clients drawn from the seed, whose clusters' models the
check compares with the reference's, and round 2 on clients of one cluster
only, after which the other cluster's model must not have changed by a bit.
Both rounds keep on the host what the check needs.  The control (the
reference's round in the program's place) runs neither the warm round nor
round 2: it compiles nothing of the program's, and touches only the
clusters it trains.

The window is a closed loop of the rounds that follow: round ``r`` draws
its distinct clients and its key, ``fold_in(root, r)``, before its clock
starts; the round is ``PACFL.run_round`` ended by
``jax.block_until_ready`` on the cluster models.  ``round_s`` is the
window's time over the rounds it completed.

The check, after the window, with the program's models freed from the
device:

* ``logits_rel_err``: the program's forward logits on round 1's first
  batch (its first client's first step), from the initial model, against
  the reference's, ``||a - b|| / ||b||``.  The timed client update never
  forms the logits (its loss is blocked over positions), so they come from
  ``lfm2.forward``, a separate program over the same ``lfm2.features`` the
  update's ``apply`` runs, without the update's recompute; what the timed
  program itself produced is held to the reference by the gaps below;
* ``first_round_worst_gap`` and ``first_round_median_gap``: each leaf's gap
  between the norms of the program's and the reference's change of a
  cluster's model in round 1 (``round.leaf_gaps``, as ``round.mix4-lenet5``
  reads them);
* the program's router on each MoE layer's reference input against the
  reference's routing of it, over the tokens the reference routes clearly
  (its 4th and 5th biased scores more than ``ROUTING_EPS`` apart):
  ``routing_weight_l1``, the mean L1 distance between their top-k weights,
  and exact, 0, ``routing_mismatch``, the tokens whose held experts differ;
* exact, 0: ``dropped_pairs`` (per MoE layer, over round 1's clients and steps, the
  distance between the pairs the program's router sent to held experts and
  the rows its grouped products' output holds, counted from that output;
  the control is the reference, which computes every held expert on every
  token, and reads 0), ``untouched_changed``
  and ``label_mismatch`` (set-up's clusters against float64 eq2 and SciPy).
"""
from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import lfm2_reference
import lmtokens
import reference
from drivers.round import leaf_gaps

# ``fold_in(root, INIT_FOLD)`` makes the initial model; the warm round takes
# 2**31 - 1, rounds 1 and 2 are the check's.
INIT_FOLD = 2**30
# Routing is compared, on the reference's input, where the reference's 4th
# and 5th biased scores are further apart than this: a sigmoid score moves
# by at most a quarter of its logit's rounding, and the router's product at
# the TPU's default precision (one bfloat16 pass over 2,048 terms) rounds
# logits of order 1 by about 2e-3 (one standard deviation).
ROUTING_EPS = 1e-2
# A client's data matrix for PACFL: each sequence's token counts folded into
# this many buckets by ``id mod BUCKETS``.
BUCKETS = 3072


def arch_config(cfg: dict):
    from repro.configs.lfm2_8b_a1b import from_hf

    return from_hf(cfg)


def _host(tree) -> dict:
    return jax.tree.map(np.asarray, tree)


def _one(stack, z: int) -> dict:
    return jax.tree.map(lambda l: np.asarray(l[z]), stack)


class LMRound:
    def __init__(self, run):
        self.run = run
        self.cfg = run.config

    def _stamp(self, what: str) -> None:
        now = time.perf_counter()
        print(f"[lmround setup] {what}: {now - self._t:.3f} s", file=sys.stderr, flush=True)
        self._t = now

    def setup(self) -> None:
        from repro.core.pacfl import PACFLConfig
        from repro.fl.client import stack_clients
        from repro.fl.partition import ClientData
        from repro.fl.strategies import PACFL, FLConfig
        from repro.models import lfm2

        cfg, seed = self.cfg, self.run.seed
        self._t = time.perf_counter()
        c = self.clients = lmtokens.clients(cfg, seed)
        given = [ClientData(c.x[k, : c.n[k]], np.zeros(c.n[k], np.int64), c.x[k, :1],
                            np.zeros(1, np.int64), f"domain{c.domain[k]}")
                 for k in range(c.n_clients)]
        self.arch = arch_config(cfg)
        pc = cfg["pacfl"]
        self.strat = PACFL(
            functools.partial(lfm2.apply, cfg=self.arch),
            functools.partial(lfm2.init_params, cfg=self.arch),
            FLConfig(
                sample_frac=float(cfg["sample_frac"]), local_epochs=int(cfg["local_epochs"]),
                batch_size=int(cfg["batch_size"]), lr=float(cfg["lr"]),
                momentum=float(cfg["momentum"]),
                pacfl=PACFLConfig(p=int(pc["p"]), beta=float(pc["beta"]),
                                  measure=pc["measure"], linkage=pc["linkage"]),
            ),
        )
        self.root = jax.random.PRNGKey(seed)
        self.strat.setup(jax.random.fold_in(self.root, 0), stack_clients(given))
        jax.block_until_ready(self.strat.cluster_params)
        self.labels = np.asarray(self.strat.labels)
        self.Z = int(jax.tree.leaves(self.strat.cluster_params)[0].shape[0])
        self._stamp(f"clients and PACFL.setup ({self.Z} clusters)")
        K = c.n_clients
        self.m = max(1, min(K, int(round(float(cfg["sample_frac"]) * K))))
        self.rng = np.random.default_rng([seed, 5])
        self.step = self._control_round if self.run.control else self.strat.run_round
        warm = np.sort(self.rng.choice(K, self.m, replace=False))
        if not self.run.control:   # the control runs none of the program's code
            self.step(-1, warm, jax.random.fold_in(self.root, 2**31 - 1))
            jax.block_until_ready(self.strat.cluster_params)
            self._stamp("warm round")
        self.strat.cluster_params = None
        init = lfm2_reference.init_params(jax.random.fold_in(self.root, INIT_FOLD), cfg, self.Z)
        self.init = _one(init, 0)
        self.strat.cluster_params = init
        del init
        first, second = self._check_samples()
        self.first = self._round(1, first)
        # the control's round returns only the clusters it trains: it leaves
        # the others by construction, and reads 0 without a round of its own
        self.untouched = 0 if self.run.control else self._untouched(second)
        self._stamp("check rounds")

    def _untouched(self, second: np.ndarray) -> int:
        """Round 2 on ``second``, clients of one cluster: the clusters it did
        not touch whose models changed by a bit."""
        others = [z for z in range(self.Z) if z not in set(self.labels[second].tolist())]
        before = {z: _one(self.strat.cluster_params, z) for z in others}
        self.step(2, second, jax.random.fold_in(self.root, 2))
        jax.block_until_ready(self.strat.cluster_params)
        return sum(
            int(not all(np.array_equal(a, np.asarray(b)) for a, b in zip(
                jax.tree.leaves(before[z]), jax.tree.leaves(jax.tree.map(lambda l: l[z], self.strat.cluster_params)))))
            for z in others
        )

    def _check_samples(self) -> tuple[np.ndarray, np.ndarray]:
        """Round 1: m distinct clients drawn from the seed; round 2: m
        clients of one cluster, so that the other keeps its model."""
        rng = np.random.default_rng([self.run.seed, 6])
        K = self.clients.n_clients
        first = np.sort(rng.choice(K, self.m, replace=False))
        group = np.flatnonzero(self.labels == self.labels[rng.integers(K)])
        second = np.sort(rng.choice(group, min(self.m, group.size), replace=False))
        return first, second

    def _round(self, rnd: int, sampled: np.ndarray):
        key = jax.random.fold_in(self.root, rnd)
        self.step(rnd, sampled, key)
        jax.block_until_ready(self.strat.cluster_params)
        touched = np.unique(self.labels[sampled])
        after = {int(z): _one(self.strat.cluster_params, int(z)) for z in touched}
        stats = _host(getattr(self.strat, "round_stats", {}))
        return sampled, key, after, stats

    def _control_round(self, rnd: int, sampled: np.ndarray, key) -> None:
        """The reference's round in the program's place, one precision step
        below the configuration's float32: parameters held in bfloat16
        between local steps."""
        models = {z: _one(self.strat.cluster_params, z) for z in range(self.Z)}
        self.strat.cluster_params = None   # the reference trains in their place
        models.update(lfm2_reference.round_updates(
            models, self.labels, sampled, key, self.clients.x, self.clients.n, self.cfg,
            store=jnp.bfloat16,
        ))
        self.strat.cluster_params = jax.tree.map(
            lambda *l: jnp.asarray(np.stack(l), jnp.float32), *[models[z] for z in range(self.Z)]
        )
        self.strat.round_stats = {}

    def window(self, seconds: float) -> None:
        run = self.run
        K = self.clients.n_clients
        times = []
        t_start = time.perf_counter()
        t_end = t_start + seconds
        rnd = 2
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

    def _first_batch(self, sampled, key) -> np.ndarray:
        """The sequences of round 1's first step of its first client (the
        program's key contract)."""
        k = int(sampled[0])
        steps = lfm2_reference.local_steps(self.cfg, self.clients.n)
        key_t = jax.random.split(jax.random.split(key, len(sampled))[0], steps)[0]
        idx = jax.random.randint(key_t, (int(self.cfg["batch_size"]),), 0, int(self.clients.n[k]))
        return self.clients.x[k][np.asarray(idx)]

    def check(self) -> list:
        from repro.models import lfm2

        cfg, limits = self.cfg, self.cfg["limits"]
        self.strat.cluster_params = None   # free the device for the reference
        mismatch = reference.label_mismatch(self.labels, self._reference_labels())
        sampled, key, after, stats = self.first
        tokens = jnp.asarray(self._first_batch(sampled, key))
        init = jax.tree.map(jnp.asarray, self.init)
        got, _ = jax.jit(lambda p, t: lfm2.forward(p, self.arch, t))(init, tokens)
        want, moe_in = lfm2_reference.reference_forward(init, tokens, cfg)
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        del got, want
        routing, weight_l1 = self._routing(init, moe_in)
        del init, moe_in
        start = {int(z): self.init for z in after}
        ref = lfm2_reference.round_updates(
            start, self.labels, sampled, key, self.clients.x, self.clients.n, cfg
        )
        gaps = [g for z, want in ref.items() for g in leaf_gaps(after[z], want, self.init)]
        if self.run.control:
            # the reference computes each held expert on every token and
            # weighs it by its gate: it drops no routed pair by construction
            dropped = 0
        else:
            # a round that reports no counts cannot show that nothing was dropped
            dropped = (int(np.abs(np.asarray(stats["routed"]) - np.asarray(stats["computed"])).sum())
                       if "computed" in stats else 1)
        return [
            ("logits_rel_err", rel, limits["logits_rel_err"]),
            ("first_round_worst_gap", max(gaps), limits["first_round_worst_gap"]),
            ("first_round_median_gap", float(np.median(gaps)), limits["first_round_median_gap"]),
            ("dropped_pairs", dropped, limits["dropped_pairs"]),
            ("routing_mismatch", routing, limits["routing_mismatch"]),
            ("routing_weight_l1", weight_l1, limits["routing_weight_l1"]),
            ("untouched_changed", self.untouched, limits["untouched_changed"]),
            ("label_mismatch", mismatch, limits["label_mismatch"]),
        ]

    def _routing(self, init, moe_in: list) -> tuple[int, float]:
        """The program's router (``moe.route``, which its MoE layer calls) on
        each MoE layer's reference input against the reference's routing of
        the same input, over the tokens the reference routes clearly (its
        4th and 5th biased scores more than ``ROUTING_EPS`` apart):
        ``routing_mismatch``, the tokens whose held experts differ, summed
        over the layers; ``routing_weight_l1``, the mean L1 distance between
        their top-k weights spread over all experts (0 where equal, 2 where
        disjoint)."""
        from repro.models import moe

        first, held, k, E = (int(self.cfg[key]) for key in
                             ("first_expert", "n_experts_held", "num_experts_per_tok", "num_experts"))
        route = jax.jit(lambda p, h: moe.route(p, h, self.arch)[1:])
        layers = [p["moe"] for p in init["layers"] if "moe" in p]
        mine = lambda e: (e >= first) & (e < first + held)
        mismatch, l1, clear_tokens = 0, 0.0, 0
        for p, h in zip(layers, moe_in):
            biased, top, g = (np.asarray(a) for a in lfm2_reference.reference_route(p, h, self.cfg))
            sel, w = (np.asarray(a) for a in route(p, h))
            ranked = -np.sort(-biased, axis=-1)
            clear = ranked[:, k - 1] - ranked[:, k] > ROUTING_EPS
            a = np.sort(np.where(mine(sel), sel, -1), axis=-1)
            b = np.sort(np.where(mine(top), top, -1), axis=-1)
            mismatch += int(np.sum(clear & np.any(a != b, axis=-1)))
            rows = np.arange(len(top))[:, None]
            dense = np.zeros((len(top), E))
            np.add.at(dense, (rows, top), -g)
            np.add.at(dense, (rows, sel), w)
            l1 += float(np.sum(np.abs(dense[clear])))
            clear_tokens += int(np.sum(clear))
        return mismatch, l1 / max(clear_tokens, 1)

    def _reference_labels(self) -> np.ndarray:
        """float64 eq2 signatures of the folded token counts, SciPy's HC."""
        pc = self.cfg["pacfl"]
        U = []
        for x in self.clients.x:
            D = np.stack([np.bincount(r % BUCKETS, minlength=BUCKETS) for r in x], 1).astype(np.float64)
            U.append(np.linalg.svd(D, full_matrices=False)[0][:, : int(pc["p"])])
        A = reference.proximity_f64(np.stack(U), pc["measure"])
        return reference.hc_labels(A, float(pc["beta"]), pc["linkage"])


def make(run):
    return LMRound(run)
