"""Federated strategies: PACFL + every baseline the paper compares against.

Global: FedAvg, FedProx, FedNova, SCAFFOLD.
Personalized: SOLO, LG-FedAvg, Per-FedAvg.
Clustered: IFCA (fixed C), CFL (Sattler bipartitioning), PACFL (this paper).

Each strategy implements ``setup``/``run_round``/``eval_params`` over the
stacked-clients representation.  Communication bytes are tracked per round
(``comm_up``/``comm_down``) for the Table 5/9/10 reproductions.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import re
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.pacfl import PACFLConfig, cluster_clients, compute_signatures
from repro.core.signatures import FamilyContext, get_family, payloads_from_stacked
from repro.fl.client import (
    StackedClients,
    batch_eval,
    ce_loss,
    feature_matrix,
    make_local_sgd,
    make_perfedavg_local,
    token_eval,
    tree_size_bytes,
    weighted_average,
)

PyTree = Any


@dataclass
class FLConfig:
    rounds: int = 50
    sample_frac: float = 0.1
    local_epochs: int = 5
    batch_size: int = 20
    lr: float = 0.01
    momentum: float = 0.5
    # strategy-specific knobs (paper defaults)
    prox_mu: float = 0.01
    perfed_alpha: float = 1e-2
    perfed_beta: float = 1e-3
    ifca_clusters: int = 2
    cfl_eps1: float = 0.4
    cfl_eps2: float = 1.6
    pacfl: PACFLConfig = field(default_factory=PACFLConfig)
    personalize_steps: int = 25   # eval-time fine-tune for Per-FedAvg

    def local_steps(self, n_avg: int) -> int:
        return max(1, self.local_epochs * max(1, n_avg // self.batch_size))


def _take(tree: PyTree, idx: np.ndarray) -> PyTree:
    return jax.tree.map(lambda l: l[idx], tree)


def _broadcast(tree: PyTree, m: int) -> PyTree:
    return jax.tree.map(lambda l: jnp.broadcast_to(l, (m,) + l.shape), tree)


def _zeros_like_stack(tree: PyTree, m: int) -> PyTree:
    return jax.tree.map(lambda l: jnp.zeros((m,) + l.shape, l.dtype), tree)


def device_memory_bytes() -> int:
    """Memory of the device the round runs on: the accelerator's own limit,
    or the host's RAM where the backend reports none (the CPU)."""
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")


def vmapped_fits(model_bytes: int, m: int, n_models: int, device_bytes: int) -> bool:
    """Whether one local-update copy per sampled client fits: the ``m``
    trained copies, their gradients and momentum (3 m models) beside the
    ``n_models`` the strategy holds, in half the device's memory (the rest
    is activations)."""
    return (3 * m + n_models) * model_bytes <= device_bytes // 2


@functools.partial(jax.jit, donate_argnums=(0,))
def _fold_client(acc, trained, w, fresh):
    """``acc <- (0 if fresh else acc) + w * trained``, in place.  Kept apart
    from the client's update: fused into it, XLA holds two more model-sized
    buffers (16.6 against 14.5 GB for LFM2's one-chip share on a v5e)."""
    return jax.tree.map(lambda a, p: jnp.where(fresh, 0.0, a) + w * p, acc, trained)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_mean(cluster_params, acc, wsum, z):
    """Cluster ``z``'s model becomes the running weighted sum over its
    weight: the per-cluster weighted mean, in place."""
    return jax.tree.map(lambda all_, a: all_.at[z].set(a / wsum), cluster_params, acc)


def _cluster_means_program() -> Callable:
    """A new jitted ``(cluster_params, new, pick, w) -> cluster_params``: the
    vmapped round's per-cluster FedAvg as one program.  Row ``z`` of the
    (Z, m) weights is ``w[i]`` where client ``i`` picked model ``z`` and 0
    elsewhere; each row goes through :func:`weighted_average` (a normalised
    ``tensordot``, here at ``HIGHEST``), and models no sampled client picked
    keep theirs bit for bit.  Its shapes are (Z, m) whatever the sample's
    clusters, so it compiles once per (m, Z).  Made anew per strategy, so
    each traces the module's ``weighted_average`` as it stands then."""

    def cluster_means(cluster_params, new, pick, w):
        Z = jax.tree.leaves(cluster_params)[0].shape[0]
        member = pick[None, :] == jnp.arange(Z, dtype=pick.dtype)[:, None]
        with jax.default_matmul_precision("highest"):
            means = jax.vmap(weighted_average, in_axes=(None, 0))(
                new, jnp.where(member, w[None, :], 0.0)
            )
        touched = member.any(axis=1)
        return jax.tree.map(
            lambda old, mean: jnp.where(
                touched.reshape((Z,) + (1,) * (old.ndim - 1)), mean, old
            ),
            cluster_params, means,
        )

    return jax.jit(cluster_means)


@jax.jit
def _zeros_one(cluster_params):
    return jax.tree.map(lambda l: jnp.zeros(l.shape[1:], l.dtype), cluster_params)


def bucket_steps(steps: int) -> int:
    """Geometric step buckets for post-churn local-update rebuilds.

    Snaps to {1..4, 6, 8, 12, 16, 24, 32, ...} — powers of two plus
    midpoints — so a drifting mean client size causes O(log steps) distinct
    jit compiles over a federation's lifetime instead of one per churn
    batch, while keeping the step count (and FedNova's tau) within ~20% of
    the exact post-churn value.
    """
    steps = int(steps)
    if steps <= 4:
        return steps
    base = 1 << int(np.floor(np.log2(steps)))
    cands = (base, base + (base >> 1), base << 1)
    return int(min(cands, key=lambda c: abs(c - steps)))


class Strategy:
    """Base: holds jitted vmapped local updates and communication counters."""

    name = "base"
    uses_anchor = False
    uses_cv = False
    # Strategies that can absorb clients joining/leaving between rounds set
    # this and (if they hold per-client or per-cluster state) override
    # handle_churn.  The trainer refuses a churn schedule otherwise.
    supports_churn = False

    def __init__(self, apply_fn: Callable, init_fn: Callable, cfg: FLConfig):
        self.apply_fn = apply_fn
        self.init_fn = init_fn
        self.cfg = cfg
        self.comm_up = 0      # cumulative bytes clients -> server
        self.comm_down = 0    # cumulative bytes server -> clients
        self.history: list[dict] = []

    # -- to be provided by subclasses -------------------------------------
    def setup(self, key: jax.Array, data: StackedClients) -> None:
        raise NotImplementedError

    def run_round(self, rnd: int, sampled: np.ndarray, key: jax.Array) -> None:
        raise NotImplementedError

    def eval_params(self) -> PyTree:
        """Stacked per-client params (K, ...) used for local-test evaluation."""
        raise NotImplementedError

    def handle_churn(self, data: StackedClients, batch) -> None:
        """Absorb one drained churn batch (``repro.fl.churn.ChurnBatch``).

        ``data`` is the stacked clients *after the full drain* (the trainer
        restacks once per drain, not per batch); per-batch engine work must
        come from the batch itself — leave positions resolve against the
        strategy's own membership state and newcomer signatures arrive
        precomputed on the batch.  The base implementation swaps the
        stacked data and refreshes the jitted local update for the
        post-churn client sizes — correct for strategies whose state is
        global (FedAvg/FedProx/FedNova/Per-FedAvg).  Strategies with
        per-client or per-cluster state must override (PACFL routes the
        batch through its cluster engine) or leave ``supports_churn``
        False.
        """
        if not self.supports_churn:
            raise NotImplementedError(f"{self.name} does not support churn")
        self.data = data
        self._refresh_local(data)

    def churn_signature_fn(self):
        """Eager-signature hook for the async churn queue.

        Returns a callable ``(ClientData) -> (n, p) signature`` the queue
        runs at enqueue time (overlapping the in-flight round), or ``None``
        when the strategy needs no signatures (everyone but PACFL).
        """
        return None

    # -- shared machinery ---------------------------------------------------
    def _build(self, data: StackedClients, *, prox_mu: float = 0.0, use_cv: bool = False):
        self._prox_mu = prox_mu
        self._use_cv = use_cv
        self._local_cache: dict[int, Callable] = {}
        self._steps_exact = self.cfg.local_steps(int(np.mean(data.n)))
        self._set_steps(self._steps_exact)
        self.data = data
        self._P = None  # model bytes, set after init
        self._device_bytes = device_memory_bytes()
        self._seq_cache: dict[int, Callable] = {}

    def _make_local(self, steps: int, **kw) -> Callable:
        """Local-update factory for a given step count (Per-FedAvg overrides)."""
        return make_local_sgd(
            self.apply_fn,
            steps=steps,
            batch_size=self.cfg.batch_size,
            lr=self.cfg.lr,
            momentum=self.cfg.momentum,
            prox_mu=self._prox_mu,
            use_control_variates=self._use_cv,
            **kw,
        )

    def _set_steps(self, steps: int) -> None:
        self._steps = steps
        fn = self._local_cache.get(steps)
        if fn is None:
            fn = jax.jit(jax.vmap(self._make_local(steps)))
            self._local_cache[steps] = fn
        self._vupdate = fn

    def _refresh_local(self, data: StackedClients) -> None:
        """Rebuild the jitted local update when churn shifts the mean client
        size: ``self._steps`` (and with it FedNova's tau normalization and
        the local-epoch budget) would otherwise stay sized from the
        *pre-churn* mean.  The trigger compares *exact* step counts — churn
        that leaves the mean unchanged is a true no-op — while the rebuilt
        count is shape-bucketed (:func:`bucket_steps`) and the compiled
        updates memoized per step count, so oscillating churn cannot
        trigger a recompile storm.
        """
        exact = self.cfg.local_steps(int(np.mean(data.n)))
        if exact != self._steps_exact:
            self._steps_exact = exact
            steps = bucket_steps(exact)
            if steps != self._steps:
                self._set_steps(steps)

    def _model_bytes(self, params: PyTree) -> int:
        if self._P is None:
            self._P = tree_size_bytes(params)
        return self._P

    def _run_local(self, stacked_params, sampled, key, anchors=None, c_diffs=None):
        m = len(sampled)
        x = jnp.asarray(self.data.x[sampled])
        y = jnp.asarray(self.data.y[sampled])
        n = jnp.asarray(self.data.n[sampled])
        keys = jax.random.split(key, m)
        if anchors is None:
            anchors = stacked_params
        if c_diffs is None:
            c_diffs = _zeros_like_stack(jax.tree.map(lambda l: l[0], stacked_params), m)
        return self._vupdate(stacked_params, x, y, n, keys, anchors, c_diffs)

    def _client_update(self) -> Callable:
        """The jitted update of one client from its model in a stack:
        ``(stack, x, y, n, key, z) -> (trained, stats)``, one program for
        every client of the round's shape (``jit_client_update``)."""
        fn = self._seq_cache.get(self._steps)
        if fn is None:
            local = self._make_local(self._steps, with_stats=True)

            def client_update(stack, x, y, n, key, z):
                start = jax.tree.map(lambda l: l[z], stack)
                return local(start, x, y, n, key, start, start)

            fn = self._seq_cache[self._steps] = jax.jit(client_update)
        return fn

    def _run_sequential(self, stack, pick, sampled, key):
        """A round's local updates one client after another, each from model
        ``pick[i]`` of ``stack``, folded into a running weighted sum per
        touched model that replaces it once its last client is in; models no
        sampled client picked keep theirs bit for bit.  The clients keep
        their sample-order keys (``split(key, m)[i]``) and run grouped by
        model.  Used where one model copy per sampled client does not fit
        (:func:`vmapped_fits`).  Returns the new stack and the model's
        counts summed over the round's clients and steps."""
        keys = jax.random.split(key, len(sampled))
        order = np.argsort(pick, kind="stable")
        update = self._client_update()
        seq_len = int(self.data.x.shape[-1])
        acc, stats = _zeros_one(stack), []
        for j, i in enumerate(order):
            k, z = int(sampled[i]), int(pick[i])
            w = float(self.data.n[k])
            fresh = j == 0 or pick[order[j - 1]] != z
            wsum = w if fresh else wsum + w
            with tracing.span("lmround.local") as sp:
                sp.count("tokens", int(self.data.n[k]) * seq_len)
                trained, st = update(
                    stack, jnp.asarray(self.data.x[k]), jnp.asarray(self.data.y[k]),
                    jnp.int32(self.data.n[k]), keys[i], jnp.int32(z),
                )
                acc = _fold_client(acc, trained, jnp.float32(w), fresh)
                del trained
            stats.append(st)
            if j == len(order) - 1 or pick[order[j + 1]] != z:
                with tracing.span("lmround.aggregate"):
                    stack = _write_mean(stack, acc, jnp.float32(wsum), jnp.int32(z))
        return stack, jax.tree.map(lambda *s: sum(s), *stats)

    def evaluate(self) -> np.ndarray:
        params = self.eval_params()
        acc = batch_eval(
            self.apply_fn, params,
            jnp.asarray(self.data.x_test), jnp.asarray(self.data.y_test),
            jnp.asarray(self.data.t),
        )
        return np.asarray(acc)


# ===========================================================================
# Global strategies
# ===========================================================================


class FedAvg(Strategy):
    name = "fedavg"
    supports_churn = True   # all state is global: churn just swaps the data

    def setup(self, key, data):
        self._build(data)
        self.global_params = self.init_fn(key)

    def run_round(self, rnd, sampled, key):
        m = len(sampled)
        P = self._model_bytes(self.global_params)
        stacked = _broadcast(self.global_params, m)
        new = self._run_local(stacked, sampled, key)
        w = jnp.asarray(self.data.n[sampled], jnp.float32)
        self.global_params = weighted_average(new, w)
        self.comm_down += P * m
        self.comm_up += P * m

    def eval_params(self):
        return _broadcast(self.global_params, self.data.n_clients)


class FedProx(FedAvg):
    name = "fedprox"

    def setup(self, key, data):
        self._build(data, prox_mu=self.cfg.prox_mu)
        self.global_params = self.init_fn(key)


class FedNova(FedAvg):
    name = "fednova"

    def run_round(self, rnd, sampled, key):
        # With uniform local steps FedNova == FedAvg up to the tau_eff scale;
        # we implement the normalized-update form explicitly.
        m = len(sampled)
        P = self._model_bytes(self.global_params)
        stacked = _broadcast(self.global_params, m)
        new = self._run_local(stacked, sampled, key)
        w = jnp.asarray(self.data.n[sampled], jnp.float32)
        w = w / jnp.sum(w)
        tau = jnp.full((m,), float(self._steps))
        tau_eff = jnp.sum(w * tau)

        def nova(g, ns):
            # d_k = (g - theta_k) / tau_k ; g' = g - tau_eff * sum_k w_k d_k
            d = (g[None] - ns) / tau[(...,) + (None,) * (ns.ndim - 1)]
            return g - tau_eff * jnp.tensordot(w, d, axes=(0, 0))

        self.global_params = jax.tree.map(nova, self.global_params, new)
        self.comm_down += P * m
        self.comm_up += P * m


class Scaffold(Strategy):
    name = "scaffold"

    def setup(self, key, data):
        self._build(data, use_cv=True)
        self.global_params = self.init_fn(key)
        self.c = jax.tree.map(jnp.zeros_like, self.global_params)
        self.c_k = _zeros_like_stack(self.global_params, data.n_clients)

    def run_round(self, rnd, sampled, key):
        m = len(sampled)
        P = self._model_bytes(self.global_params)
        stacked = _broadcast(self.global_params, m)
        c_k_s = _take(self.c_k, sampled)
        c_diffs = jax.tree.map(lambda c, ck: c[None] - ck, self.c, c_k_s)
        new = self._run_local(stacked, sampled, key, c_diffs=c_diffs)
        # option II control-variate update
        coef = 1.0 / (self._steps * self.cfg.lr)
        new_c_k = jax.tree.map(
            lambda ck, c, g, nn: ck - c[None] + coef * (g[None] - nn),
            c_k_s, self.c, self.global_params, new,
        )
        dc = jax.tree.map(lambda a, b: jnp.mean(a - b, axis=0), new_c_k, c_k_s)
        frac = m / self.data.n_clients
        self.c = jax.tree.map(lambda c, d: c + frac * d, self.c, dc)
        self.c_k = jax.tree.map(
            lambda all_, upd: all_.at[jnp.asarray(sampled)].set(upd), self.c_k, new_c_k
        )
        w = jnp.asarray(self.data.n[sampled], jnp.float32)
        self.global_params = weighted_average(new, w)
        self.comm_down += 2 * P * m   # model + server control variate
        self.comm_up += 2 * P * m

    def eval_params(self):
        return _broadcast(self.global_params, self.data.n_clients)


# ===========================================================================
# Personalized strategies
# ===========================================================================


class Solo(Strategy):
    name = "solo"

    def setup(self, key, data):
        self._build(data)
        keys = jax.random.split(key, data.n_clients)
        self.params = jax.vmap(self.init_fn)(keys)

    def run_round(self, rnd, sampled, key):
        stacked = _take(self.params, sampled)
        new = self._run_local(stacked, sampled, key)
        self.params = jax.tree.map(
            lambda all_, upd: all_.at[jnp.asarray(sampled)].set(upd), self.params, new
        )
        # no communication

    def eval_params(self):
        return self.params


class LGFedAvg(Strategy):
    """LG-FedAvg: local representation layers + global head.

    Param split: leaves whose path contains one of ``global_keys`` are
    aggregated; the rest stay per-client.
    """

    name = "lg"

    def __init__(self, apply_fn, init_fn, cfg, global_keys=("layers_-1", "f3", "fc")):
        super().__init__(apply_fn, init_fn, cfg)
        self.global_keys = global_keys

    def _is_global(self, path: str) -> bool:
        return any(g in path for g in self.global_keys)

    def setup(self, key, data):
        self._build(data)
        keys = jax.random.split(key, data.n_clients)
        self.params = jax.vmap(self.init_fn)(keys)
        # label each leaf by path
        paths = []
        jax.tree_util.tree_map_with_path(
            lambda p, l: paths.append(jax.tree_util.keystr(p)), self.params
        )
        self._paths = paths
        # auto-detect the classifier head for list-of-layers models (MLP):
        # the LAST entry of a "layers" list is global, the rest local.
        idxs = [
            int(m.group(1))
            for p in paths
            for m in [re.match(r".*\['layers'\]\[(\d+)\]", p)]
            if m
        ]
        if idxs:
            self.global_keys = tuple(self.global_keys) + (f"['layers'][{max(idxs)}]",)

    def _split_bytes(self) -> int:
        sizes = []
        jax.tree_util.tree_map_with_path(
            lambda p, l: sizes.append(
                l.size // l.shape[0] * l.dtype.itemsize
                if self._is_global(jax.tree_util.keystr(p))
                else 0
            ),
            self.params,
        )
        return int(sum(sizes))

    def run_round(self, rnd, sampled, key):
        stacked = _take(self.params, sampled)
        new = self._run_local(stacked, sampled, key)
        w = jnp.asarray(self.data.n[sampled], jnp.float32)

        def agg(path, all_, upd):
            upd_new = upd
            if self._is_global(jax.tree_util.keystr(path)):
                g = weighted_average(upd, w)
                upd_new = jnp.broadcast_to(g, upd.shape)
            return all_.at[jnp.asarray(sampled)].set(upd_new)

        self.params = jax.tree_util.tree_map_with_path(agg, self.params, new)
        gb = self._split_bytes()
        self.comm_down += gb * len(sampled)
        self.comm_up += gb * len(sampled)

    def eval_params(self):
        return self.params


class PerFedAvg(Strategy):
    name = "perfedavg"
    supports_churn = True   # global params; personalization happens at eval

    def _make_local(self, steps):
        # the churn-refresh path rebuilds through this factory too, so a
        # post-churn rebuild keeps the FO-MAML update (not plain SGD)
        return make_perfedavg_local(
            self.apply_fn,
            steps=steps,
            batch_size=self.cfg.batch_size,
            alpha=self.cfg.perfed_alpha,
            beta=self.cfg.perfed_beta,
        )

    def setup(self, key, data):
        self._build(data)
        self.global_params = self.init_fn(key)
        # personalization fine-tune (eval time)
        pers = make_local_sgd(
            self.apply_fn, steps=self.cfg.personalize_steps,
            batch_size=self.cfg.batch_size, lr=self.cfg.perfed_alpha, momentum=0.0,
        )
        self._vpers = jax.jit(jax.vmap(pers))

    def run_round(self, rnd, sampled, key):
        m = len(sampled)
        P = self._model_bytes(self.global_params)
        stacked = _broadcast(self.global_params, m)
        new = self._run_local(stacked, sampled, key)
        w = jnp.asarray(self.data.n[sampled], jnp.float32)
        self.global_params = weighted_average(new, w)
        self.comm_down += P * m
        self.comm_up += P * m

    def eval_params(self):
        K = self.data.n_clients
        stacked = _broadcast(self.global_params, K)
        keys = jax.random.split(jax.random.PRNGKey(1234), K)
        c0 = _zeros_like_stack(self.global_params, K)
        return self._vpers(
            stacked, jnp.asarray(self.data.x), jnp.asarray(self.data.y),
            jnp.asarray(self.data.n), keys, stacked, c0,
        )


# ===========================================================================
# Clustered strategies
# ===========================================================================


class IFCA(Strategy):
    name = "ifca"
    supports_churn = True
    PROBE = 64   # samples per client used to probe cluster fit

    def handle_churn(self, data, batch):
        # cluster models are global; the per-client assignment cache just
        # resizes (re-derived from losses on the next round / eval anyway)
        super().handle_churn(data, batch)
        self.assign = np.zeros(data.n_clients, np.int64)

    def setup(self, key, data):
        self._build(data)
        C = self.cfg.ifca_clusters
        keys = jax.random.split(key, C)
        self.cluster_params = jax.vmap(self.init_fn)(keys)
        self.assign = np.zeros(data.n_clients, np.int64)

        def losses(cparams, x, y, n):
            # loss of every cluster model on one client's train data head,
            # masked to the n_k real samples: the stacked rows cycle the
            # local data, so for n_k < PROBE an unmasked mean double-counts
            # the cycled prefix and skews the cluster assignment
            xb, yb = x[: self.PROBE], y[: self.PROBE]
            mask = (jnp.arange(xb.shape[0]) < n).astype(jnp.float32)
            return jax.vmap(
                lambda p: ce_loss(self.apply_fn, p, xb, yb, mask=mask)
            )(cparams)

        self._vlosses = jax.jit(jax.vmap(losses, in_axes=(None, 0, 0, 0)))

    def run_round(self, rnd, sampled, key):
        m = len(sampled)
        C = self.cfg.ifca_clusters
        P = self._model_bytes(jax.tree.map(lambda l: l[0], self.cluster_params))
        x = jnp.asarray(self.data.x[sampled])
        y = jnp.asarray(self.data.y[sampled])
        n = jnp.asarray(self.data.n[sampled])
        ls = np.asarray(self._vlosses(self.cluster_params, x, y, n))   # (m, C)
        pick = ls.argmin(axis=1)
        self.assign[sampled] = pick
        stacked = _take(self.cluster_params, pick)
        new = self._run_local(stacked, sampled, key)
        w = jnp.asarray(self.data.n[sampled], jnp.float32)
        for c in range(C):
            mask = pick == c
            if not mask.any():
                continue
            avg = weighted_average(_take(new, np.where(mask)[0]), w[np.asarray(mask)])
            self.cluster_params = jax.tree.map(
                lambda all_, a: all_.at[c].set(a), self.cluster_params, avg
            )
        # every sampled client downloads ALL C cluster models (IFCA's cost)
        self.comm_down += C * P * m
        self.comm_up += P * m

    def eval_params(self):
        # unsampled clients pick their best cluster at eval
        x = jnp.asarray(self.data.x)
        y = jnp.asarray(self.data.y)
        n = jnp.asarray(self.data.n)
        ls = np.asarray(self._vlosses(self.cluster_params, x, y, n))
        pick = ls.argmin(axis=1)
        return _take(self.cluster_params, pick)


class CFL(Strategy):
    """Sattler et al. recursive bipartitioning on client-update cosine sim."""

    name = "cfl"

    def setup(self, key, data):
        self._build(data)
        self.labels = np.zeros(data.n_clients, np.int64)
        self.models: list[PyTree] = [self.init_fn(key)]

    @staticmethod
    def _flat(tree) -> np.ndarray:
        return np.concatenate([np.asarray(l).ravel() for l in jax.tree.leaves(tree)])

    def run_round(self, rnd, sampled, key):
        m = len(sampled)
        P = self._model_bytes(self.models[0])
        stacked = jax.tree.map(
            lambda *ls: jnp.stack([ls[self.labels[k]] for k in sampled]),
            *[jax.tree.map(lambda l: l, mp) for mp in self.models],
        ) if len(self.models) > 1 else _broadcast(self.models[0], m)
        new = self._run_local(stacked, sampled, key)
        w = jnp.asarray(self.data.n[sampled], jnp.float32)
        # aggregate per cluster + collect update vectors
        updates = {}
        for c in range(len(self.models)):
            mask = self.labels[sampled] == c
            if not mask.any():
                continue
            idx = np.where(mask)[0]
            new_c = _take(new, idx)
            self.models[c] = weighted_average(new_c, w[np.asarray(idx)])
            du = [
                self._flat(jax.tree.map(lambda a, b: a - b, _take(new_c, np.array([i])),
                                        _broadcast(self.models[c], 1)))
                for i in range(len(idx))
            ]
            updates[c] = (sampled[idx], np.stack(du))
        # split check (Sattler criteria)
        for c, (cl_ids, du) in list(updates.items()):
            if len(cl_ids) < 4:
                continue
            norms = np.linalg.norm(du, axis=1)
            mean_norm = np.linalg.norm(du.mean(axis=0))
            if mean_norm < self.cfg.cfl_eps1 and norms.max() > self.cfg.cfl_eps2:
                sim = (du @ du.T) / (
                    np.linalg.norm(du, axis=1)[:, None] * np.linalg.norm(du, axis=1)[None] + 1e-9
                )
                i, j = np.unravel_index(np.argmin(sim), sim.shape)
                part = sim[i] >= sim[j]
                new_label = len(self.models)
                self.models.append(jax.tree.map(jnp.copy, self.models[c]))
                moved = cl_ids[~part]
                self.labels[moved] = new_label
        self.comm_down += P * m
        self.comm_up += P * m

    def eval_params(self):
        return jax.tree.map(
            lambda *ls: jnp.stack([ls[self.labels[k]] for k in range(self.data.n_clients)]),
            *self.models,
        )


class PACFL(Strategy):
    """The paper's method: one-shot principal-angle clustering + per-cluster
    FedAvg (Algorithm 1).

    Membership is owned by the streaming cluster engine, so clients can join
    *and leave* between rounds (``handle_churn``): departures drop out of
    the condensed distance store, newcomers cost only their signature upload
    plus the (M, B) cross block, and surviving clients keep their stable
    cluster ids — cluster models persist across churn.

    Server memory at scale is governed by ``cfg.pacfl.memory`` /
    ``memory_budget_bytes`` (the engine's tiered distance-store policy:
    dense mirror, banded hot-row window, or condensed-only — see
    ``docs/ENGINE.md``); every tier yields bitwise-identical cluster
    labels, so the knob never changes training behavior.
    """

    name = "pacfl"
    supports_churn = True

    def setup(self, key, data):
        self._build(data)
        # the vmapped round's gather and aggregation: one dispatch each, with
        # shapes that do not depend on how the sample falls into clusters
        self._gather = jax.jit(_take)
        self._cluster_means = _cluster_means_program()
        self._key = key
        self._sig_seq = 0   # deterministic key stream for eager signatures
        # One-shot phase: clients compute + upload their signatures through
        # the family selected by cfg.pacfl.family (repro.core.signatures).
        # For the default "svd" family the ragged (features, samples)
        # matrices go through the shape-bucketed batched SVD; model-based
        # families warm up this strategy's own model from a shared init.
        # The proximity matrix goes through the backend dispatch selected by
        # cfg.pacfl.proximity_backend — all scale knobs live on the config.
        pcfg = self.cfg.pacfl
        self._family = get_family(pcfg.family)
        payloads = self._family_payloads(data)
        self._fam_ctx = self._family.prepare_context(
            payloads, pcfg,
            FamilyContext(apply_fn=self.apply_fn, init_fn=self.init_fn, key0=key),
        )
        U = compute_signatures(payloads, pcfg, key=key, context=self._fam_ctx)
        self.clustering = cluster_clients(U, pcfg)
        self.labels = self.clustering.labels
        Z = self.clustering.n_clusters
        self.cluster_params = jax.vmap(self.init_fn)(
            jnp.broadcast_to(key, (Z,) + key.shape)
        )  # all clusters start from the same theta_g^0 (Algorithm 1 line 12)
        self.comm_up += self.clustering.signature_bytes
        self.comm_down += self._family.downlink_bytes(
            pcfg, self._fam_ctx, data.n_clients
        )

    @staticmethod
    def _client_mats(data):
        """(features, samples) data matrices, one per stacked client."""
        return [
            jnp.asarray(feature_matrix(data.x[k, : data.n[k]]))
            for k in range(data.n_clients)
        ]

    def _family_payloads(self, data):
        """Per-client payloads in the current family's native form.

        The svd family gets the exact (features, samples) matrices the
        pre-registry path built (bitwise parity is gated in CI); model-based
        families get (x_train, y_train) payloads sliced from the stack.
        """
        if self.cfg.pacfl.family == "svd":
            return self._client_mats(data)
        return payloads_from_stacked(data)

    def churn_signature_fn(self):
        """Eager per-client signature for the async queue: every family's
        extractor is membership-independent, so it runs at enqueue time and
        overlaps the in-flight round.  Keys come from a deterministic
        per-strategy stream (exact SVD ignores them; randomized SVD and the
        model-warmup families stay reproducible)."""

        def signature(client) -> jnp.ndarray:
            key = jax.random.fold_in(self._key, 1_000_003 + self._sig_seq)
            self._sig_seq += 1
            payload = (
                jnp.asarray(feature_matrix(client.x_train))
                if self.cfg.pacfl.family == "svd" else client
            )
            return self._family.signature_one(
                payload, self.cfg.pacfl, key=key, context=self._fam_ctx
            )

        return signature

    def handle_churn(self, data, batch):
        """Fold one drained churn batch into the engine (move/depart/admit).

        Deliberately mutates ``self.clustering.engine`` in place — the
        strategy owns its clustering for the federation's lifetime, and the
        engine IS the streaming-mutation API (the fork-on-write convention
        of ``PACFLClustering.extend``/``depart`` is for core callers that
        hand out snapshots).  The strategy tracks the trainer's client-list
        order as a stable-id roster (``self._client_ids``): leave positions
        resolve against it, joins append the engine-assigned ids, and
        refreshes leave it untouched — necessary because a fused ``move``
        re-orders engine *rows* (movers re-enter at the tail) while the
        trainer's list keeps movers in place, so row order and list order
        diverge after the first refresh.  Newcomer signatures
        arrive precomputed on the batch (eager enqueue-time SVD); a batch
        without them (direct legacy calls) falls back to computing from the
        stacked data.  Refresh batches (a client's distribution shifted;
        drained exclusive of leaves/joins) route through the engine's fused
        ``move`` — one replay pass, movers keep their stable client ids —
        and pay the same signature upload a newcomer would.  New clusters
        (a newcomer unlike every seen client, or an old cluster split by
        departures or moves) get fresh models from theta_g^0; existing
        clusters keep their trained models.
        """
        engine = self.clustering.engine
        roster = getattr(self, "_client_ids", None)
        if roster is None:
            # engine rows == trainer positions until the first move
            roster = [int(i) for i in engine.membership().ids]
        if getattr(batch, "refresh", None):
            ids_mv = np.asarray(
                [roster[p] for p in batch.refresh], dtype=np.int64
            )
            U_ref = getattr(batch, "refresh_signatures", None)
            if U_ref is None:
                payloads = (
                    [jnp.asarray(feature_matrix(c.x_train)) for c in batch.refresh_clients]
                    if self.cfg.pacfl.family == "svd"
                    else list(batch.refresh_clients)
                )
                U_ref = compute_signatures(
                    payloads, self.cfg.pacfl,
                    key=jax.random.fold_in(self._key, engine.version),
                    context=self._fam_ctx,
                )
            engine.move(ids_mv, U_ref)
            extra = self._family.upload_bytes(U_ref)
            self.clustering.signature_bytes += extra
            self.comm_up += extra
        if batch.leave:
            gone, roster = batch.resolve_leaves(roster)
            engine.depart(np.asarray(gone, dtype=np.int64))
        if batch.join:
            U_new = getattr(batch, "signatures", None)
            if U_new is None:
                # compute from the batch's own join payloads — the stacked
                # data reflects the whole drain, so its trailing rows are
                # NOT this batch's newcomers when a drain splits batches
                payloads = (
                    [jnp.asarray(feature_matrix(c.x_train)) for c in batch.join]
                    if self.cfg.pacfl.family == "svd" else list(batch.join)
                )
                U_new = compute_signatures(
                    payloads, self.cfg.pacfl,
                    key=jax.random.fold_in(self._key, engine.version),
                    context=self._fam_ctx,
                )
            admitted = engine.admit(U_new)
            roster.extend(int(i) for i in admitted.ids)
            extra = self._family.upload_bytes(U_new)
            self.clustering.signature_bytes += extra
            self.comm_up += extra
        self._client_ids = roster
        # trainer-ordered labels: look stable labels up by client id (engine
        # row order stops matching trainer order after the first move)
        snap = engine.membership()
        label_of = {int(i): l for i, l in zip(snap.ids, snap.labels)}
        self.labels = np.asarray(
            [label_of[i] for i in roster], dtype=snap.labels.dtype
        )
        # grow the per-cluster model stack for any fresh stable ids
        Z_have = jax.tree.leaves(self.cluster_params)[0].shape[0]
        Z_need = int(self.labels.max()) + 1
        if Z_need > Z_have:
            fresh = jax.vmap(self.init_fn)(
                jnp.broadcast_to(self._key, (Z_need - Z_have,) + self._key.shape)
            )
            self.cluster_params = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0),
                self.cluster_params, fresh,
            )
        super().handle_churn(data, batch)   # data swap + local-steps refresh

    def run_round(self, rnd, sampled, key):
        m = len(sampled)
        # from the shapes: slicing a model out of the stack would copy it
        P = self._model_bytes(jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), self.cluster_params))
        pick = self.labels[sampled]
        Z = jax.tree.leaves(self.cluster_params)[0].shape[0]
        if vmapped_fits(P, m, Z, self._device_bytes):
            pick_d = jnp.asarray(pick, jnp.int32)
            new = self._run_local(self._gather(self.cluster_params, pick_d), sampled, key)
            w = jnp.asarray(self.data.n[sampled], jnp.float32)
            with tracing.span("pacfl.aggregate") as sp:
                sp.count("clients", m)
                sp.count("clusters_touched", np.unique(pick).size)
                self.cluster_params = self._cluster_means(self.cluster_params, new, pick_d, w)
        else:
            with tracing.span("lmround") as sp:
                sp.count("clients", m)
                self.cluster_params, self.round_stats = self._run_sequential(
                    self.cluster_params, pick, sampled, key
                )
                load = self.round_stats.get("expert_load")
                if load is not None and load.size and tracing.enabled():
                    load = np.asarray(load)   # read once a round, only while traced
                    sp.count("expert_load_max", load.max(axis=1).sum())
                    sp.count("expert_load_sum", load.sum())
                    sp.count("experts_held", load.shape[1])
        self.comm_down += P * m   # each client downloads only ITS cluster model
        self.comm_up += P * m

    def eval_params(self):
        return _take(self.cluster_params, self.labels)

    def evaluate(self) -> np.ndarray:
        if not self.data.tokens:
            return super().evaluate()
        acc = token_eval(
            self.apply_fn, self.cluster_params, jnp.asarray(self.labels),
            jnp.asarray(self.data.x_test), jnp.asarray(self.data.t),
        )
        return np.asarray(acc)


STRATEGIES: dict[str, type] = {
    s.name: s
    for s in [FedAvg, FedProx, FedNova, Scaffold, Solo, LGFedAvg, PerFedAvg, IFCA, CFL, PACFL]
}
