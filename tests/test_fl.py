"""FL substrate tests: partitioners, strategies, trainer, communication.

Federation configs here are deliberately trimmed (few rounds/clients) so
tier-1 stays fast; the full-scale runs carry ``@pytest.mark.slow`` and are
deselected by default (see pytest.ini) — opt in with ``pytest -m slow``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import make_dataset
from repro.fl import (
    FLConfig, STRATEGIES, dirichlet_skew, iid_split, label_skew,
    mix_datasets, run_federation,
)
from repro.core.pacfl import PACFLConfig
from repro.models.cnn import init_mlp_clf, mlp_clf_apply

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def ds():
    return make_dataset("cifar10s", n_train=1200, n_test=400, dim=128, seed=0)


class TestPartitioners:
    def test_label_skew_support(self, ds):
        clients = label_skew(ds, 10, rho=0.2, seed=0)
        assert len(clients) == 10
        for c in clients:
            labels = np.unique(c.y_train)
            assert len(labels) <= 2   # 20% of 10 classes
            # local test set restricted to the client's labels
            assert set(np.unique(c.y_test)) <= set(c.meta["labels"].tolist())

    def test_dirichlet_all_data_assigned(self, ds):
        clients = dirichlet_skew(ds, 8, alpha=0.1, seed=0)
        assert sum(c.n_train for c in clients) >= ds.x_train.shape[0] * 0.95

    def test_mix_datasets_offsets(self):
        d1 = make_dataset("cifar10s", n_train=600, n_test=200, dim=64)
        d2 = make_dataset("fmnists", n_train=600, n_test=200, dim=64)
        clients = mix_datasets([d1, d2], [3, 2], samples_per_client=100)
        assert len(clients) == 5
        assert set(np.unique(clients[0].y_train)) <= set(range(10))
        assert set(np.unique(clients[4].y_train)) <= set(range(10, 20))

    def test_iid(self, ds):
        clients = iid_split(ds, 5)
        assert len(clients) == 5


@pytest.fixture(scope="module")
def small_fed(ds):
    clients = label_skew(ds, 12, rho=0.2, seed=1, test_per_client=80)
    init_fn = lambda key: init_mlp_clf(key, ds.dim, ds.n_classes, hidden=(64,))
    cfg = FLConfig(rounds=4, sample_frac=0.34, local_epochs=2, batch_size=16,
                   lr=0.05, pacfl=PACFLConfig(p=3, beta=20.0, measure="eq2"))
    return clients, init_fn, cfg


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_strategy_runs_and_learns(small_fed, name):
    clients, init_fn, cfg = small_fed
    res = run_federation(name, clients, mlp_clf_apply, init_fn, cfg,
                         seed=0, eval_every=2)
    assert np.isfinite(res.final_mean)
    assert 0.0 <= res.final_mean <= 1.0
    # better than chance (10 classes) after a few rounds for all methods
    assert res.final_mean > 0.12, (name, res.final_mean)


def test_pacfl_beats_fedavg_on_label_skew(ds):
    """Trimmed fast config — the paper-scale version is the ``slow`` variant."""
    clients = label_skew(ds, 16, rho=0.2, seed=2, test_per_client=80)
    init_fn = lambda key: init_mlp_clf(key, ds.dim, ds.n_classes, hidden=(64,))
    # eq3 discriminates label support best on label-skew (see EXPERIMENTS.md);
    # beta tuned as the paper does (Fig. 2 sweep).
    cfg = FLConfig(rounds=8, sample_frac=0.5, local_epochs=2, batch_size=16,
                   lr=0.05, pacfl=PACFLConfig(p=3, beta=175.0, measure="eq3"))
    r_pacfl = run_federation("pacfl", clients, mlp_clf_apply, init_fn, cfg, seed=0)
    r_fedavg = run_federation("fedavg", clients, mlp_clf_apply, init_fn, cfg, seed=0)
    assert r_pacfl.final_mean > r_fedavg.final_mean


@pytest.mark.slow
def test_pacfl_beats_fedavg_on_label_skew_full(ds):
    """Full-scale (multi-minute) version of the label-skew comparison.

    Marked ``slow``: deselected by default, run with ``pytest -m slow``.
    """
    clients = label_skew(ds, 24, rho=0.2, seed=2, test_per_client=80)
    init_fn = lambda key: init_mlp_clf(key, ds.dim, ds.n_classes, hidden=(64,))
    cfg = FLConfig(rounds=30, sample_frac=0.5, local_epochs=3, batch_size=16,
                   lr=0.05, pacfl=PACFLConfig(p=3, beta=175.0, measure="eq3"))
    r_pacfl = run_federation("pacfl", clients, mlp_clf_apply, init_fn, cfg, seed=0)
    r_fedavg = run_federation("fedavg", clients, mlp_clf_apply, init_fn, cfg, seed=0)
    # fedavg partially recovers at long horizons, so the gap narrows — the
    # ordering, not a fixed margin, is the stable claim at this scale.
    assert r_pacfl.final_mean > r_fedavg.final_mean


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_strategy_full_scale(ds, name):
    """Every strategy at fuller scale (more rounds/clients than the trimmed
    default above).  Marked ``slow``; run with ``pytest -m slow``."""
    clients = label_skew(ds, 20, rho=0.2, seed=4, test_per_client=80)
    init_fn = lambda key: init_mlp_clf(key, ds.dim, ds.n_classes, hidden=(64,))
    cfg = FLConfig(rounds=16, sample_frac=0.4, local_epochs=3, batch_size=16,
                   lr=0.05, pacfl=PACFLConfig(p=3, beta=20.0, measure="eq2"))
    res = run_federation(name, clients, mlp_clf_apply, init_fn, cfg,
                         seed=0, eval_every=4)
    assert np.isfinite(res.final_mean)
    assert res.final_mean > 0.15, (name, res.final_mean)


def test_ifca_downloads_all_cluster_models(small_fed):
    clients, init_fn, cfg = small_fed
    r_ifca = run_federation("ifca", clients, mlp_clf_apply, init_fn, cfg, seed=0)
    r_pacfl = run_federation("pacfl", clients, mlp_clf_apply, init_fn, cfg, seed=0)
    # IFCA's downlink carries C models per client per round (paper's cost
    # argument); PACFL downloads one cluster model.
    assert r_ifca.strategy_obj.comm_down > 1.9 * r_pacfl.strategy_obj.comm_down


def test_pacfl_signature_upload_accounted(small_fed):
    clients, init_fn, cfg = small_fed
    res = run_federation("pacfl", clients, mlp_clf_apply, init_fn, cfg, seed=0)
    strat = res.strategy_obj
    K, dim, p = len(clients), clients[0].x_train.shape[1], cfg.pacfl.p
    assert strat.clustering.signature_bytes == K * dim * p * 4


def test_solo_no_communication(small_fed):
    clients, init_fn, cfg = small_fed
    res = run_federation("solo", clients, mlp_clf_apply, init_fn, cfg, seed=0)
    assert res.strategy_obj.comm_up == 0
    assert res.strategy_obj.comm_down == 0


def test_pacfl_iid_one_cluster(ds):
    """IID split -> all client subspaces coincide -> 1 cluster (paper claim)."""
    clients = iid_split(ds, 10, seed=3)
    from repro.fl.client import stack_clients
    from repro.fl.strategies import PACFL

    init_fn = lambda key: init_mlp_clf(key, ds.dim, ds.n_classes, hidden=(32,))
    cfg = FLConfig(rounds=1, sample_frac=0.5, local_epochs=1, batch_size=8,
                   lr=0.05, pacfl=PACFLConfig(p=3, beta=20.0, measure="eq2"))
    strat = PACFL(mlp_clf_apply, init_fn, cfg)
    strat.setup(KEY, stack_clients(clients))
    assert strat.clustering.n_clusters == 1


def test_pacfl_mix2_two_clusters():
    """Two structurally different datasets -> 2 clusters."""
    d1 = make_dataset("cifar10s", n_train=600, n_test=200, dim=128)
    d2 = make_dataset("fmnists", n_train=600, n_test=200, dim=128)
    clients = mix_datasets([d1, d2], [5, 5], samples_per_client=120)
    from repro.fl.client import stack_clients
    from repro.fl.strategies import PACFL

    init_fn = lambda key: init_mlp_clf(key, 128, 20, hidden=(32,))
    cfg = FLConfig(pacfl=PACFLConfig(p=3, beta=45.0, measure="eq2"))
    strat = PACFL(mlp_clf_apply, init_fn, cfg)
    strat.setup(KEY, stack_clients(clients))
    assert strat.clustering.n_clusters == 2
    labels = strat.labels
    assert len(set(labels[:5])) == 1 and len(set(labels[5:])) == 1
    assert labels[0] != labels[5]


class TestChurn:
    """Mid-federation membership changes via the streaming cluster engine."""

    def test_pacfl_join_and_leave_between_rounds(self, small_fed):
        from repro.fl import ChurnEvent

        clients, init_fn, cfg = small_fed
        churn = [ChurnEvent(rnd=2, join=clients[10:12], leave=[0, 3]),
                 ChurnEvent(rnd=4, leave=[1])]
        res = run_federation("pacfl", clients[:10], mlp_clf_apply, init_fn,
                             cfg, seed=0, churn=churn)
        # 10 - 2 + 2 - 1 clients remain, labels/evals sized to match
        assert len(res.final_accs) == 9
        strat = res.strategy_obj
        assert strat.labels.shape == (9,)
        assert strat.clustering.engine.n_clients == 9
        # cluster model stack covers every live stable label
        Z = jax.tree.leaves(strat.cluster_params)[0].shape[0]
        assert int(strat.labels.max()) < Z
        # engine membership is oracle-exact after the churn sequence
        from repro.core.hc import hierarchical_clustering
        eng = strat.clustering.engine
        oracle = hierarchical_clustering(
            eng.dense(np.float64), cfg.pacfl.beta, linkage=cfg.pacfl.linkage)

        def canon(l):
            seen = {}
            return np.array([seen.setdefault(int(x), len(seen)) for x in l])
        assert (canon(oracle) == canon(eng.canonical_labels)).all()

    def test_global_strategies_absorb_churn(self, small_fed):
        from repro.fl import ChurnEvent

        clients, init_fn, cfg = small_fed
        churn = [ChurnEvent(rnd=3, join=clients[10:11], leave=[2])]
        for name in ("fedavg", "ifca"):
            res = run_federation(name, clients[:10], mlp_clf_apply, init_fn,
                                 cfg, seed=0, churn=churn)
            assert len(res.final_accs) == 10

    def test_unsupported_strategy_rejects_churn(self, small_fed):
        from repro.fl import ChurnEvent

        clients, init_fn, cfg = small_fed
        with pytest.raises(ValueError, match="churn"):
            run_federation("solo", clients[:10], mlp_clf_apply, init_fn,
                           cfg, seed=0,
                           churn=[ChurnEvent(rnd=2, leave=[0])])


class TestVmappedAggregation:
    """PACFL's vmapped round averages each touched cluster's local updates in
    one compiled program: the float64 per-cluster weighted mean, models no
    sampled client picked kept bit for bit, one compile per (m, Z)."""

    D = 32

    @classmethod
    def _clients(cls, groups, seed=0):
        """One client per entry of ``groups``, of 20 or 40 samples in turn,
        whose data lies near plane ``g`` of four fixed random planes:
        clients of one group share a subspace, clients of two are far
        apart."""
        from repro.fl.partition import ClientData

        planes = np.random.default_rng(99).normal(size=(4, 2, cls.D))
        rng = np.random.default_rng(seed)
        out = []
        for k, g in enumerate(groups):
            n = (20, 40)[k % 2]
            x = rng.normal(size=(n + 4, 2)) @ planes[g] + 0.05 * rng.normal(size=(n + 4, cls.D))
            x = x.astype(np.float32)
            y = rng.integers(0, 4, n + 4)
            out.append(ClientData(x[:n], y[:n], x[n:], y[n:], f"g{g}"))
        return out

    @classmethod
    def _strategy(cls, clients, sample_frac=0.34):
        from repro.fl.client import stack_clients
        from repro.fl.strategies import PACFL

        cfg = FLConfig(sample_frac=sample_frac, local_epochs=1, batch_size=10, lr=0.05,
                       pacfl=PACFLConfig(p=2, beta=45.0, measure="eq2"))
        strat = PACFL(mlp_clf_apply, lambda key: init_mlp_clf(key, cls.D, 4, hidden=(16,)), cfg)
        strat.setup(jax.random.PRNGKey(0), stack_clients(clients))
        return strat

    @staticmethod
    def _round(strat, sampled, key):
        """Run one round, returning the models before it and the sampled
        clients' local updates it averaged."""
        before, seen = strat.cluster_params, {}
        real = strat._run_local

        def keep(*a, **kw):
            seen["new"] = real(*a, **kw)
            return seen["new"]

        strat._run_local = keep
        try:
            strat.run_round(1, sampled, key)
        finally:
            del strat._run_local
        return before, seen["new"]

    @staticmethod
    def _check(strat, sampled, before, new):
        pick = strat.labels[sampled]
        w = strat.data.n[sampled].astype(np.float64)
        for got, old, upd in zip(*(jax.tree.leaves(t) for t in (strat.cluster_params, before, new))):
            for z in range(got.shape[0]):
                idx = np.flatnonzero(pick == z)
                if idx.size == 0:
                    assert np.array_equal(np.asarray(got[z]), np.asarray(old[z]))
                    continue
                want = np.tensordot(w[idx] / w[idx].sum(), np.asarray(upd, np.float64)[idx], axes=(0, 0))
                np.testing.assert_allclose(np.asarray(got[z], np.float64), want, rtol=1e-6, atol=1e-7)

    def test_touched_clusters_get_the_float64_weighted_mean(self):
        """Two of three clusters touched: their means against float64, the
        third cluster's model bit for bit."""
        strat = self._strategy(self._clients([0, 1, 2] * 4))
        assert len(np.unique(strat.labels)) == 3
        sampled = np.flatnonzero(strat.labels != strat.labels[0])[:4]
        before, new = self._round(strat, sampled, jax.random.PRNGKey(3))
        assert len(np.unique(strat.labels[sampled])) == 2
        self._check(strat, sampled, before, new)

    def test_one_compile_for_every_count_per_cluster(self):
        strat = self._strategy(self._clients([0, 1] * 6))
        m = 4
        a = np.flatnonzero(strat.labels == strat.labels[0])
        b = np.flatnonzero(strat.labels != strat.labels[0])
        for cnt in range(1, m + 1):
            sampled = np.sort(np.concatenate([a[:cnt], b[: m - cnt]]))
            before, new = self._round(strat, sampled, jax.random.PRNGKey(cnt))
            self._check(strat, sampled, before, new)
        assert strat._cluster_means._cache_size() == 1

    def test_round_after_churn_grows_the_stack(self):
        from repro.fl.churn import ChurnBatch
        from repro.fl.client import stack_clients

        clients = self._clients([0, 1] * 6)
        strat = self._strategy(clients)
        strat.run_round(0, np.arange(4), jax.random.PRNGKey(5))
        Z = jax.tree.leaves(strat.cluster_params)[0].shape[0]
        joins = self._clients([3, 3], seed=1)
        strat.handle_churn(stack_clients(clients + joins), ChurnBatch(join=joins))
        assert jax.tree.leaves(strat.cluster_params)[0].shape[0] == Z + 1
        sampled = np.array([0, 1, 12, 13])
        assert strat.labels[12] == strat.labels[13] == Z
        before, new = self._round(strat, sampled, jax.random.PRNGKey(6))
        self._check(strat, sampled, before, new)
        assert strat._cluster_means._cache_size() == 2
