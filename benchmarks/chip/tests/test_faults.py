"""A run with its timed path broken underneath has to read ``correct: false``.

Each test drives a whole run of a cell through the harness (set-up, window,
check) at a size the CPU holds, without the harness's look for a chip, with
one fault planted in the program: an answer altered where it is produced,
a step that leaves the state unchanged, a served epoch left stale, or a
round that trains or averages wrongly.  The same runs without a fault read
``correct: true``.
"""
import io
import time

import numpy as np
import pytest

import harness

# MIX-4 at a size the CPU holds: 15 clients of 60 samples at the cell's
# 3,072 features, 4 sampled per round
ROUND = {
    "n_clients": 16, "samples_per_client": 60, "sample_frac": 0.25,
    "dataset_train": 600, "dataset_test": 200, "test_per_client": 20,
}
# 25 clients of 20 and 200 samples in turn, 8 a round: 3 check rounds of 8
# distinct clients
RAGGED = {"n_clients": 26, "samples_per_client": [20, 200], "sample_frac": 0.32}
CONFIG = {
    "boot.femnist-eq3": {"n_clients": 192},
    "churn.femnist-eq3": {"n_clients": 192},
    "round.mix4-lenet5": ROUND,
}
E2E = {
    "boot.femnist-eq3": {"bootstrap_s", "setup_s"},
    "churn.femnist-eq3": {"drain_p50_ms", "drain_p80_ms", "setup_s"},
    "round.mix4-lenet5": {"round_s", "setup_s"},
}


def _run(workload, seed=2**31 + 5, **overrides):
    return harness.run_cell(
        workload, seed, 0.5, False, t_start=time.perf_counter(), require_tpu=False,
        config_overrides={**CONFIG[workload], **overrides},
        log=io.StringIO(),
    )


@pytest.mark.parametrize("workload", sorted(CONFIG))
def test_sound_run_is_correct(workload):
    line = _run(workload)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == E2E[workload]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"


def test_boot_altered_distance(monkeypatch):
    import repro.core.engine.engine as engine

    real = engine.proximity_matrix

    def altered(U, *a, **kw):
        A = real(U, *a, **kw)
        return A.at[0, 1].add(0.01).at[1, 0].add(0.01)

    monkeypatch.setattr(engine, "proximity_matrix", altered)
    line = _run("boot.femnist-eq3")
    assert not line["correct"]
    assert line["checks"]["max_dev_deg"]["value"] > line["checks"]["max_dev_deg"]["limit"]


def test_boot_altered_label(monkeypatch):
    from repro.core.engine import ClusterEngine

    real = ClusterEngine._bootstrap

    def altered(self, A, U):
        real(self, A, U)
        self._stable = self._stable.copy()
        self._stable[0] = self._stable.max() + 1

    monkeypatch.setattr(ClusterEngine, "_bootstrap", altered)
    line = _run("boot.femnist-eq3")
    assert not line["correct"]
    assert line["checks"]["label_mismatch"]["value"] > 0


def test_churn_drain_leaves_state_unchanged(monkeypatch):
    from repro.serving import AssignmentServer

    def unchanged(self, *, force=True):
        self.queue.drain(force=force)

    monkeypatch.setattr(AssignmentServer, "drain", unchanged)
    line = _run("churn.femnist-eq3")
    assert not line["correct"]
    assert line["checks"]["roster_mismatch"]["value"] > 0


def test_churn_altered_cross_block(monkeypatch):
    import repro.core.pme as pme

    real = pme.proximity_blocks

    def altered(*a, **kw):
        cross, square = real(*a, **kw)
        cross = np.array(cross, copy=True)
        cross[0, 0] += 0.01
        return cross, square

    monkeypatch.setattr(pme, "proximity_blocks", altered)
    line = _run("churn.femnist-eq3")
    assert not line["correct"]
    assert line["checks"]["max_dev_deg"]["value"] > line["checks"]["max_dev_deg"]["limit"]



def test_churn_commit_keeps_stale_snapshot(monkeypatch):
    from repro.serving import AssignmentServer

    real = AssignmentServer._commit

    def stale(self):
        if self._snapshot is None:
            real(self)

    monkeypatch.setattr(AssignmentServer, "_commit", stale)
    line = _run("churn.femnist-eq3")
    assert not line["correct"]
    assert line["checks"]["epoch_skips"]["value"] > 0
    assert line["checks"]["roster_mismatch"]["value"] > 0


def test_churn_commit_keeps_stale_representatives(monkeypatch):
    from repro.serving.representatives import RepresentativeCache

    real = RepresentativeCache.refresh

    def stale(self, engine):
        if self._version is None:
            real(self, engine)

    monkeypatch.setattr(RepresentativeCache, "refresh", stale)
    line = _run("churn.femnist-eq3")
    assert not line["correct"]
    assert line["checks"]["rep_mismatch"]["value"] > 0


def test_churn_served_representative_altered(monkeypatch):
    from repro.serving.representatives import RepresentativeCache

    real = RepresentativeCache.refresh

    def altered(self, engine):
        real(self, engine)
        self._stack = self._stack.at[0, 0, 0].add(1e-3)

    monkeypatch.setattr(RepresentativeCache, "refresh", altered)
    line = _run("churn.femnist-eq3")
    assert not line["correct"]
    assert line["checks"]["rep_mismatch"]["value"] > 0


def test_churn_representative_not_the_medoid(monkeypatch):
    import jax.numpy as jnp
    from repro.serving.representatives import ClusterRepresentative, RepresentativeCache

    def farthest(self, engine, lbl, pos, member_ids):
        rows = engine.store.gather_rows(pos, promote=False)
        m = pos[int(np.argmax(rows[:, pos].sum(axis=1)))]
        rep = jnp.take(engine.U, jnp.asarray(m), axis=0)
        return ClusterRepresentative(lbl, member_ids, rep, int(engine.ids[m]))

    monkeypatch.setattr(RepresentativeCache, "_build", farthest)
    line = _run("churn.femnist-eq3")
    assert not line["correct"]
    assert line["checks"]["rep_mismatch"]["value"] > 0


def _round_fails(line, checks=("first_round_worst_gap", "first_round_median_gap", "three_round_worst_gap")):
    assert not line["correct"], line["checks"]
    assert any(line["checks"][c]["value"] > line["checks"][c]["limit"] for c in checks), line["checks"]


def test_round_unweighted_average(monkeypatch):
    """Clients of 20 and 200 samples in turn, 8 of them a round so that
    clusters average clients of different sizes: the cell's clients all hold
    300, where an unweighted mean is the same answer."""
    import repro.fl.strategies as strategies

    real = strategies.weighted_average
    monkeypatch.setattr(
        strategies, "weighted_average", lambda stacked, w: real(stacked, np.ones(w.shape, np.float32))
    )
    _round_fails(_run("round.mix4-lenet5", **RAGGED))


def test_round_ragged_clients_sound():
    """The same ragged sizes without the fault read correct."""
    assert _run("round.mix4-lenet5", **RAGGED)["correct"]


def test_round_too_few_clients_for_the_check():
    """The check's rounds take distinct clients; a federation too small for
    them is refused, not checked on fewer rounds."""
    with pytest.raises(ValueError, match="3 check rounds of 8 distinct clients"):
        _run("round.mix4-lenet5", sample_frac=0.5)


def test_round_client_update_dropped(monkeypatch):
    """The first sampled client's update is lost: the server averages the
    model it sent out in its place.  Clients of 200 samples train 30 steps,
    so that the updates of one cluster's clients share a direction and the
    averaged change comes out short by the dropped client's share."""
    import jax
    from repro.fl.strategies import Strategy

    real = Strategy._run_local

    def dropped(self, stacked_params, *a, **kw):
        new = real(self, stacked_params, *a, **kw)
        return jax.tree.map(lambda n, s: n.at[0].set(s[0]), new, stacked_params)

    monkeypatch.setattr(Strategy, "_run_local", dropped)
    _round_fails(_run("round.mix4-lenet5", samples_per_client=200))


def test_round_average_written_to_another_cluster(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.fl.strategies import PACFL, _take, weighted_average

    def misplaced(self, rnd, sampled, key):
        pick = self.labels[sampled]
        new = self._run_local(_take(self.cluster_params, pick), sampled, key)
        w = jnp.asarray(self.data.n[sampled], jnp.float32)
        Z = jax.tree.leaves(self.cluster_params)[0].shape[0]
        for z in np.unique(pick):
            idx = np.flatnonzero(pick == z)
            avg = weighted_average(_take(new, idx), w[idx])
            self.cluster_params = jax.tree.map(
                lambda all_, a: all_.at[(int(z) + 1) % Z].set(a), self.cluster_params, avg
            )

    monkeypatch.setattr(PACFL, "run_round", misplaced)
    _round_fails(_run("round.mix4-lenet5"))


def test_round_step_without_momentum(monkeypatch):
    import repro.fl.strategies as strategies

    real = strategies.make_local_sgd
    monkeypatch.setattr(
        strategies, "make_local_sgd", lambda *a, **kw: real(*a, **{**kw, "momentum": 0.0})
    )
    _round_fails(_run("round.mix4-lenet5"))


def test_round_leaves_state_unchanged(monkeypatch):
    from repro.fl.strategies import PACFL

    monkeypatch.setattr(PACFL, "run_round", lambda self, rnd, sampled, key: None)
    _round_fails(_run("round.mix4-lenet5"))


def test_round_half_batch_left_out(monkeypatch):
    """The loss is the mean over the first half of each batch."""
    import repro.fl.client as client

    real = client.ce_loss

    def half(apply_fn, params, xb, yb, mask=None):
        h = xb.shape[0] // 2
        return real(apply_fn, params, xb[:h], yb[:h], mask)

    monkeypatch.setattr(client, "ce_loss", half)
    _round_fails(_run("round.mix4-lenet5"))


def test_round_averaged_model_altered(monkeypatch):
    """Each averaged model's first convolution comes out shifted by 1e-2."""
    import repro.fl.strategies as strategies

    real = strategies.weighted_average

    def altered(stacked, w):
        avg = real(stacked, w)
        return {**avg, "c1": avg["c1"] + 1e-2}

    monkeypatch.setattr(strategies, "weighted_average", altered)
    _round_fails(_run("round.mix4-lenet5"))


def test_round_client_label_altered(monkeypatch):
    from repro.fl.strategies import PACFL

    real = PACFL.setup

    def altered(self, key, data):
        real(self, key, data)
        self.labels = self.labels.copy()
        self.labels[0] = self.labels[np.flatnonzero(self.labels != self.labels[0])[0]]

    monkeypatch.setattr(PACFL, "setup", altered)
    _round_fails(_run("round.mix4-lenet5"), ("label_mismatch",))
