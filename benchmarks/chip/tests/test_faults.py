"""A run with its timed path broken underneath has to read ``correct: false``.

Each test drives a whole run of a cell through the harness (set-up, window,
check) at a size the CPU holds, without the harness's look for a chip, with
one fault planted in the program: an answer altered where it is produced,
a step that leaves the state unchanged, or a served epoch left stale.  The same runs without a fault
read ``correct: true``.
"""
import io
import time

import numpy as np
import pytest

import harness

CONFIG = {
    "boot.femnist-eq3": {"n_clients": 192},
    "churn.femnist-eq3": {"n_clients": 192},
}
E2E = {
    "boot.femnist-eq3": {"bootstrap_s", "setup_s"},
    "churn.femnist-eq3": {"drain_p50_ms", "drain_p80_ms", "setup_s"},
}


def _run(workload, seed=2**31 + 5):
    return harness.run_cell(
        workload, seed, 0.5, False, t_start=time.perf_counter(), require_tpu=False,
        config_overrides=CONFIG[workload],
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
