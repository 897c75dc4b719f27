"""The program's spans (:mod:`repro.tracing`).

* off (no profiler session): ``span`` hands back the shared no-op and
  records nothing;
* on (``jax.profiler.trace``): one K=64 bootstrap and one drain through
  :class:`~repro.serving.AssignmentServer` record every span at the
  bootstrap's and the drain's layer boundaries, with their parent links,
  one root per bootstrap or drain, and the counts the engine's own
  telemetry holds;
* the ring keeps the newest records and counts the ones it drops;
* the profiler's trace holds a ``span.<name>`` host event for every record,
  nested the same way and starting within 1 ms of it: one clock.
"""
import collections
from pathlib import Path

import jax
import numpy as np
import pytest

from conftest import clustered_signatures
from repro import tracing
from repro.core import hc
from repro.core.angles import proximity_matrix
from repro.core.engine import ClusterEngine, EngineConfig
from repro.serving import AssignmentServer

K, JOINS, LEAVES = 64, 8, 4

BOOT_PARENT = {
    "bootstrap.proximity": "engine.bootstrap",
    "bootstrap.device_wait": "engine.bootstrap",
    "bootstrap.readback": "engine.bootstrap",
    "bootstrap.upload": "engine.bootstrap",
    "engine.hc": "engine.bootstrap",
    "store.condense": "engine.hc",
    "hc.working": "engine.hc",
    "hc.merge_forest": "engine.hc",
}
DRAIN_PARENT = {
    "queue.drain": "serve.drain",
    "engine.depart": "serve.drain",
    "engine.admit": "serve.drain",
    "serve.commit": "serve.drain",
    "store.remove": "engine.depart",
    "depart.script": "engine.depart",
    "admit.cross_block": "engine.admit",
    "store.append": "engine.admit",
    "commit.fork": "serve.commit",
    "commit.refresh": "serve.commit",
}


def _signatures():
    U = np.asarray(clustered_signatures(jax.random.PRNGKey(3), K + JOINS, spread=0.05))
    A = np.asarray(proximity_matrix(U[:K], "eq3", backend="jnp"))
    base = np.arange(K) % 6
    same = base[:, None] == base[None, :]
    beta = 0.5 * (A[same & ~np.eye(K, dtype=bool)].max() + A[~same].min())
    return U, EngineConfig(beta=float(beta), memory="dense")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One bootstrap and one drain under a profiler session."""
    U, cfg = _signatures()
    server = AssignmentServer(ClusterEngine.from_signatures(U[:K], cfg))
    departs = []
    real_depart = ClusterEngine.depart

    def depart(self, ids):
        departs.append(real_depart(self, ids))
        return departs[-1]

    trace_dir = tmp_path_factory.mktemp("trace")
    dens_before = server._write.store.memory.stats.densifications
    tracing.reset()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ClusterEngine, "depart", depart)
        with jax.profiler.trace(str(trace_dir)):
            engine = ClusterEngine.from_signatures(U[:K], cfg)
            for cid in (3, 10, 20, 33):
                server.submit_leave(cid)
            for u in U[K:]:
                server.submit_join(u)
            server.drain()
    recs = tracing.records()
    tracing.reset()
    return {
        "records": recs, "engine": engine, "server": server, "departs": departs,
        "densifications": server._write.store.memory.stats.densifications - dens_before
        + server.snapshot.engine.store.memory.stats.densifications,
        "trace": next(Path(trace_dir).rglob("*.xplane.pb")),
    }


def _by_name(recs):
    out = collections.defaultdict(list)
    for r in recs:
        out[r.name].append(r)
    return out


def test_off_returns_the_shared_noop_and_records_nothing():
    tracing.reset()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert tracing.span("engine.hc") is tracing.NO_SPAN
    with tracing.span("engine.hc") as h:
        h.count("K", 3)
        ClusterEngine.from_signatures(_signatures()[0][:16], EngineConfig(beta=30.0))
    assert h is tracing.NO_SPAN
    assert tracing.records() == [] and tracing.dropped() == 0


def test_bootstrap_spans(traced):
    recs = [r for r in traced["records"] if r.name in BOOT_PARENT or r.name == "engine.bootstrap"]
    by = _by_name(recs)
    assert set(by) == set(BOOT_PARENT) | {"engine.bootstrap"}
    assert all(len(v) == 1 for v in by.values())
    top = by["engine.bootstrap"][0]
    assert top.parent is None and top.root == top.id
    assert top.counts == {"K": K}
    for name, parent in BOOT_PARENT.items():
        r = by[name][0]
        assert r.parent == by[parent][0].id, name
        assert r.root == top.id, name
        assert top.start_ns <= r.start_ns <= r.end_ns <= top.end_ns
    eng = traced["engine"]
    # K is below the compaction width: every merge ran at full width
    merges = len(eng._script)
    assert by["hc.merge_forest"][0].counts == {
        "merges": merges, "compactions": 0, "width_sum": merges * K}
    assert by["bootstrap.readback"][0].counts == {"bytes": 4 * K * K}


def test_drain_spans(traced):
    recs = traced["records"]
    by = _by_name(recs)
    drain = by["serve.drain"]
    assert len(drain) == 1
    top = drain[0]
    assert top.parent is None
    assert top.counts == {"batches": 1, "joins": JOINS, "leaves": LEAVES}
    ids = {r.id: r for r in recs}
    for name, parent in DRAIN_PARENT.items():
        assert len(by[name]) == 1, name
        r = by[name][0]
        assert ids[r.parent].name == parent, name
        assert r.root == top.id, name
    # under one drain: every span but the submits, which are roots of their own
    under = [r for r in recs if r.root == top.id]
    assert {r.name for r in under} >= set(DRAIN_PARENT) | {
        "engine.replay", "engine.stack", "engine.remap", "store.densify", "serve.drain"}
    submits = by["serve.submit"]
    assert len(submits) == JOINS + LEAVES
    assert all(s.parent is None and s.root == s.id for s in submits)
    assert sum(s.counts.get("join", 0) for s in submits) == JOINS
    assert sum(s.counts.get("leave", 0) for s in submits) == LEAVES

    assert by["engine.depart"][0].counts == {"B": LEAVES}
    assert by["engine.admit"][0].counts == {"B": JOINS}
    assert by["store.remove"][0].counts == {"removed": LEAVES}
    assert by["admit.cross_block"][0].counts == {"pairs": (K - LEAVES) * JOINS}

    # each replay's counts are the ReplayStats it returned
    server = traced["server"]
    stats_of = {"engine.depart": traced["departs"][0].stats,
                "engine.admit": server._write.last_stats}
    replays = by["engine.replay"]
    assert sorted(ids[r.parent].name for r in replays) == sorted(stats_of)
    for r in replays:
        st = stats_of[ids[r.parent].name]
        assert r.counts == {"promotions": st.promotions, "dirty_merges": st.dirty_merges,
                            "script_applied": st.script_applied}

    # a refresh rebuilds or reuses every cluster it serves, once
    refresh = by["commit.refresh"][0].counts
    assert refresh["rebuilt"] + refresh["reused"] == server.snapshot.engine.n_clusters
    assert refresh["rebuilt"] >= 1
    # the dense cache the departure dropped is rebuilt, once per count in
    # the write engine's and the served fork's MemoryStats
    dens = by["store.densify"]
    assert len(dens) == traced["densifications"] >= 1
    assert all(ids[d.parent].name in ("engine.replay", "commit.refresh") for d in dens)


def test_densify_span_where_replay_crosses_the_threshold(tmp_path):
    """A dense-tier store with no cache densifies once a gather passes K/8
    distinct rows; the span sits where ``MemoryStats.densifications`` counts."""
    U, cfg = _signatures()
    eng = ClusterEngine.from_signatures(U[:K], cfg)
    store = eng.store
    store.drop_dense_cache()
    before = store.memory.stats.densifications
    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("engine.replay"):
            store.gather_rows(np.arange(K // 8 + 1))
    recs = tracing.records()
    tracing.reset()
    assert store.memory.stats.densifications == before + 1
    by = _by_name(recs)
    assert len(by["store.densify"]) == 1
    assert by["store.densify"][0].parent == by["engine.replay"][0].id


@pytest.mark.parametrize("n", [hc.COMPACT_MIN_WIDTH - 1, 3 * hc.COMPACT_MIN_WIDTH])
def test_merge_forest_span_counts_compactions_and_width(tmp_path, n):
    """A dense-tier bootstrap above the compaction width compacts its working
    matrix and pays for fewer columns than ``merges * K``; one below it never
    compacts."""
    rng = np.random.default_rng(n)
    X = rng.normal(size=(n, 4)) + 8.0 * rng.integers(0, 6, (n, 1))
    A = np.sqrt(((X[:, None] - X[None]) ** 2).sum(-1)).astype(np.float32)
    U = np.zeros((n, 2, 1), dtype=np.float32)
    cfg = EngineConfig(n_clusters=6, memory="dense")
    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        eng = ClusterEngine.from_proximity(A, U, cfg)
    recs = tracing.records()
    tracing.reset()
    (rec,) = _by_name(recs)["hc.merge_forest"]
    counts = rec.counts
    assert counts["merges"] == len(eng._script) == n - 6
    if n < hc.COMPACT_MIN_WIDTH:
        assert counts["compactions"] == 0
        assert counts["width_sum"] == counts["merges"] * n
    else:
        assert counts["compactions"] > 0
        assert counts["width_sum"] < counts["merges"] * n


def test_ring_drops_oldest_and_counts_them(tmp_path):
    extra = 5
    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(tracing.RING_SIZE + extra):
            with tracing.span("ring.test"):
                pass
    recs = tracing.records()
    assert len(recs) == tracing.RING_SIZE
    assert tracing.dropped() == extra
    ids = [r.id for r in recs]
    assert ids == sorted(ids) and ids[-1] - ids[0] == tracing.RING_SIZE - 1
    tracing.reset()
    assert tracing.records() == [] and tracing.dropped() == 0


def test_profiler_trace_holds_every_record_on_one_clock(traced):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(traced["trace"]))
    base = None
    events = collections.defaultdict(list)
    for plane in pd.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            base = int(stats["profile_start_time"])
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("span."):
                        events[ev.name[len("span."):]].append(
                            (float(ev.start_ns), float(ev.start_ns + ev.duration_ns)))
    assert base is not None
    recs = traced["records"]
    by = _by_name(recs)
    assert {n: len(v) for n, v in events.items()} == {n: len(v) for n, v in by.items()}
    matched = {}
    for name, rs in by.items():
        evs = sorted(events[name])
        for r, (s, e) in zip(sorted(rs, key=lambda r: r.start_ns), evs):
            assert abs(base + s - r.start_ns) < 1e6, name
            matched[r.id] = (s, e)
    for r in recs:
        if r.parent is not None:
            ps, pe = matched[r.parent]
            s, e = matched[r.id]
            assert ps <= s and e <= pe, r.name
