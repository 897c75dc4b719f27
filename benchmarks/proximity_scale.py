"""Proximity-matrix scale sweep: K x measure x backend.

The one-shot phase's server cost is the (K, K) proximity matrix.  The dense
einsum reference materializes a (K, K, p, p) Gram tensor — ~10 GB of f32 at
K=10k, p=5 — while the blocked backend tiles it into (bk, bk) client blocks
(peak intermediate O(bk^2 p^2)) and the sharded backend additionally splits
row strips across local devices.  eq2 runs on the shared measure core's
batched Jacobi eigensolve in the scalable paths (the dense reference keeps
the LAPACK svd as the oracle).  This sweep times the backends across K in
{128, 512, 2048} and both paper measures, verifies cross-backend parity at
K=128, runs the sharded engine under a forced 4-device host platform
(K=512, asserting bitwise-identical HC labels vs the single-device blocked
backend), and writes ``BENCH_proximity_scale.json`` at the repo root.

A ``streaming`` section times the cluster-engine admission path (cross
blocks + en-bloc dendrogram replay) against both the sequential
(pre-en-bloc) replay and the re-cluster-the-world baseline
(extend_proximity_matrix + full hierarchical_clustering) for newcomer
batches B at K in {512, 2048}, asserting label parity; a ``churn_queue``
section checks that draining an async ChurnQueue (policy-sized admission
batches, DrainPolicy fitted from a seeded probe) reproduces the labels of
the equivalent synchronous schedule bitwise.

A ``memory_sweep`` section measures the distance-store memory tiers
(``dense`` | ``banded`` | ``condensed_only`` — see
repro.core.engine.memory) at K in {2048, 8192}: each (K, policy) runs in
its own subprocess so ``ru_maxrss`` is a clean peak-RSS reading, reporting
bootstrap time, steady-state admission time, persistent store/cache bytes
and band hit rates, and asserting cross-policy label parity (bitwise).
``memory_parity`` is the in-process cross-tier bitwise gate (admit +
depart under every tier) that ``--quick`` runs in CI.

A ``serving`` section measures the membership-as-a-service read path
(repro.serving): p50/p99 assignment latency and sustained QPS of the
batched :class:`AssignmentServer` dispatch against the per-cluster
representative cache at K in {2048, 8192} and batch sizes {1, 16, 128},
with a bitwise gate that batched served labels equal one-by-one
``engine.admit`` assignment (``assignment_parity_ok``).
``serving_parity`` is the cheap in-process smoke of the same gate (plus
snapshot-epoch isolation) that ``--quick`` runs in CI; ``--serving`` runs
only the full serving sweep and merges its section into the existing json.

A ``move_parity`` section gates the engine's fused ``move`` (drift-aware
signature refresh): under every memory tier the one-pass fused move must
reproduce bitwise the labels of sequential depart-then-admit (canonical)
and of a full re-clustering of the post-move store; ``--quick`` runs it in
CI.  A ``drift_churn`` section (full sweep only) times the fused move
against the sequential composition at K=2048 / B=64, gating the speedup at
``DRIFT_SPEEDUP_GATE`` with canonical-label CRC parity.

A ``family_parity`` section gates the pluggable signature families
(repro.core.signatures): the registry-dispatched ``svd`` family must be
bitwise-identical — signatures, cluster labels and dendrogram merge script
— to an inline replica of the pre-refactor bucketed loop, and
``weight_delta`` / ``inference`` run end-to-end through the unchanged
engine with their canonical-label CRCs recorded.  A ``streaming_bootstrap``
section times the condensed bootstrap's cache-blocked nearest-neighbor
pass against the strided row-gather path it replaced (bitwise-gated).

Run: PYTHONPATH=src python benchmarks/proximity_scale.py [--full | --quick]

``--quick`` is the CI parity smoke: K=128 only, every backend and eq2
solver against the dense reference, the 4-device label check at K=128, the
engine-vs-full-re-cluster streaming parity check, the queue-drain parity
check, the signature-family gates, the bootstrap-prepare bitwise check,
the cross-tier memory-policy parity check, and the fused-move parity
check; nonzero exit on any parity failure.  ``--quick`` does not rerun the
expensive sweeps: it merges only its own ``family_parity`` /
``streaming_bootstrap`` / ``serving_parity`` / ``move_parity`` sections
into an existing BENCH_proximity_scale.json (no other fields are touched).
(also registered as the ``proximity_scale`` suite of benchmarks.run).

Every field of the emitted json is documented in ``docs/BENCHMARKS.md``.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # direct-run mode

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import ROOT
from repro.core.angles import _DEFAULT_BLOCK, proximity_matrix

KS = (128, 512, 2048)
MEASURES = ("eq2", "eq3")
# block_size=None: each backend's default (blocked: 64 eq3 / 96 eq2,
# sharded: 64, pallas: 128) — what PACFLConfig.proximity_block=None also
# uses.
BLOCK_SIZE = None
# The dense path's (K, K, p, p) tensor passes ~400 MB at K=2048; keep the
# reference to sizes where it is the sensible baseline.
DENSE_MAX_K = 512
# Off-TPU the Pallas kernel runs in interpret mode — O(K^2/bk^2) Python-level
# grid steps — so only sample it at the smallest K there.
PALLAS_MAX_K_INTERPRET = 128
PARITY_K = 128
PARITY_TOL_DEG = 1e-3
SHARDED_DEVICES = 4
SHARDED_K = 512

# The eq2 solver each backend resolves to under eq2_solver="auto" — recorded
# so the json says what was actually measured.
_EQ2_SOLVER = {
    "jnp": "svd", "jnp_blocked": "jacobi", "jnp_sharded": "jacobi",
    "pallas": "jacobi",
}


def _signatures(K: int, n: int = 64, p: int = 5) -> jax.Array:
    """Stacked orthonormal signatures, vmapped QR (a K-long Python loop of
    per-client QRs would dwarf the timings we are measuring)."""
    X = jax.random.normal(jax.random.PRNGKey(0), (K, n, p))
    return jax.vmap(lambda x: jnp.linalg.qr(x)[0])(X)


def _clustered_signatures(K: int, n_bases: int = 16, n: int = 64, p: int = 5,
                          seed: int = 0) -> jax.Array:
    """Signatures concentrated on n_bases subspaces — gives the streaming
    section a clustering with real structure instead of one giant blob."""
    key = jax.random.PRNGKey(seed)
    kb, kc = jax.random.split(key)
    bases = jnp.stack([
        jnp.linalg.qr(jax.random.normal(jax.random.fold_in(kb, i), (n, p)))[0]
        for i in range(n_bases)
    ])
    noise = 0.15 * jax.random.normal(kc, (K, n, p))
    X = bases[jnp.arange(K) % n_bases] + noise
    return jax.vmap(lambda x: jnp.linalg.qr(x)[0])(X)


def _backends_for(K: int) -> list[str]:
    backends = []
    if K <= DENSE_MAX_K:
        backends.append("jnp")
    backends.append("jnp_blocked")
    backends.append("jnp_sharded")
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or K <= PALLAS_MAX_K_INTERPRET:
        backends.append("pallas")
    return backends


# Runs in a subprocess with --xla_force_host_platform_device_count: compares
# the sharded engine against the single-device blocked backend and reports
# timings + HC-label identity on a non-trivial partition.
_SHARDED_SCRIPT = r"""
import json, sys, time
import jax, jax.numpy as jnp, numpy as np
from repro.core.angles import proximity_matrix
from repro.core.hc import hierarchical_clustering

K = int(sys.argv[1])
U = jax.vmap(lambda x: jnp.linalg.qr(x)[0])(
    jax.random.normal(jax.random.PRNGKey(0), (K, 64, 5))
)
out = {"ndev": len(jax.devices()), "K": K, "rows": []}
for measure in ("eq2", "eq3"):
    times = {}
    mats = {}
    for backend in ("jnp_blocked", "jnp_sharded"):
        fn = lambda: proximity_matrix(U, measure, backend=backend)
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times[backend] = (time.perf_counter() - t0) * 1e6
        mats[backend] = np.asarray(fn())
    beta = float(np.quantile(mats["jnp_blocked"][mats["jnp_blocked"] > 0], 0.02))
    lb = hierarchical_clustering(mats["jnp_blocked"], beta=beta)
    ls = hierarchical_clustering(mats["jnp_sharded"], beta=beta)
    out["rows"].append({
        "measure": measure,
        "us_blocked": times["jnp_blocked"],
        "us_sharded": times["jnp_sharded"],
        "max_dev_deg": float(np.abs(mats["jnp_blocked"] - mats["jnp_sharded"]).max()),
        "hc_labels_identical": bool((lb == ls).all()),
        "n_clusters": int(lb.max()) + 1,
    })
print("RESULT" + json.dumps(out))
"""


def _sharded_multi_device(K: int, ndev: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ndev}"
    ).strip()
    env["PYTHONPATH"] = str(ROOT / "src")
    # A virtual-device rehearsal, and the parent already holds the chip.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT, str(K)],
        capture_output=True, text=True, env=env, timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"sharded subprocess failed:\n{proc.stderr[-4000:]}")
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def _parity_rows(record, rows):
    """K=128: every backend and every eq2 solver against the dense svd ref."""
    U = _signatures(PARITY_K)
    ref = {
        m: np.asarray(proximity_matrix(U, m, backend="jnp")) for m in MEASURES
    }
    checks = [(m, b, "auto") for m in MEASURES for b in _backends_for(PARITY_K)]
    checks += [("eq2", "jnp_blocked", s) for s in ("jacobi", "eigh", "svd")]
    for measure, backend, solver in checks:
        got = np.asarray(
            proximity_matrix(
                U, measure, backend=backend, block_size=BLOCK_SIZE,
                eq2_solver=solver,
            )
        )
        err = float(np.abs(got - ref[measure]).max())
        entry = {
            "K": PARITY_K,
            "measure": measure,
            "backend": backend,
            "eq2_solver": solver if measure == "eq2" else None,
            "max_err_vs_ref_deg": err,
        }
        record["parity"].append(entry)
        rows.append((
            f"proximity_scale/parity_{measure}_{backend}_{solver}",
            None,
            f"maxerr={err:.2e}deg",
        ))


def _canon(labels):
    seen = {}
    return np.array([seen.setdefault(int(x), len(seen)) for x in labels])


def _streaming_rows(record, rows, Ks, Bs, iters):
    """Admission latency: engine (cross blocks + en-bloc dendrogram replay)
    vs the re-cluster-the-world baseline (Alg. 2 extension + full HC over
    the extended matrix), with label-parity checks.  The sequential
    (pre-en-bloc) replay is timed alongside so the json records what the
    run batching itself buys."""
    import time as _time

    import repro.core.engine.dendrogram as _dg
    from repro.core.engine import ClusterEngine, EngineConfig
    from repro.core.hc import hierarchical_clustering
    from repro.core.pme import extend_proximity_matrix

    record["streaming"] = []
    for K in Ks:
        # 64 bases: clusters stay local, so a B-newcomer batch dirties only
        # the clusters it actually lands in — the engine's designed regime
        U_all = _clustered_signatures(K + max(Bs), n_bases=64)
        U_seen = U_all[:K]
        cfg = EngineConfig(beta=0.0, measure="eq3")  # beta set below
        A_seen = np.asarray(
            proximity_matrix(U_seen, cfg.measure, backend="jnp_blocked")
        )
        off = A_seen[A_seen > 0]
        cfg = EngineConfig(beta=float(np.quantile(off, 0.05)), measure="eq3")
        base_engine = ClusterEngine.from_proximity(A_seen, U_seen, cfg)
        # steady-state streaming: the dense read-only cache is warm (one
        # admission builds it and append_block keeps it in sync; forks
        # share it), so timed admissions measure the recurring cost
        base_engine.warm_cache()
        for B in Bs:
            U_new = U_all[K : K + B]
            # engine: fork outside the timed region (the fork is a plain
            # condensed-store memcpy, not part of the admission algorithm)
            t_eng, t_seq, t_base = [], [], []
            parity = True
            stats = None
            # warmup: compile the cross/square proximity kernels for these
            # shapes outside the timed region (both paths share them)
            base_engine.copy().admit(U_new)
            extend_proximity_matrix(A_seen, U_seen, U_new, measure=cfg.measure)
            min_run = _dg.ENBLOC_MIN_RUN
            for _ in range(iters):
                eng = base_engine.copy()
                t0 = _time.perf_counter()
                eng.admit(U_new)
                t_eng.append((_time.perf_counter() - t0) * 1e6)
                stats = eng.last_stats
                try:  # sequential replay reference (en-bloc disabled)
                    _dg.ENBLOC_MIN_RUN = 10**9
                    eng_s = base_engine.copy()
                    t0 = _time.perf_counter()
                    eng_s.admit(U_new)
                    t_seq.append((_time.perf_counter() - t0) * 1e6)
                finally:
                    _dg.ENBLOC_MIN_RUN = min_run
                parity &= bool(
                    (eng_s.canonical_labels == eng.canonical_labels).all()
                )
                t0 = _time.perf_counter()
                A_ext, _ = extend_proximity_matrix(
                    A_seen, U_seen, U_new, measure=cfg.measure
                )
                base_labels = hierarchical_clustering(
                    A_ext.astype(np.float64), cfg.beta, linkage=cfg.linkage
                )
                t_base.append((_time.perf_counter() - t0) * 1e6)
                parity &= bool(
                    (_canon(base_labels) == _canon(eng.canonical_labels)).all()
                )
            us_e = sorted(t_eng)[len(t_eng) // 2]
            us_s = sorted(t_seq)[len(t_seq) // 2]
            us_b = sorted(t_base)[len(t_base) // 2]
            entry = {
                "K": K,
                "B": B,
                "beta": cfg.beta,
                "us_engine_admit": us_e,
                "us_engine_admit_sequential_replay": us_s,
                "us_recluster_baseline": us_b,
                "speedup": us_b / us_e,
                "enbloc_speedup_vs_sequential": us_s / us_e,
                "labels_parity": parity,
                "replay": {
                    "script_applied": stats.script_applied,
                    "dirty_merges": stats.dirty_merges,
                    "promotions": stats.promotions,
                    "enbloc_runs": stats.enbloc_runs,
                    "enbloc_entries": stats.enbloc_entries,
                    "enbloc_fallbacks": stats.enbloc_fallbacks,
                },
            }
            record["streaming"].append(entry)
            rows.append((
                f"proximity_scale/streaming_K{K}_B{B}_engine",
                us_e,
                f"recluster={us_b:.0f}us speedup={us_b / us_e:.1f}x "
                f"enbloc_vs_seq={us_s / us_e:.1f}x parity={parity}",
            ))
    if len(Ks) > 1:
        # growth across the K sweep: the engine should scale ~linearly in M
        # (cross block + script walk) while the re-cluster baseline scales
        # quadratically — the "sublinear vs baseline" acceptance signal.
        record["streaming_scaling"] = []
        for B in Bs:
            es = [e for e in record["streaming"] if e["B"] == B]
            ge = es[-1]["us_engine_admit"] / es[0]["us_engine_admit"]
            gb = es[-1]["us_recluster_baseline"] / es[0]["us_recluster_baseline"]
            entry = {
                "B": B,
                "K_ratio": Ks[-1] / Ks[0],
                "engine_latency_growth": ge,
                "baseline_latency_growth": gb,
                "sublinear_vs_baseline": ge < gb,
            }
            record["streaming_scaling"].append(entry)
            rows.append((
                f"proximity_scale/streaming_scaling_B{B}",
                None,
                f"engine x{ge:.1f} vs recluster x{gb:.1f} over K x{Ks[-1] // Ks[0]}",
            ))
    return all(e["labels_parity"] for e in record["streaming"])


# --------------------------------------------------------------------------
# Memory-policy sweep: per-tier peak RSS + admission latency, in clean
# subprocesses (ru_maxrss is a high-water mark, so tiers must not share a
# process), against data precomputed once by the parent.
# --------------------------------------------------------------------------

MEMORY_KS = (2048, 8192)
MEMORY_POLICIES = ("dense", "banded", "condensed_only", "spilled")
MEMORY_B = 16
# Sweep window: sized to the workload's hot set (the members of the
# clusters successive admissions dirty) — 2048 rows is 1/4 of the dense
# mirror at K=8192.  The policy default (512) targets smaller hot sets.
MEMORY_BAND_ROWS = 2048

# The subprocess performs NO proximity computation: the parent precomputes
# the full (Kmax + 2B) proximity matrix and the subprocess slices its
# admission blocks out of it, driving the store + replay directly.  This
# keeps XLA compilation (whose ~GB-scale arena would dwarf every tier's
# working set in ru_maxrss) out of the measured process, so peak RSS and
# admission time reflect exactly what the memory policy governs: store
# caches, bootstrap working set, and replay gathers.
_MEMORY_SCRIPT = r"""
import json, resource, sys, time, zlib
import numpy as np, jax.numpy as jnp
from repro.core.engine import ClusterEngine, EngineConfig, replay


def peak_rss_mb():
    # /proc VmHWM is per-address-space and resets on execve; ru_maxrss does
    # NOT (the forking benchmark parent would leak its own high-water mark
    # into every child reading).  Some sandboxed kernels propagate even
    # VmHWM across exec — baseline_rss_mb (read right after imports) is
    # reported alongside so the tier delta is recoverable either way.
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


RSS0 = peak_rss_mb()


path, mode, K_s = sys.argv[1], sys.argv[2], sys.argv[3]
K = int(K_s)
data = np.load(path)
# the (Kmax+5B)^2 shared input is memory-mapped so each child only pages in
# the rows it actually slices — otherwise the ~274 MB load would swamp the
# per-tier RSS deltas this sweep exists to measure
A = np.load(str(data["A_path"]), mmap_mode="r")
beta = float(data["beta"])
B = int(data["B"])
cfg = EngineConfig(beta=beta, measure="eq3", memory=mode,
                   band_rows=int(data["band_rows"]))
if mode == "spilled":
    # quarter-of-the-store budget (2 K (K-1) bytes condensed, 4 MiB floor):
    # most of the vector must live on disk for the RSS delta to mean much
    cfg = EngineConfig(beta=beta, measure="eq3", memory=mode,
                       band_rows=int(data["band_rows"]),
                       memory_budget_bytes=max(1 << 22, K * (K - 1) // 2),
                       spill_segment_rows=512)
t0 = time.perf_counter()
eng = ClusterEngine.from_proximity(A[:K, :K], jnp.zeros((K, 2, 1)), cfg)
boot_s = time.perf_counter() - t0
eng.warm_cache()
# warmup admission (rows K..K+B as newcomers): builds + warms the tier's
# cache in place (dense: append keeps the (K, K) f32 in sync; banded: the
# replay's gathers populate the hot window and append extends it)
eng.store.append_block(A[:K, K : K + B], A[K : K + B, K : K + B])
labels, script, _ = replay(
    eng.store, eng._script, [[K + t] for t in range(B)], beta=beta
)
M = K + B
# steady-state: the timed batch arrives from the SAME cohorts as the
# warmup batch (rows chosen base-aligned by the parent), so its replay
# dirties clusters whose member rows the warmup already pulled into the
# banded tier's hot window — admission-stream locality, not a cold start
idx2 = np.arange(int(data["idx2_start"]), int(data["idx2_start"]) + B)
cross2 = A[:M, idx2]
square2 = A[np.ix_(idx2, idx2)]
t_adm = []
st = None
for _ in range(3):
    st = None                   # free the previous fork (its band copy)
    st = eng.store.copy()       # before forking anew, outside the timer
    t0 = time.perf_counter()
    st.append_block(cross2, square2)
    labels, _, _ = replay(
        st, script, [[M + t] for t in range(B)], beta=beta
    )
    t_adm.append((time.perf_counter() - t0) * 1e6)
mem = st.memory
band = mem.band
out = {
    "mode": mode,
    "K": K,
    "boot_s": boot_s,
    "us_admit": sorted(t_adm)[len(t_adm) // 2],
    "peak_rss_mb": peak_rss_mb(),
    "baseline_rss_mb": RSS0,
    "store_bytes": int(st.nbytes),
    "boot_work_bytes": 8 * K * K if mode == "dense" else 4 * K * (K - 1),
    "dense_cache_bytes": 4 * K * K if st.has_dense_cache else 0,
    "band_bytes": int(band.nbytes) if band is not None else 0,
    "band_hits": int(band.hits) if band is not None else 0,
    "band_misses": int(band.misses) if band is not None else 0,
    "peak_gather_bytes": int(mem.stats.peak_gather_bytes),
    "spilled_bytes": int(getattr(st, "spilled_nbytes", 0)),
    "resident_store_bytes": int(getattr(st, "resident_nbytes", 0)),
    "cold_segment_reads": int(getattr(st, "cold_segment_reads", 0)),
    "labels_sum": int(np.asarray(labels, dtype=np.int64).sum()),
    "labels_crc": int(zlib.crc32(
        np.ascontiguousarray(np.asarray(labels, dtype=np.int64)).tobytes())),
    "n_clusters": int(np.unique(labels).size),
}
print("RESULT" + json.dumps(out))
"""


def _memory_rows(record, rows, Ks=MEMORY_KS, policies=MEMORY_POLICIES):
    """Per-tier bootstrap + admission cost at scale, one subprocess each."""
    import tempfile

    record["memory_sweep"] = []
    ok = True
    Kmax = max(Ks)
    # 5B extra rows: warmup newcomers are rows [K, K+B) and the timed batch
    # rows [Kmax+4B, Kmax+5B) — with n_bases=64 and B=16 both land on bases
    # 0..15 (K and Kmax+4B are multiples of 64), i.e. successive admissions
    # arrive from the same cohorts (the banded tier's locality assumption)
    U_all = _clustered_signatures(Kmax + 5 * MEMORY_B, n_bases=64)
    A = np.asarray(
        proximity_matrix(U_all, "eq3", backend="jnp_blocked")
    ).astype(np.float32)
    beta = float(np.quantile(A[A > 0], 0.05))
    with tempfile.NamedTemporaryFile(suffix=".npz", delete=False) as f:
        tmp = f.name
    tmp_a = tmp + ".A.npy"
    try:
        np.save(tmp_a, A)  # standalone .npy: children mmap it read-only
        np.savez(
            tmp, A_path=tmp_a, beta=beta, B=MEMORY_B,
            band_rows=MEMORY_BAND_ROWS, idx2_start=Kmax + 4 * MEMORY_B,
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = str(ROOT / "src")
        # No device work by design, and the parent already holds the chip.
        env["JAX_PLATFORMS"] = "cpu"
        for K in Ks:
            per_k = []
            for mode in policies:
                proc = subprocess.run(
                    [sys.executable, "-c", _MEMORY_SCRIPT, tmp, mode, str(K)],
                    capture_output=True, text=True, env=env, timeout=1800,
                )
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"memory sweep subprocess failed ({mode}, K={K}):\n"
                        f"{proc.stderr[-4000:]}"
                    )
                line = [
                    l for l in proc.stdout.splitlines() if l.startswith("RESULT")
                ][-1]
                entry = json.loads(line[len("RESULT"):])
                per_k.append(entry)
                record["memory_sweep"].append(entry)
                rows.append((
                    f"proximity_scale/memory_K{K}_{mode}",
                    entry["us_admit"],
                    f"rss={entry['peak_rss_mb']:.0f}MB boot={entry['boot_s']:.2f}s "
                    f"cache={(entry['dense_cache_bytes'] + entry['band_bytes']) / 2**20:.1f}MB",
                ))
            same = len({e["labels_crc"] for e in per_k}) == 1
            ok &= same
            rows.append((
                f"proximity_scale/memory_K{K}_label_parity", None, str(same)
            ))
            by_mode = {e["mode"]: e for e in per_k}
            if K >= 8192 and {"spilled", "condensed_only"} <= by_mode.keys():
                # the tier's acceptance claim: with most of the condensed
                # vector on disk, spilled peak RSS must sit strictly below
                # condensed_only's (whose vector is fully resident)
                below = (
                    by_mode["spilled"]["peak_rss_mb"]
                    < by_mode["condensed_only"]["peak_rss_mb"]
                )
                ok &= below
                rows.append((
                    f"proximity_scale/memory_K{K}_spilled_rss_below",
                    None,
                    f"{by_mode['spilled']['peak_rss_mb']:.0f}MB < "
                    f"{by_mode['condensed_only']['peak_rss_mb']:.0f}MB: {below}",
                ))
    finally:
        os.unlink(tmp)
        if os.path.exists(tmp_a):
            os.unlink(tmp_a)
    record["memory_sweep_parity"] = ok
    return ok


def _memory_parity_rows(record, rows):
    """Cross-tier bitwise parity gate: bootstrap + admit + depart under
    every memory tier reproduce the dense tier's labels bitwise (--quick
    CI smoke; band_rows small enough to force LRU eviction, and the spilled
    tier's budget small enough that cold segments really hit the disk)."""
    from repro.core.engine import ClusterEngine, EngineConfig

    K, B = 192, 12
    U_all = _clustered_signatures(K + B, n_bases=16, seed=7)
    A = np.asarray(proximity_matrix(U_all[:K], "eq3", backend="jnp_blocked"))
    beta = float(np.quantile(A[A > 0], 0.05))
    results = {}
    for mode in ("dense", "banded", "condensed_only", "auto", "spilled"):
        spill = (
            {"memory_budget_bytes": 1 << 14, "spill_segment_rows": 64}
            if mode == "spilled"
            else {}
        )
        cfg = EngineConfig(
            beta=beta, measure="eq3", memory=mode, band_rows=16, **spill
        )
        eng = ClusterEngine.from_proximity(A, U_all[:K], cfg)
        eng.admit(U_all[K:])
        eng.depart(np.arange(40, 60))
        results[mode] = (eng.labels.copy(), eng.canonical_labels.copy())
    ok = all(
        np.array_equal(results[m][0], results["dense"][0])
        and np.array_equal(results[m][1], results["dense"][1])
        for m in results
    )
    record["memory_parity"] = {
        "K": K, "B": B, "modes": sorted(results), "labels_bitwise": ok,
    }
    rows.append(("proximity_scale/memory_tier_parity", None, f"bitwise={ok}"))
    return ok


def _move_parity_rows(record, rows):
    """Fused-move bitwise parity gate (--quick CI smoke).

    Under every memory tier, ``engine.move`` (one replay pass) must
    reproduce — bitwise — (a) the dense tier's labels, (b) the canonical
    labels of the *sequential* depart-then-admit composition it fuses, and
    (c) a full re-clustering of the post-move store (the oracle).  Stable
    labels differ between fused and sequential by design (movers keep
    their client ids under move; depart+admit assigns fresh ones), so the
    cross-path gate is on canonical labels.
    """
    import zlib

    from repro.core.engine import ClusterEngine, EngineConfig
    from repro.core.hc import hierarchical_clustering

    K, B = 192, 12
    movers = np.arange(30, 30 + B, dtype=np.int64)
    U_all = _clustered_signatures(K + B, n_bases=16, seed=7)
    U_ref = U_all[K:]
    A = np.asarray(proximity_matrix(U_all[:K], "eq3", backend="jnp_blocked"))
    beta = float(np.quantile(A[A > 0], 0.05))
    results = {}
    ok = True
    for mode in ("dense", "banded", "condensed_only", "auto", "spilled"):
        spill = (
            {"memory_budget_bytes": 1 << 14, "spill_segment_rows": 64}
            if mode == "spilled"
            else {}
        )
        cfg = EngineConfig(
            beta=beta, measure="eq3", memory=mode, band_rows=16, **spill
        )
        eng = ClusterEngine.from_proximity(A, U_all[:K], cfg)
        seq = eng.copy()
        res = eng.move(movers, U_ref)
        seq.depart(movers)
        seq.admit(U_ref)
        oracle = hierarchical_clustering(
            eng.dense(np.float64), beta=beta, linkage="average"
        )
        ok &= bool(
            np.array_equal(res.canonical, seq.canonical_labels)
            and np.array_equal(res.canonical, oracle)
        )
        results[mode] = (eng.labels.copy(), eng.canonical_labels.copy())
    ok &= all(
        np.array_equal(results[m][0], results["dense"][0])
        and np.array_equal(results[m][1], results["dense"][1])
        for m in results
    )
    record["move_parity"] = {
        "K": K, "B": B, "modes": sorted(results), "labels_bitwise": ok,
        "canonical_crc": int(zlib.crc32(np.ascontiguousarray(
            results["dense"][1].astype(np.int64)).tobytes())),
    }
    rows.append(("proximity_scale/move_parity", None, f"bitwise={ok}"))
    return ok


DRIFT_K = 2048
DRIFT_B = 64
DRIFT_SPEEDUP_GATE = 1.3


def _drift_churn_rows(record, rows, iters: int = 3):
    """Fused-move speedup gate at scale (full sweep only).

    ``move`` replaces sequential depart+admit's two replay passes (plus
    two stable-label remaps and an extra store compaction bookkeeping
    round) with one of each; at K=2048 / B=64 the fused path must be at
    least ``DRIFT_SPEEDUP_GATE``x faster with canonical-label CRC parity.

    Measured in the ``condensed_only`` memory tier — the streaming regime
    the fused move targets.  A dense-mirror tier spends most of each
    churn call on shared mirror maintenance (identical for both paths),
    which drowns the replay saving in co-tenant load noise; with the
    condensed store alone, replay dominates and the dirty-merge ratio
    (one fused pass vs depart's + admit's) shows through.  Iterations
    are interleaved (fused, sequential, fused, ...) and the gated
    statistic is the *median of per-pair ratios*: adjacent runs see the
    same machine load, so each ratio is load-normalized even when a
    spike spans several seconds.
    """
    import time as _time
    import zlib

    from repro.core.engine import ClusterEngine, EngineConfig

    K, B = DRIFT_K, DRIFT_B
    U_all = _clustered_signatures(K + B, n_bases=64, seed=13)
    U_ref = U_all[K:]
    # movers spread across the roster, not one contiguous range
    movers = np.linspace(0, K - 1, B).astype(np.int64)
    A = np.asarray(proximity_matrix(U_all[:K], "eq3", backend="jnp_blocked"))
    beta = float(np.quantile(A[A > 0], 0.05))
    cfg = EngineConfig(beta=beta, measure="eq3", memory="condensed_only")
    base = ClusterEngine.from_proximity(A, U_all[:K], cfg)

    def fused(e):
        e.move(movers, U_ref)
        return e

    def sequential(e):
        e.depart(movers)
        e.admit(U_ref)
        return e

    def timed_once(fn):
        eng = base.copy()
        t0 = _time.perf_counter()
        out = fn(eng)
        return (_time.perf_counter() - t0) * 1e6, out

    fused(base.copy())  # warmup: compile the (M, B) cross-block kernels
    sequential(base.copy())
    fused_ts, seq_ts = [], []
    for _ in range(iters):
        us, fused_eng = timed_once(fused)
        fused_ts.append(us)
        us, seq_eng = timed_once(sequential)
        seq_ts.append(us)
    fused_us, seq_us = min(fused_ts), min(seq_ts)
    ratios = sorted(s / f for f, s in zip(fused_ts, seq_ts))
    pair_speedup = ratios[len(ratios) // 2]

    def crc(labels):
        return int(zlib.crc32(np.ascontiguousarray(
            np.asarray(labels, dtype=np.int64)).tobytes()))

    crc_fused = crc(fused_eng.canonical_labels)
    crc_seq = crc(seq_eng.canonical_labels)
    parity = crc_fused == crc_seq
    record["drift_churn"] = {
        "K": K, "B": B, "iters": iters,
        "fused_move_us": fused_us, "depart_admit_us": seq_us,
        "speedup": pair_speedup, "speedup_gate": DRIFT_SPEEDUP_GATE,
        "min_ratio_speedup": seq_us / max(fused_us, 1e-9),
        "canonical_crc_fused": crc_fused, "canonical_crc_seq": crc_seq,
        "crc_parity": parity,
    }
    rows.append((
        f"proximity_scale/drift_churn_K{K}_B{B}_fused", fused_us,
        f"speedup={pair_speedup:.2f}x crc_parity={parity}",
    ))
    return parity and pair_speedup >= DRIFT_SPEEDUP_GATE


def _family_parity_rows(record, rows):
    """Signature-family gates (always run, --quick included).

    1. svd family bitwise parity: the registry-dispatched
       ``compute_signatures`` against an inline replica of the
       pre-refactor bucketed/batched loop — signature stack, cluster
       labels AND the engine's dendrogram merge script must all match
       exactly (the tentpole's "same engine, unchanged svd path" claim).
    2. Cross-family smoke: ``weight_delta`` and ``inference`` run
       end-to-end on a small labeled federation through the SAME
       family-agnostic engine; canonical-label CRCs are recorded so a
       behavioral drift in either extractor shows up as a changed CRC in
       the json history.
    """
    import zlib

    from repro.core.pacfl import (
        PACFLConfig, cluster_clients, compute_signatures,
    )
    from repro.core.signatures.svd import SIG_BATCH_MAX
    from repro.core.svd import batched_client_signatures, bucket_samples

    # -- 1: svd bitwise gate on ragged clients ----------------------------
    cfg = PACFLConfig(p=3, measure="eq2", beta=45.0)
    key = jax.random.PRNGKey(11)
    rng = np.random.default_rng(5)
    mats = [
        jnp.asarray(rng.normal(size=(32, m)).astype(np.float32))
        for m in rng.integers(12, 180, size=48)
    ]

    def inline_svd():  # the pre-registry compute_signatures loop, verbatim
        K, n = len(mats), int(mats[0].shape[0])
        buckets: dict[int, list[int]] = {}
        for k, D in enumerate(mats):
            buckets.setdefault(bucket_samples(int(D.shape[1])), []).append(k)
        U = np.zeros((K, n, cfg.p), dtype=np.float32)
        for mb, idxs in sorted(buckets.items()):
            for lo in range(0, len(idxs), SIG_BATCH_MAX):
                chunk = idxs[lo : lo + SIG_BATCH_MAX]
                D_stack = jnp.stack([
                    jnp.pad(
                        jnp.asarray(mats[k], dtype=jnp.float32),
                        ((0, 0), (0, mb - mats[k].shape[1])),
                    )
                    for k in chunk
                ])
                keys = jnp.stack([jax.random.fold_in(key, k) for k in chunk])
                sigs = batched_client_signatures(
                    D_stack, keys, cfg.p, cfg.svd_method
                )
                U[np.asarray(chunk)] = np.asarray(sigs)
        return jnp.asarray(U)

    U_ref = inline_svd()
    U_fam = compute_signatures(mats, cfg, key=key)
    sig_bitwise = bool((np.asarray(U_ref) == np.asarray(U_fam)).all())
    clu_ref = cluster_clients(U_ref, cfg)
    clu_fam = cluster_clients(U_fam, cfg)
    labels_bitwise = bool(
        np.array_equal(clu_ref.labels, clu_fam.labels)
        and np.array_equal(
            clu_ref.engine.canonical_labels, clu_fam.engine.canonical_labels
        )
    )
    script_bitwise = clu_ref.engine._script == clu_fam.engine._script
    svd_ok = sig_bitwise and labels_bitwise and script_bitwise
    record["family_parity"] = {
        "svd": {
            "K": len(mats),
            "signatures_bitwise": sig_bitwise,
            "labels_bitwise": labels_bitwise,
            "merge_script_bitwise": script_bitwise,
        },
        "families": [],
    }
    rows.append((
        "proximity_scale/family_svd_parity", None,
        f"signatures={sig_bitwise} labels={labels_bitwise} "
        f"script={script_bitwise}",
    ))

    # -- 2: cross-family end-to-end CRCs ----------------------------------
    from repro.data.synthetic import make_dataset
    from repro.fl.partition import label_skew

    ds = make_dataset("cifar10s", n_train=360, n_test=60, dim=32, seed=2)
    clients = label_skew(ds, n_clients=12, rho=0.2, seed=2, test_per_client=10)
    fam_cfgs = {
        "svd": PACFLConfig(p=3, measure="eq2", beta=45.0),
        "weight_delta": PACFLConfig(
            p=3, measure="eq2", family="weight_delta", beta_quantile=0.15,
            family_params={"segments": 3, "steps": 4, "sketch_dim": 64},
        ),
        "inference": PACFLConfig(
            p=3, measure="eq2", family="inference", beta_quantile=0.15,
            family_params={"probe_per_dataset": 16, "steps": 4},
        ),
    }
    fam_ok = True
    for fam, fcfg in fam_cfgs.items():
        payloads = (
            [jnp.asarray(c.x_train.T) for c in clients]
            if fam == "svd" else clients
        )
        clu = cluster_clients(
            compute_signatures(payloads, fcfg, key=jax.random.PRNGKey(3)),
            fcfg,
        )
        canon = np.asarray(clu.engine.canonical_labels, dtype=np.int64)
        crc = int(zlib.crc32(np.ascontiguousarray(canon).tobytes()))
        n_sig = tuple(int(s) for s in clu.U.shape[1:])
        ok = clu.n_clusters >= 1 and clu.labels.size == len(clients)
        fam_ok &= ok
        record["family_parity"]["families"].append({
            "family": fam,
            "K": len(clients),
            "sig_shape": n_sig,
            "n_clusters": int(clu.n_clusters),
            "labels_crc": crc,
            "signature_bytes": int(clu.signature_bytes),
        })
        rows.append((
            f"proximity_scale/family_{fam}", None,
            f"clusters={clu.n_clusters} sig={n_sig} crc={crc:#010x}",
        ))
    return svd_ok and fam_ok


def _streaming_bootstrap_rows(record, rows, quick=True):
    """Carried speed item (b): the condensed bootstrap's initial
    nearest-neighbor pass — cache-blocked column-segment layout
    (``CondensedWorkingMatrix.prepare``) vs the strided row-gather path it
    replaced (``prepare_rowgather``), bitwise-gated."""
    import time as _time

    from repro.core.hc import CondensedWorkingMatrix

    Ks = (1024,) if quick else (1024, 4096, 8192)
    iters = 3 if quick else 5
    record["streaming_bootstrap"] = []
    ok = True
    rng = np.random.default_rng(0)
    for K in Ks:
        v = rng.random(K * (K - 1) // 2)
        w = CondensedWorkingMatrix(v, K)
        t_blk, t_row = [], []
        for _ in range(iters):
            t0 = _time.perf_counter()
            nn_b, nnd_b = w.prepare()
            t_blk.append((_time.perf_counter() - t0) * 1e6)
            t0 = _time.perf_counter()
            nn_r, nnd_r = w.prepare_rowgather()
            t_row.append((_time.perf_counter() - t0) * 1e6)
        bitwise = bool(
            np.array_equal(nn_b, nn_r) and np.array_equal(nnd_b, nnd_r)
        )
        ok &= bitwise
        us_b = sorted(t_blk)[iters // 2]
        us_r = sorted(t_row)[iters // 2]
        entry = {
            "K": K,
            "us_prepare_blocked": us_b,
            "us_prepare_rowgather": us_r,
            "speedup": us_r / us_b,
            "bitwise": bitwise,
        }
        record["streaming_bootstrap"].append(entry)
        rows.append((
            f"proximity_scale/bootstrap_prepare_K{K}",
            us_b,
            f"rowgather={us_r:.0f}us speedup={us_r / us_b:.1f}x "
            f"bitwise={bitwise}",
        ))
    return ok


# --------------------------------------------------------------------------
# Serving: the membership-as-a-service read path (repro.serving).
# --------------------------------------------------------------------------

SERVING_KS = (2048, 8192)
SERVING_BATCHES = (1, 16, 128)
SERVING_POOL = 512  # query pool size, rotated through by the timing loops


def _serving_parity(server, engine, queries):
    """Batched served labels vs one-by-one engine.admit on throwaway forks."""
    from repro.serving import admit_oracle

    res = server.assign(queries)
    ok = True
    for i in range(int(queries.shape[0])):
        lbl, is_new = admit_oracle(engine, queries[i])
        if is_new:
            ok &= bool(res.new_cluster[i]) and int(res.labels[i]) == -1
        else:
            ok &= (not bool(res.new_cluster[i])) and int(res.labels[i]) == lbl
    return ok, res


def _serving_rows(record, rows, Ks=SERVING_KS, batch_sizes=SERVING_BATCHES,
                  quick=False):
    """Assignment-serving latency/QPS sweep + the bitwise parity gate.

    Per K: clustered signatures (64 latent bases, the streaming regime),
    beta from the 5% off-diagonal quantile, C from the fitted dendrogram.
    Queries rotate through a pool drawn from the same bases.  The parity
    gate admits a query subset one-by-one on engine forks and demands the
    batched served labels match bitwise (new-cluster outcomes included).
    """
    import time as _time

    from repro.core.engine import ClusterEngine, EngineConfig
    from repro.serving import AssignmentServer

    record["serving"] = {
        "representative": "medoid",
        "rows": [],
        "parity": [],
        "batch_speedup_p99": [],
    }
    ok = True
    iters_by_B = {1: 32, 16: 16, 128: 8} if quick else {1: 256, 16: 64, 128: 24}
    for K in Ks:
        n_par = 48 if K <= 2048 else 12  # per-query oracle admits are O(K)
        U_all = _clustered_signatures(K + SERVING_POOL, n_bases=64)
        U_seen, pool = U_all[:K], U_all[K:]
        A = np.asarray(proximity_matrix(U_seen, "eq3", backend="jnp_blocked"))
        beta = float(np.quantile(A[A > 0], 0.05))
        cfg = EngineConfig(beta=beta, measure="eq3")
        engine = ClusterEngine.from_proximity(A, U_seen, cfg)
        engine.warm_cache()
        server = AssignmentServer(
            engine, representative="medoid", batch_max=max(batch_sizes)
        )
        C = int(server.snapshot.rep_labels.size)
        per_query_p99 = {}
        for B in batch_sizes:
            iters = iters_by_B.get(B, 16)
            server.assign(pool[:B])  # warmup: compile this pad bucket
            ts = []
            for i in range(iters):
                lo = (i * B) % (SERVING_POOL - B + 1)
                q = pool[lo : lo + B]
                t0 = _time.perf_counter()
                server.assign(q)
                ts.append((_time.perf_counter() - t0) * 1e6)
            ts.sort()
            p50 = ts[len(ts) // 2]
            p99 = ts[min(len(ts) - 1, int(len(ts) * 0.99))]
            qps = B * len(ts) / (sum(ts) / 1e6)
            per_query_p99[B] = p99 / B
            entry = {
                "K": K, "C": C, "B": B, "beta": beta,
                "p50_us": p50, "p99_us": p99,
                "p50_per_query_us": p50 / B, "p99_per_query_us": p99 / B,
                "qps": qps,
            }
            record["serving"]["rows"].append(entry)
            rows.append((
                f"proximity_scale/serving_K{K}_B{B}",
                p50,
                f"p99={p99:.0f}us qps={qps:.0f} C={C}",
            ))
        b_lo, b_hi = batch_sizes[0], batch_sizes[-1]
        speedup = per_query_p99[b_lo] / per_query_p99[b_hi]
        record["serving"]["batch_speedup_p99"].append({
            "K": K, "B_from": b_lo, "B_to": b_hi,
            "per_query_speedup": speedup,
        })
        par, _ = _serving_parity(server, engine, pool[:n_par])
        ok &= par
        record["serving"]["parity"].append({
            "K": K, "C": C, "queries": n_par, "bitwise": par,
        })
        rows.append((
            f"proximity_scale/serving_K{K}_parity",
            None,
            f"bitwise={par} batch_p99_speedup_B{b_lo}->B{b_hi}={speedup:.1f}x",
        ))
    record["serving"]["assignment_parity_ok"] = ok
    return ok


def _serving_parity_rows(record, rows):
    """Serving smoke (--quick CI gate): batched served assignments equal
    one-by-one ``engine.admit`` labels bitwise, and an epoch swap leaves a
    held pre-drain snapshot answering unchanged."""
    from repro.core.engine import ClusterEngine, EngineConfig
    from repro.serving import AssignmentServer

    K, Q = 256, 24
    U_all = _clustered_signatures(K + Q + 4, n_bases=64)
    A = np.asarray(proximity_matrix(U_all[:K], "eq3", backend="jnp_blocked"))
    beta = float(np.quantile(A[A > 0], 0.05))
    engine = ClusterEngine.from_proximity(
        A, U_all[:K], EngineConfig(beta=beta, measure="eq3")
    )
    # batch_max below Q: the gate also covers the chunked multi-dispatch path
    server = AssignmentServer(engine, representative="medoid", batch_max=16)
    queries = U_all[K : K + Q]
    par, res = _serving_parity(server, engine, queries)

    snap0 = server.snapshot
    for i in range(4):
        server.submit_join(U_all[K + Q + i])
    server.drain()
    iso = server.snapshot.epoch == snap0.epoch + 1
    res0 = server.assign(queries[:4], snapshot=snap0)
    iso &= bool(np.array_equal(res0.labels, res.labels[:4]))
    ok = par and iso
    record["serving_parity"] = {
        "K": K, "queries": Q,
        "assignment_bitwise": par, "epoch_isolation": iso,
    }
    rows.append((
        "proximity_scale/serving_parity", None,
        f"bitwise={par} epoch_iso={iso}",
    ))
    return ok


def _queue_parity_rows(record, rows):
    """Async churn queue smoke: draining a ChurnQueue (policy-sized
    admission batches) reproduces the labels of the equivalent synchronous
    schedule bitwise, and the drain policy fits from a seeded probe."""
    import numpy as _np

    from repro.core.engine import ClusterEngine, EngineConfig
    from repro.fl import ChurnEvent, ChurnQueue, DrainPolicy

    K = 64
    U_all = _clustered_signatures(K + 12, n_bases=8, seed=3)
    U_seen = U_all[:K]
    joins = [U_all[K + i] for i in range(12)]
    cfg = EngineConfig(beta=0.0, measure="eq3")
    A = np.asarray(proximity_matrix(U_seen, cfg.measure, backend="jnp_blocked"))
    cfg = EngineConfig(beta=float(np.quantile(A[A > 0], 0.1)), measure="eq3")
    schedule = [
        ChurnEvent(rnd=1, join=joins[:3], leave=[5]),
        ChurnEvent(rnd=2, join=joins[3:8], leave=[0, 11]),
        ChurnEvent(rnd=3, join=joins[8:], leave=[2]),
    ]

    sync = ClusterEngine.from_proximity(A, U_seen, cfg)
    for ev in schedule:
        if ev.leave:
            sync.depart(sync.ids[_np.asarray(ev.leave)])
        if ev.join:
            sync.admit(jnp.stack(ev.join))

    policy = DrainPolicy.measure(U_seen, seed=0, reps=1, probe_batch=4,
                                 measure=cfg.measure)
    # exercise a batch split different from the event grouping
    policy = DrainPolicy(policy.dispatch_cost_us, policy.per_newcomer_us,
                         target_overhead=policy.target_overhead, max_batch=2)
    queued = ClusterEngine.from_proximity(A, U_seen, cfg)
    q = ChurnQueue(signature_fn=lambda u: u, policy=policy)
    for ev in schedule:
        q.enqueue_event(ev)
    batches = q.drain()
    for batch in batches:
        if batch.leave:
            gone, _ = batch.resolve_leaves(queued.ids)
            queued.depart(_np.asarray(gone))
        if batch.join:
            queued.admit(batch.signatures)

    ok = bool(
        _np.array_equal(sync.labels, queued.labels)
        and _np.array_equal(sync.canonical_labels, queued.canonical_labels)
    )
    record["churn_queue"] = {
        "K": K,
        "events": len(schedule),
        "drained_batches": len(batches),
        "policy": {
            "dispatch_cost_us": policy.dispatch_cost_us,
            "per_newcomer_us": policy.per_newcomer_us,
            "batch_size": policy.batch_size,
        },
        "labels_bitwise": ok,
    }
    rows.append((
        "proximity_scale/churn_queue_parity",
        None,
        f"batches={len(batches)} bitwise={ok}",
    ))
    return ok


def run(quick: bool = True, parity_only: bool = False):
    rows = []
    record = {
        "jax_backend": jax.default_backend(),
        "block_size": _DEFAULT_BLOCK,
        "parity_tol_deg": PARITY_TOL_DEG,
        "eq2_solver_by_backend": _EQ2_SOLVER,
        "sweep": [],
        "parity": [],
    }

    _parity_rows(record, rows)

    if not parity_only:
        import time as _time

        for K in KS:
            U = _signatures(K)
            ref = None
            if K <= DENSE_MAX_K:
                ref = {
                    m: np.asarray(proximity_matrix(U, m, backend="jnp"))
                    for m in MEASURES
                }
            # Interleaved timing: one round-robin pass over every
            # (measure, backend) combo per iteration, so transient load on
            # shared CI boxes hits all combos alike and derived ratios
            # (e.g. eq2 vs eq3 on the same backend) stay meaningful.
            iters = 1 if (quick and K >= 2048) else (5 if K >= 2048 else 3)
            combos = [
                (m, b) for m in MEASURES for b in _backends_for(K)
            ]
            fns = {}
            for measure, backend in combos:
                fn = lambda measure=measure, backend=backend: proximity_matrix(
                    U, measure, backend=backend, block_size=BLOCK_SIZE
                )
                jax.block_until_ready(fn())  # warmup/compile
                fns[(measure, backend)] = fn
            samples = {c: [] for c in combos}
            for _ in range(iters):
                for c in combos:
                    t0 = _time.perf_counter()
                    jax.block_until_ready(fns[c]())
                    samples[c].append((_time.perf_counter() - t0) * 1e6)
            for measure, backend in combos:
                us = sorted(samples[(measure, backend)])[iters // 2]
                err = (
                    float(
                        np.abs(
                            np.asarray(fns[(measure, backend)]()) - ref[measure]
                        ).max()
                    )
                    if ref is not None
                    else None
                )
                entry = {
                    "K": K,
                    "measure": measure,
                    "backend": backend,
                    "eq2_solver": (
                        _EQ2_SOLVER[backend] if measure == "eq2" else None
                    ),
                    "us_per_call": us,
                    "max_err_vs_ref_deg": err,
                }
                record["sweep"].append(entry)
                rows.append((
                    f"proximity_scale/K{K}_{measure}_{backend}",
                    us,
                    "" if err is None else f"maxerr={err:.2e}deg",
                ))

    # sharded engine under a forced multi-device host platform; in the quick
    # smoke a small K keeps the subprocess cheap while still exercising the
    # 4-way row-strip split + label identity.
    sharded_K = PARITY_K if parity_only else SHARDED_K
    sharded = _sharded_multi_device(sharded_K, SHARDED_DEVICES)
    record["sharded_multi_device"] = sharded
    for r in sharded["rows"]:
        rows.append((
            f"proximity_scale/sharded{SHARDED_DEVICES}dev_K{sharded_K}_{r['measure']}",
            r["us_sharded"],
            f"labels_identical={r['hc_labels_identical']}",
        ))

    # streaming admission: engine vs re-cluster baseline (cheap single-shot
    # parity smoke in --quick; latency sweep at K in {512, 2048} otherwise)
    if parity_only:
        streaming_ok = _streaming_rows(record, rows, Ks=(PARITY_K,), Bs=(16,), iters=1)
    else:
        streaming_ok = _streaming_rows(
            record, rows, Ks=(512, 2048), Bs=(16, 64), iters=1 if quick else 3
        )

    queue_ok = _queue_parity_rows(record, rows)

    # serving read path: the cheap parity/isolation smoke always runs; the
    # full latency/QPS sweep at K in {2048, 8192} only outside --quick
    serving_ok = _serving_parity_rows(record, rows)
    if not parity_only:
        serving_ok &= _serving_rows(record, rows, quick=quick)

    family_ok = _family_parity_rows(record, rows)
    bootstrap_ok = _streaming_bootstrap_rows(record, rows, quick=quick or parity_only)

    memory_ok = _memory_parity_rows(record, rows)
    if not parity_only:
        # full-scale tier sweep (peak RSS + admission time per policy),
        # subprocess-isolated; --quick keeps only the in-process gate above
        memory_ok &= _memory_rows(record, rows)

    move_ok = _move_parity_rows(record, rows)
    if not parity_only:
        # fused-move speedup + CRC parity at K=2048 (full sweep only)
        move_ok &= _drift_churn_rows(record, rows, iters=3 if quick else 5)

    parity_ok = all(
        e["max_err_vs_ref_deg"] <= PARITY_TOL_DEG for e in record["parity"]
    ) and all(
        r["hc_labels_identical"] and r["max_dev_deg"] <= PARITY_TOL_DEG
        for r in sharded["rows"]
    ) and (streaming_ok and queue_ok and serving_ok and memory_ok
           and family_ok and bootstrap_ok and move_ok)
    record["parity_ok"] = parity_ok
    rows.append((
        f"proximity_scale/parity_K{PARITY_K}_ok", None, str(parity_ok)
    ))
    for e in record["parity"]:
        assert e["max_err_vs_ref_deg"] <= PARITY_TOL_DEG, (
            f"{e['backend']}/{e['measure']}/{e['eq2_solver']} diverged from "
            f"the einsum reference at K={PARITY_K}: "
            f"{e['max_err_vs_ref_deg']:.3e} deg"
        )
    assert streaming_ok, (
        "cluster-engine admission diverged from the full re-cluster baseline"
    )
    assert queue_ok, (
        "ChurnQueue drain diverged from the synchronous churn schedule"
    )
    assert serving_ok, (
        "serving assignment parity failed: batched served labels diverged "
        "from one-by-one engine.admit assignment (or epoch isolation broke)"
    )
    assert memory_ok, (
        "memory-policy tiers diverged from the dense tier's labels"
    )
    assert move_ok, (
        "fused move diverged from sequential depart+admit / the re-cluster "
        "oracle, or missed the drift_churn speedup gate"
    )
    assert family_ok, (
        "signature-family gate failed: svd family diverged from the "
        "pre-refactor inline path, or a family run produced no clustering"
    )
    assert bootstrap_ok, (
        "cache-blocked condensed bootstrap diverged from the row-gather path"
    )
    assert parity_ok, "sharded engine diverged from the blocked backend"

    out = ROOT / "BENCH_proximity_scale.json"
    if not parity_only:
        out.write_text(json.dumps(record, indent=2))
        rows.append(("proximity_scale/json", None, str(out)))
    elif out.exists():
        # --quick reruns only the cheap gates; merge their sections into the
        # existing full-sweep json instead of discarding the expensive
        # measurements (documented in docs/BENCHMARKS.md)
        existing = json.loads(out.read_text())
        existing["family_parity"] = record["family_parity"]
        existing["streaming_bootstrap"] = record["streaming_bootstrap"]
        existing["serving_parity"] = record["serving_parity"]
        existing["move_parity"] = record["move_parity"]
        out.write_text(json.dumps(existing, indent=2))
        rows.append(("proximity_scale/json_merged", None, str(out)))
    return rows


def run_serving_only(quick: bool = False):
    """--serving mode: run just the serving sweep (plus its parity smoke)
    and read-modify-write the ``serving`` / ``serving_parity`` sections
    into the existing BENCH json — refreshing the serving numbers without
    re-running the multi-minute full sweep."""
    rows = []
    record = {}
    ok = _serving_parity_rows(record, rows)
    ok &= _serving_rows(record, rows, quick=quick)
    assert ok, (
        "serving assignment parity failed: batched served labels diverged "
        "from one-by-one engine.admit assignment (or epoch isolation broke)"
    )
    out = ROOT / "BENCH_proximity_scale.json"
    existing = json.loads(out.read_text()) if out.exists() else {}
    existing["serving"] = record["serving"]
    existing["serving_parity"] = record["serving_parity"]
    out.write_text(json.dumps(existing, indent=2))
    rows.append(("proximity_scale/json_merged", None, str(out)))
    return rows


if __name__ == "__main__":
    import argparse

    from benchmarks.common import emit

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="3 timing iters at every K")
    ap.add_argument(
        "--quick", action="store_true",
        help="parity smoke only: no timing sweep, no json rewrite",
    )
    ap.add_argument(
        "--serving", action="store_true",
        help="serving sweep only; merges its sections into the existing json",
    )
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.serving:
        emit(run_serving_only(quick=not args.full))
    else:
        emit(run(quick=not args.full, parity_only=args.quick))
