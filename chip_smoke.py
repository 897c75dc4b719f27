"""Chip smoke run: the clustered-FL server's main path, end to end, on a TPU.

    python chip_smoke.py [--seed 0]          # one chip, phases (a)-(e)
    python chip_smoke.py --four-chips        # only the sharded path, 4 chips

Default phases, in order, on one chip and in one process:

(a) signatures — ``compute_signatures`` (``svd`` family, exact) for K=4,096
    FEMNIST-shaped clients: n=784 features, p=5, per-client sample counts
    drawn log-normal with LEAF FEMNIST's mean and spread (226.8 +- 88.9
    samples per writer), so several shape buckets compile;
(b) one-shot bootstrap — ``ClusterEngine.from_signatures`` with
    ``backend="auto"`` for eq3 and eq2: ``auto`` must resolve to the Pallas
    kernel, compiled (``tpu_custom_call``), within 1e-3 degrees of a float64
    NumPy reference on a row sample, with HC labels bitwise equal to the
    ``jnp_blocked`` backend's; then the kernel alone at K=16,384;
(c) admission — one B=64 join batch through ``engine.admit`` and one
    ``move`` refresh, labels equal to a full re-cluster of the store;
(d) serving — ``AssignmentServer`` batches of 32 queries, each label equal
    to ``admit_oracle``'s;
(e) federated rounds — two PACFL rounds of the paper's MIX-4 setting through
    ``run_federation`` with LeNet-5 at 32x32x3, finite losses and accuracy.

``--four-chips`` runs only a K=16,384 eq3 and eq2 bootstrap with
``backend="jnp_sharded"`` over all four devices, compared with the
one-device ``pallas`` result: within 1e-3 degrees and bitwise HC labels,
with the sharded output split over all four devices.

All data is generated from ``--seed``; nothing is downloaded.  Any failed
check raises, so the script exits non-zero; it also refuses to run where
JAX finds no TPU.  The last stdout line is one JSON object naming the
device.  Times printed are one unwarmed run, compilation included: they
are not measurements.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.angles import proximity_matrix, resolve_backend  # noqa: E402
from repro.core.engine import ClusterEngine, EngineConfig  # noqa: E402
from repro.core.hc import hierarchical_clustering  # noqa: E402
from repro.core.pacfl import PACFLConfig, compute_signatures  # noqa: E402
from repro.fl import FLConfig, run_federation  # noqa: E402
from repro.fl.client import ce_loss  # noqa: E402
from repro.kernels.proximity import proximity  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.fl_train import build_clients, build_model  # noqa: E402
from repro.serving import AssignmentServer, admit_oracle  # noqa: E402

TOL_DEG = 1e-3          # backend parity contract, degrees
P = 5                   # principal vectors per client
N_FEATURES = 784        # FEMNIST: 28x28 pixels
N_STYLES = 16           # latent writer styles: the ground-truth clusters
SPREAD = 0.005          # per-entry perturbation of a client's style basis
NOISE = 0.002           # per-entry sensor noise on raw samples
SPECTRUM = 0.6 ** np.arange(P)
# LEAF FEMNIST (Caldas et al., arXiv:1812.01097): 226.83 +- 88.94 samples
# per writer, matched by a log-normal.
_SIGMA = float(np.sqrt(np.log1p((88.94 / 226.83) ** 2)))
_MU = float(np.log(226.83) - _SIGMA**2 / 2)
# HC thresholds in the gap between intra-style (~12 / ~65 degrees) and
# inter-style (>= ~75 / ~430 degrees) distances of this population.
BETA = {"eq2": 45.0, "eq3": 240.0}


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Phase sizes; the defaults are the chip run's."""

    k_boot: int = 4096          # (a)-(d) clients
    k_kernel: int = 16384       # (b) kernel alone, and --four-chips
    n_features: int = N_FEATURES
    ref_rows: int = 64          # float64 reference row sample
    joins: int = 64             # (c) admission batch
    movers: int = 8             # (c) refreshed clients
    query_batches: int = 3      # (d)
    query_batch: int = 32
    fl_clients: int = 40        # (e) MIX-4 clients
    fl_dim: int = 3072          # (e) 32x32x3, the paper's CIFAR width
    fl_train: int = 3000


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _stamp(t0: float) -> str:
    return f"[{time.perf_counter() - t0:.1f} s, one unwarmed run incl. compile, not a measurement]"


def _canon(labels: np.ndarray) -> np.ndarray:
    """Relabel a partition by order of first appearance."""
    _, first, inv = np.unique(labels, return_index=True, return_inverse=True)
    return np.argsort(np.argsort(first))[inv]


class Population:
    """FEMNIST-shaped clients around ``N_STYLES`` latent 5-dim subspaces."""

    def __init__(self, seed: int, n_features: int):
        self.rng = np.random.default_rng(seed)
        self.n = n_features
        self.styles = np.linalg.qr(
            self.rng.standard_normal((N_STYLES, n_features, P))
        )[0]

    def bases(self, style: np.ndarray) -> np.ndarray:
        """Orthonormal (K, n, p) bases perturbed around the given styles."""
        X = self.styles[style] + SPREAD * self.rng.standard_normal(
            (style.size, self.n, P)
        )
        return np.linalg.qr(X)[0].astype(np.float32)

    def far(self, k: int) -> np.ndarray:
        """Random subspaces, far from every style."""
        return np.linalg.qr(self.rng.standard_normal((k, self.n, P)))[0].astype(
            np.float32
        )

    def raw_clients(self, style: np.ndarray) -> list[np.ndarray]:
        """Per-client (n, M_k) data matrices, samples as columns."""
        counts = np.maximum(
            np.rint(self.rng.lognormal(_MU, _SIGMA, style.size)), 2 * P
        ).astype(int)
        out = []
        for B, m in zip(self.bases(style), counts):
            coeffs = self.rng.standard_normal((P, m), dtype=np.float32)
            noise = self.rng.standard_normal((self.n, m), dtype=np.float32)
            out.append((B * SPECTRUM.astype(np.float32)) @ coeffs + NOISE * noise)
        return out


def reference_rows(U64: np.ndarray, rows: np.ndarray, measure: str) -> np.ndarray:
    """Float64 NumPy proximity rows ``A[rows, :]`` in degrees."""
    Ur = U64[rows]
    if measure == "eq3":
        cos = np.stack([Ur[:, :, r] @ U64[:, :, r].T for r in range(P)], -1)
        ref = np.degrees(np.arccos(np.clip(np.abs(cos), 0.0, 1.0))).sum(-1)
    else:
        G = np.tensordot(Ur, U64, axes=([1], [1])).transpose(0, 2, 1, 3)
        smax = np.linalg.svd(G, compute_uv=False)[..., 0]
        ref = np.degrees(np.arccos(np.clip(smax, 0.0, 1.0)))
    ref[np.arange(rows.size), rows] = 0.0
    return ref


def max_dev_rows(A_rows: np.ndarray, U: np.ndarray, rows: np.ndarray, measure: str) -> float:
    ref = reference_rows(np.asarray(U, np.float64), rows, measure)
    return float(np.abs(np.asarray(A_rows, np.float64) - ref).max())


def oracle_labels(engine: ClusterEngine) -> np.ndarray:
    """Full re-cluster of the engine's distance store."""
    cfg = engine.config
    return hierarchical_clustering(
        engine.dense(np.float64), beta=cfg.beta, linkage=cfg.linkage
    )


# --- phases ------------------------------------------------------------------


def phase_signatures(pop: Population, sz: Sizes):
    t0 = time.perf_counter()
    style = np.arange(sz.k_boot) % N_STYLES
    clients = pop.raw_clients(style)
    cfg = PACFLConfig(p=P, svd_method="exact")
    U = compute_signatures(clients, cfg)
    U.block_until_ready()
    Un = np.asarray(U, np.float64)
    check(U.shape == (sz.k_boot, sz.n_features, P), f"signature shape {U.shape}")
    check(bool(np.isfinite(Un).all()), "non-finite signature")
    gram_err = float(
        np.abs(np.einsum("knp,knq->kpq", Un, Un) - np.eye(P)).max()
    )
    check(gram_err < 1e-4, f"signatures not orthonormal: {gram_err}")
    # Largest principal angle to a float64 SVD's subspace on a few clients,
    # from its sine (the arccos of a cosine near 1 would floor at ~0.03 deg).
    worst = 0.0
    for k in pop.rng.choice(sz.k_boot, 8, replace=False):
        u64 = np.linalg.svd(clients[k].astype(np.float64), full_matrices=False)[0][:, :P]
        resid = Un[k] - u64 @ (u64.T @ Un[k])
        sin = min(float(np.linalg.norm(resid, 2)), 1.0)
        worst = max(worst, float(np.degrees(np.arcsin(sin))))
    check(worst < 1e-2, f"signature subspace off float64 SVD by {worst} deg")
    buckets = sorted({int(2 ** np.ceil(np.log2(max(c.shape[1], 16)))) for c in clients})
    print(
        f"(a) signatures: K={sz.k_boot} n={sz.n_features} p={P}, sample counts "
        f"{min(c.shape[1] for c in clients)}-{max(c.shape[1] for c in clients)} "
        f"in buckets {buckets}; orthonormal to {gram_err:.1e}, largest angle to "
        f"float64 SVD {worst:.2e} deg {_stamp(t0)}"
    )
    return U, style


def phase_bootstrap(U, style: np.ndarray, pop: Population, sz: Sizes) -> dict:
    on_tpu = jax.devices()[0].platform == "tpu"
    K = int(U.shape[0])
    rows = np.sort(pop.rng.choice(K, sz.ref_rows, replace=False))
    Un = np.asarray(U)
    engines = {}
    for measure in ("eq3", "eq2"):
        t0 = time.perf_counter()
        resolved = resolve_backend("auto", K)
        hlo = jax.jit(lambda u: proximity_matrix(u, measure)).lower(U).as_text()
        kernel = "tpu_custom_call" in hlo
        if on_tpu:
            check(resolved == "pallas", f"auto resolved to {resolved}")
            check(kernel, "compiled proximity call has no tpu_custom_call")
        cfg = EngineConfig(beta=BETA[measure], measure=measure)
        eng = ClusterEngine.from_signatures(U, cfg)
        A = eng.dense()
        dev = max_dev_rows(A[rows], Un, rows, measure)
        check(dev <= TOL_DEG, f"{measure} {resolved}: {dev} deg off float64")
        blocked = ClusterEngine.from_signatures(
            U, dataclasses.replace(cfg, backend="jnp_blocked")
        )
        dev_b = max_dev_rows(blocked.dense()[rows], Un, rows, measure)
        check(dev_b <= TOL_DEG, f"{measure} jnp_blocked: {dev_b} deg off float64")
        check(
            np.array_equal(eng.labels, blocked.labels),
            f"{measure}: {resolved} and jnp_blocked HC labels differ",
        )
        check(
            np.array_equal(_canon(eng.labels), _canon(style)),
            f"{measure}: {eng.n_clusters} clusters do not recover the "
            f"{N_STYLES} styles",
        )
        print(
            f"(b) bootstrap {measure}: auto -> {resolved} (tpu_custom_call: "
            f"{kernel}); max dev vs float64 on {rows.size} rows: {resolved} "
            f"{dev:.2e} deg, jnp_blocked {dev_b:.2e} deg; HC labels bitwise "
            f"equal, {eng.n_clusters} clusters = styles {_stamp(t0)}"
        )
        engines[measure] = eng

    style_k = np.arange(sz.k_kernel) % N_STYLES
    Uk = pop.bases(style_k)
    rows_k = np.sort(pop.rng.choice(sz.k_kernel, sz.ref_rows // 2, replace=False))
    for measure in ("eq3", "eq2"):
        t0 = time.perf_counter()
        A = proximity(jnp.asarray(Uk), measure=measure)
        check(A.shape == (sz.k_kernel, sz.k_kernel), f"kernel output {A.shape}")
        A_rows = np.asarray(A[rows_k])
        finite = bool(jnp.isfinite(A).all())
        del A
        check(finite, f"{measure}: non-finite kernel output")
        dev = max_dev_rows(A_rows, Uk, rows_k, measure)
        check(dev <= TOL_DEG, f"kernel {measure} K={sz.k_kernel}: {dev} deg off")
        print(
            f"(b) kernel alone {measure}: K={sz.k_kernel} -> ({sz.k_kernel}, "
            f"{sz.k_kernel}) f32, finite, max dev vs float64 on {rows_k.size} "
            f"rows {dev:.2e} deg {_stamp(t0)}"
        )
    return engines


def phase_admission(eng: ClusterEngine, pop: Population, sz: Sizes) -> None:
    t0 = time.perf_counter()
    measure = eng.config.measure
    join_style = pop.rng.integers(0, N_STYLES, sz.joins - 2)
    joins = np.concatenate([pop.bases(join_style), pop.far(2)])
    res = eng.admit(jnp.asarray(joins))
    check(
        np.array_equal(_canon(res.canonical), _canon(oracle_labels(eng))),
        f"{measure}: admission labels differ from a full re-cluster",
    )
    check(int(res.new_cluster.sum()) == 2, f"{measure}: far joins {res.new_cluster.sum()}")
    ids = pop.rng.choice(eng.ids[: sz.k_boot], sz.movers, replace=False)
    target = (ids + 1) % N_STYLES            # client id k started in style k % 16
    mv = eng.move(ids, jnp.asarray(pop.bases(target)))
    check(
        np.array_equal(_canon(mv.canonical), _canon(oracle_labels(eng))),
        f"{measure}: move labels differ from a full re-cluster",
    )
    check(bool(mv.changed.all()), f"{measure}: not every mover changed cluster")
    print(
        f"(c) admission {measure}: B={sz.joins} joins ({int(res.new_cluster.sum())} "
        f"new clusters) and a {sz.movers}-client move, labels = full re-cluster "
        f"at K={eng.n_clients} {_stamp(t0)}"
    )


def phase_serving(eng: ClusterEngine, pop: Population, sz: Sizes) -> None:
    t0 = time.perf_counter()
    server = AssignmentServer(eng, batch_max=sz.query_batch)
    checked = new = 0
    for _ in range(sz.query_batches):
        q = np.concatenate([
            pop.bases(pop.rng.integers(0, N_STYLES, sz.query_batch - 1)),
            pop.far(1),
        ])
        res = server.assign(jnp.asarray(q))
        for i in range(sz.query_batch):
            label, is_new = admit_oracle(eng, q[i])
            check(bool(res.new_cluster[i]) == is_new, f"query {i}: new-cluster flag")
            if not is_new:
                check(int(res.labels[i]) == label, f"query {i}: label")
            checked += 1
            new += int(is_new)
    print(
        f"(d) serving {eng.config.measure}: {sz.query_batches} batches of "
        f"{sz.query_batch}, {checked} answers = admit_oracle ({new} new "
        f"clusters) {_stamp(t0)}"
    )


def phase_federation(seed: int, sz: Sizes) -> None:
    t0 = time.perf_counter()
    clients, n_classes = build_clients("mix4", sz.fl_clients, sz.fl_dim, sz.fl_train, seed)
    init_fn, apply_fn = build_model("lenet5", sz.fl_dim, n_classes)
    cfg = FLConfig(
        rounds=2, sample_frac=0.1, local_epochs=3, batch_size=20, lr=0.05,
        pacfl=PACFLConfig(p=3, beta=50.0, measure="eq2"),
    )
    res = run_federation("pacfl", clients, apply_fn, init_fn, cfg, seed=seed)
    strat = res.strategy_obj
    params = jax.tree.map(lambda l: l[strat.labels], strat.cluster_params)
    data = strat.data
    losses = np.asarray(jax.vmap(lambda p, x, y: ce_loss(apply_fn, p, x, y))(
        params, jnp.asarray(data.x_test), jnp.asarray(data.y_test)
    ))
    accs = res.final_accs
    check(bool(np.isfinite(losses).all()), "non-finite federation loss")
    check(bool(np.isfinite(accs).all()), "non-finite federation accuracy")
    print(
        f"(e) federation: 2 PACFL rounds, MIX-4, {sz.fl_clients} clients, LeNet-5 "
        f"at {sz.fl_dim} features, {strat.clustering.n_clusters} clusters; test "
        f"loss {losses.mean():.3f}, accuracy {accs.mean():.3f}, all finite "
        f"{_stamp(t0)}"
    )


def run_default(seed: int, sz: Sizes = Sizes()) -> None:
    pop = Population(seed, sz.n_features)
    U, style = phase_signatures(pop, sz)
    engines = phase_bootstrap(U, style, pop, sz)
    for eng in engines.values():
        phase_admission(eng, pop, sz)
    for eng in engines.values():
        phase_serving(eng, pop, sz)
    phase_federation(seed, sz)


def run_four_chips(seed: int, sz: Sizes = Sizes()) -> None:
    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, found {len(devices)}")
    pop = Population(seed, sz.n_features)
    style = np.arange(sz.k_kernel) % N_STYLES
    Un = pop.bases(style)
    U = jnp.asarray(Un)
    rows = np.sort(pop.rng.choice(sz.k_kernel, sz.ref_rows // 2, replace=False))
    for measure in ("eq3", "eq2"):
        t0 = time.perf_counter()
        cfg = EngineConfig(beta=BETA[measure], measure=measure)
        A_dev = proximity_matrix(U, measure, backend="jnp_sharded")
        shards = A_dev.addressable_shards
        check(
            len(A_dev.sharding.device_set) == 4 and len(shards) == 4,
            f"sharded output on {len(A_dev.sharding.device_set)} devices",
        )
        check(
            all(s.data.shape[0] * 4 == sz.k_kernel for s in shards),
            f"sharded output not split by rows: {[s.data.shape for s in shards]}",
        )
        A_s = np.asarray(A_dev)
        del A_dev
        one = ClusterEngine.from_signatures(U, dataclasses.replace(cfg, backend="pallas"))
        A_p = one.dense()
        dev = float(np.abs(A_s - A_p).max())
        check(dev <= TOL_DEG, f"{measure}: sharded vs pallas {dev} deg")
        dev_ref = max_dev_rows(A_s[rows], Un, rows, measure)
        check(dev_ref <= TOL_DEG, f"{measure}: sharded {dev_ref} deg off float64")
        del A_s, A_p
        sharded = ClusterEngine.from_signatures(
            U, dataclasses.replace(cfg, backend="jnp_sharded")
        )
        check(
            np.array_equal(sharded.labels, one.labels),
            f"{measure}: sharded and pallas HC labels differ",
        )
        check(
            np.array_equal(_canon(one.labels), _canon(style)),
            f"{measure}: clusters do not recover the styles",
        )
        print(
            f"four chips {measure}: K={sz.k_kernel} jnp_sharded rows split over "
            f"{len(shards)} devices; max dev vs one-device pallas {dev:.2e} deg, "
            f"vs float64 {dev_ref:.2e} deg; HC labels bitwise equal "
            f"({one.n_clusters} clusters) {_stamp(t0)}"
        )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the jnp_sharded bootstrap over four chips vs pallas",
    )
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {device.platform}); refusing to run",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    if args.four_chips:
        run_four_chips(args.seed)
    else:
        run_default(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
