"""Seeded MIX-4 clients, the benchmark's own generator.

PACFL's MIX-4 federation (Vahidian et al., AAAI 2023): every client holds
samples of exactly one of four datasets (CIFAR-10, SVHN, FMNIST, USPS), with
labels offset per dataset so that the union is one 40-class task.  Nothing is
downloaded, so each dataset is a synthetic stand-in with the subspace
relations the paper's Table 1 reports: a low-rank basis with a decaying
spectrum, CIFAR-10 and SVHN sharing their dominant directions, FMNIST and
USPS sharing weak ones, the cross pairs unrelated; each class a prototype in
the dataset's subspace, in two super-clusters.

Copied from the repository's ``repro.data.synthetic.make_dataset`` and
``repro.fl.partition.mix_datasets`` (with ``repro.launch.fl_train``'s client
counts), so that a change to the program cannot change the yardstick.  The
draws are those of the originals, from the run's ``--seed``.
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    rank: int = 12                 # intrinsic dimension
    shared_frac: float = 0.0       # share of the basis taken from `shared_with`
    shared_with: str | None = None
    share_tail: bool = False       # share the parent's weak directions only
    n_classes: int = 10
    class_spread: float = 0.55
    super_gap: float = 1.6
    noise: float = 0.06


SPECS = {
    "cifar10s": Spec(),
    "svhns": Spec(shared_frac=0.8, shared_with="cifar10s"),
    "fmnists": Spec(),
    "uspss": Spec(shared_frac=0.3, shared_with="fmnists", share_tail=True),
}


@dataclasses.dataclass
class Clients:
    """The federation's clients, each holding rows of one dataset."""

    x: np.ndarray        # (K, n_max, n_features) float32, zero past n
    y: np.ndarray        # (K, n_max) int64, offset per dataset
    n: np.ndarray        # (K,) samples each client holds
    x_test: np.ndarray   # (K, test, n_features) float32
    y_test: np.ndarray   # (K, test) int64
    names: list[str]     # each client's dataset

    @property
    def n_clients(self) -> int:
        return self.x.shape[0]


def _digest(name: str) -> int:
    return zlib.crc32(name.encode()) % (2**31)


def _orth(rng: np.random.Generator, dim: int, r: int) -> np.ndarray:
    Q, _ = np.linalg.qr(rng.standard_normal((dim, r)))
    return Q.astype(np.float32)


def dataset(name: str, *, n_train: int, n_test: int, dim: int, seed: int):
    """``(x_train, y_train, x_test, y_test, n_classes)`` of one stand-in."""
    bases: dict[str, np.ndarray] = {}

    def basis_for(nm: str) -> np.ndarray:
        if nm in bases:
            return bases[nm]
        sp = SPECS[nm]
        own = _orth(np.random.default_rng([seed, _digest(nm)]), dim, sp.rank)
        if sp.shared_with is not None and sp.shared_frac > 0:
            parent = basis_for(sp.shared_with)
            k = int(round(sp.shared_frac * sp.rank))
            if sp.share_tail:
                mix = np.concatenate([own[:, : sp.rank - k], parent[:, sp.rank - k:]], axis=1)
            else:
                mix = np.concatenate([parent[:, :k], own[:, k:]], axis=1)
            own = np.linalg.qr(mix)[0].astype(np.float32)
        bases[nm] = own
        return own

    sp = SPECS[name]
    B, r = basis_for(name), sp.rank
    spectrum = (0.82 ** np.arange(r)).astype(np.float32)
    rng = np.random.default_rng([seed + 1, _digest(name)])
    centers = rng.standard_normal((2, r)).astype(np.float32)
    centers *= sp.super_gap / np.linalg.norm(centers, axis=1, keepdims=True)
    protos = np.stack([
        centers[c % 2] + sp.class_spread * rng.standard_normal(r).astype(np.float32)
        for c in range(sp.n_classes)
    ])

    def sample(n: int, sub: np.random.Generator):
        y = sub.integers(0, sp.n_classes, size=n)
        latent = (protos[y] + sub.standard_normal((n, r)).astype(np.float32)) * spectrum
        x = latent @ B.T + sp.noise * sub.standard_normal((n, dim)).astype(np.float32)
        return x.astype(np.float32), y.astype(np.int64)

    x_tr, y_tr = sample(n_train, np.random.default_rng([seed + 2, _digest(name)]))
    x_te, y_te = sample(n_test, np.random.default_rng([seed + 3, _digest(name)]))
    return x_tr, y_tr, x_te, y_te, sp.n_classes


def client_counts(cfg: dict) -> list[int]:
    """Clients per dataset: each share of the requested count, rounded."""
    n = int(cfg["n_clients"])
    counts = [max(1, round(n * f)) for f in cfg["dataset_shares"]]
    while sum(counts) > n:
        counts[int(np.argmax(counts))] -= 1
    return counts


def sample_counts(cfg: dict, K: int) -> np.ndarray:
    """Samples per client: ``samples_per_client``, or a list of counts that
    the clients take in turn."""
    sizes = np.atleast_1d(np.asarray(cfg["samples_per_client"], np.int64))
    return sizes[np.arange(K) % sizes.size]


def clients(cfg: dict, seed: int) -> Clients:
    """The configuration's MIX-4 clients for ``seed``."""
    dim = int(cfg["n_features"])
    counts = client_counts(cfg)
    n = sample_counts(cfg, sum(counts))
    n_test = int(cfg["test_per_client"])
    rng = np.random.default_rng(seed)
    xs, ys, xts, yts, names = [], [], [], [], []
    offset = 0
    for name, n_k in zip(cfg["datasets"], counts):
        x_tr, y_tr, x_te, y_te, n_cls = dataset(
            name, n_train=int(cfg["dataset_train"]), n_test=int(cfg["dataset_test"]),
            dim=dim, seed=seed,
        )
        for _ in range(n_k):
            idx = rng.choice(x_tr.shape[0], size=n[len(xs)], replace=False)
            tidx = rng.choice(x_te.shape[0], size=min(n_test, x_te.shape[0]), replace=False)
            xs.append(x_tr[idx])
            ys.append(y_tr[idx] + offset)
            xts.append(x_te[tidx])
            yts.append(y_te[tidx] + offset)
            names.append(name)
        offset += n_cls
    x = np.zeros((n.size, int(n.max()), dim), np.float32)
    y = np.zeros(x.shape[:2], np.int64)
    for k, (xk, yk) in enumerate(zip(xs, ys)):
        x[k, : n[k]], y[k, : n[k]] = xk, yk
    return Clients(x, y, n, np.stack(xts), np.stack(yts), names)
