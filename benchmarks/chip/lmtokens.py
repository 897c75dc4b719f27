"""Seeded token clients for the LM round: domains of Zipf unigrams.

Each of ``n_domains`` domains is a Zipf law (exponent ``zipf_exponent``)
over the vocabulary slice this chip holds, put on the ids by the domain's
own seeded permutation; client ``k`` belongs to domain ``k % n_domains`` and
holds ``sequences_per_client`` sequences of ``seq_len`` ids drawn from it.
Everything comes from the run's ``--seed``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class TokenClients:
    x: np.ndarray          # (K, n, S) int32 ids
    n: np.ndarray          # (K,) sequences per client
    domain: np.ndarray     # (K,) the domain each client draws from

    @property
    def n_clients(self) -> int:
        return self.x.shape[0]


def unigram(cfg: dict) -> np.ndarray:
    """The Zipf law over ranks 1..vocab."""
    p = np.arange(1, int(cfg["vocab"]) + 1, dtype=np.float64) ** -float(cfg["zipf_exponent"])
    return p / p.sum()


def sizes(cfg: dict) -> np.ndarray:
    """Sequences of each client: ``sequences_per_client``, or a list of
    sizes that the clients take in turn."""
    per = cfg["sequences_per_client"]
    per = [per] if isinstance(per, int) else list(per)
    return np.asarray([per[k % len(per)] for k in range(int(cfg["n_clients"]))], np.int64)


def clients(cfg: dict, seed: int) -> TokenClients:
    """Clients with fewer sequences than the largest repeat theirs to fill
    the ``(K, n_max, S)`` array; ``n`` says how many are their own."""
    V, K, D = int(cfg["vocab"]), int(cfg["n_clients"]), int(cfg["n_domains"])
    n, S = sizes(cfg), int(cfg["seq_len"])
    cdf = np.cumsum(unigram(cfg))
    perms = [np.random.default_rng([seed, 1, d]).permutation(V) for d in range(D)]
    rng = np.random.default_rng([seed, 2])
    domain = np.arange(K) % D
    ranks = np.minimum(np.searchsorted(cdf, rng.random((K, n.max(), S))), V - 1)
    x = np.stack([perms[d][r] for d, r in zip(domain, ranks)]).astype(np.int32)
    for k in range(K):
        x[k] = x[k][np.arange(n.max()) % n[k]]
    return TokenClients(x, n, domain)
