"""Frozen reference for :func:`repro.core.hc.merge_forest` on dense input.

The full-width generic merge loop as it stood before the working matrix was
compacted to the live clusters: one (C, C) float64 matrix for the whole run,
dead clusters cleared to inf in their row and column, every vector pass over
the full width.  Kept verbatim as the oracle the parity tests hold the
production loop to, bit for bit.  Nothing in ``src/`` imports it.
"""
from __future__ import annotations

from typing import Optional

import numpy as np


def _lance_williams(di, dj, si, sj, linkage):
    if linkage == "single":
        return np.minimum(di, dj)
    if linkage == "complete":
        return np.maximum(di, dj)
    return (si * di + sj * dj) / (si + sj)


def merge_forest_oracle(
    D: np.ndarray,
    size: np.ndarray,
    members: list[list[int]],
    *,
    beta: Optional[float] = None,
    n_clusters: Optional[int] = None,
    linkage: str = "average",
) -> tuple[np.ndarray, list[list[int]], list[tuple[int, int, float]]]:
    """``(active, members, merges)`` of the full-width loop; ``D``, ``size``
    and ``members`` are consumed."""
    if (beta is None) == (n_clusters is None):
        raise ValueError("specify exactly one of beta / n_clusters")
    K = D.shape[0]
    merges: list[tuple[int, int, float]] = []
    active = np.ones(K, dtype=bool)
    if K == 1:
        return active, members, merges

    remaining = K
    np.fill_diagonal(D, np.inf)
    nn = D.argmin(axis=1)
    nn_dist = D[np.arange(K), nn]

    target = 1 if n_clusters is None else max(int(n_clusters), 1)
    while remaining > target:
        masked = np.where(active, nn_dist, np.inf)
        i = int(np.argmin(masked))
        dmin = float(masked[i])
        if beta is not None and dmin > beta:
            break
        j = int(nn[i])
        if i > j:
            i, j = j, i
        new = _lance_williams(D[i], D[j], size[i], size[j], linkage)
        new[i] = new[j] = np.inf
        D[i, :] = new
        D[:, i] = new
        D[j, :] = np.inf
        D[:, j] = np.inf
        merges.append((min(members[i]), min(members[j]), dmin))
        size[i] += size[j]
        members[i].extend(members[j])
        active[j] = False
        nn_dist[j] = np.inf
        remaining -= 1

        touched = active & ((nn == i) | (nn == j))
        touched[i] = False
        for k in np.where(touched)[0]:
            r = D[k]
            a = int(r.argmin())
            nn[k], nn_dist[k] = a, r[a]
        others = active & ~touched
        others[i] = False
        better = others & ((new < nn_dist) | ((new == nn_dist) & (i < nn)))
        nn[better] = i
        nn_dist[better] = new[better]
        r = D[i]
        a = int(r.argmin())
        nn[i], nn_dist[i] = a, r[a]

    return active, members, merges
