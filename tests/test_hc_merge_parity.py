"""The dense merge loop against the frozen full-width loop, bit for bit.

:func:`repro.core.hc.merge_forest` runs a dense input over the live clusters
only: it leaves dead rows and columns stale and compacts the working matrix
once the live count falls to ``COMPACT_FRACTION`` of its width.  Every case
here checks that the merge script (reps, order and float64 height bits), the
liveness mask and the member lists are exactly what the full-width loop of
``_hc_merge_oracle`` returns, and that compaction engaged or not as the width
says it should.
"""
import numpy as np
import pytest

from _hc_merge_oracle import merge_forest_oracle
from repro.core import hc
from repro.core.hc import cluster_distance_matrix, merge_forest


class _Counts:
    def __init__(self):
        self.counts = {}

    def count(self, key, n):
        self.counts[key] = int(n)


def _clustered(K, seed):
    """Euclidean distances of K points around 12 blobs (float64, zero diag)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(scale=6.0, size=(12, 8))
    X = centers[rng.integers(0, 12, K)] + rng.normal(size=(K, 8))
    return np.sqrt(((X[:, None, :] - X[None, :, :]) ** 2).sum(-1))


def _ties(K, seed):
    """Symmetric small-integer grid: most picks are ties."""
    rng = np.random.default_rng(seed)
    M = np.triu(rng.integers(1, 5, (K, K)).astype(np.float64), 1)
    return M + M.T


def _sparse_ties(K, seed):
    """Distances 1-3 on a sparse set of pairs, 9 elsewhere: a merge can tie
    a row's cached minimum at a lower index, which the neighbor update's
    tie rule has to resolve."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(1, 4, (K, K))
    M = np.where(rng.random((K, K)) < 0.002, vals, 9)
    M = np.triu(M.astype(np.float64), 1)
    return M + M.T


def _forest(K, C, seed):
    """A non-singleton start, as the replay's n_clusters tail builds it:
    C groups of K leaves, ordered by smallest member, with their linkage
    distances and sizes."""
    rng = np.random.default_rng(seed)
    cut = np.sort(rng.choice(np.arange(1, K), C - 1, replace=False))
    groups = np.split(rng.permutation(K), cut)
    groups = sorted((sorted(int(m) for m in g) for g in groups), key=min)
    return _clustered(K, seed), groups


def _first_compaction_merges(K):
    """Merges after which the first compaction runs on a K-wide start."""
    return next(m for m in range(1, K) if K - m <= hc.COMPACT_FRACTION * K)


def _heights(merges):
    return np.array([h for _, _, h in merges], dtype=np.float64).view(np.int64)


# (name, matrix kind, K, stop, linkage, start, compacts)
# stop: ("beta", q) cuts at quantile q of the full run's heights; ("n", z)
# stops at z clusters; ("boundary", 0) cuts exactly at the first compaction.
CASES = [
    ("clustered-beta-avg", "clustered", 700, ("beta", 0.6), "average", "leaves", True),
    ("clustered-beta-single", "clustered", 700, ("beta", 0.6), "single", "leaves", True),
    ("clustered-beta-complete", "clustered", 700, ("beta", 0.6), "complete", "leaves", True),
    ("clustered-n-avg", "clustered", 900, ("n", 12), "average", "leaves", True),
    ("clustered-n-single", "clustered", 900, ("n", 1), "single", "leaves", True),
    ("clustered-n-complete", "clustered", 600, ("n", 40), "complete", "leaves", True),
    ("ties-beta-avg", "ties", 600, ("beta", 0.5), "average", "leaves", True),
    ("ties-beta-single", "ties", 600, ("beta", 0.5), "single", "leaves", True),
    ("ties-n-complete", "ties", 600, ("n", 3), "complete", "leaves", True),
    ("ties-n-avg", "ties", 500, ("n", 1), "average", "leaves", True),
    ("sparse-ties-n-single", "sparse", 500, ("n", 3), "single", "leaves", True),
    ("sparse-ties-beta-avg", "sparse", 500, ("beta", 0.9), "average", "leaves", True),
    ("forest-n-avg", "clustered", 1000, ("n", 5), "average", "forest", True),
    ("forest-beta-complete", "clustered", 1000, ("beta", 0.7), "complete", "forest", True),
    ("forest-n-single", "ties", 900, ("n", 9), "single", "forest", True),
    ("boundary-beta-avg", "clustered", 800, ("boundary", 0), "average", "leaves", True),
    ("boundary-beta-complete", "clustered", 800, ("boundary", 0), "complete", "leaves", True),
    ("small-beta-avg", "clustered", 120, ("beta", 0.6), "average", "leaves", False),
    ("small-n-single", "ties", 200, ("n", 4), "single", "leaves", False),
    ("small-forest-avg", "clustered", 300, ("n", 2), "average", "forest", False),
]


@pytest.mark.parametrize(
    "kind,K,stop,linkage,start,compacts",
    [c[1:] for c in CASES],
    ids=[c[0] for c in CASES],
)
def test_dense_merge_loop_bitwise_equals_full_width_loop(
    kind, K, stop, linkage, start, compacts
):
    seed = K + len(linkage)
    if start == "forest":
        A, groups = _forest(K, max(K // 2, 2), seed)
        D0 = cluster_distance_matrix(A, groups, linkage)
        size0 = np.array([len(g) for g in groups], dtype=np.int64)
    else:
        D0 = {"clustered": _clustered, "ties": _ties, "sparse": _sparse_ties}[kind](K, seed)
        groups = [[i] for i in range(K)]
        size0 = np.ones(K, dtype=np.int64)
    C = D0.shape[0]

    def run(fn, **kw):
        return fn(D0.copy(), size0.copy(), [list(g) for g in groups], linkage=linkage, **kw)

    mode, q = stop
    if mode == "n":
        crit = {"n_clusters": q}
    else:
        *_, full = run(merge_forest_oracle, n_clusters=1)
        heights = np.array([h for _, _, h in full])
        if mode == "beta":
            crit = {"beta": float(np.quantile(heights, q))}
        else:
            m = _first_compaction_merges(C)
            assert heights[m] > heights[m - 1]  # the cut falls just after merge m
            crit = {"beta": float(heights[m - 1])}

    want_active, want_members, want_merges = run(merge_forest_oracle, **crit)
    counts = _Counts()
    active, members, merges = run(merge_forest, counts=counts, **crit)

    assert [(a, b) for a, b, _ in merges] == [(a, b) for a, b, _ in want_merges]
    np.testing.assert_array_equal(_heights(merges), _heights(want_merges))
    np.testing.assert_array_equal(active, want_active)
    assert members == want_members
    assert (counts.counts["compactions"] > 0) == compacts
    if mode == "boundary":
        # compacted after the last merge: every merge ran at full width
        assert len(merges) == _first_compaction_merges(C)
        assert counts.counts["compactions"] == 1
        assert counts.counts["width_sum"] == len(merges) * C
    elif compacts:
        assert counts.counts["width_sum"] < len(merges) * C
    else:
        assert counts.counts["width_sum"] == len(merges) * C
