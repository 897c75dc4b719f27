"""Plain references that decide ``correct``, and the lower-precision control.

Nothing here imports the program.  The float64 references follow the
paper's definitions directly:

* eq3: ``A[i, j] = sum_r degrees(arccos(|U_i[:, r] . U_j[:, r]|))``;
* eq2: ``A[i, j] = degrees(arccos(s_max(U_i^T U_j)))``;

and hierarchical clustering is SciPy's average/single/complete linkage cut
at the threshold ``beta`` (``fcluster(..., criterion="distance")``), an
implementation independent of the program's own merge loop.

The control is the same computation put in the program's place one
precision step below what the configuration states (float32 Gram products at
``HIGHEST``): float32 with every Gram product at ``Precision.HIGH``, three
bfloat16 passes on a TPU.  Off the TPU the precision flag does nothing, so
the control reads as the control only on the chip.
"""
from __future__ import annotations

import numpy as np
from scipy.cluster.hierarchy import fcluster, linkage
from scipy.spatial.distance import squareform

ROW_BLOCK = 512


def _angles_f64(Ua: np.ndarray, Ub: np.ndarray, measure: str) -> np.ndarray:
    # (p, K, n) contiguous: a strided column slice would keep NumPy off BLAS
    Ua = np.ascontiguousarray(np.asarray(Ua, np.float64).transpose(2, 0, 1))
    Ub = np.ascontiguousarray(np.asarray(Ub, np.float64).transpose(2, 0, 1))
    if measure == "eq3":
        out = np.zeros((Ua.shape[1], Ub.shape[1]))
        for r in range(Ua.shape[0]):
            cos = np.abs(Ua[r] @ Ub[r].T)
            out += np.degrees(np.arccos(np.clip(cos, 0.0, 1.0)))
        return out
    if measure == "eq2":
        (p, ka, n), (q, kb, _) = Ua.shape, Ub.shape
        G = Ua.reshape(p * ka, n) @ Ub.reshape(q * kb, n).T
        G = G.reshape(p, ka, q, kb).transpose(1, 3, 0, 2)  # (ka, kb, p, q)
        smax = np.linalg.svd(G, compute_uv=False)[..., 0]
        return np.degrees(np.arccos(np.clip(smax, 0.0, 1.0)))
    raise ValueError(f"unknown measure {measure!r}")


def cross_f64(Ua: np.ndarray, Ub: np.ndarray, measure: str) -> np.ndarray:
    """(Ka, Kb) float64 proximity block in degrees, in row blocks."""
    blocks = [
        _angles_f64(Ua[lo : lo + ROW_BLOCK], Ub, measure)
        for lo in range(0, Ua.shape[0], ROW_BLOCK)
    ]
    return np.concatenate(blocks) if blocks else np.zeros((0, Ub.shape[0]))


def proximity_f64(U: np.ndarray, measure: str) -> np.ndarray:
    """(K, K) float64 proximity matrix, exact zero diagonal."""
    A = cross_f64(U, U, measure)
    A = 0.5 * (A + A.T)
    np.fill_diagonal(A, 0.0)
    return A


def hc_labels(A: np.ndarray, beta: float, method: str = "average") -> np.ndarray:
    """SciPy agglomerative clustering of a (K, K) distance matrix, cut at
    ``beta``: merging stops once the closest pair of clusters is farther."""
    if A.shape[0] == 1:
        return np.zeros(1, dtype=np.int64)
    Z = linkage(squareform(A, checks=False), method=method)
    return fcluster(Z, t=beta, criterion="distance").astype(np.int64)


def canonical(labels: np.ndarray) -> np.ndarray:
    """Relabel a partition by order of first appearance."""
    _, first, inv = np.unique(labels, return_index=True, return_inverse=True)
    return np.argsort(np.argsort(first))[inv]


def label_mismatch(a: np.ndarray, b: np.ndarray) -> int:
    """Clients whose cluster differs between two partitions of one roster."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return max(a.size, b.size)
    return int((canonical(a) != canonical(b)).sum())


# --- the control: the reference one precision step lower ---------------------


def cross_control(Ua: np.ndarray, Ub: np.ndarray, measure: str) -> np.ndarray:
    """(Ka, Kb) proximity block in float32 with Gram products at
    ``Precision.HIGH`` (three bfloat16 passes on a TPU)."""
    import jax
    import jax.numpy as jnp

    high = jax.lax.Precision.HIGH

    @jax.jit
    def block(a, b):
        if measure == "eq3":
            cos = jnp.abs(jnp.einsum("anr,bnr->abr", a, b, precision=high))
            return jnp.sum(jnp.degrees(jnp.arccos(jnp.clip(cos, 0.0, 1.0))), -1)
        G = jnp.einsum("anp,bnq->abpq", a, b, precision=high)
        smax = jnp.linalg.svd(G, compute_uv=False)[..., 0]
        return jnp.degrees(jnp.arccos(jnp.clip(smax, 0.0, 1.0)))

    Ub_d = jnp.asarray(Ub, jnp.float32)
    out = [
        np.asarray(block(jnp.asarray(Ua[lo : lo + ROW_BLOCK], jnp.float32), Ub_d), np.float64)
        for lo in range(0, Ua.shape[0], ROW_BLOCK)
    ]
    return np.concatenate(out)


def proximity_control(U: np.ndarray, measure: str) -> np.ndarray:
    A = cross_control(U, U, measure)
    A = 0.5 * (A + A.T)
    np.fill_diagonal(A, 0.0)
    return A
