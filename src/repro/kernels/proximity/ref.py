"""Pure-jnp oracle for the proximity-matrix kernel (Eq. 2 / Eq. 3, degrees).

Reduces through the shared measure core with the LAPACK ``svd`` eq2 solver,
so the kernel's on-chip Jacobi path is always tested against an independent
factorization.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.measures import GRAM_PRECISION, measure_from_gram


def proximity_ref(U: jnp.ndarray, measure: str = "eq3") -> jnp.ndarray:
    """U: (K, n, p) orthonormal signatures -> (K, K) angle matrix, degrees."""
    U = U.astype(jnp.float32)
    G = jnp.einsum("inp,jnq->ijpq", U, U, precision=GRAM_PRECISION)
    A = measure_from_gram(G, measure, eq2_solver="svd")
    A = 0.5 * (A + A.T)
    return A * (1.0 - jnp.eye(A.shape[0], dtype=A.dtype))
