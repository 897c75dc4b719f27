"""Jitted public wrapper for the proximity kernel."""
from __future__ import annotations

import jax

from repro.kernels.proximity.proximity import BLOCK, proximity_pallas


def proximity(U: jax.Array, *, measure: str = "eq3", bk: int = BLOCK) -> jax.Array:
    """(K, n, p) signatures -> (K, K) proximity matrix (degrees).

    ``measure`` is "eq3" (trace angle) or "eq2" (smallest principal angle).
    ``proximity_pallas`` auto-detects the backend: compiled on TPU,
    interpret mode elsewhere.
    """
    return proximity_pallas(U, measure=measure, bk=bk)
