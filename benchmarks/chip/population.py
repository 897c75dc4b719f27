"""Seeded FEMNIST-shaped client signatures, the benchmark's own generator.

Clients upload the p-column orthonormal basis of their local data.  A
population sits around ``n_styles`` latent writer styles: a client's basis is
its style's basis plus ``spread`` per-entry noise, re-orthonormalised, and
its columns keep the style's order (eq3 pairs identically ordered columns).
A "far" client is a random subspace, far from every style, so it forms a
cluster of its own.

Copied from the repository's ``chip_smoke.Population`` so that a change to
the program cannot change the yardstick.  Everything is drawn from one
``numpy.random.Generator`` seeded by the run's ``--seed``.
"""
from __future__ import annotations

import numpy as np


class Population:
    """Styles and signature draws for one configuration and seed."""

    def __init__(self, cfg: dict, seed: int):
        self.n = int(cfg["n_features"])
        self.p = int(cfg["p"])
        self.n_styles = int(cfg["n_styles"])
        self.spread = float(cfg["spread"])
        self.rng = np.random.default_rng(seed)
        self.styles = np.linalg.qr(
            self.rng.standard_normal((self.n_styles, self.n, self.p))
        )[0]

    def near(self, style: np.ndarray) -> np.ndarray:
        """(len(style), n, p) float32 bases perturbed around the styles."""
        style = np.asarray(style, dtype=np.int64)
        X = self.styles[style] + self.spread * self.rng.standard_normal(
            (style.size, self.n, self.p)
        )
        return np.linalg.qr(X)[0].astype(np.float32)

    def far(self, k: int) -> np.ndarray:
        """(k, n, p) float32 random subspaces."""
        return np.linalg.qr(self.rng.standard_normal((k, self.n, self.p)))[0].astype(
            np.float32
        )

    def draw(self, k: int, far_share: float) -> tuple[np.ndarray, np.ndarray]:
        """``k`` signatures, ``round(k * far_share)`` of them far, in a
        seeded random order.  Returns ``(U, far)`` with ``far`` a bool mask."""
        n_far = int(round(k * far_share))
        U = np.concatenate(
            [self.near(np.arange(k - n_far) % self.n_styles), self.far(n_far)]
        )
        is_far = np.arange(k) >= k - n_far
        order = self.rng.permutation(k)
        return U[order], is_far[order]
