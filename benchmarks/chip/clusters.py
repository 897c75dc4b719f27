"""What the clustering cells share: the resident population and the engine
configuration, both from the cell's configuration file and seed."""
from __future__ import annotations

import numpy as np

from population import Population


def engine_config(cfg: dict):
    from repro.core.engine import EngineConfig

    return EngineConfig(
        beta=float(cfg["beta"]),
        measure=cfg["measure"],
        linkage=cfg["linkage"],
        backend=cfg["backend"],
        memory=cfg["memory"],
    )


def resident(cfg: dict, seed: int) -> tuple[Population, np.ndarray, np.ndarray]:
    """The population's generator, its (K, n, p) float32 signatures and
    the mask of far clients."""
    pop = Population(cfg, seed)
    U, far = pop.draw(int(cfg["n_clients"]), float(cfg["far_share"]))
    return pop, U, far

