"""Useful proximity flops of a bootstrap over (step time x peak bf16 FLOP/s)."""
import flops


def read(run):
    cfg = run.config
    K, n, p = int(cfg["n_clients"]), int(cfg["n_features"]), int(cfg["p"])
    step_s = sum(run.step_s) / len(run.step_s)
    useful = flops.proximity_flops(K, n, p, cfg["measure"])
    return 100.0 * useful / (step_s * run.peak["bf16_flops_per_s"])
