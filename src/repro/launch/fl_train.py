"""PACFL federation driver (the paper's end-to-end pipeline).

``python -m repro.launch.fl_train --setting mix4 --strategy pacfl --rounds 20``
"""
import argparse
import functools
import json

import numpy as np

from repro.core.pacfl import PACFLConfig
from repro.data import make_dataset
from repro.fl import FLConfig, STRATEGIES, dirichlet_skew, label_skew, mix_datasets, run_federation
from repro.launch.compile_cache import enable_compile_cache
from repro.models.cnn import MODEL_ZOO


def build_clients(setting: str, n_clients: int, dim: int, n_train: int, seed: int = 0):
    if setting == "mix4":
        dss = [make_dataset(n, n_train=n_train, n_test=800, dim=dim, seed=seed)
               for n in ("cifar10s", "svhns", "fmnists", "uspss")]
        counts = [max(1, round(n_clients * f)) for f in (0.31, 0.25, 0.27, 0.14)]
        while sum(counts) > n_clients:
            counts[np.argmax(counts)] -= 1
        return mix_datasets(dss, counts, samples_per_client=300, seed=seed), 40
    ds = make_dataset("cifar10s", n_train=n_train, n_test=800, dim=dim, seed=seed)
    if setting == "label20":
        return label_skew(ds, n_clients, rho=0.2, seed=seed), ds.n_classes
    if setting == "label30":
        return label_skew(ds, n_clients, rho=0.3, seed=seed), ds.n_classes
    if setting == "dir01":
        return dirichlet_skew(ds, n_clients, alpha=0.1, seed=seed), ds.n_classes
    raise ValueError(setting)


def build_model(name: str, dim: int, n_classes: int):
    """``(init_fn, apply_fn)`` of a ``MODEL_ZOO`` model for flat inputs of
    width ``dim``; the CNNs read them as square RGB images (3072 = 32x32x3)."""
    init_raw, apply_raw = MODEL_ZOO[name]
    if name == "mlp":
        return (lambda key: init_raw(key, dim, n_classes, hidden=(128, 64))), apply_raw
    hw = int((dim // 3) ** 0.5)
    init_fn = lambda key: init_raw(key, in_hw=(hw, hw), in_ch=3, n_classes=n_classes)
    return init_fn, functools.partial(apply_raw, in_hw=(hw, hw), in_ch=3)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--setting", default="mix4",
                    choices=("mix4", "label20", "label30", "dir01"))
    ap.add_argument("--strategy", default="pacfl", choices=sorted(STRATEGIES))
    ap.add_argument("--model", default="mlp", choices=sorted(MODEL_ZOO))
    ap.add_argument("--clients", type=int, default=40)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--beta", type=float, default=None)
    ap.add_argument("--measure", default=None, choices=(None, "eq2", "eq3"))
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    clients, n_classes = build_clients(args.setting, args.clients, args.dim, 3000)
    init_fn, apply_fn = build_model(args.model, args.dim, n_classes)

    pac = PACFLConfig(
        p=3,
        beta=args.beta if args.beta is not None else (50.0 if args.setting == "mix4" else 175.0),
        measure=args.measure or ("eq2" if args.setting == "mix4" else "eq3"),
    )
    cfg = FLConfig(rounds=args.rounds, sample_frac=0.1, local_epochs=3,
                   batch_size=20, lr=0.05, pacfl=pac)
    res = run_federation(args.strategy, clients, apply_fn, init_fn, cfg,
                         seed=args.seed, eval_every=5, verbose=True)
    summary = {
        "strategy": args.strategy, "setting": args.setting,
        "final_acc_mean": res.final_mean, "final_acc_std": res.final_std,
        "comm_mb": (res.strategy_obj.comm_up + res.strategy_obj.comm_down) / 1e6,
    }
    if args.strategy == "pacfl":
        summary["n_clusters"] = int(res.strategy_obj.clustering.n_clusters)
    print(json.dumps(summary, indent=2))


if __name__ == "__main__":
    main()
