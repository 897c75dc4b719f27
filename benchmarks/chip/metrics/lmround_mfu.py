"""LFM2's forward and backward flops of a round's local training (the share
this chip holds, useful work counted from the shapes in ``flops_lm.py``,
the held experts at their expected pairs), over (``round_s`` x peak bf16
FLOP/s)."""
import flops_lm
import lfm2_reference
import lmtokens


def read(run):
    cfg = run.config
    m = max(1, min(int(cfg["n_clients"]), int(round(float(cfg["sample_frac"]) * int(cfg["n_clients"])))))
    steps = lfm2_reference.local_steps(cfg, lmtokens.sizes(cfg))
    tokens = m * steps * int(cfg["batch_size"]) * int(cfg["seq_len"])
    useful = tokens * flops_lm.train_flops_per_token(cfg)
    return 100.0 * useful / (run.e2e["round_s"] * run.peak["bf16_flops_per_s"])
