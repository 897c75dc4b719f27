"""LeNet-5's forward and backward flops of a round's local training (3 x the
forward flops counted from the shapes in ``flops.py``, times the samples
trained: sampled clients x local steps x batch), over (``round_s`` x peak
bf16 FLOP/s)."""
import flops
import mix4
import round_reference


def read(run):
    cfg = run.config
    n = mix4.sample_counts(cfg, sum(mix4.client_counts(cfg)))
    m = max(1, min(n.size, int(round(float(cfg["sample_frac"]) * n.size))))
    samples = m * round_reference.local_steps(cfg, n) * int(cfg["batch_size"])
    useful = samples * flops.lenet5_train_flops(
        int(cfg["image_hw"]), int(cfg["channels"]), int(cfg["n_classes"])
    )
    return 100.0 * useful / (run.e2e["round_s"] * run.peak["bf16_flops_per_s"])
