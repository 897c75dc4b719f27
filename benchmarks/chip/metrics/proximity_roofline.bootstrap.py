"""Share of its roofline that the ``proximity`` kernel reaches.

Useful work of the K(K-1)/2 unique pairs (``flops.py``) over the kernel's
device time; the roofline time is the larger of flops over peak FLOP/s and
bytes over peak bytes/s.
"""
import sys

import flops

KERNEL = "proximity"


def read(run):
    ts, cfg = run.trace_summary, run.config
    if not ts.kernel_calls.get(KERNEL):
        return None  # the kernel is off the path: the metric is left out, never 0
    K, n, p = int(cfg["n_clients"]), int(cfg["n_features"]), int(cfg["p"])
    t_roof, bound = flops.roofline_s(
        flops.proximity_flops(K, n, p, cfg["measure"]), flops.proximity_bytes(K, n, p), run.peak
    )
    per_call = ts.kernel_s[KERNEL] / ts.kernel_calls[KERNEL]
    print(f"proximity_roofline: {bound}-bound, roofline {t_roof:.6e} s per call", file=sys.stderr)
    return 100.0 * t_roof / per_call
