"""Share of its roofline that the held experts' grouped products reach:
the useful flops and bytes of the pairs they computed in the window (the
program's ``lmround`` span counts ``expert_load_sum``; ``flops_lm.py``),
against their device time (``expert_ms.lmround``'s ops).  The roofline
time is the larger of flops over peak bf16 FLOP/s and bytes over peak
bytes/s."""
import importlib.util
import sys
from pathlib import Path

import flops
import flops_lm
import lfm2_reference
import lmtokens
import program_spans

_spec = importlib.util.spec_from_file_location(
    "chipbench.metrics.expert_ms_lmround", Path(__file__).with_name("expert_ms.lmround.py"))
expert_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(expert_ms)


def read(run):
    got = expert_ms.expert_s(run.trace_summary)
    recs = program_spans.program_records()
    pairs = program_spans.counted(recs, "lmround", "expert_load_sum") if recs else None
    if got is None or not pairs:
        return None
    cfg = run.config
    n_moe = int(cfg["n_layers"]) - int(cfg["num_dense_layers"])
    steps = lfm2_reference.local_steps(cfg, lmtokens.sizes(cfg))
    clients = program_spans.counted(recs, "lmround", "clients") or 0
    t_roof, bound = flops.roofline_s(
        flops_lm.expert_flops(cfg, pairs), flops_lm.expert_bytes(cfg, pairs, n_moe * steps * clients),
        run.peak,
    )
    print(f"expert_roofline: {bound}-bound, roofline {t_roof:.6e} s over the window", file=sys.stderr)
    return 100.0 * t_roof / got[0]
