"""Load imbalance of the held experts: over the window's rounds, the summed
per-layer largest load (pairs on one held expert) over the summed mean
load, from the counts the program's ``lmround`` span reads once a round
(``expert_load_max``, ``expert_load_sum``, ``experts_held``)."""
import program_spans


def read(run):
    recs = program_spans.program_records()
    if not recs:
        return None
    top = program_spans.counted(recs, "lmround", "expert_load_max")
    total = program_spans.counted(recs, "lmround", "expert_load_sum")
    rounds = [r for r in recs if r.name == "lmround" and "experts_held" in r.counts]
    if not top or not total or not rounds:
        return None
    held = rounds[0].counts["experts_held"]
    return top * held / total
