"""Host time of ``PACFL.run_round`` less its ``Strategy._run_local``: the
per-cluster gather, weighted average and scatter of the cluster models, per
round."""
SPANS = {
    "run_round": "repro.fl.strategies:PACFL.run_round",
    "run_local": "repro.fl.strategies:Strategy._run_local",
}


def read(run):
    rounds = run.spans.durations.get("run_round", [])
    local = run.spans.durations.get("run_local", [])
    return 1e3 * (sum(rounds) - sum(local)) / run.steps if rounds else None
