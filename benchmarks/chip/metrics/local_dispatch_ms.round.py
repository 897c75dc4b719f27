"""Host time of ``Strategy._run_local``: the gather of the sampled clients'
rows, their upload and the local update's dispatch, per round."""
SPANS = {"run_local": "repro.fl.strategies:Strategy._run_local"}


def read(run):
    spans = run.spans.durations.get("run_local", [])
    return 1e3 * sum(spans) / run.steps if spans else None
