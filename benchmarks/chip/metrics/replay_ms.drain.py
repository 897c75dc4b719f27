"""Host time of the engine's dendrogram ``replay`` calls, summed per drain."""
SPANS = {"replay": "repro.core.engine.engine:replay"}


def read(run):
    spans = run.spans.durations.get("replay", [])
    return 1e3 * sum(spans) / run.steps if spans else None
