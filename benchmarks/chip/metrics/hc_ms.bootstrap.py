"""Host time of ``ClusterEngine._bootstrap`` (store build + ``merge_forest``),
per bootstrap."""
SPANS = {"hc": "repro.core.engine.engine:ClusterEngine._bootstrap"}


def read(run):
    spans = run.spans.durations.get("hc", [])
    return 1e3 * sum(spans) / run.steps if spans else None
