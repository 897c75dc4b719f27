"""Host time of the serving epoch swap (``AssignmentServer._commit``: engine
fork and representative refresh), per drain."""
SPANS = {"commit": "repro.serving.server:AssignmentServer._commit"}


def read(run):
    spans = run.spans.durations.get("commit", [])
    return 1e3 * sum(spans) / run.steps if spans else None
