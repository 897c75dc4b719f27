"""Device time of the sequential round's client update (the XLA module
``jit_client_update``: one client's local SGD from its cluster's model,
without the fold into the running sum, which ``aggregate_ms.lmround``
reads), per round."""
MODULE = "jit_client_update"


def read(run):
    ts = run.trace_summary
    if not ts.module_calls.get(MODULE):
        return None  # the module is off the path: the metric is left out, never 0
    return 1e3 * ts.module_s[MODULE] / run.steps
