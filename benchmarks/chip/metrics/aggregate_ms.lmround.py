"""Device time of the sequential round's per-cluster aggregation, per round:
the XLA modules ``jit__fold_client`` (each client's trained model folded
into its cluster's running weighted sum) and ``jit__write_mean`` (a touched
cluster's model becomes that sum over its weight)."""
MODULES = ("jit__fold_client", "jit__write_mean")


def read(run):
    ts = run.trace_summary
    if not any(ts.module_calls.get(m) for m in MODULES):
        return None  # the modules are off the path: the metric is left out, never 0
    return 1e3 * sum(ts.module_s.get(m, 0.0) for m in MODULES) / run.steps
