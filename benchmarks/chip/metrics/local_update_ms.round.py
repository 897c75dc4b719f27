"""Device time of the program's jitted, vmapped local update (the XLA module
``jit_local_sgd``), per round."""
MODULE = "jit_local_sgd"


def read(run):
    ts = run.trace_summary
    if not ts.module_calls.get(MODULE):
        return None  # the module is off the path: the metric is left out, never 0
    return 1e3 * ts.module_s[MODULE] / run.steps
