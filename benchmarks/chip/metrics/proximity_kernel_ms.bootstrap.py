"""Device time of the Pallas kernel named ``proximity``, per bootstrap."""
KERNEL = "proximity"


def read(run):
    ts = run.trace_summary
    if not ts.kernel_calls.get(KERNEL):
        return None  # the kernel is off the path: the metric is left out, never 0
    return 1e3 * ts.kernel_s[KERNEL] / run.steps
