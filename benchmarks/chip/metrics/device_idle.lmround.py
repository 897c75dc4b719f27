"""Share of the traced window in which no operation ran on the device."""


def read(run):
    return 100.0 * run.trace_summary.idle_share
