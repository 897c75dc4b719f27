"""Device time of the held experts' grouped matrix products, forward and
backward, per round: XLA's ``ragged-dot`` kernels on the TPU (instructions
``ragged-dot-*``, without the ``ragged-dot-metadata`` that lays out their
groups)."""
PREFIX = "ragged-dot"
LAYOUT = "ragged-dot-metadata"


def expert_s(ts):
    """Summed device seconds and calls of the grouped products, or None."""
    names = [k for k in ts.kernel_s if k.startswith(PREFIX) and k != LAYOUT]
    if not names:
        return None
    return sum(ts.kernel_s[k] for k in names), sum(ts.kernel_calls[k] for k in names)


def read(run):
    got = expert_s(run.trace_summary)
    return None if got is None else 1e3 * got[0] / run.steps
