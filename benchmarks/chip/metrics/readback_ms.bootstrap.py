"""Host time of the (K, K) device-to-host copy of the proximity output (the program's
``bootstrap.readback`` span), per bootstrap."""
import program_spans


def read(run):
    return program_spans.self_ms_per_step(run, "bootstrap.readback")
