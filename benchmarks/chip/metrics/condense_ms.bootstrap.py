"""Host time of the condensed store build from the (K, K) matrix (the program's
``store.condense`` span), per bootstrap."""
import program_spans


def read(run):
    return program_spans.self_ms_per_step(run, "store.condense")
