"""Host time of the store's append of the admission blocks (the program's
``store.append`` span), per drain."""
import program_spans


def read(run):
    return program_spans.self_ms_per_step(run, "store.append")
