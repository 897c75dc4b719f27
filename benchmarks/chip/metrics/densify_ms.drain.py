"""Host time of the dense cache rebuilds (the program's ``store.densify`` spans,
in replay or in the representative refresh), per drain."""
import program_spans


def read(run):
    return program_spans.self_ms_per_step(run, "store.densify")
