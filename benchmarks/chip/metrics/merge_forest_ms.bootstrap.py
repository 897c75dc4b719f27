"""Host time of the HC merge loop (the program's ``hc.merge_forest`` span), per
bootstrap."""
import program_spans


def read(run):
    return program_spans.self_ms_per_step(run, "hc.merge_forest")
