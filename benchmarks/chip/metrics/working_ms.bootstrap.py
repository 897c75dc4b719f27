"""Host time of the HC working matrix build (the program's ``hc.working`` span),
per bootstrap."""
import program_spans


def read(run):
    return program_spans.self_ms_per_step(run, "hc.working")
