"""Host time of the write path's intake (the program's ``serve.submit`` spans, one
per join or leave), per drain."""
import program_spans


def read(run):
    return program_spans.self_ms_per_step(run, "serve.submit")
