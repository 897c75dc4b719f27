"""Host time of the engine fork of the epoch swap (the program's ``commit.fork``
span), per drain."""
import program_spans


def read(run):
    return program_spans.self_ms_per_step(run, "commit.fork")
