"""Host time of the representative refresh of the epoch swap, less the dense cache
rebuilds inside it (the program's ``commit.refresh`` span), per drain."""
import program_spans


def read(run):
    return program_spans.self_ms_per_step(run, "commit.refresh")
