"""Representatives rebuilt by the epoch swap's refresh (the ``rebuilt`` count on
the program's ``commit.refresh`` span), per drain."""
import program_spans


def read(run):
    return program_spans.count_per_step(run, "commit.refresh", "rebuilt")
