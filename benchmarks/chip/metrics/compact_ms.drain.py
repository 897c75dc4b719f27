"""Host time of the store's compaction on departures (the program's
``store.remove`` span), per drain."""
import program_spans


def read(run):
    return program_spans.self_ms_per_step(run, "store.remove")
