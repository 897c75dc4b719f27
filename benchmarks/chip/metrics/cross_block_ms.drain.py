"""Host time of the admission blocks, dispatch to readback (the program's
``admit.cross_block`` span), per drain."""
import program_spans


def read(run):
    return program_spans.self_ms_per_step(run, "admit.cross_block")
