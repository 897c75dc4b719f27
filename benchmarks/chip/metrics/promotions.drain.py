"""Dirty-cluster promotions of the engine's replays (the ``promotions`` count on
the program's ``engine.replay`` spans), per drain."""
import program_spans


def read(run):
    return program_spans.count_per_step(run, "engine.replay", "promotions")
