"""Median host time a generation waits for the compiled generation's
outputs (the ``search.fetch`` span), from the program's ring over the
whole window. Layer: whole step. Moves ``search_ind_per_s``."""
from bench.lib.program import window_ms


def read(obs):
    return window_ms(obs, "nsga2.generation", ("search.fetch",))
