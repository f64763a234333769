"""Median host time a generation spends building the stacked initial
train state (the ``search.init`` span: eager init, ``tile``, transfers),
from the program's ring over the whole window. Layer: search engine.
Moves ``search_ind_per_s``."""
from bench.lib.program import window_ms


def read(obs):
    return window_ms(obs, "nsga2.generation", ("search.init",))
