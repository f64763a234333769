"""Median host time a generation spends pricing ADC areas (the
``search.areas`` span: mask repair and the design-rule walk), from the
program's ring over the whole window. Layer: search engine. Moves
``search_ind_per_s``."""
from bench.lib.program import window_ms


def read(obs):
    return window_ms(obs, "nsga2.generation", ("search.areas",))
