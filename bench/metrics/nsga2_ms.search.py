"""Median host time of NSGA-II's own work in a generation: the
``nsga2.variation`` (sort, crowding, tournaments, crossover, mutation)
and ``nsga2.survival`` spans of each ``nsga2.generation``, from the
program's ring over the whole window. Layer: search engine. Moves
``search_ind_per_s``."""
from bench.lib.program import window_ms


def read(obs):
    return window_ms(obs, "nsga2.generation",
                     ("nsga2.variation", "nsga2.survival"))
