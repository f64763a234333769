"""Median wall time of one NSGA-II generation (host clock between the
search's ``log`` callbacks): selection, variation, the compiled QAT
generation, fitness and survival. Layer: search engine. Moves
``search_ind_per_s``."""
from bench.lib.readers import median_ms


def read(obs):
    return median_ms(obs.spans.get("generation", []))
