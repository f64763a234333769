"""Median count of host dispatches to the device (``PjitFunction*`` and
``DevicePut*`` host events) inside each ``repro.nsga2.generation`` of the
device trace. Layer: search engine. Moves ``search_ind_per_s``."""
from bench.lib.program import trace_dispatches


def read(obs):
    return trace_dispatches(obs, "nsga2.generation")
