"""Median count of host dispatches to the device (``PjitFunction*`` and
``DevicePut*`` host events) inside each
``repro.deploy.evaluate_robustness`` of the device trace. Layer:
robustness analysis. Moves ``mc_evals_per_s``."""
from bench.lib.program import trace_dispatches


def read(obs):
    return trace_dispatches(obs, "deploy.evaluate_robustness")
