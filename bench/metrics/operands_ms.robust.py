"""Median host time a robustness call spends on its Monte-Carlo operands
(the ``deploy.mc_operands`` span: masks, the draw, the interval
compilation), from the program's ring over the whole window. Layer:
robustness analysis. Moves ``mc_evals_per_s``."""
from bench.lib.program import window_ms


def read(obs):
    return window_ms(obs, "deploy.evaluate_robustness",
                     ("deploy.mc_operands",))
