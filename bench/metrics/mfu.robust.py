"""Model FLOP/s of the Monte-Carlo re-score over the bf16 peak: one
printed-MLP forward over the test rows per evaluated instance
(bench/work/mlp_forward.py), over the window. Layer: whole MC step.
Moves ``mc_evals_per_s``."""
from bench.lib.readers import mfu


def read(obs):
    c = obs.config
    w = obs.work("mlp_forward").work(int(obs.counters.get("test_rows", 0)),
                                     c["channels"], c["hidden"],
                                     c["classes"])
    return mfu(obs, obs.counters.get("instances", 0) * w["flops"])
