"""Model FLOP/s of the whole search over the bf16 peak: every delivered
individual's QAT (forward and backward matmuls of every step on the
training rows, one forward on the test rows; bench/work/mlp_qat.py),
duplicates that dedup shared counted as delivered, over the window.
Layer: whole search step. Moves ``search_ind_per_s``."""
from bench.lib.readers import mfu


def read(obs):
    c, t = obs.config, obs.traffic
    w = obs.work("mlp_qat").work(
        int(obs.counters.get("train_rows", 0)),
        int(obs.counters.get("test_rows", 0)), c["channels"], c["hidden"],
        c["classes"], t["train_steps"])
    return mfu(obs, obs.counters.get("individuals", 0) * w["flops"])
