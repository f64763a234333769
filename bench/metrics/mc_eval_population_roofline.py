"""Share of the roofline of the Monte-Carlo population kernel
(``kernels/mc_eval.py``): its traced launches against the bytes the
algorithm needs (bench/work/mc_eval_population.py). The interval
compare/select work has no published peak, so the bound is the bytes
alone. Layer: kernels. Moves ``mc_evals_per_s``."""
from bench.lib.readers import real_rows, roofline

# the kernel's op name in the device trace
NAMES = ("mc_adc_eval_pallas_population",)


def read(obs):
    rows = (int(obs.counters.get("test_rows", 0)),)
    work = obs.work("mc_eval_population").work
    bits = obs.config["adc_bits"]
    return roofline(obs, NAMES, lambda dims: work(
        dims[0], dims[1], real_rows(dims[2], rows), dims[3], bits))
