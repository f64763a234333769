"""Share of the roofline of the population ADC kernel
(``kernels/adc_quantize.py``): its traced launches against the bytes the
algorithm needs (bench/work/adc_quantize_population.py). The kernel's
compare/select work has no published peak, so the bound is the bytes
alone. Layer: kernels. Moves ``search_ind_per_s``."""
from bench.lib.readers import real_rows, roofline

# the kernel's op name in the device trace
NAMES = ("adc_quantize_pallas_population",)


def read(obs):
    rows = (int(obs.counters.get("train_rows", 0)),
            int(obs.counters.get("test_rows", 0)))
    work = obs.work("adc_quantize_population").work
    bits = obs.config["adc_bits"]
    return roofline(obs, NAMES, lambda dims: work(
        dims[0], real_rows(dims[1], rows), dims[2], bits))
