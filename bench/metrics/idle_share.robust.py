"""Share of the traced window in which no operation ran on the device
(1 - union of the device's op intervals / window). Layer: device.
Moves ``mc_evals_per_s``."""
from bench.lib.readers import idle_share


def read(obs):
    return idle_share(obs)
