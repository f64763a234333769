"""Banks of deployed designs made by the benchmark from a seed.

A design is a pruned 4-bit ADC mask per channel plus a printed MLP with
power-of-two weights. The benchmark makes both itself, so that the plain
reference takes nothing the program made: masks keep each level with
probability one half (a channel with fewer than two kept levels gets its
lowest pruned levels turned on, as the search's repair does), and the
weights are He-normal draws projected onto 8-bit powers of two at
decimal position 0, made on the device in one jitted call.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

DP = 0.0


def masks(seed: int, d: int, channels: int, bits: int,
          min_levels: int = 2) -> np.ndarray:
    """(D, C, 2^N) int32 kept-level masks."""
    rng = np.random.default_rng([seed, 0xada])
    m = (rng.random((d, channels, 2 ** bits)) < 0.5).astype(np.int32)
    for k in range(d):
        for c in range(channels):
            for lvl in range(2 ** bits):
                if m[k, c].sum() >= min_levels:
                    break
                m[k, c, lvl] = 1
    return m


def weights(seed: int, d: int, c: int, h: int, o: int,
            weight_bits: int) -> Tuple[np.ndarray, ...]:
    """(w1 (D, C, H), b1 (D, H), w2 (D, H, O), b2 (D, O)) float32 values
    on the power-of-two / fixed-point grid, made in one jitted call."""
    import jax
    import jax.numpy as jnp

    from bench.lib import refs

    def make(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        w1 = jax.random.normal(k1, (d, c, h)) * jnp.sqrt(2.0 / c)
        w2 = jax.random.normal(k2, (d, h, o)) * jnp.sqrt(2.0 / h)
        b1 = 0.1 * jax.random.normal(k3, (d, h))
        b2 = 0.1 * jax.random.normal(k4, (d, o))
        return (refs.po2(w1, DP, weight_bits), refs.fixed(b1, DP, weight_bits),
                refs.po2(w2, DP, weight_bits), refs.fixed(b2, DP, weight_bits))

    out = jax.jit(make)(jax.random.PRNGKey(seed))
    return tuple(np.asarray(a, np.float32) for a in out)


def bank(seed: int, config: Dict, d: int):
    """(designs for the program, raw (masks, weights) for the reference)."""
    from repro.core import area
    from repro.core.deploy import DeployedClassifier
    from repro.core.spec import AdcSpec
    c, h, o = config["channels"], config["hidden"], config["classes"]
    bits = config["adc_bits"]
    ms = masks(seed, d, c, bits)
    ws = weights(seed, d, c, h, o, config["weight_bits"])
    spec = AdcSpec(bits=bits, mode=config["adc_mode"])
    designs: List = []
    for k in range(d):
        designs.append(DeployedClassifier(
            kind="mlp", bits=bits, mode=config["adc_mode"], vmin=0.0,
            vmax=1.0, dp=DP, mask=ms[k],
            table=np.asarray(spec.value_table(ms[k]), np.float32),
            weights=tuple(w[k] for w in ws),
            area_tc=int(area.system_tc(ms[k], "ours")), accuracy=0.0))
    return designs, ms, ws
