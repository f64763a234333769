"""Printed-MLP classifier (the paper's target workload, topology per [21]).

Functional: ``init_mlp(key, sizes)`` -> params list of (W, b);
``apply_mlp(params, x, dp=None)`` with optional in-graph power-of-2 weight
fake-quant (QAT, genome-controlled decimal position ``dp``).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import qat
from repro.kernels.envelope import MATMUL_PRECISION

Params = List[Tuple[jnp.ndarray, jnp.ndarray]]


def init_mlp(key, sizes: Sequence[int]) -> Params:
    params = []
    for i, (fan_in, fan_out) in enumerate(zip(sizes[:-1], sizes[1:])):
        key, sub = jax.random.split(key)
        w = jax.random.normal(sub, (fan_in, fan_out), jnp.float32)
        w = w * jnp.sqrt(2.0 / fan_in)
        # inputs live in [0,1] (paper normalization): zero-mean each column and
        # bias slightly positive so tiny printed-MLP hidden units start alive.
        w = w - w.mean(axis=0, keepdims=True)
        b = jnp.full((fan_out,), 0.1, jnp.float32)
        params.append((w, b))
    return params


def apply_mlp(params: Params, x: jnp.ndarray, dp: Optional[jnp.ndarray] = None,
              weight_bits: int = 8) -> jnp.ndarray:
    h = x
    n = len(params)
    for i, (w, b) in enumerate(params):
        if dp is not None:
            w = qat.quantize_po2(w, dp, weight_bits)
            b = qat.quantize_fixed(b, dp, weight_bits)
        h = jnp.dot(h, w, precision=MATMUL_PRECISION) + b
        if i < n - 1:
            h = jax.nn.relu(h)
    return h


def n_correct(params: Params, x, y, dp=None, weight_bits: int = 8
              ) -> jnp.ndarray:
    """Correctly classified samples, as an int32 count. The accuracy is
    this count over ``len(y)``, divided on the host (search.accuracy):
    a device-side f32 mean rounds differently under vmap, jit and
    backend, which breaks bit-for-bit equality between engines."""
    logits = apply_mlp(params, x, dp, weight_bits)
    return jnp.sum(jnp.argmax(logits, -1) == y, dtype=jnp.int32)
