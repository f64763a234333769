"""Linear SVM classifier (one-vs-rest, squared hinge) — the paper's second
target model family ("common classifiers in this domain such as MLPs and
SVMs"). Same functional interface as the MLP so ``core.search`` can optimize
ADCs for either.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import qat
from repro.kernels.envelope import MATMUL_PRECISION

Params = Tuple[jnp.ndarray, jnp.ndarray]      # (W: (F, C), b: (C,))


def init_svm(key, features: int, classes: int) -> Params:
    w = jax.random.normal(key, (features, classes), jnp.float32) * 0.1
    return (w, jnp.zeros((classes,), jnp.float32))


def apply_svm(params: Params, x: jnp.ndarray,
              dp: Optional[jnp.ndarray] = None, weight_bits: int = 8):
    w, b = params
    if dp is not None:
        w = qat.quantize_po2(w, dp, weight_bits)
        b = qat.quantize_fixed(b, dp, weight_bits)
    return jnp.dot(x, w, precision=MATMUL_PRECISION) + b


def svm_loss(params: Params, x, y, dp=None, margin: float = 1.0,
             l2: float = 1e-3, weight_bits: int = 8) -> jnp.ndarray:
    """Multiclass squared hinge (Crammer-Singer style one-vs-rest)."""
    scores = apply_svm(params, x, dp, weight_bits)
    C = scores.shape[-1]
    tgt = jax.nn.one_hot(y, C) * 2.0 - 1.0          # +-1 per class
    hinge = jnp.maximum(0.0, margin - tgt * scores)
    return (hinge ** 2).mean() + l2 * jnp.sum(params[0] ** 2)


def n_correct(params: Params, x, y, dp=None, weight_bits: int = 8
              ) -> jnp.ndarray:
    """Correctly classified samples, int32 (see mlp.n_correct)."""
    scores = apply_svm(params, x, dp, weight_bits)
    return jnp.sum(jnp.argmax(scores, -1) == y, dtype=jnp.int32)
