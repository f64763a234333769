"""Plain references for the benchmark's correctness checks.

Each function restates the semantics of one layer of the system under
test in straightforward numpy / ``jax.numpy`` at float32, from the inputs
the benchmark made itself (data, genomes, masks, weights, seeds). None of
it imports the program. The references are what ``correct`` is decided
against, and their lower-precision twins are the controls.

Semantics restated here:

* binary-search ADC in tree mode: an input's raw code
  ``clip(floor((x - vmin) * 2^N / (vmax - vmin)), 0, 2^N - 1)`` descends
  the comparator tree; at a node whose two halves both hold kept levels
  it compares against the midpoint, otherwise it takes the half that
  holds kept levels; the leaf's level value is its interval midpoint;
* genome decode: ``C * 2^N`` mask bits, then 4 bits (LSB first) of the
  decimal-point position ``dp = value - 8``; a channel with fewer than
  ``min_levels`` kept levels gets its lowest-index pruned levels turned on;
* power-of-two weights with decimal position ``dp`` and ``B`` bits:
  ``sign(w) * 2^e``, ``e = clip(round(log2 |w|), dp - B + 1, dp)``, and 0
  below half the smallest power; biases are fixed point with step
  ``2^(dp - B + 1)``;
* the printed MLP: ``relu(xq @ W1 + b1) @ W2 + b2`` at float32;
* QAT: AdamW (b1 0.9, b2 0.95, eps 1e-8, no decay) on the full training
  batch's mean cross-entropy, weights fake-quantized in the forward with a
  straight-through gradient, from the seeded He-normal init with column
  means removed and biases at 0.1;
* Monte-Carlo instances: per comparator an offset ``sigma_offset * eps``
  (in LSB), stuck-at faults where a uniform draw is below ``fault_rate``
  (direction from a fair coin), per-channel range endpoints drifted by
  ``sigma_range * drift * full scale``; the perturbed tree walk then runs
  on the drifted code position.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DP_BITS = 4
HIGHEST = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------- the ADC
def decode_genome(genome: np.ndarray, channels: int, bits: int,
                  min_levels: int = 2) -> Tuple[np.ndarray, float]:
    """uint8 genome -> (mask (C, 2^N) int32, dp)."""
    n = 2 ** bits
    g = np.asarray(genome, np.int64)
    mask = g[: channels * n].reshape(channels, n).astype(np.int32)
    for c in range(channels):
        need = min_levels - int(mask[c].sum())
        for k in range(n):
            if need <= 0:
                break
            if mask[c, k] == 0:
                mask[c, k] = 1
                need -= 1
    dpb = g[channels * n: channels * n + DP_BITS]
    dp = float(sum(int(b) << i for i, b in enumerate(dpb)) - 8)
    return mask, dp


def tree_leaf(mask_row: np.ndarray, code: int) -> int:
    """The kept level the pruned comparator tree routes raw ``code`` to."""
    lo, hi = 0, len(mask_row)
    while hi - lo > 1:
        mid = (lo + hi) // 2
        left = mask_row[lo:mid].any()
        right = mask_row[mid:hi].any()
        go_left = (code < mid) if (left and right) else bool(left)
        lo, hi = (lo, mid) if go_left else (mid, hi)
    return lo


def level_value(bits: int, vmin: float, vmax: float) -> np.ndarray:
    """(2^N,) interval midpoints of the levels, float32."""
    n = 2 ** bits
    return (vmin + (np.arange(n, dtype=np.float64) + 0.5)
            * (vmax - vmin) / n).astype(np.float32)


def value_lut(mask: np.ndarray, bits: int, vmin: float = 0.0,
              vmax: float = 1.0) -> np.ndarray:
    """(C, 2^N) mask -> (C, 2^N) float32: value served for each raw code."""
    vals = level_value(bits, vmin, vmax)
    n = 2 ** bits
    return np.array([[vals[tree_leaf(np.asarray(row) > 0, k)]
                      for k in range(n)] for row in mask], np.float32)


def scale_of(bits: int, vmin: float, vmax: float) -> np.float32:
    return np.float32(2 ** bits / (float(vmax) - float(vmin)))


def raw_codes(x, bits: int, vmin: float = 0.0, vmax: float = 1.0):
    """(..., C) analog -> int32 raw codes of the unpruned ADC."""
    u = (x - jnp.float32(vmin)) * scale_of(bits, vmin, vmax)
    return jnp.clip(jnp.floor(u), 0, 2 ** bits - 1).astype(jnp.int32)


def adc(x, lut) -> jnp.ndarray:
    """(M, C) analog x through the ADC whose code->value map is ``lut``
    (C, 2^N) -> (M, C) float32."""
    bits = int(np.log2(lut.shape[-1]))
    code = raw_codes(x, bits)
    return jnp.take_along_axis(jnp.asarray(lut).T, code, axis=0)


# --------------------------------------------------------- weights and MLP
def po2(w, dp, bits: int):
    """Power-of-two projection (no gradient rule)."""
    dp = jnp.asarray(dp, jnp.float32)
    mag = jnp.abs(w)
    e = jnp.clip(jnp.round(jnp.log2(jnp.maximum(mag, 1e-12))),
                 dp - (bits - 1), dp)
    q = jnp.sign(w) * jnp.exp2(e)
    return jnp.where(mag < jnp.exp2(dp - (bits - 1)) * 0.5, 0.0, q)


def fixed(b, dp, bits: int):
    """Symmetric fixed point with step 2^(dp - bits + 1)."""
    dp = jnp.asarray(dp, jnp.float32)
    step = jnp.exp2(dp - (bits - 1))
    hi = jnp.exp2(dp) - step
    return jnp.clip(jnp.round(b / step) * step, -hi - step, hi)


def straight_through(w, q):
    """Forward value ``q``, gradient of the identity."""
    return w + jax.lax.stop_gradient(q - w)


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def matmul_high(a, b):
    """float32 matmul in three bfloat16 passes (``high`` precision):
    a_hi b_hi + a_hi b_lo + a_lo b_hi, each product exact, summed in
    float32. Written out so that it means the same on every backend."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    d = lambda x, y: jnp.matmul(x, y, precision=HIGHEST)
    return d(ah, bh) + d(ah, bl) + d(al, bh)


def _high_fwd(a, b):
    return matmul_high(a, b), (a, b)


def _high_bwd(res, g):
    a, b = res
    return (matmul_high(g, jnp.swapaxes(b, -1, -2)),
            matmul_high(jnp.swapaxes(a, -1, -2), g))


matmul_high.defvjp(_high_fwd, _high_bwd)


def matmul(a, b, precision=HIGHEST):
    """``precision``: a ``jax.lax.Precision`` or ``"high"``."""
    if isinstance(precision, str) and precision == "high":
        return matmul_high(a, b)
    return jnp.matmul(a, b, precision=precision)


def mlp_logits(xq, w1, b1, w2, b2, precision=HIGHEST):
    h = matmul(xq, w1, precision) + b1
    h = jnp.where(h > 0, h, 0.0)            # relu, derivative 0 at 0
    return matmul(h, w2, precision) + b2


def init_mlp(seed: int, sizes: Sequence[int]):
    """Seeded He-normal weights with zero column means, biases 0.1."""
    key = jax.random.PRNGKey(seed)
    params = []
    for fan_in, fan_out in zip(sizes[:-1], sizes[1:]):
        key, sub = jax.random.split(key)
        w = jax.random.normal(sub, (fan_in, fan_out), jnp.float32)
        w = w * jnp.sqrt(2.0 / fan_in)
        w = w - w.mean(axis=0, keepdims=True)
        params.append((w, jnp.full((fan_out,), 0.1, jnp.float32)))
    return params


def qat_correct(xq_tr, y_tr, xq_te, y_te, dp, params, *, steps: int,
                lr: float, weight_bits: int, classes: int,
                precision=HIGHEST):
    """QAT of one individual from its quantized inputs; returns the int32
    count of correctly classified test rows."""
    def fq(p):
        (w1, b1), (w2, b2) = p
        return (straight_through(w1, po2(w1, dp, weight_bits)),
                straight_through(b1, fixed(b1, dp, weight_bits)),
                straight_through(w2, po2(w2, dp, weight_bits)),
                straight_through(b2, fixed(b2, dp, weight_bits)))

    def loss(p):
        logits = mlp_logits(xq_tr, *fq(p), precision=precision)
        logp = jax.nn.log_softmax(logits)
        return -(jax.nn.one_hot(y_tr, classes) * logp).sum(-1).mean()

    def step(carry, _):
        p, m, v, t = carry
        g = jax.grad(loss)(p)
        t = t + 1
        c1 = 1.0 - 0.9 ** t.astype(jnp.float32)
        c2 = 1.0 - 0.95 ** t.astype(jnp.float32)
        m = jax.tree_util.tree_map(lambda m_, g_: m_ * 0.9 + g_ * 0.1, m, g)
        v = jax.tree_util.tree_map(lambda v_, g_: v_ * 0.95 + g_ * g_ * 0.05,
                                   v, g)
        p = jax.tree_util.tree_map(
            lambda p_, m_, v_: p_ - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2)
                                                       + 1e-8)), p, m, v)
        return (p, m, v, t), ()

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    (params, _, _, _), _ = jax.lax.scan(
        step, (params, zeros, zeros, jnp.zeros((), jnp.int32)),
        length=steps)
    logits = mlp_logits(xq_te, *fq(params), precision=precision)
    return jnp.sum(jnp.argmax(logits, -1) == y_te, dtype=jnp.int32)


# ------------------------------------------------------ Monte-Carlo wafers
class Wafer(NamedTuple):
    """The raw randomness of S manufactured instances of a C-channel,
    N-bit ADC bank (shared by every design of a front)."""
    eps: jnp.ndarray        # (S, C, 2^N - 1) offsets in LSB units
    fault_u: jnp.ndarray    # (S, C, 2^N - 1) uniforms
    stuck_hi: jnp.ndarray   # (S, C, 2^N - 1) stuck direction
    drift: jnp.ndarray      # (S, C, 2) endpoint drift normals


def draw_wafer(seed: int, bits: int, channels: int, samples: int) -> Wafer:
    """Seeded wafer: four independent streams split from one key."""
    nodes = 2 ** bits - 1
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (samples, channels, nodes)
    return Wafer(jax.random.normal(k1, shape, jnp.float32),
                 jax.random.uniform(k2, shape, jnp.float32),
                 jax.random.bernoulli(k3, 0.5, shape),
                 jax.random.normal(k4, (samples, channels, 2), jnp.float32))


def instance_codes(x, mask, wafer: Wafer, *, bits: int, sigma_offset: float,
                   sigma_range: float, fault_rate: float,
                   vmin: float = 0.0, vmax: float = 1.0, dtype=jnp.float32):
    """Perturbed tree walk of every instance: x (M, C), mask (C, 2^N) ->
    (S, M, C) int32 leaf codes. The code position is computed in
    ``dtype`` (float32; the control passes a lower precision)."""
    n = 2 ** bits
    scale = jnp.float32(scale_of(bits, vmin, vmax))
    span = jnp.float32(n) / scale
    d_lo = sigma_range * wafer.drift[..., 0] * span            # (S, C)
    d_hi = sigma_range * wafer.drift[..., 1] * span
    lo_s = jnp.float32(vmin) + d_lo
    scale_s = scale * (span / (span + (d_hi - d_lo)))
    u = ((x[None].astype(dtype) - lo_s[:, None].astype(dtype))
         * scale_s[:, None].astype(dtype)).astype(jnp.float32)  # (S, M, C)
    m = (jnp.asarray(mask) > 0).astype(jnp.int32)
    lo = jnp.zeros(u.shape, jnp.int32)
    hi = jnp.full(u.shape, n, jnp.int32)
    # kept levels below each code, per channel: [lo, hi) holds kept
    # levels iff cs[hi] - cs[lo] > 0
    cs = jnp.concatenate([jnp.zeros((m.shape[0], 1), jnp.int32),
                          jnp.cumsum(m, axis=1)], axis=1)       # (C, n + 1)
    chan = jnp.arange(m.shape[0])[None, None, :]
    inst = jnp.arange(u.shape[0])[:, None, None]
    for depth in range(bits):
        seg = n >> depth
        mid = lo + seg // 2
        left = (cs[chan, mid] - cs[chan, lo]) > 0
        right = (cs[chan, hi] - cs[chan, mid]) > 0
        node = (2 ** depth - 1) + lo // seg                     # heap index
        # each comparator of the level as one threshold per (instance,
        # channel, node): its offset midpoint, or -inf / +inf when stuck
        # at 1 / 0, so the walk gathers one array a level
        nodes = np.arange(2 ** depth - 1, 2 ** (depth + 1) - 1)
        mids = jnp.asarray((nodes - (2 ** depth - 1)) * seg + seg // 2,
                           jnp.float32)
        t = mids + sigma_offset * wafer.eps[..., nodes]          # (S, C, k)
        stuck = jnp.where(wafer.stuck_hi[..., nodes], -jnp.inf, jnp.inf)
        t = jnp.where(wafer.fault_u[..., nodes] < fault_rate, stuck, t)
        thr = t[inst, chan, node - (2 ** depth - 1)]             # (S, M, C)
        go_right = jnp.where(left & right, u >= thr, right)
        lo = jnp.where(go_right, mid, lo)
        hi = jnp.where(go_right, hi, mid)
    return lo


def mc_correct(x, y, mask, weights, wafer: Wafer, *, bits: int,
               sigma_offset: float, sigma_range: float, fault_rate: float,
               dtype=jnp.float32, precision=HIGHEST):
    """(S,) correct test counts of one design's S instances."""
    codes = instance_codes(x, mask, wafer, bits=bits,
                           sigma_offset=sigma_offset,
                           sigma_range=sigma_range, fault_rate=fault_rate,
                           dtype=dtype)
    xq = jnp.asarray(level_value(bits, 0.0, 1.0))[codes]        # (S, M, C)
    logits = mlp_logits(xq, *weights, precision=precision)
    return jnp.sum(jnp.argmax(logits, -1) == y[None], axis=-1,
                   dtype=jnp.int32)

