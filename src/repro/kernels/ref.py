"""Pure-jnp oracles for every Pallas kernel (kernel-vs-ref allclose tests).
These are the *semantic* references; `repro.core.adc` is the modelling API
and tests assert the three agree.

Range handling: ``vmin``/``vmax`` may be scalars or per-channel (length-C)
sequences (spec.AdcSpec). Codes derive from the exact same f64-computed
``(vmin_row, scale_row)`` constants the Pallas kernels bake at trace time
(core/adc.range_rows), so oracle-vs-kernel parity is bitwise — including
the heterogeneous-sensor per-channel-range scenario — not merely allclose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import adc
from repro.kernels.envelope import MATMUL_PRECISION


def value_table(mask: jnp.ndarray, bits: int, vmin=0.0, vmax=1.0,
                mode: str = "tree") -> jnp.ndarray:
    """Per-channel code->reconstruction-value table: VALUES[..., c, k] is
    the analog value the pruned ADC returns for raw code k on channel c.
    mask: (C, 2^bits) or population-batched (P, C, 2^bits) — the LUT walk
    in ``adc`` is shape-polymorphic over leading axes (DESIGN.md §2), so a
    whole NSGA-II generation's tables are built in one call. Per-channel
    ``vmin``/``vmax`` give each channel its own value ladder. Returns a
    float32 array of the mask's shape (a channel-shared 1-D mask with
    per-channel ladders expands to (C, 2^bits))."""
    values = adc.level_values(bits, vmin, vmax)
    lut_fn = adc.tree_lut if mode == "tree" else adc._nearest_lut
    lut = lut_fn(mask.astype(jnp.int32))                  # (..., C, n)
    if values.ndim == 1:
        return values[lut]
    if lut.ndim == 1:
        # channel-shared mask + per-channel ladders -> (C, n) table
        # (mirrors adc.adc_quantize's 1-D-mask semantics)
        return values[:, lut]
    # per-channel ladders: table[..., c, k] = values[c, lut[..., c, k]]
    if lut.shape[-2] != values.shape[0]:
        raise ValueError(f"mask has {lut.shape[-2]} channels but the "
                         f"per-channel range pins {values.shape[0]}")
    return jnp.take_along_axis(jnp.broadcast_to(values, lut.shape), lut,
                               axis=-1)


def _codes(x: jnp.ndarray, bits: int, vmin, vmax) -> jnp.ndarray:
    """Raw (unpruned) codes via the canonical row constants — the shared
    front half of every oracle below."""
    n = 2 ** bits
    lo, scale = adc.range_rows(bits, vmin, vmax, x.shape[-1])
    code = jnp.floor((x - lo[0]) * scale[0])
    return jnp.clip(code, 0, n - 1).astype(jnp.int32)


def adc_quantize_ref(x: jnp.ndarray, table: jnp.ndarray, bits: int,
                     vmin=0.0, vmax=1.0) -> jnp.ndarray:
    """x: (M, C); table: (C, 2^bits) from value_table. Returns (M, C)."""
    code = _codes(x, bits, vmin, vmax)                     # (M, C)
    return jnp.take_along_axis(table.T, code, axis=0).astype(x.dtype)


def adc_quantize_ref_population(x: jnp.ndarray, tables: jnp.ndarray,
                                bits: int, vmin=0.0, vmax=1.0
                                ) -> jnp.ndarray:
    """Population-batched oracle: one shared sample batch through P pruned
    ADC banks. x: (M, C); tables: (P, C, 2^bits). Returns (P, M, C) —
    out[p, m, c] = tables[p, c, code(x[m, c])]."""
    code = _codes(x, bits, vmin, vmax)                     # (M, C)
    taker = lambda t: jnp.take_along_axis(t.T, code, axis=0)
    return jax.vmap(taker)(tables).astype(x.dtype)


def mc_adc_eval_ref(x: jnp.ndarray, lb: jnp.ndarray, ub: jnp.ndarray,
                    values: jnp.ndarray, lo: jnp.ndarray,
                    scale: jnp.ndarray) -> jnp.ndarray:
    """Monte-Carlo non-ideal ADC oracle (core/nonideal.py operands):
    x (M, C) shared samples; lb/ub (S, C, 2^N) per-instance interval
    tables in code units; values (C, 2^N) nominal reconstruction ladder;
    lo/scale (S, C) per-instance drifted range rows. Returns (S, M, C):
    ``out[s, m, c] = values[c, k]`` for the unique kept leaf ``k`` with
    ``lb[s, c, k] <= (x[m, c] - lo[s, c]) * scale[s, c] < ub[s, c, k]``
    (the perturbed pruned-tree walk; regions partition the line, so the
    selection sum has exactly one live term and is exact)."""
    u = (x[None, :, :] - lo[:, None, :]) * scale[:, None, :]   # (S, M, C)
    sel = ((u[..., None] >= lb[:, None, :, :])
           & (u[..., None] < ub[:, None, :, :]))               # (S, M, C, n)
    return jnp.sum(jnp.where(sel, values[None, None, :, :], 0.0),
                   axis=-1).astype(x.dtype)


def mc_adc_eval_ref_population(x: jnp.ndarray, lb: jnp.ndarray,
                               ub: jnp.ndarray, values: jnp.ndarray,
                               lo: jnp.ndarray, scale: jnp.ndarray
                               ) -> jnp.ndarray:
    """Population-batched MC oracle: lb/ub carry a leading design axis
    (P, S, C, 2^N); draws (values/lo/scale) are shared across designs
    (common random numbers — core/nonideal.Draws). Returns (P, S, M, C)."""
    fn = lambda l, u_: mc_adc_eval_ref(x, l, u_, values, lo, scale)
    return jax.vmap(fn)(lb, ub)


def mc_adc_eval_cal_ref(x: jnp.ndarray, lb: jnp.ndarray, ub: jnp.ndarray,
                        values: jnp.ndarray, lo: jnp.ndarray,
                        scale: jnp.ndarray) -> jnp.ndarray:
    """Calibrated-table MC oracle (faulttol.calibrate operands): like
    ``mc_adc_eval_ref`` but each perturbed instance reconstructs through
    its own re-baked value table — values (S, C, 2^N) instead of a shared
    (C, 2^N) nominal ladder. Returns (S, M, C)."""
    u = (x[None, :, :] - lo[:, None, :]) * scale[:, None, :]   # (S, M, C)
    sel = ((u[..., None] >= lb[:, None, :, :])
           & (u[..., None] < ub[:, None, :, :]))               # (S, M, C, n)
    return jnp.sum(jnp.where(sel, values[:, None, :, :], 0.0),
                   axis=-1).astype(x.dtype)


def mc_adc_eval_cal_ref_population(x: jnp.ndarray, lb: jnp.ndarray,
                                   ub: jnp.ndarray, values: jnp.ndarray,
                                   lo: jnp.ndarray, scale: jnp.ndarray
                                   ) -> jnp.ndarray:
    """Population-batched calibrated-table MC oracle: lb/ub/values carry
    the design axis (P, S, C, 2^N) — per-design tables let one launch mix
    calibrated and uncalibrated designs; lo/scale stay shared (common
    random numbers). Returns (P, S, M, C)."""
    fn = lambda l, u_, v: mc_adc_eval_cal_ref(x, l, u_, v, lo, scale)
    return jax.vmap(fn)(lb, ub, values)


def bespoke_mlp_ref(x: jnp.ndarray, table: jnp.ndarray, bits: int,
                    w1: jnp.ndarray, b1: jnp.ndarray,
                    w2: jnp.ndarray, b2: jnp.ndarray,
                    vmin=0.0, vmax=1.0) -> jnp.ndarray:
    """Fused analog-frontend + printed-MLP forward:
    logits = relu(ADC(x) @ w1 + b1) @ w2 + b2."""
    xq = adc_quantize_ref(x, table, bits, vmin, vmax)
    h = jax.nn.relu(jnp.dot(xq, w1, precision=MATMUL_PRECISION) + b1)
    return jnp.dot(h, w2, precision=MATMUL_PRECISION) + b2


def bespoke_svm_ref(x: jnp.ndarray, table: jnp.ndarray, bits: int,
                    w: jnp.ndarray, b: jnp.ndarray,
                    vmin=0.0, vmax=1.0) -> jnp.ndarray:
    """Fused analog-frontend + linear-SVM forward: scores = ADC(x) @ w + b."""
    xq = adc_quantize_ref(x, table, bits, vmin, vmax)
    return jnp.dot(xq, w, precision=MATMUL_PRECISION) + b


def bespoke_mlp_bank_ref(x: jnp.ndarray, tables: jnp.ndarray, bits: int,
                         w1: jnp.ndarray, b1: jnp.ndarray,
                         w2: jnp.ndarray, b2: jnp.ndarray,
                         vmin=0.0, vmax=1.0) -> jnp.ndarray:
    """Multi-design bank oracle: one shared sample batch through D deployed
    MLP designs. x (M, F); tables (D, F, 2^bits); weights stacked over D.
    Returns (D, M, O) — row d == ``bespoke_mlp_ref`` on design d."""
    fn = lambda t, a1, c1, a2, c2: bespoke_mlp_ref(x, t, bits, a1, c1, a2,
                                                   c2, vmin, vmax)
    return jax.vmap(fn)(tables, w1, b1, w2, b2)


def bespoke_svm_bank_ref(x: jnp.ndarray, tables: jnp.ndarray, bits: int,
                         w: jnp.ndarray, b: jnp.ndarray,
                         vmin=0.0, vmax=1.0) -> jnp.ndarray:
    """Multi-design bank oracle for SVM designs: (D, M, O)."""
    fn = lambda t, a, c: bespoke_svm_ref(x, t, bits, a, c, vmin, vmax)
    return jax.vmap(fn)(tables, w, b)
