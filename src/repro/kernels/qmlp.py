"""Pallas TPU kernels: the fused analog-frontend + classifier family.

One kernel body quantizes a (block_m, F) sample tile through a baked
code->value table (the one-hot selection sum of adc_quantize.py) and runs
the classifier forward without the xq/h intermediates ever touching HBM —
the serving hot path of the paper's deployed ADC+classifier pairs. Printed
classifiers are tiny (F, H, O <= a few hundred), so tables and weights are
fully VMEM-resident. fp32 accumulation; fp32 logits out.

Analog ranges follow adc_quantize.py: ``vmin``/``vmax`` are static (float
or per-channel tuple, spec.AdcSpec), baked at trace time into f32 (1, F)
range rows that ride as VMEM operands — per-sensor spans reach the fused
serving path with bitwise oracle parity.

Four entries share the body:

* ``bespoke_mlp_pallas``  — one design, 1-hidden-layer MLP:
      ADC-quantize -> x @ W1 + b1 (MXU) -> ReLU -> h @ W2 + b2 (MXU).
* ``bespoke_svm_pallas``  — one design, linear SVM: ADC-quantize -> x @ W + b.
* ``bespoke_mlp_bank_pallas`` / ``bespoke_svm_bank_pallas`` — an entire
  deployed Pareto front (D designs) against one shared sample batch: the
  grid is (D, M/block_m) with M innermost, mirroring
  ``adc_quantize_pallas_population`` — design d's table *and* weights load
  into VMEM once and stay resident while every sample tile streams past
  (index maps constant in the inner grid axis), out (D, M, O). This is the
  fused multi-design serving engine (core/deploy.py, launch/
  serve_classifier.py); under a sharded bank D is the local design slice.

``interpret=None`` (default) autodetects the backend via
``envelope.interpret_default`` — compiled on TPU, interpret elsewhere —
the same convention the dispatch registry (kernels/dispatch.py) applies
uniformly for every wrapped entry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import envelope
from repro.kernels.adc_quantize import _range_rows


def auto_block_m_mlp(m: int, f: int, n: int, h: int, o: int) -> int:
    """VMEM-heuristic M-tile for the fused MLP entries: the (F, 2^N)
    table, both weight matrices/biases and the two (1, F) range rows stay
    resident per grid step (envelope.auto_block_m owns the budget split).
    Bank launches keep one design's operands resident at a time, so the
    same footprint applies."""
    resident = f * n + f * h + h + h * o + o + 2 * f
    return envelope.auto_block_m(m, f, resident)


def auto_block_m_svm(m: int, f: int, n: int, o: int) -> int:
    """VMEM-heuristic M-tile for the fused SVM entries (resident: table,
    (F, O) weights, bias, range rows)."""
    return envelope.auto_block_m(m, f, f * n + f * o + o + 2 * f)


def _dequant(x, table, lo, scale, *, bits: int):
    """(bm, F) tile + (F, 2^bits) table + (1, F) range rows -> quantized
    tile, as the one-hot selection sum (gathers are weak on the TPU VPU;
    N<=6 unrolls to pure compare/select/fma)."""
    n = 2 ** bits
    code = jnp.clip(jnp.floor((x - lo) * scale), 0.0, float(n - 1))
    xq = jnp.zeros_like(x)
    for k in range(n):                                  # static unroll
        xq = xq + jnp.where(code == float(k), table[:, k][None, :], 0.0)
    return xq


def _mlp_forward(xq, w1, b1, w2, b2):
    """Biases arrive as (1, H) / (1, O) rows (see ``_bias_rows``)."""
    h = jnp.dot(xq, w1, preferred_element_type=jnp.float32,
                precision=envelope.MATMUL_PRECISION)
    h = jnp.maximum(h + b1, 0.0)
    o = jnp.dot(h, w2, preferred_element_type=jnp.float32,
                precision=envelope.MATMUL_PRECISION)
    return o + b2


def _mlp_kernel(x_ref, table_ref, lo_ref, scale_ref, w1_ref, b1_ref, w2_ref,
                b2_ref, o_ref, *, bits: int):
    xq = _dequant(x_ref[...].astype(jnp.float32), table_ref[...],
                  lo_ref[...], scale_ref[...], bits=bits)
    o_ref[...] = _mlp_forward(xq, w1_ref[...], b1_ref[...], w2_ref[...],
                              b2_ref[...])


def _svm_kernel(x_ref, table_ref, lo_ref, scale_ref, w_ref, b_ref, o_ref, *,
                bits: int):
    xq = _dequant(x_ref[...].astype(jnp.float32), table_ref[...],
                  lo_ref[...], scale_ref[...], bits=bits)
    o = jnp.dot(xq, w_ref[...], preferred_element_type=jnp.float32,
                precision=envelope.MATMUL_PRECISION)
    o_ref[...] = o + b_ref[...]


def _mlp_bank_kernel(x_ref, table_ref, lo_ref, scale_ref, w1_ref, b1_ref,
                     w2_ref, b2_ref, o_ref, *, bits: int):
    """Bank tile: x (bm, F) shared, per-design operands carry a leading
    1-axis (the current design), range rows shared, out (1, bm, O)."""
    xq = _dequant(x_ref[...].astype(jnp.float32), table_ref[0],
                  lo_ref[...], scale_ref[...], bits=bits)
    o_ref[0] = _mlp_forward(xq, w1_ref[0], b1_ref[0], w2_ref[0], b2_ref[0])


def _svm_bank_kernel(x_ref, table_ref, lo_ref, scale_ref, w_ref, b_ref,
                     o_ref, *, bits: int):
    xq = _dequant(x_ref[...].astype(jnp.float32), table_ref[0],
                  lo_ref[...], scale_ref[...], bits=bits)
    o = jnp.dot(xq, w_ref[0], preferred_element_type=jnp.float32,
                precision=envelope.MATMUL_PRECISION)
    o_ref[0] = o + b_ref[0]


def _pad_batch(x, block_m: int):
    m = x.shape[0]
    bm = min(block_m, m)
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, bm


def _f32(*arrays):
    return tuple(a.astype(jnp.float32) for a in arrays)


def _row_specs(c: int, ngrid: int):
    """BlockSpecs for the two (1, C) range-row operands (constant index
    maps — the rows stay VMEM-resident across the whole grid)."""
    if ngrid == 1:
        idx = lambda i: (0, 0)
    else:
        idx = lambda di, i: (0, 0)
    return [pl.BlockSpec((1, c), idx), pl.BlockSpec((1, c), idx)]


@functools.partial(jax.jit,
                   static_argnames=("bits", "vmin", "vmax", "block_m",
                                    "interpret"))
def bespoke_mlp_pallas(x, table, w1, b1, w2, b2, *, bits: int,
                       vmin=0.0, vmax=1.0,
                       block_m: int | None = None,
                       interpret: bool | None = None):
    """x (M, F), table (F, 2^bits), 1-hidden-layer weights -> (M, O) logits.
    ``block_m=None`` auto-sizes the tile from the VMEM budget (the
    dispatch registry may override it with a tuned value)."""
    if interpret is None:
        interpret = envelope.interpret_default()
    m, f = x.shape
    h = w1.shape[1]
    o = w2.shape[1]
    lo, scale = _range_rows(bits, vmin, vmax, f)
    x, bm = _pad_batch(x, block_m or auto_block_m_mlp(m, f, 2 ** bits, h, o))
    grid = (x.shape[0] // bm,)
    out = pl.pallas_call(
        functools.partial(_mlp_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, f), lambda i: (i, 0)),
            pl.BlockSpec((f, 2 ** bits), lambda i: (0, 0)),
            *_row_specs(f, 1),
            pl.BlockSpec((f, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((h, o), lambda i: (0, 0)),
            pl.BlockSpec((1, o), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, o), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], o), jnp.float32),
        interpret=interpret,
    )(x, *_f32(table), jnp.asarray(lo), jnp.asarray(scale),
      *_f32(w1), envelope.unit_rows(b1), *_f32(w2), envelope.unit_rows(b2))
    return out[:m]


@functools.partial(jax.jit,
                   static_argnames=("bits", "vmin", "vmax", "block_m",
                                    "interpret"))
def bespoke_svm_pallas(x, table, w, b, *, bits: int,
                       vmin=0.0, vmax=1.0,
                       block_m: int | None = None,
                       interpret: bool | None = None):
    """x (M, F), table (F, 2^bits), SVM weights (F, O)/(O,) -> (M, O)."""
    if interpret is None:
        interpret = envelope.interpret_default()
    m, f = x.shape
    o = w.shape[1]
    lo, scale = _range_rows(bits, vmin, vmax, f)
    x, bm = _pad_batch(x, block_m or auto_block_m_svm(m, f, 2 ** bits, o))
    grid = (x.shape[0] // bm,)
    out = pl.pallas_call(
        functools.partial(_svm_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, f), lambda i: (i, 0)),
            pl.BlockSpec((f, 2 ** bits), lambda i: (0, 0)),
            *_row_specs(f, 1),
            pl.BlockSpec((f, o), lambda i: (0, 0)),
            pl.BlockSpec((1, o), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, o), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], o), jnp.float32),
        interpret=interpret,
    )(x, *_f32(table), jnp.asarray(lo), jnp.asarray(scale), *_f32(w),
      envelope.unit_rows(b))
    return out[:m]


@functools.partial(jax.jit,
                   static_argnames=("bits", "vmin", "vmax", "block_m",
                                    "interpret"))
def bespoke_mlp_bank_pallas(x, tables, w1, b1, w2, b2, *, bits: int,
                            vmin=0.0, vmax=1.0,
                            block_m: int | None = None,
                            interpret: bool | None = None):
    """Shared x (M, F); per-design tables (D, F, 2^bits) and weights
    (D, F, H)/(D, H)/(D, H, O)/(D, O). Returns (D, M, O) — the whole
    deployed front's logits in one launch, design operands VMEM-resident
    across the inner M axis."""
    if interpret is None:
        interpret = envelope.interpret_default()
    m, f = x.shape
    d = tables.shape[0]
    h = w1.shape[2]
    o = w2.shape[2]
    lo, scale = _range_rows(bits, vmin, vmax, f)
    x, bm = _pad_batch(x, block_m or auto_block_m_mlp(m, f, 2 ** bits, h, o))
    grid = (d, x.shape[0] // bm)
    out = pl.pallas_call(
        functools.partial(_mlp_bank_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, f), lambda di, i: (i, 0)),
            pl.BlockSpec((1, f, 2 ** bits), lambda di, i: (di, 0, 0)),
            *_row_specs(f, 2),
            pl.BlockSpec((1, f, h), lambda di, i: (di, 0, 0)),
            pl.BlockSpec((1, 1, h), lambda di, i: (di, 0, 0)),
            pl.BlockSpec((1, h, o), lambda di, i: (di, 0, 0)),
            pl.BlockSpec((1, 1, o), lambda di, i: (di, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, o), lambda di, i: (di, i, 0)),
        out_shape=jax.ShapeDtypeStruct((d, x.shape[0], o), jnp.float32),
        interpret=interpret,
    )(x, *_f32(tables), jnp.asarray(lo), jnp.asarray(scale),
      *_f32(w1), envelope.unit_rows(b1), *_f32(w2), envelope.unit_rows(b2))
    return out[:, :m]


@functools.partial(jax.jit,
                   static_argnames=("bits", "vmin", "vmax", "block_m",
                                    "interpret"))
def bespoke_svm_bank_pallas(x, tables, w, b, *, bits: int,
                            vmin=0.0, vmax=1.0,
                            block_m: int | None = None,
                            interpret: bool | None = None):
    """Shared x (M, F); per-design tables (D, F, 2^bits), w (D, F, O),
    b (D, O). Returns (D, M, O)."""
    if interpret is None:
        interpret = envelope.interpret_default()
    m, f = x.shape
    d = tables.shape[0]
    o = w.shape[2]
    lo, scale = _range_rows(bits, vmin, vmax, f)
    x, bm = _pad_batch(x, block_m or auto_block_m_svm(m, f, 2 ** bits, o))
    grid = (d, x.shape[0] // bm)
    out = pl.pallas_call(
        functools.partial(_svm_bank_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, f), lambda di, i: (i, 0)),
            pl.BlockSpec((1, f, 2 ** bits), lambda di, i: (di, 0, 0)),
            *_row_specs(f, 2),
            pl.BlockSpec((1, f, o), lambda di, i: (di, 0, 0)),
            pl.BlockSpec((1, 1, o), lambda di, i: (di, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, o), lambda di, i: (di, i, 0)),
        out_shape=jax.ShapeDtypeStruct((d, x.shape[0], o), jnp.float32),
        interpret=interpret,
    )(x, *_f32(tables), jnp.asarray(lo), jnp.asarray(scale), *_f32(w),
      envelope.unit_rows(b))
    return out[:, :m]
