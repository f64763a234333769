"""Pallas TPU kernels: Monte-Carlo non-ideal ADC evaluation
(DESIGN.md §10).

Robustness evaluation asks one question S times: what does this pruned
design compute when its comparators are perturbed? core/nonideal.py
compiles each perturbed instance into interval tables ``(lb, ub)`` in
code units plus drifted range rows, so the per-tile work is the same
compare/select sweep as the ideal kernels (adc_quantize.py) with the
one-hot ``code == k`` test replaced by the interval test
``lb_k <= u < ub_k`` — still ~2^N VPU compare/select/fma steps per
element, still HBM-bound, N <= 6 statically unrolled.

Four entries share one body:

* ``mc_adc_eval_pallas`` — one design, S perturbed instances in one
  launch: x (M, C) shared, lb/ub (S, C, 2^N), values (C, 2^N) nominal
  ladder, lo/scale (S, C) drifted rows, out (S, M, C). Grid (S, M/bm)
  with M innermost: instance s's interval tables and range rows load
  into VMEM once and stay resident while every sample tile streams past.
* ``mc_adc_eval_pallas_population`` — a whole NSGA-II generation's
  robustness in one launch: lb/ub (P, S, C, 2^N) per design, draws
  shared across designs (common random numbers), out (P, S, M, C).
  Grid (P, S, M/bm) — the compiled inner loop of the robustness-aware
  co-search objective (core/search.py).
* ``mc_adc_eval_cal_pallas`` / ``..._cal_pallas_population`` — the
  calibrated-table variants (fault-tolerance subsystem, DESIGN.md §15):
  values gain the instance axis ((S, C, 2^N), population (P, S, C, 2^N))
  because post-fabrication calibration re-bakes each instance's (and
  each design's) reconstruction ladder from its measured intervals.
  Same grid, one more per-instance table resident per step.

Range handling matches the rest of the family: the *nominal* rows are
baked from the f64-derived AdcSpec constants; drift adds per-instance
deltas that are exact zeros at ``sigma_range == 0``, so the ideal limit
of the MC path is bitwise the ideal kernels' code math. The jnp oracle is
kernels/ref.mc_adc_eval_ref; parity is bitwise for fixed draws because
both run the identical f32 compare/select arithmetic and the interval
partition leaves exactly one live term per element.

``interpret=None`` autodetects the backend; the dispatch registry's auto
policy routes to the jnp oracle off-TPU like every other entry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import envelope


def auto_block_m(m: int, c: int, n: int) -> int:
    """VMEM-heuristic M-tile for the MC family: per grid step the two
    (C, 2^N) interval tables, the (C, 2^N) ladder and the two (1, C)
    drifted rows stay resident (envelope.auto_block_m owns the shared
    budget split)."""
    return envelope.auto_block_m(m, c, 3 * c * n + 2 * c)


def _mc_tile(x, lb, ub, values, lo, scale):
    """(bm, C) tile through the interval selection sum: per-instance code
    position u against the (C, 2^N) interval tables, nominal ladder
    values out. Exactly one interval is live per element (the perturbed
    tree walk partitions the line), so the sum is exact."""
    n = lb.shape[-1]
    u = (x - lo) * scale                               # (bm, C)
    out = jnp.zeros_like(x)
    for k in range(n):                                 # static unroll
        sel = (u >= lb[:, k][None, :]) & (u < ub[:, k][None, :])
        out = out + jnp.where(sel, values[:, k][None, :], 0.0)
    return out


def _mc_kernel(x_ref, lb_ref, ub_ref, val_ref, lo_ref, scale_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                 # (bm, C)
    out = _mc_tile(x, lb_ref[0], ub_ref[0], val_ref[...],
                   lo_ref[0], scale_ref[0])
    o_ref[0] = out.astype(o_ref.dtype)


def _mc_pop_kernel(x_ref, lb_ref, ub_ref, val_ref, lo_ref, scale_ref,
                   o_ref):
    x = x_ref[...].astype(jnp.float32)                 # (bm, C)
    out = _mc_tile(x, lb_ref[0, 0], ub_ref[0, 0], val_ref[...],
                   lo_ref[0], scale_ref[0])
    o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def mc_adc_eval_pallas(x: jnp.ndarray, lb: jnp.ndarray, ub: jnp.ndarray,
                       values: jnp.ndarray, lo: jnp.ndarray,
                       scale: jnp.ndarray, *,
                       block_m: int | None = None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """x (M, C); lb/ub (S, C, 2^N); values (C, 2^N); lo/scale (S, C).
    Returns (S, M, C) — S perturbed instances in one launch."""
    if interpret is None:
        interpret = envelope.interpret_default()
    m, c = x.shape
    s, _, n = lb.shape
    bm = min(block_m, m) if block_m else auto_block_m(m, c, n)
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    grid = (s, x.shape[0] // bm)
    f32 = lambda a: a.astype(jnp.float32)
    out = pl.pallas_call(
        _mc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, c), lambda si, i: (i, 0)),
            pl.BlockSpec((1, c, n), lambda si, i: (si, 0, 0)),
            pl.BlockSpec((1, c, n), lambda si, i: (si, 0, 0)),
            pl.BlockSpec((c, n), lambda si, i: (0, 0)),
            pl.BlockSpec((1, 1, c), lambda si, i: (si, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda si, i: (si, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, c), lambda si, i: (si, i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, x.shape[0], c), x.dtype),
        interpret=interpret,
    )(x, f32(lb), f32(ub), f32(values), envelope.unit_rows(lo),
      envelope.unit_rows(scale))
    return out[:, :m]


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def mc_adc_eval_pallas_population(x: jnp.ndarray, lb: jnp.ndarray,
                                  ub: jnp.ndarray, values: jnp.ndarray,
                                  lo: jnp.ndarray, scale: jnp.ndarray, *,
                                  block_m: int | None = None,
                                  interpret: bool | None = None
                                  ) -> jnp.ndarray:
    """x (M, C); lb/ub (P, S, C, 2^N) per design; values (C, 2^N) and
    lo/scale (S, C) shared across designs (common random numbers).
    Returns (P, S, M, C) — the whole population's perturbed views in one
    (P, S, M/bm) launch, instance operands VMEM-resident across the
    inner M axis."""
    if interpret is None:
        interpret = envelope.interpret_default()
    m, c = x.shape
    p, s, _, n = lb.shape
    bm = min(block_m, m) if block_m else auto_block_m(m, c, n)
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    grid = (p, s, x.shape[0] // bm)
    f32 = lambda a: a.astype(jnp.float32)
    out = pl.pallas_call(
        _mc_pop_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, c), lambda pi, si, i: (i, 0)),
            pl.BlockSpec((1, 1, c, n), lambda pi, si, i: (pi, si, 0, 0)),
            pl.BlockSpec((1, 1, c, n), lambda pi, si, i: (pi, si, 0, 0)),
            pl.BlockSpec((c, n), lambda pi, si, i: (0, 0)),
            pl.BlockSpec((1, 1, c), lambda pi, si, i: (si, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda pi, si, i: (si, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bm, c),
                               lambda pi, si, i: (pi, si, i, 0)),
        out_shape=jax.ShapeDtypeStruct((p, s, x.shape[0], c), x.dtype),
        interpret=interpret,
    )(x, f32(lb), f32(ub), f32(values), envelope.unit_rows(lo),
      envelope.unit_rows(scale))
    return out[:, :, :m]


# ------------------------------------------ calibrated-table variants (§15)
def auto_block_m_cal(m: int, c: int, n: int) -> int:
    """VMEM-heuristic M-tile for the calibrated MC entries: one more
    per-instance (C, 2^N) table resident than the nominal family."""
    return envelope.auto_block_m(m, c, 4 * c * n + 2 * c)


def _mc_cal_kernel(x_ref, lb_ref, ub_ref, val_ref, lo_ref, scale_ref,
                   o_ref):
    x = x_ref[...].astype(jnp.float32)                 # (bm, C)
    out = _mc_tile(x, lb_ref[0], ub_ref[0], val_ref[0],
                   lo_ref[0], scale_ref[0])
    o_ref[0] = out.astype(o_ref.dtype)


def _mc_cal_pop_kernel(x_ref, lb_ref, ub_ref, val_ref, lo_ref, scale_ref,
                       o_ref):
    x = x_ref[...].astype(jnp.float32)                 # (bm, C)
    out = _mc_tile(x, lb_ref[0, 0], ub_ref[0, 0], val_ref[0, 0],
                   lo_ref[0], scale_ref[0])
    o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def mc_adc_eval_cal_pallas(x: jnp.ndarray, lb: jnp.ndarray,
                           ub: jnp.ndarray, values: jnp.ndarray,
                           lo: jnp.ndarray, scale: jnp.ndarray, *,
                           block_m: int | None = None,
                           interpret: bool | None = None) -> jnp.ndarray:
    """x (M, C); lb/ub AND values (S, C, 2^N) per instance (calibrated
    reconstruction ladders); lo/scale (S, C). Returns (S, M, C)."""
    if interpret is None:
        interpret = envelope.interpret_default()
    m, c = x.shape
    s, _, n = lb.shape
    bm = min(block_m, m) if block_m else auto_block_m_cal(m, c, n)
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    grid = (s, x.shape[0] // bm)
    f32 = lambda a: a.astype(jnp.float32)
    out = pl.pallas_call(
        _mc_cal_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, c), lambda si, i: (i, 0)),
            pl.BlockSpec((1, c, n), lambda si, i: (si, 0, 0)),
            pl.BlockSpec((1, c, n), lambda si, i: (si, 0, 0)),
            pl.BlockSpec((1, c, n), lambda si, i: (si, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda si, i: (si, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda si, i: (si, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, c), lambda si, i: (si, i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, x.shape[0], c), x.dtype),
        interpret=interpret,
    )(x, f32(lb), f32(ub), f32(values), envelope.unit_rows(lo),
      envelope.unit_rows(scale))
    return out[:, :m]


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def mc_adc_eval_cal_pallas_population(x: jnp.ndarray, lb: jnp.ndarray,
                                      ub: jnp.ndarray, values: jnp.ndarray,
                                      lo: jnp.ndarray, scale: jnp.ndarray,
                                      *, block_m: int | None = None,
                                      interpret: bool | None = None
                                      ) -> jnp.ndarray:
    """x (M, C); lb/ub/values (P, S, C, 2^N) per design and instance
    (mixed calibrated/nominal populations broadcast the nominal ladder
    into their value rows); lo/scale (S, C) shared. Returns (P, S, M, C)
    — the fault-tolerant co-search's compiled inner loop."""
    if interpret is None:
        interpret = envelope.interpret_default()
    m, c = x.shape
    p, s, _, n = lb.shape
    bm = min(block_m, m) if block_m else auto_block_m_cal(m, c, n)
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    grid = (p, s, x.shape[0] // bm)
    f32 = lambda a: a.astype(jnp.float32)
    out = pl.pallas_call(
        _mc_cal_pop_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, c), lambda pi, si, i: (i, 0)),
            pl.BlockSpec((1, 1, c, n), lambda pi, si, i: (pi, si, 0, 0)),
            pl.BlockSpec((1, 1, c, n), lambda pi, si, i: (pi, si, 0, 0)),
            pl.BlockSpec((1, 1, c, n), lambda pi, si, i: (pi, si, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda pi, si, i: (si, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda pi, si, i: (si, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bm, c),
                               lambda pi, si, i: (pi, si, i, 0)),
        out_shape=jax.ShapeDtypeStruct((p, s, x.shape[0], c), x.dtype),
        interpret=interpret,
    )(x, f32(lb), f32(ub), f32(values), envelope.unit_rows(lo),
      envelope.unit_rows(scale))
    return out[:, :, :m]
