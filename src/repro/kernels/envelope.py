"""Shared static limits + backend detection for the fused kernel family.

One place answers the three questions the dispatch registry
(kernels/dispatch.py) asks for every registered entry:

* ``interpret_default()`` — compiled (non-interpret) Pallas kernels are the
  default on TPU; everywhere else interpret mode executes the kernel bodies
  in Python (correct but slow — per-tile Python, so the registry's auto
  policy routes every entry to the jnp oracles off-TPU).
* the static envelope the kernels were written for: the one-hot selection
  sum unrolls 2^bits compare/select/fma steps (``MAX_UNROLL_BITS``) and a
  (C, 2^N) table plus a (block_m, C) tile must fit a VMEM budget
  (``MAX_CHANNELS``). Outside the envelope the registry routes to the jnp
  oracles (kernels/ref.py) — same math, no tiling assumptions.
* the VMEM-budget M-tile heuristic (``auto_block_m``) every kernel family
  sizes its grid from when no explicit/tuned ``block_m`` is given: each
  family states only its resident-operand footprint (tables, weights,
  interval tables) and the shared formula splits the remaining budget
  between the streamed x/out tiles. The perf layer (repro/perf) uses the
  SAME helper as the fallback the autotuner must beat, so heuristic and
  tuned choices are always comparable.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MAX_UNROLL_BITS = 6
MAX_CHANNELS = 4096

# The f32 matmul precision of the fused classifier kernels AND of every
# forward they are compared with (the QAT forward in models/, the jnp
# oracles in kernels/ref.py). On TPU, XLA's default f32 dot is one bf16
# pass while Mosaic's need not be; with po2-quantized weights and
# few-bit ADC levels every product and partial sum is exact at f32
# precision, so stating HIGHEST on both sides keeps search fitness,
# exported and served accuracy equal bit for bit on the chip.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST

# ~2 MB of f32 VMEM for the streamed x + out tiles and the resident
# operands: half a conservative 4 MB working budget, leaving room for the
# double-buffered next tile the grid pipeline prefetches.
VMEM_BUDGET_F32 = (1 << 21) // 4


def interpret_default() -> bool:
    """True when Pallas should run in interpret mode (any non-TPU backend)."""
    return jax.default_backend() != "tpu"


def unit_rows(a):
    """(..., X) -> f32 (..., 1, X). Mosaic tiles the last two block dims,
    so a per-design or per-instance row (bank biases, MC range rows)
    must be a whole (1, X) slab rather than a (1, X) slice of a
    (D|S, X) array."""
    return a.astype(jnp.float32)[..., None, :]


def outside_envelope(bits: int, channels: int) -> bool:
    """True when (bits, C) exceeds what the fused kernels statically
    unroll/tile — callers then use the jnp oracle instead."""
    return bits > MAX_UNROLL_BITS or channels > MAX_CHANNELS


def auto_block_m(m: int, c: int, resident_floats: int) -> int:
    """Largest M-tile (f32-sublane aligned, <= 4096) such that the (bm, C)
    x-tile + (bm, C) out-tile + ``resident_floats`` grid-constant operands
    (tables/weights/rows, fetched once per outer grid index) fit
    ``VMEM_BUDGET_F32``. Clamped to ``m`` — a single tile covers small
    batches. This is the one VMEM heuristic every kernel family falls back
    to when the dispatch registry has no tuned ``block_m`` for the shape."""
    avail = max(VMEM_BUDGET_F32 - resident_floats, 0)
    bm = max(avail // (2 * c), 8)
    bm = max((bm // 8) * 8, 8)
    return min(bm, 4096, m)
