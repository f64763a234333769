"""Analytic per-entry cost model + roofline estimates (DESIGN.md §11).

Every dispatch-registry entry gets a closed-form FLOP/byte count as a
function of its ``Workload`` (P, D, S, C, M, bits) and the ``block_m``
tile choice, in the spirit of dace's ``RooflineModel`` — an analytic
machine-model-backed estimate, not a measurement. Three consumers:

* the **autotuner** (repro/perf/autotune.py) uses ``roofline_estimate``
  to order candidate tiles and ``heuristic_block_m`` as the fallback
  every tuned choice is compared against;
* the **property tests** sweep the registry and check the counts are
  positive, monotone in every batch axis, and that the MXU component
  (``Cost.dot_flops``) agrees with the HLO dot-flops parser
  (launch/analysis.py) on small shapes;
* the **benchmarks** stamp estimates next to measurements so a perf
  regression can be judged against what the hardware should deliver.

The FLOP accounting follows the kernel bodies literally: the one-hot
selection sum costs ~3 VPU ops per level per element (compare, select,
accumulate) on top of the ~5-op code computation; the MC interval test
costs ~5 per level (two compares, and, select, accumulate) on top of the
2-op position math; classifier matmuls are 2*K MACs on the MXU. HBM
bytes count every operand stream the grid actually performs: x and out
tiles re-stream per outer grid index, grid-constant operands (tables,
weights, rows) are fetched once per outer index — exactly the BlockSpec
index maps of the kernels. Everything is f32 (4 bytes).

``roofline_estimate`` returns the record shape benchmarks/roofline.py
renders (compute_s / memory_s / collective_s / dominant /
roofline_fraction), plus the per-tile pipeline overhead term that makes
the estimate sensitive to ``block_m`` — the quantity the autotuner
actually ranks by.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

from repro.perf.workload import Workload

F32 = 4  # bytes

# Per-element VPU op counts of the two tile bodies (see module docstring).
_DEQUANT_BASE = 5     # sub, mul, floor, clip lo, clip hi
_DEQUANT_PER_LEVEL = 3   # compare, select, accumulate
_MC_BASE = 2          # sub, mul
_MC_PER_LEVEL = 5     # two compares, and, select, accumulate


@dataclasses.dataclass(frozen=True)
class MachineModel:
    """Peak rates of one backend — the roofline ceilings. ``tile_overhead_s``
    is the fixed per-grid-step pipeline cost (tile setup + VMEM swap) that
    penalises tiny ``block_m`` choices; it is what makes the analytic
    estimate non-trivially dependent on the tile size."""
    name: str
    peak_flops: float        # FLOP/s (f32 vector or MXU as labelled)
    hbm_bw: float            # bytes/s off-chip
    vmem_bw: float           # bytes/s on-chip (diagnostic only)
    tile_overhead_s: float   # seconds per grid step


# TPU v5e figures mirror launch/analysis.py; the cpu/gpu rows are coarse
# single-socket / single-card placeholders so estimates stay finite (and
# honest about being estimates) off-TPU.
MACHINE_MODELS: Dict[str, MachineModel] = {
    "tpu": MachineModel("tpu-v5e", 197e12, 819e9, 22e12, 1.0e-6),
    "gpu": MachineModel("gpu-generic", 50e12, 1000e9, 10e12, 3.0e-6),
    "cpu": MachineModel("cpu-host", 2e11, 50e9, 2e11, 5.0e-6),
}


# device_kind strings of the chip the "tpu" row describes
TPU_V5E_KINDS = ("TPU v5 lite", "TPU v5e")


def machine_model(backend: Optional[str] = None) -> MachineModel:
    """The machine model for ``backend`` (default: the active jax
    backend). A backend without a row, or an active TPU that is not a
    v5e, raises: pricing a device with another device's peaks would
    make every estimate silently wrong."""
    if backend is None:
        import jax
        backend = jax.default_backend()
        kind = jax.devices()[0].device_kind
        if backend == "tpu" and kind not in TPU_V5E_KINDS:
            raise ValueError(f"no machine peaks for TPU device kind "
                             f"{kind!r} (known: {TPU_V5E_KINDS})")
    try:
        return MACHINE_MODELS[backend]
    except KeyError:
        raise ValueError(f"no machine peaks for backend {backend!r} "
                         f"(known: {sorted(MACHINE_MODELS)})") from None


@dataclasses.dataclass(frozen=True)
class Cost:
    """Analytic cost of one kernel launch. ``flops`` is the total
    (VPU + MXU); ``dot_flops`` is the MXU matmul share alone — the part
    an HLO dot-flops parse of the jnp oracle sees."""
    flops: float
    dot_flops: float
    hbm_bytes: float
    vmem_bytes: float        # resident + streamed working set per step
    grid_steps: int

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.hbm_bytes, 1.0)

    def to_meta(self) -> Dict:
        d = dataclasses.asdict(self)
        d["arithmetic_intensity"] = self.arithmetic_intensity
        return d


def heuristic_block_m(w: Workload) -> int:
    """The VMEM-budget tile the kernel family would pick on its own for
    this workload — the registry's fallback when no tuned entry matches,
    and the baseline every autotuned choice is measured against. Delegates
    to the same per-family helpers the kernels use, so heuristic and
    kernel never disagree."""
    from repro.kernels import adc_quantize, mc_eval, qmlp
    n = w.levels
    if w.entry in ("adc_quantize", "adc_quantize_population"):
        return adc_quantize.auto_block_m(w.m, w.c, n)
    if w.entry in ("mc_eval", "mc_eval_population"):
        return mc_eval.auto_block_m(w.m, w.c, n)
    if w.entry in ("mc_eval_cal", "mc_eval_cal_population"):
        return mc_eval.auto_block_m_cal(w.m, w.c, n)
    if w.entry in ("bespoke_mlp", "classifier_bank_mlp"):
        return qmlp.auto_block_m_mlp(w.m, w.c, n, w.h, w.o)
    if w.entry in ("bespoke_svm", "classifier_bank_svm"):
        return qmlp.auto_block_m_svm(w.m, w.c, n, w.o)
    raise ValueError(f"no block-size heuristic for entry {w.entry!r}")


def _steps(m: int, bm: int) -> int:
    return math.ceil(m / max(min(bm, m), 1))


def cost(w: Workload, block_m: Optional[int] = None) -> Cost:
    """FLOP/byte counts for one launch of ``w.entry`` at tile ``block_m``
    (default: the VMEM heuristic). Counts are monotone (non-decreasing)
    in each of M, P, S, D and positive for every valid workload."""
    bm = block_m if block_m else heuristic_block_m(w)
    n, c, m = w.levels, w.c, w.m
    elems = m * c
    dequant_flops = elems * (_DEQUANT_BASE + _DEQUANT_PER_LEVEL * n)
    mc_flops = elems * (_MC_BASE + _MC_PER_LEVEL * n)
    table_b = c * n * F32
    rows_b = 2 * c * F32
    xio_b = 2 * elems * F32                      # x stream + out stream
    inner = _steps(m, bm)
    if w.entry == "adc_quantize":
        return Cost(dequant_flops, 0.0, xio_b + table_b + rows_b,
                    (2 * min(bm, m) * c + c * n + 2 * c) * F32, inner)
    if w.entry == "adc_quantize_population":
        # x re-streams per individual; each table is fetched once (the
        # inner-axis-constant index map keeps it VMEM-resident).
        return Cost(w.p * dequant_flops, 0.0,
                    w.p * (xio_b + table_b) + rows_b,
                    (2 * min(bm, m) * c + c * n + 2 * c) * F32,
                    w.p * inner)
    if w.entry == "mc_eval":
        return Cost(w.s * mc_flops, 0.0,
                    w.s * (xio_b + 2 * table_b + rows_b) + table_b,
                    (2 * min(bm, m) * c + 3 * c * n + 2 * c) * F32,
                    w.s * inner)
    if w.entry == "mc_eval_population":
        return Cost(w.p * w.s * mc_flops, 0.0,
                    w.p * w.s * (xio_b + 2 * table_b)
                    + w.s * rows_b + table_b,
                    (2 * min(bm, m) * c + 3 * c * n + 2 * c) * F32,
                    w.p * w.s * inner)
    if w.entry == "mc_eval_cal":
        # per-instance value tables: three (C, 2^N) streams per instance
        return Cost(w.s * mc_flops, 0.0,
                    w.s * (xio_b + 3 * table_b + rows_b),
                    (2 * min(bm, m) * c + 4 * c * n + 2 * c) * F32,
                    w.s * inner)
    if w.entry == "mc_eval_cal_population":
        return Cost(w.p * w.s * mc_flops, 0.0,
                    w.p * w.s * (xio_b + 3 * table_b) + w.s * rows_b,
                    (2 * min(bm, m) * c + 4 * c * n + 2 * c) * F32,
                    w.p * w.s * inner)
    # classifier entries: dequant + MXU matmuls; logits stream out.
    if w.entry in ("bespoke_mlp", "classifier_bank_mlp"):
        dot = 2.0 * m * c * w.h + 2.0 * m * w.h * w.o
        vpu = dequant_flops + 2 * m * w.h + m * w.o      # bias+relu, bias
        weights_b = (c * w.h + w.h + w.h * w.o + w.o) * F32
        out_b = m * w.o * F32
    elif w.entry in ("bespoke_svm", "classifier_bank_svm"):
        dot = 2.0 * m * c * w.o
        vpu = dequant_flops + m * w.o                     # bias add
        weights_b = (c * w.o + w.o) * F32
        out_b = m * w.o * F32
    else:
        raise ValueError(f"no cost rule for kernel entry {w.entry!r}")
    d = w.d
    per_design_b = elems * F32 + out_b + table_b + weights_b
    return Cost(d * (dot + vpu), d * dot, d * per_design_b + rows_b,
                (min(bm, m) * (c + w.o) + c * n + 2 * c) * F32
                + weights_b,
                d * inner)


def roofline_estimate(w: Workload, block_m: Optional[int] = None,
                      machine: Optional[MachineModel] = None,
                      backend: Optional[str] = None) -> Dict:
    """Roofline-model estimate of one launch, in the record shape
    benchmarks/roofline.py renders: the compute/memory/collective terms,
    the dominant one, and the achievable fraction — plus the per-tile
    overhead term and the estimated wall time the autotuner ranks
    candidate ``block_m`` values by (``estimated_s``). Single-chip, so
    the collective term is structurally zero."""
    mm = machine if machine is not None else machine_model(backend)
    bm = block_m if block_m else heuristic_block_m(w)
    cst = cost(w, bm)
    compute_s = cst.flops / mm.peak_flops
    memory_s = cst.hbm_bytes / mm.hbm_bw
    overhead_s = cst.grid_steps * mm.tile_overhead_s
    bound = max(compute_s, memory_s)
    dominant = "compute" if compute_s >= memory_s else "memory"
    if overhead_s > bound:
        dominant = "overhead"
    return {
        "entry": w.entry, "workload": w.to_meta(), "block_m": bm,
        "machine": mm.name,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": 0.0, "overhead_s": overhead_s,
        "dominant": dominant,
        "model_flops_global": cst.flops,
        "useful_flops_ratio": 1.0,
        "roofline_fraction": min((cst.flops / mm.peak_flops)
                                 / max(bound + overhead_s, 1e-30), 1.0),
        "arithmetic_intensity": cst.arithmetic_intensity,
        "estimated_s": bound + overhead_s,
        "cost": cst.to_meta(),
    }
