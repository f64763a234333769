"""Declarative kernel dispatch registry (DESIGN.md §9).

Every fused-classifier entry used to hand-copy the same four-way routing
in ops.py — envelope check, interpret autodetect, oracle fallback,
shard_map wrapper — and the copies drifted (the population path silently
chose the oracle in auto mode while the single-sample path ran the
interpret kernel). Here each entry is *registered once* as a
``KernelEntry``:

  name                {oracle, kernel, sharded_axes, envelope_predicate,
                       interpret_policy}

and one ``dispatch()`` resolves oracle-vs-kernel-vs-sharded uniformly for
all of them. The resolution rules, in order:

1. ``envelope_predicate(spec, channels)`` False (bits > 6 unrolls too far,
   C > 4096 busts the VMEM tile) -> jnp **oracle** (kernels/ref.py).
2. ``interpret`` explicitly True/False -> **kernel** with that flag (tests
   opt into interpret mode; TPU runs force-compile with False).
3. ``interpret=None`` (auto) -> the entry's ``interpret_policy``:
   * on TPU: compiled **kernel** (interpret=False);
   * off-TPU: ``'oracle'`` routes to the jnp oracle (interpret-mode grids
     run per-tile Python — minutes for population/bank launches).
   Every registered entry declares ``'oracle'``, so the auto behaviour is
   now *identical* across single-sample, population and bank paths
   (previously the single-sample entries ran the interpret kernel).

All entries consume **baked value tables** (spec.AdcSpec.value_table /
kernels/ref.value_table output) — the mask->table decode happens once in
the caller, never per dispatch. Each resolution is logged (INFO the first
time a distinct (entry, path) pair is chosen, DEBUG after), and
``resolve()`` returns the machine-readable ``Resolution`` record the
benchmark harness persists so perf regressions are attributable to the
path actually taken.

Kernel-path resolutions also pick the M-tile (DESIGN.md §11): a **tuned
policy** — by default the autotuned table persisted next to this module
(``tuned_tables.json``, written by repro/perf/autotune.py) — is consulted
first; when it has no entry for the (entry, shape class) pair, or the
table is missing/corrupt/stale, the kernels' own VMEM-budget heuristic
applies (``block_m=None`` forwarded to the kernel). The choice and its
provenance (``block_m_source``: 'tuned' | 'heuristic') ride on the
``Resolution`` record and are logged like the path decision. Tuning can
only change speed: ``block_m`` never enters the kernels' math, so the
bitwise kernel==oracle parity contract holds under every tuned table.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.kernels import envelope, ref
from repro.kernels.adc_quantize import (adc_quantize_pallas,
                                        adc_quantize_pallas_population)
from repro.kernels.mc_eval import (mc_adc_eval_cal_pallas,
                                   mc_adc_eval_cal_pallas_population,
                                   mc_adc_eval_pallas,
                                   mc_adc_eval_pallas_population)
from repro.kernels.qmlp import (bespoke_mlp_bank_pallas, bespoke_mlp_pallas,
                                bespoke_svm_bank_pallas, bespoke_svm_pallas)
from repro.perf.workload import Workload, workload_of

log = logging.getLogger(__name__)


def _inside_envelope(spec, channels: int) -> bool:
    """Default envelope predicate: the static unroll/VMEM-tile envelope
    shared by the whole fused kernel family (kernels/envelope.py)."""
    return not envelope.outside_envelope(spec.bits, channels)


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One registered hot-path: everything dispatch() needs, stated once.

    oracle / kernel share the uniform signature
    ``fn(x, tables, *weights, spec=..., [interpret=...])`` — adapters bind
    the concrete ref/pallas callables at registration.
    ``sharded_axes(mesh, leading_dim)`` names the mesh axes the leading
    (population/design) axis may split over, or None for entries with no
    sharded variant. ``interpret_policy`` is what auto (interpret=None)
    means off-TPU: 'oracle' | 'interpret'."""
    name: str
    oracle: Callable
    kernel: Callable
    envelope_predicate: Callable = _inside_envelope
    interpret_policy: str = "oracle"
    sharded_axes: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class Resolution:
    """The routing decision for one call — stable, JSON-able provenance
    (benchmarks/run.py records it next to every timing). ``block_m`` is
    the tuned M-tile on kernel paths resolved with a workload (None means
    'kernel picks its own VMEM heuristic'); ``block_m_source`` says where
    it came from ('tuned' | 'heuristic', None on oracle paths)."""
    entry: str
    path: str                       # 'oracle' | 'kernel'
    interpret: Optional[bool]       # None for the oracle path
    sharded: bool
    reason: str
    block_m: Optional[int] = None
    block_m_source: Optional[str] = None

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


_REGISTRY: Dict[str, KernelEntry] = {}
_LOGGED: set = set()

# ------------------------------------------------------------ tuned policy
# policy(entry_name, Workload) -> Optional[int]. Default: lazily load the
# committed tuned_tables.json via repro/perf/autotune.load_policy (which
# validates version/backend and degrades to None on any problem).
_TUNED_POLICY: Optional[Callable] = None
_TUNED_LOADED = False


def set_tuned_policy(policy: Optional[Callable]) -> None:
    """Install ``policy(entry, workload) -> Optional[int]`` as the tuned
    block_m source (None disables tuning; the heuristic then always
    applies). Overrides the default table-file lookup."""
    global _TUNED_POLICY, _TUNED_LOADED
    _TUNED_POLICY = policy
    _TUNED_LOADED = True


def reset_tuned_policy() -> None:
    """Forget any installed/cached policy; the next resolution re-reads
    the default table file."""
    global _TUNED_POLICY, _TUNED_LOADED
    _TUNED_POLICY = None
    _TUNED_LOADED = False


def _tuned_policy() -> Optional[Callable]:
    global _TUNED_POLICY, _TUNED_LOADED
    if not _TUNED_LOADED:
        from repro.perf import autotune
        _TUNED_POLICY = autotune.load_policy()
        _TUNED_LOADED = True
    return _TUNED_POLICY


def tuned_block_m(name: str, workload: Optional[Workload]
                  ) -> Tuple[Optional[int], Optional[str]]:
    """The (block_m, source) pair a kernel-path resolution stamps: the
    tuned table's choice when it has one for this (entry, shape class),
    else (None, 'heuristic') — the kernel then applies its own VMEM
    heuristic."""
    if workload is not None:
        policy = _tuned_policy()
        if policy is not None:
            bm = policy(name, workload)
            if bm is not None:
                return int(bm), "tuned"
    return None, "heuristic"


def register(entry: KernelEntry) -> KernelEntry:
    if entry.name in _REGISTRY:
        raise ValueError(f"kernel entry {entry.name!r} already registered")
    if entry.interpret_policy not in ("oracle", "interpret"):
        raise ValueError(f"unknown interpret_policy "
                         f"{entry.interpret_policy!r} for {entry.name!r}")
    _REGISTRY[entry.name] = entry
    return entry


def get(name: str) -> KernelEntry:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"no kernel entry {name!r}; registered: "
                         f"{entries()}") from None


def entries() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve(name: str, spec, channels: int,
            interpret: Optional[bool] = None,
            sharded: bool = False,
            workload: Optional[Workload] = None) -> Resolution:
    """The routing decision alone (no execution) — also the benchmark
    harness' provenance hook. Pass the call's ``workload`` to have
    kernel-path resolutions also pick the M-tile (tuned table first,
    VMEM heuristic fallback); without one, ``block_m`` stays None and
    the kernel applies its own heuristic."""
    entry = get(name)
    if not entry.envelope_predicate(spec, channels):
        return Resolution(name, "oracle", None, sharded,
                          f"outside kernel envelope (bits={spec.bits}, "
                          f"C={channels})")
    bm, bm_src = tuned_block_m(name, workload)
    if interpret is not None:
        return Resolution(name, "kernel", bool(interpret), sharded,
                          f"explicit interpret={bool(interpret)}",
                          bm, bm_src)
    if not envelope.interpret_default():
        return Resolution(name, "kernel", False, sharded,
                          "auto: TPU backend, compiled kernel", bm, bm_src)
    if entry.interpret_policy == "oracle":
        return Resolution(name, "oracle", None, sharded,
                          "auto off-TPU: interpret grids run per-tile "
                          "Python, jnp oracle instead")
    return Resolution(name, "kernel", True, sharded,
                      "auto off-TPU: interpret kernel", bm, bm_src)


def _log(res: Resolution, workload: Optional[Workload] = None) -> None:
    """Log a routing decision; a heuristic tile is logged as the value
    the kernel's VMEM heuristic picks for ``workload`` (when known)."""
    bm = res.block_m
    if bm is None and res.block_m_source == "heuristic" \
            and workload is not None:
        from repro.perf import cost_model
        bm = cost_model.heuristic_block_m(workload)
    key = (res.entry, res.path, res.interpret, res.sharded, bm,
           res.block_m_source)
    level = logging.DEBUG if key in _LOGGED else logging.INFO
    _LOGGED.add(key)
    tile = ("" if res.block_m_source is None
            else f"[block_m={bm or 'auto'}:{res.block_m_source}]")
    log.log(level, "dispatch %s -> %s%s%s (%s)", res.entry, res.path,
            "" if res.interpret is None else f"[interpret={res.interpret}]",
            tile, res.reason)


def _workload_of(name: str, x, tables, weights, spec
                 ) -> Optional[Workload]:
    """Best-effort shape readout for tuned-tile lookup; entries the perf
    layer doesn't know (e.g. test doubles registered on the fly) resolve
    without one and keep the kernel's own heuristic."""
    try:
        return workload_of(name, tuple(x.shape), tuple(tables.shape),
                           tuple(tuple(w.shape) for w in weights),
                           spec.bits)
    except (ValueError, IndexError, AttributeError):
        return None


def _run(name: str, x, tables, *weights, spec,
         interpret: Optional[bool], log_resolution: bool):
    entry = get(name)
    workload = _workload_of(name, x, tables, weights, spec)
    res = resolve(name, spec, x.shape[-1], interpret, workload=workload)
    if log_resolution:
        _log(res, workload)
    if res.path == "oracle":
        return entry.oracle(x, tables, *weights, spec=spec)
    return entry.kernel(x, tables, *weights, spec=spec,
                        interpret=res.interpret, block_m=res.block_m)


def dispatch(name: str, x, tables, *weights, spec,
             interpret: Optional[bool] = None):
    """Run entry ``name`` on (x, tables, *weights) through whichever of
    {oracle, kernel} ``resolve`` picks. ``tables`` are baked value tables;
    ``spec`` is the AdcSpec they were baked with."""
    return _run(name, x, tables, *weights, spec=spec, interpret=interpret,
                log_resolution=True)


def dispatch_sharded(name: str, x, tables, *weights, spec, mesh, axes=None,
                     interpret: Optional[bool] = None):
    """``dispatch`` with the leading (population / design) axis of
    ``tables`` and ``weights`` partitioned over ``mesh``: each device gets
    its slice, builds nothing global, and runs the per-shard grid; ``x``
    replicates (one shared sample batch). ``axes`` defaults to the entry's
    registered rule (distributed/sharding); when nothing divides the
    leading dim the single-device path runs unsharded — results identical
    either way."""
    from jax.sharding import PartitionSpec as P

    entry = get(name)
    if entry.sharded_axes is None:
        raise ValueError(f"kernel entry {name!r} has no sharded variant")
    if axes is None:
        axes = entry.sharded_axes(mesh, tables.shape[0])
    if axes is None:
        return dispatch(name, x, tables, *weights, spec=spec,
                        interpret=interpret)
    res = resolve(name, spec, x.shape[-1], interpret, sharded=True)
    _log(res)
    pspec = P(axes)

    # the routing decision was logged once above (sharded=True); the
    # per-shard body must not re-log it as an unsharded call
    def body(xs, ts, *ws):
        return _run(name, xs, ts, *ws, spec=spec, interpret=interpret,
                    log_resolution=False)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(),) + (pspec,) * (1 + len(weights)),
                       out_specs=pspec, check_vma=False)
    return fn(x, tables, *weights)


# --------------------------------------------------------------- registry
# Adapters translate the uniform (x, tables, *weights, spec[, interpret])
# calling convention onto the concrete ref/pallas signatures. The sharded
# rules live in distributed/sharding (imported lazily: that module pulls
# in the full mesh stack).
def _population_axes(mesh, dim):
    from repro.distributed import sharding
    return sharding.population_axes(mesh, dim)


def _design_bank_axes(mesh, dim):
    from repro.distributed import sharding
    return sharding.design_bank_axes(mesh, dim)


register(KernelEntry(
    name="adc_quantize",
    oracle=lambda x, t, *, spec: ref.adc_quantize_ref(
        x, t, spec.bits, spec.vmin, spec.vmax),
    kernel=lambda x, t, *, spec, interpret, block_m=None: adc_quantize_pallas(
        x, t, bits=spec.bits, vmin=spec.vmin, vmax=spec.vmax,
        interpret=interpret, block_m=block_m),
))

register(KernelEntry(
    name="adc_quantize_population",
    oracle=lambda x, t, *, spec: ref.adc_quantize_ref_population(
        x, t, spec.bits, spec.vmin, spec.vmax),
    kernel=lambda x, t, *, spec, interpret, block_m=None:
        adc_quantize_pallas_population(
            x, t, bits=spec.bits, vmin=spec.vmin, vmax=spec.vmax,
            interpret=interpret, block_m=block_m),
    sharded_axes=_population_axes,
))

# Monte-Carlo non-ideality entries (DESIGN.md §10): tables is the lb
# interval table; ub/values/lo/scale ride as the remaining operands
# (core/nonideal.mc_operands builds them in exactly this order). The
# spec's role here is resolution only (bits/channels envelope) — the
# non-ideal code math is fully baked into the operands.
register(KernelEntry(
    name="mc_eval",
    oracle=lambda x, lb, ub, v, lo, sc, *, spec: ref.mc_adc_eval_ref(
        x, lb, ub, v, lo, sc),
    kernel=lambda x, lb, ub, v, lo, sc, *, spec, interpret, block_m=None:
        mc_adc_eval_pallas(x, lb, ub, v, lo, sc, interpret=interpret,
                           block_m=block_m),
))

register(KernelEntry(
    name="mc_eval_population",
    oracle=lambda x, lb, ub, v, lo, sc, *, spec:
        ref.mc_adc_eval_ref_population(x, lb, ub, v, lo, sc),
    kernel=lambda x, lb, ub, v, lo, sc, *, spec, interpret, block_m=None:
        mc_adc_eval_pallas_population(x, lb, ub, v, lo, sc,
                                      interpret=interpret, block_m=block_m),
))

# Calibrated-table MC entries (DESIGN.md §15): same operand order, but
# the value tables are per instance ((S, C, 2^N); population adds the
# design axis) because post-fabrication calibration re-bakes each
# measured instance's reconstruction ladder
# (faulttol.calibrate.mc_operands_ft builds the operands).
register(KernelEntry(
    name="mc_eval_cal",
    oracle=lambda x, lb, ub, v, lo, sc, *, spec: ref.mc_adc_eval_cal_ref(
        x, lb, ub, v, lo, sc),
    kernel=lambda x, lb, ub, v, lo, sc, *, spec, interpret, block_m=None:
        mc_adc_eval_cal_pallas(x, lb, ub, v, lo, sc, interpret=interpret,
                               block_m=block_m),
))

register(KernelEntry(
    name="mc_eval_cal_population",
    oracle=lambda x, lb, ub, v, lo, sc, *, spec:
        ref.mc_adc_eval_cal_ref_population(x, lb, ub, v, lo, sc),
    kernel=lambda x, lb, ub, v, lo, sc, *, spec, interpret, block_m=None:
        mc_adc_eval_cal_pallas_population(x, lb, ub, v, lo, sc,
                                          interpret=interpret,
                                          block_m=block_m),
))

register(KernelEntry(
    name="bespoke_mlp",
    oracle=lambda x, t, w1, b1, w2, b2, *, spec: ref.bespoke_mlp_ref(
        x, t, spec.bits, w1, b1, w2, b2, spec.vmin, spec.vmax),
    kernel=lambda x, t, w1, b1, w2, b2, *, spec, interpret, block_m=None:
        bespoke_mlp_pallas(x, t, w1, b1, w2, b2, bits=spec.bits,
                           vmin=spec.vmin, vmax=spec.vmax,
                           interpret=interpret, block_m=block_m),
))

register(KernelEntry(
    name="bespoke_svm",
    oracle=lambda x, t, w, b, *, spec: ref.bespoke_svm_ref(
        x, t, spec.bits, w, b, spec.vmin, spec.vmax),
    kernel=lambda x, t, w, b, *, spec, interpret, block_m=None:
        bespoke_svm_pallas(x, t, w, b, bits=spec.bits, vmin=spec.vmin,
                           vmax=spec.vmax, interpret=interpret,
                           block_m=block_m),
))

register(KernelEntry(
    name="classifier_bank_mlp",
    oracle=lambda x, t, w1, b1, w2, b2, *, spec: ref.bespoke_mlp_bank_ref(
        x, t, spec.bits, w1, b1, w2, b2, spec.vmin, spec.vmax),
    kernel=lambda x, t, w1, b1, w2, b2, *, spec, interpret, block_m=None:
        bespoke_mlp_bank_pallas(x, t, w1, b1, w2, b2, bits=spec.bits,
                                vmin=spec.vmin, vmax=spec.vmax,
                                interpret=interpret, block_m=block_m),
    sharded_axes=_design_bank_axes,
))

register(KernelEntry(
    name="classifier_bank_svm",
    oracle=lambda x, t, w, b, *, spec: ref.bespoke_svm_bank_ref(
        x, t, spec.bits, w, b, spec.vmin, spec.vmax),
    kernel=lambda x, t, w, b, *, spec, interpret, block_m=None:
        bespoke_svm_bank_pallas(x, t, w, b, bits=spec.bits, vmin=spec.vmin,
                                vmax=spec.vmax, interpret=interpret,
                                block_m=block_m),
    sharded_axes=_design_bank_axes,
))
