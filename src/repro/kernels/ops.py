"""Named entry points over the declarative dispatch registry
(kernels/dispatch.py — DESIGN.md §9).

These wrappers exist for two reasons only:

* they fix the **calling convention**: every entry takes ``spec=`` (a
  required ``AdcSpec`` keyword — the loose ``bits=/vmin=/vmax=/mode=``
  kwargs were deprecation shims through PR 5 and are gone; passing them
  now raises ``TypeError`` like any unknown kwarg, see CHANGES.md);
* they own the mask -> baked-value-table decode, so the registry itself
  only ever sees tables (the deployment path hands it baked tables
  directly).

All routing — envelope fallback to the jnp oracles, interpret
autodetection, the oracle-vs-interpret-kernel auto policy (identical for
single-sample, population and bank paths), tuned-vs-heuristic ``block_m``
selection, shard_map partitioning of the population/design axis — lives
in ``dispatch.dispatch`` / ``dispatch.dispatch_sharded`` and is logged
there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.spec import AdcSpec, as_spec
from repro.kernels import dispatch


def adc_quantize(x: jnp.ndarray, mask: jnp.ndarray, *, spec: AdcSpec,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Quantize (M, C) samples through per-channel pruned binary-search
    ADCs (kernel when the registry resolves one, jnp oracle otherwise)."""
    spec = as_spec(spec)
    table = spec.value_table(mask)
    return dispatch.dispatch("adc_quantize", x, table, spec=spec,
                             interpret=interpret)


def adc_quantize_population(x: jnp.ndarray, masks: jnp.ndarray, *,
                            spec: AdcSpec,
                            interpret: bool | None = None) -> jnp.ndarray:
    """Quantize one shared (M, C) sample batch through an entire NSGA-II
    population of pruned ADC banks. masks: (P, C, 2^bits). Returns
    (P, M, C). Kernel when the registry resolves one (population grid,
    per-individual value table resident in VMEM), batched jnp oracle
    otherwise — the auto (interpret=None) policy is the registry's,
    identical to every other entry."""
    spec = as_spec(spec)
    tables = spec.value_table(masks)                      # (P, C, n)
    return dispatch.dispatch("adc_quantize_population", x, tables,
                             spec=spec, interpret=interpret)


def adc_quantize_variants(xv: jnp.ndarray, masks: jnp.ndarray, *,
                          spec: AdcSpec,
                          interpret: bool | None = None) -> jnp.ndarray:
    """``adc_quantize_population`` over a variant-stacked sample batch:
    xv (V, M, C) — one featurized variant per subsample factor of the
    streaming co-search (timeseries/feature.stack_variants) — through a
    population of pruned banks. Returns (P, V, M, C); the caller gathers
    its genome's variant per individual. Not a registry entry: the ADC is
    elementwise over samples, so reshaping (V, M) into one flat sample
    axis reuses the existing population kernel (and its tuned/sharded
    routing) bit-for-bit — quantize-then-gather equals gather-then-
    quantize."""
    v, m, c = xv.shape
    flat = jnp.reshape(xv, (v * m, c))
    q = adc_quantize_population(flat, masks, spec=spec,
                                interpret=interpret)
    return jnp.reshape(q, (masks.shape[0], v, m, c))


def adc_quantize_population_sharded(x: jnp.ndarray, masks: jnp.ndarray, *,
                                    mesh, spec: AdcSpec, axes=None,
                                    interpret: bool | None = None
                                    ) -> jnp.ndarray:
    """``adc_quantize_population`` with the population axis partitioned
    over ``mesh``: each device receives only its (P/D, C, 2^bits) mask
    slice, builds value tables for *that slice alone*, and launches the
    per-shard (P_local, M/block_m) population grid; x replicates (it is
    one shared sample batch). ``axes`` defaults to the registry's rule
    (distributed/sharding.RULES_POPULATION); when nothing divides P the
    single-device path runs unsharded (same results)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as sharding_lib

    spec = as_spec(spec)
    p = masks.shape[0]
    if axes is None:
        axes = sharding_lib.population_axes(mesh, p)
    if axes is None:
        return adc_quantize_population(x, masks, spec=spec,
                                       interpret=interpret)
    pspec = P(axes)

    # value tables are built INSIDE the shard body from the local mask
    # slice, so dispatch_sharded (which shards pre-baked tables) is not
    # used here — the per-device table build is the point of this entry.
    def body(xs, ms):
        return adc_quantize_population(xs, ms, spec=spec,
                                       interpret=interpret)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(), pspec),
                         out_specs=pspec, check_vma=False)(x, masks)


# ------------------------------------------------ fused classifier serving
def bespoke_mlp(x, mask, w1, b1, w2, b2, *, spec: AdcSpec,
                interpret: bool | None = None):
    """Fused ADC + 1-hidden-layer printed MLP inference (mask-based: the
    value table is built here; deployment passes baked tables through
    ``classifier_bank``)."""
    spec = as_spec(spec)
    table = spec.value_table(mask)
    return dispatch.dispatch("bespoke_mlp", x, table, w1, b1, w2, b2,
                             spec=spec, interpret=interpret)


def bespoke_svm(x, mask, w, b, *, spec: AdcSpec,
                interpret: bool | None = None):
    """Fused ADC + linear-SVM inference (the paper's second model family),
    same registry contract as ``bespoke_mlp``."""
    spec = as_spec(spec)
    table = spec.value_table(mask)
    return dispatch.dispatch("bespoke_svm", x, table, w, b, spec=spec,
                             interpret=interpret)


def _bank_entry(kind: str) -> str:
    if kind not in ("mlp", "svm"):
        raise ValueError(f"unknown classifier kind {kind!r}")
    return f"classifier_bank_{kind}"


def classifier_bank(x, tables, weights, *, kind: str, spec: AdcSpec,
                    interpret: bool | None = None):
    """One shared (M, C) sample batch through a deployed multi-design bank.

    tables: (D, C, 2^bits) *baked* value tables (the deployment artifact —
    no mask decode at serve time); weights: stacked po2-quantized
    parameters, ``(w1, b1, w2, b2)`` for kind='mlp' or ``(w, b)`` for
    kind='svm'. Returns (D, M, O) logits. Kernel-vs-oracle routing is the
    registry's ((D, M/block_m) grid, per-design table+weights resident in
    VMEM when the kernel applies)."""
    spec = as_spec(spec)
    return dispatch.dispatch(_bank_entry(kind), x, tables, *weights,
                             spec=spec, interpret=interpret)


def classifier_bank_sharded(x, tables, weights, *, mesh, kind: str,
                            spec: AdcSpec, axes=None,
                            interpret: bool | None = None):
    """``classifier_bank`` with the design axis partitioned over ``mesh``:
    each device holds only its (D/Dev, ...) slice of tables and weights
    and serves the shared sample batch against it — Pareto designs are
    embarrassingly parallel exactly like GA individuals, so the registered
    axis rule reuses the population rules
    (distributed/sharding.design_bank_axes). When nothing divides D the
    single-device bank runs unsharded (same results)."""
    spec = as_spec(spec)
    return dispatch.dispatch_sharded(_bank_entry(kind), x, tables,
                                     *weights, spec=spec, mesh=mesh,
                                     axes=axes, interpret=interpret)
