"""Deployment artifacts: the searched front as runnable mixed-signal
inference designs (DESIGN.md §8).

The paper's end product is a *deployed* ADC+classifier pair on a flexible
substrate; the search (core/search.py) finds it but used to throw the
trained state away with the last generation. This module freezes each
Pareto individual into a ``DeployedClassifier``:

* the **baked value table** (C, 2^N) — the pruned comparator tree collapsed
  to its code->value map, exactly what the fused serving kernels consume
  (no mask decode / tree walk at serve time);
* **po2-quantized weights** — ``qat.quantize_po2`` / ``quantize_fixed``
  applied once at export with the genome's decimal position ``dp``, so
  inference is a plain forward pass over the same numbers QAT measured;
* the genome's ``dp``, the provenance ``mask``, and the **exact
  transistor-count area report** (core/area.system_tc);
* the export-time test ``accuracy`` — bit-for-bit the search-time fitness
  (every QAT lane is a pure function of (genome, data, cfg);
  ``search.train_pareto_front`` re-derives it deterministically).

Fronts save/load through checkpoint/manager.py (atomic commit, one .npy
per leaf, JSON-packed metadata via ``pack_json`` — the structure is
data-dependent in the design count, so loading goes through
``CheckpointManager.restore_flat``). Serving routes every design — single
or the whole front at once — through the fused bank kernels
(kernels/ops.classifier_bank, optionally sharded over the mesh).
"""
from __future__ import annotations

from dataclasses import dataclass, replace as dataclass_replace
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro import obs
from repro.checkpoint.manager import CheckpointManager, pack_json, unpack_json
from repro.core import area, qat
from repro.core import nonideal as nonideal_lib
from repro.core.nonideal import NonIdealSpec
from repro.core.spec import AdcSpec, Range
from repro.core.search import (SearchConfig, accuracy,
                               decode_genome_cosearch,
                               decode_genome_faulttol, train_pareto_front)
from repro.faulttol import calibrate as faulttol_cal
from repro.faulttol import redundancy as ft_redundancy
from repro.kernels import ops
from repro.timeseries import feature as feature_lib
from repro.timeseries.feature import FeatureSpec

FORMAT_VERSION = 1

# weight leaf names per classifier family, in ops.classifier_bank order
_WEIGHT_LEAVES = {"mlp": ("w1", "b1", "w2", "b2"), "svm": ("w", "b")}


@dataclass(frozen=True)
class DeployedClassifier:
    """One frozen ADC+classifier design, ready to serve."""
    kind: str                      # 'mlp' | 'svm'
    bits: int
    mode: str                      # pruned-ADC semantics the table was baked with
    vmin: Range                    # analog range: float or per-channel tuple
    vmax: Range
    dp: float                      # genome decimal-point position
    mask: np.ndarray               # (C, 2^N) int32 — provenance, not used to serve
    table: np.ndarray              # (C, 2^N) float32 baked value table
    weights: Tuple[np.ndarray, ...]  # po2-quantized, _WEIGHT_LEAVES order
    area_tc: int                   # exact ADC (+ front-end) transistor count
    accuracy: float                # export-time test accuracy (== search fitness)
    # baked analog front end of a streaming co-searched design (DESIGN.md
    # §14): None for the tabular (M, C) designs of PRs 1-8, a
    # subsample/alloc-baked FeatureSpec for designs that consume raw
    # (M, W, C_raw) windows
    feature: Optional[FeatureSpec] = None
    # fault-tolerance provenance of a §15 co-searched design: the
    # per-channel TMR genes (None for plain designs — the spare levels
    # are already folded into ``mask``) and the calibrate gene: every
    # fabricated instance of a calibrated design re-bakes its value
    # table against its measured non-idealities (the robustness
    # evaluation applies per-instance calibrated tables;
    # ``calibrate_front`` materializes ONE measured instance's re-bake)
    tmr: Optional[np.ndarray] = None   # (C,) int32 {0,1}
    calibrated: bool = False

    @property
    def spec(self) -> AdcSpec:
        """The ADC design point this classifier was exported against."""
        return AdcSpec(bits=self.bits, mode=self.mode, vmin=self.vmin,
                       vmax=self.vmax)

    @property
    def channels(self) -> int:
        """ADC input channel count C (feature channels for a co-searched
        design) — the width the baked table and weights consume."""
        return int(self.table.shape[0])

    @property
    def sample_shape(self) -> Tuple[int, ...]:
        """Shape of ONE raw sample this design serves: (C,) for tabular
        designs, (window, raw_channels) for streaming co-searched ones —
        the serving engine's wrong-domain check compares request shape
        against this."""
        if self.feature is not None:
            return (self.feature.window, self.feature.channels)
        return (self.channels,)

    def logits(self, x, interpret: Optional[bool] = None) -> np.ndarray:
        """Samples -> (M, O) logits, served as a size-1 bank through the
        fused kernel registry. A feature-baked design accepts raw
        (M, W, C_raw) windows and runs them through THE compiled
        featurize program the search data was built with
        (feature.featurize_fn — the §8 parity contract); already-
        featurized (M, C) input passes straight to the bank."""
        x = np.asarray(x, np.float32)
        if self.feature is not None and x.ndim == 3:
            x = np.asarray(feature_lib.featurize_fn(self.feature)(x))
        out = ops.classifier_bank(
            x, self.table[None],
            tuple(w[None] for w in self.weights), kind=self.kind,
            spec=self.spec, interpret=interpret)
        return np.asarray(out)[0]

    def predict(self, x, interpret: Optional[bool] = None) -> np.ndarray:
        return np.argmax(self.logits(x, interpret=interpret), axis=-1)

    def accuracy_on(self, x, y, interpret: Optional[bool] = None) -> float:
        return float(mean_acc(
            self.predict(x, interpret=interpret)[None] == np.asarray(y))[0])


# -------------------------------------------------------- search -> artifact
def export_front(genomes: np.ndarray, data: Dict, sizes: Sequence[int],
                 cfg: SearchConfig,
                 trained=None) -> List[DeployedClassifier]:
    """Freeze (typically Pareto-front) genomes into deployable designs:
    deterministic QAT re-train (``search.train_pareto_front``), bake value
    tables, quantize the trained weights once with each genome's dp, and
    attach the exact transistor-count area report.

    ``trained`` short-circuits the re-train: pass the (accs, params,
    masks, dps) tuple already produced by ``train_pareto_front`` /
    ``run_search(..., return_trained=True)`` for these same genomes so
    the front's vmapped QAT runs once, not twice."""
    if cfg.model == "mlp" and len(sizes) != 3:
        raise ValueError(
            f"the fused serving kernels cover the paper's 1-hidden-layer "
            f"printed-MLP topology; got sizes={tuple(sizes)}")
    accs, params, masks, dps = (train_pareto_front(genomes, data, sizes, cfg)
                                if trained is None else trained)
    if len(accs) != len(genomes):
        raise ValueError(f"trained tuple covers {len(accs)} individuals, "
                         f"got {len(genomes)} genomes")
    spec = cfg.adc_spec.validate_channels(sizes[0])
    fe = cfg.frontend
    designs = []
    for k in range(len(accs)):
        dp = float(dps[k])
        feature, fe_tc = None, 0
        tmr, calibrated, ft_tc = None, False, 0
        if cfg.faulttol is not None:
            # the masks from train_pareto_front already carry the spare
            # levels; the TMR/calibrate genes price the voter and
            # calibration-store overhead on the same budget axis
            _, _, tmr_k, _, cal_k = decode_genome_faulttol(
                genomes[k], sizes[0], cfg.bits, cfg.min_levels,
                cfg.faulttol)
            tmr = np.asarray(tmr_k, np.int32)
            calibrated = bool(int(cal_k))
            ft_tc = area.faulttol_tc(np.asarray(masks[k], np.int32), tmr,
                                     calibrated)
        if fe is not None:
            # bake this genome's searched front-end point: the subsample
            # factor and alloc ladder come from the feature genes (the
            # masks from train_pareto_front already carry the alloc
            # pruning, so the baked table matches the measured fitness)
            _, _, sub, alloc = decode_genome_cosearch(
                genomes[k], sizes[0], cfg.bits, cfg.min_levels, fe)
            sub_f = fe.sub_grid[int(sub)]
            alloc_t = tuple(int(a) for a in np.asarray(alloc))
            feature = fe.bake(sub_f, alloc_t)
            fe_tc = feature_lib.frontend_tc(fe, sub_f, alloc_t)
        if cfg.model == "svm":
            w, b = jax.tree_util.tree_map(lambda a: a[k], params)
            weights = (_po2(w, dp, cfg.weight_bits),
                       _fixed(b, dp, cfg.weight_bits))
        else:
            (w1, b1), (w2, b2) = [
                (layer[0][k], layer[1][k]) for layer in params]
            weights = (_po2(w1, dp, cfg.weight_bits),
                       _fixed(b1, dp, cfg.weight_bits),
                       _po2(w2, dp, cfg.weight_bits),
                       _fixed(b2, dp, cfg.weight_bits))
        mask = np.asarray(masks[k], np.int32)
        designs.append(DeployedClassifier(
            kind=cfg.model, bits=spec.bits, mode=spec.mode,
            vmin=spec.vmin, vmax=spec.vmax, dp=dp, mask=mask,
            table=np.asarray(spec.value_table(mask), np.float32),
            weights=weights,
            area_tc=area.system_tc(mask, cfg.design) + fe_tc + ft_tc,
            accuracy=float(accs[k]), feature=feature, tmr=tmr,
            calibrated=calibrated))
    return designs


def verify_front_parity(designs: Sequence[DeployedClassifier],
                        genomes: np.ndarray, data: Dict,
                        sizes: Sequence[int], cfg: SearchConfig) -> bool:
    """Bit-for-bit contract check (DESIGN.md §8/§13): re-train the given
    genomes through the exact batched fitness path and compare against
    the accuracies the designs report. Every QAT lane is a pure function
    of (genome, data, cfg), so this must hold exactly — for fronts from
    the evolutionary engines AND for snapped gradient-engine designs
    (their pool re-score IS this path). Exact float equality on purpose:
    any drift means the purity contract broke, not a tolerance issue."""
    accs, _, _, _ = train_pareto_front(genomes, data, sizes, cfg)
    reported = np.array([d.accuracy for d in designs], np.float64)
    return bool(np.array_equal(np.asarray(accs, np.float64), reported))


def _po2(w, dp: float, weight_bits: int) -> np.ndarray:
    return np.asarray(qat.quantize_po2(np.asarray(w), dp, weight_bits),
                      np.float32)


def _fixed(b, dp: float, weight_bits: int) -> np.ndarray:
    return np.asarray(qat.quantize_fixed(np.asarray(b), dp, weight_bits),
                      np.float32)


# ----------------------------------------------------------------- save/load
def save_front(directory, designs: Sequence[DeployedClassifier],
               extra_meta: Optional[Dict] = None) -> None:
    """Persist a deployed front under ``directory`` (CheckpointManager
    step 0: atomic commit, one .npy per leaf)."""
    if not designs:
        raise ValueError("refusing to save an empty front")
    kinds = {d.kind for d in designs}
    specs = {d.spec for d in designs}
    feats = {None if d.feature is None else d.feature.base()
             for d in designs}
    if len(kinds) != 1 or len(specs) != 1 or len(feats) != 1:
        raise ValueError(f"mixed fronts unsupported: kinds={kinds} "
                         f"specs={specs} features={feats}")
    # spec fields serialize through AdcSpec.to_meta (per-channel tuples
    # become JSON lists; load_front restores the canonical tuples). A
    # co-searched front additionally carries the shared base FeatureSpec
    # in the meta and each design's baked (subsample, alloc) as leaves.
    meta = {"format": FORMAT_VERSION, "kind": designs[0].kind,
            **designs[0].spec.to_meta(),
            "num_designs": len(designs), **(extra_meta or {})}
    fe = next(iter(feats))
    if fe is not None:
        meta["feature"] = fe.to_meta()
    tree = {"meta": pack_json(meta)}
    for i, d in enumerate(designs):
        leaf = {"mask": d.mask.astype(np.int32), "table": d.table,
                "dp": np.float32(d.dp), "acc": np.float64(d.accuracy),
                "area_tc": np.int64(d.area_tc)}
        if d.feature is not None:
            leaf["subsample"] = np.int64(d.feature.subsample)
            leaf["alloc"] = np.asarray(d.feature.alloc, np.int32)
        if d.tmr is not None:
            leaf["tmr"] = np.asarray(d.tmr, np.int32)
        if d.tmr is not None or d.calibrated:
            leaf["calibrated"] = np.int64(d.calibrated)
        leaf.update(zip(_WEIGHT_LEAVES[d.kind], d.weights))
        tree[f"design_{i:03d}"] = leaf
    CheckpointManager(directory, keep=1).save(0, tree, blocking=True)


def front_meta(directory) -> Dict:
    """The metadata ``save_front`` persisted (format/kind/bits plus any
    ``extra_meta`` provenance such as the training dataset) — so serving
    can validate a front against the traffic it is asked to serve."""
    flat = CheckpointManager(directory, keep=1).restore_flat(0)
    return unpack_json(flat["meta"])


def load_front(directory) -> List[DeployedClassifier]:
    """Inverse of ``save_front`` — reconstructs every design from the
    self-describing leaf set (no shape/count foreknowledge needed)."""
    flat = CheckpointManager(directory, keep=1).restore_flat(0)
    meta = unpack_json(flat["meta"])
    if meta["format"] != FORMAT_VERSION:
        raise ValueError(f"unknown front format {meta['format']}")
    spec = AdcSpec.from_meta(meta)
    fe = (FeatureSpec.from_meta(meta["feature"])
          if meta.get("feature") is not None else None)
    designs = []
    for i in range(meta["num_designs"]):
        p = f"design_{i:03d}/"
        feature = None
        if fe is not None:
            feature = fe.bake(int(flat[p + "subsample"]),
                              tuple(int(a) for a in flat[p + "alloc"]))
        designs.append(DeployedClassifier(
            kind=meta["kind"], bits=spec.bits, mode=spec.mode,
            vmin=spec.vmin, vmax=spec.vmax,
            dp=float(flat[p + "dp"]), mask=flat[p + "mask"],
            table=flat[p + "table"],
            weights=tuple(flat[p + n] for n in _WEIGHT_LEAVES[meta["kind"]]),
            area_tc=int(flat[p + "area_tc"]),
            accuracy=float(flat[p + "acc"]), feature=feature,
            tmr=(np.asarray(flat[p + "tmr"], np.int32)
                 if p + "tmr" in flat else None),
            calibrated=bool(int(flat.get(p + "calibrated", 0)))))
    return designs


# -------------------------------------------------------------- bank serving
def bank_arrays(designs: Sequence[DeployedClassifier]
                ) -> Tuple[np.ndarray, Tuple[np.ndarray, ...]]:
    """Stack a front into the fused bank kernel's operands:
    (tables (D, C, 2^N), weights each (D, ...)). Designs from one search
    share (kind, bits, shapes) by construction; mixed banks are rejected."""
    kinds = {d.kind for d in designs}
    if len(kinds) != 1:
        raise ValueError(f"bank needs one classifier kind, got {kinds}")
    tables = np.stack([d.table for d in designs])
    weights = tuple(np.stack([d.weights[j] for d in designs])
                    for j in range(len(designs[0].weights)))
    return tables, weights


def _feature_groups(designs: Sequence[DeployedClassifier]) -> Dict:
    """{subsample -> design indices} of a feature-baked front. The fused
    bank kernels consume ONE shared sample batch, but co-searched designs
    can bake different subsample factors (different featurized views of
    the same windows) — so bank serving runs once per subsample group and
    scatters the logits back into front order. Mixed feature/None fronts
    are rejected (they would not even share a sample shape)."""
    withf = {d.feature is not None for d in designs}
    if len(withf) != 1:
        raise ValueError("mixed feature/tabular fronts unsupported")
    bases = {d.feature.base() for d in designs}
    if len(bases) != 1:
        raise ValueError(f"bank needs one base FeatureSpec, got {bases}")
    groups: Dict = {}
    for i, d in enumerate(designs):
        groups.setdefault(int(d.feature.subsample), []).append(i)
    return dict(sorted(groups.items()))


def make_bank_fn(designs: Sequence[DeployedClassifier], *, mesh=None,
                 interpret: Optional[bool] = None):
    """One jitted bank call closed over device-resident tables and weights
    (host->device once, not once per microbatch) — the serving hot path
    the continuous-batching driver (launch/serve_classifier) and the
    benchmarks dispatch. The jit matters off-TPU too, where auto mode
    serves the jnp bank oracle: unjitted it would re-dispatch every op
    eagerly per microbatch. With ``mesh`` the design axis shards D/device
    (ops.classifier_bank_sharded)."""
    import jax.numpy as jnp
    designs = list(designs)
    if designs and designs[0].feature is not None:
        return _make_feature_bank_fn(designs, mesh=mesh,
                                     interpret=interpret)
    tables, weights = bank_arrays(designs)
    tables = jnp.asarray(tables)
    weights = tuple(jnp.asarray(w) for w in weights)
    d0 = designs[0]
    kw = dict(kind=d0.kind, spec=d0.spec, interpret=interpret)
    if mesh is not None:
        return jax.jit(lambda xb: ops.classifier_bank_sharded(
            xb, tables, weights, mesh=mesh, **kw))
    return jax.jit(lambda xb: ops.classifier_bank(xb, tables, weights, **kw))


def _make_feature_bank_fn(designs: Sequence[DeployedClassifier], *,
                          mesh=None, interpret: Optional[bool] = None):
    """The streaming twin of ``make_bank_fn``: (M, W, C_raw) windows ->
    (D, M, O) logits. Designs group by baked subsample factor; each group
    serves its own fused bank over THE cached compiled featurize program
    of its factor (feature.featurize_fn — identical to the search-data
    build, so served accuracies reproduce search fitness bit-for-bit),
    and group logits scatter back into front order. Group banks are
    jitted closures over device-resident operands like the tabular
    path; the scatter is a cheap host-side reindex."""
    groups = _feature_groups(designs)
    d0 = designs[0]
    sub_banks = []
    for sub, idx in groups.items():
        grp = [designs[i] for i in idx]
        feat = feature_lib.featurize_fn(grp[0].feature)
        tables, weights = bank_arrays(grp)
        kw = dict(kind=d0.kind, spec=d0.spec, interpret=interpret)
        bank = jax.jit(_bank_closure(tables, weights, mesh, kw))
        sub_banks.append((np.asarray(idx), feat, bank))

    def fn(xb):
        out = None
        for idx, feat, bank in sub_banks:
            lg = np.asarray(bank(feat(xb)))
            if out is None:
                out = np.zeros((len(designs),) + lg.shape[1:], lg.dtype)
            out[idx] = lg
        return out

    return fn


def _bank_closure(tables, weights, mesh, kw):
    """One group's bank call closed over device-resident operands."""
    import jax.numpy as jnp
    tb = jnp.asarray(tables)
    wb = tuple(jnp.asarray(w) for w in weights)
    if mesh is not None:
        return lambda xb: ops.classifier_bank_sharded(xb, tb, wb,
                                                      mesh=mesh, **kw)
    return lambda xb: ops.classifier_bank(xb, tb, wb, **kw)


def serve_bank(designs: Sequence[DeployedClassifier], x, *,
               mesh=None, interpret: Optional[bool] = None) -> np.ndarray:
    """One shared sample batch through the whole deployed front:
    (D, M, O) logits via the fused multi-design kernel — with ``mesh``,
    the design axis shards D/device (ops.classifier_bank_sharded). A
    feature-baked front takes raw (M, W, C_raw) windows and serves per
    subsample group (``_make_feature_bank_fn``)."""
    designs = list(designs)
    x = np.asarray(x, np.float32)
    if designs and designs[0].feature is not None:
        return _make_feature_bank_fn(designs, mesh=mesh,
                                     interpret=interpret)(x)
    tables, weights = bank_arrays(designs)
    d0 = designs[0]
    kw = dict(kind=d0.kind, spec=d0.spec, interpret=interpret)
    if mesh is not None:
        out = ops.classifier_bank_sharded(x, tables, weights, mesh=mesh, **kw)
    else:
        out = ops.classifier_bank(x, tables, weights, **kw)
    return np.asarray(out)


def served_accuracies(designs: Sequence[DeployedClassifier], x, y, *,
                      mesh=None, interpret: Optional[bool] = None
                      ) -> np.ndarray:
    """(D,) test accuracies of the whole served front — the round-trip
    parity check against each design's exported ``accuracy``."""
    logits = serve_bank(designs, x, mesh=mesh, interpret=interpret)
    return mean_acc(np.argmax(logits, -1) == np.asarray(y)[None, :])


def mean_acc(correct: np.ndarray) -> np.ndarray:
    """(D, M) correctness bools -> (D,) accuracies through
    ``search.accuracy``, the division the search-time fitness uses, so
    served and exported accuracies compare bit for bit."""
    correct = np.asarray(correct)
    return accuracy(correct.sum(axis=-1), correct.shape[-1])


# -------------------------------------------------- robustness (DESIGN §10)
def _stacked_model_params(designs: Sequence[DeployedClassifier]):
    """The front's baked weights re-assembled as the model family's params
    pytree with a leading design axis — the exact structure
    models.{mlp,svm}.n_correct consumes, so the Monte-Carlo accuracy path
    below is op-for-op the in-search robustness objective
    (search._mc_correct_fn) evaluated on the exported numbers. The
    stacking itself is ``bank_arrays``' (one site owns the weight-leaf
    layout); this only regroups the flat leaves into params."""
    import jax.numpy as jnp
    w = tuple(jnp.asarray(a) for a in bank_arrays(designs)[1])
    if designs[0].kind == "svm":
        return (w[0], w[1])
    return [(w[0], w[1]), (w[2], w[3])]


def _mc_instance_accuracies(designs: Sequence[DeployedClassifier],
                            nonideal: NonIdealSpec, x, y, *,
                            draws: Optional[nonideal_lib.Draws] = None,
                            samples: Optional[int] = None,
                            interpret: Optional[bool] = None) -> np.ndarray:
    """(D, S) per-design, per-MC-instance test accuracies of a deployed
    front under ``nonideal`` — the shared core of ``evaluate_robustness``
    and the non-ideal serving path. The perturbed views come from the MC
    population entry (one (D, S, M/bm) launch); each view is re-scored by
    the design's baked classifier with the same vmap structure (design
    axis outer, instance axis inner) as the in-search objective, keeping
    the search -> deploy robustness numbers bit-for-bit reproducible.

    Spans: ``deploy.mc_operands`` (masks, the draw, the interval
    operands), ``deploy.mc_launch`` (the MC population dispatch) and
    ``deploy.rescore`` (the vmapped count, and the wait for it)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import dispatch
    from repro.models import mlp as mlp_lib
    from repro.models import svm as svm_lib
    d0 = designs[0]
    spec = d0.spec
    with obs.span("deploy.mc_operands"):
        masks = np.stack([np.asarray(d.mask, np.int32) for d in designs])
        xj = jnp.asarray(np.asarray(x, np.float32))
        yj = jnp.asarray(np.asarray(y))
        # a §15 fault-tolerant front (TMR/calibrate provenance, or an
        # explicit RedundantDraws stream) evaluates through the
        # calibrated-table entry: redundancy folds into the draw stream
        # and calibrated designs reconstruct through per-instance
        # re-baked tables — op-for-op the in-search FT objective
        ft = (isinstance(draws, ft_redundancy.RedundantDraws)
              or any(d.tmr is not None or d.calibrated for d in designs))
        if ft:
            masks = jnp.asarray(masks)
            if draws is None:
                draws = ft_redundancy.draw_redundant(
                    spec.bits, masks.shape[1], samples if samples else 32,
                    nonideal)
            tmr = jnp.stack([
                jnp.zeros(masks.shape[1], jnp.int32) if d.tmr is None
                else jnp.asarray(d.tmr, jnp.int32) for d in designs])
            cal = jnp.asarray([int(d.calibrated) for d in designs],
                              jnp.int32)
            entry = "mc_eval_cal_population"
            operands = faulttol_cal.mc_operands_ft(spec, nonideal, masks,
                                                   tmr, cal, draws)
        else:
            entry = "mc_eval_population"
            operands = nonideal_lib.compiled_mc_operands(
                spec, nonideal, masks, draws=draws,
                samples=samples if samples else 32)
    with obs.span("deploy.mc_launch"):
        xq_mc = dispatch.dispatch(entry, xj, *operands, spec=spec,
                                  interpret=interpret)   # (D, S, M, C)
    count = svm_lib.n_correct if d0.kind == "svm" else mlp_lib.n_correct
    # dp=None: the baked weights are already po2/fixed-quantized at
    # export; re-quantization would be a no-op by construction and the
    # in-graph path was only ever there for traced search-time dp
    per_design = lambda p, xq_s: jax.vmap(lambda xq: count(p, xq, yj))(xq_s)
    with obs.span("deploy.rescore"):
        correct = jax.vmap(per_design)(_stacked_model_params(designs), xq_mc)
        return accuracy(correct, len(yj))


def evaluate_robustness(designs: Sequence[DeployedClassifier],
                        nonideal: NonIdealSpec, x, y, samples: int = 32, *,
                        draws: Optional[nonideal_lib.Draws] = None,
                        yield_margins: Tuple[float, ...] = (0.01, 0.05),
                        interpret: Optional[bool] = None) -> Dict:
    """Monte-Carlo robustness report for a deployed front: S perturbed
    hardware instances of every design against the shared (x, y) test
    set, through the MC kernel family (DESIGN.md §10).

    Returns a JSON-able report: per design the exported (ideal) accuracy,
    mean/worst/std over instances, the two search objectives
    (``expected`` accuracy drop, ``worst``-case error — the identical
    host-side f64 reductions as core/search applies to the identical
    per-instance accuracies, so a 3-objective front's robustness fitness
    column is reproduced *bit-for-bit* from the same ``NonIdealSpec``),
    the per-instance accuracies, and the *yield*: the fraction of
    instances within each ``yield_margins`` accuracy drop of the exported
    value (the arXiv:2602.10790 question — how many manufactured devices
    still classify acceptably).

    One call is one ``deploy.evaluate_robustness`` span (args
    ``designs``, ``samples``; counter ``deploy.instances``), the report's
    assembly its ``deploy.report`` child (DESIGN.md §16)."""
    designs = list(designs)
    with obs.span("deploy.evaluate_robustness", designs=len(designs),
                  samples=samples):
        mc_accs = _mc_instance_accuracies(designs, nonideal, x, y,
                                          draws=draws, samples=samples,
                                          interpret=interpret)
        obs.count("deploy.instances", mc_accs.size)
        with obs.span("deploy.report"):
            return _robustness_report(designs, nonideal, mc_accs,
                                      yield_margins)


def _robustness_report(designs: List[DeployedClassifier],
                       nonideal: NonIdealSpec, mc_accs: np.ndarray,
                       yield_margins: Tuple[float, ...]) -> Dict:
    """``evaluate_robustness``'s JSON-able report from the (D, S) instance
    accuracies."""
    exported = np.array([d.accuracy for d in designs])
    expected = nonideal_lib.robust_objective(exported, mc_accs, "expected")
    worst = nonideal_lib.robust_objective(exported, mc_accs, "worst")
    means = nonideal_lib.mc_mean_accuracy(mc_accs)
    rows = []
    for i, d in enumerate(designs):
        inst = mc_accs[i]
        rows.append({
            "exported_accuracy": float(d.accuracy),
            "area_tc": int(d.area_tc),
            "mean_accuracy": float(means[i]),
            "worst_accuracy": float(inst.min()),
            "std_accuracy": float(np.asarray(inst, np.float64).std()),
            "expected_drop": float(expected[i]),
            "worst_case_error": float(worst[i]),
            # the same f64 count nonideal.robust_objective('yield')
            # reduces in-search, so the searched yield column reproduces
            # bit-for-bit as 1 - yield[margin]
            "yield": {f"{m:g}": float(nonideal_lib.yield_fraction(
                np.float64(d.accuracy), inst[None], m)[0])
                for m in yield_margins},
            "instance_accuracies": [float(a) for a in inst],
        })
    return {"nonideal": nonideal.to_meta(), "samples": int(mc_accs.shape[1]),
            "yield_margins": [float(m) for m in yield_margins],
            "kind": designs[0].kind, "num_designs": len(designs),
            "designs": rows}


def robustness_curve(designs: Sequence[DeployedClassifier], x, y,
                     sigmas: Sequence[float], samples: int = 32, *,
                     base: Optional[NonIdealSpec] = None,
                     interpret: Optional[bool] = None) -> Dict:
    """Accuracy-vs-sigma sweep: one ``evaluate_robustness`` report per
    comparator-offset sigma (other knobs from ``base``), the artifact the
    paper-style robustness figure plots. The sigma=0 point reproduces the
    exported accuracies bit-for-bit (the ideal-limit contract)."""
    base = base if base is not None else NonIdealSpec()
    points = []
    for s in sigmas:
        rep = evaluate_robustness(designs, base.replace(sigma_offset=s), x,
                                  y, samples, interpret=interpret)
        points.append(rep)
    return {"sigma_offset": [float(s) for s in sigmas],
            "samples": samples, "base": base.to_meta(),
            "mean_accuracy": [[d["mean_accuracy"] for d in p["designs"]]
                              for p in points],
            "points": points}


def save_robustness(directory, report: Dict) -> None:
    """Persist a robustness report/curve next to the front artifact
    (``<front-dir>/robustness.json`` — the front leaves stay under the
    CheckpointManager step layout, the report is plain JSON)."""
    import json
    from pathlib import Path
    path = Path(directory)
    path.mkdir(parents=True, exist_ok=True)
    with open(path / "robustness.json", "w") as f:
        json.dump(report, f, indent=1)


def load_robustness(directory) -> Dict:
    import json
    from pathlib import Path
    with open(Path(directory) / "robustness.json") as f:
        return json.load(f)


def make_nonideal_bank_fn(designs: Sequence[DeployedClassifier],
                          nonideal: NonIdealSpec, *, instance: int = 0,
                          samples: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """One jitted bank call serving through a *sampled non-ideal hardware
    instance*: (M, C) samples -> (D, M, O) logits, the degraded twin of
    ``make_bank_fn`` — what launch/serve_classifier drives to demonstrate
    live accuracy degradation. The instance's interval tables and drifted
    rows are baked into the closure (built once, device-resident).

    ``samples`` names the MC stream the ``instance`` index refers to:
    JAX PRNG bits depend on the drawn array's total size, so instance
    ``k`` of an S-sample ``evaluate_robustness`` report is reproduced
    only by drawing the same S-sample stream and slicing it — pass the
    report's ``samples`` to serve exactly the instance whose accuracy
    the report lists. Default (None) draws a minimal
    ``instance + 1``-sample stream (a valid sampled instance, but NOT
    row ``instance`` of some larger report)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import dispatch
    from repro.models import mlp as mlp_lib
    from repro.models import svm as svm_lib
    designs = list(designs)
    d0 = designs[0]
    spec = d0.spec
    masks = np.stack([np.asarray(d.mask, np.int32) for d in designs])
    if samples is None:
        samples = instance + 1
    if not 0 <= instance < samples:
        raise ValueError(f"instance {instance} outside the "
                         f"{samples}-sample MC stream")
    # the report's own compiled operands, cut to the one instance
    lb, ub, values, lo, scale = nonideal_lib.compiled_mc_operands(
        spec, nonideal, masks, samples=samples)
    one = slice(instance, instance + 1)
    mc = (lb[:, one], ub[:, one], values, lo[one], scale[one])
    params = _stacked_model_params(designs)
    apply = svm_lib.apply_svm if d0.kind == "svm" else mlp_lib.apply_mlp

    def fn(xb):
        xq = dispatch.dispatch("mc_eval_population", xb, *mc, spec=spec,
                               interpret=interpret)      # (D, 1, M, C)
        return jax.vmap(lambda p, xq_d: apply(p, xq_d[0]))(params, xq)

    return jax.jit(fn)


# ------------------------------------------- serve-time calibration (§15)
def _measured_instance(designs: Sequence["DeployedClassifier"],
                       nonideal: NonIdealSpec, instance: int,
                       samples: Optional[int]):
    """The shared front half of the calibration paths: re-derive the
    redundant MC stream (a pure function of ``nonideal.seed`` — the
    identical stream the search and ``evaluate_robustness`` consume,
    same ``samples`` semantics as ``make_nonideal_bank_fn``), slice the
    measured ``instance``, and compile the calibrated-table operands for
    the whole front with the calibrate action forced ON."""
    import jax.numpy as jnp
    d0 = designs[0]
    spec = d0.spec
    masks = jnp.stack([jnp.asarray(d.mask, jnp.int32) for d in designs])
    if samples is None:
        samples = instance + 1
    if not 0 <= instance < samples:
        raise ValueError(f"instance {instance} outside the "
                         f"{samples}-sample MC stream")
    draws = ft_redundancy.draw_redundant(spec.bits, masks.shape[1],
                                         samples, nonideal)
    one = ft_redundancy.RedundantDraws(
        *(a[instance:instance + 1] for a in draws))
    tmr = jnp.stack([
        jnp.zeros(masks.shape[1], jnp.int32) if d.tmr is None
        else jnp.asarray(d.tmr, jnp.int32) for d in designs])
    cal = jnp.ones(len(designs), jnp.int32)
    return spec, faulttol_cal.mc_operands_ft(spec, nonideal, masks, tmr,
                                             cal, one)


def calibrate_front(designs: Sequence[DeployedClassifier],
                    nonideal: NonIdealSpec, *, instance: int = 0,
                    samples: Optional[int] = None
                    ) -> List[DeployedClassifier]:
    """Re-bake a deployed front against ONE measured hardware instance
    (DESIGN.md §15): each design's value table becomes the measured
    interval midpoints (``faulttol.calibrated_value_rows``) and its
    range rows become the instance's drifted analog range, so the plain
    ideal-kernel serving path (``make_bank_fn``/``logits``) reconstructs
    through calibrated values: the serving code walk is
    ``floor((x - vmin_meas) * scale_meas)`` and each code's table entry
    is the calibrated value of the measured leaf interval containing
    that code's midpoint. The re-bake corrects the value ladder and the
    range drift exactly; residual comparator offsets still move leaf
    *boundaries* off the integer code grid — ``make_calibrated_bank_fn``
    serves the measured instance's exact interval walk when that
    matters. For an all-zero ``NonIdealSpec`` and an unpruned design
    the re-bake reproduces the nominal table (the ideal-limit contract
    the tests pin); merged regions of a pruned design get their
    measured-region midpoint — the best constant reconstruction."""
    designs = list(designs)
    spec, (lb, ub, values, lo, scale) = _measured_instance(
        designs, nonideal, instance, samples)
    n = 2 ** spec.bits
    lo0, scale0 = np.asarray(lo, np.float64)[0], \
        np.asarray(scale, np.float64)[0]                      # (C,)
    vmin = tuple(float(v) for v in lo0)
    vmax = tuple(float(v) for v in lo0 + n / scale0)
    probes = np.arange(n, dtype=np.float64) + 0.5    # measured code units
    out = []
    for k, d in enumerate(designs):
        lbk = np.asarray(lb[k, 0], np.float64)                # (C, n)
        ubk = np.asarray(ub[k, 0], np.float64)
        vals = np.asarray(values[k, 0], np.float32)           # leaf values
        # sel[c, code, leaf]: probes partition over the measured leaf
        # intervals — exactly one live term per code
        sel = ((probes[None, :, None] >= lbk[:, None, :])
               & (probes[None, :, None] < ubk[:, None, :]))
        table = (sel * vals[:, None, :]).sum(-1).astype(np.float32)
        out.append(dataclass_replace(d, table=table, vmin=vmin,
                                     vmax=vmax, calibrated=True))
    return out


def make_calibrated_bank_fn(designs: Sequence[DeployedClassifier],
                            nonideal: NonIdealSpec, *, instance: int = 0,
                            samples: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """The calibrated twin of ``make_nonideal_bank_fn``: one jitted bank
    call serving (M, C) samples -> (D, M, O) logits through a sampled
    hardware instance's *exact* measured interval walk with per-design
    re-baked value tables (the ``mc_eval_cal_population`` entry) — what
    the serving engine swaps in when it calibrates a recovered device
    against its measured non-idealities instead of serving degraded."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import dispatch
    from repro.models import mlp as mlp_lib
    from repro.models import svm as svm_lib
    designs = list(designs)
    d0 = designs[0]
    spec, ops_ft = _measured_instance(designs, nonideal, instance, samples)
    ops_ft = tuple(jnp.asarray(a) for a in ops_ft)
    params = _stacked_model_params(designs)
    apply = svm_lib.apply_svm if d0.kind == "svm" else mlp_lib.apply_mlp

    def fn(xb):
        xq = dispatch.dispatch("mc_eval_cal_population", xb, *ops_ft,
                               spec=spec, interpret=interpret)
        return jax.vmap(lambda p, xq_d: apply(p, xq_d[0]))(params, xq)

    return jax.jit(fn)
