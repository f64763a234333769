"""In-training ADC optimization (paper §3.2): NSGA-II over per-channel
level masks + weight decimal positions, with quantization-aware training in
the inner loop, minimizing {1 - accuracy, normalized ADC area}.

Beyond-paper systems contribution (DESIGN.md §2): the paper evaluates GA
individuals one-by-one through pymoo. Here a *generation* is one compiled
program: genomes decode to a (P, C, 2^N) mask batch, the shared sample
batch is pushed through all P pruned ADC banks at once
(kernels/ops.adc_quantize_population — the Pallas population kernel on
TPU), and the P QAT loops run as a single ``jax.vmap``-batched
train-and-score call whose initial parameter/optimizer buffers are donated
(identical math, P× arithmetic intensity) — evolutionary QAT as an SPMD
workload. ``evaluate_population_reference`` keeps the paper's sequential
per-individual path alive as the parity oracle; tests assert both produce
the same fitness matrix, hence the same Pareto front.

The ``sharded`` engine (DESIGN.md §7) partitions the population axis over
the device mesh via ``shard_map``: genomes, the stacked initial
parameter/optimizer buffers, and hence the (P, C, 2^N) value-table batch
and the vmapped QAT loops all split P/D-per-device (axis choice via
distributed/sharding.population_axes, with divisibility-checked fallback
to the batched single-device engine). Search state checkpoints through
checkpoint/manager.py — genomes, fitness matrix, RNG state, generation
counter — so a killed search resumes mid-run bit-identically
(``run_search(..., ckpt=..., resume=True)``).

Genome layout per individual (C input channels, N-bit ADC):
  [ C * 2^N mask bits | 4 bits decimal-point position (dp in [-8, 7]) ]

Sensor→feature→ADC→classifier co-search (DESIGN.md §14): a config with
``frontend`` (a timeseries.FeatureSpec) appends feature genes AFTER the
dp bits — a subsample-grid index plus a 2-bit resolution-allocation gene
per feature channel — and the data dict stacks one featurized variant
per subsample factor ((V, M, C) instead of (M, C)). All three engines
co-search the joint space in the same compiled programs: quantization
runs over the whole variant stack through the registered population
entry and each individual's subsample gene gathers its variant.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace as dataclass_replace
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core import adc, area, nsga2
from repro.core import nonideal as nonideal_lib
from repro.core.nonideal import NonIdealSpec
from repro.core.spec import AdcSpec, Range, normalize_range
from repro.distributed import sharding as sharding_lib
from repro.faulttol import calibrate as faulttol_cal
from repro.faulttol import redundancy as ft_redundancy
from repro.faulttol.spec import FaultTolSpec
from repro.kernels import ops
from repro.models import mlp as mlp_lib
from repro.timeseries import feature as feature_lib
from repro.timeseries.feature import ALLOC_BITS, FULL_ALLOC, FeatureSpec

DP_BITS = 4


@dataclass(frozen=True)
class SearchConfig:
    bits: int = 4
    pop_size: int = 32
    generations: int = 16
    train_steps: int = 300
    lr: float = 5e-2
    weight_bits: int = 8
    min_levels: int = 2
    seed: int = 0
    mode: str = "tree"            # circuit-faithful pruned-ADC semantics
    design: str = "ours"          # area model used in the fitness
    model: str = "mlp"            # 'mlp' | 'svm' (paper targets both)
    engine: str = "batched"       # 'batched' | 'sharded' | 'reference'
                                  # | 'gradient' (DESIGN.md §13)
    # exact-duplicate genome dedup before QAT (identical individuals in a
    # generation share one compiled train — fitness bit-identical either
    # way; the unique set pads to a power-of-two bucket so recompiles
    # stay bounded)
    dedup: bool = True
    # surrogate-screened NSGA-II (DESIGN.md §13): > 1 oversamples each
    # generation's offspring by this factor and keeps the pop_size
    # candidates a tiny online-trained MLP fitness predictor ranks best;
    # 1 (default) leaves the evolutionary stream bit-identical to PR 3
    screen_factor: int = 1
    surrogate_steps: int = 64     # online predictor train steps per eval
    surrogate_hidden: int = 32
    # gradient engine knobs: lane count (0 -> 4 * pop_size), the
    # log-spaced area-regularizer sweep spreading lanes along the front,
    # gate temperature anneal, soft-value-table sharpness, and the
    # per-chunk snapshot count (also the checkpoint granularity)
    grad_points: int = 0
    grad_train_steps: int = 0     # gate-train budget; 0 -> 8 * train_steps
    grad_lambda_lo: float = 3e-2
    grad_lambda_hi: float = 10.0
    grad_tau0: float = 4.0
    grad_tau1: float = 0.25
    grad_beta: float = 2.0
    grad_snapshots: int = 4
    # surrogate-screened exact polish after the snap+re-score: each round
    # flips every single gate of the current elite (pareto set plus the
    # grad_polish_beam best-accuracy rows), the online surrogate ranks the
    # unseen neighbors (accuracy predicted, area computed exactly), and
    # the top grad_polish_evals go through the exact batched QAT path
    grad_polish_rounds: int = 2
    grad_polish_beam: int = 4
    grad_polish_evals: int = 192
    # analog range — scalar or per-channel tuple (heterogeneous sensors);
    # normalized to hashable form so the config stays a valid static jit arg
    vmin: Range = 0.0
    vmax: Range = 1.0
    # robustness-aware co-search (DESIGN.md §10): with a NonIdealSpec and
    # mc_samples > 0 the fitness grows a third minimized column —
    # 'expected' accuracy drop or 'worst'-case error over the MC instances
    nonideal: Optional[NonIdealSpec] = None
    mc_samples: int = 0
    robust_objective: str = "expected"
    # yield@margin (DESIGN.md §15): the robustness column 'yield' counts
    # the fraction of MC instances within ``yield_margin`` of the ideal
    # accuracy (minimized as 1 - yield)
    yield_margin: float = 0.01
    # fault-tolerant co-search (DESIGN.md §15): a FaultTolSpec appends
    # redundancy/repair genes (per-channel TMR + spare levels, a global
    # calibrate bit) and routes the MC generation through the
    # calibrated-table kernel entries; requires the robustness objective
    # (the genes only matter under the perturbed instance stream)
    faulttol: Optional[FaultTolSpec] = None
    # sensor→feature→ADC→classifier co-search (DESIGN.md §14): a
    # FeatureSpec appends feature genes to the genome and switches the
    # data contract to stacked featurized variants (V, M, C_feat);
    # hashable, so the config stays a valid static jit argument
    frontend: Optional[FeatureSpec] = None

    def __post_init__(self):
        object.__setattr__(self, "vmin", normalize_range(self.vmin))
        object.__setattr__(self, "vmax", normalize_range(self.vmax))
        nonideal_lib.robust_objective_name(self.robust_objective)
        if self.mc_samples < 0:
            raise ValueError(f"mc_samples must be >= 0, got "
                             f"{self.mc_samples}")
        if self.screen_factor < 1:
            raise ValueError(f"screen_factor must be >= 1, got "
                             f"{self.screen_factor}")
        if self.grad_lambda_lo <= 0 or self.grad_lambda_hi <= 0:
            raise ValueError("grad_lambda_lo/hi must be > 0 (log-spaced "
                             "sweep)")
        if self.grad_polish_rounds < 0 or self.grad_polish_beam < 1 \
                or self.grad_polish_evals < 1:
            raise ValueError("grad_polish_rounds must be >= 0 and "
                             "grad_polish_beam/evals >= 1")
        if self.frontend is not None and self.mc_samples > 0:
            raise ValueError(
                "the feature-frontend co-search and the Monte-Carlo "
                "robustness objective are mutually exclusive: the MC "
                "kernel family consumes flat (M, C) test batches, not "
                "the co-search's stacked (V, M, C) variant data")
        if not 0.0 <= self.yield_margin < 1.0:
            raise ValueError(f"yield_margin must be in [0, 1), got "
                             f"{self.yield_margin}")
        if self.faulttol is not None and not self.wants_robustness:
            raise ValueError(
                "fault-tolerant co-search needs the Monte-Carlo "
                "robustness objective (a NonIdealSpec and mc_samples "
                "> 0) — redundancy genes only matter under the "
                "perturbed instance stream")

    @property
    def wants_robustness(self) -> bool:
        """True when the search optimizes the third (robustness) objective."""
        return self.nonideal is not None and self.mc_samples > 0

    @property
    def n_objectives(self) -> int:
        return 3 if self.wants_robustness else 2

    @property
    def adc_spec(self) -> AdcSpec:
        """The ADC design point this search optimizes around — the single
        object every downstream layer (value tables, kernels, deployment
        artifacts) consumes (core/spec.py)."""
        return AdcSpec(bits=self.bits, mode=self.mode, vmin=self.vmin,
                       vmax=self.vmax)

    @classmethod
    def for_spec(cls, spec: AdcSpec, **kw) -> "SearchConfig":
        """Build a config around an AdcSpec (the repro.api entry path)."""
        return cls(bits=spec.bits, mode=spec.mode, vmin=spec.vmin,
                   vmax=spec.vmax, **kw)


def genome_len(channels: int, bits: int,
               frontend: Optional[FeatureSpec] = None,
               faulttol: Optional[FaultTolSpec] = None) -> int:
    base = channels * 2 ** bits + DP_BITS
    base += frontend.gene_bits if frontend is not None else 0
    return base + (faulttol.gene_bits(channels)
                   if faulttol is not None else 0)


def _faulttol_genes(genomes: jnp.ndarray, channels: int, bits: int,
                    ft: FaultTolSpec):
    """(..., G) genomes -> (tmr (..., C), spares (..., C), cal (...))
    int32. Fault-tolerance genes sit after the dp bits (the frontend
    genes of §14 are mutually exclusive with robustness search, so the
    slot never collides)."""
    base = channels * 2 ** bits + DP_BITS
    genes = genomes[..., base:base + ft.gene_bits(channels)]
    return ft_redundancy.decode_genes(genes, channels, ft)


def decode_population_faulttol(genomes: jnp.ndarray, channels: int,
                               bits: int, min_levels: int,
                               faulttol: FaultTolSpec):
    """FT decode: (P, G) -> (masks (P, C, 2^N) with the spare levels
    applied, dps (P,) f32, tmr (P, C), spares (P, C), cal (P,)). Spares
    re-enable pruned levels AFTER repair (adc.add_levels), so the mask
    the fitness quantizes through — and the area walk prices — is the
    spare-augmented one."""
    masks, dps = decode_population(genomes, channels, bits, min_levels)
    tmr, spares, cal = _faulttol_genes(genomes, channels, bits, faulttol)
    return adc.add_levels(masks, spares), dps, tmr, spares, cal


def decode_genome_faulttol(genome: jnp.ndarray, channels: int, bits: int,
                           min_levels: int, faulttol: FaultTolSpec):
    """Single-genome FT decode -> (mask, dp, tmr, spares, cal)."""
    masks, dps, tmr, spares, cal = decode_population_faulttol(
        jnp.asarray(genome)[None], channels, bits, min_levels, faulttol)
    return masks[0], dps[0], tmr[0], spares[0], cal[0]


def _frontend_genes(genomes: jnp.ndarray, channels: int, bits: int,
                    frontend: FeatureSpec
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(P, G) genomes -> (sub (P,) int32 indices into frontend.sub_grid,
    alloc (P, C) int32 in [0, FULL_ALLOC]). Feature genes sit after the
    dp bits, LSB-first — the layout feature.encode_genes writes."""
    base = channels * 2 ** bits + DP_BITS
    sb = frontend.sub_bits
    if sb:
        subb = genomes[:, base:base + sb].astype(jnp.int32)
        sub = jnp.sum(subb * (2 ** jnp.arange(sb))[None, :], axis=-1)
    else:
        sub = jnp.zeros(genomes.shape[0], jnp.int32)
    ab = genomes[:, base + sb:base + sb + channels * ALLOC_BITS]
    ab = ab.astype(jnp.int32).reshape(-1, channels, ALLOC_BITS)
    alloc = jnp.sum(ab * (2 ** jnp.arange(ALLOC_BITS))[None, None, :],
                    axis=-1)
    return sub, alloc


def _alloc_masks(masks: jnp.ndarray, alloc: jnp.ndarray, bits: int,
                 min_levels: int) -> jnp.ndarray:
    """Apply the per-channel resolution-allocation ladder to repaired
    masks (P, C, 2^N): alloc a in [1, FULL_ALLOC] restricts the kept set
    to every 2^(FULL_ALLOC - a)-th level (then re-repairs, so min_levels
    still holds); a = 0 turns the channel OFF — a one-hot level-0 mask,
    i.e. a constant input with zero comparators
    (area.pruned_binary_tc == 0). The off override applies AFTER repair:
    repair would otherwise re-enable levels on a dead channel."""
    n = 2 ** bits
    idx = jnp.arange(n)
    stride = 2 ** (FULL_ALLOC - jnp.clip(alloc, 1, FULL_ALLOC))
    allowed = (idx[None, None, :] % stride[..., None]) == 0      # (P, C, n)
    laddered = adc.repair_mask(masks * allowed.astype(jnp.int32),
                               min_levels)
    onehot0 = jnp.zeros((n,), jnp.int32).at[0].set(1)
    return jnp.where((alloc == 0)[..., None], onehot0[None, None, :],
                     laddered)


def decode_population_cosearch(genomes: jnp.ndarray, channels: int,
                               bits: int, min_levels: int,
                               frontend: FeatureSpec):
    """Co-search decode: (P, G) -> (masks (P, C, 2^N) with the allocation
    ladder applied, dps (P,) f32, sub (P,) variant indices,
    alloc (P, C))."""
    masks, dps = decode_population(genomes, channels, bits, min_levels)
    sub, alloc = _frontend_genes(genomes, channels, bits, frontend)
    return _alloc_masks(masks, alloc, bits, min_levels), dps, sub, alloc


def decode_genome_cosearch(genome: jnp.ndarray, channels: int, bits: int,
                           min_levels: int, frontend: FeatureSpec):
    """Single-genome co-search decode -> (mask, dp, sub, alloc)."""
    masks, dps, sub, alloc = decode_population_cosearch(
        jnp.asarray(genome)[None], channels, bits, min_levels, frontend)
    return masks[0], dps[0], sub[0], alloc[0]


def decode_genome(genome: jnp.ndarray, channels: int, bits: int,
                  min_levels: int = 2) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """genome (G,) uint8 -> (mask (C, 2^N) int32, dp scalar float)."""
    n = 2 ** bits
    mask = genome[: channels * n].reshape(channels, n).astype(jnp.int32)
    mask = adc.repair_mask(mask, min_levels)
    dpb = genome[channels * n: channels * n + DP_BITS].astype(jnp.int32)
    dp = jnp.sum(dpb * (2 ** jnp.arange(DP_BITS))) - 8   # [-8, 7]
    return mask, dp.astype(jnp.float32)


def decode_population(genomes: jnp.ndarray, channels: int, bits: int,
                      min_levels: int = 2) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(P, G) genomes -> (masks (P, C, 2^N) int32, dp (P,) float32).
    Pure reshape/arithmetics — no per-individual loop; ``repair_mask`` and
    the LUT walk downstream are batched over the population axis."""
    p = genomes.shape[0]
    n = 2 ** bits
    masks = genomes[:, : channels * n].reshape(p, channels, n).astype(jnp.int32)
    masks = adc.repair_mask(masks, min_levels)
    dpb = genomes[:, channels * n: channels * n + DP_BITS].astype(jnp.int32)
    dps = jnp.sum(dpb * (2 ** jnp.arange(DP_BITS))[None, :], axis=-1) - 8
    return masks, dps.astype(jnp.float32)


# ------------------------------------------------------------- QAT inner loop
def _init_model(sizes, cfg: SearchConfig):
    """Initial (params, opt) for one individual — every individual starts
    from the same seed (the genome only controls the ADC + dp)."""
    from repro.models import svm as svm_lib
    from repro.optim import adamw
    if cfg.model == "svm":
        params = svm_lib.init_svm(jax.random.PRNGKey(cfg.seed), sizes[0],
                                  sizes[-1])
    else:
        params = mlp_lib.init_mlp(jax.random.PRNGKey(cfg.seed), sizes)
    return params, adamw.init(params)


def _train_from_quantized(xq_tr, xq_te, y_tr, y_te, dp, params, opt,
                          sizes, cfg: SearchConfig,
                          return_params: bool = False):
    """QAT one individual from its already-quantized inputs: returns the
    int32 count of correct test predictions (``accuracy`` divides it on
    the host), or ``(count, trained params)`` with ``return_params`` —
    the export path keeps the parameters the fitness was measured on
    instead of throwing them away. vmap target — all
    operands carry the population axis at the call site; ``dp`` may be
    traced per individual."""
    from repro.models import svm as svm_lib
    from repro.optim import adamw
    # cfg.weight_bits flows into BOTH the loss and the accuracy: the
    # fitness must be measured on the same quantized forward the deployed
    # artifact bakes (deploy.export_front), or the bit-for-bit round-trip
    # contract would only hold at the 8-bit default
    if cfg.model == "svm":
        loss_of = lambda p: svm_lib.svm_loss(p, xq_tr, y_tr, dp,
                                             weight_bits=cfg.weight_bits)
        correct_of = lambda p: svm_lib.n_correct(p, xq_te, y_te, dp,
                                                 cfg.weight_bits)
    else:
        def loss_of(p):
            logits = mlp_lib.apply_mlp(p, xq_tr, dp, cfg.weight_bits)
            logp = jax.nn.log_softmax(logits)
            onehot = jax.nn.one_hot(y_tr, sizes[-1])
            return -(onehot * logp).sum(-1).mean()

        correct_of = lambda p: mlp_lib.n_correct(p, xq_te, y_te, dp,
                                                 cfg.weight_bits)

    def step(carry, _):
        p, o = carry
        g = jax.grad(loss_of)(p)
        p, o = adamw.update(g, o, p, lr=cfg.lr)
        return (p, o), ()

    (params, _), _ = jax.lax.scan(step, (params, opt), length=cfg.train_steps)
    if return_params:
        return correct_of(params), params
    return correct_of(params)


def _train_eval_one(genome, data, sizes, cfg: SearchConfig,
                    draws: Optional[nonideal_lib.Draws] = None):
    """QAT one individual end-to-end (decode -> quantize -> train). The
    paper-faithful sequential path; also the per-individual parity oracle
    for the batched engine. With a robustness-enabled config and
    ``draws`` returns ``(correct, (S,) per-instance MC correct counts)`` —
    the single-design MC entry standing in for the population launch."""
    channels = sizes[0]
    if cfg.frontend is not None:
        # co-search: the subsample gene gathers this individual's
        # featurized variant from the (V, M, C) stack (dynamic index
        # under jit); quantization is elementwise, so gather-then-
        # quantize here equals the batched engine's quantize-then-gather
        # bit for bit
        mask, dp, sub, _ = decode_genome_cosearch(
            genome, channels, cfg.bits, cfg.min_levels, cfg.frontend)
        x_tr, x_te = data["x_train"][sub], data["x_test"][sub]
    elif cfg.faulttol is not None:
        mask, dp, tmr, _, cal = decode_genome_faulttol(
            genome, channels, cfg.bits, cfg.min_levels, cfg.faulttol)
        x_tr, x_te = data["x_train"], data["x_test"]
    else:
        mask, dp = decode_genome(genome, channels, cfg.bits,
                                 cfg.min_levels)
        x_tr, x_te = data["x_train"], data["x_test"]
    # ste=False: inputs are data, no gradient flows to them, and skipping
    # the x + (xq - x) round-trip keeps the values bitwise-identical to the
    # batched engine's value-table gather (parity tests rely on this).
    xq_tr = adc.adc_quantize(x_tr, mask, bits=cfg.bits,
                             vmin=cfg.vmin, vmax=cfg.vmax,
                             mode=cfg.mode, ste=False)
    xq_te = adc.adc_quantize(x_te, mask, bits=cfg.bits,
                             vmin=cfg.vmin, vmax=cfg.vmax,
                             mode=cfg.mode, ste=False)
    params, opt = _init_model(sizes, cfg)
    robust = cfg.wants_robustness and draws is not None
    out = _train_from_quantized(xq_tr, xq_te, data["y_train"],
                                data["y_test"], dp, params, opt, sizes,
                                cfg, return_params=robust)
    if not robust:
        return out
    correct, trained = out
    if cfg.faulttol is not None:
        from repro.kernels import dispatch
        ft_ops = faulttol_cal.mc_operands_ft(cfg.adc_spec, cfg.nonideal,
                                             mask, tmr, cal, draws)
        xq_mc = dispatch.dispatch("mc_eval_cal", data["x_test"], *ft_ops,
                                  spec=cfg.adc_spec)           # (S, M, C)
    else:
        xq_mc = nonideal_lib.mc_quantize(data["x_test"], mask,
                                         cfg.adc_spec, cfg.nonideal,
                                         draws=draws)
    return correct, _mc_correct_fn(data, cfg)(trained, dp, xq_mc)  # (S,)


def _mc_correct_fn(data: Dict, cfg: SearchConfig):
    """Per-individual MC scoring: (trained params, dp, xq (S, M, C)) ->
    (S,) correct test counts — the same op as the ideal fitness, vmapped
    over the MC instance axis."""
    from repro.models import svm as svm_lib
    count = svm_lib.n_correct if cfg.model == "svm" else mlp_lib.n_correct

    def fn(params, dp, xq_s):
        one = lambda xq: count(params, xq, data["y_test"], dp,
                               cfg.weight_bits)
        return jax.vmap(one)(xq_s)

    return fn


def accuracy(correct, n: int) -> np.ndarray:
    """Correct counts -> f32 accuracies: the count over ``n`` divided on
    the host and rounded once to f32. Every engine, the export and the
    serving parity checks divide here, so a design's accuracy is the
    same number on every path and backend. f32 keeps the host-side f64
    MC reductions exact (nonideal.mc_mean_accuracy)."""
    return (np.asarray(correct, np.float64) / n).astype(np.float32)


def _train_and_score(genomes: jnp.ndarray, params0, opt0, data: Dict,
                     sizes: Tuple[int, ...], cfg: SearchConfig,
                     return_params: bool = False,
                     draws: Optional[nonideal_lib.Draws] = None) -> Dict:
    """(P, G) genomes -> ``{'correct': (P,) correct test counts}`` as ONE
    compiled program; ``return_params=True`` adds the trained parameter
    stacks under ``'params'`` (each leaf (P, ...) — the raw material of a
    deployment export, core/deploy.py); a robustness-enabled config plus
    ``draws`` adds ``'mc_correct'``, the raw (P, S) per-instance MC
    correct counts: the MC population kernel pushes the shared test batch
    through ``cfg.mc_samples`` perturbed instances of every individual's
    ADC (one (P, S, M/bm) launch) and the trained models re-score each
    perturbed view (DESIGN.md §10); callers reduce the third fitness
    column host-side via ``nonideal.robust_objective``.

    The population's initial parameter and optimizer buffers (``params0``,
    ``opt0``, stacked over P) are donated: XLA reuses their memory for the
    training-state carry instead of holding both generations live. The
    input quantization runs through the population kernel path *before*
    the vmap, so on TPU it is one (P, M/bm)-grid Pallas launch rather than
    P gathers."""
    spec = cfg.adc_spec
    if cfg.frontend is not None:
        # co-search: quantize the WHOLE (V, M, C) variant stack through
        # the registered population entry (one launch, reshaped), then
        # let each individual's subsample gene gather its variant
        masks, dps, sub, _ = decode_population_cosearch(
            genomes, sizes[0], cfg.bits, cfg.min_levels, cfg.frontend)
        lane = jnp.arange(genomes.shape[0])
        xq_tr = ops.adc_quantize_variants(data["x_train"], masks,
                                          spec=spec)[lane, sub]
        xq_te = ops.adc_quantize_variants(data["x_test"], masks,
                                          spec=spec)[lane, sub]
    elif cfg.faulttol is not None:
        # FT co-search: the spare-augmented masks feed BOTH the ideal
        # quantization (spare levels are real resolution) and the MC
        # interval compilation below
        masks, dps, tmr, _, cal = decode_population_faulttol(
            genomes, sizes[0], cfg.bits, cfg.min_levels, cfg.faulttol)
        xq_tr = ops.adc_quantize_population(data["x_train"], masks,
                                            spec=spec)
        xq_te = ops.adc_quantize_population(data["x_test"], masks,
                                            spec=spec)
    else:
        masks, dps = decode_population(genomes, sizes[0], cfg.bits,
                                       cfg.min_levels)
        xq_tr = ops.adc_quantize_population(data["x_train"], masks,
                                            spec=spec)
        xq_te = ops.adc_quantize_population(data["x_test"], masks,
                                            spec=spec)
    robust = cfg.wants_robustness and draws is not None
    want_params = return_params or robust
    fn = lambda xtr, xte, dp, p, o: _train_from_quantized(
        xtr, xte, data["y_train"], data["y_test"], dp, p, o, sizes, cfg,
        want_params)
    out = jax.vmap(fn)(xq_tr, xq_te, dps, params0, opt0)
    correct, params = out if want_params else (out, None)
    result = {"correct": correct}
    if robust:
        from repro.kernels import dispatch
        if cfg.faulttol is not None:
            # redundancy folds into the draw stream (majority-voted
            # effective draws) and calibration into per-design value
            # tables — one mixed-population calibrated-table launch
            ft_ops = faulttol_cal.mc_operands_ft(spec, cfg.nonideal,
                                                 masks, tmr, cal, draws)
            xq_mc = dispatch.dispatch("mc_eval_cal_population",
                                      data["x_test"], *ft_ops,
                                      spec=spec)           # (P, S, M, C)
        else:
            mc = nonideal_lib.mc_operands(spec, cfg.nonideal, masks,
                                          draws=draws)
            xq_mc = dispatch.dispatch("mc_eval_population",
                                      data["x_test"], *mc,
                                      spec=spec)           # (P, S, M, C)
        # per-instance counts leave the compiled program raw; the
        # objective reduction happens host-side in f64
        # (nonideal.robust_objective) so the search fitness and
        # deploy.evaluate_robustness agree bit-for-bit
        result["mc_correct"] = jax.vmap(_mc_correct_fn(data, cfg))(
            params, dps, xq_mc)
    if return_params:
        result["params"] = params
    return result


@functools.lru_cache(maxsize=1)
def _train_and_score_jit():
    """Jitted generation step. Optimizer/parameter buffers are donated on
    accelerator backends (XLA CPU cannot alias them and would warn)."""
    donate = (1, 2) if jax.default_backend() != "cpu" else ()
    return jax.jit(_train_and_score,
                   static_argnames=("sizes", "cfg", "return_params"),
                   donate_argnums=donate)


def _stacked_init(pop: int, sizes, cfg: SearchConfig):
    """P copies of the shared initial (params, opt) pytrees, materialized
    so the jit can donate them."""
    params, opt = _init_model(sizes, cfg)
    tile = lambda a: jnp.tile(a[None], (pop,) + (1,) * a.ndim)
    return (jax.tree_util.tree_map(tile, params),
            jax.tree_util.tree_map(tile, opt))


def search_draws(cfg: SearchConfig, channels: int):
    """The search's Monte-Carlo draw block — one stream per run, fixed
    across generations and shared across individuals (common random
    numbers), a pure function of ``cfg.nonideal.seed``. None when the
    config has no robustness objective; a fault-tolerant config draws
    the 3-replica ``RedundantDraws`` stream instead (the TMR genes pick
    per channel whether the vote or replica 0 applies).
    ``deploy.evaluate_robustness`` re-derives the identical stream from
    the same NonIdealSpec, which is what makes the third fitness column
    reproducible from a deployed front."""
    if not cfg.wants_robustness:
        return None
    if cfg.faulttol is not None:
        return ft_redundancy.draw_redundant(cfg.bits, channels,
                                            cfg.mc_samples, cfg.nonideal)
    return nonideal_lib.draw(cfg.bits, channels, cfg.mc_samples,
                             cfg.nonideal)


def train_pareto_front(genomes: np.ndarray, data: Dict,
                       sizes: Tuple[int, ...], cfg: SearchConfig):
    """Re-train the given (typically Pareto-front) genomes and keep what
    the search-time fitness threw away: the trained parameter stacks.

    Returns ``(accs (K,) f64, params, masks (K, C, 2^N) i32, dps (K,) f32)``
    with every ``params`` leaf stacked over K. Each individual's QAT is a
    pure function of (genome, data, cfg) — every lane of the vmapped
    program is independent — so the accuracies reproduce the search-time
    fitness *bit-for-bit* regardless of which generation (or population
    size) originally evaluated the genome; tests/test_deploy_serve.py
    pins that contract. This is the search -> deployment-artifact bridge
    (core/deploy.export_front)."""
    genomes = np.asarray(genomes, np.uint8)
    dev_data = {k: jnp.asarray(v) for k, v in data.items()}
    params0, opt0 = _stacked_init(len(genomes), sizes, cfg)
    out = _train_and_score_jit()(
        jnp.asarray(genomes), params0, opt0, dev_data, tuple(sizes), cfg,
        return_params=True)
    accs = accuracy(out["correct"], len(data["y_test"]))
    params = out["params"]
    if cfg.frontend is not None:
        # alloc-applied masks: the exported design must bake the SAME
        # pruned levels the fitness was measured on
        masks, dps, _, _ = decode_population_cosearch(
            jnp.asarray(genomes), sizes[0], cfg.bits, cfg.min_levels,
            cfg.frontend)
    elif cfg.faulttol is not None:
        masks, dps, _, _, _ = decode_population_faulttol(
            jnp.asarray(genomes), sizes[0], cfg.bits, cfg.min_levels,
            cfg.faulttol)
    else:
        masks, dps = decode_population(jnp.asarray(genomes), sizes[0],
                                       cfg.bits, cfg.min_levels)
    return (np.asarray(accs, np.float64), jax.device_get(params),
            np.asarray(masks), np.asarray(dps))


# ------------------------------------------------------------------- fitness
def population_areas(genomes: np.ndarray, channels: int, cfg: SearchConfig
                     ) -> np.ndarray:
    """(P, G) genomes -> (P,) normalized ADC areas (vs the full flash bank).
    Mask decode + repair is one batched device call; the exact-integer
    design-rule walk stays in numpy per mask (it is not the bottleneck)."""
    n = 2 ** cfg.bits
    g = np.asarray(genomes)
    masks = jnp.asarray(g[:, : channels * n].reshape(-1, channels, n),
                        jnp.int32)
    masks = adc.repair_mask(masks, cfg.min_levels)
    fe = cfg.frontend
    if fe is not None:
        # co-search area: ADC transistors of the alloc-applied masks plus
        # the exact front-end count of (subsample, alloc), normalized by
        # the full-flash + full-frontend reference so transistor count
        # stays the single budget axis
        sub, alloc = _frontend_genes(jnp.asarray(g, jnp.uint8), channels,
                                     cfg.bits, fe)
        masks = np.asarray(_alloc_masks(masks, alloc, cfg.bits,
                                        cfg.min_levels))
        sub, alloc = np.asarray(sub), np.asarray(alloc)
        denom = max(area.flash_full_tc(cfg.bits) * channels
                    + feature_lib.frontend_full_tc(fe), 1)
        tc = [area.system_tc(m, cfg.design)
              + feature_lib.frontend_tc(fe, fe.sub_grid[int(s)], a)
              for m, s, a in zip(masks, sub, alloc)]
        return np.array(tc, np.float64) / denom
    ft = cfg.faulttol
    if ft is not None:
        # FT area: ADC transistors of the spare-augmented masks plus the
        # exact voter/calibration overhead of (tmr, calibrate), on the
        # same full-flash budget axis — redundancy is PAID, not free
        tmr, spares, cal = _faulttol_genes(jnp.asarray(g, jnp.uint8),
                                           channels, cfg.bits, ft)
        masks = np.asarray(adc.add_levels(masks, spares))
        tmr, cal = np.asarray(tmr), np.asarray(cal)
        flash_full = max(area.flash_full_tc(cfg.bits) * channels, 1)
        tc = [area.system_tc(m, cfg.design)
              + area.faulttol_tc(m, t, bool(cv))
              for m, t, cv in zip(masks, tmr, cal)]
        return np.array(tc, np.float64) / flash_full
    masks = np.asarray(masks)
    flash_full = max(area.flash_full_tc(cfg.bits) * channels, 1)
    return np.array([area.system_tc(m, cfg.design) for m in masks],
                    np.float64) / flash_full


def _dedup_bucket(unique: int, pop: int) -> int:
    """Smallest power-of-two >= the unique count (capped at the
    population size) — the padded shape the compiled program runs at, so
    dedup triggers at most log2(P) distinct compilations per config
    instead of one per unique-count."""
    b = 1
    while b < unique:
        b *= 2
    return min(b, pop)


def _eval_dedup(genomes: np.ndarray, cfg: SearchConfig, core) -> Dict:
    """Exact-duplicate genome dedup around a population evaluation:
    ``core`` maps a (B, G) uint8 batch to a dict of (B, ...) arrays.
    Duplicates share one QAT lane; the unique set pads (by repeating row
    0) to a power-of-two bucket and results scatter back through the
    inverse index. Bit-identical to evaluating the full population:
    every vmapped QAT lane is a pure function of its own genome (the
    PR 3 contract ``train_pareto_front`` pins), so neither the sharing
    nor the padding changes any individual's fitness."""
    genomes = np.asarray(genomes, np.uint8)
    if not cfg.dedup or len(genomes) <= 1:
        return core(genomes)
    uniq, inverse = np.unique(genomes, axis=0, return_inverse=True)
    if len(uniq) == len(genomes):
        return core(genomes)
    bucket = _dedup_bucket(len(uniq), len(genomes))
    if bucket > len(uniq):
        uniq = np.concatenate(
            [uniq, np.repeat(uniq[:1], bucket - len(uniq), axis=0)])
    out = core(uniq)
    inverse = np.asarray(inverse).reshape(-1)
    return {k: np.asarray(v)[inverse] for k, v in out.items()}


def evaluate_population(genomes: np.ndarray, data: Dict, sizes,
                        cfg: SearchConfig,
                        draws: Optional[nonideal_lib.Draws] = None
                        ) -> np.ndarray:
    """Batched engine. Full fitness: [1 - accuracy, normalized ADC area]
    plus, for a robustness-enabled config, the Monte-Carlo robustness
    column (all minimized) — one donated-buffer compiled call per
    generation, with exact-duplicate genomes sharing one QAT lane
    (``cfg.dedup``)."""
    if draws is None:
        draws = search_draws(cfg, sizes[0])
    dev_data = {k: jnp.asarray(v) for k, v in data.items()}

    def core(g):
        params0, opt0 = _stacked_init(len(g), sizes, cfg)
        out = _train_and_score_jit()(
            jnp.asarray(g, jnp.uint8), params0, opt0, dev_data,
            tuple(sizes), cfg, draws=draws)
        return {k: np.asarray(v) for k, v in out.items()}

    return _fitness(_eval_dedup(genomes, cfg, core), genomes, data, sizes,
                    cfg)


def _fitness(out: Dict, genomes: np.ndarray, data: Dict, sizes,
             cfg: SearchConfig) -> np.ndarray:
    """Correct counts (+ MC counts) -> the minimized fitness columns
    [1 - accuracy, normalized area(, robustness)], reduced on the host
    in f64 — shared by every engine."""
    n = len(data["y_test"])
    accs = accuracy(out["correct"], n)
    cols = [1.0 - accs, population_areas(genomes, sizes[0], cfg)]
    if "mc_correct" in out:
        cols.append(nonideal_lib.robust_objective(
            accs, accuracy(out["mc_correct"], n), cfg.robust_objective,
            margin=cfg.yield_margin))
    return np.stack(cols, axis=1)


# ------------------------------------------------------------ sharded engine
def default_search_mesh() -> jax.sharding.Mesh:
    """All visible devices on a ('data', 'model') mesh, model=1 — GA
    individuals are embarrassingly parallel, so every chip takes
    population slices. (A caller with a real 2D mesh passes it in and
    population_axes folds both axes into the population split.)"""
    return jax.make_mesh((len(jax.devices()), 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@functools.lru_cache(maxsize=8)
def _sharded_train_and_score(mesh, axes, sizes, cfg: SearchConfig):
    """Jitted shard_map'd generation step: the population axis of the
    genomes and the donated-style stacked train states splits over
    ``axes``; the shared dataset replicates. Inside the body every device
    runs the plain batched program on its P/D slice — decode, value
    tables, the (P_local, M/bm) population-kernel grid, and the vmapped
    QAT scan all stay local, so no cross-device traffic exists between
    the initial scatter and the final fitness gather."""
    pspec = PartitionSpec(axes)

    def body(genomes, params0, opt0, data, draws):
        return _train_and_score(genomes, params0, opt0, data, sizes, cfg,
                                draws=draws)

    # mirror the batched engine: donate the stacked train states on
    # accelerators so each device's initial buffers alias the scan carry
    # (XLA CPU cannot alias and would warn). The genome/train-state
    # population axis splits over ``axes``; the dataset AND the MC draw
    # block replicate (common random numbers must be common across
    # shards); every output leaf (acc, robust) carries the population
    # axis, so the single pspec prefix covers the dict.
    donate = (1, 2) if jax.default_backend() != "cpu" else ()
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, pspec, pspec, PartitionSpec(), PartitionSpec()),
        out_specs=pspec, check_vma=False), donate_argnums=donate)


def evaluate_population_sharded(genomes: np.ndarray, data: Dict, sizes,
                                cfg: SearchConfig,
                                mesh: Optional[jax.sharding.Mesh] = None,
                                draws: Optional[nonideal_lib.Draws] = None
                                ) -> np.ndarray:
    """Device-sharded engine: same fitness contract as
    ``evaluate_population`` with the population partitioned P/D per
    device — exact-duplicate dedup included (``cfg.dedup``). Falls back
    to the batched engine when no mesh axis set divides the batch (the
    divisibility-checked fallback — results identical, just unsharded);
    the dedup bucket is checked the same way, so a non-divisible unique
    bucket runs batched rather than skipping the dedup."""
    mesh = default_search_mesh() if mesh is None else mesh
    if draws is None:
        draws = search_draws(cfg, sizes[0])
    dev_data = {k: jnp.asarray(v) for k, v in data.items()}

    def core(g):
        axes = sharding_lib.population_axes(mesh, len(g))
        params0, opt0 = _stacked_init(len(g), sizes, cfg)
        if axes is None:
            out = _train_and_score_jit()(
                jnp.asarray(g, jnp.uint8), params0, opt0, dev_data,
                tuple(sizes), cfg, draws=draws)
        else:
            fn = _sharded_train_and_score(mesh, axes, tuple(sizes), cfg)
            out = fn(jnp.asarray(g, jnp.uint8), params0, opt0, dev_data,
                     draws)
        return {k: np.asarray(v) for k, v in out.items()}

    return _fitness(_eval_dedup(genomes, cfg, core), genomes, data, sizes,
                    cfg)


@functools.partial(jax.jit, static_argnames=("sizes", "cfg"))
def _eval_one(genome, data, sizes, cfg: SearchConfig, draws=None):
    return _train_eval_one(genome, data, sizes, cfg, draws=draws)


def evaluate_population_reference(genomes: np.ndarray, data: Dict, sizes,
                                  cfg: SearchConfig,
                                  draws: Optional[nonideal_lib.Draws] = None
                                  ) -> np.ndarray:
    """Per-individual reference path (the paper's pymoo-style loop): same
    fitness as ``evaluate_population`` — robustness column included for a
    robustness-enabled config — one compiled QAT per individual."""
    if draws is None:
        draws = search_draws(cfg, sizes[0])
    dev_data = {k: jnp.asarray(v) for k, v in data.items()}
    rows = [_eval_one(jnp.asarray(g, jnp.uint8), dev_data,
                      tuple(sizes), cfg, draws=draws)
            for g in genomes]
    if cfg.wants_robustness:
        out = {"correct": np.array([int(c) for c, _ in rows]),
               "mc_correct": np.stack([np.asarray(m) for _, m in rows])}
    else:
        out = {"correct": np.array([int(c) for c in rows])}
    return _fitness(out, genomes, data, sizes, cfg)


def make_eval_fn(data: Dict, sizes, cfg: SearchConfig,
                 mesh: Optional[jax.sharding.Mesh] = None
                 ) -> Callable[[np.ndarray], np.ndarray]:
    """The (P, G) -> (P, n_objectives) fitness function ``nsga2.evolve``
    consumes, dispatched on ``cfg.engine``. The dataset moves
    host->device once here, not once per generation (``jnp.asarray``
    downstream no-ops on the device copies); so does the MC draw block of
    a robustness-enabled config (one stream for the whole run — fixed
    instances across generations keep the third objective a
    deterministic function of the genome)."""
    dev_data = {k: jnp.asarray(v) for k, v in data.items()}
    draws = search_draws(cfg, sizes[0])
    if cfg.engine == "reference":
        return lambda pop: evaluate_population_reference(
            pop, dev_data, sizes, cfg, draws=draws)
    if cfg.engine == "sharded":
        m = default_search_mesh() if mesh is None else mesh
        return lambda pop: evaluate_population_sharded(
            pop, dev_data, sizes, cfg, mesh=m, draws=draws)
    if cfg.engine == "gradient":
        raise ValueError("the gradient engine is not a per-generation "
                         "eval_fn — run it through run_search / "
                         "run_gradient_search (DESIGN.md §13)")
    if cfg.engine != "batched":
        raise ValueError(f"unknown engine {cfg.engine!r}")
    return lambda pop: evaluate_population(pop, dev_data, sizes, cfg,
                                           draws=draws)


# --------------------------------------------------- search-state checkpoint
def search_state_tree(state: nsga2.EvolveState,
                      surrogate_state=None) -> Dict[str, np.ndarray]:
    """EvolveState -> the flat array tree CheckpointManager persists
    (DESIGN.md §7 format): genomes, fitness matrix, the numpy Generator's
    bit_generator state (JSON packed to uint8 — PCG64 words exceed
    int64), and the completed-generation counter. A screened search
    (``cfg.screen_factor > 1``) adds the online surrogate's leaves under
    indexed keys so a resumed run screens with the identical predictor
    (DESIGN.md §13)."""
    from repro.checkpoint import manager
    tree = {
        "genomes": np.asarray(state.pop, np.uint8),
        "fitness": np.asarray(state.fit, np.float64),
        "rng_state": manager.pack_json(state.rng.bit_generator.state),
        "generation": np.asarray(state.generation, np.int64),
    }
    if surrogate_state is not None:
        for i, leaf in enumerate(jax.tree_util.tree_leaves(surrogate_state)):
            tree[f"surrogate_{i}"] = np.asarray(jax.device_get(leaf))
    return tree


def restore_search_state(ckpt, step: int, pop_size: int, glen: int,
                         n_obj: int = 2, surrogate_like=None):
    """Inverse of ``search_state_tree``. host=True keeps float64 fitness
    and the exact RNG words (device_put would canonicalize to f32).
    ``n_obj`` is the fitness width the config implies (3 for a
    robustness-enabled search). With ``surrogate_like`` (a template
    surrogate state carrying the expected leaf shapes) returns
    ``(EvolveState, restored surrogate state)`` instead of the bare
    EvolveState."""
    from repro.checkpoint import manager
    like = {"genomes": np.zeros((pop_size, glen), np.uint8),
            "fitness": np.zeros((pop_size, n_obj), np.float64),
            "rng_state": np.zeros(1, np.uint8),
            "generation": np.zeros((), np.int64)}
    sur_leaves, sur_def = (jax.tree_util.tree_flatten(surrogate_like)
                           if surrogate_like is not None else ([], None))
    for i, leaf in enumerate(sur_leaves):
        like[f"surrogate_{i}"] = np.zeros(leaf.shape,
                                          np.asarray(leaf).dtype)
    tree = ckpt.restore(step, like, host=True)
    if tuple(tree["genomes"].shape) != (pop_size, glen):
        raise ValueError(
            f"checkpoint at step {step} holds genomes of shape "
            f"{tree['genomes'].shape}, but the current config expects "
            f"({pop_size}, {glen}) — resuming with changed --pop/--bits/"
            f"dataset would silently corrupt the search")
    rng = np.random.default_rng()
    rng.bit_generator.state = manager.unpack_json(tree["rng_state"])
    state = nsga2.EvolveState(np.asarray(tree["genomes"], np.uint8),
                              np.asarray(tree["fitness"], np.float64),
                              int(tree["generation"]), rng)
    if surrogate_like is None:
        return state
    restored = jax.tree_util.tree_unflatten(
        sur_def, [jnp.asarray(tree[f"surrogate_{i}"])
                  for i in range(len(sur_leaves))])
    return state, restored


def _validate_frontend(data: Dict, sizes, cfg: SearchConfig) -> None:
    """Co-search data contract: sizes[0] counts FEATURE channels and the
    x arrays stack one featurized variant per sub_grid factor."""
    fe = cfg.frontend
    if fe is None:
        return
    if fe.feature_channels != sizes[0]:
        raise ValueError(
            f"frontend produces {fe.feature_channels} feature channels "
            f"({fe.channels} raw x {len(fe.features)} features) but "
            f"sizes[0] is {sizes[0]}")
    xt = np.shape(data["x_train"])
    if len(xt) != 3 or xt[0] != len(fe.sub_grid):
        raise ValueError(
            f"co-search data must stack one featurized variant per "
            f"sub_grid factor — expected x_train of shape "
            f"(V={len(fe.sub_grid)}, M, {fe.feature_channels}), got "
            f"{xt} (build it with timeseries.feature.stack_variants)")


def run_search(data: Dict, sizes, cfg: SearchConfig,
               log: Optional[Callable] = None,
               ckpt=None, resume: bool = False,
               mesh: Optional[jax.sharding.Mesh] = None,
               return_trained: bool = False,
               init: Optional[np.ndarray] = None):
    """Full in-training optimization. Returns (pareto_genomes, pareto_fit,
    decode) where fit columns are [1-acc, normalized area]; with
    ``return_trained=True`` a fourth element carries the final front's
    trained state — ``train_pareto_front``'s (accs, params, masks, dps) —
    so the searched designs can become deployment artifacts instead of
    being thrown away with the last generation (core/deploy.export_front
    consumes exactly this tuple).

    ``ckpt`` (a checkpoint.manager.CheckpointManager) snapshots the search
    state after the initial evaluation and every generation; with
    ``resume=True`` the latest snapshot restarts the run bit-identically —
    a killed-and-resumed search matches an uninterrupted one
    generation-for-generation. ``mesh`` feeds the 'sharded' engine.

    ``cfg.engine == 'gradient'`` routes to ``run_gradient_search`` (same
    return contract, no generations). ``cfg.screen_factor > 1`` turns on
    surrogate-screened offspring oversampling (core/surrogate.py): an
    online-trained predictor picks which of the ``screen_factor * P``
    offspring pay the compiled QAT evaluation each generation.

    ``init`` seeds the initial population ((pop_size, G) uint8) instead
    of the random draw — e.g. embedding an ADC-only front into the
    co-search space so its points are guaranteed candidates (the
    cosearch_stream benchmark's ε-dominance anchor)."""
    if cfg.engine == "gradient":
        return run_gradient_search(data, sizes, cfg, log=log, ckpt=ckpt,
                                   resume=resume,
                                   return_trained=return_trained)
    from repro.core import surrogate as surrogate_lib
    C = sizes[0]
    cfg.adc_spec.validate_channels(C)   # per-channel ranges must match data
    _validate_frontend(data, sizes, cfg)
    G = genome_len(C, cfg.bits, cfg.frontend, cfg.faulttol)
    screened = cfg.screen_factor > 1
    sur = [surrogate_lib.init(G, cfg.n_objectives,
                              hidden=cfg.surrogate_hidden,
                              seed=cfg.seed)] if screened else [None]
    state = None
    if ckpt is not None and resume:
        step = ckpt.latest_step()
        if step is not None:
            restored = restore_search_state(
                ckpt, step, cfg.pop_size, G, n_obj=cfg.n_objectives,
                surrogate_like=sur[0] if screened else None)
            if screened:
                state, sur[0] = restored
            else:
                state = restored
    on_gen = None
    if ckpt is not None:
        # blocking: the state is a few KB and the atomic-commit rename must
        # land before the next generation can be declared done.
        on_gen = lambda st: ckpt.save(
            st.generation, search_state_tree(st, sur[0]), blocking=True)
    screen_fn = on_eval = None
    if screened:
        def on_eval(genomes, fitness):
            sur[0] = surrogate_lib.observe(sur[0], genomes, fitness,
                                           steps=cfg.surrogate_steps)

        screen_fn = lambda cands: surrogate_lib.screen(sur[0], cands,
                                                       cfg.pop_size)
    pop, fit = nsga2.evolve(
        make_eval_fn(data, sizes, cfg, mesh=mesh), G, pop_size=cfg.pop_size,
        generations=cfg.generations, seed=cfg.seed, init=init, log=log,
        state=state, on_generation=on_gen,
        offspring_factor=cfg.screen_factor, screen_fn=screen_fn,
        on_evaluated=on_eval)
    pg, pf = nsga2.pareto_front(pop, fit)
    if cfg.frontend is not None:
        decode = lambda g: decode_genome_cosearch(
            jnp.asarray(g), C, cfg.bits, cfg.min_levels, cfg.frontend)
    elif cfg.faulttol is not None:
        decode = lambda g: decode_genome_faulttol(
            jnp.asarray(g), C, cfg.bits, cfg.min_levels, cfg.faulttol)
    else:
        decode = lambda g: decode_genome(jnp.asarray(g), C, cfg.bits,
                                         cfg.min_levels)
    if return_trained:
        return pg, pf, decode, train_pareto_front(pg, data, sizes, cfg)
    return pg, pf, decode


def run_gradient_search(data: Dict, sizes, cfg: SearchConfig,
                        log: Optional[Callable] = None,
                        ckpt=None, resume: bool = False,
                        return_trained: bool = False,
                        progress: Optional[Callable[[str], None]] = None):
    """The gradient engine (DESIGN.md §13): ONE jitted gate-training run
    (core/grad_gates.train_gate_family) sweeps an area-regularizer family
    of lanes along the accuracy/area front, snapshots each lane's snapped
    genome at every temperature chunk, and re-scores the whole candidate
    pool through the exact batched fitness path. Because the pool is
    evaluated by the same compiled program the evolutionary engines use,
    the returned fitness keeps the bit-for-bit pure-function-of-genome
    contract: re-training any returned genome reproduces its fitness
    exactly (deploy.verify_front_parity). Same return shape as
    ``run_search``; ``ckpt``/``resume`` checkpoint gate-training chunks.

    Anchor genomes (the full unpruned design and the dp=-3 baseline) join
    the pool so the exported front's accuracy endpoint can never fall
    below the no-pruning design — the quality floor the bench's front
    comparison leans on. After the re-score, ``cfg.grad_polish_rounds``
    rounds of surrogate-screened exact polish walk the one-gate-flip
    neighborhood of the elite (the relaxation's basins end a flip or two
    short of the exact optima the evolutionary engines eventually find):
    the online surrogate — the same predictor that screens NSGA-II
    offspring — ranks the unseen neighbors (accuracy predicted, area
    computed exactly) and only the top ``cfg.grad_polish_evals`` pay for
    a compiled QAT evaluation."""
    from repro.core import grad_gates
    from repro.core import surrogate as surrogate_lib
    C = sizes[0]
    cfg.adc_spec.validate_channels(C)
    _validate_frontend(data, sizes, cfg)
    fe = cfg.frontend
    ft = cfg.faulttol
    G = genome_len(C, cfg.bits, fe, ft)
    dp_lo = C * 2 ** cfg.bits                        # dp bits live here
    # 4 lanes per requested front point: the λ sweep, the dp grid and the
    # density strata each need room to cover their axis (lanes ride one
    # vmapped train — arithmetic intensity, not extra compiled calls)
    lanes = cfg.grad_points if cfg.grad_points > 0 else 4 * cfg.pop_size
    if fe is not None:
        # the gate relaxation differentiates masks, not the combinatorial
        # feature genes: train gates on the full-rate variant (index 0),
        # then cover the subsample axis by cycling the grid over snapshot
        # rows (the DP_INIT_GRID lane idiom) — the exact re-score and the
        # polish flips explore the feature genes from there
        gate_cfg = dataclass_replace(cfg, frontend=None)
        gate_data = {"x_train": np.asarray(data["x_train"])[0],
                     "x_test": np.asarray(data["x_test"])[0],
                     "y_train": data["y_train"],
                     "y_test": data["y_test"]}
    else:
        gate_cfg, gate_data = cfg, data
    snaps, diag = grad_gates.train_gate_family(
        gate_data, tuple(sizes), gate_cfg, lanes=lanes, ckpt=ckpt,
        resume=resume, progress=progress)
    snaps = np.asarray(snaps, np.uint8)
    if fe is not None:
        ext = np.ones((len(snaps), G - dp_lo - DP_BITS), np.uint8)
        subs = np.arange(len(snaps)) % len(fe.sub_grid)
        ext[:, :fe.sub_bits] = (subs[:, None]
                                >> np.arange(fe.sub_bits)) & 1
        snaps = np.concatenate([snaps, ext], axis=1)
    elif ft is not None:
        # the relaxation differentiates masks only; the redundancy genes
        # start zeroed (plain single-comparator designs) and the exact
        # polish flips explore TMR/spare/calibrate from there
        snaps = np.concatenate(
            [snaps, np.zeros((len(snaps), ft.gene_bits(C)), np.uint8)],
            axis=1)
    # the mask family comes from the gate train; the decimal position is
    # combinatorial (the STE gradient only drifts it locally), so each
    # snapped mask re-scores at every grid dp — pure batched-rescore
    # cost after dedup, and the exact path picks the winners
    variants = []
    for dpv in grad_gates.DP_INIT_GRID:
        v = snaps.copy()
        code = int(dpv) + 8
        v[:, dp_lo:dp_lo + DP_BITS] = (code >> np.arange(DP_BITS)) & 1
        variants.append(v)
    anchors = np.ones((2, G), np.uint8)
    anchors[1, dp_lo:dp_lo + DP_BITS] = [1, 0, 1, 0]  # dp = 5 - 8 = -3
    if fe is not None:
        # anchors embed the full-rate, full-allocation front end (sub
        # index 0; all-ones alloc genes already mean FULL_ALLOC)
        anchors[:, dp_lo + DP_BITS:dp_lo + DP_BITS + fe.sub_bits] = 0
    elif ft is not None:
        # anchors stay plain full-ADC designs — no redundancy overhead
        anchors[:, dp_lo + DP_BITS:] = 0
    pool = np.unique(np.concatenate(variants + [anchors]), axis=0)
    fit = evaluate_population(pool, data, sizes, cfg)
    seen_g, seen_f = pool, fit
    sur = None
    if cfg.grad_polish_rounds > 0:
        sur = surrogate_lib.init(G, cfg.n_objectives,
                                 hidden=cfg.surrogate_hidden,
                                 seed=cfg.seed)
        sur = surrogate_lib.observe(sur, seen_g, seen_f,
                                    steps=cfg.surrogate_steps)
    # polish flips every non-dp gene: mask bits, plus (co-search) the
    # subsample/alloc genes — dp stays on the rescored grid
    flip_pos = np.concatenate([np.arange(dp_lo),
                               np.arange(dp_lo + DP_BITS, G)])
    for rnd in range(cfg.grad_polish_rounds):
        front_g, _ = nsga2.pareto_front(seen_g, seen_f)
        elite = seen_g[np.argsort(seen_f[:, 0],
                                  kind="stable")[:cfg.grad_polish_beam]]
        beam = np.unique(np.concatenate([np.unique(front_g, axis=0),
                                         elite]), axis=0)
        flips = np.repeat(beam, len(flip_pos), axis=0)
        j = np.tile(flip_pos, len(beam))
        flips[np.arange(len(flips)), j] ^= 1
        cand = np.unique(flips, axis=0)
        # unseen neighbors only — every exact evaluation is spent once
        comb = np.concatenate([seen_g, cand])
        _, first = np.unique(comb, axis=0, return_index=True)
        cand = comb[np.sort(first[first >= len(seen_g)])]
        if not len(cand):
            break
        if len(cand) > cfg.grad_polish_evals:
            keep = surrogate_lib.screen(
                sur, cand, cfg.grad_polish_evals,
                override_cols={1: population_areas(cand, C, cfg)})
            cand = cand[np.sort(np.asarray(keep))]
        cfit = evaluate_population(cand, data, sizes, cfg)
        if progress is not None:
            progress(f"polish round {rnd + 1}/{cfg.grad_polish_rounds}: "
                     f"{len(cand)} exact evals")
        seen_g = np.concatenate([seen_g, cand])
        seen_f = np.concatenate([seen_f, cfit])
        sur = surrogate_lib.observe(sur, cand, cfit,
                                    steps=cfg.surrogate_steps)
    if log is not None:
        log(0, seen_g, seen_f)
    pg, pf = nsga2.pareto_front(seen_g, seen_f)
    if fe is not None:
        decode = lambda g: decode_genome_cosearch(
            jnp.asarray(g), C, cfg.bits, cfg.min_levels, fe)
    elif ft is not None:
        decode = lambda g: decode_genome_faulttol(
            jnp.asarray(g), C, cfg.bits, cfg.min_levels, ft)
    else:
        decode = lambda g: decode_genome(jnp.asarray(g), C, cfg.bits,
                                         cfg.min_levels)
    if return_trained:
        return pg, pf, decode, train_pareto_front(pg, data, sizes, cfg)
    return pg, pf, decode


def full_adc_baseline(data: Dict, sizes, cfg: SearchConfig) -> Dict[str, float]:
    """Reference point: full (unpruned) ADC + QAT — the paper's 'Baseline'
    column in Table 5, plus the three full-design area models."""
    C = sizes[0]
    G = genome_len(C, cfg.bits, cfg.frontend, cfg.faulttol)
    dp_lo = C * 2 ** cfg.bits
    genome = np.ones((1, G), np.uint8)
    genome[0, dp_lo:dp_lo + DP_BITS] = [1, 0, 1, 0]  # dp = 5 - 8 = -3
    if cfg.frontend is not None:
        # full-rate (sub index 0), full-allocation front end
        genome[0, dp_lo + DP_BITS:
               dp_lo + DP_BITS + cfg.frontend.sub_bits] = 0
    elif cfg.faulttol is not None:
        genome[0, dp_lo + DP_BITS:] = 0   # baseline: no redundancy
    fit = evaluate_population(genome, data, sizes, cfg)
    return {
        "accuracy": 1.0 - float(fit[0, 0]),
        "area_flash_tc": area.flash_full_tc(cfg.bits) * C,
        "area_binary_baseline_tc": area.baseline_binary_tc(cfg.bits) * C,
        "area_binary_ours_tc": area.ours_full_tc(cfg.bits) * C,
    }
