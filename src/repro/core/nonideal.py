"""Hardware non-ideality model for pruned binary-search ADCs
(DESIGN.md §10).

The reproduction so far evaluates every design under ideal comparators;
real flexible/IGZO devices do not cooperate (the fault-tolerant-ADC
follow-up, arXiv:2602.10790, and the robustness-aware co-design argument
of arXiv:2508.19637). Three non-idealities dominate:

* **per-comparator input-referred offset** — each surviving comparator's
  threshold shifts by a Gaussian draw, ``sigma_offset`` expressed in LSBs
  of the full ladder;
* **per-channel reference-ladder / range drift** — the analog endpoints
  the ladder is generated from drift per instance,
  ``sigma_range`` expressed as a fraction of the channel's full scale;
* **stuck-at-0/1 faults** — a surviving comparator's output wires to a
  constant with probability ``fault_rate`` (direction a fair coin), so
  the search tree always takes one branch at that node.

``NonIdealSpec`` freezes the three knobs the way ``AdcSpec`` freezes the
design point: hashable (valid static jit argument), pytree-registered,
``to_meta``/``from_meta`` JSON round trip. ``seed`` names the Monte-Carlo
draw stream, so a robustness number is reproducible from the spec alone.

The modelling trick that keeps the hot path on the existing kernel
family: a binary-search tree with perturbed thresholds still maps each
input to exactly one leaf, and the set of inputs reaching kept leaf ``k``
is an *interval* — lower bound the max over alive ancestors ``k``
descends right from, upper bound the min over alive ancestors it
descends left from; bypassed (pruned-dead) and stuck ancestors either
contribute no constraint or empty the region. ``instance_bounds``
therefore compiles mask + draws into per-instance interval tables
``(lb, ub)`` of shape ``(..., S, C, 2^N)`` in *code units* (the same
``u = (x - vmin_row) * scale_row`` domain every kernel already computes),
and the MC kernel is one compare/select sweep per level — identical
structure, arithmetic and constants as the ideal path. With
``sigma_offset = fault_rate = sigma_range = 0`` the intervals collapse to
the exact integer code boundaries, which is what makes the ideal-limit
contract *bit-for-bit* rather than approximate: zero-sigma Monte-Carlo
accuracy equals the exported accuracy exactly (tests/test_nonideal.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

ROBUST_OBJECTIVES = ("expected", "worst", "yield")


@dataclasses.dataclass(frozen=True)
class NonIdealSpec:
    """Frozen description of one hardware non-ideality regime.

    sigma_offset: per-comparator input-referred offset sigma, in LSBs.
    sigma_range: per-channel reference-ladder drift sigma, as a fraction
        of the channel's full scale (applied to both endpoints).
    fault_rate: stuck-at-0/1 probability per surviving comparator.
    seed: Monte-Carlo draw stream identity (``draw`` is a pure function
        of (spec, bits, channels, samples)).
    """
    sigma_offset: float = 0.0
    sigma_range: float = 0.0
    fault_rate: float = 0.0
    seed: int = 0

    def __post_init__(self):
        object.__setattr__(self, "sigma_offset", float(self.sigma_offset))
        object.__setattr__(self, "sigma_range", float(self.sigma_range))
        object.__setattr__(self, "fault_rate", float(self.fault_rate))
        object.__setattr__(self, "seed", int(self.seed))
        if self.sigma_offset < 0 or self.sigma_range < 0:
            raise ValueError(f"sigmas must be >= 0, got "
                             f"sigma_offset={self.sigma_offset} "
                             f"sigma_range={self.sigma_range}")
        if not 0.0 <= self.fault_rate <= 1.0:
            raise ValueError(f"fault_rate must be in [0, 1], got "
                             f"{self.fault_rate}")

    @property
    def ideal(self) -> bool:
        """True when every knob is zero — the MC path then reproduces the
        ideal pipeline bit-for-bit."""
        return (self.sigma_offset == 0.0 and self.sigma_range == 0.0
                and self.fault_rate == 0.0)

    def replace(self, **kw) -> "NonIdealSpec":
        return dataclasses.replace(self, **kw)

    def to_meta(self) -> dict:
        return {"sigma_offset": self.sigma_offset,
                "sigma_range": self.sigma_range,
                "fault_rate": self.fault_rate, "seed": self.seed}

    @classmethod
    def from_meta(cls, meta: dict) -> "NonIdealSpec":
        return cls(sigma_offset=float(meta["sigma_offset"]),
                   sigma_range=float(meta["sigma_range"]),
                   fault_rate=float(meta["fault_rate"]),
                   seed=int(meta.get("seed", 0)))

    def describe(self) -> str:
        return (f"sigma_offset={self.sigma_offset}LSB "
                f"sigma_range={self.sigma_range}FS "
                f"fault_rate={self.fault_rate} seed={self.seed}")


def _nonideal_flatten(s: NonIdealSpec):
    return (s.sigma_offset, s.sigma_range, s.fault_rate), (s.seed,)


def _nonideal_unflatten(aux, children):
    obj = object.__new__(NonIdealSpec)
    object.__setattr__(obj, "sigma_offset", children[0])
    object.__setattr__(obj, "sigma_range", children[1])
    object.__setattr__(obj, "fault_rate", children[2])
    object.__setattr__(obj, "seed", aux[0])
    return obj


jax.tree_util.register_pytree_node(NonIdealSpec, _nonideal_flatten,
                                   _nonideal_unflatten)


class Draws(NamedTuple):
    """The raw Monte-Carlo randomness for S instances, drawn once per
    evaluation and *independent of any mask* — per-design application
    happens in ``instance_bounds``. Mask-independence is what makes the
    draws common random numbers across an NSGA-II population (cheaper AND
    lower-variance design ranking) and lets ``evaluate_robustness``
    reproduce an in-search robustness objective exactly from the same
    ``NonIdealSpec.seed``.

    eps: (S, C, 2^N - 1) standard-normal threshold offsets, one per tree
        node (flat heap order: node (d, i) at index 2^d - 1 + i).
    fault_u: (S, C, 2^N - 1) uniforms; node faults when < fault_rate.
    stuck_hi: (S, C, 2^N - 1) bools; a faulted node sticks at 1 (always
        takes the upper half) when True, at 0 otherwise.
    drift: (S, C, 2) standard normals for the two range endpoints.
    """
    eps: jnp.ndarray
    fault_u: jnp.ndarray
    stuck_hi: jnp.ndarray
    drift: jnp.ndarray

    @property
    def samples(self) -> int:
        return self.eps.shape[0]


def draw(bits: int, channels: int, samples: int,
         nonideal: NonIdealSpec) -> Draws:
    """Draw the full randomness block for ``samples`` MC instances —
    a pure function of ``nonideal.seed`` and the shapes."""
    return draw_from_key(jax.random.PRNGKey(nonideal.seed), bits, channels,
                         samples)


def prng_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)``'s raw threefry words, built on the
    host: (hi, lo) uint32. JAX reads an int seed as int64 in 64-bit mode
    and as its low 32 bits otherwise, where hi is 0."""
    s = int(seed)
    hi = (s >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([hi, s & 0xFFFFFFFF], np.uint32)


def draw_from_key(key, bits: int, channels: int, samples: int) -> Draws:
    """``draw`` from a raw PRNG key (traceable: the key may be data)."""
    if samples < 1:
        raise ValueError(f"need >= 1 MC sample, got {samples}")
    nodes = 2 ** bits - 1
    k1, k2, k3, k4 = jax.random.split(key, 4)
    shape = (samples, channels, nodes)
    return Draws(
        eps=jax.random.normal(k1, shape, jnp.float32),
        fault_u=jax.random.uniform(k2, shape, jnp.float32),
        stuck_hi=jax.random.bernoulli(k3, 0.5, shape),
        drift=jax.random.normal(k4, (samples, channels, 2), jnp.float32))


def instance_bounds(mask: jnp.ndarray, bits: int, draws: Draws,
                    nonideal: NonIdealSpec
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compile mask + draws into per-instance interval tables.

    mask: (C, 2^N) or population-batched (P, C, 2^N) {0,1}.
    Returns ``(lb, ub)`` f32 of shape (S, C, 2^N) / (P, S, C, 2^N): input
    ``u`` (in code units) reaches kept leaf ``k`` of instance ``s`` iff
    ``lb[..., s, c, k] <= u < ub[..., s, c, k]``. Regions partition the
    real line (the perturbed tree walk always lands on exactly one kept
    leaf); unreachable leaves get (+inf, -inf) never-true sentinels.

    With an all-zero ``NonIdealSpec`` the bounds are the exact integer
    code boundaries of the ideal pruned walk, so
    ``lb <= u < ub`` selects exactly the level
    ``tree_lut(mask)[clip(floor(u))]`` — bitwise, not approximately
    (kernels/ref.mc_adc_eval_ref pins this against the ideal oracle).
    """
    m = jnp.asarray(mask, jnp.int32)
    n = 2 ** bits
    if m.shape[-1] != n:
        raise ValueError(f"mask last dim {m.shape[-1]} != 2^bits {n}")
    cs = jnp.concatenate([jnp.zeros(m.shape[:-1] + (1,), jnp.int32),
                          jnp.cumsum(m, axis=-1)], axis=-1)
    codes = np.arange(n)
    sigma = float(nonideal.sigma_offset)
    frate = float(nonideal.fault_rate)
    # (..., C, n) mask-side arrays broadcast against (S, C, n) draw-side
    # arrays through an inserted sample axis at -3
    ex = lambda a: jnp.expand_dims(a, -3)
    bshape = jnp.broadcast_shapes(ex(m).shape, draws.eps.shape[:-1] + (n,))
    L = jnp.full(bshape, -jnp.inf, jnp.float32)
    U = jnp.full(bshape, jnp.inf, jnp.float32)
    empty = jnp.zeros(bshape, bool)
    for d in range(bits):
        seg = n >> d
        anc_lo = (codes // seg) * seg                 # ancestor segment start
        mid = anc_lo + seg // 2
        right = jnp.asarray((codes % seg) >= seg // 2)        # (n,) bool
        at = lambda idx: jnp.take(cs, jnp.asarray(idx), axis=-1)
        la = (at(mid) - at(anc_lo)) > 0               # (..., C, n)
        ra = (at(anc_lo + seg) - at(mid)) > 0
        alive = la & ra
        node_idx = (2 ** d - 1) + codes // seg        # flat heap index, (n,)
        pick = lambda a: jnp.take(a, jnp.asarray(node_idx), axis=-1)
        t = jnp.asarray(mid, jnp.float32) + sigma * pick(draws.eps)
        faulty = ex(alive) & (pick(draws.fault_u) < frate)
        healthy = ex(alive) & ~faulty
        L = jnp.where(healthy & right, jnp.maximum(L, t), L)
        U = jnp.where(healthy & ~right, jnp.minimum(U, t), U)
        # a stuck comparator always takes its stuck half; a bypassed
        # (dead) node always takes its surviving half — leaves on the
        # other side become unreachable
        empty = empty | (faulty & (pick(draws.stuck_hi) != right))
        empty = empty | ex((~alive) & ((la & right) | (ra & ~right)
                                       | (~la & ~ra)))
    lb = jnp.where(empty, jnp.inf, L)
    ub = jnp.where(empty, -jnp.inf, U)
    return lb, ub


def instance_rows(spec, channels: int, draws: Draws,
                  nonideal: NonIdealSpec
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-instance reference-ladder code math: the canonical f64-derived
    ``(vmin_row, scale_row)`` of ``spec`` with per-(instance, channel)
    endpoint drift applied. Returns f32 ``(lo (S, C), scale (S, C))``.
    With ``sigma_range == 0`` both rows equal the ideal rows bitwise
    (the drift terms are exact zeros / exact unit gains)."""
    lo, scale = spec.range_rows(channels)             # (1, C) f32 numpy
    lo = jnp.asarray(lo)
    scale = jnp.asarray(scale)
    n = jnp.float32(2 ** spec.bits)
    span = n / scale                                  # (1, C) full scale
    sr = float(nonideal.sigma_range)
    d_lo = sr * draws.drift[..., 0] * span            # (S, C)
    d_hi = sr * draws.drift[..., 1] * span
    lo_s = lo + d_lo
    scale_s = scale * (span / (span + (d_hi - d_lo)))
    return lo_s, scale_s


def level_value_rows(spec, channels: int) -> jnp.ndarray:
    """The (C, 2^N) per-channel reconstruction ladder the MC kernels
    select from — ``AdcSpec.level_values`` broadcast to explicit channel
    rows (the digital back end is unperturbed: drift and offsets live in
    the analog comparisons, the classifier still consumes the design's
    nominal level values)."""
    values = spec.level_values(channels).astype(jnp.float32)
    if values.ndim == 1:
        values = jnp.broadcast_to(values[None, :],
                                  (channels, values.shape[0]))
    return values


def mc_operands(spec, nonideal: NonIdealSpec, mask: jnp.ndarray,
                draws: Optional[Draws] = None,
                samples: Optional[int] = None):
    """One-stop compile of (spec, nonideal, mask) into the MC kernel
    operand tuple ``(lb, ub, values, lo, scale)`` — the exact argument
    order of the ``mc_eval`` / ``mc_eval_population`` dispatch entries.
    Pass ``draws`` to reuse a stream (the co-search does, once per run);
    otherwise ``samples`` fresh draws come from ``nonideal.seed``."""
    mask = jnp.asarray(mask)
    channels = mask.shape[-2]
    if draws is None:
        if samples is None:
            raise ValueError("pass draws= or samples=")
        draws = draw(spec.bits, channels, samples, nonideal)
    lb, ub = instance_bounds(mask, spec.bits, draws, nonideal)
    lo, scale = instance_rows(spec, channels, draws, nonideal)
    return lb, ub, level_value_rows(spec, channels), lo, scale


def compiled_mc_operands(spec, nonideal: NonIdealSpec, mask,
                         draws: Optional[Draws] = None,
                         samples: Optional[int] = None):
    """``mc_operands`` as one compiled program per (spec, shapes, sigmas):
    the draw and the interval compilation in one dispatch rather than
    hundreds of eager ops. The mask, and the key (``prng_key(seed)``) or
    the caller's ``draws``, are data, so a fresh wafer seed reuses the
    compiled program. The sigmas and fault rate are compile-time
    constants, as the co-search's static ``cfg.nonideal`` makes them in
    its own graph: XLA then folds ``sigma_range * span`` the same way in
    both, and the operands match the search's bit for bit (DESIGN.md
    §10). Counter ``deploy.operand_traces`` counts its traces."""
    if draws is None:
        if samples is None:
            raise ValueError("pass draws= or samples=")
        key, samples = prng_key(nonideal.seed), int(samples)
    else:
        key, samples = None, None
    return _compiled_mc_operands(
        mask, key, draws, spec=spec, samples=samples,
        sigma_offset=nonideal.sigma_offset,
        sigma_range=nonideal.sigma_range, fault_rate=nonideal.fault_rate)


@functools.partial(jax.jit, static_argnames=(
    "spec", "samples", "sigma_offset", "sigma_range", "fault_rate"))
def _compiled_mc_operands(mask, key, draws, *, spec, samples, sigma_offset,
                          sigma_range, fault_rate):
    obs.count("deploy.operand_traces")             # runs once per trace
    if draws is None:
        draws = draw_from_key(key, spec.bits, mask.shape[-2], samples)
    nonideal = NonIdealSpec(sigma_offset, sigma_range, fault_rate)
    return mc_operands(spec, nonideal, mask, draws=draws)


def mc_quantize(x, mask, spec, nonideal: NonIdealSpec, *,
                draws: Optional[Draws] = None,
                samples: Optional[int] = None,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Quantize one shared (M, C) sample batch through S Monte-Carlo
    perturbed instances of the pruned design(s): returns (S, M, C) for a
    (C, 2^N) mask, (P, S, M, C) for a population (P, C, 2^N) batch —
    routed through the dispatch registry (Pallas MC kernel on TPU, jnp
    oracle otherwise)."""
    from repro.kernels import dispatch
    mask = jnp.asarray(mask)
    spec.validate_channels(mask.shape[-2])
    ops = mc_operands(spec, nonideal, mask, draws=draws, samples=samples)
    entry = "mc_eval_population" if mask.ndim == 3 else "mc_eval"
    return dispatch.dispatch(entry, x, *ops, spec=spec, interpret=interpret)


def robust_objective_name(kind: str) -> str:
    if kind not in ROBUST_OBJECTIVES:
        raise ValueError(f"robust_objective must be one of "
                         f"{ROBUST_OBJECTIVES}, got {kind!r}")
    return kind


def mc_mean_accuracy(mc_accs: np.ndarray) -> np.ndarray:
    """Mean accuracy over the MC instance axis, reduced HOST-side in f64.
    The instance accuracies are f32-precision values, so the f64 sum is
    exact (no rounding for any realistic S) and the final division is
    correctly rounded — the mean is therefore order-independent and, for
    S identical ideal-limit instances, *exactly* the instance value:
    ``(S * a) / S == a`` in f64. A device-side f32 ``jnp.mean`` would
    break both properties (last-ulp drift between the in-search and
    deployed reductions, and mean-of-identical != identical)."""
    mc = np.asarray(mc_accs, np.float64)
    return mc.sum(axis=-1) / mc.shape[-1]


def yield_fraction(accs: np.ndarray, mc_accs: np.ndarray,
                   margin: float) -> np.ndarray:
    """yield@margin: the fraction of MC instances whose accuracy stays
    within ``margin`` of the design's ideal accuracy. Reduced host-side
    in f64 — the comparison is exact (f32-precision operands widened to
    f64) and the count/S division is correctly rounded, so the search
    fitness and the deployed report compute the identical number from
    the identical instance accuracies (bit-for-bit, not approximately).
    accs: (...,) ideal accuracies; mc_accs: (..., S)."""
    accs = np.asarray(accs, np.float64)
    mc = np.asarray(mc_accs, np.float64)
    ok = mc >= (accs[..., None] - float(margin))
    return ok.sum(axis=-1, dtype=np.float64) / mc.shape[-1]


def robust_objective(accs: np.ndarray, mc_accs: np.ndarray,
                     kind: str, *, margin: float = 0.01) -> np.ndarray:
    """The minimized robustness fitness column, reduced host-side in f64
    (see ``mc_mean_accuracy`` for why). accs: (P,) ideal accuracies;
    mc_accs: (P, S) per-instance MC accuracies.

    'expected': expected accuracy drop ``acc - mean_s(acc_s)``;
    'worst': worst-case error ``1 - min_s(acc_s)``;
    'yield': yield loss ``1 - yield@margin`` (the fault-tolerance
    subsystem's first-class objective, DESIGN.md §15; ``margin`` only
    applies here).
    ``deploy.evaluate_robustness`` applies the identical reductions to
    the identical per-instance accuracies, which is what makes a
    3-objective front's robustness fitness column reproducible from the
    deployed artifact bit-for-bit (acceptance contract,
    tests/test_nonideal.py)."""
    robust_objective_name(kind)
    accs = np.asarray(accs, np.float64)
    mc = np.asarray(mc_accs, np.float64)
    if kind == "worst":
        return 1.0 - mc.min(axis=-1)
    if kind == "yield":
        return 1.0 - yield_fraction(accs, mc, margin)
    return accs - mc_mean_accuracy(mc)
