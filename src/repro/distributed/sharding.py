"""Logical-axis sharding rules -> PartitionSpecs, with divisibility-checked
fallback (e.g. kv_heads=8 cannot split over model=16, so head_dim takes the
axis; hymba's 25 heads fall back to replicated).

Two rule sets:
  * FSDP (default): params' 'embed' dims shard over ('pod','data') — ZeRO-3
    style; optimizer state inherits param sharding leaf-wise.
  * TP-only (grad_compression mode): params replicate over dp and shard over
    'model' only, so per-dp-shard gradients exist for the int8
    error-feedback ring (optim/compression.py).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# candidates tried in order; a candidate applies iff all its axes exist in
# the mesh, none is already used in this tensor, and the dim divides evenly.
RULES_FSDP: Dict[Optional[str], tuple] = {
    "batch": (("pod", "data"), ("data",)),
    "vocab": (("model",),),
    "embed": (("pod", "data"), ("data",)),
    "heads": (("model",),),
    "kv_heads": (("model",),),
    "head_dim": (("model",),),
    "mlp": (("model",),),
    "expert": (("model",),),
    "expert_mlp": (),
    "ssm_inner": (("model",),),
    "ssm_heads": (("model",),),
    "ssm_bc": (),
    "layers": (), "seq": (), "state": (), None: (),
}
RULES_TP_ONLY = dict(RULES_FSDP)
RULES_TP_ONLY["embed"] = ()          # replicate over dp: local grads exist
RULES_TP_ONLY["vocab"] = (("model",),)

# archs that cannot TP their attention/SSD heads (musicgen 24H, hymba 25H /
# 50 SSD heads): the model axis becomes extra data parallelism; weights are
# FSDP over 'data' only (replicated over 'model')
RULES_EXTRA_DP = {
    "batch": (("pod", "data", "model"), ("data", "model"),
              ("pod", "data"), ("data",)),
    "vocab": (), "embed": (("data",),), "heads": (), "kv_heads": (),
    "head_dim": (), "mlp": (), "expert": (), "expert_mlp": (),
    "ssm_inner": (), "ssm_heads": (), "ssm_bc": (),
    "layers": (), "seq": (), "state": (), None: (),
}


def rules_for(cfg) -> Dict[Optional[str], tuple]:
    if cfg.grad_compression != "none":
        return RULES_TP_ONLY
    if getattr(cfg, "extra_dp", False):
        return RULES_EXTRA_DP
    return RULES_FSDP


def spec_for(shape: Tuple[int, ...], logical: Tuple[Optional[str], ...],
             mesh: Mesh, rules: Dict) -> P:
    assert len(shape) == len(logical), (shape, logical)
    used: set = set()
    parts = []
    for dim, name in zip(shape, logical):
        chosen = None
        for cand in rules.get(name, ()):
            axes = tuple(a for a in cand if a in mesh.axis_names)
            if len(axes) != len(cand) or any(a in used for a in axes):
                continue
            size = math.prod(mesh.shape[a] for a in axes)
            if size > 1 and dim % size == 0:
                chosen = axes
                used.update(axes)
                break
        parts.append(None if chosen is None
                     else (chosen if len(chosen) > 1 else chosen[0]))
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


# ---------------- parameter tree -> logical axes (path-pattern matched) ----
_VECTOR = ("ln1", "ln2", "ln1p", "ln2p", "final_norm", "attn_scale",
           "ssm_scale", "norm_w", "conv_b_x", "conv_b_bc", "A_log", "D",
           "dt_bias")


def _leaf_logical(path: Tuple[str, ...], ndim: int,
                  inference: bool = False) -> Tuple[Optional[str], ...]:
    name = path[-1]
    stacked = any(k in ("layers", "layers2", "prelayers") for k in path)
    lead: Tuple[Optional[str], ...] = ("layers",) if stacked else ()
    in_moe = "moe" in path and "shared" not in path

    def pad(t):
        out = lead + t
        assert len(out) == ndim, (path, ndim, out)
        return out

    if name == "embed":
        return pad(("vocab", "embed"))
    if name == "head":
        return pad(("embed", "vocab"))
    if name == "front_proj":
        return pad((None, "embed"))
    if name in _VECTOR:
        return pad((None,)) if ndim == len(lead) + 1 else pad((None, None))
    # NOTE (§Perf iteration 1): weights are NEVER head_dim-sharded — a
    # sharded contraction dim in QK^T/PV turns every attention block into a
    # score-tensor all-reduce (measured 39 TB/step on musicgen train_4k).
    # When heads don't divide tp the attention runs replicated over 'model'
    # (FSDP still covers memory); decode caches keep the head_dim fallback
    # (decode scores are tiny). See EXPERIMENTS.md §Perf.
    if name == "q":
        return pad(("embed", "heads", None))
    if name in ("k", "v"):
        return pad(("embed", "kv_heads", None))
    if name == "o":
        return pad(("heads", None, "embed"))
    if name == "router":
        # FSDP the embed dim; shard_map gathers the (small) per-layer slice
        return pad(("embed", None))
    if in_moe and name in ("wi", "wg"):
        # inference (decode): hidden dim over dp so the token-gathered MoE
        # (moe.moe_ffn_decode) never moves weights — §Perf iteration 7
        return pad(("expert", None, "embed") if inference
                   else ("expert", "embed", "expert_mlp"))
    if in_moe and name == "wo":
        return pad(("expert", "embed", None) if inference
                   else ("expert", "expert_mlp", "embed"))
    if name in ("wi", "wg"):
        return pad(("embed", "mlp"))
    if name == "wo":
        return pad(("mlp", "embed"))
    if name in ("z_proj", "x_proj"):
        return pad(("embed", "ssm_inner"))
    if name == "bc_proj":
        return pad(("embed", "ssm_bc"))
    if name == "dt_proj":
        return pad(("embed", "ssm_heads"))
    if name == "conv_w_x":
        return pad((None, "ssm_inner"))
    if name == "conv_w_bc":
        return pad((None, "ssm_bc"))
    if name == "out_proj":
        return pad(("ssm_inner", "embed"))
    raise KeyError(f"no logical-axis rule for param path {path}")


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            out.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return tuple(out)


def param_specs(params_shape, mesh: Mesh, cfg, inference: bool = False) -> Any:
    """Pytree of PartitionSpec matching ``params_shape`` (real arrays or
    ShapeDtypeStructs)."""
    rules = rules_for(cfg)

    def one(path, leaf):
        logical = _leaf_logical(_path_names(path), len(leaf.shape), inference)
        return spec_for(tuple(leaf.shape), logical, mesh, rules)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def param_shardings(params_shape, mesh: Mesh, cfg, inference: bool = False
                    ) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        param_specs(params_shape, mesh, cfg, inference))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# GA individuals are embarrassingly parallel, so the population axis of the
# in-training ADC search (core/search.py, engine='sharded') may take EVERY
# mesh axis — candidates tried in preference order, same contract as the
# parameter rules above: all axes present and the dim divides evenly.
RULES_POPULATION: Tuple[Tuple[str, ...], ...] = (
    ("pod", "data", "model"), ("data", "model"), ("pod", "data"),
    ("data",), ("model",))


def population_axes(mesh: Mesh, p: int) -> Optional[Tuple[str, ...]]:
    """Mesh axes the (P,)-leading population batch shards over: the
    divisible candidate from RULES_POPULATION covering the most devices.
    A size-1 winner is legal (trivial shard — the shard_map engine still
    runs, each device holding the full population). None means no
    candidate divides P: the caller must fall back to the single-device
    batched engine."""
    best: Optional[Tuple[str, ...]] = None
    best_size = 0
    for cand in RULES_POPULATION:
        axes = tuple(a for a in cand if a in mesh.axis_names)
        if len(axes) != len(cand):
            continue
        size = math.prod(mesh.shape[a] for a in axes)
        if p % size == 0 and size > best_size:
            best, best_size = axes, size
    return best


def design_bank_axes(mesh: Mesh, d: int) -> Optional[Tuple[str, ...]]:
    """Mesh axes the (D,)-leading deployed-design bank shards over for
    serving (ops.classifier_bank_sharded / launch/serve_classifier). A
    Pareto front's designs are embarrassingly parallel exactly like GA
    individuals — one shared sample batch, independent per-design tables
    and weights — so the candidate set and the divisibility/fallback
    contract are the population rules verbatim."""
    return population_axes(mesh, d)


def batch_axes(mesh: Mesh, cfg, b: int) -> Optional[Tuple[str, ...]]:
    """Mesh axes the batch dim shards over (first divisible candidate)."""
    for cand in rules_for(cfg)["batch"]:
        axes = tuple(a for a in cand if a in mesh.axis_names)
        if len(axes) != len(cand):
            continue
        size = math.prod(mesh.shape[a] for a in axes)
        if size > 1 and b % size == 0:
            return axes
    return None


def batch_spec(mesh: Mesh, extra_dims: int = 1) -> P:
    """Sharding for (B, ...) activations/inputs: batch over dp axes."""
    dp = dp_axes(mesh)
    return P(dp if len(dp) > 1 else (dp[0] if dp else None),
             *([None] * extra_dims))


def cache_specs(cache_shape, mesh: Mesh, cfg) -> Any:
    """Decode-cache shardings: batch over dp; kv_heads (or head_dim) over
    model; ssm heads over model when divisible."""
    rules = rules_for(cfg)

    tp = mesh.shape.get("model", 1)

    def one(path, leaf):
        names = _path_names(path)
        nd = len(leaf.shape)
        key = names[-1]
        if key in ("pos",):
            return P()
        if key in ("kpos", "kpos2"):
            return P(None)
        if key.startswith("k") or key.startswith("v"):
            # kv cache (L,B,C,KV,hd): prefer kv_heads over 'model'; when the
            # head count doesn't divide tp, shard the SEQUENCE dim instead —
            # decode then all-reduces only softmax stats (B,H,1) rather than
            # score tensors (§Perf iteration 6).
            if nd == 5 and tp > 1 and cfg.num_kv_heads % tp and \
                    leaf.shape[2] % tp == 0:
                logical = ("layers", "batch", "cache_seq", "kv_heads",
                           "head_dim")
                loc_rules = dict(rules)
                loc_rules["cache_seq"] = (("model",),)
                loc_rules["kv_heads"] = ()
                loc_rules["head_dim"] = ()
                return spec_for(tuple(leaf.shape), logical, mesh, loc_rules)
            logical = ("layers", "batch", "seq", "kv_heads", "head_dim")[:nd]
        elif key == "conv_x":
            logical = ("layers", "batch", None, "ssm_inner")
        elif key == "conv_bc":
            logical = ("layers", "batch", None, "ssm_bc")
        elif key == "state":
            logical = ("layers", "batch", "ssm_heads", "state", None)
        else:
            logical = tuple([None] * nd)
        return spec_for(tuple(leaf.shape), logical, mesh, rules)

    return jax.tree_util.tree_map_with_path(one, cache_shape)
