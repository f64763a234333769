"""Mixture-of-Experts FFN with expert parallelism over the mesh 'model' axis.

TPU-native design (DESIGN.md §4): instead of emulating NCCL all-to-all
dispatch, each chip holds E/tp experts and *every* chip sees its data-shard's
tokens (activations are replicated over 'model' inside the block). A chip:

  1. routes locally (router weights replicated — they're tiny),
  2. sort-compacts the (token, expert) pairs that target ITS experts into an
     (E_local, capacity, D) buffer — no 2^30-element one-hot dispatch tensors,
  3. runs the expert SwiGLU as one batched einsum (MXU-friendly),
  4. scatter-adds gated outputs back to token positions,
  5. psum over 'model' combines partial outputs (same collective cost as the
     dense-FFN TP all-reduce it replaces).

Tokens overflowing an expert's capacity are dropped (GShard semantics,
capacity_factor configurable). Aux load-balance loss follows Switch.

Used vs. dormant: consumed only by the beyond-paper LM substrate —
``models/transformer.py`` wires this FFN into the routed (moe) arch
family and ``models/serving.py`` runs it at decode; the arch smoke
tests cover both. It is fully dormant with respect to the paper's ADC
pipeline (search/deploy/serving-engine/timeseries), which uses the
dense MLP/SVM heads instead. No other module imports it, so changes
here can only affect the moe/hybrid LM benches and smoke tests.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig


def init_moe(key, d_model: int, m: MoEConfig, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 5)
    sc_in = 1.0 / jnp.sqrt(d_model)
    sc_out = 1.0 / jnp.sqrt(m.d_expert)
    p = {
        "router": (jax.random.normal(ks[0], (d_model, m.num_experts)) * 0.02
                   ).astype(jnp.float32),           # router stays fp32
        "wi": (jax.random.normal(ks[1], (m.num_experts, d_model, m.d_expert)) * sc_in).astype(dtype),
        "wg": (jax.random.normal(ks[2], (m.num_experts, d_model, m.d_expert)) * sc_in).astype(dtype),
        "wo": (jax.random.normal(ks[3], (m.num_experts, m.d_expert, d_model)) * sc_out).astype(dtype),
    }
    if m.num_shared_experts:
        f = m.d_shared * m.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wi": (jax.random.normal(k1, (d_model, f)) * sc_in).astype(dtype),
            "wg": (jax.random.normal(k2, (d_model, f)) * sc_in).astype(dtype),
            "wo": (jax.random.normal(k3, (f, d_model)) * sc_in).astype(dtype),
        }
    return p


def _dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _local_moe(x, router_w, wi, wg, wo, *, m: MoEConfig, tp: int,
               model_axis: str, dp_axes: tuple):
    """shard_map body. x: (b_l, S, D) local tokens, replicated over 'model'.
    wi/wg/wo: (E_local, ...) this chip's experts."""
    b_l, S, D = x.shape
    E, k = m.num_experts, m.top_k
    E_l = E // tp
    T = b_l * S
    xf = x.reshape(T, D)

    logits = xf.astype(jnp.float32) @ router_w                    # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, ids = lax.top_k(probs, k)                          # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch aux load-balance loss (computed on local tokens, mean over dp)
    me = probs.mean(0)                                            # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(me * ce)
    if dp_axes:
        aux = lax.pmean(aux, dp_axes)

    # ---- sort-compact tokens headed for this chip's expert range ----
    rank = lax.axis_index(model_axis) if tp > 1 else 0
    e0 = rank * E_l
    flat_ids = ids.reshape(-1)                                    # (T*k,)
    local_eid = jnp.where((flat_ids >= e0) & (flat_ids < e0 + E_l),
                          flat_ids - e0, E_l)                     # E_l = "not mine"
    order = jnp.argsort(local_eid)                                # stable
    sorted_eid = local_eid[order]
    sorted_tok = order // k
    sorted_gate = gate_vals.reshape(-1)[order]
    starts = jnp.searchsorted(sorted_eid, jnp.arange(E_l + 1), side="left")
    pos = jnp.arange(T * k) - starts[jnp.clip(sorted_eid, 0, E_l)]
    cap = int(max(1, round(T * k / E * m.capacity_factor)))
    keep = (sorted_eid < E_l) & (pos < cap)
    slot = jnp.where(keep, sorted_eid * cap + pos, E_l * cap)     # OOB -> dropped
    xbuf = jnp.zeros((E_l * cap, D), x.dtype).at[slot].set(
        xf[sorted_tok], mode="drop")
    xbuf = xbuf.reshape(E_l, cap, D)

    h = jnp.einsum("ecd,edf->ecf", xbuf, wi.astype(x.dtype))
    g = jnp.einsum("ecd,edf->ecf", xbuf, wg.astype(x.dtype))
    obuf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h,
                      wo.astype(x.dtype)).reshape(E_l * cap, D)

    contrib = obuf.at[slot].get(mode="fill", fill_value=0.0)      # (T*k, D)
    contrib = contrib * (sorted_gate * keep).astype(x.dtype)[:, None]
    y = jnp.zeros((T, D), x.dtype).at[sorted_tok].add(contrib)
    if tp > 1:
        y = lax.psum(y, model_axis)
        aux = lax.pmean(aux, model_axis)
    return y.reshape(b_l, S, D), aux


def _gathered_moe(x, router_w, wi, wg, wo, *, m: MoEConfig, tp: int,
                  dp_axes: tuple, dp_size: int):
    """Decode-path MoE (§Perf iteration 7): the token batch is tiny, so
    tokens are REPLICATED over dp (MBs) and expert weights never move —
    each chip holds (E/tp experts x 1/dp of the hidden dim) and contributes
    rank-partial expert matmuls; all collectives are token-sized psums
    instead of the 100+GB/step FSDP weight gathers the train-path sharding
    would need. x: (T, D) replicated; wi/wg: (E_l, D, F_l); wo: (E_l, F_l, D).
    """
    T, D = x.shape
    E, k = m.num_experts, m.top_k
    E_l = E // tp
    logits = x.astype(jnp.float32) @ router_w                     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, ids = lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    rank = lax.axis_index("model") if tp > 1 else 0
    e0 = rank * E_l
    flat_ids = ids.reshape(-1)
    local_eid = jnp.where((flat_ids >= e0) & (flat_ids < e0 + E_l),
                          flat_ids - e0, E_l)
    order = jnp.argsort(local_eid)
    sorted_eid = local_eid[order]
    sorted_tok = order // k
    sorted_gate = gate_vals.reshape(-1)[order]
    starts = jnp.searchsorted(sorted_eid, jnp.arange(E_l + 1), side="left")
    pos = jnp.arange(T * k) - starts[jnp.clip(sorted_eid, 0, E_l)]
    cap = int(min(max(1, round(T * k / E * m.capacity_factor * 4)), T * k))
    keep = (sorted_eid < E_l) & (pos < cap)
    slot = jnp.where(keep, sorted_eid * cap + pos, E_l * cap)
    xbuf = jnp.zeros((E_l * cap, D), x.dtype).at[slot].set(
        x[sorted_tok], mode="drop").reshape(E_l, cap, D)

    h = jnp.einsum("ecd,edf->ecf", xbuf, wi.astype(x.dtype))      # F-partial
    g = jnp.einsum("ecd,edf->ecf", xbuf, wg.astype(x.dtype))
    obuf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h,
                      wo.astype(x.dtype))                         # D rank-part
    if dp_axes:
        obuf = lax.psum(obuf, dp_axes)       # sum hidden-dim partials
    obuf = obuf.reshape(E_l * cap, D)
    contrib = obuf.at[slot].get(mode="fill", fill_value=0.0)
    contrib = contrib * (sorted_gate * keep).astype(x.dtype)[:, None]
    y = jnp.zeros((T, D), x.dtype).at[sorted_tok].add(contrib)
    if tp > 1:
        y = lax.psum(y, "model")
    return y


def moe_ffn_decode(x: jnp.ndarray, params: Dict, m: MoEConfig, mesh
                   ) -> jnp.ndarray:
    """Token-gathered MoE for single-token decode. x: (B, 1, D)."""
    dp = _dp_axes(mesh)
    tp = mesh.shape["model"]
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    B, S, D = x.shape
    body = partial(_gathered_moe, m=m, tp=tp, dp_axes=dp, dp_size=dp_size)

    def wrapped(xf, router_w, wi, wg, wo):
        return body(xf, router_w, wi, wg, wo)

    fn = jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=(P(None, None),                       # tokens replicated
                  P(None, None),                       # router replicated
                  P("model", None, dp if len(dp) > 1 else (dp[0] if dp else None)),
                  P("model", None, dp if len(dp) > 1 else (dp[0] if dp else None)),
                  P("model", dp if len(dp) > 1 else (dp[0] if dp else None), None)),
        out_specs=P(None, None),
        check_vma=False)
    y = fn(x.reshape(B * S, D), params["router"], params["wi"],
           params["wg"], params["wo"])
    return y.reshape(B, S, D)


def moe_ffn(x: jnp.ndarray, params: Dict, m: MoEConfig, mesh
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routed-experts FFN. x: (B, S, D) sharded over dp axes. Returns
    (y, aux_loss). Shared experts (if any) are applied OUTSIDE via plain TP
    einsums (see transformer.py) — they're dense compute."""
    dp = _dp_axes(mesh)
    tp = mesh.shape["model"]
    body = partial(_local_moe, m=m, tp=tp, model_axis="model", dp_axes=dp)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp if dp else None, None, None),   # x over batch
                  P(None, None),                        # router replicated
                  P("model", None, None),               # experts over tp
                  P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dp if dp else None, None, None), P()),
        check_vma=False)
    return fn(x, params["router"], params["wi"], params["wg"], params["wo"])


def shared_ffn(x: jnp.ndarray, params: Dict) -> jnp.ndarray:
    sp = params["shared"]
    h = jnp.einsum("bsd,df->bsf", x, sp["wi"].astype(x.dtype))
    g = jnp.einsum("bsd,df->bsf", x, sp["wg"].astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * h,
                      sp["wo"].astype(x.dtype))
