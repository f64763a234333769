"""Int8 error-feedback gradient compression over the data-parallel axes.

Attacks the *collective* roofline term: the fp32 ring all-reduce that
dominates DP training of replicated-gradient models becomes

  1. int8 ring reduce-scatter over 'data' (per-hop requantization, 16 hops),
  2. int8 partner exchange over 'pod' (cross-pod links are the scarce ones),
  3. int8 ring all-gather over 'data',

cutting bytes-on-wire 4x (8 B/elem -> 2 B/elem). Per-hop requantization
noise is compensated at the origin by a persistent bf16 error-feedback
buffer (1-bit-Adam / EF-SGD lineage); tests bound the end-to-end error and
verify EF removes bias across steps.

Used inside a ``shard_map`` that is *manual* over ('pod','data') and auto
over 'model' (see steps.make_train_step). Requires TP-only param sharding
(params replicated over dp) — configs opt in via ``grad_compression='int8'``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax



def _quant(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _ring_perm(n):
    return [(i, (i + 1) % n) for i in range(n)]


def ring_allreduce_int8(x: jnp.ndarray, axis: str, n: int) -> jnp.ndarray:
    """Mean over ``axis`` (size n) of the flat fp32 vector ``x`` using int8
    messages. Must run inside shard_map manual over ``axis``."""
    if n == 1:
        return x
    r = lax.axis_index(axis)
    k = -(-x.shape[0] // n)                       # ceil
    xp = jnp.pad(x, (0, n * k - x.shape[0])).reshape(n, k)
    perm = _ring_perm(n)

    # ---- reduce-scatter: after n-1 hops rank r owns chunk (r+1) % n ----
    def rs_body(t, carry):
        part = carry                              # fp32 partial sum (k,)
        q, s = _quant(part)
        q = lax.ppermute(q, axis, perm)
        s = lax.ppermute(s, axis, perm)
        recv_idx = (r - t - 1) % n
        nxt = q.astype(jnp.float32) * s + lax.dynamic_index_in_dim(
            xp, recv_idx, axis=0, keepdims=False)
        return nxt

    part0 = lax.dynamic_index_in_dim(xp, r % n, axis=0, keepdims=False)
    owned = lax.fori_loop(0, n - 1, rs_body, part0) / n   # mean

    # ---- all-gather: circulate each owned chunk (quantize once) ----
    q_own, s_own = _quant(owned)

    def ag_body(t, carry):
        buf, q, s = carry                         # buf (n, k) fp32 assembled
        q = lax.ppermute(q, axis, perm)
        s = lax.ppermute(s, axis, perm)
        src = (r - t) % n                         # rank that owns what arrived
        chunk_idx = (src + 1) % n
        buf = lax.dynamic_update_index_in_dim(
            buf, q.astype(jnp.float32) * s, chunk_idx, axis=0)
        return buf, q, s

    buf = jnp.zeros((n, k), jnp.float32)
    buf = lax.dynamic_update_index_in_dim(buf, q_own.astype(jnp.float32) * s_own,
                                          (r + 1) % n, axis=0)
    buf, _, _ = lax.fori_loop(1, n, ag_body, (buf, q_own, s_own))
    return buf.reshape(-1)[: x.shape[0]]


def compressed_mean(x: jnp.ndarray, dp_axes: Tuple[str, ...],
                    dp_sizes: Tuple[int, ...]) -> jnp.ndarray:
    """Hierarchical compressed mean over ('pod','data') or ('data',)."""
    sizes = dict(zip(dp_axes, dp_sizes))
    if "data" in sizes:
        x = ring_allreduce_int8(x, "data", sizes["data"])
    if "pod" in sizes and sizes["pod"] > 1:
        npod = sizes["pod"]
        assert npod == 2, "partner exchange implemented for 2 pods"
        q, s = _quant(x)
        q2 = lax.ppermute(q, "pod", [(0, 1), (1, 0)])
        s2 = lax.ppermute(s, "pod", [(0, 1), (1, 0)])
        x = (x + q2.astype(jnp.float32) * s2) / 2.0
    return x


def sync_grads(grads, err, dp_axes: Tuple[str, ...], dp_sizes: Tuple[int, ...]):
    """Flatten grad pytree -> one vector -> compressed mean -> unflatten.

    Error feedback is *exact for the local quantization*: each leaf is
    fake-quantized (per-leaf int8 scale) before entering the ring; the
    residual (g + e) - deq(Q(g + e)) is carried to the next step in bf16.
    Per-hop requantization noise inside the ring is additional, unbiased
    across ranks, and bounded by tests. Returns (mean_grads, new_err)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    shapes = [l.shape for l in leaves]
    sizes = [l.size for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    if err is not None:
        flat = flat + err.astype(jnp.float32)
    # local fake-quant per leaf (exact EF boundary)
    deq_parts, off = [], 0
    for sz in sizes:
        seg = flat[off:off + sz]
        q, s = _quant(seg)
        deq_parts.append(q.astype(jnp.float32) * s)
        off += sz
    flat_deq = jnp.concatenate(deq_parts)
    new_err = (flat - flat_deq).astype(jnp.bfloat16) if err is not None else None
    synced = compressed_mean(flat_deq, dp_axes, dp_sizes)
    out, off = [], 0
    for sh, sz, l in zip(shapes, sizes, leaves):
        out.append(synced[off:off + sz].reshape(sh).astype(l.dtype))
        off += sz
    return jax.tree_util.tree_unflatten(treedef, out), new_err


def init_error_buffer(params, dp_total: int = 1) -> jnp.ndarray:
    """Per-dp-rank error state, materialised as a (dp_total, n) array whose
    leading dim is sharded over the dp axes (each rank sees its own row)."""
    n = sum(l.size for l in jax.tree_util.tree_leaves(params))
    return jnp.zeros((dp_total, n), jnp.bfloat16)
