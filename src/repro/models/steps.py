"""Step factories: jit-able train_step / prefill_step / decode_step closures
plus ``input_specs`` (ShapeDtypeStruct stand-ins for every model input —
the dry-run lowers against these; nothing is allocated).

train_step semantics:
  * microbatch gradient accumulation (scan) — bounds attention/logit memory,
  * AdamW with warmup-cosine schedule and global-norm clipping,
  * optional int8 error-feedback gradient compression: the whole grad
    computation runs in a shard_map that is manual over ('pod','data') and
    auto over 'model'; gradients cross dp on an int8 ring
    (optim/compression.py). Requires TP-only sharding rules.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.distributed import sharding as sh
from repro.models import serving, transformer
from repro.optim import adamw, compression, schedule


class TrainState(NamedTuple):
    params: Any
    opt: adamw.OptState
    err: Optional[jnp.ndarray]          # compression error-feedback buffer


def init_state(key, cfg: ArchConfig, mesh=None) -> TrainState:
    params = transformer.init_params(key, cfg)
    opt = adamw.init(params, cfg.opt_state_dtype)
    err = None
    if cfg.grad_compression == "int8":
        dp_total = 1
        if mesh is not None:
            for a in sh.dp_axes(mesh):
                dp_total *= mesh.shape[a]
        err = compression.init_error_buffer(params, dp_total)
    return TrainState(params, opt, err)


def default_microbatches(cfg: ArchConfig, shape: ShapeConfig, mesh) -> int:
    """Pick a microbatch count so the per-device microbatch is small while
    each microbatch still fills the batch-sharding axes."""
    baxes = sh.batch_axes(mesh, cfg, shape.global_batch)
    dp = 1
    for a in (baxes or ()):
        dp *= mesh.shape[a]
    per_dev = max(shape.global_batch // max(dp, 1), 1)
    mb = min(per_dev, 8)
    while mb > 1 and (shape.global_batch % (mb * dp)
                      or sh.batch_axes(mesh, cfg, shape.global_batch // mb)
                      != baxes):
        mb -= 1
    return mb


# ---------------------------------------------------------------- factory
def make_train_step(cfg: ArchConfig, mesh, shape: ShapeConfig,
                    microbatches: Optional[int] = None, total_steps: int = 10_000):
    n_mb = microbatches or default_microbatches(cfg, shape, mesh)
    dp = sh.dp_axes(mesh)
    dp_sizes = tuple(mesh.shape[a] for a in dp)

    def mb_grads(params, batch_mb):
        """Gradients of the mean loss over one microbatch."""
        def lf(p):
            loss, metrics = transformer.loss_fn(p, batch_mb, cfg, mesh)
            return loss, metrics
        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
        return loss, metrics, grads

    def accum_grads(params, batch):
        """Scan microbatches, averaging grads. batch leaves: (n_mb, b, ...)
        except non-batched constants (adc_mask), which are closed over."""
        batch = dict(batch)
        const = {k: batch.pop(k) for k in ("adc_mask",) if k in batch}

        def body(carry, mb):
            gsum, lsum = carry
            loss, _, g = mb_grads(params, {**mb, **const})
            gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
            return (gsum, lsum + loss), None
        # accumulate in fp32 for fp32 masters, bf16 when params are bf16
        # (kimi-k2: an fp32 accum buffer alone would cost 8 GB/chip)
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.promote_types(p.dtype,
                                                           jnp.bfloat16)),
            params)
        (gsum, lsum), _ = lax.scan(body, (zeros, jnp.zeros((), jnp.float32)),
                                   batch)
        scale = 1.0 / n_mb
        return (jax.tree_util.tree_map(lambda g: g * scale, gsum),
                lsum * scale)

    def train_step(state: TrainState, batch, step):
        lr = schedule.warmup_cosine(step, peak_lr=cfg.learning_rate,
                                    total=total_steps)
        if cfg.grad_compression == "int8":
            # manual over dp, auto over model: per-dp-shard grads + int8 ring
            def local(params, err, batch):
                grads, loss = accum_grads(params, batch)
                grads, new_err = compression.sync_grads(grads, err[0], dp,
                                                        dp_sizes)
                loss = lax.pmean(loss, dp)
                return grads, new_err[None], loss
            pspec = jax.tree_util.tree_map(lambda _: P(), state.params)
            bspec = jax.tree_util.tree_map(
                lambda _: P(None, dp if len(dp) > 1 else dp[0], *()), batch)
            errspec = P(dp if len(dp) > 1 else dp[0], None)
            grads, new_err, loss = jax.shard_map(
                local, mesh=mesh,
                in_specs=(pspec, errspec, bspec),
                out_specs=(pspec, errspec, P()),
                axis_names=set(dp), check_vma=False,
            )(state.params, state.err, batch)
        else:
            grads, loss = accum_grads(state.params, batch)
            new_err = state.err
        params, opt = adamw.update(
            grads, state.opt, state.params, lr=lr,
            weight_decay=cfg.weight_decay, grad_clip=cfg.grad_clip)
        metrics = {"loss": loss, "lr": lr,
                   "grad_norm": adamw.global_norm(grads)}
        return TrainState(params, opt, new_err), metrics

    return train_step


def make_prefill_step(cfg: ArchConfig, mesh):
    def prefill_step(params, batch):
        return serving.prefill(params, batch, cfg, mesh)
    return prefill_step


def make_decode_step(cfg: ArchConfig, mesh):
    def decode_step(params, batch, cache):
        return serving.decode_step(params, batch, cache, cfg, mesh)
    return decode_step


# ------------------------------------------------------------- input specs
def _pos_shape(cfg: ArchConfig, b: int, s: int):
    return (b, s, 3) if cfg.mrope else (b, s)


def input_specs(cfg: ArchConfig, shape: ShapeConfig, mesh,
                microbatches: Optional[int] = None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins (with shardings) for every model input of
    the given (arch x shape) cell. kind='train' returns the microbatched
    batch; decode kinds return (batch, cache)."""
    dt = jnp.dtype(cfg.dtype)

    def sds(shp, dtype, spec):
        return jax.ShapeDtypeStruct(shp, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def batch_struct(b: int, s: int, lead: tuple = ()):
        """One (micro)batch; ``lead`` prepends the n_mb axis."""
        baxes = sh.batch_axes(mesh, cfg, b)   # divisibility-checked (b=1 ok)

        def mk(shp, dtype, batch_axis_idx):
            parts = [None] * len(shp)
            if baxes:
                parts[batch_axis_idx] = (baxes if len(baxes) > 1 else baxes[0])
            return sds(lead + shp, dtype, P(*( [None] * len(lead) + parts )))
        out: Dict[str, Any] = {}
        if cfg.frontend:
            out["embeddings"] = mk((b, s, cfg.frontend_dim), dt, 0)
            if cfg.adc.enable:
                # non-batched constant: never gets the microbatch lead dim
                out["adc_mask"] = sds((cfg.frontend_dim, 2 ** cfg.adc.bits),
                                      jnp.int32, P())
        else:
            out["tokens"] = mk((b, s), jnp.int32, 0)
        out["positions"] = mk(_pos_shape(cfg, b, s), jnp.int32, 0)
        if shape.kind == "train":
            out["labels"] = mk((b, s), jnp.int32, 0)
        return out

    if shape.kind == "train":
        n_mb = microbatches or default_microbatches(cfg, shape, mesh)
        b_mb = shape.global_batch // n_mb
        return {"batch": batch_struct(b_mb, shape.seq_len, lead=(n_mb,)),
                "n_microbatches": n_mb}
    if shape.kind == "prefill":
        return {"batch": batch_struct(shape.global_batch, shape.seq_len)}
    # decode: one new token against a seq_len cache
    b = shape.global_batch
    cache_shapes = jax.eval_shape(
        lambda: serving.init_cache(cfg, b, shape.seq_len))
    cache_specs = sh.cache_specs(cache_shapes, mesh, cfg)
    cache = jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                          sharding=NamedSharding(mesh, s)),
        cache_shapes, cache_specs)
    return {"batch": batch_struct(b, 1), "cache": cache}
