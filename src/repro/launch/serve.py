"""Serving launcher: batched prefill + decode loop with a simple continuous
request queue (the inference-side end-to-end driver).

  PYTHONPATH=src python -m repro.launch.serve --arch musicgen-medium --smoke \
      --requests 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch import mesh as mesh_lib
from repro.models import serving, steps, transformer


def make_batch(cfg, b, s, start_pos=0, rng=None):
    rng = rng or np.random.default_rng(0)
    out = {}
    if cfg.frontend:
        out["embeddings"] = jnp.asarray(
            rng.random((b, s, cfg.frontend_dim), np.float32))
        if cfg.adc.enable:
            out["adc_mask"] = jnp.ones((cfg.frontend_dim, 2 ** cfg.adc.bits),
                                       jnp.int32)
    else:
        out["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    pos = np.arange(start_pos, start_pos + s, dtype=np.int32)[None].repeat(b, 0)
    out["positions"] = jnp.asarray(np.stack([pos] * 3, -1) if cfg.mrope else pos)
    return out


def token_to_batch(cfg, tokens, pos_scalar, b, rng):
    """Next-step decode inputs from sampled tokens."""
    out = {}
    if cfg.frontend:
        out["embeddings"] = jnp.asarray(
            rng.random((b, 1, cfg.frontend_dim), np.float32))
        if cfg.adc.enable:
            out["adc_mask"] = jnp.ones((cfg.frontend_dim, 2 ** cfg.adc.bits),
                                       jnp.int32)
    else:
        out["tokens"] = tokens[:, None]
    pos = np.full((b, 1), pos_scalar, np.int32)
    out["positions"] = jnp.asarray(np.stack([pos] * 3, -1) if cfg.mrope else pos)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=1.0)
    args = ap.parse_args(argv)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = mesh_lib.make_host_mesh(1, 1)
    rng = np.random.default_rng(0)
    with jax.set_mesh(mesh):
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        prefill = jax.jit(steps.make_prefill_step(cfg, mesh))
        decode = jax.jit(steps.make_decode_step(cfg, mesh),
                         donate_argnums=(2,))
        b, s = args.requests, args.prompt_len
        batch = make_batch(cfg, b, s, rng=rng)
        t0 = time.time()
        logits, cache = prefill(params, batch)
        t_prefill = time.time() - t0
        key = jax.random.PRNGKey(1)
        toks = []
        t0 = time.time()
        for i in range(args.gen):
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, logits / args.temperature, -1)
            toks.append(np.asarray(nxt))
            step_batch = token_to_batch(cfg, nxt, s + i, b, rng)
            logits, cache = decode(params, step_batch, cache)
        t_decode = time.time() - t0
        gen = np.stack(toks, 1)
        print(f"prefill: {b}x{s} in {t_prefill:.2f}s; "
              f"decode: {args.gen} steps in {t_decode:.2f}s "
              f"({t_decode / max(args.gen, 1) * 1e3:.0f} ms/tok)")
        print("generated token matrix:\n", gen)
        assert gen.shape == (b, args.gen)
        assert np.isfinite(np.asarray(logits)).all()
    return gen


if __name__ == "__main__":
    main()
