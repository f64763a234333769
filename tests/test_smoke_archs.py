"""Per-architecture smoke tests: REDUCED config of the same family, one
forward/train step + prefill/decode on CPU, asserting shapes and no NaNs.
(The FULL configs are exercised via the dry-run only.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, smoke_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.models import steps, transformer, serving


def _batch_for(cfg, b, s, kind="train"):
    rng = np.random.default_rng(0)
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
    pos = np.arange(s, dtype=np.int32)[None].repeat(b, 0)
    if cfg.mrope:
        pos = np.stack([pos] * 3, axis=-1)
    out["positions"] = jnp.asarray(pos)
    if kind == "train":
        out["labels"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    return out


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh(1, 1)


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_forward_and_train_step(arch, mesh):
    cfg = smoke_config(arch)
    b, s = 2, 16
    batch = _batch_for(cfg, b, s)
    state = steps.init_state(jax.random.PRNGKey(0), cfg, mesh)
    with jax.set_mesh(mesh):
        loss, metrics = transformer.loss_fn(state.params, batch, cfg, mesh)
        assert np.isfinite(float(loss)), (arch, float(loss))
        shape = ShapeConfig("smoke", s, b, "train")
        ts = steps.make_train_step(cfg, mesh, shape, microbatches=2,
                                   total_steps=10)
        mb = {k: (v if k == "adc_mask"
                  else v.reshape(2, b // 2, *v.shape[1:]))
              for k, v in batch.items()}
        state2, m = jax.jit(ts)(state, mb, jnp.zeros((), jnp.int32))
        assert np.isfinite(float(m["loss"])), arch
        # params actually changed
        d0 = jax.tree_util.tree_leaves(state.params)[1]
        d1 = jax.tree_util.tree_leaves(state2.params)[1]
        assert float(jnp.abs(d0 - d1).max()) > 0


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_prefill_decode_consistency(arch, mesh):
    """Prefill then one decode step: logits finite, cache advances."""
    cfg = smoke_config(arch)
    b, s = 2, 16
    batch = _batch_for(cfg, b, s, kind="prefill")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    with jax.set_mesh(mesh):
        logits, cache = serving.prefill(params, batch, cfg, mesh)
        assert logits.shape == (b, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all()), arch
        step_batch = _batch_for(cfg, b, 1, kind="decode")
        pos = np.full((b, 1), s, np.int32)
        step_batch["positions"] = jnp.asarray(
            np.stack([pos] * 3, -1) if cfg.mrope else pos)
        lg2, cache2 = serving.decode_step(params, step_batch, cache, cfg, mesh)
        assert lg2.shape == (b, cfg.vocab_size)
        assert bool(jnp.isfinite(lg2).all()), arch
        assert int(cache2["pos"]) == int(cache["pos"]) + 1
