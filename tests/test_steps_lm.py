"""LM training/serving integration tests on smoke configs:
* training loss decreases on the synthetic corpus,
* prefill last-token logits == full forward logits (serving == training
  numerics),
* decode continuation matches teacher-forced forward (cache correctness),
* checkpoint restore resumes training bit-identically.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.launch.mesh import make_host_mesh
from repro.launch.train import build
from repro.models import serving, steps, transformer


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh(1, 1)


def test_train_loss_decreases(mesh):
    cfg, mesh_, train_step, data = build("deepseek-7b", smoke=True, seq=64,
                                         batch=8, microbatches=2,
                                         steps_total=30)
    with jax.set_mesh(mesh_):
        state = steps.init_state(jax.random.PRNGKey(0), cfg, mesh_)
        jstep = jax.jit(train_step, donate_argnums=(0,))
        losses = []
        for i in range(30):
            state, m = jstep(state, data.device_batch(i),
                             jnp.asarray(i, jnp.int32))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.3, (losses[0], losses[-1])
        assert np.isfinite(losses).all()


@pytest.mark.parametrize("arch", ["deepseek-7b", "gemma2-2b", "mamba2-1.3b",
                                  "hymba-1.5b"])
def test_prefill_matches_forward(arch, mesh):
    """Last-position prefill logits must equal the training-path logits."""
    cfg = smoke_config(arch)
    b, s = 2, 16
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    batch = {"tokens": tokens, "positions": pos}
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)
    with jax.set_mesh(mesh):
        full = transformer.logits_fn(params, batch, cfg, mesh)      # (b,s,V)
        pre, cache = serving.prefill(params, batch, cfg, mesh)      # (b,V)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, -1]),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch", ["deepseek-7b", "gemma2-2b", "mamba2-1.3b",
                                  "hymba-1.5b"])
def test_decode_matches_teacher_forcing(arch, mesh):
    """Decoding token s+1 with the cache == forward over the extended seq."""
    cfg = smoke_config(arch)
    b, s = 2, 16
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s + 1)), jnp.int32)
    pos_full = jnp.broadcast_to(jnp.arange(s + 1, dtype=jnp.int32), (b, s + 1))
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)
    with jax.set_mesh(mesh):
        want = transformer.logits_fn(
            params, {"tokens": toks, "positions": pos_full}, cfg, mesh)[:, -1]
        _, cache = serving.prefill(
            params, {"tokens": toks[:, :s], "positions": pos_full[:, :s]},
            cfg, mesh, extra_slots=1)
        got, _ = serving.decode_step(
            params, {"tokens": toks[:, s:s + 1],
                     "positions": pos_full[:, s:s + 1]}, cache, cfg, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2, atol=3e-2)


def test_checkpoint_resume_bitwise(tmp_path, mesh):
    from repro.checkpoint.manager import CheckpointManager
    cfg, mesh_, train_step, data = build("phi3-mini-3.8b", smoke=True,
                                         seq=32, batch=4, microbatches=1,
                                         steps_total=10)
    with jax.set_mesh(mesh_):
        jstep = jax.jit(train_step)
        s0 = steps.init_state(jax.random.PRNGKey(0), cfg, mesh_)
        # straight run: 6 steps
        s = s0
        for i in range(6):
            s, _ = jstep(s, data.device_batch(i), jnp.asarray(i, jnp.int32))
        ref = s.params
        # checkpointed run: 3 steps, save, restore, 3 more
        ck = CheckpointManager(tmp_path, keep=1)
        s = s0
        for i in range(3):
            s, _ = jstep(s, data.device_batch(i), jnp.asarray(i, jnp.int32))
        ck.save(3, s, blocking=True)
        s = ck.restore(3, jax.tree_util.tree_map(jnp.zeros_like, s))
        for i in range(3, 6):
            s, _ = jstep(s, data.device_batch(i), jnp.asarray(i, jnp.int32))
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(s.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
