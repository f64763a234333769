"""HLO roofline-parser validation: the parsed (trip-count-scaled) dot FLOPs
must track analytic model FLOPs, and multipliers must recover scan trip
counts (the whole §Roofline methodology rests on this)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import analysis


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_multipliers_recover_scan_trip_count():
    L = 7

    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y

    text = _compiled_text(
        f, jnp.ones((8, 16)), jnp.ones((L, 16, 16)))
    comps = analysis._split_computations(text)
    mult, fused = analysis.computation_multipliers(comps)
    assert any(abs(m - L) < 1e-6 for m in mult.values()), mult


def test_parsed_flops_match_analytic_matmul():
    m, k, n = 64, 128, 32

    def f(a, b):
        return a @ b

    text = _compiled_text(f, jnp.ones((m, k)), jnp.ones((k, n)))
    st = analysis.hlo_stats(text)
    assert st.dot_ops >= 1
    np.testing.assert_allclose(st.flops, 2 * m * k * n, rtol=0.01)


def test_parsed_flops_scale_with_scan():
    L, m, k = 5, 32, 64

    def f(x, w):
        def body(c, wi):
            return c @ wi, None
        y, _ = jax.lax.scan(body, x, w)
        return y

    text = _compiled_text(f, jnp.ones((m, k)), jnp.ones((L, k, k)))
    st = analysis.hlo_stats(text)
    np.testing.assert_allclose(st.flops, L * 2 * m * k * k, rtol=0.05)


def test_end_to_end_vs_6nd():
    """Tiny train step: parsed flops within ~40% of 6*N*D (attention and
    normalisation add the overhead; gross scan-miscounting would be >5x)."""
    from repro.configs import smoke_config
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import steps

    cfg = smoke_config("deepseek-7b").replace(num_layers=3)
    mesh = make_host_mesh(1, 1)
    shape = ShapeConfig("t", 64, 4, "train")
    with jax.set_mesh(mesh):
        state = steps.init_state(jax.random.PRNGKey(0), cfg, mesh)
        fn = steps.make_train_step(cfg, mesh, shape, microbatches=2)
        specs = steps.input_specs(cfg, shape, mesh, microbatches=2)
        text = jax.jit(fn).lower(
            state, specs["batch"],
            jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    st = analysis.hlo_stats(text)
    n = cfg.param_counts()["total"]
    model = 6.0 * n * shape.seq_len * shape.global_batch
    assert 0.6 < st.flops / model < 2.0, (st.flops, model)


def test_ideal_bytes_sane():
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    cfg = get_config("deepseek-7b")
    tr = analysis.ideal_bytes(cfg, SHAPES["train_4k"], 256, 8)
    de = analysis.ideal_bytes(cfg, SHAPES["decode_32k"], 256)
    assert tr > 0 and de > 0
    # decode floor is at least the params per chip (deepseek is MHA, so its
    # 32k cache actually exceeds train's weight traffic — both are counted)
    assert de >= cfg.param_counts()["active"] * 2 / 256