"""Device-sharded search engine (DESIGN.md §7): parity against the
batched single-device oracle (1-device and a forced 2x1 CPU mesh), the
population-axis sharding rules, the ops-level sharded population
quantize, and the search-state checkpoint/resume contract (a
killed-and-resumed search matches an uninterrupted run
generation-for-generation, bit-identically)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from repro.checkpoint import manager
from repro.checkpoint.manager import CheckpointManager
from repro.core.spec import AdcSpec
from repro.core import nsga2, search
from repro.distributed import sharding

REPO = Path(__file__).resolve().parents[1]

SIZES = (7, 4, 3)


def _data():
    from repro.data import tabular
    return tabular.make_dataset("seeds")


def _genomes(pop, bits, seed=0):
    G = search.genome_len(SIZES[0], bits)
    rng = np.random.default_rng(seed)
    g = (rng.random((pop, G)) < 0.5).astype(np.uint8)
    g[0] = 1
    return g


# ----------------------------------------------------------- sharding rules
def test_population_axes_prefers_widest_divisible_candidate():
    mesh = SimpleNamespace(axis_names=("data", "model"),
                           shape={"data": 4, "model": 2})
    assert sharding.population_axes(mesh, 16) == ("data", "model")
    assert sharding.population_axes(mesh, 12) == ("data",)   # 12 % 8 != 0
    assert sharding.population_axes(mesh, 6) == ("model",)   # 6 % 4 != 0
    # nothing divides 7 except nothing at all -> caller falls back
    assert sharding.population_axes(mesh, 7) is None


def test_population_axes_trivial_mesh_still_shards():
    mesh = SimpleNamespace(axis_names=("data", "model"),
                           shape={"data": 1, "model": 1})
    # size-1 shard is legal: the shard_map engine runs, trivially
    assert sharding.population_axes(mesh, 5) == ("data", "model")


def test_population_axes_pod_mesh():
    mesh = SimpleNamespace(axis_names=("pod", "data", "model"),
                           shape={"pod": 2, "data": 4, "model": 2})
    assert sharding.population_axes(mesh, 16) == ("pod", "data", "model")
    # 8 % 16 != 0: ties at size 8 resolve to the earliest candidate
    assert sharding.population_axes(mesh, 8) == ("data", "model")


# ------------------------------------------------------------ engine parity
def test_sharded_engine_matches_batched_single_device():
    """Acceptance: identical fitness matrix (and hence Pareto front) from
    the sharded engine and the batched oracle on the host mesh."""
    data = _data()
    cfg = search.SearchConfig(bits=2, pop_size=6, generations=1,
                              train_steps=30)
    pop = _genomes(cfg.pop_size, cfg.bits)
    fb = search.evaluate_population(pop, data, SIZES, cfg)
    fs = search.evaluate_population_sharded(pop, data, SIZES, cfg)
    np.testing.assert_array_equal(fb[:, 1], fs[:, 1])    # areas exact
    np.testing.assert_allclose(fb[:, 0], fs[:, 0], atol=1e-6)
    rank_b = nsga2.fast_non_dominated_sort(fb)
    rank_s = nsga2.fast_non_dominated_sort(fs)
    np.testing.assert_array_equal(rank_b == 0, rank_s == 0)


def test_run_search_sharded_engine_agrees_with_batched():
    data = _data()
    kw = dict(bits=2, pop_size=6, generations=2, train_steps=20)
    pg_b, pf_b, _ = search.run_search(
        data, SIZES, search.SearchConfig(engine="batched", **kw))
    pg_s, pf_s, _ = search.run_search(
        data, SIZES, search.SearchConfig(engine="sharded", **kw))
    np.testing.assert_array_equal(pg_b, pg_s)
    np.testing.assert_allclose(pf_b, pf_s, atol=1e-6)


def test_ops_population_sharded_matches_unsharded():
    import jax.numpy as jnp

    from repro.kernels import ops
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.random((40, 5)), jnp.float32)
    masks = (rng.random((6, 5, 4)) < 0.6).astype(np.int32)
    masks[..., 0] = 1
    masks[..., -1] = 1
    masks = jnp.asarray(masks)
    mesh = search.default_search_mesh()
    want = ops.adc_quantize_population(x, masks, spec=AdcSpec(bits=2))
    got = ops.adc_quantize_population_sharded(x, masks, mesh=mesh,
                                               spec=AdcSpec(bits=2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np, jax
    from repro.core import search, nsga2
    from repro.data import tabular

    assert len(jax.devices()) == 2, jax.devices()
    mesh = jax.make_mesh((2, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    data = tabular.make_dataset("seeds")
    sizes = (7, 4, 3)
    cfg = search.SearchConfig(bits=2, pop_size=6, generations=1,
                              train_steps=30)
    G = search.genome_len(sizes[0], cfg.bits)
    rng = np.random.default_rng(0)
    pop = (rng.random((cfg.pop_size, G)) < 0.5).astype(np.uint8)
    pop[0] = 1
    fb = search.evaluate_population(pop, data, sizes, cfg)
    fs = search.evaluate_population_sharded(pop, data, sizes, cfg,
                                            mesh=mesh)
    np.testing.assert_array_equal(fb[:, 1], fs[:, 1])
    np.testing.assert_allclose(fb[:, 0], fs[:, 0], atol=1e-6)
    rb = nsga2.fast_non_dominated_sort(fb)
    rs = nsga2.fast_non_dominated_sort(fs)
    np.testing.assert_array_equal(rb == 0, rs == 0)
    # odd population: no axis set divides 5 except the size-1 'model'
    # candidate -> replicated-compute degradation, results unchanged
    f5b = search.evaluate_population(pop[:5], data, sizes, cfg)
    f5s = search.evaluate_population_sharded(pop[:5], data, sizes, cfg,
                                             mesh=mesh)
    np.testing.assert_allclose(f5b, f5s, atol=1e-6)
    print("OK-SHARDED-2DEV")
""")


def test_sharded_parity_on_forced_two_device_mesh():
    """jax locks the device count at init, so the 2x1 CPU mesh check runs
    in a subprocess with XLA_FLAGS set (same pattern as
    test_compression)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK-SHARDED-2DEV" in out.stdout


# ----------------------------------------------------- checkpoint + resume
def test_pack_unpack_json_roundtrip_rng_state():
    rng = np.random.default_rng(42)
    rng.random(17)                                  # advance the stream
    st = rng.bit_generator.state
    arr = manager.pack_json(st)
    assert arr.dtype == np.uint8
    rng2 = np.random.default_rng()
    rng2.bit_generator.state = manager.unpack_json(arr)
    np.testing.assert_array_equal(rng.random(8), rng2.random(8))


def test_search_state_tree_roundtrip(tmp_path):
    rng = np.random.default_rng(1)
    state = nsga2.EvolveState(
        pop=(rng.random((6, 30)) < 0.5).astype(np.uint8),
        fit=rng.random((6, 2)).astype(np.float64),
        generation=3, rng=rng)
    ckpt = CheckpointManager(tmp_path, keep=2)
    ckpt.save(state.generation, search.search_state_tree(state),
              blocking=True)
    got = search.restore_search_state(ckpt, 3, 6, 30)
    np.testing.assert_array_equal(got.pop, state.pop)
    np.testing.assert_array_equal(got.fit, state.fit)   # f64 bit-exact
    assert got.fit.dtype == np.float64
    assert got.generation == 3
    np.testing.assert_array_equal(got.rng.random(8), state.rng.random(8))


def test_killed_and_resumed_search_matches_uninterrupted(tmp_path):
    """Acceptance: run 4 generations straight through; separately run 2
    generations (the 'kill'), then resume to 4 from the checkpoint. The
    resumed run must replay generations 2..4 bit-identically — same
    per-generation fitness matrices, same final Pareto front."""
    data = _data()
    kw = dict(bits=2, pop_size=6, generations=4, train_steps=20)

    hist_ref = {}
    pg_ref, pf_ref, _ = search.run_search(
        data, SIZES, search.SearchConfig(**kw),
        log=lambda g, p, f: hist_ref.__setitem__(g, (p.copy(), f.copy())))

    ckpt = CheckpointManager(tmp_path / "search", keep=2)
    search.run_search(data, SIZES,
                      search.SearchConfig(**dict(kw, generations=2)),
                      ckpt=ckpt)
    assert ckpt.latest_step() == 2

    hist_res = {}
    pg_res, pf_res, _ = search.run_search(
        data, SIZES, search.SearchConfig(**kw), ckpt=ckpt, resume=True,
        log=lambda g, p, f: hist_res.__setitem__(g, (p.copy(), f.copy())))

    assert sorted(hist_res) == [2, 3]               # only the tail re-ran
    for g in hist_res:
        np.testing.assert_array_equal(hist_res[g][0], hist_ref[g][0])
        np.testing.assert_array_equal(hist_res[g][1], hist_ref[g][1])
    np.testing.assert_array_equal(pg_ref, pg_res)
    np.testing.assert_array_equal(pf_ref, pf_res)


def test_resume_past_target_returns_checkpointed_archive(tmp_path):
    data = _data()
    kw = dict(bits=2, pop_size=6, generations=2, train_steps=20)
    ckpt = CheckpointManager(tmp_path / "s", keep=2)
    pg, pf, _ = search.run_search(data, SIZES, search.SearchConfig(**kw),
                                  ckpt=ckpt)
    # resume with the same generation target: nothing re-runs
    pg2, pf2, _ = search.run_search(data, SIZES, search.SearchConfig(**kw),
                                    ckpt=ckpt, resume=True)
    np.testing.assert_array_equal(pg, pg2)
    np.testing.assert_array_equal(pf, pf2)


def test_evolve_state_stepping_matches_monolithic_loop():
    """evolve() == init_state + N x evolve_step on a cheap synthetic
    fitness (no QAT), including the RNG stream."""
    def eval_fn(pop):
        s = pop.sum(1).astype(np.float64)
        return np.stack([s, -s + pop.shape[1]], axis=1)

    pop_a, fit_a = nsga2.evolve(eval_fn, 12, pop_size=8, generations=5,
                                seed=3)
    st = nsga2.init_state(eval_fn, 12, pop_size=8, seed=3)
    for _ in range(5):
        st = nsga2.evolve_step(st, eval_fn)
    np.testing.assert_array_equal(pop_a, st.pop)
    np.testing.assert_array_equal(fit_a, st.fit)
    assert st.generation == 5
