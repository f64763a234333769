"""Hardware non-ideality subsystem (DESIGN.md §10): NonIdealSpec
invariants, MC kernel-vs-oracle bitwise parity, the ideal-limit
bit-for-bit contract, the robustness-aware 3-objective co-search, and
the search -> deploy reproduction of the robustness objective."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adc, deploy, nonideal, search
from repro.core.nonideal import NonIdealSpec
from repro.core.spec import AdcSpec
from repro.data import tabular
from repro.kernels import dispatch, ops, ref

SEEDS = tabular.make_dataset("seeds")
SIZES = (7, 4, 3)


def _rand_masks(rng, p, c, bits):
    masks = jnp.asarray((rng.random((p, c, 2 ** bits)) < 0.6)
                        .astype(np.int32))
    return adc.repair_mask(masks)


# ------------------------------------------------------------ NonIdealSpec
def test_nonideal_spec_invariants():
    s = NonIdealSpec(sigma_offset=0.5, sigma_range=0.01, fault_rate=0.1,
                     seed=3)
    assert hash(s) == hash(NonIdealSpec(0.5, 0.01, 0.1, 3))
    {s: 1}                                       # static-jit-arg safe
    assert not s.ideal and NonIdealSpec().ideal
    # pytree round trip
    leaves, treedef = jax.tree_util.tree_flatten(s)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back == s and isinstance(back, NonIdealSpec)
    # JSON meta round trip
    assert NonIdealSpec.from_meta(
        json.loads(json.dumps(s.to_meta()))) == s


def test_nonideal_spec_validation():
    with pytest.raises(ValueError):
        NonIdealSpec(sigma_offset=-0.1)
    with pytest.raises(ValueError):
        NonIdealSpec(sigma_range=-1.0)
    with pytest.raises(ValueError):
        NonIdealSpec(fault_rate=1.5)
    with pytest.raises(ValueError):
        search.SearchConfig(robust_objective="magic")
    with pytest.raises(ValueError):
        search.SearchConfig(mc_samples=-1)


def test_draws_are_mask_independent_and_seeded():
    ni = NonIdealSpec(sigma_offset=1.0, seed=5)
    d1 = nonideal.draw(3, 4, 6, ni)
    d2 = nonideal.draw(3, 4, 6, ni)
    for a, b in zip(d1, d2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    d3 = nonideal.draw(3, 4, 6, ni.replace(seed=6))
    assert not np.array_equal(np.asarray(d1.eps), np.asarray(d3.eps))
    assert d1.samples == 6 and d1.eps.shape == (6, 4, 7)


# ------------------------------------------- compiled operand entry
@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 - 1, 2 ** 31, 2 ** 32 + 5,
                                  -7])
def test_host_prng_key_is_jax_prngkey(seed):
    assert NonIdealSpec(seed=seed).seed == seed          # admitted as is
    want = jax.random.key_data(jax.random.PRNGKey(seed))
    np.testing.assert_array_equal(nonideal.prng_key(seed), np.asarray(want))


@pytest.mark.parametrize("path", ["seed", "draws"])
@pytest.mark.parametrize("lead", [(), (4,)], ids=["single", "population"])
def test_compiled_operands_match_in_graph_mc_operands(path, lead):
    """The compiled entry equals, bit for bit, ``mc_operands`` traced
    inside a jit with the sigmas static and the draws as data — the way
    the co-search's generation builds them — whether it draws from the
    seed or takes the caller's draws."""
    rng = np.random.default_rng(5)
    spec = AdcSpec(bits=3, vmin=(0.0, -1.0, 0.5), vmax=(1.0, 1.0, 2.5))
    masks = _rand_masks(rng, 4, 3, 3)
    masks = np.asarray(masks[0] if not lead else masks)
    ni = NonIdealSpec(sigma_offset=0.5, sigma_range=0.03, fault_rate=0.1,
                      seed=2 ** 31 + 3)
    draws = nonideal.draw(3, 3, 6, ni)
    in_graph = jax.jit(lambda m, d: nonideal.mc_operands(spec, ni, m,
                                                         draws=d))
    want = in_graph(jnp.asarray(masks), draws)
    kw = dict(samples=6) if path == "seed" else dict(draws=draws)
    got = nonideal.compiled_mc_operands(spec, ni, masks, **kw)
    assert len(got) == len(want) == 5
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_compiled_operands_trace_once_across_seeds():
    """Three wafers at one shape: one trace, since the seed is data."""
    from repro import obs
    rng = np.random.default_rng(6)
    spec = AdcSpec(bits=2)
    masks = np.asarray(_rand_masks(rng, 3, 5, 2))       # a shape of its own
    ni = NonIdealSpec(sigma_offset=0.4, sigma_range=0.02, fault_rate=0.05)
    before = obs.counters().get("deploy.operand_traces", 0)
    outs = [nonideal.compiled_mc_operands(spec, ni.replace(seed=s), masks,
                                          samples=9)
            for s in (11, 2 ** 31 + 11, 12)]
    assert obs.counters()["deploy.operand_traces"] - before == 1
    assert not np.array_equal(np.asarray(outs[0][0]), np.asarray(outs[2][0]))


# ------------------------------------------------- kernel-vs-oracle parity
@pytest.mark.parametrize("spec", [
    AdcSpec(bits=3),
    AdcSpec(bits=2, vmin=(0.0, -1.0, 0.0, 0.2), vmax=(1.0, 1.0, 2.0, 0.8)),
])
def test_mc_kernel_matches_oracle_bitwise(spec):
    """The MC Pallas kernel (interpret mode off-TPU) matches the jnp
    oracle bitwise for fixed draws — scalar and per-channel ranges."""
    rng = np.random.default_rng(0)
    c = spec.channels or 4
    x = jnp.asarray(rng.uniform(-1.5, 2.5, (37, c)), jnp.float32)
    mask = _rand_masks(rng, 1, c, spec.bits)[0]
    ni = NonIdealSpec(sigma_offset=0.8, sigma_range=0.05, fault_rate=0.2,
                      seed=11)
    mc = nonideal.mc_operands(spec, ni, mask, samples=5)
    kern = dispatch.get("mc_eval").kernel(x, *mc, spec=spec,
                                          interpret=True)
    orac = ref.mc_adc_eval_ref(x, *mc)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(orac))


def test_mc_population_kernel_matches_oracle_bitwise():
    rng = np.random.default_rng(1)
    spec = AdcSpec(bits=3, vmin=(0.0, -1.0, 0.5), vmax=(1.0, 1.0, 2.5))
    x = jnp.asarray(rng.uniform(-1.5, 3.0, (19, 3)), jnp.float32)
    masks = _rand_masks(rng, 4, 3, 3)
    ni = NonIdealSpec(sigma_offset=0.5, sigma_range=0.03, fault_rate=0.1,
                      seed=2)
    mc = nonideal.mc_operands(spec, ni, masks, samples=3)
    kern = dispatch.get("mc_eval_population").kernel(x, *mc, spec=spec,
                                                     interpret=True)
    orac = ref.mc_adc_eval_ref_population(x, *mc)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(orac))
    # population oracle rows == per-design single-entry oracle
    for p in range(masks.shape[0]):
        one = nonideal.mc_operands(spec, ni, masks[p], samples=3)
        np.testing.assert_array_equal(np.asarray(orac[p]),
                                      np.asarray(ref.mc_adc_eval_ref(
                                          x, *one)))


def test_mc_registry_entries():
    assert "mc_eval" in dispatch.entries()
    assert "mc_eval_population" in dispatch.entries()
    for name in ("mc_eval", "mc_eval_population"):
        assert dispatch.get(name).interpret_policy == "oracle"
    # auto policy off-TPU -> oracle, same as every other entry
    res = dispatch.resolve("mc_eval_population", AdcSpec(bits=3), 4)
    if jax.default_backend() != "tpu":
        assert res.path == "oracle"


# ------------------------------------------------------------- ideal limit
def test_ideal_limit_is_bitwise_the_ideal_pipeline():
    """sigma=0, fault_rate=0, drift=0: every MC instance equals the ideal
    quantizer output bit-for-bit (single and population paths)."""
    rng = np.random.default_rng(3)
    spec = AdcSpec(bits=3, vmin=(0.0, -1.0), vmax=(1.0, 2.0))
    x = jnp.asarray(rng.uniform(-1.5, 2.5, (23, 2)), jnp.float32)
    masks = _rand_masks(rng, 3, 2, 3)
    out = nonideal.mc_quantize(x, masks, spec, NonIdealSpec(), samples=4)
    base = ops.adc_quantize_population(x, masks, spec=spec)
    for p in range(3):
        for s in range(4):
            np.testing.assert_array_equal(np.asarray(out[p, s]),
                                          np.asarray(base[p]))


def test_faulted_outputs_are_still_ladder_values():
    """Whatever the faults/offsets do, the ADC emits values from the
    design's nominal reconstruction ladder (the digital back end is
    unperturbed), and every input lands in exactly one interval."""
    rng = np.random.default_rng(4)
    spec = AdcSpec(bits=3)
    mask = _rand_masks(rng, 1, 4, 3)[0]
    ni = NonIdealSpec(sigma_offset=2.0, fault_rate=1.0, seed=8)
    x = jnp.asarray(rng.uniform(-0.5, 1.5, (64, 4)), jnp.float32)
    mc = nonideal.mc_operands(spec, ni, mask, samples=4)
    lb, ub = np.asarray(mc[0]), np.asarray(mc[1])
    u = (np.asarray(x)[None] - np.asarray(mc[3])[:, None, :]) \
        * np.asarray(mc[4])[:, None, :]
    hits = ((u[..., None] >= lb[:, None, :, :])
            & (u[..., None] < ub[:, None, :, :])).sum(-1)
    assert np.all(hits == 1), "intervals must partition the input line"
    out = np.asarray(ref.mc_adc_eval_ref(x, *mc))
    ladder = np.asarray(nonideal.level_value_rows(spec, 4))
    for c in range(4):
        assert np.all(np.isin(out[..., c], ladder[c]))


# ------------------------------------------- robustness-aware co-search
NI = NonIdealSpec(sigma_offset=0.6, sigma_range=0.02, fault_rate=0.05,
                  seed=9)
CFG = search.SearchConfig(bits=2, pop_size=6, generations=1,
                          train_steps=30, nonideal=NI, mc_samples=5)


def test_search_config_robustness_fields():
    assert CFG.wants_robustness and CFG.n_objectives == 3
    base = search.SearchConfig(bits=2)
    assert not base.wants_robustness and base.n_objectives == 2
    # nonideal without samples (or vice versa) stays 2-objective
    assert not search.SearchConfig(bits=2, nonideal=NI).wants_robustness
    assert not search.SearchConfig(bits=2, mc_samples=8).wants_robustness
    hash(CFG)                                    # static-jit-arg safe


def test_three_objective_engines_agree():
    rng = np.random.default_rng(0)
    genomes = (rng.random((4, search.genome_len(7, 2))) < 0.5
               ).astype(np.uint8)
    fb = search.evaluate_population(genomes, SEEDS, SIZES, CFG)
    assert fb.shape == (4, 3)
    fs = search.evaluate_population_sharded(genomes, SEEDS, SIZES, CFG)
    np.testing.assert_array_equal(fb, fs)
    fr = search.evaluate_population_reference(genomes, SEEDS, SIZES, CFG)
    # the per-individual reference path is a semantic oracle: identical
    # ideal columns, robustness equal to f32 reduction tolerance
    np.testing.assert_array_equal(fb[:, :2], fr[:, :2])
    np.testing.assert_allclose(fb[:, 2], fr[:, 2], atol=1e-6)


def test_three_objective_front_reproduced_by_evaluate_robustness(tmp_path):
    """Acceptance contract: the searched front's robustness column is
    reproduced bit-for-bit by evaluate_robustness on the exported designs
    from the same NonIdealSpec (same seed -> same draws), for both
    objective kinds; and the ideal-limit robustness equals the exported
    accuracy bit-for-bit."""
    for kind, col in (("expected", "expected_drop"),
                      ("worst", "worst_case_error")):
        cfg = search.SearchConfig(bits=2, pop_size=6, generations=1,
                                  train_steps=30, nonideal=NI,
                                  mc_samples=5, robust_objective=kind)
        pg, pf, _, trained = search.run_search(SEEDS, SIZES, cfg,
                                               return_trained=True)
        assert pf.shape[1] == 3
        designs = deploy.export_front(pg, SEEDS, SIZES, cfg,
                                      trained=trained)
        rep = deploy.evaluate_robustness(designs, NI, SEEDS["x_test"],
                                         SEEDS["y_test"],
                                         samples=cfg.mc_samples)
        got = np.array([d[col] for d in rep["designs"]])
        np.testing.assert_array_equal(pf[:, 2], got)
    # ideal limit: zero spec reproduces the exported accuracy exactly
    rep0 = deploy.evaluate_robustness(designs, NonIdealSpec(),
                                      SEEDS["x_test"], SEEDS["y_test"],
                                      samples=3)
    accs = np.array([d.accuracy for d in designs])
    for key in ("mean_accuracy", "worst_accuracy"):
        np.testing.assert_array_equal(
            np.array([d[key] for d in rep0["designs"]]), accs)
    assert all(d["expected_drop"] == 0.0 for d in rep0["designs"])
    assert all(v == 1.0 for d in rep0["designs"]
               for v in d["yield"].values())
    # the report persists alongside the front and round-trips
    deploy.save_robustness(tmp_path, rep0)
    assert deploy.load_robustness(tmp_path)["designs"][0][
        "mean_accuracy"] == rep0["designs"][0]["mean_accuracy"]


def test_three_objective_search_checkpoint_roundtrip(tmp_path):
    """The (P, 3) fitness matrix survives the per-generation checkpoint
    (restore_search_state width comes from the config)."""
    from repro.checkpoint.manager import CheckpointManager
    cfg = search.SearchConfig(bits=2, pop_size=4, generations=1,
                              train_steps=10, nonideal=NI, mc_samples=2)
    ckpt = CheckpointManager(tmp_path / "ck", keep=2)
    pg, pf, _ = search.run_search(SEEDS, SIZES, cfg, ckpt=ckpt)
    step = ckpt.latest_step()
    state = search.restore_search_state(
        ckpt, step, cfg.pop_size, search.genome_len(SIZES[0], cfg.bits),
        n_obj=cfg.n_objectives)
    assert state.fit.shape == (cfg.pop_size, 3)


def test_robustness_degrades_with_sigma():
    """Deterministic sanity: under the fixed draw stream, more comparator
    offset can only hurt the mean served accuracy of a real front."""
    cfg = search.SearchConfig(bits=2, pop_size=4, generations=0,
                              train_steps=20)
    pg, _, _, trained = search.run_search(SEEDS, SIZES, cfg,
                                          return_trained=True)
    designs = deploy.export_front(pg, SEEDS, SIZES, cfg, trained=trained)
    curve = deploy.robustness_curve(designs, SEEDS["x_test"],
                                    SEEDS["y_test"], [0.0, 1.0, 3.0],
                                    samples=6)
    means = np.array(curve["mean_accuracy"]).mean(axis=1)
    assert means[0] >= means[1] >= means[2] - 1e-9
    exported = np.array([d.accuracy for d in designs])
    np.testing.assert_array_equal(
        np.array([d["mean_accuracy"]
                  for d in curve["points"][0]["designs"]]), exported)


# ------------------------------------------------------------- api facade
def test_api_robustness_facade(tmp_path):
    from repro import api
    front = api.search(api.AdcSpec(bits=2), SEEDS, SIZES, pop_size=4,
                       generations=0, train_steps=20)
    bank = api.deploy(front)
    ni = api.NonIdealSpec(sigma_offset=0.7, fault_rate=0.1, seed=1)
    rep = api.evaluate_robustness(bank, ni, SEEDS["x_test"],
                                  SEEDS["y_test"], samples=4)
    assert rep["num_designs"] == len(bank)
    assert len(rep["designs"][0]["instance_accuracies"]) == 4
    rep_m = bank.evaluate_robustness(ni, SEEDS["x_test"], SEEDS["y_test"],
                                     samples=4)
    assert rep_m["designs"][0]["mean_accuracy"] == \
        rep["designs"][0]["mean_accuracy"]
    curve = api.robustness_curve(bank, SEEDS["x_test"], SEEDS["y_test"],
                                 [0.0, 0.5], samples=3)
    assert len(curve["points"]) == 2


def test_nonideal_bank_fn_reproduces_report_instance():
    """The sampled-instance serving bank, given the report's stream size,
    serves exactly the instance evaluate_robustness listed (JAX PRNG
    bits depend on the drawn array size, so instance k only exists
    relative to its S-sample stream)."""
    cfg = search.SearchConfig(bits=2, pop_size=4, generations=0,
                              train_steps=20)
    pg, _, _, trained = search.run_search(SEEDS, SIZES, cfg,
                                          return_trained=True)
    designs = deploy.export_front(pg, SEEDS, SIZES, cfg, trained=trained)
    ni = NonIdealSpec(sigma_offset=1.0, fault_rate=0.1, seed=4)
    S, k = 6, 3
    rep = deploy.evaluate_robustness(designs, ni, SEEDS["x_test"],
                                     SEEDS["y_test"], samples=S)
    fn = deploy.make_nonideal_bank_fn(designs, ni, instance=k, samples=S)
    logits = np.asarray(fn(jnp.asarray(SEEDS["x_test"], jnp.float32)))
    served = deploy.mean_acc(
        np.argmax(logits, -1) == np.asarray(SEEDS["y_test"])[None, :])
    want = np.array([d["instance_accuracies"][k] for d in rep["designs"]])
    np.testing.assert_array_equal(served.astype(np.float64), want)
    with pytest.raises(ValueError, match="instance"):
        deploy.make_nonideal_bank_fn(designs, ni, instance=S, samples=S)


def test_nonideal_serving_driver_smoke(capsys):
    """launch/serve_classifier --smoke with a sampled non-ideal instance:
    runs end-to-end and reports degradation instead of asserting the
    ideal parity contract."""
    from repro.launch import serve_classifier as sc
    rep = sc.main(["--smoke", "--nonideal-sigma", "0.8",
                   "--fault-rate", "0.05"])
    assert "nonideal" in rep and len(rep["served_accuracies"]) >= 1
    out = capsys.readouterr().out
    assert "non-ideal instance" in out
    # the sampled-instance bank serves logits of the right shape and the
    # degradation is measured against the exported accuracies
    assert "exported=" in out and "drop" in out
