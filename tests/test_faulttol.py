"""Fault-tolerance subsystem tests (DESIGN.md §15, arXiv:2602.10790):
majority-vote draw folding, redundancy gene decode, the yield-first
search path across engines, the deploy-side bit-for-bit yield
reproduction, per-instance calibration (ideal limit + calibrated
serving), and the serving engine's calibrate-on-recovery."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import deploy, search
from repro.core.nonideal import NonIdealSpec
from repro.data import tabular
from repro.faulttol import (FaultTolSpec, RedundantDraws, decode_genes,
                            draw_redundant, effective_draws)

SIZES = (7, 4, 3)


def _ft_config(**kw):
    base = dict(bits=2, pop_size=6, generations=1, train_steps=20, seed=0,
                nonideal=NonIdealSpec(sigma_offset=0.5, sigma_range=0.02,
                                      fault_rate=0.1, seed=0),
                mc_samples=4, robust_objective="yield", yield_margin=0.01,
                faulttol=FaultTolSpec(max_spares=2))
    base.update(kw)
    return search.SearchConfig(**base)


# ---------------------------------------------------------------- spec
def test_faulttol_spec_contract():
    ft = FaultTolSpec(max_spares=2)
    assert ft.spare_bits == 2
    assert ft.gene_bits(7) == 7 + 14 + 1
    assert FaultTolSpec(tmr=False, max_spares=0).gene_bits(7) == 1
    assert FaultTolSpec.from_meta(ft.to_meta()) == ft
    assert ft.describe() == "tmr+spares<=2+calibrate"
    hash(ft)                                   # static-jit-arg safe
    with pytest.raises(ValueError):
        FaultTolSpec(max_spares=-1)
    with pytest.raises(ValueError):
        FaultTolSpec(tmr=False, max_spares=0, calibrate=False)


def test_search_config_rejects_faulttol_without_robustness():
    with pytest.raises(ValueError):
        search.SearchConfig(bits=2, pop_size=4,
                            faulttol=FaultTolSpec(max_spares=1))


# ------------------------------------------------------- majority vote
def _one_node_draws(eps3, fault3, hi3):
    """RedundantDraws with S=1, C=1, one tree node (bits=1)."""
    shape = (1, 1, 1, 3)
    return RedundantDraws(
        eps=jnp.asarray(np.reshape(eps3, shape), jnp.float32),
        fault_u=jnp.asarray(np.reshape(fault3, shape), jnp.float32),
        stuck_hi=jnp.asarray(np.reshape(hi3, shape), bool),
        drift=jnp.zeros((1, 1, 2), jnp.float32))


def _vote(eps3, fault3, hi3, tmr=1):
    ni = NonIdealSpec(fault_rate=0.5, seed=0)
    d = effective_draws(_one_node_draws(eps3, fault3, hi3),
                        jnp.asarray([tmr], jnp.int32), ni)
    return (float(d.eps[0, 0, 0]), float(d.fault_u[0, 0, 0]),
            bool(d.stuck_hi[0, 0, 0]))


def test_vote_semantics():
    healthy, stuck = 0.9, 0.1            # vs fault_rate = 0.5
    # all healthy -> median threshold, vote not faulted
    eps, fu, _ = _vote([3.0, -1.0, 0.5], [healthy] * 3, [0, 0, 0])
    assert eps == 0.5 and fu == 1.0
    # one stuck-at-1 -> min of the two healthy replicas
    eps, fu, _ = _vote([3.0, -1.0, 0.5], [stuck, healthy, healthy],
                       [1, 0, 0])
    assert eps == -1.0 and fu == 1.0
    # one stuck-at-0 -> max of the two healthy replicas
    eps, fu, _ = _vote([3.0, -1.0, 0.5], [stuck, healthy, healthy],
                       [0, 1, 1])
    assert eps == 0.5 and fu == 1.0
    # one high + one low -> the lone healthy replica decides
    eps, fu, _ = _vote([3.0, -1.0, 0.5], [stuck, stuck, healthy],
                       [1, 0, 0])
    assert eps == 0.5 and fu == 1.0
    # two stuck the same way -> the vote itself is stuck that way
    _, fu, sh = _vote([3.0, -1.0, 0.5], [stuck, stuck, healthy], [1, 1, 0])
    assert fu == 0.0 and sh is True
    _, fu, sh = _vote([3.0, -1.0, 0.5], [stuck, stuck, stuck], [0, 0, 1])
    assert fu == 0.0 and sh is False
    # TMR gene off -> replica 0 passes through verbatim
    eps, fu, sh = _vote([3.0, -1.0, 0.5], [stuck, healthy, healthy],
                       [1, 0, 0], tmr=0)
    assert eps == 3.0 and fu == np.float32(stuck) and sh is True


def test_effective_draws_population_broadcast():
    ni = NonIdealSpec(sigma_offset=0.5, fault_rate=0.2, seed=3)
    rd = draw_redundant(2, 3, samples=5, nonideal=ni)
    tmr = jnp.asarray([[1, 0, 1], [0, 0, 0]], jnp.int32)     # (P, C)
    d = effective_draws(rd, tmr, ni)
    assert d.eps.shape == (2, 5, 3, 3)
    # the all-zero-TMR row IS the plain replica-0 stream
    np.testing.assert_array_equal(np.asarray(d.eps[1]),
                                  np.asarray(rd.eps[..., 0]))
    np.testing.assert_array_equal(np.asarray(d.eps[0, :, 1]),
                                  np.asarray(rd.eps[:, 1, :, 0]))


# ------------------------------------------------------------- decode
def test_decode_genes_lsb_first_and_clip():
    ft = FaultTolSpec(max_spares=2)                # spare_bits = 2
    c = 2
    genes = np.zeros(ft.gene_bits(c), np.uint8)
    genes[0] = 1                                   # tmr channel 0
    genes[2:4] = [1, 0]                            # ch0 spares: LSB=1 -> 1
    genes[4:6] = [1, 1]                            # ch1 spares: 3 -> clip 2
    genes[6] = 1                                   # calibrate
    tmr, spares, cal = decode_genes(genes, c, ft)
    np.testing.assert_array_equal(np.asarray(tmr), [1, 0])
    np.testing.assert_array_equal(np.asarray(spares), [1, 2])
    assert int(cal) == 1
    with pytest.raises(ValueError):
        decode_genes(genes[:-1], c, ft)


def test_genome_len_and_population_decode():
    ft = FaultTolSpec(max_spares=2)
    cfg = _ft_config()
    G = search.genome_len(SIZES[0], cfg.bits, faulttol=ft)
    assert G == SIZES[0] * 4 + search.DP_BITS + ft.gene_bits(SIZES[0])
    rng = np.random.default_rng(0)
    genomes = (rng.random((5, G)) < 0.5).astype(np.uint8)
    masks, dps, tmr, spares, cal = search.decode_population_faulttol(
        jnp.asarray(genomes), SIZES[0], cfg.bits, cfg.min_levels, ft)
    assert masks.shape == (5, SIZES[0], 4)
    assert tmr.shape == spares.shape == (5, SIZES[0])
    assert cal.shape == (5,)
    # spare levels are already applied: kept count >= plain decode's
    plain, _ = search.decode_population(jnp.asarray(genomes), SIZES[0],
                                        cfg.bits, cfg.min_levels)
    assert (np.asarray(masks).sum((1, 2)) >= np.asarray(plain).sum((1, 2))).all()


# -------------------------------------------------- engines + fitness
def test_engines_agree_on_faulttol_fitness():
    data = tabular.make_dataset("seeds")
    cfg = _ft_config()
    G = search.genome_len(SIZES[0], cfg.bits, faulttol=cfg.faulttol)
    rng = np.random.default_rng(1)
    genomes = (rng.random((4, G)) < 0.5).astype(np.uint8)
    fb = np.asarray(search.evaluate_population(genomes, data, SIZES, cfg))
    fr = np.asarray(search.evaluate_population_reference(genomes, data,
                                                         SIZES, cfg))
    assert fb.shape == (4, 3)
    # areas are exact integers; accuracy / yield columns may differ by
    # float32 reduction order between the vmapped and per-individual paths
    np.testing.assert_array_equal(fb[:, 1], fr[:, 1])
    np.testing.assert_allclose(fb[:, [0, 2]], fr[:, [0, 2]], atol=1e-6)


def test_search_export_reproduces_yield_bitforbit(tmp_path):
    """The §15 acceptance contract: a deployed fault-tolerant front's
    measured yield reproduces the searched fitness column bit-for-bit
    from the same NonIdealSpec — through save/load as well."""
    data = tabular.make_dataset("seeds")
    cfg = _ft_config()
    pg, pf, _, trained = search.run_search(data, SIZES, cfg,
                                           return_trained=True)
    pf = np.asarray(pf)
    designs = deploy.export_front(pg, data, SIZES, cfg, trained=trained)
    for d, g in zip(designs, np.asarray(pg, np.uint8)):
        _, _, tmr_g, _, cal_g = search.decode_genome_faulttol(
            jnp.asarray(g), SIZES[0], cfg.bits, cfg.min_levels,
            cfg.faulttol)
        np.testing.assert_array_equal(d.tmr, np.asarray(tmr_g))
        assert d.calibrated == bool(int(cal_g))
    deploy.save_front(tmp_path / "f", designs)
    loaded = deploy.load_front(tmp_path / "f")
    for a, b in zip(designs, loaded):
        np.testing.assert_array_equal(a.tmr, b.tmr)
        assert a.calibrated == b.calibrated
    rep = deploy.evaluate_robustness(loaded, cfg.nonideal, data["x_test"],
                                     data["y_test"],
                                     samples=cfg.mc_samples,
                                     yield_margins=(cfg.yield_margin,))
    got = np.array([1.0 - r["yield"][f"{cfg.yield_margin:g}"]
                    for r in rep["designs"]])
    np.testing.assert_array_equal(got, pf[:, 2])


# --------------------------------------------------------- calibration
def _small_front():
    data = tabular.make_dataset("seeds")
    cfg = search.SearchConfig(bits=2, pop_size=6, generations=1,
                              train_steps=20, seed=0)
    pg, _, _ = search.run_search(data, SIZES, cfg)
    return deploy.export_front(pg, data, SIZES, cfg), data


def test_calibrate_front_ideal_limit():
    """Zero-spec calibration is the identity on unpruned channels (code
    midpoints ARE the nominal reconstruction); pruned channels re-bake
    merged-region codes to finite in-range best-constant values."""
    designs, _ = _small_front()
    cal = deploy.calibrate_front(designs, NonIdealSpec())
    for d0, dc in zip(designs, cal):
        assert dc.calibrated and not d0.calibrated
        np.testing.assert_array_equal(np.asarray(dc.vmin),
                                      np.asarray(d0.vmin))
        np.testing.assert_array_equal(np.asarray(dc.vmax),
                                      np.asarray(d0.vmax))
        t0, tc = np.asarray(d0.table), np.asarray(dc.table)
        assert np.isfinite(tc).all()
        full = np.asarray(d0.mask).sum(-1) == d0.mask.shape[-1]
        np.testing.assert_array_equal(tc[full], t0[full])
        lo = np.broadcast_to(np.atleast_1d(np.asarray(d0.vmin, np.float32)),
                             (tc.shape[0],))
        hi = np.broadcast_to(np.atleast_1d(np.asarray(d0.vmax, np.float32)),
                             (tc.shape[0],))
        assert (tc >= lo[:, None] - 1e-6).all()
        assert (tc <= hi[:, None] + 1e-6).all()


def test_calibrated_bank_matches_calibrate_front():
    """Serving a measured instance through make_calibrated_bank_fn (the
    mc_eval_cal_population kernel path) and through the re-baked
    ideal-kernel front (calibrate_front + make_bank_fn) agree — two
    routes to the same calibrated hardware. With zero comparator offset
    the measured leaf boundaries stay on the integer code grid, so the
    re-baked table's code walk IS the measured interval walk (with
    offsets the routes legitimately diverge near moved boundaries —
    calibrate_front's documented residual)."""
    designs, data = _small_front()
    ni = NonIdealSpec(sigma_range=0.03, fault_rate=0.1, seed=2)
    x = jnp.asarray(data["x_test"], jnp.float32)
    y = np.asarray(data["y_test"])
    fn = deploy.make_calibrated_bank_fn(designs, ni, instance=1, samples=3)
    acc_kernel = deploy.mean_acc(
        np.argmax(np.asarray(fn(x)), -1) == y[None, :])
    cal = deploy.calibrate_front(designs, ni, instance=1, samples=3)
    acc_rebaked = deploy.served_accuracies(cal, data["x_test"], y)
    np.testing.assert_allclose(acc_kernel, acc_rebaked, atol=1e-6)


def test_serving_engine_calibrate_on_recovery():
    """A tenant on measured non-ideal hardware serves calibrated tables
    and re-calibrates against a fresh instance after a device loss."""
    import jax

    from repro.launch import loadgen, serving_engine
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for a survivable device loss")
    designs, data = _small_front()
    ni = NonIdealSpec(sigma_offset=0.3, fault_rate=0.05, seed=0)
    tenant = serving_engine.Tenant(
        name="seeds", designs=designs,
        parity_data=(data["x_test"], data["y_test"]), nonideal=ni)
    wl = loadgen.make_workload(data["x_test"], 12, tenant="seeds",
                               rate_rps=400.0, request_size=4,
                               deadline_ms=5000.0, seed=0)
    rep = serving_engine.run_workload(
        [tenant], wl, target_latency_ms=25.0, max_batch=64,
        inject_device_failure=lambda b: 0 if b == 1 else None)
    assert rep["recoveries"] == 1
    assert rep["calibrations"]["seeds"] == 2     # startup + recovery
    slo = rep["tenants"]["seeds"]
    assert slo["completed"] + slo["shed"] == 12 and slo["rejected"] == 0
