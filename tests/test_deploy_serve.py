"""Deployment subsystem (DESIGN.md §8): search -> artifact -> fused
serving round trip. The acceptance contract: for every individual on a
searched Pareto front, the exported DeployedClassifier served through the
fused bank kernel reproduces the search-time test accuracy *bit-for-bit*
vs the jnp oracle — for MLP and SVM targets, on 1 device and on a forced
2x1 CPU device mesh."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import area, deploy, search
from repro.data import tabular

REPO = Path(__file__).resolve().parents[1]
SIZES = (7, 4, 3)


def _data():
    return tabular.make_dataset("seeds")


def _searched_front(model, **overrides):
    kw = dict(bits=2, pop_size=6, generations=1, train_steps=30,
              model=model)
    kw.update(overrides)
    cfg = search.SearchConfig(**kw)
    data = _data()
    pg, pf, _ = search.run_search(data, SIZES, cfg)
    return data, cfg, pg, pf


# ------------------------------------------------------- search -> artifact
@pytest.mark.parametrize("model", ["mlp", "svm"])
def test_train_pareto_front_reproduces_search_fitness(model):
    """Re-training the front genomes is a pure function of (genome, data,
    cfg): the returned accuracies equal the search-time fitness column
    bit-for-bit, whatever generation/population originally scored them."""
    data, cfg, pg, pf = _searched_front(model)
    accs, params, masks, dps = search.train_pareto_front(pg, data, SIZES,
                                                         cfg)
    np.testing.assert_array_equal(accs, 1.0 - pf[:, 0])
    assert masks.shape == (len(pg), SIZES[0], 2 ** cfg.bits)
    assert dps.shape == (len(pg),)


def test_run_search_return_trained_matches_front():
    data = _data()
    cfg = search.SearchConfig(bits=2, pop_size=6, generations=1,
                              train_steps=20)
    pg, pf, _, trained = search.run_search(data, SIZES, cfg,
                                           return_trained=True)
    accs = trained[0]
    np.testing.assert_array_equal(accs, 1.0 - pf[:, 0])
    assert len(accs) == len(pg)
    # the tuple feeds export_front directly (no second QAT) and yields
    # the same artifacts as the re-training path
    a = deploy.export_front(pg, data, SIZES, cfg, trained=trained)
    b = deploy.export_front(pg, data, SIZES, cfg)
    for x, y in zip(a, b):
        assert x.accuracy == y.accuracy and x.area_tc == y.area_tc
        np.testing.assert_array_equal(x.table, y.table)
        for wx, wy in zip(x.weights, y.weights):
            np.testing.assert_array_equal(wx, wy)
    if len(pg) > 1:
        with pytest.raises(ValueError):
            deploy.export_front(pg[:1], data, SIZES, cfg, trained=trained)


@pytest.mark.parametrize("model", ["mlp", "svm"])
def test_export_front_bakes_tables_weights_and_area(model):
    from repro.core import qat
    from repro.kernels import ref
    data, cfg, pg, pf = _searched_front(model)
    designs = deploy.export_front(pg, data, SIZES, cfg)
    assert len(designs) == len(pg)
    for d in designs:
        assert d.kind == model and d.bits == cfg.bits
        # the baked table is the mask's value table
        np.testing.assert_array_equal(
            d.table, np.asarray(ref.value_table(d.mask, cfg.bits),
                                np.float32))
        # the area report is the exact transistor count of the mask
        assert d.area_tc == area.system_tc(d.mask, cfg.design)
        # weights are already projected: re-quantizing is a no-op
        w = d.weights[0]
        np.testing.assert_array_equal(
            w, np.asarray(qat.quantize_po2(w, d.dp, cfg.weight_bits)))


# ------------------------------------------------------- round-trip parity
@pytest.mark.parametrize("model", ["mlp", "svm"])
def test_served_front_reproduces_search_accuracy_bitforbit(model):
    """Acceptance (1 device): exported accuracy == search fitness ==
    accuracy served through the bank oracle == through the interpret-mode
    fused bank kernel, exactly."""
    data, cfg, pg, pf = _searched_front(model)
    designs = deploy.export_front(pg, data, SIZES, cfg)
    exported = np.array([d.accuracy for d in designs])
    np.testing.assert_array_equal(exported, 1.0 - pf[:, 0])
    oracle = deploy.served_accuracies(designs, data["x_test"],
                                      data["y_test"])
    np.testing.assert_array_equal(oracle, exported)
    kernel = deploy.served_accuracies(designs, data["x_test"],
                                      data["y_test"], interpret=True)
    np.testing.assert_array_equal(kernel, exported)
    # single-design path (size-1 bank) agrees too
    one = designs[0].accuracy_on(data["x_test"], data["y_test"])
    assert one == exported[0]


def test_round_trip_parity_nondefault_weight_bits():
    """Regression: the fitness must be measured on the same quantized
    forward the artifact bakes — with weight_bits=4 the search-time
    accuracy, the export, and the served bank still agree bit-for-bit
    (the QAT loss *and* accuracy thread cfg.weight_bits through)."""
    data, cfg, pg, pf = _searched_front("mlp", weight_bits=4)
    designs = deploy.export_front(pg, data, SIZES, cfg)
    exported = np.array([d.accuracy for d in designs])
    np.testing.assert_array_equal(exported, 1.0 - pf[:, 0])
    served = deploy.served_accuracies(designs, data["x_test"],
                                      data["y_test"])
    np.testing.assert_array_equal(served, exported)


def test_serve_bank_rows_match_single_design_logits():
    data, cfg, pg, pf = _searched_front("mlp")
    designs = deploy.export_front(pg, data, SIZES, cfg)
    x = data["x_test"][:40]
    bank = deploy.serve_bank(designs, x)
    for i, d in enumerate(designs):
        np.testing.assert_array_equal(bank[i], d.logits(x))


@pytest.mark.parametrize("model", ["mlp", "svm"])
def test_save_load_round_trip(tmp_path, model):
    data, cfg, pg, pf = _searched_front(model)
    designs = deploy.export_front(pg, data, SIZES, cfg)
    deploy.save_front(tmp_path / "front", designs,
                      extra_meta={"dataset": "seeds"})
    back = deploy.load_front(tmp_path / "front")
    assert len(back) == len(designs)
    for a, b in zip(designs, back):
        assert (a.kind, a.bits, a.mode, a.vmin, a.vmax) == \
               (b.kind, b.bits, b.mode, b.vmin, b.vmax)
        assert a.dp == b.dp and a.area_tc == b.area_tc
        assert a.accuracy == b.accuracy
        np.testing.assert_array_equal(a.mask, b.mask)
        np.testing.assert_array_equal(a.table, b.table)
        for wa, wb in zip(a.weights, b.weights):
            np.testing.assert_array_equal(wa, wb)
    served = deploy.served_accuracies(back, data["x_test"], data["y_test"])
    np.testing.assert_array_equal(served,
                                  np.array([d.accuracy for d in designs]))


def test_save_front_rejects_empty_and_mixed(tmp_path):
    data, cfg, pg, pf = _searched_front("mlp")
    designs = deploy.export_front(pg, data, SIZES, cfg)
    with pytest.raises(ValueError):
        deploy.save_front(tmp_path / "e", [])
    import dataclasses
    other = dataclasses.replace(designs[0], bits=3)
    with pytest.raises(ValueError):
        deploy.save_front(tmp_path / "m", [designs[0], other])


# --------------------------------------------------- serving driver (queue)
def test_continuous_batching_driver_routes_responses():
    """Microbatches span request boundaries (continuous batching); every
    response must still carry exactly its own rows' predictions for all D
    designs, whatever the batch/request-size relation."""
    from repro.launch import serve_classifier as sc
    data, cfg, pg, pf = _searched_front("mlp")
    designs = deploy.export_front(pg, data, SIZES, cfg)
    # request sizes straddle the batch size: 5 rows/request, batch 8
    requests = sc.make_request_stream(data["x_test"], 7, 5, seed=3)
    rep = sc.serve(designs, requests, batch=8)
    assert rep["requests"] == 7 and rep["samples"] == 35
    assert rep["batches"] == int(np.ceil(35 / 8))
    for rid, x in requests:
        want = np.argmax(deploy.serve_bank(designs, x), axis=-1)
        np.testing.assert_array_equal(rep["responses"][rid], want)


def test_export_front_cli_flag(tmp_path):
    """launch.train --adc-search --export-front writes a loadable front
    whose served accuracies match the printed Pareto points, with
    dataset provenance the serving driver validates against."""
    from repro.launch import serve_classifier as sc
    from repro.launch import train as train_cli
    pf = train_cli.main([
        "--adc-search", "--dataset", "seeds", "--bits", "2", "--pop", "6",
        "--generations", "1", "--train-steps", "20",
        "--ckpt-dir", str(tmp_path), "--export-front"])
    designs = deploy.load_front(tmp_path / "front")
    assert len(designs) == len(pf)
    data = _data()
    served = deploy.served_accuracies(designs, data["x_test"],
                                      data["y_test"])
    np.testing.assert_array_equal(np.sort(served),
                                  np.sort(1.0 - pf[:, 0]))
    meta = deploy.front_meta(tmp_path / "front")
    assert meta["dataset"] == "seeds"
    assert meta["num_designs"] == len(designs)
    # serving the front against a different dataset is rejected up front
    # (wrong-domain traffic), not deep in a kernel shape error
    with pytest.raises(SystemExit):
        sc.main(["--front-dir", str(tmp_path / "front"),
                 "--dataset", "mammographic", "--requests", "2"])


# ------------------------------------------------------- forced 2x1 mesh
_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np, jax
    from repro.core import deploy, search
    from repro.data import tabular

    assert len(jax.devices()) == 2, jax.devices()
    mesh = jax.make_mesh((2, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    data = tabular.make_dataset("seeds")
    sizes = (7, 4, 3)
    for model in ("mlp", "svm"):
        cfg = search.SearchConfig(bits=2, pop_size=6, generations=1,
                                  train_steps=20, model=model)
        pg, pf, _ = search.run_search(data, sizes, cfg)
        designs = deploy.export_front(pg, data, sizes, cfg)
        exported = np.array([d.accuracy for d in designs])
        np.testing.assert_array_equal(exported, 1.0 - pf[:, 0])
        # D designs shard D/2 per device when divisible; otherwise the
        # fallback serves unsharded — results identical either way
        logits_1 = deploy.serve_bank(designs, data["x_test"])
        logits_2 = deploy.serve_bank(designs, data["x_test"], mesh=mesh)
        np.testing.assert_array_equal(logits_1, logits_2)
        served = deploy.served_accuracies(designs, data["x_test"],
                                          data["y_test"], mesh=mesh)
        np.testing.assert_array_equal(served, exported)
    print("OK-SERVE-2DEV")
""")


def test_served_parity_on_forced_two_device_mesh():
    """Acceptance (2x1 CPU mesh): the design bank sharded over two devices
    reproduces the exported (== search-time) accuracies bit-for-bit. jax
    locks the device count at init, so this runs in a subprocess with
    XLA_FLAGS set (same pattern as test_search_sharded)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK-SERVE-2DEV" in out.stdout
