#!/usr/bin/env python3
"""Chip smoke test: the paper's main path once on a TPU, end to end.

Runs through the entry points a user calls (``repro.api``,
``core/search``, ``core/deploy``, ``launch/serving_engine``) on data made
from ``--seed``, at the widest tabular spec (cardio: 21 channels, hidden
5, 3 classes) and the paper's search defaults (4-bit ADCs, population
32, 300 QAT steps):

  search      3 generations of the batched NSGA-II x vmapped-QAT engine
  export      the final front frozen into deployable designs; search
              fitness == exported accuracy, re-checked by
              ``deploy.verify_front_parity``
  serve       the whole test set as >= 64 requests through the async
              serving engine; served accuracy == exported accuracy
  robustness  ``evaluate_robustness`` over the front at S = 128, and its
              zero-sigma limit == exported accuracy
  entries     every dispatch entry compiled (``interpret=False``) at
              C = 21, bits = 4, M = 1488 (the cardio training rows),
              P = 32, S = 256, D = 8 against its jnp oracle, bit for bit

``--chips 4`` runs only what exists across chips, each against its
single-device twin: one sharded search generation against the batched
one, and the design-sharded serving bank losing a device mid-stream
against single-device served predictions.

Each phase prints its wall time, and every dispatch entry used prints its
``dispatch.resolve`` record. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. The script exits
non-zero without that line if the device is not a TPU, a main-path entry
resolves to the jnp oracle or to interpret mode, a parity check fails, or
any phase raises.

Usage: python3 chip_smoke.py [--seed N] [--chips {1,4}]
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import logging
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DATASET = "cardio"
BITS, POP, TRAIN_STEPS, GENERATIONS = 4, 32, 300, 3
MC_SAMPLES = 128
REQUEST_ROWS = 8
MIN_REQUESTS = 64
# per-entry check extents besides M (all cardio training rows): the
# search population, an MC instance count that resolves yield@1%, a
# bank of 8 designs
ENTRY_SHAPES = dict(p=32, s=256, d=8)
SHARDED_BANK = 12          # designs: divisible by 4 devices and by 3


class SmokeFailure(RuntimeError):
    """A check of the smoke test did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    print(f"phase {name}: {time.perf_counter() - t0:.3f} s", flush=True)


def entry_workload(name: str, m: int, c: int, bits: int, *, p: int = 1,
                   s: int = 1, d: int = 1, h: int = 0, o: int = 0):
    """The Workload of one dispatch entry at the given extents (only the
    axes the entry has)."""
    from repro.perf.workload import Workload
    kw = {}
    if name.endswith("_population"):
        kw["p"] = p
    if name.startswith("mc_eval"):
        kw["s"] = s
    if name.startswith("classifier_bank"):
        kw["d"] = d
    if name.endswith("_mlp"):
        kw["h"], kw["o"] = h, o
    elif name.endswith("_svm"):
        kw["o"] = o
    return Workload(name, m=m, c=c, bits=bits, **kw)


def report_resolution(name: str, spec, workload, **kw):
    """Print one entry's routing record and return it."""
    from repro.kernels import dispatch
    res = dispatch.resolve(name, spec, workload.c, workload=workload, **kw)
    route = res.path + ("" if res.interpret is None
                        else f"[interpret={res.interpret}]")
    print(f"dispatch {name} -> {route} {json.dumps(res.as_dict())}",
          flush=True)
    return res


def check_compiled(res) -> None:
    """A main-path entry must run as the compiled Mosaic kernel."""
    check(res.path == "kernel" and res.interpret is False,
          f"{res.entry} resolved to {res.path}"
          f"{'' if res.interpret is None else f'[interpret={res.interpret}]'}"
          f" ({res.reason}), not the compiled kernel")


# ---------------------------------------------------------------- one chip
def search_phase(data, sizes, seed: int):
    from repro import api
    front = api.search(api.AdcSpec(bits=BITS), data, sizes=sizes,
                       pop_size=POP, generations=GENERATIONS,
                       train_steps=TRAIN_STEPS, engine="batched", seed=seed)
    check(len(front) >= 1, "empty Pareto front")
    check(np.all(np.isfinite(front.fitness)), "non-finite fitness")
    check(np.all((front.accuracies >= 0) & (front.accuracies <= 1)),
          "accuracy outside [0, 1]")
    print(f"  front: {len(front)} designs, accuracy "
          f"{front.accuracies.tolist()}, area {front.areas.tolist()}",
          flush=True)
    return front


def export_phase(front, data, sizes):
    from repro import api
    from repro.core import deploy
    bank = api.deploy(front)
    exported = np.array([d.accuracy for d in bank.designs])
    # the fitness column is 1 - the f32 accuracy the search measured
    check(np.array_equal(front.fitness[:, 0],
                         1.0 - exported.astype(np.float32)),
          f"search fitness {front.fitness[:, 0]} != 1 - exported "
          f"accuracy {exported}")
    check(deploy.verify_front_parity(bank.designs, front.genomes, data,
                                     sizes, front.config),
          "verify_front_parity: re-trained accuracy != exported")
    return bank, exported


def whole_set_workload(x, tenant: str, seed: int):
    """The whole test set, in order, as REQUEST_ROWS-row requests on a
    seeded bursty arrival trace with deadlines no request can miss."""
    from repro import api
    n_req = -(-len(x) // REQUEST_ROWS)
    wl = api.make_workload(x, n_req, tenant=tenant, rate_rps=400.0,
                           request_size=REQUEST_ROWS, deadline_ms=60_000.0,
                           shape="bursty", seed=seed)
    return [dataclasses.replace(
        r, x=np.asarray(x[r.rid * REQUEST_ROWS:(r.rid + 1) * REQUEST_ROWS],
                        np.float32)) for r in wl]


def served_predictions(rep, wl) -> np.ndarray:
    """(D, M) predicted classes reassembled from the engine's responses."""
    return np.concatenate([rep["responses"][r.rid] for r in wl], axis=1)


def serve_phase(bank, exported, data, seed: int):
    from repro import api
    from repro.core import deploy
    x, y = data["x_test"], data["y_test"]
    wl = whole_set_workload(x, DATASET, seed)
    check(len(wl) >= MIN_REQUESTS, f"only {len(wl)} requests")
    rep = api.serve_stream(bank, wl, parity_data=(x, y))
    slo = rep["tenants"][DATASET]
    check(slo["completed"] == len(wl) and slo["shed"] == 0
          and slo["rejected"] == 0, f"requests lost: {slo}")
    served = deploy.mean_acc(served_predictions(rep, wl) == y[None, :])
    check(np.array_equal(served, exported),
          f"served accuracy {served} != exported {exported}")
    print(f"  {len(wl)} requests in {rep['batches']} batches, p50 "
          f"{slo['p50_ms']:.3f} ms, p99 {slo['p99_ms']:.3f} ms",
          flush=True)


def robustness_phase(bank, exported, data, seed: int):
    from repro import api
    x, y = data["x_test"], data["y_test"]
    ni = api.NonIdealSpec(sigma_offset=0.5, sigma_range=0.01,
                          fault_rate=0.01, seed=seed)
    rep = api.evaluate_robustness(bank, ni, x, y, samples=MC_SAMPLES)
    inst = np.array([d["instance_accuracies"] for d in rep["designs"]])
    check(inst.shape == (len(bank), MC_SAMPLES), f"shape {inst.shape}")
    check(np.all(np.isfinite(inst) & (inst >= 0) & (inst <= 1)),
          "instance accuracy outside [0, 1]")
    ideal = api.evaluate_robustness(bank, api.NonIdealSpec(seed=seed), x, y,
                                    samples=MC_SAMPLES)
    means = np.array([d["mean_accuracy"] for d in ideal["designs"]])
    check(np.array_equal(means, exported),
          f"zero-sigma MC accuracy {means} != exported {exported}")
    print(f"  mean accuracy under {ni.describe()}: "
          f"{[d['mean_accuracy'] for d in rep['designs']]}", flush=True)


def entry_operands(name: str, x, w, seed: int):
    """Registry-order operands (x, tables, *weights) for one entry:
    masks, MC draws and weights from the autotuner's seeded generator,
    with classifier weights po2/fixed-point quantized the way an exported
    design's are, and the real cardio rows as x."""
    import jax.numpy as jnp

    from repro.core import qat
    from repro.perf import autotune
    ops, _ = autotune._tuning_operands(w, seed=seed)
    ops = (jnp.asarray(x),) + tuple(ops[1:])
    if name.startswith(("bespoke", "classifier_bank")):
        dp = 1.0
        n_w = len(ops) - 2
        quant = [qat.quantize_po2 if i % 2 == 0 else qat.quantize_fixed
                 for i in range(n_w)]          # (w, b) pairs in order
        ops = ops[:2] + tuple(q(a, dp, 8) for q, a in zip(quant, ops[2:]))
    return ops


def _compare(got, want):
    """(differing elements, max |diff|, all finite) of two device arrays,
    reduced on the device."""
    import jax.numpy as jnp
    return (jnp.sum(got != want), jnp.max(jnp.abs(got - want)),
            jnp.all(jnp.isfinite(got)))


def entries_phase(x, sizes, seed: int):
    """Every dispatch entry compiled vs its jnp oracle, bit for bit."""
    import jax

    from repro.core.spec import AdcSpec
    from repro.kernels import dispatch
    _compare_jit = jax.jit(_compare)
    spec = AdcSpec(bits=BITS)
    x = np.asarray(x, np.float32)
    for name in dispatch.entries():
        w = entry_workload(name, *x.shape, BITS, h=sizes[1], o=sizes[2],
                           **ENTRY_SHAPES)
        res = report_resolution(name, spec, w, interpret=False)
        check_compiled(res)
        ops = entry_operands(name, x, w, seed)
        oracle = jax.jit(functools.partial(dispatch.get(name).oracle,
                                           spec=spec))
        t0 = time.perf_counter()
        got = dispatch.dispatch(name, *ops, spec=spec, interpret=False)
        want = oracle(*ops)
        check(got.shape == want.shape, f"{name}: shape {got.shape} != "
                                       f"{want.shape}")
        n_diff, max_diff, finite = (float(v) for v in _compare_jit(got, want))
        print(f"  {name}: {got.shape} kernel==oracle "
              f"{'bitwise' if n_diff == 0 else 'NO'} (differing elements "
              f"{n_diff:.0f}, max |diff| {max_diff:.3e}) "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        check(finite, f"{name}: non-finite kernel output")
        check(n_diff == 0, f"{name}: kernel != oracle in {n_diff:.0f} "
                           f"elements")
        del got, want


def main_path_resolutions(data, sizes) -> None:
    """The entries search, serving and robustness dispatch through must
    resolve to the compiled kernel (auto policy, as those paths call)."""
    from repro.core.spec import AdcSpec
    spec = AdcSpec(bits=BITS)
    c, h, o = sizes
    m_train, m_test = len(data["x_train"]), len(data["x_test"])
    for name, m, kw in (
            ("adc_quantize_population", m_train, dict(p=POP)),
            ("classifier_bank_mlp", m_test, dict(d=8, h=h, o=o)),
            ("mc_eval_population", m_test, dict(p=8, s=MC_SAMPLES))):
        check_compiled(report_resolution(
            name, spec, entry_workload(name, m, c, BITS, **kw)))


def run_one_chip(seed: int) -> None:
    from repro.data import tabular
    spec = tabular.SPECS[DATASET]
    sizes = (spec.features, spec.hidden, spec.classes)
    data = tabular.make_dataset(DATASET, seed=seed)
    main_path_resolutions(data, sizes)
    with phase("search"):
        front = search_phase(data, sizes, seed)
    with phase("export"):
        bank, exported = export_phase(front, data, sizes)
    with phase("serve"):
        serve_phase(bank, exported, data, seed)
    with phase("robustness"):
        robustness_phase(bank, exported, data, seed)
    with phase("entries"):
        entries_phase(data["x_train"], sizes, seed)


# -------------------------------------------------------------- four chips
def sharded_generation_phase(data, sizes, cfg, seed: int):
    """One sharded generation == the batched generation, with the
    population really split over every device."""
    import jax
    import jax.numpy as jnp

    from repro.core import search
    from repro.distributed import sharding as sharding_lib
    n_dev = len(jax.devices())
    rng = np.random.default_rng(seed)
    genomes = (rng.random((POP, search.genome_len(sizes[0], BITS)))
               < 0.5).astype(np.uint8)
    mesh = search.default_search_mesh()
    check(mesh.devices.size == n_dev, f"search mesh {mesh.devices.shape}")
    axes = sharding_lib.population_axes(mesh, POP)
    check(axes is not None, "population does not divide the mesh")
    report_resolution("adc_quantize_population", cfg.adc_spec,
                      entry_workload("adc_quantize_population",
                                     len(data["x_train"]), sizes[0], BITS,
                                     p=POP // n_dev), sharded=True)
    fb = search.evaluate_population(genomes, data, sizes, cfg)
    fs = search.evaluate_population_sharded(genomes, data, sizes, cfg,
                                            mesh=mesh)
    check(np.array_equal(fb, fs), f"sharded fitness != batched: "
                                  f"{np.argwhere(fb != fs).tolist()}")
    # placement: the generation's output lives on every device, P/n each
    params0, opt0 = search._stacked_init(POP, sizes, cfg)
    dev_data = {k: jnp.asarray(v) for k, v in data.items()}
    out = search._sharded_train_and_score(mesh, axes, tuple(sizes), cfg)(
        jnp.asarray(genomes), params0, opt0, dev_data,
        search.search_draws(cfg, sizes[0]))
    arr = out["correct"]
    check(len(arr.sharding.device_set) == n_dev,
          f"generation output on {arr.sharding.device_set}")
    check({s.data.shape[0] for s in arr.addressable_shards}
          == {POP // n_dev}, "uneven population shards")
    print(f"  {POP} individuals over {n_dev} devices "
          f"({arr.sharding}); fitness == batched", flush=True)
    return genomes


def sharded_serving_phase(genomes, data, sizes, cfg, seed: int):
    """The design-sharded bank loses device 0 mid-stream, re-shards over
    the survivors, and serves exactly the single-device predictions."""
    import jax

    from repro.core import deploy
    from repro.distributed import elastic
    from repro.launch import serving_engine
    n_dev = len(jax.devices())
    designs = deploy.export_front(genomes[:SHARDED_BANK], data, sizes, cfg)
    exported = np.array([d.accuracy for d in designs])
    x, y = data["x_test"], data["y_test"]
    report_resolution("classifier_bank_mlp", designs[0].spec,
                      entry_workload("classifier_bank_mlp", len(x),
                                     sizes[0], BITS,
                                     d=SHARDED_BANK // n_dev, h=sizes[1],
                                     o=sizes[2]), sharded=True)
    single = deploy.serve_bank(designs, x)
    for devices in (jax.devices(), jax.devices()[1:]):
        logits = deploy.make_bank_fn(
            designs, mesh=elastic.bank_pool_mesh(devices))(x)
        check(logits.sharding.device_set == set(devices)
              and {sh.data.shape[0] for sh in logits.addressable_shards}
              == {SHARDED_BANK // len(devices)},
              f"bank not split over the pool {devices}: {logits.sharding}")
        check(np.array_equal(np.asarray(logits), single),
              f"bank sharded over {len(devices)} devices != single device")
    wl = whole_set_workload(x, DATASET, seed)
    tenant = serving_engine.Tenant(name=DATASET, designs=designs,
                                   parity_data=(x, y))
    rep = serving_engine.run_workload(
        [tenant], wl, sharded=True, target_latency_ms=25.0,
        inject_device_failure=lambda launch: 0 if launch == 2 else None)
    slo = rep["tenants"][DATASET]
    check(rep["recoveries"] == 1, f"recoveries {rep['recoveries']}")
    check(rep["devices"] == {"alive": n_dev - 1, "lost": 1,
                             "sharded": True}, f"pool {rep['devices']}")
    check(slo["completed"] == len(wl) and slo["shed"] == 0,
          f"requests lost: {slo}")
    preds = served_predictions(rep, wl)
    check(np.array_equal(preds, np.argmax(single, axis=-1)),
          "served predictions after device loss != single device")
    served = deploy.mean_acc(preds == y[None, :])
    check(np.array_equal(served, exported),
          f"served accuracy {served} != exported {exported}")
    print(f"  {SHARDED_BANK} designs over {n_dev} -> {n_dev - 1} devices, "
          f"{len(wl)} requests, {rep['recoveries']} recovery, predictions "
          f"== single device", flush=True)


def run_four_chips(seed: int) -> None:
    import jax

    from repro.core import search
    from repro.data import tabular
    check(len(jax.devices()) == 4, f"--chips 4 needs 4 devices, found "
                                   f"{len(jax.devices())}")
    spec = tabular.SPECS[DATASET]
    sizes = (spec.features, spec.hidden, spec.classes)
    data = tabular.make_dataset(DATASET, seed=seed)
    cfg = search.SearchConfig(bits=BITS, pop_size=POP,
                              train_steps=TRAIN_STEPS, seed=seed)
    with phase("sharded_generation"):
        genomes = sharded_generation_phase(data, sizes, cfg, seed)
    with phase("sharded_serving"):
        sharded_serving_phase(genomes, data, sizes, cfg, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's sources are not next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    compile_cache.enable()
    import jax
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(levelname)s %(name)s: %(message)s")
    logging.getLogger("repro.kernels.dispatch").setLevel(logging.INFO)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x "
          f"{len(jax.devices())}, jax {jax.__version__}", flush=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    except Exception as e:                  # noqa: BLE001 — report, exit 1
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    print(f"total: {time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
