"""Serving launcher for deployed ADC+classifier fronts.

Two drivers:

* ``--driver async`` (DESIGN.md §12) — the production serving engine
  (launch/serving_engine.py): asyncio ingestion of an open-loop load
  trace (launch/loadgen.py: ``--rate``, ``--traffic
  uniform|bursty|diurnal``), per-request deadlines with counted
  shedding, per-tenant p50/p95/p99 SLO snapshot, adaptive microbatch
  sizing on the tuned block_m ladder, multi-tenant routing (repeat
  ``--front-dir`` to make several exported fronts resident — each
  front's ``front_meta`` dataset names its tenant), and elastic
  device-pool recovery (``--fail-device-at N`` simulates a device loss
  at batch N: the bank re-shards over the survivors and bit-for-bit
  parity is re-asserted before serving resumes).
* ``--driver batch`` (default; DESIGN.md §8) — the fixed-microbatch
  continuous-batching loop: drain a request list into ``--batch``-row
  microbatches (a microbatch may span many small requests or a slice of
  one large request, tail padded), one fused bank launch each, report
  requests/sec + samples/sec.

Both push every microbatch through the *whole* deployed front in one
fused bank launch (every response carries all D designs' predictions, so
the accuracy/area trade-off is selectable per response). With
``--sharded`` the design bank partitions D/device over the mesh
(ops.classifier_bank_sharded via distributed/sharding.design_bank_axes).

  # search + export first:
  PYTHONPATH=src python -m repro.launch.train --adc-search --dataset seeds \
      --bits 3 --pop 16 --generations 4 --ckpt-dir /tmp/adc --export-front
  # then serve the exported front:
  PYTHONPATH=src python -m repro.launch.serve_classifier \
      --front-dir /tmp/adc/front --requests 64 --batch 128
  # production driver, bursty open-loop traffic at 500 req/s:
  PYTHONPATH=src python -m repro.launch.serve_classifier \
      --front-dir /tmp/adc/front --driver async --rate 500 \
      --traffic bursty --deadline-ms 100

``--smoke`` (no --front-dir needed) searches a tiny fixed-seed front
inline and serves it — the CI lane; every derived field except wall-clock
is deterministic.

``--nonideal-sigma/--fault-rate/--range-drift`` serve the front through
ONE sampled non-ideal hardware instance (MC instance ``--nonideal-instance``
of the ``--nonideal-seed`` stream, DESIGN.md §10) — the live demonstration
of what comparator offsets and stuck-at faults do to served accuracy; the
report prints served-vs-exported degradation per design instead of
asserting the ideal-hardware parity contract, plus a yield@margin summary
over the instance stream (``--yield-margins``). Add ``--calibrate`` to
re-bake the front against the sampled instance's *measured*
non-idealities (DESIGN.md §15) and serve through the calibrated tables —
the report then also prints the recovered accuracy per design.
"""
from __future__ import annotations

import argparse
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import deploy
from repro.launch import compile_cache


def make_request_stream(x: np.ndarray, num_requests: int, request_size: int,
                        seed: int = 0) -> List[Tuple[int, np.ndarray]]:
    """Synthetic client traffic: ``num_requests`` requests of
    ``request_size`` sample rows each, drawn (with replacement) from the
    dataset — deterministic under ``seed``."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(x), size=(num_requests, request_size))
    return [(rid, np.asarray(x[idx[rid]], np.float32))
            for rid in range(num_requests)]


def serve(designs: Sequence[deploy.DeployedClassifier],
          requests: Sequence[Tuple[int, np.ndarray]], batch: int, *,
          mesh=None, interpret: Optional[bool] = None,
          bank_fn=None) -> Dict:
    """Drain ``requests`` through the fused bank in fixed ``batch``-row
    microbatches (continuous batching: the row stream ignores request
    boundaries; the tail pads to keep one compiled shape). Returns the
    throughput report plus per-request responses
    ``{rid: (D, n_rows) predicted classes}``. ``bank_fn`` overrides the
    jitted (M, C) -> (D, M, O) bank closure — the non-ideal serving path
    passes a sampled-instance bank (deploy.make_nonideal_bank_fn) built
    once by the caller."""
    if bank_fn is not None:
        if mesh is not None:
            raise ValueError("a custom bank_fn (non-ideal serving) and "
                             "--sharded are mutually exclusive")
        fn = bank_fn
    else:
        fn = deploy.make_bank_fn(designs, mesh=mesh, interpret=interpret)
    channels = designs[0].table.shape[0]
    queue = deque(requests)
    carry: Optional[Tuple[int, np.ndarray]] = None
    responses: Dict[int, List[np.ndarray]] = {rid: [] for rid, _ in requests}
    total_rows = sum(len(x) for _, x in requests)
    batches = padded_rows = 0
    # warmup on a dummy batch so the report times serving, not compilation
    jax.block_until_ready(fn(jnp.zeros((batch, channels), jnp.float32)))
    t0 = time.perf_counter()
    while queue or carry:
        rows, meta, filled = [], [], 0
        while filled < batch and (queue or carry):
            rid, x = carry if carry is not None else queue.popleft()
            carry = None
            take = min(batch - filled, len(x))
            rows.append(x[:take])
            meta.append((rid, take))
            filled += take
            if take < len(x):
                carry = (rid, x[take:])
        xb = np.concatenate(rows, axis=0)
        pad = batch - len(xb)
        if pad:
            xb = np.pad(xb, ((0, pad), (0, 0)))
            padded_rows += pad
        logits = np.asarray(jax.block_until_ready(fn(jnp.asarray(xb))))
        preds = np.argmax(logits, axis=-1)            # (D, batch)
        off = 0
        for rid, take in meta:
            responses[rid].append(preds[:, off:off + take])
            off += take
        batches += 1
    wall_s = time.perf_counter() - t0
    out = {rid: np.concatenate(chunks, axis=1)
           for rid, chunks in responses.items()}
    return {
        "num_designs": len(designs),
        "kind": designs[0].kind,
        "bits": designs[0].bits,
        "batch": batch,
        "requests": len(requests),
        "samples": total_rows,
        "batches": batches,
        "pad_fraction": padded_rows / max(batches * batch, 1),
        "wall_s": wall_s,
        "requests_per_s": len(requests) / wall_s,
        "samples_per_s": total_rows / wall_s,
        "responses": out,
    }


def _smoke_front(dataset: str):
    """Tiny fixed-seed search + export (the CI lane needs no pre-exported
    front on disk): same config family as benchmarks' --smoke search."""
    from repro.core import search
    from repro.data import tabular
    spec = tabular.SPECS[dataset]
    data = tabular.make_dataset(dataset)
    sizes = (spec.features, spec.hidden, spec.classes)
    cfg = search.SearchConfig(bits=2, pop_size=6, generations=1,
                              train_steps=30)
    pg, _, _ = search.run_search(data, sizes, cfg)
    return deploy.export_front(pg, data, sizes, cfg), data


def _serve_async(fronts, args, nonideal=None):
    """The --driver async path: one Tenant per loaded front, an open-loop
    load trace per tenant, merged into one stream through the engine.
    With ``nonideal`` (--calibrate) every tenant serves calibrated
    tables and re-calibrates on device-loss recovery (DESIGN.md §15)."""
    from repro.launch import loadgen, serving_engine

    tenants, traces = [], []
    for name, designs, data in fronts:
        tenants.append(serving_engine.Tenant(
            name=name, designs=designs,
            parity_data=(data["x_test"], data["y_test"]),
            nonideal=nonideal))
        traces.append(loadgen.make_workload(
            data["x_test"], args.requests, tenant=name,
            rate_rps=args.rate, request_size=args.request_size,
            deadline_ms=args.deadline_ms, shape=args.traffic,
            seed=args.seed))
    workload = loadgen.merge_workloads(*traces)
    print(f"  load: {loadgen.describe(workload)}")

    inject = None
    if args.fail_device_at is not None:
        fail_at = args.fail_device_at
        inject = lambda b: 0 if b == fail_at else None   # noqa: E731

    rep = serving_engine.run_workload(
        tenants, workload,
        target_latency_ms=args.target_latency_ms,
        max_batch=args.max_batch, sharded=args.sharded,
        inject_device_failure=inject)
    for name, slo in sorted(rep["tenants"].items()):
        print(f"  tenant {name}: {slo['completed']}/{slo['requests']} ok "
              f"({slo['shed']} shed, {slo['rejected']} rejected)  "
              f"p50={slo['p50_ms']:.1f}ms p95={slo['p95_ms']:.1f}ms "
              f"p99={slo['p99_ms']:.1f}ms  "
              f"{slo['requests_per_s']:.1f} req/s "
              f"{slo['samples_per_s']:.0f} samples/s")
    bs = rep["batch_sizes"]
    print(f"  {rep['batches']} batches "
          f"({rep['pad_fraction'] * 100:.1f}% pad, "
          f"{rep['stragglers']} stragglers); batch ladders: "
          + ", ".join(f"{n}: quantum {v['quantum']} ({v['quantum_source']})"
                      f" -> final {v['final']}" for n, v in sorted(bs.items())))
    dv = rep["devices"]
    print(f"  devices: {dv['alive']} alive, {dv['lost']} lost, "
          f"{rep['recoveries']} recoveries (sharded={dv['sharded']})")
    if rep.get("calibrations"):
        print("  calibrations: " + ", ".join(
            f"{n}: {c}" for n, c in sorted(rep["calibrations"].items())))
    if args.fail_device_at is not None and rep["recoveries"] < 1:
        raise SystemExit("requested --fail-device-at but no recovery ran "
                         "(stream ended before the failing batch?)")
    # post-run parity: served accuracies on the CURRENT pool reproduce the
    # export bit-for-bit (after a recovery this re-checks the re-shard)
    for name, designs, data in fronts:
        served = deploy.served_accuracies(designs, data["x_test"],
                                          data["y_test"])
        exported = np.array([d.accuracy for d in designs])
        if not np.array_equal(served, exported):
            raise SystemExit(f"tenant {name}: served accuracies diverge "
                             f"from the exported front: {served} != "
                             f"{exported}")
    print("  parity OK: served == exported accuracy for every tenant")
    return rep


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--front-dir", action="append",
                    help="exported front (launch.train --export-front); "
                         "omit with --smoke to search one inline; repeat "
                         "with --driver async for multi-tenant serving")
    ap.add_argument("--dataset", default="seeds",
                    help="sample stream + labels for the parity check")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--request-size", type=int, default=8)
    ap.add_argument("--batch", type=int, default=128,
                    help="compiled microbatch rows (continuous batching)")
    ap.add_argument("--driver", choices=("batch", "async"), default="batch",
                    help="batch: fixed-microbatch loop (§8); async: the "
                         "production serving engine (§12)")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="[async] offered load, requests/s (open loop)")
    ap.add_argument("--traffic", choices=("uniform", "bursty", "diurnal"),
                    default="uniform", help="[async] arrival-rate envelope")
    ap.add_argument("--deadline-ms", type=float, default=100.0,
                    help="[async] per-request deadline budget")
    ap.add_argument("--target-latency-ms", type=float, default=50.0,
                    help="[async] adaptive batcher's latency target")
    ap.add_argument("--max-batch", type=int, default=512,
                    help="[async] batch-ladder ceiling")
    ap.add_argument("--seed", type=int, default=0,
                    help="[async] load-generator seed")
    ap.add_argument("--fail-device-at", type=int, default=None,
                    help="[async] simulate losing device 0 at this "
                         "bank-launch index (elastic recovery demo; "
                         "needs >= 2 devices)")
    ap.add_argument("--sharded", action="store_true",
                    help="shard the design bank D/device over the mesh")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fixed-seed front + traffic (CI lane)")
    ap.add_argument("--nonideal-sigma", type=float, default=0.0,
                    help="serve through a sampled non-ideal instance: "
                         "comparator offset sigma in LSBs")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="stuck-at-0/1 probability per comparator")
    ap.add_argument("--range-drift", type=float, default=0.0,
                    help="reference-ladder drift sigma (fraction of "
                         "full scale)")
    ap.add_argument("--nonideal-seed", type=int, default=0)
    ap.add_argument("--nonideal-instance", type=int, default=0,
                    help="which MC instance of the seed's stream to "
                         "sample the served hardware from")
    ap.add_argument("--mc-samples", type=int, default=0,
                    help="the MC stream size --nonideal-instance indexes "
                         "into — pass the samples count of an "
                         "evaluate_robustness report to serve exactly "
                         "the instance it lists (0: minimal "
                         "instance+1-sample stream)")
    ap.add_argument("--calibrate", action="store_true",
                    help="with --nonideal-*: calibrate the front against "
                         "the sampled instance's measured non-idealities "
                         "(DESIGN.md §15) and serve through the "
                         "calibrated tables instead of degraded — the "
                         "report compares degraded vs recovered accuracy")
    ap.add_argument("--yield-margins", default="0.01,0.05",
                    help="with --nonideal-*: comma list of accuracy-drop "
                         "margins for the served front's yield summary")
    return ap


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    compile_cache.enable()

    from repro.data import tabular
    if args.smoke:
        args.requests, args.request_size = 16, 4
        args.batch = min(args.batch, 32)
        args.rate = min(args.rate, 400.0)
    fronts = []          # (tenant name, designs, data) per resident front
    if args.front_dir:
        if args.driver == "batch" and len(args.front_dir) > 1:
            ap.error("--driver batch serves one front; repeat --front-dir "
                     "only with --driver async (multi-tenant routing)")
        for fdir in args.front_dir:
            designs = deploy.load_front(fdir)
            meta = deploy.front_meta(fdir)
            trained_on = meta.get("dataset")
            # --driver async routes by front provenance: the tenant IS
            # the front's dataset. The batch driver keeps the CLI-level
            # wrong-domain check against --dataset.
            name = trained_on or args.dataset
            if (args.driver == "batch" and trained_on is not None
                    and trained_on != args.dataset):
                ap.error(f"front at {fdir} was exported from dataset "
                         f"{trained_on!r}; serving {args.dataset!r} "
                         f"traffic through it would be wrong-domain "
                         f"(pass --dataset {trained_on})")
            data = tabular.make_dataset(name if args.driver == "async"
                                        else args.dataset)
            channels = designs[0].channels
            if channels != data["x_test"].shape[1]:
                ap.error(f"front expects {channels} sensor channels but "
                         f"dataset {name!r} has {data['x_test'].shape[1]}")
            fronts.append((name, designs, data))
    elif args.smoke:
        designs, data = _smoke_front(args.dataset)
        fronts.append((args.dataset, designs, data))
    else:
        ap.error("--front-dir is required unless --smoke is given")
    designs, data = fronts[0][1], fronts[0][2]

    nonideal = None
    if (args.nonideal_sigma > 0 or args.fault_rate > 0
            or args.range_drift > 0):
        from repro.core.nonideal import NonIdealSpec
        nonideal = NonIdealSpec(sigma_offset=args.nonideal_sigma,
                                sigma_range=args.range_drift,
                                fault_rate=args.fault_rate,
                                seed=args.nonideal_seed)

    if args.driver == "async" and nonideal is not None and not args.calibrate:
        ap.error("--driver async serves the ideal-hardware parity "
                 "contract; --nonideal-* needs --driver batch, or add "
                 "--calibrate to serve calibrated tables with "
                 "calibrate-on-recovery")
    if args.calibrate and nonideal is None:
        ap.error("--calibrate re-bakes the front against a measured "
                 "non-ideal instance; it needs --nonideal-sigma / "
                 "--fault-rate / --range-drift")
    from repro.launch.train import parse_yield_margins
    yield_margins = parse_yield_margins(args.yield_margins)

    mesh = None
    if args.sharded and args.driver == "batch":
        if nonideal is not None:
            ap.error("--sharded and --nonideal-* are mutually exclusive")
        from repro.core import search
        mesh = search.default_search_mesh()
    print(f"serve_classifier[driver={args.driver} "
          f"tenants={[f[0] for f in fronts]} D={len(designs)} "
          f"{designs[0].kind} {designs[0].spec.describe()}] "
          f"devices={len(jax.devices())} sharded={args.sharded}"
          + (f" nonideal=({nonideal.describe()} "
             f"instance={args.nonideal_instance})" if nonideal else ""))

    if args.driver == "async":
        return _serve_async(fronts, args,
                            nonideal=nonideal if args.calibrate else None)

    nonideal_fn = cal_fn = None
    if nonideal is not None:
        # built ONCE: serve() drives it for throughput and the
        # degradation report below re-uses the same compiled closure
        nonideal_fn = deploy.make_nonideal_bank_fn(
            designs, nonideal, instance=args.nonideal_instance,
            samples=args.mc_samples or None)
        if args.calibrate:
            cal_fn = deploy.make_calibrated_bank_fn(
                designs, nonideal, instance=args.nonideal_instance,
                samples=args.mc_samples or None)

    requests = make_request_stream(data["x_test"], args.requests,
                                   args.request_size)
    rep = serve(designs, requests, args.batch, mesh=mesh,
                bank_fn=cal_fn if cal_fn is not None else nonideal_fn)
    print(f"  {rep['requests']} requests ({rep['samples']} samples) in "
          f"{rep['wall_s']:.3f}s: {rep['requests_per_s']:.1f} req/s, "
          f"{rep['samples_per_s']:.0f} samples/s "
          f"({rep['batches']} batches of {rep['batch']}, "
          f"{rep['pad_fraction'] * 100:.1f}% pad)")

    exported = np.array([d.accuracy for d in designs])
    if nonideal is not None:
        # degraded-hardware demonstration: score the sampled instance
        # (same compiled closure serve() used) against the exported
        # (ideal) accuracies
        y_np = np.asarray(data["y_test"])[None, :]
        x_jnp = jnp.asarray(data["x_test"], jnp.float32)
        logits = np.asarray(nonideal_fn(x_jnp))
        served = deploy.mean_acc(np.argmax(logits, -1) == y_np)
        recovered = None
        if cal_fn is not None:
            # calibration demonstration: the SAME measured instance,
            # served through the re-baked tables (DESIGN.md §15)
            recovered = deploy.mean_acc(
                np.argmax(np.asarray(cal_fn(x_jnp)), -1) == y_np)
        for i, d in enumerate(designs):
            rec = (f" calibrated={recovered[i]:.3f} "
                   f"(recovered {recovered[i] - served[i]:+.3f})"
                   if recovered is not None else "")
            print(f"  design {i}: area={d.area_tc:4d}T  acc "
                  f"exported={d.accuracy:.3f} served={served[i]:.3f} "
                  f"(drop {d.accuracy - served[i]:+.3f}){rec}")
        print(f"  served a sampled non-ideal instance "
              f"({nonideal.describe()}): mean accuracy drop "
              f"{float(np.mean(exported - served)):+.3f}"
              + (f", calibrated recovery "
                 f"{float(np.mean(recovered - served)):+.3f}"
                 if recovered is not None else ""))
        # yield summary over the instance stream the served instance was
        # drawn from (same seed/size, so the served row is one of the S)
        rob = deploy.evaluate_robustness(
            designs, nonideal, data["x_test"], data["y_test"],
            samples=args.mc_samples or args.nonideal_instance + 1,
            yield_margins=yield_margins)
        for m in yield_margins:
            ys = "  ".join(f"{row['yield'][f'{m:g}']:.2f}"
                           for row in rob["designs"])
            print(f"  yield@{m:g} over {rob['samples']} instances: {ys}")
        rep["nonideal"] = nonideal.to_meta()
        rep["served_accuracies"] = [float(a) for a in served]
        if recovered is not None:
            rep["calibrated_accuracies"] = [float(a) for a in recovered]
        rep["yield_margins"] = [float(m) for m in yield_margins]
        rep["yield"] = [row["yield"] for row in rob["designs"]]
        return rep

    # round-trip parity: the served front must reproduce each design's
    # export-time accuracy bit-for-bit (the deployment contract)
    served = deploy.served_accuracies(designs, data["x_test"],
                                      data["y_test"], mesh=mesh)
    for i, d in enumerate(designs):
        print(f"  design {i}: area={d.area_tc:4d}T  dp={int(d.dp):+d}  "
              f"acc exported={d.accuracy:.3f} served={served[i]:.3f}")
    if not np.array_equal(served, exported):
        raise SystemExit(f"served accuracies diverge from the exported "
                         f"front: {served} != {exported}")
    print("  parity OK: served == exported accuracy for every design")
    return rep


if __name__ == "__main__":
    main()
