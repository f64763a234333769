"""Training launcher: end-to-end driver usable both for CPU-scale runs
(examples, CI) and as the entrypoint a pod job would exec.

LM training:

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

In-training ADC optimization (the paper's §3.2 search, population-batched
engine of DESIGN.md §2 — reports per-generation wall time and
individuals/sec):

  PYTHONPATH=src python -m repro.launch.train --adc-search --dataset seeds \
      --bits 3 --pop 16 --generations 4 --train-steps 100

Add ``--export-front`` to freeze the searched Pareto front into deployable
classifier artifacts (core/deploy.py) under <ckpt-dir>/front, servable by
``repro.launch.serve_classifier``.

Robustness-aware co-search (DESIGN.md §10): ``--mc-samples S`` with any of
``--nonideal-sigma`` (comparator offset, LSBs), ``--fault-rate`` (stuck-at
probability) or ``--range-drift`` (reference-ladder sigma, fraction of
full scale) adds the third NSGA-II objective (``--robust-objective
expected|worst``) and, with ``--export-front``, persists the Monte-Carlo
yield report next to the front (<ckpt-dir>/front/robustness.json):

  PYTHONPATH=src python -m repro.launch.train --adc-search --dataset seeds \
      --bits 3 --pop 16 --generations 4 --mc-samples 16 \
      --nonideal-sigma 0.5 --fault-rate 0.02 --export-front
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config, smoke_config
from repro.core.spec import parse_range
from repro.configs.base import ShapeConfig
from repro.data.lm import LMDataConfig, SyntheticLM
from repro.distributed import fault
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.models import steps


def build(arch: str, *, smoke: bool, seq: int, batch: int, microbatches: int,
          data_ax: int = 1, model_ax: int = 1, steps_total: int = 100):
    cfg = smoke_config(arch) if smoke else get_config(arch)
    mesh = mesh_lib.make_host_mesh(data_ax, model_ax)
    shape = ShapeConfig("cli", seq, batch, "train")
    train_step = steps.make_train_step(cfg, mesh, shape,
                                       microbatches=microbatches,
                                       total_steps=steps_total)
    data = SyntheticLM(LMDataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                    global_batch=batch,
                                    microbatches=microbatches), cfg)
    return cfg, mesh, train_step, data


def adc_search_config(args, channels: int, data=None):
    """argv -> the search's (AdcSpec, SearchConfig) pair — factored out of
    ``run_adc_search`` so the CLI parsing round trip (per-channel
    --vmin/--vmax comma lists, non-ideality knobs, --auto-range) is
    testable without running a search (tests/test_cli_roundtrip.py).
    ``data`` (the dataset dict) is required for ``--auto-range``, which
    derives per-channel vmin/vmax from the training data's percentiles
    (AdcSpec.from_data) instead of hand-typed comma lists."""
    from repro.core import nonideal, search
    from repro.core.spec import AdcSpec

    if args.auto_range:
        if args.vmin != "0.0" or args.vmax != "1.0":
            raise ValueError(
                "--auto-range derives vmin/vmax from the training data; "
                "drop the explicit --vmin/--vmax (or drop --auto-range)")
        if data is None:
            raise ValueError("--auto-range needs the dataset to derive "
                             "ranges from")
        adc_spec = AdcSpec.from_data(data["x_train"], bits=args.bits,
                                     pct=args.auto_range_pct)
    else:
        adc_spec = AdcSpec(bits=args.bits, vmin=parse_range(args.vmin),
                           vmax=parse_range(args.vmax))
    adc_spec.validate_channels(channels)
    ni = None
    knobs = (args.nonideal_sigma > 0 or args.fault_rate > 0
             or args.range_drift > 0)
    if knobs and args.mc_samples <= 0:
        raise ValueError(
            "--nonideal-sigma/--fault-rate/--range-drift need "
            "--mc-samples > 0 to take effect; refusing to silently run "
            "an ideal-hardware search")
    if args.mc_samples > 0 and not knobs:
        raise ValueError(
            "--mc-samples without any non-ideality knob "
            "(--nonideal-sigma/--fault-rate/--range-drift) would "
            "Monte-Carlo ideal hardware; set at least one knob > 0")
    if knobs:
        ni = nonideal.NonIdealSpec(sigma_offset=args.nonideal_sigma,
                                   sigma_range=args.range_drift,
                                   fault_rate=args.fault_rate,
                                   seed=args.nonideal_seed)
    ft = None
    if args.faulttol:
        if not knobs or args.mc_samples <= 0:
            raise ValueError(
                "--faulttol extends the robustness co-search; it needs "
                "--mc-samples > 0 and at least one non-ideality knob")
        from repro.faulttol import FaultTolSpec
        ft = FaultTolSpec(max_spares=args.max_spares)
    cfg = search.SearchConfig.for_spec(
        adc_spec, pop_size=args.pop, generations=args.generations,
        train_steps=args.train_steps, engine=args.engine,
        screen_factor=args.screen_factor,
        nonideal=ni, mc_samples=args.mc_samples if ni else 0,
        robust_objective=args.robust_objective,
        yield_margin=args.yield_margin, faulttol=ft)
    return adc_spec, cfg


def parse_yield_margins(text: str):
    """'--yield-margins 0.01,0.05' -> (0.01, 0.05) — the accuracy-drop
    margins the exported robustness report tabulates yield at."""
    try:
        margins = tuple(float(t) for t in str(text).split(",") if t.strip())
    except ValueError:
        margins = ()
    if not margins or any(not 0.0 <= m < 1.0 for m in margins):
        raise ValueError(f"--yield-margins must be a comma list of "
                         f"fractions in [0, 1), got {text!r}")
    return margins


def run_adc_search(args):
    """Drive the population-batched/sharded in-training ADC search: one
    compiled train-and-score call per generation, timed via the evolve log
    hook. Search state checkpoints every generation under
    <ckpt-dir>/adc_search; --resume restarts a killed run bit-identically
    from the latest snapshot."""
    from pathlib import Path

    from repro.core import area, search
    from repro.data import tabular

    spec = tabular.SPECS[args.dataset]
    data = tabular.make_dataset(args.dataset)
    sizes = (spec.features, spec.hidden, spec.classes)
    adc_spec, cfg = adc_search_config(args, spec.features, data=data)
    mesh = search.default_search_mesh() if cfg.engine == "sharded" else None
    ckpt_dir = Path(args.ckpt_dir) / "adc_search"
    if not args.resume and ckpt_dir.exists():
        # fresh start: stale higher-numbered snapshots would otherwise
        # out-survive this run's in the keep-N GC and hijack a later
        # --resume with a previous run's state
        import shutil
        shutil.rmtree(ckpt_dir)
    ckpt = CheckpointManager(ckpt_dir, keep=2)
    if args.resume and ckpt.latest_step() is not None:
        print(f"resuming from generation {ckpt.latest_step()} "
              f"({ckpt.dir})")
    print(f"adc-search[{cfg.engine}] dataset={args.dataset} "
          f"adc=({adc_spec.describe()}) pop={cfg.pop_size} "
          f"gens={cfg.generations} qat-steps={cfg.train_steps} "
          f"devices={len(jax.devices())}")
    if cfg.wants_robustness:
        margin = (f"@{cfg.yield_margin:g}"
                  if cfg.robust_objective == "yield" else "")
        print(f"  robustness objective [{cfg.robust_objective}{margin}] "
              f"over {cfg.mc_samples} MC instances: "
              f"{cfg.nonideal.describe()}")
    if cfg.faulttol is not None:
        print(f"  fault-tolerance genome: {cfg.faulttol.describe()} "
              f"(+{cfg.faulttol.gene_bits(sizes[0])} genes)")
    marks = [time.perf_counter()]

    def log(g, pop, fit):
        marks.append(time.perf_counter())
        dt = marks[-1] - marks[-2]
        extra = (f"  best-robust {fit[:, 2].min():.3f}"
                 if fit.shape[1] > 2 else "")
        print(f"  gen {g:2d}: {dt:6.2f}s/gen "
              f"{cfg.pop_size / dt:7.1f} individuals/s  "
              f"best-acc {1 - fit[:, 0].min():.3f}  "
              f"min-area {fit[:, 1].min():.3f}{extra}", flush=True)

    # return_trained: with --export-front the final front's vmapped QAT
    # runs once here and its trained stacks feed the export directly
    out = search.run_search(data, sizes, cfg, log=log, ckpt=ckpt,
                            resume=args.resume, mesh=mesh,
                            return_trained=args.export_front)
    (pg, pf, decode), trained = out[:3], (out[3] if args.export_front
                                          else None)
    gen_s = [b - a for a, b in zip(marks[:-1], marks[1:])]
    if cfg.engine == "gradient":
        # one gate train + one exact pool re-score, no generations
        total = marks[-1] - marks[0]
        print(f"pareto points: {len(pf)}; gate family + exact re-score "
              f"in {total:.2f}s ({cfg.pop_size / total:.1f} "
              f"individuals/s incl. compile)")
    elif gen_s:
        # first generation pays the XLA compile; steady state is the tail
        steady = gen_s[1:] or gen_s
        print(f"pareto points: {len(pf)}; per-generation "
              f"{sum(steady) / len(steady):.2f}s steady "
              f"({cfg.pop_size * len(steady) / sum(steady):.1f} "
              f"individuals/s), {gen_s[0]:.2f}s first (incl. compile)")
    else:
        print(f"pareto points: {len(pf)} (initial population only — "
              f"no generations evolved)")
    flash = area.flash_full_tc(cfg.bits) * sizes[0]
    for f in pf[np.argsort(pf[:, 0])]:
        print(f"  acc={1 - f[0]:.3f}  area={f[1] * flash:.0f}T (norm {f[1]:.3f})")
    if args.export_front:
        from repro.core import deploy
        front_dir = Path(args.ckpt_dir) / "front"
        designs = deploy.export_front(pg, data, sizes, cfg, trained=trained)
        deploy.save_front(front_dir, designs,
                          extra_meta={"dataset": args.dataset,
                                      "sizes": list(sizes)})
        print(f"exported {len(designs)} deployed design(s) -> {front_dir}")
        for i, d in enumerate(designs):
            print(f"  design {i}: acc={d.accuracy:.3f}  area={d.area_tc}T  "
                  f"dp={int(d.dp)}  kept-levels="
                  f"{int(d.mask.sum())}/{d.mask.size}")
        if cfg.wants_robustness:
            # the yield report rides with the artifact: same NonIdealSpec
            # (hence same draw stream) as the search's third objective
            margins = parse_yield_margins(args.yield_margins)
            rep = deploy.evaluate_robustness(
                designs, cfg.nonideal, data["x_test"], data["y_test"],
                samples=cfg.mc_samples, yield_margins=margins)
            deploy.save_robustness(front_dir, rep)
            for i, row in enumerate(rep["designs"]):
                ys = "  ".join(f"yield@{m:g} {row['yield'][f'{m:g}']:.2f}"
                               for m in margins)
                print(f"  design {i} robustness: mean "
                      f"{row['mean_accuracy']:.3f}  worst "
                      f"{row['worst_accuracy']:.3f}  {ys}")
            print(f"robustness report -> {front_dir}/robustness.json")
        print(f"serve it:  PYTHONPATH=src python -m repro.launch."
              f"serve_classifier --front-dir {front_dir}")
    return pf


def build_parser() -> argparse.ArgumentParser:
    """The launcher's full CLI — a separate function so tests can parse
    argv without running anything (the --vmin/--vmax round-trip test)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="LM architecture (required unless "
                                   "--adc-search)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--adc-search", action="store_true",
                    help="run the paper's in-training ADC optimization "
                         "instead of LM training")
    ap.add_argument("--dataset", default="seeds")
    ap.add_argument("--bits", type=int, default=3)
    ap.add_argument("--vmin", default="0.0",
                    help="analog range minimum: scalar, or comma-separated "
                         "per-channel list (heterogeneous sensors)")
    ap.add_argument("--vmax", default="1.0",
                    help="analog range maximum (same forms as --vmin)")
    ap.add_argument("--auto-range", action="store_true",
                    help="derive per-channel vmin/vmax from the training "
                         "data's percentiles (AdcSpec.from_data) instead "
                         "of --vmin/--vmax — heterogeneous sensors "
                         "without hand-typed comma lists")
    ap.add_argument("--auto-range-pct", type=float, default=0.5,
                    help="percentile clip for --auto-range: range covers "
                         "[pct, 100-pct] of each channel's distribution")
    ap.add_argument("--pop", type=int, default=16)
    ap.add_argument("--generations", type=int, default=4)
    ap.add_argument("--train-steps", type=int, default=100)
    ap.add_argument("--engine", default="batched",
                    choices=("batched", "sharded", "reference", "gradient"),
                    help="'gradient': one jitted gate-logit train sweeps "
                         "the whole accuracy/area family, then re-scores "
                         "through the exact batched path (DESIGN.md §13)")
    ap.add_argument("--screen-factor", type=int, default=1,
                    help="surrogate-screened NSGA-II: oversample offspring "
                         "by this factor and let the online fitness "
                         "predictor pick which pay the compiled QAT "
                         "evaluation (1 = off, bit-identical to PR 3)")
    ap.add_argument("--resume", action="store_true",
                    help="restart the ADC search from its latest "
                         "checkpoint under <ckpt-dir>/adc_search "
                         "(bit-identical continuation)")
    ap.add_argument("--export-front", action="store_true",
                    help="after --adc-search, freeze the Pareto front "
                         "into deployable classifiers (baked value "
                         "tables + po2-quantized weights + area report) "
                         "under <ckpt-dir>/front — servable via "
                         "repro.launch.serve_classifier")
    ap.add_argument("--mc-samples", type=int, default=0,
                    help="Monte-Carlo instances per design for the "
                         "robustness objective (0 disables)")
    ap.add_argument("--nonideal-sigma", type=float, default=0.0,
                    help="per-comparator input-referred offset sigma, "
                         "in LSBs")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="stuck-at-0/1 probability per surviving "
                         "comparator")
    ap.add_argument("--range-drift", type=float, default=0.0,
                    help="reference-ladder drift sigma, as a fraction "
                         "of each channel's full scale")
    ap.add_argument("--nonideal-seed", type=int, default=0,
                    help="MC draw stream seed (NonIdealSpec.seed)")
    ap.add_argument("--robust-objective", default="expected",
                    choices=("expected", "worst", "yield"),
                    help="third NSGA-II objective: expected accuracy "
                         "drop, worst-case error, or 1 - yield@margin "
                         "over the MC instances (DESIGN.md §15)")
    ap.add_argument("--yield-margin", type=float, default=0.01,
                    help="accuracy-drop margin of the in-search 'yield' "
                         "objective (fraction, e.g. 0.01 = 1%%)")
    ap.add_argument("--yield-margins", default="0.01,0.05",
                    help="comma list of margins the exported robustness "
                         "report tabulates yield at "
                         "(robustness.json)")
    ap.add_argument("--faulttol", action="store_true",
                    help="fault-tolerant co-search (DESIGN.md §15): "
                         "append per-channel TMR + spare-level genes and "
                         "a calibrate gene to the genome; needs "
                         "--mc-samples and a non-ideality knob")
    ap.add_argument("--max-spares", type=int, default=2,
                    help="per-channel spare-level gene range of "
                         "--faulttol (0 disables the spare action)")
    return ap


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.adc_search:
        return run_adc_search(args)
    if not args.arch:
        ap.error("--arch is required unless --adc-search is given")

    cfg, mesh, train_step, data = build(
        args.arch, smoke=args.smoke, seq=args.seq, batch=args.batch,
        microbatches=args.microbatches, steps_total=args.steps)
    with jax.set_mesh(mesh):
        state = steps.init_state(jax.random.PRNGKey(0), cfg, mesh)
        jstep = jax.jit(train_step, donate_argnums=(0,))
        ckpt = CheckpointManager(args.ckpt_dir, keep=2)

        losses = []

        def on_metrics(step, metrics):
            losses.append(float(metrics["loss"]))
            if step % args.log_every == 0:
                print(f"step {step:5d} loss {losses[-1]:.4f} "
                      f"lr {float(metrics['lr']):.2e}", flush=True)

        t0 = time.time()
        state, info = fault.run_with_recovery(
            lambda s, b, i: jstep(s, b, jnp.asarray(i, jnp.int32)),
            state,
            lambda i: data.device_batch(i),
            num_steps=args.steps, ckpt=ckpt, ckpt_every=args.ckpt_every,
            on_metrics=on_metrics)
        dt = time.time() - t0
        print(f"done: {args.steps} steps in {dt:.1f}s "
              f"({dt / max(args.steps, 1):.2f}s/step); "
              f"loss {losses[0]:.3f} -> {losses[-1]:.3f}; {info}")
        assert losses[-1] < losses[0], "loss did not improve"
    return losses


if __name__ == "__main__":
    main()
