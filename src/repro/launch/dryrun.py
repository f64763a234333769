import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell against the production meshes and extract memory/cost/collective
evidence for EXPERIMENTS.md §Dry-run and §Roofline.

MUST be a fresh process (the XLA_FLAGS line above runs before any jax
import — jax locks the device count on first init). Usage:

  PYTHONPATH=src python -m repro.launch.dryrun                  # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi

Writes one JSON per cell to experiments/dryrun/.
"""
import argparse
import functools
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, applicable_shapes, get_config
from repro.distributed import sharding as sh
from repro.launch import analysis
from repro.launch.mesh import make_production_mesh
from repro.models import steps, transformer
from repro.optim import adamw


def _struct_tree(tree, shardings):
    return jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shardings)


def state_structs(cfg, mesh, inference: bool = False):
    pshapes = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    pshard = sh.param_shardings(pshapes, mesh, cfg, inference)
    params = _struct_tree(pshapes, pshard)
    opt_dt = jnp.dtype(cfg.opt_state_dtype)
    mv = jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, opt_dt, sharding=s),
        pshapes, pshard)
    rep = NamedSharding(mesh, P())
    opt = adamw.OptState(
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep), m=mv, v=mv)
    err = None
    if cfg.grad_compression == "int8":
        n = sum(l.size for l in jax.tree_util.tree_leaves(pshapes))
        dp = sh.dp_axes(mesh)
        dpt = 1
        for a in dp:
            dpt *= mesh.shape[a]
        err = jax.ShapeDtypeStruct(
            (dpt, n), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(dp if len(dp) > 1 else dp[0], None)))
    return steps.TrainState(params, opt, err), params


def lower_cell(cfg, shape, mesh, microbatches=None):
    """Returns (lowered, compiled, meta). The heart of the dry-run."""
    specs = steps.input_specs(cfg, shape, mesh, microbatches)
    meta = {"kind": shape.kind}
    if shape.kind == "train":
        state, _ = state_structs(cfg, mesh)
        fn = steps.make_train_step(cfg, mesh, shape,
                                   microbatches=specs["n_microbatches"])
        step_struct = jax.ShapeDtypeStruct((), jnp.int32)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, donate_argnums=(0,)).lower(
                state, specs["batch"], step_struct)
        meta["n_microbatches"] = specs["n_microbatches"]
    elif shape.kind == "prefill":
        _, params = state_structs(cfg, mesh)
        fn = steps.make_prefill_step(cfg, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn).lower(params, specs["batch"])
    else:
        _, params = state_structs(cfg, mesh, inference=True)
        fn = steps.make_decode_step(cfg, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, donate_argnums=(2,)).lower(
                params, specs["batch"], specs["cache"])
    compiled = lowered.compile()
    return lowered, compiled, meta


def _mem_dict(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception as e:                        # pragma: no cover
        return {"error": str(e)}
    if ma is None:
        return {"unavailable": True}
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes", "host_generated_code_size_in_bytes",
              "host_argument_size_in_bytes", "host_output_size_in_bytes",
              "host_temp_size_in_bytes", "host_alias_size_in_bytes",
              "serialized_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _cost_dict(compiled):
    try:
        ca = compiled.cost_analysis()
    except Exception as e:                        # pragma: no cover
        return {"error": str(e)}
    if not ca:
        return {"unavailable": True}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and not k.startswith("utilization")}


def run_cell(arch: str, shape, mesh_name: str, outdir: Path,
             force: bool = False) -> dict:
    out = outdir / f"{arch}__{shape.name}__{mesh_name}.json"
    if out.exists() and not force:
        return json.loads(out.read_text())
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = mesh.devices.size
    rec = {"arch": arch, "shape": shape.name, "mesh": mesh_name,
           "chips": chips, "kind": shape.kind,
           "params": cfg.param_counts()}
    t0 = time.time()
    try:
        lowered, compiled, meta = lower_cell(cfg, shape, mesh)
        rec.update(meta)
        rec["lower_compile_s"] = round(time.time() - t0, 1)
        rec["memory_analysis"] = _mem_dict(compiled)
        rec["cost_analysis_raw"] = _cost_dict(compiled)
        t1 = time.time()
        text = compiled.as_text()
        st = analysis.hlo_stats(text)
        rec["hlo_stats"] = st.to_dict()
        mf = analysis.model_flops(cfg, shape)
        ib = analysis.ideal_bytes(cfg, shape, chips,
                                  rec.get("n_microbatches", 1))
        rec["roofline"] = analysis.roofline(st, chips=chips,
                                            model_flops_global=mf,
                                            ideal_bytes_per_dev=ib)
        rec["analyze_s"] = round(time.time() - t1, 1)
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    out.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else list(ARCH_NAMES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    n_ok = n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            if args.shape and shape.name != args.shape:
                continue
            for mesh_name in meshes:
                rec = run_cell(arch, shape, mesh_name, outdir, args.force)
                ok = rec.get("ok")
                n_ok += bool(ok)
                n_fail += not ok
                r = rec.get("roofline", {})
                print(f"{arch:24s} {shape.name:12s} {mesh_name:6s} "
                      f"ok={str(bool(ok)):5s} t={rec.get('lower_compile_s','-'):>7}s "
                      f"dom={r.get('dominant','-'):10s} "
                      f"cmp={r.get('compute_s',0):.3e} mem={r.get('memory_s',0):.3e} "
                      f"col={r.get('collective_s',0):.3e}",
                      flush=True)
                if not ok:
                    print("   ERROR:", rec.get("error"), flush=True)
    print(f"\ndone: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
