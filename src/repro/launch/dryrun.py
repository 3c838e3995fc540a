import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, and dump memory/cost/collective evidence.

This is the §3.3 "placement + partition" validation with XLA's SPMD
partitioner standing in for the paper's graph partitioner: if a sharding
assignment is incoherent (mismatched collective, non-divisible dim, OOM at
compile), it fails HERE, not on a 512-chip reservation.

Usage:
  python -m repro.launch.dryrun --arch glm4_9b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/]
"""

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import (ARCHS, SHAPES, OptimizerConfig, ParallelConfig,
                          get_config, shape_applicable)
from repro.launch.mesh import make_production_mesh
from repro.launch.presets import default_pcfg
from repro.models import api
from repro.optim import optimizers as opt
from repro.spmd import sharding as shd
from repro.spmd import steps as steps_mod


def abstract_tree(shapes_tree, shardings_tree):
    def one(sds, sh):
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sh)
    return jax.tree.map(one, shapes_tree, shardings_tree)


def input_specs(arch: str, shape_name: str, mesh, pcfg=None):
    """ShapeDtypeStruct stand-ins (weak-type-correct, shardable, no device
    allocation) for every input of the step being lowered."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    pcfg = pcfg or default_pcfg(arch, shape_name)
    bsh = steps_mod.batch_shardings(cfg, shape, mesh)
    batch = {
        name: jax.ShapeDtypeStruct(shp, dt, sharding=bsh[name])
        for name, (shp, dt) in api.batch_shapes(cfg, shape).items()
    }
    out = {"batch": batch}
    if shape.kind == "decode":
        cshapes = api.init_cache_shapes(cfg, shape.global_batch,
                                        shape.seq_len)
        csh = steps_mod.cache_shardings(cfg, shape.global_batch,
                                        shape.seq_len, mesh)
        out["cache"] = abstract_tree(cshapes, csh)
    return out


def lower_cell(arch: str, shape_name: str, mesh, pcfg=None, ocfg=None):
    """Returns (lowered, compiled, info dict)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    pcfg = pcfg or default_pcfg(arch, shape_name)
    from repro.launch.presets import default_ocfg
    ocfg = ocfg or default_ocfg(arch, shape_name)

    with jax.set_mesh(mesh):
        pshapes, specs = api.abstract_params(cfg)
        psh = steps_mod.resolve_param_shardings(pshapes, specs, cfg, pcfg,
                                                mesh)
        # working params are bf16; fp32 masters live in the optimizer state
        pshapes_bf16 = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), pshapes)
        params_abs = abstract_tree(pshapes_bf16, psh)
        ins = input_specs(arch, shape_name, mesh, pcfg)
        t0 = time.time()

        if shape.kind == "train":
            oshapes = jax.eval_shape(
                lambda: opt.init_train_state(ocfg, pshapes))
            osh = steps_mod.opt_state_shardings(oshapes, pshapes, specs, cfg,
                                                pcfg, mesh)
            opt_abs = abstract_tree(oshapes, osh)
            step_abs = jax.ShapeDtypeStruct((), jnp.int32)
            fn = steps_mod.make_train_step(cfg, pcfg, ocfg)
            metr_sh = NamedSharding(mesh, P())
            lowered = jax.jit(
                fn,
                in_shardings=(psh, osh, None, {
                    k: v.sharding for k, v in ins["batch"].items()}),
                out_shardings=(psh, osh, None),
                donate_argnums=(0, 1),
            ).lower(params_abs, opt_abs, step_abs, ins["batch"])
        elif shape.kind == "prefill":
            fn = steps_mod.make_prefill_step(cfg, pcfg)
            csh = steps_mod.cache_shardings(cfg, shape.global_batch,
                                            shape.seq_len, mesh)
            lowered = jax.jit(
                fn,
                in_shardings=(psh, {k: v.sharding
                                    for k, v in ins["batch"].items()}),
                out_shardings=(csh, NamedSharding(
                    mesh, steps_mod.shd.batch_spec(
                        shape.global_batch, mesh, extra_dims=0))),
            ).lower(params_abs, ins["batch"])
        else:  # decode
            fn = steps_mod.make_decode_step(cfg, pcfg)
            csh = jax.tree.map(lambda x: x.sharding, ins["cache"])
            lowered = jax.jit(
                fn,
                in_shardings=(psh, csh, {k: v.sharding
                                         for k, v in ins["batch"].items()}),
                out_shardings=(NamedSharding(mesh, steps_mod.shd.batch_spec(
                    shape.global_batch, mesh, extra_dims=0)), csh),
                donate_argnums=(1,),
            ).lower(params_abs, ins["cache"], ins["batch"])

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    n_dev = mesh.devices.size
    info = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(mesh.shape), "devices": int(n_dev),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_per_device": (
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        },
        "cost": {k: ca[k] for k in ("flops", "bytes accessed")
                 if k in ca} if ca else {},
        "params": get_config(arch).param_count(),
        "active_params": get_config(arch).active_param_count(),
        "microbatches": pcfg.microbatches,
        "remat": pcfg.remat,
        "fsdp": pcfg.fsdp,
    }
    return lowered, compiled, info


def run_cell(arch, shape_name, multi_pod, out_dir: Path | None,
             save_hlo=True, pcfg=None, variant=""):
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    tag = f"{arch}.{shape_name}.{'pod2' if multi_pod else 'pod1'}"
    if variant:
        tag += f".{variant}"
    if not ok:
        print(f"[dryrun] {tag}: {why}")
        return {"arch": arch, "shape": shape_name, "skipped": why}
    lowered, compiled, info = lower_cell(arch, shape_name, mesh, pcfg=pcfg)
    print(f"[dryrun] {tag}: compile={info['compile_s']}s "
          f"peak/device={info['memory']['peak_bytes_per_device']/2**30:.2f}GiB "
          f"flops={info['cost'].get('flops', 0):.3e}")
    if out_dir is not None:
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{tag}.json").write_text(json.dumps(info, indent=1))
        if save_hlo:
            import gzip
            hlo = compiled.as_text()
            with gzip.open(out_dir / f"{tag}.hlo.gz", "wt") as f:
                f.write(hlo)
    return info


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-hlo", action="store_true")
    # §Perf hillclimb overrides — lower a variant without touching presets
    ap.add_argument("--variant", default="",
                    help="tag for output files of an overridden config")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--seq-shard-acts", action="store_true", default=None)
    ap.add_argument("--fsdp", type=int, default=None)
    ap.add_argument("--expert-ff-2d", type=int, default=None)
    args = ap.parse_args()

    out = Path(args.out)
    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                pcfg = None
                if any(v is not None for v in (
                        args.remat, args.microbatches, args.seq_shard_acts,
                        args.fsdp, args.expert_ff_2d)):
                    import dataclasses
                    base = default_pcfg(arch, shape_name)
                    kw = {}
                    if args.remat is not None:
                        kw["remat"] = args.remat
                    if args.microbatches is not None:
                        kw["microbatches"] = args.microbatches
                    if args.seq_shard_acts is not None:
                        kw["seq_shard_activations"] = args.seq_shard_acts
                    if args.fsdp is not None:
                        kw["fsdp"] = bool(args.fsdp)
                    if args.expert_ff_2d is not None:
                        kw["expert_ff_2d"] = bool(args.expert_ff_2d)
                    pcfg = dataclasses.replace(base, **kw)
                try:
                    results.append(run_cell(arch, shape_name, mp, out,
                                            save_hlo=not args.no_hlo,
                                            pcfg=pcfg,
                                            variant=args.variant))
                except Exception as e:  # noqa: BLE001 - report and continue
                    print(f"[dryrun] {arch}.{shape_name}."
                          f"{'pod2' if mp else 'pod1'}: FAILED {e}")
                    results.append({"arch": arch, "shape": shape_name,
                                    "multi_pod": mp, "error": str(e)})
    n_fail = sum(1 for r in results if "error" in r)
    print(f"[dryrun] done: {len(results)} cells, {n_fail} failures")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
