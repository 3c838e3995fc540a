"""Compile a cell's step programs for a described TPU v5e, without a chip,
and print their memory: arguments, outputs, temporaries.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py \\
        --workload starcoder2_3b.ide_completion [--num-blocks N] [--reference]

Nothing runs, so this says nothing about results or times; it sizes the
page pool. For each of the cell's step programs (with and without a chunk;
with the full sampling pipeline too where the traffic samples) it prints
the bytes, and compiles the plain step at a second pool size to show
whether the temporaries grow with the pool. ``--reference`` also compiles
the plain reference at the cell's longest sequence (the configuration's
own reference module, through its ``hidden``).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GB = 1e9


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--num-blocks", type=int, default=None)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--reference", action="store_true")
    ap.add_argument("--scale-check", action="store_true",
                    help="also compile the plain step at half the pool")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    jax.config.update("jax_enable_compilation_cache", False)
    from chipbench import engine_run, reference, spec
    from repro.config import ParallelConfig
    from repro.kernels import ops
    from repro.models import api
    from repro.serving.engine import InferenceEngine
    from repro.serving.runners import make_runner
    from repro.serving.scheduler import StepPlan

    ops._use_pallas = lambda: "compiled"     # the chip's path, not the CPU's
    cell = spec.Cell(args.workload, bench=spec.benchmark())
    cfg = engine_run.program_config(cell)
    e = dict(cell.engine)
    if args.num_blocks:
        e["num_blocks"] = args.num_blocks
    if args.max_batch:
        e["max_batch"] = args.max_batch
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    rep = NamedSharding(mesh, PartitionSpec())

    def sds(tree, dtype=None):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=rep), tree)

    params = sds(api.abstract_params(cfg)[0], jnp.bfloat16)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"weights: {n_params} parameters, {2 * n_params / GB:.3f} GB bf16")
    runner = make_runner(cfg, ParallelConfig(remat="none"))
    B, bs = e["max_batch"], e["block_size"]
    nbmax = -(-e["max_len"] // bs)
    fake = SimpleNamespace(max_batch=B, chunk_width=e["chunk"],
                           max_blocks_per_seq=nbmax, prefill_pack=1,
                           samp_buf=SimpleNamespace(vocab_size=cfg.vocab_size),
                           bm=None)
    sampled = cell.traffic.get("sampling", {}).get("temperature", 0.0) > 0

    def compile_step(num_blocks, has_chunk, full):
        cache = sds(jax.eval_shape(lambda: runner.init_cache(
            num_blocks, bs, B, kv_dtype=e["kv_dtype"])))
        arrays = sds(jax.eval_shape(lambda: InferenceEngine._build_arrays(
            fake, StepPlan([], [], []), full)))
        fn = jax.jit(functools.partial(runner.step, has_chunk=has_chunk,
                                       full_sampling=full),
                     donate_argnums=(1,))
        with jax.set_mesh(mesh):
            c = fn.lower(params, cache, arrays).compile()
        m = c.memory_analysis()
        pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
        print(f"step chunk={has_chunk} full={full} num_blocks={num_blocks}: "
              f"pool {pool / GB:.3f} GB, arguments "
              f"{m.argument_size_in_bytes / GB:.3f} GB, outputs "
              f"{m.output_size_in_bytes / GB:.3f} GB, aliased "
              f"{m.alias_size_in_bytes / GB:.3f} GB, temporaries "
              f"{m.temp_size_in_bytes / GB:.3f} GB, kernels "
              f"{c.as_text().count('tpu_custom_call')}", flush=True)
        return m

    for has_chunk in (True, False):
        for full in ((False, True) if sampled else (False,)):
            compile_step(e["num_blocks"], has_chunk, full)
    if args.scale_check:
        compile_step(e["num_blocks"] // 2 + 1, False, False)
    if args.reference:
        T = -(-e["max_len"] // 1024) * 1024
        P = -(-cell.traffic["output"]["max"] // 128) * 128
        sz_json = json.dumps(cell.config["sizes"], sort_keys=True)
        hidden = spec.load_reference(cell).hidden
        for control in (False, True):
            fn = functools.partial(reference._gaps.__wrapped__,
                                   sz_json=sz_json, control=control,
                                   hidden_fn=hidden)
            i32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32,  # noqa
                                                 sharding=rep)
            with jax.default_matmul_precision("highest"):
                c = jax.jit(fn).lower(params, i32(T), i32(P),
                                      i32(P)).compile()
            m = c.memory_analysis()
            print(f"reference control={control} T={T} P={P}: temporaries "
                  f"{m.temp_size_in_bytes / GB:.3f} GB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
