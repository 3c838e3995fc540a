"""Compile the serving path's Pallas kernels for a TPU v5e at starcoder2_3b's
published widths (the grouped matmul at Mellum2's), without a chip.

Interpret mode (every other kernel test) never applies Mosaic's tiling rules;
this file does: each kernel is lowered and compiled for a described v5e and
the compiled program must hold the kernel (``tpu_custom_call``). Nothing
runs, so these tests say nothing about results — the interpret-mode tests
and ``chip_smoke.py`` do.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU compiler library, and it keeps it until
it exits.
"""

from __future__ import annotations

import ast
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.kernels import embedding as emb
from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pa

CFG = get_config("starcoder2_3b", smoke=False)
H, K, HD = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
# the serving defaults chip_smoke.py runs: block_size 16, max_len 2048,
# max_batch 8, a 32-token prefill chunk; a ragged pack of 4 in 128 rows
BS, NB, B, C, T, S = 16, 128, 8, 32, 128, 4
N = B * NB + 1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    was = jax.config.jax_enable_compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # else libtpu logs under /tmp
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _decode(kv_dtype):
    with_scales = kv_dtype != jnp.bfloat16

    def f(q, kp, vp, bt, ctx, *scales):
        ks, vs = scales if with_scales else (None, None)
        return pa.paged_attention(q, kp, vp, bt, ctx, k_scale=ks,
                                  v_scale=vs)

    shapes = [((B, H, HD), jnp.bfloat16), ((N, K, BS, HD), kv_dtype),
              ((N, K, BS, HD), kv_dtype), ((B, NB), jnp.int32),
              ((B,), jnp.int32)]
    if with_scales:
        shapes += [((N, K, BS, 1), jnp.float32)] * 2
    return f, shapes


def _ragged(fused):
    def f(q, kp, vp, bt, ctx, starts, ends, *new):
        k_new, v_new = new if fused else (None, None)
        return pa.ragged_paged_prefill_attention(
            q, kp, vp, bt, ctx, starts, ends, k_new=k_new, v_new=v_new)

    shapes = [((T, H, HD), jnp.bfloat16), ((N, K, BS, HD), jnp.bfloat16),
              ((N, K, BS, HD), jnp.bfloat16), ((S, NB), jnp.int32),
              ((S,), jnp.int32), ((S,), jnp.int32), ((S,), jnp.int32)]
    if fused:
        shapes += [((T, K, HD), jnp.bfloat16)] * 2
    return f, shapes


KERNELS = {
    "paged_decode": lambda: _decode(jnp.bfloat16),
    "paged_decode_int8": lambda: _decode(jnp.int8),
    # whisper's 64-wide heads, which the decode kernel pads to 128 lanes
    "paged_decode_hd64": lambda: (
        pa.paged_attention,
        [((B, 20, 64), jnp.bfloat16), ((N, 20, BS, 64), jnp.bfloat16),
         ((N, 20, BS, 64), jnp.bfloat16), ((B, NB), jnp.int32),
         ((B,), jnp.int32)]),
    "paged_chunked_prefill": lambda: (
        pa.paged_prefill_attention,
        [((1, C, H, HD), jnp.bfloat16), ((N, K, BS, HD), jnp.bfloat16),
         ((N, K, BS, HD), jnp.bfloat16), ((1, NB), jnp.int32),
         ((1,), jnp.int32), ((1,), jnp.int32)]),
    "ragged_prefill": lambda: _ragged(False),
    "ragged_prefill_fused_write": lambda: _ragged(True),
    "embedding_gather": lambda: (
        emb.gather,
        [((CFG.padded_vocab_size, CFG.d_model), jnp.bfloat16),
         ((B, C), jnp.int32)]),
    "flash_forward": lambda: (
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        [((1, 512, H, HD), jnp.bfloat16), ((1, 512, K, HD), jnp.bfloat16),
         ((1, 512, K, HD), jnp.bfloat16)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _roofline_pattern(metric="paged_decode_roofline"):
    """``PATTERN`` of one of the benchmark's roofline metrics, read from its
    file (importing it would need the harness's package)."""
    path = (pathlib.Path(__file__).parents[1] / "benchmarks" / "chip" /
            "metrics" / f"{metric}.py")
    for node in ast.parse(path.read_text()).body:
        if isinstance(node, ast.Assign) and \
                getattr(node.targets[0], "id", None) == "PATTERN":
            return ast.literal_eval(node.value)
    raise AssertionError(f"no PATTERN in {path}")


# the benchmark cells' decode shapes: starcoder2_3b.ide_completion (batch
# 16, 24/2 heads) and glm4_9b-pp2.chat_batch (batch 32, 32/2 heads), both
# max_len 4096 at block 16
CELL_DECODES = {"ide_completion": (16, 24), "chat_batch": (32, 32)}


@pytest.mark.parametrize("cell", sorted(CELL_DECODES))
def test_decode_kernel_keeps_the_roofline_signature(cell, one_chip):
    """The compiled decode call at a cell's shapes is the custom call the
    benchmark's roofline metric matches in the device trace, whose event
    names print the operands with their shapes."""
    b, h = CELL_DECODES[cell]
    nb, n = 256, 256 * b + 1
    shapes = [((b, h, HD), jnp.bfloat16), ((n, K, BS, HD), jnp.bfloat16),
              ((n, K, BS, HD), jnp.bfloat16), ((b, nb), jnp.int32),
              ((b,), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(pa.paged_attention).lower(*args).compile()
    calls = _custom_calls(compiled)
    assert len(calls) == 1
    assert re.search(_roofline_pattern(), calls[0]), calls[0][:300]


def _custom_calls(compiled):
    """The compiled program's kernel calls as the device trace names them,
    operands printed with their shapes."""
    from jax._src.lib import _jax
    opts = _jax.HloPrintOptions()
    opts.print_operand_shape = True
    text = "\n".join(m.to_string(opts)
                     for m in compiled.runtime_executable().hlo_modules())
    return [line for line in text.splitlines() if "tpu_custom_call" in line]


# mellum2_12b-ep4.ide_completion's grouped matmuls: 16 held experts of
# 2304 x 896, 8 routed per token; a decode step's 16 rows, a 256-token chunk
GMM_SHAPES = {"decode_gate": (16 * 8, 2304, 896),
              "chunk_out": (256 * 8, 896, 2304)}


@pytest.mark.parametrize("name", sorted(GMM_SHAPES))
def test_grouped_matmul_compiles_with_the_roofline_signature(
        name, one_chip, monkeypatch):
    """megablox gmm at the tiles ``gmm_tiling`` takes from the shapes
    compiles for v5e, and is the custom call the benchmark's
    ``expert_gmm_roofline`` matches in the device trace."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_use_pallas", lambda: "compiled")
    m, k, n = GMM_SHAPES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
            (((m, k), jnp.bfloat16), ((16, k, n), jnp.bfloat16),
             ((16,), jnp.int32))]
    compiled = jax.jit(ops.grouped_matmul).lower(*args).compile()
    calls = _custom_calls(compiled)
    assert len(calls) == 1
    assert re.search(_roofline_pattern("expert_gmm_roofline"), calls[0]), \
        calls[0][:300]


# The serving step's layer scan carries the KV pools in place (one flat
# (layers·N, K, bs, hd) view per leaf; models.transformer._scan_periods).
# Compiled for v5e at published widths, cut in depth, each step program must
# hold no pool-sized copy, slice or relayout, and its temporaries must not
# grow with the pool. Each pool leaf is sized past v5e's 128 MiB of VMEM
# (134 and 268 MB), as every cell's is: a leaf that fits there is staged
# into VMEM and back by copies that a served pool never sees.
SCAN_CASES = {"starcoder2_3b": ("starcoder2_3b", 2, (8193, 16385)),
              "mellum2_12b-ep4": ("mellum2_12b_a2p5b", 4, (8193, 16385))}
SCAN_B, SCAN_C, SCAN_NB = 16, 256, 256       # the ide cells' batch and chunk


def _scan_config(arch, layers):
    import dataclasses
    cfg = dataclasses.replace(get_config(arch), num_layers=layers)
    if cfg.moe is not None:     # one chip's share: 16 of the 64 experts
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, experts_held=16))
    return cfg


def _argmax_sample(logits_d, logits_c, *_):
    rows = logits_d if logits_c is None else \
        jnp.concatenate([logits_d, logits_c])
    return jnp.argmax(rows, axis=-1)


def _compile_step(cfg, num_blocks, has_chunk, device):
    """runner.step as the engine jits it (cache donated), for v5e. Its
    sampling touches no pool and takes most of the compile time, so an
    argmax stands in for it."""
    import functools
    from types import SimpleNamespace
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.config import ParallelConfig
    from repro.models import api
    from repro.serving.engine import InferenceEngine
    from repro.serving.runners import make_runner
    from repro.serving.scheduler import StepPlan

    mesh = Mesh(np.array([device]).reshape(1, 1), ("data", "model"))
    rep = NamedSharding(mesh, PartitionSpec())

    def sds(tree, dtype=None):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=rep), tree)

    runner = make_runner(cfg, ParallelConfig(remat="none"))
    runner._sample = _argmax_sample
    engine = SimpleNamespace(
        max_batch=SCAN_B, chunk_width=SCAN_C, max_blocks_per_seq=SCAN_NB,
        prefill_pack=1, bm=None,
        samp_buf=SimpleNamespace(vocab_size=cfg.vocab_size))
    params = sds(api.abstract_params(cfg)[0], jnp.bfloat16)
    cache = sds(jax.eval_shape(
        lambda: runner.init_cache(num_blocks, BS, SCAN_B)))
    arrays = sds(jax.eval_shape(lambda: InferenceEngine._build_arrays(
        engine, StepPlan([], [], []), False)))
    fn = jax.jit(functools.partial(runner.step, has_chunk=has_chunk),
                 donate_argnums=(1,))
    with jax.set_mesh(mesh):
        return fn.lower(params, cache, arrays).compile()


_DTYPE_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "s8": 1, "u32": 4}


def _pool_sized_ops(compiled, min_bytes):
    """Instructions outside fused computations whose output has a pool's
    trailing (block_size, head_dim) dims and at least ``min_bytes``, and
    which do not leave the pool where it is: anything but a parameter,
    bitcast, tuple access, loop, or a fusion whose root is a scatter (the
    in-place KV write)."""
    text = compiled.as_text()
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    roots, computation = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            computation = head.group(1)
        elif line.lstrip().startswith("ROOT ") and computation in fused:
            roots[computation] = re.search(r"\} ([\w\-]+)\(", line)
    scatter = {c for c, m in roots.items() if m and m.group(1) == "scatter"}
    keep = {"parameter", "bitcast", "get-tuple-element", "tuple", "while"}
    found, computation = [], None
    instr = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]"
                       r"\S* ([\w\-]+)\(")
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            computation = head.group(1)
            continue
        m = instr.match(line)
        if computation in fused or not m:
            continue
        name, dtype, dims, op = m.groups()
        shape = [int(d) for d in dims.split(",")] if dims else []
        if shape[-2:] != [BS, HD] or op in keep:
            continue
        if int(np.prod(shape)) * _DTYPE_BYTES.get(dtype, 4) < min_bytes:
            continue
        called = re.search(r"calls=%([\w.\-]+)", line)
        if op == "fusion" and called and called.group(1) in scatter:
            continue
        found.append(f"{op} {name} {dtype}{shape}")
    return found


@pytest.mark.parametrize("has_chunk", [False, True], ids=["decode", "chunk"])
@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_layer_scan_keeps_the_pools_in_place(case, has_chunk, one_chip,
                                             monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_use_pallas", lambda: "compiled")
    arch, layers, sizes = SCAN_CASES[case]
    cfg = _scan_config(arch, layers)
    device = next(iter(one_chip.device_set))
    # one layer's k pool at the smaller size, and both sizes' whole pools
    leaf = lambda n: n * cfg.num_kv_heads * BS * cfg.head_dim * 2  # noqa
    n_stacks = 2 * len(cfg.block_pattern)                  # k, v per kind
    periods = layers // len(cfg.block_pattern)
    pools = [leaf(n) * n_stacks * periods for n in sizes]
    temps, programs = [], []
    for n in sizes:
        compiled = _compile_step(cfg, n, has_chunk, device)
        assert not _pool_sized_ops(compiled, leaf(sizes[0])), \
            _pool_sized_ops(compiled, leaf(sizes[0]))
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
        programs.append(compiled)
    assert abs(temps[1] - temps[0]) < 0.01 * (pools[1] - pools[0]), temps

    # the kernels keep the signatures the benchmark's rooflines match: one
    # decode call per attention layer of a period in each layer scan, one
    # chunk call too in the chunk's scan, three grouped matmuls per expert
    # layer in each scan, and no call matches two patterns or a wrong one
    patterns = {m: _roofline_pattern(m) for m in (
        "paged_decode_roofline", "paged_prefill_roofline",
        "expert_gmm_roofline")}
    scans = 2 if has_chunk else 1
    per_period = len(cfg.block_pattern)
    want = {"paged_decode_roofline": per_period,
            "paged_prefill_roofline": per_period if has_chunk else 0,
            "expert_gmm_roofline":
                3 * per_period * scans if cfg.moe is not None else 0}
    calls = _custom_calls(programs[0])
    hits = [[m for m, p in patterns.items() if re.search(p, c)]
            for c in calls]
    assert all(len(h) <= 1 for h in hits), hits
    got = {m: sum(h == [m] for h in hits) for m in patterns}
    assert got == want, (got, want)
