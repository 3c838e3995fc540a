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
