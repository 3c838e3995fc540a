"""Quantized KV-cache representations (int8 / fp8 page pools).

The serving page pools can be stored in a narrow dtype with a per-row
fp32 scale carried alongside each pool leaf ("k_scale" / "v_scale" next
to "k" / "v" in every layer-stack dict).  A row here is one (token,
kv-head) vector of head_dim values: symmetric absmax scaling over the
head dim keeps the quantizer a pure elementwise function of the bf16
input, so the repo-wide rounding convention still holds — bit-identical
bf16 K/V across prefill/chunk/decode quantizes to bit-identical int8
pages, and the prefix-cache / COW / preemption byte-identity story
survives quantization unchanged (equivalence vs bf16 itself is
tolerance-based, pinned by tests).

Scale layout: pool leaf (NP, num_blocks, K, block_size, hd) gets a
scale leaf (NP, num_blocks, K, block_size, 1) in fp32 — rank-5 with
num_blocks at axis 1 and kv heads at axis 2, so the engine's
block-indexed copy/COW/swap helpers and the kv-head sharding treat value
and scale leaves uniformly (``serving.kv_cache.pool_shape``).

Dequantization always round-trips through bf16 — (q.f32 * scale).bf16 —
before entering the attention matmuls, in kernels, XLA mirrors and
oracles alike, so every path sees the same dequantized operands.
"""

from __future__ import annotations

import jax.numpy as jnp

# Serving KV dtypes by CLI/engine name.  fp8 support depends on the
# backend; jnp.float8_e4m3fn exists on every jax we target, but real
# MXU support is TPU-generation dependent — the kernels dequantize to
# bf16 before the matmul either way.
KV_DTYPES = {
    "bf16": jnp.bfloat16,
    "int8": jnp.int8,
    "fp8": jnp.float8_e4m3fn,
}

# Largest representable magnitude per quantized dtype (symmetric).
QMAX = {"int8": 127.0, "fp8": 448.0}

# Guards the absmax so all-zero rows get scale eps/qmax, not 0 (a zero
# scale would turn dequant into 0*inf on any later nonzero write).
_AMAX_EPS = 1e-6


def is_quantized(kv_dtype: str) -> bool:
    return kv_dtype in QMAX


def kv_dtype_bytes(kv_dtype: str) -> int:
    """Bytes per pool element for a serving kv dtype name."""
    return jnp.dtype(KV_DTYPES[kv_dtype]).itemsize


def kv_dtype_name(dtype) -> str:
    """Serving kv-dtype name for a pool leaf dtype (inverse of KV_DTYPES)."""
    d = jnp.dtype(dtype)
    for name, dt in KV_DTYPES.items():
        if jnp.dtype(dt) == d:
            return name
    raise ValueError(f"not a serving kv dtype: {dtype}")


def quantize_kv(x, kv_dtype: str):
    """Quantize new K/V rows to the pool dtype.

    x: (..., hd) bf16/f32.  Returns (q (..., hd) narrow dtype,
    scale (..., 1) fp32).  Symmetric per-row absmax over the head dim;
    deterministic round-half-away handled by jnp.round for int8 and the
    hardware cast for fp8.
    """
    qmax = QMAX[kv_dtype]
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, _AMAX_EPS) / qmax
    y = xf / scale
    if kv_dtype == "int8":
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    else:
        q = jnp.clip(y, -qmax, qmax).astype(KV_DTYPES[kv_dtype])
    return q, scale


def dequantize_kv(q, scale, out_dtype=jnp.bfloat16):
    """Inverse of quantize_kv: (q (..., hd), scale (..., 1)) -> bf16.

    The bf16 round-trip is load-bearing: kernels, XLA mirrors and the
    oracles all dequantize exactly this way so their attention inputs
    are bit-identical.
    """
    return (q.astype(jnp.float32) * scale).astype(out_dtype)
