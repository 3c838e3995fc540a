"""Quantized KV-page tests: quantize→dequantize error bounds per dtype,
and the fused-dequant attention paths — a quantized pool + scale sidecar
fed to the op must be *bit-identical* to dequantizing the pool by hand
and calling the same op, because every path round-trips through the one
``dequantize_kv`` convention before the attention math."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.quant import (KV_DTYPES, QMAX, dequantize_kv,
                                is_quantized, kv_dtype_bytes, kv_dtype_name,
                                quantize_kv)

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# Round-trip error bounds
# ---------------------------------------------------------------------------


# int8: half-step rounding error <= amax/254, plus bf16 output rounding
# (~2^-8 relative). fp8 e4m3: 3 mantissa bits, half-ulp relative error
# 2^-4 of the element, <= amax elementwise.
@pytest.mark.parametrize("name,err_frac", [("int8", 0.01), ("fp8", 0.07)])
def test_roundtrip_error_bound(name, err_frac):
    x = jnp.asarray(RNG.normal(0, 3, (5, 7, 2, 32)),
                    jnp.float32).astype(jnp.bfloat16)
    q, s = quantize_kv(x, name)
    assert q.dtype == KV_DTYPES[name]
    assert s.dtype == jnp.float32 and s.shape == x.shape[:-1] + (1,)
    deq = dequantize_kv(q, s)
    assert deq.dtype == jnp.bfloat16
    xf = np.asarray(x, np.float32)
    amax = np.max(np.abs(xf), -1, keepdims=True)
    err = np.abs(np.asarray(deq, np.float32) - xf)
    assert np.all(err <= err_frac * amax + 1e-6), float(np.max(err / amax))


def test_roundtrip_zero_rows_exact():
    q, s = quantize_kv(jnp.zeros((3, 4, 8), jnp.bfloat16), "int8")
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.asarray(s) > 0)          # eps-guarded, never 0
    assert np.all(np.asarray(dequantize_kv(q, s)) == 0)


def test_int8_symmetric_extremes_hit_qmax():
    q, s = quantize_kv(jnp.asarray([[1.0, -1.0, 0.5, -0.25]],
                                   jnp.bfloat16), "int8")
    qn = np.asarray(q, np.int32)
    assert qn[0, 0] == 127 and qn[0, 1] == -127     # symmetric full range
    np.testing.assert_allclose(np.asarray(s)[0, 0], 1.0 / 127.0, rtol=1e-6)


def test_dtype_helpers_roundtrip():
    for name, dt in KV_DTYPES.items():
        assert kv_dtype_name(dt) == name
        assert kv_dtype_bytes(name) == jnp.dtype(dt).itemsize
        assert is_quantized(name) == (name in QMAX)
    with pytest.raises(ValueError):
        kv_dtype_name(jnp.float64)


# ---------------------------------------------------------------------------
# Fused dequant in the attention ops
# ---------------------------------------------------------------------------


def _quant_case(name, B, K, hd, bs, nblk):
    N = 1 + B * nblk
    kp = jnp.asarray(RNG.normal(0, 1, (N, bs, K, hd)).transpose(0, 2, 1, 3),
                     jnp.float32).astype(jnp.bfloat16)
    vp = jnp.asarray(RNG.normal(0, 1, (N, bs, K, hd)).transpose(0, 2, 1, 3),
                     jnp.float32).astype(jnp.bfloat16)
    qk, sk = quantize_kv(kp, name)
    qv, sv = quantize_kv(vp, name)
    perm = RNG.permutation(np.arange(1, N))[:B * nblk].reshape(B, nblk)
    bt = jnp.asarray(perm, jnp.int32)
    ctx = jnp.asarray(RNG.integers(1, nblk * bs + 1, (B,)), jnp.int32)
    return (dequantize_kv(qk, sk), dequantize_kv(qv, sv),
            qk, sk, qv, sv, bt, ctx)


@pytest.mark.parametrize("name", ["int8", "fp8"])
def test_paged_decode_fused_dequant_bit_identical(name):
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref
    B, H, K, hd, bs, nblk = 3, 4, 2, 16, 8, 4
    dk, dv, qk, sk, qv, sv, bt, ctx = _quant_case(name, B, K, hd, bs, nblk)
    q = jnp.asarray(RNG.normal(0, 1, (B, H, hd)),
                    jnp.float32).astype(jnp.bfloat16)
    o_pre = paged_attention_ref(q, dk, dv, bt, ctx)
    o_fused = paged_attention_ref(q, qk, qv, bt, ctx,
                                  k_scale=sk, v_scale=sv)
    np.testing.assert_array_equal(np.asarray(o_fused, np.float32),
                                  np.asarray(o_pre, np.float32))
    # the interpret-mode kernel fuses the same dequant convention
    o_k = paged_attention(q, qk, qv, bt, ctx, k_scale=sk, v_scale=sv,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_pre, np.float32), atol=2e-2)


@pytest.mark.parametrize("name", ["int8", "fp8"])
def test_paged_chunk_fused_dequant_bit_identical(name):
    from repro.kernels import ops as kops
    B, H, K, hd, bs, nblk, C = 2, 4, 2, 16, 8, 4, 8
    dk, dv, qk, sk, qv, sv, bt, _ = _quant_case(name, B, K, hd, bs, nblk)
    q = jnp.asarray(RNG.normal(0, 1, (B, C, H, hd)),
                    jnp.float32).astype(jnp.bfloat16)
    qlen = jnp.asarray([C, C - 3], jnp.int32)
    ctx = jnp.asarray([C + 5, C], jnp.int32)
    o_pre = kops.paged_prefill_attention(q, dk, dv, bt, ctx, qlen)
    o_fused = kops.paged_prefill_attention(q, qk, qv, bt, ctx, qlen,
                                           k_scale=sk, v_scale=sv)
    np.testing.assert_array_equal(np.asarray(o_fused, np.float32),
                                  np.asarray(o_pre, np.float32))
