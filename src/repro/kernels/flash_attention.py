"""Flash attention as a Pallas TPU kernel.

Layout: grid = (batch * q_heads, num_q_blocks, num_kv_blocks) with the kv
block as the minormost (sequential) dim; an (m, l, acc) streaming-softmax
state lives in VMEM scratch and survives across kv iterations because the
output BlockSpec ignores the kv grid index. Causal + sliding-window masks
skip fully-masked kv blocks via ``pl.when``. GQA uses the repo-wide g-major
convention: q head h reads kv head ``h % K``.

Block shapes: (BLOCK_Q x head_dim) q tiles and (BLOCK_KV x head_dim) kv
tiles — head_dim is 64..128 for every assigned arch, so tiles are MXU-aligned
(multiples of (8,128) lanes) and the VMEM working set is
BLOCK_Q*(hd + BLOCK_KV) * 4B ≈ 2.2 MiB at the defaults, well under ~16 MiB.

Backward: custom_vjp with a recompute-based flash backward (no O(S^2)
residuals; dq/dk/dv from (q,k,v,o,lse,do) in blocked jnp). The Pallas
forward returns lse for exactly this purpose — the production pattern.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
NEG_INF = -1.0e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, cap, causal, window, block_q, block_kv,
                kv_len, q_offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)

    def compute():
        q = q_ref[...].astype(jnp.float32)         # (block_q, hd)
        k = k_ref[...].astype(jnp.float32)         # (block_kv, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        mask = k_pos < kv_len
        if causal:
            d = q_pos - k_pos
            mask &= d >= 0
            if window is not None:
                mask &= d < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[...].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # skip kv blocks strictly after this q block's last position
        first_q = q_offset + qi * block_q
        last_q = first_q + block_q - 1
        first_k = ki * block_kv
        live = first_k <= last_q
        if window is not None:
            last_k = first_k + block_kv - 1
            live &= last_k > first_q - window
        pl.when(live)(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(l)


def _flash_fwd(q, k, v, *, causal, window, cap, scale, q_offset,
               block_q, block_kv, interpret):
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    nq = -(-Sq // block_q)
    nk = -(-Skv // block_kv)
    pad_q = nq * block_q - Sq
    pad_kv = nk * block_kv - Skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))

    # (B, S, H, hd) -> (B*H, S, hd) with g-major q->kv head mapping
    qb = q.transpose(0, 2, 1, 3).reshape(B * H, nq * block_q, hd)
    kb = k.transpose(0, 2, 1, 3).reshape(B * K, nk * block_kv, hd)
    vb = v.transpose(0, 2, 1, 3).reshape(B * K, nk * block_kv, hd)

    def kv_index(bh, qi, ki):
        b, h = bh // H, bh % H
        return (b * K + h % K, ki, 0)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, cap=cap, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, kv_len=Skv, q_offset=q_offset)

    o, lse = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, block_kv, hd), kv_index),
            pl.BlockSpec((None, block_kv, hd), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            # lse as a (block_q, 1) column: a (block_q,) row block of a
            # (B*H, S) array is not a legal Mosaic tile
            pl.BlockSpec((None, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, nq * block_q, hd), q.dtype),
            jax.ShapeDtypeStruct((B * H, nq * block_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb)

    o = o.reshape(B, H, nq * block_q, hd).transpose(0, 2, 1, 3)[:, :Sq]
    lse = lse[..., 0].reshape(B, H, nq * block_q).transpose(0, 2, 1)[:, :Sq]
    return o, lse


# ---------------------------------------------------------------------------
# Backward (recompute; blocked jnp — no O(S^2) residuals stored)
# ---------------------------------------------------------------------------


def _bwd_ref(q, k, v, o, lse, do, *, causal, window, cap, scale, q_offset):
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    q32 = q.astype(jnp.float32).reshape(B, Sq, G, K, hd)
    k32 = k.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    do32 = do.astype(jnp.float32).reshape(B, Sq, G, K, hd)
    o32 = o.astype(jnp.float32).reshape(B, Sq, G, K, hd)
    lse_g = lse.reshape(B, Sq, G, K)

    u = jnp.einsum("bqgkh,bskh->bqgks", q32, k32) * scale
    if cap is not None:
        z = cap * jnp.tanh(u / cap)
        dz_du = 1.0 - jnp.square(z / cap)
    else:
        z = u
        dz_du = None
    if causal:
        d = (q_offset + jnp.arange(Sq))[:, None] - jnp.arange(Skv)[None, :]
        ok = d >= 0
        if window is not None:
            ok &= d < window
        z = jnp.where(ok[None, :, None, None, :], z, NEG_INF)
    p = jnp.exp(z - lse_g[..., None])
    dv = jnp.einsum("bqgks,bqgkh->bskh", p, do32)
    dp = jnp.einsum("bqgkh,bskh->bqgks", do32, v32)
    delta = jnp.sum(do32 * o32, axis=-1)                  # (B,Sq,G,K)
    ds = p * (dp - delta[..., None])
    if dz_du is not None:
        ds = ds * dz_du
    ds = ds * scale
    dq = jnp.einsum("bqgks,bskh->bqgkh", ds, k32)
    dk = jnp.einsum("bqgks,bqgkh->bskh", ds, q32)
    return (dq.reshape(B, Sq, H, hd).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype))


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def flash_attention(q, k, v, causal=True, window=None, cap=None, scale=None,
                    q_offset=0, block_q=DEFAULT_BLOCK_Q,
                    block_kv=DEFAULT_BLOCK_KV, interpret=False):
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    o, _ = _flash_fwd(q, k, v, causal=causal, window=window, cap=cap,
                      scale=scale, q_offset=q_offset, block_q=block_q,
                      block_kv=block_kv, interpret=interpret)
    return o


def _vjp_fwd(q, k, v, causal, window, cap, scale, q_offset, block_q,
             block_kv, interpret):
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    o, lse = _flash_fwd(q, k, v, causal=causal, window=window, cap=cap,
                        scale=scale, q_offset=q_offset, block_q=block_q,
                        block_kv=block_kv, interpret=interpret)
    return o, (q, k, v, o, lse)


def _vjp_bwd(causal, window, cap, scale, q_offset, block_q, block_kv,
             interpret, res, do):
    q, k, v, o, lse = res
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    dq, dk, dv = _bwd_ref(q, k, v, o, lse, do, causal=causal, window=window,
                          cap=cap, scale=scale, q_offset=q_offset)
    return dq, dk, dv


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
