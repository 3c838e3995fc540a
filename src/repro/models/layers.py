"""Norms, rotary embeddings (incl. M-RoPE) and MLP blocks."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import modules as m

# ---------------------------------------------------------------------------
# Norms — computed in fp32, cast back.
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, dim: int | None = None):
    dim = dim or cfg.d_model
    if cfg.norm == "layernorm":
        p, s = m.merge(m.named("scale", m.ones_init((dim,), ("embed",))),
                       m.named("bias", m.zeros_init((dim,), ("embed",))))
    else:
        p, s = m.named("scale", m.ones_init((dim,), ("embed",)))
    return p, s


def apply_norm(params, x, cfg: ModelConfig, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"] + params["bias"]
    else:
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(dt)


def rms_norm_fp32(x, scale, eps: float = 1e-6):
    """Bare RMS-norm used for qk-norm / gated SSM norm."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (y * scale).astype(dt)


# ---------------------------------------------------------------------------
# RoPE (+ M-RoPE for qwen2-vl)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_freqs(head_dim: int, theta: float, scaling) -> jnp.ndarray:
    """YaRN's inverse frequencies (``scaling``: a ``config.YarnScaling``),
    as the published definition computes them: the band between the
    dimensions that turn ``beta_fast`` and ``beta_slow`` times over
    ``original_max_positions`` (bounds truncated to integers, floor and
    ceil) ramps linearly from the plain frequency to it over ``factor``."""
    def dim_at(rotations):
        return (head_dim * math.log(scaling.original_max_positions
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim_at(scaling.beta_fast)), 0)
    hi = min(math.ceil(dim_at(scaling.beta_slow)), head_dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    inv = rope_freqs(head_dim, theta)
    return inv / scaling.factor * ramp + inv * (1.0 - ramp)


def rope_cos_sin(positions, head_dim: int, theta: float,
                 sections: tuple[int, ...] | None = None, scaling=None):
    """cos/sin tables.

    positions: (B, S) int32, or (3, B, S) for M-RoPE where the three planes
    are temporal / height / width position ids. With M-RoPE, frequency slots
    are split into ``sections`` groups (sizes in half-dim units), each group
    indexed by its own plane — the qwen2-vl scheme. ``scaling`` (a
    ``config.YarnScaling``) takes YaRN's frequencies and multiplies both
    tables by its ``attention_factor``.
    """
    inv = (rope_freqs(head_dim, theta) if scaling is None
           else yarn_freqs(head_dim, theta, scaling))      # (hd/2,)
    if positions.ndim == 2:
        ang = positions[..., None].astype(jnp.float32) * inv   # (B,S,hd/2)
    else:
        assert sections is not None and sum(sections) == head_dim // 2
        ang_all = positions[..., None].astype(jnp.float32) * inv  # (3,B,S,hd/2)
        parts, start = [], 0
        for i, sec in enumerate(sections):
            parts.append(ang_all[i, :, :, start:start + sec])
            start += sec
        ang = jnp.concatenate(parts, axis=-1)              # (B,S,hd/2)
    if scaling is not None:
        a = scaling.attention_factor
        return jnp.cos(ang) * a, jnp.sin(ang) * a
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, hd); cos/sin: (B, S, hd/2). Rotate-half convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

_ACT = {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True)}


def init_mlp(cfg: ModelConfig, key):
    ks = m.split_keys(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_activation == "gelu_mlp":  # ungated 2-matrix MLP
        return m.merge(
            m.named("w_in", m.dense_init(ks[0], (d, f), ("embed", "ff"))),
            m.named("w_out", m.dense_init(ks[1], (f, d), ("ff", "embed"))),
        )
    return m.merge(
        m.named("w_gate", m.dense_init(ks[0], (d, f), ("embed", "ff"))),
        m.named("w_in", m.dense_init(ks[1], (d, f), ("embed", "ff"))),
        m.named("w_out", m.dense_init(ks[2], (f, d), ("ff", "embed"))),
    )


def apply_mlp(params, x, cfg: ModelConfig):
    w = {k: v.astype(x.dtype) for k, v in params.items()}
    if cfg.mlp_activation == "gelu_mlp":
        h = _ACT["gelu"](jnp.einsum("bsd,df->bsf", x, w["w_in"]))
        return jnp.einsum("bsf,fd->bsd", h, w["w_out"])
    act = _ACT[cfg.mlp_activation]
    g = act(jnp.einsum("bsd,df->bsf", x, w["w_gate"]))
    h = g * jnp.einsum("bsd,df->bsf", x, w["w_in"])
    return jnp.einsum("bsf,fd->bsd", h, w["w_out"])


def softcap(x, cap: float | None):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)
