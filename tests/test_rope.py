"""Rotary tables: YaRN against an independent numpy transcription of the
published definition, and each layer kind rotating with its own table."""

import math

import jax.numpy as jnp
import numpy as np

from repro.config import YarnScaling, get_config
from repro.models import transformer
from repro.models.layers import rope_cos_sin, rope_freqs

# Mellum2-12B-A2.5B's full-attention rope parameters
HD, THETA = 128, 500000.0
YARN = YarnScaling(factor=16.0, original_max_positions=8192, beta_fast=32.0,
                   beta_slow=1.0, attention_factor=1.2772588722239782)


def yarn_numpy(hd, theta, factor, original, beta_fast, beta_slow):
    """transformers' ``_compute_yarn_parameters``, in float64 numpy."""
    def correction_dim(rotations):
        return hd * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), hd - 1)
    pos_freqs = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    extrapolation, interpolation = 1 / pos_freqs, 1 / (factor * pos_freqs)
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    return (interpolation * (1 - extrapolation_factor)
            + extrapolation * extrapolation_factor)


def test_yarn_table_matches_the_published_formula():
    inv = yarn_numpy(HD, THETA, 16.0, 8192, 32.0, 1.0)
    # the band: dims below 18 keep their frequency, from 35 on divided by 16
    assert inv[17] == 1 / THETA ** (34 / HD)
    np.testing.assert_allclose(inv[35:] * 16, 1 / THETA ** (
        np.arange(70, HD, 2) / HD), rtol=1e-12)
    pos = np.array([[0, 1, 17, 1000, 2047, 4095]], np.int32)
    cos, sin = rope_cos_sin(jnp.asarray(pos), HD, THETA, scaling=YARN)
    ang = pos[..., None].astype(np.float64) * inv
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang) * YARN.attention_factor,
                               atol=2e-3, rtol=0)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang) * YARN.attention_factor,
                               atol=2e-3, rtol=0)
    # the frequencies themselves, to float32
    got = np.asarray(rope_cos_sin(jnp.ones((1, 1), jnp.int32), HD, THETA,
                                  scaling=YarnScaling(16.0, 8192))[1])
    np.testing.assert_allclose(np.arcsin(got[0, 0]), inv, rtol=2e-6)


def test_no_scaling_is_the_plain_table():
    pos = jnp.arange(64, dtype=jnp.int32)[None]
    cos, sin = rope_cos_sin(pos, HD, THETA)
    ang = pos[..., None].astype(jnp.float32) * rope_freqs(HD, THETA)
    np.testing.assert_array_equal(np.asarray(cos), np.asarray(jnp.cos(ang)))
    np.testing.assert_array_equal(np.asarray(sin), np.asarray(jnp.sin(ang)))


def test_each_layer_kind_rotates_with_its_own_table():
    """Sliding ("local") layers see the plain table at rope_theta, full
    ("attn") layers the YaRN one; a config without scaling builds one
    table that every kind shares."""
    cfg = get_config("mellum2_12b_a2p5b", smoke=True)
    assert cfg.layer_kinds() == ("local", "local", "local", "attn")
    pos = jnp.arange(40, dtype=jnp.int32)[None]
    ctx = {"cos_sin": transformer._rope_tables(cfg, pos)}
    plain = rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)
    yarn = rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta,
                        scaling=cfg.rope_scaling)
    for got, want in ((transformer._rope(ctx, "local"), plain),
                      (transformer._rope(ctx, "attn"), yarn)):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert not np.allclose(np.asarray(plain[0]), np.asarray(yarn[0]))
    dense = get_config("gemma2_27b", smoke=True)
    one = transformer._rope_tables(dense, pos)
    ctx = {"cos_sin": one}
    assert isinstance(one, tuple)
    assert transformer._rope(ctx, "local") is transformer._rope(ctx, "attn")
