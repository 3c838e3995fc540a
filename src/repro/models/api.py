"""Family-dispatching model API + synthetic batch builders.

``input_specs`` (launch/dryrun.py) builds ShapeDtypeStruct stand-ins from the
same ``batch_shapes`` used here, so smoke tests and the dry-run cannot drift
apart. Modality frontends (audio conv / vision patches) are stubs: the batch
carries precomputed frame/patch-position embeddings, per the assignment.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, ParallelConfig, ShapeConfig
from repro.models import encdec, transformer


def is_encdec(cfg: ModelConfig) -> bool:
    return cfg.encoder_layers > 0


def init_model(cfg: ModelConfig, key):
    if is_encdec(cfg):
        return encdec.init_encdec(cfg, key)
    return transformer.init_lm(cfg, key)


def init_params_bf16(cfg: ModelConfig, key):
    """Random bf16 weights in one jitted program: every leaf is drawn in
    fp32 and cast inside it, so the fp32 tree never exists as a whole —
    for a 3B-parameter model that tree alone would be 12 GB beside the
    6 GB bf16 one."""
    def init(key):
        params, _ = init_model(cfg, key)
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)

    return jax.jit(init)(key)


def loss_fn(params, batch, cfg: ModelConfig, pcfg: ParallelConfig,
            sampled_ids=None):
    if is_encdec(cfg):
        return encdec.forward_loss(params, batch, cfg, pcfg)
    return transformer.forward_loss(params, batch, cfg, pcfg, sampled_ids)


def prefill_fn(params, batch, cfg: ModelConfig, pcfg: ParallelConfig):
    if is_encdec(cfg):
        return encdec.prefill(params, batch, cfg, pcfg)
    return transformer.prefill(params, batch, cfg, pcfg)


def decode_fn(params, cache, batch, cfg: ModelConfig, pcfg: ParallelConfig):
    if is_encdec(cfg):
        return encdec.decode_step(params, cache, batch, cfg, pcfg)
    return transformer.decode_step(params, cache, batch, cfg, pcfg)


def abstract_params(cfg: ModelConfig):
    """(ShapeDtypeStruct tree, logical specs) without allocating anything."""
    captured = {}

    def f():
        p, s = init_model(cfg, jax.random.key(0))
        captured["specs"] = s
        return p

    shapes = jax.eval_shape(f)
    return shapes, captured["specs"]


def init_cache_shapes(cfg: ModelConfig, B: int, S: int):
    """Abstract cache pytree (no allocation)."""
    if is_encdec(cfg):
        L, K, hd, Te = (cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
                        cfg.encoder_seq_len)
        bf = jnp.bfloat16
        return {
            "k": jax.ShapeDtypeStruct((L, B, S, K, hd), bf),
            "v": jax.ShapeDtypeStruct((L, B, S, K, hd), bf),
            "xk": jax.ShapeDtypeStruct((L, B, Te, K, hd), bf),
            "xv": jax.ShapeDtypeStruct((L, B, Te, K, hd), bf),
        }
    return jax.eval_shape(
        lambda: transformer.init_cache(cfg, B, S))


def init_cache(cfg: ModelConfig, B: int, S: int):
    """Concrete zero cache (smoke tests)."""
    if is_encdec(cfg):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            init_cache_shapes(cfg, B, S))
    return transformer.init_cache(cfg, B, S)


# ---------------------------------------------------------------------------
# Batch shapes (shared by smoke tests and the dry-run)
# ---------------------------------------------------------------------------


def batch_shapes(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """name -> (shape, dtype) for every model input except the cache."""
    B, S = shape.global_batch, shape.seq_len
    i32, bf = jnp.int32, jnp.bfloat16
    if shape.kind in ("train", "prefill"):
        d = {"tokens": ((B, S), i32)}
        if shape.kind == "train":
            d["labels"] = ((B, S), i32)
        if cfg.frontend == "audio":
            d["frames"] = ((B, cfg.encoder_seq_len, cfg.d_model), bf)
        if cfg.frontend == "vision":
            d["positions"] = ((3, B, S), i32)
        return d
    # decode: one token against an S-length cache
    return {"token": ((B, 1), i32), "pos": ((B,), i32)}


def make_batch(cfg: ModelConfig, shape: ShapeConfig, seed: int = 0) -> dict:
    """Concrete synthetic batch (numpy RNG; host side)."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, (shp, dt) in batch_shapes(cfg, shape).items():
        if dt == jnp.int32:
            if name == "pos":
                out[name] = jnp.full(shp, shape.seq_len - 1, jnp.int32)
            elif name == "positions":
                B, S = shp[1], shp[2]
                out[name] = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32)[None, None], shp)
            else:
                out[name] = jnp.asarray(
                    rng.integers(0, cfg.vocab_size, shp), jnp.int32)
        else:
            out[name] = jnp.asarray(rng.normal(0, 1, shp), jnp.float32
                                    ).astype(dt)
    return out
