"""Prefill/decode cache correctness: decoding token t+1 from a prefilled
cache must equal running the full forward on the extended sequence. This is
the strongest single check of the KV-cache / SSM-state plumbing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ParallelConfig, get_config
from repro.models import api

RNG = np.random.default_rng(7)
PCFG = ParallelConfig(remat="none")


@pytest.mark.parametrize("arch", ["glm4_9b", "gemma2_27b", "mamba2_370m",
                                  "zamba2_2p7b", "qwen3_moe_30b_a3b",
                                  "mellum2_12b_a2p5b"])
def test_decode_equals_fresh_prefill(arch, tiny_mesh):
    """prefill(S) -> decode(token at S) must produce the same next token as
    prefill(S+1) on the extended sequence. Expert layers serve dropless, at
    the config's own capacity_factor."""
    cfg = get_config(arch, smoke=True)
    B, S = 2, 12
    toks = RNG.integers(0, cfg.vocab_size, (B, S + 1)).astype(np.int32)
    with jax.set_mesh(tiny_mesh):
        params, _ = api.init_model(cfg, jax.random.key(0))

        # ground truth: prefill the full S+1 prefix
        full_batch = {"tokens": jnp.asarray(toks)}
        if cfg.frontend == "vision":
            full_batch["positions"] = jnp.broadcast_to(
                jnp.arange(S + 1, dtype=jnp.int32)[None, None],
                (3, B, S + 1))
        _, tok_truth = api.prefill_fn(params, full_batch, cfg, PCFG)

        # prefill S tokens, then decode the (S+1)-th
        batch = {"tokens": jnp.asarray(toks[:, :S])}
        if cfg.frontend == "vision":
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, None], (3, B, S))
        cache, _ = api.prefill_fn(params, batch, cfg, PCFG)
        # grow attention caches S -> S+1 capacity
        def grow(x):
            if (x.ndim == 5 and x.shape[2] == S and cfg.num_kv_heads
                    and x.shape[-1] == cfg.head_dim):
                return jnp.pad(x, ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0)))
            return x
        cache = jax.tree.map(grow, cache)
        tok_dec, _ = api.decode_fn(
            params, cache,
            {"token": jnp.asarray(toks[:, S:S + 1]),
             "pos": jnp.full((B,), S, jnp.int32)}, cfg, PCFG)

    np.testing.assert_array_equal(np.asarray(tok_dec),
                                  np.asarray(tok_truth))


def test_multi_step_decode_matches_teacher_forcing(tiny_mesh):
    """Decode 4 steps against teacher-forced prefill next-tokens (glm4)."""
    cfg = get_config("glm4_9b", smoke=True)
    B, S, N = 1, 8, 4
    toks = RNG.integers(0, cfg.vocab_size, (B, S + N)).astype(np.int32)
    with jax.set_mesh(tiny_mesh):
        params, _ = api.init_model(cfg, jax.random.key(1))
        cache, _ = api.prefill_fn(
            params, {"tokens": jnp.asarray(toks[:, :S])}, cfg, PCFG)

        def grow(x):
            if (x.ndim == 5 and x.shape[2] == S and cfg.num_kv_heads
                    and x.shape[-1] == cfg.head_dim):
                return jnp.pad(x, ((0, 0), (0, 0), (0, N), (0, 0), (0, 0)))
            return x
        cache = jax.tree.map(grow, cache)
        for i in range(N):
            truth_batch = {"tokens": jnp.asarray(toks[:, :S + i + 1])}
            _, tok_truth = api.prefill_fn(params, truth_batch, cfg, PCFG)
            tok_dec, cache = api.decode_fn(
                params, cache,
                {"token": jnp.asarray(toks[:, S + i:S + i + 1]),
                 "pos": jnp.full((B,), S + i, jnp.int32)}, cfg, PCFG)
            np.testing.assert_array_equal(np.asarray(tok_dec),
                                          np.asarray(tok_truth),
                                          err_msg=f"step {i}")
