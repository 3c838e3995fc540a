"""The serving layer scan keeps the KV page pools in place: they ride in the
scan's carry as one flat (NP·N, K, bs, hd) view, each period's layers
address their own blocks as ``block_tables + p·N`` and write one row per
(token, kv head). These tests pin that step to an oracle that runs the
layer scan the plain way — each layer's pool sliced out of the stacked pool
as scan input and stacked back as output, every write a row across the kv
heads into that layer's own pool, trash block 0 — and demand the same
logits, pools, expert counts and slot state, byte for byte.

The cases cover idle decode slots and chunk padding rows (both write the
layer's trash block), packed rows owned by no sequence, the ragged
kernel's fused KV write (under the interpreter), sliding-window layers, an
expert model, int8 pools with their scale sidecars, and a hybrid whose
per-slot mamba state stays in the scan's input and output.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ParallelConfig, get_config
from repro.kernels import ops
from repro.models import api, attention, transformer
from repro.models.layers import apply_mlp, apply_norm
from repro.serving.cache import init_slot_state
from repro.serving.kv_cache import init_paged_cache

BS, NB, N = 4, 12, 64            # block size, table width, blocks per layer
PCFG = ParallelConfig(remat="none")

CASES = {
    # a sliding layer and a full one per period, attention softcap
    "gemma2": ("gemma2_27b", {}, "bf16"),
    "gemma2_int8": ("gemma2_27b", {}, "int8"),
    # sparse experts, three sliding layers to one full, two periods
    "mellum2": ("mellum2_12b_a2p5b", {"num_layers": 8}, "bf16"),
    # mamba slot state beside the shared attention block's pool
    "zamba2": ("zamba2_2p7b", {}, "bf16"),
}


def _window_write(pages, blk, off, new):
    """The oracle's KV write: rows across all kv heads at once."""
    return pages.at[blk, :, off].set(new.astype(pages.dtype))


def _stacked_scan(params, x, cfg, ctx, mode, pcfg, cache=None):
    """Oracle layer scan: every cache stack (pools and slot state alike) is
    scan input, sliced per period, and comes back stacked as scan output;
    idle and padding rows write block 0 of the layer's own pool."""
    kinds, _ = transformer.period_structure(cfg)
    ctx = dict(ctx, trash=0)
    attend = {"decode_paged": transformer._attn_decode_paged,
              "chunk_paged": transformer._attn_chunk_paged}

    def body(carry, xs):
        x, aux = carry
        blocks, layer = xs
        new = {}
        for i, kind in enumerate(kinds):
            x, new[f"sub{i}"], a = transformer._block_apply(
                kind, blocks[f"sub{i}"], x, cfg, ctx, mode,
                layer[f"sub{i}"])
            aux = aux + a
        if cfg.shared_attn_period:
            sp = params["shared"]
            x, new["shared"] = attend[mode](sp, x, cfg, ctx,
                                            layer["shared"], "attn")
            x = x + apply_mlp(sp["mlp"], apply_norm(sp["norm2"], x, cfg),
                              cfg)
        return (x, aux), new

    aux0 = (jnp.zeros((2,), jnp.int32) if cfg.moe is not None
            else jnp.zeros((), jnp.float32))
    (x, aux), caches = jax.lax.scan(body, (x, aux0),
                                    (params["blocks"], cache))
    return x, aux, caches


def _random_like(rng, leaf):
    if leaf.dtype == jnp.int8:
        return jnp.asarray(rng.integers(-127, 128, leaf.shape), jnp.int8)
    if leaf.shape[-1] == 1:                    # fp32 scale sidecar
        return jnp.asarray(rng.uniform(0.005, 0.02, leaf.shape),
                           leaf.dtype)
    return jnp.asarray(rng.normal(0, 1, leaf.shape), leaf.dtype)


def _case(name):
    arch, overrides, kv_dtype = CASES[name]
    cfg = dataclasses.replace(get_config(arch, smoke=True), **overrides)
    params = api.init_params_bf16(cfg, jax.random.key(1))
    rng = np.random.default_rng(7)
    pools = init_paged_cache(cfg, N, BS, kv_dtype=kv_dtype)
    pools = jax.tree.map(lambda t: _random_like(rng, t), pools)
    return cfg, params, pools, rng


def _tables(rng, rows, live):
    """Disjoint tables of real blocks (1..N-1) for the live rows; an idle
    row's table is all trash-block ids (0), as the engine builds it."""
    ids = rng.permutation(np.arange(1, N))[:rows * NB].reshape(rows, NB)
    return jnp.asarray(np.where(np.asarray(live)[:, None], ids, 0),
                       jnp.int32)


def _decode(cfg, rng, slot_cache):
    live = [True, False, True, True, False]       # two idle slots
    B = len(live)
    pos = np.array([37, 0, 5, 22, 0], np.int32)   # 37 > the window of 16
    batch = {"token": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)),
                                  jnp.int32),
             "pos": jnp.asarray(pos),
             "block_tables": _tables(rng, B, live),
             "ctx_lens": jnp.asarray(np.where(live, pos + 1, 0), jnp.int32)}
    return transformer.decode_step_paged, batch, slot_cache(B)


def _chunk(cfg, rng, slot_cache):
    C = 8
    q_start = np.array([24, 0, 8], np.int32)
    q_lens = np.array([8, 5, 3], np.int32)        # padding rows in two
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (3, C)),
                                   jnp.int32),
             "q_start": jnp.asarray(q_start), "q_lens": jnp.asarray(q_lens),
             "block_tables": _tables(rng, 3, [True] * 3),
             "ctx_lens": jnp.asarray(q_start + q_lens)}
    return transformer.prefill_chunk_paged, batch, slot_cache(3)


def _ragged(cfg, rng, slot_cache):
    lens, T = [6, 0, 9], 20                       # 5 rows owned by nobody
    starts = np.array([0, 6, 6], np.int32)
    ends = starts + np.array(lens, np.int32)
    ctx = np.array([30, 0, 9], np.int32)
    row_seq = np.zeros(T, np.int32)
    for s, (a, b) in enumerate(zip(starts, ends)):
        row_seq[a:b] = s
    pos = np.zeros(T, np.int32)
    for s, (a, b) in enumerate(zip(starts, ends)):
        pos[a:b] = ctx[s] - (b - a) + np.arange(b - a)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (1, T)),
                                   jnp.int32),
             "positions": jnp.asarray(pos[None]),
             "starts": jnp.asarray(starts), "ends": jnp.asarray(ends),
             "row_seq": jnp.asarray(row_seq),
             "block_tables": _tables(rng, 3, [True, False, True]),
             "ctx_lens": jnp.asarray(ctx)}
    return transformer.prefill_chunk_ragged, batch, slot_cache(0)


STEPS = {"decode": _decode, "chunk": _chunk, "ragged": _ragged,
         # the ragged kernel under the interpreter: its fused KV write
         # aliases the carried flat pool
         "ragged_fused": _ragged}
# packed prefill is attention-only (ModelRunner.supports_packed_prefill);
# the fused kernel runs on the dense cases only, to keep the interpreter
# off the grouped matmul
PAIRS = [(c, s) for c in sorted(CASES) for s in sorted(STEPS)
         if not (c == "zamba2" and s.startswith("ragged"))
         and not (c == "mellum2" and s == "ragged_fused")]


def _run(fn, params, cache, batch, cfg, mesh):
    with jax.set_mesh(mesh):
        return jax.jit(functools.partial(fn, cfg=cfg, pcfg=PCFG))(
            params, cache, batch)


def _bytes_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype
    return a.tobytes() == b.tobytes()


@pytest.mark.parametrize("case,step", PAIRS)
def test_flat_pool_step_matches_the_stacked_oracle(case, step, tiny_mesh,
                                                  monkeypatch):
    cfg, params, pools, rng = _case(case)
    if step == "ragged_fused":
        monkeypatch.setattr(ops, "_use_pallas", lambda: "interpret")

    def slot_cache(rows):
        if cfg.ssm is None:
            return {}
        st = init_slot_state(cfg, rows)
        return jax.tree.map(lambda t: _random_like(rng, t), st)

    fn, batch, state = STEPS[step](cfg, rng, slot_cache)
    cache = {**pools, **state}
    logits, new, *counts = _run(fn, params, cache, batch, cfg, tiny_mesh)
    with monkeypatch.context() as mp:
        mp.setattr(transformer, "_scan_periods", _stacked_scan)
        mp.setattr(attention, "_write_rows", _window_write)
        want_logits, want, *want_counts = _run(fn, params, cache, batch,
                                               cfg, tiny_mesh)

    assert _bytes_equal(logits, want_logits)
    assert jax.tree.structure(new) == jax.tree.structure(want)
    for got_leaf, want_leaf in zip(jax.tree.leaves(new),
                                   jax.tree.leaves(want)):
        assert _bytes_equal(got_leaf, want_leaf)
    if cfg.moe is not None:
        assert _bytes_equal(counts[0], want_counts[0])
    # the step wrote: every layer's pool changed, and where idle or padding
    # rows exist they landed in that layer's own trash block
    _, NP = transformer.period_structure(cfg)
    for name, stack in pools.items():
        old, now = np.asarray(stack["k"]), np.asarray(new[name]["k"])
        for p in range(NP):
            assert (old[p] != now[p]).any()
            if not step.startswith("ragged"):
                assert (old[p, 0] != now[p, 0]).any(), (name, p)
