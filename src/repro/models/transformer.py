"""Decoder-only LM assembly covering all assigned families:

  dense GQA (glm4, starcoder2, qwen3), alternating local/global + softcaps
  (gemma2), M-RoPE VLM backbone (qwen2-vl), MoE (qwen3-moe, grok-1), pure SSM
  (mamba2) and hybrid SSM + shared-attention (zamba2).

Layers are stacked into *periods* and scanned with ``lax.scan`` (one period =
one tile of ``block_pattern``, or ``shared_attn_period`` mamba blocks + one
application of the shared attention block for zamba2). Scanning keeps the
HLO small at 64 layers and is what the dry-run compiles.

Entry points per model: ``loss_fn`` (train), ``prefill`` (build cache, emit
first token), ``decode_step`` (one token against the cache), and the paged
serving pair ``prefill_chunk_paged`` / ``decode_step_paged`` (prompt chunks
and single tokens against block-paged page pools). The serving pair is
mesh-aware through the attention ops: on a mesh with a "model" axis the
page pools arrive sharded by kv head and ``paged_decode_attention`` /
``paged_chunk_attention`` run under shard_map over their local head
slices (docs/multi-host.md); nothing here mentions the mesh.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ParallelConfig
from repro.models import modules as m
from repro.models import moe as moe_mod
from repro.models import quant
from repro.models import ssm as ssm_mod
from repro.models.attention import (attention_scale, decode_attention,
                                    init_attention, out_proj,
                                    paged_chunk_attention,
                                    paged_decode_attention, project_kv,
                                    project_q, ragged_chunk_update_attend,
                                    sharded_attention, update_cache,
                                    update_paged_cache,
                                    update_paged_cache_chunk)
from repro.models.embedding import (decode_logits, decode_logits_argmax,
                                    embed, head_table, init_embedding,
                                    lm_loss, sampled_softmax_loss)
from repro.models.layers import apply_norm, init_mlp, apply_mlp, init_norm, \
    rope_cos_sin

MOE_AUX_COEF = 0.01


# ---------------------------------------------------------------------------
# Structure
# ---------------------------------------------------------------------------


def period_structure(cfg: ModelConfig) -> tuple[tuple[str, ...], int]:
    """(kinds within one period, number of periods)."""
    if cfg.shared_attn_period:
        P = cfg.shared_attn_period
        kinds = cfg.layer_kinds()[:P]
    else:
        kinds = cfg.block_pattern
        P = len(kinds)
    assert cfg.num_layers % P == 0, (cfg.num_layers, P)
    return tuple(kinds), cfg.num_layers // P


def _init_block(kind: str, cfg: ModelConfig, key):
    ks = m.split_keys(key, 4)
    if kind == "mamba":
        return m.merge(
            m.named("norm", init_norm(cfg)),
            m.named("mamba", ssm_mod.init_mamba(cfg, ks[0])),
        )
    pairs = [
        m.named("norm", init_norm(cfg)),
        m.named("attn", init_attention(cfg, ks[0])),
        m.named("norm2", init_norm(cfg)),
    ]
    if cfg.moe is not None:
        pairs.append(m.named("moe", moe_mod.init_moe(cfg, ks[1])))
    else:
        pairs.append(m.named("mlp", init_mlp(cfg, ks[1])))
    if cfg.post_block_norm:
        pairs.append(m.named("post_norm", init_norm(cfg)))
        pairs.append(m.named("post_norm2", init_norm(cfg)))
    return m.merge(*pairs)


def init_lm(cfg: ModelConfig, key):
    kinds, NP = period_structure(cfg)
    ks = m.split_keys(key, NP * len(kinds) + 4)
    ki = iter(ks)
    pairs = [m.named("embed", init_embedding(cfg, next(ki)))]
    blocks_p, blocks_s = {}, {}
    for i, kind in enumerate(kinds):
        per = [_init_block(kind, cfg, next(ki)) for _ in range(NP)]
        p, s = m.stack_layer_params(per)
        blocks_p[f"sub{i}"], blocks_s[f"sub{i}"] = p, s
    pairs.append(({"blocks": blocks_p}, {"blocks": blocks_s}))
    if cfg.shared_attn_period:
        shared_cfg = cfg
        pairs.append(m.named("shared", _init_shared(shared_cfg, next(ki))))
    pairs.append(m.named("final_norm", init_norm(cfg)))
    return m.merge(*pairs)


def _init_shared(cfg: ModelConfig, key):
    ks = m.split_keys(key, 2)
    return m.merge(
        m.named("norm", init_norm(cfg)),
        m.named("attn", init_attention(cfg, ks[0])),
        m.named("norm2", init_norm(cfg)),
        m.named("mlp", init_mlp(cfg, ks[1])),
    )


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def _attn_full(bp, x, cfg: ModelConfig, ctx, kind: str):
    """Full-sequence self attention (train / prefill). Returns (y, cache)."""
    window = cfg.sliding_window if kind == "local" else None
    h = apply_norm(bp["norm"], x, cfg)
    q = project_q(bp["attn"], h, cfg, _rope(ctx, kind))
    k, v = project_kv(bp["attn"], h, cfg, _rope(ctx, kind))
    y = sharded_attention(
        q, k, v, cfg, causal=True, window=window,
        cap=cfg.attn_logit_softcap, scale=attention_scale(cfg),
        chunk_kv=min(1024, k.shape[1]))
    y = out_proj(bp["attn"], y, x.dtype)
    if cfg.post_block_norm:
        y = apply_norm(bp["post_norm"], y, cfg)
    x = x + y
    return x, {"k": k, "v": v}


def _mlp_part(bp, x, cfg: ModelConfig, ctx=None, mode: str = "train"):
    """Returns (x, aux): an expert layer's load-balancing loss in training,
    its int32 (2,) counts when serving (``moe.moe_block``); zero else."""
    h = apply_norm(bp["norm2"], x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None and "moe" in bp:
        y, aux = moe_mod.moe_block(bp["moe"], h, cfg,
                                   f2d=bool(ctx and ctx.get("moe_f2d")),
                                   train=mode == "train",
                                   valid=(ctx or {}).get("valid"))
    else:
        y = apply_mlp(bp["mlp"], h, cfg)
    if cfg.post_block_norm:
        y = apply_norm(bp["post_norm2"], y, cfg)
    return x + y, aux


def _attn_decode(bp, x, cfg: ModelConfig, ctx, cache, kind: str):
    window = cfg.sliding_window if kind == "local" else None
    h = apply_norm(bp["norm"], x, cfg)
    q = project_q(bp["attn"], h, cfg, _rope(ctx, kind))
    k, v = project_kv(bp["attn"], h, cfg, _rope(ctx, kind))
    kc = update_cache(cache["k"], k, ctx["pos"])
    vc = update_cache(cache["v"], v, ctx["pos"])
    y = decode_attention(q, kc, vc, ctx["pos"], window=window,
                         cap=cfg.attn_logit_softcap,
                         scale=attention_scale(cfg))
    y = out_proj(bp["attn"], y, x.dtype)
    if cfg.post_block_norm:
        y = apply_norm(bp["post_norm"], y, cfg)
    return x + y, {"k": kc, "v": vc}


def _attn_decode_paged(bp, x, cfg: ModelConfig, ctx, cache, kind: str):
    """One-token attention against a block-paged KV cache (serving engine).
    cache: {"k","v"} page pools (num_blocks, K, block_size, hd), plus
    {"k_scale","v_scale"} fp32 per-row scale pools when quantized — the
    new row quantizes before the scatter (no bf16 pool copy) and the
    dequant is fused into the attention kernel."""
    window = cfg.sliding_window if kind == "local" else None
    h = apply_norm(bp["norm"], x, cfg)
    q = project_q(bp["attn"], h, cfg, _rope(ctx, kind))
    k, v = project_kv(bp["attn"], h, cfg, _rope(ctx, kind))
    if "k_scale" in cache:
        kvd = quant.kv_dtype_name(cache["k"].dtype)
        k, ksr = quant.quantize_kv(k, kvd)
        v, vsr = quant.quantize_kv(v, kvd)
        ksc = update_paged_cache(cache["k_scale"], ksr,
                                 ctx["block_tables"], ctx["pos"])
        vsc = update_paged_cache(cache["v_scale"], vsr,
                                 ctx["block_tables"], ctx["pos"])
        scales = {"k_scale": ksc, "v_scale": vsc}
    else:
        ksc = vsc = None
        scales = {}
    kc = update_paged_cache(cache["k"], k, ctx["block_tables"], ctx["pos"])
    vc = update_paged_cache(cache["v"], v, ctx["block_tables"], ctx["pos"])
    y = paged_decode_attention(q, kc, vc, ctx["block_tables"],
                               ctx["ctx_lens"], window=window,
                               cap=cfg.attn_logit_softcap,
                               scale=attention_scale(cfg),
                               k_scale=ksc, v_scale=vsc)
    y = out_proj(bp["attn"], y, x.dtype)
    if cfg.post_block_norm:
        y = apply_norm(bp["post_norm"], y, cfg)
    return x + y, {"k": kc, "v": vc, **scales}


def _attn_chunk_paged(bp, x, cfg: ModelConfig, ctx, cache, kind: str):
    """Chunked-prefill attention against a block-paged KV cache: scatter
    this chunk's KV into the pages, then attend the chunk's queries
    causally over the whole paged context (prior chunks included).
    cache: {"k","v"} page pools (num_blocks, K, block_size, hd)."""
    window = cfg.sliding_window if kind == "local" else None
    h = apply_norm(bp["norm"], x, cfg)
    q = project_q(bp["attn"], h, cfg, _rope(ctx, kind))
    k, v = project_kv(bp["attn"], h, cfg, _rope(ctx, kind))
    if "k_scale" in cache:
        kvd = quant.kv_dtype_name(cache["k"].dtype)
        k, ksr = quant.quantize_kv(k, kvd)
        v, vsr = quant.quantize_kv(v, kvd)
        ksc = update_paged_cache_chunk(cache["k_scale"], ksr,
                                       ctx["block_tables"], ctx["q_start"],
                                       ctx["q_lens"])
        vsc = update_paged_cache_chunk(cache["v_scale"], vsr,
                                       ctx["block_tables"], ctx["q_start"],
                                       ctx["q_lens"])
        scales = {"k_scale": ksc, "v_scale": vsc}
    else:
        ksc = vsc = None
        scales = {}
    kc = update_paged_cache_chunk(cache["k"], k, ctx["block_tables"],
                                  ctx["q_start"], ctx["q_lens"])
    vc = update_paged_cache_chunk(cache["v"], v, ctx["block_tables"],
                                  ctx["q_start"], ctx["q_lens"])
    y = paged_chunk_attention(q, kc, vc, ctx["block_tables"],
                              ctx["ctx_lens"], ctx["q_lens"], window=window,
                              cap=cfg.attn_logit_softcap,
                              scale=attention_scale(cfg),
                              k_scale=ksc, v_scale=vsc)
    y = out_proj(bp["attn"], y, x.dtype)
    if cfg.post_block_norm:
        y = apply_norm(bp["post_norm"], y, cfg)
    return x + y, {"k": kc, "v": vc, **scales}


def _attn_ragged_paged(bp, x, cfg: ModelConfig, ctx, cache, kind: str):
    """Packed (ragged) chunked-prefill attention against a block-paged KV
    cache: chunks of several sequences ride one flat (1, T, d) row batch.
    The KV scatter and the attention run as one fused op on the Pallas
    path; row-wise projections/MLP are shared across the pack."""
    window = cfg.sliding_window if kind == "local" else None
    h = apply_norm(bp["norm"], x, cfg)
    q = project_q(bp["attn"], h, cfg, _rope(ctx, kind))
    k, v = project_kv(bp["attn"], h, cfg, _rope(ctx, kind))
    if "k_scale" in cache:
        y, kc, vc, ksc, vsc = ragged_chunk_update_attend(
            q, k, v, cache["k"], cache["v"], ctx["block_tables"],
            ctx["ctx_lens"], ctx["starts"], ctx["ends"], ctx["row_seq"],
            window=window, cap=cfg.attn_logit_softcap,
            scale=attention_scale(cfg), k_scale=cache["k_scale"],
            v_scale=cache["v_scale"])
        new_cache = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
    else:
        y, kc, vc = ragged_chunk_update_attend(
            q, k, v, cache["k"], cache["v"], ctx["block_tables"],
            ctx["ctx_lens"], ctx["starts"], ctx["ends"], ctx["row_seq"],
            window=window, cap=cfg.attn_logit_softcap,
            scale=attention_scale(cfg))
        new_cache = {"k": kc, "v": vc}
    y = out_proj(bp["attn"], y, x.dtype)
    if cfg.post_block_norm:
        y = apply_norm(bp["post_norm"], y, cfg)
    return x + y, new_cache


def _block_apply(kind, bp, x, cfg, ctx, mode, cache=None):
    """Returns (x, new_cache, aux)."""
    zero = jnp.zeros((), jnp.float32)
    if kind == "mamba":
        h = apply_norm(bp["norm"], x, cfg)
        if mode in ("decode", "decode_paged"):
            # the per-slot (conv_tail, ssm_state) cache is the serving
            # SlotStateCache's device half: same entry for both cache kinds
            y, st = ssm_mod.mamba_decode(bp["mamba"], h, cfg, cache)
            return x + y, st, zero
        if mode == "chunk_paged":
            y, st = ssm_mod.mamba_chunk(bp["mamba"], h, cfg, cache,
                                        ctx["q_lens"])
            return x + y, st, zero
        if mode == "ragged_paged":
            raise NotImplementedError(
                "packed prefill needs per-row chunk state; SSM blocks are "
                "gated out by ModelRunner.supports_packed_prefill")
        y, st = ssm_mod.mamba_block(bp["mamba"], h, cfg)
        return x + y, (st if mode == "prefill" else None), zero
    if mode == "ragged_paged":
        x, c = _attn_ragged_paged(bp, x, cfg, ctx, cache, kind)
        x, aux = _mlp_part(bp, x, cfg, ctx, mode)
        return x, c, aux
    if mode == "chunk_paged":
        x, c = _attn_chunk_paged(bp, x, cfg, ctx, cache, kind)
        x, aux = _mlp_part(bp, x, cfg, ctx, mode)
        return x, c, aux
    if mode == "decode_paged":
        x, c = _attn_decode_paged(bp, x, cfg, ctx, cache, kind)
        x, aux = _mlp_part(bp, x, cfg, ctx, mode)
        return x, c, aux
    if mode == "decode":
        x, c = _attn_decode(bp, x, cfg, ctx, cache, kind)
        x, aux = _mlp_part(bp, x, cfg, ctx, mode)
        return x, c, aux
    x, c = _attn_full(bp, x, cfg, ctx, kind)
    x, aux = _mlp_part(bp, x, cfg, ctx, mode)
    return x, (c if mode == "prefill" else None), aux


# ---------------------------------------------------------------------------
# Context (positions / rope tables)
# ---------------------------------------------------------------------------


def _rope_tables(cfg: ModelConfig, positions):
    """The cos/sin tables of the layers: one (cos, sin) pair for every
    kind where the config has no rope scaling (so every dense program
    builds the one table it always has), else {kind: pair}, with the
    scaling on the full-attention ("attn") layers and the plain table on
    the rest, as the published per-kind rope parameters have it. None
    for a model without attention heads."""
    if not cfg.num_heads:
        return None
    plain = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                         cfg.rope_sections)
    if cfg.rope_scaling is None:
        return plain
    return {"attn": rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                                 cfg.rope_sections, cfg.rope_scaling),
            "local": plain}


def _rope(ctx, kind: str):
    """The table that layers of this kind rotate with."""
    cs = ctx["cos_sin"]
    return cs[kind] if isinstance(cs, dict) else cs


def _make_ctx(cfg: ModelConfig, positions, pcfg: ParallelConfig = None):
    cos_sin = _rope_tables(cfg, positions)
    pos = positions if positions.ndim == 1 else None
    return {"cos_sin": cos_sin, "pos": pos,
            "moe_f2d": bool(pcfg and pcfg.expert_ff_2d)}


def _default_positions(batch, B, S):
    if "positions" in batch:
        return batch["positions"]
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _scan_periods(params, x, cfg: ModelConfig, ctx, mode: str,
                  pcfg: ParallelConfig, cache=None):
    """Scan the period body over NP periods.

    mode="train":    xs=blocks,         carry=(x, aux), ys=None
    mode="prefill":  xs=blocks,         carry=(x, aux), ys=cache slices
    mode="decode":   xs=(blocks,cache), carry=(x, aux), ys=new cache slices

    aux: the experts' load-balancing loss in training; in every serving
    mode of an expert model, the counts of ``moe.moe_block``.
    """
    kinds, NP = period_structure(cfg)

    # Megatron-style sequence parallelism: keep the residual stream sharded
    # over "model" on the seq dim between blocks. GSPMD then turns the TP
    # activation all-reduces into reduce-scatter + all-gather pairs (half
    # the wire bytes) and the remat-saved carries shrink by the TP degree.
    def _sp_constrain(x):
        if not (pcfg.seq_shard_activations and mode == "train"):
            return x
        mesh = jax.sharding.get_abstract_mesh()
        tp = mesh.shape.get("model", 1)
        if tp <= 1 or x.shape[1] % tp != 0:
            return x
        from repro.spmd.sharding import batch_spec
        from jax.sharding import PartitionSpec as P, NamedSharding
        b = batch_spec(x.shape[0], mesh, extra_dims=0)
        spec = P(b[0] if len(b) else None, "model", None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))

    def body(carry, xs):
        x, aux = carry
        if mode in ("decode", "decode_paged", "chunk_paged", "ragged_paged"):
            bslices, cslices = xs
        else:
            bslices, cslices = xs, None
        new_cache = {}
        for i, kind in enumerate(kinds):
            cc = None if cslices is None else cslices.get(f"sub{i}")
            x, c, a = _block_apply(kind, bslices[f"sub{i}"], x, cfg, ctx,
                                   mode, cc)
            aux = aux + a
            if c is not None:
                new_cache[f"sub{i}"] = c
        if cfg.shared_attn_period:
            sp = params["shared"]
            cc = None if cslices is None else cslices.get("shared")
            if mode == "chunk_paged":
                x, c = _attn_chunk_paged(sp, x, cfg, ctx, cc, "attn")
            elif mode == "decode_paged":
                x, c = _attn_decode_paged(sp, x, cfg, ctx, cc, "attn")
            elif mode == "decode":
                x, c = _attn_decode(sp, x, cfg, ctx, cc, "attn")
            else:
                x, c = _attn_full(sp, x, cfg, ctx, "attn")
                c = c if mode == "prefill" else None
            h = apply_norm(sp["norm2"], x, cfg)
            x = x + apply_mlp(sp["mlp"], h, cfg)
            if c is not None:
                new_cache["shared"] = c
        x = _sp_constrain(x)
        return (x, aux), (new_cache if new_cache else None)

    if pcfg.remat != "none" and mode == "train":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if pcfg.remat == "dots" else None)
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)

    xs = ((params["blocks"], cache)
          if mode in ("decode", "decode_paged", "chunk_paged", "ragged_paged")
          else params["blocks"])
    # an expert model's serving modes carry its layers' summed counts
    aux0 = (jnp.zeros((2,), jnp.int32) if cfg.moe is not None
            and mode != "train" else jnp.zeros((), jnp.float32))
    (x, aux), caches = jax.lax.scan(body, (x, aux0), xs)
    return x, aux, caches


def _counts(cfg: ModelConfig, moe):
    """A serving step's expert counts (int32 (2,): rows computed on held
    experts, routed assignments, summed over layers), or None for a model
    without experts."""
    return moe if cfg.moe is not None else None


def forward_loss(params, batch, cfg: ModelConfig, pcfg: ParallelConfig,
                 sampled_ids=None):
    """batch: tokens (B,S), labels (B,S) [, positions]. Returns (loss, metr)."""
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    x = embed(params["embed"]["table"], tokens, cfg)
    ctx = _make_ctx(cfg, _default_positions(batch, B, S), pcfg)
    x, aux, _ = _scan_periods(params, x, cfg, ctx, "train", pcfg)
    x = apply_norm(params["final_norm"], x, cfg)
    ht = head_table(params["embed"], cfg)
    if sampled_ids is not None:
        ce = sampled_softmax_loss(x, ht, labels, sampled_ids, cfg)
    else:
        ce = lm_loss(x, ht, labels, cfg)
    loss = ce + MOE_AUX_COEF * aux
    return loss, {"ce": ce, "aux": aux}


def init_cache(cfg: ModelConfig, B: int, S: int, dtype=jnp.bfloat16):
    """Zero cache pytree matching prefill/decode layouts."""
    kinds, NP = period_structure(cfg)
    cache = {}
    for i, kind in enumerate(kinds):
        if kind == "mamba":
            s = cfg.ssm
            di = s.d_inner(cfg.d_model)
            gn = s.n_groups * s.state_dim
            cache[f"sub{i}"] = (
                jnp.zeros((NP, B, s.conv_kernel - 1, di + 2 * gn), dtype),
                jnp.zeros((NP, B, s.n_heads(cfg.d_model), s.head_dim,
                           s.state_dim), jnp.float32))
        else:
            cache[f"sub{i}"] = {
                "k": jnp.zeros((NP, B, S, cfg.num_kv_heads, cfg.head_dim),
                               dtype),
                "v": jnp.zeros((NP, B, S, cfg.num_kv_heads, cfg.head_dim),
                               dtype)}
    if cfg.shared_attn_period:
        cache["shared"] = {
            "k": jnp.zeros((NP, B, S, cfg.num_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((NP, B, S, cfg.num_kv_heads, cfg.head_dim), dtype)}
    return cache


def prefill(params, batch, cfg: ModelConfig, pcfg: ParallelConfig):
    """Process the prompt; returns (cache, next_token (B,)).

    Attention caches hold the prompt's K/V; SSM blocks return their final
    (conv_tail, state). Cache seq capacity == prompt length (the dry-run
    decode shapes supply their own full-length cache).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed(params["embed"]["table"], tokens, cfg)
    ctx = _make_ctx(cfg, _default_positions(batch, B, S), pcfg)
    x, _, caches = _scan_periods(params, x, cfg, ctx, "prefill", pcfg)
    x = apply_norm(params["final_norm"], x, cfg)
    nxt = decode_logits_argmax(x[:, -1:], head_table(params["embed"], cfg),
                               cfg)
    return caches, nxt


def prefill_chunk_paged(params, cache, batch, cfg: ModelConfig,
                        pcfg: ParallelConfig, *, all_logits: bool = False):
    """One chunk of prompt prefill against a block-paged KV cache.

    batch: tokens (B, C) the chunk's token slice (right-padded), q_start
    (B,) absolute position of column 0 (= tokens already computed), q_lens
    (B,) valid columns, block_tables (B, nb), ctx_lens (B,) visible tokens
    including this chunk (= q_start + q_lens).
    Returns (logits (B, V_pad) fp32 at each row's last valid token,
    new_cache, expert counts (``_counts``) of the valid columns). The engine samples from the logits only when the chunk
    completes its prompt. With ``all_logits=True`` the logits cover every
    chunk position — (B, C, V_pad) — which is what the speculative verify
    step needs: one widened pass scoring all K+1 candidate positions.
    """
    tokens = batch["tokens"]
    B, C = tokens.shape
    assert cfg.rope_sections is None, "chunked prefill: no M-RoPE frontends"
    x = embed(params["embed"]["table"], tokens, cfg)
    positions = batch["q_start"][:, None] + jnp.arange(C, dtype=jnp.int32)
    ctx = {"cos_sin": _rope_tables(cfg, positions), "pos": None,
           "q_start": batch["q_start"], "q_lens": batch["q_lens"],
           "block_tables": batch["block_tables"],
           "ctx_lens": batch["ctx_lens"],
           "moe_f2d": bool(pcfg and pcfg.expert_ff_2d)}
    if cfg.moe is not None:
        ctx["valid"] = jnp.arange(C)[None, :] < batch["q_lens"][:, None]
    x, moe, new_cache = _scan_periods(params, x, cfg, ctx, "chunk_paged",
                                      ParallelConfig(remat="none"), cache)
    x = apply_norm(params["final_norm"], x, cfg)
    ht = head_table(params["embed"], cfg)
    if all_logits:
        logits = decode_logits(x.reshape(B * C, 1, -1), ht, cfg)
        return logits.reshape(B, C, -1), new_cache, _counts(cfg, moe)
    last = jnp.clip(batch["q_lens"] - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)   # (B,1,d)
    logits = decode_logits(x_last, ht, cfg)
    return logits, new_cache, _counts(cfg, moe)


def prefill_chunk_ragged(params, cache, batch, cfg: ModelConfig,
                         pcfg: ParallelConfig):
    """Packed (ragged) prompt prefill: chunks of up to S sequences ride one
    flat token batch against the block-paged KV cache.

    batch: tokens (1, T) chunks packed back to back (right-padded),
    positions (1, T) each row's absolute position, starts/ends (S,) flat
    row ranges per packed sequence (start == end marks an unused pack
    slot), row_seq (T,) each row's owning pack slot, block_tables (S, nb),
    ctx_lens (S,) visible tokens including each chunk.
    Returns (logits (S, V_pad) fp32 at each sequence's last packed row,
    new_cache, expert counts of the packed rows). Row-wise work (embedding, norms, projections, MLP) runs
    once over the flat batch; only the attention is per-sequence. S == 1
    is the single-chunk path in a different layout — the engine keeps
    outputs byte-identical across the two (tests pin it).
    """
    tokens = batch["tokens"]
    _, T = tokens.shape
    assert cfg.rope_sections is None, "packed prefill: no M-RoPE frontends"
    assert cfg.ssm is None and not cfg.shared_attn_period, \
        "packed prefill is attention-only (see supports_packed_prefill)"
    x = embed(params["embed"]["table"], tokens, cfg)
    ctx = {"cos_sin": _rope_tables(cfg, batch["positions"]), "pos": None,
           "starts": batch["starts"], "ends": batch["ends"],
           "row_seq": batch["row_seq"],
           "block_tables": batch["block_tables"],
           "ctx_lens": batch["ctx_lens"],
           "moe_f2d": bool(pcfg and pcfg.expert_ff_2d)}
    if cfg.moe is not None:
        row, seq = jnp.arange(T), batch["row_seq"]
        ctx["valid"] = ((row >= batch["starts"][seq])
                        & (row < batch["ends"][seq]))[None]
    x, moe, new_cache = _scan_periods(params, x, cfg, ctx, "ragged_paged",
                                      ParallelConfig(remat="none"), cache)
    x = apply_norm(params["final_norm"], x, cfg)
    ht = head_table(params["embed"], cfg)
    last = jnp.clip(batch["ends"] - 1, 0, T - 1)                   # (S,)
    x_last = jnp.take(x[0], last, axis=0)[:, None]                 # (S,1,d)
    logits = decode_logits(x_last, ht, cfg)
    return logits, new_cache, _counts(cfg, moe)


def decode_step_paged(params, cache, batch, cfg: ModelConfig,
                      pcfg: ParallelConfig):
    """One decode token against a block-paged KV cache (all serving slots).

    batch: token (B,1), pos (B,) write position, block_tables (B, nb),
    ctx_lens (B,) — visible tokens incl. this one; 0 masks an idle slot.
    cache: pytree of {"k","v"} page pools with leading layer-stack dim.
    Returns (logits (B, V_pad) fp32, new_cache, expert counts of the
    active slots).
    """
    token, pos = batch["token"], batch["pos"]
    B = token.shape[0]
    x = embed(params["embed"]["table"], token, cfg)
    if cfg.rope_sections is not None:
        positions = jnp.broadcast_to(pos[None, :, None], (3, B, 1))
    else:
        positions = pos[:, None]
    ctx = {"cos_sin": _rope_tables(cfg, positions), "pos": pos,
           "block_tables": batch["block_tables"],
           "ctx_lens": batch["ctx_lens"],
           "moe_f2d": bool(pcfg and pcfg.expert_ff_2d)}
    if cfg.moe is not None:
        ctx["valid"] = (batch["ctx_lens"] > 0)[:, None]
    x, moe, new_cache = _scan_periods(params, x, cfg, ctx, "decode_paged",
                                      ParallelConfig(remat="none"), cache)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = decode_logits(x, head_table(params["embed"], cfg), cfg)
    return logits, new_cache, _counts(cfg, moe)


def decode_step(params, cache, batch, cfg: ModelConfig,
                pcfg: ParallelConfig):
    """One token. batch: token (B,1), pos (B,) — position to write at.
    Returns (next_token (B,), new_cache)."""
    token, pos = batch["token"], batch["pos"]
    B = token.shape[0]
    x = embed(params["embed"]["table"], token, cfg)
    if cfg.rope_sections is not None:
        positions = jnp.broadcast_to(pos[None, :, None], (3, B, 1))
    else:
        positions = pos[:, None]
    ctx = {"cos_sin": _rope_tables(cfg, positions), "pos": pos,
           "moe_f2d": bool(pcfg and pcfg.expert_ff_2d)}
    x, _, new_cache = _scan_periods(params, x, cfg, ctx, "decode",
                                    ParallelConfig(remat="none"), cache)
    x = apply_norm(params["final_norm"], x, cfg)
    nxt = decode_logits_argmax(x, head_table(params["embed"], cfg), cfg)
    return nxt, new_cache
