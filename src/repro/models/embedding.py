"""Vocab-sharded embeddings and softmax losses (paper §4.2, §6.4).

The embedding table is the canonical "too big to replicate" state. The paper
shards it across PS tasks and builds the lookup as
DynamicPartition → Gather (colocated with the shard) → DynamicStitch.
Here the table is sharded over the "model" mesh axis on its vocab dim and the
same three steps happen inside shard_map:

  Part:    each shard masks the token ids that fall in its vocab range
  Gather:  a local table gather (Pallas kernel on TPU)
  Stitch:  psum over the "model" axis (out-of-range rows contribute zeros)

The LM head is the transpose: vocab-parallel cross-entropy that never
materializes a replicated (T, V) logit matrix (max/lse stitched with
pmax/psum), token-chunked so the live logit block is (chunk, V/tp).
``sampled_softmax_loss`` implements the paper's §6.4 sampled softmax.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models import modules as m
from repro.models.layers import softcap
from repro.spmd.sharding import dp_axes

NEG = -1.0e30


def init_embedding(cfg: ModelConfig, key):
    ks = m.split_keys(key, 2)
    V = cfg.padded_vocab_size
    pairs = [m.named("table", m.dense_init(
        ks[0], (V, cfg.d_model), ("vocab", "embed"), scale=0.02))]
    if not cfg.tie_embeddings:
        pairs.append(m.named("head", m.dense_init(
            ks[1], (V, cfg.d_model), ("vocab", "embed"))))
    return m.merge(*pairs)


def head_table(params, cfg: ModelConfig):
    return params["table"] if cfg.tie_embeddings else params["head"]


def _dp_spec(mesh, n: int):
    dp = dp_axes(mesh)
    sz = math.prod(mesh.shape[a] for a in dp) if dp else 1
    if dp and n % sz == 0:
        return dp if len(dp) > 1 else dp[0]
    return None


def embed(table, tokens, cfg: ModelConfig):
    """tokens: (B, S) int32 -> (B, S, d). Part/Gather/Stitch over "model"."""
    mesh = jax.sharding.get_abstract_mesh()
    dps = _dp_spec(mesh, tokens.shape[0])

    def body(table_l, tok):
        V_l = table_l.shape[0]
        off = jax.lax.axis_index("model") * V_l
        loc = tok - off
        ok = (loc >= 0) & (loc < V_l)
        from repro.kernels import ops as kops
        rows = kops.embedding_gather(table_l, jnp.clip(loc, 0, V_l - 1))
        rows = jnp.where(ok[..., None], rows, 0)
        return jax.lax.psum(rows, "model")

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("model", None), P(dps, None)),
        out_specs=P(dps, None, None),
        # the gather kernel's pallas_call output carries no varying-axes type
        check_vma=False,
    )(table, tokens)
    out = out.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    if cfg.embedding_scale:
        out = out * jnp.asarray(math.sqrt(cfg.d_model), out.dtype)
    return out


def _xent_local(x, table_l, labels, off, V_l, cap, chunk, v_real):
    """Chunked vocab-parallel cross-entropy partials. x: (T, d), any dtype —
    the logits matmul keeps bf16 inputs with an fp32 MXU accumulator
    (half the HBM reads of an fp32 upcast; §Perf iteration 2).

    Returns (lse_partials (T,), true_logit_partials (T,)) before stitching:
    local max/sumexp need a pmax/psum combine by the caller. Columns at or
    beyond ``v_real`` are vocab padding and masked out.
    """
    T, d = x.shape
    nc = max(T // chunk, 1)
    xc = x.reshape(nc, T // nc, d)
    lc = labels.reshape(nc, T // nc)
    col_ok = (off + jnp.arange(V_l)) < v_real

    def body(_, inp):
        xb, lb = inp
        logits = jnp.einsum("td,vd->tv", xb, table_l,
                            preferred_element_type=jnp.float32)
        logits = softcap(logits, cap)
        logits = jnp.where(col_ok[None, :], logits, NEG)
        # LSE is exact for any constant shift -> stop_gradient keeps the
        # backward pass the plain (softmax - onehot) form with no pmax-grad.
        mx = jax.lax.stop_gradient(logits.max(axis=-1))
        loc = lb - off
        ok = (loc >= 0) & (loc < V_l)
        tl = jnp.take_along_axis(
            logits, jnp.clip(loc, 0, V_l - 1)[:, None], axis=1)[:, 0]
        tl = jnp.where(ok, tl, 0.0)
        # stable partial: sum of exp(logits - gmax) needs the global max;
        # emit (mx, sumexp-at-local-max) and let the caller rescale.
        se = jnp.exp(logits - mx[:, None]).sum(axis=-1)
        return None, (mx, se, tl)

    _, (mx, se, tl) = jax.lax.scan(body, None, (xc, lc))
    return mx.reshape(T), se.reshape(T), tl.reshape(T)


def lm_loss(x, table, labels, cfg: ModelConfig, chunk: int = 4096):
    """Mean token cross-entropy. x: (B, S, d); labels: (B, S).

    Vocab-parallel: logits live only as (chunk, V/tp) blocks per shard.
    """
    mesh = jax.sharding.get_abstract_mesh()
    B, S, d = x.shape
    dps = _dp_spec(mesh, B)
    cap = cfg.final_logit_softcap

    def body(x, table_l, labels):
        b, s, _ = x.shape
        T = b * s
        V_l = table_l.shape[0]
        off = jax.lax.axis_index("model") * V_l
        ck = chunk if T % chunk == 0 else T
        mx, se, tl = _xent_local(
            x.reshape(T, d), table_l.astype(x.dtype), labels.reshape(T),
            off, V_l, cap, ck, cfg.vocab_size)
        gmx = jax.lax.stop_gradient(jax.lax.pmax(mx, "model"))
        se = jax.lax.psum(se * jnp.exp(mx - gmx), "model")
        tl = jax.lax.psum(tl, "model")
        loss = jnp.log(se) + gmx - tl
        loss = loss.mean()
        dp = dp_axes(mesh)
        return jax.lax.pmean(loss, dp) if dp else loss

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dps, None, None), P("model", None), P(dps, None)),
        out_specs=P(),
    )(x, table, labels)


def sampled_softmax_loss(x, table, labels, sampled_ids, cfg: ModelConfig):
    """Paper §4.2/§6.4: softmax over {true class} ∪ {S sampled classes}.

    The (S+1)-row weight slice is gathered from the vocab-sharded table
    (Part/Gather/Stitch again), then the small softmax runs data-parallel.
    x: (B, S, d); labels: (B, S); sampled_ids: (n_samples,).
    """
    mesh = jax.sharding.get_abstract_mesh()
    B, S, d = x.shape
    dps = _dp_spec(mesh, B)
    cap = cfg.final_logit_softcap

    def body(x, table_l, labels, sampled_ids):
        b, s, _ = x.shape
        T = b * s
        xt = x.reshape(T, d).astype(jnp.float32)
        lab = labels.reshape(T)
        V_l = table_l.shape[0]
        off = jax.lax.axis_index("model") * V_l
        tl32 = table_l.astype(jnp.float32)

        def shard_gather(ids):
            loc = ids - off
            ok = (loc >= 0) & (loc < V_l)
            rows = tl32[jnp.clip(loc, 0, V_l - 1)]
            return jax.lax.psum(jnp.where(ok[..., None], rows, 0), "model")

        w_true = shard_gather(lab)                       # (T, d)
        w_samp = shard_gather(sampled_ids)               # (n, d)
        lt = softcap(jnp.sum(xt * w_true, -1), cap)
        ls = softcap(xt @ w_samp.T, cap)
        ls = jnp.where(sampled_ids[None, :] == lab[:, None], NEG, ls)
        mx = jnp.maximum(lt, ls.max(-1))
        lse = mx + jnp.log(jnp.exp(lt - mx) + jnp.exp(ls - mx[:, None]).sum(-1))
        loss = (lse - lt).mean()
        dp = dp_axes(mesh)
        return jax.lax.pmean(loss, dp) if dp else loss

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dps, None, None), P("model", None), P(dps, None), P(None)),
        out_specs=P(),
    )(x, table, labels, sampled_ids)


def decode_logits(x, table, cfg: ModelConfig):
    """Full vocab-parallel logits for sampling. x: (B, 1, d) -> (B, V_pad)
    fp32, vocab-padding columns masked to NEG; the output stays sharded
    over "model" on its vocab dim (the shard_map out_spec reassembles)."""
    mesh = jax.sharding.get_abstract_mesh()
    B = x.shape[0]
    dps = _dp_spec(mesh, B)
    cap = cfg.final_logit_softcap

    def body(x, table_l):
        V_l = table_l.shape[0]
        off = jax.lax.axis_index("model") * V_l
        logits = jnp.einsum("bd,vd->bv", x[:, 0].astype(jnp.float32),
                            table_l.astype(jnp.float32))
        logits = softcap(logits, cap)
        col_ok = (off + jnp.arange(V_l)) < cfg.vocab_size
        return jnp.where(col_ok[None, :], logits, NEG)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dps, None, None), P("model", None)),
        out_specs=P(dps, "model"),
    )(x, table)


def decode_logits_argmax(x, table, cfg: ModelConfig):
    """Greedy next token from vocab-parallel logits. x: (B, 1, d) -> (B,)."""
    mesh = jax.sharding.get_abstract_mesh()
    B = x.shape[0]
    dps = _dp_spec(mesh, B)
    cap = cfg.final_logit_softcap

    def body(x, table_l):
        V_l = table_l.shape[0]
        off = jax.lax.axis_index("model") * V_l
        logits = jnp.einsum("bd,vd->bv", x[:, 0].astype(jnp.float32),
                            table_l.astype(jnp.float32))
        logits = softcap(logits, cap)
        col_ok = (off + jnp.arange(V_l)) < cfg.vocab_size
        logits = jnp.where(col_ok[None, :], logits, NEG)
        mx = logits.max(-1)
        am = off + jnp.argmax(logits, -1).astype(jnp.int32)
        # stitch: pick argmax across shards
        all_mx = jax.lax.all_gather(mx, "model", axis=0)     # (tp, B)
        all_am = jax.lax.all_gather(am, "model", axis=0)
        best = jnp.argmax(all_mx, axis=0)
        return jnp.take_along_axis(all_am, best[None, :], axis=0)[0]

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dps, None, None), P("model", None)),
        out_specs=P(dps),
        check_vma=False,   # result is replicated over "model" post-gather
    )(x, table)
