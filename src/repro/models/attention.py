"""Attention: GQA projections, chunked online-softmax attention (the pure-XLA
scalable path; the Pallas kernel in ``repro.kernels`` is the TPU hot path),
sliding windows, logit softcaps, qk-norm, and a sequence-sharded
flash-decode for serving against huge KV caches.

The flash-decode (``decode_attention``) is the paper's §4.2 idea transposed:
*computation moves to where the state lives*. The KV cache is sharded over
the "model" axis on its sequence dim; each shard computes a partial
softmax-attention over its slice and the partials are stitched with an
LSE-combine (pmax/psum) — Part → Gather-at-shard → Stitch, exactly.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models import modules as m
from repro.models import quant
from repro.models.layers import apply_rope, rms_norm_fp32, softcap

NEG_INF = -1.0e30


def init_attention(cfg: ModelConfig, key):
    ks = m.split_keys(key, 4)
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pairs = [
        m.named("wq", m.dense_init(ks[0], (d, H, hd),
                                   ("embed", "heads", "head_dim"))),
        m.named("wk", m.dense_init(ks[1], (d, K, hd),
                                   ("embed", "kv_heads", "head_dim"))),
        m.named("wv", m.dense_init(ks[2], (d, K, hd),
                                   ("embed", "kv_heads", "head_dim"))),
        m.named("wo", m.dense_init(ks[3], (H, hd, d),
                                   ("heads", "head_dim", "embed"),
                                   scale=1.0 / math.sqrt(H * hd))),
    ]
    if cfg.qk_norm:
        pairs.append(m.named("q_norm", m.ones_init((hd,), ("head_dim",))))
        pairs.append(m.named("k_norm", m.ones_init((hd,), ("head_dim",))))
    return m.merge(*pairs)


def project_q(params, x, cfg: ModelConfig, cos_sin=None):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_norm_fp32(q, params["q_norm"])
    if cos_sin is not None:
        q = apply_rope(q, *cos_sin)
    return q


def project_kv(params, x, cfg: ModelConfig, cos_sin=None):
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm:
        k = rms_norm_fp32(k, params["k_norm"])
    if cos_sin is not None:
        k = apply_rope(k, *cos_sin)
    return k, v


def out_proj(params, y, x_dtype):
    return jnp.einsum("bshk,hkd->bsd", y, params["wo"].astype(x_dtype))


def _attn_scale(cfg: ModelConfig) -> float:
    return cfg.attn_scale if cfg.attn_scale is not None else cfg.head_dim ** -0.5


# ---------------------------------------------------------------------------
# Dense attention (bf16 probabilities; the cheap path for short sequences —
# under layer-remat its backward saves one (B,H,Sq,Skv) bf16 block).
# ---------------------------------------------------------------------------


def dense_attention(q, k, v, *, causal=True, window=None, cap=None,
                    scale=None, q_offset=0):
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    scale = hd ** -0.5 if scale is None else scale
    # g-major grouping (head h uses kv head h % K): reshaping H -> (G, K)
    # keeps a "model"-sharded H dim expressible as a sharded G dim, so the
    # big logit tensors stay sharded under GSPMD (k-major would replicate).
    qg = q.reshape(B, Sq, G, K, hd)
    logits = jnp.einsum("bqgkh,bskh->bgkqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, cap)
    if causal:
        d = (q_offset + jnp.arange(Sq))[:, None] - jnp.arange(Skv)[None, :]
        ok = d >= 0
        if window is not None:
            ok &= d < window
        logits = jnp.where(ok[None, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    o = jnp.einsum("bgkqs,bskh->bqgkh", p, v)
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (pure XLA; O(chunk) memory).
# ---------------------------------------------------------------------------


def block_causal_attention(q, k, v, *, window=None, cap=None, scale=None,
                           chunk_kv=1024, block_q=2048, q_offset=0):
    """Causal attention with *static* triangular block skipping.

    The q range is cut into static blocks; block i only attends to the
    kv prefix it can see (and, with a sliding window, only from the first
    in-window block). Halves causal-attention flops vs the rectangular
    chunked scan — visible in the compiled HLO, hence in §Roofline.
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    assert q_offset == 0 and Sq == Skv, "self-attention prefill only"
    nb = -(-Sq // block_q)
    outs = []
    for qi in range(nb):
        lo, hi = qi * block_q, min((qi + 1) * block_q, Sq)
        start = 0
        if window is not None:
            start = max(0, (lo - window) // chunk_kv * chunk_kv)
        outs.append(chunked_attention(
            q[:, lo:hi], k[:, start:hi], v[:, start:hi], causal=True,
            window=window, cap=cap, scale=scale, chunk_kv=chunk_kv,
            q_offset=lo - start))
    return jnp.concatenate(outs, axis=1)


def chunked_attention(q, k, v, *, causal=True, window=None, cap=None,
                      scale=None, chunk_kv=1024, q_offset=0):
    """q: (B,Sq,H,hd); k,v: (B,Skv,K,hd) with H % K == 0 (GQA).

    Scans over KV chunks with a streaming (max, sum, acc) softmax state, so
    peak logit memory is O(Sq * chunk_kv) instead of O(Sq * Skv). ``q_offset``
    is the absolute position of q[0] (for prefill continuation / decode).
    """
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    scale = hd ** -0.5 if scale is None else scale
    chunk_kv = min(chunk_kv, Skv)
    n_chunks = -(-Skv // chunk_kv)
    pad = n_chunks * chunk_kv - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qg = q.reshape(B, Sq, G, K, hd)     # g-major; see dense_attention
    q_pos = q_offset + jnp.arange(Sq)

    kc = k.reshape(B, n_chunks, chunk_kv, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk_kv, K, hd).transpose(1, 0, 2, 3, 4)

    def body(carry, inputs):
        mx, sm, acc = carry
        ci, k_i, v_i = inputs
        k_pos = ci * chunk_kv + jnp.arange(chunk_kv)
        logits = jnp.einsum("bqgkh,bckh->bqgkc", qg, k_i,
                            preferred_element_type=jnp.float32) * scale
        logits = softcap(logits, cap)
        valid = (k_pos < Skv)[None, None, None, None, :]
        if causal:
            d = q_pos[:, None] - k_pos[None, :]
            ok = d >= 0
            if window is not None:
                ok &= d < window
            valid = valid & ok[None, :, None, None, :]
        logits = jnp.where(valid, logits, NEG_INF)
        new_mx = jnp.maximum(mx, logits.max(axis=-1))
        p = jnp.exp(logits - new_mx[..., None])
        corr = jnp.exp(mx - new_mx)
        sm = sm * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqgkc,bckh->bqgkh", p.astype(v_i.dtype), v_i,
            preferred_element_type=jnp.float32)
        return (new_mx, sm, acc), None

    init = (jnp.full((B, Sq, G, K), NEG_INF, jnp.float32),
            jnp.zeros((B, Sq, G, K), jnp.float32),
            jnp.zeros((B, Sq, G, K, hd), jnp.float32))
    (mx, sm, acc), _ = jax.lax.scan(
        body, init, (jnp.arange(n_chunks), kc, vc))
    out = acc / jnp.maximum(sm, 1e-37)[..., None]
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode against a sequence-sharded KV cache (flash-decode LSE combine).
# ---------------------------------------------------------------------------


def _decode_attn_local(q, k, v, pos, seq_offset, *, window, cap, scale):
    """Partial attention over a local cache slice.

    Returns (o, lse) fp32 where o is the *normalized* local attention
    output (softmax over the local slice only) and lse its log-sum-exp;
    the cross-shard stitch is o_glob = Σ o_i·exp(lse_i - m) / Σ exp(lse_i-m).
    """
    B, _, H, hd = q.shape
    _, S_l, K, _ = k.shape
    G = H // K
    qg = q.reshape(B, G, K, hd)         # g-major; see dense_attention
    logits = jnp.einsum("bgkh,bskh->bgks", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, cap)
    k_pos = seq_offset + jnp.arange(S_l)
    ok = k_pos[None, :] <= pos[:, None]                       # (B, S_l)
    if window is not None:
        ok &= k_pos[None, :] > pos[:, None] - window
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    mx = logits.max(axis=-1)
    p = jnp.exp(logits - mx[..., None])
    sm = jnp.maximum(p.sum(axis=-1), 1e-37)
    # normalize in fp32 then cast, like dense_attention — keeps the static
    # Server's decode bit-identical to the paged engine's (which matters
    # for the serving equivalence tests, where one path recomputes tokens
    # the other produced incrementally)
    o = jnp.einsum("bgks,bskh->bgkh", (p / sm[..., None]).astype(v.dtype),
                   v, preferred_element_type=jnp.float32)
    lse = mx + jnp.log(sm)
    return o, lse


def decode_attention(q, k_cache, v_cache, pos, *, window=None, cap=None,
                     scale=None, dp_axes=("data",), seq_axis="model"):
    """q: (B,1,H,hd); caches: (B,S,K,hd) sharded (batch->dp, seq->model).

    pos: (B,) int32 — index of the newest token (attends to [0, pos]).
    Runs as shard_map over the mesh; each model shard attends over its local
    sequence slice; partials are combined with a max/LSE psum stitch.
    """
    B, _, H, hd = q.shape
    S = k_cache.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    mesh = jax.sharding.get_abstract_mesh()
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    dp_b = dp if (dp and B % math.prod(mesh.shape[a] for a in dp) == 0) else ()
    dps = dp_b if dp_b else None
    # drop seq sharding when the cache length doesn't divide the axis
    # (e.g. whisper's 1500-frame cross cache on a 16-wide axis); the
    # LSE-stitch stays correct because num and den scale identically.
    if seq_axis not in mesh.axis_names or S % mesh.shape[seq_axis] != 0:
        seq_axis_eff = None
    else:
        seq_axis_eff = seq_axis

    def body(q, k, v, pos):
        if seq_axis_eff is not None:
            idx = jax.lax.axis_index(seq_axis_eff)
        else:
            idx = 0
        S_l = k.shape[1]
        o, lse = _decode_attn_local(q, k, v, pos, idx * S_l,
                                    window=window, cap=cap, scale=scale)
        mx = jax.lax.pmax(lse, seq_axis)
        w = jnp.exp(lse - mx)
        den = jax.lax.psum(w, seq_axis)
        num = jax.lax.psum(o * w[..., None], seq_axis)
        r = num / jnp.maximum(den, 1e-37)[..., None]       # (B_l, G, K, hd)
        return r.reshape(r.shape[0], 1, H, hd)

    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dps, None, None, None),
                  P(dps, seq_axis_eff, None, None),
                  P(dps, seq_axis_eff, None, None), P(dps)),
        out_specs=P(dps, None, None, None),
    )(q, k_cache, v_cache, pos)
    return out.astype(q.dtype)


def decode_attention_local(q, k_cache, v_cache, pos, *, window=None, cap=None,
                           scale=None):
    """Unsharded decode attention (smoke tests / cross-attention)."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    o, _ = _decode_attn_local(q, k_cache, v_cache, pos, 0,
                              window=window, cap=cap, scale=scale)
    B, G, K, hd = o.shape       # o is already normalized
    return o.reshape(B, 1, G * K, hd).astype(q.dtype)


def update_cache(cache, new, pos):
    """cache: (B,S,K,hd); new: (B,1,K,hd); pos: (B,) — scatter at positions."""
    B = cache.shape[0]
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (p, 0, 0)))(
            cache, new, pos)


# A head-major page pool (num_blocks, K, block_size, hd) sharded by kv head.
POOL_SPEC = P(None, "model", None, None)


def _constrain_pool(pages):
    """Keep a page pool sharded by kv head across the scatter update —
    without the constraint GSPMD is free to replicate the (large) pools
    between the KV write and the shard_map'd attention read."""
    tp, mesh = _paged_tp(pages.shape[1])
    if tp == 1:
        return pages
    return jax.lax.with_sharding_constraint(
        pages, jax.sharding.NamedSharding(mesh, POOL_SPEC))


def _write_rows(pages, blk, off, new):
    """Write KV rows into a head-major pool: pool row ``(blk[r], h,
    off[r])`` of every kv head h takes ``new[r, h]``. One row of hd per
    (token, kv head), so the scatter's window is that row alone and the
    pool keeps the layout the attention kernels read: a window across
    the kv heads makes XLA relayout the whole pool around each write.

    pages: (N, K, block_size, hd); blk, off: (R,); new: (R, K, hd)."""
    heads = jnp.arange(pages.shape[1], dtype=jnp.int32)[None]
    return _constrain_pool(pages.at[blk[:, None], heads, off[:, None]].set(
        new.astype(pages.dtype)))


def update_paged_cache(pages, new, block_tables, pos):
    """Scatter one new KV row per sequence into its block-table page.

    pages: (num_blocks, K, block_size, hd); new: (B, 1, K, hd); pos: (B,)
    absolute write position. Inactive serving slots carry a table row of
    trash-block ids (block 0, or a layer's own first block in the layer
    scan's flat pool view), so their writes corrupt nothing.
    """
    bs = pages.shape[2]
    block_ids = jnp.take_along_axis(
        block_tables, (pos // bs)[:, None], axis=1)[:, 0]     # (B,)
    return _write_rows(pages, block_ids, pos % bs, new[:, 0])


def update_paged_cache_chunk(pages, new, block_tables, q_start, q_lens,
                             trash=0):
    """Scatter a chunk of new KV rows per sequence into its pages.

    pages: (num_blocks, K, block_size, hd); new: (B, C, K, hd); q_start:
    (B,) absolute position of chunk row 0; q_lens: (B,) valid rows. Rows
    past q_lens are routed to the reserved trash block ``trash`` (block 0
    of a layer's pool, never allocated to a request), like an idle decode
    slot's write.
    """
    bs = pages.shape[2]
    B, C = new.shape[:2]
    nb = block_tables.shape[1]
    pos = q_start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]   # (B, C)
    idx = jnp.clip(pos // bs, 0, nb - 1)
    blk = jnp.take_along_axis(block_tables, idx, axis=1)            # (B, C)
    valid = jnp.arange(C)[None] < q_lens[:, None]
    blk = jnp.where(valid, blk, trash)              # trash the padding rows
    return _write_rows(pages, blk.reshape(-1), (pos % bs).reshape(-1),
                       new.reshape(B * C, *new.shape[2:]))


def update_paged_cache_ragged(pages, new, block_tables, ctx_lens, starts,
                              ends, row_seq, trash=0):
    """Scatter a packed (ragged) multi-sequence chunk of KV into pages.

    pages: (num_blocks, K, block_size, hd); new: (1, T, K, hd) — chunks of
    up to S sequences packed back to back; sequence s owns flat rows
    [starts[s], ends[s]) and row_seq maps each flat row to its owner. Flat
    row t lands at absolute position ``ctx_lens[s] - (ends[s] - starts[s])
    + (t - starts[s])`` in sequence s's block table. Rows owned by nobody
    are routed to the reserved trash block ``trash``, exactly like the
    padding rows of :func:`update_paged_cache_chunk` — same values, same
    destination rows, so the pool contents match the single-chunk path
    bit for bit.
    """
    bs = pages.shape[2]
    T = new.shape[1]
    nb = block_tables.shape[1]
    t = jnp.arange(T, dtype=jnp.int32)
    q_start = (ctx_lens - (ends - starts))[row_seq]           # (T,)
    valid = (t >= starts[row_seq]) & (t < ends[row_seq])
    pos = jnp.where(valid, q_start + (t - starts[row_seq]), 0)
    idx = jnp.clip(pos // bs, 0, nb - 1)
    blk = jnp.where(valid, block_tables[row_seq, idx], trash)
    return _write_rows(pages, blk, pos % bs, new[0])


def replicate_over_model(x):
    """Gather ``x`` to replicated when the mesh has a nontrivial "model"
    axis (no-op otherwise). The serving TP invariant hangs on this: state
    shards by kv head (paged KV pools, per-slot cross K/V), per-head
    compute is exact on its shard, and the head-sharded result is
    gathered *before* any contraction that crosses heads (out-proj). The
    gather is an exact collective, so every weight contraction then runs
    whole on every shard in single-device op order — engine outputs stay
    bitwise identical on any mesh shape (docs/multi-host.md)."""
    mesh = jax.sharding.get_abstract_mesh()
    if "model" not in mesh.axis_names or mesh.shape["model"] <= 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, P(*([None] * x.ndim))))


def _paged_tp(num_kv_heads: int):
    """(tp, mesh) for the serving kv-head-sharded paged-attention path.

    tp > 1 only when the ambient mesh has a "model" axis that divides the
    kv-head count — the pools shard by whole kv heads, so an indivisible
    count falls back to the replicated single-device path (the engine
    refuses such meshes up front; see spmd.sharding.paged_pool_pspec)."""
    mesh = jax.sharding.get_abstract_mesh()
    if "model" not in mesh.axis_names:
        return 1, None
    tp = mesh.shape["model"]
    if tp <= 1 or num_kv_heads % tp != 0:
        return 1, None
    return tp, mesh


def paged_decode_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                           window=None, cap=None, scale=None,
                           k_scale=None, v_scale=None):
    """Decode attention via block tables. q: (B,1,H,hd) -> (B,1,H,hd).

    On a mesh with a "model" axis that divides the kv-head count this runs
    under ``shard_map``: the page pools stay sharded by kv head, each
    shard runs the paged kernel over its own head slice (all G query heads
    of each local kv head — attention per head is complete on its shard,
    no cross-shard stitch), and only the host-replicated block table and
    context lengths are shared. Computation moves to where the KV lives —
    the paper's §4.2 argument, applied to the serving pools. Quantized
    pools pass their fp32 scale pools (same kv-head sharding, hd dim 1).
    """
    from repro.kernels import ops as kops
    B, _, H, hd = q.shape
    K = k_pages.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    tp, mesh = _paged_tp(K)
    if tp == 1:
        o = kops.paged_attention(q[:, 0], k_pages, v_pages, block_tables,
                                 ctx_lens, window=window, cap=cap,
                                 scale=scale, k_scale=k_scale,
                                 v_scale=v_scale)
        return o[:, None].astype(q.dtype)
    G = H // K
    qg = q[:, 0].reshape(B, G, K, hd)         # g-major; see dense_attention

    def body(qg, kp, vp, bt, ctx, *scales):
        K_l = kp.shape[1]
        ks, vs = scales if scales else (None, None)
        o = kops.paged_attention(qg.reshape(B, G * K_l, hd), kp, vp, bt,
                                 ctx, window=window, cap=cap, scale=scale,
                                 k_scale=ks, v_scale=vs)
        return o.reshape(B, G, K_l, hd)

    extra = (k_scale, v_scale) if k_scale is not None else ()
    o = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, "model", None), POOL_SPEC, POOL_SPEC,
                  P(None, None), P(None), *([POOL_SPEC] * len(extra))),
        out_specs=P(None, None, "model", None),
        # the paged kernels' pallas_call outputs carry no varying-axes type
        check_vma=False,
    )(qg, k_pages, v_pages, block_tables, ctx_lens, *extra)
    return replicate_over_model(o).reshape(B, 1, H, hd).astype(q.dtype)


def paged_chunk_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                          q_lens, *, window=None, cap=None, scale=None,
                          k_scale=None, v_scale=None):
    """Chunked-prefill attention via block tables: the C queries of one
    prompt chunk attend causally to the paged context (prior chunks' KV
    read through the table; this chunk's KV already scattered in).
    q: (B,C,H,hd) -> (B,C,H,hd). Sharded over kv heads exactly like
    :func:`paged_decode_attention` when the mesh allows."""
    from repro.kernels import ops as kops
    B, C, H, hd = q.shape
    K = k_pages.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    tp, mesh = _paged_tp(K)
    if tp == 1:
        o = kops.paged_prefill_attention(q, k_pages, v_pages, block_tables,
                                         ctx_lens, q_lens, window=window,
                                         cap=cap, scale=scale,
                                         k_scale=k_scale, v_scale=v_scale)
        return o.astype(q.dtype)
    G = H // K
    qg = q.reshape(B, C, G, K, hd)            # g-major; see dense_attention

    def body(qg, kp, vp, bt, ctx, qlen, *scales):
        K_l = kp.shape[1]                     # (nb, K_l, bs, hd)
        ks, vs = scales if scales else (None, None)
        o = kops.paged_prefill_attention(
            qg.reshape(B, C, G * K_l, hd), kp, vp, bt, ctx, qlen,
            window=window, cap=cap, scale=scale, k_scale=ks, v_scale=vs)
        return o.reshape(B, C, G, K_l, hd)

    extra = (k_scale, v_scale) if k_scale is not None else ()
    o = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, None, "model", None),
                  POOL_SPEC, POOL_SPEC, P(None, None), P(None),
                  P(None), *([POOL_SPEC] * len(extra))),
        out_specs=P(None, None, None, "model", None),
        # the paged kernels' pallas_call outputs carry no varying-axes type
        check_vma=False,
    )(qg, k_pages, v_pages, block_tables, ctx_lens, q_lens, *extra)
    return replicate_over_model(o).reshape(B, C, H, hd).astype(q.dtype)


def stitch_paged_partials(os, lses):
    """Combine per-shard partial paged attentions into the global result.

    os: (S, ..., hd) locally-normalized fp32 outputs; lses: (...,) matching
    fp32 log-sum-exps (one entry per shard along axis 0). The combine is
    the flash-decode stitch ``decode_attention`` uses across its "model"
    shards: renormalize each partial by its share of the global softmax
    mass. Rows no shard attended (all lse <= -1e30) come out zero.
    """
    m = lses.max(axis=0)
    w = jnp.exp(lses - m[None])
    den = jnp.maximum(w.sum(axis=0), 1e-37)
    return (os * w[..., None]).sum(axis=0) / den[..., None]


def paged_shard_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                          n_shards, *, window=None, cap=None, scale=None):
    """Pool-sharded paged decode attention: blocks-axis sharding + stitch.

    The substrate for scaling the page pools past the kv-head count
    (multi-host serving, docs/multi-host.md): shard s holds the pages of
    table entries ``j % n_shards == s`` (round-robin stand-in for
    by-residence ownership), runs the partial-softmax kernel over its
    shard-local table, and the partials are LSE-stitched. Equivalent to
    :func:`paged_decode_attention`'s math for any n_shards — pinned
    against ``kernels.ref.paged_shard_attention_ref`` and the dense
    reference by the stitch tests. q: (B, H, hd) -> (B, H, hd).
    """
    from repro.kernels import ops as kops
    if n_shards < 1:
        raise ValueError(f"n_shards={n_shards} must be >= 1")
    B, nb = block_tables.shape
    entry = jnp.arange(nb)[None, :]
    parts = [kops.paged_attention_partial(
        q, k_pages, v_pages, block_tables, ctx_lens,
        jnp.broadcast_to(entry % n_shards == s, (B, nb)),
        window=window, cap=cap, scale=scale) for s in range(n_shards)]
    o = stitch_paged_partials(jnp.stack([p[0] for p in parts]),
                              jnp.stack([p[1] for p in parts]))
    return o.astype(q.dtype)


def _dense_pages(pages, block_tables):
    """Gather a head-major pool (N, K, block_size, t) through the block
    table into the dense per-sequence layout (B, nb * block_size, K, t)."""
    g = pages[block_tables]                           # (B, nb, K, bs, t)
    B, nb, K, bs, t = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(B, nb * bs, K, t)


def paged_chunk_attention_xla(q, k_pages, v_pages, block_tables, ctx_lens,
                              q_lens, *, window=None, cap=None, scale=None,
                              k_scale=None, v_scale=None):
    """Pure-XLA chunked-prefill path: densify the block-table gather, then
    ``dense_attention``'s exact op sequence (fp32 logits, *normalized*
    softmax cast to bf16, then p @ v) with per-sequence query offsets.

    Mirroring ``dense_attention`` bit-for-bit matters: the engine promises
    greedy outputs identical to a monolithic prefill, and the masked-out
    padded keys contribute exact fp32 zeros, so only the probability
    rounding order could diverge — this keeps it the same. Padding rows
    (i >= q_lens) emit garbage; their KV went to the trash block and the
    engine discards their logits. Quantized pools dequantize right after
    the gather (same ``quant.dequantize_kv`` round-trip the kernels use,
    so attention operands are bit-identical across paths).
    """
    B, C, H, hd = q.shape
    K = k_pages.shape[1]
    G = H // K
    scale = hd ** -0.5 if scale is None else scale
    k = _dense_pages(k_pages, block_tables)
    v = _dense_pages(v_pages, block_tables)
    if k_scale is not None:
        k = quant.dequantize_kv(k, _dense_pages(k_scale, block_tables))
        v = quant.dequantize_kv(v, _dense_pages(v_scale, block_tables))
    S = k.shape[1]
    qg = q.reshape(B, C, G, K, hd)
    logits = jnp.einsum("bqgkh,bskh->bgkqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, cap)
    q_pos = (ctx_lens - q_lens)[:, None] + jnp.arange(C)[None]      # (B, C)
    d = q_pos[..., None] - jnp.arange(S)[None, None]                # (B,C,S)
    ok = d >= 0
    if window is not None:
        ok &= d < window
    logits = jnp.where(ok[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    o = jnp.einsum("bgkqs,bskh->bqgkh", p, v)
    return o.reshape(B, C, H, hd).astype(q.dtype)


def ragged_chunk_attention_xla(q, k_pages, v_pages, block_tables, ctx_lens,
                               starts, ends, row_seq, *, window=None,
                               cap=None, scale=None, k_scale=None,
                               v_scale=None):
    """Pure-XLA packed (ragged) chunked-prefill path.

    q: (T, H, hd) flat packed rows (layout contract on
    ``kernels.ref.ragged_paged_prefill_attention_ref``). Gathers each
    packed sequence's rows into the dense (S, T, H, hd) layout, runs
    ``paged_chunk_attention_xla`` — the *same function, same op order* the
    single-chunk engine path uses, just with S batch rows instead of 1 —
    and scatters the rows back flat. The gather/scatter are exact copies,
    so per-row outputs match the single-chunk path bit for bit; rows owned
    by no sequence come back zero.
    """
    T = q.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    q_lens = ends - starts
    gidx = jnp.clip(starts[:, None] + t[None], 0, T - 1)      # (S, T)
    od = paged_chunk_attention_xla(
        q[gidx], k_pages, v_pages, block_tables, ctx_lens, q_lens,
        window=window, cap=cap, scale=scale, k_scale=k_scale,
        v_scale=v_scale)                                      # (S, T, H, hd)
    off = jnp.clip(t - starts[row_seq], 0, T - 1)
    o = od[row_seq, off]                                      # (T, H, hd)
    valid = (t >= starts[row_seq]) & (t < ends[row_seq])
    return jnp.where(valid[:, None, None], o, 0.0).astype(q.dtype)


def ragged_chunk_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                           starts, ends, row_seq, *, window=None, cap=None,
                           scale=None, k_scale=None, v_scale=None):
    """Packed (ragged) chunked-prefill attention via block tables: chunks
    of up to S sequences ride one flat (1, T, H, hd) token batch, each row
    attending causally to its owner's paged context (the chunk's KV
    already scattered in). Sharded over kv heads exactly like
    :func:`paged_chunk_attention` when the mesh allows."""
    from repro.kernels import ops as kops
    _, T, H, hd = q.shape
    K = k_pages.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    tp, mesh = _paged_tp(K)
    if tp == 1:
        o = kops.ragged_paged_prefill_attention(
            q[0], k_pages, v_pages, block_tables, ctx_lens, starts, ends,
            row_seq, window=window, cap=cap, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
        return o[None].astype(q.dtype)
    G = H // K
    qg = q[0].reshape(T, G, K, hd)            # g-major; see dense_attention

    def body(qg, kp, vp, bt, ctx, st, en, rs, *scales):
        K_l = kp.shape[1]
        ks, vs = scales if scales else (None, None)
        o = kops.ragged_paged_prefill_attention(
            qg.reshape(T, G * K_l, hd), kp, vp, bt, ctx, st, en, rs,
            window=window, cap=cap, scale=scale, k_scale=ks, v_scale=vs)
        return o.reshape(T, G, K_l, hd)

    extra = (k_scale, v_scale) if k_scale is not None else ()
    o = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, "model", None), POOL_SPEC, POOL_SPEC,
                  P(None, None), P(None), P(None), P(None), P(None),
                  *([POOL_SPEC] * len(extra))),
        out_specs=P(None, None, "model", None),
        # the paged kernels' pallas_call outputs carry no varying-axes type
        check_vma=False,
    )(qg, k_pages, v_pages, block_tables, ctx_lens, starts, ends, row_seq,
      *extra)
    return replicate_over_model(o).reshape(1, T, H, hd).astype(q.dtype)


def ragged_chunk_update_attend(q, k_new, v_new, k_pages, v_pages,
                               block_tables, ctx_lens, starts, ends,
                               row_seq, *, window=None, cap=None,
                               scale=None, k_scale=None, v_scale=None,
                               trash=0):
    """Scatter a packed chunk's KV into the pages and attend, fused when
    the backend allows.

    q: (1, T, H, hd); k_new/v_new: (1, T, K, hd) — same flat row layout.
    Returns ``(o, k_pages, v_pages)``. On the single-shard Pallas path the
    scatter rides inside the ragged kernel (aliased page outputs); the XLA
    path and the kv-head-sharded mesh path run
    :func:`update_paged_cache_ragged` then the attend — same pool bytes,
    same outputs.

    Quantized pools (``k_scale``/``v_scale`` given): the chunk's bf16 KV
    is quantized here — chunk-sized, so no bf16 copy of the *pool* ever
    materializes — and its scale rows are scattered into the scale pools
    *before* the fused kernel launches (the kernel reads scale pages for
    the dequant). Returns ``(o, k_pages, v_pages, k_scale, v_scale)``.
    ``trash``: the trash block of the scatters, as in
    :func:`update_paged_cache_ragged`.
    """
    from repro.kernels import ops as kops
    K = k_pages.shape[1]
    tp, _ = _paged_tp(K)
    if k_scale is not None:
        kvd = quant.kv_dtype_name(k_pages.dtype)
        kq, ksr = quant.quantize_kv(k_new, kvd)      # (1,T,K,hd),(1,T,K,1)
        vq, vsr = quant.quantize_kv(v_new, kvd)
        ks = update_paged_cache_ragged(k_scale, ksr, block_tables, ctx_lens,
                                       starts, ends, row_seq, trash)
        vs = update_paged_cache_ragged(v_scale, vsr, block_tables, ctx_lens,
                                       starts, ends, row_seq, trash)
        if tp == 1:
            o, kc, vc = kops.ragged_prefill_update_attend(
                q[0], kq[0], vq[0], k_pages, v_pages, block_tables,
                ctx_lens, starts, ends, row_seq, window=window, cap=cap,
                scale=scale, k_scale=ks, v_scale=vs, trash=trash)
            return o[None].astype(q.dtype), kc, vc, ks, vs
        kc = update_paged_cache_ragged(k_pages, kq, block_tables, ctx_lens,
                                       starts, ends, row_seq, trash)
        vc = update_paged_cache_ragged(v_pages, vq, block_tables, ctx_lens,
                                       starts, ends, row_seq, trash)
        o = ragged_chunk_attention(q, kc, vc, block_tables, ctx_lens,
                                   starts, ends, row_seq, window=window,
                                   cap=cap, scale=scale, k_scale=ks,
                                   v_scale=vs)
        return o, kc, vc, ks, vs
    if tp == 1:
        o, kc, vc = kops.ragged_prefill_update_attend(
            q[0], k_new[0], v_new[0], k_pages, v_pages, block_tables,
            ctx_lens, starts, ends, row_seq, window=window, cap=cap,
            scale=scale, trash=trash)
        return o[None].astype(q.dtype), kc, vc
    kc = update_paged_cache_ragged(k_pages, k_new, block_tables, ctx_lens,
                                   starts, ends, row_seq, trash)
    vc = update_paged_cache_ragged(v_pages, v_new, block_tables, ctx_lens,
                                   starts, ends, row_seq, trash)
    o = ragged_chunk_attention(q, kc, vc, block_tables, ctx_lens, starts,
                               ends, row_seq, window=window, cap=cap,
                               scale=scale)
    return o, kc, vc


def attention_scale(cfg: ModelConfig) -> float:
    return _attn_scale(cfg)


def sharded_attention(q, k, v, cfg: ModelConfig, **kw):
    """Full-sequence attention with an automatic sequence-parallel fallback.

    When num_heads doesn't divide the "model" axis (starcoder2's 24,
    whisper's 20, qwen2-vl's 12 on a 16-wide axis), head-sharding cannot
    apply and GSPMD would replicate the whole attention computation on every
    chip. Instead we constrain q (and the output) to be sharded over "model"
    on the *query sequence* dim — causal masking is position-based, so each
    shard computes its own q rows against full K/V: attention flops drop by
    the model-axis size.
    """
    from repro.kernels import ops as kops
    mesh = jax.sharding.get_abstract_mesh()
    tp = mesh.shape.get("model", 1)
    Sq = q.shape[1]
    if tp > 1 and cfg.num_heads % tp != 0 and Sq % tp == 0:
        from repro.spmd.sharding import batch_spec
        b = batch_spec(q.shape[0], mesh, extra_dims=0)
        spec = P(b[0] if len(b) else None, "model", None, None)
        sh = jax.sharding.NamedSharding(mesh, spec)
        q = jax.lax.with_sharding_constraint(q, sh)
        y = kops.flash_attention(q, k, v, **kw)
        return jax.lax.with_sharding_constraint(y, sh)
    return kops.flash_attention(q, k, v, **kw)
