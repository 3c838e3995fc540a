"""Pure-jnp oracles for every kernel. Naive, exact, O(S^2)/O(S·N) memory —
tests only. The scalable XLA paths live in repro.models.*; the TPU paths in
repro.kernels.<name>."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import quant


def _gather_pages(pages, scale, block_tables):
    """Densify a head-major page pool ``(N, K, block_size, hd)`` through
    the block table into ``(B, nb * block_size, K, hd)``; a quantized pool
    (per-row scale supplied) dequantizes right after the gather — the
    bf16 round-trip in ``quant.dequantize_kv`` is the same one the
    kernels apply in-tile, so both paths attend identical operands."""
    g = pages[block_tables]                         # (B, nb, K, bs, hd)
    if scale is not None:
        g = quant.dequantize_kv(g, scale[block_tables])
    B, nb, K, bs, hd = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(B, nb * bs, K, hd)


def attention_ref(q, k, v, *, causal=True, window=None, cap=None, scale=None,
                  q_offset=0):
    """Naive full-materialization attention. q: (B,Sq,H,hd); k/v: (B,Skv,K,hd)."""
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    scale = hd ** -0.5 if scale is None else scale
    # g-major GQA grouping (head h uses kv head h % K) — matches models/.
    qg = q.reshape(B, Sq, G, K, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    logits = jnp.einsum("bqgkh,bskh->bqgks", qg, kf) * scale
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)
    if causal:
        qp = q_offset + jnp.arange(Sq)
        kp = jnp.arange(Skv)
        d = qp[:, None] - kp[None, :]
        ok = d >= 0
        if window is not None:
            ok &= d < window
        logits = jnp.where(ok[None, :, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bqgks,bskh->bqgkh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, block_tables, ctx_lens, *,
                        window=None, cap=None, scale=None,
                        k_scale=None, v_scale=None):
    """Paged decode attention oracle: densify the block-table gather, then
    the exact masked-softmax math of ``models.attention._decode_attn_local``.

    q: (B, H, hd); pages: (num_blocks, K, block_size, hd);
    block_tables: (B, nb) int32; ctx_lens: (B,) int32 (0 => zero output).
    k_scale/v_scale: optional (num_blocks, K, block_size, 1) fp32 per-row
    scales for a quantized pool (dequantized after the gather).
    """
    B, H, hd = q.shape
    _, K, bs, _ = k_pages.shape
    G = H // K
    scale = hd ** -0.5 if scale is None else scale
    # densify: (B, S, K, hd), S = nb * bs
    k = _gather_pages(k_pages, k_scale, block_tables)
    v = _gather_pages(v_pages, v_scale, block_tables)
    S = k.shape[1]
    qg = q.reshape(B, G, K, hd)
    logits = jnp.einsum("bgkh,bskh->bgks", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)
    k_pos = jnp.arange(S)
    ok = k_pos[None, :] < ctx_lens[:, None]                   # (B, S)
    if window is not None:
        ok &= k_pos[None, :] > ctx_lens[:, None] - 1 - window
    logits = jnp.where(ok[:, None, None, :], logits, -1e30)
    mx = logits.max(axis=-1)
    p = jnp.exp(logits - mx[..., None])
    p = jnp.where(ok[:, None, None, :], p, 0.0)   # ctx=0 rows -> all zero
    sm = jnp.maximum(p.sum(axis=-1), 1e-37)
    # repo-wide rounding convention (matches dense_attention): normalize in
    # fp32, cast, then multiply — so decode-written KV is bit-identical to
    # the same position recomputed by prefill/chunked-prefill.
    p = (p / sm[..., None]).astype(v.dtype)
    o = jnp.einsum("bgks,bskh->bgkh", p, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, hd).astype(q.dtype)


def paged_attention_partial_ref(q, k_pages, v_pages, block_tables, ctx_lens,
                                block_mask, *, window=None, cap=None,
                                scale=None, k_scale=None, v_scale=None):
    """Partial-softmax paged decode oracle for pool-sharded serving.

    Identical math to :func:`paged_attention_ref` except keys are *also*
    masked where their table entry's ``block_mask`` is False (a shard
    attends only the pages it holds), and the per-(b, head) fp32
    log-sum-exp comes back alongside the locally-normalized output —
    ``(o, lse)`` with o (B, H, hd) fp32, lse (B, H). A row that attended
    nothing has o = 0 and lse <= -1e30 (zero weight in the stitch). With a
    full mask, o equals ``paged_attention_ref`` bit for bit (same op
    order) before the final q.dtype cast.
    """
    B, H, hd = q.shape
    _, K, bs, _ = k_pages.shape
    G = H // K
    scale = hd ** -0.5 if scale is None else scale
    k = _gather_pages(k_pages, k_scale, block_tables)
    v = _gather_pages(v_pages, v_scale, block_tables)
    S = k.shape[1]
    qg = q.reshape(B, G, K, hd)
    logits = jnp.einsum("bgkh,bskh->bgks", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)
    k_pos = jnp.arange(S)
    ok = k_pos[None, :] < ctx_lens[:, None]                   # (B, S)
    if window is not None:
        ok &= k_pos[None, :] > ctx_lens[:, None] - 1 - window
    ok &= jnp.repeat(block_mask.astype(bool), bs, axis=1)     # shard-local
    logits = jnp.where(ok[:, None, None, :], logits, -1e30)
    mx = logits.max(axis=-1)
    p = jnp.exp(logits - mx[..., None])
    p = jnp.where(ok[:, None, None, :], p, 0.0)
    sm = jnp.maximum(p.sum(axis=-1), 1e-37)
    lse = mx + jnp.log(sm)
    p = (p / sm[..., None]).astype(v.dtype)
    o = jnp.einsum("bgks,bskh->bgkh", p, v,
                   preferred_element_type=jnp.float32)
    return (o.reshape(B, H, hd).astype(jnp.float32),
            lse.reshape(B, H))


def paged_shard_attention_ref(q, k_pages, v_pages, block_tables, ctx_lens,
                              n_shards, *, window=None, cap=None,
                              scale=None, k_scale=None, v_scale=None):
    """LSE-stitch oracle for pool-sharded paged decode attention.

    Simulates ``n_shards`` shards that each hold a disjoint subset of a
    sequence's pages (table entry j belongs to shard ``j % n_shards`` —
    the round-robin stand-in for by-pool-residence ownership), computes
    each shard's partial softmax attention, and stitches the partials with
    the same max/LSE combine ``models.attention.decode_attention`` uses
    for dense flash-decode:

        m   = max_i lse_i
        o   = sum_i o_i * exp(lse_i - m) / sum_i exp(lse_i - m)

    Must agree with :func:`paged_attention_ref` for every n_shards — the
    property the stitch tests pin. Raises ValueError for n_shards < 1.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards={n_shards} must be >= 1")
    B, nb = block_tables.shape
    entry = jnp.arange(nb)[None, :]
    os, lses = [], []
    for s in range(n_shards):
        mask = jnp.broadcast_to(entry % n_shards == s, (B, nb))
        o, lse = paged_attention_partial_ref(
            q, k_pages, v_pages, block_tables, ctx_lens, mask,
            window=window, cap=cap, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
        os.append(o)
        lses.append(lse)
    os, lses = jnp.stack(os), jnp.stack(lses)         # (S, B, H, [hd])
    m = lses.max(axis=0)
    w = jnp.exp(lses - m[None])
    den = jnp.maximum(w.sum(axis=0), 1e-37)
    out = (os * w[..., None]).sum(axis=0) / den[..., None]
    return out.astype(q.dtype)


def paged_prefill_attention_ref(q, k_pages, v_pages, block_tables, ctx_lens,
                                q_lens, *, window=None, cap=None, scale=None,
                                k_scale=None, v_scale=None):
    """Multi-query (chunked-prefill) paged attention oracle.

    q: (B, C, H, hd) — row i of sequence b is the query at absolute
    position ``ctx_lens[b] - q_lens[b] + i`` and attends causally to keys
    ``[0, position]`` gathered through the block table (the chunk's own KV
    is assumed already scattered into the pages). Rows at i >= q_lens[b]
    are padding and produce zeros. q_lens == 1 reduces to the decode
    oracle above.
    """
    B, C, H, hd = q.shape
    _, K, bs, _ = k_pages.shape
    G = H // K
    scale = hd ** -0.5 if scale is None else scale
    k = _gather_pages(k_pages, k_scale, block_tables)
    v = _gather_pages(v_pages, v_scale, block_tables)
    S = k.shape[1]
    qg = q.reshape(B, C, G, K, hd)
    logits = jnp.einsum("bcgkh,bskh->bcgks", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)
    q_pos = (ctx_lens - q_lens)[:, None] + jnp.arange(C)[None]      # (B, C)
    k_pos = jnp.arange(S)
    ok = k_pos[None, None] <= q_pos[..., None]                      # causal
    if window is not None:
        ok &= k_pos[None, None] > q_pos[..., None] - window
    ok &= (jnp.arange(C)[None] < q_lens[:, None])[..., None]        # padding
    ok = ok[:, :, None, None, :]                                    # g,k dims
    logits = jnp.where(ok, logits, -1e30)
    mx = logits.max(axis=-1)
    p = jnp.exp(logits - mx[..., None])
    p = jnp.where(ok, p, 0.0)             # fully-masked rows -> all zero
    sm = jnp.maximum(p.sum(axis=-1), 1e-37)
    p = (p / sm[..., None]).astype(v.dtype)   # normalize-then-cast; see
    o = jnp.einsum("bcgks,bskh->bcgkh", p, v,  # paged_attention_ref
                   preferred_element_type=jnp.float32)
    return o.reshape(B, C, H, hd).astype(q.dtype)


def ragged_paged_prefill_attention_ref(q, k_pages, v_pages, block_tables,
                                       ctx_lens, starts, ends, row_seq, *,
                                       window=None, cap=None, scale=None,
                                       k_scale=None, v_scale=None):
    """Packed (ragged) multi-sequence chunked-prefill oracle.

    q: (T, H, hd) — chunks of up to S sequences packed into one flat token
    batch; sequence s owns flat rows [starts[s], ends[s]) and row_seq maps
    each flat row to its owner. Flat row t (owned by s) is the query at
    absolute position ``ctx_lens[s] - (ends[s] - starts[s]) + (t -
    starts[s])`` and attends causally to sequence s's keys gathered through
    block_tables[s] (the chunk's own KV assumed already scattered). Rows
    owned by no sequence (t outside every [start, end)) produce zeros.
    S == 1 with starts = [0] reduces to ``paged_prefill_attention_ref``
    with B == 1.
    """
    T, H, hd = q.shape
    _, K, bs, _ = k_pages.shape
    G = H // K
    S = starts.shape[0]
    scale = hd ** -0.5 if scale is None else scale
    k = _gather_pages(k_pages, k_scale, block_tables)      # (S, E, K, hd)
    v = _gather_pages(v_pages, v_scale, block_tables)
    E = k.shape[1]
    qg = q.reshape(T, G, K, hd)
    logits = jnp.einsum("tgkh,sekh->tgkse", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)
    t = jnp.arange(T)
    own = (t[:, None] >= starts[None]) & (t[:, None] < ends[None]) \
        & (row_seq[:, None] == jnp.arange(S)[None])                  # (T, S)
    q_pos = (ctx_lens - (ends - starts))[row_seq] + (t - starts[row_seq])
    k_pos = jnp.arange(E)
    ok = own[:, :, None] & (k_pos[None, None] <= q_pos[:, None, None])
    if window is not None:
        ok &= k_pos[None, None] > q_pos[:, None, None] - window
    ok = ok[:, None, None]                                # (T, 1, 1, S, E)
    logits = jnp.where(ok, logits, -1e30)
    # one softmax over the flattened (sequence, key) axes: exactly one
    # sequence is unmasked per row, so this is that sequence's softmax
    flat = logits.reshape(T, G, K, S * E)
    okf = ok.reshape(T, 1, 1, S * E)
    mx = flat.max(axis=-1)
    p = jnp.exp(flat - mx[..., None])
    p = jnp.where(okf, p, 0.0)            # unowned rows -> all zero
    sm = jnp.maximum(p.sum(axis=-1), 1e-37)
    p = (p / sm[..., None]).astype(v.dtype)   # normalize-then-cast; see
    o = jnp.einsum("tgkf,fkh->tgkh",          # paged_attention_ref
                   p, v.reshape(S * E, K, hd),
                   preferred_element_type=jnp.float32)
    return o.reshape(T, H, hd).astype(q.dtype)


def ssd_ref(x, dt, A, B, C, h0=None):
    """Exact SSD recurrence, step by step (lax.scan over time).

    x: (b,S,nh,hp); dt: (b,S,nh); A: (nh,); B,C: (b,S,G,N).
    Returns (y (b,S,nh,hp), h_last (b,nh,hp,N)).
    """
    b, S, nh, hp = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = nh // G
    x32 = x.astype(jnp.float32)
    dt32 = dt.astype(jnp.float32)
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)   # (b,S,nh,N)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)

    def step(h, inp):
        xt, dtt, Bt, Ct = inp
        dec = jnp.exp(dtt * A)                            # (b,nh)
        h = h * dec[..., None, None] + jnp.einsum(
            "bh,bhs,bhp->bhps", dtt, Bt, xt)
        y = jnp.einsum("bhs,bhps->bhp", Ct, h)
        return h, y

    h_init = (jnp.zeros((b, nh, hp, N), jnp.float32) if h0 is None
              else h0.astype(jnp.float32))
    h_last, ys = jax.lax.scan(
        step, h_init,
        (x32.transpose(1, 0, 2, 3), dt32.transpose(1, 0, 2),
         Bh.transpose(1, 0, 2, 3), Ch.transpose(1, 0, 2, 3)))
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), h_last


def sampled_softmax_loss_ref(x, table, labels, sampled_ids, *, cap=None):
    """Sampled softmax (paper §4.2/§6.4). Per-token loss over the true class
    + a shared set of sampled false classes.

    x: (T, d); table: (V, d); labels: (T,); sampled_ids: (S,).
    Returns mean loss (scalar, fp32). No sampling-correction term (uniform
    proposal, matching the paper's microbenchmark usage).
    """
    x32 = x.astype(jnp.float32)
    w_true = table[labels].astype(jnp.float32)            # (T, d)
    w_samp = table[sampled_ids].astype(jnp.float32)       # (S, d)
    logit_true = jnp.sum(x32 * w_true, axis=-1)           # (T,)
    logit_samp = x32 @ w_samp.T                           # (T, S)
    if cap is not None:
        logit_true = cap * jnp.tanh(logit_true / cap)
        logit_samp = cap * jnp.tanh(logit_samp / cap)
    # mask accidental hits (sampled id == true label)
    hit = sampled_ids[None, :] == labels[:, None]
    logit_samp = jnp.where(hit, -1e30, logit_samp)
    allz = jnp.concatenate([logit_true[:, None], logit_samp], axis=1)
    lse = jax.scipy.special.logsumexp(allz, axis=1)
    return jnp.mean(lse - logit_true)


def softmax_xent_ref(logits, labels):
    """Full-softmax cross entropy oracle. logits: (T, V); labels: (T,)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - true)
