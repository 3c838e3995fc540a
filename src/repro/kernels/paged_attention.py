"""Paged-attention decode as a Pallas TPU kernel (vLLM-style).

One query token per sequence attends to a KV cache that lives in fixed-size
*blocks* scattered through two page pools shaped
``(num_blocks, K, block_size, hd)`` — head-major, so a pool row is one
contiguous page of every kv head and one kv head's page is a whole
``(block_size, hd)`` tile (Mosaic requires a block's last two dims to be
tile-aligned or whole). A per-sequence *block table* names the pool rows
holding that sequence's KV, in order; the serving block manager
(``repro.serving.kv_cache``) owns the tables and the free list.

The decode kernel: grid = (B,), one program per sequence holding all K kv
heads. The pools stay in HBM (``memory_space=pl.ANY``); the block table,
context lengths and block mask are scalar-prefetched. A ``fori_loop`` runs
over the row's *live* compute blocks only — from the first block inside
the sliding window to ``cdiv(ctx, P * block_size)`` — and each block
copies just the table entries that hold keys the row attends, one DMA per
pool row, double-buffered so block j+1's copies overlap block j's
attention. Entries at or past ``cdiv(ctx, block_size)`` are never read; an
inactive slot (ctx_len == 0) is one program that copies nothing and writes
zeros. The pages per compute block P come from the shapes
(``decode_pages_per_block``: as many as two (k, v) buffers hold in 512 KiB
of VMEM, 16 at K 2, hd 128, block 16 in bf16); no option or environment
variable sets them. An fp32 streaming softmax (m, l, acc) per kv head runs
over the P * block_size keys of each block.

GQA uses the repo-wide g-major convention: q head h reads kv head h % K,
so q is regrouped to (B*K, G, hd) and each program computes all G query
heads of each of its kv heads. ``interpret=True`` runs the same kernel on
CPU for tests.

``paged_prefill_attention`` is the multi-query sibling for chunked prefill:
C chunk queries per sequence, each causally masked at its absolute position
against the same paged context (C == 1 reproduces the decode kernel
exactly at the same pages per block). The serving engine uses it to stream
long prompts in while other sequences keep decoding. Its grid is
(B * K, cdiv(max_blocks_per_seq, P)), one program per (sequence, kv head,
P table entries), live or not: scalar-prefetched block tables feed the
BlockSpec index maps, dead steps are skipped with ``pl.when``, and
``pages_per_compute_block`` (``REPRO_PAGES_PER_BLOCK`` through
``kernels.ops``) batches P pages per grid step through P separate page
operands.

``ragged_paged_prefill_attention`` packs chunks of *several* sequences into
one flat (T, H, hd) batch (per-sequence [start, end) row offsets, scalar-
prefetched) so one jitted step can prefill many short prompts at once, and
can optionally fuse the chunk's KV scatter into the same kernel via aliased
page-pool outputs. See the function docstring for the layout contract.

Both fixed-shape kernels expose a *partial-softmax return path* for
pool-sharded (multi-host) serving: with ``block_mask`` a shard attends only
the table entries whose pages it holds (a shard-local block table — masked
entries are skipped entirely, never read), and with ``return_lse=True`` it
also returns each row's log-sum-exp so partials from different shards
stitch exactly like ``models.attention.decode_attention`` stitches dense
flash-decode: ``o = Σ o_i·exp(lse_i - m) / Σ exp(lse_i - m)``. The stitch
combiner lives in ``models.attention.stitch_paged_partials``; the oracle
proving the math is ``kernels.ref.paged_shard_attention_ref``. The
kv-head-sharded engine path (docs/multi-host.md) needs no stitch — each
model shard owns whole kv heads — so this path is the substrate for
sharding the *blocks* axis past the kv-head count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _dequant_tile(x, s):
    """Fused per-row dequant of a gathered page tile: (bs, hd) narrow x
    (bs, 1) fp32 scale -> bf16 -> fp32. The bf16 round-trip matches
    ``quant.dequantize_kv`` exactly, so kernels and oracles attend
    bit-identical operands."""
    return (x.astype(jnp.float32) * s).astype(jnp.bfloat16) \
        .astype(jnp.float32)


def _live_columns(lives, shape, block_size, axis=1):
    """Mask over ``shape`` of the key positions (along ``axis``, P *
    block_size of them) whose page is live. Built from an iota: Mosaic
    cannot lower a concatenate of booleans."""
    page = jax.lax.broadcasted_iota(jnp.int32, shape, axis) // block_size
    return functools.reduce(lambda a, c: a | c,
                            [(page == i) & li for i, li in enumerate(lives)])


# VMEM for one decode row's two (k, v) page buffers: the pages fetched per
# compute block are as many as fit (16 pages, 256 keys, at K 2, hd 128,
# block 16 in bf16)
_DECODE_VMEM_BYTES = 512 * 1024


def _scale_lanes(num_kv_heads, block_size):
    """Lanes of one page's scale row: its K * block_size fp32 scales,
    padded to whole 128-lane tiles so a DMA can fetch the row."""
    return -(-num_kv_heads * block_size // 128) * 128


def decode_pages_per_block(num_kv_heads, block_size, head_dim, kv_dtype,
                           with_scales, table_width):
    """Pages per compute block of the decode kernel, from the pool's
    shapes: as many whole pages (every kv head of a pool row) as two
    (k, v) buffers hold in ``_DECODE_VMEM_BYTES``, at least one and at
    most the table width. A scale row takes an (8, lanes) fp32 tile."""
    page = num_kv_heads * block_size * head_dim * \
        jnp.dtype(kv_dtype).itemsize
    if with_scales:
        page += 8 * _scale_lanes(num_kv_heads, block_size) * 4
    return max(1, min(table_width, _DECODE_VMEM_BYTES // (2 * 2 * page)))


def _decode_kernel(bt_ref, ctx_ref, mask_ref, q_ref, k_hbm, v_hbm, *rest,
                   scale, cap, window, block_size, pages_per_block,
                   table_width, with_mask, with_lse, with_scales):
    """One program per sequence; a loop over its live compute blocks.

    The pools stay in HBM. Block j covers table entries [j*P, (j+1)*P);
    only entries that hold keys this row attends (below ``ctx``, inside
    the window, held per ``block_mask``) are copied, each pool row —
    every kv head of the page — in one DMA, into one of two VMEM slots:
    block j+1's copies run while block j is attended. The loop runs from
    the first block in the window to ``cdiv(ctx, P * block_size)``, so an
    inactive slot (ctx 0) copies nothing. Stale slot contents (entries
    not copied this block) are masked out of both matmuls.

    Quantized pools come with one (1, lanes) scale row per page
    (``_scale_lanes``); a transpose puts each head's scales in a column
    for the dequant of its (block_size, hd) rows.
    """
    P, bs = pages_per_block, block_size
    if with_scales:
        ks_hbm, vs_hbm, *rest = rest
    o_ref, *rest = rest
    if with_lse:
        lse_ref, *rest = rest
    k_buf, v_buf, *rest = rest
    if with_scales:
        ks_buf, vs_buf, *rest = rest
    sem, m_scr, l_scr, acc_scr = rest
    K = q_ref.shape[0]
    b = pl.program_id(0)
    ctx = ctx_ref[b]
    n_live = (ctx + bs - 1) // bs               # pages holding a key < ctx
    first = 0 if window is None else jnp.maximum(ctx - window, 0) // bs
    lo, hi = first // P, (n_live + P - 1) // P

    def live(e):
        ok = (e >= first) & (e < n_live)
        if with_mask:
            ok &= mask_ref[b, jnp.minimum(e, table_width - 1)] != 0
        return ok

    def fetch(j, slot, op):
        """Start (or wait for) the copies of block j's live pages."""
        pairs = [(k_hbm, k_buf, 0), (v_hbm, v_buf, 1)]
        if with_scales:
            pairs += [(ks_hbm, ks_buf, 0), (vs_hbm, vs_buf, 1)]
        for i in range(P):
            e = j * P + i

            @pl.when(live(e))
            def _():
                row = bt_ref[b, jnp.minimum(e, table_width - 1)]
                for src, dst, kind in pairs:
                    copy = pltpu.make_async_copy(
                        src.at[row], dst.at[slot, i], sem.at[kind, slot])
                    getattr(copy, op)()

    def tile(buf, slot, h, scales_t=None):
        """(P*bs, hd) fp32 keys or values of kv head h in a slot."""
        x = buf[slot, :, h].astype(jnp.float32)          # (P, bs, hd)
        if scales_t is not None:
            col = jnp.stack([scales_t[h * bs:(h + 1) * bs, i:i + 1]
                             for i in range(P)])         # (P, bs, 1)
            x = _dequant_tile(x, col)
        return x.reshape(P * bs, x.shape[-1])

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(lo < hi)
    def _():
        fetch(lo, 0, "start")

    def block(j, carry):
        slot = (j - lo) % 2

        @pl.when(j + 1 < hi)
        def _():
            fetch(j + 1, 1 - slot, "start")

        fetch(j, slot, "wait")
        lives = [live(j * P + i) for i in range(P)]

        def key_mask(shape, axis):
            k_pos = j * (P * bs) + jax.lax.broadcasted_iota(
                jnp.int32, shape, axis)
            mask = k_pos < ctx
            if window is not None:
                mask &= k_pos > ctx - 1 - window
            if with_mask:
                mask &= _live_columns(lives, shape, bs, axis)
            return mask

        def attend():
            G = q_ref.shape[1]
            mask = key_mask((G, P * bs), 1)
            v_rows = key_mask((P * bs, 1), 0)
            ks_t = vs_t = None
            if with_scales:                              # (lanes, P8)
                ks_t = ks_buf[slot, :, 0, :].T
                vs_t = vs_buf[slot, :, 0, :].T
            for h in range(K):
                q = q_ref[h].astype(jnp.float32)                 # (G, hd)
                s = jax.lax.dot_general(
                    q, tile(k_buf, slot, h, ks_t), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # (G, P*bs)
                if cap is not None:
                    s = cap * jnp.tanh(s / cap)
                s = jnp.where(mask, s, NEG_INF)
                m_prev = m_scr[h]
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_scr[h] = l_scr[h] * corr + p.sum(axis=1, keepdims=True)
                m_scr[h] = m_new
                # a slot row not copied this block may hold anything (NaN
                # too): zero it, since p == 0 does not cancel a NaN
                v = jnp.where(v_rows, tile(v_buf, slot, h, vs_t), 0.0)
                acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        if with_mask:
            # a block whose every entry another shard holds attends nothing
            pl.when(functools.reduce(lambda a, c: a | c, lives))(attend)
        else:
            attend()
        return carry

    jax.lax.fori_loop(lo, hi, block, 0)
    l = jnp.maximum(l_scr[...], 1e-37)
    o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
    if with_lse:
        lse_ref[...] = m_scr[...] + jnp.log(l)


def _head_major(o, B, K, G):
    """(B*K, G, ...) -> g-major (B, G, K, ...) -> (B, H, ...)."""
    tail = o.shape[2:]
    o = o.reshape(B, K, G, *tail)
    perm = (0, 2, 1) + tuple(range(3, o.ndim))
    return o.transpose(*perm).reshape(B, G * K, *tail)


def _page_specs(nb, P, K, block_size, hd):
    """P (k, v) BlockSpecs of the chunk kernel, each fetching table entry
    j*P + i.

    Entries past the table width (last grid step when P does not divide
    nb) and block-masked entries redirect the fetch to pool row 0 so a
    shard neither reads nor DMAs pages it does not hold; the kernel's
    per-page liveness masks their columns.
    """
    def mk(i):
        def page_index(bk, j, bt_ref, ctx_ref, qlen_ref, mask_ref):
            b = bk // K
            entry = jnp.minimum(j * P + i, nb - 1)
            ok = (j * P + i < nb) & (mask_ref[b, entry] != 0)
            return (jnp.where(ok, bt_ref[b, entry], 0), bk % K, 0, 0)
        return page_index

    return [pl.BlockSpec((None, None, block_size, hd), mk(i))
            for i in range(P)]


def paged_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                    window=None, cap=None, scale=None, interpret=False,
                    block_mask=None, return_lse=False,
                    pages_per_compute_block=None,
                    k_scale=None, v_scale=None):
    """q: (B, H, hd) one decode token per sequence.
    k_pages/v_pages: (num_blocks, K, block_size, hd).
    block_tables: (B, max_blocks_per_seq) int32 pool-row ids (entries at
    or past cdiv(ctx, block_size) are never read). ctx_lens: (B,) int32 —
    tokens visible per sequence, 0 for an inactive slot (output row is
    zeros). Returns (B, H, hd) in q.dtype.

    The pages per compute block come from the shapes
    (:func:`decode_pages_per_block`); ``pages_per_compute_block`` pins
    them (clamped to the table width), which the tests use to run many
    blocks per row at small shapes. The result is the same up to fp
    reduction order.

    ``block_mask`` (B, max_blocks_per_seq) selects the table entries this
    shard holds pages for (None = all): masked entries are skipped, never
    read — the shard-local-table path for pool-sharded serving. With
    ``return_lse`` the output switches to fp32 partials ``(o, lse)`` —
    o the locally-normalized output, lse the per-(b, head) log-sum-exp of
    the attended (masked, in-context) keys — ready for
    ``models.attention.stitch_paged_partials`` (rounding o to q.dtype
    before the stitch would make the result shard-count-dependent). Rows
    that attended nothing return lse <= NEG_INF (zero stitch weight).

    ``k_scale``/``v_scale`` ((num_blocks, K, block_size, 1) fp32) mark a
    quantized pool: each fetched page tile is dequantized in-VMEM (the
    ``quant.dequantize_kv`` bf16 round-trip) before the matmuls — the
    pool itself is never widened.
    """
    B, H, hd_in = q.shape
    _, K, block_size, _ = k_pages.shape
    G = H // K
    nb = block_tables.shape[1]
    scale = hd_in ** -0.5 if scale is None else scale
    # Mosaic copies only pool rows whose last dim fills whole 128-lane
    # tiles: a narrower head (whisper's 64, zamba2's 80) is zero-padded,
    # which leaves every score and the output's first hd_in lanes as they
    # were
    hd = -(-hd_in // 128) * 128
    if hd != hd_in:
        widen = [(0, 0)] * 3 + [(0, hd - hd_in)]
        q = jnp.pad(q, widen[1:])
        k_pages, v_pages = jnp.pad(k_pages, widen), jnp.pad(v_pages, widen)
    with_scales = k_scale is not None
    if pages_per_compute_block is None:
        P = decode_pages_per_block(K, block_size, hd, k_pages.dtype,
                                   with_scales, nb)
    else:
        P = max(1, min(int(pages_per_compute_block), nb))
    with_mask = block_mask is not None
    if block_mask is None:
        block_mask = jnp.ones((B, nb), jnp.int32)

    # g-major regroup: (B, H, hd) -> (B, G, K, hd) -> (B*K, G, hd); one
    # program takes the K consecutive rows of its sequence
    qg = q.reshape(B, G, K, hd).transpose(0, 2, 1, 3).reshape(B * K, G, hd)

    kernel = functools.partial(
        _decode_kernel, scale=scale, cap=cap, window=window,
        block_size=block_size, pages_per_block=P, table_width=nb,
        with_mask=with_mask, with_lse=return_lse, with_scales=with_scales)

    row = pl.BlockSpec((K, G, hd), lambda b, *_: (b, 0, 0))
    out_specs = row
    if return_lse:
        # partials stay fp32: they are re-weighted by exp(lse - m) in the
        # stitch, and rounding them to q.dtype first would make the
        # stitched result depend on the shard count
        out_specs = (row, pl.BlockSpec((K, G, 1), lambda b, *_: (b, 0, 0)))
        out_shape = (jax.ShapeDtypeStruct((B * K, G, hd), jnp.float32),
                     jax.ShapeDtypeStruct((B * K, G, 1), jnp.float32))
    else:
        out_shape = jax.ShapeDtypeStruct((B * K, G, hd), q.dtype)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    buffers = [pltpu.VMEM((2, P, K, block_size, hd), k_pages.dtype),
               pltpu.VMEM((2, P, K, block_size, hd), v_pages.dtype)]
    scale_operands = []
    if with_scales:
        # (N, K, bs, 1) -> one (1, lanes) row per page: Mosaic copies only
        # rows whose last dim fills whole 128-lane tiles; the transpose in
        # the kernel wants the buffer's rows padded to a multiple of 8
        lanes = _scale_lanes(K, block_size)
        scale_operands = [
            jnp.pad(x.reshape(x.shape[0], 1, K * block_size),
                    ((0, 0), (0, 0), (0, lanes - K * block_size)))
            for x in (k_scale, v_scale)]
        buffers += [pltpu.VMEM((2, -(-P // 8) * 8, 1, lanes),
                               jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[row, hbm, hbm, *([hbm] * len(scale_operands))],
        out_specs=out_specs,
        scratch_shapes=[
            *buffers,
            pltpu.SemaphoreType.DMA((2, 2)),        # (k | v, slot)
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, hd), jnp.float32),
        ],
    )

    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      block_mask.astype(jnp.int32), qg, k_pages, v_pages, *scale_operands)

    if return_lse:
        o, lse = o
        return (_head_major(o[..., :hd_in], B, K, G),
                _head_major(lse[..., 0], B, K, G))
    return _head_major(o[..., :hd_in], B, K, G)


def _chunk_kernel(bt_ref, ctx_ref, qlen_ref, mask_ref, q_ref, *rest, scale,
                  cap, window, block_size, num_kv_heads, num_groups,
                  pages_per_block, table_width, with_lse, with_scales):
    """Multi-query sibling of ``_decode_kernel`` for chunked prefill.

    One program owns all C chunk queries of one (sequence, kv-head) pair;
    queries are causally masked per absolute position against the paged
    context, so C == 1 reduces exactly to the decode kernel. Rows past
    ``q_len`` are padding: every key masked, and the masked-row guard in
    the streaming softmax (p zeroed where masked, not exp(0)) keeps their
    (l, acc) at zero so they finalize to zeros.
    """
    P = pages_per_block
    k_refs, v_refs = rest[:P], rest[P:2 * P]
    rest = rest[2 * P:]
    ks_refs = vs_refs = None
    if with_scales:
        ks_refs, vs_refs = rest[:P], rest[P:2 * P]
        rest = rest[2 * P:]
    o_ref = rest[0]
    tail = rest[1:]
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = tail
    else:
        m_scr, l_scr, acc_scr = tail
    bk = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    b = bk // num_kv_heads
    ctx = ctx_ref[b]                 # visible tokens incl. the whole chunk
    qlen = qlen_ref[b]
    qstart = ctx - qlen              # absolute position of chunk row 0
    G = num_groups

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    first_k = j * (P * block_size)
    lives = []
    for i in range(P):
        entry = j * P + i
        seg_first = first_k + i * block_size
        li = (seg_first < ctx) & \
            (mask_ref[b, jnp.minimum(entry, table_width - 1)] != 0)
        if P > 1:
            li &= entry < table_width
        if window is not None:
            # earliest in-window key over the chunk: qstart - window + 1
            li &= seg_first + block_size - 1 > qstart - window
        lives.append(li)
    live = functools.reduce(lambda a, c: a | c, lives)

    @pl.when(live)
    def _compute():
        C = q_ref.shape[0]
        q = q_ref[...].astype(jnp.float32).reshape(C * G, -1)  # (C*G, hd)
        if with_scales:
            k = jnp.concatenate(
                [_dequant_tile(r[...], sr[...])
                 for r, sr in zip(k_refs, ks_refs)], axis=0)
        else:
            k = jnp.concatenate(
                [r[...] for r in k_refs], axis=0).astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (C*G, P*bs)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        k_pos = first_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
        q_pos = qstart + row
        mask = (k_pos <= q_pos) & (row < qlen)
        if window is not None:
            mask &= k_pos > q_pos - window
        if P > 1:
            mask &= _live_columns(lives, s.shape, block_size)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # masked-row guard: exp(NEG_INF - NEG_INF) would be 1, poisoning
        # fully-masked (padding) rows — zero those probabilities instead
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        m_scr[...] = m_new
        if with_scales:
            v = jnp.concatenate(
                [_dequant_tile(r[...], sr[...])
                 for r, sr in zip(v_refs, vs_refs)], axis=0)
        else:
            v = jnp.concatenate(
                [r[...] for r in v_refs], axis=0).astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        C = o_ref.shape[0]
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype).reshape(
            C, G, -1)
        if with_lse:
            lse_ref[...] = (m_scr[...] + jnp.log(l)).reshape(C, G, 1)


def paged_prefill_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                            q_lens, *, window=None, cap=None, scale=None,
                            interpret=False, block_mask=None,
                            return_lse=False, pages_per_compute_block=1,
                            k_scale=None, v_scale=None):
    """Chunked-prefill attention against a paged KV cache.

    q: (B, C, H, hd) — C chunk queries per sequence; row i sits at absolute
    position ``ctx_lens[b] - q_lens[b] + i`` and attends causally to the
    paged context (the chunk's own KV must already be scattered into the
    pages). q_lens: (B,) valid rows; padding rows produce zeros, as does a
    wholly inactive sequence (q_len == 0). Returns (B, C, H, hd) in q.dtype.

    ``pages_per_compute_block`` / ``block_mask`` / ``return_lse`` /
    ``k_scale``/``v_scale`` are as on :func:`paged_attention`; the lse
    output is (B, C, H) fp32.
    """
    B, C, H, hd = q.shape
    _, K, block_size, _ = k_pages.shape
    G = H // K
    nb = block_tables.shape[1]
    P = max(1, min(int(pages_per_compute_block), nb))
    scale = hd ** -0.5 if scale is None else scale
    with_scales = k_scale is not None
    if block_mask is None:
        block_mask = jnp.ones((B, nb), jnp.int32)

    # g-major regroup: (B,C,H,hd) -> (B,C,G,K,hd) -> (B*K, C, G, hd)
    qg = q.reshape(B, C, G, K, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(B * K, C, G, hd)

    kernel = functools.partial(
        _chunk_kernel, scale=scale, cap=cap, window=window,
        block_size=block_size, num_kv_heads=K, num_groups=G,
        pages_per_block=P, table_width=nb, with_lse=return_lse,
        with_scales=with_scales)

    out_specs = pl.BlockSpec((None, C, G, hd),
                             lambda bk, j, *_: (bk, 0, 0, 0))
    if return_lse:
        # fp32 partials for the stitch; see paged_attention
        out_specs = (out_specs,
                     pl.BlockSpec((None, C, G, 1),
                                  lambda bk, j, *_: (bk, 0, 0, 0)))
        out_shape = (jax.ShapeDtypeStruct((B * K, C, G, hd), jnp.float32),
                     jax.ShapeDtypeStruct((B * K, C, G, 1), jnp.float32))
    else:
        out_shape = jax.ShapeDtypeStruct((B * K, C, G, hd), q.dtype)

    page_specs = _page_specs(nb, P, K, block_size, hd)
    scale_specs, scale_operands = [], []
    if with_scales:
        scale_specs = 2 * _page_specs(nb, P, K, block_size, 1)
        scale_operands = [k_scale] * P + [v_scale] * P
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B * K, pl.cdiv(nb, P)),
        in_specs=[
            pl.BlockSpec((None, C, G, hd),
                         lambda bk, j, *_: (bk, 0, 0, 0)),
            *page_specs,
            *page_specs,
            *scale_specs,
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((C * G, 1), jnp.float32),
            pltpu.VMEM((C * G, 1), jnp.float32),
            pltpu.VMEM((C * G, hd), jnp.float32),
        ],
    )

    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      q_lens.astype(jnp.int32), block_mask.astype(jnp.int32),
      qg, *([k_pages] * P), *([v_pages] * P), *scale_operands)

    def head_major(x):
        # (B*K, C, G, t) -> (B, K, C, G, t) -> (B, C, G, K, t) -> (B, C, H, t)
        t = x.shape[-1]
        return x.reshape(B, K, C, G, t).transpose(0, 2, 3, 1, 4) \
            .reshape(B, C, H, t)

    if return_lse:
        o, lse = o
        return head_major(o), head_major(lse)[..., 0]
    return head_major(o)


def _ragged_kernel(start_ref, end_ref, ctx_ref, bt_ref, q_ref, *rest,
                   scale, cap, window, block_size, num_kv_heads,
                   num_groups, pages_per_block, table_width, with_write,
                   with_scales):
    """Packed multi-sequence prefill over one flat (T, G, hd) query batch.

    Grid (K, S, cdiv(nb, P)): program (k, s, j) attends *all* T flat rows
    against kv pages [j*P, (j+1)*P) of packed sequence s, masking rows
    outside [start_s, end_s) — each row's (m, l, acc) state only ever
    advances while its owning sequence is being swept, so the streaming
    softmax per row sees exactly that sequence's keys. The output tile is
    indexed by k alone and stays VMEM-resident across (s, j); each
    sequence's finalize merges only its own rows (read-modify-write),
    rows owned by nobody stay zero.

    With ``with_write`` (P == 1 only — the aliased page outputs must be
    written exactly once per grid step) the chunk's own KV (flat, same
    row layout as q) rides along and each page fetched is *merged* —
    chunk rows whose absolute position lands in this page replace the
    stale pool rows via a (block_size, T) one-hot matmul — before the
    attention reads it, then written back through aliased page-pool
    outputs: the scatter that ``update_paged_cache_ragged`` does as a
    separate XLA pass is fused into the same kernel launch.

    With ``with_scales`` the pools are quantized: fetched page tiles
    dequantize in-VMEM through the per-row scale pages before attending.
    Combined with ``with_write`` the chunk KV arrives *already quantized*
    (and its scale rows already scattered into the scale pool, which the
    kernel's scale-page fetch then sees) — the one-hot merge shuffles
    narrow integer codes exactly (values ≤ qmax are exact in fp32).
    """
    P = pages_per_block
    k_refs, v_refs = rest[:P], rest[P:2 * P]
    rest = rest[2 * P:]
    if with_write:
        kc_ref, vc_ref = rest[:2]
        rest = rest[2:]
    ks_refs = vs_refs = None
    if with_scales:
        ks_refs, vs_refs = rest[:P], rest[P:2 * P]
        rest = rest[2 * P:]
    if with_write:
        o_ref, ko_ref, vo_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    s_id = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    start = start_ref[s_id]
    end = end_ref[s_id]
    ctx = ctx_ref[s_id]
    qlen = end - start
    qstart = ctx - qlen              # absolute position of flat row `start`
    G = num_groups
    T = q_ref.shape[0]

    @pl.when((s_id == 0) & (j == 0))
    def _init_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j == 0)
    def _init_scratch():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    first_k = j * (P * block_size)
    active = start < end
    # per-page liveness; the step runs if any of its P pages is live
    lives = []
    for i in range(P):
        entry = j * P + i
        seg_first = first_k + i * block_size
        li = (seg_first < ctx) & active
        if P > 1:
            li &= entry < table_width
        if window is not None:
            li &= seg_first + block_size - 1 > qstart - window
        lives.append(li)
    live = functools.reduce(lambda a, c: a | c, lives)

    if with_write:
        # fused chunk-KV scatter: merge this sequence's chunk rows whose
        # absolute position falls in this page, write the page back
        # (unchanged when no row lands here — dead/redirected pages too)
        p_col = first_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_size, 1), 0)                  # (bs, 1)
        in_chunk = (p_col >= qstart) & (p_col < ctx) & active
        t_col = start + (p_col - qstart)                    # flat row per col
        t_row = jax.lax.broadcasted_iota(
            jnp.int32, (block_size, T), 1)
        sel = ((t_col == t_row) & in_chunk).astype(jnp.float32)
        k_blk = jnp.where(
            in_chunk,
            jax.lax.dot_general(
                sel, kc_ref[...].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(ko_ref.dtype),
            k_refs[0][...])
        v_blk = jnp.where(
            in_chunk,
            jax.lax.dot_general(
                sel, vc_ref[...].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(vo_ref.dtype),
            v_refs[0][...])
        ko_ref[...] = k_blk
        vo_ref[...] = v_blk
        if with_scales:
            k_att = _dequant_tile(k_blk, ks_refs[0][...])
            v_att = _dequant_tile(v_blk, vs_refs[0][...])
        else:
            k_att = k_blk.astype(jnp.float32)
            v_att = v_blk.astype(jnp.float32)
    elif with_scales:
        k_att = jnp.concatenate(
            [_dequant_tile(r[...], sr[...])
             for r, sr in zip(k_refs, ks_refs)], axis=0)
        v_att = jnp.concatenate(
            [_dequant_tile(r[...], sr[...])
             for r, sr in zip(v_refs, vs_refs)], axis=0)
    else:
        k_att = jnp.concatenate(
            [r[...] for r in k_refs], axis=0).astype(jnp.float32)
        v_att = jnp.concatenate(
            [r[...] for r in v_refs], axis=0).astype(jnp.float32)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32).reshape(T * G, -1)  # (T*G, hd)
        s = jax.lax.dot_general(
            q, k_att, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (T*G, P*bs)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        k_pos = first_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
        q_pos = qstart + (row - start)
        mask = (row >= start) & (row < end) & (k_pos <= q_pos)
        if window is not None:
            mask &= k_pos > q_pos - window
        if P > 1:
            # columns of dead pages (past the table or wholly past ctx)
            # carry redirected/garbage KV — mask them out
            mask &= _live_columns(lives, s.shape, block_size)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # masked-row guard as in _chunk_kernel: rows outside this
        # sequence must not accumulate
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v_att, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((j == nj - 1) & active)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-37)
        res = (acc_scr[...] / l).astype(o_ref.dtype).reshape(T, G, -1)
        row = jax.lax.broadcasted_iota(jnp.int32, (T, 1, 1), 0)
        mine = (row >= start) & (row < end)
        o_ref[...] = jnp.where(mine, res, o_ref[...])


def ragged_paged_prefill_attention(q, k_pages, v_pages, block_tables,
                                   ctx_lens, starts, ends, *, k_new=None,
                                   v_new=None, window=None, cap=None,
                                   scale=None, interpret=False,
                                   pages_per_compute_block=1,
                                   k_scale=None, v_scale=None):
    """Packed (ragged) chunked-prefill attention against a paged KV cache.

    q: (T, H, hd) — chunks of up to S sequences packed back to back into
    one flat token batch. Sequence s owns flat rows [starts[s], ends[s]);
    its row i sits at absolute position ``ctx_lens[s] - (ends[s] -
    starts[s]) + i`` and attends causally to that sequence's paged context
    (block_tables: (S, max_blocks_per_seq); ctx_lens counts the chunk
    itself). ``starts[s] == ends[s]`` marks an unused pack slot; flat rows
    owned by no sequence produce zeros. Returns (T, H, hd) in q.dtype.

    With ``k_new``/``v_new`` ((T, K, hd), same flat row layout as q) the
    chunk's KV scatter is *fused*: the kernel merges chunk rows into each
    page it fetches before attending and writes the pages back in place
    (aliased outputs), returning ``(o, k_pages, v_pages)``. Without them
    the pages must already contain the chunk KV and only ``o`` returns.

    ``pages_per_compute_block`` batches P pages per grid step on the
    *non-fused* path only — the fused write pins P == 1 because each
    aliased page output must be produced exactly once per grid step, and
    revisiting an output block across a wider step would clobber pages
    the merge did not fetch. ``k_scale``/``v_scale`` mark quantized
    pools as on :func:`paged_attention`; with the fused write the chunk
    KV must arrive already quantized with its scale rows already
    scattered into the scale pools (``models.attention`` does both).
    """
    T, H, hd = q.shape
    _, K, block_size, _ = k_pages.shape
    G = H // K
    S = starts.shape[0]
    nb = block_tables.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    with_write = k_new is not None
    if with_write and v_new is None:
        raise ValueError("k_new and v_new must be given together")
    with_scales = k_scale is not None
    # fused write pins P=1: an aliased page output must be written exactly
    # once, by the single grid step that fetched that page
    P = 1 if with_write else max(1, min(int(pages_per_compute_block), nb))

    # g-major regroup: (T, H, hd) -> (T, G, K, hd) -> (K, T, G, hd)
    qg = q.reshape(T, G, K, hd).transpose(2, 0, 1, 3)

    def mk_page_spec(i, hd_):
        def idx(k, s, j, starts_ref, ends_ref, ctx_ref, bt_ref):
            # entries past the table width or wholly past the context
            # redirect to pool row 0 (never attended: liveness skips them)
            entry = jnp.minimum(j * P + i, nb - 1)
            ok = (j * P + i < nb) & (entry * block_size < ctx_ref[s])
            return (jnp.where(ok, bt_ref[s, entry], 0), k, 0, 0)
        return pl.BlockSpec((None, None, block_size, hd_), idx)

    kernel = functools.partial(
        _ragged_kernel, scale=scale, cap=cap, window=window,
        block_size=block_size, num_kv_heads=K, num_groups=G,
        pages_per_block=P, table_width=nb, with_write=with_write,
        with_scales=with_scales)

    q_spec = pl.BlockSpec((None, T, G, hd), lambda k, s, j, *_: (k, 0, 0, 0))
    page_specs = [mk_page_spec(i, hd) for i in range(P)]
    in_specs = [q_spec, *page_specs, *page_specs]
    operands = [qg, *([k_pages] * P), *([v_pages] * P)]
    out_specs = [pl.BlockSpec((None, T, G, hd),
                              lambda k, s, j, *_: (k, 0, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((K, T, G, hd), q.dtype)]
    aliases = {}
    if with_write:
        # (T, K, hd) -> (K, T, hd): one kv head's chunk rows are a whole
        # (T, hd) block
        new_spec = pl.BlockSpec((None, T, hd), lambda k, s, j, *_: (k, 0, 0))
        in_specs += [new_spec, new_spec]
        operands += [k_new.transpose(1, 0, 2), v_new.transpose(1, 0, 2)]
        out_specs += [page_specs[0], page_specs[0]]
        out_shape += [jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                      jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)]
        # flattened operand order: 4 prefetched scalars, q, k_pages,
        # v_pages, k_new, v_new[, k_scale, v_scale] -> pages alias the
        # page outputs in place
        aliases = {5: 1, 6: 2}
    if with_scales:
        scale_page_specs = [mk_page_spec(i, 1) for i in range(P)]
        in_specs += [*scale_page_specs, *scale_page_specs]
        operands += [*([k_scale] * P), *([v_scale] * P)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(K, S, pl.cdiv(nb, P)),
        in_specs=in_specs,
        out_specs=tuple(out_specs) if with_write else out_specs[0],
        scratch_shapes=[
            pltpu.VMEM((T * G, 1), jnp.float32),
            pltpu.VMEM((T * G, 1), jnp.float32),
            pltpu.VMEM((T * G, hd), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(out_shape) if with_write else out_shape[0],
        interpret=interpret,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(starts.astype(jnp.int32), ends.astype(jnp.int32),
      ctx_lens.astype(jnp.int32), block_tables.astype(jnp.int32),
      *operands)

    def flat_head_major(o):
        # (K, T, G, hd) -> (T, G, K, hd) -> (T, H, hd)
        return o.transpose(1, 2, 0, 3).reshape(T, H, hd)

    if with_write:
        o, kc, vc = out
        return flat_head_major(o), kc, vc
    return flat_head_major(out)
