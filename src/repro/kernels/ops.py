"""Public kernel entry points with backend dispatch.

Each op has three implementations:
  1. a Pallas TPU kernel (``repro.kernels.<name>``) — the production hot path,
     validated on CPU via ``interpret=True``;
  2. a scalable pure-XLA path (chunked/streaming jnp) used on CPU and for the
     dry-run lowering;
  3. a naive oracle in ``repro.kernels.ref`` used only by tests.

Dispatch: compiled Pallas on TPU backends; off the TPU the XLA path, or the
Pallas kernels in interpret mode when ``REPRO_FORCE_PALLAS=interpret`` is
set (kernel validation on CPU). ``REPRO_FORCE_PALLAS`` exists only for that
validation: on a TPU backend any value of it raises, so nothing can quietly
route the chip around its kernels.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp


def _use_pallas() -> str | None:
    """Returns None (XLA path), "compiled", or "interpret"."""
    force = os.environ.get("REPRO_FORCE_PALLAS", "")
    if jax.default_backend() == "tpu":
        if force:
            raise RuntimeError(
                f"REPRO_FORCE_PALLAS={force!r} is for validating kernels on "
                "CPU; on a TPU backend the compiled Pallas kernels always "
                "run. Unset it.")
        return "compiled"
    if force == "interpret":
        return "interpret"
    return None


def _pages_per_block(pages_per_compute_block) -> int:
    """KV pages fetched per grid step of the chunk and ragged kernels.
    Explicit argument wins; ``REPRO_PAGES_PER_BLOCK`` sets the fleet-wide
    default (1 = the single-page kernel, bit-for-bit). The decode kernel
    takes its pages per block from the shapes and reads neither."""
    if pages_per_compute_block is not None:
        return int(pages_per_compute_block)
    return int(os.environ.get("REPRO_PAGES_PER_BLOCK", "1"))


# XLA-path dispatch: dense attention keeps a single bf16 (Sq,Skv) block per
# head and is the right trade under layer remat up to this many kv positions;
# beyond it the streaming chunked form bounds memory at O(chunk).
DENSE_ATTN_MAX_KV = 8192


def flash_attention(q, k, v, *, causal=True, window=None, cap=None,
                    scale=None, chunk_kv=1024, q_offset=0):
    mode = _use_pallas()
    if mode is not None:
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(
            q, k, v, causal=causal, window=window, cap=cap, scale=scale,
            q_offset=q_offset, interpret=(mode == "interpret"))
    from repro.models.attention import (block_causal_attention,
                                        chunked_attention, dense_attention)
    if k.shape[1] <= DENSE_ATTN_MAX_KV:
        return dense_attention(q, k, v, causal=causal, window=window,
                               cap=cap, scale=scale, q_offset=q_offset)
    if causal and q_offset == 0 and q.shape[1] == k.shape[1]:
        # static triangular block skipping: ~2x fewer attention flops
        return block_causal_attention(q, k, v, window=window, cap=cap,
                                      scale=scale, chunk_kv=chunk_kv)
    return chunked_attention(q, k, v, causal=causal, window=window, cap=cap,
                             scale=scale, chunk_kv=chunk_kv,
                             q_offset=q_offset)


def paged_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                    window=None, cap=None, scale=None, k_scale=None,
                    v_scale=None):
    """Decode attention through a block table (serving hot path).
    See kernels/paged_attention.py; the XLA path densifies the gather.
    ``k_scale``/``v_scale`` are the per-row fp32 scale pools of a
    quantized page pool (dequant fused into the kernel)."""
    mode = _use_pallas()
    if mode is not None:
        from repro.kernels import paged_attention as pa
        return pa.paged_attention(
            q, k_pages, v_pages, block_tables, ctx_lens, window=window,
            cap=cap, scale=scale, interpret=(mode == "interpret"),
            k_scale=k_scale, v_scale=v_scale)
    from repro.kernels.ref import paged_attention_ref
    return paged_attention_ref(q, k_pages, v_pages, block_tables, ctx_lens,
                               window=window, cap=cap, scale=scale,
                               k_scale=k_scale, v_scale=v_scale)


def paged_attention_partial(q, k_pages, v_pages, block_tables, ctx_lens,
                            block_mask, *, window=None, cap=None,
                            scale=None, k_scale=None, v_scale=None):
    """Partial-softmax paged decode over a shard-local block table:
    attends only table entries selected by ``block_mask`` and returns
    ``(o, lse)`` for the cross-shard LSE stitch
    (``models.attention.stitch_paged_partials``). See
    kernels/paged_attention.py."""
    mode = _use_pallas()
    if mode is not None:
        from repro.kernels import paged_attention as pa
        return pa.paged_attention(     # fp32 (o, lse) partials
            q, k_pages, v_pages, block_tables, ctx_lens, window=window,
            cap=cap, scale=scale, block_mask=block_mask, return_lse=True,
            interpret=(mode == "interpret"),
            k_scale=k_scale, v_scale=v_scale)
    from repro.kernels.ref import paged_attention_partial_ref
    return paged_attention_partial_ref(
        q, k_pages, v_pages, block_tables, ctx_lens, block_mask,
        window=window, cap=cap, scale=scale,
        k_scale=k_scale, v_scale=v_scale)


def paged_prefill_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                            q_lens, *, window=None, cap=None, scale=None,
                            pages_per_compute_block=None, k_scale=None,
                            v_scale=None):
    """Chunked-prefill attention through a block table: C queries per
    sequence, causally masked against the paged context. See
    kernels/paged_attention.py; the XLA path densifies the gather and
    mirrors ``dense_attention``'s rounding so chunked and monolithic
    prefill stay greedy-equivalent on CPU."""
    mode = _use_pallas()
    if mode is not None:
        from repro.kernels import paged_attention as pa
        return pa.paged_prefill_attention(
            q, k_pages, v_pages, block_tables, ctx_lens, q_lens,
            window=window, cap=cap, scale=scale,
            interpret=(mode == "interpret"),
            pages_per_compute_block=_pages_per_block(
                pages_per_compute_block),
            k_scale=k_scale, v_scale=v_scale)
    from repro.models.attention import paged_chunk_attention_xla
    return paged_chunk_attention_xla(
        q, k_pages, v_pages, block_tables, ctx_lens, q_lens,
        window=window, cap=cap, scale=scale,
        k_scale=k_scale, v_scale=v_scale)


def ragged_paged_prefill_attention(q, k_pages, v_pages, block_tables,
                                   ctx_lens, starts, ends, row_seq, *,
                                   window=None, cap=None, scale=None,
                                   pages_per_compute_block=None,
                                   k_scale=None, v_scale=None):
    """Packed (ragged) chunked-prefill attention through per-sequence
    block tables: chunks of several sequences ride one flat (T, H, hd)
    batch, sequence s owning flat rows [starts[s], ends[s]). The chunk's
    own KV must already be scattered into the pages. See
    kernels/paged_attention.py; the XLA path gathers the packed rows into
    the dense (S, T) layout and reuses the single-chunk rounding."""
    mode = _use_pallas()
    if mode is not None:
        from repro.kernels import paged_attention as pa
        return pa.ragged_paged_prefill_attention(
            q, k_pages, v_pages, block_tables, ctx_lens, starts, ends,
            window=window, cap=cap, scale=scale,
            interpret=(mode == "interpret"),
            pages_per_compute_block=_pages_per_block(
                pages_per_compute_block),
            k_scale=k_scale, v_scale=v_scale)
    from repro.models.attention import ragged_chunk_attention_xla
    return ragged_chunk_attention_xla(
        q, k_pages, v_pages, block_tables, ctx_lens, starts, ends, row_seq,
        window=window, cap=cap, scale=scale,
        k_scale=k_scale, v_scale=v_scale)


def ragged_prefill_update_attend(q, k_new, v_new, k_pages, v_pages,
                                 block_tables, ctx_lens, starts, ends,
                                 row_seq, *, window=None, cap=None,
                                 scale=None, k_scale=None, v_scale=None,
                                 trash=0):
    """Fused packed-prefill KV scatter + attention: returns
    ``(o, k_pages, v_pages)``. On the Pallas path the scatter rides inside
    the ragged kernel through aliased page-pool outputs (one launch, no
    separate scatter pass); the XLA path scatters then attends — same pool
    bytes, same outputs. ``trash``: the block the XLA scatter sends rows
    owned by no sequence to (the fused write drops them).

    Quantized pools: ``k_new``/``v_new`` must arrive *already quantized*
    to the pool dtype and ``k_scale``/``v_scale`` must already contain the
    chunk's scattered scale rows (``models.attention`` does both before
    calling) — the kernel reads scale pages for the dequant and only
    aliases the value pools."""
    mode = _use_pallas()
    if mode is not None:
        from repro.kernels import paged_attention as pa
        return pa.ragged_paged_prefill_attention(
            q, k_pages, v_pages, block_tables, ctx_lens, starts, ends,
            k_new=k_new, v_new=v_new, window=window, cap=cap, scale=scale,
            interpret=(mode == "interpret"),
            k_scale=k_scale, v_scale=v_scale)
    from repro.models.attention import (ragged_chunk_attention_xla,
                                        update_paged_cache_ragged)
    kc = update_paged_cache_ragged(k_pages, k_new[None], block_tables,
                                   ctx_lens, starts, ends, row_seq, trash)
    vc = update_paged_cache_ragged(v_pages, v_new[None], block_tables,
                                   ctx_lens, starts, ends, row_seq, trash)
    o = ragged_chunk_attention_xla(
        q, kc, vc, block_tables, ctx_lens, starts, ends, row_seq,
        window=window, cap=cap, scale=scale,
        k_scale=k_scale, v_scale=v_scale)
    return o, kc, vc


def ssd(x, dt, A, B, C, *, chunk, h0=None):
    mode = _use_pallas()
    if mode is not None:
        from repro.kernels import ssd as ssd_k
        return ssd_k.ssd(x, dt, A, B, C, chunk=chunk, h0=h0,
                         interpret=(mode == "interpret"))
    from repro.models.ssm import ssd_chunked
    return ssd_chunked(x, dt, A, B, C, chunk=chunk, h0=h0)


def sampled_softmax_loss(x, table, labels, sampled_ids, *, cap=None):
    """See kernels/sampled_softmax.py and models/embedding.py."""
    mode = _use_pallas()
    if mode is not None:
        from repro.kernels import sampled_softmax as ss
        return ss.sampled_softmax_loss(
            x, table, labels, sampled_ids, cap=cap,
            interpret=(mode == "interpret"))
    from repro.kernels.ref import sampled_softmax_loss_ref
    return sampled_softmax_loss_ref(x, table, labels, sampled_ids, cap=cap)


def embedding_gather(table, ids):
    mode = _use_pallas()
    if mode is not None:
        from repro.kernels import embedding as emb
        return emb.gather(table, ids, interpret=(mode == "interpret"))
    return table[ids]


# a grouped-matmul grid step holds one (tk, tn) tile of a group's matrix in
# VMEM, double-buffered; this bounds it (v5e scopes 16 MiB per kernel)
GMM_RHS_TILE_BYTES = 4 * 2 ** 20


def _lane_tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap``; ``dim`` itself where it is no multiple of 128 (a block may
    span a whole axis)."""
    if dim % 128:
        return dim
    return max(t for t in range(128, min(dim, max(cap, 128)) + 1, 128)
               if dim % t == 0)


def gmm_tiling(m: int, k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """(tm, tk, tn) of megablox ``gmm`` for an (m, k) by (G, k, n) product.
    tm is the MXU's 128 rows, or m rounded up to 16 (a bf16 sublane pair)
    where fewer rows come; tn spans n up to 2048 lanes; tk is as deep as
    GMM_RHS_TILE_BYTES allows, so most products run one grid step per m
    tile a group touches."""
    tm = min(128, -(-m // 16) * 16)
    tn = _lane_tile(n, 2048)
    tk = _lane_tile(k, GMM_RHS_TILE_BYTES // (tn * itemsize))
    return tm, tk, tn


def grouped_matmul(lhs, rhs, group_sizes, *, out_dtype=jnp.float32):
    """Rows of ``lhs`` (m, k), sorted by group, each times its group's
    matrix of ``rhs`` (G, k, n): group g owns the ``group_sizes[g]`` rows
    after those of groups 0 .. g-1. Rows past ``sum(group_sizes)`` belong
    to no group and are never multiplied; their output is undefined
    (zero off the TPU), so the caller masks them.

    On a TPU (and under ``REPRO_FORCE_PALLAS=interpret``) megablox ``gmm``,
    which visits only the m tiles some group covers, at ``gmm_tiling``'s
    tiles; lhs is padded to a whole m tile. Elsewhere
    ``jax.lax.ragged_dot``. Accumulates in float32."""
    mode = _use_pallas()
    if mode is not None:
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        m, k = lhs.shape
        tiling = gmm_tiling(m, k, rhs.shape[2], rhs.dtype.itemsize)
        pad = -m % tiling[0]
        if pad:
            lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        # positional: gmm is a custom_vjp whose static arguments are
        # numbered (out dtype, tiling, group_offset, existing_out,
        # transpose_rhs, interpret)
        out = gmm(lhs, rhs, group_sizes, out_dtype, tiling, None, None,
                  False, mode == "interpret")
        return out[:m]
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=out_dtype)
