"""Serving subsystem tests: paged-attention kernels (decode + chunked
prefill) vs densifying oracles, refcounted block-manager / prefix-cache /
COW invariants, budgeted-scheduler behaviour, and engine-vs-static-Server
greedy equivalence (the continuous-batching path must be a pure
latency/memory optimization — never a numerics change)."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.kernels.paged_attention import (paged_attention,
                                           paged_prefill_attention)
from repro.kernels.ref import (attention_ref, paged_attention_ref,
                               paged_prefill_attention_ref)
from repro.serving.kv_cache import (TRASH_BLOCK, BlockManager,
                                    chain_block_hashes)
from repro.serving.scheduler import Request, SamplingParams, Scheduler

RNG = np.random.default_rng(0)


def _paged_case(B, H, K, hd, bs, nblk, dtype, rng=RNG):
    """Random page pools + disjoint per-seq block tables + ctx lens."""
    N = 1 + B * nblk
    q = jnp.asarray(rng.normal(0, 1, (B, H, hd)), jnp.float32).astype(dtype)
    kp = jnp.asarray(rng.normal(0, 1, (N, bs, K, hd)).transpose(0, 2, 1, 3),
                     jnp.float32).astype(dtype)
    vp = jnp.asarray(rng.normal(0, 1, (N, bs, K, hd)).transpose(0, 2, 1, 3),
                     jnp.float32).astype(dtype)
    perm = rng.permutation(np.arange(1, N))[:B * nblk].reshape(B, nblk)
    bt = jnp.asarray(perm, jnp.int32)
    ctx = jnp.asarray(rng.integers(1, nblk * bs + 1, (B,)), jnp.int32)
    return q, kp, vp, bt, ctx


PAGED_CASES = [
    # B, H, K, hd, block_size, blocks_per_seq, window, cap, dtype
    (3, 4, 2, 16, 8, 4, None, None, jnp.float32),
    (2, 8, 2, 32, 16, 3, None, 50.0, jnp.bfloat16),
    (2, 6, 6, 16, 8, 5, 12, None, jnp.float32),     # MHA (G=1) + window
    (1, 8, 1, 64, 8, 4, None, None, jnp.bfloat16),  # MQA (K=1)
    (2, 4, 2, 64, 16, 2, 8, 30.0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_kernel_vs_ref(case):
    B, H, K, hd, bs, nblk, window, cap, dt = case
    q, kp, vp, bt, ctx = _paged_case(B, H, K, hd, bs, nblk, dt)
    o_k = paged_attention(q, kp, vp, bt, ctx, window=window, cap=cap,
                          interpret=True)
    o_r = paged_attention_ref(q, kp, vp, bt, ctx, window=window, cap=cap)
    tol = 1e-2 if dt == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=tol)


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_ref_vs_dense_oracle(case):
    """Densify the pages by hand and compare against the plain attention
    oracle at q_offset = ctx-1 (GQA g-major grouping included)."""
    B, H, K, hd, bs, nblk, window, cap, dt = case
    q, kp, vp, bt, ctx = _paged_case(B, H, K, hd, bs, nblk, dt)
    o_p = np.asarray(paged_attention_ref(q, kp, vp, bt, ctx, window=window,
                                         cap=cap), np.float32)
    for b in range(B):
        S = int(ctx[b])
        k = np.asarray(kp, np.float32)[np.asarray(bt[b])].transpose(
            0, 2, 1, 3).reshape(-1, K, hd)[:S]
        v = np.asarray(vp, np.float32)[np.asarray(bt[b])].transpose(
            0, 2, 1, 3).reshape(-1, K, hd)[:S]
        o_d = attention_ref(
            jnp.asarray(q[b:b + 1, None], jnp.float32),
            jnp.asarray(k[None]), jnp.asarray(v[None]),
            causal=True, window=window, cap=cap, q_offset=S - 1)
        tol = 2e-2 if dt == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(o_p[b], np.asarray(o_d)[0, 0], atol=tol)


def test_paged_inactive_slot_is_zero():
    q, kp, vp, bt, _ = _paged_case(2, 4, 2, 16, 8, 3, jnp.float32)
    ctx = jnp.asarray([0, 5], jnp.int32)
    for fn in (lambda: paged_attention(q, kp, vp, bt, ctx, interpret=True),
               lambda: paged_attention_ref(q, kp, vp, bt, ctx)):
        o = np.asarray(fn())
        assert np.all(o[0] == 0)
        assert np.all(np.isfinite(o))


# The decode kernel loops over a row's live compute blocks only: cases for
# where a context ends (mid-page, mid-block, at max_len), inactive rows,
# every variant the kernel carries, and pages it must never read.
# B, H, K, hd, bs, nblk, P, ctx, window, cap, kv_dtype, variant
LIVE_CASES = {
    "mid_page_mid_block_max_len": (
        6, 4, 2, 16, 8, 6, 2, [5, 0, 20, 48, 0, 33], None, None, None,
        None),
    "window_cap_int8": (
        4, 4, 2, 16, 8, 6, 2, [7, 48, 30, 0], 12, 30.0, "int8", None),
    "stitched_shards": (
        3, 4, 2, 16, 8, 6, 2, [48, 0, 21], None, None, None, "shards"),
    # K_local 1 (a TP shard), and P left to the shapes (the whole table)
    "fp8_k_local_1": (
        3, 4, 1, 32, 8, 3, None, [17, 0, 24], 20, None, "fp8", None),
    "dead_pages_never_read": (
        5, 4, 2, 16, 8, 6, 2, [20, 0, 48, 26, 35], 10, None, None, "nan"),
}


@pytest.mark.parametrize("name", sorted(LIVE_CASES))
def test_decode_kernel_live_blocks_vs_ref(name):
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.ref import paged_attention_partial_ref
    from repro.models.attention import stitch_paged_partials
    from repro.models.quant import dequantize_kv, quantize_kv
    B, H, K, hd, bs, nblk, P, ctx, window, cap, kv, variant = \
        LIVE_CASES[name]
    # a generator of its own: the module's stream feeds the other tests
    q, kp, vp, bt, _ = _paged_case(B, H, K, hd, bs, nblk, jnp.float32,
                                   np.random.default_rng(15))
    ctx = jnp.asarray(ctx, jnp.int32)
    kw = dict(window=window, cap=cap)
    o_r = paged_attention_ref(q, kp, vp, bt, ctx, **kw)
    if variant == "nan":
        # only pool rows a live entry names hold numbers (a page before
        # the window, or a stale buffer, must not poison o), and table
        # entries at or past cdiv(ctx, bs) name no pool row: reading one
        # raises
        first = np.maximum(np.asarray(ctx) - window, 0) // bs
        n_live = -(-np.asarray(ctx) // bs)
        live = {int(bt[b, e]) for b in range(B)
                for e in range(first[b], n_live[b])}
        dead = np.asarray([r not in live for r in range(kp.shape[0])])
        kp = jnp.where(dead[:, None, None, None], jnp.nan, kp)
        vp = jnp.where(dead[:, None, None, None], jnp.nan, vp)
        past = np.arange(nblk)[None] >= n_live[:, None]
        bt = jnp.where(past, kp.shape[0] + 7, bt)
    if kv is not None:
        kp, sk = quantize_kv(kp, kv)
        vp, sv = quantize_kv(vp, kv)
        # the oracle on the dequantized pools, in fp32 as the kernel runs
        o_r = paged_attention_ref(
            q, dequantize_kv(kp, sk).astype(jnp.float32),
            dequantize_kv(vp, sv).astype(jnp.float32), bt, ctx, **kw)
        kw.update(k_scale=sk, v_scale=sv)
    # the TPU interpreter fills memory no DMA wrote with NaN
    interp = pltpu.InterpretParams() if variant == "nan" else True
    if variant == "shards":
        # two shards, each holding alternate table entries, stitched
        even = (np.arange(nblk)[None] % 2 == 0).repeat(B, 0)
        parts = []
        for m in (even, ~even):
            mask = jnp.asarray(m, jnp.int32)
            o_k, lse_k = paged_attention(
                q, kp, vp, bt, ctx, block_mask=mask, return_lse=True,
                interpret=interp, pages_per_compute_block=P, **kw)
            o_p, lse_p = paged_attention_partial_ref(q, kp, vp, bt, ctx,
                                                     mask, **kw)
            np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_p),
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(lse_k), np.asarray(lse_p),
                                       atol=1e-5)
            parts.append((o_k, lse_k))
        o_k = stitch_paged_partials(jnp.stack([o for o, _ in parts]),
                                    jnp.stack([lse for _, lse in parts]))
    else:
        o_k = paged_attention(q, kp, vp, bt, ctx, interpret=interp,
                              pages_per_compute_block=P, **kw)
    o_k = np.asarray(o_k, np.float32)
    assert np.all(np.isfinite(o_k))
    assert np.all(o_k[np.asarray(ctx) == 0] == 0)
    np.testing.assert_allclose(o_k, np.asarray(o_r, np.float32), atol=1e-5)


# ---------------------------------------------------------------------------
# Multi-query (chunked prefill) kernel
# ---------------------------------------------------------------------------


def _chunk_case(B, H, K, hd, bs, nblk, C, dtype):
    N = 1 + B * nblk
    q = jnp.asarray(RNG.normal(0, 1, (B, C, H, hd)),
                    jnp.float32).astype(dtype)
    kp = jnp.asarray(RNG.normal(0, 1, (N, bs, K, hd)).transpose(0, 2, 1, 3),
                     jnp.float32).astype(dtype)
    vp = jnp.asarray(RNG.normal(0, 1, (N, bs, K, hd)).transpose(0, 2, 1, 3),
                     jnp.float32).astype(dtype)
    perm = RNG.permutation(np.arange(1, N))[:B * nblk].reshape(B, nblk)
    bt = jnp.asarray(perm, jnp.int32)
    qlen = RNG.integers(0, C + 1, (B,))
    qlen[0] = C                     # always one full chunk in the batch
    ctx = np.array([RNG.integers(ql, nblk * bs + 1) if ql else 0
                    for ql in qlen])
    return (q, kp, vp, bt, jnp.asarray(ctx, jnp.int32),
            jnp.asarray(qlen, jnp.int32))


# acceptance: chunk lengths {1, block_size, 2.5 blocks} with causal masking
CHUNK_CASES = [
    # B, H, K, hd, block_size, blocks_per_seq, C, window, cap, dtype
    (3, 4, 2, 16, 8, 4, 1, None, None, jnp.float32),
    (2, 8, 2, 32, 16, 3, 16, None, 50.0, jnp.bfloat16),  # C == block_size
    (2, 6, 6, 16, 8, 5, 20, None, None, jnp.float32),    # C == 2.5 blocks
    (2, 6, 2, 16, 8, 5, 20, 12, None, jnp.float32),      # + sliding window
    (1, 8, 1, 64, 8, 4, 20, None, None, jnp.bfloat16),   # MQA, 2.5 blocks
]


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_chunk_kernel_vs_ref(case):
    B, H, K, hd, bs, nblk, C, window, cap, dt = case
    q, kp, vp, bt, ctx, qlen = _chunk_case(B, H, K, hd, bs, nblk, C, dt)
    o_k = paged_prefill_attention(q, kp, vp, bt, ctx, qlen, window=window,
                                  cap=cap, interpret=True)
    o_r = paged_prefill_attention_ref(q, kp, vp, bt, ctx, qlen,
                                      window=window, cap=cap)
    tol = 1e-2 if dt == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=tol)


def test_chunk_kernel_qlen1_matches_decode_kernel():
    """A 1-token chunk is exactly a decode step over the same pages per
    compute block (the decode kernel takes the whole table in one here)
    and the same head width (it zero-pads the head dim to 128 lanes)."""
    B, H, K, hd, bs, nblk = 3, 4, 2, 16, 8, 4
    q, kp, vp, bt, ctx = _paged_case(B, H, K, hd, bs, nblk, jnp.float32)
    o_d = paged_attention(q, kp, vp, bt, ctx, interpret=True)

    def widen(x):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, 128 - hd)])

    o_c = paged_prefill_attention(widen(q)[:, None], widen(kp), widen(vp),
                                  bt, ctx, jnp.ones(B, jnp.int32),
                                  scale=hd ** -0.5, interpret=True,
                                  pages_per_compute_block=nblk)
    np.testing.assert_array_equal(np.asarray(o_c)[:, 0, :, :hd],
                                  np.asarray(o_d))


def test_chunk_ref_vs_dense_oracle():
    """Densify by hand, run the plain oracle over the chunk's query span."""
    B, H, K, hd, bs, nblk, C = 2, 4, 2, 16, 8, 4, 12
    q, kp, vp, bt, ctx, qlen = _chunk_case(B, H, K, hd, bs, nblk, C,
                                           jnp.float32)
    o_p = np.asarray(paged_prefill_attention_ref(q, kp, vp, bt, ctx, qlen),
                     np.float32)
    for b in range(B):
        n, S = int(qlen[b]), int(ctx[b])
        if n == 0:
            assert np.all(o_p[b] == 0)
            continue
        k = np.asarray(kp, np.float32)[np.asarray(bt[b])].transpose(
            0, 2, 1, 3).reshape(-1, K, hd)[:S]
        v = np.asarray(vp, np.float32)[np.asarray(bt[b])].transpose(
            0, 2, 1, 3).reshape(-1, K, hd)[:S]
        o_d = attention_ref(
            jnp.asarray(q[b:b + 1, :n], jnp.float32),
            jnp.asarray(k[None]), jnp.asarray(v[None]),
            causal=True, q_offset=S - n)
        np.testing.assert_allclose(o_p[b, :n], np.asarray(o_d)[0],
                                   atol=1e-5)


def test_chunk_padding_rows_are_zero():
    q, kp, vp, bt, ctx, _ = _chunk_case(2, 4, 2, 16, 8, 3, 8, jnp.float32)
    qlen = jnp.asarray([3, 0], jnp.int32)
    ctx = jnp.asarray([10, 0], jnp.int32)
    for fn in (lambda: paged_prefill_attention(q, kp, vp, bt, ctx, qlen,
                                               interpret=True),
               lambda: paged_prefill_attention_ref(q, kp, vp, bt, ctx,
                                                   qlen)):
        o = np.asarray(fn())
        assert np.all(o[0, 3:] == 0)
        assert np.all(o[1] == 0)
        assert np.all(np.isfinite(o))


# ---------------------------------------------------------------------------
# Block manager
# ---------------------------------------------------------------------------


def test_block_manager_alloc_free_invariants():
    bm = BlockManager(num_blocks=9, block_size=4)
    t1 = bm.allocate(1, 9)          # 3 blocks
    t2 = bm.allocate(2, 4)          # 1 block
    bm.check()
    assert TRASH_BLOCK not in t1 + t2
    assert len(set(t1) | set(t2)) == 4
    assert bm.stats().blocks_in_use == 4
    assert bm.ensure(1, 12) and len(bm.table(1)) == 3      # no growth
    assert bm.ensure(1, 13) and len(bm.table(1)) == 4
    bm.check()
    with pytest.raises(KeyError):
        bm.allocate(1, 1)           # double alloc
    assert bm.num_free == 3
    assert not bm.ensure(2, 100)    # OOM -> False, table unchanged
    assert len(bm.table(2)) == 1
    bm.free(1)
    bm.check()
    assert bm.num_free == 7
    assert bm.stats().utilization == pytest.approx(1 / 8)


def test_block_manager_exhaustion_and_reuse():
    bm = BlockManager(num_blocks=5, block_size=2)
    bm.allocate(1, 8)               # all 4 allocatable blocks
    assert not bm.can_allocate(1)
    with pytest.raises(MemoryError):
        bm.allocate(2, 2)
    bm.free(1)
    assert sorted(bm.allocate(3, 8)) == [1, 2, 3, 4]
    bm.check()


def test_block_manager_fork_refcount_and_cow():
    bm = BlockManager(num_blocks=8, block_size=4)
    t1 = bm.allocate(1, 8)          # 2 blocks
    bm.fork(1, 2)
    bm.check()
    assert bm.table(2) == t1
    assert all(bm.refcount(b) == 2 for b in t1)
    assert bm.stats().blocks_in_use == 2       # shared, counted once
    assert bm.stats().shared_blocks == 2
    # COW the second block for writer 2
    new = bm.cow(2, 1)
    assert new is not None and new != t1[1]
    assert bm.refcount(t1[1]) == 1 and bm.refcount(new) == 1
    assert bm.table(1) == t1 and bm.table(2) == [t1[0], new]
    assert bm.cow(2, 1) is None                # already exclusive: in place
    bm.check()
    # freeing one sharer keeps the shared block alive
    bm.free(2)
    bm.check()
    assert bm.refcount(t1[0]) == 1
    assert bm.table(1) == t1
    bm.free(1)
    bm.check()
    assert bm.num_free == 7


def test_block_manager_cow_oom():
    bm = BlockManager(num_blocks=3, block_size=2)
    bm.allocate(1, 4)               # both allocatable blocks
    bm.fork(1, 2)
    with pytest.raises(MemoryError):
        bm.cow(2, 0)


def test_prefix_hash_register_match_and_revival():
    bm = BlockManager(num_blocks=9, block_size=4)
    toks = np.arange(14, dtype=np.int32)
    hashes = chain_block_hashes(toks, 4)
    assert len(hashes) == 3                    # full blocks only
    # chained: a different first block changes every downstream hash
    other = chain_block_hashes(np.concatenate([[99], toks[1:]]), 4)
    assert all(a != b for a, b in zip(hashes, other))
    t1 = bm.allocate(1, 14)
    for b, h in zip(t1, hashes):
        bm.register(b, h)
    bm.check()
    assert bm.match(hashes) == t1[:3]
    assert bm.match(other) == []
    assert bm.match(hashes[:2] + [12345]) == t1[:2]    # longest prefix
    # adopt shares the matched blocks
    t2 = bm.adopt(2, bm.match(hashes))
    assert t2 == t1[:3] and all(bm.refcount(b) == 2 for b in t2)
    bm.check()
    # freeing the original keeps the cached blocks matchable (revival)
    bm.free(2)
    bm.free(1)
    bm.check()
    assert bm.num_free == 8
    assert bm.match(hashes) == t1[:3]          # still cached while free
    t3 = bm.adopt(3, bm.match(hashes))
    assert t3 == t1[:3]
    assert bm.num_free == 5                    # revived out of the free list
    bm.check()


def test_block_manager_truncate_rewind():
    """Speculative rollback: truncate frees tail blocks (newest first),
    respects sharing via refcounts, and keeps content hashes on freed
    blocks so prefix entries survive a rewind."""
    bm = BlockManager(num_blocks=9, block_size=4)
    t = bm.allocate(1, 16)              # 4 blocks
    assert bm.truncate(1, 9) == [t[3]]  # keep ceil(9/4) = 3
    assert bm.table(1) == t[:3] and bm.num_free == 5
    bm.check()
    bm.fork(1, 2)
    bm.truncate(2, 4)                   # rid 2 keeps 1 block
    assert bm.table(2) == t[:1]
    assert bm.refcount(t[1]) == 1 and bm.table(1) == t[:3]
    bm.check()
    bm.register(t[2], b"spec")
    bm.truncate(1, 5)                   # drops the hashed tail block
    assert bm.match([b"spec"]) == [t[2]]     # cached-free, revivable
    assert bm.truncate(1, 0) == [t[1], t[0]]
    assert bm.truncate(1, 0) == []           # idempotent on empty
    bm.check()


def test_prefix_cache_eviction_prefers_unhashed():
    bm = BlockManager(num_blocks=5, block_size=2)
    t = bm.allocate(1, 8)
    bm.register(t[0], 111)
    bm.free(1)
    # allocating 2 blocks must prefer the 3 unhashed ones
    t2 = bm.allocate(2, 4)
    assert t[0] not in t2
    assert bm.match([111]) == [t[0]]
    # allocating past the unhashed supply evicts the cached block
    bm.ensure(2, 8)
    assert bm.match([111]) == []
    bm.check()


# ---------------------------------------------------------------------------
# Property test: random walks over the block manager
# ---------------------------------------------------------------------------


def _bm_random_walk(tape):
    """Interpret ``tape`` (an iterator of ints) as add/grow/fork/free/COW/
    register/adopt/truncate/swap ops against a BlockManager with a host
    tier, asserting the full invariant set and exact free-block accounting
    on both tiers after every op (truncate is the speculative draft/target
    rewind path; swap-out/swap-in/swap-discard are the host-residency
    preemption/abort paths)."""
    NB, BS, NH = 9, 4, 6
    bm = BlockManager(num_blocks=NB, block_size=BS, num_host_blocks=NH)
    tokens: dict[int, int] = {}       # rid -> tokens covered
    swapped: dict[int, int] = {}      # rid -> host slots owned
    next_rid = [0]
    next_hash = [0]

    def draw(n):
        return next(tape) % n

    def new_rid():
        next_rid[0] += 1
        return next_rid[0]

    def check_accounting():
        bm.check()
        in_use = {b for rid in tokens for b in bm.table(rid)}
        assert bm.num_free == (NB - 1) - len(in_use)
        assert bm.stats().blocks_in_use == len(in_use)
        assert bm.num_host_free == NH - sum(swapped.values())
        for rid in swapped:
            assert bm.is_swapped(rid)

    for _ in range(160):
        op = draw(11)
        rids = list(tokens)
        if op == 0 or (not rids and op < 8):          # allocate
            rid = new_rid()
            try:
                bm.allocate(rid, draw(3 * BS + 1))
                tokens[rid] = 0
            except MemoryError:
                pass
        elif op == 1:                                 # grow
            rid = rids[draw(len(rids))]
            want = len(bm.table(rid)) * BS + draw(2 * BS) + 1
            if bm.ensure(rid, want):
                tokens[rid] = want
        elif op == 2:                                 # fork
            rid = new_rid()
            src = rids[draw(len(rids))]
            bm.fork(src, rid)
            tokens[rid] = tokens[src]
        elif op == 3:                                 # cow
            rid = rids[draw(len(rids))]
            t = bm.table(rid)
            if t:
                try:
                    bm.cow(rid, draw(len(t)))
                except MemoryError:
                    pass
        elif op == 4:                                 # free
            rid = rids[draw(len(rids))]
            bm.free(rid)
            del tokens[rid]
        elif op == 5:                                 # register a block
            rid = rids[draw(len(rids))]
            t = bm.table(rid)
            if t:
                next_hash[0] += 1
                bm.register(t[draw(len(t))], next_hash[0])
        elif op == 7:                                 # truncate (spec rewind)
            rid = rids[draw(len(rids))]
            cover = len(bm.table(rid)) * BS
            n = draw(cover + 1) if cover else 0
            bm.truncate(rid, n)
            tokens[rid] = min(tokens[rid], n)
        elif op == 8:                                 # swap out (preempt)
            if rids:
                rid = rids[draw(len(rids))]
                if bm.can_swap_out(rid):
                    n = len(bm.table(rid))
                    pairs = bm.swap_out(rid)
                    assert len(pairs) == n
                    swapped[rid] = n
                    del tokens[rid]
        elif op == 9:                                 # swap in (re-admit)
            srids = list(swapped)
            if srids:
                rid = srids[draw(len(srids))]
                if bm.can_swap_in(rid):
                    t, pairs = bm.swap_in(rid)
                    assert len(t) == swapped.pop(rid)
                    assert len(pairs) <= len(t)   # revivals copy nothing
                    tokens[rid] = 0
        elif op == 10:                                # swap discard (abort)
            srids = list(swapped)
            if srids:
                rid = srids[draw(len(srids))]
                bm.swap_discard(rid)
                del swapped[rid]
        else:                                         # adopt cached blocks
            if next_hash[0]:
                h = draw(next_hash[0]) + 1
                blocks = bm.match([h])
                if blocks:
                    rid = new_rid()
                    bm.adopt(rid, blocks)
                    tokens[rid] = 0
        check_accounting()
    for rid in list(tokens):
        bm.free(rid)
        del tokens[rid]
        check_accounting()
    for rid in list(swapped):
        bm.swap_discard(rid)
        del swapped[rid]
        check_accounting()
    assert bm.num_free == NB - 1
    assert bm.num_host_free == NH


def test_block_manager_random_walk_seeded():
    for seed in range(8):
        rng = random.Random(seed)
        _bm_random_walk(iter(lambda: rng.randrange(1 << 20), None))


def test_block_manager_random_walk_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(st.lists(st.integers(0, (1 << 20) - 1), max_size=900))
    @hyp.settings(max_examples=60, deadline=None)
    def prop(tape):
        it = iter(tape)
        _bm_random_walk(iter(lambda: next(it, 0), None))

    prop()


# ---------------------------------------------------------------------------
# Host tier: swap-out / swap-in residency
# ---------------------------------------------------------------------------


def test_swap_roundtrip_revives_free_device_blocks():
    """Swap out then immediately swap in: hashed device blocks survived on
    the free list (pages are never written while free), so the table is
    rebuilt in place with zero h2d copies."""
    bm = BlockManager(num_blocks=6, block_size=4, num_host_blocks=4)
    bm.allocate(1, 8)
    t0 = bm.table(1)
    bm.register(t0[0], b"h0"), bm.register(t0[1], b"h1")
    pairs = bm.swap_out(1)
    assert [b for b, _ in pairs] == t0 and bm.is_swapped(1)
    assert bm.num_host_free == 2 and bm.num_free == 5
    assert bm.match_host([b"h0", b"h1"]) == [s for _, s in pairs]
    bm.check()
    t1, copies = bm.swap_in(1)
    assert t1 == t0 and copies == []              # pure revival
    assert bm.num_host_free == 4 and not bm.is_swapped(1)
    bm.check()
    bm.free(1)
    bm.check()


def test_swap_in_copies_after_device_eviction():
    """If the freed device twins get recycled while a request is swapped
    out, swap-in must allocate fresh blocks and return h2d copy pairs,
    re-registering the hashes on the new blocks."""
    bm = BlockManager(num_blocks=6, block_size=4, num_host_blocks=4)
    bm.allocate(1, 8)
    t0 = bm.table(1)
    bm.register(t0[0], b"h0"), bm.register(t0[1], b"h1")
    bm.swap_out(1)
    bm.allocate(2, 20)                 # recycles every free block
    assert bm.match([b"h0", b"h1"]) == []         # device hashes wiped
    assert not bm.can_swap_in(1)
    bm.free(2)
    t1, copies = bm.swap_in(1)
    assert len(t1) == 2 and len(copies) == 2      # no revival possible
    assert bm.match([b"h0", b"h1"]) == t1         # hashes re-registered
    bm.check()


def test_match_host_and_host_copy_in_shares_blocks():
    """A host prefix hit copies swapped slots into fresh device blocks
    without disturbing the swapped-out owner; a later swap-in of the
    owner dedups onto the re-registered blocks (refcount share)."""
    bm = BlockManager(num_blocks=6, block_size=4, num_host_blocks=4)
    bm.allocate(1, 8)
    h = chain_block_hashes(np.arange(8, dtype=np.int32), 4)
    for b, hb in zip(bm.table(1), h):
        bm.register(b, hb)
    bm.swap_out(1)
    bm.allocate(2, 20)                 # wipe the device-side hash index
    bm.free(2)
    assert bm.match(h) == []
    slots = bm.match_host(h)
    assert len(slots) == 2
    t3, copies = bm.host_copy_in(3, slots, h)
    assert len(t3) == 2 and [s for s, _ in copies] == slots
    assert bm.match(h) == t3           # host hit re-registered on device
    bm.check()
    t1, copies1 = bm.swap_in(1)        # owner dedups onto rid 3's blocks
    assert t1 == t3 and copies1 == []
    assert bm.refcount(t1[0]) == 2
    bm.check()
    bm.free(1), bm.free(3)
    bm.check()


def test_swap_discard_releases_host_slots():
    bm = BlockManager(num_blocks=6, block_size=4, num_host_blocks=4)
    bm.allocate(1, 8)
    bm.register(bm.table(1)[0], b"h0")
    bm.swap_out(1)
    assert bm.num_host_free == 2
    bm.swap_discard(1)
    assert bm.num_host_free == 4 and not bm.is_swapped(1)
    assert bm.match_host([b"h0"]) == []           # host hash died with slot
    bm.check()


def test_swap_cost_model_prefers_cheaper_side():
    from repro.serving.scheduler import SwapCostModel
    m = SwapCostModel(block_bytes=1 << 20)        # defaults: 4 GB/s, 20k t/s
    # 2 blocks: 4 MiB both ways / 4 GB/s ~ 1.0 ms < 100 tokens / 20k t/s
    assert m.prefer_swap(2, 100)
    assert not m.prefer_swap(64, 4)               # 128 MiB vs 0.2 ms
    assert SwapCostModel(block_bytes=1, policy="always").prefer_swap(9, 0)
    assert not SwapCostModel(block_bytes=1, policy="never").prefer_swap(0, 9)
    # EMA observations move the estimates toward the measured rates
    m.observe_swap(1 << 30, 1.0)                  # measured 1 GB/s
    assert m.bytes_per_s < 4e9
    m.observe_prefill(100_000, 1.0)               # measured 100k tok/s
    assert m.prefill_tok_s > 2e4


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------


def _req(n_prompt=8, max_new=4, **kw):
    return Request(np.arange(n_prompt, dtype=np.int32), max_new=max_new,
                   **kw)


def _sched(bm, max_batch=2, max_blocks_per_seq=4, budget=12, chunk=8, **kw):
    return Scheduler(bm, max_batch, max_blocks_per_seq, budget, chunk, **kw)


def _complete_chunk(plan):
    """Simulate the engine finishing the planned chunk (+ a sampled token
    when the prompt completes)."""
    slot, req, n = plan.chunk
    req.num_computed += n
    if req.num_computed == req.context_len:
        req.out.append(7)
    return slot, req


def test_scheduler_budget_and_fcfs_order():
    bm = BlockManager(num_blocks=17, block_size=4)
    s = _sched(bm, max_batch=2, budget=9, chunk=8)
    reqs = [_req(n_prompt=12) for _ in range(3)]
    for r in reqs:
        s.add(r)
    p1 = s.schedule()                       # admit first; chunk of 8
    assert p1.decodes == [] and p1.admitted == 1
    assert p1.chunk[1] is reqs[0] and p1.chunk[2] == 8
    assert p1.scheduled_tokens <= 9
    _complete_chunk(p1)
    p2 = s.schedule()                       # finish req0's prompt (4 left)
    assert p2.chunk[1] is reqs[0] and p2.chunk[2] == 4
    _complete_chunk(p2)                     # samples req0's first token
    assert reqs[0].decode_ready
    p3 = s.schedule()                       # req0 decodes, req1 admits
    assert [r.rid for _, r in p3.decodes] == [reqs[0].rid]
    assert p3.chunk[1] is reqs[1]
    assert p3.chunk[2] == 8                 # 9 budget - 1 decode
    assert len(s.waiting) == 1              # no slot for the third yet


def test_scheduler_admission_waits_for_free_slot():
    bm = BlockManager(num_blocks=33, block_size=4)
    s = _sched(bm, max_batch=1, budget=16, chunk=8)
    a, b = _req(), _req()
    s.add(a), s.add(b)
    p = s.schedule()
    _complete_chunk(p)
    assert a.decode_ready and len(s.waiting) == 1
    p2 = s.schedule()                       # slot busy: b keeps waiting
    assert p2.chunk is None and len(p2.decodes) == 1
    a.out.append(9)
    a.num_computed += 1
    s.retire(0)
    p3 = s.schedule()
    assert p3.chunk[1] is b


def test_scheduler_preempts_newest_and_requeues_front():
    # 6 allocatable blocks of 2 tokens; two requests of prompt 4 fill the
    # pool after their first sampled token; growth must evict the newest.
    bm = BlockManager(num_blocks=7, block_size=2)
    s = _sched(bm, max_batch=2, max_blocks_per_seq=6, budget=8, chunk=4,
               enable_prefix_caching=False)
    a, b, c = _req(n_prompt=4), _req(n_prompt=4), _req(n_prompt=4)
    s.add(a), s.add(b)
    _complete_chunk(s.schedule())           # a prefills, samples
    _complete_chunk(s.schedule())           # b prefills, samples
    assert a.decode_ready and b.decode_ready
    s.schedule()                            # both decode: 3 blocks each
    for r in (a, b):
        r.out.append(8)
        r.num_computed += 1
    s.add(c)                                # queued behind any preemption
    # a now at ctx 6 -> needs a 4th block; pool is dry -> b is evicted
    plan = s.schedule()
    assert [r.rid for _, r in plan.decodes] == [a.rid]
    assert b.n_preempted == 1 and s.n_preemptions == 1
    assert b.out == [7, 8]                  # keeps generated tokens
    # requeued at the FRONT: b re-admits ahead of c, recomputing
    # prompt + generated from scratch
    assert plan.chunk[1] is b and b.num_computed == 0
    assert s.waiting[0].rid == c.rid
    assert np.array_equal(b.prefill_tokens(),
                          np.concatenate([b.prompt, [7, 8]]))
    bm.check()


def _swap_preempt_setup():
    """The growth-pressure choreography of the preemption test above, but
    with a host tier and a policy="always" cost model: the evicted victim
    is swap-preempted instead of released."""
    from repro.serving.scheduler import SwapCostModel
    bm = BlockManager(num_blocks=7, block_size=2, num_host_blocks=8)
    s = _sched(bm, max_batch=2, max_blocks_per_seq=6, budget=8, chunk=4,
               enable_prefix_caching=False,
               swap_cost=SwapCostModel(block_bytes=64, policy="always"))
    a, b = _req(n_prompt=4), _req(n_prompt=4)
    s.add(a), s.add(b)
    _complete_chunk(s.schedule())           # a prefills, samples
    _complete_chunk(s.schedule())           # b prefills, samples
    s.schedule()                            # both decode: 3 blocks each
    for r in (a, b):
        r.out.append(8)
        r.num_computed += 1
    plan = s.schedule()         # a's growth evicts b -> swapped, not reset
    return bm, s, a, b, plan


def test_scheduler_swap_preemption_preserves_progress():
    bm, s, a, b, plan = _swap_preempt_setup()
    assert s.n_swap_preemptions == 1    # counted within n_preemptions
    assert len(plan.swap_outs) == 3         # b's whole table went to host
    assert bm.is_swapped(b.rid) and s.waiting[0] is b
    assert b.num_computed == 5              # progress survives the swap
    assert b.out == [7, 8]
    bm.check()
    # a finishes and retires; b swaps back in and resumes *decoding* —
    # no recompute chunk is scheduled for it
    slot_a = next(sl for sl, r in s.running.items() if r is a)
    s.retire(slot_a)
    plan2 = s.schedule()
    assert s.n_swap_ins == 1 and not bm.is_swapped(b.rid)
    assert len(plan2.swap_ins) == 3         # unhashed blocks: all copied
    assert plan2.chunk is None              # no recompute chunk for b
    assert b.num_computed == 5
    plan3 = s.schedule()                    # decodes are planned pre-admit
    assert [r.rid for _, r in plan3.decodes] == [b.rid]
    bm.check()


def test_scheduler_abort_swapped_request_discards_host_slots():
    bm, s, a, b, _ = _swap_preempt_setup()
    assert bm.num_host_free == 8 - 3
    assert s.abort(b.rid)
    assert s.n_aborts == 1
    assert bm.num_host_free == 8 and not bm.is_swapped(b.rid)
    assert not s.waiting
    bm.check()


def test_scheduler_abort_running_and_waiting():
    bm = BlockManager(num_blocks=17, block_size=4)
    s = _sched(bm, max_batch=1, budget=16, chunk=8)
    a, b = _req(), _req()
    s.add(a), s.add(b)
    _complete_chunk(s.schedule())           # a running, b waiting
    assert s.abort(b.rid)                   # waiting abort: just dequeues
    assert not s.waiting
    assert s.abort(a.rid)                   # running abort: frees the slot
    assert not s.running and not s.has_work
    assert bm.stats().blocks_in_use == 0
    assert not s.abort(999_999)             # unknown rid: no-op
    assert s.n_aborts == 2
    bm.check()


def test_scheduler_rejects_horizon_past_capacity():
    # the one place horizon validation lives: submission. Admission relies
    # on it instead of re-checking.
    bm = BlockManager(num_blocks=99, block_size=4)
    s = _sched(bm, max_batch=1, max_blocks_per_seq=4)   # 16-token cap
    with pytest.raises(ValueError, match="exceeds max_len capacity"):
        s.add(_req(n_prompt=8, max_new=9))
    s.add(_req(n_prompt=8, max_new=8))                     # exactly fits
    assert len(s.waiting) == 1


def test_admission_full_hit_cow_with_drained_free_list():
    """Regression: a full-prompt hit whose matched chain mixes a cached
    *free* block (revived by adoption) with a *live* shared block must
    drop the last hit when adoption drains the free list — the boundary
    COW would otherwise raise an uncaught MemoryError."""
    bm = BlockManager(num_blocks=5, block_size=2)
    s = _sched(bm, max_batch=2, max_blocks_per_seq=3, budget=8, chunk=4)
    toks = np.arange(4, dtype=np.int32)
    h0, h1 = chain_block_hashes(toks, 2)
    # stale cached-free copy of the first block (an earlier request's)
    x = bm.allocate(7777, 2)[0]
    bm.register(x, h0)
    bm.free(7777)
    # running request b computed its OWN copy of the prefix (h0 was taken
    # first, so only its second block registered) and holds all remaining
    # blocks; it is decode-ready and needs no growth
    b = Request(toks.copy(), max_new=4)
    b.out.append(8)
    b.num_computed = 4
    b.n_published = 2
    bm.allocate(b.rid, 6)                          # 3 blocks
    bm.register(bm.table(b.rid)[1], h1)
    s.running[0] = b
    s._join_order.append(0)
    assert bm.match([h0, h1]) == [x, bm.table(b.rid)[1]]
    assert bm.num_free == 1                        # exactly {x}
    c = Request(toks.copy(), max_new=2)
    s.add(c)
    plan = s.schedule()                            # must not raise
    assert plan.admitted == 1
    assert c.num_computed == 2                     # last hit dropped
    assert bm.table(c.rid) == [x]
    bm.check()


def test_admission_in_place_boundary_write_leaves_cache():
    """Regression: a full-prompt hit revived with refcount 1 recomputes
    its last token *in place*; until that write lands the block must leave
    the hash index, or an admission in the same pass adopts a block with a
    pending write (the decode would then write into a shared block)."""
    bm = BlockManager(num_blocks=9, block_size=2)
    s = _sched(bm, max_batch=2, max_blocks_per_seq=4, budget=8, chunk=4)
    stream = np.array([0, 1, 2, 7], np.int32)
    h0, h1 = chain_block_hashes(stream, 2)
    old = bm.allocate(4242, 4)
    bm.register(old[0], h0)
    bm.register(old[1], h1)
    bm.free(4242)                     # retired: both blocks cached-free
    # d: preempted recompute of prompt [0,1,2] + generated [7] — full hit,
    # immediately decode-ready, with a pending in-place write at pos 3
    d = Request(stream[:3].copy(), max_new=4)
    d.out.append(7)
    e = Request(stream.copy(), max_new=2)
    s.add(d)
    s.add(e)
    s.schedule()
    assert d.decode_ready and bm.table(d.rid) == old
    # e, admitted in the same pass, must NOT share d's pending-write block
    assert old[1] not in bm.table(e.rid)
    assert bm.refcount(old[1]) == 1
    assert bm.match([h0, h1]) == [old[0]]
    bm.check()


def test_scheduler_budget_must_exceed_max_batch():
    bm = BlockManager(num_blocks=9, block_size=4)
    with pytest.raises(ValueError, match="must exceed max_batch"):
        Scheduler(bm, 4, 4, 4, 1)


def test_request_eos_and_maxnew_done():
    r = _req(max_new=3, eos_id=42)
    assert not r.done
    r.out.append(1)
    assert not r.done
    r.out.append(42)
    assert r.done                       # EOS before max_new
    r2 = _req(max_new=2)
    r2.out += [1, 2]
    assert r2.done                      # max_new without EOS


# ---------------------------------------------------------------------------
# Engine end-to-end (smoke model on the host mesh)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def glm_smoke(tiny_mesh_module):
    from helpers import StaticServerOracle
    cfg = get_config("glm4_9b", smoke=True)
    server = StaticServerOracle(cfg, tiny_mesh_module, max_batch=4,
                                prompt_len=32, max_len=96)
    return cfg, tiny_mesh_module, server


@pytest.fixture(scope="module")
def tiny_mesh_module():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_engine_matches_static_server_greedy(glm_smoke):
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(4)]
    legacy = server.serve_batch(prompts, [8] * 4)
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          params=server.params, debug_invariants=True)
    reqs = [Request(p, max_new=8) for p in prompts]
    outs = eng.run(reqs, arrival_steps=[0, 0, 2, 5])
    for i, r in enumerate(reqs):
        # max_batch=2 < 4 requests + staggered arrivals: identical greedy
        # tokens regardless of batch composition over time
        np.testing.assert_array_equal(outs[r.rid], legacy[i])


def test_engine_chunked_prefill_matches_monolithic(glm_smoke):
    """A chunk budget smaller than the prompt streams the prefill over
    several steps — greedy outputs must not change."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    legacy = server.serve_batch(prompts, [6] * 2)
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          max_num_batched_tokens=2 + 12,   # 12-token chunks
                          params=server.params, debug_invariants=True)
    reqs = [Request(p, max_new=6) for p in prompts]
    outs = eng.run(reqs)
    assert eng.stats["prefill_chunks"] >= 6     # ceil(32/12) = 3 per prompt
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(outs[r.rid], legacy[i])


def test_engine_no_decode_stall_during_long_prefill(glm_smoke):
    """While a long prompt streams in chunks, running decodes must make
    progress every step (the two-phase engine's full-batch prefill stall
    is gone)."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    short = Request(RNG.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new=24)
    long_r = Request(RNG.integers(0, cfg.vocab_size, 64).astype(np.int32),
                     max_new=4)
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          max_num_batched_tokens=2 + 8,    # 8-token chunks
                          params=server.params, debug_invariants=True)
    eng.sched.add(short)
    eng.step()                       # short's whole prompt is one chunk
    eng.step()                       # short decodes alone once
    eng.sched.add(long_r)
    decoded_during_prefill = 0
    while long_r.num_computed < long_r.context_len and not long_r.out:
        before = len(short.out)
        assert eng.step()
        assert len(short.out) == before + 1    # a decode token EVERY step
        decoded_during_prefill += 1
    assert decoded_during_prefill >= 8         # 64 tokens / 8-token chunks
    while eng.sched.has_work:
        eng.step()
    assert len(short.out) == 24 and len(long_r.out) == 4


def test_engine_eos_early_stop_frees_slot(glm_smoke):
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    # probe: find a token request 0 greedily emits for the first time at
    # some early step — using it as EOS must stop generation right there
    eng = InferenceEngine(cfg, mesh, max_batch=1, block_size=16, max_len=96,
                          params=server.params, debug_invariants=True)
    probe = Request(prompts[0], max_new=6)
    pout = eng.run([probe])[probe.rid].tolist()
    idx = next((i for i in range(1, 6) if pout[i] not in pout[:i]), None)
    if idx is None:
        pytest.skip("probe emitted no first-occurrence token")
    eos = pout[idx]

    eng = InferenceEngine(cfg, mesh, max_batch=1, block_size=16, max_len=96,
                          params=server.params, debug_invariants=True)
    r0 = Request(prompts[0], max_new=32, eos_id=eos)
    r1 = Request(prompts[1], max_new=4)
    outs = eng.run([r0, r1])
    assert outs[r0.rid][-1] == eos and len(outs[r0.rid]) == idx + 1
    assert len(outs[r1.rid]) == 4
    # retired-at-EOS request stopped consuming steps: with one slot, each
    # request costs 1 prefill-chunk step plus one decode step per further
    # token — nowhere near r0's max_new=32
    assert eng.stats["steps"] == (1 + idx) + (1 + 3)
    assert eng.bm.stats().blocks_in_use == 0       # everything freed


def test_engine_preemption_preserves_greedy_output(glm_smoke):
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    base = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                           max_len=96, params=server.params,
                           debug_invariants=True)
    want = base.run([Request(p, max_new=20) for p in prompts])
    want = list(want.values())

    # 7 allocatable blocks of 16: two ctx-33 requests take 3 blocks each;
    # growth past 48 tokens (ctx 32+16) forces preempting the newer one.
    tight = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                            max_len=96, num_blocks=8, params=server.params,
                            debug_invariants=True)
    reqs = [Request(p, max_new=20) for p in prompts]
    got = tight.run(reqs)
    assert tight.stats["preemptions"] >= 1
    # the victim's recompute hits its own just-freed cached blocks
    assert tight.stats["cache_hit_tokens"] > 0
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)


def test_engine_swap_preemption_preserves_greedy_output(glm_smoke):
    """Swap-preemption is byte-identical to the unconstrained engine (and
    hence to recompute-preemption): swapped pages come back exact copies,
    and the host round-trip shows up in the swap counters."""
    from repro.serving import InferenceEngine, Request
    from repro.serving.kv_cache import block_bytes
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    base = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                           max_len=96, params=server.params,
                           debug_invariants=True)
    want = list(base.run([Request(p, max_new=20) for p in prompts])
                .values())
    bb = block_bytes(cfg, 16)
    tight = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                            max_len=96, num_blocks=8, params=server.params,
                            swap_space_bytes=8 * bb, swap_policy="always",
                            debug_invariants=True)
    reqs = [Request(p, max_new=20) for p in prompts]
    got = tight.run(reqs)
    assert tight.stats["swap_preemptions"] >= 1
    assert tight.stats["swap_ins"] >= 1
    assert tight.stats["swapped_out_blocks"] > 0
    assert tight.stats["swapped_out_bytes"] \
        == tight.stats["swapped_out_blocks"] * bb
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)
    assert tight.bm.stats().blocks_in_use == 0
    tight.bm.check()


def test_engine_abort_mid_run_releases_resources(glm_smoke):
    """Aborting a running and a waiting request mid-serve frees their
    slots/blocks, counts in stats, and leaves the survivors untouched."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(3)]
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          params=server.params, debug_invariants=True)
    reqs = [Request(p, max_new=24) for p in prompts]
    for r in reqs:
        eng.sched.add(r)
    for _ in range(6):
        eng.step()
    assert eng.abort(reqs[0].rid)          # running
    assert eng.abort(reqs[2].rid)          # still waiting (max_batch=2)
    assert not eng.abort(reqs[0].rid)      # already gone
    while eng.sched.has_work:
        eng.step()
    assert eng.stats["aborts"] == 2
    assert len(reqs[1].out) == 24          # survivor ran to completion
    assert 0 < len(reqs[0].out) < 24       # victim stopped where aborted
    assert len(reqs[2].out) <= 1           # never got a slot
    assert eng.bm.stats().blocks_in_use == 0
    eng.bm.check()


def test_engine_int8_cross_path_identity(glm_smoke):
    """One kv_dtype, every path: the int8 engine's greedy outputs are
    byte-identical across an unconstrained run, a prefix-cache re-run,
    recompute preemption and swap preemption — quantization is a pure
    elementwise function of the bf16 writes, so the repo's cross-path
    byte-identity story survives storage narrowing."""
    from repro.serving import InferenceEngine, Request
    from repro.serving.kv_cache import block_bytes
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    kw = dict(max_batch=2, block_size=16, max_len=96, params=server.params,
              kv_dtype="int8", debug_invariants=True)
    base = InferenceEngine(cfg, mesh, **kw)
    assert base.stats["kv_dtype"] == "int8"
    want = list(base.run([Request(p, max_new=20) for p in prompts])
                .values())
    rerun = list(base.run([Request(p, max_new=20) for p in prompts])
                 .values())                # second pass: prefix-cache hits
    assert base.stats["cache_hit_tokens"] > 0
    for w, g in zip(want, rerun):
        np.testing.assert_array_equal(w, g)
    bb = block_bytes(cfg, 16, kv_dtype="int8")
    for swap_bytes in (0, 8 * bb):
        tight = InferenceEngine(cfg, mesh, num_blocks=8,
                                swap_space_bytes=swap_bytes,
                                swap_policy="always" if swap_bytes
                                else "auto", **kw)
        reqs = [Request(p, max_new=20) for p in prompts]
        got = tight.run(reqs)
        n_pre = (tight.stats["swap_preemptions"] if swap_bytes
                 else tight.stats["preemptions"])
        assert n_pre >= 1
        for w, r in zip(want, reqs):
            np.testing.assert_array_equal(got[r.rid], w)


def test_engine_quantized_tolerance_vs_bf16(glm_smoke):
    """Quantized engines are tolerance-equivalent to bf16 on greedy
    tokens: the prompt-prefill (first) token matches on nearly every
    request, and int8 (8-bit mantissa budget) tracks the full trajectory
    far more closely than the tiny-signal random-weight setup lets fp8
    (3-bit mantissa) — calibrated against the fixed fixture params."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(4)]
    kw = dict(max_batch=2, block_size=16, max_len=96, params=server.params,
              debug_invariants=True)
    outs = {}
    for dtype in ("bf16", "int8", "fp8"):
        eng = InferenceEngine(cfg, mesh, kv_dtype=dtype, **kw)
        reqs = [Request(p, max_new=12) for p in prompts]
        got = eng.run(reqs)
        outs[dtype] = [got[r.rid] for r in reqs]
    for dtype, min_first, min_total in (("int8", 3, 0.75), ("fp8", 2, 0.4)):
        first = sum(a[0] == b[0]
                    for a, b in zip(outs[dtype], outs["bf16"]))
        total = sum(int(np.sum(a == b))
                    for a, b in zip(outs[dtype], outs["bf16"]))
        assert first >= min_first, (dtype, first)
        assert total >= min_total * 4 * 12, (dtype, total)


def test_engine_int8_cache_layout_and_footprint(glm_smoke):
    """The int8 engine's paged pools really are int8 with fp32 (..., 1)
    scale leaves riding the same block axis, and the device footprint
    shrinks accordingly."""
    import jax
    from repro.serving import InferenceEngine
    cfg, mesh, server = glm_smoke
    kw = dict(max_batch=2, block_size=16, max_len=96, params=server.params,
              num_blocks=8)
    bf = InferenceEngine(cfg, mesh, **kw)
    i8 = InferenceEngine(cfg, mesh, kv_dtype="int8", **kw)
    dtypes = {str(p.dtype) for p in jax.tree.leaves(i8.cache)
              if p.ndim >= 2 and p.shape[1] == 8}
    assert "int8" in dtypes and "float32" in dtypes
    scales = [p for p in jax.tree.leaves(i8.cache)
              if p.ndim == 5 and p.shape[1] == 8 and p.shape[-1] == 1]
    assert scales and all(p.dtype == np.float32 for p in scales)
    assert i8.stats["kv_cache_mib"] < bf.stats["kv_cache_mib"]


def test_engine_shared_prefix_shares_blocks(glm_smoke):
    """N requests with a long common prefix: byte-identical outputs to the
    no-sharing engine, with measurably fewer blocks in use."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    common = RNG.integers(0, cfg.vocab_size, 64).astype(np.int32)
    prompts = [np.concatenate(
        [common, RNG.integers(0, cfg.vocab_size, 8).astype(np.int32)])
        for _ in range(6)]
    kw = dict(max_batch=4, block_size=16, max_len=96, params=server.params,
              debug_invariants=True)
    shared = InferenceEngine(cfg, mesh, **kw)
    o_s = shared.run([Request(p, max_new=6) for p in prompts])
    plain = InferenceEngine(cfg, mesh, enable_prefix_caching=False, **kw)
    o_p = plain.run([Request(p, max_new=6) for p in prompts])
    for a, b in zip(o_s.values(), o_p.values()):
        np.testing.assert_array_equal(a, b)
    # 4 shared 16-token blocks per request after the first
    assert shared.stats["cache_hit_tokens"] >= 5 * 64
    assert shared.stats["peak_blocks_in_use"] \
        < plain.stats["peak_blocks_in_use"]
    assert shared.stats["peak_block_utilization"] \
        < plain.stats["peak_block_utilization"]


def test_engine_full_prompt_cache_hit_cow(glm_smoke):
    """Identical block-aligned prompts: the whole prompt hits the cache,
    the recomputed last token's write lands in a shared block, and the
    copy-on-write keeps outputs byte-identical."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompt = RNG.integers(0, cfg.vocab_size, 64).astype(np.int32)
    kw = dict(max_batch=4, block_size=16, max_len=96, params=server.params,
              debug_invariants=True)
    shared = InferenceEngine(cfg, mesh, **kw)
    reqs = [Request(prompt.copy(), max_new=6) for _ in range(3)]
    o_s = shared.run(reqs, arrival_steps=[0, 3, 6])
    assert shared.stats["cow_copies"] >= 1
    assert shared.stats["cache_hit_tokens"] >= 2 * 63
    plain = InferenceEngine(cfg, mesh, enable_prefix_caching=False, **kw)
    reqs_p = [Request(prompt.copy(), max_new=6) for _ in range(3)]
    o_p = plain.run(reqs_p, arrival_steps=[0, 3, 6])
    for a, b in zip(o_s.values(), o_p.values()):
        np.testing.assert_array_equal(a, b)


def test_engine_latency_stats(glm_smoke):
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(3)]
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          params=server.params, debug_invariants=True)
    reqs = [Request(p, max_new=4) for p in prompts]
    eng.run(reqs, arrival_steps=[0, 0, 3])
    lat = eng.stats["latency"]
    assert set(lat) == {r.rid for r in reqs}
    for r in reqs:
        rec = lat[r.rid]
        assert rec["arrival_step"] <= rec["first_token_step"] \
            <= rec["done_step"]
        assert rec["arrival_wall"] <= rec["first_token_wall"] \
            <= rec["done_wall"]
        # 4 tokens = first + 3 decodes, plus any preemption stalls
        assert rec["done_step"] - rec["first_token_step"] >= 3


def test_engine_latency_retention_bounded(glm_smoke):
    """Per-request latency records are evicted past the cap, but the
    retirement-time histograms keep every observation — the serve loop's
    memory stays O(cap + buckets) over millions of requests."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          params=server.params, latency_record_cap=4,
                          debug_invariants=True)
    reqs = [Request(RNG.integers(0, cfg.vocab_size, 16).astype(np.int32),
                    max_new=2) for _ in range(8)]
    eng.run(reqs)
    assert eng.stats["requests_done"] == 8
    assert len(eng.stats["latency"]) <= 4          # bounded retention
    for key in ("ttft_steps", "e2e_steps", "ttft_seconds", "e2e_seconds"):
        assert eng.hist[key].count == 8            # nothing lost
    # e2e dominates ttft observation-by-observation, so also in the mean
    assert eng.hist["e2e_steps"].mean >= eng.hist["ttft_steps"].mean


def test_engine_rate_accessors(glm_smoke):
    """cache_hit_rate / preemption_rate / mean_accept_len are div-zero
    guarded on a fresh engine and land in range after traffic — the one
    code path /metrics, the bench, and serve.py all report."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          params=server.params, debug_invariants=True)
    assert eng.cache_hit_rate == 0.0
    assert eng.preemption_rate == 0.0
    assert eng.mean_accept_len == 0.0
    prompt = RNG.integers(0, cfg.vocab_size, 64).astype(np.int32)
    eng.run([Request(prompt.copy(), max_new=2) for _ in range(2)],
            arrival_steps=[0, 3])                  # duplicate: prefix hit
    assert 0.0 < eng.cache_hit_rate < 1.0
    assert 0.0 <= eng.preemption_rate <= 1.0
    assert eng.mean_accept_len == 0.0              # no speculation here


def test_runner_dispatch_and_vision_rejection(glm_smoke):
    from repro.config import ParallelConfig
    from repro.serving import (EncDecRunner, HybridRunner, InferenceEngine,
                               SSMRunner, TransformerRunner, make_runner)
    _, mesh, _ = glm_smoke
    pcfg = ParallelConfig(remat="none")
    pairs = [("glm4_9b", TransformerRunner), ("mamba2_370m", SSMRunner),
             ("zamba2_2p7b", HybridRunner),
             ("whisper_large_v3", EncDecRunner)]
    for arch, klass in pairs:
        assert type(make_runner(get_config(arch, smoke=True), pcfg)) is klass
    with pytest.raises(ValueError, match="frontend"):
        InferenceEngine(get_config("qwen2_vl_2b", smoke=True), mesh)


# ---------------------------------------------------------------------------
# SlotStateCache / EncoderCache
# ---------------------------------------------------------------------------


def test_slot_state_cache_basic():
    from repro.serving import SlotStateCache
    sc = SlotStateCache(2)
    assert sc.allocate(10) == 0 and sc.allocate(11, 1) == 1
    sc.check()
    assert sc.num_free == 0 and sc.owner(0) == 10 and sc.slot(11) == 1
    with pytest.raises(KeyError):
        sc.allocate(10)                     # double alloc
    with pytest.raises(MemoryError):
        sc.allocate(12)                     # no free slot
    assert sc.free(10) == 0
    sc.check()
    with pytest.raises(MemoryError):
        sc.allocate(12, 1)                  # requested slot taken
    assert sc.allocate(12) == 0
    sc.check()
    assert sc.stats().utilization == 1.0


def _slot_cache_random_walk(tape):
    """Interpret ``tape`` (an iterator of ints) as allocate/allocate-at/
    free/preempt-readmit ops against a SlotStateCache, asserting the
    bijection invariant and exact free-slot accounting after every op —
    mirroring the BlockManager walks."""
    from repro.serving import SlotStateCache
    NS = 4
    sc = SlotStateCache(NS)
    bound: dict[int, int] = {}            # rid -> slot (our shadow model)
    next_rid = [0]

    def draw(n):
        return next(tape) % n

    def new_rid():
        next_rid[0] += 1
        return next_rid[0]

    def check():
        sc.check()
        assert sc.num_free == NS - len(bound)
        assert sorted(sc.free_slots()) == sorted(
            set(range(NS)) - set(bound.values()))
        for rid, slot in bound.items():
            assert sc.slot(rid) == slot and sc.owner(slot) == rid

    for _ in range(150):
        op = draw(4)
        rids = list(bound)
        if op == 0 or not rids:                     # allocate lowest-free
            rid = new_rid()
            try:
                bound[rid] = sc.allocate(rid)
            except MemoryError:
                assert len(bound) == NS
        elif op == 1:                               # allocate a chosen slot
            rid, slot = new_rid(), draw(NS)
            try:
                assert sc.allocate(rid, slot) == slot
                bound[rid] = slot
            except MemoryError:
                assert slot in bound.values()
        elif op == 2:                               # retire
            rid = rids[draw(len(rids))]
            assert sc.free(rid) == bound.pop(rid)
        else:                                       # preempt + readmit
            rid = rids[draw(len(rids))]
            sc.free(rid)
            del bound[rid]
            check()
            rid2 = new_rid()                 # recompute joins as a fresh
            bound[rid2] = sc.allocate(rid2)  # binding, any free slot
        check()
    for rid in list(bound):
        sc.free(rid)
        del bound[rid]
        check()
    assert sc.num_free == NS


def test_slot_cache_random_walk_seeded():
    for seed in range(8):
        rng = random.Random(seed)
        _slot_cache_random_walk(iter(lambda: rng.randrange(1 << 20), None))


def test_slot_cache_random_walk_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(st.lists(st.integers(0, (1 << 20) - 1), max_size=900))
    @hyp.settings(max_examples=60, deadline=None)
    def prop(tape):
        it = iter(tape)
        _slot_cache_random_walk(iter(lambda: next(it, 0), None))

    prop()


# ---------------------------------------------------------------------------
# SSM / hybrid / enc-dec runners vs the static oracle
# ---------------------------------------------------------------------------


def _oracle(arch, mesh, prompt_len, max_len=96, max_batch=4):
    from helpers import StaticServerOracle
    cfg = get_config(arch, smoke=True)
    return cfg, StaticServerOracle(cfg, mesh, max_batch=max_batch,
                                   prompt_len=prompt_len, max_len=max_len)


def test_engine_matches_static_mamba2(tiny_mesh_module):
    """Pure SSM through the engine: slot-state cache, no block manager,
    greedy outputs byte-identical to the static oracle — including a
    chunked prefill whose boundaries land on SSD chunk multiples."""
    from repro.serving import InferenceEngine, Request
    mesh = tiny_mesh_module
    cfg, server = _oracle("mamba2_370m", mesh, prompt_len=24)
    prompts = [RNG.integers(0, cfg.vocab_size, 24).astype(np.int32)
               for _ in range(4)]
    legacy = server.serve_batch(prompts, [8] * 4)
    # chunk budget 16 < prompt 24: two chunks (16 then 8); the smoke SSD
    # chunk_size is 8, so the 16-token boundary is quantum-aligned
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          max_num_batched_tokens=2 + 16,
                          params=server.params, debug_invariants=True)
    assert eng.bm is None and eng.slot_cache is not None
    assert eng.sched.chunk_quantum == cfg.ssm.chunk_size == 8
    reqs = [Request(p, max_new=8) for p in prompts]
    outs = eng.run(reqs, arrival_steps=[0, 0, 3, 5])
    assert eng.stats["prefill_chunks"] >= 8        # 2 chunks per prompt
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(outs[r.rid], legacy[i])


def test_engine_ssm_quantized_chunk_lengths(tiny_mesh_module):
    """Non-final SSM chunks are quantized to the SSD chunk size even when
    the leftover step budget is not a multiple."""
    from repro.serving import InferenceEngine, Request
    mesh = tiny_mesh_module
    cfg, server = _oracle("mamba2_370m", mesh, prompt_len=24)
    prompts = [RNG.integers(0, cfg.vocab_size, 24).astype(np.int32)
               for _ in range(2)]
    legacy = server.serve_batch(prompts, [6] * 2)
    # budget leaves 13 tokens of chunk: quantized down to 8 until the
    # final chunk (24 = 8 + 8 + final 8; with a decode running, 13 -> 8)
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          max_num_batched_tokens=2 + 13,
                          params=server.params, debug_invariants=True)
    reqs = [Request(p, max_new=6) for p in prompts]
    outs = eng.run(reqs)
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(outs[r.rid], legacy[i])


def test_engine_matches_static_zamba2(tiny_mesh_module):
    """Hybrid runner: mamba slot state + paged shared-attention KV behind
    one block table; byte-identical to the static oracle under staggered
    arrivals and chunked prefill."""
    from repro.serving import InferenceEngine, Request
    mesh = tiny_mesh_module
    cfg, server = _oracle("zamba2_2p7b", mesh, prompt_len=24)
    prompts = [RNG.integers(0, cfg.vocab_size, 24).astype(np.int32)
               for _ in range(4)]
    legacy = server.serve_batch(prompts, [8] * 4)
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          max_num_batched_tokens=2 + 16,
                          params=server.params, debug_invariants=True)
    assert eng.bm is not None and eng.slot_cache is not None
    assert not eng.sched.enable_prefix_caching   # state is not shareable
    reqs = [Request(p, max_new=8) for p in prompts]
    outs = eng.run(reqs, arrival_steps=[0, 0, 2, 5])
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(outs[r.rid], legacy[i])


def test_engine_zamba2_preemption_resets_slot_state(tiny_mesh_module):
    """A hybrid victim of block-pool preemption recomputes from zeroed
    slot state: greedy outputs stay preemption-invariant."""
    from repro.serving import InferenceEngine, Request
    mesh = tiny_mesh_module
    cfg, server = _oracle("zamba2_2p7b", mesh, prompt_len=32)
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    base = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                           max_len=96, params=server.params,
                           debug_invariants=True)
    want = list(base.run([Request(p, max_new=20) for p in prompts]).values())
    # 7 allocatable blocks of 16: two ctx-33 requests take 3 blocks each;
    # growth past 48 tokens forces preempting the newer one.
    tight = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                            max_len=96, num_blocks=8, params=server.params,
                            debug_invariants=True)
    reqs = [Request(p, max_new=20) for p in prompts]
    got = tight.run(reqs)
    assert tight.stats["preemptions"] >= 1
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)


def test_engine_matches_static_whisper(tiny_mesh_module):
    """Enc-dec runner: paged decoder self-KV + per-slot read-only cross
    K/V written by the admission encode pass; byte-identical to the
    static oracle, with per-request (distinct) encoder inputs."""
    from repro.serving import InferenceEngine, Request
    mesh = tiny_mesh_module
    cfg, server = _oracle("whisper_large_v3", mesh, prompt_len=8,
                          max_len=64)
    prompts = [RNG.integers(0, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(3)]
    frames = [RNG.normal(0, 1, (cfg.encoder_seq_len, cfg.d_model)
                         ).astype(np.float32) for _ in range(3)]
    # oracle decodes one batch per request so each keeps its own frames
    legacy = [server.serve_batch([p], [6], frames=[f])[0]
              for p, f in zip(prompts, frames)]
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=64,
                          params=server.params, debug_invariants=True)
    assert eng.encoder_cache is not None
    reqs = [Request(p, max_new=6, frames=f)
            for p, f in zip(prompts, frames)]
    outs = eng.run(reqs, arrival_steps=[0, 1, 4])
    assert eng.stats["encodes"] == 3
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(outs[r.rid], legacy[i])


def test_engine_whisper_preemption_reencodes(tiny_mesh_module):
    """An enc-dec victim of block-pool preemption re-runs its encode pass
    on readmission — cross K/V at the (possibly different) slot is its
    own, and greedy outputs stay preemption-invariant."""
    from repro.serving import InferenceEngine, Request
    mesh = tiny_mesh_module
    cfg, server = _oracle("whisper_large_v3", mesh, prompt_len=32,
                          max_len=96)
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    frames = [RNG.normal(0, 1, (cfg.encoder_seq_len, cfg.d_model)
                         ).astype(np.float32) for _ in range(2)]

    def make():
        return [Request(p, max_new=20, frames=f)
                for p, f in zip(prompts, frames)]

    base = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                           max_len=96, params=server.params,
                           debug_invariants=True)
    want = list(base.run(make()).values())
    # 7 allocatable blocks of 16: growth past 48 tokens preempts the newer
    tight = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                            max_len=96, num_blocks=8, params=server.params,
                            debug_invariants=True)
    reqs = make()
    got = tight.run(reqs)
    assert tight.stats["preemptions"] >= 1
    # one encode per admission: initial 2 + one per readmission
    assert tight.stats["encodes"] >= 2 + tight.stats["preemptions"]
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)


def test_engine_ssm_no_horizon_validation(tiny_mesh_module):
    """Slot caches have no block horizon: an SSM request whose
    prompt+max_new exceeds max_len capacity is accepted (the state is
    constant-size), while the paged transformer still rejects it."""
    from repro.serving import InferenceEngine, Request
    mesh = tiny_mesh_module
    cfg, server = _oracle("mamba2_370m", mesh, prompt_len=24, max_len=32)
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=32,
                          max_num_batched_tokens=2 + 16,
                          params=server.params, debug_invariants=True)
    long_req = Request(RNG.integers(0, cfg.vocab_size, 24).astype(np.int32),
                       max_new=24)                 # 48 > 32-token "cap"
    outs = eng.run([long_req])
    assert len(outs[long_req.rid]) == 24


# ---------------------------------------------------------------------------
# Sampling determinism (rid + step folded into the key)
# ---------------------------------------------------------------------------


def test_sampling_reproducible_across_preemption(glm_smoke):
    """Temperature sampling is a pure function of (seed, rid, step):
    outputs are identical with and without recompute-preemption."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    sp = SamplingParams(temperature=0.8, top_k=16, seed=3)

    def make():
        # pin rids: the sampling key folds (seed, rid, step), so replaying
        # the same logical requests must reuse their ids
        return [Request(p, max_new=20, sampling=sp, rid=77000 + i)
                for i, p in enumerate(prompts)]

    base = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                           max_len=96, params=server.params,
                           debug_invariants=True)
    want = list(base.run(make()).values())
    tight = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                            max_len=96, num_blocks=8, params=server.params,
                            debug_invariants=True)
    reqs = make()
    got = tight.run(reqs)
    assert tight.stats["preemptions"] >= 1
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)


# ---------------------------------------------------------------------------
# Speculative decoding (draft-and-verify)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def star_params(tiny_mesh_module):
    """Shared target params for the speculative tests (starcoder2-class
    dense GQA config, per the acceptance bar for byte-equivalence)."""
    import jax.numpy as jnp
    from repro.models import api
    cfg = get_config("starcoder2_3b", smoke=True)
    with jax.set_mesh(tiny_mesh_module):
        params_f32, _ = api.init_model(cfg, jax.random.key(0))
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params_f32)
    return cfg, params


def _spec_engine(cfg, mesh, params, k, *, self_draft=False, **kw):
    from repro.serving import InferenceEngine
    return InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                           max_len=96, params=params,
                           num_speculative_tokens=k,
                           draft_params=params if self_draft else None,
                           debug_invariants=True, **kw)


@pytest.mark.parametrize("self_draft", [True, False])
def test_engine_speculative_greedy_matches_plain(tiny_mesh_module,
                                                 star_params, self_draft):
    """Greedy speculative decode is byte-identical to plain decode, both
    with a self-draft (full acceptance: every verify row agrees) and with
    an independently initialized draft (near-zero acceptance: every token
    is the target's correction) — acceptance only moves *throughput*."""
    from repro.serving import InferenceEngine, Request, SpeculativeRunner
    cfg, params = star_params
    mesh = tiny_mesh_module
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(4)]
    plain = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                            max_len=96, params=params,
                            debug_invariants=True)
    want = plain.run([Request(p, max_new=8) for p in prompts])
    want = list(want.values())
    spec = _spec_engine(cfg, mesh, params, 2, self_draft=self_draft)
    assert isinstance(spec.runner, SpeculativeRunner)
    reqs = [Request(p, max_new=8) for p in prompts]
    got = spec.run(reqs, arrival_steps=[0, 0, 2, 5])
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)
    assert spec.stats["spec_decodes"] >= 1
    if self_draft:
        # identical draft == target logits: every draft token is accepted
        assert spec.stats["mean_accept_len"] > 1.0


def test_engine_int8_speculative_matches_plain_int8(tiny_mesh_module,
                                                    star_params):
    """Speculative decode (k=2, self-draft) over int8 pools is
    byte-identical to the plain int8 engine: draft and target quantize
    the same bf16 writes, so verify rows see the same dequantized KV."""
    from repro.serving import InferenceEngine, Request
    cfg, params = star_params
    mesh = tiny_mesh_module
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(4)]
    plain = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                            max_len=96, params=params, kv_dtype="int8",
                            debug_invariants=True)
    want = list(plain.run([Request(p, max_new=8) for p in prompts])
                .values())
    spec = _spec_engine(cfg, mesh, params, 2, self_draft=True,
                        kv_dtype="int8")
    reqs = [Request(p, max_new=8) for p in prompts]
    got = spec.run(reqs, arrival_steps=[0, 0, 2, 5])
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)
    assert spec.stats["mean_accept_len"] > 1.0   # self-draft still accepts


def test_engine_speculative_prefix_cache_hit_cow(tiny_mesh_module,
                                                 star_params):
    """Full-prompt prefix-cache hits (boundary COW included) under
    speculation: cached blocks carry draft *and* target KV — outputs stay
    byte-identical to the non-speculative engine on the same workload."""
    from repro.serving import InferenceEngine, Request
    cfg, params = star_params
    mesh = tiny_mesh_module
    prompt = RNG.integers(0, cfg.vocab_size, 64).astype(np.int32)
    kw = dict(max_batch=4, block_size=16, max_len=96, params=params,
              debug_invariants=True)
    plain = InferenceEngine(cfg, mesh, **kw)
    reqs_p = [Request(prompt.copy(), max_new=6) for _ in range(3)]
    o_p = plain.run(reqs_p, arrival_steps=[0, 3, 6])
    spec = InferenceEngine(cfg, mesh, num_speculative_tokens=2,
                           draft_params=params, **kw)
    reqs_s = [Request(prompt.copy(), max_new=6) for _ in range(3)]
    o_s = spec.run(reqs_s, arrival_steps=[0, 3, 6])
    assert spec.stats["cow_copies"] >= 1
    assert spec.stats["cache_hit_tokens"] >= 2 * 63
    assert spec.stats["mean_accept_len"] > 1.0
    for a, b in zip(reqs_p, reqs_s):
        np.testing.assert_array_equal(o_p[a.rid], o_s[b.rid])


def test_engine_speculative_preemption_greedy(tiny_mesh_module, star_params):
    """Recompute-preemption under speculation (lookahead block pressure
    included): greedy outputs byte-identical to the unconstrained plain
    engine, and rejected lookahead blocks are rolled back (truncate) so
    the tight pool never leaks."""
    from repro.serving import InferenceEngine, Request
    cfg, params = star_params
    mesh = tiny_mesh_module
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    plain = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                            max_len=96, params=params,
                            debug_invariants=True)
    want = list(plain.run([Request(p, max_new=20) for p in prompts])
                .values())
    tight = _spec_engine(cfg, mesh, params, 2, num_blocks=8)
    reqs = [Request(p, max_new=20) for p in prompts]
    got = tight.run(reqs)
    assert tight.stats["preemptions"] >= 1
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)
    assert tight.bm.stats().blocks_in_use == 0


def test_engine_speculative_temperature_replays_across_preemption(
        tiny_mesh_module, star_params):
    """Temperature speculative sampling is a pure function of
    (seed, rid, counter): the draft/accept/residual streams key off the
    same rid-folded base keys as plain sampling, and preemption-recompute
    stops one token short so verify windows stay aligned — outputs replay
    identically under block-pool pressure."""
    cfg, params = star_params
    from repro.serving import Request
    mesh = tiny_mesh_module
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    sp = SamplingParams(temperature=0.9, top_k=16, seed=3)

    def make():
        return [Request(p, max_new=20, sampling=sp, rid=88000 + i)
                for i, p in enumerate(prompts)]

    base = _spec_engine(cfg, mesh, params, 2)
    want = list(base.run(make()).values())
    tight = _spec_engine(cfg, mesh, params, 2, num_blocks=8)
    reqs = make()
    got = tight.run(reqs)
    assert tight.stats["preemptions"] >= 1
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)


def test_engine_speculative_k0_degenerates_to_plain(tiny_mesh_module,
                                                    star_params):
    """k = 0 is the non-speculative path byte for byte, *including* the
    temperature RNG stream (the bonus sample uses the plain stream key)."""
    from repro.serving import InferenceEngine, Request
    cfg, params = star_params
    mesh = tiny_mesh_module
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    sp = SamplingParams(temperature=0.9, top_k=16, seed=7)

    def make():
        return [Request(p, max_new=10, sampling=sp, rid=99000 + i)
                for i, p in enumerate(prompts)]

    plain = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                            max_len=96, params=params,
                            debug_invariants=True)
    want = list(plain.run(make()).values())
    k0 = _spec_engine(cfg, mesh, params, 0, draft_cfg=cfg)
    got = k0.run(make())
    for w, (rid, g) in zip(want, sorted(got.items())):
        np.testing.assert_array_equal(g, w)


def test_speculative_verify_preserves_target_distribution():
    """Rejection sampling must leave the realized first-token marginal
    equal to the target distribution p even when the draft q is badly
    miscalibrated (chi-square-ish bound over many independent rids)."""
    from repro.serving.sampling import propose_tokens, speculative_verify
    V, N = 4, 4000
    p_logits = jnp.asarray([0.0, 1.0, -1.0, 0.5], jnp.float32)
    q_logits = jnp.asarray([2.0, -2.0, 0.0, 0.0], jnp.float32)
    temps = jnp.ones((N,), jnp.float32)
    top_ks = jnp.zeros((N,), jnp.int32)
    seeds = jnp.zeros((N,), jnp.int32)
    rids = jnp.arange(N, dtype=jnp.int32)
    cnts = jnp.zeros((N,), jnp.int32)
    q_rows = jnp.broadcast_to(q_logits, (N, V))
    d_toks = propose_tokens(q_rows, temps, top_ks, seeds, rids, cnts)
    out, n_acc = speculative_verify(
        d_toks[:, None], q_rows[:, None],
        jnp.broadcast_to(p_logits, (N, 2, V)),
        temps, top_ks, seeds, rids, cnts)
    first = np.asarray(out[:, 0])
    want = np.asarray(jax.nn.softmax(p_logits))
    got = np.bincount(first, minlength=V) / N
    np.testing.assert_allclose(got, want, atol=0.03)
    # and the proposals themselves follow q, not p
    got_q = np.bincount(np.asarray(d_toks), minlength=V) / N
    np.testing.assert_allclose(got_q, np.asarray(jax.nn.softmax(q_logits)),
                               atol=0.03)


def test_speculative_runner_rejects_bad_pairs():
    from repro.config import ParallelConfig
    from repro.serving import make_runner
    pcfg = ParallelConfig(remat="none")
    star = get_config("starcoder2_3b", smoke=True)
    with pytest.raises(ValueError, match="paged-transformer"):
        make_runner(get_config("mamba2_370m", smoke=True), pcfg,
                    draft_cfg=star, num_speculative_tokens=2)
    with pytest.raises(ValueError, match="paged-transformer"):
        make_runner(star, pcfg,
                    draft_cfg=get_config("mamba2_370m", smoke=True),
                    num_speculative_tokens=2)
    # full-size configs: smoke vocabs all coincide at 256
    with pytest.raises(ValueError, match="vocab"):
        make_runner(get_config("starcoder2_3b"), pcfg,
                    draft_cfg=get_config("glm4_9b"),
                    num_speculative_tokens=2)


def test_sampling_same_seed_requests_decorrelated(glm_smoke):
    """Folding the rid into the key keeps two same-seed, same-prompt
    requests on distinct sampling streams."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompt = RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
    sp = SamplingParams(temperature=1.2, top_k=0, seed=7)
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          params=server.params, debug_invariants=True)
    a = Request(prompt.copy(), max_new=12, sampling=sp)
    b = Request(prompt.copy(), max_new=12, sampling=sp)
    outs = eng.run([a, b])
    assert not np.array_equal(outs[a.rid], outs[b.rid])


# ---------------------------------------------------------------------------
# Full sampling pipeline in the engine (top-p/min-p/penalties/stop/logprobs)
# ---------------------------------------------------------------------------


FULL_SP = dict(temperature=0.9, top_k=16, top_p=0.85,
               repetition_penalty=1.3, frequency_penalty=0.2,
               stop=((3, 1, 4),))


def test_engine_full_pipeline_replays_across_preemption(glm_smoke):
    """Preemption-recompute with penalties and stop sequences active:
    the SamplingBuffer rebinds from (prompt, out) on re-admission, so
    penalty counts and stop rings land back exactly where the
    uninterrupted run had them — streams stay byte-identical."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    sp = SamplingParams(seed=3, **FULL_SP)

    def make():
        return [Request(p, max_new=20, sampling=sp, rid=66000 + i)
                for i, p in enumerate(prompts)]

    base = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                           max_len=96, params=server.params,
                           debug_invariants=True)
    want = list(base.run(make()).values())
    assert base.stats["full_sampling_steps"] > 0
    tight = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                            max_len=96, num_blocks=8, params=server.params,
                            debug_invariants=True)
    reqs = make()
    got = tight.run(reqs)
    assert tight.stats["preemptions"] >= 1
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)


def test_engine_full_pipeline_replays_across_swap_in(glm_smoke):
    """Swap-preemption + swap-in with the full pipeline active: the
    sampling row is freed at swap-out and rebuilt at swap-in, and the
    streams are byte-identical to the unconstrained engine."""
    from repro.serving import InferenceEngine, Request
    from repro.serving.kv_cache import block_bytes
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    sp = SamplingParams(seed=5, **FULL_SP)

    def make():
        return [Request(p, max_new=20, sampling=sp, rid=67000 + i)
                for i, p in enumerate(prompts)]

    base = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                           max_len=96, params=server.params,
                           debug_invariants=True)
    want = list(base.run(make()).values())
    bb = block_bytes(cfg, 16)
    tight = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                            max_len=96, num_blocks=8, params=server.params,
                            swap_space_bytes=8 * bb, swap_policy="always",
                            debug_invariants=True)
    reqs = make()
    got = tight.run(reqs)
    assert tight.stats["swap_preemptions"] >= 1
    assert tight.stats["swap_ins"] >= 1
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)
    assert tight.bm.stats().blocks_in_use == 0


def test_engine_speculative_full_pipeline_replays(tiny_mesh_module,
                                                  star_params):
    """Speculative k=2 with top-p + penalties: proposal-side counts
    accumulate draft one-hots, the verifier derives the identical
    per-position counts, and rollback never commits rejected tokens —
    outputs replay byte-identically under block-pool pressure."""
    from repro.serving import Request
    cfg, params = star_params
    mesh = tiny_mesh_module
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    sp = SamplingParams(seed=11, **FULL_SP)

    def make():
        return [Request(p, max_new=20, sampling=sp, rid=68000 + i)
                for i, p in enumerate(prompts)]

    base = _spec_engine(cfg, mesh, params, 2)
    want = list(base.run(make()).values())
    assert base.stats["full_sampling_steps"] > 0
    tight = _spec_engine(cfg, mesh, params, 2, num_blocks=8)
    reqs = make()
    got = tight.run(reqs)
    assert tight.stats["preemptions"] >= 1
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)
    assert tight.bm.stats().blocks_in_use == 0


def test_engine_pure_greedy_skips_full_pipeline(glm_smoke):
    """The fast-path guard: an all-greedy workload never compiles or
    runs the full sampling executables (no sampling collectives traced),
    and its bytes still match the static-server oracle."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(4)]
    legacy = server.serve_batch(prompts, [8] * 4)
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          params=server.params, debug_invariants=True)
    reqs = [Request(p, max_new=8) for p in prompts]
    outs = eng.run(reqs)
    assert eng._full_steps == {}            # full path never even traced
    assert eng.stats["full_sampling_steps"] == 0
    assert eng.stats["stop_hits"] == 0
    for r, want in zip(reqs, legacy):
        np.testing.assert_array_equal(outs[r.rid], want)


def test_engine_mixed_batch_full_path_preserves_plain_rows(glm_smoke):
    """A greedy request batched with a top-p batchmate rides the full
    executables (the batchmate needs them) yet emits bytes identical to
    its all-greedy solo run: every full-path transform is an exact
    identity at default params."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompt = RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
    solo = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                           max_len=96, params=server.params,
                           debug_invariants=True)
    g = Request(prompt.copy(), max_new=12, rid=70001)
    want = solo.run([g])[g.rid]
    mixed = InferenceEngine(cfg, mesh, max_batch=2, block_size=16,
                            max_len=96, params=server.params,
                            debug_invariants=True)
    g2 = Request(prompt.copy(), max_new=12, rid=70001)
    other = Request(
        RNG.integers(0, cfg.vocab_size, 32).astype(np.int32), max_new=12,
        sampling=SamplingParams(temperature=1.0, top_p=0.8, seed=9),
        rid=70002)
    outs = mixed.run([g2, other])
    assert mixed.stats["full_sampling_steps"] > 0
    np.testing.assert_array_equal(outs[g2.rid], want)


def test_engine_stop_sequence_retires_in_engine(glm_smoke):
    """A matched stop sequence retires the request inside the engine —
    shorter output, stop_hit set, counters bumped, blocks and the batch
    slot released — without consuming the remaining max_new steps."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompt = RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
    probe_eng = InferenceEngine(cfg, mesh, max_batch=1, block_size=16,
                                max_len=96, params=server.params,
                                debug_invariants=True)
    probe = Request(prompt.copy(), max_new=8)
    pout = probe_eng.run([probe])[probe.rid].tolist()
    # two-token stop ending at index 3 of the deterministic greedy stream
    stop = (int(pout[2]), int(pout[3]))

    eng = InferenceEngine(cfg, mesh, max_batch=1, block_size=16, max_len=96,
                          params=server.params, debug_invariants=True)
    r = Request(prompt.copy(), max_new=32,
                sampling=SamplingParams(stop=(stop,)))
    outs = eng.run([r])
    assert len(outs[r.rid]) == 4 and r.stop_hit
    assert tuple(outs[r.rid][-2:]) == stop
    assert eng.stats["stop_hits"] == 1
    assert not eng.sched.running                    # slot released
    assert eng.bm.stats().blocks_in_use == 0        # blocks released
    # stop sequences alone stay on the plain executables (host-side check)
    assert eng.stats["full_sampling_steps"] == 0


def test_engine_min_new_defers_eos_and_stop(glm_smoke):
    """min_new holds off EOS and stop retirement until the floor is
    reached; max_new still wins."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    prompt = RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
    probe_eng = InferenceEngine(cfg, mesh, max_batch=1, block_size=16,
                                max_len=96, params=server.params,
                                debug_invariants=True)
    probe = Request(prompt.copy(), max_new=20)
    pout = probe_eng.run([probe])[probe.rid].tolist()
    tok = int(pout[1])
    min_new = 6
    # expected: first re-occurrence at index >= min_new-1, else max_new
    exp = next((i + 1 for i in range(min_new - 1, 20) if pout[i] == tok), 20)

    eng = InferenceEngine(cfg, mesh, max_batch=1, block_size=16, max_len=96,
                          params=server.params, debug_invariants=True)
    r_eos = Request(prompt.copy(), max_new=20, eos_id=tok, min_new=min_new)
    assert len(eng.run([r_eos])[r_eos.rid]) == exp
    eng2 = InferenceEngine(cfg, mesh, max_batch=1, block_size=16, max_len=96,
                           params=server.params, debug_invariants=True)
    r_stop = Request(prompt.copy(), max_new=20, min_new=min_new,
                     sampling=SamplingParams(stop=((tok,),)))
    assert len(eng2.run([r_stop])[r_stop.rid]) == exp
    assert r_stop.stop_hit == (exp < 20)


def test_engine_logprobs_surface(glm_smoke):
    """logprobs route through on_token for every emitted token (chunk-
    final prefill tokens included), with the chosen token's logprob and
    a sorted top-n of the post-penalty distribution."""
    from repro.serving import InferenceEngine, Request
    cfg, mesh, server = glm_smoke
    eng = InferenceEngine(cfg, mesh, max_batch=2, block_size=16, max_len=96,
                          params=server.params, debug_invariants=True)
    got = {}
    eng.on_token = (lambda req, tok, lp=None:
                    got.setdefault(req.rid, []).append((int(tok), lp)))
    reqs = [Request(RNG.integers(0, cfg.vocab_size, 32).astype(np.int32),
                    max_new=6,
                    sampling=SamplingParams(temperature=0.8, seed=i,
                                            top_p=0.9, logprobs=3))
            for i in range(2)]
    outs = eng.run(reqs)
    for r in reqs:
        events = got[r.rid]
        assert len(events) == 6
        assert [t for t, _ in events] == list(outs[r.rid])
        for _, lp in events:
            assert lp is not None and len(lp["top"]) == 3
            assert all(isinstance(i, int) for i, _ in lp["top"])
            lps = [v for _, v in lp["top"]]
            assert lps == sorted(lps, reverse=True)
            assert lp["token_logprob"] <= 0.0
