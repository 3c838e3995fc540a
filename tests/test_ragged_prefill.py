"""Ragged packed-prefill tests: the flat-batch Pallas kernel and its
densifying oracle, the fused KV-write variant vs a separate scatter, the
pack/unpack layout round-trip, multi-chunk scheduler plans, multi-page
kernel fetch (``pages_per_compute_block``), and engine byte-identity of
packed (prefill_pack > 1) vs single-chunk serving — packing must be a
pure throughput optimization, never a numerics change."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.kernels.paged_attention import (paged_attention,
                                           paged_prefill_attention,
                                           ragged_paged_prefill_attention)
from repro.kernels.ref import (paged_attention_partial_ref,
                               paged_attention_ref,
                               paged_prefill_attention_ref,
                               ragged_paged_prefill_attention_ref)
from repro.models.attention import (ragged_chunk_attention_xla,
                                    update_paged_cache_ragged)
from repro.serving.engine import pack_ragged, unpack_ragged
from repro.serving.kv_cache import BlockManager
from repro.serving.scheduler import Request, Scheduler

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# Ragged packed-prefill kernel vs oracle
# ---------------------------------------------------------------------------


def _ragged_case(S, H, K, hd, bs, nblk, dtype, lens, pad=0):
    """Random pools + disjoint per-seq tables + a packed flat chunk batch:
    sequence i owns flat rows [starts[i], ends[i]) of length lens[i]; the
    trailing ``pad`` rows belong to nobody. ctx counts the chunk itself."""
    assert len(lens) == S
    T = int(sum(lens)) + pad
    N = 1 + S * nblk
    q = jnp.asarray(RNG.normal(0, 1, (T, H, hd)), jnp.float32).astype(dtype)
    kp = jnp.asarray(RNG.normal(0, 1, (N, bs, K, hd)).transpose(0, 2, 1, 3),
                     jnp.float32).astype(dtype)
    vp = jnp.asarray(RNG.normal(0, 1, (N, bs, K, hd)).transpose(0, 2, 1, 3),
                     jnp.float32).astype(dtype)
    perm = RNG.permutation(np.arange(1, N))[:S * nblk].reshape(S, nblk)
    bt = jnp.asarray(perm, jnp.int32)
    starts = np.zeros(S, np.int32)
    ends = np.zeros(S, np.int32)
    row_seq = np.zeros(T, np.int32)
    off = 0
    for i, L in enumerate(lens):
        starts[i], ends[i] = off, off + L
        row_seq[off:off + L] = i
        off += L
    ctx = np.array([L + RNG.integers(0, nblk * bs - L + 1) if L else 0
                    for L in lens], np.int32)
    return (q, kp, vp, bt, jnp.asarray(ctx), jnp.asarray(starts),
            jnp.asarray(ends), jnp.asarray(row_seq))


RAGGED_CASES = [
    # S, H, K, hd, block_size, blocks_per_seq, lens, pad, window, cap, dtype
    (3, 4, 2, 16, 8, 4, (5, 3, 8), 2, None, None, jnp.float32),   # GQA + pad
    (2, 6, 6, 16, 8, 5, (7, 9), 0, 12, None, jnp.float32),        # MHA + win
    (3, 8, 1, 64, 8, 4, (1, 8, 4), 3, None, 50.0, jnp.bfloat16),  # MQA + cap
    (4, 4, 2, 32, 16, 3, (16, 0, 5, 11), 4, 8, 30.0, jnp.bfloat16),
    # ^ empty pack slot (starts == ends) + window + cap + pad rows
]


@pytest.mark.parametrize("case", RAGGED_CASES)
def test_ragged_kernel_vs_ref(case):
    S, H, K, hd, bs, nblk, lens, pad, window, cap, dt = case
    q, kp, vp, bt, ctx, starts, ends, row_seq = _ragged_case(
        S, H, K, hd, bs, nblk, dt, lens, pad)
    o_k = ragged_paged_prefill_attention(q, kp, vp, bt, ctx, starts, ends,
                                         window=window, cap=cap,
                                         interpret=True)
    o_r = ragged_paged_prefill_attention_ref(q, kp, vp, bt, ctx, starts,
                                             ends, row_seq, window=window,
                                             cap=cap)
    tol = 1e-2 if dt == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=tol)
    if pad:                               # rows owned by nobody: exact zeros
        assert np.all(np.asarray(o_k)[sum(lens):] == 0)
        assert np.all(np.asarray(o_r)[sum(lens):] == 0)
    assert np.all(np.isfinite(np.asarray(o_k, np.float32)))


@pytest.mark.parametrize("case", RAGGED_CASES)
def test_ragged_xla_path_vs_ref(case):
    """The pure-XLA packed path (dense gather + the single-chunk
    ``paged_chunk_attention_xla``) agrees with the flat oracle."""
    S, H, K, hd, bs, nblk, lens, pad, window, cap, dt = case
    q, kp, vp, bt, ctx, starts, ends, row_seq = _ragged_case(
        S, H, K, hd, bs, nblk, dt, lens, pad)
    o_x = ragged_chunk_attention_xla(q, kp, vp, bt, ctx, starts, ends,
                                     row_seq, window=window, cap=cap)
    o_r = ragged_paged_prefill_attention_ref(q, kp, vp, bt, ctx, starts,
                                             ends, row_seq, window=window,
                                             cap=cap)
    tol = 1e-2 if dt == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o_x, np.float32),
                               np.asarray(o_r, np.float32), atol=tol)
    if pad:
        assert np.all(np.asarray(o_x)[sum(lens):] == 0)


def test_ragged_kernel_single_seq_matches_chunk_kernel():
    """S == 1 with starts = [0] is exactly the single-chunk prefill kernel
    (same streaming-softmax math, flat vs batched layout)."""
    H, K, hd, bs, nblk, C = 4, 2, 16, 8, 4, 12
    q, kp, vp, bt, ctx, starts, ends, _ = _ragged_case(
        1, H, K, hd, bs, nblk, jnp.float32, (C,), 0)
    o_ragged = ragged_paged_prefill_attention(q, kp, vp, bt, ctx, starts,
                                              ends, interpret=True)
    o_chunk = paged_prefill_attention(q[None], kp, vp, bt, ctx,
                                      jnp.asarray([C], jnp.int32),
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(o_ragged),
                                  np.asarray(o_chunk)[0])


def test_ragged_fused_write_matches_separate_scatter():
    """The fused-KV-write kernel (chunk K/V merged into the visited pages
    through aliased pool outputs) produces the same pool bytes as the
    separate ``update_paged_cache_ragged`` scatter, and its attention
    output matches the oracle run on the updated pools. Trash row 0 is
    excluded: the XLA scatter parks padding rows there, the kernel just
    redirects dead table entries to it."""
    S, H, K, hd, bs, nblk = 3, 4, 2, 16, 8, 4
    lens, pad = (5, 3, 8), 2
    q, kp, vp, bt, ctx, starts, ends, row_seq = _ragged_case(
        S, H, K, hd, bs, nblk, jnp.float32, lens, pad)
    T = q.shape[0]
    k_new = jnp.asarray(RNG.normal(0, 1, (T, K, hd)), jnp.float32)
    v_new = jnp.asarray(RNG.normal(0, 1, (T, K, hd)), jnp.float32)
    o_f, kp_f, vp_f = ragged_paged_prefill_attention(
        q, kp, vp, bt, ctx, starts, ends, k_new=k_new, v_new=v_new,
        interpret=True)
    kc = update_paged_cache_ragged(kp, k_new[None], bt, ctx, starts, ends,
                                   row_seq)
    vc = update_paged_cache_ragged(vp, v_new[None], bt, ctx, starts, ends,
                                   row_seq)
    np.testing.assert_array_equal(np.asarray(kp_f)[1:], np.asarray(kc)[1:])
    np.testing.assert_array_equal(np.asarray(vp_f)[1:], np.asarray(vc)[1:])
    o_r = ragged_paged_prefill_attention_ref(q, kc, vc, bt, ctx, starts,
                                             ends, row_seq)
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_r), atol=1e-5)
    assert np.all(np.asarray(o_f)[sum(lens):] == 0)


# ---------------------------------------------------------------------------
# Multi-page fetch (pages_per_compute_block)
# ---------------------------------------------------------------------------


def _paged_case(B, H, K, hd, bs, nblk, dtype):
    N = 1 + B * nblk
    q = jnp.asarray(RNG.normal(0, 1, (B, H, hd)), jnp.float32).astype(dtype)
    kp = jnp.asarray(RNG.normal(0, 1, (N, bs, K, hd)).transpose(0, 2, 1, 3),
                     jnp.float32).astype(dtype)
    vp = jnp.asarray(RNG.normal(0, 1, (N, bs, K, hd)).transpose(0, 2, 1, 3),
                     jnp.float32).astype(dtype)
    perm = RNG.permutation(np.arange(1, N))[:B * nblk].reshape(B, nblk)
    bt = jnp.asarray(perm, jnp.int32)
    ctx = jnp.asarray(RNG.integers(1, nblk * bs + 1, (B,)), jnp.int32)
    return q, kp, vp, bt, ctx


@pytest.mark.parametrize("P", [2, 3])
@pytest.mark.parametrize("window,cap", [(None, None), (12, 50.0)])
def test_decode_kernel_multipage_vs_ref(P, window, cap):
    """P pages per grid step (non-divisible P included: 5 blocks / P=2|3
    leaves a partially-dead last tile) matches the single-page oracle."""
    B, H, K, hd, bs, nblk = 3, 4, 2, 16, 8, 5
    q, kp, vp, bt, ctx = _paged_case(B, H, K, hd, bs, nblk, jnp.float32)
    o_k = paged_attention(q, kp, vp, bt, ctx, window=window, cap=cap,
                          interpret=True, pages_per_compute_block=P)
    o_r = paged_attention_ref(q, kp, vp, bt, ctx, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-5)


@pytest.mark.parametrize("P", [2, 3])
def test_prefill_kernel_multipage_vs_ref(P):
    B, H, K, hd, bs, nblk, C = 2, 6, 2, 16, 8, 5, 20
    N = 1 + B * nblk
    q = jnp.asarray(RNG.normal(0, 1, (B, C, H, hd)), jnp.float32)
    kp = jnp.asarray(RNG.normal(0, 1, (N, bs, K, hd)).transpose(0, 2, 1, 3),
                     jnp.float32)
    vp = jnp.asarray(RNG.normal(0, 1, (N, bs, K, hd)).transpose(0, 2, 1, 3),
                     jnp.float32)
    perm = RNG.permutation(np.arange(1, N))[:B * nblk].reshape(B, nblk)
    bt = jnp.asarray(perm, jnp.int32)
    qlen = np.array([C, C // 2])
    ctx = np.array([RNG.integers(ql, nblk * bs + 1) for ql in qlen])
    o_k = paged_prefill_attention(q, kp, vp, bt,
                                  jnp.asarray(ctx, jnp.int32),
                                  jnp.asarray(qlen, jnp.int32), window=12,
                                  interpret=True, pages_per_compute_block=P)
    o_r = paged_prefill_attention_ref(q, kp, vp, bt,
                                      jnp.asarray(ctx, jnp.int32),
                                      jnp.asarray(qlen, jnp.int32),
                                      window=12)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-5)


def test_decode_kernel_multipage_block_mask_lse():
    """The P knob composes with the pool-sharded partial-softmax path:
    masked table entries stay skipped inside multi-page tiles and the
    returned LSE matches the partial oracle."""
    B, H, K, hd, bs, nblk = 2, 4, 2, 16, 8, 4
    q, kp, vp, bt, ctx = _paged_case(B, H, K, hd, bs, nblk, jnp.float32)
    mask = jnp.asarray(RNG.integers(0, 2, (B, nblk)), jnp.int32)
    mask = mask.at[:, 0].set(1)            # keep at least one live block
    o_k, lse_k = paged_attention(q, kp, vp, bt, ctx, block_mask=mask,
                                 return_lse=True, interpret=True,
                                 pages_per_compute_block=2)
    o_r, lse_r = paged_attention_partial_ref(q, kp, vp, bt, ctx, mask)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse_k), np.asarray(lse_r),
                               atol=1e-5)


def test_decode_kernel_multipage_clamps_to_table_width():
    """P larger than the table is clamped, not an error."""
    B, H, K, hd, bs, nblk = 2, 4, 2, 16, 8, 3
    q, kp, vp, bt, ctx = _paged_case(B, H, K, hd, bs, nblk, jnp.float32)
    o_k = paged_attention(q, kp, vp, bt, ctx, interpret=True,
                          pages_per_compute_block=16)
    o_r = paged_attention_ref(q, kp, vp, bt, ctx)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-5)


# ---------------------------------------------------------------------------
# pack_ragged / unpack_ragged round-trip
# ---------------------------------------------------------------------------


def test_pack_unpack_roundtrip_basic():
    rows = [np.array([3, 1, 4], np.int32), np.array([1], np.int32),
            np.array([5, 9, 2, 6], np.int32)]
    tok, seq, starts, ends = pack_ragged(rows, width=10, max_seqs=4)
    assert tok.shape == (10,) and starts.shape == (4,)
    back = unpack_ragged(tok, starts, ends, 3)
    for r, b in zip(rows, back):
        np.testing.assert_array_equal(r, b)
    np.testing.assert_array_equal(seq[:8], [0, 0, 0, 1, 2, 2, 2, 2])
    assert starts[3] == ends[3] == 0       # unused slot marks empty range


def test_pack_unpack_roundtrip_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(st.data())
    @hyp.settings(max_examples=80, deadline=None)
    def prop(data):
        max_seqs = data.draw(st.integers(1, 6))
        n = data.draw(st.integers(0, max_seqs))
        lens = [data.draw(st.integers(0, 8)) for _ in range(n)]
        width = sum(lens) + data.draw(st.integers(0, 5))
        width = max(width, 1)
        rows = [np.arange(L, dtype=np.int32) + 100 * i
                for i, L in enumerate(lens)]
        tok, seq, starts, ends = pack_ragged(rows, width, max_seqs)
        back = unpack_ragged(tok, starts, ends, n)
        assert len(back) == n
        for r, b in zip(rows, back):
            np.testing.assert_array_equal(r, b)
        # layout invariants the kernel's ownership masks rely on:
        # back-to-back packing, owner id per flat position, pad rows
        # outside every [start, end) range
        off = 0
        for i, L in enumerate(lens):
            assert starts[i] == off and ends[i] == off + L
            assert (seq[off:off + L] == i).all()
            off += L
        assert (seq[off:] == 0).all() and (tok[off:] == 0).all()

    prop()


# ---------------------------------------------------------------------------
# Scheduler: multi-chunk plans
# ---------------------------------------------------------------------------


def _req(n_prompt=8, max_new=4, **kw):
    return Request(np.arange(n_prompt, dtype=np.int32), max_new=max_new,
                   **kw)


def _sched(bm, max_batch=4, max_blocks_per_seq=8, budget=40, chunk=32, **kw):
    return Scheduler(bm, max_batch, max_blocks_per_seq, budget, chunk, **kw)


def test_scheduler_packs_multiple_prefills():
    bm = BlockManager(num_blocks=33, block_size=4)
    s = _sched(bm, budget=40, chunk=32, prefill_pack=4)
    reqs = [_req(n_prompt=n) for n in (12, 8, 6)]
    for r in reqs:
        s.add(r)
    plan = s.schedule()
    assert plan.admitted == 3
    assert [(c[1], c[2]) for c in plan.chunks] == [
        (reqs[0], 12), (reqs[1], 8), (reqs[2], 6)]
    assert plan.chunk == plan.chunks[0]     # compat accessor
    assert plan.scheduled_tokens == 26 <= 40


def test_scheduler_pack_shares_one_budget():
    """Chunks are funded by ONE leftover budget, in FCFS order; a request
    that doesn't fit this step gets the next step's budget."""
    bm = BlockManager(num_blocks=33, block_size=4)
    s = _sched(bm, budget=20, chunk=32, prefill_pack=4)
    reqs = [_req(n_prompt=n) for n in (12, 8, 6)]
    for r in reqs:
        s.add(r)
    p1 = s.schedule()
    assert [(c[1], c[2]) for c in p1.chunks] == [(reqs[0], 12), (reqs[1], 8)]
    for _, r, n in p1.chunks:
        r.num_computed += n
        r.out.append(7)
    p2 = s.schedule()                       # 2 decodes + the deferred chunk
    assert len(p2.decodes) == 2
    assert [(c[1], c[2]) for c in p2.chunks] == [(reqs[2], 6)]


def test_scheduler_pack_shares_chunk_width():
    """The packed flat batch is one compiled buffer: chunks also share the
    chunk_width allowance."""
    bm = BlockManager(num_blocks=33, block_size=4)
    s = _sched(bm, budget=40, chunk=16, prefill_pack=4)
    reqs = [_req(n_prompt=n) for n in (12, 8, 6)]
    for r in reqs:
        s.add(r)
    plan = s.schedule()
    assert [(c[1], c[2]) for c in plan.chunks] == [(reqs[0], 12),
                                                  (reqs[1], 4)]


def test_scheduler_pack_one_is_single_chunk():
    """prefill_pack=1 (the default) never plans more than one chunk — the
    old single-chunk contract."""
    bm = BlockManager(num_blocks=33, block_size=4)
    s = _sched(bm, budget=40, chunk=32)     # default pack
    assert s.prefill_pack == 1
    for n in (12, 8, 6):
        s.add(_req(n_prompt=n))
    while s.has_work:
        plan = s.schedule()
        assert len(plan.chunks) <= 1
        for _, r, n in plan.chunks:
            r.num_computed += n
            if r.num_computed == r.context_len:
                r.out.append(7)
        for _, r in plan.decodes:
            r.out.append(7)
        for slot, r in list(s.running.items()):
            if r.done:
                s.retire(slot)


def test_scheduler_pack_rejects_zero():
    with pytest.raises(ValueError):
        _sched(BlockManager(num_blocks=9, block_size=4), prefill_pack=0)


def test_scheduler_quantum_remainder_rolls_and_counts():
    """With a chunk quantum, a chunk's rounded-off remainder stays in the
    shared budget (funding the NEXT chunk) instead of evaporating; only
    the final chunk's loss is unrecoverable and lands in
    ``quantum_dropped_tokens``."""
    bm = BlockManager(num_blocks=33, block_size=4)
    s = _sched(bm, budget=23, chunk=32, prefill_pack=4, chunk_quantum=4)
    reqs = [_req(n_prompt=n, max_new=2) for n in (10, 10)]
    for r in reqs:
        s.add(r)
    p1 = s.schedule()
    # req0: want min(23, 32, 10) = 10 = remaining -> final chunk, exempt
    # req1: want min(13, 22, 10) = 10 -> final too: both run whole
    assert [(c[1], c[2]) for c in p1.chunks] == [(reqs[0], 10), (reqs[1], 10)]
    assert s.quantum_dropped_tokens == 0

    s2 = _sched(bm, budget=23, chunk=32, prefill_pack=4, chunk_quantum=4)
    reqs2 = [_req(n_prompt=n, max_new=2) for n in (14, 14)]
    for r in reqs2:
        s2.add(r)
    p = s2.schedule()
    # req0: want 14 = remaining, final, takes 14; req1: want min(9, 18, 14)
    # = 9, non-final -> quantized to 8, ONE token dropped and counted
    assert [(c[1], c[2]) for c in p.chunks] == [(reqs2[0], 14), (reqs2[1], 8)]
    assert s2.quantum_dropped_tokens == 1


# ---------------------------------------------------------------------------
# Engine: packed prefill is byte-identical to single-chunk serving
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def glm_params(tiny_mesh):
    from repro.models import api
    cfg = get_config("glm4_9b", smoke=True)
    with jax.set_mesh(tiny_mesh):
        params_f32, _ = api.init_model(cfg, jax.random.key(0))
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params_f32)
    return cfg, params


def test_engine_packed_prefill_matches_unpacked(tiny_mesh, glm_params):
    """A burst of short prompts: prefill_pack=4 packs several prompts into
    each step (fewer steps) with byte-identical greedy outputs."""
    from repro.serving import InferenceEngine, Request
    cfg, params = glm_params
    prompts = [RNG.integers(0, cfg.vocab_size, 24).astype(np.int32)
               for _ in range(6)]
    kw = dict(max_batch=8, block_size=16, max_len=96,
              max_num_batched_tokens=8 + 48, params=params,
              debug_invariants=True)
    plain = InferenceEngine(cfg, tiny_mesh, **kw)
    reqs_p = [Request(p.copy(), max_new=6) for p in prompts]
    want = plain.run(reqs_p, arrival_steps=[0] * 6)
    packed = InferenceEngine(cfg, tiny_mesh, prefill_pack=4, **kw)
    assert packed.prefill_pack == 4
    reqs_k = [Request(p.copy(), max_new=6) for p in prompts]
    got = packed.run(reqs_k, arrival_steps=[0] * 6)
    for a, b in zip(reqs_p, reqs_k):
        np.testing.assert_array_equal(want[a.rid], got[b.rid])
    # two 24-token chunks fit the 48-wide packed buffer per step
    assert packed.stats["steps"] < plain.stats["steps"]
    assert packed.stats["prefill_chunks"] == plain.stats["prefill_chunks"]


def test_engine_packed_prefix_cache_hits_match(tiny_mesh, glm_params):
    """Prefix-cache adoption under packing: staggered requests sharing a
    prompt adopt published blocks mid-pack, outputs stay identical."""
    from repro.serving import InferenceEngine, Request
    cfg, params = glm_params
    prompt = RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
    kw = dict(max_batch=4, block_size=16, max_len=96, params=params,
              debug_invariants=True)
    plain = InferenceEngine(cfg, tiny_mesh, **kw)
    reqs_p = [Request(prompt.copy(), max_new=6) for _ in range(3)]
    want = plain.run(reqs_p, arrival_steps=[0, 2, 4])
    packed = InferenceEngine(cfg, tiny_mesh, prefill_pack=4, **kw)
    reqs_k = [Request(prompt.copy(), max_new=6) for _ in range(3)]
    got = packed.run(reqs_k, arrival_steps=[0, 2, 4])
    assert packed.stats["cache_hit_tokens"] > 0
    for a, b in zip(reqs_p, reqs_k):
        np.testing.assert_array_equal(want[a.rid], got[b.rid])


def test_engine_packed_preemption_matches(tiny_mesh, glm_params):
    """Recompute-preemption with packing on: the re-admitted victim's
    recompute chunk rides a packed batch; outputs match the unconstrained
    single-chunk engine byte for byte."""
    from repro.serving import InferenceEngine, Request
    cfg, params = glm_params
    prompts = [RNG.integers(0, cfg.vocab_size, 32).astype(np.int32)
               for _ in range(2)]
    base = InferenceEngine(cfg, tiny_mesh, max_batch=2, block_size=16,
                           max_len=96, params=params, debug_invariants=True)
    want = base.run([Request(p.copy(), max_new=20) for p in prompts])
    want = list(want.values())
    tight = InferenceEngine(cfg, tiny_mesh, max_batch=2, block_size=16,
                            max_len=96, num_blocks=8, params=params,
                            prefill_pack=4, debug_invariants=True)
    reqs = [Request(p.copy(), max_new=20) for p in prompts]
    got = tight.run(reqs)
    assert tight.stats["preemptions"] >= 1
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(got[r.rid], w)


def test_engine_packed_speculative_matches(tiny_mesh):
    """Speculative decoding (k=2, self-draft) with packed prefill: both
    the draft and target prefill the packed batch; greedy outputs equal
    the single-chunk speculative engine byte for byte."""
    from repro.models import api
    from repro.serving import InferenceEngine, Request, SpeculativeRunner
    cfg = get_config("starcoder2_3b", smoke=True)
    with jax.set_mesh(tiny_mesh):
        params_f32, _ = api.init_model(cfg, jax.random.key(0))
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params_f32)
    prompts = [RNG.integers(0, cfg.vocab_size, 24).astype(np.int32)
               for _ in range(4)]
    kw = dict(max_batch=4, block_size=16, max_len=96, params=params,
              num_speculative_tokens=2, draft_params=params,
              debug_invariants=True)
    plain = InferenceEngine(cfg, tiny_mesh, **kw)
    reqs_p = [Request(p.copy(), max_new=8) for p in prompts]
    want = plain.run(reqs_p, arrival_steps=[0] * 4)
    packed = InferenceEngine(cfg, tiny_mesh, prefill_pack=4, **kw)
    assert isinstance(packed.runner, SpeculativeRunner)
    assert packed.prefill_pack == 4
    reqs_k = [Request(p.copy(), max_new=8) for p in prompts]
    got = packed.run(reqs_k, arrival_steps=[0] * 4)
    for a, b in zip(reqs_p, reqs_k):
        np.testing.assert_array_equal(want[a.rid], got[b.rid])
    assert packed.stats["spec_decodes"] >= 1


def test_engine_packed_forced_off_for_unsupported_runner(tiny_mesh):
    """Runners without a ragged prefill path (SSM) silently fall back to
    single-chunk plans instead of crashing."""
    from repro.serving import InferenceEngine
    cfg = get_config("mamba2_370m", smoke=True)
    eng = InferenceEngine(cfg, tiny_mesh, max_batch=2, block_size=16,
                          max_len=96, prefill_pack=4)
    assert eng.prefill_pack == 1
    assert eng.sched.prefill_pack == 1


# ---------------------------------------------------------------------------
# Front-end: dropped-stream counter surfaces in /metrics
# ---------------------------------------------------------------------------


def test_dropped_streams_metric_renders(tiny_mesh, glm_params):
    from repro.serving import InferenceEngine
    from repro.serving.frontend import AsyncEngineDriver
    from repro.serving.frontend.metrics import render_metrics
    cfg, params = glm_params
    eng = InferenceEngine(cfg, tiny_mesh, max_batch=2, block_size=16,
                          max_len=96, params=params)
    drv = AsyncEngineDriver(eng)
    assert "repro_frontend_dropped_streams_total 0" in render_metrics(
        eng, drv)
    drv.dropped_streams += 1            # what http.py does on SSE reset
    text = render_metrics(eng, drv)
    assert "repro_frontend_dropped_streams_total 1" in text
    assert "repro_engine_quantum_dropped_tokens_total 0" in text
