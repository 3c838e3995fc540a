"""Tensor-parallel paged serving tests (docs/multi-host.md).

Three layers of proof that sharding the serving engine over the mesh
"model" axis is a pure placement change:

* **Stitch math** — the partial-softmax / LSE-stitch path of the paged
  kernels (``block_mask`` + ``return_lse``) reproduces the dense
  reference for every shard count and head-count shape, including the
  Pallas kernels in interpret mode, plus the explicit error path when
  kv heads don't divide the mesh.
* **Host metadata mesh-invariance** — the BlockManager / SlotStateCache
  random walks re-run under different mesh-model parameters and their
  full state traces must be identical (the managers never see the mesh;
  only per-shard byte accounting divides).
* **Engine byte-identity** — subprocess tests on a forced 4-device host:
  greedy engine outputs (prefix-cache hits + COW, preemption-recompute,
  speculative k=2, hybrid SSM and enc-dec runners) on model=2 and
  model=4 meshes are byte-identical to the single-device engine, with
  identical scheduling stats.
"""

import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_with_devices
from repro.config import get_config
from repro.kernels.paged_attention import (paged_attention,
                                           paged_prefill_attention)
from repro.kernels.ref import (paged_attention_partial_ref,
                               paged_attention_ref,
                               paged_prefill_attention_ref,
                               paged_shard_attention_ref)
from repro.models.attention import paged_shard_attention, \
    stitch_paged_partials
from repro.serving.kv_cache import BlockManager, block_bytes
from repro.spmd.sharding import (paged_pool_pspec, serving_cache_pspec,
                                 serving_cache_shardings, serving_tp)

RNG = np.random.default_rng(7)


def _case(B, H, K, hd, bs, nblk, dtype=jnp.float32):
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


# ---------------------------------------------------------------------------
# Partial-softmax / LSE-stitch math
# ---------------------------------------------------------------------------


# head-count shapes: GQA, MHA (G=1), MQA (K=1), deeper GQA
HEAD_CASES = [
    # B, H, K, hd, block_size, blocks_per_seq, window, cap
    (3, 4, 2, 16, 8, 4, None, None),
    (2, 6, 6, 16, 8, 5, 12, None),        # MHA + sliding window
    (2, 8, 1, 32, 8, 4, None, 50.0),      # MQA + softcap
    (2, 8, 2, 16, 16, 3, None, None),
]


def test_partial_ref_full_mask_is_exact():
    """A full mask makes the partial oracle the plain oracle bit for bit
    (same op order) — the stitch path is a strict generalization."""
    q, kp, vp, bt, ctx = _case(3, 4, 2, 16, 8, 4)
    o, lse = paged_attention_partial_ref(
        q, kp, vp, bt, ctx, jnp.ones(bt.shape, bool))
    o_r = paged_attention_ref(q, kp, vp, bt, ctx)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_r))
    assert np.all(np.asarray(lse) > -1e29)     # every row attended something


@pytest.mark.parametrize("case", HEAD_CASES)
@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
def test_shard_oracle_matches_plain_ref(case, n_shards):
    B, H, K, hd, bs, nblk, window, cap = case
    q, kp, vp, bt, ctx = _case(B, H, K, hd, bs, nblk)
    o_s = paged_shard_attention_ref(q, kp, vp, bt, ctx, n_shards,
                                    window=window, cap=cap)
    o_r = paged_attention_ref(q, kp, vp, bt, ctx, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_r), atol=1e-5)


@pytest.mark.parametrize("case", HEAD_CASES)
def test_production_stitch_matches_oracle(case):
    """kops partial kernel + ``stitch_paged_partials`` == the ref oracle
    == the plain path (the production blocks-axis-sharded route)."""
    B, H, K, hd, bs, nblk, window, cap = case
    q, kp, vp, bt, ctx = _case(B, H, K, hd, bs, nblk)
    o_p = paged_shard_attention(q, kp, vp, bt, ctx, 3, window=window,
                                cap=cap)
    o_r = paged_attention_ref(q, kp, vp, bt, ctx, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r), atol=1e-5)


def test_partials_stay_fp32_for_bf16_pools():
    """Regression: partial outputs must come back fp32 even when the
    pools/queries are bf16 — rounding each shard's o to bf16 before the
    stitch would make the stitched result depend on the shard count."""
    q, kp, vp, bt, ctx = _case(2, 4, 2, 16, 8, 4, dtype=jnp.bfloat16)
    o, lse = paged_attention(q, kp, vp, bt, ctx, interpret=True,
                             block_mask=jnp.ones(bt.shape, jnp.int32),
                             return_lse=True)
    assert o.dtype == jnp.float32 and lse.dtype == jnp.float32
    from repro.kernels import ops as kops
    o2, lse2 = kops.paged_attention_partial(
        q, kp, vp, bt, ctx, jnp.ones(bt.shape, bool))
    assert o2.dtype == jnp.float32 and lse2.dtype == jnp.float32
    # a 1-shard "stitch" is exactly the plain path (w = 1, den = 1)
    o_plain = paged_attention_ref(q, kp, vp, bt, ctx)
    np.testing.assert_array_equal(
        np.asarray(paged_shard_attention(q, kp, vp, bt, ctx, 1)),
        np.asarray(o_plain))
    # multi-shard stitches agree with the plain bf16 path to bf16 ulp
    for s in (2, 3):
        np.testing.assert_allclose(
            np.asarray(paged_shard_attention(q, kp, vp, bt, ctx, s),
                       np.float32),
            np.asarray(o_plain, np.float32), atol=2e-2)


def test_pallas_partial_matches_partial_ref():
    """Interpret-mode Pallas decode kernel with a shard-local mask returns
    the same (o, lse) as the oracle; skipped entries are never read."""
    q, kp, vp, bt, ctx = _case(3, 4, 2, 16, 8, 4)
    for seed in range(4):
        mask = jnp.asarray(
            np.random.default_rng(seed).integers(0, 2, bt.shape), jnp.int32)
        o_k, lse_k = paged_attention(q, kp, vp, bt, ctx, block_mask=mask,
                                     return_lse=True, interpret=True)
        o_r, lse_r = paged_attention_partial_ref(q, kp, vp, bt, ctx, mask)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   atol=1e-5)
        lk, lr = np.asarray(lse_k), np.asarray(lse_r)
        live = lr > -1e29
        np.testing.assert_allclose(lk[live], lr[live], atol=1e-5)
        assert np.all(lk[~live] < -1e29)       # empty rows: zero weight


def test_pallas_partial_random_partition_stitches_exact():
    """Property: ANY partition of the table entries over shards stitches
    back to the plain answer (not just round-robin) — seeded sweep."""
    B, H, K, hd, bs, nblk = 2, 4, 2, 16, 8, 5
    q, kp, vp, bt, ctx = _case(B, H, K, hd, bs, nblk)
    o_full = np.asarray(paged_attention_ref(q, kp, vp, bt, ctx))
    for seed in range(6):
        rng = np.random.default_rng(100 + seed)
        n_shards = int(rng.integers(2, 5))
        owner = rng.integers(0, n_shards, (B, nblk))
        parts = [paged_attention_partial_ref(
            q, kp, vp, bt, ctx, jnp.asarray(owner == s))
            for s in range(n_shards)]
        o = stitch_paged_partials(jnp.stack([p[0] for p in parts]),
                                  jnp.stack([p[1] for p in parts]))
        np.testing.assert_allclose(np.asarray(o), o_full, atol=1e-5)


def test_chunk_kernel_partial_path():
    """The multi-query kernel's partial path: a full mask reproduces the
    plain chunk kernel exactly; a 2-way split of the *context-only* blocks
    stitches back to it (the chunk's own keys live in unmasked blocks)."""
    B, H, K, hd, bs, nblk, C = 2, 4, 2, 16, 8, 4, 8
    q = jnp.asarray(RNG.normal(0, 1, (B, C, H, hd)), jnp.float32)
    _, kp, vp, bt, _ = _case(B, H, K, hd, bs, nblk)
    qlen = jnp.asarray([C, 3], jnp.int32)
    ctx = jnp.asarray([24, 11], jnp.int32)
    o_plain = paged_prefill_attention(q, kp, vp, bt, ctx, qlen,
                                      interpret=True)
    o_f, lse_f = paged_prefill_attention(q, kp, vp, bt, ctx, qlen,
                                         block_mask=jnp.ones(bt.shape,
                                                             jnp.int32),
                                         return_lse=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(o_f), np.asarray(o_plain))
    lse = np.asarray(lse_f)
    assert np.all(lse[0] > -1e29)              # full row attended
    assert np.all(lse[1, 3:] < -1e29)          # padding rows: empty
    entry = np.arange(nblk)[None, :]
    parts = [paged_prefill_attention(
        q, kp, vp, bt, ctx, qlen,
        block_mask=jnp.asarray(entry % 2 == s), return_lse=True,
        interpret=True) for s in range(2)]
    o = stitch_paged_partials(
        jnp.stack([p[0].astype(jnp.float32) for p in parts]),
        jnp.stack([p[1] for p in parts]))
    valid = np.asarray(jnp.arange(C)[None] < qlen[:, None])
    np.testing.assert_allclose(np.asarray(o)[valid],
                               np.asarray(o_plain)[valid], atol=1e-5)


def test_chunk_ref_unchanged_by_full_mask_path():
    """Plain multi-query ref still matches the kernel after the partial
    plumbing (regression guard for the added scalar-prefetch arg)."""
    B, H, K, hd, bs, nblk, C = 2, 6, 2, 16, 8, 5, 20
    q = jnp.asarray(RNG.normal(0, 1, (B, C, H, hd)), jnp.float32)
    _, kp, vp, bt, _ = _case(B, H, K, hd, bs, nblk)
    qlen = jnp.asarray([C, 7], jnp.int32)
    ctx = jnp.asarray([32, 20], jnp.int32)
    o_k = paged_prefill_attention(q, kp, vp, bt, ctx, qlen, interpret=True)
    o_r = paged_prefill_attention_ref(q, kp, vp, bt, ctx, qlen)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-5)


# ---------------------------------------------------------------------------
# Sharding specs: the kv-head layout and its error path
# ---------------------------------------------------------------------------


def test_paged_pool_pspec_and_error_path():
    from jax.sharding import PartitionSpec as P
    assert paged_pool_pspec(4, 1) == P(None, None, None, None, None)
    assert paged_pool_pspec(4, 2) == P(None, None, "model", None, None)
    assert paged_pool_pspec(4, 4) == P(None, None, "model", None, None)
    for K, tp in ((2, 4), (3, 2), (1, 2), (6, 4)):
        with pytest.raises(ValueError, match="not divisible"):
            paged_pool_pspec(K, tp)


def test_shard_oracle_rejects_bad_shard_count():
    q, kp, vp, bt, ctx = _case(2, 4, 2, 16, 8, 3)
    with pytest.raises(ValueError, match="n_shards"):
        paged_shard_attention_ref(q, kp, vp, bt, ctx, 0)
    with pytest.raises(ValueError, match="n_shards"):
        paged_shard_attention(q, kp, vp, bt, ctx, -1)


def test_serving_cache_pspec_by_leaf_kind():
    """Pool / encoder leaves shard by kv head; indivisible head counts
    fall back to replicated storage (the hard error for paged kinds lives
    in paged_pool_pspec / engine construction); Mamba slot-state tuples
    stay replicated — storing recurrent state sharded lets GSPMD
    repartition the SSD scan's contractions, which would cost the engine
    its bitwise mesh-invariance (see serving_cache_pspec docstring)."""
    from jax.sharding import PartitionSpec as P
    from jax.tree_util import DictKey, SequenceKey
    pool = jnp.zeros((2, 9, 4, 8, 16))          # head-major pages
    enc = jnp.zeros((2, 4, 15, 4, 16))
    state = jnp.zeros((2, 4, 8, 16, 8))
    tail = jnp.zeros((2, 4, 3, 24))
    kpath = (DictKey("sub0"), DictKey("k"))
    assert serving_cache_pspec(kpath, pool, 2) \
        == P(None, None, "model", None, None)
    assert serving_cache_pspec((DictKey("cross"), DictKey("xk")), enc, 2) \
        == P(None, None, None, "model", None)
    # kv heads (4) don't divide tp=3: replicated storage
    assert serving_cache_pspec(kpath, pool, 3) == P(None, None, None,
                                                    None, None)
    assert serving_cache_pspec((DictKey("sub1"), SequenceKey(1)),
                               state, 2) == P()
    assert serving_cache_pspec((DictKey("sub1"), SequenceKey(0)), tail, 2) \
        == P()
    assert serving_cache_pspec(kpath, pool, 1) == P()


def test_serving_tp_and_cache_shardings_on_host_mesh(tiny_mesh):
    """On the 1x1 host mesh everything resolves to replicated and the
    shardings tree is well-formed for a real runner cache."""
    from repro.config import ParallelConfig
    from repro.serving.runners import make_runner
    assert serving_tp(tiny_mesh) == 1
    assert serving_tp(None) == 1
    cfg = get_config("zamba2_2p7b", smoke=True)
    runner = make_runner(cfg, ParallelConfig(remat="none"))
    with jax.set_mesh(tiny_mesh):
        cache = runner.init_cache(9, 16, 2)
    sh = serving_cache_shardings(cache, tiny_mesh)
    assert jax.tree.structure(sh) == jax.tree.structure(cache)


# ---------------------------------------------------------------------------
# Host-side metadata is mesh-invariant (random walks x mesh shape)
# ---------------------------------------------------------------------------


def _bm_walk_trace(seed: int, mesh_model: int) -> list:
    """Run a seeded BlockManager walk and capture the full host-visible
    state after every op. ``mesh_model`` enters exactly the way it does in
    the engine — per-shard byte accounting and pool specs — and must not
    perturb one bit of the manager's state: block ids are global (pools
    shard by kv head, not by block), so tables/refcounts/hashes/free
    lists are identical on every mesh. The trace equality across
    mesh_model values pins that, and would catch anyone threading the
    mesh into the manager."""
    cfg = dataclasses.replace(get_config("glm4_9b", smoke=True),
                              num_kv_heads=4)
    # mesh-parametric accounting: a block's bytes divide exactly over
    # shards, and the pool spec resolves (4 kv heads, model in {1,2,4})
    assert block_bytes(cfg, 16) == mesh_model * block_bytes(
        cfg, 16, tp=mesh_model)
    paged_pool_pspec(cfg.num_kv_heads, mesh_model)
    rng = random.Random(seed)
    NB, BS = 9, 4
    bm = BlockManager(num_blocks=NB, block_size=BS)
    live: set[int] = set()
    next_rid, next_hash = [0], [0]
    trace = []

    def snap():
        trace.append((
            {rid: tuple(bm.table(rid)) for rid in sorted(live)},
            tuple(sorted(bm._ref.items())),
            tuple(bm._free),
            tuple(sorted((b, h) for b, h in bm._hash_of.items())),
        ))

    for _ in range(150):
        op = rng.randrange(8)
        rids = sorted(live)
        if op == 0 or not rids:
            next_rid[0] += 1
            try:
                bm.allocate(next_rid[0], rng.randrange(3 * BS + 1))
                live.add(next_rid[0])
            except MemoryError:
                pass
        elif op == 1:
            rid = rids[rng.randrange(len(rids))]
            bm.ensure(rid, len(bm.table(rid)) * BS + rng.randrange(BS) + 1)
        elif op == 2:
            next_rid[0] += 1
            bm.fork(rids[rng.randrange(len(rids))], next_rid[0])
            live.add(next_rid[0])
        elif op == 3:
            rid = rids[rng.randrange(len(rids))]
            t = bm.table(rid)
            if t:
                try:
                    bm.cow(rid, rng.randrange(len(t)))
                except MemoryError:
                    pass
        elif op == 4:
            rid = rids[rng.randrange(len(rids))]
            bm.free(rid)
            live.discard(rid)
        elif op == 5:
            rid = rids[rng.randrange(len(rids))]
            t = bm.table(rid)
            if t:
                next_hash[0] += 1
                bm.register(t[rng.randrange(len(t))], next_hash[0])
        elif op == 6:
            rid = rids[rng.randrange(len(rids))]
            cover = len(bm.table(rid)) * BS
            bm.truncate(rid, rng.randrange(cover + 1) if cover else 0)
        else:
            if next_hash[0]:
                blocks = bm.match([rng.randrange(next_hash[0]) + 1])
                if blocks:
                    next_rid[0] += 1
                    bm.adopt(next_rid[0], blocks)
                    live.add(next_rid[0])
        bm.check()
        snap()
    return trace


@pytest.mark.parametrize("mesh_model", [2, 4])
def test_block_manager_walk_mesh_invariant(mesh_model):
    for seed in range(4):
        ref = _bm_walk_trace(seed, 1)
        got = _bm_walk_trace(seed, mesh_model)
        assert got == ref


def _slot_walk_trace(seed: int, mesh_model: int) -> list:
    """SlotStateCache walk under a mesh parameter: the rid<->slot binding
    never sees the mesh (slot state shards on the ssm-head axis, slots
    stay global), so the binding trace is mesh-invariant."""
    from repro.serving import SlotStateCache
    cfg = get_config("mamba2_370m", smoke=True)
    nh = cfg.ssm.n_heads(cfg.d_model)
    # the mesh-parametric piece: the state spec resolves (replicated —
    # see serving_cache_pspec) without ever touching the slot binding
    from jax.tree_util import DictKey, SequenceKey
    state = jnp.zeros((1, 4, nh, cfg.ssm.head_dim, cfg.ssm.state_dim))
    serving_cache_pspec((DictKey("sub0"), SequenceKey(1)), state,
                        mesh_model)
    rng = random.Random(seed)
    sc = SlotStateCache(4)
    bound: dict[int, int] = {}
    next_rid = [0]
    trace = []
    for _ in range(150):
        op = rng.randrange(3)
        rids = sorted(bound)
        if op == 0 or not rids:
            next_rid[0] += 1
            try:
                bound[next_rid[0]] = sc.allocate(next_rid[0])
            except MemoryError:
                pass
        elif op == 1:
            rid = rids[rng.randrange(len(rids))]
            sc.free(rid)
            del bound[rid]
        else:                                   # preempt + readmit
            rid = rids[rng.randrange(len(rids))]
            sc.free(rid)
            del bound[rid]
            next_rid[0] += 1
            bound[next_rid[0]] = sc.allocate(next_rid[0])
        sc.check()
        trace.append(tuple(sorted(sc._slot_of.items())))
    return trace


@pytest.mark.parametrize("mesh_model", [2, 4])
def test_slot_cache_walk_mesh_invariant(mesh_model):
    for seed in range(4):
        assert _slot_walk_trace(seed, mesh_model) \
            == _slot_walk_trace(seed, 1)


# ---------------------------------------------------------------------------
# Engine byte-identity across mesh shapes (subprocess, 4 virtual devices)
# ---------------------------------------------------------------------------


TP_CODE = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.config import get_config
from repro.models import api
from repro.serving import InferenceEngine, Request

def mesh_of(model):
    return jax.make_mesh((1, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

def params_for(cfg, seed=0):
    with jax.set_mesh(mesh_of(1)):
        pf32, _ = api.init_model(cfg, jax.random.key(seed))
        return jax.tree.map(
            lambda x: np.asarray(x.astype(jnp.bfloat16)), pf32)

def check(run, stat_keys):
    outs1, stats1 = run(1)
    for tp in (2, 4):
        outs, stats = run(tp)
        assert stats == stats1, (tp, stats, stats1)
        for a, b in zip(outs1, outs):
            np.testing.assert_array_equal(a, b)
    return stats1

rng = np.random.default_rng(0)
cfg = dataclasses.replace(get_config("glm4_9b", smoke=True),
                          num_kv_heads=4)
params = params_for(cfg)

# -- scenario A: shared prefix (cache hits + boundary COW), staggered ----
common = rng.integers(0, cfg.vocab_size, 32).astype(np.int32)
pa = [np.concatenate([common,
                      rng.integers(0, cfg.vocab_size, 8).astype(np.int32)])
      for _ in range(3)] + [common.copy()]          # full-prompt hit too

def run_prefix(model):
    eng = InferenceEngine(cfg, mesh_of(model), max_batch=2, block_size=16,
                          max_len=96, params=params, debug_invariants=True)
    reqs = [Request(p.copy(), max_new=8) for p in pa]
    outs = eng.run(reqs, arrival_steps=[0, 0, 2, 5])
    return [outs[r.rid] for r in reqs], {
        k: eng.stats[k] for k in ("steps", "tokens", "cache_hit_tokens",
                                  "cow_copies", "preemptions")}

s = check(run_prefix, None)
# two suffix requests hit the full 32-token common prefix; the duplicate
# full-prompt request hits all but its recomputed last token (31)
assert s["cache_hit_tokens"] >= 2 * 32 + 31, s
assert s["cow_copies"] >= 1, s
print("PREFIX-OK", s)

# -- scenario B: preemption-recompute under a tight pool -----------------
pb = [rng.integers(0, cfg.vocab_size, 32).astype(np.int32)
      for _ in range(2)]

def run_tight(model):
    eng = InferenceEngine(cfg, mesh_of(model), max_batch=2, block_size=16,
                          max_len=96, num_blocks=8, params=params,
                          debug_invariants=True)
    reqs = [Request(p.copy(), max_new=20) for p in pb]
    outs = eng.run(reqs)
    return [outs[r.rid] for r in reqs], {
        k: eng.stats[k] for k in ("steps", "tokens", "preemptions")}

s = check(run_tight, None)
assert s["preemptions"] >= 1, s
print("PREEMPT-OK", s)

# -- scenario C: speculative k=2 (self-draft params: accept > 1) ---------
scfg = dataclasses.replace(get_config("starcoder2_3b", smoke=True),
                           num_heads=8, num_kv_heads=4)
sparams = params_for(scfg)
pc = [rng.integers(0, scfg.vocab_size, 32).astype(np.int32)
      for _ in range(3)]

def run_spec(model):
    eng = InferenceEngine(scfg, mesh_of(model), max_batch=2, block_size=16,
                          max_len=96, params=sparams, draft_params=sparams,
                          num_speculative_tokens=2, debug_invariants=True)
    reqs = [Request(p.copy(), max_new=8) for p in pc]
    outs = eng.run(reqs, arrival_steps=[0, 0, 2])
    return [outs[r.rid] for r in reqs], {
        k: eng.stats[k] for k in ("steps", "tokens", "spec_decodes",
                                  "spec_emitted", "mean_accept_len")}

s = check(run_spec, None)
assert s["mean_accept_len"] > 1.0, s
print("SPEC-OK", s)

# -- error path: kv heads must divide the model axis ---------------------
try:
    InferenceEngine(get_config("glm4_9b", smoke=True),   # K = 2
                    mesh_of(4), max_batch=2, block_size=16, max_len=96)
    raise AssertionError("expected ValueError for K=2 on model=4")
except ValueError as e:
    assert "not divisible" in str(e)
print("ERRPATH-OK")
"""


def test_engine_tp_byte_identical_subprocess():
    """model=2 and model=4 engines are byte-identical to single-device —
    greedy outputs AND scheduling stats — across prefix-cache hits with
    boundary COW, preemption-recompute, and speculative k=2; and an
    indivisible kv-head count is refused at construction."""
    out = run_with_devices(TP_CODE, n_devices=4, timeout=1800)
    for tag in ("PREFIX-OK", "PREEMPT-OK", "SPEC-OK", "ERRPATH-OK"):
        assert tag in out, out


TP_FAMILY_CODE = """
import jax, jax.numpy as jnp, numpy as np
from repro.config import get_config
from repro.models import api
from repro.serving import InferenceEngine, Request

def mesh_of(model):
    return jax.make_mesh((1, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

def params_for(cfg):
    with jax.set_mesh(mesh_of(1)):
        pf32, _ = api.init_model(cfg, jax.random.key(0))
        return jax.tree.map(
            lambda x: np.asarray(x.astype(jnp.bfloat16)), pf32)

rng = np.random.default_rng(3)

# hybrid: mamba slot state (replicated — see serving_cache_pspec) +
# paged shared-attention KV sharded by kv head
zcfg = get_config("zamba2_2p7b", smoke=True)
zp = params_for(zcfg)
zprompts = [rng.integers(0, zcfg.vocab_size, 24).astype(np.int32)
            for _ in range(3)]

def run_z(model):
    eng = InferenceEngine(zcfg, mesh_of(model), max_batch=2, block_size=16,
                          max_len=96, max_num_batched_tokens=2 + 16,
                          params=zp, debug_invariants=True)
    outs = eng.run([Request(p.copy(), max_new=8) for p in zprompts],
                   arrival_steps=[0, 0, 3])
    return [outs[r] for r in sorted(outs)]

z1 = run_z(1)
for a, b in zip(z1, run_z(2)):
    np.testing.assert_array_equal(a, b)
print("HYBRID-OK")

# enc-dec: paged self-KV + per-slot cross K/V, both sharded by kv head
wcfg = get_config("whisper_large_v3", smoke=True)
wp = params_for(wcfg)
wprompts = [rng.integers(0, wcfg.vocab_size, 8).astype(np.int32)
            for _ in range(2)]
wframes = [rng.normal(0, 1, (wcfg.encoder_seq_len, wcfg.d_model)
                      ).astype(np.float32) for _ in range(2)]

def run_w(model):
    eng = InferenceEngine(wcfg, mesh_of(model), max_batch=2, block_size=16,
                          max_len=64, params=wp, debug_invariants=True)
    outs = eng.run([Request(p.copy(), max_new=6, frames=f)
                    for p, f in zip(wprompts, wframes)])
    return [outs[r] for r in sorted(outs)]

w1 = run_w(1)
for a, b in zip(w1, run_w(2)):
    np.testing.assert_array_equal(a, b)
print("ENCDEC-OK")
"""


def test_engine_tp_hybrid_and_encdec_subprocess():
    """The other cache kinds stay byte-identical under TP too: zamba2
    (hybrid: replicated slot state + sharded shared-attention pools) and
    whisper (enc-dec: sharded self-KV pools + sharded per-slot cross
    K/V) on a model=2 mesh match single-device byte for byte."""
    out = run_with_devices(TP_FAMILY_CODE, n_devices=4, timeout=1800)
    assert "HYBRID-OK" in out and "ENCDEC-OK" in out, out
