"""Continuous-batching inference engine over per-family model runners.

One ``InferenceEngine`` owns: model params, a :class:`ModelRunner` (which
declares the cache kinds it needs and builds the device cache), the host
cache managers (``BlockManager`` for paged KV, ``SlotStateCache`` /
``EncoderCache`` for constant-size per-slot state), and a ``Scheduler``.
Every iteration is **one jitted step** spending a token budget
(``max_num_batched_tokens``):

    while work:
        plan = scheduler.schedule()       # decodes (1 tok each) + one
                                          # prefill chunk, within budget
        run admission-time encode passes (enc-dec), apply COW page copies
        one jitted runner step:
            chunk: C-token slice of one prompt (attention against the
                paged cache and/or SSM state continuation), logits at its
                last token
            decode: full max_batch-wide batch, one token per running slot
            per-slot sampling over decode logits + the chunk's logits
        append sampled tokens; retire on EOS/max_new; publish content
            hashes of newly-full blocks (paged prefix cache only)

The decode half always runs at the full ``max_batch`` width — idle slots
are masked with ctx_len 0: their KV writes land in the trash block and
their slot-state rows are reverted after the step. The chunk half always
runs at the fixed ``chunk_width``. So there are exactly **two** compiled
step executables per model family (with / without a chunk) regardless of
occupancy or prompt length, plus one encode executable for enc-dec.

With speculative decoding (``num_speculative_tokens`` = k > 0, paged
transformers only) the decode half is the draft-and-verify step: k draft
proposals per slot, one k+1-wide target verify row, in-jit rejection
sampling (greedy byte-identical to plain decode), and the host appends
the accepted prefix and rewinds rejected lookahead blocks via
``BlockManager.truncate``. See docs/speculative.md.

Time is measured in engine steps; request arrivals are given in the same
unit so runs are deterministic and testable (launch/serve.py maps Poisson
arrival times onto it).
"""

from __future__ import annotations

import functools
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.config import ModelConfig, ParallelConfig
from repro.models import api, quant
from repro.serving.cache import (EncoderCache, SlotStateCache,
                                 encoder_cache_bytes, slot_state_bytes)
from repro.serving.kv_cache import (TRASH_BLOCK, BlockManager, block_bytes)
from repro.serving.runners import make_runner
from repro.serving.sampling import SamplingBuffer
from repro.serving.scheduler import (Request, SamplingParams, Scheduler,
                                     StepPlan, SwapCostModel)
from repro.serving.stats import (Histogram, SECONDS_BUCKETS, STEP_BUCKETS,
                                 StepRecord)
from repro.spmd import sharding as shd

__all__ = ["InferenceEngine", "Request", "SamplingParams"]

# oldest completed per-request latency records are dropped past this, so
# a long-running serve loop doesn't grow stats["latency"] without bound;
# nothing is lost — every retirement is first aggregated into the
# fixed-size TTFT/e2e histograms (`self.hist`) that /metrics exports
LATENCY_RECORD_CAP = 4096


def pack_ragged(rows: list[np.ndarray], width: int,
                max_seqs: int) -> tuple[np.ndarray, np.ndarray,
                                        np.ndarray, np.ndarray]:
    """Pack variable-length rows back-to-back into the flat ragged-batch
    layout: ``(tok (width,), seq (width,), starts (S,), ends (S,))`` with
    row i owning flat positions ``[starts[i], ends[i])`` and ``seq``
    holding the owner id per flat position (pad positions keep owner 0 —
    they fall outside every ``[start, end)`` range, so ownership masks
    reject them)."""
    assert len(rows) <= max_seqs
    tok = np.zeros(width, np.int32)
    seq = np.zeros(width, np.int32)
    starts = np.zeros(max_seqs, np.int32)
    ends = np.zeros(max_seqs, np.int32)
    off = 0
    for i, r in enumerate(rows):
        n = len(r)
        assert off + n <= width
        tok[off:off + n] = r
        seq[off:off + n] = i
        starts[i] = off
        ends[i] = off + n
        off += n
    return tok, seq, starts, ends


def _jit_step(name: str, step_fn, **kw):
    """``step_fn`` with ``kw`` bound, jitted under a stable name: the
    compiled module is ``jit_<name>``, which is what a profile's XLA
    Modules line shows for it. The cache (argument 1) is donated."""
    fn = functools.partial(step_fn, **kw)
    fn.__name__ = name
    return jax.jit(fn, donate_argnums=(1,))


def unpack_ragged(tok: np.ndarray, starts: np.ndarray, ends: np.ndarray,
                  n_rows: int) -> list[np.ndarray]:
    """Inverse of :func:`pack_ragged` for the first ``n_rows`` rows."""
    return [np.asarray(tok[starts[i]:ends[i]]).copy()
            for i in range(n_rows)]


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, mesh, pcfg: ParallelConfig = None,
                 *, max_batch: int = 8, block_size: int = 16,
                 max_len: int = 128, num_blocks: int | None = None,
                 max_num_batched_tokens: int | None = None,
                 enable_prefix_caching: bool = True,
                 debug_invariants: bool = False,
                 seed: int = 0, params=None,
                 draft_cfg: ModelConfig | None = None,
                 num_speculative_tokens: int = 0, draft_params=None,
                 shard_params: bool = False,
                 latency_record_cap: int = LATENCY_RECORD_CAP,
                 prefill_pack: int = 1, kv_dtype: str = "bf16",
                 swap_space_bytes: int = 0, swap_policy: str = "auto",
                 max_logprobs: int = 8, max_stop_len: int = 8,
                 shared_index=None):
        self.cfg, self.mesh = cfg, mesh
        self.pcfg = pcfg or ParallelConfig(remat="none")
        # tensor parallelism over the mesh "model" axis: page pools and
        # the encoder cache shard by kv head; Mamba slot state and (by
        # default) weights stay replicated so engine outputs are bitwise
        # mesh-invariant. All host-side metadata (tables, refcounts,
        # hashes, slots) stays global, so scheduling is identical on
        # every mesh shape (docs/multi-host.md).
        self.tp = shd.serving_tp(mesh)
        self.shard_params = shard_params
        if num_speculative_tokens and draft_cfg is None:
            draft_cfg = cfg          # self-speculation (a fresh-init draft
            #                          unless draft_params shares weights)
        self.draft_cfg = draft_cfg
        self.runner = make_runner(                  # raises if unsupported
            cfg, self.pcfg, draft_cfg=draft_cfg,
            num_speculative_tokens=num_speculative_tokens)
        if self.tp > 1 and self.runner.needs_blocks:
            # fail at construction, not in the jitted step: pools shard by
            # whole kv heads (target and draft pools alike)
            shd.paged_pool_pspec(cfg.num_kv_heads, self.tp)
            if draft_cfg is not None:
                shd.paged_pool_pspec(draft_cfg.num_kv_heads, self.tp)
        spec = self.runner.spec_tokens
        self.block_size = block_size
        self.max_len = max_len
        # block-table rows are widened past max_len by the speculative
        # lookahead: a verify step writes up to spec positions past the
        # context even on a request that retires before using them
        self.max_blocks_per_seq = -(-max_len // block_size) \
            + -(-spec // block_size)
        if num_blocks is None:
            # every slot can reach max_len (+ lookahead); +1 trash block
            num_blocks = max_batch * self.max_blocks_per_seq + 1
        if max_num_batched_tokens is None:
            max_num_batched_tokens = max_batch * (1 + spec) + 2 * block_size
        self.max_num_batched_tokens = max_num_batched_tokens
        # static chunk-buffer width: a full decode batch plus a full chunk
        # together stay within the budget; no chunk can exceed max_len, so
        # a huge budget must not widen the compiled buffer past it
        self.chunk_width = min(
            max_num_batched_tokens - max_batch * (1 + spec), max_len)
        if kv_dtype not in quant.KV_DTYPES:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} not in {sorted(quant.KV_DTYPES)}")
        self.kv_dtype = kv_dtype
        # host-swap tier: pinned host memory for preempted requests' KV,
        # sized in device block units so the BlockManager can account it.
        # Only pure paged runners qualify — slot-state (SSM/hybrid) and
        # encoder caches have no per-block representation to move.
        self._dev_block_bytes = 0
        if self.runner.needs_blocks:
            self._dev_block_bytes = block_bytes(cfg, block_size,
                                                kv_dtype=kv_dtype)
            if draft_cfg is not None:
                self._dev_block_bytes += block_bytes(draft_cfg, block_size,
                                                     kv_dtype=kv_dtype)
        swap_capable = (self.runner.needs_blocks
                        and not self.runner.needs_slots
                        and not self.runner.needs_encoder)
        if swap_space_bytes and not swap_capable:
            raise ValueError(
                "swap_space_bytes requires a pure paged-KV runner (slot "
                "state and encoder caches have no block-swap form)")
        if shared_index is not None and not swap_capable:
            raise ValueError(
                "shared_index (cross-replica prefix sharing) requires a "
                "pure paged-KV runner — the transfer unit is a hashed "
                "block, which slot-state and encoder caches don't have")
        if shared_index is not None and not enable_prefix_caching:
            raise ValueError(
                "shared_index requires enable_prefix_caching=True: the "
                "shared unit is the content-hashed block")
        self.shared_index = shared_index
        num_host_blocks = (swap_space_bytes // self._dev_block_bytes
                           if swap_space_bytes and self._dev_block_bytes
                           else 0)
        self._swap_cost = (SwapCostModel(block_bytes=self._dev_block_bytes,
                                         policy=swap_policy)
                           if num_host_blocks > 0 else None)
        self.bm = (BlockManager(num_blocks, block_size,
                                num_host_blocks=num_host_blocks,
                                shared_index=shared_index)
                   if self.runner.needs_blocks else None)
        self.slot_cache = (SlotStateCache(max_batch)
                           if self.runner.needs_slots else None)
        self.encoder_cache = (EncoderCache(max_batch)
                              if self.runner.needs_encoder else None)
        # prefix caching requires KV that is a pure function of the token
        # prefix — only the paged transformer kind qualifies
        enable_prefix_caching = (enable_prefix_caching
                                 and self.runner.supports_prefix_caching)
        # ragged packed prefill: several prompts' chunks share one flat
        # token batch per step. Only runners with a ragged prefill path
        # can consume multi-chunk plans; everyone else stays single-chunk.
        if not self.runner.supports_packed_prefill:
            prefill_pack = 1
        self.prefill_pack = max(1, prefill_pack)
        # dense per-slot sampling state (full path): param counts, prompt
        # masks and stop rings, bound/released alongside the slot caches
        self.max_logprobs = max_logprobs
        self.max_stop_len = max_stop_len
        self.runner.max_logprobs = max_logprobs
        self.samp_buf = SamplingBuffer(max_batch, cfg.vocab_size,
                                       max_stop_len=max_stop_len,
                                       max_logprobs=max_logprobs)
        self.sched = Scheduler(self.bm, max_batch, self.max_blocks_per_seq,
                               max_num_batched_tokens, self.chunk_width,
                               enable_prefix_caching=enable_prefix_caching,
                               chunk_quantum=self.runner.chunk_quantum,
                               slot_cache=self.slot_cache,
                               encoder_cache=self.encoder_cache,
                               spec_tokens=spec,
                               max_context=-(-max_len // block_size)
                               * block_size,
                               prefill_pack=self.prefill_pack,
                               swap_cost=self._swap_cost,
                               sampling_buffer=self.samp_buf)
        self.max_batch = max_batch
        self.debug_invariants = debug_invariants

        with jax.set_mesh(mesh):
            if params is None:
                params = api.init_params_bf16(cfg, jax.random.key(seed))
            if draft_cfg is not None:
                if draft_params is None:
                    draft_params = api.init_params_bf16(
                        draft_cfg, jax.random.key(seed + 1))
                params = {"tgt": self._place_params(params, cfg),
                          "dft": self._place_params(draft_params,
                                                    draft_cfg)}
            else:
                params = self._place_params(params, cfg)
            self.params = params
            self.cache = self.runner.init_cache(num_blocks, block_size,
                                                max_batch,
                                                kv_dtype=kv_dtype)
            if self.tp > 1:
                self.cache = jax.device_put(
                    self.cache, shd.serving_cache_shardings(self.cache,
                                                            mesh))

        self._step_chunk = _jit_step("serve_step_chunk", self.runner.step,
                                     has_chunk=True)
        self._step_plain = _jit_step("serve_step_decode", self.runner.step,
                                     has_chunk=False)
        # full-sampling executables are built LAZILY: a deployment that
        # never sees a top-p/penalty/logprobs request never compiles (or
        # traces) the full pipeline — the pure-greedy fast-path guard
        # test asserts this dict stays empty on all-greedy traffic
        self._full_steps: dict[bool, object] = {}
        if self.runner.needs_encoder:
            self._encode = jax.jit(self.runner.encode, donate_argnums=(1,))
        if self.runner.needs_blocks:
            self._copy_block = jax.jit(self._copy_block_fn,
                                       donate_argnums=(0,))

        # host pool: one pinned numpy array per paged cache leaf, block-
        # slot-major, aligned with jax.tree.leaves order (deterministic).
        # Scale leaves ride along automatically — they share the pools'
        # rank-5 num_blocks axis.
        self._host_pool: list[np.ndarray] = []
        self._host_block_nbytes = 0
        if num_host_blocks > 0:
            for p in jax.tree.leaves(self.cache):
                if p.ndim >= 2 and p.shape[1] == num_blocks:
                    shape = (num_host_blocks, p.shape[0]) + p.shape[2:]
                    self._host_pool.append(np.zeros(shape, p.dtype))
                    self._host_block_nbytes += int(
                        np.prod(shape[1:])) * p.dtype.itemsize
        if num_host_blocks > 0 or shared_index is not None:
            # the shared-index publish/adopt path reuses the host-swap
            # gather/scatter executables even with no local host tier
            self._swap_gather = jax.jit(self._swap_gather_fn)
            self._swap_scatter = jax.jit(self._swap_scatter_fn,
                                         donate_argnums=(0,))
        if shared_index is not None:
            # shared pool slots mirror the host-tier layout: one slot =
            # one block's pages across every paged leaf (scale sidecars
            # included — they share the num_blocks axis)
            shared_index.attach_pool(
                [((p.shape[0],) + p.shape[2:], p.dtype)
                 for p in jax.tree.leaves(self.cache)
                 if p.ndim >= 2 and p.shape[1] == num_blocks])

        cache_mib = 0.0
        if self.runner.needs_blocks:
            cache_mib += num_blocks * block_bytes(cfg, block_size,
                                                  kv_dtype=kv_dtype)
        if draft_cfg is not None:
            cache_mib += num_blocks * block_bytes(draft_cfg, block_size,
                                                  kv_dtype=kv_dtype)
        if self.runner.needs_slots:
            cache_mib += max_batch * slot_state_bytes(cfg)
        if self.runner.needs_encoder:
            cache_mib += max_batch * encoder_cache_bytes(cfg)
        self.stats = {"steps": 0, "prefill_chunks": 0, "preemptions": 0,
                      "tokens": 0, "prefill_tokens": 0,
                      "quantum_dropped_tokens": 0,
                      "cache_hit_tokens": 0, "cow_copies": 0,
                      "encodes": 0, "requests": 0, "requests_done": 0,
                      "spec_decodes": 0, "spec_emitted": 0,
                      "peak_block_utilization": 0.0, "peak_blocks_in_use": 0,
                      "latency": {},
                      "kv_cache_mib": round(cache_mib / 2 ** 20, 3),
                      "kv_dtype": kv_dtype, "aborts": 0,
                      "stop_hits": 0, "full_sampling_steps": 0,
                      "swap_preemptions": 0, "swap_ins": 0,
                      "host_hit_blocks": 0,
                      "shared_hit_blocks": 0, "shared_published_blocks": 0,
                      "swapped_out_blocks": 0, "swapped_in_blocks": 0,
                      "swapped_out_bytes": 0, "swapped_in_bytes": 0,
                      "swap_space_mib": round(
                          num_host_blocks * self._dev_block_bytes
                          / 2 ** 20, 3),
                      "queue_wait_s": 0.0, "first_admits": 0}
        if self.runner.counts_experts:
            # Σ over steps of the rows computed on held experts and of the
            # routed assignments (tokens × k), all layers
            self.stats.update(moe_rows=0, moe_assignments=0)
        self.step_count = 0           # virtual clock: one step() = one tick
        self.latency_record_cap = latency_record_cap
        # retirement-time latency aggregation: bounded state the metrics
        # endpoint exports no matter how many requests have flowed through
        self.hist = {"ttft_seconds": Histogram(SECONDS_BUCKETS),
                     "e2e_seconds": Histogram(SECONDS_BUCKETS),
                     "ttft_steps": Histogram(STEP_BUCKETS),
                     "e2e_steps": Histogram(STEP_BUCKETS),
                     "queue_wait_seconds": self.sched.queue_wait}
        # streaming hooks for the async front-end (serving/frontend/):
        # on_token(req, tok) after every appended token, on_finish(req)
        # after the request has retired and released its cache resources
        self.on_token = None
        self.on_finish = None
        # on_step(StepRecord) after every step that scheduled tokens; no
        # record is built while it is None
        self.on_step = None
        self._step_calls = 0          # every step() call, for serve.step

    def _place_params(self, params, cfg: ModelConfig):
        """Place one model's weights on the mesh.

        Default (``shard_params=False``): explicitly *replicated*. Every
        contraction over weights then happens whole on every shard, in the
        same order as on one device, so engine outputs are bitwise
        mesh-invariant — the property the TP equivalence suite enforces.
        Only the page pools / encoder caches (the memory that actually
        grows with traffic) and the attention compute over them shard.

        ``shard_params=True`` additionally shards the weights with the
        standard logical-axis rules (``spmd.sharding.make_rules``): less
        HBM and TP matmul flops, but GSPMD's partial-sum all-reduces
        reorder float adds, so outputs are only argmax-close, not bitwise
        equal, across mesh shapes — don't combine it with tests that
        demand byte identity."""
        if self.tp <= 1:
            return params
        if not self.shard_params:
            return jax.device_put(
                params, jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec()))
        _, specs = api.abstract_params(cfg)
        rules = shd.make_rules(cfg, self.pcfg)
        return jax.device_put(
            params, shd.tree_shardings(params, specs, rules, self.mesh))

    # -- derived stats (single code path for bench, serve.py and /metrics) -

    @property
    def cache_hit_rate(self) -> float:
        """Fraction of prefill KV served from the prefix cache instead of
        recomputed: hits / (hits + prefill tokens actually computed).
        0.0 before any prefill work (guarded against division by zero)."""
        hits = self.stats["cache_hit_tokens"]
        denom = hits + self.stats["prefill_tokens"]
        return hits / denom if denom else 0.0

    @property
    def preemption_rate(self) -> float:
        """Recompute preemptions per arrived request (a request preempted
        twice counts twice). 0.0 before any arrivals."""
        n = self.stats["requests"]
        return self.stats["preemptions"] / n if n else 0.0

    @property
    def mean_accept_len(self) -> float:
        """Realized tokens per speculative decode slot-step (1.0 = no
        draft token ever survived, 1 + k is the cap); 0.0 when
        speculation is off / no speculative decode has run yet."""
        n = self.stats["spec_decodes"]
        return self.stats["spec_emitted"] / n if n else 0.0

    def lower_steps(self) -> dict:
        """Lower the two plain step executables — with and without a
        prefill chunk — on this engine's own parameters, cache and array
        shapes, without running them: ``{"chunk": Lowered, "plain":
        Lowered}``, for inspecting what the compiled program contains."""
        arrays = self._build_arrays(StepPlan([], [], []), False)
        with jax.set_mesh(self.mesh):
            return {"chunk": self._step_chunk.lower(self.params, self.cache,
                                                    arrays),
                    "plain": self._step_plain.lower(self.params, self.cache,
                                                    arrays)}

    # -- jitted bodies -----------------------------------------------------

    def _copy_block_fn(self, cache, src, dst):
        """Copy one pool row (every attention layer stack, k and v) — the
        device half of a copy-on-write. Only paged leaves have a
        num_blocks axis; slot-state and encoder leaves are left alone."""
        nb = self.bm.num_blocks

        def leaf(p):
            if p.ndim >= 2 and p.shape[1] == nb:
                return p.at[:, dst].set(p[:, src])
            return p

        return jax.tree.map(leaf, cache)

    def _swap_gather_fn(self, cache, idx):
        """Pull ``idx`` block rows out of every paged leaf, block-major —
        the device half of a d2h swap-out. Issued on *pre-step* pool
        content and materialized to the host pool later (overlapping the
        jitted step), which is safe because the handle pins the pre-
        donation buffers regardless of what rewrites the pool after."""
        nb = self.bm.num_blocks
        return [jnp.moveaxis(p[:, idx], 1, 0)
                for p in jax.tree.leaves(cache)
                if p.ndim >= 2 and p.shape[1] == nb]

    def _swap_scatter_fn(self, cache, idx, vals):
        """Write host rows back into ``idx`` block slots of every paged
        leaf (h2d swap-in). Pad entries target the trash block."""
        nb = self.bm.num_blocks
        it = iter(vals)

        def leaf(p):
            if p.ndim >= 2 and p.shape[1] == nb:
                return p.at[:, idx].set(jnp.moveaxis(next(it), 0, 1))
            return p

        return jax.tree.map(leaf, cache)

    @staticmethod
    def _pad_pow2(n: int) -> int:
        """Swap batch sizes round up to a power of two so the jitted
        gather/scatter compile O(log) variants, not one per count."""
        return 1 << max(0, n - 1).bit_length()

    def _issue_swap_out(self, pairs):
        """Dispatch the d2h gather for this step's swap-outs. Returns the
        (handle, pairs) token to drain later — after the step for overlap,
        or immediately when this step's swap-ins reuse the slots."""
        m = self._pad_pow2(len(pairs))
        idx = np.full(m, TRASH_BLOCK, np.int32)
        idx[:len(pairs)] = [b for b, _ in pairs]
        return self._swap_gather(self.cache, jnp.asarray(idx)), pairs

    def _drain_swap_out(self, token) -> None:
        """Materialize a pending d2h gather into the host pool."""
        handle, pairs = token
        t0 = time.monotonic()
        slots = [s for _, s in pairs]
        for hp, g in zip(self._host_pool, handle):
            hp[slots] = np.asarray(g[:len(slots)])
        nbytes = len(slots) * self._host_block_nbytes
        self.stats["swapped_out_blocks"] += len(slots)
        self.stats["swapped_out_bytes"] += nbytes
        self._swap_cost.observe_swap(nbytes, time.monotonic() - t0)

    def _swap_in(self, pairs) -> None:
        """h2d: copy host slots into freshly allocated device blocks,
        before COW copies (which may read them) and the step."""
        n = len(pairs)
        m = self._pad_pow2(n)
        idx = np.full(m, TRASH_BLOCK, np.int32)
        idx[:n] = [b for _, b in pairs]
        slots = [s for s, _ in pairs]
        vals = []
        for hp in self._host_pool:
            buf = np.zeros((m,) + hp.shape[1:], hp.dtype)
            buf[:n] = hp[slots]
            vals.append(jnp.asarray(buf))
        self.cache = self._swap_scatter(self.cache, jnp.asarray(idx), vals)
        self.stats["swapped_in_blocks"] += n
        self.stats["swapped_in_bytes"] += n * self._host_block_nbytes

    def _shared_in(self, pairs) -> None:
        """h2d: copy shared-index pool slots (blocks another replica
        published) into freshly allocated device blocks — the ``_swap_in``
        contract with the process-global pool as the source. Admission
        pinned the slots; they are released here, once the payload has
        been captured into the scatter operands."""
        shared = self.shared_index
        n = len(pairs)
        m = self._pad_pow2(n)
        idx = np.full(m, TRASH_BLOCK, np.int32)
        idx[:n] = [b for _, b in pairs]
        slots = [s for s, _ in pairs]
        vals = []
        for hp in shared.pool:
            buf = np.zeros((m,) + hp.shape[1:], hp.dtype)
            buf[:n] = hp[slots]
            vals.append(jnp.asarray(buf))
        shared.release(slots)
        self.cache = self._swap_scatter(self.cache, jnp.asarray(idx), vals)

    def _flush_shared_publish(self) -> None:
        """Publish this replica's newly hash-registered blocks into the
        shared index: d2h-gather their pages into reserved pool slots and
        commit the hashes. Runs at step boundaries (payloads are complete:
        registration happens only after the writing exec has synced) and
        at stream close (``_append_token`` retirement), which is what
        makes cross-replica adoption deterministic — a request submitted
        after a producer's stream finished always finds its blocks."""
        if self.shared_index is None or self.bm is None:
            return
        pend = self.bm.drain_publishable()
        if not pend:
            return
        shared = self.shared_index
        blocks, slots, hashes = [], [], []
        for b, h in pend:
            s = shared.reserve(h)
            if s is None:
                continue     # raced with another replica, or pool pinned full
            blocks.append(b)
            slots.append(s)
            hashes.append(h)
        if not blocks:
            return
        n = len(blocks)
        idx = np.full(self._pad_pow2(n), TRASH_BLOCK, np.int32)
        idx[:n] = blocks
        g = self._swap_gather(self.cache, jnp.asarray(idx))
        for pool, leaf in zip(shared.pool, g):
            pool[slots] = np.asarray(leaf[:n])
        for s, h in zip(slots, hashes):
            shared.commit(s, h)
        self.stats["shared_published_blocks"] += n

    # -- host-side step ----------------------------------------------------

    def _full_step(self, has_chunk: bool):
        """The jitted step with the full sampling pipeline, compiled on
        first use only (see ``_full_steps``)."""
        if has_chunk not in self._full_steps:
            name = ("serve_step_chunk_full" if has_chunk
                    else "serve_step_decode_full")
            self._full_steps[has_chunk] = _jit_step(
                name, self.runner.step, has_chunk=has_chunk,
                full_sampling=True)
        return self._full_steps[has_chunk]

    def _build_arrays(self, plan: StepPlan, full: bool = False) -> dict:
        B, C, nbmax = self.max_batch, self.chunk_width, self.max_blocks_per_seq
        S = self.prefill_pack
        a = {"d_tok": np.zeros(B, np.int32),
             "d_pos": np.zeros(B, np.int32),
             "d_tables": np.zeros((B, nbmax), np.int32),
             "d_active": np.zeros(B, bool),
             "temps": np.zeros(B + S, np.float32),
             "top_ks": np.zeros(B + S, np.int32),
             "seeds": np.zeros(B + S, np.int32),
             "rids": np.zeros(B + S, np.int32),
             "counters": np.zeros(B + S, np.int32)}
        if full:
            # full-pipeline rows: identity defaults on every inactive /
            # plain-params row, dense count state gathered per request
            V = self.samp_buf.vocab_size
            a.update({"top_ps": np.ones(B + S, np.float32),
                      "min_ps": np.zeros(B + S, np.float32),
                      "rep_pens": np.ones(B + S, np.float32),
                      "pres_pens": np.zeros(B + S, np.float32),
                      "freq_pens": np.zeros(B + S, np.float32),
                      "pmask": np.zeros((B + S, V), bool),
                      "ocounts": np.zeros((B + S, V), np.int32)})
        if S == 1:
            a.update({"c_tok": np.zeros((1, C), np.int32),
                      "c_start": np.zeros(1, np.int32),
                      "c_len": np.zeros(1, np.int32),
                      "c_slot": np.zeros(1, np.int32),
                      "c_table": np.full((1, nbmax), TRASH_BLOCK, np.int32)})
        else:
            # flat ragged layout: chunk ci owns rows [c_starts[ci],
            # c_ends[ci]) of the (1, C) token batch; pad rows are owned by
            # nobody (row_seq 0 but outside sequence 0's range) so their
            # KV lands in the trash block and their logits are discarded
            a.update({"c_tok": np.zeros((1, C), np.int32),
                      "c_pos": np.zeros((1, C), np.int32),
                      "c_seq": np.zeros(C, np.int32),
                      "c_starts": np.zeros(S, np.int32),
                      "c_ends": np.zeros(S, np.int32),
                      "c_ctx": np.zeros(S, np.int32),
                      "c_tables": np.full((S, nbmax), TRASH_BLOCK,
                                          np.int32)})

        def samp(i, req):
            a["temps"][i] = req.sampling.temperature
            a["top_ks"][i] = req.sampling.top_k
            a["seeds"][i] = req.sampling.seed
            a["rids"][i] = req.rid
            a["counters"][i] = len(req.out)
            if full:
                sp = req.sampling
                a["top_ps"][i] = sp.top_p
                a["min_ps"][i] = sp.min_p
                a["rep_pens"][i] = sp.repetition_penalty
                a["pres_pens"][i] = sp.presence_penalty
                a["freq_pens"][i] = sp.frequency_penalty
                pmask, ocounts = self.samp_buf.row(req.rid)
                a["pmask"][i] = pmask
                a["ocounts"][i] = ocounts

        for slot, req in plan.decodes:
            a["d_active"][slot] = True
            a["d_tok"][slot] = req.out[-1]
            a["d_pos"][slot] = req.context_len - 1  # write position of out[-1]
            if self.bm is not None:
                row = self.bm.table(req.rid)
                a["d_tables"][slot, :len(row)] = row
            samp(slot, req)

        if S == 1:
            if plan.chunk is not None:
                slot, req, n = plan.chunk
                toks = req.prefill_tokens()
                a["c_tok"][0, :n] = \
                    toks[req.num_computed:req.num_computed + n]
                a["c_start"][0] = req.num_computed
                a["c_len"][0] = n
                a["c_slot"][0] = slot
                if self.bm is not None:
                    row = self.bm.table(req.rid)
                    a["c_table"][0, :len(row)] = row
                samp(B, req)
        elif plan.chunks:
            tok_rows, pos_rows = [], []
            for ci, (slot, req, n) in enumerate(plan.chunks):
                toks = req.prefill_tokens()
                tok_rows.append(
                    toks[req.num_computed:req.num_computed + n])
                pos_rows.append(np.arange(req.num_computed,
                                          req.num_computed + n, dtype=np.int32))
                a["c_ctx"][ci] = req.num_computed + n
                if self.bm is not None:
                    row = self.bm.table(req.rid)
                    a["c_tables"][ci, :len(row)] = row
                samp(B + ci, req)
            tok, seq, starts, ends = pack_ragged(tok_rows, C, S)
            pos, _, _, _ = pack_ragged(pos_rows, C, S)
            a["c_tok"][0], a["c_pos"][0] = tok, pos
            a["c_seq"], a["c_starts"], a["c_ends"] = seq, starts, ends
        with TraceAnnotation("serve.h2d"):
            return {k: jnp.asarray(v) for k, v in a.items()}

    def _lat(self, rid: int) -> dict:
        return self.stats["latency"].setdefault(rid, {})

    def _note_arrival(self, req: Request) -> None:
        # monotonic: the *_wall fields are only ever differenced, and an
        # NTP step must not produce negative latencies
        self.stats["requests"] += 1
        self._lat(req.rid).update(arrival_step=self.step_count,
                                  arrival_wall=time.monotonic())

    def _observe_latency(self, rec: dict) -> None:
        """Fold one completed request's record into the TTFT/e2e
        histograms — the bounded aggregate that survives record eviction
        and backs the /metrics endpoint."""
        if "arrival_step" not in rec:        # driven without _note_arrival
            return                           # (scheduler-level tests)
        self.hist["ttft_steps"].observe(
            rec["first_token_step"] - rec["arrival_step"])
        self.hist["e2e_steps"].observe(
            rec["done_step"] - rec["arrival_step"])
        self.hist["ttft_seconds"].observe(
            rec["first_token_wall"] - rec["arrival_wall"])
        self.hist["e2e_seconds"].observe(
            rec["done_wall"] - rec["arrival_wall"])

    def _req_logprobs(self, req: Request, lp, idx):
        """Format one emitted token's logprobs for the ``on_token`` hook:
        ``{"token_logprob": float, "top": [(id, logprob), ...]}`` trimmed
        to the request's ``logprobs`` count, or None when the request
        didn't ask (or the step ran the plain path)."""
        n = req.sampling.logprobs
        if lp is None or n <= 0:
            return None
        return {"token_logprob": float(lp["chosen"][idx]),
                "top": [(int(t), float(v))
                        for t, v in zip(lp["top_ids"][idx][:n],
                                        lp["top_lp"][idx][:n])]}

    def _append_token(self, slot: int, req: Request, tok: int,
                      logprobs=None) -> None:
        req.out.append(tok)
        self.samp_buf.commit(req.rid, tok)
        self.stats["tokens"] += 1
        rec = self._lat(req.rid)
        if "first_token_step" not in rec:
            # first token emitted *on this engine* — for a request
            # submitted with `out` pre-filled (a disaggregated decode
            # continuation), that's its first locally produced token
            rec.update(first_token_step=self.step_count,
                       first_token_wall=time.monotonic())
        self.sched.note_progress(req)
        if (req.sampling.stop and not req.stop_hit
                and len(req.out) >= req.min_new
                and self.samp_buf.check_stop(req.rid, req.sampling.stop)
                is not None):
            req.stop_hit = True
            self.stats["stop_hits"] += 1
        if self.on_token is not None:
            self.on_token(req, tok, logprobs)
        if req.done:
            rec = self._lat(req.rid)
            rec.update(done_step=self.step_count,
                       done_wall=time.monotonic())
            self._observe_latency(rec)
            self.stats["requests_done"] += 1
            lat = self.stats["latency"]
            if len(lat) > self.latency_record_cap:
                # evict oldest *completed* records only — an in-flight
                # request must keep its arrival marks for TTFT reporting
                for rid in list(lat):
                    if "done_step" in lat[rid]:
                        del lat[rid]
                        if len(lat) <= self.latency_record_cap:
                            break
            if self.shared_index is not None:
                # stream-close publish barrier: before anyone can observe
                # this request as finished (on_finish → its stream ends),
                # every full block it registered is committed to the
                # shared index — so a request submitted *after* a
                # producer's stream closed deterministically adopts its
                # blocks on any replica (docs/multi-host.md)
                self._flush_shared_publish()
            self.sched.retire(slot)
            if self.on_finish is not None:
                self.on_finish(req)

    def abort(self, rid: int) -> bool:
        """Cancel an in-flight request between steps (front-end client
        disconnect). Cache resources are released immediately — blocks
        hash-retained, swapped host slots discarded — and no further
        tokens are produced. Safe no-op for unknown/retired rids."""
        ok = self.sched.abort(rid)
        if ok:
            self.stats["aborts"] = self.sched.n_aborts
        return ok

    def _run_encodes(self, plan: StepPlan) -> None:
        """Admission-time encoder passes: write each new request's cross
        K/V into its slot row before any decoder work touches it."""
        for slot, req in plan.encodes:
            frames = req.frames
            if frames is None:
                frames = np.zeros(
                    (self.cfg.encoder_seq_len, self.cfg.d_model),
                    np.float32)
            self.cache = self._encode(
                self.params, self.cache, jnp.asarray(slot, jnp.int32),
                jnp.asarray(frames, jnp.bfloat16))
            self.stats["encodes"] += 1

    def step(self) -> bool:
        """One engine iteration. Returns True when any work ran.

        Each phase is a profiler span (``jax.profiler.TraceAnnotation``,
        about a microsecond when no trace is being captured), so a trace
        taken with ``jax.profiler.start_trace`` puts the host's work on the
        same clock as the device's operations: ``serve.step`` (the whole
        call; stats ``step``, ``rows``, ``decode_pages`` (KV pages the
        decode rows attend), ``chunk_tokens``, ``full``, and in an expert
        model ``moe_rows`` and ``moe_assign``: rows computed on held
        experts and routed assignments, summed over layers) holds
        ``serve.schedule`` (with ``serve.admit`` per admission),
        ``serve.copies`` (swaps, encodes, copy-on-write), ``serve.build``
        (host arrays, ``serve.h2d`` their transfer), ``serve.dispatch``,
        ``serve.sync`` (waiting for the outputs) and ``serve.emit`` (token
        appends, hooks, prefix publishing, retirement)."""
        k = self._step_calls
        self._step_calls += 1
        on_step = self.on_step
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh), \
                TraceAnnotation("serve.step", step=k) as span:
            with TraceAnnotation("serve.schedule"):
                plan = self.sched.schedule()
            self.stats["preemptions"] = self.sched.n_preemptions
            self.stats["swap_preemptions"] = self.sched.n_swap_preemptions
            self.stats["swap_ins"] = self.sched.n_swap_ins
            self.stats["host_hit_blocks"] = self.sched.host_hit_blocks
            self.stats["shared_hit_blocks"] = self.sched.shared_hit_blocks
            self.stats["cache_hit_tokens"] = self.sched.cache_hit_tokens
            self.stats["quantum_dropped_tokens"] = \
                self.sched.quantum_dropped_tokens
            self.stats["queue_wait_s"] = self.sched.queue_wait.total
            self.stats["first_admits"] = self.sched.queue_wait.count
            if self.bm is not None:
                st = self.bm.stats()
                self.stats["peak_block_utilization"] = max(
                    self.stats["peak_block_utilization"], st.utilization)
                self.stats["peak_blocks_in_use"] = max(
                    self.stats["peak_blocks_in_use"], st.blocks_in_use)
            if self.debug_invariants:
                self._check_invariants(plan)
            # host-swap copies. The d2h gather is issued FIRST — on the
            # pre-step pool content, before anything (swap-in scatter, COW
            # copies, the step itself) can rewrite a freed block — and
            # materialized to the host pool after the step is dispatched,
            # overlapping the host copy with device compute. Swap-ins must
            # land before COW copies: a host-copied block registered this
            # step can already be a COW source for a later admission.
            with TraceAnnotation("serve.copies", cow=len(plan.copies)):
                d2h_token = None
                if plan.swap_outs:
                    d2h_token = self._issue_swap_out(plan.swap_outs)
                if plan.swap_ins:
                    if d2h_token is not None:
                        # same-step slot reuse: host content must exist
                        self._drain_swap_out(d2h_token)
                        d2h_token = None
                    self._swap_in(plan.swap_ins)
                if plan.shared_ins:
                    # cross-replica adoptions land with the swap-ins,
                    # before COW copies (an adopted block can be a COW
                    # source)
                    self._shared_in(plan.shared_ins)
                self._run_encodes(plan)
                for src, dst in plan.copies:
                    self.stats["cow_copies"] += 1
                    self.cache = self._copy_block(
                        self.cache, jnp.asarray(src, jnp.int32),
                        jnp.asarray(dst, jnp.int32))
            if plan.scheduled_tokens == 0:
                # no compute, but an admission (e.g. a full prefix-cache
                # hit that is immediately decode-ready) is still progress
                if d2h_token is not None:
                    self._drain_swap_out(d2h_token)
                self._flush_shared_publish()
                if plan.admitted:
                    self.step_count += 1
                return plan.admitted > 0
            # per-step fast-path switch: the full pipeline compiles and
            # runs only when some scheduled request actually needs it —
            # pure-greedy (and temperature/top-k-only) batches stay on
            # the two plain executables, byte-identical to before
            full = (any(r.sampling.needs_pipeline
                        for _, r in plan.decodes)
                    or any(r.sampling.needs_pipeline
                           for _, r, _ in plan.chunks))
            bs = self.block_size
            span.set_metadata(rows=len(plan.decodes),
                              decode_pages=sum(-(-r.context_len // bs)
                                               for _, r in plan.decodes),
                              chunk_tokens=sum(n for *_, n in plan.chunks),
                              full=full)
            if on_step is not None:
                # the plan as it runs: contexts and chunk starts move below
                planned = dict(
                    decode_ctxs=tuple(r.context_len for _, r in plan.decodes),
                    chunks=tuple((r.num_computed, n)
                                 for _, r, n in plan.chunks),
                    sampled=sum(r.num_computed + n == r.context_len
                                for _, r, n in plan.chunks))
            with TraceAnnotation("serve.build", full=full):
                arrays = self._build_arrays(plan, full)
            if full:
                self.stats["full_sampling_steps"] += 1
                step_exec = self._full_step(plan.chunk is not None)
            else:
                step_exec = (self._step_chunk if plan.chunk is not None
                             else self._step_plain)
            t_step = time.monotonic()
            with TraceAnnotation("serve.dispatch"):
                nxt, self.cache = step_exec(self.params, self.cache, arrays)
            with TraceAnnotation("serve.sync"):
                if d2h_token is not None:
                    self._drain_swap_out(d2h_token)
                nxt = jax.tree.map(np.asarray, nxt)
            moe = {}
            if self.runner.counts_experts:
                nxt, counts = nxt
                moe = {"moe_rows": int(counts[0]),
                       "moe_assign": int(counts[1])}
                span.set_metadata(**moe)
                self.stats["moe_rows"] += moe["moe_rows"]
                self.stats["moe_assignments"] += moe["moe_assign"]
            with TraceAnnotation("serve.emit"):
                self._emit(plan, nxt, full, t_step)
            self.stats["steps"] += 1
            self.step_count += 1
            if self.debug_invariants and self.bm is not None:
                self.bm.check()
                if self.shared_index is not None:
                    self.shared_index.check()
            if on_step is not None:
                s = self.stats
                on_step(StepRecord(
                    step=k, t0=t0, t1=time.perf_counter(), **planned,
                    full=full, waiting=len(self.sched.waiting),
                    cache_hit_tokens=s["cache_hit_tokens"],
                    prefill_tokens=s["prefill_tokens"],
                    queue_wait_s=s["queue_wait_s"],
                    first_admits=s["first_admits"], **moe))
            return True

    def _emit(self, plan: StepPlan, nxt, full: bool, t_step: float) -> None:
        """Hand the step's synced outputs (numpy) to their requests:
        append tokens (``on_token``), advance chunks, retire finished
        requests, and publish newly full prefix blocks."""
        chunk_lp = None
        if self.runner.spec_tokens or self.draft_cfg is not None:
            if full:
                toks, n_acc, chunk_toks, lp_d, chunk_lp = nxt
            else:
                (toks, n_acc, chunk_toks), lp_d = nxt, None
            for slot, req in plan.decodes:
                self.stats["spec_decodes"] += 1
                # accepted draft prefix + the corrected / bonus token,
                # cut short by EOS or max_new retirement
                for i in range(int(n_acc[slot]) + 1):
                    req.num_computed += 1
                    self.stats["spec_emitted"] += 1
                    self._append_token(
                        slot, req, int(toks[slot, i]),
                        self._req_logprobs(req, lp_d, (slot, i)))
                    if req.done:
                        break
                if self.sched.running.get(slot) is req:
                    # roll back lookahead blocks the rejected draft
                    # tail reserved (in both models' pools at once —
                    # they share the block table)
                    self.bm.truncate(req.rid, req.context_len)
        else:
            if full:
                toks, lp = nxt
                chunk_lp = {k: v[self.max_batch:] for k, v in lp.items()}
            else:
                toks, lp = nxt, None
            chunk_toks = toks[self.max_batch:]
            for slot, req in plan.decodes:
                req.num_computed += 1
                self._append_token(slot, req, int(toks[slot]),
                                   self._req_logprobs(req, lp, slot))
        for ci, (slot, req, n) in enumerate(plan.chunks):
            req.num_computed += n
            self.stats["prefill_chunks"] += 1
            self.stats["prefill_tokens"] += n
            if req.num_computed == req.context_len:
                self._append_token(
                    slot, req, int(chunk_toks[ci]),
                    self._req_logprobs(req, chunk_lp, ci))
            else:
                self.sched.note_progress(req)
        if self._swap_cost is not None and plan.chunks:
            # the step's outputs were synced before this, so this wall
            # time covers real device work: feed the recompute-throughput
            # EMA the cost model weighs against moving bytes
            self._swap_cost.observe_prefill(
                sum(c[2] for c in plan.chunks),
                time.monotonic() - t_step)
        self._flush_shared_publish()

    def _check_invariants(self, plan: StepPlan) -> None:
        for cache in (self.slot_cache, self.encoder_cache):
            if cache is not None:
                cache.check()
                for slot, req in self.sched.running.items():
                    assert cache.slot(req.rid) == slot, (req.rid, slot)
        assert plan.scheduled_tokens <= self.max_num_batched_tokens
        if self.bm is None:
            return
        self.bm.check()
        bs = self.block_size
        for slot, req in self.sched.running.items():
            t = self.bm.table(req.rid)
            assert len(t) <= self.max_blocks_per_seq, (req.rid, len(t))
            assert len(t) * bs >= req.num_computed, \
                f"request {req.rid}: table does not cover computed KV"
        for _, req, n in plan.chunks:
            t = self.bm.table(req.rid)
            assert len(t) * bs >= req.num_computed + n
            # COW guarantee: the chunk writes only exclusively-owned blocks
            lo, hi = req.num_computed // bs, (req.num_computed + n - 1) // bs
            for j in range(lo, hi + 1):
                assert self.bm.refcount(t[j]) == 1, \
                    f"chunk would write shared block {t[j]}"
        for slot, req in plan.decodes:
            t = self.bm.table(req.rid)
            # the decode (or the speculative verify row) writes positions
            # context_len-1 .. context_len-1+spec: all exclusively owned
            for p in range(req.context_len - 1,
                           req.context_len + plan.spec_tokens):
                assert self.bm.refcount(t[p // bs]) == 1, \
                    f"decode would write shared block {t[p // bs]}"

    def run(self, requests: list[Request],
            arrival_steps: list[int] | None = None) -> dict[int, np.ndarray]:
        """Serve ``requests`` to completion. ``arrival_steps[i]`` is the
        engine-step index at which request i becomes visible (default: all
        at step 0). Returns {rid: generated token array}; wall-clock,
        throughput and per-request latency land in ``self.stats``."""
        if arrival_steps is None:
            arrival_steps = [0] * len(requests)
        for r in requests:
            self.sched.validate(r)         # fail fast, not at arrival time
        pending = deque(sorted(zip(arrival_steps, range(len(requests))),
                               key=lambda t: t[0]))
        t0 = time.time()
        tok0 = self.stats["tokens"]
        while pending or self.sched.has_work:
            while pending and pending[0][0] <= self.step_count:
                req = requests[pending.popleft()[1]]
                self.sched.add(req)
                self._note_arrival(req)
            if not self.sched.has_work and pending:
                self.step_count = pending[0][0]      # idle: jump the clock
                continue
            if not self.step():
                # defensive: the scheduler admits whenever a slot is free
                # and raises MemoryError itself when the pool can't ever
                # fit, so reaching this means a scheduling-policy bug
                state = (self.bm.stats() if self.bm is not None
                         else self.slot_cache.stats())
                raise RuntimeError(
                    "engine stuck: scheduler made no progress with work "
                    f"pending — {state}")
        dt = time.time() - t0
        self.stats["wall_s"] = round(dt, 3)
        self.stats["tok_s"] = round((self.stats["tokens"] - tok0)
                                    / max(dt, 1e-9), 1)
        if self.stats["spec_decodes"]:
            self.stats["mean_accept_len"] = round(self.mean_accept_len, 3)
        return {r.rid: np.asarray(r.out, np.int32) for r in requests}
