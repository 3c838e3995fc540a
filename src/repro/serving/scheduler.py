"""Token-budget continuous-batching scheduler.

Every engine step the scheduler hands out up to ``max_num_batched_tokens``
of model work in one :class:`StepPlan`:

* every decode-ready running request gets **1 token** (the wide decode
  batch — running decodes are never starved), and
* the remaining budget funds **one prefill chunk**: the next slice of the
  request currently streaming its prompt in, or a freshly admitted one.

Requests track ``num_computed`` — how many of their ``prefill_tokens()``
already have KV in the paged cache. A request whose prompt (or
post-preemption recompute) is longer than the leftover budget streams in
over several steps while everyone else keeps decoding: no full-batch
prefill stall, no prompt-length bucketing.

Admission consults the :class:`~repro.serving.kv_cache.BlockManager`
prefix cache: full blocks whose chained token hash is already resident are
shared (refcount++) instead of recomputed, and ``num_computed`` starts
past them. When the whole prompt is cached the last token is recomputed
for its logits; since its write position lands inside a shared block, the
scheduler emits a copy-on-write (the plan's ``copies`` are device page
copies the engine must perform before the step).

Preemption follows vLLM's recompute strategy: the victim (most recently
joined — oldest requests are closest to done) releases its blocks and
returns to the *front* of the waiting queue carrying the tokens generated
so far; on re-admission it recomputes prompt+generated (prefix-cache hits
on its own just-freed blocks usually make this cheap), so greedy outputs
are preemption-invariant.

With speculative decoding (``spec_tokens`` = k > 0) each decode slot
costs ``1 + k`` budget tokens (the widened verify row) and its block
horizon is ensured at ``context_len + 1 + k`` — the engine rewinds the
rejected tail via ``BlockManager.truncate`` after the step — and a
preemption victim's recompute chunk stops one token short of its stream
so the final token is re-emitted by the verify step with the original
rejection-sampling window alignment (temperature replay invariance).

Invariants this module maintains (asserted by ``validate``, the engine's
``debug_invariants`` checks, and the scheduler tests):

* a request is accepted only if ``prompt + max_new`` fits the per-request
  block-table capacity (``max_context``) — checked once, at submission;
* every decode-ready request owns blocks covering
  ``context_len + 1 + spec_tokens`` before its step runs;
* a step's ``scheduled_tokens`` never exceeds ``max_num_batched_tokens``;
* running decodes are never starved: admission and chunk growth spend
  only the *leftover* budget, and admission never preempts;
* slot-kind caches hold a rid<->slot bijection, bound at admission and
  released exactly once on preempt/retire;
* everything here is mesh-invariant: block ids, tables, hashes and slots
  are global regardless of how the device pools shard over the mesh
  "model" axis (docs/multi-host.md), so the same request stream produces
  the same plans on any mesh shape — pinned by the TP walks and the
  subprocess stats-equality tests in tests/test_serving_tp.py.

Queue wait: the seconds from ``add`` to a request's first slot binding
(``time.perf_counter``) feed ``queue_wait``, a histogram whose ``total`` and
``count`` are the cumulative wait and the number of requests that have
reached a slot; a preempted request's re-admission does not count again,
nor does a request that arrives already holding output tokens (the decode
continuation of a disaggregated request, whose wait its prefill replica
counted).

Pure host-side so the policy is unit-testable in isolation; each admission
is a ``serve.admit`` profiler span.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving.kv_cache import BlockManager, extend_chain_hashes
from repro.serving.stats import SECONDS_BUCKETS, Histogram

_RID = itertools.count()


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling surface (docs/sampling.md).

    Every default is an exact identity: a request left at the defaults
    samples byte-identically on the plain (greedy/temperature/top-k)
    path and the full pipeline, and ``needs_pipeline`` is what lets the
    engine keep pure-greedy batches on the plain compiled executables.
    ``stop`` holds token-id sequences (tuples, so the dataclass stays
    hashable); matching happens host-side against the SamplingBuffer's
    per-slot ring of recent tokens.
    """

    temperature: float = 0.0       # 0 => greedy
    top_k: int = 0                 # 0 => no truncation
    seed: int = 0
    top_p: float = 1.0             # 1.0 => no nucleus truncation
    min_p: float = 0.0             # 0 => no min-p truncation
    repetition_penalty: float = 1.0
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    logprobs: int = 0              # top-N logprobs per token (0 = off)
    stop: tuple = ()               # stop sequences: tuples of token ids

    def __post_init__(self):
        # normalize list-of-lists from JSON frontends into the hashable
        # tuple-of-tuples form (frozen dataclass: go through __setattr__)
        object.__setattr__(self, "stop",
                           tuple(tuple(int(t) for t in s)
                                 for s in self.stop))

    @property
    def needs_pipeline(self) -> bool:
        """True when sampling this request needs the full in-jit
        pipeline (penalties / top-p / min-p / logprobs). Stop sequences
        and min_new are host-side checks and do *not* force it."""
        return (self.top_p < 1.0 or self.min_p > 0.0
                or self.repetition_penalty != 1.0
                or self.presence_penalty != 0.0
                or self.frequency_penalty != 0.0
                or self.logprobs > 0)


@dataclass
class SwapCostModel:
    """Per-victim swap-vs-recompute decision for preemption.

    Swapping moves ``2 * n_blocks * block_bytes`` over the device<->host
    link (out now, back in later); recomputing replays ``num_computed``
    prefill tokens through the model. Both rates start at conservative
    defaults and are refined online by the engine's measurements (EMA), so
    the policy adapts to the actual machine instead of a guessed ratio.
    jax-free, like everything else in this module.
    """

    block_bytes: int                 # device bytes one block id costs
    policy: str = "auto"             # "always" | "never" | "auto"
    bytes_per_s: float = 4.0e9       # d2h+h2d bandwidth EMA
    prefill_tok_s: float = 2.0e4     # recompute throughput EMA
    ema_alpha: float = 0.2

    def prefer_swap(self, n_blocks: int, n_recompute_tokens: int) -> bool:
        if self.policy == "always":
            return True
        if self.policy == "never":
            return False
        move_s = 2.0 * n_blocks * self.block_bytes \
            / max(self.bytes_per_s, 1.0)
        recompute_s = n_recompute_tokens / max(self.prefill_tok_s, 1.0)
        return move_s < recompute_s

    def observe_swap(self, nbytes: int, seconds: float) -> None:
        if nbytes > 0 and seconds > 0:
            self.bytes_per_s += self.ema_alpha * (nbytes / seconds
                                                  - self.bytes_per_s)

    def observe_prefill(self, n_tokens: int, seconds: float) -> None:
        if n_tokens > 0 and seconds > 0:
            self.prefill_tok_s += self.ema_alpha * (n_tokens / seconds
                                                    - self.prefill_tok_s)


@dataclass
class Request:
    prompt: np.ndarray                      # (prompt_len,) int32
    max_new: int = 16
    sampling: SamplingParams = field(default_factory=SamplingParams)
    eos_id: int | None = None
    # EOS and stop sequences are ignored until min_new tokens exist
    # (max_new still wins; validation rejects min_new > max_new)
    min_new: int = 0
    # set by the engine when a stop sequence matched the output tail;
    # host state on the request, so it survives preemption like `out`
    stop_hit: bool = False
    # enc-dec only: (T_enc, d_model) stub frame embeddings for the
    # admission-time encode pass (zeros when None)
    frames: np.ndarray | None = field(default=None, repr=False)
    rid: int = field(default_factory=lambda: next(_RID))
    out: list[int] = field(default_factory=list)
    num_computed: int = 0                   # prefill_tokens() with KV cached
    n_published: int = 0                    # full blocks hash-registered
    n_preempted: int = 0
    # cached chain of full-block content hashes over prefill_tokens();
    # append-only (tokens only grow), survives preemption
    hash_chain: list = field(default_factory=list, repr=False)

    @property
    def done(self) -> bool:
        if len(self.out) >= self.max_new:
            return True
        if len(self.out) < self.min_new:
            return False               # EOS/stop ignored before min_new
        if self.stop_hit:
            return True
        return bool(self.out) and self.eos_id is not None \
            and self.out[-1] == self.eos_id

    def prefill_tokens(self) -> np.ndarray:
        """Prompt plus already-generated tokens (recompute after preempt)."""
        if not self.out:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.out, np.int32)])

    @property
    def context_len(self) -> int:
        return len(self.prompt) + len(self.out)

    @property
    def decode_ready(self) -> bool:
        """Exactly one token left to compute and a sampled token to feed:
        the request rides the wide decode batch. (The final 1-token slice
        of a recompute is a decode too — same operation.)"""
        return bool(self.out) and self.num_computed == self.context_len - 1


@dataclass
class StepPlan:
    """One step's worth of work, within the token budget."""
    decodes: list[tuple[int, Request]]            # slot -> 1 token each
    # prefill chunks funded by the leftover budget, each (slot, req,
    # n_tokens); more than one only with ``prefill_pack > 1`` (the packed
    # ragged-prefill path runs them in a single flat token batch)
    chunks: list[tuple[int, Request, int]]
    copies: list[tuple[int, int]]                 # device page copies (COW)
    admitted: int = 0                             # waiting -> running joins
    # freshly admitted enc-dec requests needing an encode pass this step
    encodes: list[tuple[int, Request]] = field(default_factory=list)
    # speculative lookahead: each decode slot costs 1 + spec_tokens target
    # positions (the widened verify row)
    spec_tokens: int = 0
    # host-swap copies the engine must perform around this step:
    # swap_outs are (device_block, host_slot) d2h gathers of *pre-step*
    # pool content (issue before anything can rewrite a freed block);
    # swap_ins are (host_slot, device_block) h2d copies that must land
    # before the step (and before COW copies, which may read them)
    swap_outs: list[tuple[int, int]] = field(default_factory=list)
    swap_ins: list[tuple[int, int]] = field(default_factory=list)
    # cross-replica prefix adoption: (shared_index_slot, device_block)
    # h2d copies out of the SharedPrefixIndex pool — same contract as
    # swap_ins (land before the step), different source pool
    shared_ins: list[tuple[int, int]] = field(default_factory=list)

    @property
    def chunk(self) -> tuple[int, Request, int] | None:
        """The single prefill chunk, for the unpacked (``prefill_pack=1``)
        path where at most one exists per step."""
        return self.chunks[0] if self.chunks else None

    @property
    def scheduled_tokens(self) -> int:
        return (len(self.decodes) * (1 + self.spec_tokens)
                + sum(c[2] for c in self.chunks))


class Scheduler:
    """Cache-kind-aware token-budget scheduler.

    ``bm`` is the paged cache's block manager, or None for runners whose
    state is purely slot-based (pure SSM): with no block pool there is no
    block horizon to validate, no growth to ensure, no preemption pressure
    and no prefix cache — admission is slot-limited only. ``slot_cache``
    and ``encoder_cache`` (``serving.cache``) are bound to the scheduler's
    chosen slot at admission and released on preempt/retire.

    ``chunk_quantum`` quantizes non-final prefill chunks down to a
    multiple (SSM runners: the SSD inner chunk size, so a chunked prefill
    re-groups the scan exactly like a monolithic one). Quantization
    rounding only ever drops tokens from the *last* chunk of a step —
    earlier chunks' remainders roll into the next chunk's budget — and the
    dropped count is tracked in ``quantum_dropped_tokens``.

    ``prefill_pack`` caps how many prefill chunks one step may carry
    (ragged packed prefill); 1 reproduces the classic single-chunk plans
    exactly.
    """

    def __init__(self, bm: BlockManager | None, max_batch: int,
                 max_blocks_per_seq: int, max_num_batched_tokens: int,
                 chunk_width: int, *, enable_prefix_caching: bool = True,
                 chunk_quantum: int = 1, slot_cache=None,
                 encoder_cache=None, spec_tokens: int = 0,
                 max_context: int | None = None, prefill_pack: int = 1,
                 swap_cost: SwapCostModel | None = None,
                 sampling_buffer=None):
        if max_num_batched_tokens <= max_batch * (1 + spec_tokens):
            raise ValueError(
                f"max_num_batched_tokens={max_num_batched_tokens} must "
                f"exceed max_batch={max_batch} x (1 + spec_tokens="
                f"{spec_tokens}) (each decode slot costs a 1 + k wide "
                "verify row; a prefill chunk needs leftover budget)")
        if chunk_width < chunk_quantum:
            raise ValueError(
                f"chunk_width={chunk_width} below chunk_quantum="
                f"{chunk_quantum}: no non-final chunk could ever run")
        self.bm = bm
        self.max_batch = max_batch
        self.max_blocks_per_seq = max_blocks_per_seq
        self.max_num_batched_tokens = max_num_batched_tokens
        self.chunk_width = chunk_width
        self.chunk_quantum = chunk_quantum
        self.slot_cache = slot_cache
        self.encoder_cache = encoder_cache
        # speculative lookahead: decodes reserve blocks for k extra
        # positions and cost 1 + k budget tokens (the verify row width).
        # max_context caps prompt+max_new at validation when the engine
        # widened the block tables past max_len to fit the lookahead.
        self.spec_tokens = spec_tokens
        self.max_context = (max_context if max_context is not None
                            else max_blocks_per_seq
                            * (bm.block_size if bm is not None else 0))
        self.enable_prefix_caching = enable_prefix_caching and bm is not None
        if prefill_pack < 1:
            raise ValueError(f"prefill_pack={prefill_pack} must be >= 1")
        self.prefill_pack = prefill_pack
        # host-swap preemption: active only when a cost model is supplied
        # AND the block manager actually has a host tier
        self.swap_cost = swap_cost
        # dense per-slot sampling state (sampling.SamplingBuffer): bound
        # at admission like the slot/encoder caches, rebuilt on re-bind
        # so recompute/swap-in replay penalties and stop rings exactly
        self.sampling_buffer = sampling_buffer
        self.waiting: deque[Request] = deque()
        self.running: dict[int, Request] = {}      # slot -> request
        self._join_order: list[int] = []           # slots, oldest first
        self.n_preemptions = 0
        self.n_swap_preemptions = 0
        self.n_swap_ins = 0
        self.n_aborts = 0
        self.host_hit_blocks = 0
        self.shared_hit_blocks = 0
        # copy pairs accumulated while building the current plan
        self._pending_swap_outs: list[tuple[int, int]] = []
        self._pending_swap_ins: list[tuple[int, int]] = []
        self._pending_shared_ins: list[tuple[int, int]] = []
        self.cache_hit_tokens = 0
        # prefill tokens lost to chunk_quantum rounding on a step's final
        # chunk (earlier chunks' remainders roll into the next chunk)
        self.quantum_dropped_tokens = 0
        # graceful-drain mode: in-flight work finishes, new submissions
        # are refused (the front-end flips this on shutdown)
        self.draining = False
        # front-end hook: called as on_admit(slot, req) whenever a request
        # moves waiting -> running (including preemption re-admissions)
        self.on_admit = None
        # add() time of each request not yet bound to a slot, and the
        # waits of those that were (their first binding only)
        self._queued_at: dict[int, float] = {}
        self.queue_wait = Histogram(SECONDS_BUCKETS)

    # -- queries ----------------------------------------------------------

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    @property
    def _swap_enabled(self) -> bool:
        return (self.swap_cost is not None and self.bm is not None
                and self.bm.num_host_blocks > 0)

    def free_slots(self) -> list[int]:
        return [s for s in range(self.max_batch) if s not in self.running]

    # -- submission -------------------------------------------------------

    def validate(self, req: Request) -> None:
        # A request's full horizon must fit its block-table row — reject at
        # submission instead of crashing mid-run when the table overflows.
        # (Single source of truth: admission relies on this having run.)
        # Slot-state caches are constant-size: no block horizon to check.
        if self.sampling_buffer is not None:
            self.sampling_buffer.validate(req)
        if self.bm is None:
            return
        horizon = len(req.prompt) + req.max_new
        capacity = self.max_context
        if horizon > capacity:
            raise ValueError(
                f"request {req.rid}: prompt+max_new = {horizon} tokens "
                f"exceeds max_len capacity {capacity}")

    def add(self, req: Request) -> None:
        if self.draining:
            raise RuntimeError(
                f"scheduler is draining: request {req.rid} refused "
                "(in-flight work finishes; no new admissions)")
        self.validate(req)
        if not req.out:
            self._queued_at[req.rid] = time.perf_counter()
        self.waiting.append(req)

    def drain(self) -> None:
        """Stop accepting new requests; everything already submitted
        (waiting or running) still runs to retirement. Idempotent."""
        self.draining = True

    # -- the budgeted step ------------------------------------------------

    def schedule(self) -> StepPlan:
        """Build one step's plan: decode capacity first (preempting the
        newest requests when the pool runs dry), then spend the leftover
        budget on up to ``prefill_pack`` prefill chunks — continuing
        in-flight prefills and admitting waiting requests (with
        prefix-cache sharing). All chunks of a step share one leftover
        budget and one ``chunk_width`` allowance, so packing never starves
        decodes harder than the single-chunk policy."""
        copies: list[tuple[int, int]] = []
        encodes: list[tuple[int, Request]] = []
        self._pending_swap_outs = []
        self._pending_swap_ins = []
        self._pending_shared_ins = []
        self._ensure_decode_capacity()
        decodes = [(s, r) for s, r in sorted(self.running.items())
                   if r.decode_ready]
        budget_left = self.max_num_batched_tokens \
            - len(decodes) * (1 + self.spec_tokens)

        chunks: list[tuple[int, Request, int]] = []
        admitted = 0
        pres = [(s, r) for s, r in sorted(self.running.items())
                if not r.decode_ready]
        while (len(pres) < self.prefill_pack and budget_left > 0
               and self.waiting and len(self.running) < self.max_batch):
            head = self.waiting[0]
            if (self.bm is not None and self.bm.is_swapped(head.rid)
                    and not self.bm.can_swap_in(head.rid)):
                break           # FCFS: wait for device blocks to free up
            slot, req = self._admit_one(copies, encodes)
            admitted += 1
            if not req.decode_ready:
                pres.append((slot, req))
                                        # else: full cache hit minus one —
                                        # it joins the decode batch next step
        width_left = self.chunk_width
        pending_q_loss = 0
        for slot, req in pres:
            if budget_left <= 0 or width_left <= 0:
                break
            remaining = req.context_len - req.num_computed
            if self.spec_tokens and req.out:
                # speculative preemption-recompute stops one token short:
                # the final token must be re-emitted by the verify step,
                # not resampled from the chunk row, so the rejection-
                # sampling windows stay aligned with the uninterrupted
                # run (a preemption only ever lands on a window boundary)
                # and temperature streams replay identically
                remaining -= 1
            want = min(budget_left, width_left, remaining)
            n = self._quantize(want, remaining)
            # remainder below one quantum: rolls into the next chunk's
            # budget (we only deduct n below); for the step's last chunk
            # there is no next chunk — it is accounted, not silently lost
            pending_q_loss = want - n
            if n > 0:
                n = self._quantize(self._fit_chunk(req, n), remaining)
            if n > 0:
                chunks.append((slot, req, n))
                budget_left -= n
                width_left -= n
        self.quantum_dropped_tokens += pending_q_loss
        plan = StepPlan(decodes=decodes, chunks=chunks, copies=copies,
                        admitted=admitted, encodes=encodes,
                        spec_tokens=self.spec_tokens,
                        swap_outs=self._pending_swap_outs,
                        swap_ins=self._pending_swap_ins,
                        shared_ins=self._pending_shared_ins)
        self._pending_swap_outs = []
        self._pending_swap_ins = []
        self._pending_shared_ins = []
        return plan

    def _quantize(self, n: int, remaining: int) -> int:
        """Round a non-final chunk down to the chunk quantum (SSM runners:
        the SSD inner chunk size, so chunked == monolithic bitwise). The
        final chunk of a prompt is exempt — SSD padding is an exact
        identity step there."""
        if self.chunk_quantum > 1 and n < remaining:
            return n // self.chunk_quantum * self.chunk_quantum
        return n

    def _ensure_decode_capacity(self) -> None:
        """Every decode-ready request must own blocks for context_len + 1
        (the token about to be written) plus ``spec_tokens`` lookahead
        positions the speculative verify row may write (rejected tail
        blocks are rolled back after the step via ``BlockManager.truncate``).
        Preempts newest requests until the survivors fit. Slot-state-only
        runners have constant-size state: decode can never run out of
        capacity."""
        if self.bm is None:
            return
        for slot in list(self._join_order):             # oldest first
            req = self.running.get(slot)
            if req is None or not req.decode_ready:
                continue
            horizon = req.context_len + 1 + self.spec_tokens
            while not self.bm.ensure(req.rid, horizon):
                victim_slot = self._pick_victim()       # newest running
                if victim_slot == slot and len(self.running) == 1 and \
                        self.bm.blocks_for(horizon) \
                        > self.bm.num_blocks - 1:
                    raise MemoryError(
                        f"block pool too small for request {req.rid} "
                        f"at {horizon} tokens")
                self._preempt(victim_slot)
                if victim_slot == slot:
                    break        # self-preempted: back to waiting, move on

    def _fit_chunk(self, req: Request, n: int) -> int:
        """Reserve blocks for the next ``n`` prefill tokens, shrinking the
        chunk to what the pool can actually cover. Admission never preempts
        running work — a starved chunk waits for decodes to retire."""
        if self.bm is None:
            return n                     # slot state: nothing to reserve
        avail = (len(self.bm.table(req.rid)) + self.bm.num_free) \
            * self.bm.block_size - req.num_computed
        n = min(n, avail)
        if n <= 0:
            if len(self.running) == 1:
                raise MemoryError(
                    f"block pool too small for request {req.rid} "
                    f"at {req.num_computed + 1} tokens")
            return 0
        ok = self.bm.ensure(req.rid, req.num_computed + n)
        assert ok, "ensure failed after availability check"
        return n

    def _admit_one(self, copies: list[tuple[int, int]],
                   encodes: list[tuple[int, Request]] | None = None) -> \
            tuple[int, Request]:
        """FCFS admission with prefix-cache sharing (paged kinds only).
        The new table starts as the matched cached blocks (refcounted);
        fresh blocks arrive chunk by chunk via ``_fit_chunk``. Slot-kind
        caches are bound to the chosen slot; enc-dec requests are queued
        for their admission-time encode pass."""
        req = self.waiting.popleft()
        with TraceAnnotation("serve.admit", prompt_tokens=req.context_len):
            return self._admit(req, copies, encodes)

    def _admit(self, req: Request, copies: list[tuple[int, int]],
               encodes: list[tuple[int, Request]] | None) -> \
            tuple[int, Request]:
        if self.bm is None:
            return self._bind_slot(req, encodes)
        if self.bm.is_swapped(req.rid):
            # swap-preempted victim returning: its KV rows come back from
            # the host tier byte-for-byte — num_computed survived the
            # eviction, so there is no recompute chunk at all (hashed
            # blocks whose device twin is still cached revive copy-free)
            _, pairs = self.bm.swap_in(req.rid)
            self._pending_swap_ins.extend(pairs)
            self.n_swap_ins += 1
            return self._bind_slot(req, encodes)
        bs = self.bm.block_size
        total = req.context_len
        hits: list[int] = []
        hashes: list = []
        if self.enable_prefix_caching:
            hashes = extend_chain_hashes(
                req.hash_chain, req.prefill_tokens(), bs)
            hits = self.bm.match(hashes)
        host_ext: list[int] = []
        if hashes and self._swap_enabled:
            # a swapped request's hashed blocks are findable by *other*
            # requests too: extend the device prefix with host-resident
            # blocks (copied in, not recomputed), capped by free blocks
            # left after adoption revives the cached-free device hits
            hh = self.bm.match_host(hashes)
            if len(hh) > len(hits):
                n_revived = sum(
                    1 for b in hits if self.bm.refcount(b) == 0)
                avail = max(0, self.bm.num_free - n_revived)
                host_ext = hh[len(hits):len(hits) + avail]
        shared_pairs: list[tuple[int, bytes]] = []
        if hashes and self.bm.shared is not None:
            # cross-replica extension: blocks another replica published
            # into the process-global index extend the prefix further
            # (copied from the shared host pool, not recomputed), again
            # capped by the free blocks left after revival + host copies
            n_local = len(hits) + len(host_ext)
            if n_local < len(hashes):
                n_revived = sum(
                    1 for b in hits if self.bm.refcount(b) == 0)
                avail = max(0, self.bm.num_free - n_revived
                            - len(host_ext))
                shared_pairs = self.bm.shared.acquire(
                    hashes[n_local:], limit=avail)
        n_cached = (len(hits) + len(host_ext) + len(shared_pairs)) * bs
        cow_idx = None
        if n_cached > total - 1:
            # Whole stream cached: recompute the last token for its logits.
            # Its KV write lands *inside* the final shared block — COW it,
            # or drop that hit when no spare block exists for the copy.
            # The copy target must still be free *after* adoption revives
            # the matched cached-free blocks out of the free list.
            # (When host_ext/shared_pairs is nonempty the final block is a
            # fresh copy with refcount 1 — always writable in place after
            # the deregister below, so no spare block is ever needed.)
            n_cached = total - 1
            cow_idx = n_cached // bs
            if not host_ext and not shared_pairs:
                n_revived = sum(
                    1 for b in hits if self.bm.refcount(b) == 0)
                if self.bm.refcount(hits[-1]) >= 1 \
                        and self.bm.num_free - n_revived < 1:
                    hits = hits[:-1]
                    n_cached = len(hits) * bs
                    cow_idx = None
        self.bm.adopt(req.rid, hits)
        if host_ext:
            _, pairs = self.bm.host_copy_in(
                req.rid, host_ext,
                hashes[len(hits):len(hits) + len(host_ext)])
            self._pending_swap_ins.extend(pairs)
            self.host_hit_blocks += len(host_ext)
        if shared_pairs:
            # same allocate-and-register path, sourced from the shared
            # pool; pairs stay pinned in the index until the engine's
            # h2d scatter lands (it releases them)
            _, pairs = self.bm.host_copy_in(
                req.rid, [s for s, _ in shared_pairs],
                [h for _, h in shared_pairs])
            self._pending_shared_ins.extend(pairs)
            self.shared_hit_blocks += len(shared_pairs)
        req.num_computed = n_cached
        req.n_published = (len(hits) + len(host_ext)
                           + len(shared_pairs))     # all registered
        self.cache_hit_tokens += n_cached
        if cow_idx is not None:
            src = self.bm.table(req.rid)[cow_idx]
            dst = self.bm.cow(req.rid, cow_idx)
            if dst is not None:
                copies.append((src, dst))
            else:
                # refcount was 1 (a revived cached block, or a fresh host
                # copy): the recompute will write its last position in
                # place, so pull it from the cache index — a concurrent
                # admission must not adopt a block with a pending write.
                # It re-registers via note_progress after the write.
                self.bm.deregister(src)
                req.n_published = cow_idx
        return self._bind_slot(req, encodes)

    def _bind_slot(self, req: Request,
                   encodes: list[tuple[int, Request]] | None) -> \
            tuple[int, Request]:
        slot = self.free_slots()[0]
        t_add = self._queued_at.pop(req.rid, None)
        if t_add is not None:
            self.queue_wait.observe(time.perf_counter() - t_add)
        self.running[slot] = req
        self._join_order.append(slot)
        if self.sampling_buffer is not None:
            self.sampling_buffer.bind(req, slot)
        if self.slot_cache is not None:
            self.slot_cache.allocate(req.rid, slot)
        if self.encoder_cache is not None:
            self.encoder_cache.allocate(req.rid, slot)
            if encodes is not None:
                encodes.append((slot, req))
        if self.on_admit is not None:
            self.on_admit(slot, req)
        return slot, req

    # -- progress / bookkeeping -------------------------------------------

    def note_progress(self, req: Request) -> None:
        """Publish content hashes for every block req has fully computed,
        making them shareable by later (or preempted-and-returning)
        requests. Called by the engine after each step, before retirement
        frees the blocks (freed blocks keep their hash)."""
        if not self.enable_prefix_caching or self.bm is None:
            return
        bs = self.bm.block_size
        n_full = req.num_computed // bs
        if n_full <= req.n_published:       # nothing newly full this step
            return
        table = self.bm.table(req.rid)
        hashes = extend_chain_hashes(req.hash_chain,
                                     req.prefill_tokens(), bs)
        for j in range(req.n_published, n_full):
            self.bm.register(table[j], hashes[j])
        req.n_published = n_full

    def _pick_victim(self) -> int | None:
        for slot in reversed(self._join_order):         # newest first
            if slot in self.running:
                return slot
        return None

    def _release(self, req: Request) -> None:
        if self.bm is not None:
            self.bm.free(req.rid)
        if self.slot_cache is not None:
            self.slot_cache.free(req.rid)
        if self.encoder_cache is not None:
            self.encoder_cache.free(req.rid)
        if self.sampling_buffer is not None:
            self.sampling_buffer.free(req.rid)

    def _preempt(self, slot: int) -> Request:
        """Evict one running request. With a host tier, the cost model
        picks swap (KV bytes move to pinned host memory; ``num_computed``
        survives) or recompute (blocks freed hash-retained; the prompt +
        generated tokens replay on re-admission) per victim."""
        req = self.running.pop(slot)
        self._join_order.remove(slot)
        if (self._swap_enabled and req.num_computed > 0
                and self.bm.can_swap_out(req.rid)
                and self.swap_cost.prefer_swap(
                    len(self.bm.table(req.rid)), req.num_computed)):
            self._pending_swap_outs.extend(self.bm.swap_out(req.rid))
            if self.slot_cache is not None:
                self.slot_cache.free(req.rid)
            if self.encoder_cache is not None:
                self.encoder_cache.free(req.rid)
            if self.sampling_buffer is not None:
                self.sampling_buffer.free(req.rid)
            self.n_swap_preemptions += 1
            # num_computed / n_published survive: the KV rows themselves
            # come back via swap_in, nothing is recomputed
        else:
            self._release(req)
            req.num_computed = 0
            req.n_published = 0     # re-admission gets a different table
        req.n_preempted += 1
        self.n_preemptions += 1
        self.waiting.appendleft(req)
        return req

    def abort(self, rid: int) -> bool:
        """Cancel a request wherever it currently lives: waiting (dropping
        any host-swapped KV), or running (blocks freed hash-retained, slot
        released). Returns False when the rid is unknown — already retired
        or never submitted — which the caller treats as a no-op."""
        for i, r in enumerate(self.waiting):
            if r.rid == rid:
                del self.waiting[i]
                self._queued_at.pop(rid, None)
                if self.bm is not None and self.bm.is_swapped(rid):
                    self.bm.swap_discard(rid)
                self.n_aborts += 1
                return True
        for slot, r in list(self.running.items()):
            if r.rid == rid:
                self.running.pop(slot)
                self._join_order.remove(slot)
                self._release(r)
                self.n_aborts += 1
                return True
        return False

    def retire(self, slot: int) -> Request:
        req = self.running.pop(slot)
        self._join_order.remove(slot)
        self._release(req)
        return req
