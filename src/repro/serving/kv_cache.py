"""Block-table KV-cache management for continuous-batching serving.

The device side is a pytree of page pools, one {"k","v"} pair per scanned
layer stack, each shaped ``(NP, num_blocks, K, block_size, hd)`` —
head-major pages, so one kv head's page is a whole ``(block_size, hd)``
tile that the Pallas kernels fetch as one block (``pool_shape``;
docs/kv-cache.md). Every layer uses the *same* block ids (one table per
sequence, all layers), so allocating a block grants one
``block_size``-token slice of KV capacity across the whole model at once.

The host side is ``BlockManager``: a refcounted allocator with per-request
block tables plus a content-hash index for prefix caching:

* **Refcounts** — a block may appear in several tables at once (shared
  prefix, fork). It returns to the free list only when its last reference
  drops.
* **Content hashes** — a *full* block's identity is the chained hash of
  every token from position 0 through its end, so equal hashes imply equal
  KV content (positions are absolute). ``register`` publishes a full
  block; ``match`` resolves the longest cached prefix of a token stream.
  Freed blocks keep their hash (their pages are never written while free),
  so a later request can *revive* them from the free list — prefix hits
  survive retirement and preemption.
* **Copy-on-write** — a request must never write into a block another
  table can read. ``cow`` swaps a shared table entry for a fresh block and
  tells the caller which device page to copy.
* **Truncate** — ``truncate`` rewinds a table's tail (free semantics,
  hash retained): the speculative-decoding rollback for lookahead blocks
  whose draft tokens were rejected (docs/kv-cache.md, docs/speculative.md).

Block 0 is reserved as the *trash block* — idle serving slots carry
all-zero table rows, so the decode step's unconditional KV write for an
inactive slot lands there and corrupts nothing.

On a tensor-parallel mesh the pools shard over the "model" axis by whole
kv heads (``spmd.sharding.paged_pool_pspec``); block ids index pool rows
on *every* shard at once, so nothing in this module — tables, refcounts,
content hashes, free lists, truncate — ever sees the mesh. The
mesh-invariance walks in tests/test_serving_tp.py pin that property.
"""

from __future__ import annotations

import hashlib
import threading
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models import quant
from repro.models.transformer import period_structure

TRASH_BLOCK = 0

_HASH_SEED = b"repro-paged-kv-v1"


def extend_chain_hashes(chain: list[bytes], tokens,
                        block_size: int) -> list[bytes]:
    """Extend ``chain`` in place with hashes for every *full* block of
    ``tokens`` not yet covered — the chain only ever grows (a request's
    token stream is append-only), so callers cache it and each new block
    costs one sha256 instead of re-hashing from position 0."""
    h = chain[-1] if chain else hashlib.sha256(_HASH_SEED).digest()
    for i in range(len(chain), len(tokens) // block_size):
        blk = np.asarray(tokens[i * block_size:(i + 1) * block_size],
                         np.int32).tobytes()
        h = hashlib.sha256(h + blk).digest()
        chain.append(h)
    return chain


def chain_block_hashes(tokens, block_size: int) -> list[bytes]:
    """Chained content hashes for every *full* block of ``tokens``.

    ``h_i`` covers tokens ``[0, (i+1) * block_size)`` — a match on ``h_i``
    implies the whole prefix matches, so a single dict lookup per block
    resolves prefix sharing. sha256 over the token bytes (not Python
    ``hash``): adopting a colliding block would silently splice another
    request's KV into a new table, so collisions must be cryptographically
    improbable.
    """
    return extend_chain_hashes([], tokens, block_size)


def attn_layer_stacks(cfg: ModelConfig) -> list[str]:
    """Names of the scanned cache sub-stacks that hold attention KV."""
    kinds, _ = period_structure(cfg)
    out = [f"sub{i}" for i, k in enumerate(kinds) if k != "mamba"]
    if cfg.shared_attn_period:
        out.append("shared")
    return out


def mamba_layer_stacks(cfg: ModelConfig) -> list[str]:
    """Names of the scanned cache sub-stacks holding per-slot SSM state."""
    kinds, _ = period_structure(cfg)
    return [f"sub{i}" for i, k in enumerate(kinds) if k == "mamba"]


def pool_shape(n_layers: int, num_blocks: int, block_size: int,
               cfg: ModelConfig, width: int | None = None) -> tuple:
    """Shape of one layer-stacked page pool: ``(n_layers, num_blocks, K,
    block_size, width)`` with width = head_dim for value pools, 1 for the
    per-row scale pools of a quantized cache. Block ids index axis 1 and
    kv heads axis 2 for every pool leaf."""
    return (n_layers, num_blocks, cfg.num_kv_heads, block_size,
            cfg.head_dim if width is None else width)


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, kv_dtype: str = "bf16"):
    """Zero page pools matching ``transformer.decode_step_paged``.

    Covers the *attention* stacks only; mamba stacks carry constant-size
    per-slot state (``serving.cache.init_slot_state``) rather than paged
    KV — a hybrid model's serving cache is the union of both.

    With a quantized ``kv_dtype`` ("int8" / "fp8") the k/v leaves store
    the narrow dtype and each stack gains fp32 ``k_scale`` / ``v_scale``
    leaves shaped ``(NP, num_blocks, K, block_size, 1)`` — same rank and
    block axis as the pools, so block-indexed copy/COW/swap helpers
    handle value and scale leaves uniformly (docs/kv-cache.md)."""
    kinds, NP = period_structure(cfg)
    shape = pool_shape(NP, num_blocks, block_size, cfg)
    if quant.is_quantized(kv_dtype):
        dtype = quant.KV_DTYPES[kv_dtype]
    sshape = pool_shape(NP, num_blocks, block_size, cfg, width=1)

    def stack():
        c = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if quant.is_quantized(kv_dtype):
            c["k_scale"] = jnp.zeros(sshape, jnp.float32)
            c["v_scale"] = jnp.zeros(sshape, jnp.float32)
        return c

    cache = {}
    for i, kind in enumerate(kinds):
        if kind == "mamba":
            continue
        cache[f"sub{i}"] = stack()
    if cfg.shared_attn_period:
        cache["shared"] = stack()
    return cache


def block_bytes(cfg: ModelConfig, block_size: int, dtype_bytes: int = 2,
                tp: int = 1, kv_dtype: str = "bf16"):
    """HBM bytes one block id costs across every layer's k+v pools.

    ``tp`` > 1 gives the *per-shard* cost on a kv-head-sharded mesh
    (docs/multi-host.md): each model shard holds num_kv_heads/tp heads of
    every page, so a block's footprint divides exactly — the accounting
    the mesh-invariance walks pin. Requires tp to divide num_kv_heads
    (the engine validates via ``spmd.sharding.paged_pool_pspec``).

    A quantized ``kv_dtype`` narrows the per-element cost and adds the
    fp32 per-row scale leaves (4 bytes per (token, head) row)."""
    kinds, NP = period_structure(cfg)
    n_stacks = len(attn_layer_stacks(cfg))
    if cfg.num_kv_heads % tp != 0:
        raise ValueError(
            f"num_kv_heads={cfg.num_kv_heads} is not divisible by tp={tp}"
            " (see spmd.sharding.paged_pool_pspec)")
    row_bytes = cfg.head_dim * dtype_bytes
    if quant.is_quantized(kv_dtype):
        row_bytes = cfg.head_dim * quant.kv_dtype_bytes(kv_dtype) + 4
    return (2 * NP * n_stacks * block_size * (cfg.num_kv_heads // tp)
            * row_bytes)


class SharedPrefixIndex:
    """Process-global content-hash index + pinned host payload pool shared
    by every replica's :class:`BlockManager` (docs/multi-host.md §DP).

    The per-replica prefix cache maps ``hash -> device block``; block ids
    are meaningless outside their replica, so cross-replica sharing needs
    a payload medium. This index owns a pool of *host* slots (one slot =
    one block's pages across every layer, same layout as the PR-8 swap
    tier) plus a ``hash -> slot`` map. Replicas **publish**: after a full
    block's hash is registered locally, the engine reserves a slot,
    d2h-gathers the block's pages into the shared pool, and commits the
    hash. Any replica's admission then **adopts**: ``acquire`` resolves
    the longest cached prefix to (slot, hash) pairs, the adopting
    ``BlockManager.host_copy_in`` allocates fresh device blocks, and the
    engine h2d-scatters the shared payload — exactly the existing host
    prefix-hit path, pointed at the shared pool.

    Locking rules (every mutator takes ``self._lock``; replicas run on
    separate step-loop threads):

    * a **reserved** slot (publish in flight) is invisible to ``acquire``
      and immune to eviction until ``commit`` or ``abandon``;
    * an **acquired** slot is pinned until ``release`` (after the h2d
      copy lands), so no adopted block's payload can be evicted or
      rewritten under a pending copy;
    * eviction (pool full on ``reserve``) takes the least-recently-used
      unpinned committed slot; acquire refreshes recency.

    Byte identity needs none of this to be deterministic: adopted KV is a
    pure function of the token prefix (the prefix-caching qualification),
    so a racing miss just recomputes the same bytes. The lock protects
    *bookkeeping*, not output equivalence.
    """

    def __init__(self, num_slots: int):
        assert num_slots >= 1
        self.num_slots = num_slots
        self._lock = threading.Lock()
        self._free = list(range(num_slots - 1, -1, -1))
        self._slot_of: dict[bytes, int] = {}   # hash -> committed slot
        self._hash_of: dict[int, bytes] = {}   # committed slot -> hash
        self._reserved: set[int] = set()       # publish in flight
        self._pins: dict[int, int] = {}        # slot -> acquire count
        self._order: list[int] = []            # committed slots, LRU first
        # pinned numpy payload pool, one array per paged cache leaf
        # (attach_pool; allocated once by the first replica's engine)
        self.pool: list[np.ndarray] = []
        self._pool_key = None
        self.published_blocks = 0
        self.adopted_blocks = 0
        self.evicted_blocks = 0

    # -- payload pool ------------------------------------------------------

    def attach_pool(self, leaf_shapes: list[tuple[tuple, object]]) -> None:
        """Allocate the shared host pool: one ``(num_slots,) + tail`` array
        per paged cache leaf (tail excludes the per-replica num_blocks
        axis, so replicas with different pool sizes still share). First
        replica allocates; later replicas must present the same layout."""
        key = tuple((tuple(shape), np.dtype(dt).str)
                    for shape, dt in leaf_shapes)
        with self._lock:
            if self._pool_key is not None:
                if key != self._pool_key:
                    raise ValueError(
                        "shared prefix pool layout mismatch across "
                        f"replicas: {key} != {self._pool_key}")
                return
            self._pool_key = key
            self.pool = [np.zeros((self.num_slots,) + tuple(shape), dt)
                         for shape, dt in leaf_shapes]

    # -- publish (writer side) ---------------------------------------------

    def contains(self, h: bytes) -> bool:
        with self._lock:
            return h in self._slot_of

    def reserve(self, h: bytes) -> int | None:
        """Claim a slot for publishing ``h``. None when the hash is
        already committed or no slot can be freed (all pinned/reserved).
        The caller copies the payload in, then ``commit``s."""
        with self._lock:
            if h in self._slot_of:
                return None
            if not self._free:
                victim = next((s for s in self._order
                               if not self._pins.get(s)), None)
                if victim is None:
                    return None
                self._evict_locked(victim)
            s = self._free.pop()
            self._reserved.add(s)
            return s

    def commit(self, slot: int, h: bytes) -> None:
        with self._lock:
            assert slot in self._reserved, slot
            self._reserved.discard(slot)
            if h in self._slot_of:
                # two replicas raced the same hash through reserve (the
                # register-time dedup is only best-effort); first commit
                # wins, the loser's copy is dropped
                self._free.append(slot)
                return
            self._slot_of[h] = slot
            self._hash_of[slot] = h
            self._order.append(slot)
            self.published_blocks += 1

    def abandon(self, slot: int) -> None:
        """Return a reserved slot unused (publish aborted)."""
        with self._lock:
            assert slot in self._reserved, slot
            self._reserved.discard(slot)
            self._free.append(slot)

    def _evict_locked(self, slot: int) -> None:
        self._order.remove(slot)
        h = self._hash_of.pop(slot)
        del self._slot_of[h]
        self._free.append(slot)
        self.evicted_blocks += 1

    # -- adopt (reader side) -----------------------------------------------

    def acquire(self, hashes: list[bytes],
                limit: int | None = None) -> list[tuple[int, bytes]]:
        """Longest prefix of ``hashes`` resolving to committed slots, each
        pinned against eviction until ``release``. ``limit`` caps the
        match (the adopter's free-block budget)."""
        out: list[tuple[int, bytes]] = []
        with self._lock:
            for h in hashes if limit is None else hashes[:max(limit, 0)]:
                s = self._slot_of.get(h)
                if s is None:
                    break
                self._pins[s] = self._pins.get(s, 0) + 1
                self._order.remove(s)          # refresh recency (MRU)
                self._order.append(s)
                out.append((s, h))
            self.adopted_blocks += len(out)
        return out

    def release(self, slots: list[int]) -> None:
        """Unpin after the adopter's h2d copies have landed."""
        with self._lock:
            for s in slots:
                n = self._pins[s] - 1
                if n:
                    self._pins[s] = n
                else:
                    del self._pins[s]

    # -- audit -------------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            return {"slots": self.num_slots,
                    "committed": len(self._slot_of),
                    "pinned": len(self._pins),
                    "published_blocks": self.published_blocks,
                    "adopted_blocks": self.adopted_blocks,
                    "evicted_blocks": self.evicted_blocks}

    def check(self) -> None:
        """Invariants: slot partition exact, maps mutually consistent,
        pins only on committed (payload-bearing) slots — i.e. no adopted
        block can outlive its payload."""
        with self._lock:
            committed = set(self._hash_of)
            free = set(self._free)
            assert len(free) == len(self._free), "free list duplicates"
            assert not (free & committed), "free slot holds a hash"
            assert not (free & self._reserved), "free slot is reserved"
            assert not (self._reserved & committed), "reserved committed"
            assert len(free) + len(committed) + len(self._reserved) \
                == self.num_slots, "slots lost"
            assert sorted(self._order) == sorted(committed), "order drift"
            for h, s in self._slot_of.items():
                assert self._hash_of.get(s) == h, "hash maps disagree"
            assert len(self._slot_of) == len(self._hash_of)
            for s, n in self._pins.items():
                assert n > 0, (s, n)
                assert s in committed, f"pin on a payload-less slot {s}"


@dataclass
class CacheStats:
    num_blocks: int          # allocatable blocks (excludes the trash block)
    blocks_in_use: int       # distinct blocks with refcount > 0
    num_tables: int
    shared_blocks: int = 0   # blocks with refcount >= 2
    cached_free: int = 0     # free blocks still holding a registered hash

    @property
    def utilization(self) -> float:
        return self.blocks_in_use / max(self.num_blocks, 1)


class BlockManager:
    """Refcounted free-list allocator over page-pool rows + block tables.

    Pure host-side bookkeeping: allocation never touches device memory
    (pages are preallocated); it only decides which pool rows a request's
    tokens may occupy. The one device-side consequence is ``cow``, which
    returns the page copy the *caller* must perform.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 num_host_blocks: int = 0,
                 shared_index: SharedPrefixIndex | None = None):
        assert num_blocks >= 2 and block_size >= 1
        self.num_blocks = num_blocks
        self.block_size = block_size
        # Cross-replica prefix sharing: registered hashes are queued for
        # publication into the process-global index (the engine drains the
        # queue and d2h-copies the payloads at step boundaries).
        self.shared = shared_index
        self._publish_q: list[tuple[int, bytes]] = []
        # LIFO free list: recently-freed (cache-warm) blocks are reused first
        self._free = list(range(num_blocks - 1, TRASH_BLOCK, -1))
        self._tables: dict[int, list[int]] = {}
        self._ref: dict[int, int] = {}        # block -> refcount (> 0 only)
        self._hash_of: dict[int, bytes] = {}  # block -> content hash
        self._block_of: dict[bytes, int] = {}  # content hash -> block
        # Host tier (swap-preemption): slots in a pinned host pool, one
        # slot holding one block's pages across every layer. A swapped
        # request owns its slots exclusively until swap_in/swap_discard.
        self.num_host_blocks = num_host_blocks
        self._host_free = list(range(num_host_blocks - 1, -1, -1))
        self._swapped: dict[int, list[int]] = {}      # rid -> host slots
        self._host_hash_of: dict[int, bytes] = {}     # slot -> content hash
        self._host_block_of: dict[bytes, int] = {}    # content hash -> slot

    # -- queries ----------------------------------------------------------

    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    @property
    def num_free(self) -> int:
        return len(self._free)

    def can_allocate(self, n_tokens: int) -> bool:
        return self.blocks_for(n_tokens) <= self.num_free

    def table(self, rid: int) -> list[int]:
        return list(self._tables[rid])

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def stats(self) -> CacheStats:
        return CacheStats(
            num_blocks=self.num_blocks - 1,
            blocks_in_use=len(self._ref),
            num_tables=len(self._tables),
            shared_blocks=sum(1 for r in self._ref.values() if r >= 2),
            cached_free=sum(1 for b in self._free if b in self._hash_of))

    # -- prefix-cache index -----------------------------------------------

    def register(self, block: int, h: bytes) -> None:
        """Publish a *full* block's content hash so later requests can share
        it. First writer wins; re-registration is a no-op."""
        assert block != TRASH_BLOCK
        if h in self._block_of or block in self._hash_of:
            return
        self._hash_of[block] = h
        self._block_of[h] = block
        if self.shared is not None and not self.shared.contains(h):
            self._publish_q.append((block, h))

    def match(self, hashes: list[bytes]) -> list[int]:
        """Longest prefix of ``hashes`` resolving to cached blocks."""
        out = []
        for h in hashes:
            b = self._block_of.get(h)
            if b is None:
                break
            out.append(b)
        return out

    def deregister(self, block: int) -> None:
        """Withdraw a block from the prefix cache before (re)writing it in
        place — e.g. the final block of a full-prompt hit adopted with
        refcount 1, whose last position is about to be recomputed. Leaving
        it registered would let a concurrent admission adopt a block that
        still has a pending write."""
        self._deregister(block)

    def _deregister(self, block: int) -> None:
        h = self._hash_of.pop(block, None)
        if h is not None:
            del self._block_of[h]

    def drain_publishable(self) -> list[tuple[int, bytes]]:
        """Queued (block, hash) registrations still current — i.e. the
        block still carries that hash in the local index, so its pages
        hold exactly the hashed content. Stale entries (deregistered for
        an in-place write, or evicted and rewritten since registration)
        are dropped. The caller d2h-copies survivors into the shared
        index. Clears the queue."""
        out = [(b, h) for b, h in self._publish_q
               if self._hash_of.get(b) == h]
        self._publish_q.clear()
        return out

    def _pop_free(self) -> int:
        """Take a free block for new content. Prefer blocks with no cached
        hash (LIFO — recently freed, cache-warm on device) so prefix-cache
        entries survive as long as possible; when only cached blocks
        remain, evict the *least recently freed* (front of the list) so
        the warmest entries — e.g. a preemption victim's just-freed
        blocks, which its recompute is about to re-adopt — go last."""
        for i in range(len(self._free) - 1, -1, -1):
            if self._free[i] not in self._hash_of:
                return self._free.pop(i)
        b = self._free.pop(0)
        self._deregister(b)          # its content is about to be rewritten
        return b

    # -- mutations --------------------------------------------------------

    def allocate(self, rid: int, n_tokens: int) -> list[int]:
        """Fresh table covering n_tokens. Raises KeyError on double-alloc,
        MemoryError when the pool can't cover it (caller admits later)."""
        if rid in self._tables:
            raise KeyError(f"request {rid} already has a table")
        n = self.blocks_for(n_tokens)
        if n > self.num_free:
            raise MemoryError(f"need {n} blocks, have {self.num_free}")
        self._tables[rid] = t = []
        for _ in range(n):
            b = self._pop_free()
            self._ref[b] = 1
            t.append(b)
        return self.table(rid)

    def adopt(self, rid: int, blocks: list[int]) -> list[int]:
        """Start rid's table from already-populated (cached/shared) blocks:
        refcount each, reviving any that sit in the free list."""
        if rid in self._tables:
            raise KeyError(f"request {rid} already has a table")
        t = []
        for b in blocks:
            assert b != TRASH_BLOCK
            if self._ref.get(b, 0) == 0:
                self._free.remove(b)          # revive a cached free block
            self._ref[b] = self._ref.get(b, 0) + 1
            t.append(b)
        self._tables[rid] = t
        return self.table(rid)

    def fork(self, src_rid: int, dst_rid: int) -> list[int]:
        """dst shares every block of src (refcount++). Writers must go
        through ``cow`` before touching a shared block."""
        if dst_rid in self._tables:
            raise KeyError(f"request {dst_rid} already has a table")
        t = list(self._tables[src_rid])
        for b in t:
            self._ref[b] += 1
        self._tables[dst_rid] = t
        return self.table(dst_rid)

    def ensure(self, rid: int, n_tokens: int) -> bool:
        """Grow rid's table to cover n_tokens. False (no change) on OOM —
        the caller preempts somebody and retries."""
        t = self._tables[rid]
        need = self.blocks_for(n_tokens) - len(t)
        if need <= 0:
            return True
        if need > self.num_free:
            return False
        for _ in range(need):
            b = self._pop_free()
            self._ref[b] = 1
            t.append(b)
        return True

    def cow(self, rid: int, idx: int) -> int | None:
        """Make table slot ``idx`` exclusively owned before a write.

        Shared (refcount >= 2) -> swap in a fresh block and return its id;
        the caller must copy the old block's pages into it. Exclusive ->
        None (write in place). Raises MemoryError when no block is free."""
        t = self._tables[rid]
        old = t[idx]
        if self._ref[old] <= 1:
            return None
        if not self._free:
            raise MemoryError("copy-on-write needs a free block")
        new = self._pop_free()
        self._ref[old] -= 1
        self._ref[new] = 1
        t[idx] = new
        return new

    def truncate(self, rid: int, n_tokens: int) -> list[int]:
        """Rewind rid's table to cover only ``n_tokens``, freeing the tail.

        The speculative-decoding rollback: a verify step reserves blocks
        for up to k+1 lookahead positions; when fewer draft tokens are
        accepted the tail blocks past the surviving context are returned
        to the pool. Dropped blocks follow ``free`` semantics — refcount
        decremented, content hash retained while on the free list (the
        engine only ever truncates past ``num_computed``, so a dropped
        block is never one whose hash was published for *this* request's
        stream). Returns the freed block ids (newest first)."""
        t = self._tables[rid]
        keep = self.blocks_for(max(n_tokens, 0))
        dropped = []
        while len(t) > keep:
            b = t.pop()
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._free.append(b)
            dropped.append(b)
        return dropped

    def free(self, rid: int) -> None:
        """Drop rid's references. Blocks keep their content hash while on
        the free list (pages aren't written while free), so they stay
        matchable until ``_pop_free`` hands them out for new content."""
        for b in self._tables.pop(rid):
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._free.append(b)

    # -- host tier (swap-preemption) --------------------------------------

    def is_swapped(self, rid: int) -> bool:
        return rid in self._swapped

    @property
    def num_host_free(self) -> int:
        return len(self._host_free)

    def can_swap_out(self, rid: int) -> bool:
        return len(self._tables.get(rid, ())) <= len(self._host_free)

    def swap_out(self, rid: int) -> list[tuple[int, int]]:
        """Move rid's table to host slots. Returns the (device_block,
        host_slot) copy pairs the *caller* must perform — on the pre-step
        pool contents, before anything in the same step can rewrite a
        freed block (the engine issues the d2h gather first, then lets it
        overlap the jitted step). Device blocks follow ``free`` semantics
        (hash retained while on the free list), so a quick swap-in can
        revive them without any copy at all; hashed blocks also publish
        into the host index so *other* requests' admissions can
        prefix-hit swapped content (``match_host``)."""
        t = self._tables.pop(rid)
        pairs = []
        slots = []
        for b in t:
            s = self._host_free.pop()
            pairs.append((b, s))
            slots.append(s)
            h = self._hash_of.get(b)
            if h is not None and h not in self._host_block_of:
                self._host_hash_of[s] = h
                self._host_block_of[h] = s
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._free.append(b)
        self._swapped[rid] = slots
        return pairs

    def can_swap_in(self, rid: int) -> bool:
        # Worst case every slot needs a fresh device block; hashed slots
        # whose device twin survived on the free list revive for free.
        return len(self._swapped.get(rid, ())) <= self.num_free

    def swap_in(self, rid: int) -> tuple[list[int], list[tuple[int, int]]]:
        """Rebuild rid's device table from its host slots. Returns
        (table, copy_pairs) where copy_pairs is the (host_slot,
        device_block) h2d copies the caller must perform *before* the
        step computes over them. A hashed slot whose original device
        block still sits on the free list (hash intact — pages are never
        written while free) is revived in place with no copy."""
        if rid in self._tables:
            raise KeyError(f"request {rid} already has a table")
        slots = self._swapped.pop(rid)
        pairs = []
        t = []
        for s in slots:
            h = self._host_hash_of.pop(s, None)
            if h is not None and self._host_block_of.get(h) == s:
                del self._host_block_of[h]
            b = self._block_of.get(h) if h is not None else None
            if b is not None:
                # device twin survived: revive, no copy
                if self._ref.get(b, 0) == 0:
                    self._free.remove(b)
                self._ref[b] = self._ref.get(b, 0) + 1
            else:
                b = self._pop_free()
                self._ref[b] = 1
                pairs.append((s, b))
                if h is not None:
                    self.register(b, h)
            t.append(b)
            self._host_free.append(s)
        self._tables[rid] = t
        return self.table(rid), pairs

    def swap_discard(self, rid: int) -> None:
        """Drop a swapped-out request's host slots without copying back
        (abort while swapped). Host hashes go with the slots — unlike the
        device free list there is no in-place revival of a freed slot."""
        for s in self._swapped.pop(rid):
            h = self._host_hash_of.pop(s, None)
            if h is not None and self._host_block_of.get(h) == s:
                del self._host_block_of[h]
            self._host_free.append(s)

    def match_host(self, hashes: list[bytes]) -> list[int]:
        """Longest prefix of ``hashes`` resolving to *host* slots — used
        by admission after the device index runs dry, so a prefix that
        only survives swapped-out is copied back instead of recomputed."""
        out = []
        for h in hashes:
            s = self._host_block_of.get(h)
            if s is None:
                break
            out.append(s)
        return out

    def host_copy_in(self, rid: int, slots: list[int],
                     hashes: list[bytes]) -> tuple[list[int],
                                                   list[tuple[int, int]]]:
        """Non-destructive host prefix hit: copy ``slots`` (still owned
        by their swapped-out request) into freshly allocated device
        blocks appended to rid's table (created if absent — admission
        adopts the device-hit prefix first, then extends it from here),
        registering ``hashes`` on the new blocks. Returns (blocks,
        (host_slot, device_block) copy pairs)."""
        if len(slots) > self.num_free:
            raise MemoryError(
                f"need {len(slots)} blocks, have {self.num_free}")
        t = self._tables.setdefault(rid, [])
        pairs = []
        for s, h in zip(slots, hashes):
            b = self._pop_free()
            self._ref[b] = 1
            t.append(b)
            pairs.append((s, b))
            self.register(b, h)
        return self.table(rid), pairs

    def check(self) -> None:
        """Invariants: refcounts == table references, free list exact,
        hash index consistent, no trash block anywhere."""
        counts: dict[int, int] = {}
        for rid, t in self._tables.items():
            assert len(set(t)) == len(t), f"table {rid} repeats a block"
            for b in t:
                assert b != TRASH_BLOCK, (rid, t)
                counts[b] = counts.get(b, 0) + 1
        assert counts == self._ref, "refcounts drifted from table refs"
        free_set = set(self._free)
        assert len(free_set) == len(self._free), "free list duplicates"
        assert not (free_set & set(self._ref)), "free list overlaps tables"
        assert len(self._ref) + len(self._free) == self.num_blocks - 1
        for b, h in self._hash_of.items():
            assert b != TRASH_BLOCK
            assert self._block_of.get(h) == b, "hash maps disagree"
        assert len(self._block_of) == len(self._hash_of)
        # host tier
        owned = [s for slots in self._swapped.values() for s in slots]
        assert len(set(owned)) == len(owned), "host slot double-owned"
        host_free = set(self._host_free)
        assert len(host_free) == len(self._host_free), "host free dups"
        assert not (host_free & set(owned)), "host free overlaps swapped"
        assert len(owned) + len(self._host_free) == self.num_host_blocks
        for s, h in self._host_hash_of.items():
            assert s not in host_free, "hashed host slot is free"
            assert self._host_block_of.get(h) == s, "host hash disagree"
        assert len(self._host_block_of) == len(self._host_hash_of)
