"""Per-family model runners for the serving engine.

A :class:`ModelRunner` owns everything family-specific about serving one
model: which cache kinds it needs (paged KV blocks / per-slot SSM state /
read-only encoder state), how to build the zero device cache, and the
jitted budgeted step — one prefill chunk plus the wide decode batch plus
per-slot sampling. ``InferenceEngine`` and the ``Scheduler`` see only the
runner's declared cache needs and its step/encode callables, so admitting
a Mamba request and a transformer request is the same control flow.

Runners:

* :class:`TransformerRunner` — decoder-only attention models (paged KV).
* :class:`SSMRunner` — pure Mamba2 (slot state only; no block horizon).
* :class:`HybridRunner` — zamba2's interleaved mamba + shared attention
  (slot state for the mamba stacks, paged KV for the attention stacks,
  one block table spanning the attention layers).
* :class:`EncDecRunner` — whisper (paged decoder self-KV + per-slot
  read-only cross K/V written by an encode pass at admission).
* :class:`SpeculativeRunner` — draft-and-verify speculative decoding
  over two TransformerRunners (one shared block table indexing a target
  and a draft page-pool set; greedy byte-identical to plain decode).

The step functions are shape-stable: decode always runs ``max_batch``
wide (idle slots masked; their KV writes land in the trash block, their
slot-state rows are reverted after the step), the chunk always runs at
``chunk_width``. Sampling row B is the chunk's last-token logits.

Invariants every runner upholds (the engine equivalence tests pin them):

* an idle decode slot never corrupts state — paged writes land in the
  trash block, slot-state rows are reverted via the ``d_active`` mask;
* a chunk that starts a (re)computed sequence reads zeroed slot state,
  never a previous occupant's;
* token KV/state is identical whether produced by monolithic prefill, a
  chunk, or a decode step (the shared rounding convention — see
  docs/kernels.md), which is what makes chunked prefill, preemption-
  recompute, prefix-cache adoption and greedy speculative decode all
  byte-identical to the plain path;
* runners are mesh-oblivious: tensor parallelism enters only through the
  engine's cache/param placement and the shard_map'd paged-attention
  core (docs/multi-host.md), so a runner's step is byte-identical on
  every mesh shape — the TP equivalence suite pins this per family.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ParallelConfig
from repro.models import encdec, transformer
from repro.serving.cache import init_encoder_cache, init_slot_state
from repro.serving.kv_cache import (init_paged_cache, attn_layer_stacks,
                                    mamba_layer_stacks, pool_shape)
from repro.serving.sampling import (SP_KEYS, propose_tokens,
                                    propose_tokens_full, sample_tokens,
                                    sample_tokens_full, speculative_verify,
                                    speculative_verify_full)

__all__ = ["ModelRunner", "TransformerRunner", "SSMRunner", "HybridRunner",
           "EncDecRunner", "SpeculativeRunner", "make_runner"]


def _slice_slot(tree, slot):
    """Gather one slot row (axis 1 after the layer-stack dim) -> width 1."""
    return jax.tree.map(
        lambda t: jax.lax.dynamic_slice_in_dim(t, slot, 1, axis=1), tree)


def _scatter_slot(full, row, slot):
    """Write a width-1 slot row back (inverse of ``_slice_slot``)."""
    return jax.tree.map(
        lambda f, r: jax.lax.dynamic_update_slice_in_dim(
            f, r.astype(f.dtype), slot, axis=1), full, row)


def _mask_slot_rows(new, old, active):
    """Keep updated state only for active decode slots; idle slots must
    not have their state corrupted by the masked wide-batch compute."""
    def leaf(n, o):
        m = active.reshape((1, -1) + (1,) * (n.ndim - 2))
        return jnp.where(m, n, o)
    return jax.tree.map(leaf, new, old)


class ModelRunner:
    """Family-agnostic interface the engine/scheduler program against."""

    needs_blocks: bool = False        # paged KV pools + block tables
    needs_slots: bool = False         # constant-size per-slot SSM state
    needs_encoder: bool = False       # read-only per-slot cross K/V
    supports_prefix_caching: bool = False
    # can consume multi-chunk (ragged packed-prefill) plans: several
    # prompts' chunks ride one flat token batch per step. SSM/enc-dec
    # runners stay single-chunk (recurrent state and cross-KV slot rows
    # are sliced per chunk sequence, which the flat layout doesn't carry).
    supports_packed_prefill: bool = False
    chunk_quantum: int = 1            # chunk lengths must be multiples
                                      # (except a prompt's final chunk)
    spec_tokens: int = 0              # draft tokens per slot per step
                                      # (speculative decoding lookahead)
    max_logprobs: int = 8             # top-L logprob rows the full path
                                      # returns (engine knob, set at init)
    counts_experts: bool = False      # step returns ((sampled, int32 (2,)
                                      # expert counts), cache): rows
                                      # computed on held experts, routed
                                      # assignments, summed over layers

    def __init__(self, cfg: ModelConfig, pcfg: ParallelConfig):
        self.cfg, self.pcfg = cfg, pcfg

    def init_cache(self, num_blocks: int, block_size: int, max_batch: int,
                   kv_dtype: str = "bf16"):
        raise NotImplementedError

    def step(self, params, cache, a, *, has_chunk: bool,
             full_sampling: bool = False):
        """One budgeted step. ``a`` is the engine's array dict (chunk row,
        decode batch, sampling params). Returns (sampled (B+1,), cache);
        with ``full_sampling`` the sampled half is ``(tokens, logprobs)``
        from the full pipeline. Like ``has_chunk``, ``full_sampling`` is
        a *static* jit flag: pure-greedy traffic only ever compiles the
        plain executables and never traces the penalty/top-p/logprob
        work."""
        raise NotImplementedError

    def encode(self, params, cache, slot, frames):
        """Admission-time encode pass (enc-dec only)."""
        raise NotImplementedError

    # -- shared step halves ------------------------------------------------

    def _sample(self, logits_d, logits_c, a, has_chunk,
                full_sampling=False):
        if not has_chunk:
            # sampling rows B.. are sized for the engine's prefill_pack
            # (1 for classic single-chunk, S for the ragged packed path)
            n_extra = a["temps"].shape[0] - logits_d.shape[0]
            logits_c = jnp.zeros((n_extra,) + logits_d.shape[1:],
                                 logits_d.dtype)
        logits = jnp.concatenate([logits_d, logits_c], axis=0)
        if full_sampling:
            return sample_tokens_full(logits, {k: a[k] for k in SP_KEYS},
                                      max_logprobs=self.max_logprobs)
        return sample_tokens(logits, a["temps"], a["top_ks"], a["seeds"],
                             a["rids"], a["counters"])

    @staticmethod
    def _chunk_batch(a):
        return {"tokens": a["c_tok"], "q_start": a["c_start"],
                "q_lens": a["c_len"], "block_tables": a["c_table"],
                "ctx_lens": a["c_start"] + a["c_len"]}

    @staticmethod
    def _ragged_batch(a):
        """Packed multi-chunk prefill batch (``prefill_pack > 1``): one
        flat (1, C) token row carrying several sequences' chunks, each
        owning flat positions [starts[s], ends[s])."""
        return {"tokens": a["c_tok"], "positions": a["c_pos"],
                "starts": a["c_starts"], "ends": a["c_ends"],
                "row_seq": a["c_seq"], "block_tables": a["c_tables"],
                "ctx_lens": a["c_ctx"]}

    @staticmethod
    def _decode_batch(a):
        ctx_lens = jnp.where(a["d_active"], a["d_pos"] + 1, 0)
        return {"token": a["d_tok"][:, None], "pos": a["d_pos"],
                "block_tables": a["d_tables"], "ctx_lens": ctx_lens}


class TransformerRunner(ModelRunner):
    """Decoder-only attention families: everything is paged KV, prefix
    caching applies (KV depends only on the token prefix)."""

    needs_blocks = True
    supports_prefix_caching = True
    supports_packed_prefill = True

    @property
    def counts_experts(self) -> bool:
        return self.cfg.moe is not None

    def step(self, params, cache, a, *, has_chunk, full_sampling=False):
        moe_c = None
        if has_chunk:
            if "c_starts" in a:
                logits_c, cache, moe_c = transformer.prefill_chunk_ragged(
                    params, cache, self._ragged_batch(a), self.cfg,
                    self.pcfg)
            else:
                logits_c, cache, moe_c = transformer.prefill_chunk_paged(
                    params, cache, self._chunk_batch(a), self.cfg,
                    self.pcfg)
        else:
            logits_c = None
        logits_d, cache, moe = transformer.decode_step_paged(
            params, cache, self._decode_batch(a), self.cfg, self.pcfg)
        out = self._sample(logits_d, logits_c, a, has_chunk, full_sampling)
        if not self.counts_experts:
            return out, cache
        return (out, moe if moe_c is None else moe + moe_c), cache

    def init_cache(self, num_blocks, block_size, max_batch,
                   kv_dtype="bf16"):
        return init_paged_cache(self.cfg, num_blocks, block_size,
                                kv_dtype=kv_dtype)


class SSMRunner(ModelRunner):
    """Pure Mamba2: constant-size slot state, no blocks, no horizon.
    Prefix caching is off — a cached block id cannot stand in for the
    recurrent state that produced it."""

    needs_slots = True

    def __init__(self, cfg, pcfg):
        super().__init__(cfg, pcfg)
        self._state_keys = tuple(mamba_layer_stacks(cfg))
        # serving chunk boundaries must land on SSD inner-chunk boundaries
        # so chunked prefill is bit-identical to a monolithic one
        self.chunk_quantum = cfg.ssm.chunk_size
        self.needs_blocks = bool(attn_layer_stacks(cfg))

    def init_cache(self, num_blocks, block_size, max_batch,
                   kv_dtype="bf16"):
        if kv_dtype != "bf16":
            raise ValueError(
                f"kv_dtype={kv_dtype}: SSM/hybrid runners keep bf16 pools "
                "(slot state has no quantized form)")
        cache = (init_paged_cache(self.cfg, num_blocks, block_size)
                 if self.needs_blocks else {})
        cache.update(init_slot_state(self.cfg, max_batch))
        return cache

    def step(self, params, cache, a, *, has_chunk, full_sampling=False):
        logits_c = None
        if has_chunk:
            slot = a["c_slot"][0]
            fresh = a["c_start"][0] == 0
            chunk_cache = {}
            for key, val in cache.items():
                if key in self._state_keys:
                    st = _slice_slot(val, slot)
                    # first chunk after (re)admission starts from zeros —
                    # never from a previous occupant's state
                    st = jax.tree.map(
                        lambda t: jnp.where(fresh, jnp.zeros_like(t), t),
                        st)
                    chunk_cache[key] = st
                else:
                    chunk_cache[key] = val
            logits_c, out, _ = transformer.prefill_chunk_paged(
                params, chunk_cache, self._chunk_batch(a), self.cfg,
                self.pcfg)
            cache = {key: (_scatter_slot(cache[key], out[key], slot)
                           if key in self._state_keys else out[key])
                     for key in cache}
        old_state = {key: cache[key] for key in self._state_keys}
        logits_d, cache, _ = transformer.decode_step_paged(
            params, cache, self._decode_batch(a), self.cfg, self.pcfg)
        for key in self._state_keys:
            cache[key] = _mask_slot_rows(cache[key], old_state[key],
                                         a["d_active"])
        return self._sample(logits_d, logits_c, a, has_chunk,
                            full_sampling), cache


class HybridRunner(SSMRunner):
    """zamba2: mamba stacks carry slot state, the shared attention block
    reads/writes paged KV through one block table per sequence."""


class EncDecRunner(ModelRunner):
    """whisper: paged decoder self-KV + read-only per-slot cross K/V
    (written once by ``encode`` at admission). Prefix caching is off —
    decoder KV depends on the request's encoder output, so equal token
    prefixes do *not* imply equal KV."""

    needs_blocks = True
    needs_encoder = True

    def init_cache(self, num_blocks, block_size, max_batch,
                   kv_dtype="bf16"):
        if kv_dtype != "bf16":
            raise ValueError(
                f"kv_dtype={kv_dtype}: the enc-dec runner keeps bf16 pools "
                "(cross K/V is per-slot, not paged)")
        cfg = self.cfg
        shape = pool_shape(cfg.num_layers, num_blocks, block_size, cfg)
        return {"self": {"k": jnp.zeros(shape, jnp.bfloat16),
                         "v": jnp.zeros(shape, jnp.bfloat16)},
                "cross": init_encoder_cache(cfg, max_batch)}

    def encode(self, params, cache, slot, frames):
        kv = encdec.encode_cross_kv(params, frames[None], self.cfg,
                                    self.pcfg)
        return {"self": cache["self"],
                "cross": _scatter_slot(cache["cross"], kv, slot)}

    def step(self, params, cache, a, *, has_chunk, full_sampling=False):
        logits_c = None
        if has_chunk:
            cross_row = _slice_slot(cache["cross"], a["c_slot"][0])
            logits_c, out = encdec.prefill_chunk_paged(
                params, {"self": cache["self"], "cross": cross_row},
                self._chunk_batch(a), self.cfg, self.pcfg)
            cache = {"self": out["self"], "cross": cache["cross"]}
        logits_d, out = encdec.decode_step_paged(
            params, cache, self._decode_batch(a), self.cfg, self.pcfg)
        cache = {"self": out["self"], "cross": cache["cross"]}
        return self._sample(logits_d, logits_c, a, has_chunk,
                            full_sampling), cache


class SpeculativeRunner(ModelRunner):
    """Draft-and-verify speculative decoding over two TransformerRunners.

    A small *draft* model proposes ``spec_tokens`` (= k) tokens per slot
    per step; the *target* model scores all k+1 candidate positions in one
    widened chunk pass (``prefill_chunk_paged`` with ``all_logits=True``,
    i.e. ``paged_chunk_attention`` with k+1 query rows per slot); the
    longest agreeing prefix is accepted by rejection sampling that
    preserves the target distribution (``sampling.speculative_verify``) —
    greedy outputs stay byte-identical to non-speculative decode.

    Cache design: draft and target KV always cover *the same token
    positions* (the draft writes every token it is fed, the verify pass
    writes the same k+1 positions in the target pools, chunk prefill runs
    through both models), so both live in one pytree
    ``{"tgt": ..., "dft": ...}`` indexed by **one shared block table per
    request** — a single :class:`~repro.serving.kv_cache.BlockManager`
    covers both models, and prefix caching, COW page copies and
    preemption-recompute apply to the pair at once (a cached block's
    content hash vouches for the draft KV exactly as it does for the
    target's, since both are pure functions of the token prefix).

    Per step and slot the draft runs k+1 single-token decodes (the last
    one writes KV for the final proposal so the draft cache never trails
    the accepted stream), the target runs one k+1-wide verify row, and the
    host rolls rejected lookahead blocks back via ``BlockManager.truncate``.
    ``params`` is the pair ``{"tgt": target_params, "dft": draft_params}``.
    """

    needs_blocks = True
    supports_prefix_caching = True
    supports_packed_prefill = True

    def __init__(self, cfg: ModelConfig, pcfg: ParallelConfig,
                 draft_cfg: ModelConfig, spec_tokens: int):
        super().__init__(cfg, pcfg)
        if spec_tokens < 0:
            raise ValueError(f"spec_tokens={spec_tokens} must be >= 0")
        if draft_cfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft vocab {draft_cfg.vocab_size} != target vocab "
                f"{cfg.vocab_size}: draft proposals must be target ids")
        self.draft_cfg = draft_cfg
        self.spec_tokens = spec_tokens

    def init_cache(self, num_blocks, block_size, max_batch,
                   kv_dtype="bf16"):
        return {"tgt": init_paged_cache(self.cfg, num_blocks, block_size,
                                        kv_dtype=kv_dtype),
                "dft": init_paged_cache(self.draft_cfg, num_blocks,
                                        block_size, kv_dtype=kv_dtype)}

    def step(self, params, cache, a, *, has_chunk, full_sampling=False):
        k = self.spec_tokens
        B = a["d_tok"].shape[0]
        tgt, dft = cache["tgt"], cache["dft"]
        logits_c = None
        if has_chunk:
            if "c_starts" in a:
                # packed ragged chunks run through both models (draft KV
                # must mirror the target's positions exactly)
                rb = self._ragged_batch(a)
                logits_c, tgt, _ = transformer.prefill_chunk_ragged(
                    params["tgt"], tgt, rb, self.cfg, self.pcfg)
                _, dft, _ = transformer.prefill_chunk_ragged(
                    params["dft"], dft, rb, self.draft_cfg, self.pcfg)
            else:
                cb = self._chunk_batch(a)
                logits_c, tgt, _ = transformer.prefill_chunk_paged(
                    params["tgt"], tgt, cb, self.cfg, self.pcfg)
                _, dft, _ = transformer.prefill_chunk_paged(
                    params["dft"], dft, cb, self.draft_cfg, self.pcfg)
        temps, top_ks = a["temps"][:B], a["top_ks"][:B]
        seeds, rids, cnts = a["seeds"][:B], a["rids"][:B], a["counters"][:B]
        sp_d = ({key: a[key][:B] for key in SP_KEYS} if full_sampling
                else None)
        # committed counts, incremented with each proposal's one-hot so
        # proposal i and verify row i share identical penalty counts
        oc = a["ocounts"][:B] if full_sampling else None
        # -- draft phase: k proposals, k+1 KV writes (the last write backs
        # the final proposal so the draft cache mirrors the target's) ----
        toks = [a["d_tok"]]
        dlogits = []
        if k > 0:
            for i in range(k + 1):
                db = {"token": toks[-1][:, None], "pos": a["d_pos"] + i,
                      "block_tables": a["d_tables"],
                      "ctx_lens": jnp.where(a["d_active"],
                                            a["d_pos"] + i + 1, 0)}
                lg, dft, _ = transformer.decode_step_paged(
                    params["dft"], dft, db, self.draft_cfg, self.pcfg)
                if i < k:
                    dlogits.append(lg)
                    if full_sampling:
                        nt = propose_tokens_full(
                            lg, dict(sp_d, ocounts=oc, counters=cnts + i))
                        oc = oc + jax.nn.one_hot(nt, lg.shape[-1],
                                                 dtype=oc.dtype)
                    else:
                        nt = propose_tokens(lg, temps, top_ks, seeds,
                                            rids, cnts + i)
                    toks.append(nt)
        # -- verify phase: one widened target pass over all k+1 positions
        verify_tokens = jnp.stack(toks, axis=1)                  # (B, k+1)
        vb = {"tokens": verify_tokens, "q_start": a["d_pos"],
              "q_lens": jnp.where(a["d_active"], k + 1, 0),
              "block_tables": a["d_tables"],
              "ctx_lens": jnp.where(a["d_active"], a["d_pos"] + k + 1, 0)}
        tlogits, tgt, _ = transformer.prefill_chunk_paged(
            params["tgt"], tgt, vb, self.cfg, self.pcfg, all_logits=True)
        draft_logits = (jnp.stack(dlogits, axis=1) if dlogits else
                        jnp.zeros((B, 0, tlogits.shape[-1]),
                                  tlogits.dtype))
        if full_sampling:
            out_toks, n_acc, lp_d = speculative_verify_full(
                verify_tokens[:, 1:], draft_logits, tlogits, sp_d,
                max_logprobs=self.max_logprobs)
        else:
            out_toks, n_acc = speculative_verify(
                verify_tokens[:, 1:], draft_logits, tlogits,
                temps, top_ks, seeds, rids, cnts)
        if has_chunk:
            if full_sampling:
                c_tok, lp_c = sample_tokens_full(
                    logits_c, {key: a[key][B:] for key in SP_KEYS},
                    max_logprobs=self.max_logprobs)
            else:
                c_tok = sample_tokens(logits_c, a["temps"][B:],
                                      a["top_ks"][B:], a["seeds"][B:],
                                      a["rids"][B:], a["counters"][B:])
        else:
            c_tok = jnp.zeros((1,), jnp.int32)
            if full_sampling:
                S = a["temps"].shape[0] - B
                L = min(self.max_logprobs, tlogits.shape[-1])
                lp_c = {"chosen": jnp.zeros((S,), tlogits.dtype),
                        "top_lp": jnp.zeros((S, L), tlogits.dtype),
                        "top_ids": jnp.zeros((S, L), jnp.int32)}
        if full_sampling:
            return ((out_toks, n_acc, c_tok, lp_d, lp_c),
                    {"tgt": tgt, "dft": dft})
        return (out_toks, n_acc, c_tok), {"tgt": tgt, "dft": dft}


def make_runner(cfg: ModelConfig, pcfg: ParallelConfig, *,
                draft_cfg: ModelConfig | None = None,
                num_speculative_tokens: int = 0) -> ModelRunner:
    """Family dispatch. Raises for configs no runner covers yet.

    With ``draft_cfg`` set, wraps target and draft in a
    :class:`SpeculativeRunner` — both must resolve to the plain paged
    transformer family (slot-state kinds have no fork/rewind story for
    recurrent state yet; see ROADMAP)."""
    if cfg.frontend == "vision":
        raise ValueError(
            f"no serving runner for {cfg.name}: modality frontends need "
            "per-request position streams")
    if draft_cfg is not None:
        base = make_runner(cfg, pcfg)
        draft = make_runner(draft_cfg, pcfg)
        if type(base) is not TransformerRunner \
                or type(draft) is not TransformerRunner:
            raise ValueError(
                "speculative decoding needs paged-transformer target and "
                f"draft, got {type(base).__name__} target / "
                f"{type(draft).__name__} draft")
        return SpeculativeRunner(cfg, pcfg, draft_cfg,
                                 num_speculative_tokens)
    if cfg.encoder_layers:
        return EncDecRunner(cfg, pcfg)
    if cfg.ssm is not None:
        if cfg.shared_attn_period or any(
                k != "mamba" for k in cfg.block_pattern):
            return HybridRunner(cfg, pcfg)
        return SSMRunner(cfg, pcfg)
    return TransformerRunner(cfg, pcfg)
