"""Operations and bytes the algorithm needs, from shapes alone.

Conventions (copied from the repository's ``analysis/roofline.py`` and
made exact for the serving step): a matmul of an (m, k) by a (k, n)
operand is 2·m·k·n FLOPs; a token through the model costs 2 × its matmul
parameters, plus the LM head (2·V·d) only where its logits are sampled;
attention of one query at context c costs 4·H·hd·c per layer (q·kᵀ and
p·v), with c capped at the window in a sliding layer. Bytes count what the
algorithm must move, not what a kernel fetches: each decode row reads its
c cached tokens (at most the window, in a sliding layer) × K heads × hd ×
2 (k and v) × the pool's bytes per value, plus its q and its output.

The configuration file's ``sizes`` may state, besides the dense widths:
``layer_types`` (``"full"`` or ``"sliding"`` per layer) with ``window``,
and ``moe`` (``experts``, ``experts_held``, ``experts_per_token``,
``d_ff_expert``), which makes every layer's MLP an expert layer. Without
them the model is dense and every layer attends to its whole context.
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass
from functools import cached_property

LAYER_TYPES = ("full", "sliding")


@dataclass(frozen=True)
class Experts:
    experts: int              # routed experts of the published layer
    held: int                 # of them on this chip
    per_token: int
    d_ff: int                 # width of one routed expert


@dataclass(frozen=True)
class Model:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool               # SwiGLU (3 matrices) or ungated (2)
    kv_bytes: int = 2         # bf16 pool
    act_bytes: int = 2        # bf16 q and attention output
    layer_types: tuple = ()   # per layer; () means every layer is "full"
    window: int | None = None  # keys a query sees in a "sliding" layer
    moe: Experts | None = None  # every layer's MLP, where given

    def __post_init__(self):
        kinds = self.layer_types
        if kinds and (len(kinds) != self.layers
                      or not set(kinds) <= set(LAYER_TYPES)):
            raise ValueError(f"layer_types must list one of {LAYER_TYPES} "
                             f"for each of the {self.layers} layers")
        if "sliding" in kinds and not self.window:
            raise ValueError("sliding layers need a window")

    @classmethod
    def from_config(cls, c: dict) -> "Model":
        """From a ``configs/<name>.json`` file's ``sizes`` block."""
        s = c["sizes"]
        moe = None
        if "moe" in s:
            e = s["moe"]
            moe = Experts(e["experts"], e["experts_held"],
                          e["experts_per_token"], e["d_ff_expert"])
        return cls(layers=s["layers"], d=s["d_model"], heads=s["heads"],
                   kv_heads=s["kv_heads"], head_dim=s["head_dim"],
                   d_ff=s["d_ff"], vocab=s["vocab"],
                   gated=s["mlp"] == "swiglu",
                   layer_types=tuple(s.get("layer_types", ())),
                   window=s.get("window"), moe=moe)

    @cached_property
    def kinds(self) -> dict[str, int]:
        """Number of layers of each attention kind."""
        return dict(Counter(self.layer_types or ("full",) * self.layers))

    def keys_seen(self, kind: str, ctx: int) -> int:
        """Keys one query at context ``ctx`` attends to in a layer."""
        return min(ctx, self.window) if kind == "sliding" else ctx

    @property
    def attn_matmul_params(self) -> int:
        d, hq, hkv = self.d, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return d * hq + 2 * d * hkv + hq * d

    @property
    def layer_matmul_params(self):
        """Matmul parameters one token meets in one layer on this chip. In
        an expert layer: attention, the router (d × experts) and
        ``experts_per_token × held ÷ experts`` of the routed experts: the
        share of a token's experts that lie on this chip under uniform
        routing, which is what the chip computes on average."""
        mats = 3 if self.gated else 2
        e = self.moe
        if e is None:
            return self.attn_matmul_params + mats * self.d * self.d_ff
        routed = mats * self.d * e.d_ff * e.per_token * e.held / e.experts
        return self.attn_matmul_params + self.d * e.experts + routed

    @property
    def token_flops(self):
        """Matmul FLOPs of one token through every layer, head excluded."""
        return 2 * self.layers * self.layer_matmul_params

    @property
    def head_flops(self) -> int:
        return 2 * self.vocab * self.d

    def attn_flops(self, ctx: int) -> int:
        """One query attending at context ``ctx``, every layer."""
        return 4 * self.heads * self.head_dim * sum(
            n * self.keys_seen(k, ctx) for k, n in self.kinds.items())

    def chunk_keys(self, kind: str, start: int, n: int) -> int:
        """Keys the n queries at positions start .. start+n-1 attend to,
        summed, in a layer of this kind."""
        if kind != "sliding":
            return n * start + n * (n + 1) // 2
        # the first ``early`` queries see p + 1 keys, the rest the window
        w = self.window
        early = min(n, max(0, w - start))
        return early * start + early * (early + 1) // 2 + (n - early) * w

    # -- one kernel call (one layer) ---------------------------------------

    def decode_kernel(self, ctxs) -> dict[str, tuple[int, int]]:
        """{layer kind: (FLOPs, bytes)} of one paged-decode call in a layer
        of that kind, over rows with these context lengths (idle rows,
        ctx 0, do no work)."""
        hd, H, K = self.head_dim, self.heads, self.kv_heads
        live = [c for c in ctxs if c > 0]
        qo = len(live) * H * hd * 2 * self.act_bytes
        out = {}
        for kind in self.kinds:
            keys = sum(self.keys_seen(kind, c) for c in live)
            out[kind] = (4 * H * hd * keys,
                         keys * K * hd * 2 * self.kv_bytes + qo)
        return out

    def chunk_kernel(self, start: int, n: int) -> dict[str, tuple[int, int]]:
        """{layer kind: (FLOPs, bytes)} of one chunked-prefill call: n
        queries at positions start .. start+n-1, each attending causally to
        every key up to its own position (within the window, in a sliding
        layer), over the keys visible to any of them."""
        hd, H, K = self.head_dim, self.heads, self.kv_heads
        qo = n * H * hd * 2 * self.act_bytes
        out = {}
        for kind in self.kinds:
            visible = start + n
            if kind == "sliding":
                visible = min(visible, n + self.window - 1)
            out[kind] = (4 * H * hd * self.chunk_keys(kind, start, n),
                         visible * K * hd * 2 * self.kv_bytes + qo)
        return out

    # -- one engine step -----------------------------------------------------

    def step_flops(self, decode_ctxs, chunk: tuple[int, int] | None,
                   chunk_sampled: bool):
        """Model FLOPs of the useful tokens of one step: every active decode
        row (sampled), plus the chunk's tokens (its last row sampled only
        when it completes the prompt)."""
        f = sum(self.token_flops + self.head_flops + self.attn_flops(c)
                for c in decode_ctxs)
        if chunk is not None:
            start, n = chunk
            f += n * self.token_flops
            f += 4 * self.heads * self.head_dim * sum(
                m * self.chunk_keys(k, start, n)
                for k, m in self.kinds.items())
            if chunk_sampled:
                f += self.head_flops
        return f


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> tuple[float, str]:
    """The least time for the work, and which bound sets it."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
