"""Operations and bytes the algorithm needs, from shapes alone.

Conventions (copied from the repository's ``analysis/roofline.py`` and
made exact for the serving step): a matmul of an (m, k) by a (k, n)
operand is 2·m·k·n FLOPs; a token through the model costs 2 × its matmul
parameters, plus the LM head (2·V·d) only where its logits are sampled;
attention of one query at context c costs 4·H·hd·c per layer (q·kᵀ and
p·v). Bytes count what the algorithm must move, not what a kernel fetches:
each decode row reads its c cached tokens × K heads × hd × 2 (k and v) ×
the pool's bytes per value, plus its q and its output.
"""

from __future__ import annotations

from dataclasses import dataclass


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

    @classmethod
    def from_config(cls, c: dict) -> "Model":
        """From a ``configs/<name>.json`` file's ``sizes`` block."""
        s = c["sizes"]
        return cls(layers=s["layers"], d=s["d_model"], heads=s["heads"],
                   kv_heads=s["kv_heads"], head_dim=s["head_dim"],
                   d_ff=s["d_ff"], vocab=s["vocab"],
                   gated=s["mlp"] == "swiglu")

    @property
    def layer_matmul_params(self) -> int:
        d, hq, hkv = self.d, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        attn = d * hq + 2 * d * hkv + hq * d
        mlp = (3 if self.gated else 2) * d * self.d_ff
        return attn + mlp

    @property
    def token_flops(self) -> int:
        """Matmul FLOPs of one token through every layer, head excluded."""
        return 2 * self.layers * self.layer_matmul_params

    @property
    def head_flops(self) -> int:
        return 2 * self.vocab * self.d

    def attn_flops(self, ctx: int) -> int:
        """One query attending ``ctx`` keys, every layer."""
        return 4 * self.layers * self.heads * self.head_dim * ctx

    # -- one kernel call (one layer) ---------------------------------------

    def decode_kernel(self, ctxs) -> tuple[int, int]:
        """(FLOPs, bytes) of one paged-decode call over rows with these
        context lengths (idle rows, ctx 0, do no work)."""
        hd, H, K = self.head_dim, self.heads, self.kv_heads
        live = [c for c in ctxs if c > 0]
        flops = 4 * H * hd * sum(live)
        kv = sum(live) * K * hd * 2 * self.kv_bytes
        qo = len(live) * H * hd * 2 * self.act_bytes
        return flops, kv + qo

    def chunk_kernel(self, start: int, n: int) -> tuple[int, int]:
        """(FLOPs, bytes) of one chunked-prefill call: n queries at
        positions start .. start+n-1, each attending causally to every
        key up to its own position, over the start+n visible keys."""
        hd, H, K = self.head_dim, self.heads, self.kv_heads
        keys = n * start + n * (n + 1) // 2
        flops = 4 * H * hd * keys
        kv = (start + n) * K * hd * 2 * self.kv_bytes
        qo = n * H * hd * 2 * self.act_bytes
        return flops, kv + qo

    # -- one engine step -----------------------------------------------------

    def step_flops(self, decode_ctxs, chunk: tuple[int, int] | None,
                   chunk_sampled: bool) -> int:
        """Model FLOPs of the useful tokens of one step: every active decode
        row (sampled), plus the chunk's tokens (its last row sampled only
        when it completes the prompt)."""
        f = sum(self.token_flops + self.head_flops + self.attn_flops(c)
                for c in decode_ctxs)
        if chunk is not None:
            start, n = chunk
            f += n * self.token_flops
            f += self.attn_flops(1) * (n * start + n * (n + 1) // 2)
            if chunk_sampled:
                f += self.head_flops
        return f


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> tuple[float, str]:
    """The least time for the work, and which bound sets it."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
