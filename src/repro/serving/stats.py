"""Serving statistics primitives: Prometheus-style histograms and the
per-step record the engine hands its ``on_step`` hook.

The engine aggregates per-request TTFT / end-to-end latency into fixed-
bucket :class:`Histogram`\\ s at retirement time, so the rolling
``stats["latency"]`` dict can stay bounded (old per-request records are
evicted) without the metrics surface losing data: a histogram is O(number
of buckets) forever, which is what lets a serve loop run for millions of
requests. ``frontend/metrics.py`` renders these in the Prometheus text
exposition format.

Dependency-free on purpose (no jax, no numpy): the scheduler/engine host
path and the asyncio front-end both import it.
"""

from __future__ import annotations

from bisect import bisect_left
from dataclasses import dataclass

__all__ = ["Histogram", "SECONDS_BUCKETS", "STEP_BUCKETS", "StepRecord"]

# wall-clock latency buckets (seconds): spans interpret-mode CPU smoke
# runs (tens of seconds) down to real-accelerator decode steps (ms)
SECONDS_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)

# virtual-clock buckets (engine steps): deterministic across hosts, the
# unit the scheduler tests and the bench's `steps` percentiles use
STEP_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
                512.0, 1024.0)


class Histogram:
    """Fixed-bucket histogram with Prometheus exposition semantics.

    ``uppers`` are inclusive bucket upper bounds (``le``); an implicit
    ``+Inf`` bucket catches the tail. ``render`` emits *cumulative* bucket
    counts plus ``_sum`` / ``_count``, exactly the text format Prometheus
    scrapes. ``percentile`` gives a conservative (bucket-upper-bound)
    estimate for host-side reporting and the admission controller.
    """

    def __init__(self, uppers=SECONDS_BUCKETS):
        self.uppers = tuple(sorted(float(u) for u in uppers))
        if not self.uppers:
            raise ValueError("histogram needs at least one bucket bound")
        self.counts = [0] * (len(self.uppers) + 1)     # + the +Inf bucket
        self.count = 0
        self.total = 0.0

    def observe(self, v: float) -> None:
        self.counts[bisect_left(self.uppers, float(v))] += 1
        self.count += 1
        self.total += float(v)

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def percentile(self, q: float) -> float:
        """Upper bound of the bucket containing the q-th percentile
        (q in [0, 100]); 0.0 when empty, last finite bound for the +Inf
        bucket. Conservative by construction — never underestimates."""
        if not self.count:
            return 0.0
        need = max(1, -(-int(q * self.count) // 100))   # ceil(q% of count)
        seen = 0
        for upper, c in zip(self.uppers, self.counts):
            seen += c
            if seen >= need:
                return upper
        return self.uppers[-1]

    def merge(self, other: "Histogram") -> "Histogram":
        """Add ``other``'s observations into this histogram, in place.
        Bucket bounds must match exactly (fleet aggregation merges
        replicas built from the same constants). Returns self, so
        ``reduce(Histogram.merge, hists, Histogram(b))`` folds a fleet.

        Equivalence contract (pinned by tests): merging N histograms is
        indistinguishable — counts, sum, count, percentiles, rendering —
        from one histogram that observed the concatenated samples."""
        if other.uppers != self.uppers:
            raise ValueError(
                f"bucket mismatch: {self.uppers} != {other.uppers}")
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.count += other.count
        self.total += other.total
        return self

    def render(self, name: str, help_: str, out: list[str],
               labels: dict | None = None, header: bool = True) -> None:
        """Append Prometheus text-format lines for this histogram.

        ``labels`` adds constant label pairs to every series (e.g.
        ``{"replica": "0"}`` for per-replica fleet series); ``header``
        False suppresses the HELP/TYPE preamble so several labeled
        histograms can share one metric family."""
        if header:
            out.append(f"# HELP {name} {help_}")
            out.append(f"# TYPE {name} histogram")
        base = "".join(f'{k}="{v}",' for k, v in (labels or {}).items())
        tail = ("{" + base.rstrip(",") + "}") if base else ""
        cum = 0
        for upper, c in zip(self.uppers, self.counts):
            cum += c
            out.append(
                f'{name}_bucket{{{base}le="{format(upper, "g")}"}} {cum}')
        out.append(f'{name}_bucket{{{base}le="+Inf"}} {self.count}')
        out.append(f"{name}_sum{tail} {format(self.total, 'g')}")
        out.append(f"{name}_count{tail} {self.count}")


@dataclass(frozen=True)
class StepRecord:
    """One ``InferenceEngine.step`` that scheduled tokens, as its
    ``on_step`` hook receives it.

    ``step`` is the index the ``serve.step`` profiler span carries, so a
    record can be matched with its span (and the device operations inside
    it) in a captured trace. ``t0``/``t1`` are ``time.perf_counter()`` at
    the call's entry and just before the hook runs. The plan fields are
    read before the step ran: each decode row's context length (the token
    fed included), each prefill chunk's ``(start, n)``, and how many chunks
    end their prompt and so sample a token. The counters are cumulative
    engine totals after the step. ``moe_rows``/``moe_assign`` are this
    step's expert counts, summed over layers: rows computed on the held
    experts and routed assignments (tokens × k); 0 in a model without
    experts."""
    step: int
    t0: float
    t1: float
    decode_ctxs: tuple[int, ...]
    chunks: tuple[tuple[int, int], ...]
    sampled: int
    full: bool                 # ran the full sampling pipeline
    waiting: int               # requests in the scheduler's queue after
    cache_hit_tokens: int
    prefill_tokens: int
    queue_wait_s: float        # Σ add → first slot binding, seconds
    first_admits: int          # requests that reached a slot at least once
    moe_rows: int = 0
    moe_assign: int = 0

    @property
    def chunk(self) -> tuple[int, int] | None:
        """The first (with ``prefill_pack=1`` the only) chunk, or None."""
        return self.chunks[0] if self.chunks else None
