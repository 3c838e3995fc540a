"""Arithmetic the metric readers share: latencies from the client's
timestamps, model FLOPs of steps, and kernel roofline shares from the
trace. Every function returns None where there is nothing to read."""

from __future__ import annotations

import re

import numpy as np

from chipbench import counts


def pct(xs, q: float):
    return float(np.percentile(xs, q)) if len(xs) else None


def ttfts(run) -> list[float]:
    """First-token time minus due time of every request due in the window;
    one still waiting at the window's end enters with its wait so far."""
    out = []
    for r in run.recs:
        if run.w0 <= r.due < run.w1:
            first = r.times[0] if r.times and r.times[0] <= run.w1 else run.w1
            out.append(first - r.due)
    return out


def itls(run) -> list[float]:
    """Every gap between consecutive tokens of one request whose later
    token was received in the window."""
    out = []
    for r in run.recs:
        t = r.times
        out += [t[i] - t[i - 1] for i in range(1, len(t))
                if run.w0 < t[i] <= run.w1]
    return out


def tokens_in_window(run) -> int:
    return sum(1 for r in run.recs for t in r.times if run.w0 < t <= run.w1)


def flops(run, s) -> int:
    return run.model.step_flops(s.decode_ctxs, s.chunk, s.chunk_sampled)


def mfu(run, steps, seconds: float):
    """Model FLOPs of these steps over seconds × the chip's bf16 peak, %."""
    if not steps or seconds <= 0:
        return None
    f = sum(flops(run, s) for s in steps)
    return 100.0 * f / (seconds * run.peaks["bf16_flops"])


def roofline(run, pattern: str, work) -> float | None:
    """Least time for the work ÷ summed device time of the kernel events
    whose name matches ``pattern``, %, over the whole steps of the traced
    slice. ``work(step)`` gives one call's work per layer kind,
    ``{kind: (flops, bytes)}`` as ``counts.Model``'s kernels count it, or
    one ``(flops, bytes)`` for every layer; there is one call per layer of
    every step where the work is nonzero."""
    if run.trace is None:
        return None
    rx = re.compile(pattern)
    least, spent = 0.0, 0
    for rec, ops in run.traced_steps():
        w = work(rec)
        per_kind = w.items() if isinstance(w, dict) else [(None, w)]
        mine = [o for o in ops if rx.search(o.name)]
        if not mine or all(fb[0] == 0 for _, fb in per_kind):
            continue
        for kind, (f, b) in per_kind:
            t, _ = counts.roofline_s(f, b, run.peaks["bf16_flops"],
                                     run.peaks["hbm_bytes_per_s"])
            least += t * (run.model.layers if kind is None
                          else run.model.kinds[kind])
        spent += sum(o.dur for o in mine)
    if spent == 0:
        return None
    return 100.0 * least / (spent / 1e9)
