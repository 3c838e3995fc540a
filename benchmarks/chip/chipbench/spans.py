"""Readers of what the serving program reports about itself: its
``serve.*`` profiler spans, the engine's ``on_step`` records
(``repro.serving.stats.StepRecord``) and ``TokenEvent.emitted``.

Every reader returns None where there is nothing to read, as a program
without these spans and counters gives nothing. ``spans_run.py`` runs a
cell with them and prints the readings.
"""

from __future__ import annotations

import math

from chipbench import reduce, trace

PREFIX = "serve."
# the spans whose idle time is read (idle_share.<phase>)
IDLE_PHASES = {"schedule": "serve.schedule", "build": "serve.build",
               "emit": "serve.emit"}


def _profile(path):
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(str(path))


def module_names(path) -> list[str]:
    """The distinct names on the devices' ``XLA Modules`` lines, sorted."""
    return sorted({e.name for plane in _profile(path).planes
                   if plane.name.startswith("/device:")
                   for line in plane.lines if line.name == "XLA Modules"
                   for e in line.events})


def load(path) -> list[trace.Event]:
    """The ``serve.*`` host events of a trace file, sorted by start."""
    out = []
    for plane in _profile(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [trace.Event(e.name, int(e.start_ns), int(e.end_ns),
                                    dict(e.stats))
                        for e in line.events if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda e: e.start)


def overlap_ns(intervals, spans: list[trace.Event], lo: int, hi: int) -> int:
    """Length of the part of ``intervals`` ([start, end) pairs, disjoint,
    sorted) that lies inside the union of ``spans``, within [lo, hi)."""
    cover = trace.union(((s.start, s.end) for s in spans), lo, hi)
    total, j = 0, 0
    for s, e in intervals:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            total += max(0, min(e, cover[k][1]) - max(s, cover[k][0]))
            k += 1
    return total


def idle_share(run, spans: list[trace.Event], name: str):
    """Device idle time inside ``name`` spans ÷ the traced slice, %."""
    if run.trace is None or not run.trace.device_ops or not spans:
        return None
    lo, hi = run.trace_lo, run.trace_hi
    idle = trace.gaps(run.trace.device_ops[0], lo, hi)
    mine = [s for s in spans if s.name == name]
    return 100.0 * overlap_ns(idle, mine, lo, hi) / (hi - lo)


def idle_by_span(run, spans: list[trace.Event]) -> dict:
    """Device idle seconds of the slice by the innermost ``serve.*`` span
    open at each gap's midpoint, "none" outside every one."""
    if run.trace is None or not run.trace.device_ops:
        return {}
    out: dict[str, float] = {}
    for s, e in trace.gaps(run.trace.device_ops[0], run.trace_lo,
                           run.trace_hi):
        if e - s >= trace.MIN_GAP_NS:
            k = trace.open_span(spans, (s + e) // 2)
            out[k] = out.get(k, 0.0) + (e - s) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def step_device_ms(run, spans: list[trace.Event], steps: list,
                   chunk: bool):
    """Mean device busy time (union of the device's operations) inside the
    whole ``serve.step`` spans of the slice whose record carries a chunk
    (``chunk``) or none, ms."""
    if run.trace is None or not run.trace.device_ops or not steps:
        return None
    recs = {r.step: r for r in steps}
    ops = run.trace.device_ops[0]
    busy = []
    for sp in spans:
        rec = recs.get(sp.stats.get("step"))
        if (sp.name != PREFIX + "step" or rec is None
                or sp.start < run.trace_lo or sp.end > run.trace_hi
                or (rec.chunk is not None) != chunk):
            continue
        busy.append(trace.busy_ns(ops, sp.start, sp.end))
    return sum(busy) / len(busy) / 1e6 if busy else None


def queue_wait_ms(steps: list, w0: float, w1: float):
    """Mean queue wait of the requests that first reached a slot in the
    window: Δ``queue_wait_s`` ÷ Δ``first_admits`` between the last record
    ending at or before ``w0`` and the last ending in the window, ms."""
    before = [r for r in steps if r.t1 <= w0]
    inside = [r for r in steps if w0 < r.t1 <= w1]
    if not before or not inside:
        return None
    n = inside[-1].first_admits - before[-1].first_admits
    if n <= 0:
        return None
    return 1e3 * (inside[-1].queue_wait_s - before[-1].queue_wait_s) / n


def token_handoff_ms(handoffs: list, w0: float, w1: float):
    """95th percentile of receipt − ``TokenEvent.emitted`` over the tokens
    received in the window; ``handoffs`` holds (receipt, emitted), emitted
    NaN where the program did not stamp it."""
    return reduce.pct([1e3 * (t - e) for t, e in handoffs
                       if w0 < t <= w1 and math.isfinite(e)], 95)


def read_all(run, spans: list[trace.Event], steps: list,
             handoffs: list) -> dict:
    """The seven readings, by metric name; None where nothing was read."""
    out = {"queue_wait_ms": queue_wait_ms(steps, run.w0, run.w1)}
    for phase, name in IDLE_PHASES.items():
        out[f"idle_share.{phase}"] = idle_share(run, spans, name)
    out["step_device_ms.decode"] = step_device_ms(run, spans, steps, False)
    out["step_device_ms.chunk"] = step_device_ms(run, spans, steps, True)
    out["token_handoff_ms"] = token_handoff_ms(handoffs, run.w0, run.w1)
    return out
