"""Reduce a profiler trace (``.xplane.pb``) to busy time, kernel time and
idle gaps labelled by the host span open at the time.

The device's operations are the events of the ``XLA Ops`` line of each
``/device:TPU:n`` plane. On a CPU (tests only) they are the events of the
host's XLA threads that carry an ``hlo_op`` stat. Host spans are the
``bench.*`` ``TraceAnnotation`` events the harness writes; they share the
trace's clock with the device's operations.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."


@dataclass
class Event:
    name: str
    start: int                # ns, trace clock
    end: int
    stats: dict = field(default_factory=dict)

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclass
class Trace:
    device_ops: list[list[Event]]      # per device, sorted by start
    spans: list[Event]                 # harness host spans, sorted


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line) -> list[Event]:
    out = []
    for e in line.events:
        out.append(Event(e.name, int(e.start_ns), int(e.end_ns),
                         dict(e.stats)))
    return out


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file, or a gzipped one (``.gz``)."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices, cpu_ops, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append(sorted(_events(line),
                                          key=lambda e: e.start))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                spans += [e for e in evs if e.name.startswith(SPAN_PREFIX)]
                if line.name.startswith("tf_XLA"):
                    cpu_ops += [e for e in evs if "hlo_op" in e.stats]
    if not devices and cpu_ops:
        devices = [sorted(cpu_ops, key=lambda e: e.start)]
    return Trace(devices, sorted(spans, key=lambda e: e.start))


# -- interval arithmetic -------------------------------------------------------


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: list[Event], lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(((o.start, o.end) for o in ops),
                                       lo, hi))


def gaps(ops: list[Event], lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle [start, end) intervals of the device within [lo, hi)."""
    out, t = [], lo
    for s, e in union(((o.start, o.end) for o in ops), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def open_span(spans: list[Event], t: int) -> str:
    """Name of the innermost harness span open at time t, or "none"."""
    best = None
    for s in spans:
        if s.start > t:
            break
        if s.end > t and (best is None or s.start >= best.start):
            best = s
    return best.name if best is not None else "none"


def window(tr: Trace, step_span: str = SPAN_PREFIX + "step"
           ) -> tuple[int, int]:
    """The traced slice: from the first to the end of the last whole step
    span, or the extent of the device's operations when there is none."""
    steps = [s for s in tr.spans if s.name == step_span]
    if steps:
        return steps[0].start, steps[-1].end
    ops = [o for d in tr.device_ops for o in d]
    if not ops:
        raise RuntimeError("the traced slice holds no engine step and no "
                           "device operation")
    return min(o.start for o in ops), max(o.end for o in ops)


def label(name: str) -> str:
    """A short name for a device operation: the HLO instruction's name
    without its number, or for a Pallas kernel (a ``tpu_custom_call``,
    which the trace does not name) its output type."""
    head, _, rest = name.partition(" = ")
    if "tpu_custom_call" in rest:
        return "tpu_custom_call " + rest.split("{")[0]
    return re.sub(r"\.\d+$", "", head.lstrip("%"))


def self_times(ops: list[Event]) -> list[tuple[Event, int]]:
    """Each operation with its time minus that of the operations nested
    in it (a ``while`` holds its body's operations on the same line)."""
    order = sorted(ops, key=lambda o: (o.start, -o.end))
    child = {id(o): 0 for o in order}
    stack: list[Event] = []
    for o in order:
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack:
            child[id(stack[-1])] += o.dur
        stack.append(o)
    return [(o, o.dur - child[id(o)]) for o in order]


MIN_GAP_NS = 1000         # shorter gaps are clock rounding between ops


def summary(tr: Trace, lo: int, hi: int, top: int = 10) -> dict:
    """Busy and idle seconds averaged over the devices, the device
    operations that took most time (self time), and the longest idle gaps
    labelled by the host span open at their midpoint."""
    n = max(len(tr.device_ops), 1)
    busy = sum(busy_ns(d, lo, hi) for d in tr.device_ops) / n
    by_op: dict[str, int] = {}
    for d in tr.device_ops:
        for o, t in self_times([o for o in d if lo <= o.start < hi]):
            key = label(o.name)
            by_op[key] = by_op.get(key, 0) + t
    idle = []
    by_label: dict[str, int] = {}
    for d in tr.device_ops[:1]:
        for s, e in gaps(d, lo, hi):
            if e - s < MIN_GAP_NS:
                continue
            label_ = open_span(tr.spans, (s + e) // 2)
            idle.append((label_, e - s))
            by_label[label_] = by_label.get(label_, 0) + e - s
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    idle_top = sorted(idle, key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": [[k, v / n / 1e9] for k, v in ops_top],
            "idle_gaps": [[k, v / 1e9] for k, v in idle_top],
            "idle_by_span": {k: v / 1e9 for k, v in
                             sorted(by_label.items(), key=lambda kv: -kv[1])}}
