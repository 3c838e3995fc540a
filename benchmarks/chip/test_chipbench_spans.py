"""The readers of the serving program's own spans and counters
(``chipbench/spans.py``): on hand-made events, and on a CPU run of a smoke
cell through ``spans_run.py``'s instrumentation, where the trace's device
operations are the host's XLA threads."""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import spans, trace  # noqa: E402

E = trace.Event


def test_idle_overlap_with_spans_on_hand_made_events():
    ops = [E("a", 0, 10), E("b", 5, 20), E("c", 30, 40), E("d", 35, 38)]
    idle = trace.gaps(ops, 0, 50)                   # (20, 30), (40, 50)
    sp = [E("serve.build", 15, 25), E("serve.build", 24, 27),
          E("serve.emit", 38, 45), E("serve.build", 48, 60)]
    assert spans.overlap_ns(idle, sp, 0, 50) == 7 + 5 + 2
    builds = [s for s in sp if s.name == "serve.build"]
    assert spans.overlap_ns(idle, builds, 0, 50) == 7 + 2
    assert spans.overlap_ns(idle, builds, 0, 49) == 7 + 1
    assert spans.overlap_ns(idle, [], 0, 50) == 0
    assert spans.overlap_ns([], sp, 0, 50) == 0
    # a span that covers several gaps counts each gap once
    assert spans.overlap_ns(idle, [E("serve.step", 0, 50)], 0, 50) == 20


def test_readers_return_none_without_the_program_s_records():
    from chipbench.engine_run import RunData
    run = RunData(None, {}, 1.0, 0.0, 0.0, 1.0, [], [], True)
    assert all(v is None for v in spans.read_all(run, [], [], []).values())
    assert spans.token_handoff_ms([(0.5, float("nan"))], 0.0, 1.0) is None


def test_smoke_run_reads_every_program_metric(monkeypatch, tmp_path):
    """A traced CPU run of the smoke cell: each of the seven readings is a
    number, and the idle time inside the three phases is part of the
    device's idle time."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")
    import spans_run
    from test_chipbench_run import PEAKS, SMOKE, smoke_tree
    from chipbench import engine_run, spec
    bench = smoke_tree(tmp_path)
    cell = spec.Cell(SMOKE, root=tmp_path, bench=bench)
    got = spans_run.instrument(monkeypatch.setattr)
    res = engine_run.run(cell, 2 ** 31 + 9, 2.0, True, t_start=0.0,
                         peaks=PEAKS, log=lambda m: None)
    logged = []
    out = spans_run.readings(res, got, logged.append)
    assert all(v is not None for v in out.values()), out
    assert logged and "serve." in logged[0]
    data = res["data"]
    s = trace.summary(data.trace, data.trace_lo, data.trace_hi)
    idle = 100.0 * (1.0 - s["busy_s"] / s["window_s"])
    parts = [out[f"idle_share.{p}"] for p in spans.IDLE_PHASES]
    assert all(p >= 0 for p in parts)
    assert sum(parts) <= idle + 1e-9
    assert out["queue_wait_ms"] >= 0 and out["token_handoff_ms"] >= 0
    # device time inside a step never exceeds the step's span
    longest = max(sp.dur for sp in got["spans"] if sp.name == "serve.step")
    assert out["step_device_ms.decode"] <= longest / 1e6
    assert res["check"]["requests"] > 0
