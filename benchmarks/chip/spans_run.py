"""Run one cell as ``run.py --trace 1`` does, and read besides what the
serving program reports about itself: its ``serve.*`` spans, the engine's
``on_step`` records and each token's ``TokenEvent.emitted``.

    python3 benchmarks/chip/spans_run.py --workload glm4_9b-pp2.chat_batch \\
        --seed 1234 --seconds 51

It wraps four of the harness's functions before ``run.py``'s ``main``
runs: the engine build (to install ``on_step``), the client's stream (to
note each token's receipt and ``emitted`` times), the trace loader (to
keep the ``serve.*`` events) and the result line (to add the readings).
The last line of standard output is
``run.py``'s traced result line with one key more, ``program``: the
readings of ``chipbench/spans.py``, each None where nothing was read.
Earlier lines give the names on the trace's ``XLA Modules`` line and the
device's idle time by innermost ``serve.*`` span.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench_run  # noqa: E402
from chipbench import engine_run, spans, trace  # noqa: E402


def instrument(patch=setattr) -> dict:
    """Wrap the harness's functions with ``patch(module, name, value)``;
    returns what the wrappers collect: ``steps`` (StepRecord),
    ``handoffs`` (receipt, emitted) and ``spans`` (``serve.*`` events)."""
    got = {"steps": [], "handoffs": [], "spans": []}
    build, consume = engine_run.build, engine_run._consume
    load, result_line = trace.load, bench_run.result_line

    def build_hooked(cell, cfg, seed):
        eng, params = build(cell, cfg, seed)
        eng.on_step = got["steps"].append
        return eng, params

    async def stamped(stream):
        async for ev in stream:
            got["handoffs"].append((time.perf_counter(),
                                    getattr(ev, "emitted", math.nan)))
            yield ev

    def consume_stamped(stream, rec):
        return consume(stamped(stream), rec)

    def load_both(path):
        got["spans"] += spans.load(path)
        bench_run.log(f"XLA modules: {spans.module_names(path)}")
        return load(path)

    def result_line_read(bench, cell, res, traced, device):
        line = result_line(bench, cell, res, traced, device)
        line["program"] = readings(res, got, bench_run.log)
        return line

    patch(engine_run, "build", build_hooked)
    patch(engine_run, "_consume", consume_stamped)
    patch(trace, "load", load_both)
    patch(bench_run, "result_line", result_line_read)
    return got


def readings(res: dict, got: dict, log) -> dict:
    data = res["data"]
    log(f"idle by serve span: {spans.idle_by_span(data, got['spans'])}")
    return spans.read_all(data, got["spans"], got["steps"], got["handoffs"])


if __name__ == "__main__":
    bench_run.T_START = T_START
    instrument()
    sys.exit(bench_run.main(sys.argv[1:] + ["--trace", "1"]))
