"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload starcoder2_3b.ide_completion \\
        --seed 1234 --seconds 30 --trace 0

From the root of a checkout, on a machine that holds the chips the cell
asks for. It builds the engine with weights drawn from the seed, warms up
every step program the cell's traffic uses (set-up), measures for
``--seconds``, then compares a sample of the served tokens with the plain
reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``check``: each
number compared beside its limit. Without a TPU, or with fewer chips than
the cell asks for, it exits 3 and prints no result.

``--control 1`` (for calibrating the check, never in a benchmark run) also
reads the fp8 control's gap on the same served tokens.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(bench: dict, cell, res: dict, traced: bool,
                device: dict) -> dict:
    from chipbench import spec, trace
    data, chk = res["data"], res["check"]
    metrics = {}
    for m in spec.metrics_for(bench, cell.name, traced):
        v = spec.load_reader(m["name"])(data)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    w0, w1 = data.w0, data.w1
    if data.open_loop:
        att = [r for r in data.recs if w0 <= r.due < w1]
    else:
        att = [r for r in data.recs if any(w0 < t <= w1 for t in r.times)]
    failed = sum(r.error is not None for r in data.recs)
    limit = cell.check["logit_gap_limit"]
    correct = (chk["requests"] > 0 and failed == 0
               and chk["logit_gap"] is not None
               and chk["logit_gap"] <= limit)
    device = dict(device, memory_peak_bytes=res["mem_peak"])
    line = {"correct": bool(correct), "attempted": len(att),
            "failed": failed, "metrics": metrics, "device": device}
    if traced:
        s = trace.summary(data.trace, data.trace_lo, data.trace_hi)
        line["device"].update(busy_s=s["busy_s"], window_s=s["window_s"])
        line["breakdown"] = {"device_ops": s["device_ops"],
                             "idle_gaps": s["idle_gaps"]}
        log(f"idle by host span: {s['idle_by_span']}")
    check = {"logit_gap": {"value": chk["logit_gap"], "limit": limit},
             "compared_requests": {"value": chk["requests"], "limit": 1},
             "failed_requests": {"value": failed, "limit": 0}}
    if "control_gap" in chk:
        check["control_gap"] = {"value": chk["control_gap"], "limit": limit}
    line["check"] = check
    return line


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import engine_run, spec
    bench = spec.benchmark()
    cell = spec.Cell(args.workload, bench=bench)
    from repro.launch.compile_cache import configure_compile_cache
    log(f"compile cache: {configure_compile_cache()}")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"[chipbench] needs {cell.chips} TPU chip(s); JAX reports "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    from chipbench.peaks import peaks
    pk = peaks(devs[0].device_kind)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    res = engine_run.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, pk, log, control=bool(args.control))
    line = result_line(bench, cell, res, bool(args.trace), device)
    for name, c in line["check"].items():
        print(f"[chipbench] check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    print(json.dumps(line, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
