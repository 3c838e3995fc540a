"""Find the knee of an open-loop cell: the highest offered rate at which the
scheduler's queue does not grow over a window.

    python3 benchmarks/chip/sweep.py --workload starcoder2_3b.ide_completion \\
        --seed 7 --seconds 20 --rates 1.0,1.5,2.0,2.5

One process on the chip: the engine is built once, then each rate runs the
cell's traffic (warm-up, ramp, window) on it in turn. Prints, per rate, the
queue at the window's start and end, TTFT and ITL medians and 95th
percentiles, and output tokens per second. A calibration tool: the cell
file holds the rate it chose, and benchmark runs never call this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    from chipbench import counts, engine_run, reduce, spec, traffic
    cell = spec.Cell(args.workload, bench=spec.benchmark())
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("[sweep] needs a TPU", file=sys.stderr)
        return 3
    log = lambda m: print(f"[sweep] {m}", flush=True)   # noqa: E731
    cfg = engine_run.program_config(cell)
    eng, _ = engine_run.build(cell, cfg, args.seed)
    probe = engine_run.Probe(eng, traced=False)
    model = counts.Model.from_config(cell.config)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.load["rate_per_s"] = rate
        sch = traffic.schedule(cell.traffic, cell.load, cfg.vocab_size,
                               args.seed, args.seconds)
        probe.steps.clear()
        out = asyncio.run(engine_run._drive(eng, probe, sch, args.seconds,
                                            None, log))
        d = engine_run.RunData(model, {}, args.seconds, 0.0, out["w0"],
                               out["w1"], probe.steps, out["recs"], True)
        ws = d.window_steps
        tt, it = reduce.ttfts(d), reduce.itls(d)
        print(json.dumps({
            "rate_per_s": rate, "due_in_window": len(tt),
            "queue_start": ws[0].waiting, "queue_end": ws[-1].waiting,
            "queue_max": max(s.waiting for s in ws),
            "ttft_p50_ms": 1e3 * reduce.pct(tt, 50),
            "ttft_p95_ms": 1e3 * reduce.pct(tt, 95),
            "itl_p50_ms": 1e3 * reduce.pct(it, 50),
            "itl_p95_ms": 1e3 * reduce.pct(it, 95),
            "output_tok_s": reduce.tokens_in_window(d) / args.seconds,
            "prefix_hit_share": spec.load_reader("prefix_hit_share")(d)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
