"""Benchmark harness: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (DESIGN.md §6 maps each to the
paper's Table 1 / Figures 6-9 / §5 executor claim).

``--json OUT.json`` additionally writes the rows machine-readable: every
row carries ``name``, ``us_per_call`` and the derived string parsed into
typed fields (``tok_s``, ``ttft_p50_steps``, ``ttft_p95_ms``, ...), so CI
can archive the bench trajectory and tools can diff runs without scraping
the CSV. A positional filter selects benches by substring, comma-
separated: ``python benchmarks/run.py serving,paged_kernels``.
"""

from __future__ import annotations

import json
import sys
import time


def main() -> None:
    from benchmarks import paper_benches as pb
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()

    args = sys.argv[1:]
    json_out = None
    if "--json" in args:
        i = args.index("--json")
        json_out = args[i + 1]
        del args[i:i + 2]
    only = args[0].split(",") if args else None

    rows: list[dict] = []
    print("name,us_per_call,derived")
    benches = [
        pb.bench_table1_step_time,
        pb.bench_serving_throughput,
        pb.bench_serving_ragged_prefill,
        pb.bench_serving_kv_tiering,
        pb.bench_serving_sampling,
        pb.bench_serving_dp,
        pb.bench_paged_kernels,
        pb.bench_fig6_null_step,
        pb.bench_fig7_scaling,
        pb.bench_fig8_backup_workers,
        pb.bench_fig9_softmax,
        pb.bench_executor_dispatch,
    ]
    t0 = time.time()
    for bench in benches:
        if only and not any(o in bench.__name__ for o in only):
            continue
        try:
            bench(rows)
        except Exception as e:  # noqa: BLE001 - report and continue
            print(f"{bench.__name__},ERROR,{type(e).__name__}: {e}")
    print(f"# {len(rows)} rows in {time.time()-t0:.1f}s")
    if json_out:
        with open(json_out, "w") as f:
            json.dump({"rows": rows}, f, indent=1)
        print(f"# wrote {json_out}")


if __name__ == "__main__":
    main()
