"""The trace reduction, on hand-made events and on a trace recorded on a
TPU v5e (``testdata/ide_trace.xplane.pb.gz``: 0.5 s of
starcoder2_3b.ide_completion, three engine steps — one decode-only step and
two that carry a 256-token prefill chunk)."""

from __future__ import annotations

import importlib.util
import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import counts, reduce, trace  # noqa: E402
from chipbench.engine_run import RunData, StepRec  # noqa: E402

E = trace.Event


def _pattern(metric: str) -> str:
    s = importlib.util.spec_from_file_location(
        f"pattern_{metric}", HERE / "metrics" / f"{metric}.py")
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m.PATTERN


def test_union_busy_and_gaps_on_hand_made_events():
    ops = [E("a", 0, 10), E("b", 5, 20), E("c", 30, 40), E("d", 35, 38)]
    assert trace.union([(o.start, o.end) for o in ops], 0, 50) == \
        [(0, 20), (30, 40)]
    assert trace.busy_ns(ops, 0, 50) == 30
    assert trace.busy_ns(ops, 8, 35) == 17          # clipped to the slice
    assert trace.gaps(ops, 0, 50) == [(20, 30), (40, 50)]
    assert trace.gaps(ops, -5, 45) == [(-5, 0), (20, 30), (40, 45)]


def test_self_time_takes_nested_operations_out_of_their_parent():
    ops = [E("%while.1 = ...", 0, 100), E("%fusion.2 = ...", 10, 30),
           E("%fusion.3 = ...", 40, 90), E("%copy.4 = ...", 50, 60)]
    got = {o.name.split(" ")[0]: t for o, t in trace.self_times(ops)}
    assert got == {"%while.1": 30, "%fusion.2": 20, "%fusion.3": 40,
                   "%copy.4": 10}


def test_open_span_is_the_innermost():
    spans = [E("bench.step", 0, 100), E("bench.schedule", 5, 20),
             E("bench.build_arrays", 25, 30)]
    assert trace.open_span(spans, 10) == "bench.schedule"
    assert trace.open_span(spans, 27) == "bench.build_arrays"
    assert trace.open_span(spans, 50) == "bench.step"
    assert trace.open_span(spans, 150) == "none"


def test_labels_name_pallas_kernels_by_output_type():
    assert trace.label("%fusion.12 = bf16[2] fusion(...)") == "fusion"
    assert trace.label('%closed_call.3 = bf16[32,12,128]{2,1,0} custom-call('
                       's32[1]) custom_call_target="tpu_custom_call"') == \
        "tpu_custom_call bf16[32,12,128]"


@pytest.fixture(scope="module")
def chip_trace():
    return trace.load(HERE / "testdata" / "ide_trace.xplane.pb.gz")


def test_recorded_trace_busy_and_idle(chip_trace):
    lo, hi = trace.window(chip_trace)
    steps = [s for s in chip_trace.spans if s.name == "bench.step"]
    assert [s.stats["step"] for s in steps] == [513, 514, 515]
    assert (lo, hi) == (steps[0].start, steps[-1].end)
    s = trace.summary(chip_trace, lo, hi)
    assert s["window_s"] == pytest.approx(0.496607337)
    assert s["busy_s"] == pytest.approx(0.455701086)
    idle = sum(s["idle_by_span"].values())
    # gaps under MIN_GAP_NS are dropped: they are clock rounding
    assert idle == pytest.approx(s["window_s"] - s["busy_s"], abs=1e-5)
    assert s["idle_by_span"]["bench.schedule"] > 0.02
    assert [g[0] for g in s["idle_gaps"][:2]] == ["bench.schedule"] * 2


def test_recorded_trace_kernels_match_by_signature(chip_trace):
    ops = chip_trace.device_ops[0]
    dec = [o for o in ops
           if re.search(_pattern("paged_decode_roofline"), o.name)]
    pre = [o for o in ops
           if re.search(_pattern("paged_prefill_roofline"), o.name)]
    assert {trace.label(o.name) for o in dec} == \
        {"tpu_custom_call bf16[32,12,128]"}
    assert {trace.label(o.name) for o in pre} == \
        {"tpu_custom_call bf16[2,256,12,128]"}
    # one call per layer (30) per step; chunks ride the last two steps only
    assert len(dec) == 90 and len(pre) == 60
    s = trace.summary(chip_trace, *trace.window(chip_trace))
    top = dict(s["device_ops"])
    assert top["tpu_custom_call bf16[32,12,128]"] == pytest.approx(
        sum(o.dur for o in dec) / 1e9)


def test_roofline_share_over_the_recorded_steps(chip_trace):
    model = counts.Model(layers=30, d=3072, heads=24, kv_heads=2,
                         head_dim=128, d_ff=12288, vocab=49152, gated=False)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    recs = [StepRec(i, 0, 0, (2048,) * 16, None if i == 513 else (0, 256),
                    False, False, 0, 0, 0) for i in (513, 514, 515)]
    run = RunData(model, peaks, 1.0, 0.0, 0.0, 1.0, recs, [], True,
                  chip_trace, *trace.window(chip_trace))
    dec = reduce.roofline(run, _pattern("paged_decode_roofline"),
                          lambda s: model.decode_kernel(s.decode_ctxs))
    f, b = model.decode_kernel((2048,) * 16)["full"]
    least = 3 * 30 * max(f / 197e12, b / 819e9)
    assert dec == pytest.approx(100 * least / 0.144479949, rel=1e-6)
    pre = reduce.roofline(
        run, _pattern("paged_prefill_roofline"),
        lambda s: model.chunk_kernel(*s.chunk) if s.chunk else (0, 0))
    f, b = model.chunk_kernel(0, 256)["full"]
    least = 2 * 30 * max(f / 197e12, b / 819e9)
    assert pre == pytest.approx(100 * least / 0.1255249, rel=1e-6)
    assert reduce.roofline(run, r"no such kernel", lambda s: (1, 1)) is None


def test_roofline_sums_each_layer_kind_s_least_time():
    """3 sliding layers and 1 full: the least time of a step's calls is
    3 × that of a sliding call + 1 × that of a full one, not 4 × the
    full one's."""
    model = counts.Model(layers=4, d=2304, heads=32, kv_heads=4,
                         head_dim=128, d_ff=7168, vocab=98304, gated=True,
                         layer_types=("sliding",) * 3 + ("full",),
                         window=1024)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctxs = (4096,) * 8
    kernel = ("%k = bf16[32,8,128]{2,1,0} custom-call(s32[8,256]{1,0} %a, "
              "s32[8]{0} %b, s32[8,256]{1,0} %c, bf16[8,32,128] %q), "
              'custom_call_target="tpu_custom_call"')
    spent_ns = 400_000
    tr = trace.Trace([[E(kernel, 10, 10 + spent_ns)]],
                     [E("bench.step", 0, 1_000_000, {"step": 0})])
    run = RunData(model, peaks, 1.0, 0.0, 0.0, 1.0,
                  [StepRec(0, 0, 0, ctxs, None, False, False, 0, 0, 0)], [],
                  False, tr, 0, 1_000_000)
    got = reduce.roofline(run, _pattern("paged_decode_roofline"),
                          lambda s: model.decode_kernel(s.decode_ctxs))
    work = model.decode_kernel(ctxs)
    least = sum(n * counts.roofline_s(*work[k], 197e12, 819e9)[0]
                for k, n in (("sliding", 3), ("full", 1)))
    assert got == pytest.approx(100 * least / (spent_ns / 1e9), rel=1e-12)
    # the window cuts each sliding call's KV bytes by 4 at context 4,096:
    # counting 4 full calls would overstate the least time by 16 / 7
    full_only = 4 * counts.roofline_s(*work["full"], 197e12, 819e9)[0]
    assert least == pytest.approx(full_only * 7 / 16, rel=1e-2)
