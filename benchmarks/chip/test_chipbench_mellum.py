"""Mellum2-12B-A2.5B's configuration at smoke size, through the harness's
own functions: its file's sizes, program fields and reference checked as
the chip run checks them, one whole run with its reference file (prefill
then paged decode, Pallas kernels in interpret mode), and the reference
pinned to what it compares: without the window, or without YaRN on the
full layers, the same served tokens fail the gap. Also the grouped
matmul's roofline reader, on trace lines made from what a v5e compile
prints for the kernel and for the fusion that stages its weights.

The smoke run routes every token to all 8 experts (4 held). With fewer
per token, a near tie between the k-th and (k+1)-th router probability
falls one way in the program's bf16 and the other in the reference's
float32, and at this size one such flip moves a served logit as far as
the fp8 control does; top-k selection itself is checked against a dense
reference in tests/test_moe.py."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(REPO / "src"))

from chipbench import counts, engine_run, spec, trace  # noqa: E402
from chipbench.engine_run import RunData, StepRec  # noqa: E402

NAME = "mellum.sessions"
WINDOW = 16
# served tokens lie within ~1e-3 of the reference's best (bf16 against
# float32); a wrong token lies ~0.1 below it
LIMIT = 0.02
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
YARN = {"factor": 16.0, "original_max_positions": 32, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.2772588722239782}
SIZES = {"layers": 4, "d_model": 64, "heads": 4, "kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab": 256, "mlp": "swiglu",
         "norm": "rmsnorm", "norm_eps": 1e-6, "tied": False,
         "rope_theta": 500000.0,
         "layer_types": ["sliding", "sliding", "sliding", "full"],
         "window": WINDOW,
         "moe": {"experts": 8, "experts_held": 4, "experts_per_token": 8,
                 "d_ff_expert": 32},
         "yarn": YARN}


def mellum_tree(root: Path) -> dict:
    """A cells/ configs/ traffic/ references/ tree for the smoke-size
    configuration (4 of 8 experts held, all 8 routed), and the
    BENCHMARK.json naming its cell."""
    for d in ("cells", "configs", "traffic", "references"):
        (root / d).mkdir(parents=True, exist_ok=True)
    moe = {"num_experts": 8, "experts_per_token": 8, "d_ff_expert": 32,
           "experts_held": 4}
    config = {
        "sizes": SIZES,
        "program": {"arch": "mellum2_12b_a2p5b", "smoke": True,
                    "overrides": {"moe": moe},
                    "expect": {"moe.experts_held": 4} | {
                        f"rope_scaling.{k}": v for k, v in YARN.items()}},
        "reference": "references/mellum.py"}
    shutil.copy(HERE / "references" / "mellum2_12b-ep4.py",
                root / "references" / "mellum.py")
    sessions = {"arrival": "poisson",
                "prompt": {"median": 40, "sigma": 0.5, "min": 24, "max": 96},
                "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
                "sessions": {"count": 4, "zipf": 1.1, "new_share": 0.1,
                             "append_min": 4, "append_max": 16,
                             "max_context": 128},
                "sampling": {"temperature": 0.0}, "warmup_max_new": 2}
    cell = {"config": "mellum", "traffic": "sessions",
            "engine": {"max_batch": 4, "max_len": 128, "block_size": 16,
                       "num_blocks": 40, "chunk": 32, "prefill_pack": 1,
                       "kv_dtype": "bf16"},
            "load": {"rate_per_s": 4.0, "ramp_s": 0.5, "trace_s": 1.0},
            "check": {"logit_gap_limit": LIMIT, "max_requests": 8,
                      "tokens": 64}}
    (root / "configs" / "mellum.json").write_text(json.dumps(config))
    (root / "traffic" / "sessions.json").write_text(json.dumps(sessions))
    (root / "cells" / f"{NAME}.json").write_text(json.dumps(cell))
    bench = spec.benchmark()
    return dict(bench, workloads=[{"name": NAME, "config": "mellum",
                                   "traffic": "sessions", "chips": 1}])


def test_configuration_file_is_taken_unchanged():
    """The benchmark's file: the program the arch and overrides give has
    the sizes' widths, layer kinds, window and experts, and the expected
    held experts and YaRN fields; its own reference is found."""
    cell = spec.Cell("mellum2_12b-ep4.ide_completion", bench=spec.benchmark())
    cfg = engine_run.program_config(cell)
    assert cfg.moe.held == 16 and cfg.moe.num_experts == 64
    assert cfg.layer_kinds()[:4] == ("local", "local", "local", "attn")
    assert cfg.rope_scaling.original_max_positions == 8192
    assert spec.load_reference(cell).bucket(1) == 1024
    m = counts.Model.from_config(cell.config)
    assert m.kinds == {"sliding": 21, "full": 7}
    # attention + router + 2 of the 8 routed experts (8 x 16 / 64)
    assert m.layer_matmul_params == 21_233_664 + 147_456 + 2 * 3 * 2304 * 896


@pytest.fixture(scope="module")
def mellum(tmp_path_factory):
    root = tmp_path_factory.mktemp("chipbench_mellum")
    return root, mellum_tree(root)


@pytest.fixture(scope="module")
def run(mellum):
    """One run with the fp8 control, Pallas kernels in interpret mode."""
    import os
    root, bench = mellum
    os.environ["REPRO_FORCE_PALLAS"] = "interpret"
    try:
        cell = spec.Cell(NAME, root=root, bench=bench)
        res = engine_run.run(cell, 2 ** 31 + 77, 2.0, False, t_start=0.0,
                             peaks=PEAKS, log=lambda m: None, control=True)
    finally:
        del os.environ["REPRO_FORCE_PALLAS"]
    return cell, res


def test_run_through_the_engine_matches_its_reference(run):
    cell, res = run
    chk = res["check"]
    assert chk["requests"] > 0 and res["compiles"] == 0
    assert chk["logit_gap"] <= LIMIT < chk["control_gap"], chk
    done = [r for r in res["data"].recs if r.finished]
    # prompts reach past the window
    assert max(len(r.item.prompt) for r in done) > 2 * WINDOW


@pytest.mark.parametrize("drop", ["window", "yarn"])
def test_reference_without_the_window_or_yarn_fails_the_gap(run, drop):
    """The same served tokens against a reference whose sliding layers see
    every key, or whose full layers rotate with the plain table."""
    from chipbench import weights
    from repro.models import api
    cell, res = run
    cfg = engine_run.program_config(cell)
    params = weights.make_params(api.abstract_params(cfg)[0], 2 ** 31 + 77)
    ref = spec.load_reference(cell)
    sz = dict(SIZES)
    if drop == "window":
        sz["window"] = 10 ** 6
    else:
        del sz["yarn"]
    done = [r for r in res["data"].recs if r.finished]
    worst = max(float(ref.gaps(
        params, np.concatenate([r.item.prompt, r.tokens]),
        len(r.item.prompt), sz)[0].max()) for r in done)
    assert worst > LIMIT, worst


# the v5e trace prints a custom call with its operands' types; this one is
# the grouped matmul of a decode step (16 rows × 8 experts a token, 16
# held experts of 2304 × 896), with the operands a v5e compile gives it
GMM_LINE = (
    "%gmm.7 = f32[128,896]{1,0:T(8,128)} custom-call(s32[]{:T(128)} "
    "%get-tuple-element.73, s32[17]{0:T(128)} %pad_add_fusion.1, "
    "s32[16]{0:T(128)} %dynamic_slice.4, s32[16]{0:T(128)} %fusion.2, "
    "s32[1]{0:T(128)} %constant.84, bf16[128,2304]{1,0:T(8,128)(2,1)} "
    "%gather.3, bf16[16,2304,896]{2,1,0:T(8,128)(2,1)} %dynamic-slice.9), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    "{s32[], s32[17]{0}, s32[16]{0}, s32[16]{0}, s32[1]{0}, "
    "bf16[128,2304]{1,0}, bf16[16,2304,896]{2,1,0}}")


def _reader():
    import importlib.util
    s = importlib.util.spec_from_file_location(
        "expert_gmm_roofline", HERE / "metrics" / "expert_gmm_roofline.py")
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m


# the fusion that stages a layer's gate matrices for it (v5e compile): the
# layer scan slices them out of the stacked leaf into VMEM
STAGE_LINE = (
    "%dynamic-slice_bitcast_fusion.28 = bf16[16,2304,896]{2,1,0:T(8,128)"
    "(2,1)S(1)} fusion(bf16[7,16,2304,896]{3,2,1,0:T(8,128)(2,1)} "
    "%get-tuple-element.3066, s32[]{:T(128)} %get-tuple-element.2964), "
    "kind=kLoop, calls=%fused_computation.58.clone.clone")


def test_gmm_roofline_reads_the_grouped_matmul_only():
    import re
    m = _reader()
    cell = spec.Cell("mellum2_12b-ep4.ide_completion", bench=spec.benchmark())
    model = counts.Model.from_config(cell.config)
    assert re.search(m.PATTERN, GMM_LINE)
    assert re.search(m.pattern(model), GMM_LINE)
    assert re.search(m.pattern(model), STAGE_LINE)
    assert not re.search(m.PATTERN, STAGE_LINE)
    # the recorded ide trace's kernels and pool slices match neither
    recorded = trace.load(HERE / "testdata" / "ide_trace.xplane.pb.gz")
    assert not any(re.search(m.pattern(model), o.name)
                   for o in recorded.device_ops[0])
    # 5 decode rows: 40 routed rows, 10 on the 16 held experts, which
    # they touch 16 (1 - (7/8)^5) = 7.79 of
    f, b = m.work(model, 5)
    assert f == pytest.approx(2 * 10 * 3 * 2304 * 896)
    hit = 16 * (1 - (7 / 8) ** 5)
    assert b == pytest.approx(hit * 3 * 2304 * 896 * 2
                              + 10 * (2 * 2304 + 3 * 896) * 2)
    assert m.work(model, 0) == (0, 0)
    # two steps, three grouped matmuls a layer each, in a synthetic slice
    steps = [StepRec(1, 0, 1, (100,) * 5, None, False, False, 0, 0, 0),
             StepRec(2, 1, 2, (100,) * 5, (0, 256), False, False, 0, 0, 0)]
    E = trace.Event
    ms = 10 ** 6
    spans = [E("bench.step", 0, 1000 * ms, {"step": 1}),
             E("bench.step", 1000 * ms, 2000 * ms, {"step": 2})]
    # 100 calls of 1 ms in each step, and a staging fusion of 1 ms before
    # every tenth
    ops = [E(GMM_LINE, t, t + ms) for t in range(0, 2000 * ms, 10 * ms)]
    ops += [E(STAGE_LINE, t - 2 * ms, t - ms)
            for t in range(50 * ms, 2000 * ms, 100 * ms)]
    ops.append(E("%fusion.1 = bf16[2] fusion()", 5 * ms, 9 * ms))
    ops.sort(key=lambda o: o.start)
    run = RunData(model, {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                  1.0, 0.0, 0.0, 2.0, steps, [], True,
                  trace=trace.Trace([ops], spans), trace_hi=2000 * ms)
    least = sum(max(f / 197e12, b / 819e9) for f, b in
                (m.work(model, 5), m.work(model, 261))) * 28
    got = m.read(run)
    assert got == pytest.approx(100 * least / 0.22)
    assert 0 < got < 100
    dense = counts.Model.from_config(spec.Cell(
        "starcoder2_3b.ide_completion", bench=spec.benchmark()).config)
    run.model = dense
    assert m.read(run) == 0.0
