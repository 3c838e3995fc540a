"""CPU rehearsal of whole runs at smoke size, through the harness's own
functions: Pallas kernels in interpret mode, the chip check skipped, and
everything else as on the chip — weights, engine, warm-up, window, trace
reduction, metric readers, the reference comparison. Also: the command
itself exits non-zero off the chip, a cell dropped into a ``cells/``
directory is found by name, the fp8 control fails the comparison, a token
altered where it is produced makes ``correct`` false, and a configuration
with windowed layers and its own reference file is taken with files only.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(REPO / "src"))

from chipbench import engine_run, reference, spec  # noqa: E402

SMOKE = "smoke.sessions"
SMOKE_BATCH = "smoke.batch"
SMOKE_WIDE = "smoke.wide"
SMOKE_WINDOWED = "smoke.windowed"
WINDOW = 16
# the smoke model's served tokens lie within 1e-3 of the reference's best
# (bf16 against float32); a wrong token lies ~0.1 below it
LIMIT = 0.02
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def _load_run_module():
    s = importlib.util.spec_from_file_location("chipbench_run_cli",
                                               HERE / "run.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def smoke_tree(root: Path) -> dict:
    """A cells/ configs/ traffic/ tree for two smoke cells, and the
    BENCHMARK.json entries naming them."""
    for d in ("cells", "configs", "traffic"):
        (root / d).mkdir(parents=True, exist_ok=True)
    cfg = {"sizes": {"layers": 2, "d_model": 48, "heads": 6, "kv_heads": 2,
                     "head_dim": 8, "d_ff": 192, "vocab": 256, "mlp": "gelu",
                     "norm": "layernorm", "norm_eps": 1e-6, "tied": True,
                     "rope_theta": 10000.0},
           "program": {"arch": "starcoder2_3b", "smoke": True}}
    sessions = {"arrival": "poisson",
                "prompt": {"median": 40, "sigma": 0.5, "min": 16, "max": 96},
                "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
                "sessions": {"count": 4, "zipf": 1.1, "new_share": 0.1,
                             "append_min": 4, "append_max": 16,
                             "max_context": 128},
                "sampling": {"temperature": 0.0}, "warmup_max_new": 2}
    batch = {"arrival": "backlog",
             "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 60},
             "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
             "sampling": {"temperature": 0.7, "top_p": 0.9,
                          "greedy_every": 2},
             "warmup": [{"prompt": 8, "max_new": 3},
                        {"prompt": 8, "max_new": 3, "sampled": True}]}
    engine = {"max_batch": 4, "max_len": 128, "block_size": 16,
              "num_blocks": 40, "chunk": 32, "prefill_pack": 1,
              "kv_dtype": "bf16"}
    check = {"logit_gap_limit": LIMIT, "max_requests": 3, "tokens": 24}
    cells = {SMOKE: {"config": "smoke", "traffic": "sessions",
                     "engine": engine, "check": check,
                     "load": {"rate_per_s": 4.0, "ramp_s": 0.5,
                              "trace_s": 1.0}},
             SMOKE_BATCH: {"config": "smoke", "traffic": "batch",
                           "engine": engine, "check": check,
                           "load": {"backlog": 24, "warm_steps": 4,
                                    "trace_s": 1.0}}}
    wide = {"sizes": dict(cfg["sizes"], d_model=256, d_ff=1024, vocab=4096),
            "program": {"arch": "starcoder2_3b", "smoke": True,
                        "overrides": {"d_model": 256, "d_ff": 1024,
                                      "vocab_size": 4096}}}
    cells[SMOKE_WIDE] = dict(cells[SMOKE], config="wide",
                             check=dict(check, max_requests=8, tokens=64))
    # wide, with a sliding layer and a full one: the program is told its
    # layer kinds and window, checks them against the sizes, and is
    # compared against the reference file the configuration names
    kinds = {"block_pattern": ["local", "attn"], "sliding_window": WINDOW}
    windowed = {
        "sizes": dict(wide["sizes"], layer_types=["sliding", "full"],
                      window=WINDOW),
        "program": dict(wide["program"],
                        overrides=dict(wide["program"]["overrides"], **kinds)),
        "reference": "references/windowed.py"}
    cells[SMOKE_WINDOWED] = dict(cells[SMOKE_WIDE], config="windowed")
    (root / "references").mkdir(exist_ok=True)
    shutil.copy(HERE / "testdata" / "windowed_reference.py",
                root / "references" / "windowed.py")
    (root / "configs" / "smoke.json").write_text(json.dumps(cfg))
    (root / "configs" / "wide.json").write_text(json.dumps(wide))
    (root / "configs" / "windowed.json").write_text(json.dumps(windowed))
    (root / "traffic" / "sessions.json").write_text(json.dumps(sessions))
    (root / "traffic" / "batch.json").write_text(json.dumps(batch))
    for name, c in cells.items():
        (root / "cells" / f"{name}.json").write_text(json.dumps(c))
    bench = spec.benchmark()
    bench = dict(bench, workloads=[
        {"name": SMOKE, "config": "smoke", "traffic": "sessions", "chips": 1},
        {"name": SMOKE_BATCH, "config": "smoke", "traffic": "batch",
         "chips": 1},
        {"name": SMOKE_WIDE, "config": "wide", "traffic": "sessions",
         "chips": 1},
        {"name": SMOKE_WINDOWED, "config": "windowed",
         "traffic": "sessions", "chips": 1}])
    for kind in ("end_to_end", "per_layer"):
        bench[kind] = [dict(m, workloads=[SMOKE]) if "workloads" in m
                       else m for m in bench[kind]]
    return bench


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    root = tmp_path_factory.mktemp("chipbench")
    return root, smoke_tree(root)


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")


def _run(root, bench, name, seed, traced, control=False):
    cell = spec.Cell(name, root=root, bench=bench)
    res = engine_run.run(cell, seed, 2.0, traced, t_start=0.0, peaks=PEAKS,
                         log=lambda m: None, control=control)
    line = _load_run_module().result_line(
        bench, cell, res, traced, {"platform": "cpu", "kind": "cpu",
                                   "count": 1})
    return cell, res, line


@pytest.mark.parametrize("name,traced", [(SMOKE, False), (SMOKE, True),
                                         (SMOKE_BATCH, False)])
def test_smoke_run_is_correct_and_reports_its_metrics(smoke, name, traced):
    root, bench = smoke
    _, res, line = _run(root, bench, name, 2 ** 31 + 5, traced)
    assert line["correct"], line["check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "check"
    assert res["compiles"] == 0
    want = {m["name"] for m in spec.metrics_for(bench, name, traced)}
    if traced:
        # the CPU has no Pallas kernel events: rooflines stay silent
        want -= {"paged_decode_roofline", "paged_prefill_roofline"}
        assert line["device"]["busy_s"] > 0
        assert line["breakdown"]["device_ops"]
        assert line["breakdown"]["idle_gaps"]
    assert want <= set(line["metrics"]), (want, line["metrics"])
    json.dumps(line, allow_nan=False)


def test_fp8_control_fails_the_comparison(smoke):
    root, bench = smoke
    _, res, _ = _run(root, bench, SMOKE_WIDE, 11, False, control=True)
    assert res["check"]["logit_gap"] <= LIMIT
    assert res["check"]["control_gap"] > LIMIT


def test_windowed_configuration_with_its_own_reference(smoke):
    """A configuration with a sliding layer, its program's layer kinds and
    window checked against its sizes, and its own reference file, runs
    correct through the whole harness; the fp8 control fails it; and the
    same served tokens fail a reference without the window, so the window
    is what it compares."""
    from chipbench import weights
    from repro.models import api
    root, bench = smoke
    seed = 2 ** 32 + 13
    cell, res, line = _run(root, bench, SMOKE_WINDOWED, seed, False,
                           control=True)
    assert line["correct"], line["check"]
    assert res["check"]["control_gap"] > LIMIT
    ref = spec.load_reference(cell)
    assert ref is not reference and ref.bucket(1) == 1024
    cfg = engine_run.program_config(cell)
    assert cfg.layer_kinds() == ("local", "attn")
    params = weights.make_params(api.abstract_params(cfg)[0], seed)
    sz = cell.config["sizes"]
    unwindowed = dict(sz, window=10 ** 6)
    done = [r for r in res["data"].recs if r.finished]
    assert max(len(r.item.prompt) for r in done) > 4 * WINDOW
    worst = {}
    for name, s in (("windowed", sz), ("full", unwindowed)):
        worst[name] = max(float(ref.gaps(
            params, np.concatenate([r.item.prompt, r.tokens]),
            len(r.item.prompt), s)[0].max()) for r in done)
    assert worst["windowed"] <= LIMIT < worst["full"], worst


def test_altered_token_makes_the_run_incorrect(smoke, monkeypatch):
    """A token altered where it is produced: the sampler hands back the
    id after the one it chose."""
    from repro.serving import runners
    real = runners.sample_tokens

    def off_by_one(logits, *a):
        return (real(logits, *a) + 1) % 256

    monkeypatch.setattr(runners, "sample_tokens", off_by_one)
    root, bench = smoke
    _, res, line = _run(root, bench, SMOKE, 12, False)
    assert not line["correct"]
    assert res["check"]["logit_gap"] > LIMIT


def test_cell_file_in_a_cells_directory_is_found_by_name(tmp_path):
    smoke_tree(tmp_path)
    extra = json.loads((tmp_path / "cells" / f"{SMOKE}.json").read_text())
    (tmp_path / "cells" / "smoke.added.json").write_text(json.dumps(extra))
    cell = spec.Cell("smoke.added", root=tmp_path)
    assert cell.config["sizes"]["d_model"] == 48
    assert cell.traffic["arrival"] == "poisson"
    with pytest.raises(FileNotFoundError):
        spec.Cell("smoke.absent", root=tmp_path)


def test_command_exits_nonzero_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_FORCE_PALLAS", None)
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "starcoder2_3b.ide_completion", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_reference_matches_itself_across_buckets():
    """The padded tail never reaches earlier positions (causal)."""
    import jax
    from chipbench import weights
    from repro.config import get_config
    from repro.models import api
    cfg = get_config("starcoder2_3b", smoke=True)
    shapes, _ = api.abstract_params(cfg)
    params = weights.make_params(shapes, 3)
    sz = {"layers": 2, "d_model": 48, "heads": 6, "kv_heads": 2,
          "head_dim": 8, "d_ff": 192, "vocab": 256, "mlp": "gelu",
          "norm": "layernorm", "norm_eps": 1e-6, "tied": True,
          "rope_theta": 10000.0}
    seq = np.random.default_rng(0).integers(0, 256, 80).astype(np.int32)
    a, _ = reference.gaps(params, seq, 60, sz, t_len=512)
    b, _ = reference.gaps(params, seq, 60, sz, t_len=1024, p_len=256)
    np.testing.assert_allclose(a, b, atol=1e-5)
    assert a.shape == (20,) and (a >= 0).all()
    assert jax.tree.leaves(params)[0].dtype == "bfloat16"


def test_reference_weight_matmuls_are_float32_exact():
    """Three bf16 parts of the activations against bf16 weights give the
    float32 product; the control's one-pass fp8 matmul equals its scaled
    operands multiplied out."""
    import jax
    import jax.numpy as jnp
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (32, 256), jnp.float32)
    w = jax.random.normal(k2, (256, 128)).astype(jnp.bfloat16) \
        .astype(jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(w, np.float64)
    np.testing.assert_allclose(reference._mm(a, w, False), exact,
                               rtol=0, atol=1e-4)
    (qa, sa), (qw, sw) = reference._q8(a, -1), reference._q8(w, 0)
    ctl = (np.asarray(qa, np.float64) * np.asarray(sa)) @ \
        (np.asarray(qw, np.float64) * np.asarray(sw))
    np.testing.assert_allclose(reference._mm(a, w, True), ctl, rtol=0,
                               atol=1e-4)
    # fp8 is coarse: the control moves the product far more than that
    assert np.abs(ctl - exact).max() > 1e-2
    assert reference.bucket(1) == reference.bucket(1024) == 1024
    assert reference.bucket(1025) == 2048
