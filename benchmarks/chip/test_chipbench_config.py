"""A configuration file tells the harness everything it needs: the
program fields to check (its ``sizes``, and ``program.expect`` for what
they do not state), which reference to compare against (``"reference"``),
and, through the program's parameter tree, how to draw the weights.
Checked here without running a step."""

from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import counts, engine_run, reference, spec, weights  # noqa: E402

# sha256 over each leaf's path and bytes of ``make_params`` at smoke size,
# seed 2**33 + 7, from before the fan-in rule looked at a leaf's parent:
# dense trees draw exactly the same weights
PARAMS_SHA256 = {
    "starcoder2_3b":
        "fe0eec66172ba19b58b15a0b5e6f50ced740fe720069c5d906220d4e8529442c",
    "glm4_9b":
        "cbb9b9abdee1ef51ead2f20698a034fb20bdbd15aca02b6a4f04218edf92593a",
}

SMOKE_SIZES = {"layers": 2, "d_model": 48, "heads": 6, "kv_heads": 2,
               "head_dim": 8, "d_ff": 192, "vocab": 256, "mlp": "gelu",
               "norm": "layernorm", "norm_eps": 1e-6, "tied": True,
               "rope_theta": 10000.0}
WINDOWED = {"block_pattern": ["local", "attn"], "sliding_window": 16}
WINDOWED_SIZES = {"layer_types": ["sliding", "full"], "window": 16}


def _cell(root: Path, config: dict) -> spec.Cell:
    for d in ("cells", "configs", "traffic"):
        (root / d).mkdir(parents=True, exist_ok=True)
    (root / "configs" / "c.json").write_text(json.dumps(config))
    (root / "traffic" / "t.json").write_text("{}")
    (root / "cells" / "c.json").write_text(json.dumps(
        {"config": "c", "traffic": "t", "engine": {}, "check": {}}))
    return spec.Cell("c", root=root)


@pytest.mark.parametrize("arch", sorted(PARAMS_SHA256))
def test_dense_trees_draw_the_same_weights(arch):
    import jax
    from repro.config import get_config
    from repro.models import api
    shapes, _ = api.abstract_params(get_config(arch, smoke=True))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            weights.make_params(shapes, 2 ** 33 + 7))[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == PARAMS_SHA256[arch]


@pytest.mark.parametrize("path,shape,fan_in", [
    (("blocks", "sub0", "moe", "router"), (2, 256, 64), 256),
    (("blocks", "sub0", "moe", "w_gate"), (2, 64, 256, 96), 256),
    (("blocks", "sub0", "moe", "w_in"), (2, 64, 256, 96), 256),
    (("blocks", "sub0", "moe", "w_out"), (2, 64, 96, 256), 96),
])
def test_expert_and_router_leaves_scale_by_their_input_width(path, shape,
                                                             fan_in):
    import jax
    x = jax.jit(lambda k: weights._leaf(k, path, shape, stacked=True))(
        jax.random.key(5))
    assert x.shape == shape and x.dtype == "bfloat16"
    std = float(np.asarray(x, np.float32).std())
    assert std == pytest.approx(fan_in ** -0.5, rel=0.05)


def test_a_leaf_without_a_rule_is_refused_by_its_path():
    import jax
    with pytest.raises(KeyError, match="blocks/sub0/attn/q_norm"):
        weights._leaf(jax.random.key(0), ("blocks", "sub0", "attn",
                                          "q_norm"), (2, 16), stacked=True)


def test_an_expert_configuration_is_taken_as_files(tmp_path):
    """A sparse-expert configuration (the program's grok-1 smoke preset,
    made SwiGLU) stated in ``sizes``, its soft cap in ``program.expect``:
    its cell, program config, weights and counts, with no other file
    touched."""
    from repro.models import api
    sizes = {"layers": 2, "d_model": 64, "heads": 4, "kv_heads": 2,
             "head_dim": 16, "d_ff": 64, "vocab": 256, "mlp": "swiglu",
             "norm": "rmsnorm", "norm_eps": 1e-6, "tied": True,
             "rope_theta": 10000.0,
             "moe": {"experts": 4, "experts_held": 4,
                     "experts_per_token": 2, "d_ff_expert": 64}}
    cell = _cell(tmp_path, {"sizes": sizes, "program": {
        "arch": "grok1_314b", "smoke": True,
        "overrides": {"mlp_activation": "silu"},
        "expect": {"attn_logit_softcap": 30.0}}})
    cfg = engine_run.program_config(cell)
    experts = weights.make_params(api.abstract_params(cfg)[0],
                                  7)["blocks"]["sub0"]["moe"]
    assert experts["router"].shape == (2, 64, 4)
    for name in ("w_gate", "w_in", "w_out"):          # (L, E, 64, 64)
        std = float(np.asarray(experts[name], np.float32).std())
        assert std == pytest.approx(64 ** -0.5, rel=0.05)
    m = counts.Model.from_config(cell.config)
    # attention 2 x 64 x 64 + 2 x 64 x 32; router 64 x 4; 2 of 4 experts
    # per token, all held: 2 x 3 x 64 x 64
    assert m.token_flops == 2 * 2 * (12288 + 256 + 2 * 3 * 64 * 64)


def _program(sizes=None, **program) -> dict:
    return {"sizes": dict(SMOKE_SIZES, **(sizes or {})),
            "program": dict({"arch": "starcoder2_3b", "smoke": True},
                            **program)}


QWEN3_MOE_SIZES = dict(SMOKE_SIZES, d_model=64, heads=4, head_dim=16,
                       d_ff=32, mlp="swiglu", norm="rmsnorm", tied=False,
                       moe={"experts": 8, "experts_held": 8,
                            "experts_per_token": 2, "d_ff_expert": 32})


def test_program_config_takes_matching_sizes_and_expect(tmp_path):
    cfg = engine_run.program_config(_cell(tmp_path, _program(
        WINDOWED_SIZES, overrides=WINDOWED,
        expect={"attn_logit_softcap": None})))
    assert cfg.layer_kinds() == ("local", "attn")
    assert cfg.sliding_window == 16
    moe = engine_run.program_config(_cell(tmp_path, {
        "sizes": QWEN3_MOE_SIZES,
        "program": {"arch": "qwen3_moe_30b_a3b", "smoke": True,
                    "expect": {"moe.router_jitter": 0.0}}}))
    assert moe.moe.d_ff_expert == 32


@pytest.mark.parametrize("sizes,program,match", [
    (dict(WINDOWED_SIZES, window=32), {"overrides": WINDOWED},
     "sliding_window"),
    (WINDOWED_SIZES, {"overrides": WINDOWED,
                      "expect": {"window.size": 4}}, "'window.size'"),
    ({"moe": QWEN3_MOE_SIZES["moe"]}, {},
     "'moe.num_experts'"),
    ({"layer_types": ["sliding", "full"]}, {"overrides": WINDOWED},
     "sliding_window"),
    ({}, {"overrides": {"sliding_window": 16}}, "sliding_window"),
    ({"window": 16}, {"overrides": WINDOWED}, "layer_types"),
    ({"layer_types": ["full", "sliding"], "window": 16},
     {"overrides": WINDOWED}, "layer_types"),
    (WINDOWED_SIZES, {"overrides": WINDOWED,
                      "expect": {"sliding_window": 16}}, "sizes state"),
    ({"moe": QWEN3_MOE_SIZES["moe"]}, {"expect": {"moe.num_experts": 8}},
     "sizes state"),
])
def test_program_config_refuses_what_the_file_does_not_announce(
        tmp_path, sizes, program, match):
    """A window, layer kinds or experts in the sizes that the program does
    not have, or the other way round; a path the program lacks; and a
    field that ``expect`` states twice."""
    with pytest.raises(ValueError, match=match):
        engine_run.program_config(_cell(tmp_path, _program(sizes,
                                                           **program)))


def test_program_config_refuses_experts_the_sizes_do_not_state(tmp_path):
    sizes = {k: v for k, v in QWEN3_MOE_SIZES.items() if k != "moe"}
    with pytest.raises(ValueError, match="'moe'"):
        engine_run.program_config(_cell(tmp_path, {
            "sizes": sizes,
            "program": {"arch": "qwen3_moe_30b_a3b", "smoke": True}}))
    wrong = dict(QWEN3_MOE_SIZES, moe=dict(QWEN3_MOE_SIZES["moe"],
                                           experts_per_token=4))
    with pytest.raises(ValueError, match="moe.experts_per_token"):
        engine_run.program_config(_cell(tmp_path, {
            "sizes": wrong,
            "program": {"arch": "qwen3_moe_30b_a3b", "smoke": True}}))


@pytest.mark.parametrize("extra", [
    {"layer_types": ["sliding", "full"], "window": 16},
    {"moe": {"experts": 8, "experts_held": 8, "experts_per_token": 2,
             "d_ff_expert": 32}},
    {"layer_types": ["full", "linear"]},
    {"window": 16},
])
def test_dense_reference_refuses_sizes_it_does_not_implement(extra):
    seq = np.arange(40, dtype=np.int32)
    with pytest.raises(ValueError, match="own reference"):
        reference.gaps(None, seq, 30, dict(SMOKE_SIZES, **extra))


def test_reference_is_the_configuration_s_file_or_the_dense_one(tmp_path):
    root = tmp_path / "bench"
    assert spec.load_reference(_cell(root, _program())) is reference
    (root / "references").mkdir()
    mine = "def gaps(*a, **k):\n    return 'mine'\n"
    (root / "references" / "mine.py").write_text(mine)
    (tmp_path / "outside.py").write_text(mine)
    cell = _cell(root, dict(_program(), reference="references/mine.py"))
    assert spec.load_reference(cell).gaps() == "mine"
    for bad in ("references/absent.py", "../outside.py"):
        with pytest.raises(FileNotFoundError):
            spec.load_reference(_cell(root, dict(_program(), reference=bad)))
