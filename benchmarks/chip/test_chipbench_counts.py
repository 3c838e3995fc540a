"""Operation and byte counts against numbers worked out by hand."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import counts, spec  # noqa: E402


def model(name: str) -> counts.Model:
    return counts.Model.from_config(
        spec.load_json(HERE / "configs" / f"{name}.json"))


def test_matmul_parameters_per_layer():
    # q, o: 3072 x 3072 each; k, v: 3072 x 256 each; GeLU MLP 2 x 3072 x 12288
    assert model("starcoder2_3b").layer_matmul_params == 95_944_704
    # q, o: 4096 x 4096; k, v: 4096 x 256; SwiGLU 3 x 4096 x 13696
    assert model("glm4_9b-pp2").layer_matmul_params == 203_948_032


def test_model_flops_per_token():
    m = model("starcoder2_3b")
    assert m.token_flops == 2 * 30 * 95_944_704
    assert m.head_flops == 2 * 49152 * 3072
    assert m.attn_flops(1000) == 4 * 30 * 24 * 128 * 1000


def test_decode_kernel_for_a_table_of_contexts():
    m = model("starcoder2_3b")
    flops, nbytes = m.decode_kernel((100, 0, 2048))
    # live rows 100 + 2048 tokens x 2 kv heads x 128 x (k, v) x 2 bytes,
    # plus q and o of the two live rows: 24 heads x 128 x 2 x 2 bytes
    assert nbytes == 2148 * 2 * 128 * 2 * 2 + 2 * 24 * 128 * 2 * 2
    assert nbytes == 2_224_128
    assert flops == 4 * 24 * 128 * 2148 == 26_394_624
    assert m.decode_kernel((0, 0)) == (0, 0)


def test_chunk_kernel_is_causal():
    m = model("glm4_9b-pp2")
    # 4 queries at positions 10..13 see 11 + 12 + 13 + 14 = 50 keys
    flops, nbytes = m.chunk_kernel(10, 4)
    assert flops == 4 * 32 * 128 * 50
    assert nbytes == 14 * 2 * 128 * 2 * 2 + 4 * 32 * 128 * 2 * 2


def test_step_flops_count_useful_tokens_only():
    m = model("starcoder2_3b")
    step = m.step_flops((10, 20), (0, 3), chunk_sampled=True)
    want = (2 * (m.token_flops + m.head_flops) + m.attn_flops(30)
            + 3 * m.token_flops + m.attn_flops(1 + 2 + 3) + m.head_flops)
    assert step == want
    assert m.step_flops((), (0, 3), False) == \
        3 * m.token_flops + m.attn_flops(6)


def test_roofline_names_its_bound():
    assert counts.roofline_s(197e12, 1, 197e12, 819e9) == \
        pytest.approx((1.0, "compute"))
    assert counts.roofline_s(1, 819e9, 197e12, 819e9) == \
        pytest.approx((1.0, "memory"))
