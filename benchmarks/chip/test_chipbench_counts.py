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
    flops, nbytes = m.decode_kernel((100, 0, 2048))["full"]
    # live rows 100 + 2048 tokens x 2 kv heads x 128 x (k, v) x 2 bytes,
    # plus q and o of the two live rows: 24 heads x 128 x 2 x 2 bytes
    assert nbytes == 2148 * 2 * 128 * 2 * 2 + 2 * 24 * 128 * 2 * 2
    assert nbytes == 2_224_128
    assert flops == 4 * 24 * 128 * 2148 == 26_394_624
    assert m.decode_kernel((0, 0)) == {"full": (0, 0)}


def test_chunk_kernel_is_causal():
    m = model("glm4_9b-pp2")
    # 4 queries at positions 10..13 see 11 + 12 + 13 + 14 = 50 keys
    flops, nbytes = m.chunk_kernel(10, 4)["full"]
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


# counts.Model of both configuration files before layer kinds and experts
# could be stated: the same sizes must count the same to the digit
GOLDEN = {
    "starcoder2_3b": {
        "token_flops": 5756682240, "head_flops": 301989888,
        "attn_flops": [368640, 368640000, 1509949440],
        "decode_kernel": [(26394624, 2224128), (805306368, 67305472),
                          (66809856, 5628928)],
        "chunk_kernel": [(404226048, 3407872), (3549954048, 4431872),
                         (12483821568, 7340032)],
        "step_flops": [29702651904, 241063428096, 1596066103296]},
    "glm4_9b-pp2": {
        "token_flops": 8157921280, "head_flops": 1241513984,
        "attn_flops": [327680, 327680000, 1342177280],
        "decode_kernel": [(35192832, 2232320), (1073741824, 67371008),
                          (89079808, 5649408)],
        "chunk_kernel": [(538968064, 4456448), (4733272064, 5480448),
                         (16645095424, 8388608)],
        "step_flops": [44525944832, 342724968448, 2205216145408]},
}
CTXS = [(100, 0, 2048), (4096,) * 16, (1, 17, 300, 1024, 4095)]
CHUNKS = [(0, 256), (1000, 256), (3840, 256)]
STEPS = [((10, 20), (0, 3), True), ((4000,) * 32, None, False),
         ((1500, 2500), (1024, 256), False)]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_published_sizes_count_as_before(name):
    m, want = model(name), GOLDEN[name]
    assert m.kinds == {"full": m.layers}
    got = {"token_flops": m.token_flops, "head_flops": m.head_flops,
           "attn_flops": [m.attn_flops(c) for c in (1, 1000, 4096)],
           "decode_kernel": [m.decode_kernel(c)["full"] for c in CTXS],
           "chunk_kernel": [m.chunk_kernel(*c)["full"] for c in CHUNKS],
           "step_flops": [m.step_flops(*s) for s in STEPS]}
    assert got == want
    assert all(type(v) is int for v in got["step_flops"])


def mixed(**kw) -> counts.Model:
    """Mellum2-like: 3 sliding layers (window 1,024) and 1 full, 16 of 64
    experts held, 8 per token, experts 896 wide, SwiGLU."""
    sizes = {"layers": 4, "d_model": 2304, "heads": 32, "kv_heads": 4,
             "head_dim": 128, "d_ff": 7168, "vocab": 98304,
             "mlp": "swiglu", "layer_types": ["sliding"] * 3 + ["full"],
             "window": 1024,
             "moe": {"experts": 64, "experts_held": 16,
                     "experts_per_token": 8, "d_ff_expert": 896}}
    return counts.Model.from_config({"sizes": dict(sizes, **kw)})


def test_expert_layers_count_router_and_held_share():
    m = mixed()
    # q, o: 2304 x 4096 each; k, v: 2304 x 512 each
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert attn == 21_233_664
    router = 2304 * 64
    # 8 experts per token x 16 held / 64 = 2 experts of 3 x 2304 x 896
    routed = 2 * 3 * 2304 * 896
    assert m.token_flops == 2 * 4 * (attn + router + routed) == 270_139_392
    # all 64 held: 8 experts per token on this chip
    whole = mixed(moe={"experts": 64, "experts_held": 64,
                       "experts_per_token": 8, "d_ff_expert": 896})
    assert whole.token_flops == 2 * 4 * (attn + router + 4 * routed)


def test_sliding_layers_see_at_most_the_window():
    m = mixed()
    assert m.kinds == {"sliding": 3, "full": 1}
    H, hd, K = 32, 128, 4
    assert m.attn_flops(4096) == 4 * H * hd * (3 * 1024 + 4096)
    assert m.attn_flops(500) == 4 * H * hd * 4 * 500
    work = m.decode_kernel((4096, 500, 0))
    qo = 2 * H * hd * 2 * 2
    assert work["full"] == (4 * H * hd * 4596, 4596 * K * hd * 2 * 2 + qo)
    assert work["sliding"] == (4 * H * hd * 1524,
                               1524 * K * hd * 2 * 2 + qo)
    # queries at 3000..3255 each see 1,024 keys, 1,279 keys in all
    f, b = m.chunk_kernel(3000, 256)["sliding"]
    assert f == 4 * H * hd * 256 * 1024
    assert b == 1279 * K * hd * 2 * 2 + 256 * H * hd * 2 * 2
    # queries at 900..1022 see 901..1023 keys, those at 1023..1155 1,024
    assert m.chunk_keys("sliding", 900, 256) == \
        (901 + 1023) * 123 // 2 + 133 * 1024 == 254_518
    for start, n in ((0, 256), (700, 256), (768, 256), (1023, 1),
                     (5000, 7)):
        assert m.chunk_keys("sliding", start, n) == sum(
            min(p + 1, 1024) for p in range(start, start + n))
        assert m.chunk_keys("full", start, n) == sum(
            p + 1 for p in range(start, start + n))
    step = m.step_flops((4096,), (3000, 256), False)
    assert step == (257 * m.token_flops + m.head_flops + m.attn_flops(4096)
                    + 4 * H * hd * (3 * 256 * 1024
                                    + 256 * 3000 + 256 * 257 // 2))


@pytest.mark.parametrize("bad", [
    {"layer_types": ["sliding"] * 3},                 # one short
    {"layer_types": ["full", "local", "full", "full"]},
    {"window": None},
    {"layer_types": ["sliding"] * 5},                 # one too many
])
def test_layer_kinds_that_cannot_be_counted_are_refused(bad):
    with pytest.raises(ValueError):
        mixed(**bad)
