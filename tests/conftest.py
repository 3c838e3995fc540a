"""Shared fixtures. NOTE: no global XLA_FLAGS here — smoke tests and benches
must see the real single CPU device; multi-device tests spawn subprocesses
(tests/helpers.py) that set --xla_force_host_platform_device_count first."""

import jax
import pytest


@pytest.fixture(scope="session")
def tiny_mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


ALL_ARCHS = [
    "glm4_9b", "starcoder2_3b", "gemma2_27b", "qwen3_32b",
    "whisper_large_v3", "zamba2_2p7b", "qwen2_vl_2b",
    "qwen3_moe_30b_a3b", "grok1_314b", "mamba2_370m", "mellum2_12b_a2p5b",
]
