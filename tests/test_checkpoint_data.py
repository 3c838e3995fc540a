"""Checkpoint (§4.3) + data pipeline tests: roundtrip, retention policies,
best-metric keeps, async save, elastic restore on a different mesh, and
queue-pipeline backpressure/sharding."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_with_devices
from repro.checkpoint.checkpoint import CheckpointManager
from repro.config import get_config
from repro.data.pipeline import Pipeline, ShardedSource


def _state(v):
    return {"params": {"w": np.full((4, 2), v, np.float32),
                       "b": np.arange(3).astype(np.float32) * v},
            "opt": ({"m": np.ones(2, np.float32) * v},)}


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=False)
    mgr.save(10, _state(3.0), metric=1.0)
    step, restored = mgr.restore(_state(0.0))
    assert step == 10
    np.testing.assert_allclose(restored["params"]["w"],
                               _state(3.0)["params"]["w"])
    np.testing.assert_allclose(restored["opt"][0]["m"], 3.0)


def test_retention_keeps_last_k(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_save=False)
    for s in range(5):
        mgr.save(s, _state(float(s)))
    assert mgr.steps() == [3, 4]


def test_retention_keeps_best_metric(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=1, keep_best=1, async_save=False)
    metrics = {0: 5.0, 1: 1.0, 2: 3.0, 3: 2.0}
    for s, m in metrics.items():
        mgr.save(s, _state(float(s)), metric=m)
    # step 1 (best metric) survives alongside the latest (3)
    assert set(mgr.steps()) == {1, 3}


def test_async_save_then_restore(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=True)
    mgr.save(7, _state(9.0))
    step, restored = mgr.restore(_state(0.0))   # restore waits for writer
    assert step == 7
    np.testing.assert_allclose(restored["params"]["b"],
                               np.arange(3) * 9.0)


ELASTIC_CODE = """
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint.checkpoint import CheckpointManager
from repro.checkpoint.elastic import restore_for_mesh, save_global
from jax.sharding import NamedSharding, PartitionSpec as P
import tempfile

d = tempfile.mkdtemp()
mgr = CheckpointManager(d, async_save=False)
mesh_a = jax.make_mesh((4, 2), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
mesh_b = jax.make_mesh((2, 2), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
w = jnp.arange(64.0).reshape(8, 8)
sh_a = NamedSharding(mesh_a, P("data", "model"))
sh_b = NamedSharding(mesh_b, P(None, "model"))
state = {"w": jax.device_put(w, sh_a)}
save_global(mgr, 1, state)
step, restored = restore_for_mesh(mgr, {"w": w}, {"w": sh_b})
assert step == 1
np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
assert restored["w"].sharding == sh_b
print("ELASTIC OK: 8 devices (4,2) -> 4 devices (2,2)")
"""


def test_elastic_restore_different_mesh():
    out = run_with_devices(ELASTIC_CODE, n_devices=8)
    assert "ELASTIC OK" in out


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_source_rank_sharding_disjoint_and_deterministic():
    cfg = get_config("glm4_9b", smoke=True)
    s0 = ShardedSource(cfg, 16, rank=0, world=2, seed=1)
    s1 = ShardedSource(cfg, 16, rank=1, world=2, seed=1)
    b0 = s0.batch(0, 8)
    b1 = s1.batch(0, 8)
    assert b0["tokens"].shape == (4, 16)
    assert not np.array_equal(b0["tokens"], b1["tokens"])
    # determinism: same (rank, index, seed) -> identical batch
    np.testing.assert_array_equal(b0["tokens"], s0.batch(0, 8)["tokens"])
    # labels shifted by one
    full = ShardedSource(cfg, 16, seed=1).batch(3, 4)
    np.testing.assert_array_equal(full["tokens"][:, 1:],
                                  full["labels"][:, :-1])


def test_pipeline_backpressure_and_flow():
    cfg = get_config("glm4_9b", smoke=True)
    src = ShardedSource(cfg, 8, seed=0)
    pipe = Pipeline(src, 4, capacity=2, producers=1)
    time.sleep(0.3)
    assert pipe.q.qsize() <= 2          # bounded despite fast producer
    seen = [pipe.get() for _ in range(5)]
    assert all(b["tokens"].shape == (4, 8) for b in seen)
    pipe.close()
