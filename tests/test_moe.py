"""MoE block correctness: the capacity-dispatch shard_map implementation of
training and the dropless held layer of serving vs a dense reference that
evaluates the routed experts directly; shares of the experts adding up to
the whole layer; a served row's independence of its batch mates; the
grouped matmul the held layer runs on."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ParallelConfig, get_config
from repro.kernels import ops
from repro.models import api
from repro.models import moe as moe_mod

RNG = np.random.default_rng(11)


def dense_moe_reference(params, x, cfg):
    """Evaluate top-k experts per token exactly (no capacity, no drops)."""
    mo = cfg.moe
    T, d = x.shape
    w, idx, probs = moe_mod._route(
        jnp.asarray(x), params["router"].astype(jnp.float32),
        mo.experts_per_token)
    wg = np.asarray(params["w_gate"], np.float32)
    wi = np.asarray(params["w_in"], np.float32)
    wo = np.asarray(params["w_out"], np.float32)
    act = jax.nn.silu if cfg.mlp_activation == "silu" else (
        lambda v: jax.nn.gelu(v, approximate=True))
    y = np.zeros((T, d), np.float32)
    for t in range(T):
        for j in range(mo.experts_per_token):
            e = int(idx[t, j])
            h = np.asarray(act(jnp.asarray(x[t] @ wg[e]))) * (x[t] @ wi[e])
            y[t] += float(w[t, j]) * (h @ wo[e])
    return y


@pytest.mark.parametrize("arch", ["qwen3_moe_30b_a3b", "grok1_314b"])
def test_moe_block_matches_dense_reference(arch, tiny_mesh):
    cfg = get_config(arch, smoke=True)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    B, S = 2, 8
    x = RNG.normal(0, 0.5, (B, S, cfg.d_model)).astype(np.float32)
    with jax.set_mesh(tiny_mesh):
        params, _ = moe_mod.init_moe(cfg, jax.random.key(0))
        y, aux = moe_mod.moe_block(params, jnp.asarray(x), cfg)
    ref = dense_moe_reference(params, x.reshape(-1, cfg.d_model), cfg)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.d_model), ref,
                               atol=2e-3, rtol=1e-2)
    assert float(aux) > 0


def test_moe_capacity_drops_tokens(tiny_mesh):
    """With capacity_factor -> tiny, overflowing tokens contribute zeros."""
    cfg = get_config("qwen3_moe_30b_a3b", smoke=True)
    tiny = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.01))
    x = jnp.asarray(RNG.normal(0, 0.5, (1, 64, cfg.d_model)), jnp.float32)
    with jax.set_mesh(tiny_mesh):
        params, _ = moe_mod.init_moe(cfg, jax.random.key(0))
        y_tiny, _ = moe_mod.moe_block(params, x, tiny)
        y_full, _ = moe_mod.moe_block(params, x, cfg)
    # dropped rows -> strictly smaller output norm
    assert float(jnp.linalg.norm(y_tiny)) < float(jnp.linalg.norm(y_full))


def test_moe_grads_flow_to_experts_and_router(tiny_mesh):
    cfg = get_config("qwen3_moe_30b_a3b", smoke=True)
    x = jnp.asarray(RNG.normal(0, 0.5, (1, 16, cfg.d_model)), jnp.float32)
    with jax.set_mesh(tiny_mesh):
        params, _ = moe_mod.init_moe(cfg, jax.random.key(0))

        def f(p):
            y, aux = moe_mod.moe_block(p, x, cfg)
            return jnp.sum(y * y) + 0.01 * aux

        grads = jax.grad(f)(params)
    for name in ("router", "w_gate", "w_in", "w_out"):
        assert float(jnp.max(jnp.abs(grads[name]))) > 0, name
        assert bool(jnp.all(jnp.isfinite(grads[name]))), name


def test_expert_shares_add_up_to_the_whole_layer():
    """E 8 held in four shares of 2 (e0 = 0, 2, 4, 6, from the axis index):
    the shares' outputs add up to the layer that holds all 8 and to the
    dense reference; each share computes only its own experts' rows."""
    cfg = get_config("mellum2_12b_a2p5b", smoke=True)
    mo = cfg.moe
    assert (mo.num_experts, mo.experts_per_token) == (8, 2)
    params, _ = moe_mod.init_moe(cfg, jax.random.key(3))
    x = RNG.normal(0, 0.5, (24, cfg.d_model)).astype(np.float32)
    valid = jnp.ones(24, bool)
    whole, _, counts = moe_mod._moe_held(jnp.asarray(x), valid, params, cfg,
                                         e_axis=None)
    assert counts.tolist() == [24 * 2, 24 * 2]
    shares = {n: params[n].reshape((4, 2) + params[n].shape[1:])
              for n in ("w_gate", "w_in", "w_out")}

    def share(sh):
        return moe_mod._moe_held(jnp.asarray(x), valid, dict(params, **sh),
                                 cfg, e_axis="model")

    ys, _, share_counts = jax.vmap(share, axis_name="model")(shares)
    np.testing.assert_allclose(np.asarray(ys).sum(0), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(whole),
                               dense_moe_reference(params, x, cfg),
                               atol=2e-5, rtol=1e-4)
    _, idx, _ = moe_mod._route(jnp.asarray(x), params["router"], 2)
    for i in range(4):
        mine = int(((idx >= 2 * i) & (idx < 2 * i + 2)).sum())
        assert share_counts[i].tolist() == [mine, 24 * 2]


@pytest.mark.parametrize("arch", ["qwen3_moe_30b_a3b", "mellum2_12b_a2p5b"])
def test_served_row_does_not_depend_on_its_batch_mates(arch, tiny_mesh):
    """Prefill at the config's own capacity_factor: the probe row's KV in
    every layer (each after an expert layer but the first) is the same
    whether its batch mate holds random tokens or one token repeated, whose
    identical rows all pick the same experts. A capacity layer drops the
    probe's assignments behind the mate's there; the held layer drops
    none."""
    cfg = get_config(arch, smoke=True)
    S = 16
    probe = RNG.integers(0, cfg.vocab_size, S)
    mates = {"random": RNG.integers(0, cfg.vocab_size, S),
             "repeated": np.full(S, 7)}
    kv = {}
    with jax.set_mesh(tiny_mesh):
        params, _ = api.init_model(cfg, jax.random.key(0))
        for name, mate in mates.items():
            toks = jnp.asarray(np.stack([mate, probe]), jnp.int32)
            cache, _ = api.prefill_fn(params, {"tokens": toks}, cfg,
                                      ParallelConfig(remat="none"))
            kv[name] = [np.asarray(leaf[:, 1], np.float32)
                        for leaf in jax.tree.leaves(cache)]
    for a, b in zip(kv["random"], kv["repeated"]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("path", ["xla", "interpret"])
def test_grouped_matmul_equals_a_loop_over_experts(path, monkeypatch):
    """Each group's rows times its own matrix; rows past the groups are
    not multiplied (zero on the XLA path, undefined under gmm)."""
    if path == "interpret":
        monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")
    else:
        monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    G, m, k, n = 4, 40, 64, 48
    sizes = np.array([5, 0, 17, 9], np.int32)
    lhs = RNG.normal(0, 1, (m, k)).astype(np.float32)
    rhs = RNG.normal(0, 1, (G, k, n)).astype(np.float32)
    lhs_b = jnp.asarray(lhs, jnp.bfloat16)
    rhs_b = jnp.asarray(rhs, jnp.bfloat16)
    out = np.asarray(ops.grouped_matmul(lhs_b, rhs_b, jnp.asarray(sizes)))
    assert out.dtype == np.float32 and out.shape == (m, n)
    want = np.zeros((m, n), np.float32)
    start = 0
    lb = np.asarray(lhs_b, np.float32)
    rb = np.asarray(rhs_b, np.float32)
    for g, size in enumerate(sizes):
        want[start:start + size] = lb[start:start + size] @ rb[g]
        start += size
    np.testing.assert_allclose(out[:start], want[:start], rtol=1e-4,
                               atol=1e-3)
    if path == "xla":
        assert not out[start:].any()


def test_gmm_tiling_follows_the_shapes():
    """Mellum2's expert widths: one grid step per m tile a group touches;
    few rows take a 16-row tile."""
    assert ops.gmm_tiling(2048, 2304, 896, 2) == (128, 2304, 896)
    assert ops.gmm_tiling(2048, 896, 2304, 2) == (128, 896, 1152)
    assert ops.gmm_tiling(40, 64, 48, 2) == (48, 64, 48)


HELD_MESH_CODE = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.config import get_config
from repro.models import moe as moe_mod

x = np.random.default_rng(0).normal(0, 0.5, (2, 5, 64)).astype(np.float32)
valid = np.array([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], bool)


def serve(cfg, shape, f2d):
    mesh = jax.make_mesh(shape, ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        params, _ = moe_mod.init_moe(cfg, jax.random.key(0))
        y, counts = jax.jit(lambda p, x, v: moe_mod.moe_block(
            p, x, cfg, f2d=f2d, train=False, valid=v))(params, x, valid)
    return np.asarray(y), np.asarray(counts)


for arch in ("qwen3_moe_30b_a3b", "grok1_314b"):
    full = get_config(arch, smoke=True)
    # 2 held of E: fewer than the model axis's 4 shards, so TP
    two = dataclasses.replace(full, moe=dataclasses.replace(
        full.moe, experts_held=2))
    k = full.moe.experts_per_token
    # EP (experts over "model"), grok's f2d layout (d_ff over both axes),
    # TP (d_ff over "model"), each against one device
    for cfg, shape, f2d in ((full, (1, 4), False), (full, (2, 2), True),
                            (two, (1, 4), False)):
        want, want_counts = serve(cfg, (1, 1), False)
        y, counts = serve(cfg, shape, f2d)
        np.testing.assert_allclose(y, want, atol=2e-5, rtol=1e-4)
        assert counts.tolist() == want_counts.tolist()
        assert counts[1] == 8 * k
        assert (counts[0] == counts[1]) == (cfg is full)
print("HELD-MESH OK")
"""


def test_held_layer_is_mesh_invariant():
    """The serving layer gives the same output and counts as on one
    device with experts over "model" (each shard its own, psum-stitched),
    in grok's f2d layout, and with d_ff over "model" where fewer experts
    are held than there are shards."""
    from helpers import run_with_devices
    out = run_with_devices(HELD_MESH_CODE, n_devices=4, timeout=600)
    assert "HELD-MESH OK" in out
