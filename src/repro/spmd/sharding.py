"""Logical-axis → mesh-axis sharding rules.

The paper separates the *graph* from its *placement* (§3.3): users express
constraints ("put parameters on PS tasks"), the runtime picks devices. Here
parameters carry logical axis names (repro.models.modules specs) and a rules
table maps them to mesh axes. Changing a parallelism strategy = changing the
rules — the model code never mentions mesh axes (except the explicitly
collective shard_map blocks, which take their axes from helpers here).

Mesh axes: ("pod",)? + ("data", "model"). "pod" is the multi-pod DP/PP axis.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, ParallelConfig

Rules = dict[str, Any]   # logical name -> mesh axis | tuple | None


def dp_axes(mesh=None) -> tuple[str, ...]:
    """Data-parallel axes present in the mesh (pod folds into DP by default)."""
    mesh = mesh or jax.sharding.get_abstract_mesh()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_rules(cfg: ModelConfig, pcfg: ParallelConfig) -> Rules:
    """Baseline rules; per-arch auto choices documented in DESIGN.md."""
    moe_ep = cfg.moe is not None and cfg.moe.num_experts >= 16
    rules: Rules = {
        "vocab": "model",
        "embed": "data" if pcfg.fsdp else None,
        "heads": "model",
        "kv_heads": "model",       # dropped automatically if not divisible
        "head_dim": None,
        "ff": "model",
        "experts": "model" if moe_ep else None,
        "expert_ff": (("data", "model") if pcfg.expert_ff_2d
                      else (None if moe_ep else "model")),
        "expert_embed": "data" if (pcfg.fsdp and not pcfg.expert_ff_2d)
                        else None,
        "ssm_inner": "model",
        "ssm_heads": "model",
        "layers": None,
        None: None,
    }
    return rules


def resolve_spec(shape: tuple[int, ...], logical: tuple[str | None, ...],
                 rules: Rules, mesh) -> P:
    """Map logical axes to a PartitionSpec, dropping any assignment whose
    mesh-axis product does not divide the dim (the paper's "feasible set")."""
    out = []
    used: set[str] = set()
    for dim, name in zip(shape, logical):
        ax = rules.get(name, None)
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        axes = tuple(a for a in axes if a in mesh.axis_names and a not in used)
        size = math.prod(mesh.shape[a] for a in axes) if axes else 1
        if axes and dim % size == 0:
            out.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        else:
            out.append(None)
    return P(*out)


def tree_shardings(params, specs, rules: Rules, mesh):
    """NamedSharding tree for a (params, logical-specs) pair."""
    def one(p, s):
        return NamedSharding(mesh, resolve_spec(p.shape, s, rules, mesh))
    return _map2(one, params, specs)


def _map2(fn, params, specs):
    if isinstance(params, dict):
        return {k: _map2(fn, params[k], specs[k]) for k in params}
    return fn(params, specs)


def tree_pspecs(params, specs, rules: Rules, mesh):
    def one(p, s):
        return resolve_spec(p.shape, s, rules, mesh)
    return _map2(one, params, specs)


def abstract_params(params):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)


def batch_spec(global_batch: int, mesh, extra_dims: int = 1) -> P:
    """Spec for (B, ...) activations: batch over DP axes when divisible."""
    dp = dp_axes(mesh)
    size = math.prod(mesh.shape[a] for a in dp) if dp else 1
    first = dp if (dp and global_batch % size == 0) else None
    if isinstance(first, tuple) and len(first) == 1:
        first = first[0]
    return P(first, *([None] * extra_dims))


def kv_cache_spec(global_batch: int, seq: int, mesh) -> P:
    """(B, S, K, hd): batch over DP, sequence over "model" (flash-decode)."""
    b = batch_spec(global_batch, mesh, extra_dims=0)
    seq_ax = "model" if ("model" in mesh.axis_names
                         and seq % mesh.shape["model"] == 0) else None
    return P(b[0] if len(b) else None, seq_ax, None, None)


# ---------------------------------------------------------------------------
# Serving cache sharding (tensor-parallel paged engine; docs/multi-host.md)
# ---------------------------------------------------------------------------


def serving_tp(mesh) -> int:
    """Tensor-parallel degree of the serving engine: the "model" axis."""
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return mesh.shape["model"]


def paged_pool_pspec(num_kv_heads: int, tp: int) -> P:
    """Spec for a page-pool stack (NP, num_blocks, K, block_size, hd).

    Pools shard over "model" by *whole kv heads* — the one pool dim whose
    slices are self-contained (every query group of a kv head attends only
    that head's K/V), so block tables, refcounts, hashes and every other
    piece of host-side metadata stay global and mesh-invariant. An
    indivisible head count cannot shard this way; raising here (rather
    than silently replicating a cache that exists precisely to be big)
    surfaces the misconfiguration at engine construction.
    """
    if tp > 1 and num_kv_heads % tp != 0:
        raise ValueError(
            f"num_kv_heads={num_kv_heads} is not divisible by the mesh "
            f"model axis ({tp}): page pools shard by whole kv heads. "
            "Choose a model-axis size that divides num_kv_heads, or shard "
            "the blocks axis via the LSE-stitch path (docs/multi-host.md).")
    return P(None, None, "model" if tp > 1 else None, None, None)


def serving_cache_pspec(path, leaf, tp: int) -> P:
    """Spec for one serving-cache leaf, keyed on the cache pytree path.

    * paged pools and their quantization scales (dict leaves "k"/"v"/
      "k_scale"/"v_scale", 5D with kv heads on axis 2) and encoder K-V
      ("xk"/"xv", 5D with kv heads on axis 3) shard by kv head — per-head
      attention over them is computed entirely on the owning shard and
      gathered before any cross-head contraction, so outputs stay bitwise
      mesh-invariant;
    * Mamba slot-state tuples (conv tail, ssm state) stay **replicated**:
      they are constant-size per slot (nothing grows with context), and
      storing the recurrent state sharded lets GSPMD propagate that
      sharding back into the SSD scan's inner contractions, reordering
      float adds — sharding it bitwise-safely needs a shard_map'd SSD
      (ROADMAP);
    * anything else is replicated.
    """
    if tp <= 1:
        return P()
    keys = [k.key for k in path if isinstance(k, jax.tree_util.DictKey)]
    axis = {"k": 2, "v": 2, "k_scale": 2, "v_scale": 2,
            "xk": 3, "xv": 3}.get(keys[-1] if keys else None)
    if axis is None or leaf.ndim != 5:
        return P()
    spec = [None] * 5
    if leaf.shape[axis] % tp == 0:
        spec[axis] = "model"
    return P(*spec)


def serving_cache_shardings(cache, mesh):
    """NamedSharding tree for a runner's device cache (see
    ``serving_cache_pspec``); the engine device_puts the zero cache with
    these at construction and jit/donation keep them in place."""
    tp = serving_tp(mesh)
    return jax.tree_util.tree_map_with_path(
        lambda p, x: NamedSharding(mesh, serving_cache_pspec(p, x, tp)),
        cache)
