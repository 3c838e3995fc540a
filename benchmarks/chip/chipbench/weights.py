"""Random weights from the seed, made by the benchmark in one jitted call on
the device, in the type they are served in (bf16).

The layout is the program's (its parameter tree, as shapes); the values are
the benchmark's own, so the plain reference takes nothing the program made.
Each leaf is drawn in fp32 and cast inside the program; layer-stacked
leaves are drawn one layer at a time, so no fp32 copy of a whole leaf
exists.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# (parent, leaf name) -> the axes (after the layer axis) a weight's fan-in
# spans: its input width. Attention (d, heads, hd) and (heads, hd, d); dense
# MLP (d, f) and (f, d); experts (E, d, f) and (E, f, d), router (d, E).
FAN_IN_AXES = {("attn", "wq"): (0,), ("attn", "wk"): (0,),
               ("attn", "wv"): (0,), ("attn", "wo"): (0, 1),
               ("mlp", "w_in"): (0,), ("mlp", "w_gate"): (0,),
               ("mlp", "w_out"): (0,),
               ("moe", "router"): (0,), ("moe", "w_in"): (1,),
               ("moe", "w_gate"): (1,), ("moe", "w_out"): (1,)}
EMBED_STD = 0.02
NORM_STD = 0.1


def base_key(seed: int):
    """A key from any non-negative seed, including ones past 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, path: tuple[str, ...], shape, stacked: bool):
    """One leaf at ``path`` (the tree's keys down to it): norms near 1,
    biases near 0, the embedding at EMBED_STD, every matrix at
    1/sqrt(fan-in)."""
    name = path[-1]
    axes = FAN_IN_AXES.get(path[-2:])
    if axes is None and name not in ("scale", "bias", "table", "head"):
        raise KeyError(f"no weight rule for the leaf {'/'.join(path)}")

    def draw(k, shp):
        z = jax.random.normal(k, shp, jnp.float32)
        if name == "scale":
            return 1.0 + NORM_STD * z
        if name == "bias":
            return NORM_STD * z
        if name == "table":
            return EMBED_STD * z
        if name == "head":
            return z / math.sqrt(shp[-1])
        return z / math.sqrt(math.prod(shp[a] for a in axes))

    if not stacked:
        return draw(key, shape).astype(jnp.bfloat16)
    keys = jax.random.split(key, shape[0])
    return jax.lax.map(lambda k: draw(k, shape[1:]).astype(jnp.bfloat16),
                       keys)


def make_params(shapes, seed: int):
    """A bf16 tree shaped like ``shapes`` (a pytree of ShapeDtypeStruct)."""
    paths = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree_util.tree_structure(shapes)

    def init(key):
        leaves = []
        for i, (path, s) in enumerate(paths):
            keys = [getattr(p, "key", str(p)) for p in path]
            leaves.append(_leaf(jax.random.fold_in(key, i), tuple(keys),
                                s.shape, stacked="blocks" in keys))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(init)(base_key(seed))
