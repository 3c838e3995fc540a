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

# leaf name -> number of leading axes (after the layer axis) summed over
FAN_IN_AXES = {"wq": 1, "wk": 1, "wv": 1, "wo": 2,
               "w_in": 1, "w_gate": 1, "w_out": 1}
EMBED_STD = 0.02
NORM_STD = 0.1


def base_key(seed: int):
    """A key from any non-negative seed, including ones past 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, name: str, shape, stacked: bool):
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
        n = FAN_IN_AXES[name]
        return z / math.sqrt(math.prod(shp[:n]))

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
            leaves.append(_leaf(jax.random.fold_in(key, i), keys[-1],
                                s.shape, stacked="blocks" in keys))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(init)(base_key(seed))
