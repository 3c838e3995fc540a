"""The dense reference with its sliding layers masked to their window: a
configuration's own reference, as a file that its ``"reference"`` key
names. The layer kinds repeat with the period of the parameter tree's
sub-blocks (``blocks/sub0``, ``sub1``, …, one per layer kind of the
program's ``block_pattern``); ``sizes["layer_types"]`` says which of them
are ``"sliding"`` and ``sizes["window"]`` how many keys those see."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import reference as dense

bucket = dense.bucket


def hidden(params, tokens, sz, control=False):
    """Final-normed hidden states (T, d) of one token sequence."""
    blocks = params["blocks"]
    period = len(blocks)
    kinds = sz["layer_types"]
    if kinds != kinds[:period] * (len(kinds) // period):
        raise ValueError(f"layer_types {kinds} do not repeat every "
                         f"{period} layers")
    windows = [sz["window"] if k == "sliding" else None
               for k in kinds[:period]]

    def body(x, lps):
        for lp, w in zip(lps, windows):
            x, _ = dense._layer(sz, control, x, lp, window=w)
        return x, None

    x = params["embed"]["table"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(body, x, [blocks[f"sub{i}"] for i in range(period)])
    fn = jax.tree.map(lambda t: t.astype(jnp.float32), params["final_norm"])
    return dense._norm(x, fn, sz["norm"], sz["norm_eps"])


def gaps(params, seq, n_prompt, sz, control=False, t_len=None, p_len=128):
    return dense.served_gaps(hidden, params, seq, n_prompt, sz, control,
                             t_len, p_len)
