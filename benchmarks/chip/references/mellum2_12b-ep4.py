"""The plain reference of Mellum2-12B-A2.5B as one chip's share of four-way
expert parallelism: a float32-exact forward pass in ``jax.numpy``, built on
``chipbench/reference.py``'s matmul, norm, attention and served-gap pieces.
It imports nothing of the program and reads only the configuration file's
``sizes`` and the benchmark's own weights.

One layer: x += o(attn(rope_kind(q(n1(x))), rope_kind(k(n1(x))),
v(n1(x)))), then x += experts(n2(x)); n is RMSNorm; query head h reads kv
head h mod K. ``sizes["layer_types"]`` gives each layer's kind: a
``"sliding"`` layer sees the ``sizes["window"]`` keys up to its own and
rotates with the plain rope at ``rope_theta``; a ``"full"`` layer sees
every earlier key and rotates with YaRN (``sizes["yarn"]``: factor,
original_max_positions, beta_fast, beta_slow, attention_factor), whose
table is computed here in float64 numpy from the published definition.

The expert layer: a float32 softmax over all ``moe.experts`` router
outputs, the top ``experts_per_token`` renormalised to sum to 1; experts
0 .. ``experts_held`` - 1 (the ones this chip holds) add their SwiGLU
output times their weight, the rest add nothing, exactly as on the chip.
The routing is the reference's own, on its own float32 activations.

``control=True`` runs every matmul in fp8 and rounds K/V to fp8, as the
dense reference's control does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as dense

bucket = dense.bucket


def yarn_inv_freq(hd: int, theta: float, yarn: dict) -> np.ndarray:
    """YaRN's inverse frequencies (transformers' ``_compute_yarn_parameters``)
    in float64: a linear ramp between the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over the original context
    (bounds truncated) mixes theta^(-2i/hd) ÷ factor with theta^(-2i/hd)."""
    def correction_dim(rotations):
        return hd * math.log(yarn["original_max_positions"]
                             / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    freq = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0, 1)
    return 1 / (yarn["factor"] * freq) * ramp + 1 / freq * (1 - ramp)


def rope_table(T: int, hd: int, theta: float, yarn: dict | None):
    """(cos, sin) (T, 1, hd/2) for positions 0 .. T-1, float64 angles; YaRN
    scales both by its attention_factor."""
    inv = (1 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
           if yarn is None else yarn_inv_freq(hd, theta, yarn))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv
    scale = 1.0 if yarn is None else yarn["attention_factor"]
    return (jnp.asarray(np.cos(ang)[:, None] * scale, jnp.float32),
            jnp.asarray(np.sin(ang)[:, None] * scale, jnp.float32))


def _rotate(x, table):
    cos, sin = table
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _experts(sz, control, h, mp):
    """The held experts' part of the expert layer for every row of h."""
    e = sz["moe"]
    logits = dense._mm(h, mp["router"], control)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, e["experts_per_token"])
    w = w / w.sum(-1, keepdims=True)

    def one(y, i):
        u = jax.nn.silu(dense._mm(h, mp["w_gate"][i], control)) \
            * dense._mm(h, mp["w_in"][i], control)
        gate = jnp.where(idx == i, w, 0.0).sum(-1)           # (T,)
        return y + gate[:, None] * dense._mm(u, mp["w_out"][i], control), \
            None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        jnp.arange(e["experts_held"]))
    return y


def _layer(sz, control, x, lp, table, window):
    lp = jax.tree.map(lambda t: t.astype(jnp.float32), lp)
    T, d = x.shape
    H, K, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    h = dense._norm(x, lp["norm"], sz["norm"], sz["norm_eps"])
    a = lp["attn"]
    q = dense._mm(h, a["wq"].reshape(d, H * hd), control).reshape(T, H, hd)
    k = dense._mm(h, a["wk"].reshape(d, K * hd), control).reshape(T, K, hd)
    v = dense._mm(h, a["wv"].reshape(d, K * hd), control).reshape(T, K, hd)
    q, k = _rotate(q, table), _rotate(k, table)
    if control:
        k, v = dense._fp8(k, -1), dense._fp8(v, -1)
    o = dense._attention(q, k, v, window).reshape(T, H * hd)
    x = x + dense._mm(o, a["wo"].reshape(H * hd, d), control)
    h = dense._norm(x, lp["norm2"], sz["norm"], sz["norm_eps"])
    return x + _experts(sz, control, h, lp["moe"])


def hidden(params, tokens, sz, control=False):
    """Final-normed hidden states (T, d) of one token sequence."""
    blocks = params["blocks"]
    period = len(blocks)
    kinds = sz["layer_types"]
    if kinds != kinds[:period] * (len(kinds) // period):
        raise ValueError(f"layer_types {kinds} do not repeat every "
                         f"{period} layers")
    T, hd = tokens.shape[0], sz["head_dim"]
    tables = {"sliding": rope_table(T, hd, sz["rope_theta"], None),
              "full": rope_table(T, hd, sz["rope_theta"], sz.get("yarn"))}

    def body(x, lps):
        for lp, kind in zip(lps, kinds[:period]):
            x = _layer(sz, control, x, lp, tables[kind],
                       sz["window"] if kind == "sliding" else None)
        return x, None

    x = params["embed"]["table"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(body, x, [blocks[f"sub{i}"] for i in range(period)])
    fn = jax.tree.map(lambda t: t.astype(jnp.float32), params["final_norm"])
    return dense._norm(x, fn, sz["norm"], sz["norm_eps"])


def gaps(params, seq, n_prompt, sz, control=False, t_len=None, p_len=128):
    return dense.served_gaps(hidden, params, seq, n_prompt, sz, control,
                             t_len, p_len)
