"""The plain reference: a straightforward float32 forward pass of the dense
decoder block both configurations share, in ``jax.numpy`` with every
matmul at ``HIGHEST`` precision (on a TPU a float32 matmul otherwise runs
as one bf16 pass). It imports nothing of the program and reads only the
configuration file's ``sizes`` and the benchmark's own weights.

One block: x += o(attn(rope(q(n1(x))), rope(k(n1(x))), v(n1(x)))), then
x += mlp(n2(x)); n is LayerNorm (with bias) or RMSNorm; rope rotates the
two halves of every head over the whole head dimension; query head h reads
kv head h mod K; the MLP is tanh-GeLU ungated or SwiGLU. A final norm, then
the LM head (the embedding table when tied).

``control=True`` computes the same pass with every matmul's operands in
fp8 (e4m3, scaled per row of the activations and per output channel of
the weights) and the K/V rows rounded to fp8 per row: the next precision
below the configuration's bf16. fp8 values are exact in bf16, so its
weight matmuls are one bf16 pass with the scales applied after. It exists
to show that the comparison fails a lower-precision program.

This is the default reference of a configuration. One whose file names
another (``"reference": "references/<name>.py"``) is compared against that
module's ``gaps`` instead; such a module may build on the pieces here
(``_mm``, ``_norm``, ``_rope``, ``_attention`` with its ``window``,
``_layer``, ``served_gaps``). ``gaps`` here refuses sizes that describe
more than this dense, full-attention block, so such a configuration is
never compared against it by default.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512             # attention queries per block (bounds the scores)
BUCKET = 1024             # sequences are padded to a multiple of this
V_BLOCKS = 16             # the LM head is read in this many row blocks
BF = jnp.bfloat16
# the sizes this block implements
DENSE_SIZES = {"layers", "d_model", "heads", "kv_heads", "head_dim", "d_ff",
               "vocab", "mlp", "norm", "norm_eps", "tied", "rope_theta"}


FP8_MAX = 448.0           # largest finite float8_e4m3fn


def _q8(x, axis):
    """(fp8 values, scale): x rounded to fp8 (e4m3) with a scale per slice
    along ``axis`` that maps the slice's largest magnitude to FP8_MAX."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s


def _fp8(x, axis):
    q, s = _q8(x, axis)
    return q * s


def _bf16_mm(a, w):
    """One bf16 pass, float32 accumulation."""
    return jnp.matmul(a.astype(BF), w.astype(BF),
                      precision=jax.lax.Precision.DEFAULT,
                      preferred_element_type=jnp.float32)


def _mm(a, w, control):
    """a (..., k) float32 @ w (k, n) holding bf16 values, float32-exact; or
    in fp8 for the control."""
    if control:
        (qa, sa), (qw, sw) = _q8(a, -1), _q8(w, 0)
        return _bf16_mm(qa, qw) * sa * sw
    out = 0.0
    for _ in range(3):
        part = a.astype(BF)
        out = out + _bf16_mm(part, w)
        a = a - part.astype(jnp.float32)
    return out


def _norm(x, p, kind, eps):
    if kind == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * p["scale"]


def _rope(x, pos, theta):
    """x (T, heads, hd); rotate the halves by pos × theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, window=None):
    """Causal attention. q (T, H, hd); k, v (T, K, hd); head h reads kv
    head h mod K; with a ``window``, a query sees the ``window`` keys up
    to its own. Queries go in blocks of Q_BLOCK."""
    T, H, hd = q.shape
    K = k.shape[1]
    kh = jnp.tile(k, (1, H // K, 1))          # head h -> kv head h % K
    vh = jnp.tile(v, (1, H // K, 1))
    keys = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, kh, precision=HI) / math.sqrt(hd)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        seen = keys[None, None, :] <= rows[None, :, None]
        if window is not None:
            seen &= keys[None, None, :] > rows[None, :, None] - window
        s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vh, precision=HI)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    return out.reshape(T, H, hd)


def _layer(sz, control, x, lp, window=None):
    f32 = lambda t: t.astype(jnp.float32)          # noqa: E731
    lp = jax.tree.map(f32, lp)
    T, d = x.shape
    H, K, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    pos = jnp.arange(T)
    h = _norm(x, lp["norm"], sz["norm"], sz["norm_eps"])
    a = lp["attn"]
    q = _mm(h, a["wq"].reshape(d, H * hd), control).reshape(T, H, hd)
    k = _mm(h, a["wk"].reshape(d, K * hd), control).reshape(T, K, hd)
    v = _mm(h, a["wv"].reshape(d, K * hd), control).reshape(T, K, hd)
    q, k = _rope(q, pos, sz["rope_theta"]), _rope(k, pos, sz["rope_theta"])
    if control:
        k, v = _fp8(k, -1), _fp8(v, -1)
    o = _attention(q, k, v, window).reshape(T, H * hd)
    x = x + _mm(o, a["wo"].reshape(H * hd, d), control)
    h = _norm(x, lp["norm2"], sz["norm"], sz["norm_eps"])
    m = lp["mlp"]
    if sz["mlp"] == "swiglu":
        u = jax.nn.silu(_mm(h, m["w_gate"], control)) * _mm(h, m["w_in"],
                                                             control)
    else:
        u = jax.nn.gelu(_mm(h, m["w_in"], control), approximate=True)
    return x + _mm(u, m["w_out"], control), None


def hidden(params, tokens, sz, control=False):
    """Final-normed hidden states (T, d) of one token sequence."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, sz, control), x,
                        params["blocks"]["sub0"])
    fn = jax.tree.map(lambda t: t.astype(jnp.float32), params["final_norm"])
    return _norm(x, fn, sz["norm"], sz["norm_eps"])


@functools.partial(jax.jit, static_argnames=("sz_json", "control",
                                             "hidden_fn"))
def _gaps(params, tokens, positions, served, sz_json, control,
          hidden_fn=hidden):
    """(served gaps, control gaps) at the given positions; ``sz_json`` is
    the sizes as JSON, a hashable static argument."""
    sz = json.loads(sz_json)
    h_ref = hidden_fn(params, tokens, sz)[positions]
    h_ctl = hidden_fn(params, tokens, sz, True)[positions] if control \
        else None
    head = params["embed"]["table" if sz["tied"] else "head"]
    V, d = head.shape
    blocks = head.reshape(V_BLOCKS, V // V_BLOCKS, d)
    P = positions.shape[0]

    def body(carry, inp):
        r_max, r_served, c_max, r_at_c = carry
        i, w = inp
        w = w.astype(jnp.float32)
        ids = i * (V // V_BLOCKS) + jnp.arange(V // V_BLOCKS)
        ok = ids < sz["vocab"]
        lr = jnp.where(ok, _mm(h_ref, w.T, False), -jnp.inf)
        r_max = jnp.maximum(r_max, lr.max(-1))
        hit = ids[None, :] == served[:, None]
        r_served = jnp.where(hit.any(-1),
                             jnp.where(hit, lr, 0.0).sum(-1), r_served)
        if control:
            lc = jnp.where(ok, _mm(h_ctl, w.T, True), -jnp.inf)
            bm, ba = lc.max(-1), lc.argmax(-1)
            better = bm > c_max
            c_max = jnp.where(better, bm, c_max)
            r_at_c = jnp.where(better, jnp.take_along_axis(
                lr, ba[:, None], 1)[:, 0], r_at_c)
        return (r_max, r_served, c_max, r_at_c), None

    neg = jnp.full((P,), -jnp.inf, jnp.float32)
    (r_max, r_served, _, r_at_c), _ = jax.lax.scan(
        body, (neg, neg, neg, neg), (jnp.arange(V_BLOCKS), blocks))
    return r_max - r_served, r_max - r_at_c


def bucket(n: int) -> int:
    """The padded length of an n-token sequence: one compiled shape per
    BUCKET tokens."""
    return -(-n // BUCKET) * BUCKET


def _dense_only(sz: dict) -> None:
    """Refuse sizes that state layer kinds or experts this block lacks."""
    extra = set(sz) - DENSE_SIZES - {"layer_types"}
    if extra or set(sz.get("layer_types", ())) - {"full"}:
        raise ValueError("the dense reference does not implement "
                         f"{sorted(extra) or 'these layer kinds'}: the "
                         "configuration has to name its own reference")


def gaps(params, seq, n_prompt, sz: dict, control: bool = False,
         t_len: int | None = None, p_len: int = 128):
    """``served_gaps`` of the dense block, for sizes that state nothing
    more than it implements."""
    _dense_only(sz)
    return served_gaps(hidden, params, seq, n_prompt, sz, control, t_len,
                       p_len)


def served_gaps(hidden_fn, params, seq, n_prompt, sz: dict,
                control: bool = False, t_len: int | None = None,
                p_len: int = 128):
    """For a request with prompt ``seq[:n_prompt]`` and served tokens
    ``seq[n_prompt:]``: at each served token, how far its logit lies below
    the reference's best (0 where it is the reference's argmax); with
    ``control``, the same for the token the fp8 pass puts first. The
    sequence is padded to ``t_len`` tokens (a multiple of Q_BLOCK; by
    default its bucket) and the served positions to ``p_len``; causality
    keeps the padding out. Returns numpy arrays (served gaps, control gaps
    or None). ``hidden_fn(params, tokens, sz, control)`` gives the
    final-normed hidden states of the whole sequence."""
    import numpy as np
    seq = np.asarray(seq, np.int32)
    n_out = len(seq) - n_prompt
    t_len = bucket(len(seq)) if t_len is None else t_len
    assert len(seq) <= t_len and n_out <= p_len and t_len % Q_BLOCK == 0
    tokens = np.zeros(t_len, np.int32)
    tokens[:len(seq)] = seq
    positions = np.zeros(p_len, np.int32)
    positions[:n_out] = np.arange(n_prompt - 1, len(seq) - 1)
    served = np.full(p_len, -1, np.int32)
    served[:n_out] = seq[n_prompt:]
    with jax.default_matmul_precision("highest"):
        g, c = _gaps(params, tokens, positions, served,
                     json.dumps(sz, sort_keys=True), control, hidden_fn)
    g = np.asarray(g)[:n_out]
    return g, (np.asarray(c)[:n_out] if control else None)
