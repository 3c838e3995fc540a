"""Mixture-of-Experts with production sharding strategies.

This is the paper's parameter-server insight at its sharpest: *move the
computation to the shard that owns the state*. Expert weights are the sharded
state; tokens are dynamically partitioned (`Part`), computed on the owning
shard (`Gather` + matmul), and stitched back (`Stitch`) — §4.2's
Part/Gather/Stitch pipeline, realized as shard_map + all_to_all / psum.

Which experts a model holds: the router always scores all ``num_experts``
(E), while the expert leaves hold experts [0, ``experts_held``) — all of
them by default, or one chip's share of expert parallelism, where the
absent experts' part of the result is left out (no stand-in for their
chips or their exchange).

Serving (every mode but training) runs the dropless held layer
(``_moe_held``): the top-k assignments that fall on the experts a shard
holds are sorted by expert and go through ``kernels.ops.grouped_matmul``
with the held experts' row counts as group sizes, then are weighted by
the renormalised router weights and summed back to their tokens. A
token's output depends on its own routing only, never on its batch mates.
Layouts (auto-chosen from the held experts vs the mesh "model" size):
  EP  (held >= model axis, e.g. qwen3-moe's 128): experts sharded over
      "model", tokens replicated; shard i holds experts [i·H_l, (i+1)·H_l)
      and the outputs are stitched with a psum.
  TP  (held < model axis, e.g. grok-1's 8; and ``f2d``): every shard holds
      all experts but a slice of d_ff; the psum adds the partial outputs.
The held layer also counts, per call, the rows it computed and the routed
assignments of the valid tokens (tokens × k): the serving step sums both
over its layers.

Training keeps the standard TPU formulation: fixed expert capacity with
drop + zero-fill and an auxiliary load-balancing loss, tokens split over
"model" on the sequence dim with all_to_all dispatch where the sequence
divides (EP), or all experts local with d_ff sharded (TP). Elsewhere in
training (EP with an undivided sequence) the held layer runs, with the loss.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.kernels import ops
from repro.models import modules as m
from repro.spmd.sharding import dp_axes

_ACT = {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True)}


def init_moe(cfg: ModelConfig, key):
    mo = cfg.moe
    d, E, H, f = cfg.d_model, mo.num_experts, mo.held, mo.d_ff_expert
    ks = m.split_keys(key, 4)
    return m.merge(
        m.named("router", m.dense_init(ks[0], (d, E), ("embed", None))),
        m.named("w_gate", m.dense_init(
            ks[1], (H, d, f), ("experts", "expert_embed", "expert_ff"))),
        m.named("w_in", m.dense_init(
            ks[2], (H, d, f), ("experts", "expert_embed", "expert_ff"))),
        m.named("w_out", m.dense_init(
            ks[3], (H, f, d), ("experts", "expert_ff", "expert_embed"))),
    )


def _route(x, router, k: int):
    """x: (T, d) -> (weights (T,k) fp32, idx (T,k) int32, probs (T,E))."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, idx, probs


def _aux_loss(probs, idx, E: int):
    """Switch-style load-balance loss: E * sum_e f_e * p_e."""
    hits = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(axis=1)   # (T, E)
    f = hits.mean(axis=0) / max(idx.shape[-1], 1)
    p = probs.mean(axis=0)
    return E * jnp.sum(f * p)


def _positions_in_expert(idx, E: int):
    """idx: (T, k) -> per-assignment rank within its expert (T, k)."""
    flat = idx.reshape(-1)
    oh = jax.nn.one_hot(flat, E, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - oh
    pos = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    return pos.reshape(idx.shape)


def _expert_ffn(disp, w_gate, w_in, w_out, act):
    """disp: (E?, C, d); weights (E?, d, f)/(E?, f, d) -> (E?, C, d)."""
    g = act(jnp.einsum("ecd,edf->ecf", disp, w_gate))
    h = g * jnp.einsum("ecd,edf->ecf", disp, w_in)
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def _moe_local(x, params, cfg: ModelConfig, *, mode: str, axis: str):
    """Per-shard capacity MoE body of training (inside shard_map).
    x: (T_l, d).

    mode: "ep_a2a" | "tp".
    """
    mo = cfg.moe
    E, k = mo.num_experts, mo.experts_per_token
    T, d = x.shape
    C = max(8, int(math.ceil(T * k / E * mo.capacity_factor)))
    act = _act(cfg)

    w, idx, probs = _route(x, params["router"].astype(x.dtype), k)
    aux = _aux_loss(probs, idx, E)
    pos = _positions_in_expert(idx, E)
    keep = pos < C

    wg = params["w_gate"].astype(x.dtype)
    wi = params["w_in"].astype(x.dtype)
    wo = params["w_out"].astype(x.dtype)

    # common dispatch build over all E buckets
    lpos = jnp.minimum(pos, C - 1)
    xk = jnp.broadcast_to(x[:, None, :], (T, k, d)).reshape(T * k, d)
    contrib = jnp.where(keep.reshape(-1, 1), xk, 0)
    disp = jnp.zeros((E, C, d), x.dtype).at[
        idx.reshape(-1), lpos.reshape(-1)].add(contrib)

    if mode == "ep_a2a":
        tp = jax.lax.axis_size(axis)
        E_l = E // tp
        snd = disp.reshape(tp, E_l, C, d)
        rcv = jax.lax.all_to_all(snd, axis, split_axis=0, concat_axis=0)
        rows = rcv.transpose(1, 0, 2, 3).reshape(E_l, tp * C, d)
        out_rows = _expert_ffn(rows, wg, wi, wo, act)
        back = out_rows.reshape(E_l, tp, C, d).transpose(1, 0, 2, 3)
        comb = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=0)
        comb = comb.reshape(E, C, d)
    else:  # tp: all experts local, f sharded over `axis`
        comb = _expert_ffn(disp, wg, wi, wo, act)
        comb = jax.lax.psum(comb, axis)

    got = comb[idx.reshape(-1), lpos.reshape(-1)].reshape(T, k, d)
    wk = jnp.where(keep, w, 0.0).astype(x.dtype)
    y = jnp.einsum("tkd,tk->td", got, wk)
    return y, aux


def _act(cfg: ModelConfig):
    return _ACT["gelu" if cfg.mlp_activation == "gelu_mlp"
                else cfg.mlp_activation]


def _moe_held(x, valid, params, cfg: ModelConfig, *, e_axis):
    """The dropless expert layer over the experts this shard holds (inside
    shard_map). x: (T, d); valid: (T,) bool, the tokens to compute.

    The shard holds experts [e0, e0 + H) of the E the router scores, H the
    expert leaves' count, e0 = the ``e_axis`` index × H (0 without one).
    Of the T·k assignments (the static row bound), those of valid tokens to
    held experts are sorted by expert and multiplied by their expert's
    matrices in one grouped matmul each (gate, in, out); the others are
    never multiplied. Returns (y (T, d), aux loss, counts int32 (2,): rows
    computed here, assignments of the valid tokens)."""
    mo = cfg.moe
    E, k = mo.num_experts, mo.experts_per_token
    T, d = x.shape
    H = params["w_gate"].shape[0]
    e0 = jax.lax.axis_index(e_axis) * H if e_axis else 0

    w, idx, probs = _route(x, params["router"].astype(x.dtype), k)
    aux = _aux_loss(probs, idx, E)
    here = (idx >= e0) & (idx < e0 + H) & valid[:, None]
    group = jnp.where(here, idx - e0, H).reshape(-1)    # H: not computed
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((H,), jnp.int32).at[group].add(1, mode="drop")
    rows = jnp.take(x, order // k, axis=0)             # (T·k, d), by expert

    def gmm(lhs, name):
        return ops.grouped_matmul(lhs, params[name].astype(x.dtype), sizes)

    h = _act(cfg)(gmm(rows, "w_gate")) * gmm(rows, "w_in")
    out = gmm(h.astype(x.dtype), "w_out")               # (T·k, d) float32
    n = jnp.sum(sizes)
    out = jnp.where((jnp.arange(T * k) < n)[:, None], out, 0.0)
    # back to (token, slot) order, each token's k rows summed in slot order
    got = jnp.take(out, jnp.argsort(order), axis=0).reshape(T, k, d)
    y = jnp.einsum("tkd,tk->td", got, jnp.where(here, w, 0.0))
    counts = jnp.stack([n, jnp.sum(valid.astype(jnp.int32)) * k])
    return y.astype(x.dtype), aux, counts


def moe_block(params, x, cfg: ModelConfig, f2d: bool = False, *,
              train: bool = True, valid=None):
    """x: (B, S, d) global -> (y, second). shard_map wrapper.

    ``train``: the capacity layer, or the held layer where EP cannot split
    the sequence; second = the load-balancing loss (f32 scalar). Otherwise
    (serving) the dropless held layer, and second = int32 (2,) counts: rows
    computed on held experts and routed assignments (tokens × k), of the
    tokens ``valid`` (B, S) marks (all, when None).

    f2d: serving layout for small-E models (grok-1) — expert d_ff sharded
    over BOTH mesh axes, tokens replicated; the partial outputs psum over
    (data, model). No per-step weight gathers (vs FSDP), tiny activation
    psums — the right trade when tokens-per-step is small (decode).
    """
    mesh = jax.sharding.get_abstract_mesh()
    H = cfg.moe.held
    tp = mesh.shape.get("model", 1)
    ep = not f2d and tp > 1 and H % tp == 0
    B, S, d = x.shape
    dp = dp_axes(mesh)
    dp_sz = math.prod(mesh.shape[a] for a in dp) if dp else 1
    dpb = dp if (dp and B % dp_sz == 0) else ()
    dps = (dpb if len(dpb) > 1 else (dpb[0] if dpb else None))

    if not train or (ep and S % tp):
        mode = "held"
    else:
        mode = "ep_a2a" if ep else "tp"

    f_axes = (tuple(dp) + ("model",)) if f2d else ("model",)
    f_axis = f_axes if len(f_axes) > 1 else f_axes[0]
    seq_ax = "model" if mode == "ep_a2a" else None
    e_ax = "model" if ep else None
    f_ax = None if ep else f_axis
    x_dps = None if f2d else dps
    wspec = {
        "router": P(None, None),
        "w_gate": P(e_ax, None, f_ax),
        "w_in": P(e_ax, None, f_ax),
        "w_out": P(e_ax, f_ax, None),
    }
    if valid is None:
        valid = jnp.ones((B, S), bool)

    def body(params, x, valid):
        b, s, _ = x.shape
        xt = x.reshape(b * s, d)
        if mode == "held":
            y, aux, counts = _moe_held(xt, valid.reshape(b * s), params, cfg,
                                       e_axis=e_ax)
            y = jax.lax.psum(y, "model" if ep else f_axis)
            if ep:      # each shard computed its own experts' rows
                counts = counts.at[0].set(jax.lax.psum(counts[0], "model"))
        else:
            y, aux = _moe_local(xt, params, cfg, mode=mode, axis=f_axis)
        if dp and not f2d:
            aux = jax.lax.pmean(aux, dp)
            if mode == "held":
                counts = jax.lax.psum(counts, dp)
        if mode != "held" and not f2d:
            aux = jax.lax.pmean(aux, "model")
        return y.reshape(b, s, d), (aux if train else counts)

    x_spec = P(x_dps, seq_ax, None)
    y, second = jax.shard_map(
        body, mesh=mesh,
        in_specs=(wspec, x_spec, P(x_dps, seq_ax)),
        out_specs=(x_spec, P()),
        # the grouped matmul's pallas_call outputs carry no varying-axes type
        check_vma=mode != "held",
    )(params, x, valid)
    return y, second
