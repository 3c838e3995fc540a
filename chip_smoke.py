"""Bring-up check of the serving path on one TPU chip, at published widths.

    python chip_smoke.py               # one chip: phases (a)-(e) below
    python chip_smoke.py --four-chips  # only the tensor-parallel comparison

Serves starcoder2_3b (30 layers, d_model 3072, 3.03 B parameters, random
weights from a seed) through the same entry points as
``python -m repro.launch.serve --arch starcoder2_3b --no-smoke``, in one
process that holds the chip:

  (a) the device is a TPU (anything else exits non-zero before any result);
  (b) the engine is built and the device's peak bytes printed;
  (c) the chunk and plain step programs hold ``tpu_custom_call`` — the
      Pallas kernels were compiled, not bypassed;
  (d) each main-path kernel, at these widths on the chip, agrees with its
      oracle in ``repro.kernels.ref`` within the tolerance printed;
  (e) 8 requests are served and every one completes with its own max_new
      tokens, all in-vocab.

``--four-chips`` runs starcoder2_3b on the largest "model" axis its kv heads
allow (page pools sharded by kv head) and compares it with a one-chip engine
on the same requests, in the same process: greedy tokens of the first steps
must be equal and last-position logits agree within tolerance.

The last line of standard output is one JSON object naming the device; it is
printed only when every phase passed. Times printed here are bring-up facts,
not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SERVE_FLAGS = ["--arch", "starcoder2_3b", "--no-smoke", "--max-len", "2048",
               "--max-batch", "8", "--prompt-len", "512", "--requests", "8",
               "--max-new", "32"]
# bf16 attention outputs of magnitude <~ 1: a few bf16 ulps. The kernels
# keep probabilities in fp32 where the oracles round them to bf16 first.
ATTN_ATOL = 2e-2
# last-position logits, one-chip vs kv-head-sharded engine
LOGITS_ATOL = 5e-2
FIRST_STEPS = 8


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def require_tpu() -> dict:
    from repro.launch.serve import device_summary
    dev = device_summary()
    log(f"(a) device platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    check(dev["platform"] == "tpu",
          f"no TPU: JAX's first device is {dev['platform']!r}")
    return dev


def serve_args():
    from repro.launch.serve import build_parser
    return build_parser().parse_args(SERVE_FLAGS)


# -- (d) kernels vs oracles ---------------------------------------------------


def kernel_checks(cfg, block_size: int, max_len: int, max_batch: int,
                  chunk_width: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import embedding as emb
    from repro.kernels import paged_attention as pa
    from repro.kernels import ref
    from repro.models.attention import update_paged_cache_ragged

    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    nb = max_len // block_size
    N = max_batch * nb + 1
    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.key(seed), 16))
    rng = np.random.default_rng(seed)

    def normal(shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(bf)

    kp, vp = normal((N, K, block_size, hd)), normal((N, K, block_size, hd))

    def tables(n):
        perm = rng.permutation(np.arange(1, N))[:n * nb].reshape(n, nb)
        return jnp.asarray(perm, jnp.int32)

    def report(name, got, want, atol):
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        finite = bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
        log(f"(d) {name}: max_abs_err={err} atol={atol} finite={finite}")
        check(finite and err <= atol, f"{name} disagrees with its oracle")

    # decode: one query per sequence over up to max_len paged tokens
    bt = tables(max_batch)
    ctx = jnp.asarray(rng.integers(1, max_len + 1, max_batch), jnp.int32)
    q = normal((max_batch, H, hd))
    report("paged decode", pa.paged_attention(q, kp, vp, bt, ctx),
           ref.paged_attention_ref(q, kp, vp, bt, ctx), ATTN_ATOL)

    # chunked prefill: chunk_width queries at the tail of each context
    qlen = np.array([chunk_width, chunk_width // 2], np.int32)
    ctx = np.array([rng.integers(n, max_len + 1) for n in qlen], np.int32)
    bt = tables(2)
    q = normal((2, chunk_width, H, hd))
    args = (q, kp, vp, bt, jnp.asarray(ctx), jnp.asarray(qlen))
    report("paged chunked prefill", pa.paged_prefill_attention(*args),
           ref.paged_prefill_attention_ref(*args), ATTN_ATOL)

    # ragged packed prefill: 4 pack slots (one empty) + unowned pad rows
    lens, pad = (40, 32, 0, 50), 6
    T = sum(lens) + pad
    starts = np.cumsum((0,) + lens[:-1]).astype(np.int32)
    ends = (starts + np.array(lens)).astype(np.int32)
    row_seq = np.zeros(T, np.int32)
    for s, (a, b) in enumerate(zip(starts, ends)):
        row_seq[a:b] = s
    ctx = np.array([n + rng.integers(0, max_len - n + 1) if n else 0
                    for n in lens], np.int32)
    bt = tables(len(lens))
    q = normal((T, H, hd))
    rag = (bt, jnp.asarray(ctx), jnp.asarray(starts), jnp.asarray(ends))
    report("ragged prefill",
           pa.ragged_paged_prefill_attention(q, kp, vp, *rag),
           ref.ragged_paged_prefill_attention_ref(
               q, kp, vp, *rag, jnp.asarray(row_seq)), ATTN_ATOL)

    # ragged prefill with the chunk's KV write fused into the kernel
    k_new, v_new = normal((T, K, hd)), normal((T, K, hd))
    o, kc, vc = pa.ragged_paged_prefill_attention(
        q, kp, vp, *rag, k_new=k_new, v_new=v_new)
    k_ref = update_paged_cache_ragged(kp, k_new[None], *rag,
                                      jnp.asarray(row_seq))
    v_ref = update_paged_cache_ragged(vp, v_new[None], *rag,
                                      jnp.asarray(row_seq))
    # block 0 is the trash block: the reference scatter parks pad rows
    # there, the kernel only redirects dead table entries to it
    same = bool(jnp.array_equal(kc[1:], k_ref[1:])
                and jnp.array_equal(vc[1:], v_ref[1:]))
    log(f"(d) ragged fused write: pools equal to the scatter oracle={same}")
    check(same, "fused KV write differs from the scatter oracle")
    report("ragged prefill, fused write", o,
           ref.ragged_paged_prefill_attention_ref(
               q, k_ref, v_ref, *rag, jnp.asarray(row_seq)), ATTN_ATOL)

    # embedding gather over the published vocab x d_model table
    table = normal((cfg.padded_vocab_size, cfg.d_model))
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (max_batch, 64)),
                      jnp.int32)
    report("embedding gather", emb.gather(table, ids), table[ids], 0.0)


# -- one chip: (a)-(e) -------------------------------------------------------


def one_chip() -> dict:
    import jax
    import numpy as np
    from repro.config import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import (build_engine, peak_bytes_in_use,
                                    serve_workload)

    dev = require_tpu()
    args = serve_args()
    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh(1, 1)

    t0 = time.perf_counter()
    eng = build_engine(cfg, mesh, args)
    jax.block_until_ready(eng.params)
    build_s = time.perf_counter() - t0
    n_params = sum(x.size for x in jax.tree.leaves(eng.params))
    log(f"(b) engine built: arch={cfg.name} layers={cfg.num_layers} "
        f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
        f"params={n_params} kv_cache_mib={eng.stats['kv_cache_mib']} "
        f"set-up_s={build_s:.1f} peak_bytes_in_use={peak_bytes_in_use()}")

    t0 = time.perf_counter()
    for name, lowered in eng.lower_steps().items():
        n = lowered.compile().as_text().count("tpu_custom_call")
        log(f"(c) {name} step: tpu_custom_call x{n}")
        check(n > 0, f"{name} step holds no Pallas kernel")
    log(f"(c) step compile set-up_s={time.perf_counter() - t0:.1f}")

    kernel_checks(cfg, eng.block_size, eng.max_len, eng.max_batch,
                  eng.chunk_width, args.seed)

    t0 = time.perf_counter()
    outs = serve_workload(eng, cfg, mesh, args)   # raises if any ends short
    wall = time.perf_counter() - t0
    toks = np.concatenate(list(outs.values()))
    in_vocab = bool(np.all((toks >= 0) & (toks < cfg.vocab_size)))
    log(f"(e) served {len(outs)} requests: tokens={toks.size} "
        f"per_request={[len(t) for t in outs.values()]} in_vocab={in_vocab} "
        f"wall_s={wall:.2f} peak_bytes_in_use={peak_bytes_in_use()}")
    check(len(outs) == args.requests, "not every request was served")
    check(in_vocab, "a sampled token is outside the vocabulary")
    return dev


# -- four chips: tensor-parallel engine vs one chip --------------------------


def last_logits(eng, prompt):
    """Logits at the last position of ``prompt`` (at most chunk_width
    tokens), through the engine's own paged chunk prefill on its own mesh,
    weights and cache. The engine's cache is left untouched."""
    import jax
    import numpy as np
    from repro.models import transformer
    from repro.serving.scheduler import StepPlan

    n = len(prompt)
    n_blocks = -(-n // eng.block_size)
    a = dict(eng._build_arrays(StepPlan([], [], []), False))
    tok = np.zeros((1, eng.chunk_width), np.int32)
    tok[0, :n] = prompt
    table = np.zeros((1, eng.max_blocks_per_seq), np.int32)
    table[0, :n_blocks] = np.arange(1, n_blocks + 1)
    a.update(c_tok=tok, c_start=np.zeros(1, np.int32),
             c_len=np.full(1, n, np.int32), c_table=table)

    def fn(params, cache, a):
        return transformer.prefill_chunk_paged(
            params, cache, eng.runner._chunk_batch(a), eng.cfg, eng.pcfg)[0]

    with jax.set_mesh(eng.mesh):
        return np.asarray(jax.jit(fn)(eng.params, eng.cache, a), np.float32)


def four_chips() -> dict:
    import gc

    import jax
    import numpy as np
    from repro.config import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import build_engine, make_requests

    dev = require_tpu()
    check(dev["count"] == 4, f"--four-chips needs 4 chips, found "
                             f"{dev['count']}")
    args = serve_args()
    cfg = get_config(args.arch, smoke=args.smoke)
    tp = max(m for m in range(1, dev["count"] + 1)
             if cfg.num_kv_heads % m == 0)

    def serve(eng):
        reqs = make_requests(cfg, args, np.random.default_rng(args.seed))
        t0 = time.perf_counter()
        outs = eng.run(reqs)
        wall = time.perf_counter() - t0
        return [outs[r.rid] for r in reqs], reqs, wall

    eng1 = build_engine(cfg, make_host_mesh(1, 1), args)
    outs1, reqs, wall1 = serve(eng1)
    prompt = reqs[0].prompt[:eng1.chunk_width]
    logits1 = last_logits(eng1, prompt)
    log(f"one chip: {sum(map(len, outs1))} tokens in {wall1:.2f}s "
        "(compile included)")
    params = jax.device_get(eng1.params)
    del eng1
    gc.collect()

    eng = build_engine(cfg, make_host_mesh(1, tp), args, params=params)
    del params
    log(f"model axis={tp} (num_kv_heads={cfg.num_kv_heads}); engine tp="
        f"{eng.tp}")
    for name, tree in (("params", eng.params), ("cache", eng.cache)):
        sizes = sorted({len(x.sharding.device_set)
                        for x in jax.tree.leaves(tree)})
        log(f"{name}: sharding.device_set sizes={sizes}")
    pool = eng.cache["sub0"]["k"]
    log(f"page pool k {pool.shape}: device_set size="
        f"{len(pool.sharding.device_set)}, per-device shard "
        f"{pool.sharding.shard_shape(pool.shape)}")
    check(len(pool.sharding.device_set) == tp,
          "page pools are not spread over the model axis")
    outs, _, wall = serve(eng)
    logits = last_logits(eng, prompt)
    log(f"model={tp}: {sum(map(len, outs))} tokens in {wall:.2f}s "
        "(compile included)")
    for i, d in enumerate(jax.devices()[:tp]):
        log(f"device {i} peak_bytes_in_use="
            f"{(d.memory_stats() or {}).get('peak_bytes_in_use')}")

    first_equal = all(np.array_equal(a[:FIRST_STEPS], b[:FIRST_STEPS])
                      for a, b in zip(outs1, outs))
    all_equal = sum(np.array_equal(a, b) for a, b in zip(outs1, outs))
    err = float(np.max(np.abs(logits - logits1)))
    same_argmax = bool(np.argmax(logits) == np.argmax(logits1))
    log(f"greedy tokens, first {FIRST_STEPS} steps equal for all "
        f"{len(outs)} requests={first_equal}; whole streams equal="
        f"{all_equal}/{len(outs)}")
    log(f"last-position logits: max_abs_err={err} atol={LOGITS_ATOL} "
        f"max_abs_logit={float(np.max(np.abs(logits1)))} "
        f"argmax_equal={same_argmax}")
    check(first_equal, "tensor-parallel greedy tokens differ from one chip")
    check(err <= LOGITS_ATOL and same_argmax,
          "tensor-parallel logits differ from one chip")
    return dev


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel comparison (4 chips)")
    opts = ap.parse_args()
    from repro.launch.compile_cache import configure_compile_cache
    log(f"compile cache: {configure_compile_cache()}")
    try:
        dev = four_chips() if opts.four_chips else one_chip()
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
