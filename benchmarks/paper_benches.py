"""One benchmark per paper table/figure (DESIGN.md §6). All runtimes are
single-host CPU; what is measured is the *mechanism* the paper measured —
coordination overheads, sharded vs sampled softmax, backup-worker tails —
with sizes scaled to minutes, not the paper's absolute 2016 numbers."""

from __future__ import annotations

import re
import time

import numpy as np


def _parse_derived(derived: str) -> dict:
    """Parse the human-readable derived string into typed fields for the
    machine-readable (``--json``) output: ``tok_s=57.1`` becomes a float
    field, ``ttft_p95=3steps/41ms`` splits into ``ttft_p95_steps`` and
    ``ttft_p95_ms``."""
    fields: dict = {}
    for part in derived.split():
        key, _, val = part.partition("=")
        if not val:
            continue
        m = re.fullmatch(r"(-?[0-9.]+)steps/(-?[0-9.]+)ms", val)
        if m:
            fields[key + "_steps"] = float(m.group(1))
            fields[key + "_ms"] = float(m.group(2))
            continue
        try:
            fields[key] = float(val)
        except ValueError:
            fields[key] = val
    return fields


def _csv(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}")
    return {"name": name, "us_per_call": round(us, 2), "derived": derived,
            **_parse_derived(derived)}


# ---------------------------------------------------------------------------
# Table 1: single-machine step time / framework overhead
# ---------------------------------------------------------------------------


def bench_table1_step_time(rows):
    import jax
    import jax.numpy as jnp
    from repro.config import (OptimizerConfig, ParallelConfig, ShapeConfig,
                              get_config)
    from repro.models import api
    from repro.optim import optimizers as opt
    from repro.spmd import steps as steps_mod

    shape = ShapeConfig("bench", seq_len=32, global_batch=4, kind="train")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    pcfg = ParallelConfig(remat="full")
    ocfg = OptimizerConfig(warmup_steps=0, schedule="constant")
    for arch in ("glm4_9b", "starcoder2_3b", "gemma2_27b", "qwen3_32b",
                 "qwen3_moe_30b_a3b", "mamba2_370m"):
        cfg = get_config(arch, smoke=True)
        with jax.set_mesh(mesh):
            params_f32, _ = api.init_model(cfg, jax.random.key(0))
            opt_state = opt.init_train_state(ocfg, params_f32)
            params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                                  params_f32)
            step = jax.jit(steps_mod.make_train_step(cfg, pcfg, ocfg),
                           donate_argnums=(0, 1))
            batch = api.make_batch(cfg, shape)
            params, opt_state, m = step(params, opt_state,
                                        jnp.asarray(1), batch)   # compile
            jax.block_until_ready(m["loss"])
            n = 10
            t0 = time.perf_counter()
            for i in range(n):
                params, opt_state, m = step(params, opt_state,
                                            jnp.asarray(i), batch)
            jax.block_until_ready(m["loss"])
            dt = (time.perf_counter() - t0) / n
        tok_s = shape.global_batch * shape.seq_len / dt
        rows.append(_csv(f"table1/{arch}", dt * 1e6,
                         f"tok_s={tok_s:.0f}"))


# ---------------------------------------------------------------------------
# §2.1 production inference: the continuous-batching engine under ragged
# horizons (goodput per decode step; the mechanism behind the paper's
# "serving at scale" claim) — headline transformer row, prefix-cached row,
# speculative draft-and-verify rows, and the SSM / enc-dec runner rows
# ---------------------------------------------------------------------------


def _latency_percentiles(eng, reqs):
    """p50/p95 TTFT and end-to-end latency, in engine steps and wall
    seconds, from the engine's per-request latency records."""
    recs = [eng.stats["latency"][r.rid] for r in reqs]
    ttft_steps = [r["first_token_step"] - r["arrival_step"] for r in recs]
    ttft_wall = [r["first_token_wall"] - r["arrival_wall"] for r in recs]
    e2e_steps = [r["done_step"] - r["arrival_step"] for r in recs]
    e2e_wall = [r["done_wall"] - r["arrival_wall"] for r in recs]

    def pct(xs, q):
        return float(np.percentile(xs, q))

    return (f"ttft_p50={pct(ttft_steps, 50):.0f}steps/"
            f"{pct(ttft_wall, 50) * 1e3:.0f}ms "
            f"ttft_p95={pct(ttft_steps, 95):.0f}steps/"
            f"{pct(ttft_wall, 95) * 1e3:.0f}ms "
            f"e2e_p50={pct(e2e_steps, 50):.0f}steps/"
            f"{pct(e2e_wall, 50) * 1e3:.0f}ms "
            f"e2e_p95={pct(e2e_steps, 95):.0f}steps/"
            f"{pct(e2e_wall, 95) * 1e3:.0f}ms")


def bench_serving_throughput(rows):
    from repro.config import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.serving import InferenceEngine, Request

    cfg = get_config("glm4_9b", smoke=True)
    mesh = make_host_mesh(1, 1)
    rng = np.random.default_rng(0)
    n_req, prompt_len, max_batch = 12, 32, 4
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_req)]
    # ragged horizons: static batching would decode max() steps for all
    max_news = [4 + 4 * (i % 4) for i in range(n_req)]

    # prefix caching OFF for the headline row: the warmup run (for jit
    # compile) uses the same prompts, and cache hits would let the timed
    # run skip nearly all prefill — not representative of cold traffic
    eng = InferenceEngine(cfg, mesh, max_batch=max_batch, block_size=16,
                          max_len=128, enable_prefix_caching=False)
    reqs = [Request(p, max_new=mn) for p, mn in zip(prompts, max_news)]
    eng.run(reqs)                               # includes compile
    steps0 = eng.stats["steps"]
    t0 = time.perf_counter()
    eng2_reqs = [Request(p, max_new=mn) for p, mn in zip(prompts, max_news)]
    eng.run(eng2_reqs)
    dt_eng = time.perf_counter() - t0
    n_tok = sum(mn for mn in max_news)
    eng_steps = eng.stats["steps"] - steps0
    rows.append(_csv("serving/paged_engine", dt_eng / n_tok * 1e6,
                     f"tok_s={n_tok/dt_eng:.1f} "
                     f"slot_steps={eng_steps * max_batch} "
                     + _latency_percentiles(eng, eng2_reqs)))

    # the same workload through the async streaming front-end (driver +
    # admission control + per-request token streams; docs/
    # serving-frontend.md) on the warm headline engine: measures the
    # front-end's overhead over the bare batch driver — the admission
    # path live HTTP traffic takes, so this row and the headline stay
    # comparable by construction (no SLO target: nothing sheds)
    import asyncio

    from repro.serving.frontend import AdmissionController, AsyncEngineDriver

    fe_reqs = [Request(p, max_new=mn) for p, mn in zip(prompts, max_news)]
    fe_adm = AdmissionController()

    async def _stream_workload():
        async with AsyncEngineDriver(eng, admission=fe_adm) as drv:
            streams = [await drv.submit(r) for r in fe_reqs]

            async def pull(s):
                return [ev.token async for ev in s]

            await asyncio.gather(*(pull(s) for s in streams))

    t0 = time.perf_counter()
    asyncio.run(_stream_workload())
    dt_fe = time.perf_counter() - t0
    rows.append(_csv("serving/frontend_stream", dt_fe / n_tok * 1e6,
                     f"tok_s={n_tok/dt_fe:.1f} "
                     f"submitted={fe_adm.submitted} shed={fe_adm.shed} "
                     f"queue_peak={fe_adm.queue_peak} "
                     + _latency_percentiles(eng, fe_reqs)))

    # the prefix-cache benefit, measured explicitly: same prompts through
    # a caching engine whose cache the warmup run populated
    engc = InferenceEngine(cfg, mesh, max_batch=max_batch, block_size=16,
                           max_len=128, params=eng.params)
    engc.run([Request(p, max_new=mn) for p, mn in zip(prompts, max_news)])
    t0 = time.perf_counter()
    engc_reqs = [Request(p, max_new=mn) for p, mn in zip(prompts, max_news)]
    engc.run(engc_reqs)
    dt_c = time.perf_counter() - t0
    rows.append(_csv("serving/paged_engine_prefix_cached",
                     dt_c / n_tok * 1e6,
                     f"tok_s={n_tok/dt_c:.1f} "
                     f"cache_hit_tokens={engc.stats['cache_hit_tokens']} "
                     + _latency_percentiles(engc, engc_reqs)))

    # speculative decoding (draft-and-verify): a repetitive-prompt
    # workload decoded with and without a k=2 self-draft (draft shares the
    # target's params, so the draft agrees with the target wherever the
    # decode/verify numerics do — mean accept length ~ k+1 and the row
    # isolates the mechanism's accounting + verify-step overhead rather
    # than draft quality). Prefix caching off, like the headline row.
    scfg = get_config("starcoder2_3b", smoke=True)
    pattern = np.tile(np.arange(7, dtype=np.int32), 1 + prompt_len // 7)
    sprompts = [np.roll(pattern, i)[:prompt_len].astype(np.int32)
                for i in range(n_req)]

    def spec_reqs():
        return [Request(p, max_new=mn)
                for p, mn in zip(sprompts, max_news)]

    soff = InferenceEngine(scfg, mesh, max_batch=max_batch, block_size=16,
                           max_len=128, enable_prefix_caching=False)
    soff.run(spec_reqs())                       # compile
    t0 = time.perf_counter()
    soff.run(spec_reqs())
    dt_off = time.perf_counter() - t0
    rows.append(_csv("serving/speculative_off", dt_off / n_tok * 1e6,
                     f"tok_s={n_tok/dt_off:.1f} mean_accept_len=1.0"))
    son = InferenceEngine(scfg, mesh, max_batch=max_batch, block_size=16,
                          max_len=128, enable_prefix_caching=False,
                          params=soff.params, draft_params=soff.params,
                          num_speculative_tokens=2)
    son.run(spec_reqs())                        # compile
    t0 = time.perf_counter()
    son.run(spec_reqs())
    dt_on = time.perf_counter() - t0
    rows.append(_csv("serving/speculative_k2", dt_on / n_tok * 1e6,
                     f"tok_s={n_tok/dt_on:.1f} "
                     f"mean_accept_len={son.stats['mean_accept_len']:.3f} "
                     f"steps={son.stats['steps']}"))

    # the non-transformer runners on the same hot path: pure SSM (slot
    # state, no block pool) and enc-dec (paged self-KV + admission-time
    # encoder passes) — the workload families the runner refactor opened
    for arch, plen in (("mamba2_370m", 24), ("whisper_large_v3", 8)):
        fcfg = get_config(arch, smoke=True)
        fprompts = [rng.integers(0, fcfg.vocab_size, plen).astype(np.int32)
                    for _ in range(n_req)]
        fframes = [rng.normal(0, 1, (fcfg.encoder_seq_len, fcfg.d_model)
                              ).astype(np.float32)
                   if fcfg.frontend == "audio" else None
                   for _ in range(n_req)]
        feng = InferenceEngine(fcfg, mesh, max_batch=max_batch,
                               block_size=16, max_len=128)

        def make_reqs():
            return [Request(p, max_new=mn, frames=f)
                    for p, mn, f in zip(fprompts, max_news, fframes)]

        feng.run(make_reqs())                   # compile
        t0 = time.perf_counter()
        freqs = make_reqs()
        feng.run(freqs)
        dt_f = time.perf_counter() - t0
        rows.append(_csv(f"serving/paged_engine_{arch}",
                         dt_f / n_tok * 1e6,
                         f"tok_s={n_tok/dt_f:.1f} "
                         f"encodes={feng.stats['encodes']} "
                         + _latency_percentiles(feng, freqs)))

    # tensor-parallel row: the headline workload on a forced 2-device host
    # mesh (page pools sharded by kv head over "model"; docs/multi-host.md).
    # Runs in a subprocess because the virtual device count is fixed at
    # process start, pinned to the CPU (JAX_PLATFORMS=cpu) so it never
    # reaches for an accelerator the parent process holds. It measures the
    # TP *overhead* on virtual host devices (collectives + per-shard
    # dispatch), not a speedup — a host row, labelled platform=cpu_host.
    import os
    import subprocess
    import sys
    tp_code = (
        "import jax, jax.numpy as jnp, numpy as np, time\n"
        "from repro.config import get_config\n"
        "from repro.serving import InferenceEngine, Request\n"
        "cfg = get_config('glm4_9b', smoke=True)\n"
        "mesh = jax.make_mesh((1, 2), ('data', 'model'),\n"
        "    axis_types=(jax.sharding.AxisType.Auto,) * 2)\n"
        "rng = np.random.default_rng(0)\n"
        "prompts = [rng.integers(0, cfg.vocab_size, 32).astype(np.int32)\n"
        "           for _ in range(12)]\n"
        "max_news = [4 + 4 * (i % 4) for i in range(12)]\n"
        "eng = InferenceEngine(cfg, mesh, max_batch=4, block_size=16,\n"
        "                      max_len=128, enable_prefix_caching=False)\n"
        "reqs = lambda: [Request(p, max_new=mn)\n"
        "                for p, mn in zip(prompts, max_news)]\n"
        "eng.run(reqs())\n"
        "t0 = time.perf_counter()\n"
        "eng.run(reqs())\n"
        "print('TP2RESULT', time.perf_counter() - t0, sum(max_news))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                        + env.get("XLA_FLAGS", "")).strip()
    proc = subprocess.run([sys.executable, "-c", tp_code],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("TP2RESULT"))
    dt_tp, n_tp = float(line.split()[1]), int(line.split()[2])
    rows.append(_csv("serving/paged_engine_tp2", dt_tp / n_tp * 1e6,
                     f"tok_s={n_tp/dt_tp:.1f} mesh=model2 "
                     "platform=cpu_host"))


# ---------------------------------------------------------------------------
# Ragged packed prefill: a bursty multi-prompt workload served with
# prefill_pack=1 (classic single-chunk admission) vs prefill_pack=4 (several
# prompts' chunks packed into one flat ragged token batch per step). The
# packed row must beat the baseline on admitted tokens/s and TTFT p95 —
# that delta is the tentpole claim of the ragged-prefill kernel work.
# ---------------------------------------------------------------------------


def bench_serving_ragged_prefill(rows):
    from repro.config import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.serving import InferenceEngine, Request

    cfg = get_config("glm4_9b", smoke=True)
    mesh = make_host_mesh(1, 1)
    rng = np.random.default_rng(7)
    n_req, prompt_len, max_batch = 16, 24, 8
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_req)]
    n_tok = n_req * 4

    shared_params = None
    for pack, row_name in ((1, "serving/ragged_prefill_base"),
                           (4, "serving/ragged_prefill")):
        # budget 104 leaves chunk_width 96 after the 8-wide decode batch:
        # exactly four 24-token prompts per packed step vs one for the
        # baseline — the burst drains 4x faster through prefill
        eng = InferenceEngine(cfg, mesh, max_batch=max_batch, block_size=16,
                              max_len=128, max_num_batched_tokens=104,
                              enable_prefix_caching=False,
                              prefill_pack=pack, params=shared_params)
        shared_params = eng.params          # identical weights both rows

        def mk():
            return [Request(p, max_new=4) for p in prompts]

        eng.run(mk())                       # compile
        t0 = time.perf_counter()
        reqs = mk()
        eng.run(reqs, arrival_steps=[0] * n_req)     # one burst
        dt = time.perf_counter() - t0
        rows.append(_csv(row_name, dt / n_tok * 1e6,
                         f"tok_s={n_tok/dt:.1f} prefill_pack={pack} "
                         f"steps={eng.stats['steps']} "
                         + _latency_percentiles(eng, reqs)))


# ---------------------------------------------------------------------------
# KV tiering: quantized int8 pages at a matched device-pool byte budget
# (the int8 pool holds ~2x the blocks, so the same bytes serve deeper
# contexts), and swap-vs-recompute preemption under the scheduler cost
# model (policy "always" vs "never" on the same small pool; outputs must
# be byte-identical either way — swapped KV is an exact copy and
# recompute follows the repo rounding convention).
# ---------------------------------------------------------------------------


def bench_serving_kv_tiering(rows):
    from repro.config import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.serving import InferenceEngine, Request
    from repro.serving.kv_cache import block_bytes

    cfg = get_config("glm4_9b", smoke=True)
    mesh = make_host_mesh(1, 1)
    rng = np.random.default_rng(11)
    n_req, prompt_len, max_batch = 12, 32, 4
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_req)]
    max_news = [4 + 4 * (i % 4) for i in range(n_req)]
    n_tok = sum(max_news)

    def mk():
        return [Request(p, max_new=mn) for p, mn in zip(prompts, max_news)]

    # -- matched pool bytes: bf16 vs int8 ---------------------------------
    # Both engines get the same device-pool byte budget (40 bf16 blocks'
    # worth). The int8 pool's K/V payload is exactly half the bytes per
    # row (2*head_dim -> head_dim), so payload capacity is 2.0x; the fp32
    # per-row scale sidecar carried alongside costs 4/(head_dim+4) of the
    # quantized block, which is what separates the realized block-count
    # ratio from the payload ratio.
    bb = {d: block_bytes(cfg, 16, kv_dtype=d) for d in ("bf16", "int8")}
    pool_bytes = 40 * bb["bf16"]
    hd = cfg.head_dim
    shared_params = None
    n_blocks = {}
    for dtype, row_name in (("bf16", "serving/kv_bf16_base"),
                            ("int8", "serving/kv_int8")):
        n_blocks[dtype] = pool_bytes // bb[dtype]
        eng = InferenceEngine(cfg, mesh, max_batch=max_batch, block_size=16,
                              max_len=128, num_blocks=n_blocks[dtype],
                              kv_dtype=dtype, params=shared_params)
        shared_params = eng.params          # identical weights both rows
        eng.run(mk())                       # compile
        t0 = time.perf_counter()
        eng.run(mk())
        dt = time.perf_counter() - t0
        derived = (f"tok_s={n_tok/dt:.1f} num_blocks={n_blocks[dtype]} "
                   f"kv_cache_mib={eng.stats['kv_cache_mib']:.3f}")
        if dtype == "int8":
            derived += (
                f" capacity_ratio={n_blocks['int8']/n_blocks['bf16']:.2f}"
                f" payload_ratio={2*hd/hd:.1f}"
                f" scale_overhead={4/(hd+4):.3f}")
        rows.append(_csv(row_name, dt / n_tok * 1e6, derived))

    # -- swap vs recompute preemption -------------------------------------
    # A pool too small for the full working set forces preemptions; the
    # "never" row resolves every victim by releasing blocks and
    # recomputing the prefix, the "always" row by swapping pages to the
    # pinned host tier and copying them back on re-admission. Greedy
    # outputs are asserted byte-identical across the two policies.
    swap_max_news = [8 + 8 * (i % 3) for i in range(n_req)]
    n_swap_tok = sum(swap_max_news)

    def mk_swap():
        return [Request(p, max_new=mn)
                for p, mn in zip(prompts, swap_max_news)]

    swap_bytes = 32 * bb["bf16"]
    outs = {}
    for policy, row_name in (("never", "serving/swap_recompute_base"),
                             ("always", "serving/swap_vs_recompute")):
        eng = InferenceEngine(cfg, mesh, max_batch=max_batch, block_size=16,
                              max_len=128, num_blocks=10,
                              swap_space_bytes=swap_bytes,
                              swap_policy=policy, params=shared_params)
        eng.run(mk_swap())                  # compile
        t0 = time.perf_counter()
        reqs = mk_swap()
        out = eng.run(reqs)
        dt = time.perf_counter() - t0
        outs[policy] = [out[r.rid] for r in reqs]
        rows.append(_csv(
            row_name, dt / n_swap_tok * 1e6,
            f"tok_s={n_swap_tok/dt:.1f} policy={policy} "
            f"preemptions={eng.stats['preemptions']} "
            f"swap_preemptions={eng.stats['swap_preemptions']} "
            f"swap_ins={eng.stats['swap_ins']} "
            f"swapped_out_blocks={eng.stats['swapped_out_blocks']} "
            f"swapped_in_blocks={eng.stats['swapped_in_blocks']} "
            + _latency_percentiles(eng, reqs)))
    for a, b in zip(outs["never"], outs["always"]):
        assert np.array_equal(a, b), "swap vs recompute outputs diverged"


# ---------------------------------------------------------------------------
# Production sampling surface (docs/sampling.md): the full in-jit pipeline
# (top-p + min-p + penalties + logprobs, per slot) vs the pure-greedy fast
# path on the identical workload — the cost of the richer per-slot
# transform, isolated from model/runner differences.
# ---------------------------------------------------------------------------


def bench_serving_sampling(rows):
    from repro.config import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.serving import InferenceEngine, Request
    from repro.serving.scheduler import SamplingParams

    cfg = get_config("glm4_9b", smoke=True)
    mesh = make_host_mesh(1, 1)
    rng = np.random.default_rng(21)
    n_req, prompt_len, max_batch = 12, 32, 4
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_req)]
    max_news = [4 + 4 * (i % 4) for i in range(n_req)]
    n_tok = sum(max_news)
    full_sp = [SamplingParams(temperature=0.9, top_k=16, top_p=0.85,
                              min_p=0.02, repetition_penalty=1.2,
                              frequency_penalty=0.1, logprobs=4, seed=i)
               for i in range(n_req)]

    def mk(sps=None):
        return [Request(p, max_new=mn,
                        sampling=sps[i] if sps else SamplingParams())
                for i, (p, mn) in enumerate(zip(prompts, max_news))]

    shared_params = None
    dts = {}
    for name, sps in (("serving/sampling_greedy_base", None),
                      ("serving/sampling_full", full_sp)):
        eng = InferenceEngine(cfg, mesh, max_batch=max_batch, block_size=16,
                              max_len=128, enable_prefix_caching=False,
                              params=shared_params)
        shared_params = eng.params          # identical weights both rows
        eng.run(mk(sps))                    # compile
        t0 = time.perf_counter()
        eng.run(mk(sps))
        dts[name] = dt = time.perf_counter() - t0
        derived = (f"tok_s={n_tok/dt:.1f} "
                   f"full_sampling_steps={eng.stats['full_sampling_steps']}")
        if sps is None:
            assert eng.stats["full_sampling_steps"] == 0  # fast path held
        else:
            derived += (" overhead_ratio="
                        f"{dt/dts['serving/sampling_greedy_base']:.3f}")
        rows.append(_csv(name, dt / n_tok * 1e6, derived))


# ---------------------------------------------------------------------------
# Data-parallel replicas behind the ReplicaRouter (docs/multi-host.md): a
# burst workload drained by dp=1 vs dp=2 fleets (same per-replica config,
# shared prefix index), plus the disaggregated prefill/decode split. Wall
# tok_s is reported as measured; on a single-core host the replicas'
# threads serialize, so dp scaling is additionally reported on the fleet
# *step* clock — max over replicas' engine steps, which is what wall time
# tracks when each replica owns real hardware (same deterministic virtual
# clock the ttft_steps percentiles use).
# ---------------------------------------------------------------------------


def bench_serving_dp(rows):
    from repro.config import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.serving import (InferenceEngine, ReplicaRouter, Request,
                               SharedPrefixIndex)

    cfg = get_config("glm4_9b", smoke=True)
    mesh = make_host_mesh(1, 1)
    rng = np.random.default_rng(0)
    n_req, prompt_len, max_batch = 16, 32, 4
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_req)]
    warm = [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
            for _ in range(n_req)]
    # uniform horizons: a burst of equal-cost requests, so the router's
    # least-outstanding-tokens placement splits the fleet evenly and the
    # scaling number measures replication, not workload skew (raggedness
    # is the serving_throughput rows' subject)
    max_new = 12
    n_tok = n_req * max_new

    def mk(ps, base):
        return [Request(p.copy(), max_new=max_new, rid=base + i)
                for i, p in enumerate(ps)]

    shared_params = None
    results = {}
    for dp, name in ((1, "serving/dp1"), (2, "serving/dp2")):
        shared = SharedPrefixIndex(num_slots=256)
        engines = [InferenceEngine(cfg, mesh, max_batch=max_batch,
                                   block_size=16, max_len=128,
                                   params=shared_params,
                                   shared_index=shared)
                   for _ in range(dp)]
        shared_params = engines[0].params   # identical weights, all rows
        router = ReplicaRouter(engines)
        router.run(mk(warm, 90000))         # compile + warm the replicas
        steps0 = [e.stats["steps"] for e in engines]
        routed0 = list(router.routed)
        t0 = time.perf_counter()
        router.run(mk(prompts, 91000))      # the burst: all arrive at once
        dt = time.perf_counter() - t0
        steps = [e.stats["steps"] - s0 for e, s0 in zip(engines, steps0)]
        fleet_steps = max(steps)            # replicas step concurrently
        results[name] = (dt, fleet_steps)
        routed = [n - n0 for n, n0 in zip(router.routed, routed0)]
        derived = (f"tok_s={n_tok/dt:.1f} fleet_steps={fleet_steps} "
                   f"routed={'/'.join(str(n) for n in routed)} "
                   f"shared_published_blocks="
                   f"{shared.stats()['published_blocks']}")
        if dp > 1:
            dt1, fs1 = results["serving/dp1"]
            derived += (f" wall_speedup_vs_dp1={dt1/dt:.2f} "
                        f"step_speedup_vs_dp1={fs1/fleet_steps:.2f}")
        rows.append(_csv(name, dt / n_tok * 1e6, derived))

    # disaggregated prefill/decode: probe on the prefill replica, decode
    # continuation adopts the published blocks through the shared index
    shared = SharedPrefixIndex(num_slots=256)
    engines = [InferenceEngine(cfg, mesh, max_batch=max_batch,
                               block_size=16, max_len=128,
                               params=shared_params, shared_index=shared)
               for _ in range(2)]
    router = ReplicaRouter(engines, disaggregate=True)
    router.run(mk(warm, 92000))
    steps0 = [e.stats["steps"] for e in engines]
    handoffs0 = router.handoffs
    t0 = time.perf_counter()
    router.run(mk(prompts, 93000))
    dt = time.perf_counter() - t0
    steps = [e.stats["steps"] - s0 for e, s0 in zip(engines, steps0)]
    rows.append(_csv(
        "serving/disagg_prefill_decode", dt / n_tok * 1e6,
        f"tok_s={n_tok/dt:.1f} fleet_steps={max(steps)} "
        f"handoffs={router.handoffs - handoffs0} "
        f"decode_shared_hit_blocks={engines[1].stats['shared_hit_blocks']} "
        f"prefill_published_blocks="
        f"{engines[0].stats['shared_published_blocks']}"))


# ---------------------------------------------------------------------------
# Paged-attention kernel rows: decode (pages per block from the shapes)
# and chunked prefill (pages_per_compute_block knob) through the dispatch
# layer, plus the ragged packed-prefill op (fused KV scatter + attention).
# On CPU these time the XLA dispatch path (the knob is a no-op there); on
# TPU the same calls hit the Pallas kernels, so the rows track the kernels
# wherever the bench runs.
# ---------------------------------------------------------------------------


def bench_paged_kernels(rows):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    rng = np.random.default_rng(0)
    B, H, K, hd, bs, nb = 8, 8, 4, 64, 16, 8
    num_blocks = B * nb + 1
    k_pages = jnp.asarray(rng.normal(0, 1, (num_blocks, K, bs, hd)),
                          jnp.bfloat16)
    v_pages = jnp.asarray(rng.normal(0, 1, (num_blocks, K, bs, hd)),
                          jnp.bfloat16)
    tables = jnp.asarray(
        1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb))
    ctx = jnp.asarray(rng.integers(bs, nb * bs + 1, B), jnp.int32)

    def timeit(fn, *args):
        jfn = jax.jit(fn)
        out = jax.block_until_ready(jfn(*args))
        n = 20
        t0 = time.perf_counter()
        for _ in range(n):
            out = jfn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n

    q_d = jnp.asarray(rng.normal(0, 1, (B, H, hd)), jnp.bfloat16)
    dt = timeit(lambda q: kops.paged_attention(
        q, k_pages, v_pages, tables, ctx), q_d)
    rows.append(_csv("kernels/paged_decode", dt * 1e6,
                     f"tok_s={B/dt:.0f} backend={backend}"))

    C = 32
    q_p = jnp.asarray(rng.normal(0, 1, (B, C, H, hd)), jnp.bfloat16)
    q_lens = jnp.minimum(ctx, C)
    dt = timeit(lambda q: kops.paged_prefill_attention(
        q, k_pages, v_pages, tables, ctx, q_lens,
        pages_per_compute_block=4), q_p)
    rows.append(_csv("kernels/paged_prefill_mp", dt * 1e6,
                     f"tok_s={int(q_lens.sum())/dt:.0f} pages_per_block=4 "
                     f"backend={backend}"))

    # ragged packed prefill: S=4 sequences' chunks in one flat T=64 batch,
    # chunk KV scattered and attended in one op (fused on the Pallas path)
    S, T = 4, 64
    lens = np.full(S, T // S, np.int32)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    ends = (starts + lens).astype(np.int32)
    row_seq = np.repeat(np.arange(S, dtype=np.int32), lens)
    r_ctx = jnp.asarray(bs + lens, jnp.int32)     # one context block + chunk
    r_tables = tables[:S]
    q_r = jnp.asarray(rng.normal(0, 1, (T, H, hd)), jnp.bfloat16)
    k_new = jnp.asarray(rng.normal(0, 1, (T, K, hd)), jnp.bfloat16)
    v_new = jnp.asarray(rng.normal(0, 1, (T, K, hd)), jnp.bfloat16)
    dt = timeit(lambda q: kops.ragged_prefill_update_attend(
        q, k_new, v_new, k_pages, v_pages, r_tables, r_ctx,
        jnp.asarray(starts), jnp.asarray(ends), jnp.asarray(row_seq)), q_r)
    rows.append(_csv("kernels/ragged_prefill", dt * 1e6,
                     f"tok_s={T/dt:.0f} packed_seqs={S} "
                     f"backend={backend}"))


# ---------------------------------------------------------------------------
# Figure 6: null-step synchronous replication (scalar / dense / sparse)
# ---------------------------------------------------------------------------


def bench_fig6_null_step(rows):
    import numpy as np
    from repro.core.cluster import Cluster
    from repro.core.graph import Graph
    from repro.core.gradients import gradients
    from repro.core.session import Session
    import threading

    n_ps = 4
    dense_mb = 8        # "dense" model size in MB (paper: 100MB/1GB)
    emb_rows = 65536    # "sparse" table rows (step cost must not scale)

    for variant in ("scalar", "dense", "sparse"):
        for n_workers in (1, 2, 4, 8):
            g = Graph()
            cl = Cluster(ps=n_ps, worker=n_workers)
            sess = Session(g, cl, default_device="worker:0")
            reads, updates = [], []
            if variant == "scalar":
                shapes = [(1,)] * n_ps
            elif variant == "dense":
                per = dense_mb * 1024 * 1024 // 4 // n_ps
                shapes = [(per,)] * n_ps
            else:
                shapes = [(emb_rows // n_ps, 16)] * n_ps
            for i, shp in enumerate(shapes):
                h = g.apply("Variable", var_name=f"w{i}",
                            initial=np.zeros(shp, np.float32),
                            device=f"ps:{i}")
                if variant == "sparse":
                    ids = g.constant(np.arange(32) % shp[0])
                    rd = g.apply("Gather", g.apply("Read", h), ids)
                    rd.op.colocation = h.op.name
                    upd = g.apply("ScatterAdd", h, ids,
                                  g.constant(np.ones((32, 16), np.float32)
                                             * 1e-6))
                else:
                    rd = g.apply("Read", h)
                    upd = g.apply("AssignAdd", h, g.constant(
                        np.float32(1e-6)))
                reads.append(rd)
                updates.append(upd)
            # per-worker fetch+update closure over worker device
            fetch = [g.apply("ReduceSum", r) for r in reads]
            times = []

            def worker_loop(w, n=6):
                for _ in range(n):
                    t0 = time.perf_counter()
                    sess.run(fetch + updates)
                    times.append(time.perf_counter() - t0)

            threads = [threading.Thread(target=worker_loop, args=(w,),
                                        daemon=True)
                       for w in range(n_workers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            med = float(np.median(times)) if times else 0.0
            rows.append(_csv(f"fig6/{variant}/workers{n_workers}",
                             med * 1e6, f"median_step_ms={med*1e3:.2f}"))


# ---------------------------------------------------------------------------
# Figure 7: throughput scaling, async vs sync
# ---------------------------------------------------------------------------


def bench_fig7_scaling(rows):
    from repro.core.cluster import Cluster
    from repro.core.graph import Graph
    from repro.ps.training import PSTrainer, linear_model

    rng = np.random.default_rng(0)
    W = rng.normal(0, 1, (64, 32)).astype(np.float32)

    def batch_fn(w, s):
        x = rng.normal(0, 1, (64, 64)).astype(np.float32)
        return x, (x @ W).argmax(-1)

    steps = 10
    for mode in ("async", "sync"):
        for n_workers in (1, 2, 4, 8):
            g = Graph()
            cl = Cluster(ps=2, worker=n_workers)
            tr = PSTrainer(linear_model(g, 64, 32, 2), cl, mode=mode,
                           n_workers=n_workers, lr=0.1)
            t0 = time.perf_counter()
            stats = tr.train(steps, batch_fn)
            wall = time.perf_counter() - t0
            total_steps = steps * (n_workers if mode == "async" else 1)
            thr = total_steps * 64 / wall     # examples/sec
            rows.append(_csv(f"fig7/{mode}/workers{n_workers}",
                             wall / total_steps * 1e6,
                             f"examples_s={thr:.0f}"))


# ---------------------------------------------------------------------------
# Figure 8: backup workers under injected stragglers
# ---------------------------------------------------------------------------


def bench_fig8_backup_workers(rows):
    from repro.core.cluster import Cluster
    from repro.core.graph import Graph
    from repro.ps.training import PSTrainer, linear_model

    rng = np.random.default_rng(0)
    W = rng.normal(0, 1, (32, 16)).astype(np.float32)

    def batch_fn(w, s):
        x = rng.normal(0, 1, (32, 32)).astype(np.float32)
        return x, (x @ W).argmax(-1)

    n = 6
    t0_med = None
    for b in (0, 1, 2, 3):
        g = Graph()
        cl = Cluster(ps=2, worker=n)
        tr = PSTrainer(linear_model(g, 32, 16, 2), cl,
                       mode="backup" if b else "sync", n_workers=n,
                       backup_workers=b, lr=0.1,
                       straggler_s=0.03, straggler_every=3)
        stats = tr.train(8, batch_fn)
        med = float(np.median(stats.step_times))
        if b == 0:
            t0_med = med
        # paper's normalized speedup: t(b)/t(0) * n/(n+b) — they normalize
        # by total resources; our workers are fixed so use t(0)/t(b) * n/(n)
        norm = (t0_med / med) * (n - b) / n
        rows.append(_csv(f"fig8/backup{b}", med * 1e6,
                         f"normalized_speedup={norm:.3f} "
                         f"discarded={stats.discarded}"))


# ---------------------------------------------------------------------------
# Figure 9: LM throughput, full vs sampled softmax x PS tasks
# ---------------------------------------------------------------------------


def bench_fig9_softmax(rows):
    from repro.core.cluster import Cluster
    from repro.core.graph import Graph
    from repro.ps.lm import lm_batch_fn, lstm_lm_model
    from repro.ps.training import PSTrainer

    vocab, d, unroll, batch = 8192, 64, 8, 64
    for softmax in ("full", "sampled"):
        for n_ps in (1, 2, 4):
            g = Graph()
            cl = Cluster(ps=n_ps, worker=2)
            model = lstm_lm_model(g, vocab=vocab, d=d, unroll=unroll,
                                  n_ps=n_ps, softmax=softmax)
            tr = PSTrainer(model, cl, mode="async", n_workers=2, lr=0.05)
            steps = 6
            t0 = time.perf_counter()
            tr.train(steps, lm_batch_fn(vocab, batch, unroll))
            wall = time.perf_counter() - t0
            words_s = steps * 2 * batch / wall
            rows.append(_csv(f"fig9/{softmax}/ps{n_ps}",
                             wall / (steps * 2) * 1e6,
                             f"words_s={words_s:.0f}"))


# ---------------------------------------------------------------------------
# §5 executor dispatch rate ("2,000,000 null operations per second")
# ---------------------------------------------------------------------------


def bench_executor_dispatch(rows):
    from repro.core.cluster import Cluster
    from repro.core.graph import Graph
    from repro.core.session import Session

    g = Graph()
    cl = Cluster(worker=1)
    sess = Session(g, cl)
    x = g.constant(np.float32(1.0))
    n_ops = 2000
    for _ in range(n_ops):
        x = g.apply("Identity", x)
    sess.run(x)                      # build + cache plan
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        sess.run(x)
    dt = time.perf_counter() - t0
    ops_s = n_ops * reps / dt
    rows.append(_csv("executor/null_op_dispatch", dt / reps / n_ops * 1e6,
                     f"ops_per_s={ops_s:.0f}"))
