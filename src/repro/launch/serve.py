"""Serving driver: continuous batching through the model-agnostic engine.

Mirrors the paper's training/inference duality (§2.1: same model code for
both). The engine (``repro.serving``) admits requests from a queue as
slots and cache resources free up, retires each on its own EOS/max_new,
and steps every running request in one jitted budgeted step. Per-family
runners cover decoder-only transformers (paged KV + prefix caching), pure
SSM (per-slot Mamba state), hybrid mamba+attention, encoder-decoder
(paged self-KV + per-slot cross K/V), and draft-and-verify speculative
decoding (``--num-speculative-tokens``; docs/speculative.md).

All traffic — the synthetic Poisson bench below and live HTTP alike —
flows through the async streaming front-end (``repro.serving.frontend``;
docs/serving-frontend.md): the same admission path, token streams, and
metrics surface, so bench rows stay comparable with production serving.

  PYTHONPATH=src python -m repro.launch.serve --arch glm4_9b --smoke
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2_370m --smoke
  PYTHONPATH=src python -m repro.launch.serve --arch whisper_large_v3 --smoke
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2_3b --smoke \\
      --num-speculative-tokens 2

Long-lived HTTP server (SSE token streaming + /health + /metrics;
graceful drain on SIGINT/SIGTERM — stop admitting, finish in-flight):

  PYTHONPATH=src python -m repro.launch.serve --arch glm4_9b --smoke \\
      --http 127.0.0.1:8311 --ttft-slo-ms 5000 --max-queue 64

Tensor-parallel serving (page pools sharded by kv head over the mesh
"model" axis; docs/multi-host.md) — needs that many devices, e.g. a forced
host platform for CPU smoke runs:

  XLA_FLAGS=--xla_force_host_platform_device_count=2 PYTHONPATH=src \\
      python -m repro.launch.serve --arch glm4_9b --smoke --mesh model=2

Data-parallel replicas behind one router (shared cross-replica prefix
index; add --disaggregate for prefill/decode role split —
docs/multi-host.md):

  PYTHONPATH=src python -m repro.launch.serve --arch glm4_9b --smoke --dp 2
  PYTHONPATH=src python -m repro.launch.serve --arch glm4_9b --smoke \\
      --dp 2 --disaggregate
"""

from __future__ import annotations

import argparse
import asyncio
import signal
import time

import numpy as np

from repro.config import get_config
from repro.launch.compile_cache import configure_compile_cache


def poisson_arrival_steps(n: int, rate: float, rng) -> list[int]:
    """Arrival step indices for a Poisson process with ``rate`` requests
    per decode step (the engine's virtual clock)."""
    t, out = 0.0, []
    for _ in range(n):
        t += rng.exponential(1.0 / max(rate, 1e-9))
        out.append(int(t))
    return out


def parse_mesh(spec: str | None) -> tuple[int, int]:
    """'model=2' / 'data=2,model=4' -> (data, model); None -> (1, 1)."""
    sizes = {"data": 1, "model": 1}
    if spec:
        for part in spec.split(","):
            name, _, val = part.partition("=")
            if name not in sizes or not val.isdigit() or int(val) < 1:
                raise ValueError(
                    f"bad --mesh entry {part!r}: expected data=N / model=N "
                    "with N >= 1")
            sizes[name] = int(val)
    return sizes["data"], sizes["model"]


def build_engine(cfg, mesh, args, shared_index=None, params=None):
    from repro.serving import InferenceEngine
    draft_cfg = (get_config(args.speculative_draft, smoke=args.smoke)
                 if args.speculative_draft else None)
    return InferenceEngine(
        cfg, mesh, max_batch=args.max_batch,
        block_size=args.block_size, max_len=args.max_len,
        num_blocks=args.num_blocks,
        max_num_batched_tokens=args.max_batched_tokens,
        enable_prefix_caching=not args.no_prefix_caching,
        draft_cfg=draft_cfg,
        num_speculative_tokens=args.num_speculative_tokens,
        prefill_pack=args.prefill_pack, kv_dtype=args.kv_dtype,
        swap_space_bytes=args.swap_space_bytes,
        swap_policy=args.swap_policy,
        shared_index=shared_index, params=params)


def build_fleet(cfg, mesh, args):
    """N identical engine replicas around one SharedPrefixIndex, plus the
    ReplicaRouter. Params are initialised once on replica 0 and shared by
    reference (replicas must be byte-identical for the routing to be
    output-invariant); the shared index is sized to hold one full replica
    pool's worth of published blocks."""
    from repro.serving import ReplicaRouter, SharedPrefixIndex
    dp = args.dp
    shared = SharedPrefixIndex(num_slots=args.shared_slots)
    first = build_engine(cfg, mesh, args, shared_index=shared)
    # speculative engines hold {"tgt","dft"} param dicts the ctor only
    # assembles from scratch — same seed re-init keeps replicas identical
    share = None if args.num_speculative_tokens else first.params
    engines = [first] + [
        build_engine(cfg, mesh, args, shared_index=shared, params=share)
        for _ in range(dp - 1)]
    return ReplicaRouter(engines, admission=build_controller(args, dp),
                         disaggregate=args.disaggregate,
                         n_prefill=args.n_prefill)


def build_controller(args, n_replicas: int = 1):
    from repro.serving.frontend import AdmissionController
    slo = args.ttft_slo_ms / 1e3 if args.ttft_slo_ms else None
    return AdmissionController(ttft_slo_p95_s=slo, max_queue=args.max_queue,
                               n_replicas=n_replicas)


def device_summary() -> dict:
    """The device the run is on, as JAX reports it."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes_in_use():
    """Device 0's peak allocation so far, or None where the backend
    reports no memory statistics (the CPU)."""
    import jax
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def check_completed(reqs, outs) -> None:
    """Raise unless every request produced exactly its own ``max_new``
    tokens (only valid when no EOS id or stop sequence can end it early)."""
    short = {r.rid: (len(outs.get(r.rid, ())), r.max_new) for r in reqs
             if len(outs.get(r.rid, ())) != r.max_new}
    if short:
        raise RuntimeError(
            f"requests did not complete with their own max_new tokens "
            f"(rid: (got, max_new)): {short}")


def make_requests(cfg, args, rng):
    from repro.serving import Request
    from repro.serving.scheduler import SamplingParams
    reqs = []
    for i in range(args.requests):
        # staggered horizons: each request retires on its own max_new
        max_new = max(1, args.max_new - (i % 4) * args.max_new // 4)
        stop = tuple(tuple(int(t) for t in s.split(","))
                     for s in (args.stop or []))
        sp = SamplingParams(temperature=args.temperature,
                            top_k=args.top_k, seed=i,
                            top_p=args.top_p, min_p=args.min_p,
                            repetition_penalty=args.repetition_penalty,
                            presence_penalty=args.presence_penalty,
                            frequency_penalty=args.frequency_penalty,
                            logprobs=args.logprobs, stop=stop)
        frames = None
        if cfg.frontend == "audio":
            frames = rng.normal(0, 1, (cfg.encoder_seq_len, cfg.d_model)
                                ).astype(np.float32)
        reqs.append(Request(
            rng.integers(0, cfg.vocab_size, args.prompt_len
                         ).astype(np.int32),
            max_new=max_new, sampling=sp, eos_id=args.eos_id,
            min_new=args.min_new, frames=frames))
    return reqs


async def _drive(eng, controller, reqs, arrivals):
    """Stream the Poisson workload through the front-end: the same
    admission path live HTTP traffic takes, with per-request token
    streams consumed concurrently. Returns {rid: [tokens]}."""
    from repro.serving.frontend import AsyncEngineDriver
    async with AsyncEngineDriver(eng, admission=controller) as drv:
        streams = [await drv.submit(r, arrival_step=t)
                   for r, t in zip(reqs, arrivals)]

        async def pull(s):
            return [ev.token async for ev in s]

        outs = await asyncio.gather(*(pull(s) for s in streams))
        await drv.drain()
    return {r.rid: np.asarray(t, np.int32) for r, t in zip(reqs, outs)}


def run_engine(cfg, mesh, args):
    eng = build_engine(cfg, mesh, args)
    print(f"[serve] engine built: arch={cfg.name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} peak_bytes_in_use={peak_bytes_in_use()}",
          flush=True)
    return serve_workload(eng, cfg, mesh, args)


def serve_workload(eng, cfg, mesh, args):
    """The synthetic Poisson workload through one engine and the
    front-end; prints the summary lines and returns {rid: tokens}. Raises
    if a request ends short of its own ``max_new`` tokens."""
    controller = build_controller(args)
    rng = np.random.default_rng(args.seed)
    reqs = make_requests(cfg, args, rng)
    arrivals = poisson_arrival_steps(len(reqs), args.rate, rng)
    t0 = time.time()
    tok0 = eng.stats["tokens"]
    outs = asyncio.run(_drive(eng, controller, reqs, arrivals))
    dt = time.time() - t0
    s = eng.stats
    s["wall_s"] = round(dt, 3)
    s["tok_s"] = round((s["tokens"] - tok0) / max(dt, 1e-9), 1)
    print(f"[serve] mesh=data={mesh.shape['data']},model="
          f"{mesh.shape['model']} tp={eng.tp} "
          f"prefill_pack={eng.prefill_pack}")
    print(f"[serve] kv_dtype={eng.kv_dtype} "
          f"kv_cache_mib={s['kv_cache_mib']} "
          f"swap_space_mib={s['swap_space_mib']} "
          f"swap_preemptions={s['swap_preemptions']} "
          f"swap_ins={s['swap_ins']} "
          f"swapped_out_blocks={s['swapped_out_blocks']} "
          f"swapped_in_blocks={s['swapped_in_blocks']} "
          f"aborts={s['aborts']}")
    print(f"[serve] runner={type(eng.runner).__name__} {len(reqs)} requests "
          f"(poisson rate={args.rate}/step, arrivals={arrivals}), "
          f"{s['tokens']} tokens in {s['wall_s']:.2f}s "
          f"({s['tok_s']:.1f} tok/s incl. compile)")
    print(f"[serve] steps={s['steps']} "
          f"prefill_chunks={s['prefill_chunks']} "
          f"encodes={s['encodes']} "
          f"preemptions={s['preemptions']} "
          f"cache_hit_tokens={s['cache_hit_tokens']} "
          f"cow_copies={s['cow_copies']} "
          f"peak_block_util={s['peak_block_utilization']:.2f}")
    print(f"[serve] sampling: full_sampling_steps={s['full_sampling_steps']} "
          f"stop_hits={s['stop_hits']}")
    print(f"[serve] frontend: submitted={controller.submitted} "
          f"shed={controller.shed} completed={controller.completed} "
          f"queue_peak={controller.queue_peak} "
          f"cache_hit_rate={eng.cache_hit_rate:.3f} "
          f"preemption_rate={eng.preemption_rate:.3f} "
          f"ttft_p95={eng.hist['ttft_steps'].percentile(95):.0f}steps")
    if s["spec_decodes"]:
        print(f"[serve] speculative: k={eng.runner.spec_tokens} "
              f"draft={eng.draft_cfg.name} "
              f"spec_decodes={s['spec_decodes']} "
              f"mean_accept_len={eng.mean_accept_len:.3f}")
    print("[serve] sample output ids:", outs[reqs[0].rid][:8].tolist())
    if args.eos_id is None and not args.stop:
        check_completed(reqs, outs)
    print(f"[serve] device={device_summary()} requests_completed="
          f"{s['requests_done']}/{len(reqs)}")
    return outs


def run_router(cfg, mesh, args):
    """The synthetic Poisson workload through a data-parallel fleet."""
    router = build_fleet(cfg, mesh, args)
    rng = np.random.default_rng(args.seed)
    reqs = make_requests(cfg, args, rng)
    arrivals = poisson_arrival_steps(len(reqs), args.rate, rng)
    t0 = time.time()
    outs = router.run(reqs, arrival_steps=arrivals)
    dt = time.time() - t0
    tokens = sum(router.replica_stats("tokens"))
    tok_s = tokens / max(dt, 1e-9)
    shared = router.shared_stats()
    print(f"[serve] mesh=data={mesh.shape['data']},model="
          f"{mesh.shape['model']} dp={router.dp} "
          f"disaggregate={router.disaggregate}")
    roles = (f" roles=prefill{router._prefill_ids}/decode"
             f"{router._decode_ids}" if router.disaggregate else "")
    print(f"[serve] router: dp={router.dp} routed={router.routed} "
          f"handoffs={router.handoffs} "
          f"shared_hit_blocks={sum(router.replica_stats('shared_hit_blocks'))} "
          f"shared_published_blocks={shared['published_blocks']} "
          f"shared_evicted_blocks={shared['evicted_blocks']}" + roles)
    print(f"[serve] fleet: {len(reqs)} requests "
          f"(poisson rate={args.rate}/step), {tokens} tokens in {dt:.2f}s "
          f"({tok_s:.1f} tok/s incl. compile) "
          f"steps={router.replica_stats('steps')} "
          f"preemptions={router.replica_stats('preemptions')} "
          f"cache_hit_tokens={router.replica_stats('cache_hit_tokens')}")
    ctl = router.admission
    print(f"[serve] frontend: submitted={ctl.submitted} shed={ctl.shed} "
          f"completed={ctl.completed} queue_peak={ctl.queue_peak}")
    print("[serve] sample output ids:", outs[reqs[0].rid][:8].tolist())
    return outs


async def _serve_http(eng, controller, host, port):
    from repro.serving.frontend import AsyncEngineDriver, FrontendServer
    drv = AsyncEngineDriver(eng, admission=controller)
    await drv.start()
    srv = FrontendServer(drv, host=host, port=port)
    await srv.start()
    slo = controller.ttft_slo_p95_s
    print(f"[serve] http listening on {host}:{srv.port} "
          f"(POST /generate, GET /health, GET /metrics; "
          f"ttft_slo_p95={slo if slo is not None else 'off'} "
          f"max_queue={controller.max_queue})", flush=True)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    print("[serve] draining: no new admissions, finishing "
          f"{len(eng.sched.running) + drv.queue_depth} in-flight "
          "request(s)", flush=True)
    await drv.drain()
    await srv.aclose()
    s = eng.stats
    print(f"[serve] drained cleanly: requests_done={s['requests_done']} "
          f"tokens={s['tokens']} shed={controller.shed} "
          f"steps={s['steps']}", flush=True)


async def _serve_http_router(router, host, port):
    from repro.serving.frontend import FrontendServer
    await router.start()
    srv = FrontendServer(router, host=host, port=port)
    await srv.start()
    ctl = router.admission
    slo = ctl.ttft_slo_p95_s
    print(f"[serve] http listening on {host}:{srv.port} "
          f"dp={router.dp} disaggregate={router.disaggregate} "
          f"(POST /generate, GET /health, GET /metrics; "
          f"ttft_slo_p95={slo if slo is not None else 'off'} "
          f"max_queue={ctl.max_queue})", flush=True)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    running = sum(len(e.sched.running) for e in router.engines)
    print("[serve] draining fleet: no new admissions, finishing "
          f"{running + router.queue_depth} in-flight request(s)",
          flush=True)
    await router.aclose()
    await srv.aclose()
    print(f"[serve] router: dp={router.dp} routed={router.routed} "
          f"handoffs={router.handoffs} "
          f"shared_hit_blocks={sum(router.replica_stats('shared_hit_blocks'))} "
          f"requests_done={sum(router.replica_stats('requests_done'))} "
          f"tokens={sum(router.replica_stats('tokens'))} "
          f"shed={ctl.shed}", flush=True)


def run_http(cfg, mesh, args):
    host, _, port = args.http.rpartition(":")
    if args.dp > 1 or args.disaggregate:
        router = build_fleet(cfg, mesh, args)
        asyncio.run(_serve_http_router(router, host or "127.0.0.1",
                                       int(port)))
        return
    eng = build_engine(cfg, mesh, args)
    asyncio.run(_serve_http(eng, build_controller(args),
                            host or "127.0.0.1", int(port)))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4_9b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-size config (default; --no-smoke for full)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size in blocks (default: sized for "
                         "max_batch x max_len); set low to exercise "
                         "preemption / swap under memory pressure")
    ap.add_argument("--max-batched-tokens", type=int, default=None,
                    help="per-step token budget across decodes + one "
                    "prefill chunk (default: max_batch + 2*block_size)")
    ap.add_argument("--no-prefix-caching", action="store_true",
                    help="disable cross-request KV block sharing")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=("bf16", "int8", "fp8"),
                    help="KV page-pool storage dtype; int8/fp8 store "
                    "per-row fp32 scales alongside and the kernels "
                    "dequantize fused into attention (docs/kv-cache.md)")
    ap.add_argument("--swap-space-bytes", type=int, default=0,
                    help="pinned host memory for swap-preemption, bytes "
                    "(0 = recompute-only preemption). Preemption victims "
                    "move KV to the host tier and back instead of "
                    "recomputing when the cost model prefers it")
    ap.add_argument("--swap-policy", default="auto",
                    choices=("auto", "always", "never"),
                    help="swap-vs-recompute choice per preemption victim: "
                    "auto = measured-bandwidth cost model, always/never "
                    "force one side (bench + tests)")
    ap.add_argument("--prefill-pack", type=int, default=1,
                    help="max prefill chunks packed into one step's flat "
                    "ragged token batch (1 = classic single-chunk; >1 "
                    "needs a packed-prefill-capable runner)")
    ap.add_argument("--speculative-draft", default=None,
                    help="draft-model arch for speculative decoding "
                    "(defaults to --arch, i.e. a fresh-init self-draft, "
                    "when --num-speculative-tokens > 0)")
    ap.add_argument("--num-speculative-tokens", type=int, default=0,
                    help="draft tokens proposed per slot per step; the "
                    "target verifies k+1 positions in one widened step "
                    "(0 disables speculation)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel engine replicas behind one "
                    "ReplicaRouter admission queue (threads in-process, "
                    "deterministic least-outstanding-tokens routing, "
                    "cross-replica prefix sharing; docs/multi-host.md)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="prefill/decode disaggregation: the first "
                    "--n-prefill replicas prefill (1-token probe), the "
                    "rest decode; KV hands off as hashed blocks through "
                    "the shared prefix index (implies --dp >= 2)")
    ap.add_argument("--n-prefill", type=int, default=1,
                    help="prefill-role replicas under --disaggregate")
    ap.add_argument("--shared-slots", type=int, default=512,
                    help="host-pool slots in the cross-replica "
                    "SharedPrefixIndex (blocks; LRU-evicted)")
    ap.add_argument("--mesh", default=None,
                    help='mesh axis sizes, e.g. "model=2" or '
                    '"data=2,model=2" (default: 1x1). The "model" axis '
                    "tensor-parallel-shards the page pools by kv head; "
                    "needs that many local devices")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="poisson arrivals per decode step")
    ap.add_argument("--http", default=None, metavar="HOST:PORT",
                    help="serve forever over HTTP instead of the synthetic "
                    "Poisson workload: POST /generate (SSE streaming), "
                    "GET /health, GET /metrics; SIGINT/SIGTERM drains "
                    "gracefully (docs/serving-frontend.md)")
    ap.add_argument("--ttft-slo-ms", type=float, default=None,
                    help="TTFT p95 target in ms; admission sheds (429 + "
                    "Retry-After) when the projection would exceed it "
                    "(default: no SLO, queue bound only)")
    ap.add_argument("--max-queue", type=int, default=128,
                    help="front-end waiting-queue bound; requests past it "
                    "are shed regardless of the SLO projection")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off); composes "
                    "with --top-k / --min-p (docs/sampling.md)")
    ap.add_argument("--min-p", type=float, default=0.0,
                    help="min-p truncation relative to the max "
                    "probability (0 = off)")
    ap.add_argument("--repetition-penalty", type=float, default=1.0,
                    help="divide positive / multiply negative logits of "
                    "already-seen tokens (1.0 = off)")
    ap.add_argument("--presence-penalty", type=float, default=0.0,
                    help="subtract once per distinct generated token")
    ap.add_argument("--frequency-penalty", type=float, default=0.0,
                    help="subtract per occurrence of a generated token")
    ap.add_argument("--logprobs", type=int, default=0,
                    help="per-token top-N logprobs in the stream (0 = off)")
    ap.add_argument("--stop", action="append", default=None,
                    metavar="IDS",
                    help="stop sequence as comma-separated token ids; "
                    "repeatable (each flag adds one sequence)")
    ap.add_argument("--min-new", type=int, default=0,
                    help="ignore EOS / stop sequences before this many "
                    "generated tokens (max_new still wins)")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main():
    ap = build_parser()
    args = ap.parse_args()
    configure_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    from repro.launch.mesh import make_host_mesh
    data, model = parse_mesh(args.mesh)
    mesh = make_host_mesh(data, model)
    if args.disaggregate and args.dp < 2:
        ap.error("--disaggregate needs --dp >= 2 (prefill + decode roles)")
    if args.http:
        run_http(cfg, mesh, args)
    elif args.dp > 1:
        run_router(cfg, mesh, args)
    else:
        run_engine(cfg, mesh, args)


if __name__ == "__main__":
    main()
