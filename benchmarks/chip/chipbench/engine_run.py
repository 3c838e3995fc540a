"""One run of one cell: build, warm up, measure, check.

The system under test is the program's serving path: an engine built by
``launch.serve.build_engine`` (with the benchmark's weights), driven by
``AsyncEngineDriver`` — ``submit`` → ``InferenceEngine.step``. The client
side lives here: requests go in at their wall-clock due times (open loop)
or as a backlog queued before the window, and every ``TokenEvent`` is
stamped as it reaches the asyncio loop.

The harness wraps three of the program's calls from outside, on the
engine instance: ``InferenceEngine.step``, ``Scheduler.schedule`` (whose
``StepPlan`` gives each step's decode rows and chunk) and
``_build_arrays``. Each wrapped call records its host times; in a traced
run it is also a ``jax.profiler.TraceAnnotation``, so host spans and
device operations share the trace's clock.
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import math
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from chipbench import counts, trace, traffic, weights
from chipbench.spec import Cell, load_reference


@dataclass
class StepRec:
    idx: int
    t0: float
    t1: float
    decode_ctxs: tuple
    chunk: tuple | None            # (start, n)
    chunk_sampled: bool
    full: bool
    hit_tokens: int                # engine counters after the step
    prefill_tokens: int
    waiting: int                   # requests queued in the scheduler


@dataclass
class ReqRec:
    item: traffic.Item
    due: float = 0.0
    sent: float = math.nan
    times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    error: str | None = None
    rid: int = -1

    @property
    def finished(self) -> bool:
        return self.error is None and len(self.tokens) == self.item.max_new


class Probe:
    """Host-side spans and per-step records around the engine's calls."""

    def __init__(self, eng, traced: bool):
        import jax
        self.eng, self.traced = eng, traced
        self.steps: list[StepRec] = []
        self.warm_steps = 0            # backlog: the window opens after
        self.warm_rows = 0             # this many steps, at the first step
        self.window_open = threading.Event()
        self.t_open = math.nan
        self._plan = None
        self._calls = 0
        self._ann = jax.profiler.TraceAnnotation
        sched = eng.sched
        orig_schedule, orig_step = sched.schedule, eng.step
        orig_build = eng._build_arrays

        def schedule():
            with self._span("bench.schedule"):
                plan = orig_schedule()
            chunk = None
            sampled = False
            if plan.chunks:
                _, req, n = plan.chunks[0]
                chunk = (req.num_computed, n)
                sampled = req.num_computed + n == req.context_len
            self._plan = (tuple(r.context_len for _, r in plan.decodes),
                          chunk, sampled, plan.scheduled_tokens)
            return plan

        def build(plan, full=False):
            with self._span("bench.build_arrays"):
                return orig_build(plan, full)

        def step():
            k = self._calls
            self._calls += 1
            full0 = eng.stats["full_sampling_steps"]
            t0 = time.perf_counter()
            with self._span("bench.step", step=k):
                ran = orig_step()
            t1 = time.perf_counter()
            plan, self._plan = self._plan, None
            if plan is not None and plan[3] > 0:
                s = eng.stats
                self.steps.append(StepRec(
                    k, t0, t1, plan[0], plan[1], plan[2],
                    s["full_sampling_steps"] > full0,
                    s["cache_hit_tokens"], s["prefill_tokens"],
                    len(sched.waiting)))
                if (self.warm_rows and len(plan[0]) >= self.warm_rows
                        and len(self.steps) >= self.warm_steps
                        and not self.window_open.is_set()):
                    self.t_open = t1
                    self.window_open.set()
            return ran

        sched.schedule = schedule
        eng._build_arrays = build
        eng.step = step

    def _span(self, name, **kw):
        if self.traced:
            return self._ann(name, **kw)
        return contextlib.nullcontext()


class CompileCounter:
    """Counts JAX traces and backend compiles, with their times."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.marks: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.marks.append((event, time.perf_counter()))

    def between(self, a: float, b: float) -> int:
        return sum(1 for _, t in self.marks if a <= t <= b)


# -- building -----------------------------------------------------------------


def _plain(v):
    """A config value as JSON has it: tuples as lists."""
    return [_plain(x) for x in v] if isinstance(v, (tuple, list)) else v


# the program's layer kinds in the configuration file's words (HF's)
LAYER_TYPES = {"attn": "full", "local": "sliding"}


def _attr(cfg, path: str):
    """``cfg``'s attribute at a dotted path; None below a None group."""
    for part in path.split("."):
        if cfg is None:
            return None
        if not hasattr(cfg, part):
            raise ValueError(f"the program's config has no {path!r}")
        cfg = getattr(cfg, part)
    return cfg


def program_config(cell: Cell):
    """The program's ModelConfig for the cell's configuration file, checked
    against the file's sizes: widths, layer kinds and window, experts.
    Sizes that state no ``layer_types``, ``window`` or ``moe`` ask for a
    dense program with full attention in every layer. ``program.expect``
    adds ModelConfig attribute paths (``"attn_logit_softcap"``,
    ``"moe.capacity_factor"``) and the values they must hold, for fields
    that the sizes do not state."""
    import dataclasses
    from repro.config import get_config
    prog = cell.config["program"]
    cfg = get_config(prog["arch"], smoke=prog.get("smoke", False))
    cfg = dataclasses.replace(cfg, **{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in prog.get("overrides", {}).items()})
    sz = cell.config["sizes"]
    want = {"num_layers": sz["layers"], "d_model": sz["d_model"],
            "num_heads": sz["heads"], "num_kv_heads": sz["kv_heads"],
            "head_dim": sz["head_dim"], "d_ff": sz["d_ff"],
            "vocab_size": sz["vocab"], "tie_embeddings": sz["tied"],
            "norm": sz["norm"], "rope_theta": sz["rope_theta"],
            "mlp_activation": {"gelu": "gelu_mlp",
                               "swiglu": "silu"}[sz["mlp"]],
            "sliding_window": sz.get("window")}
    moe = sz.get("moe")
    if moe is None:
        want["moe"] = None
    else:
        want.update({"moe.num_experts": moe["experts"],
                     "moe.experts_per_token": moe["experts_per_token"],
                     "moe.d_ff_expert": moe["d_ff_expert"]})
    expect = prog.get("expect", {})
    twice = [p for p in expect if p in want or p == "block_pattern"]
    if twice:
        raise ValueError(f"{cell.config_name}'s program.expect names "
                         f"{twice}, which its sizes state")
    want.update(expect)
    bad = {k: (got, v) for k, v in want.items()
           if _plain(got := _attr(cfg, k)) != v}
    kinds = [LAYER_TYPES.get(k, k) for k in cfg.layer_kinds()]
    if kinds != sz.get("layer_types", ["full"] * sz["layers"]):
        bad["layer_types"] = (kinds, sz.get("layer_types"))
    if bad:
        raise ValueError(f"program config differs from {cell.config_name}:"
                         f" {bad}")
    return cfg


def build(cell: Cell, cfg, seed: int):
    """(engine, weights): the benchmark's weights, then the engine."""
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import build_engine, build_parser
    from repro.models import api
    shapes, _ = api.abstract_params(cfg)
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape,
                                                         s.dtype), shapes)
    params = weights.make_params(shapes, seed)
    e = cell.engine
    flags = ["--max-batch", str(e["max_batch"]), "--max-len",
             str(e["max_len"]), "--block-size", str(e["block_size"]),
             "--num-blocks", str(e["num_blocks"]), "--max-batched-tokens",
             str(e["max_batch"] + e["chunk"]), "--prefill-pack",
             str(e["prefill_pack"]), "--kv-dtype", e["kv_dtype"]]
    args = build_parser().parse_args(flags)
    eng = build_engine(cfg, make_host_mesh(1, 1), args, params=params)
    jax.block_until_ready(eng.cache)
    return eng, params


def _request(item: traffic.Item):
    from repro.serving import Request
    from repro.serving.scheduler import SamplingParams
    sp = SamplingParams(temperature=item.temperature, top_p=item.top_p,
                        seed=item.seed)
    return Request(np.asarray(item.prompt, np.int32), max_new=item.max_new,
                   sampling=sp)


# -- the client ---------------------------------------------------------------


async def _consume(stream, rec: ReqRec):
    try:
        async for ev in stream:
            rec.times.append(time.perf_counter())
            rec.tokens.append(ev.token)
    except Exception as e:                    # noqa: BLE001 — counted failed
        rec.error = repr(e)


async def _submit(drv, rec: ReqRec, tasks: list, traced: bool):
    import jax
    req = _request(rec.item)
    rec.rid = req.rid
    rec.sent = time.perf_counter()
    try:
        if traced:
            with jax.profiler.TraceAnnotation("bench.submit"):
                stream = await drv.submit(req)
        else:
            stream = await drv.submit(req)
    except Exception as e:                    # noqa: BLE001
        rec.error = repr(e)
        return
    tasks.append(asyncio.ensure_future(_consume(stream, rec)))


async def _drive(eng, probe: Probe, sch: traffic.Schedule, seconds: float,
                 trace_plan: dict | None, log) -> dict:
    from repro.serving.frontend import AdmissionController, AsyncEngineDriver
    ctl = AdmissionController(max_queue=10 ** 9)
    drv = AsyncEngineDriver(eng, admission=ctl)
    await drv.start()
    tasks: list = []
    traced = trace_plan is not None
    # warm-up: every item of the warm list, to completion
    warm = [ReqRec(it) for it in sch.warmup]
    for group in (_warm_groups(warm)):
        for r in group:
            await _submit(drv, r, tasks, traced)
        await asyncio.gather(*tasks)
        tasks.clear()
    bad = [r for r in warm if not r.finished]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad[0].error}")
    log(f"set-up: {len(warm)} warm-up requests done in {len(probe.steps)} "
        "steps")
    recs = [ReqRec(it) for it in sch.timed]
    t_phase = time.perf_counter()
    if sch.open_loop:
        w0 = t_phase + sch.ramp_s
        w1 = w0 + seconds
        for r in recs:
            r.due = t_phase + r.item.due_s
    else:
        # every slot decoding, and at least warm_steps steps more
        probe.warm_steps = len(probe.steps) + sch.warm_steps
        probe.warm_rows = eng.max_batch
        for r in recs:
            r.due = t_phase
    trace_task = None
    if traced:
        trace_task = asyncio.ensure_future(_trace(probe, sch, seconds,
                                                  trace_plan, t_phase))
    if sch.open_loop:
        for r in recs:
            if r.due >= w1:
                break
            delay = r.due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            await _submit(drv, r, tasks, traced)
        await asyncio.sleep(max(0.0, w1 - time.perf_counter()))
    else:
        for r in recs:
            await _submit(drv, r, tasks, traced)
        while not probe.window_open.is_set():
            await asyncio.sleep(0.005)
        w0 = probe.t_open
        w1 = w0 + seconds
        await asyncio.sleep(max(0.0, w1 - time.perf_counter()))
    t_close = time.perf_counter()
    if trace_task is not None:
        await trace_task
    for r in recs:
        if not r.finished and r.error is None and not math.isnan(r.sent):
            drv.abort(r.rid)
    await asyncio.gather(*tasks)
    await drv.aclose()
    log(f"window closed {t_close - w1:.6f}s after its end; "
        f"{sum(r.finished for r in recs)} requests finished")
    return {"recs": recs, "w0": w0, "w1": w1, "shed": ctl.shed}


def _warm_groups(warm: list[ReqRec]) -> list[list[ReqRec]]:
    """Greedy warm items together, then each sampled one alone, so every
    step executable the window can use has run once."""
    greedy = [r for r in warm if r.item.greedy]
    sampled = [[r] for r in warm if not r.item.greedy]
    return ([greedy] if greedy else []) + sampled


async def _trace(probe, sch, seconds, plan, t_phase):
    """Trace ``plan['slice_s']`` seconds from the middle of the window."""
    import jax
    if sch.open_loop:
        mid = t_phase + sch.ramp_s + seconds / 2
    else:
        while not probe.window_open.is_set():
            await asyncio.sleep(0.005)
        mid = probe.t_open + seconds / 2
    await asyncio.sleep(max(0.0, mid - plan["slice_s"] / 2
                            - time.perf_counter()))
    jax.profiler.start_trace(plan["dir"])
    await asyncio.sleep(plan["slice_s"])
    jax.profiler.stop_trace()


# -- the run --------------------------------------------------------------------


@dataclass
class RunData:
    """What a metric reader sees."""
    model: counts.Model
    peaks: dict
    seconds: float
    setup_s: float
    w0: float
    w1: float
    steps: list
    recs: list
    open_loop: bool
    trace: trace.Trace | None = None
    trace_lo: int = 0
    trace_hi: int = 0

    @property
    def window_steps(self) -> list:
        return [s for s in self.steps if self.w0 < s.t1 <= self.w1]

    def traced_steps(self):
        """(step record, [device ops]) for every whole step span in the
        traced slice."""
        by_idx = {s.idx: s for s in self.steps}
        spans = [s for s in self.trace.spans if s.name == "bench.step"
                 and s.stats.get("step") in by_idx]
        ops = self.trace.device_ops[0] if self.trace.device_ops else []
        out, j = [], 0
        for sp in spans:
            mine = []
            while j < len(ops) and ops[j].start < sp.start:
                j += 1
            k = j
            while k < len(ops) and ops[k].start < sp.end:
                mine.append(ops[k])
                k += 1
            out.append((by_idx[sp.stats["step"]], mine))
        return out


def _log_window(d: RunData, log) -> None:
    """Medians and queue depths for PERF.md, on earlier lines."""
    from chipbench import reduce
    ws = d.window_steps
    if not ws:
        log("no engine step ended inside the window")
        return
    tt, it = reduce.ttfts(d), reduce.itls(d)
    log(f"window {d.w1 - d.w0:.3f}s: {len(ws)} steps "
        f"({sum(s.chunk is not None for s in ws)} with a chunk), "
        f"{reduce.tokens_in_window(d)} tokens, ttft n={len(tt)} "
        f"p50={reduce.pct(tt, 50)} p95={reduce.pct(tt, 95)}, itl n={len(it)}"
        f" p50={reduce.pct(it, 50)} p95={reduce.pct(it, 95)}")
    log(f"scheduler queue: {ws[0].waiting} at the window's start, "
        f"{ws[-1].waiting} at its end, max {max(s.waiting for s in ws)}")


def check_outputs(cell: Cell, params, recs: list, seed: int,
                  control: bool, log) -> dict:
    """Compare a sample of the finished greedy requests with the
    configuration's plain reference: the widest gap by which a served
    token's logit lies below the reference's best."""
    chk = cell.check
    reference = load_reference(cell)
    done = [r for r in recs if r.finished and r.item.greedy]
    if not done:
        return {"requests": 0, "tokens": 0, "logit_gap": None}
    rng = np.random.default_rng(seed + 7919)
    longest = max(done, key=lambda r: len(r.item.prompt) + len(r.tokens))
    order = [longest] + [done[i] for i in rng.permutation(len(done))
                         if done[i] is not longest]
    picked, n_tok = [], 0
    for r in order:
        if len(picked) >= chk["max_requests"] or n_tok >= chk["tokens"]:
            break
        picked.append(r)
        n_tok += len(r.tokens)
    sz = cell.config["sizes"]
    # a few shapes per cell (one per length bucket), compiled once into
    # the cache
    p_len = -(-cell.traffic["output"]["max"] // 128) * 128
    worst, worst_ctl, t0 = 0.0, 0.0, time.perf_counter()
    for r in picked:
        seq = np.concatenate([r.item.prompt, np.asarray(r.tokens, np.int32)])
        g, c = reference.gaps(params, seq, len(r.item.prompt), sz, control,
                              p_len=p_len)
        worst = max(worst, float(g.max()))
        if control:
            worst_ctl = max(worst_ctl, float(c.max()))
    log(f"reference over {len(picked)} requests, {n_tok} served tokens "
        f"(longest context {len(longest.item.prompt) + len(longest.tokens)})"
        f" in {time.perf_counter() - t0:.1f}s")
    out = {"requests": len(picked), "tokens": n_tok, "logit_gap": worst}
    if control:
        out["control_gap"] = worst_ctl
    return out


def run(cell: Cell, seed: int, seconds: float, traced: bool, t_start: float,
        peaks: dict, log, control: bool = False) -> dict:
    """Build, warm up, measure and check one cell. Returns the pieces of
    the result line; the caller picks the metrics."""
    import jax
    counter = CompileCounter()
    cfg = program_config(cell)
    model = counts.Model.from_config(cell.config)
    sch = traffic.schedule(cell.traffic, cell.load, cfg.vocab_size, seed,
                           seconds)
    log(f"set-up: backend up at {time.perf_counter() - t_start:.1f}s")
    eng, params = build(cell, cfg, seed)
    log(f"set-up: weights and engine built at "
        f"{time.perf_counter() - t_start:.1f}s")
    probe = Probe(eng, traced)
    plan = None
    tmp = None
    if traced:
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
        plan = {"dir": tmp, "slice_s": min(cell.load.get("trace_s", 3.0),
                                           seconds / 2)}
    out = asyncio.run(_drive(eng, probe, sch, seconds, plan, log))
    w0, w1, recs = out["w0"], out["w1"], out["recs"]
    setup_s = w0 - t_start
    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    n_compiles = counter.between(w0, w1)
    late = [r.sent - r.due for r in recs if not math.isnan(r.sent)
            and w0 <= r.due < w1]
    log(f"compilations inside the window: {n_compiles}")
    if late:
        log(f"generator lateness p99 (sent - due): "
            f"{float(np.percentile(late, 99)) * 1e3:.3f} ms over "
            f"{len(late)} requests")
    log(f"engine: steps={eng.stats['steps']} "
        f"prefill_chunks={eng.stats['prefill_chunks']} "
        f"preemptions={eng.stats['preemptions']} "
        f"cache_hit_tokens={eng.stats['cache_hit_tokens']} "
        f"full_sampling_steps={eng.stats['full_sampling_steps']} "
        f"peak_blocks_in_use={eng.stats['peak_blocks_in_use']}/"
        f"{cell.engine['num_blocks']} shed={out['shed']}")
    data = RunData(model, peaks, seconds, setup_s, w0, w1, probe.steps,
                   recs, sch.open_loop)
    _log_window(data, log)
    if traced:
        data.trace = trace.load(trace.find_xplane(tmp))
        data.trace_lo, data.trace_hi = trace.window(data.trace)
        shutil.rmtree(tmp, ignore_errors=True)
    # free the engine's state before the reference runs
    eng.cache = None
    eng.params = None
    del eng, probe
    gc.collect()
    chk = check_outputs(cell, params, recs, seed, control, log)
    return {"data": data, "check": chk, "mem_peak": mem_peak,
            "compiles": n_compiles}
