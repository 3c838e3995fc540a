"""What the serving path reports about itself: the ``serve.*`` profiler
spans of ``InferenceEngine.step``, the ``on_step`` hook's ``StepRecord``,
the scheduler's queue wait, ``TokenEvent.emitted`` and the names of the
step executables."""

import asyncio
import glob
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.config import get_config
from repro.serving import InferenceEngine, Request
from repro.serving.frontend import AsyncEngineDriver
from repro.serving.scheduler import StepPlan

RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture(scope="module")
def glm(mesh):
    from repro.models import api
    cfg = get_config("glm4_9b", smoke=True)
    with jax.set_mesh(mesh):
        params, _ = api.init_model(cfg, jax.random.key(0))
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    return cfg, params


def _engine(glm, mesh, **kw):
    cfg, params = glm
    kw.setdefault("max_batch", 2)
    # a 16-token chunk beside two decode rows: prompts span several steps
    kw.setdefault("max_num_batched_tokens", 18)
    return InferenceEngine(cfg, mesh, params=params, block_size=16,
                           max_len=96, debug_invariants=True, **kw)


def _requests(cfg, lens=(40, 20, 30), max_new=4):
    return [Request(RNG.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new=max_new) for n in lens]


def _serve_spans(log_dir):
    """Every ``serve.*`` host event of the trace under ``log_dir``, with
    its thread (line) name."""
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(line.name, e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events if e.name.startswith("serve.")]
    return out


def _inside(inner, outers):
    return any(o[0] == inner[0] and o[2] <= inner[2] and inner[3] <= o[3]
               for o in outers)


def test_step_spans_nest_inside_serve_step(glm, mesh, tmp_path):
    cfg, _ = glm
    eng = _engine(glm, mesh)
    recs = []
    eng.on_step = recs.append
    for r in _requests(cfg):
        eng.sched.add(r)
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.sched.has_work:
            eng.step()
    finally:
        jax.profiler.stop_trace()
    spans = _serve_spans(str(tmp_path))
    by = {}
    for s in spans:
        by.setdefault(s[1], []).append(s)
    steps = by["serve.step"]
    assert len(steps) == eng._step_calls
    for name in ("serve.schedule", "serve.admit", "serve.copies",
                 "serve.build", "serve.h2d", "serve.dispatch", "serve.sync",
                 "serve.emit"):
        assert by.get(name), name
        assert all(_inside(s, steps) for s in by[name]), name
    assert all(_inside(s, by["serve.schedule"]) for s in by["serve.admit"])
    assert all(_inside(s, by["serve.build"]) for s in by["serve.h2d"])
    assert {s[4]["prompt_tokens"] for s in by["serve.admit"]} == \
        {40, 20, 30}
    # each record's index is its span's `step` stat, whose plan stats agree
    stats = {s[4]["step"]: s[4] for s in steps}
    assert [r.step for r in recs] == sorted(r.step for r in recs)
    for r in recs:
        st = stats[r.step]
        assert st["rows"] == len(r.decode_ctxs)
        assert st["decode_pages"] == sum(-(-c // eng.block_size)
                                         for c in r.decode_ctxs)
        assert st["chunk_tokens"] == sum(n for _, n in r.chunks)
        assert bool(st["full"]) == r.full


def test_step_records_agree_with_the_plans(glm, mesh):
    cfg, _ = glm
    eng = _engine(glm, mesh)
    plans = []
    schedule = eng.sched.schedule

    def spy():
        p = schedule()
        plans.append((tuple(r.context_len for _, r in p.decodes),
                      tuple((r.num_computed, n) for _, r, n in p.chunks),
                      p.scheduled_tokens))
        return p

    eng.sched.schedule = spy
    recs = []
    eng.on_step = recs.append
    reqs = _requests(cfg)
    eng.run(reqs)
    worked = [p for p in plans if p[2] > 0]
    assert len(recs) == len(worked) == eng.stats["steps"]
    for rec, (ctxs, chunks, _) in zip(recs, worked):
        assert rec.decode_ctxs == ctxs
        assert sum(rec.decode_ctxs) == sum(ctxs)
        assert rec.chunks == chunks
        assert rec.chunk == (chunks[0] if chunks else None)
        assert rec.t0 <= rec.t1
    # the 40-token prompt streams in over three chunks of 16
    assert [r.chunk for r in recs if r.chunk and r.chunk[0] > 0][:2] == \
        [(16, 16), (32, 8)]
    assert sum(r.sampled for r in recs) == len(reqs)
    last = recs[-1]
    assert last.prefill_tokens == eng.stats["prefill_tokens"]
    assert last.cache_hit_tokens == eng.stats["cache_hit_tokens"]
    assert last.first_admits == len(reqs) and last.waiting == 0
    assert [r.step for r in recs] == sorted({r.step for r in recs})


def test_queue_wait_counts_a_preempted_request_once(glm, mesh):
    cfg, _ = glm
    # 7 allocatable blocks of 16: two 33-token contexts take 3 blocks
    # each, and growth past 48 tokens preempts the newer request
    eng = _engine(glm, mesh, num_blocks=8, max_num_batched_tokens=None)
    reqs = [Request(RNG.integers(0, cfg.vocab_size, 32).astype(np.int32),
                    max_new=20) for _ in range(2)]
    t0 = time.perf_counter()
    eng.run(reqs)
    elapsed = time.perf_counter() - t0
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["first_admits"] == 2
    assert 0.0 <= eng.stats["queue_wait_s"] <= 2 * elapsed
    hist = eng.hist["queue_wait_seconds"]
    assert hist.count == 2 and hist.total == eng.stats["queue_wait_s"]
    # a request aborted while waiting never reaches the histogram
    r = _requests(cfg, lens=(8,))[0]
    eng.sched.add(r)
    assert eng.abort(r.rid)
    assert not eng.sched._queued_at and hist.count == 2


def test_queue_wait_leaves_out_a_decode_continuation(glm, mesh):
    """A request that arrives holding output tokens (the decode half of a
    disaggregated request) was counted where its prompt was first
    queued; its own replica does not count it again."""
    cfg, _ = glm
    eng = _engine(glm, mesh)
    fresh, cont = _requests(cfg, lens=(20, 24))
    cont.out = [int(cont.prompt[-1])]
    eng.run([fresh, cont])
    assert len(fresh.out) == len(cont.out) == 4
    assert eng.stats["first_admits"] == 1
    assert eng.hist["queue_wait_seconds"].count == 1
    assert not eng.sched._queued_at


def test_step_executables_are_named(glm, mesh):
    eng = _engine(glm, mesh)
    lowered = dict(eng.lower_steps())
    arrays = eng._build_arrays(StepPlan([], [], []), True)
    with jax.set_mesh(eng.mesh):
        for chunk, kind in ((True, "chunk_full"), (False, "decode_full")):
            lowered[kind] = eng._full_step(chunk).lower(eng.params,
                                                        eng.cache, arrays)
    names = {k: v.as_text().split("\n", 1)[0] for k, v in lowered.items()}
    want = {"chunk": "serve_step_chunk", "plain": "serve_step_decode",
            "chunk_full": "serve_step_chunk_full",
            "decode_full": "serve_step_decode_full"}
    for k, name in want.items():
        assert f"module @jit_{name} " in names[k], names[k]


def test_token_events_carry_their_handoff_time(glm, mesh, tmp_path):
    cfg, _ = glm
    eng = _engine(glm, mesh)
    drv = AsyncEngineDriver(eng)
    got = []

    async def go():
        await drv.start()
        streams = [await drv.submit(r) for r in _requests(cfg)]

        async def pull(s):
            async for ev in s:
                got.append((ev, time.perf_counter()))

        await asyncio.gather(*(pull(s) for s in streams))
        await drv.aclose()

    jax.profiler.start_trace(str(tmp_path))
    try:
        asyncio.run(go())
    finally:
        jax.profiler.stop_trace()
    assert len(got) == 3 * 4
    for ev, received in got:
        assert math.isfinite(ev.emitted) and ev.emitted <= received
    # the loop's work between steps is its own span, outside serve.step
    spans = _serve_spans(str(tmp_path))
    loops = [s for s in spans if s[1] == "serve.loop"]
    steps = [s for s in spans if s[1] == "serve.step"]
    assert loops and steps
    assert not any(a[0] == b[0] and a[2] < b[3] and b[2] < a[3]
                   for a in loops for b in steps)


def test_expert_counts_on_serve_step_and_step_records(glm, mesh, tmp_path):
    """An expert model's steps report rows computed on the held experts
    and routed assignments of their own tokens: on ``serve.step``, in the
    ``StepRecord`` and summed in the stats. With every expert held the
    two agree; a dense model reports neither."""
    import dataclasses
    from repro.models import api
    cfg = get_config("mellum2_12b_a2p5b", smoke=True)
    half = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, experts_held=4))
    k = cfg.moe.experts_per_token
    seen = {}
    for name, c in (("all", cfg), ("half", half)):
        with jax.set_mesh(mesh):
            params, _ = api.init_model(c, jax.random.key(0))
            params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
        eng = _engine((c, params), mesh)
        recs = []
        eng.on_step = recs.append
        for r in _requests(c):
            eng.sched.add(r)
        jax.profiler.start_trace(str(tmp_path / name))
        try:
            while eng.sched.has_work:
                eng.step()
        finally:
            jax.profiler.stop_trace()
        steps = {s[4]["step"]: s[4] for s in _serve_spans(
            str(tmp_path / name)) if s[1] == "serve.step"}
        for r in recs:
            tokens = len(r.decode_ctxs) + sum(n for _, n in r.chunks)
            assert r.moe_assign == tokens * k * c.num_layers
            assert 0 <= r.moe_rows <= r.moe_assign
            assert (steps[r.step]["moe_rows"], steps[r.step]["moe_assign"]) \
                == (r.moe_rows, r.moe_assign)
        assert eng.stats["moe_rows"] == sum(r.moe_rows for r in recs)
        assert eng.stats["moe_assignments"] == sum(r.moe_assign
                                                   for r in recs)
        seen[name] = eng.stats
    assert seen["all"]["moe_rows"] == seen["all"]["moe_assignments"]
    assert 0 < seen["half"]["moe_rows"] < seen["half"]["moe_assignments"]
    dense = _engine(glm, mesh)
    assert "moe_rows" not in dense.stats
    assert not dense.runner.counts_experts
