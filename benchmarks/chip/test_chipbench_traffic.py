"""The traffic generator: seeded, stratified, and shaped as its mix says."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import spec, traffic  # noqa: E402

SEED = 2 ** 33 + 17


def mix(name: str) -> dict:
    return spec.load_json(HERE / "traffic" / f"{name}.json")


def cell_load(name: str) -> dict:
    return spec.load_json(HERE / "cells" / f"{name}.json")["load"]


IDE = ("ide_completion", "starcoder2_3b.ide_completion", 49152)
CHAT = ("chat_batch", "glm4_9b-pp2.chat_batch", 151552)


def make(which, seed=SEED, seconds=51.0):
    m, cell, vocab = which
    return traffic.schedule(mix(m), cell_load(cell), vocab, seed, seconds)


@pytest.mark.parametrize("which", [IDE, CHAT])
def test_same_seed_same_schedule(which):
    a, b = make(which), make(which)
    assert len(a.timed) == len(b.timed) and len(a.warmup) == len(b.warmup)
    for x, y in zip(a.timed + a.warmup, b.timed + b.warmup):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert x.temperature == y.temperature and x.seed == y.seed
        np.testing.assert_array_equal(x.prompt, y.prompt)
    c = make(which, seed=SEED + 1)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.timed, c.timed))


@pytest.mark.parametrize("which", [IDE, CHAT])
def test_seeds_share_the_set_of_sizes(which):
    """Arrival gaps are the same set for every seed, and so are the output
    lengths (of a backlog; of an open loop's window, as many as fall in
    it)."""
    a, c = make(which), make(which, seed=7)
    if not a.open_loop:
        assert sorted(x.max_new for x in a.timed) == \
            sorted(x.max_new for x in c.timed)
    if a.open_loop:
        ramp = a.ramp_s
        for s in (a, c):
            d = np.array([x.due_s for x in s.timed])
            assert (d < ramp).sum() == round(cell_load(which[1])["rate_per_s"]
                                             * ramp)
            assert d[0] == 0.0 and d[-1] < ramp + 51.0


@pytest.mark.parametrize("which", [CHAT])
def test_backlog_lengths_follow_their_clip_and_median(which):
    m = mix(which[0])
    s = make(which)
    assert len(s.timed) == cell_load(which[1])["backlog"]
    for key, get in (("prompt", lambda x: len(x.prompt)),
                     ("output", lambda x: x.max_new)):
        p, v = m[key], np.array([get(x) for x in s.timed])
        assert v.min() >= p["min"] and v.max() <= p["max"]
        assert abs(np.median(v) - p["median"]) <= 1
    assert all(x.due_s == 0.0 for x in s.timed)


def test_chat_mixes_exactly_one_greedy_request_in_four():
    s = make(CHAT)
    greedy = [x for x in s.timed if x.greedy]
    assert len(greedy) == len(s.timed) // 4
    assert all(x.temperature == 0.7 and x.top_p == 0.95
               for x in s.timed if not x.greedy)
    assert {x.greedy for x in s.warmup} == {True, False}


def test_ide_sessions_share_all_but_their_tail():
    m, s = mix("ide_completion"), make(IDE)
    ss = m["sessions"]
    assert len(s.warmup) == ss["count"]
    last = {i: w.prompt for i, w in enumerate(s.warmup)}
    fresh = 0
    for x in s.timed:
        assert len(x.prompt) + x.max_new <= ss["max_context"]
        prev = last[x.session]
        if x.shared:
            assert x.shared == len(prev)
            np.testing.assert_array_equal(x.prompt[:x.shared], prev)
            assert ss["append_min"] <= len(x.prompt) - x.shared \
                <= ss["append_max"]
        else:
            fresh += 1
        last[x.session] = x.prompt
    assert s.notes["new_sessions"] == round(ss["new_share"] * len(s.timed))
    assert fresh == s.notes["new_sessions"] + s.notes["restarts"]
    shared = sum(x.shared for x in s.timed)
    total = sum(len(x.prompt) for x in s.timed)
    # about 90 % of requests repeat a prompt of ~1.5k tokens plus 4-64
    assert 0.75 < shared / total < 0.97


def test_ide_window_outputs_are_stratified_and_new_sessions_spread():
    m, s = mix("ide_completion"), make(IDE)
    ramp = cell_load(IDE[1])["ramp_s"]
    win = [x.max_new for x in s.timed if x.due_s >= ramp]
    p = m["output"]
    want = traffic.lognormal_sizes(len(win), p, np.random.default_rng(0))
    assert sorted(win) == sorted(want)
    mask = traffic.spread_mask(100, 10, np.random.default_rng(1))
    assert mask.sum() == 10
    assert all(mask[i * 10:(i + 1) * 10].sum() == 1 for i in range(10))


def test_ide_first_prompts_follow_their_clip_and_median():
    p, s = mix("ide_completion")["prompt"], make(IDE)
    lens = np.array([len(w.prompt) for w in s.warmup])
    assert lens.min() >= p["min"] and lens.max() <= p["max"]
    assert abs(np.median(lens) - p["median"]) / p["median"] < 0.05
    dues = np.array([x.due_s for x in s.timed])
    assert (np.diff(dues) >= 0).all()
    load = cell_load(IDE[1])
    assert len(dues) == round(load["rate_per_s"] * load["ramp_s"]) \
        + round(load["rate_per_s"] * 51.0)


def test_ide_sessions_keep_their_sizes_and_pick_counts_across_seeds():
    ramp = cell_load(IDE[1])["ramp_s"]
    runs = [make(IDE, seed=s) for s in (SEED, 5)]
    firsts = [[len(w.prompt) for w in s.warmup] for s in runs]
    assert firsts[0] == firsts[1]
    counts = [np.bincount([x.session for x in s.timed
                           if x.due_s >= ramp and x.shared],
                          minlength=len(s.warmup)) for s in runs]
    # picks per session are fixed; a new session can take a picked slot
    assert np.abs(counts[0] - counts[1]).sum() <= 2 * runs[0].notes[
        "new_sessions"]
    assert sum(not x.shared for x in runs[0].timed) == \
        sum(not x.shared for x in runs[1].timed)


BURSTS = {"factor": 4.0, "on_s": 2.0, "period_s": 10.0}


def burst_schedule(seed=SEED, sessions=True):
    m = dict(mix("ide_completion"), bursts=BURSTS)
    if not sessions:
        del m["sessions"]
    return traffic.schedule(m, cell_load(IDE[1]), IDE[2], seed, 50.0)


def test_bursts_arrive_at_their_factor_and_keep_the_mean():
    load = cell_load(IDE[1])
    rate, ramp = load["rate_per_s"], load["ramp_s"]
    s = burst_schedule()
    d = np.array([x.due_s for x in s.timed]) - ramp
    win = d[d >= 0]
    assert len(win) == pytest.approx(rate * 50.0, abs=5)
    on = win[(win % BURSTS["period_s"]) < BURSTS["on_s"]]
    # 2 s of every 10 at 4x the mean: 8/10 of the arrivals
    assert len(on) / len(win) == pytest.approx(0.8, abs=0.06)
    t = burst_schedule()
    assert [x.due_s for x in s.timed] == [x.due_s for x in t.timed]
    other = burst_schedule(seed=5)
    assert np.allclose(np.diff(np.sort(d)).sum(),
                       np.diff(np.sort([x.due_s - ramp for x in other.timed
                                        ])).sum(), atol=1.0)


@pytest.mark.parametrize("bursts", [None, BURSTS,
                                    {"factor": 8.0, "on_s": 2.0,
                                     "period_s": 10.0}])
def test_rate_pieces_cover_the_segment_at_the_mean_rate(bursts):
    if bursts and bursts["factor"] * bursts["on_s"] > bursts["period_s"]:
        with pytest.raises(ValueError):
            traffic.rate_pieces(1.0, 30.0, bursts)
        return
    pieces = traffic.rate_pieces(1.5, 35.0, bursts)
    assert sum(L for L, _ in pieces) == pytest.approx(35.0)
    # whole periods carry the mean; the last, cut short, may not
    whole = pieces[:6] if bursts else pieces
    assert sum(L * r for L, r in whole) == pytest.approx(
        1.5 * sum(L for L, _ in whole))


def test_open_loop_without_sessions_sends_only_new_prompts():
    s = burst_schedule(sessions=False)
    p = mix("ide_completion")["prompt"]
    assert not s.warmup
    assert all(x.session == -1 and x.shared == 0 for x in s.timed)
    lens = np.array([len(x.prompt) for x in s.timed])
    assert lens.min() >= p["min"] and lens.max() <= p["max"]
