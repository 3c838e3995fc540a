"""The one traffic generator. A mix is a JSON file of parameters
(``traffic/<name>.json``); the cell's ``load`` adds the offered rate or the
backlog depth. Everything is drawn from ``--seed``.

Sizes are stratified: a mix's n prompt lengths are the n quantiles
``(i + 0.5) / n`` of its clipped lognormal, and the Poisson gaps the n
quantiles of the exponential, each set shuffled by the seed. So every seed
offers the same set of sizes and arrivals in another order, and runs on
different seeds differ by ordering and token ids, not by the amount of
work. An open loop's ramp and window are stratified apart, so the window
holds the same number of requests and the same set of sizes every seed.

Two arrival kinds, the two ways a server is offered load:

* ``poisson``: an open loop at ``load.rate_per_s``; sizes are stratified
  apart for the ramp before the window and the window itself. ``bursts``
  (``factor``, ``on_s``, ``period_s``) modulate the rate: the first
  ``on_s`` seconds of every period arrive at ``factor`` times the mean and
  the rest slower, so the mean stays ``rate_per_s``. With ``sessions`` the
  requests edit files: a request picks one of ``count`` sessions by Zipf,
  repeats that session's previous prompt and appends a few tokens, so the
  prompt shares all but its tail with an earlier one; ``new_share`` of the
  requests (one per stride of 1 / new_share requests) open a new session
  in place of the least recently used one, and a session restarts when
  prompt + output would pass ``max_context``. The warm-up opens every
  session once. Each session's first prompt length and its number of picks
  in the ramp and in the window are the same on every seed. Without
  ``sessions`` every prompt is new.
* ``backlog``: ``load.backlog`` unique requests queued before the window.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


@dataclass
class Item:
    due_s: float              # seconds after the timed phase starts
    prompt: np.ndarray        # int32 token ids
    max_new: int
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0
    session: int = -1         # -1: not part of a session
    shared: int = 0           # leading tokens equal to the session's last prompt

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


@dataclass
class Schedule:
    warmup: list[Item]        # submitted together before the timed phase
    timed: list[Item]
    open_loop: bool
    ramp_s: float = 0.0       # open loop: timed phase before the window opens
    warm_steps: int = 0       # backlog: engine steps before the window opens
    notes: dict = field(default_factory=dict)


def lognormal_sizes(n: int, p: dict, rng) -> np.ndarray:
    """n stratified draws of a lognormal with median ``p.median`` and log
    standard deviation ``p.sigma``, clipped to [p.min, p.max], shuffled."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.clip(np.round(p["median"] * np.exp(p["sigma"] * z)),
                p["min"], p["max"]).astype(np.int64)
    return rng.permutation(x)


def uniform_sizes(n: int, lo: int, hi: int, rng) -> np.ndarray:
    """n stratified draws of the integers lo..hi, shuffled."""
    x = lo + np.floor((np.arange(n) + 0.5) / n * (hi - lo + 1))
    return rng.permutation(x.astype(np.int64))


def segment_sizes(dues, ramp: float, p: dict, rng) -> np.ndarray:
    """Lognormal sizes stratified apart for the requests due in the ramp
    and those due after it, so the window's set of sizes hangs on how many
    requests fall in it and not on which draws they got."""
    dues = np.asarray(dues)
    out = np.zeros(len(dues), np.int64)
    for part in (dues < ramp, dues >= ramp):
        if part.any():
            out[part] = lognormal_sizes(int(part.sum()), p, rng)
    return out


def spread_mask(n: int, k: int, rng) -> np.ndarray:
    """k of n positions, one in each of k equal strides at a random place
    in it, so such events never bunch up more than the strides allow."""
    mask = np.zeros(n, bool)
    if k:
        mask[((np.arange(k) + rng.random(k)) * n / k).astype(int)] = True
    return mask


def rate_pieces(rate: float, length: float, bursts: dict | None):
    """(seconds, rate) pieces of a segment: one at ``rate``, or the on and
    off parts of each burst period, off at the rate that keeps the mean."""
    if not bursts:
        return [(length, rate)]
    f, on, period = bursts["factor"], bursts["on_s"], bursts["period_s"]
    if not 0 < on < period or f * on > period:
        raise ValueError(f"bursts {bursts} leave no rate for the off part")
    off_rate = rate * (period - f * on) / (period - on)
    out, t = [], 0.0
    while t < length - 1e-9:
        phase = t % period
        end = min(length, t + (on - phase if phase < on else period - phase))
        out.append((end - t, rate * f if phase < on else off_rate))
        t = end
    return out


def poisson_dues(rate: float, segments, rng,
                 bursts: dict | None = None) -> np.ndarray:
    """Due times at ``rate``/s over consecutive segments of the given
    lengths (the ramp, then the window), each cut into its burst pieces.
    A piece of L seconds at rate r gets round(r x L) arrivals whose gaps
    are the quantiles of the exponential, shuffled, and scaled to fill it;
    its first arrival is due when it starts. So every seed puts as many
    requests in the window."""
    out, t0 = [], 0.0
    pieces = [p for length in segments
              for p in rate_pieces(rate, length, bursts)]
    for length, rate in pieces:
        m = max(1, int(round(rate * length)))
        q = (np.arange(m) + 0.5) / m
        gaps = rng.permutation(-np.log1p(-q) / rate)
        gaps *= length / gaps.sum()
        out.append(t0 + np.cumsum(gaps) - gaps[0])
        t0 += length
    return np.concatenate(out)


def _sampling(n: int, mix: dict, rng) -> list[tuple[float, float, int]]:
    """(temperature, top_p, seed) per request. ``greedy_every`` k makes
    exactly n // k of them greedy, at shuffled positions: only greedy
    tokens can be compared with the reference."""
    s = mix.get("sampling", {})
    temp, top_p = s.get("temperature", 0.0), s.get("top_p", 1.0)
    greedy = np.zeros(n, bool)
    if temp == 0.0:
        greedy[:] = True
    elif s.get("greedy_every"):
        greedy[:n // s["greedy_every"]] = True
        greedy = rng.permutation(greedy)
    seeds = rng.integers(0, 2 ** 31 - 1, n)
    return [(0.0, 1.0, int(sd)) if g else (temp, top_p, int(sd))
            for g, sd in zip(greedy, seeds)]


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, int(n), dtype=np.int32)


def zipf_picks(m: int, weights, rng) -> np.ndarray:
    """m session picks whose count per session is its Zipf share of m
    (largest remainders), in an order shuffled by the seed."""
    share = m * np.asarray(weights) / np.sum(weights)
    counts = np.floor(share).astype(int)
    counts[np.argsort(counts - share)[:m - counts.sum()]] += 1
    return rng.permutation(np.repeat(np.arange(len(weights)), counts))


def _sessions(mix: dict, n: int, dues, ramp: float, vocab: int,
              rng) -> Schedule:
    """Sessions keep the same sizes on every seed: session k (Zipf rank k)
    opens with the same prompt length, and the ramp and the window each
    get the same number of picks of it and of new sessions; the seed picks
    the order and the token ids."""
    ss = mix["sessions"]
    count, max_ctx = ss["count"], ss["max_context"]
    first = lognormal_sizes(count, mix["prompt"], np.random.default_rng(0))
    restart_lens = iter(lognormal_sizes(n, mix["prompt"], rng))
    outs = segment_sizes(dues, ramp, mix["output"], rng)
    appends = uniform_sizes(n, ss["append_min"], ss["append_max"], rng)
    w = 1.0 / np.arange(1, count + 1) ** ss["zipf"]
    fresh = np.zeros(n, bool)
    fresh_len = np.zeros(n, np.int64)
    picks = np.zeros(n, np.int64)
    for part in (dues < ramp, dues >= ramp):
        idx = np.flatnonzero(part)
        f = spread_mask(len(idx), int(round(ss["new_share"] * len(idx))), rng)
        fresh[idx[f]] = True
        fresh_len[idx[f]] = lognormal_sizes(int(f.sum()), mix["prompt"], rng)
        picks[idx[~f]] = zipf_picks(int((~f).sum()), w, rng)
    prompts = [_tokens(rng, m, vocab) for m in first]
    last_use = list(range(count))         # slot -> last request index
    wmax = mix.get("warmup_max_new", 2)
    warm = [Item(0.0, p, wmax, session=s) for s, p in enumerate(prompts)]
    timed, restarts = [], 0
    for i in range(n):
        max_new = int(outs[i])
        if fresh[i]:
            s = int(np.argmin(last_use))
            prompts[s] = _tokens(rng, fresh_len[i], vocab)
            shared = 0
        else:
            s = int(picks[i])
            grown = np.concatenate(
                [prompts[s], _tokens(rng, appends[i], vocab)])
            if len(grown) + max_new > max_ctx:
                restarts += 1
                prompts[s] = _tokens(rng, next(restart_lens), vocab)
                shared = 0
            else:
                shared = len(prompts[s])
                prompts[s] = grown
        last_use[s] = count + i
        timed.append(Item(float(dues[i]), prompts[s], max_new, session=s,
                          shared=shared))
    return Schedule(warm, timed, open_loop=True,
                    notes={"restarts": restarts,
                           "new_sessions": int(fresh.sum())})


def schedule(mix: dict, load: dict, vocab: int, seed: int,
             seconds: float) -> Schedule:
    """The whole run's requests, from the seed."""
    rng = np.random.default_rng(seed)
    if mix["arrival"] == "poisson":
        ramp = load["ramp_s"]
        dues = poisson_dues(load["rate_per_s"], (ramp, seconds), rng,
                            mix.get("bursts"))
        n = len(dues)
        if "sessions" in mix:
            sch = _sessions(mix, n, dues, ramp, vocab, rng)
        else:
            lens = segment_sizes(dues, ramp, mix["prompt"], rng)
            outs = segment_sizes(dues, ramp, mix["output"], rng)
            sch = Schedule([], [Item(float(d), _tokens(rng, m, vocab), int(o))
                                for d, m, o in zip(dues, lens, outs)],
                           open_loop=True)
        sch.ramp_s = ramp
    elif mix["arrival"] == "backlog":
        n = load["backlog"]
        lens = lognormal_sizes(n, mix["prompt"], rng)
        outs = lognormal_sizes(n, mix["output"], rng)
        sch = Schedule([], [Item(0.0, _tokens(rng, m, vocab), int(o))
                            for m, o in zip(lens, outs)], open_loop=False,
                       warm_steps=load["warm_steps"])
    else:
        raise ValueError(f"unknown arrival kind {mix['arrival']!r}")
    for it, (t, tp, sd) in zip(sch.timed, _sampling(len(sch.timed), mix,
                                                     rng)):
        it.temperature, it.top_p, it.seed = t, tp, sd
    s = mix.get("sampling", {})
    for w in mix.get("warmup", []):
        # short requests that run each step program once before the window
        it = Item(0.0, _tokens(rng, w["prompt"], vocab), w["max_new"])
        if w.get("sampled"):
            it.temperature, it.top_p = s["temperature"], s.get("top_p", 1.0)
        sch.warmup.append(it)
    return sch
