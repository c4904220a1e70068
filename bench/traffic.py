"""The one traffic generator: a mix file of parameters -> a schedule.

A mix (``bench/traffic/<name>.json``) gives the shape of the traffic and
a cell (``bench/workloads/<cell>.json``) its rate.  Arrivals are an open
loop: each request is due at a fixed time, whatever the server does.

So that every seed offers the same work, the schedule is stratified: it is
cut into blocks of about ``block_requests`` requests, and every block of a
stretch holds the same multiset of sizes, drawn at evenly spaced quantiles
of the mix's distributions.  The seed only permutes them within each
block, and draws the prompt ids.  Inter-arrival gaps are exponential
quantiles (a Poisson process, stratified), scaled so that each block
lasts its share of the stretch exactly.

The schedule has three stretches: ``warmup_s`` of traffic that brings the
slots to steady occupancy, the measured window of ``seconds``, and a tail
of ``drain_s`` that keeps the load on while the window's last requests are
served.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Due:
    """One request of the schedule."""
    index: int
    due: float              # seconds after the traffic starts
    prompt_len: int
    max_new_tokens: int
    stretch: str            # "warmup" | "window" | "tail"


def _counts(weights, n):
    """Largest-remainder split of ``n`` items by ``weights``."""
    w = np.asarray(weights, float) / sum(weights)
    raw = w * n
    c = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - c), kind="stable")[:n - c.sum()]:
        c[i] += 1
    return c


def _quantiles(k):
    return (np.arange(k) + 0.5) / k


def output_lengths(mix, k):
    """``k`` output lengths at evenly spaced quantiles of the mix's
    clipped lognormal."""
    o = mix["output"]
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(k)])
    n = np.round(o["median"] * np.exp(o["sigma"] * z)).astype(int)
    return np.clip(n, o["min"], o["max"])


def _block(mix, k, span, rng):
    """One block of ``k`` requests lasting ``span`` seconds: (gaps,
    prompt lengths, output lengths).  The (prompt, output) pairs are fixed
    (each prompt length spread evenly over the output quantiles) and the
    seed permutes the pairs and, apart, the gaps."""
    lens, weights = zip(*mix["prompt_lengths"])
    counts = _counts(weights, k)
    prompts = np.empty(k, int)
    free = list(range(k))
    for p, c in sorted(zip(lens, counts), key=lambda pc: pc[1]):
        at = [free[int(i)] for i in (np.arange(c) + 0.5) * len(free) / c] \
            if c else []
        prompts[at] = p
        free = [i for i in free if i not in at]
    gaps = -np.log1p(-_quantiles(k))           # exponential quantiles
    gaps *= span / gaps.sum()
    order = rng.permutation(k)
    return rng.permutation(gaps), prompts[order], output_lengths(mix, k)[order]


def _stretch(mix, rate, seconds, rng):
    n = max(1, round(rate * seconds))
    n_blocks = max(1, round(n / mix["block_requests"]))
    sizes = _counts([1] * n_blocks, n)
    out = []
    for k in sizes:
        gaps, prompts, outs = _block(mix, int(k), seconds * k / n, rng)
        out += list(zip(gaps, prompts, outs))
    return out


def schedule(mix, rate, seconds, seed):
    """The whole schedule for one run: [Due], in order of due time."""
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    out, t = [], 0.0
    for stretch, span in (("warmup", mix["warmup_s"]), ("window", seconds),
                          ("tail", mix["drain_s"])):
        t0 = t
        for gap, p, n in _stretch(mix, rate, span, rng):
            out.append(Due(len(out), t, int(p), int(n), stretch))
            t += gap
        t = t0 + span
    return out


def window_bounds(mix, seconds):
    """(start, end) of the measured window, in seconds of traffic."""
    return mix["warmup_s"], mix["warmup_s"] + seconds


def prompt_ids(seed, due: Due, vocab):
    """The prompt of one request: ids drawn uniformly from [1, vocab)."""
    rng = np.random.default_rng([int(seed), 0x9120, due.index])
    return rng.integers(1, vocab, due.prompt_len, dtype=np.int32)


def describe(mix, rate, seconds):
    """What a run offers, for the log."""
    ks = output_lengths(mix, 1000)
    mean_p = sum(p * w for p, w in mix["prompt_lengths"]) / sum(
        w for _, w in mix["prompt_lengths"])
    return (f"open loop at {rate:g} req/s: prompts {mix['prompt_lengths']} "
            f"(mean {mean_p:.0f}), outputs mean {ks.mean():.1f} "
            f"[{ks.min()}, {ks.max()}], {math.ceil(rate * seconds)} "
            f"requests due in {seconds} s")
