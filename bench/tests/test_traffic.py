"""The traffic generator: deterministic by seed, the same work for every
seed, and only prompt lengths that are whole windows."""
import json
from collections import Counter

import numpy as np
import pytest

from bench import spec, traffic

MIX = spec.load("mistral7b-bsr60.doc4k").traffic


def _work(plan, stretch):
    return Counter((d.prompt_len, d.max_new_tokens) for d in plan
                   if d.stretch == stretch)


def test_same_seed_same_schedule():
    a = traffic.schedule(MIX, 3.2, 30, 2**33 + 7)
    b = traffic.schedule(MIX, 3.2, 30, 2**33 + 7)
    assert a == b
    assert np.array_equal(traffic.prompt_ids(5, a[3], 32000),
                          traffic.prompt_ids(5, b[3], 32000))


@pytest.mark.parametrize("seconds,rate", [(30, 3.2), (51, 1.28), (12, 4.0)])
def test_every_seed_offers_the_same_work(seconds, rate):
    plans = [traffic.schedule(MIX, rate, seconds, s)
             for s in (1, 2, 2**31 + 11)]
    for stretch in ("warmup", "window", "tail"):
        assert _work(plans[0], stretch) == _work(plans[1], stretch) \
            == _work(plans[2], stretch)
    assert [d.due for d in plans[0]] != [d.due for d in plans[1]]
    ws, we = traffic.window_bounds(MIX, seconds)
    win = [d for d in plans[0] if d.stretch == "window"]
    assert len(win) == round(rate * seconds)
    assert all(ws <= d.due < we for d in win)
    assert win[0].due == pytest.approx(ws)


def test_mix_shape():
    plan = traffic.schedule(MIX, 3.2, 30, 9)
    win = [d for d in plan if d.stretch == "window"]
    share = sum(d.prompt_len == 8192 for d in win) / len(win)
    assert share == pytest.approx(0.2, abs=0.03)
    outs = [d.max_new_tokens for d in win]
    assert min(outs) >= 16 and max(outs) <= 512
    assert 55 <= float(np.median(outs)) <= 75
    assert all(d.due <= e.due for d, e in zip(plan, plan[1:]))


def test_prompts_are_whole_windows():
    """The engine's decode is exact sliding-window attention only where
    the window divides the prompt (ROADMAP R1): every mix draws such
    prompts for every configuration it is run with."""
    b = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for w in b["workloads"]:
        cell = spec.load(w["name"])
        W = cell.config["sliding_window"]
        assert all(p % W == 0 for p, _ in cell.traffic["prompt_lengths"])


def test_prompt_ids_in_vocab():
    d = traffic.schedule(MIX, 3.2, 30, 3)[0]
    ids = traffic.prompt_ids(3, d, 32000)
    assert ids.shape == (d.prompt_len,) and ids.min() >= 1 \
        and ids.max() < 32000
