"""The traffic generator: deterministic per seed, stratified draws of the
stated distributions, every open-loop request due inside the window."""
import json
from collections import Counter

import numpy as np
import pytest

from bench import traffic
from bench.spec import BENCH


def _mix(name):
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _key(plan):
    return [(p.idx, p.prompt.tobytes(), p.max_new, p.due) for p in plan]


@pytest.mark.parametrize("mix,load", [("chat", {"rate_per_s": 6.0}),
                                      ("long-decode", {"clients": 6})])
def test_plan_is_deterministic_per_seed(mix, load):
    t = _mix(mix)
    big = 2 ** 33 + 17           # seeds exceed 32 bits
    a = traffic.plan(t, load, seed=big, seconds=30, vocab=1000)
    b = traffic.plan(t, load, seed=big, seconds=30, vocab=1000)
    c = traffic.plan(t, load, seed=big + 1, seconds=30, vocab=1000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    # another seed: the same sizes and gaps, in another order
    assert Counter(len(p.prompt) for p in a) == Counter(len(p.prompt)
                                                        for p in c)
    assert Counter(p.max_new for p in a) == Counter(p.max_new for p in c)
    assert all(0 <= tok < 1000 for p in a for tok in p.prompt)


def test_open_loop_offers_exact_rate_inside_window():
    t = _mix("chat")
    plan = traffic.plan(t, {"rate_per_s": 6.0}, seed=3, seconds=30,
                        vocab=100)
    due = np.array([p.due for p in plan])
    assert len(plan) == 180
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 30
    gaps = np.diff(due)
    # exponential gaps: the coefficient of variation of Exp is 1
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.15)


def test_chat_lengths_follow_the_stated_lognormals():
    t = _mix("chat")
    plan = traffic.plan(t, {"rate_per_s": 40.0}, seed=5, seconds=100,
                        vocab=100)
    prompts = np.array([len(p.prompt) for p in plan])
    outputs = np.array([p.max_new for p in plan])
    assert set(prompts) <= set(t["prompt"]["grid"])
    assert np.median(prompts) == 256
    assert prompts.min() == 16 and prompts.max() == 1024
    assert np.median(outputs) == pytest.approx(96, abs=1)
    assert outputs.min() >= 8 and outputs.max() == 256
    # sigma 0.8 in log space, before clipping: IQR of log = 2 * 0.674 * 0.8
    q1, q3 = np.percentile(np.log(outputs), [25, 75])
    assert q3 - q1 == pytest.approx(2 * 0.6745 * 0.8, rel=0.05)


def test_long_decode_lengths_are_uniform_on_the_grid():
    t = _mix("long-decode")
    plan = traffic.plan(t, {"clients": 6}, seed=9, seconds=30, vocab=100)
    prompts = [len(p.prompt) for p in plan]
    outputs = np.array([p.max_new for p in plan])
    assert len(plan) == 36 and all(p.due is None for p in plan)
    assert set(prompts) <= set(t["prompt"]["grid"])
    assert min(prompts) == 4096 and max(prompts) == 11264
    assert 512 <= outputs.min() and outputs.max() <= 1024
    assert outputs.mean() == pytest.approx(768, abs=8)
    assert max(prompts) + outputs.max() <= 12288
