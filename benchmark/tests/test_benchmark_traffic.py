"""The traffic kinds give every seed the same amount of work."""
import json
import os
from collections import Counter

import numpy as np
import pytest

from benchmark.cell import HERE, load_module

SEEDS = (0, 7, 2**31 + 5)


def _mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _kind(name):
    return load_module(os.path.join(HERE, "traffic_kinds", name + ".py"),
                       "k_" + name)


def _shape(r):
    return (len(r.prompt), r.answer_len, r.temperature)


def test_open_loop_same_count_and_multiset_for_every_seed():
    kind, mix, cfg = _kind("open_loop"), _mix("chat-steady"), _config(
        "qwen2-0.5b")
    plans = [kind.generate(mix, cfg, s, 51.0) for s in SEEDS]
    n_window = round(mix["rate"] * 51.0)
    n_lead = round(mix["rate"] * mix["lead_s"])
    for plan in plans:
        arr = plan["arrivals"]
        assert sum(r.counted for r in arr) == n_window
        assert sum(not r.counted for r in arr) == n_lead
        assert all(0.0 <= r.due < 51.0 for r in arr if r.counted)
        assert all(-mix["lead_s"] <= r.due < 0.0 for r in arr
                   if not r.counted)
        assert [r.due for r in arr] == sorted(r.due for r in arr)
        assert len(plan["preload"]) == mix["inflight_at_open"]
    for key in ("arrivals", "preload"):
        bags = [Counter(_shape(r) for r in p[key]) for p in plans]
        assert bags[0] == bags[1] == bags[2]
    # ... and differ in order, instants and token contents
    a, b = plans[0]["arrivals"], plans[1]["arrivals"]
    assert [_shape(r) for r in a] != [_shape(r) for r in b]
    assert [r.due for r in a] != [r.due for r in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    # the same seed gives the same inputs
    again = kind.generate(mix, cfg, SEEDS[0], 51.0)["arrivals"]
    assert [r.due for r in again] == [r.due for r in a]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(again, a))


def test_open_loop_mix_follows_the_weights():
    kind, mix, cfg = _kind("open_loop"), _mix("chat-steady"), _config(
        "qwen2-0.5b")
    arr = [r for r in kind.generate(mix, cfg, 3, 100.0)["arrivals"]
           if r.counted]
    n = len(arr)
    for v, w in zip(mix["prompt"]["values"], mix["prompt"]["weights"]):
        assert abs(sum(len(r.prompt) == v for r in arr) / n - w) < 0.02
    for v, w in zip(mix["answer"]["values"], mix["answer"]["weights"]):
        assert abs(sum(r.answer_len == v for r in arr) / n - w) < 0.02
    assert abs(sum(r.temperature == 0.0 for r in arr) / n - 0.5) < 0.02


def test_closed_loop_one_caller_per_slot_same_shapes_for_every_seed():
    kind, mix, cfg = _kind("closed_loop"), _mix("decode-sat"), _config(
        "qwen2-0.5b")
    plans = [kind.generate(mix, cfg, s, 51.0) for s in SEEDS]
    for plan in plans:
        assert len(plan["preload"]) == cfg["serving"]["max_slots"]
        assert all(1 <= r.answer_len <= c[1]
                   for r, c in zip(plan["preload"], plan["callers"]))
    bags = [Counter(p["callers"]) for p in plans]
    assert bags[0] == bags[1] == bags[2]
    shapes = Counter(c[:3] for c in plans[0]["callers"])
    assert len(shapes) == 12 and set(shapes.values()) == {16}
    assert plans[0]["callers"] != plans[1]["callers"]


@pytest.mark.parametrize("weights,n", [([0.3, 0.3, 0.2, 0.15, 0.05], 245),
                                       ([1, 1, 1], 10), ([1.0], 7)])
def test_exact_counts_sum_and_follow_the_weights(weights, n):
    from benchmark.serving import exact_counts

    counts = exact_counts(weights, n)
    assert sum(counts) == n
    w = np.asarray(weights) / np.sum(weights)
    assert all(abs(c - x * n) < 1.0 for c, x in zip(counts, w))
