"""Open loop: independent users. Requests fall due on a schedule whether or not
earlier ones have finished, and each is timed from when it was due.

The mix is a data file::

    {"kind": "open_loop", "rate": 4.8, "lead_s": 3.0, "inflight_at_open": 150,
     "prompt": {"values": [...], "weights": [...]},
     "answer": {"values": [...], "weights": [...]},
     "temperature": {"values": [0.0, 0.7], "weights": [0.5, 0.5]}}

What the seed may and may not change. Every seed gets the same number of
requests due before the window (``round(rate * lead_s)``) and in it
(``round(rate * seconds)``), and the same multiset of (prompt, answer,
temperature) among them: the weights turned into exact counts. The seed
permutes that multiset, draws the arrival instants (that many sorted uniforms
over each interval — a Poisson process given its count) and the token
contents. So two seeds differ in order and timing, never in the amount of work.

The window opens in steady state: set-up admits ``inflight_at_open`` streams
whose answers are length-biased (a stream in flight is more likely a long one)
and evenly staggered in progress, and arrivals start ``lead_s`` before the
window. Requests due before the window count in no metric.
"""
from __future__ import annotations

import itertools
import time

import numpy as np

from benchmark.serving import Request, exact_counts, prompt_tokens


def _joint(traffic: dict, n: int, answer_bias: bool) -> list:
    """``n`` (prompt, answer, temperature) triples in exact proportion to the
    product of the weights (answers weighted by their length too when
    ``answer_bias``), in a fixed order."""
    p, a, t = traffic["prompt"], traffic["answer"], traffic["temperature"]
    combos, weights = [], []
    for (pv, pw), (av, aw), (tv, tw) in itertools.product(
            zip(p["values"], p["weights"]), zip(a["values"], a["weights"]),
            zip(t["values"], t["weights"])):
        combos.append((int(pv), int(av), float(tv)))
        weights.append(pw * aw * tw * (av if answer_bias else 1.0))
    out = []
    for combo, c in zip(combos, exact_counts(weights, n)):
        out.extend([combo] * c)
    return out


def generate(traffic: dict, config: dict, seed: int, seconds: float) -> dict:
    rng = np.random.default_rng(seed)
    vocab = config["vocab_size"]
    n_window = int(round(traffic["rate"] * seconds))
    n_lead = int(round(traffic["rate"] * traffic["lead_s"]))

    def arrivals(n, lo, hi, counted, first_idx):
        combos = _joint(traffic, n, answer_bias=False)
        order = rng.permutation(n)
        due = np.sort(rng.uniform(lo, hi, size=n))
        return [Request(first_idx + i, prompt_tokens(rng, combos[j][0], vocab),
                        combos[j][1], combos[j][2],
                        int(rng.integers(0, 2**31 - 1)), float(due[i]),
                        counted=counted)
                for i, j in enumerate(order)]

    lead = arrivals(n_lead, -traffic["lead_s"], 0.0, False, 0)
    window = arrivals(n_window, 0.0, seconds, True, n_lead)

    # streams already in flight when arrivals start: remaining answer lengths
    # evenly staggered within each answer class
    n_pre = int(traffic["inflight_at_open"])
    combos = _joint(traffic, n_pre, answer_bias=True)
    by_answer: dict = {}
    for c in combos:
        by_answer.setdefault(c[1], []).append(c)
    staged = []
    for a, group in sorted(by_answer.items()):
        for k, c in enumerate(group):
            done = int((k + 0.5) / len(group) * a)
            staged.append((c[0], max(a - done, 1), c[2]))
    preload = [Request(-1, prompt_tokens(rng, staged[j][0], vocab),
                       staged[j][1], staged[j][2],
                       int(rng.integers(0, 2**31 - 1)), -1e9, counted=False)
               for j in rng.permutation(n_pre)]
    return {"preload": preload, "arrivals": lead + window,
            "n_window": n_window, "n_lead": n_lead}


def drive(served, plan: dict, traffic: dict, seconds: float, hooks) -> dict:
    """Arrivals from ``-lead_s``; the window opens at the first step boundary
    at or after 0 and closes at the first at or after ``seconds``. Then steps
    go on, with no new arrivals, until every request due in the window has its
    first token."""
    pending = list(plan["arrivals"])  # sorted by due
    served.open_in(traffic["lead_s"])
    i, t0, t1 = 0, None, None
    while True:
        now = served.now()
        if t0 is None and now >= 0.0:
            t0 = now
            hooks.window_open()
        if t1 is None and now >= seconds:
            t1 = now
            hooks.window_close()
        hooks.tick(now)
        while i < len(pending) and pending[i].due <= now:
            served.submit(pending[i])  # every due is < seconds: none after t1
            i += 1
        if t1 is not None and all(r.stamps or r.failed
                                  for r in pending if r.counted):
            break
        if not served.live:
            time.sleep(0.002)  # nothing to step: wait for the next arrival
            continue
        served.step()
    counted = [r for r in pending if r.counted]
    return {"t0": t0, "t1": t1, "requests": pending[:i], "preload": plan["preload"],
            "attempted": len(counted),
            "failed": sum(1 for r in counted if r.failed or not r.stamps)}


def run(cell, seed: int, seconds: float, env: dict) -> dict:
    from benchmark import serving_run

    return serving_run.run(cell, seed, seconds, env, generate, drive)
