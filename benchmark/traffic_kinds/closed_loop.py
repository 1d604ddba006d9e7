"""Closed loop: one caller per slot, each waits for its answer and then sends
its next request (offline and batch generation). A slow system receives less
load, so the end-to-end metric is the tokens completed, not a tail.

The mix is a data file::

    {"kind": "closed_loop", "callers": "max_slots",
     "prompt": {"values": [512, 1024]}, "answer": {"values": [128, 256, 512]},
     "temperature": {"values": [0.0, 0.7]}}

Every caller has one request shape (prompt, answer, temperature) for the whole
run, and the shapes are dealt to the callers in equal exact counts, so the
mix in flight is the same at every instant and for every seed. Set-up fills
every slot: caller k of a shape's c callers starts with ``(k + 0.5) / c`` of its
answer already behind it (its first request asks for the remainder only). The
seed deals the shapes to the callers, and draws the token contents.
"""
from __future__ import annotations

import itertools

import numpy as np

from benchmark.serving import Request, exact_counts, prompt_tokens


def generate(traffic: dict, config: dict, seed: int, seconds: float) -> dict:
    rng = np.random.default_rng(seed)
    n = (config["serving"]["max_slots"] if traffic["callers"] == "max_slots"
         else int(traffic["callers"]))
    shapes_ = [(int(p), int(a), float(t)) for p, a, t in itertools.product(
        traffic["prompt"]["values"], traffic["answer"]["values"],
        traffic["temperature"]["values"])]
    callers = []  # (prompt, answer, temperature, first remainder)
    for shape, c in zip(shapes_, exact_counts([1.0] * len(shapes_), n)):
        for k in range(c):
            done = int((k + 0.5) / c * shape[1])
            callers.append(shape + (max(shape[1] - done, 1),))
    callers = [callers[j] for j in rng.permutation(n)]
    vocab = config["vocab_size"]
    preload = [Request(k, prompt_tokens(rng, c[0], vocab), c[3], c[2],
                       int(rng.integers(0, 2**31 - 1)), -1e9, counted=True,
                       caller=k) for k, c in enumerate(callers)]
    return {"preload": preload, "callers": callers,
            "token_seed": int(rng.integers(0, 2**31 - 1))}


def drive(served, plan: dict, traffic: dict, seconds: float, hooks) -> dict:
    """Every slot is full when the window opens (set-up admitted the callers'
    first requests); a finished stream is replaced at once by its caller's next
    request. The window closes at the first step boundary at or after
    ``seconds``."""
    rng = np.random.default_rng(plan["token_seed"])
    vocab = served.batcher.cfg.vocab_size
    requests = list(plan["preload"])
    served.open_in(0.0)
    hooks.window_open()
    while True:
        now = served.now()
        if now >= seconds:
            hooks.window_close()
            break
        hooks.tick(now)
        for req in served.step():
            p, a, t, _ = plan["callers"][req.caller]
            nxt = Request(len(requests), prompt_tokens(rng, p, vocab), a, t,
                          int(rng.integers(0, 2**31 - 1)), served.now(),
                          caller=req.caller)
            requests.append(nxt)
            served.submit(nxt)
    return {"t0": 0.0, "t1": now, "requests": requests, "preload": [],
            "attempted": len(requests),
            "failed": sum(1 for r in requests if r.failed)}


def run(cell, seed: int, seconds: float, env: dict) -> dict:
    from benchmark import serving_run

    return serving_run.run(cell, seed, seconds, env, generate, drive)
