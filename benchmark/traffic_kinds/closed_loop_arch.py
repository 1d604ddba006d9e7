"""``closed_loop`` for a configuration whose architecture ``serving_run.run``
does not know: the same generator and driver (loaded from
``closed_loop.py``, not copied), around a run that takes the weights, the
served system and the reference from
``benchmark/architectures/<config["model_type"]>.py``::

    make_weights(config, seed) -> the tree both sides are given
    build_batcher(config, weights) -> the served ContinuousBatcher
    logit_gaps(config, weights, ids, start, served, with_control=) ->
        (gaps, control gaps or None), as ``reference.logit_gaps``

Set-up, the window's edges, the sample, the judgement and the record are
``serving_run``'s: ``Hooks``, ``pick_sample``, ``judge`` and the record's keys.
The mix file is ``closed_loop``'s with this kind's name. A later ``benchmark``
issue folds this dispatch into ``serving_run.run`` (PERF.md section 7).
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmark import serving_run, trace_reduce
from benchmark.cell import HERE, load_module
from benchmark.serving import Served, preload, traffic_shapes, warm_up

_closed_loop = load_module(
    os.path.join(HERE, "traffic_kinds", "closed_loop.py"),
    "bench_kind_closed_loop")
generate, drive = _closed_loop.generate, _closed_loop.drive


def architecture(config: dict):
    name = config["model_type"]
    return load_module(os.path.join(HERE, "architectures", name + ".py"),
                       "bench_arch_" + name)


def compare(cell, arch, weights: dict, done: list, seed: int,
            control: bool) -> dict:
    """``serving_run.compare`` with the architecture's reference."""
    import jax.numpy as jnp

    vocab = cell.config["vocab_size"]
    missing = sum(1 for r in done if r.tokens is not None and (
        len(r.tokens) != r.answer_len or int(r.tokens.min()) < 0
        or int(r.tokens.max()) >= vocab))
    sample = serving_run.pick_sample(done, seed)
    shp = traffic_shapes(cell.traffic)
    n_pad = shp["answer_max"]
    s_pad = max(shp["prompt_lens"]) + n_pad
    gaps, ctrl = [], []
    for r in sample:
        n, p = len(r.tokens), len(r.prompt)
        ids = np.zeros((s_pad,), np.int32)
        ids[:p] = r.prompt
        ids[p:p + n - 1] = r.tokens[:-1]
        served = np.zeros((n_pad,), np.int32)
        served[:n] = r.tokens
        g, c = arch.logit_gaps(cell.config, weights, jnp.asarray(ids), p - 1,
                               jnp.asarray(served), with_control=control)
        gaps.append(np.asarray(g)[:n])
        if control:
            ctrl.append(np.asarray(c)[:n])
    out = {"tokens_missing": float(missing),
           "compared_requests": len(sample),
           "compared_tokens": int(sum(len(g) for g in gaps))}
    if gaps:
        allg = np.concatenate(gaps)
        out.update(gap_max=float(allg.max()), gap_mean=float(allg.mean()))
    if ctrl:
        allc = np.concatenate(ctrl)
        out.update(control_gap_max=float(allc.max()),
                   control_gap_mean=float(allc.mean()))
    return out


def run(cell, seed: int, seconds: float, env: dict) -> dict:
    import jax

    config, traffic = cell.config, cell.traffic
    arch = architecture(config)
    weights = arch.make_weights(config, seed)
    served = Served(arch.build_batcher(config, weights))
    warm_up(served, traffic, config["vocab_size"])
    plan = generate(traffic, config, seed, seconds)
    preload(served, plan)
    hooks = serving_run.Hooks(served, seconds, env)
    window = drive(served, plan, traffic, seconds, hooks)
    setup_s = served.t_open + window["t0"] - env["t_start"]
    print(f"window: {window['t0']:.3f}..{window['t1']:.3f} s, "
          f"{served.steps} steps in the run, compiles in the window: "
          f"{hooks.compiles}", flush=True)

    reduced = None
    if env["trace"]:
        events = trace_reduce.load_events(
            trace_reduce.find_xplane(env["trace_dir"]))
        reduced = trace_reduce.reduce_events(events)
        env["dump"]("trace_reduced.json", reduced)

    done = [r for r in served.done if r.tokens is not None
            and r.stamps and r.stamps[-1] > window["t0"]]
    t0 = time.monotonic()
    numbers = compare(cell, arch, weights, done, seed, env["control"])
    print(f"reference: {time.monotonic() - t0:.2f} s over "
          f"{numbers['compared_tokens']} served tokens of "
          f"{numbers['compared_requests']} requests", flush=True)
    correct = serving_run.judge(numbers, cell.limits)

    live = (sum(hooks.live_samples) / len(hooks.live_samples)
            if hooks.live_samples else 0.0)
    return {
        "correct": correct, "attempted": window["attempted"],
        "failed": window["failed"], "setup_s": setup_s,
        "t0": window["t0"], "t1": window["t1"],
        "window_s": window["t1"] - window["t0"],
        "requests": window["requests"], "preload": window["preload"],
        "report0": hooks.report0, "report1": hooks.report1,
        "pool_live_share": live, "compiles_in_window": hooks.compiles,
        "peak_bytes": hooks.peak_bytes, "trace": reduced,
        "config": config, "traffic": traffic, "model": cell.model,
        "token_capacity": served.batcher.pool.token_capacity,
        "wire_bytes_step": None,
        "device_kind": jax.devices()[0].device_kind, "numbers": numbers,
    }
