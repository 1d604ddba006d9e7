"""One run of a serving cell: set-up, the measured window, and the comparison
that decides ``correct``. The traffic kind brings ``generate`` and ``drive``;
everything they share is here.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import reference, trace_reduce
from benchmark.serving import (Served, build_batcher, preload,
                               traffic_shapes, warm_up)
from benchmark.weights import make_weights

#: how much of the window's end a ``--trace 1`` run records
TRACE_SECONDS = 6.0
#: requests the reference is run over, the longest among them
SAMPLE = 4


class Hooks:
    """What happens at the window's edges: counters snapshot, compile events
    counted, and in a traced run the profiler around the window's last
    seconds."""

    def __init__(self, served: Served, seconds: float, env: dict):
        self.served, self.seconds, self.env = served, seconds, env
        self.compiles = 0
        self.in_window = False
        self.report0 = self.report1 = None
        self.live_samples: list = []
        self.tracing = False
        self.peak_bytes = None
        self._window_span = None

    def _on_event(self, event: str, duration: float, **_):
        if self.in_window and event.endswith("backend_compile_duration"):
            self.compiles += 1

    def window_open(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self.report0 = self.served.batcher.report()
        self.in_window = True

    def tick(self, now: float):
        if not self.in_window:
            return
        self.live_samples.append(self.served.pool_live_share())
        if (self.env["trace"] and not self.tracing
                and now >= self.seconds - TRACE_SECONDS):
            import jax.profiler

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.env["trace_dir"],
                                     profiler_options=opts)
            self.tracing = True
            self.served.annotate = jax.profiler.TraceAnnotation
            self._window_span = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW_SPAN)
            self._window_span.__enter__()

    def window_close(self):
        import jax
        import jax.monitoring

        self.in_window = False
        self.report1 = self.served.batcher.report()
        self.peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                              for d in jax.local_devices())
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        if self.tracing:
            self._window_span.__exit__(None, None, None)
            self.served.annotate = None
            jax.profiler.stop_trace()


def pick_sample(done: list, seed: int, n: int = SAMPLE) -> list:
    """Greedy requests that finished, the longest among them, the rest drawn
    from the seed."""
    greedy = [r for r in done if r.tokens is not None and r.temperature == 0.0
              and len(r.tokens) >= 1]
    if not greedy:
        return []
    greedy.sort(key=lambda r: (-len(r.tokens), -len(r.prompt), r.idx))
    rest = greedy[1:]
    rng = np.random.default_rng(seed)
    picks = rng.permutation(len(rest))[: n - 1]
    return [greedy[0]] + [rest[j] for j in sorted(picks)]


def compare(cell, weights: dict, done: list, seed: int,
            control: bool) -> dict:
    """The numbers ``correct`` is decided on. For each sampled request the
    reference runs once over its prompt and served tokens; ``gap`` is how far a
    served token's reference logit lies below the reference's best."""
    import jax.numpy as jnp

    model = cell.model
    vocab = model["vocab_size"]
    missing = sum(1 for r in done if r.tokens is not None and (
        len(r.tokens) != r.answer_len or int(r.tokens.min()) < 0
        or int(r.tokens.max()) >= vocab))
    sample = pick_sample(done, seed)
    shp = traffic_shapes(cell.traffic)
    n_pad = shp["answer_max"]
    s_pad = max(shp["prompt_lens"]) + n_pad
    hops = tuple(zip(cell.config["split"]["cuts"],
                     cell.config["split"]["hop_codecs"])) \
        if cell.config.get("split") else ()
    key = reference.model_key(model)
    gaps, ctrl = [], []
    for r in sample:
        n, p = len(r.tokens), len(r.prompt)
        ids = np.zeros((s_pad,), np.int32)
        ids[:p] = r.prompt
        ids[p:p + n - 1] = r.tokens[:-1]
        served = np.zeros((n_pad,), np.int32)
        served[:n] = r.tokens
        g, c = reference.logit_gaps(key, weights, jnp.asarray(ids), p - 1,
                                    jnp.asarray(served), hops=hops,
                                    with_control=control)
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


def judge(numbers: dict, limits: dict) -> bool:
    """Every compared number beside its limit, printed; all must hold."""
    ok = True
    for name, limit in sorted(limits.items()):
        if not isinstance(limit, (int, float)):
            continue  # a note beside the limits, such as where they came from
        value = numbers.get(name)
        held = value is not None and value <= limit
        ok = ok and held
        print(f"correct: {name} = {value} limit {limit} "
              f"{'ok' if held else 'FAILED'}", flush=True)
    for name in sorted(numbers):
        if not isinstance(limits.get(name), (int, float)):
            print(f"correct: {name} = {numbers[name]}", flush=True)
    return ok


def run(cell, seed: int, seconds: float, env: dict, generate, drive) -> dict:
    import jax

    config, traffic = cell.config, cell.traffic
    weights = make_weights(cell.model, seed, config["torch_dtype"])
    served = Served(build_batcher(config, weights))
    warm_up(served, traffic, config["vocab_size"])
    plan = generate(traffic, config, seed, seconds)
    preload(served, plan)
    hooks = Hooks(served, seconds, env)
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
    numbers = compare(cell, weights, done, seed, env["control"])
    print(f"reference: {time.monotonic() - t0:.2f} s over "
          f"{numbers['compared_tokens']} served tokens of "
          f"{numbers['compared_requests']} requests", flush=True)
    correct = judge(numbers, cell.limits)

    rt = served.batcher.rt
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
        "wire_bytes_step": (sum(rt.decode_hop_bytes(
            config["serving"]["max_slots"])) if rt is not None else None),
        "device_kind": jax.devices()[0].device_kind, "numbers": numbers,
    }
