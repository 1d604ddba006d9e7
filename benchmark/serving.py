"""The served system under test and the stamps the benchmark takes around it.

From the program this takes ``ContinuousBatcher`` (the engine that
``ServeFront`` wraps) with its ``submit``/``step``/``report``, the split
runtime for a configuration that is cut across chips, and nothing else. The
weights, the traffic, the clock and every stamp are the benchmark's own.

A client sees a token when the ``step()`` that produced it returns: the
batcher has no callback, so that is the stamp. A stream's first token comes
from the prefill inside the step that admits it, and that same step decodes
its second, so those two carry one stamp.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Request:
    idx: int
    prompt: np.ndarray        # (P,) int32
    answer_len: int
    temperature: float
    rng_seed: int
    due: float                # seconds from the window's opening; < 0: before
    counted: bool = True      # due inside the window
    submit_t: float | None = None
    stamps: list = field(default_factory=list)   # one per served token
    tokens: np.ndarray | None = None              # set when it has finished
    failed: str | None = None
    caller: int = -1          # closed loop: the caller that sent it


def model_config(config: dict):
    from edgellm_tpu.models.configs import ModelConfig

    return ModelConfig(
        family=config["model_type"], vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        tie_word_embeddings=config["tie_word_embeddings"])


def build_batcher(config: dict, weights: dict):
    """The configuration's serving engine over the benchmark's weights: one
    chip, or the split runtime over ``len(cuts) + 1`` stage devices."""
    import jax.numpy as jnp

    from edgellm_tpu.serve.batching import BatchingConfig, ContinuousBatcher

    cfg = model_config(config)
    s = config["serving"]
    bcfg = BatchingConfig(page_size=s["page_size"], num_pages=s["num_pages"],
                          max_slots=s["max_slots"],
                          pages_per_slot=s["pages_per_slot"],
                          cache_dtype=jnp.dtype(config["torch_dtype"]))
    split = config.get("split")
    if not split:
        return ContinuousBatcher(cfg, weights, bcfg)
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, make_stage_mesh

    rt = SplitRuntime(cfg, SplitConfig(cuts=tuple(split["cuts"]),
                                       hop_codecs=tuple(split["hop_codecs"])),
                      make_stage_mesh(len(split["cuts"]) + 1))
    return ContinuousBatcher(cfg, weights, bcfg, split_runtime=rt,
                             placed_params=rt.place_params(weights))


class Served:
    """Requests in, stamped tokens out, through one batcher."""

    def __init__(self, batcher, clock=time.monotonic):
        self.batcher = batcher
        self.clock = clock
        self.t_open = clock()        # moved by open_in() to the window's zero
        self.live: dict[int, Request] = {}
        self.done: list[Request] = []
        self.steps = 0
        self.annotate = None         # set to a span factory in a traced run

    def now(self) -> float:
        return self.clock() - self.t_open

    def open_in(self, delta: float) -> None:
        """Put the zero of every later stamp ``delta`` seconds from now, and
        move the stamps already taken (set-up's) onto the same zero."""
        zero = self.clock() + delta
        shift = self.t_open - zero
        for req in self.live.values():
            req.stamps = [t + shift for t in req.stamps]
            if req.submit_t is not None:
                req.submit_t += shift
        self.t_open = zero

    def _span(self, name):
        return self.annotate(name) if self.annotate else contextlib.nullcontext()

    def submit(self, req: Request) -> None:
        req.submit_t = self.now()
        with self._span("bench.submit"):
            try:
                sid = self.batcher.submit(
                    req.prompt, req.answer_len, temperature=req.temperature,
                    rng_seed=req.rng_seed)
            except ValueError as e:  # refused: counts as failed, has no TTFT
                req.failed = repr(e)
                self.done.append(req)
                return
        self.live[sid] = req

    def step(self) -> list[Request]:
        """One batcher step; stamps every token it made visible. Returns the
        requests that finished in it."""
        with self._span("bench.step"):
            self.batcher.step()
        t = self.now()
        self.steps += 1
        finished = []
        streams = self.batcher._streams  # no public per-step view of tokens
        for sid, req in list(self.live.items()):
            st = streams[sid]
            new = len(st.tokens) - len(req.stamps)
            if new:
                req.stamps.extend([t] * new)
            if st.status == "finished":
                req.tokens = np.asarray(self.batcher.pop_result(sid))
                del self.live[sid]
                self.done.append(req)
                finished.append(req)
        return finished

    def run_until_idle(self, max_steps: int = 100000) -> None:
        for _ in range(max_steps):
            if not self.live:
                return
            self.step()
        raise RuntimeError("streams did not finish")

    def pool_live_share(self) -> float:
        """Live KV positions over the pool's capacity (nothing is shared in
        these cells, so the sum of the slots' lengths counts each once)."""
        pool = self.batcher.pool
        return pool.live_tokens / pool.token_capacity


def prompt_tokens(rng: np.random.Generator, length: int,
                  vocab: int) -> np.ndarray:
    return rng.integers(1, vocab, size=length, dtype=np.int64).astype(np.int32)


def exact_counts(weights: list, n: int) -> list:
    """``n`` split by ``weights`` into whole counts that sum to ``n`` (largest
    remainders), the same for every seed."""
    w = np.asarray(weights, np.float64)
    raw = w / w.sum() * n
    base = np.floor(raw).astype(int)
    order = np.argsort(-(raw - base), kind="stable")
    base[order[: n - int(base.sum())]] += 1
    return [int(x) for x in base]


def traffic_shapes(traffic: dict) -> dict:
    """The shapes a serving mix can produce: what set-up warms and what the
    reference pads to."""
    return {"prompt_lens": [int(v) for v in traffic["prompt"]["values"]],
            "answer_max": int(max(traffic["answer"]["values"])),
            "temperatures": [float(v) for v in
                             traffic["temperature"]["values"]]}


def warm_up(served: Served, traffic: dict, vocab: int) -> None:
    """The first call of every shape this cell's traffic uses: one prefill per
    prompt length, token-0 sampling at each temperature, the decode step."""
    shp = traffic_shapes(traffic)
    prompt_lens, temperatures = shp["prompt_lens"], shp["temperatures"]
    rng = np.random.default_rng(0)
    for i, p in enumerate(sorted(set(prompt_lens))):
        temp = temperatures[i % len(temperatures)]
        served.submit(Request(-1, prompt_tokens(rng, p, vocab), 2, temp,
                              i, -1e9, counted=False))
    for j, temp in enumerate(temperatures):  # every temperature at least once
        served.submit(Request(-1, prompt_tokens(rng, min(prompt_lens), vocab),
                              2, temp, 100 + j, -1e9, counted=False))
    served.run_until_idle()
    served.done.clear()


def preload(served: Served, plan: dict) -> None:
    """Set-up admits the streams that are in flight when the window opens."""
    for req in plan["preload"]:
        served.submit(req)
    while served.batcher._waiting:
        served.step()
