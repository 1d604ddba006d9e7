"""Micro-batch pipelined split schedule: identity, validation, counters.

The pipelined schedule (``PipelineConfig(num_microbatches=M)``) is a latency
optimization, never a numerics change: every entry point that runs through
``run_pipeline_stages_microbatched`` / ``_carry_microbatched`` must produce
BIT-identical outputs to the sequential schedule at any M, because each
µ-batch's rows see exactly the same per-row compute and the same per-row
codec math (pipelining is refused outright for codecs whose scales couple
rows across the batch). That identity is asserted here for forward, the
contiguous-cache decode loop, and the batcher's ragged paged decode — at
num_microbatches in {1, 2, 4} per the ISSUE acceptance — alongside the
schedule's own bookkeeping (per-µ-batch fault counters, occupancy/bubble
accounting) and the validation surface (divisibility, batch-variant codecs,
stage-only mesh).

Also here (ISSUE satellite): >= 3-stage DECODE coverage — ``generate_split``
at cuts=(1, 3) with mixed codecs, clean and through a retrying faulty link,
token-identical to single-device ``generate``, and the batcher's paged decode
against the single-device round-trip oracle (forward-only 3-stage coverage
lives in test_split.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edgellm_tpu.codecs.faults import FaultConfig, LinkPolicy
from edgellm_tpu.models import tiny_config, init_params, forward
from edgellm_tpu.parallel import (PipelineConfig, SplitConfig, SplitRuntime,
                                  make_stage_mesh)
from edgellm_tpu.serve import generate
from edgellm_tpu.serve.batching import BatchingConfig, ContinuousBatcher
from edgellm_tpu.serve.decode import generate_split

CFG = tiny_config("qwen2", num_layers=6, hidden_size=32, num_heads=4,
                  vocab_size=128)
SPLIT = SplitConfig(cuts=(1, 3),
                    hop_codecs=("int8_per_token", "int8_per_token"))
MIXED = SplitConfig(cuts=(1, 3), hop_codecs=("int4_global", "int8_per_token"))


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(1))


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 3:
        pytest.skip("needs >= 3 devices (spoofed CPU mesh)")
    return make_stage_mesh(3)


@pytest.fixture(scope="module")
def ids():
    rng = np.random.default_rng(5)
    return jnp.asarray(rng.integers(0, CFG.vocab_size, (4, 12)))


# ---------- PipelineConfig ----------

def test_pipeline_config_validation():
    with pytest.raises(ValueError):
        PipelineConfig(num_microbatches=0)
    pc = PipelineConfig(num_microbatches=4)
    assert pc.enabled and not PipelineConfig().enabled
    assert pc.validate_batch(8) == 2
    with pytest.raises(ValueError):
        pc.validate_batch(6)
    with pytest.raises(ValueError):
        pc.validate_batch(0)


def test_pipeline_summary_accounting():
    s = PipelineConfig(num_microbatches=4).summary(n_stages=3)
    # T = M + n - 1 unroll steps; each stage busy for M of them
    assert s["unroll_steps"] == 6
    assert s["stage_occupancy"] == pytest.approx([4 / 6] * 3)
    assert s["bubble_fraction_schedule"] == pytest.approx(2 / 6)
    assert s["bubble_fraction_sequential"] == pytest.approx(2 / 3)
    # more µ-batches strictly shrink the schedule bubble
    s8 = PipelineConfig(num_microbatches=8).summary(n_stages=3)
    assert s8["bubble_fraction_schedule"] < s["bubble_fraction_schedule"]


def test_pipeline_validation_errors(params, mesh):
    # batch-variant codec: per-batch scales would change per-µ-batch
    with pytest.raises(ValueError, match="batch"):
        SplitRuntime(CFG, MIXED, mesh,
                     pipeline=PipelineConfig(num_microbatches=2))
    # batch not divisible by the µ-batch count
    rt = SplitRuntime(CFG, SPLIT, mesh,
                      pipeline=PipelineConfig(num_microbatches=4))
    placed = rt.place_params(params)
    bad = jnp.zeros((3, 8), jnp.int32)
    with pytest.raises(ValueError, match="multiple"):
        rt.forward(placed, bad)
    # data-parallel mesh: µ-batching and batch-sharding both slice the batch
    dmesh = make_stage_mesh(2, n_data=2)
    with pytest.raises(ValueError):
        SplitRuntime(CFG, SplitConfig(cuts=(3,),
                                      hop_codecs=("int8_per_token",)),
                     dmesh, pipeline=PipelineConfig(num_microbatches=2))


# ---------- tentpole identity: pipelined == sequential ----------

@pytest.mark.parametrize("m", [1, 2, 4])
def test_pipelined_forward_bit_identical(params, mesh, ids, m):
    base = SplitRuntime(CFG, SPLIT, mesh)
    rt = SplitRuntime(CFG, SPLIT, mesh,
                      pipeline=PipelineConfig(num_microbatches=m))
    placed = base.place_params(params)
    np.testing.assert_array_equal(
        np.asarray(base.forward(placed, ids)),
        np.asarray(rt.forward(placed, ids)))


@pytest.mark.parametrize("m", [1, 2, 4])
def test_pipelined_generate_split_token_identical(params, mesh, ids, m):
    base = SplitRuntime(CFG, SPLIT, mesh)
    rt = SplitRuntime(CFG, SPLIT, mesh,
                      pipeline=PipelineConfig(num_microbatches=m))
    placed = base.place_params(params)
    want = np.asarray(generate_split(base, placed, ids, 8, capacity=20))
    st: dict = {}
    got = np.asarray(generate_split(rt, placed, ids, 8, capacity=20,
                                    stats=st))
    np.testing.assert_array_equal(want, got)
    if m > 1:
        assert st["pipeline"]["num_microbatches"] == m
        assert st["pipeline"]["enabled"]


def test_pipelined_paged_decode_token_identical(params, mesh):
    bcfg = BatchingConfig(max_slots=4, num_pages=16, page_size=4,
                          pages_per_slot=6)
    results = []
    for pipe in (None, PipelineConfig(num_microbatches=2),
                 PipelineConfig(num_microbatches=4)):
        rt = SplitRuntime(CFG, SPLIT, mesh, pipeline=pipe)
        bat = ContinuousBatcher(CFG, params, bcfg, split_runtime=rt,
                                placed_params=rt.place_params(params))
        rng = np.random.default_rng(3)
        for i in range(4):
            bat.submit(rng.integers(1, CFG.vocab_size,
                                    size=4 + i).astype(np.int32),
                       6, rng_seed=i)
        results.append({k: v.tolist() for k, v in bat.run().items()})
    assert results[0] == results[1] == results[2]


# ---------- per-µ-batch fault counters ----------

def test_microbatch_fault_counters(params, mesh, ids):
    m = 2
    rt = SplitRuntime(CFG, SPLIT, mesh,
                      faults=FaultConfig(drop_rate=0.3, seed=0),
                      policy=LinkPolicy(max_retries=5),
                      pipeline=PipelineConfig(num_microbatches=m))
    placed = rt.place_params(params)
    for step in range(4):
        rt.forward(placed, ids, fault_step=step)
    per_mb = rt.microbatch_counters()
    totals = rt.link_counters()
    assert set(per_mb) == set(totals)
    for name, rows in per_mb.items():
        assert rows.shape == (m, len(SPLIT.cuts))
        # the µ-batch rows decompose the aggregate stream exactly
        np.testing.assert_array_equal(rows.sum(axis=0),
                                      np.asarray(totals[name]))
    # every µ-batch genuinely hopped: 4 forwards x 2 hops each
    np.testing.assert_array_equal(per_mb["hops"], np.full((m, 2), 4))


def test_microbatch_fault_replay_deterministic(params, mesh, ids):
    outs = []
    for _ in range(2):
        rt = SplitRuntime(CFG, SPLIT, mesh,
                          faults=FaultConfig(drop_rate=0.3, seed=0),
                          policy=LinkPolicy(max_retries=5),
                          pipeline=PipelineConfig(num_microbatches=2))
        placed = rt.place_params(params)
        outs.append(np.asarray(rt.forward(placed, ids, fault_step=1)))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_unpipelined_runtime_has_no_microbatch_counters(params, mesh):
    rt = SplitRuntime(CFG, SPLIT, mesh,
                      faults=FaultConfig(drop_rate=0.3, seed=0),
                      policy=LinkPolicy(max_retries=5))
    assert rt.microbatch_counters() is None


def test_pipelined_eval_pads_partial_tail_group(params, mesh):
    """7 windows at window_batch=2 leave a 1-window tail group: the eval must
    pad it up to the µ-batch grid (zero loss weight) instead of handing the
    pipelined schedule an indivisible batch. Scored-token totals must match
    the sequential run exactly; NLL to float tolerance (the padded window's
    rows compute in a different batch shape, same as data-axis padding)."""
    from edgellm_tpu.eval.split_eval import run_split_eval

    rng = np.random.default_rng(7)
    toks = rng.integers(0, CFG.vocab_size, size=80).astype(np.int32)
    kw = dict(cuts=(1, 3), hop_codecs=("int8_per_token",) * 2,
              max_length=16, stride=8, window_batch=2, time_hops=False)
    seq = run_split_eval(CFG, params, toks, mesh=mesh, **kw)
    pipe = run_split_eval(CFG, params, toks, mesh=mesh,
                          pipeline=PipelineConfig(num_microbatches=2), **kw)
    assert pipe["n_tokens"] == seq["n_tokens"]
    assert pipe["chunks"] == seq["chunks"]
    assert pipe["pad_fraction"] > 0.0  # the tail really was padded
    assert pipe["total_nll"] == pytest.approx(seq["total_nll"], rel=1e-5)
    assert pipe["pipeline"]["num_microbatches"] == 2


def test_pipelined_eval_refuses_batch_variant_ladder(params, mesh):
    from edgellm_tpu.eval.split_eval import run_split_eval

    toks = np.arange(64, dtype=np.int32) % CFG.vocab_size
    with pytest.raises(ValueError, match="ladder"):
        run_split_eval(CFG, params, toks, mesh=mesh,
                       cuts=(1, 3), hop_codecs=("int8_per_token",) * 2,
                       max_length=16, stride=8, window_batch=2,
                       faults=FaultConfig(drop_rate=0.1, seed=0),
                       link_policy=LinkPolicy(max_retries=1,
                                              tiers=("int4_global",)),
                       pipeline=PipelineConfig(num_microbatches=2))


# ---------- satellite: >= 3-stage decode vs single-device generate ----------

def test_three_stage_generate_split_matches_generate(params, mesh):
    rng = np.random.default_rng(5)
    ids1 = jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 8)))
    want = np.asarray(generate(CFG, params, ids1, 12, capacity=20))
    rt = SplitRuntime(CFG, MIXED, mesh)
    got = np.asarray(generate_split(rt, rt.place_params(params), ids1, 12,
                                    capacity=20))
    np.testing.assert_array_equal(want, got)


def test_three_stage_generate_split_retrying_faulty_link(params, mesh):
    """A lossy-but-retried link at cuts=(1, 3): every drop recovers within
    the retry budget (seed-pinned), so the tokens stay identical to the
    single-device greedy decode while the counters prove real retries."""
    rng = np.random.default_rng(5)
    ids1 = jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 8)))
    want = np.asarray(generate(CFG, params, ids1, 12, capacity=20))
    rt = SplitRuntime(CFG, MIXED, mesh,
                      faults=FaultConfig(drop_rate=0.3, seed=0),
                      policy=LinkPolicy(max_retries=5))
    got = np.asarray(generate_split(rt, rt.place_params(params), ids1, 12,
                                    capacity=20))
    c = {k: np.asarray(v) for k, v in rt.link_counters().items()}
    assert c["retried"].sum() > 0 and c["recovered"].sum() > 0
    assert c["substituted"].sum() == 0  # parity below is only meaningful then
    np.testing.assert_array_equal(want, got)


def _roundtrip_greedy(params, split, prompt, n_new):
    """The split runtime's single-device oracle: a greedy KV-cached decode
    that applies each hop codec's encode -> decode round trip after its cut
    layer. For row-local codecs this is mathematically what the sharded
    runtime computes for the stream, whatever else rides in the ragged step —
    so token equality is an invariant, not a hope that a lossy hop never
    flips an argmax against the UNQUANTIZED model."""
    from edgellm_tpu.models.transformer import decode_step, prefill

    bfn = split.roundtrip_boundary_fn()
    logits, cache = jax.jit(
        lambda p, x: prefill(CFG, p, x, prompt.size + n_new,
                             boundary_fn=bfn))(params,
                                               jnp.asarray(prompt)[None])
    step = jax.jit(lambda p, c, t: decode_step(CFG, p, c, t,
                                               boundary_fn=bfn))
    toks = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(n_new - 1):
        logits, cache = step(params, cache, jnp.asarray([toks[-1]]))
        toks.append(int(jnp.argmax(logits[0])))
    return np.asarray(toks, np.int32)


def test_three_stage_paged_decode_matches_roundtrip_oracle(params, mesh):
    """Four concurrent streams through the batcher's ragged paged decode on a
    3-stage split with LOSSY mixed-precision hops (ternary then int4, both
    per-token so every row's payload is its own) must equal the round-trip
    oracle token for token."""
    split = SplitConfig(cuts=(1, 3),
                        hop_codecs=("ternary_per_token", "int4_per_token"))
    bcfg = BatchingConfig(max_slots=4, num_pages=20, page_size=4,
                          pages_per_slot=6)
    rt = SplitRuntime(CFG, split, mesh)
    bat = ContinuousBatcher(CFG, params, bcfg, split_runtime=rt,
                            placed_params=rt.place_params(params))
    rng = np.random.default_rng(9)
    prompts = {}
    for i in range(4):
        p = rng.integers(1, CFG.vocab_size, size=4 + i).astype(np.int32)
        prompts[bat.submit(p, 6, rng_seed=i)] = p
    results = bat.run()
    for sid, p in prompts.items():
        np.testing.assert_array_equal(_roundtrip_greedy(params, split, p, 6),
                                      np.asarray(results[sid]))
