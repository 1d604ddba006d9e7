"""The batcher's sampling keys: one host-built table a step.

A stream's key is fixed at ``submit()``; its ``(2,) uint32`` data is kept on
the host and copied into one ``(max_slots, 2)`` table a step, which the
compiled step wraps back into typed keys. Held here, on the CPU with a toy
model: the host-made row is JAX's own key data for any seed a caller may
pass; tokens equal the per-stream reference drawn with real
``jax.random.key(seed)`` keys on the fp, quantized-tier and split paths,
through an eviction and a re-admit; and a step launches the same executables
whatever ``max_slots`` is.
"""
import dataclasses
import glob

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edgellm_tpu.models import init_params, tiny_config
from edgellm_tpu.models.paged_kv import PagedKVCache, paged_decode_step
from edgellm_tpu.serve.batching import (BatchingConfig, ContinuousBatcher,
                                        _key_data)
from edgellm_tpu.serve.decode import (_prefill_jit, _sample, generate,
                                      generate_split)

CFG = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4,
                  vocab_size=128)
# the geometry tests/test_batching.py uses, so the ragged step is shared
BCFG = BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                      pages_per_slot=4)
CODEC = "int8_per_channel"
SEEDS = [0, 1, 7, 2**31 - 1, 2**31 + 5, 2**32 - 1, 2**32 + 3, 2**40 + 9,
         -1, -5]


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(1))


@pytest.fixture(scope="module")
def split_rt(params):
    from edgellm_tpu.parallel import (SplitConfig, SplitRuntime,
                                      make_stage_mesh)

    rt = SplitRuntime(CFG, SplitConfig(cuts=(2,),
                                       hop_codecs=("int8_per_token",)),
                      make_stage_mesh(2))
    return rt, rt.place_params(params)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, size=n).astype(np.int32)


def _batcher(path, params, request, **geometry):
    """A fresh batcher on one of the step's three launch branches."""
    bcfg = BatchingConfig(**geometry) if geometry else BCFG
    if path == "split":
        rt, placed = request.getfixturevalue("split_rt")
        return ContinuousBatcher(CFG, params, bcfg, split_runtime=rt,
                                 placed_params=placed)
    if path == "quant":
        bcfg = dataclasses.replace(bcfg, kv_codec=CODEC)
    return ContinuousBatcher(CFG, params, bcfg)


# ---------------------------------------------------------------------------
# the host-made row is JAX's key data
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_stream_key_row_is_jax_key_data(params, seed):
    bat = ContinuousBatcher(CFG, params, BCFG)
    sid = bat.submit(_prompt(4), 2, temperature=0.7, rng_seed=seed)
    st = bat._streams[sid]
    want = np.asarray(jax.random.key_data(jax.random.key(seed)))
    assert st.key_data.dtype == want.dtype == np.uint32
    np.testing.assert_array_equal(st.key_data, want)
    np.testing.assert_array_equal(
        st.key_data, np.asarray(jax.random.key_data(st.key)))
    # a free slot's row is key 0's, as the per-slot filler key was
    np.testing.assert_array_equal(
        bat._free_key_rows,
        np.tile(np.asarray(jax.random.key_data(jax.random.key(0))),
                (BCFG.max_slots, 1)))


def test_key_data_is_read_from_jax_where_the_formula_does_not_hold():
    """With x64 on a seed keeps its high word, so the row comes from JAX."""
    seed = 2**40 + 9
    with jax.enable_x64(True):
        got = _key_data(seed)
        want = np.asarray(jax.random.key_data(jax.random.key(seed)))
    np.testing.assert_array_equal(got, want)
    assert got.tolist() == [256, 9] != _key_data(seed).tolist()


# ---------------------------------------------------------------------------
# tokens: the reference draws with real jax.random.key(seed) keys
# ---------------------------------------------------------------------------

STREAMS = [  # mixed lengths and temperatures; seeds over 2**31 and negative
    dict(prompt=_prompt(5, 1), max_new=6, temp=0.7, seed=2**31 + 5),
    dict(prompt=_prompt(9, 2), max_new=7, temp=0.0, seed=7),
    dict(prompt=_prompt(13, 3), max_new=8, temp=1.1, seed=-5),
    dict(prompt=_prompt(6, 4), max_new=8, temp=0.9, seed=2**40 + 9),
]


def _solo_fp(params, s, _request):
    return np.asarray(generate(
        CFG, params, jnp.asarray(s["prompt"])[None], s["max_new"],
        capacity=BCFG.span, temperature=s["temp"],
        rng_key=jax.random.key(s["seed"])))[0]


def _solo_split(params, s, request):
    rt, placed = request.getfixturevalue("split_rt")
    return np.asarray(generate_split(
        rt, placed, jnp.asarray(s["prompt"])[None], s["max_new"],
        capacity=BCFG.span, temperature=s["temp"],
        rng_key=jax.random.key(s["seed"])))[0]


_quant_step = jax.jit(paged_decode_step, static_argnames=("cfg",))


def _solo_quant(params, s, _request):
    """One stream alone over a quantized pool of the batcher's geometry,
    stepped by hand: prefill, adopt (which packs the rows), then the quant
    step and the single-row sampler with ``fold_in(key(seed), t)``."""
    pool = PagedKVCache(CFG, num_pages=BCFG.num_pages,
                        page_size=BCFG.page_size, max_slots=BCFG.max_slots,
                        pages_per_slot=BCFG.pages_per_slot,
                        dtype=BCFG.cache_dtype, kv_codec=CODEC)
    n = len(s["prompt"])
    key = jax.random.key(s["seed"])
    logits, cache = _prefill_jit(CFG, params, jnp.asarray(s["prompt"])[None],
                                 BCFG.span, None)
    toks = [int(_sample(logits, jax.random.fold_in(key, 0), s["temp"])[0])]
    slot = pool.alloc_slot()
    pool.adopt(slot, cache.k[:, 0, :n], cache.v[:, 0, :n], n)
    while len(toks) < s["max_new"]:
        pool.ensure_writable(slot, int(pool.lengths[slot]) + 1)
        token_ids = np.zeros((BCFG.max_slots,), np.int32)
        token_ids[slot] = toks[-1]
        table, lengths = pool.device_tables()
        logits, pool.pool = _quant_step(CFG, params, pool.pool, table,
                                        lengths, jnp.asarray(token_ids))
        toks.append(int(_sample(logits[slot][None],
                                jax.random.fold_in(key, len(toks)),
                                s["temp"])[0]))
        pool.lengths[slot] += 1
    return np.asarray(toks, np.int32)


@pytest.mark.parametrize("path,solo", [("fp", _solo_fp),
                                       ("quant", _solo_quant),
                                       ("split", _solo_split)])
def test_mixed_batch_with_evict_and_readmit_matches_solo(params, request,
                                                         path, solo):
    bat = _batcher(path, params, request)
    sids = [bat.submit(s["prompt"], s["max_new"], temperature=s["temp"],
                       rng_seed=s["seed"]) for s in STREAMS]
    for _ in range(3):
        bat.step()
    bat.evict(sids[0])            # the 2**31 + 5 seed, sampled at 0.7
    bat.evict(sids[2])
    results = bat.run()
    assert bat.report()["evicted"] == 2
    for sid, s in zip(sids, STREAMS):
        np.testing.assert_array_equal(results[sid], solo(params, s, request))


# ---------------------------------------------------------------------------
# a step's launches do not depend on max_slots
# ---------------------------------------------------------------------------


def _launches_of_a_full_step(bat, max_slots, trace_dir):
    """Executable launches (name -> count) inside one ``step()`` with every
    slot running, read from a profiler capture: jaxlib stamps each dispatch
    of a jitted function, eager primitives included, as
    ``PjitFunction(<name>)``."""
    from jax.profiler import ProfileData

    for i in range(max_slots):
        bat.submit(_prompt(5, i), 8, temperature=0.5 * (i % 3),
                   rng_seed=2**31 + i)
    bat.step()                    # admits all
    bat.step()
    assert len(bat._slot_to_sid) == max_slots
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        assert bat.step() == max_slots
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    launches = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("PjitFunction("):
                    launches[ev.name] = launches.get(ev.name, 0) + 1
    return launches


@pytest.mark.parametrize("path", ["fp", "quant", "split"])
def test_step_launches_the_same_executables_at_4_and_64_slots(
        params, request, tmp_path, path):
    seen = {}
    for ms in (4, 64):
        bat = _batcher(path, params, request, page_size=8,
                       num_pages=1 + 2 * ms, max_slots=ms, pages_per_slot=2)
        seen[ms] = _launches_of_a_full_step(bat, ms, tmp_path / str(ms))
    assert seen[4] == seen[64]
    # the merge that feeds the tokens in flight, the step (and on the split
    # path its sampler): nothing made per slot
    assert "PjitFunction(_fed_tokens)" in seen[4]
    assert 2 <= len(seen[4]) <= 3, seen[4]
