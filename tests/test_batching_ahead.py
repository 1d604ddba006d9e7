"""The batcher one launch ahead of its reads: ``step()`` launches step N+1
before it reads step N's tokens, which stay on the device in between.

What is held here, on the CPU at toy sizes: the tokens every stream is served
are the tokens of the old order (a drain after every call: launch, sync,
commit, which is what ``step()`` did before) on each of the step's launch
branches — plain pages, a state store, a window ring, latent rows, the split
runtime over four stages — and ``generate()``'s on the plain one, with streams
admitted mid-flight, ending by count at one, two and many tokens, a
prefix-shared admit whose first write forks a page, an eviction under page
pressure while a step is in flight, and every call that must find the tokens
on the host (``prefill_hold``, ``gather_rows``, ``release_handoff``,
``checkpoint_stream``, ``restore_stream``, ``discard``, ``evict``) made
between two calls of ``step()``. ``Stream.tokens`` never holds an unread
token; ``steps_ahead`` counts the launches that found a step unread; with the
tracer on the launch of step N+1 lies before the read of step N; the watchdog
arms at a launch and checks at that step's commit; a slot that keeps its
stream but rides no more goes to the step as a free one; the tables a step is
launched with are copies the host may go on changing.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edgellm_tpu import obs
from edgellm_tpu.models import init_params, tiny_config
from edgellm_tpu.models.configs import (tiny_hybrid_config,
                                        tiny_mellum_config,
                                        tiny_mistral4_config)
from edgellm_tpu.models.paged_kv import OutOfPages, PrefixCacheConfig
from edgellm_tpu.serve import batching
from edgellm_tpu.serve.batching import (IN_FLIGHT, BatchingConfig,
                                        ContinuousBatcher)
from edgellm_tpu.serve.decode import generate
from edgellm_tpu.serve.recovery import DecodeTimeout

CFG = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4,
                  vocab_size=128)
# the geometry tests/test_batching.py uses, so the ragged step is shared
BCFG = BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                      pages_per_slot=4)
#: one toy configuration a launch branch of ``_step_phases``
BRANCHES = {"plain": CFG,
            "state": tiny_hybrid_config(),
            "ring": tiny_mellum_config(sliding_window=10),
            "latent": tiny_mistral4_config(),
            "split4": tiny_config("qwen2", num_layers=8, hidden_size=32,
                                  num_heads=4, vocab_size=128)}


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(1))


@pytest.fixture(scope="module")
def branch_params():
    return {name: init_params(cfg, jax.random.key(1))
            for name, cfg in BRANCHES.items()}


@pytest.fixture(scope="module")
def split4(branch_params):
    from edgellm_tpu.parallel import (SplitConfig, SplitRuntime,
                                      make_stage_mesh)

    rt = SplitRuntime(BRANCHES["split4"],
                      SplitConfig(cuts=(2, 4, 6),
                                  hop_codecs=("int8_per_token",) * 3),
                      make_stage_mesh(4))
    return rt, rt.place_params(branch_params["split4"])


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    obs.get_tracer().clear()
    yield
    obs.disable()
    obs.get_tracer().clear()


def _prompt(n, seed=0, vocab=CFG.vocab_size):
    return np.random.default_rng(seed).integers(1, vocab, size=n).astype(
        np.int32)


def _solo(params, prompt, max_new, temp=0.0, seed=0, capacity=BCFG.span):
    out = generate(CFG, params, jnp.asarray(prompt)[None], max_new,
                   capacity=capacity, temperature=temp,
                   rng_key=jax.random.key(seed))
    return np.asarray(out)[0]


class _FeedTap:
    """``batching._feed_jit`` with the ids each call was handed kept."""

    def __init__(self, inner):
        self.inner, self.ids = inner, []
        self._cache_size = inner._cache_size

    def __call__(self, token_ids, prev_toks):
        self.ids.append(np.asarray(token_ids))
        return self.inner(token_ids, prev_toks)


class _Tok0Tap:
    """``batching._tok0_feed_jit`` with what each call set a token 0 into."""

    def __init__(self, inner):
        self.inner, self.into = inner, []

    def __call__(self, toks, slot, tok0):
        self.into.append(toks)
        return self.inner(toks, slot, tok0)

    def onto_nothing(self, b):
        """The launches of ``b`` that fed a token 0 and no step's tokens."""
        return sum(toks is b._no_toks for toks in self.into)


def _tokens_are_read(b):
    """Between two calls: every token in a list was read off the device, and
    the host counts exactly the one step that may be in flight."""
    riding = set()
    if b._inflight is not None:
        riding = {st.sid for st in b._inflight.riders}
    for st in b._streams.values():
        assert st.pending == (1 if st.sid in riding else 0), st.sid
        assert st.t == len(st.tokens) + st.pending
        assert all(isinstance(t, int) and 0 <= t < b.cfg.vocab_size
                   for t in st.tokens), (st.sid, st.tokens)
        assert len(st.tokens) <= st.max_new_tokens
        if st.status == "finished":
            assert st.pending == 0 and len(st.tokens) == st.max_new_tokens
        if st.status == "running":
            # the pool counts the row the step in flight writes
            assert b.pool.lengths[st.slot] == b._cache_len(st)


def _serve(b, script, ahead=True, max_calls=400):
    """Drive ``b`` by ``script``, ``{call index: [(prompt, max_new, temp,
    seed), ...]}`` submitted before that call, until nothing is left; in the
    old order when not ``ahead``. Returns the streams' tokens in the order
    they were submitted."""
    sids, call = [], 0
    while call <= max(script) or b._waiting or b._slot_to_sid:
        for prompt, max_new, temp, seed in script.get(call, ()):
            sids.append(b.submit(prompt, max_new, temperature=temp,
                                 rng_seed=seed))
        b.step()
        if not ahead:
            b._drain()
            assert b._inflight is None
        _tokens_are_read(b)
        call += 1
        assert call < max_calls
    assert b._inflight is None
    return [b.results[s].tolist() for s in sids]


def _script(vocab, long=9):
    """More streams than three slots hold, greedy and sampled, admitted
    before the first call and mid-flight, ending at 1, 2 and many tokens."""
    def spec(i, n, max_new):
        return (_prompt(n, 10 + i, vocab), max_new, 0.6 * (i % 2), i)

    return {0: [spec(0, 5, long), spec(1, 9, 2), spec(2, 7, 1)],
            2: [spec(3, 6, 4)],
            3: [spec(4, 11, long - 2), spec(5, 4, 3)],
            7: [spec(6, 8, 2)]}


def _branch_batcher(name, branch_params, request):
    bcfg = BatchingConfig(page_size=4, num_pages=61, max_slots=3,
                          pages_per_slot=8)
    if name == "split4":
        rt, placed = request.getfixturevalue("split4")
        return ContinuousBatcher(BRANCHES[name], branch_params[name], bcfg,
                                 split_runtime=rt, placed_params=placed)
    return ContinuousBatcher(BRANCHES[name], branch_params[name], bcfg)


# ---------------------------------------------------------------------------
# the served tokens are the old order's, on every launch branch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(BRANCHES))
def test_tokens_are_the_old_orders_on_every_launch_branch(
        name, branch_params, request, monkeypatch):
    tap, tok0_tap = _FeedTap(batching._feed_jit), _Tok0Tap(
        batching._tok0_feed_jit)
    fed = tap.ids
    monkeypatch.setattr(batching, "_feed_jit", tap)
    monkeypatch.setattr(batching, "_tok0_feed_jit", tok0_tap)
    vocab = BRANCHES[name].vocab_size
    old = _branch_batcher(name, branch_params, request)
    want = _serve(old, _script(vocab), ahead=False)
    assert old.report()["steps_ahead"] == 0
    # the first launch's warm-up, and the launches behind an admission: the
    # token 0 of a call's last admission is fed from the device in any order
    assert len(fed) - 1 == tok0_tap.onto_nothing(old) >= 4
    fed.clear()
    new = _branch_batcher(name, branch_params, request)
    got = _serve(new, _script(vocab))
    assert got == want
    assert [len(t) for t in got] == [9, 2, 1, 4, 7, 3, 2]
    assert len({t for toks in got for t in toks}) > 4   # not one token
    rep = new.report()
    assert rep["finished"] == 7 and rep["evicted"] == 0
    # every launch that found a step unread (or, with none in flight, a
    # token 0) merged on the device, and the host handed it no id for a slot
    # whose token was in flight
    assert rep["steps_ahead"] == len(fed) - 1 - tok0_tap.onto_nothing(new)
    assert rep["steps_ahead"] > 0.5 * rep["steps"]
    assert any((ids == IN_FLIGHT).any() for ids in fed[1:])


def test_plain_tokens_are_generates_with_streams_admitted_mid_flight(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    script = _script(CFG.vocab_size, long=12)
    got = _serve(b, script)
    specs = [s for call in sorted(script) for s in script[call]]
    for toks, (prompt, max_new, temp, seed) in zip(got, specs):
        np.testing.assert_array_equal(
            toks, _solo(params, prompt, max_new, temp, seed))
    assert b.report()["jit_misses"] <= 2


@pytest.mark.parametrize("max_new", [1, 2, 3, 6])
def test_a_stream_ends_by_count_and_keeps_its_slot_until_its_token_is_read(
        params, max_new):
    b = ContinuousBatcher(CFG, params, BCFG)
    idle_seen, inner = [], b.pool.device_tables
    b.pool.device_tables = lambda idle=(): idle_seen.append(list(idle)) \
        or inner(idle)
    long = b.submit(_prompt(6), 8, rng_seed=1)
    sid = b.submit(_prompt(7, 1), max_new, temperature=0.7, rng_seed=2)
    calls = 0
    while sid not in b.results:
        b.step()
        calls += 1
        _tokens_are_read(b)
    # token 0 is the prefill's; every other one is launched in one call and
    # read in the next
    assert calls == (1 if max_new == 1 else max_new)
    np.testing.assert_array_equal(
        b.results[sid], _solo(params, _prompt(7, 1), max_new, 0.7, 2))
    if max_new > 1:
        # in the call that read its last token it rode no step: its slot went
        # out as a free one, and only then was freed
        assert idle_seen[-1] == [1] and idle_seen[:-1] == [[]] * (calls - 1)
    b.run()
    np.testing.assert_array_equal(b.results[long],
                                  _solo(params, _prompt(6), 8, 0.0, 1))


def test_an_idle_slots_write_lands_on_the_trash_page(params):
    """A stream whose cache ends at the slot's last row: the step launched
    while its last token is in flight must not write past its table."""
    b = ContinuousBatcher(CFG, params, BCFG)
    full = b.submit(_prompt(29, 3), 4, rng_seed=3)     # 29 + 4 - 1 = span
    other = b.submit(_prompt(5, 4), 9, rng_seed=4)
    seen, inner = [], b.pool.device_tables

    def tables(idle=()):
        out = inner(idle)
        seen.append((list(idle), np.asarray(out[0]), np.asarray(out[1])))
        return out

    b.pool.device_tables = tables
    b.run()
    masked = [s for s in seen if s[0]]
    assert masked and all(idle == [0] for idle, _, _ in masked)
    for _, table, lengths in masked:
        assert not table[0].any() and lengths[0] == 0 and lengths[1] > 0
    # (generate() wants room for the last token's row too: the values do
    # not depend on the capacity)
    np.testing.assert_array_equal(
        b.results[full], _solo(params, _prompt(29, 3), 4, 0.0, 3,
                               capacity=BCFG.span + 8))
    np.testing.assert_array_equal(b.results[other],
                                  _solo(params, _prompt(5, 4), 9, 0.0, 4))


def test_a_prefix_shared_admits_first_write_forks_its_page(params):
    bcfg = dataclasses.replace(BCFG, prefix_cache=PrefixCacheConfig(
        enabled=True, min_shared_block=1))
    b = ContinuousBatcher(CFG, params, bcfg)
    shared = _prompt(12, 40)              # a full page and half of the next
    specs = [(np.concatenate([shared, _prompt(3, 41)]), 7, 0.0, 1),
             (np.concatenate([shared, _prompt(2, 42)]), 7, 0.8, 2),
             (np.concatenate([shared, _prompt(5, 43)]), 5, 0.0, 3)]
    got = _serve(b, {0: specs[:1], 2: specs[1:2], 4: specs[2:]})
    b.pool.check_invariants()
    rep = b.report()
    assert rep["prefix"]["hits"] >= 2 and rep["prefix"]["cow_forks"] >= 1
    assert rep["steps_ahead"] >= rep["steps"] - 2
    for toks, (prompt, max_new, temp, seed) in zip(got, specs):
        np.testing.assert_array_equal(
            toks, _solo(params, prompt, max_new, temp, seed))


def test_an_eviction_under_page_pressure_reads_the_step_in_flight_first(
        params):
    tight = BatchingConfig(page_size=8, num_pages=8, max_slots=4,
                           pages_per_slot=4)  # 7 allocatable pages
    b = ContinuousBatcher(CFG, params, tight)
    in_flight_at_eviction, inner = [], b._evict_for_pages

    def evict_for_pages(needed, protect):
        in_flight_at_eviction.append(b._inflight is not None)
        done = inner(needed, protect)
        assert b._inflight is None          # read before a victim was chosen
        return done

    b._evict_for_pages = evict_for_pages
    specs = [(_prompt(15, 7), 8, 0.0, 1), (_prompt(14, 8), 8, 0.9, 2),
             (_prompt(13, 9), 8, 0.0, 3)]
    got = _serve(b, {0: specs})
    assert b.report()["evicted"] > 0 and any(in_flight_at_eviction)
    for toks, (prompt, max_new, temp, seed) in zip(got, specs):
        np.testing.assert_array_equal(
            toks, _solo(params, prompt, max_new, temp, seed))


# ---------------------------------------------------------------------------
# what needs the tokens on the host drains first
# ---------------------------------------------------------------------------


def _two_streams_in_flight(params, bcfg=BCFG):
    b = ContinuousBatcher(CFG, params, bcfg)
    specs = [(_prompt(6, 50), 10, 0.0, 5), (_prompt(9, 51), 10, 0.7, 6)]
    sids = [b.submit(p, n, temperature=t, rng_seed=s)
            for p, n, t, s in specs]
    for _ in range(3):
        b.step()
    assert b._inflight is not None and len(b._inflight.riders) == 2
    assert [len(b._streams[s].tokens) for s in sids] == [3, 3]
    return b, sids, specs


def _finish_and_check(b, params, sids, specs):
    b.run()
    for sid, (prompt, max_new, temp, seed) in zip(sids, specs):
        np.testing.assert_array_equal(
            b.results[sid], _solo(params, prompt, max_new, temp, seed))


def _evict(b, sids, tmp_path):
    b.evict(sids[1])
    st = b._streams[sids[1]]
    assert st.status == "waiting" and len(st.tokens) == 4
    assert int(st.resume["length"]) == 9 + 3     # the rows of four tokens


def _discard_and_resubmit(b, sids, tmp_path):
    gone = b._streams[sids[1]]
    b.discard(sids[1])
    assert gone.status == "discarded" and gone.pending == 0
    assert len(gone.tokens) == 4 and len(b._slot_to_sid) == 1
    sids[1] = b.submit(_prompt(9, 51), 10, temperature=0.7, rng_seed=6)


def _checkpoint_discard_restore(b, sids, tmp_path):
    path = b.checkpoint_stream(sids[1], str(tmp_path / "s.ckpt"))
    st = b._streams[sids[1]]
    assert st.pending == 0 and len(st.tokens) == 4
    b.step()                                     # a step flies again
    b.discard(sids[1])
    sids[1] = b.restore_stream(path)
    assert b._inflight is None
    assert len(b._streams[sids[1]].tokens) == 4


def _prefill_hold_gather_release(b, sids, tmp_path):
    held = b.submit(_prompt(8, 52), 5, rng_seed=9)
    st = b.prefill_hold(held)
    assert st is not None and b._inflight is None and len(st.tokens) == 1
    b.step()                                     # the held slot rides too
    rows = b.gather_rows(st.slot, 0, 8)
    assert b._inflight is None and rows["k"].shape[1] == 8
    b.step()
    b.release_handoff(held)
    assert b._inflight is None and held not in b._streams


@pytest.mark.parametrize("between", [
    _evict, _discard_and_resubmit, _checkpoint_discard_restore,
    _prefill_hold_gather_release], ids=lambda f: f.__name__.strip("_"))
def test_a_call_between_two_steps_finds_every_token_on_the_host(
        params, tmp_path, between):
    bcfg = dataclasses.replace(BCFG, checkpoint_dir=None)
    b, sids, specs = _two_streams_in_flight(params, bcfg)
    between(b, sids, tmp_path)
    _tokens_are_read(b)
    _finish_and_check(b, params, sids, specs)


def test_a_drain_with_nothing_in_flight_does_nothing(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    assert b._drain() == 0
    b.submit(_prompt(5), 4)
    assert b.step() == 1 and b._inflight is not None
    r0 = b.report()
    assert b._drain() == 1 and b._inflight is None and b._drain() == 0
    r1 = b.report()
    # outside step() the read keeps no phase clock: they tile step() alone
    assert r1["sync_s"] == r0["sync_s"] and r1["commit_s"] == r0["commit_s"]
    assert len(b._streams[0].tokens) == 2


def test_run_still_stops_when_nothing_advances_and_something_waits(params):
    wedged = ContinuousBatcher(CFG, params, BatchingConfig(
        page_size=8, num_pages=3, max_slots=2, pages_per_slot=4))
    wedged.submit(_prompt(20), 4)                # needs 3 pages to admit
    with pytest.raises(OutOfPages):
        wedged.run()
    # a call that launched has advanced, and so has one that only committed
    b = ContinuousBatcher(CFG, params, BCFG)
    sid = b.submit(_prompt(5), 2)
    assert b.step() == 1 and sid not in b.results
    assert b.step() == 1 and len(b.results[sid]) == 2
    assert b.step() == 0


# ---------------------------------------------------------------------------
# the counter, the spans, the watchdog
# ---------------------------------------------------------------------------


def test_steps_ahead_counts_the_launches_that_found_a_step_unread(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    b.submit(_prompt(6), 12)
    ahead = []
    for call in range(8):
        if call in (3, 6):
            b._drain()                           # the next launch finds none
        r0 = b.report()
        unread = b._inflight is not None
        b.step()
        r1 = b.report()
        assert r1["steps"] - r0["steps"] == 1
        assert r1["steps_ahead"] - r0["steps_ahead"] == int(unread)
        ahead.append(int(unread))
    assert ahead == [0, 1, 1, 0, 1, 1, 0, 1]
    assert b.report()["steps_ahead"] == 5 and b.stats["steps_ahead"] == 5


def test_launch_of_the_next_step_lies_before_the_read_of_this_one(params):
    obs.enable(obs.ObservabilityConfig())
    b = ContinuousBatcher(CFG, params, BCFG)
    b.submit(_prompt(6), 9)
    b.submit(_prompt(9, 1), 6, temperature=0.5, rng_seed=1)
    b.run()
    by = {}
    for s in obs.get_tracer().spans():
        # (the first call's sync reads a token 0 alone and names no step)
        if s.name.startswith("batch.step.") and "step" in s.args:
            by.setdefault(s.name, {})[s.args["step"]] = s
    steps = b.report()["steps"]
    assert sorted(by["batch.step.sync"]) == sorted(
        by["batch.step.launch"]) == list(range(steps))
    for n in range(steps - 1):
        launch, sync = by["batch.step.launch"][n + 1], by["batch.step.sync"][n]
        commit = by["batch.step.commit"][n]
        # step n+1 is with the device before the host asks for step n's
        # tokens, and so before that read closes
        assert launch.ts_us + launch.dur_us <= sync.ts_us
        assert launch.ts_us < sync.ts_us + sync.dur_us <= commit.ts_us + 1
        assert by["batch.step.launch"][n].ts_us < launch.ts_us
    obs.disable()


def test_the_watchdog_arms_at_a_launch_and_checks_at_that_steps_commit(
        params):
    b = ContinuousBatcher(CFG, params, dataclasses.replace(
        BCFG, step_deadline_s=1000.0))
    sid = b.submit(_prompt(6), 8)
    b.step()
    b.step()
    first = b._inflight
    assert first.step == 1 and first.watchdog is not None
    b.step()                                     # within the deadline
    late = b._inflight
    assert late.step == 2 and late.watchdog is not first.watchdog
    late.watchdog._armed_at -= 2000.0            # step 2 was launched long ago
    with pytest.raises(DecodeTimeout):
        b.step()
    # it was step 2's commit that raised: its tokens are read, and step 3,
    # launched in the same call, flies on under its own deadline
    assert b._inflight.step == 3 and len(b._streams[sid].tokens) == 4
    b.run()
    assert len(b.results[sid]) == 8


def test_the_tables_a_step_is_launched_with_are_copies(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    b.submit(_prompt(6), 4)
    b.step()
    table, lengths = b.pool.device_tables()
    want_table, want_lengths = b.pool.page_table.copy(), b.pool.lengths.copy()
    b.pool.page_table[:] = 7                     # the host goes on
    b.pool.lengths[:] = 99
    np.testing.assert_array_equal(np.asarray(table), want_table)
    np.testing.assert_array_equal(np.asarray(lengths), want_lengths)
    masked, zero = b.pool.device_tables([0])
    assert not np.asarray(masked)[0].any() and np.asarray(zero)[0] == 0
    assert np.asarray(masked)[1:].all() and b.pool.lengths[0] == 99
