"""An admission's token 0 is a token in flight: the admit loop dispatches the
next admission before it reads the last one's token 0, and the step is
launched before the loop's last token 0 is read.

What is held here, on the CPU with a toy model (local pool and, over a 2-stage
CPU mesh, the split runtime): streams admitted four to a loop, greedy and
sampled, are served ``generate()``'s / ``generate_split()``'s tokens; a call
of ``step()`` returns with every token 0 it admitted in ``Stream.tokens``;
inside a loop admission k+1's prefill and adopt lie before the read of
admission k's token 0, and the loop's last read lies behind the launch, which
takes the id from the device at the stream's slot; ``admits_ahead`` counts the
admissions read behind device work and none that was drained early, nor a
resume; what needs the token on the host finds it there (an eviction by the
grow phase of the admitting call, ``prefill_hold``, ``discard``, a stream of
one token and of two, an ``OutOfPages`` undo behind a dispatched
predecessor); ``prefill_s`` counts no second for two admissions and
``tok0_hold_s`` runs from a token 0's read to the call's return.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edgellm_tpu import obs
from edgellm_tpu.models import init_params, tiny_config
from edgellm_tpu.models.paged_kv import OutOfPages
from edgellm_tpu.serve import batching
from edgellm_tpu.serve.batching import (IN_FLIGHT, BatchingConfig,
                                        ContinuousBatcher)
from edgellm_tpu.serve.decode import generate, generate_split

CFG = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4,
                  vocab_size=128)
# the geometry tests/test_batching.py uses, so the ragged step is shared
BCFG = BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                      pages_per_slot=4)
#: four to a loop: (prompt length, tokens asked for, temperature, seed)
FOUR = [(6, 5, 0.0, 1), (9, 4, 0.7, 2), (5, 6, 0.0, 3), (7, 3, 0.9, 4)]


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


@pytest.fixture(params=["local", "split"])
def kind(request):
    return request.param


@pytest.fixture
def make(kind, params, request):
    """A factory of fresh batchers of one kind over the shared geometry."""
    if kind == "local":
        return lambda bcfg=BCFG: ContinuousBatcher(CFG, params, bcfg)
    rt, placed = request.getfixturevalue("split_rt")
    return lambda bcfg=BCFG: ContinuousBatcher(
        CFG, params, bcfg, split_runtime=rt, placed_params=placed)


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    obs.get_tracer().clear()
    yield
    obs.disable()
    obs.get_tracer().clear()


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, size=n).astype(np.int32)


def _submit(b, specs):
    return [b.submit(_prompt(n, seed), max_new, temperature=temp,
                     rng_seed=seed) for n, max_new, temp, seed in specs]


def _solo(kind, params, request, spec):
    """One stream alone through ``generate`` (``generate_split`` over the
    same runtime): what the parent's order served, bit for bit."""
    n, max_new, temp, seed = spec
    ids, key = jnp.asarray(_prompt(n, seed))[None], jax.random.key(seed)
    if kind == "local":
        out = generate(CFG, params, ids, max_new, capacity=BCFG.span,
                       temperature=temp, rng_key=key)
    else:
        rt, placed = request.getfixturevalue("split_rt")
        out = generate_split(rt, placed, ids, max_new, capacity=BCFG.span,
                             temperature=temp, rng_key=key)
    return np.asarray(out)[0]


def _no_token0_is_unread(b):
    """Between two calls: nothing is left of an admission on the device."""
    assert not b._tok0s
    for st in b._streams.values():
        if st.status == "running":
            assert len(st.tokens) >= 1, st.sid
            assert st.pending == (b._inflight is not None
                                  and st in b._inflight.riders)


# ---------------------------------------------------------------------------
# (a) the served tokens are the parent's order's
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", FOUR, ids=lambda s: f"temp{s[2]}")
def test_tokens_of_four_admitted_in_one_loop_are_generates(
        make, kind, params, request, spec):
    b = make()
    sids = _submit(b, FOUR)
    b.step()
    # one loop admitted all four, and three of them ran ahead of their read
    # inside it, the fourth ahead of the launch
    assert b.report()["admitted"] == b.report()["admits_ahead"] == 4
    results = b.run()
    np.testing.assert_array_equal(results[sids[FOUR.index(spec)]],
                                  _solo(kind, params, request, spec))


def test_tokens_with_loops_of_four_mid_flight_are_generates(
        make, kind, params, request):
    """Loops that find a step in flight: the token 0 is set into the tokens
    that step left on the device, at the slot it was admitted into."""
    short = [(5 + i, 2 + i % 2, 0.6 * (i % 2), 10 + i) for i in range(12)]
    b = make()
    sids = _submit(b, short)
    while b._waiting or b._slot_to_sid:
        assert b.step() > 0
        _no_token0_is_unread(b)
    r = b.report()
    assert r["admitted"] == 12 and r["evicted"] == 0
    assert r["admits_ahead"] == 12 and r["admit_steps"] >= 3
    for sid, spec in zip(sids, short):
        np.testing.assert_array_equal(b.results[sid],
                                      _solo(kind, params, request, spec))


# ---------------------------------------------------------------------------
# (b) a call returns with every token 0 it admitted on the host
# ---------------------------------------------------------------------------


def test_every_token0_is_in_tokens_when_the_admitting_step_returns(
        make, kind, params, request):
    b = make()
    sids = _submit(b, FOUR)
    assert b.step() == 4
    _no_token0_is_unread(b)
    for sid, spec in zip(sids, FOUR):
        st = b._streams[sid]
        # token 0 read, the step's token in flight
        assert len(st.tokens) == 1 and st.pending == 1 and st.t == 2
        assert st.tokens[0] == _solo(kind, params, request, spec)[0]
    # mid-flight too: a stream admitted into a freed slot beside riders
    late = (8, 4, 0.5, 9)
    while len(b._slot_to_sid) == 4:
        b.step()
    (sid,) = _submit(b, [late])
    b.step()
    _no_token0_is_unread(b)
    assert b._streams[sid].tokens == [_solo(kind, params, request, late)[0]]


# ---------------------------------------------------------------------------
# (c) the order of the spans
# ---------------------------------------------------------------------------


def _ends(span):
    return span.ts_us + span.dur_us


def test_the_next_dispatch_lies_before_the_read_and_the_last_behind_the_launch(
        make):
    obs.enable(obs.ObservabilityConfig())
    b = make()
    sids = _submit(b, FOUR)
    b.step()
    by = {}
    for s in obs.get_tracer().spans():
        by.setdefault(s.name, {})[s.args.get("sid")] = s
    read, adopt = by["batch.admit.tok0_sync"], by["batch.admit.adopt"]
    prefill, admit = by["batch.admit.prefill"], by["batch.admit"]
    assert sorted(read) == sorted(admit) == sids
    for k, nxt in zip(sids, sids[1:]):
        # admission k+1 is with the device before the host asks for k's id
        assert _ends(prefill[nxt]) <= adopt[nxt].ts_us + 1
        assert _ends(adopt[nxt]) <= read[k].ts_us + 1
        # ONE behind: k is read before k+2 is dispatched
        assert _ends(read[k]) <= _ends(admit[nxt]) + 1
    (launch,) = by["batch.step.launch"].values()
    (sync,) = by["batch.step.sync"].values()
    last = read[sids[-1]]
    assert _ends(launch) <= last.ts_us + 1
    assert sync.ts_us <= last.ts_us and _ends(last) <= _ends(sync) + 1


def test_the_launch_takes_the_unread_token0_from_the_device(make,
                                                            monkeypatch):
    fed, inner = [], batching._feed_jit

    class Tap:
        _cache_size = inner._cache_size

        def __call__(self, token_ids, prev_toks):
            fed.append((np.asarray(token_ids), np.asarray(prev_toks)))
            return inner(token_ids, prev_toks)

    monkeypatch.setattr(batching, "_feed_jit", Tap())
    b = make()
    sids = _submit(b, FOUR)
    b.step()
    ids, feed = fed[0]                      # (fed[1] is the launch's warm-up)
    slots = [b._streams[s].slot for s in sids]
    tok0 = [b._streams[s].tokens[0] for s in sids]
    # the host knew the first three ids; the fourth went out as in flight and
    # the device had it at that slot
    assert [ids[s] for s in slots[:3]] == tok0[:3]
    assert ids[slots[3]] == IN_FLIGHT and feed[slots[3]] == tok0[3]


# ---------------------------------------------------------------------------
# (d) admits_ahead
# ---------------------------------------------------------------------------


def test_admits_ahead_counts_no_drained_admission_and_no_resume(make):
    b = make()
    (a,) = _submit(b, [(6, 9, 0.0, 1)])
    b.step()
    assert (b.report()["admitted"], b.report()["admits_ahead"]) == (1, 1)
    # a resume has no token 0: admitted, and not ahead
    b.evict(a)
    b.step()
    r = b.report()
    assert (r["admitted"], r["admits_ahead"], r["evicted"]) == (2, 1, 1)
    # a call that launches nothing drains what it admitted: the old order
    ones = _submit(b, [(5, 1, 0.0, 2)])
    b.discard(a)
    b.step()
    r = b.report()
    assert ones[0] in b.results and r["steps"] == 2
    assert (r["admitted"], r["admits_ahead"]) == (3, 1)
    # two of them: the first is read behind the second's dispatch
    _submit(b, [(5, 1, 0.0, 3), (6, 1, 0.5, 4)])
    b.step()
    r = b.report()
    assert (r["admitted"], r["admits_ahead"], r["steps"]) == (5, 2, 2)


def test_admits_ahead_is_additive_beside_admitted(make):
    b = make()
    _submit(b, [(5 + i, 3, 0.5 * (i % 2), i) for i in range(10)])
    seen = []
    while b._waiting or b._slot_to_sid:
        r0 = b.report()
        b.step()
        r1 = b.report()
        seen.append((r1["admitted"] - r0["admitted"],
                     r1["admits_ahead"] - r0["admits_ahead"]))
    assert sum(a for a, _ in seen) == sum(h for _, h in seen) == 10
    assert all(a == h for a, h in seen) and max(a for a, _ in seen) == 4


# ---------------------------------------------------------------------------
# (e) what needs the token on the host finds it there
# ---------------------------------------------------------------------------


def test_a_stream_evicted_by_the_grow_phase_of_its_admitting_call(
        make, kind, params, request):
    # 7 allocatable pages: three prompts of 15 take two each and fit, and the
    # admitting call's own growth (the step's row is a third page each)
    # evicts the youngest, whose token 0 the launch had not fed yet
    tight = BatchingConfig(page_size=8, num_pages=8, max_slots=4,
                           pages_per_slot=4)
    specs = [(16, 6, 0.0, 1), (16, 6, 0.9, 2), (16, 6, 0.0, 3)]
    b = make(tight)
    sids = _submit(b, specs)
    b.step()
    r = b.report()
    assert r["admitted"] == 3 and r["evicted"] >= 1
    young = b._streams[sids[-1]]
    # drained before it was gathered: its token 0 is on the host, it rode
    # nothing, and it was not ahead of anything
    assert young.status == "waiting" and young.resume is not None
    assert len(young.tokens) == 1 and young.pending == 0
    assert r["admits_ahead"] == 2
    b.run()
    for sid, spec in zip(sids, specs):
        np.testing.assert_array_equal(b.results[sid],
                                      _solo(kind, params, request, spec))


def test_prefill_hold_returns_with_token0_on_the_host(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    spec = (7, 3, 0.8, 5)
    (sid,) = _submit(b, [spec])
    st = b.prefill_hold(sid)
    want = _solo("local", params, None, spec)
    assert st.tokens == [want[0]] and st.pending == 0 and not b._tok0s
    r = b.report()
    assert (r["admitted"], r["admits_ahead"]) == (1, 0)
    assert r["prefill_s"] > 0 and r["sync_s"] == 0.0
    b.release_handoff(sid)
    # and a stream of one token comes back finished, with no slot held
    (one,) = _submit(b, [(6, 1, 0.0, 6)])
    st = b.prefill_hold(one)
    assert st.status == "finished" and not b._slot_to_sid
    np.testing.assert_array_equal(
        b.results[one], _solo("local", params, None, (6, 1, 0.0, 6)))


def test_discard_of_a_stream_the_last_call_admitted(make, kind, params,
                                                    request):
    b = make()
    sids = _submit(b, FOUR)
    b.step()
    b.discard(sids[-1])
    assert sids[-1] not in b._streams and len(b._slot_to_sid) == 3
    assert not b._tok0s and b._inflight is None
    b.run()
    for sid, spec in zip(sids[:3], FOUR[:3]):
        np.testing.assert_array_equal(b.results[sid],
                                      _solo(kind, params, request, spec))


@pytest.mark.parametrize("max_new", [1, 2])
@pytest.mark.parametrize("place", ["first", "last"])
def test_a_stream_of_one_token_and_of_two_ends_by_count(
        make, kind, params, request, max_new, place):
    """In the loop's first place its token 0 is read behind the next
    admission; in the last, behind the launch (which it rides only if it has
    a second token to sample)."""
    short, long = (7, max_new, 0.7, 2), (6, 5, 0.0, 1)
    specs = [short, long] if place == "first" else [long, short]
    b = make()
    sids = _submit(b, specs)
    assert b.step() == (2 if max_new == 2 else 1)
    _no_token0_is_unread(b)
    sid = sids[specs.index(short)]
    if max_new == 1:
        assert b._streams[sid].status == "finished"
    else:
        assert b._streams[sid].status == "running"
        b.step()
        assert b._streams[sid].status == "finished"
    assert b.report()["admits_ahead"] == 2
    b.run()
    for sid, spec in zip(sids, specs):
        np.testing.assert_array_equal(b.results[sid],
                                      _solo(kind, params, request, spec))


def test_an_out_of_pages_undo_behind_a_dispatched_predecessor(
        make, kind, params, request):
    """The second admission's fill runs out of pages with the first one's
    token 0 unread: the slot is given back, nothing of the second is left on
    the device, and the first rides the launch as the loop's last."""
    b = make()
    sids = _submit(b, [(6, 4, 0.7, 1), (9, 4, 0.0, 2)])
    inner, calls = b._admit_fill, []

    def fill(st, slot):
        calls.append(st.sid)
        if st.sid == sids[1] and calls.count(st.sid) == 1:
            raise OutOfPages("the probe over-promised")
        return inner(st, slot)

    b._admit_fill = fill
    assert b.step() == 1
    assert calls == sids and list(b._waiting) == [sids[1]]
    assert b._streams[sids[1]].status == "waiting" and len(b._slot_to_sid) == 1
    _no_token0_is_unread(b)
    r = b.report()
    assert (r["admitted"], r["admits_ahead"]) == (1, 1)
    b.pool.check_invariants()
    b.run()
    assert b.report()["admitted"] == 2
    for sid, spec in zip(sids, [(6, 4, 0.7, 1), (9, 4, 0.0, 2)]):
        np.testing.assert_array_equal(b.results[sid],
                                      _solo(kind, params, request, spec))


# ---------------------------------------------------------------------------
# (f) the clocks
# ---------------------------------------------------------------------------


class _ShiftedClock:
    """``time`` for the batcher with a monotonic clock the test can push."""

    def __init__(self):
        import time

        self._time, self.offset = time, 0.0

    def monotonic(self):
        return self._time.monotonic() + self.offset

    def __getattr__(self, name):        # the CPU clocks are the machine's
        return getattr(self._time, name)


@pytest.fixture
def clock(monkeypatch):
    clock = _ShiftedClock()
    monkeypatch.setattr(batching, "time", clock)
    monkeypatch.setattr(obs.tracing, "time", clock)
    return clock


def test_prefill_s_counts_no_second_for_two_admissions(make, clock):
    """Each admission's fill takes 10 s on the clock. Its seconds run from
    its start, or the read before it where that came later, to its own token
    0's read, so a loop's sum is the loop's wall and not the 10 + 20 + 30 + 40
    that start-to-read would add up to."""
    b = make()
    _submit(b, FOUR)
    inner = b._admit_fill

    def fill(st, slot):
        clock.offset += 10.0
        return inner(st, slot)

    b._admit_fill = fill
    b.step()
    r = b.report()
    assert 40.0 <= r["prefill_s"] <= r["step_wall_s"] - r["commit_s"] < 45.0
    # all but the last read lie in the admit loop, the last one in the sync
    assert 40.0 <= r["admit_s"] < r["prefill_s"]
    assert r["prefill_tokens"] == sum(n for n, *_ in FOUR)


def test_a_resume_between_two_fresh_admissions_keeps_prefill_s_additive(
        make, clock):
    b = make()
    (a,) = _submit(b, [(6, 9, 0.0, 1)])
    b.step()
    b.evict(a)
    _submit(b, [(7, 4, 0.0, 2)])            # behind the resume in the queue
    r0 = b.report()
    inner = b._admit_fill

    def fill(st, slot):
        clock.offset += 10.0
        return inner(st, slot)

    b._admit_fill = fill
    b.step()
    r1 = b.report()
    assert r1["admitted"] - r0["admitted"] == 2
    assert r1["prefill_tokens"] - r0["prefill_tokens"] == 7
    assert 20.0 <= r1["prefill_s"] - r0["prefill_s"] <= (
        r1["step_wall_s"] - r0["step_wall_s"]) < 25.0


def test_tok0_hold_runs_from_each_read_to_the_calls_return(make, clock):
    b = make()
    readings, inner = [], b._admitted_at

    def admitted_at(*a):
        readings.append(inner(*a))
        return readings[-1]

    b._admitted_at = admitted_at
    _submit(b, FOUR)
    grow = b._grow_writable

    def pushed(st):
        clock.offset += 5.0
        return grow(st)

    b._grow_writable = pushed
    b.step()
    r = b.report()
    assert len(readings) == 4 and readings == sorted(readings)
    assert r["tok0_hold_s"] == pytest.approx(
        sum(b._returned - t for t in readings), rel=1e-12)
    # three were read ahead of the grow phase's 20 s, one behind the launch
    assert 60.0 <= r["tok0_hold_s"] < 65.0
