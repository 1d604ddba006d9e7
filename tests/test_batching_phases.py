"""The batcher's phases: spans that enclose the work inside ``step()``, the
host-clock counters they keep, and the named scopes of the device side.

What is held here, on the CPU with a toy model (local pool and, over a
2-stage CPU mesh, the split runtime): the six phase clocks tile
``step_wall_s``, the first four of the step a call launches and the last two
of the step it reads, launched a call earlier; ``decode_s`` is every step's
launch to read with no second counted twice; ``prefill_s`` lies inside the
call ahead of its commit (every admission's but the loop's last inside
``admit_s``, the last one's token 0 read behind the launch); ``queue_wait_s`` is
the time a stream was held out; ``compiles`` sees a prefill's compile that
``jit_misses`` is blind to; spans nest by step and by stream when the tracer
is on and nothing is recorded when it is off; tokens do not depend on the
tracer; every name is registered; the named scopes change no jaxpr.
"""
import ast
import bisect
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edgellm_tpu import obs
from edgellm_tpu.lint.ast_rules import lint_source
from edgellm_tpu.lint.contracts import graph_fingerprint
from edgellm_tpu.models import init_params, paged_kv, tiny_config, transformer
from edgellm_tpu.obs import names as obs_names
from edgellm_tpu.obs.tracing import compile_totals, phase
from edgellm_tpu.parallel import split as split_mod
from edgellm_tpu.serve import batching
from edgellm_tpu.serve.batching import (BatchingConfig, ContinuousBatcher,
                                        _batched_step_jit)

CFG = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4,
                  vocab_size=128)
# the geometry tests/test_batching.py uses, so the ragged step is shared
BCFG = BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                      pages_per_slot=4)
PHASES = ("admit_s", "grow_s", "build_s", "launch_s", "sync_s", "commit_s")
STEP_SPANS = tuple("batch.step." + p[:-2] for p in PHASES)
ADMIT_SPANS = ("batch.admit.prefill", "batch.admit.adopt",
               "batch.admit.tok0_sync")


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
def make(request, params):
    """A factory of fresh batchers of one kind over the shared geometry."""
    if request.param == "local":
        return lambda: ContinuousBatcher(CFG, params, BCFG)
    rt, placed = request.getfixturevalue("split_rt")
    return lambda: ContinuousBatcher(CFG, params, BCFG, split_runtime=rt,
                                     placed_params=placed)


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


def _traffic(b, n=6):
    """More streams than slots, greedy and sampled, distinct lengths."""
    return [b.submit(_prompt(5 + i, i), 5 + i % 3, temperature=0.5 * (i % 2),
                     rng_seed=i) for i in range(n)]


def _run(make):
    b = make()
    sids = _traffic(b)
    results = b.run()
    return b, [results[s] for s in sids]


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def test_six_phases_tile_step_wall(make, monkeypatch):
    _run(make)                      # compiles land in the first batcher
    closed = []

    class Kept(phase):
        """A phase the test can read the edges of, in the order they end."""

        def __exit__(self, *exc):
            closed.append(self)
            return super().__exit__(*exc)

    monkeypatch.setattr(batching, "obs_phase", Kept)
    b, _ = _run(make)
    r = b.report()
    assert r["steps"] >= 8 and r["compiles"] == 0
    six = sum(r[k] for k in PHASES)
    assert all(r[k] > 0 for k in PHASES)
    assert six <= r["step_wall_s"]
    # the chain leaves no time between the phases: they tile every call from
    # its start to its last phase's end, on any machine (what lies between
    # that end and the call's own is the machine's: a loaded one stalls there
    # for longer than any bound a test could give)
    tiled, last, calls = 0.0, None, 0
    for ph in closed:
        if ph.name in STEP_SPANS:
            last = ph
        elif ph.name == "batch.step":
            assert ph.start < last.end <= ph.end
            tiled += last.end - ph.start
            last, calls = None, calls + 1
    assert calls >= r["steps"]
    assert six == pytest.approx(tiled, abs=1e-9 * calls)


def test_decode_s_is_launch_to_read_once_and_prefill_lies_in_the_call(make):
    b, _ = _run(make)
    r = b.report()
    # a step's seconds run from its launch, or from the read before it where
    # that came later, to its read: every wait in sync lies inside them (but
    # for the clock reading that closes the span), and no second is counted
    # for two steps, so they fit in the calls and the caller's time between
    assert r["sync_s"] - r["decode_s"] < 2e-3 * r["steps"]
    assert r["launch_s"] < r["decode_s"]
    assert r["decode_s"] <= r["step_wall_s"] + r["between_s"] + 2e-3 * r[
        "steps"]
    # an admission's seconds run to its token 0's read: inside the admit loop
    # for all but the loop's last, whose read stands behind the launch
    assert 0 < r["prefill_s"] <= r["step_wall_s"] - r["commit_s"]
    assert r["admitted"] == 6 and r["finished"] == 6


def _two_reports(make):
    """A window in which every call launched a step: from the first call's
    return to where nothing is left to launch (one step is still in flight
    there, and the call that reads it launches nothing)."""
    b = make()
    _traffic(b)
    b.step()
    r0 = b.report()
    calls = 0
    while b._riders() or b._waiting:
        b.step()
        calls += 1
    r1 = b.report()
    assert r1["steps"] - r0["steps"] == calls and b._inflight is not None
    b.run()
    return r0, r1


def _hist_delta(r0, r1):
    return [[y - x for x, y in zip(row0, row1)]
            for row0, row1 in zip(r0["step_wall_hist"], r1["step_wall_hist"])]


def _six_phases_tile_the_window(r0, r1):
    for k in PHASES + ("step_wall_s", "decode_s"):
        assert r1[k] > r0[k], k
    # (that they tile each call to its last phase's end:
    # test_six_phases_tile_step_wall; the rest of a call is the machine's)
    assert 0 < sum(r1[k] - r0[k] for k in PHASES) <= (
        r1["step_wall_s"] - r0["step_wall_s"])


def _the_table_is_the_windows_steps(r0, r1):
    """Row by row the table is additive: a window's rows count its steps and
    hold, column by column, its six phase clocks (every call launched)."""
    rows = _hist_delta(r0, r1)
    assert all(v >= 0 for row in rows for v in row)
    assert sum(row[0] for row in rows) == r1["steps"] - r0["steps"]
    for i, k in enumerate(PHASES, 1):
        assert sum(row[i] for row in rows) == pytest.approx(r1[k] - r0[k],
                                                            rel=1e-9), k
    assert r1["step_wall_edges_s"] == r0["step_wall_edges_s"]


def _admitting_steps_are_some_of_the_windows(r0, r1):
    steps = r1["steps"] - r0["steps"]
    # the first step filled the four slots; two streams are admitted later
    assert r1["admitted"] - r0["admitted"] == 2
    assert 1 <= r1["admit_steps"] - r0["admit_steps"] <= 2 < steps
    wall = r1["admit_step_wall_s"] - r0["admit_step_wall_s"]
    assert 0 < wall < r1["step_wall_s"] - r0["step_wall_s"]
    assert r1["prefill_tokens"] - r0["prefill_tokens"] == 9 + 10
    hold = r1["tok0_hold_s"] - r0["tok0_hold_s"]
    assert 0 < hold < 2 * wall


def _the_caller_took_time_between_every_two_steps(r0, r1):
    between = r1["between_s"] - r0["between_s"]
    # run() does nothing between two steps but test two containers
    assert 0 < between < r1["step_wall_s"] - r0["step_wall_s"]


@pytest.mark.parametrize("holds", [
    _six_phases_tile_the_window, _the_table_is_the_windows_steps,
    _admitting_steps_are_some_of_the_windows,
    _the_caller_took_time_between_every_two_steps],
    ids=lambda f: f.__name__.strip("_"))
def test_counters_are_additive_across_report_deltas(make, holds):
    r0, r1 = _two_reports(make)
    assert r1["steps"] - r0["steps"] >= 1
    holds(r0, r1)


def test_a_step_with_nothing_to_do_still_counts_its_wall(make):
    b = make()
    assert b.step() == 0
    r = b.report()
    assert r["steps"] == 0 and r["step_wall_s"] > 0
    # an empty step is admission and nothing else. Its two clocks are a few
    # microseconds each, read apart, so under a loaded machine their ratio is
    # anything: the ordering is what holds
    assert 0 < r["admit_s"] <= r["step_wall_s"]
    assert r["launch_s"] == r["sync_s"] == 0.0


class _ShiftedClock:
    """``time`` for the batcher with a monotonic clock the test can push."""

    def __init__(self):
        import time

        self._time, self.offset, self.reads, self.cpu_reads = time, 0.0, 0, 0

    def monotonic(self):
        self.reads += 1
        return self._time.monotonic() + self.offset

    def thread_time(self):                  # pushed seconds are no work
        self.cpu_reads += 1
        return self._time.thread_time()

    def process_time(self):
        self.cpu_reads += 1
        return self._time.process_time()


def test_queue_wait_grows_by_the_time_a_stream_was_held_out(make,
                                                            monkeypatch):
    clock = _ShiftedClock()
    monkeypatch.setattr(batching, "time", clock)
    b = make()
    for i in range(BCFG.max_slots):             # fill every slot
        b.submit(_prompt(6, i), 4, rng_seed=i)
    held = b.submit(_prompt(6, 99), 2, rng_seed=99)
    b.step()
    r0 = b.report()
    assert r0["admitted"] == BCFG.max_slots and len(b._waiting) == 1
    assert 0 <= r0["queue_wait_s"] < 5.0         # nobody waited for long
    clock.offset += 100.0                        # the held stream waits
    while b._streams[held].status == "waiting":
        b.step()
    r1 = b.report()
    assert r1["admitted"] == BCFG.max_slots + 1
    grown = r1["queue_wait_s"] - r0["queue_wait_s"]
    assert 100.0 <= grown < 105.0


def test_compiles_sees_a_prefill_at_a_new_length_where_jit_misses_is_blind(
        params):
    """A prompt length no test of this process has prefilled compiles
    ``_prefill_jit`` again; the step executable is warm, so ``jit_misses``
    stays 0 and only ``compiles`` moves."""
    warm = ContinuousBatcher(CFG, params, BCFG)
    warm.submit(_prompt(5), 3)
    warm.run()
    before = compile_totals()
    b = ContinuousBatcher(CFG, params, BCFG)
    b.submit(_prompt(23, 7), 3)                  # 23: used nowhere else
    b.run()
    r = b.report()
    assert r["jit_misses"] == 0
    assert r["compiles"] >= 1 and r["compile_s"] > 0
    assert r["compiles"] == compile_totals()[0] - before[0]
    again = ContinuousBatcher(CFG, params, BCFG)
    again.submit(_prompt(23, 8), 3)
    again.run()
    assert again.report()["compiles"] == 0


def test_prefill_hold_folds_its_clocks_without_a_step(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    sid = b.submit(_prompt(7), 3)
    st = b.prefill_hold(sid)
    r = b.report()
    assert st is not None and r["admitted"] == 1 and r["prefill_s"] > 0
    assert r["queue_wait_s"] >= 0 and r["step_wall_s"] == 0.0
    b.release_handoff(sid)


# ---------------------------------------------------------------------------
# what the fold keeps of one step: the step-wall table, admitting steps apart
# from plain ones, the caller's time between steps, a first token's hold
# ---------------------------------------------------------------------------


@pytest.fixture
def clock(monkeypatch):
    """One pushable clock under the batcher's own readings and its phases'."""
    clock = _ShiftedClock()
    monkeypatch.setattr(batching, "time", clock)
    monkeypatch.setattr(obs.tracing, "time", clock)
    return clock


def _push_in(b, method, clock, seconds):
    """Make every later call of ``b.<method>`` take ``seconds`` more on the
    clock; returns the dict whose ``"s"`` the test may change and whose
    ``"pushed_s"`` adds up what was pushed."""
    push, inner = {"s": seconds, "pushed_s": 0.0}, getattr(b, method)

    def pushed(*a, **kw):
        clock.offset += push["s"]
        push["pushed_s"] += push["s"]
        return inner(*a, **kw)

    setattr(b, method, pushed)
    return push


def _row_of(seconds):
    return bisect.bisect_right(batching._STEP_WALL_EDGES, seconds)


def _wall_of_a_step(b):
    r0 = b.report()
    b.step()
    r1 = b.report()
    assert r1["steps"] == r0["steps"] + 1
    return r1["step_wall_s"] - r0["step_wall_s"]


def test_edges_are_geometric_and_no_bucket_is_wider_than_a_fifth():
    edges = batching._STEP_WALL_EDGES
    assert edges[0] <= 0.25e-3 and edges[-1] >= 16.0
    assert all(1.0 < hi / lo <= 1.2 for lo, hi in zip(edges, edges[1:]))
    hist = batching._new_step_wall_hist()
    assert len(hist) == len(edges) + 1          # an underflow, an overflow row
    assert all(row == [0] + [0.0] * len(PHASES) for row in hist)
    assert _row_of(0.0) == 0 and _row_of(1e9) == len(edges)
    assert _row_of(edges[0]) == 1               # an edge opens its bucket


@pytest.mark.parametrize("phase_no, method", [(2, "_grow_writable"),
                                              (1, "_try_admit")],
                         ids=["grow", "admit"])
def test_a_long_step_lands_in_its_row_with_its_phase_columns(
        make, clock, phase_no, method):
    b = make()
    b.submit(_prompt(6), 8)
    b.step()
    b.submit(_prompt(7, 1), 8, rng_seed=1)      # the long step admits it
    before = b.report()
    push = _push_in(b, method, clock, 3.0)
    wall = _wall_of_a_step(b)
    push["s"], long = 0.0, push["pushed_s"]
    # two running streams grow, one waiting stream is tried
    assert long == (6.0 if method == "_grow_writable" else 3.0)
    assert long <= wall < 16.0                  # under the overflow row
    rows = _hist_delta(before, b.report())
    row = rows[_row_of(wall)]
    assert row[0] == 1 and sum(r[0] for r in rows) == 1
    assert sum(row[1:]) == pytest.approx(wall, rel=1e-3)
    # the pushed seconds lie in the pushed phase's column, in no other
    assert long <= row[phase_no] <= wall
    assert sum(row[1:]) - row[phase_no] <= wall - long + 1e-9
    edges = b.report()["step_wall_edges_s"]
    assert edges[_row_of(wall) - 1] <= wall < edges[_row_of(wall)]


def test_a_pushed_step_is_a_stall_in_the_report(make, clock):
    """The verdict on a real batcher (tests/test_batching_stalls.py drives
    the fold by hand): seven plain steps give their kind an expectation, and
    the next one, held 0.3 s in its grow phase on a clock that counts no
    work, is one stall there, off the CPU, under its ``step=``."""
    b = make()
    b.submit(_prompt(6), 12)
    for _ in range(7):
        b.step()
    r0 = b.report()
    assert r0["steps"] == 7 and r0["stalls"] == 0
    assert r0["steps_judged"] == 1              # six of its kind: the admitting
    push = _push_in(b, "_grow_writable", clock, 0.3)    # step is another
    wall = _wall_of_a_step(b)
    push["s"] = 0.0
    b.step()
    r1 = b.report()
    assert r1["stalls"] == 1 and r1["steps_judged"] == 3
    (rec,) = r1["stall_log"]
    assert (rec["step"], rec["where"]) == (7, "grow")
    assert rec["wall_s"] == pytest.approx(wall) and rec["grow_s"] >= 0.3
    assert 0.25 < rec["excess_s"] == pytest.approx(wall - rec["expected_s"])
    assert rec["excess_s"] == pytest.approx(r1["stall_excess_s"])
    assert rec["cpu_s"] < 0.25 < rec["off_cpu_s"] == r1["stall_off_cpu_s"]
    assert 0 < r1["admit_cpu_s"] <= r1["step_cpu_s"] <= r1["step_wall_s"]
    assert r1["admit_cpu_s"] <= r1["admit_s"]
    assert set(r1["host"]) == {"nr_throttled", "cpu_throttled_s",
                               "pressure_cpu_s", "pressure_memory_s",
                               "pressure_io_s"}


def test_table_rows_sum_to_steps_and_to_the_six_clocks(make):
    b = make()
    _traffic(b)
    launched = dict.fromkeys(PHASES, 0.0)
    reads_only = 0
    while b._waiting or b._slot_to_sid:
        r0 = b.report()
        assert b.step() > 0
        r1 = b.report()
        if r1["steps"] > r0["steps"]:
            for k in PHASES:
                launched[k] += r1[k] - r0[k]
        else:
            # a call that launched nothing and read the step in flight is on
            # the clocks and not in the table
            reads_only += 1
            assert r1["sync_s"] > r0["sync_s"]
            assert r1["step_wall_hist"] == r0["step_wall_hist"]
    r = b.report()
    rows = r["step_wall_hist"]
    assert reads_only >= 1 and r["finished"] == 6
    assert sum(row[0] for row in rows) == r["steps"] >= 8
    for i, k in enumerate(PHASES, 1):
        assert sum(row[i] for row in rows) == pytest.approx(launched[k],
                                                            rel=1e-9)
    # a call that finds nothing to run is on the clocks and not in the table
    assert b.step() == 0
    r1 = b.report()
    assert r1["step_wall_s"] > r["step_wall_s"] and r1["admit_s"] > r["admit_s"]
    assert r1["step_wall_hist"] == rows
    for k in ("admit_steps", "admit_step_wall_s", "between_s", "tok0_hold_s",
              "prefill_tokens"):
        assert r1[k] == r[k], k


def test_admit_steps_split_admitting_steps_from_plain_ones(make, clock):
    b = make()
    _push_in(b, "_grow_writable", clock, 0.05)  # every step is long enough
    walls = {True: [], False: []}
    b.submit(_prompt(6), 12)
    b.submit(_prompt(9, 1), 12, rng_seed=1)
    for step in range(9):
        if step in (4, 6):
            b.submit(_prompt(5 + step, step), 3, rng_seed=step)
        admitted = b.report()["admitted"]
        wall = _wall_of_a_step(b)
        walls[b.report()["admitted"] > admitted].append(wall)
    r = b.report()
    assert len(walls[True]) == 3 and len(walls[False]) == 6
    assert r["admit_steps"] == 3 and r["admitted"] == 4
    assert r["admit_step_wall_s"] == pytest.approx(sum(walls[True]),
                                                   rel=1e-9)
    table_wall = sum(sum(row[1:]) for row in r["step_wall_hist"])
    table_steps = sum(row[0] for row in r["step_wall_hist"])
    assert table_steps - r["admit_steps"] == 6
    assert table_wall - r["admit_step_wall_s"] == pytest.approx(
        sum(walls[False]), rel=1e-3)


def test_between_s_is_the_callers_time_between_two_launched_steps(make,
                                                                  clock):
    b = make()
    sid = b.submit(_prompt(6), 3)
    b.step()
    assert b.report()["between_s"] == 0.0       # no step before the first
    clock.offset += 50.0                        # the caller dawdles
    b.step()
    r = b.report()
    assert 50.0 <= r["between_s"] < 55.0
    assert r["step_wall_s"] < 40.0              # and no step's wall holds it
    while b._streams[sid].status != "finished":
        b.step()
    r0 = b.report()
    assert b.step() == 0                        # an idle batcher...
    clock.offset += 100.0                       # ...waits for work
    b.submit(_prompt(6, 1), 3, rng_seed=1)
    b.step()
    r1 = b.report()
    assert r1["steps"] == r0["steps"] + 1
    assert r1["between_s"] == r0["between_s"]   # a wait is no hand-off
    b.step()
    assert 0 < b.report()["between_s"] - r1["between_s"] < 10.0
    # nor is it one where the caller does not poll the batcher it emptied
    while b._slot_to_sid:
        b.step()
    r2 = b.report()
    clock.offset += 100.0
    b.submit(_prompt(6, 2), 3, rng_seed=2)
    b.step()
    assert b.report()["between_s"] == r2["between_s"]


def test_tok0_hold_is_the_steps_return_less_the_token0_reading(make, clock):
    b = make()
    readings, inner = [], b._admitted_at

    def admitted_at(*a):
        readings.append(inner(*a))
        return readings[-1]

    b._admitted_at = admitted_at
    b.submit(_prompt(6), 4)
    b.submit(_prompt(9, 1), 4, temperature=0.5, rng_seed=1)
    _push_in(b, "_grow_writable", clock, 10.0)  # twice: two running streams
    b.step()
    r = b.report()
    assert len(readings) == 2 and readings[0] < readings[1] < b._returned
    assert r["tok0_hold_s"] == pytest.approx(
        sum(b._returned - t for t in readings), rel=1e-12)
    # the first token 0 was read inside the admit loop, ahead of the grow
    # phase's 20 s; the loop's last one behind the launch, after them
    assert readings[1] - readings[0] >= 20.0
    assert 20.0 <= r["tok0_hold_s"] < 25.0
    b.step()                                    # a plain step holds no token
    assert b.report()["tok0_hold_s"] == r["tok0_hold_s"]
    assert b.report()["admit_steps"] == 1


def test_prefill_hold_holds_no_token_and_counts_its_prefill(params, clock):
    b = ContinuousBatcher(CFG, params, BCFG)
    sid = b.submit(_prompt(7), 3)
    st = b.prefill_hold(sid)
    clock.offset += 50.0
    r = b.report()
    assert st is not None and len(st.tokens) == 1
    assert r["tok0_hold_s"] == 0.0 and r["prefill_tokens"] == 7
    assert r["admit_steps"] == 0 and r["between_s"] == 0.0
    assert sum(row[0] for row in r["step_wall_hist"]) == 0
    b.release_handoff(sid)
    # and the reading it took is gone: a later step holds only its own
    b.submit(_prompt(5, 1), 3, rng_seed=1)
    b.step()
    assert 0 < b.report()["tok0_hold_s"] < 10.0


def test_prefill_tokens_are_the_prompt_less_the_matched_prefix(params):
    bcfg = BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                          pages_per_slot=4,
                          prefix_cache=paged_kv.PrefixCacheConfig())
    b = ContinuousBatcher(CFG, params, bcfg)
    first = _prompt(20)
    b.submit(first, 6)
    b.step()
    assert b.report()["prefill_tokens"] == 20
    second = np.concatenate([first[:16], _prompt(5, 3)])   # two pages shared
    sid = b.submit(second, 6, rng_seed=1)
    b.step()
    matched = 16
    assert b.report()["prefix"]["saved_tokens"] == matched
    assert b.report()["prefill_tokens"] == 20 + (21 - matched)
    # a resume adopts its rows back and prefills nothing
    b.evict(sid)
    b.step()
    r = b.report()
    assert r["evicted"] == 1 and r["admitted"] == 3
    assert r["prefill_tokens"] == 20 + (21 - matched)
    assert r["admit_steps"] == 3                # the resume admitted, though
    assert r["tok0_hold_s"] > 0


def test_report_no_longer_holds_occupancy_max(make):
    b, _ = _run(make)
    r = b.report()
    assert "occupancy_max" not in r and "occ_max" not in b.stats
    assert 0 < r["occupancy_mean"] <= 1


class _CountedLock:
    def __init__(self, lock):
        self.lock, self.taken = lock, 0

    def __enter__(self):
        self.taken += 1
        return self.lock.__enter__()

    def __exit__(self, *exc):
        return self.lock.__exit__(*exc)


def test_a_plain_step_takes_the_lock_once_and_reads_no_new_clock(make,
                                                                 clock):
    """The fold's cost is guarded by counts: a step that admits nothing and
    finishes nothing takes ``_stats_lock`` once and reads the clock ten times,
    as before the fold kept anything: ``batch.step`` in and out, the six
    phases out (each starts where the last stopped), ``decode_s``'s two. An
    admission reads it eleven times, as before: ``queued_t`` at ``submit()``,
    ``t0``, the reading after ``tok0_sync`` (now token 0's too: reused, not
    added), and the four ``batch.admit*`` spans in and out. The CPU clocks
    are read four times a call, admitting or not: the thread's at
    ``batch.step`` in and out and at ``batch.step.admit`` out (it starts
    where ``batch.step`` did), the process's once at the fold."""
    b = make()
    b.submit(_prompt(6), 8)
    b.step()
    b._stats_lock = lock = _CountedLock(b._stats_lock)
    reads, cpu_reads = clock.reads, clock.cpu_reads
    assert b.step() == 1
    assert lock.taken == 1 and clock.reads - reads == 10
    assert clock.cpu_reads - cpu_reads == 4
    reads, cpu_reads = clock.reads, clock.cpu_reads
    b.submit(_prompt(7, 1), 8, rng_seed=1)
    lock.taken = 0                              # submit() took it for itself
    assert b.step() == 2
    assert lock.taken == 1 and clock.reads - reads == 10 + 3 + 8
    assert clock.cpu_reads - cpu_reads == 4


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def _encloses(outer, inner):
    return (outer.ts_us <= inner.ts_us
            and inner.ts_us + inner.dur_us <= outer.ts_us + outer.dur_us + 1)


def test_spans_nest_by_step_and_by_stream_when_the_tracer_is_on(make):
    obs.enable(obs.ObservabilityConfig())
    b, _ = _run(make)
    spans = obs.get_tracer().spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    calls = by_name["batch.step"]
    steps = b.report()["steps"]
    # every call but the last launched a step, and carries its index; the
    # last one read the step in flight and launched nothing
    assert len(calls) == steps + 1
    assert [s.args["step"] for s in calls] == list(range(steps)) + [steps]

    def call_of(span):
        inside = [i for i, c in enumerate(calls) if _encloses(c, span)]
        assert len(inside) == 1, (span.name, span.args)
        return inside[0]

    # the first call found no step to read: its sync read a token 0 alone,
    # names no step and has no commit behind it
    tok0_only = [s for s in by_name["batch.step.sync"] if "step" not in s.args]
    assert len(tok0_only) == 1 and call_of(tok0_only[0]) == 0
    by_name["batch.step.sync"].remove(tok0_only[0])
    for name in STEP_SPANS:
        # the admit of the last call, which found nothing to launch
        extra = 1 if name == "batch.step.admit" else 0
        assert len(by_name[name]) == steps + extra, name
    for name in STEP_SPANS[:4]:                 # the launch's own phases
        for s in by_name[name]:
            assert call_of(s) == s.args["step"], (name, s.args)
    for name in STEP_SPANS[4:]:                 # the read, a call later
        assert sorted(s.args["step"] for s in by_name[name]) == list(
            range(steps))
        for s in by_name[name]:
            assert call_of(s) == s.args["step"] + 1, (name, s.args)
    admits = {s.args["sid"]: s for s in by_name["batch.admit"]}
    assert sorted(admits) == list(range(6))
    for s in admits.values():
        assert {"slot", "microbatch", "prompt_len", "resumed",
                "matched"} <= set(s.args)
        assert any(_encloses(p, s) for p in by_name["batch.step.admit"])
    for name in ADMIT_SPANS:
        assert len(by_name[name]) == 6, name
    for name in ADMIT_SPANS[:2]:
        for s in by_name[name]:
            assert _encloses(admits[s.args["sid"]], s), (name, s.args)
    # a token 0 is read behind the device work after it, in the call that
    # admitted it: inside the loop's next admission, behind its dispatches,
    # or, the loop's last, inside the call's sync behind the launch
    syncs = by_name["batch.step.sync"] + tok0_only
    launches = {call_of(s): s for s in by_name["batch.step.launch"]}
    for s in by_name["batch.admit.tok0_sync"]:
        sid, call = s.args["sid"], call_of(s)
        assert call == call_of(admits[sid]) and not _encloses(admits[sid], s)
        nxt = admits.get(sid + 1)
        if nxt is not None and _encloses(nxt, s):
            adopt = next(a for a in by_name["batch.admit.adopt"]
                         if a.args["sid"] == sid + 1)
            assert adopt.ts_us + adopt.dur_us <= s.ts_us + 1
        else:
            assert any(_encloses(y, s) for y in syncs), s.args
            launch = launches[call]
            assert launch.ts_us + launch.dur_us <= s.ts_us + 1
    assert sum(s.args["admitted"] for s in by_name["batch.step.admit"]) == 6
    assert sum(s.args["finished"] for s in by_name["batch.step.commit"]) <= 6
    assert len(by_name["batch.submit"]) == 6
    # no span is left that encloses nothing: every one has a duration
    assert all(s.dur_us > 0 for s in spans if s.name.startswith("batch."))


def test_nothing_is_recorded_and_tokens_are_the_same_with_the_tracer_off(
        make):
    _, off = _run(make)
    assert obs.get_tracer().spans() == []
    obs.enable(obs.ObservabilityConfig())
    _, on = _run(make)
    assert obs.get_tracer().spans()
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_phase_reaches_a_profiler_capture_without_arming_obs(tmp_path,
                                                             params):
    """The point of ``phase``: a bare ``jax.profiler`` session sees the
    batcher's spans with their attributes, the tracer untouched."""
    import glob

    from jax.profiler import ProfileData

    b = ContinuousBatcher(CFG, params, BCFG)
    b.submit(_prompt(5), 3)
    b.step()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        b.submit(_prompt(6, 1), 3)
        b.step()
    finally:
        jax.profiler.stop_trace()
    assert obs.get_tracer().spans() == []
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("batch."):
                    seen.setdefault(ev.name, []).append(dict(ev.stats))
    assert set(seen) >= set(STEP_SPANS) | set(ADMIT_SPANS) | {"batch.step",
                                                              "batch.admit"}
    assert int(seen["batch.step"][0]["step"]) == 1
    assert int(seen["batch.admit"][0]["sid"]) == 1


def test_phase_chains_its_clock_and_keeps_late_attributes_for_the_span():
    acc = {}
    obs.enable(obs.ObservabilityConfig())
    with phase("batch.step", acc, "whole", step=0) as whole:
        with phase("batch.step.admit", acc, "a", after=whole) as ph:
            ph.set(admitted=2)
        with phase("batch.step.grow", acc, "b", after=ph) as ph:
            pass
    assert acc["a"] + acc["b"] <= acc["whole"]
    # the chain leaves no time between the phases: they tile ``whole`` from
    # its start to the last one's end, on any machine (what lies between
    # that end and ``whole``'s own is the machine's: a loaded one stalls
    # there for longer than any bound a test could give)
    assert acc["a"] + acc["b"] == pytest.approx(ph.end - whole.start,
                                                abs=1e-9)
    assert whole.start < ph.end <= whole.end
    spans = {s.name: s for s in obs.get_tracer().spans()}
    assert spans["batch.step.admit"].args == {"admitted": 2}
    assert spans["batch.step"].args == {"step": 0}
    with phase("batch.step"):                  # no accumulator: span only
        pass


# ---------------------------------------------------------------------------
# names and scopes
# ---------------------------------------------------------------------------

SOURCES = ("serve/batching.py", "models/paged_kv.py", "models/transformer.py",
           "parallel/split.py", "models/mamba2.py", "models/moe.py",
           "models/hybrid.py", "models/shortconv.py",
           "models/sparse_attn.py", "models/sparse_mla.py")


def _literal_names(callees):
    root = os.path.dirname(os.path.abspath(batching.__file__))
    found = set()
    for rel in SOURCES:
        path = os.path.join(os.path.dirname(root), rel)
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and node.args and getattr(
                    node.func, "attr", getattr(node.func, "id", "")) in callees:
                arg = node.args[0]
                if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                    found.add(arg.value)
                elif isinstance(arg, ast.JoinedStr):
                    found.add("".join(
                        p.value if isinstance(p, ast.Constant) else "*"
                        for p in arg.values))
    return found


def test_every_span_and_scope_name_is_registered():
    spans = _literal_names({"obs_phase", "obs_span"})
    assert spans >= set(STEP_SPANS) | set(ADMIT_SPANS) | {
        "batch.step", "batch.admit", "batch.submit"}
    assert all(obs_names.span_registered(n) for n in spans), spans
    scopes = _literal_names({"named_scope"})
    assert scopes == set(obs_names.SCOPE_NAMES) | set(
        obs_names.SCOPE_TEMPLATES)
    assert all(obs_names.scope_registered(n) for n in scopes)
    assert not obs_names.scope_registered("paged_kv.writ")
    assert not obs_names.span_registered("batch.step.lunch")


def test_eg007_flags_an_unregistered_phase_or_scope_name():
    src = ('import jax\nfrom edgellm_tpu.obs.tracing import phase\n'
           'def f(acc):\n'
           '    with phase("batch.step.lunch", acc, "x"):\n'
           '        pass\n'
           '    with jax.named_scope("paged_kv.writ"):\n'
           '        pass\n'
           '    with jax.named_scope(f"split.hop.{1}"):\n'
           '        pass\n')
    found = [f for f in lint_source(src, "x.py") if f.rule == "EG007"]
    assert sorted(f.line for f in found) == [4, 6]


def test_batching_holds_no_span_that_encloses_only_pass():
    with open(batching.__file__) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.With):
            assert not all(isinstance(s, ast.Pass) for s in node.body), (
                f"line {node.lineno}: a with block that encloses only pass")


def _step_args(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    table, lengths = b.pool.device_tables()
    n = BCFG.max_slots
    return (params, b.pool.pool, table, lengths,
            jnp.zeros((n,), jnp.int32), jnp.asarray(b._free_key_rows),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32))


def _step(p, pool, table, lengths, toks, key_data, steps, temps):
    logits, pool = paged_kv.paged_decode_step(CFG, p, pool, table, lengths,
                                              toks)
    return batching._batched_sample(logits, key_data, steps, temps), pool


def test_named_scopes_change_no_jaxpr_of_the_batched_step(params,
                                                          monkeypatch):
    """The obs-identity contract extended to the device side: with every
    scope taken out (decorators unwrapped, ``jax.named_scope`` a no-op) the
    step traces to the same jaxpr, byte for byte; only the lowered module's
    location metadata differs."""
    import contextlib

    args = _step_args(params)
    with_scopes = graph_fingerprint(_step, *args)
    lowered = jax.jit(_step).lower(*args).as_text(debug_info=True)
    for scope in ("paged_kv.write", "attn.decode", "mlp", "unembed_sample"):
        assert scope in lowered, scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(paged_kv, "_attention_decode_paged",
                        paged_kv._attention_decode_paged.__wrapped__)
    monkeypatch.setattr(paged_kv, "write_rows",
                        paged_kv.write_rows.__wrapped__)
    monkeypatch.setattr(paged_kv, "mlp", transformer.mlp.__wrapped__)
    monkeypatch.setattr(batching, "_batched_sample",
                        batching._batched_sample.__wrapped__)

    def bare_step(*a):          # a new function: nothing cached is reused
        return _step(*a)

    assert graph_fingerprint(bare_step, *args) == with_scopes
    bare = jax.jit(bare_step).lower(*args).as_text(debug_info=True)
    assert "paged_kv.write" not in bare and "attn.decode" not in bare


def test_split_step_carries_stage_and_hop_scopes(split_rt, params):
    rt, placed = split_rt
    b = ContinuousBatcher(CFG, params, BCFG, split_runtime=rt,
                          placed_params=placed)
    fn = rt._paged_decode_fns(BCFG.num_pages, BCFG.page_size)
    table, lengths = b.pool.device_tables()
    text = fn.lower(placed, b._split_pool, table, lengths,
                    jnp.zeros((BCFG.max_slots,), jnp.int32)
                    ).as_text(debug_info=True)
    for scope in ("split.stage", "split.hop.0", "paged_kv.write",
                  "unembed_sample"):
        assert scope in text, scope
    assert "split.hop.1" not in text            # one cut
    kv = b._split_pool.kv
    # three positions' K (and V) rows, as one head as wide as the K lanes
    rows = jnp.zeros(kv.shape[:2] + (3, 1, kv.shape[-1] // 2), kv.dtype)
    adopt = split_mod._adopt_paged_impl.lower(
        b._split_pool, rows, rows, jnp.arange(3)).as_text(debug_info=True)
    assert "paged_kv.adopt" in adopt


def test_local_step_executable_keeps_its_name():
    """Trace readers find the step by ``program.step_module``; the scopes
    are metadata inside it and must not rename it."""
    assert _batched_step_jit.__name__ == "_batched_step_jit"
    assert paged_kv._adopt_impl.__name__ == "_adopt_impl"
