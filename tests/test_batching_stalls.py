"""The batcher's verdict on a ``step()`` that ran long, with no model and no
compile: ``ContinuousBatcher._fold_step`` driven through ``_fold_acc`` with
hand-made readings (a ``batch.step`` phase whose edges the test sets, the
``acc`` its phases would have left), on a batcher that is built and never
stepped.

What is held: no verdict before five of a kind; a wall 50 ms over the median
of the kind's last nine is one stall with the right ``where``, ``excess_s``
and ``step``; a long wait of the caller's is a stall of its own; a call that
launched nothing is in none of it; kinds are judged apart; the table of kinds
and the ring stay bounded; every new counter is additive over ``report()``
deltas; a machine with none of the host's files reads None and raises nothing;
``phase`` keeps the thread's CPU seconds where a key names them and reads no
clock for it where none does.
"""
import gc
import logging
import time

import pytest

from edgellm_tpu.models import tiny_config
from edgellm_tpu.obs import tracing
from edgellm_tpu.obs.tracing import compile_totals, host_counters, phase
from edgellm_tpu.serve import batching
from edgellm_tpu.serve.batching import (STALL_EXCESS_S, BatchingConfig,
                                        ContinuousBatcher)

CFG = tiny_config("qwen2", num_layers=1, hidden_size=32, num_heads=4,
                  vocab_size=128)
BCFG = BatchingConfig(page_size=8, num_pages=5, max_slots=2, pages_per_slot=2)
PHASES = batching._PHASES
COUNTERS = ("step_cpu_s", "admit_cpu_s", "steps_judged", "stalls",
            "stall_excess_s", "stall_off_cpu_s")
#: a sound step of the driver below: 10 ms, all but 1.5 ms of it in the sync
WALL = 0.010


class Driver:
    """A batcher that was built and never stepped, and the calls of ``step()``
    as its fold sees them: the test sets each call's edges on the wall and on
    the thread's clock."""

    def __init__(self):
        self.b = ContinuousBatcher(CFG, None, BCFG)
        self.b._slot_to_sid[0] = 0         # a stream runs: no idle hand-off
        self.t, self.cpu, self.step = 100.0, 1.0, 0

    def call(self, wall=WALL, *, over=None, cpu=0.002, between=0.0002,
             between_cpu=0.0001, admitted=0, prefill_tokens=0, launched=True):
        """One ``step()``: ``between`` seconds after the last one returned,
        ``wall`` long, ``over`` = (phase key, seconds) adding to one phase
        and to the wall."""
        b = self.b
        extra = over[1] if over else 0.0
        six = dict(admit_s=2e-4, grow_s=1e-4, build_s=3e-4, launch_s=8e-4,
                   sync_s=wall - 1.5e-3, commit_s=1e-4)
        if over:
            six[over[0]] += extra
        self.t += between
        self.cpu += between_cpu
        whole = phase("batch.step", step=self.step, running=1, waiting=0)
        whole.start, whole.cpu_start = self.t, self.cpu
        self.t += wall + extra
        self.cpu += cpu
        whole.end, whole.cpu_end = self.t, self.cpu
        b._acc.update(six, step_wall_s=wall + extra, step_cpu_s=cpu,
                      admit_cpu_s=1e-4)
        if launched:
            b._acc["steps"] = 1
            self.step += 1
        if admitted:
            b._acc.update(admitted=admitted, prefill_tokens=prefill_tokens)
        b._fold_acc(compile_totals(), whole)
        return whole

    def sound(self, n, **kw):
        for _ in range(n):
            self.call(**kw)


@pytest.fixture
def d():
    return Driver()


# ---------------------------------------------------------------------------
# the verdict
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seen", range(7))
def test_no_verdict_before_five_of_a_kind(d, seen):
    d.sound(seen)
    d.call(over=("sync_s", 1.0))                # a second over anything seen
    r = d.b.report()
    assert r["steps"] == seen + 1
    assert r["steps_judged"] == max(0, seen - 5) + (seen >= 5)
    assert r["stalls"] == (seen >= 5) == len(r["stall_log"])


@pytest.mark.parametrize("key", PHASES)
def test_a_wall_50ms_over_the_median_of_nine_is_one_stall(d, key):
    d.sound(9, wall=0.100)                      # older than the last nine
    for k in range(9):                          # 10 .. 18 ms: median 14
        d.call(wall=WALL + 1e-3 * k)
    r0 = d.b.report()
    d.call(wall=0.014, over=(key, STALL_EXCESS_S - 1e-3))   # 49 ms over: sound
    assert d.b.report()["stalls"] == r0["stalls"] == 0
    t_s, step = d.t + 0.0002, d.step
    # (the sound step joined its kind: the last nine are 11 .. 18 and 63 ms)
    d.call(wall=0.015, over=(key, 0.060), cpu=0.002)
    r1 = d.b.report()
    assert r1["stalls"] == 1 and r1["steps_judged"] - r0["steps_judged"] == 2
    (rec,) = r1["stall_log"]
    assert rec["where"] == key[:-2] and rec["step"] == step
    assert rec["t_s"] == pytest.approx(t_s)
    assert rec["wall_s"] == pytest.approx(0.075)
    assert rec["expected_s"] == pytest.approx(0.015)
    assert rec["excess_s"] == pytest.approx(0.060)
    assert r1["stall_excess_s"] == pytest.approx(0.060)
    # the thread computed no more than in a sound step: all of it off the CPU
    assert rec["cpu_s"] == 0.002
    assert rec["off_cpu_s"] == pytest.approx(0.060)
    assert r1["stall_off_cpu_s"] == pytest.approx(0.060)
    assert rec[key] == pytest.approx(rec["wall_s"] - sum(
        rec[k] for k in PHASES if k != key))
    assert (rec["admitted"], rec["prefill_tokens"], rec["running"],
            rec["waiting"]) == (0, 0, 1, 0)


def test_a_stall_the_thread_computed_through_is_on_the_cpu(d):
    d.sound(9)
    d.call(over=("build_s", 0.080), cpu=0.082)
    (rec,) = d.b.report()["stall_log"]
    assert rec["where"] == "build" and rec["cpu_s"] == 0.082
    assert rec["off_cpu_s"] == pytest.approx(0.0, abs=1e-9)
    assert d.b.report()["stall_off_cpu_s"] == pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize("between, stalls", [(0.049, 0), (0.051, 1),
                                             (0.080, 1)])
def test_a_long_between_is_a_stall_of_the_callers(d, between, stalls):
    d.sound(9)
    returned, step = d.t, d.step
    d.call(between=between, between_cpu=0.003)
    r = d.b.report()
    assert r["stalls"] == stalls == len(r["stall_log"])
    assert r["stall_excess_s"] == pytest.approx(between * stalls)
    if stalls:
        (rec,) = r["stall_log"]
        assert rec["where"] == "between" and rec["step"] == step
        assert rec["t_s"] == returned and rec["expected_s"] == 0.0
        assert rec["wall_s"] == rec["excess_s"] == pytest.approx(between)
        assert rec["cpu_s"] == pytest.approx(0.003)
        assert rec["off_cpu_s"] == pytest.approx(between - 0.003)
        assert all(rec[k] == 0.0 for k in PHASES)


def test_a_step_can_stall_and_follow_a_stalled_caller(d):
    d.sound(9)
    d.call(between=0.070, over=("admit_s", 0.120))
    r = d.b.report()
    assert [rec["where"] for rec in r["stall_log"]] == ["between", "admit"]
    assert r["stalls"] == 2
    assert r["stall_excess_s"] == pytest.approx(0.190)


def test_a_call_that_launched_nothing_is_in_none_of_it(d):
    d.sound(9)
    r0 = d.b.report()
    d.call(over=("sync_s", 0.500), launched=False)   # read the step in flight
    r1 = d.b.report()
    assert r1["step_wall_s"] - r0["step_wall_s"] == pytest.approx(0.510)
    assert r1["step_cpu_s"] - r0["step_cpu_s"] == pytest.approx(0.002)
    for k in ("steps", "steps_judged", "stalls", "stall_excess_s"):
        assert r1[k] == r0[k], k
    # and the launched step after it has no step before it: its wait is not
    # the caller's
    d.call(between=0.300)
    assert d.b.report()["stalls"] == 0
    assert all(len(rows) <= 9 and all(row[0] < 0.1 for row in rows)
               for rows in d.b._judge.kinds.values())


def test_an_idle_batchers_wait_is_no_stall(d):
    d.sound(9)
    del d.b._slot_to_sid[0]                     # the last stream finished
    d.call()
    d.b._slot_to_sid[0] = 0
    d.call(between=5.0)                         # the caller slept: no hand-off
    assert d.b.report()["stalls"] == 0


def test_kinds_are_judged_apart(d):
    """A step that admits four streams takes 35 ms beside a plain step's 10;
    one that evicts 80: each is held against its own kind, and an admitting
    step that is long for an admitting step is a stall."""
    for _ in range(6):
        d.sound(3)
        d.call(wall=0.035, admitted=4, prefill_tokens=2048)
        d.b.stats["evicted"] += 1
        d.call(wall=0.080)
    r0 = d.b.report()
    assert r0["stalls"] == 0 and set(d.b._judge.kinds) == {
        (0, 0, False), (4, 2048, False), (0, 0, True)}
    assert r0["steps_judged"] == r0["steps"] - 3 * 5
    d.call(wall=0.035, admitted=4, prefill_tokens=1024)     # a kind of its own
    step = d.step
    d.call(wall=0.035, over=("admit_s", 0.115), admitted=4,
           prefill_tokens=2048)
    r1 = d.b.report()
    assert r1["stalls"] == 1 and r1["steps_judged"] - r0["steps_judged"] == 1
    (rec,) = r1["stall_log"]
    assert (rec["step"], rec["where"], rec["admitted"],
            rec["prefill_tokens"]) == (step, "admit", 4, 2048)
    assert rec["expected_s"] == pytest.approx(0.035)
    assert rec["excess_s"] == pytest.approx(0.115)


def test_a_kind_follows_its_walls(d):
    """The expectation is the median of the LAST nine: a kind that grows
    slower is stalled against where it is, not against where it was."""
    d.sound(9, wall=0.010)
    d.sound(5, wall=0.200)      # the fifth moves the median to 0.2
    r = d.b.report()
    assert r["stalls"] == 5 and r["stall_log"][-1]["expected_s"] == 0.010
    d.sound(4, wall=0.200)
    assert d.b.report()["stalls"] == 5
    d.call(wall=0.200, over=("sync_s", 0.051))
    assert d.b.report()["stall_log"][-1]["expected_s"] == pytest.approx(0.2)


@pytest.mark.parametrize("what", ["kinds", "ring"])
def test_the_table_of_kinds_and_the_ring_stay_bounded(d, what):
    if what == "kinds":
        d.sound(3)                              # the oldest kind: (0, 0, False)
        for n in range(1, 200):
            d.call(admitted=1, prefill_tokens=n)
            if n == 100:
                d.call()                        # seen again: it stays
        kinds = d.b._judge.kinds
        assert len(kinds) == batching._KINDS_MAX == 128
        assert (0, 0, False) in kinds and (1, 199, False) in kinds
        assert (1, 1, False) not in kinds and (1, 72, False) not in kinds
        assert all(len(rows) <= batching._KIND_WALLS for rows in kinds.values())
    else:
        d.sound(9)
        for n in range(70):
            d.call(between=0.060 + 1e-3 * n)
        r = d.b.report()
        assert r["stalls"] == 70 and len(r["stall_log"]) == 64
        walls = [rec["wall_s"] for rec in r["stall_log"]]   # oldest first
        assert walls == pytest.approx([0.066 + 1e-3 * n for n in range(64)])
        assert len(d.b._judge.kinds[(0, 0, False)]) == 9


@pytest.mark.parametrize("key", COUNTERS)
def test_new_counters_are_additive_across_report_deltas(d, key):
    d.sound(12)
    d.call(over=("sync_s", 0.100))
    r0 = d.b.report()
    own = {"step_cpu_s": 6 * 0.002 + 0.050, "admit_cpu_s": 7e-4,
           "steps_judged": 6, "stalls": 2, "stall_excess_s": 0.070 + 0.090,
           "stall_off_cpu_s": 0.070 - 0.048 + 0.090 - 0.001}
    d.sound(3)
    d.call(over=("launch_s", 0.070), cpu=0.050)
    d.call(between=0.090, between_cpu=0.001, launched=False)
    d.call(between=0.090, between_cpu=0.001)    # ... whose wait is nobody's
    d.call(between=0.090, between_cpu=0.001)
    r1 = d.b.report()
    assert r1[key] - r0[key] == pytest.approx(own[key])
    assert r1["steps"] - r0["steps"] == 6


# ---------------------------------------------------------------------------
# what a record holds of the thread, the process and the host
# ---------------------------------------------------------------------------


def test_a_record_differences_the_threads_usage_over_the_call(d, monkeypatch):
    usage = iter((0.5 + 0.01 * n, 0.25 + 0.02 * n, 10 * n, 3 * n, 100 * n, n)
                 for n in range(1000))
    monkeypatch.setattr(batching, "thread_usage", lambda: next(usage))
    d.sound(9)
    d.call(over=("sync_s", 0.120))
    (rec,) = d.b.report()["stall_log"]
    assert rec["cpu_user_s"] == pytest.approx(0.01)
    assert rec["cpu_sys_s"] == pytest.approx(0.02)
    assert (rec["nvcsw"], rec["nivcsw"], rec["minflt"], rec["majflt"]) == (
        10, 3, 100, 1)
    assert 0.0 <= rec["proc_cpu_s"] < 5.0


def test_a_platform_without_thread_usage_leaves_the_fields_empty(d,
                                                                 monkeypatch):
    monkeypatch.setattr(batching, "thread_usage", lambda: None)
    d.sound(9)
    d.call(over=("sync_s", 0.120))
    (rec,) = d.b.report()["stall_log"]
    assert all(rec[k] is None for k in tracing.THREAD_USAGE)
    assert rec["cpu_s"] == 0.002 and rec["off_cpu_s"] == pytest.approx(0.120)


def test_a_stall_counts_collections_since_the_last_report_or_stall(d):
    d.sound(9)
    d.b.report()
    gc.collect()
    gc.collect()
    d.call(over=("commit_s", 0.120))
    d.call(over=("commit_s", 0.120))
    first, second = d.b.report()["stall_log"]
    assert first["where"] == "commit" and first["gc"][2] >= 2
    assert second["gc"][2] == 0 and len(second["gc"]) == 3


def test_a_stall_is_one_warning(d, caplog):
    d.sound(9)
    with caplog.at_level(logging.WARNING, logger=batching.__name__):
        d.sound(3)
        assert not caplog.records
        d.call(over=("admit_s", 0.115))
    (rec,) = caplog.records
    assert rec.levelno == logging.WARNING and rec.name == batching.__name__
    text = rec.getMessage()
    assert "batch.step 12 stalled in admit" in text and "115.0 ms" in text
    assert "100% of it off the CPU" in text and (
        "was throttled 0.0 ms" in text or "shows no cpu.stat" in text)


CPU_STAT_V2 = ("usage_usec 9000000\nuser_usec 8000000\nsystem_usec 1000000\n"
               "nr_periods 700\nnr_throttled 12\nthrottled_usec 1500000\n")
CPU_STAT_V1 = "nr_periods 700\nnr_throttled 7\nthrottled_time 2500000000\n"
PRESSURE = ("some avg10=0.00 avg60=0.00 avg300=0.00 total={}\n"
            "full avg10=0.00 avg60=0.00 avg300=0.00 total=1\n")
NO_HOST = dict.fromkeys(("nr_throttled", "cpu_throttled_s", "pressure_cpu_s",
                         "pressure_memory_s", "pressure_io_s"))


@pytest.fixture
def host_files(tmp_path, monkeypatch):
    """The four places ``host_counters`` reads, under a temporary directory
    that holds none of them yet."""
    paths = {"cpu_stat": (str(tmp_path / "cpu.stat"),
                          str(tmp_path / "cpu" / "cpu.stat")),
             "pressure": str(tmp_path / "pressure")}
    (tmp_path / "cpu").mkdir()
    (tmp_path / "pressure").mkdir()
    monkeypatch.setattr(tracing, "HOST_COUNTER_PATHS", paths)
    return tmp_path


@pytest.mark.parametrize("machine", ["none", "v2", "v1", "garbled"])
def test_host_counters_by_machine(host_files, machine):
    want = dict(NO_HOST)
    if machine == "v2":
        (host_files / "cpu.stat").write_text(CPU_STAT_V2)
        want.update(nr_throttled=12, cpu_throttled_s=1.5)
    if machine == "v1":
        (host_files / "cpu" / "cpu.stat").write_text(CPU_STAT_V1)
        (host_files / "pressure" / "cpu").write_text(PRESSURE.format(4000000))
        (host_files / "pressure" / "io").write_text(PRESSURE.format(250000))
        want.update(nr_throttled=7, cpu_throttled_s=2.5, pressure_cpu_s=4.0,
                    pressure_io_s=0.25)
    if machine == "garbled":
        # a v2 file of a container with no CPU controller, a pressure file
        # that is not one
        (host_files / "cpu.stat").write_text("usage_usec 5\nnr_throttled x\n")
        (host_files / "pressure" / "memory").write_text("some total=soon\n\n")
    assert host_counters() == pytest.approx(want)


def test_report_on_a_machine_with_none_of_the_files(host_files, d, caplog):
    r0 = d.b.report()
    assert r0["host"] == NO_HOST
    d.sound(9)
    with caplog.at_level(logging.WARNING, logger=batching.__name__):
        d.call(over=("sync_s", 0.120))          # and a stall there logs
    assert "the container shows no cpu.stat" in caplog.text
    r1 = d.b.report()
    (rec,) = r1["stall_log"]
    assert rec["host"] == rec["host_delta"] == NO_HOST
    assert rec["host_age_s"] >= 0.0 and r1["host"] == NO_HOST


def test_a_stall_differences_the_host_since_the_last_report(host_files, d,
                                                            caplog):
    (host_files / "cpu.stat").write_text(CPU_STAT_V2)
    d.sound(9)
    d.b.report()
    (host_files / "cpu.stat").write_text(
        CPU_STAT_V2.replace("12", "14").replace("1500000", "1700000"))
    with caplog.at_level(logging.WARNING, logger=batching.__name__):
        d.call(over=("sync_s", 0.120))
    assert "was throttled 200.0 ms (2 periods)" in caplog.text
    (rec,) = d.b.report()["stall_log"]
    assert rec["host"]["nr_throttled"] == 14
    assert rec["host_delta"]["nr_throttled"] == 2
    assert rec["host_delta"]["cpu_throttled_s"] == pytest.approx(0.2)
    assert rec["host_delta"]["pressure_cpu_s"] is None


# ---------------------------------------------------------------------------
# the phase clock
# ---------------------------------------------------------------------------


class _CountedClock:
    def __init__(self):
        self.thread_reads = 0

    def monotonic(self):
        return time.monotonic()

    def thread_time(self):
        self.thread_reads += 1
        return time.thread_time()


@pytest.mark.parametrize("work", ["sleep", "spin"])
def test_phase_with_a_cpu_key_adds_thread_seconds_under_its_wall(work):
    acc = {}
    with phase("batch.step", acc, "wall", cpu_key="cpu") as whole:
        if work == "sleep":
            time.sleep(0.02)
        else:
            end = time.thread_time() + 0.01
            while time.thread_time() < end:
                pass
    assert whole.cpu_start < whole.cpu_end
    assert acc["cpu"] == pytest.approx(whole.cpu_end - whole.cpu_start)
    # the thread's readings lie inside the wall's
    assert 0 < acc["cpu"] <= acc["wall"] + 1e-4
    if work == "sleep":
        assert acc["cpu"] < 0.01 < 0.02 <= acc["wall"]
    else:
        assert acc["cpu"] >= 0.01


def test_a_chained_cpu_key_counts_from_the_edge_its_wall_does(monkeypatch):
    clock = _CountedClock()
    monkeypatch.setattr(tracing, "time", clock)
    acc = {}
    with phase("batch.step", acc, "wall", cpu_key="cpu") as whole:
        with phase("batch.step.admit", acc, "a", after=whole,
                   cpu_key="a_cpu") as ph:
            pass
        assert ph.cpu_start == whole.cpu_start and ph.start == whole.start
        with phase("batch.step.grow", acc, "g", after=ph, cpu_key="g_cpu") as gr:
            pass
        assert gr.cpu_start == ph.cpu_end and gr.start == ph.end
    # in and out of the whole, out of each of the two: none at their starts
    assert clock.thread_reads == 4
    assert acc["a_cpu"] + acc["g_cpu"] <= acc["cpu"]
    # a phase after one that kept no thread clock reads its own
    with phase("x", acc, "x") as plain:
        pass
    with phase("y", acc, "y", after=plain, cpu_key="y_cpu") as ph:
        pass
    assert plain.cpu_end is None and ph.cpu_start is not None
    assert clock.thread_reads == 6


def test_phase_without_a_cpu_key_reads_no_thread_clock(monkeypatch):
    clock = _CountedClock()
    monkeypatch.setattr(tracing, "time", clock)
    acc = {}
    with phase("batch.step", acc, "wall") as whole:
        with phase("batch.step.admit", acc, "a", after=whole):
            pass
    with phase("batch.step"):
        pass
    assert clock.thread_reads == 0 and set(acc) == {"wall", "a"}
    assert whole.cpu_start is None and whole.cpu_end is None
