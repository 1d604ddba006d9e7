"""Tier-1 guard for the benchmark's program-trace files (the benchmark's own
tests under ``benchmark/tests`` are not collected by the driver's run): the
reduction of ``benchmark/program_trace.py`` and every per-layer reader this
layer added, on the events recorded on a v5e under ``benchmark/testdata``."""
import json
import os

import pytest

from benchmark import program_trace as pt
from benchmark.cell import HERE, load_cell, load_module

TRACE_READERS = ("idle_in_sync_ms", "idle_host_ms", "admit_dev_ms")
COUNTER_READERS = {"step_wall_ms": "step_wall_s", "host_admit_ms": "admit_s",
                   "host_grow_ms": "grow_s", "host_build_ms": "build_s",
                   "host_launch_ms": "launch_s", "host_sync_ms": "sync_s",
                   "host_commit_ms": "commit_s"}
CELLS = ("qwen2-0.5b.chat-steady", "qwen2-0.5b.decode-sat",
         "qwen2-1.5b-split4.decode-sat")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "testdata", "program_events_v5e.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def table(recorded):
    t = pt.reduce_program(recorded)
    t["step_module"] = "_batched_step_jit"
    return t


def _read(name, record):
    return load_module(os.path.join(HERE, "metrics", name + ".py"),
                       "tier1_" + name).read(record)


def _record(traced, r0=None, r1=None):
    with open(os.path.join(HERE, "configs", "qwen2-0.5b.json")) as f:
        config = json.load(f)
    return {"trace": {"busy_s": 1.0} if traced else None, "config": config,
            "report0": r0 or {}, "report1": r1 or {}}


def test_recorded_events_reduce_to_the_table_beside_them(recorded, table):
    want = recorded["expected"]
    assert table["idle_s"] == pytest.approx(want["idle_s"])
    for name, row in want["spans"].items():
        assert table["spans"][name] == pytest.approx(row), name
    assert table["scopes"] == pytest.approx(want["scopes"])
    # no idle second under two spans, none lost
    idle = (sum(r["idle_s"] for r in table["spans"].values())
            + table["idle_outside_s"])
    assert idle == pytest.approx(table["idle_s"])
    assert sum(table["scopes"].values()) == pytest.approx(
        table["window_s"] - table["idle_s"])


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_reader_on_the_recorded_events(name, recorded, table,
                                             monkeypatch):
    monkeypatch.setitem(pt._TABLES, "table", table)
    assert _read(name, _record(True)) == pytest.approx(
        recorded["expected"]["metrics"][name])
    assert _read(name, _record(False)) is None        # an untraced run
    monkeypatch.setitem(pt._TABLES, "table", None)    # no profile found
    assert _read(name, _record(True)) is None


@pytest.mark.parametrize("name", sorted(COUNTER_READERS))
def test_counter_reader_is_a_window_delta_per_step(name):
    key = COUNTER_READERS[name]
    r0, r1 = {"steps": 5, key: 1.0}, {"steps": 25, key: 3.0}
    assert _read(name, _record(False, r0, r1)) == pytest.approx(100.0)
    # the parent's report() has no such clock: nothing, and no exception
    assert _read(name, _record(False, {"steps": 5}, {"steps": 25})) is None


def test_per_admission_and_compile_readers():
    r0 = {"steps": 5, "admitted": 2, "admit_s": 1.0, "queue_wait_s": 0.5,
          "compiles": 40}
    r1 = {"steps": 25, "admitted": 12, "admit_s": 1.7, "queue_wait_s": 0.9,
          "compiles": 41}
    rec = _record(False, r0, r1)
    assert _read("admit_ms_req", rec) == pytest.approx(70.0)
    assert _read("queue_wait_ms", rec) == pytest.approx(40.0)
    assert _read("compiles_in_window", rec) == 1
    old = _record(False, {"steps": 5, "admitted": 2},
                  {"steps": 25, "admitted": 12})
    assert _read("admit_ms_req", old) is None
    assert _read("compiles_in_window", old) is None


@pytest.mark.parametrize("cell", CELLS)
def test_cell_lists_the_new_readers(cell):
    names = {m.name for m in load_cell(cell).per_layer}
    assert names >= set(TRACE_READERS) | set(COUNTER_READERS) | {
        "admit_ms_req", "compiles_in_window"}
    assert ("queue_wait_ms" in names) == (cell == CELLS[0])
    assert "kv_write_dev_ms" not in names      # PERF.md section 3 says why
