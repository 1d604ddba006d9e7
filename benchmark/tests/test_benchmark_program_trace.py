"""``program_trace`` on hand-made events whose table is worked out in the
comments, on the events recorded on a v5e under ``benchmark/testdata/``, and
the metric readers that read the table and the batcher's phase counters."""
import json
import os
import time

import numpy as np
import pytest

from benchmark import program_trace as pt
from benchmark.cell import HERE, load_module

MS = 1_000_000
STEP = "jit__batched_step_jit(1)"
FIXTURE = os.path.join(HERE, "testdata", "program_events_v5e.json")
NEW_READERS = ("step_wall_ms", "host_admit_ms", "host_grow_ms",
               "host_build_ms", "host_launch_ms", "host_sync_ms",
               "host_commit_ms", "admit_ms_req", "queue_wait_ms",
               "compiles_in_window", "idle_in_sync_ms", "idle_host_ms",
               "admit_dev_ms")


def _ms(rows):
    return [[name, a * MS, (b - a) * MS] for name, a, b in rows]


def _events():
    """A 200 ms window: step A with one admission, step B, and step C cut by
    the window's edge at 200 (it runs to 230)."""
    host = _ms([
        ("bench.window", 0, 200), ("bench.step", 4, 96),   # bench.*: ignored
        ("batch.step", 5, 95),
        ("batch.step.admit", 5, 30), ("batch.admit", 10, 28),
        ("batch.admit.prefill", 10, 18), ("batch.admit.tok0_sync", 20, 26),
        ("batch.step.build", 30, 40), ("batch.step.launch", 40, 45),
        ("batch.step.sync", 45, 90), ("batch.step.commit", 90, 94),
        ("batch.step", 100, 150),
        ("batch.step.admit", 100, 101), ("batch.step.build", 101, 110),
        ("batch.step.launch", 110, 112), ("batch.step.sync", 112, 148),
        ("batch.step.commit", 148, 150),
        ("batch.step", 190, 230),
        ("batch.step.admit", 190, 195), ("batch.step.build", 195, 215)])
    ops = _ms([
        ("", 12, 22),                                       # the prefill
        ("paged_kv.adopt", 22, 27),
        ("", 46, 86),                                       # a while: self 2
        ("paged_kv.write", 46, 66), ("attn.decode", 66, 76), ("mlp", 76, 84),
        ("paged_kv.write", 113, 130), ("mlp", 130, 145),
        ("paged_kv.adopt", 196, 206)])                      # cut at 200
    modules = _ms([("jit__prefill_jit(7)", 12, 22),
                   ("jit__adopt_impl(3)", 22, 27), (STEP, 46, 86),
                   (STEP, 113, 145), ("jit__adopt_impl(3)", 196, 206)])
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_idle_goes_to_the_innermost_open_span_and_is_counted_once():
    t = pt.reduce_program(_events())
    assert np.isclose(t["window_s"], 0.200) and t["chips"] == 1
    # busy [12,27) [46,86) [113,145) [196,200) = 91 ms; idle [0,12) [27,46)
    # [86,113) [145,196) = 109 ms
    assert np.isclose(t["idle_s"], 0.109)
    idle = {name: row["idle_s"] for name, row in t["spans"].items()}
    # [5,10) [28,30) [100,101) [190,195): the admit phase outside any admission
    assert np.isclose(idle["batch.step.admit"], 0.013)
    # [10,12): the admission's prefill span is open, not yet on the device
    assert np.isclose(idle["batch.admit.prefill"], 0.002)
    # [27,28): batch.admit after its children closed
    assert np.isclose(idle["batch.admit"], 0.001)
    assert np.isclose(idle["batch.admit.tok0_sync"], 0.0)
    assert np.isclose(idle["batch.step.build"], 0.010 + 0.009 + 0.001)
    assert np.isclose(idle["batch.step.launch"], 0.005 + 0.002)
    assert np.isclose(idle["batch.step.sync"], 0.001 + 0.004 + 0.001 + 0.003)
    assert np.isclose(idle["batch.step.commit"], 0.004 + 0.002)
    # [94,95): step A after its commit closed; B and C are tiled by phases
    assert np.isclose(idle["batch.step"], 0.001)
    # [0,5) [95,100) [150,190)
    assert np.isclose(t["idle_outside_s"], 0.050)
    assert np.isclose(sum(idle.values()) + t["idle_outside_s"], t["idle_s"])
    assert not any(name.startswith("bench.") for name in t["spans"])


def test_a_span_cut_by_the_windows_edge_counts_by_its_share_inside():
    rows = pt.reduce_program(_events())["spans"]
    assert np.isclose(rows["batch.step"]["count"], 2 + 10 / 40)
    assert np.isclose(rows["batch.step"]["total_s"], 0.090 + 0.050 + 0.010)
    assert np.isclose(rows["batch.step"]["self_s"], 0.001)
    assert np.isclose(rows["batch.step.build"]["count"], 2 + 5 / 20)
    assert np.isclose(rows["batch.step.build"]["total_s"], 0.010 + 0.009
                      + 0.005)
    assert np.isclose(rows["batch.step.admit"]["count"], 3)
    assert np.isclose(rows["batch.step.admit"]["self_s"], 0.007 + 0.001
                      + 0.005)
    assert np.isclose(rows["batch.admit"]["self_s"], 0.002 + 0.002)
    assert np.isclose(rows["batch.admit"]["count"], 1)
    # self times tile the spans: they sum to the outermost spans' time inside
    assert np.isclose(sum(r["self_s"] for r in rows.values()),
                      rows["batch.step"]["total_s"])


def test_device_seconds_by_scope_and_by_executable():
    t = pt.reduce_program(_events())
    sc = t["scopes"]
    assert np.isclose(sc["paged_kv.write"], 0.020 + 0.017)
    assert np.isclose(sc["attn.decode"], 0.010)
    assert np.isclose(sc["mlp"], 0.008 + 0.015)
    assert np.isclose(sc["paged_kv.adopt"], 0.005 + 0.004)     # clipped
    assert np.isclose(sc[pt.UNSCOPED], 0.010 + 0.002)          # while: self
    assert np.isclose(sum(sc.values()), t["window_s"] - t["idle_s"])
    assert t["modules"][STEP] == {"runs": 2.0, "seconds": pytest.approx(0.072)}
    assert np.isclose(t["modules"]["jit__adopt_impl(3)"]["seconds"], 0.009)


def test_means_are_over_the_device_planes():
    ev = _events()
    ev["devices"]["/device:TPU:1"] = {
        "ops": _ms([("split.hop.0", 0, 200)]), "modules": []}
    t = pt.reduce_program(ev)
    assert t["chips"] == 2 and np.isclose(t["idle_s"], 0.109 / 2)
    assert np.isclose(t["spans"]["batch.step.sync"]["idle_s"], 0.009 / 2)
    assert np.isclose(t["scopes"]["split.hop.0"], 0.100)
    assert np.isclose(t["scopes"]["paged_kv.write"], 0.037 / 2)
    assert t["modules"][STEP]["runs"] == 1.0


def test_a_program_without_spans_reduces_to_an_empty_table():
    """The parent of the PR that added the spans: only the benchmark's own
    annotations and unscoped operations."""
    ev = _events()
    ev["host"] = [h for h in ev["host"] if h[0].startswith("bench.")]
    for op in ev["devices"]["/device:TPU:0"]["ops"]:
        op[0] = ""
    t = pt.reduce_program(ev)
    assert t["spans"] == {} and np.isclose(t["idle_outside_s"], t["idle_s"])
    assert list(t["scopes"]) == [pt.UNSCOPED]
    with pytest.raises(ValueError):
        pt.reduce_program({"devices": ev["devices"], "host": []})
    with pytest.raises(ValueError):
        pt.reduce_program({"devices": {}, "host": ev["host"]})


def test_innermost_registered_scope_of_a_path():
    scopes = ("attn.decode", "paged_kv.write", "split.hop.*")
    path = ("jit(_batched_step_jit)/while/body/closed_call/attn.decode/"
            "paged_kv.write/scatter:")
    assert pt.innermost_scope(path, scopes) == "paged_kv.write"
    assert pt.innermost_scope("jit(f)/split.stage/split.hop.2/ppermute:",
                              scopes) == "split.hop.2"
    assert pt.innermost_scope("jit(f)/mul:", scopes) == ""
    assert pt.innermost_scope("", scopes) == ""
    from edgellm_tpu.obs import names

    assert set(pt.program_scopes()) == set(names.SCOPE_NAMES) | set(
        names.SCOPE_TEMPLATES)


def _pb(field, value):
    """One protobuf field: a varint for an int, length-delimited for bytes."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    return varint(field << 3 | 2) + varint(len(value)) + value


def test_scope_paths_are_read_out_of_the_event_metadata(tmp_path):
    """A hand-encoded XSpace: a device plane whose two operations carry their
    path as ``tf_op`` (one as a string, one as a reference to a stat
    metadata's name, the two forms the profiler writes), a third with no such
    stat, events that are never parsed, and a host plane that is skipped."""
    def stat_meta(i, name):
        return _pb(5, _pb(1, i) + _pb(2, _pb(1, i) + _pb(2, name)))

    def event_meta(i, name, *stats):
        body = _pb(1, i) + _pb(2, name) + b"".join(_pb(5, st) for st in stats)
        return _pb(4, _pb(1, i) + _pb(2, body))

    flops = _pb(1, 7) + _pb(3, 300)                  # uint64_value
    device = (_pb(1, 1) + _pb(2, b"/device:TPU:0")
              + _pb(3, _pb(2, b"XLA Ops") + _pb(4, _pb(1, 11) + _pb(2, 5)
                                                + _pb(3, 2 ** 40)))
              + stat_meta(7, b"flops") + stat_meta(9, b"tf_op")
              + stat_meta(12, b"jit(f)/attn.decode/paged_kv.write/scatter:")
              + event_meta(11, b"%fusion.1 = bf16[8]{0} fusion(%p)", flops,
                           _pb(1, 9) + _pb(5, b"jit(f)/mlp/dot_general:"))
              + event_meta(300, b"%scatter.2 = bf16[8]{0} scatter(%p)",
                           _pb(1, 9) + _pb(7, 12))
              + event_meta(13, b"%copy.3 = bf16[8]{0} copy(%p)", flops))
    host = (_pb(2, b"/host:CPU") + stat_meta(9, b"tf_op")
            + event_meta(1, b"batch.step", _pb(1, 9) + _pb(5, b"x/mlp/y")))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb(1, device) + _pb(1, host) + _pb(4, b"hostname"))
    assert pt.op_scope_paths(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = bf16[8]{0} fusion(%p)": "jit(f)/mlp/dot_general:",
        "%scatter.2 = bf16[8]{0} scatter(%p)":
            "jit(f)/attn.decode/paged_kv.write/scatter:"}}
    with pytest.raises(ValueError):
        list(pt._fields(memoryview(b"\x0b")))         # a group: not read


def _brute_force(ev):
    """The same table by another road: cut the window at every boundary and
    ask, for each piece, whether the device is busy and which open span
    started last."""
    host, plane = ev["host"], ev["devices"]["/device:TPU:0"]
    lo, hi = next((s, s + d) for n, s, d in host if n == "bench.window")
    spans = [(n, s, s + d) for n, s, d in host if n.startswith("batch.")]
    ops = [(s, s + d) for _, s, d in plane["ops"]]
    cuts = sorted({lo, hi} | {t for _, a, b in spans for t in (a, b)
                              if lo < t < hi}
                  | {t for a, b in ops for t in (a, b) if lo < t < hi})
    idle_by, total = {}, 0
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e in ops):
            continue
        total += b - a
        open_ = [(s, -e, n) for n, s, e in spans if s <= mid < e]
        name = max(open_)[2] if open_ else pt.OUTSIDE
        idle_by[name] = idle_by.get(name, 0) + b - a
    return total * 1e-9, {k: v * 1e-9 for k, v in idle_by.items()}


def test_brute_force_agrees_on_the_hand_made_events():
    ev = _events()
    t = pt.reduce_program(ev)
    total, idle_by = _brute_force(ev)
    assert np.isclose(total, t["idle_s"])
    assert np.isclose(idle_by.pop(pt.OUTSIDE), t["idle_outside_s"])
    for name, v in idle_by.items():
        assert np.isclose(t["spans"][name]["idle_s"], v), name


# ---------------------------------------------------------------------------
# the events recorded on a v5e, with their table beside them
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def test_recorded_v5e_events_reduce_to_the_table_beside_them(recorded):
    t = pt.reduce_program(recorded)
    want = recorded["expected"]
    assert t["chips"] == 1 and np.isclose(t["window_s"], want["window_s"])
    assert np.isclose(t["idle_s"], want["idle_s"])
    assert np.isclose(t["idle_outside_s"], want["idle_outside_s"])
    assert set(t["spans"]) == set(want["spans"])
    for name, row in want["spans"].items():
        for key, v in row.items():
            assert np.isclose(t["spans"][name][key], v), (name, key)
    for name, v in want["scopes"].items():
        assert np.isclose(t["scopes"][name], v), name
    # what the table must satisfy whatever the numbers: no idle second under
    # two spans, none lost; device seconds by scope are the busy seconds
    idle = sum(r["idle_s"] for r in t["spans"].values()) + t["idle_outside_s"]
    assert np.isclose(idle, t["idle_s"])
    assert np.isclose(sum(t["scopes"].values()), t["window_s"] - t["idle_s"])
    # two admissions; one step whole, one cut by each edge of the window
    assert np.isclose(t["spans"]["batch.admit"]["count"], 2)
    assert np.isclose(t["spans"]["batch.step.sync"]["count"], 2)
    assert 2 < t["spans"]["batch.step"]["count"] < 3
    # what the run showed: the device idles while the host builds the step's
    # inputs and launches it, and hardly at all while the host waits for it
    idle = {n: r["idle_s"] for n, r in t["spans"].items()}
    assert idle["batch.step.build"] > 0.5 and idle["batch.step.launch"] > 0.2
    assert idle["batch.step.sync"] < 0.01
    # the same by the brute-force road
    total, idle_by = _brute_force(recorded)
    assert np.isclose(total, t["idle_s"])
    for name, v in idle_by.items():
        got = (t["idle_outside_s"] if name == pt.OUTSIDE
               else t["spans"][name]["idle_s"])
        assert np.isclose(got, v), name


def _record(table, report0=None, report1=None):
    with open(os.path.join(HERE, "configs", "qwen2-0.5b.json")) as f:
        config = json.load(f)
    return {"trace": {"busy_s": 1.0, "window_s": 2.0} if table else None,
            "config": config, "report0": report0 or {},
            "report1": report1 or {}}


def _read(name, record):
    return load_module(os.path.join(HERE, "metrics", name + ".py"),
                       "t_" + name).read(record)


def test_trace_readers_on_the_recorded_events(recorded, monkeypatch):
    table = pt.reduce_program(recorded)
    table["step_module"] = "_batched_step_jit"
    monkeypatch.setitem(pt._TABLES, "table", table)
    rec = _record(table)
    steps = table["spans"]["batch.step"]["count"]
    sync = table["spans"]["batch.step.sync"]["idle_s"]
    assert _read("idle_in_sync_ms", rec) == pytest.approx(1e3 * sync / steps)
    assert _read("idle_host_ms", rec) == pytest.approx(
        1e3 * (table["idle_s"] - sync) / steps)
    assert _read("idle_in_sync_ms", rec) + _read("idle_host_ms", rec) == (
        pytest.approx(1e3 * table["idle_s"] / steps))
    other = sum(m["seconds"] for name, m in table["modules"].items()
                if "_batched_step_jit" not in name)
    assert _read("admit_dev_ms", rec) == pytest.approx(1e3 * other / 2)
    for name, v in recorded["expected"]["metrics"].items():
        assert _read(name, rec) == pytest.approx(v), name


def test_trace_readers_return_nothing_without_spans_or_without_a_trace(
        monkeypatch):
    ev = _events()
    ev["host"] = [h for h in ev["host"] if h[0].startswith("bench.")]
    for op in ev["devices"]["/device:TPU:0"]["ops"]:
        op[0] = ""
    table = pt.reduce_program(ev)
    table["step_module"] = "_batched_step_jit"
    monkeypatch.setitem(pt._TABLES, "table", table)
    for name in ("idle_in_sync_ms", "idle_host_ms", "admit_dev_ms"):
        assert _read(name, _record(table)) is None, name
        assert _read(name, _record(None)) is None, name
    monkeypatch.setitem(pt._TABLES, "table", None)   # no profile was found
    assert _read("idle_host_ms", _record(table)) is None


def test_counter_readers_difference_the_report_and_stand_down_on_an_old_one():
    r0 = {"steps": 10, "admitted": 4, "step_wall_s": 5.0, "admit_s": 0.5,
          "grow_s": 0.01, "build_s": 1.0, "launch_s": 0.2, "sync_s": 3.2,
          "commit_s": 0.09, "queue_wait_s": 0.4, "compiles": 7}
    r1 = {"steps": 30, "admitted": 14, "step_wall_s": 15.6, "admit_s": 1.5,
          "grow_s": 0.03, "build_s": 3.0, "launch_s": 0.6, "sync_s": 10.2,
          "commit_s": 0.27, "queue_wait_s": 0.9, "compiles": 7}
    rec = _record(None, r0, r1)
    assert _read("step_wall_ms", rec) == pytest.approx(530.0)
    assert _read("host_admit_ms", rec) == pytest.approx(50.0)
    assert _read("host_grow_ms", rec) == pytest.approx(1.0)
    assert _read("host_build_ms", rec) == pytest.approx(100.0)
    assert _read("host_launch_ms", rec) == pytest.approx(20.0)
    assert _read("host_sync_ms", rec) == pytest.approx(350.0)
    assert _read("host_commit_ms", rec) == pytest.approx(9.0)
    assert sum(_read(f"host_{p}_ms", rec) for p in (
        "admit", "grow", "build", "launch", "sync", "commit")) == (
        pytest.approx(_read("step_wall_ms", rec)))
    assert _read("admit_ms_req", rec) == pytest.approx(100.0)
    assert _read("queue_wait_ms", rec) == pytest.approx(50.0)
    assert _read("compiles_in_window", rec) == 0
    old = _record(None, {"steps": 10, "admitted": 4},
                  {"steps": 30, "admitted": 14})     # the parent's report()
    for name in NEW_READERS[:10]:
        assert _read(name, old) is None, name
    idle = _record(None, r0, dict(r1, steps=10, admitted=4))
    assert _read("step_wall_ms", idle) is None
    assert _read("admit_ms_req", idle) is None


def test_every_new_reader_has_one_entry_and_a_file():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    listed = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_READERS:
        assert os.path.exists(os.path.join(HERE, "metrics", name + ".py"))
        assert listed[name]["better"] == "lower", name
    assert listed["queue_wait_ms"]["moves"] == "ttft_mean_ms"
    assert listed["queue_wait_ms"]["workloads"] == ["qwen2-0.5b.chat-steady"]


# ---------------------------------------------------------------------------
# finding the run's profile
# ---------------------------------------------------------------------------


def _touch_profile(out_dir, cell, stamp, when):
    d = out_dir / cell / "trace" / "plugins" / "profile" / stamp
    d.mkdir(parents=True)
    p = d / "host.xplane.pb"
    p.write_bytes(b"x")
    os.utime(p, (when, when))
    return str(p)


def test_newest_profile_is_taken_and_one_older_than_the_process_refused(
        tmp_path):
    now = time.time()
    assert pt.newest_xplane(str(tmp_path)) is None
    _touch_profile(tmp_path, "cell-a", "1", now - 500)
    new = _touch_profile(tmp_path, "cell.b", "2", now - 5)
    path, cell_dir = pt.newest_xplane(str(tmp_path))
    assert path == new and cell_dir == str(tmp_path / "cell.b")
    assert pt.newest_xplane(str(tmp_path), not_before=now - 100)[0] == new
    assert pt.newest_xplane(str(tmp_path), not_before=now) is None


def test_table_is_reduced_once_per_process_and_written_beside_the_trace(
        tmp_path, monkeypatch):
    calls = []

    def fake_load(path, scopes):
        calls.append(path)
        return _events()

    _touch_profile(tmp_path, "cell.b", "2", time.time() + 5)
    monkeypatch.setattr(pt, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(pt, "load_program_events", fake_load)
    monkeypatch.setattr(pt, "_TABLES", {})
    monkeypatch.setattr(
        pt, "newest_xplane",
        lambda out_dir=None, not_before=0.0, _f=pt.newest_xplane: _f(
            str(tmp_path), not_before))
    rec = _record(True)
    assert pt.table_for(_record(None)) is None and calls == []
    first = pt.table_for(rec)
    assert pt.table_for(rec) is first and len(calls) == 1
    assert first["step_module"] == "_batched_step_jit"
    with open(tmp_path / "cell.b" / "program_spans.json") as f:
        assert json.load(f)["spans"].keys() == first["spans"].keys()
    assert _read("idle_in_sync_ms", rec) == pytest.approx(9 / 2.25)
    assert _read("admit_dev_ms", rec) == pytest.approx(19.0)
    assert len(calls) == 1
