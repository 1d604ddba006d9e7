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


# ---------------------------------------------------------------------------
# PR 34: what the batcher's fold keeps of a step, read over a window
# ---------------------------------------------------------------------------

FOLD_READERS = ("step_wall_p99_ms", "tail_step_wall_ms",
                "tail_step_admit_share", "admit_step_share",
                "admit_step_wall_ms", "plain_step_wall_ms",
                "between_steps_ms", "prefill_tok_s")
ALL_CELLS = CELLS + ("granite-4.0-h-small-ep2.decode-sat",
                     "mellum2-12b-a2.5b-pp4.decode-sat-mixed",
                     "mistral-small-4-119b-ep4.decode-sat-deep",
                     "trinity-mini-pp8.decode-sat-long",
                     "longcat-flash-chat-ep32.decode-sat-reason",
                     "lfm2-8b-a1b-pp2.decode-sat-docs",
                     "keye-vl-2.0-30b-a3b-ep4.decode-sat-context",
                     "deepseek-v3.2-exp-ep16.decode-sat-context",
                     "dots3-note-prev-ep8.decode-sat-context")
EDGES = [0.01, 0.02, 0.04, 0.08]            # five rows: under, three, over
PHASE_KEYS = ("admit_s", "grow_s", "build_s", "launch_s", "sync_s",
              "commit_s")


def _row(steps, admit, sync):
    """A table row whose wall is ``admit + sync`` but for 1 ms a step that
    the four small phases share."""
    small = 0.00025 * steps
    return [steps, admit, small, small, small, sync - 4 * small, small]


def _fold_reports(window_rows, **window):
    """``report0`` with a history of its own and ``report1`` = it + the
    window: the table row by row, every other key by ``window``, the six
    clocks by the table's columns (a closed loop: every call launched)."""
    before = [_row(7, 0.07, 0.07) for _ in window_rows]
    r0 = {"step_wall_hist": before, "step_wall_edges_s": EDGES, "steps": 35,
          "admitted": 9, "admit_steps": 5, "admit_step_wall_s": 0.7,
          "between_s": 0.1, "tok0_hold_s": 0.2, "prefill_tokens": 900,
          "prefill_s": 0.3,
          **{k: sum(r[i] for r in before)
             for i, k in enumerate(PHASE_KEYS, 1)}}
    r1 = {**r0, "step_wall_hist": [[a + b for a, b in zip(x, y)]
                                   for x, y in zip(before, window_rows)]}
    for i, k in enumerate(PHASE_KEYS, 1):
        r1[k] = r0[k] + sum(r[i] for r in window_rows)
    r1["steps"] = r0["steps"] + sum(r[0] for r in window_rows)
    for k, v in window.items():
        r1[k] = r0[k] + v
    return r0, r1


@pytest.fixture(scope="module")
def fold_record():
    """200 steps: 150 of 12 ms, 49 of 30 ms (a third of it admission), one
    of 500 ms (nine tenths admission); 20 of them admitted 25 streams."""
    rows = [_row(0, 0.0, 0.0), _row(150, 0.0, 1.8), _row(49, 0.49, 0.98),
            _row(0, 0.0, 0.0), _row(1, 0.45, 0.05)]
    return _record(False, *_fold_reports(
        rows, admitted=25, admit_steps=20, admit_step_wall_s=1.1,
        between_s=0.4, tok0_hold_s=0.25, prefill_tokens=6000,
        prefill_s=0.5))


@pytest.mark.parametrize("name, want", [
    # 198 of 200 steps lie under it: 48 of the 49 in [20, 40) ms
    ("step_wall_p99_ms", 20.0 + 20.0 * 48 / 49),
    # the slowest two: the 500 ms step and one of the 49, pro rata
    ("tail_step_wall_ms", (500.0 + 30.0) / 2),
    ("tail_step_admit_share", 100.0 * (0.45 + 0.49 / 49) / 0.53),
    ("admit_step_share", 10.0),
    ("admit_step_wall_ms", 55.0),
    ("plain_step_wall_ms", 1e3 * (1.8 + 1.47 + 0.5 - 1.1) / 180),
    ("between_steps_ms", 2.0),
    ("tok0_hold_ms", 10.0),
    ("prefill_tok_s", 12000.0)])
def test_fold_reader_on_a_hand_made_window(name, want, fold_record):
    assert _read(name, fold_record) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", FOLD_READERS + ("tok0_hold_ms",))
def test_fold_reader_gives_nothing_on_a_program_without_its_counters(name):
    # the parent's report(): steps, admissions and clocks, no table
    old = _record(False, {"steps": 5, "admitted": 2, "prefill_s": 0.1},
                  {"steps": 25, "admitted": 12, "prefill_s": 0.6})
    assert _read(name, old) is None
    # and a window in which nothing was launched or admitted divides by 0
    r0, _ = _fold_reports([_row(0, 0.0, 0.0)] * 5)
    assert _read(name, _record(False, r0, dict(r0))) is None


def test_no_admitting_step_leaves_the_plain_ones_and_the_tail():
    rows = [_row(0, 0.0, 0.0), _row(100, 0.0, 1.2)] + [_row(0, 0.0, 0.0)] * 3
    rec = _record(False, *_fold_reports(rows))
    assert _read("admit_step_share", rec) == 0.0
    assert _read("admit_step_wall_ms", rec) is None
    assert _read("plain_step_wall_ms", rec) == pytest.approx(12.0)
    assert _read("tail_step_wall_ms", rec) == pytest.approx(12.0)
    assert _read("tail_step_admit_share", rec) == 0.0
    # one row: the percentile goes by the bucket's edges, not its mean
    assert _read("step_wall_p99_ms", rec) == pytest.approx(19.9)
    assert _read("prefill_tok_s", rec) is None and _read(
        "tok0_hold_ms", rec) is None


def test_end_rows_give_their_mean_wall_to_the_percentile():
    over = [_row(0, 0.0, 0.0)] * 4 + [_row(10, 0.0, 200.0)]
    assert _read("step_wall_p99_ms", _record(
        False, *_fold_reports(over))) == pytest.approx(20000.0)
    under = [_row(10, 0.0, 0.05)] + [_row(0, 0.0, 0.0)] * 4
    assert _read("step_wall_p99_ms", _record(
        False, *_fold_reports(under))) == pytest.approx(5.0)


def test_the_two_identities_of_the_table(fold_record):
    """The window's rows sum to ``steps`` and, column by column, to the six
    ``host_*_ms`` x ``steps``; the admitting and the plain steps' walls,
    weighted by their shares, are the table's wall over its steps, which in a
    closed loop is ``step_wall_ms``."""
    r0, r1 = fold_record["report0"], fold_record["report1"]
    steps = r1["steps"] - r0["steps"]
    rows = [[b - a for a, b in zip(x, y)] for x, y in zip(
        r0["step_wall_hist"], r1["step_wall_hist"])]
    assert sum(r[0] for r in rows) == steps == 200
    reader_of = {key: name for name, key in COUNTER_READERS.items()}
    for i, key in enumerate(PHASE_KEYS, 1):
        column = sum(r[i] for r in rows)
        assert _read(reader_of[key], fold_record) * steps == pytest.approx(
            1e3 * column, rel=1e-3), key
    share = _read("admit_step_share", fold_record)
    mixed = (share * _read("admit_step_wall_ms", fold_record)
             + (100.0 - share) * _read("plain_step_wall_ms", fold_record))
    wall_ms = sum(1e3 * (r1[k] - r0[k]) for k in PHASE_KEYS) / steps
    assert mixed == pytest.approx(100.0 * wall_ms, rel=1e-3)


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_cell_lists_the_fold_readers(cell):
    per_layer = {m.name: m for m in load_cell(cell).per_layer}
    assert set(per_layer) >= set(FOLD_READERS)
    assert ("tok0_hold_ms" in per_layer) == (cell == CELLS[0])
    assert all(per_layer[n].unit == ("tokens/s" if n == "prefill_tok_s" else
                                     "%" if n.endswith("_share") else "ms")
               for n in FOLD_READERS)


# ---------------------------------------------------------------------------
# PR 36: the afmoe cell's four readers, on the recorded events and by hand
# ---------------------------------------------------------------------------

AFMOE_CELL = "trinity-mini-pp8.decode-sat-long"
AFMOE_READERS = ("afmoe_step_hbm_share", "afmoe_experts_hbm_share",
                 "dense_mlp_dev_ms", "unembed_sample_dev_ms")


@pytest.fixture
def afmoe_record(table, monkeypatch):
    """A traced window of 100 steps of 20 ms over the recorded table, the
    scopes the recorded Qwen2 step lacks put beside its ``mlp`` by hand."""
    steps = pt.span_count(table, pt.STEP_SPAN)
    scopes = {**table["scopes"], "unembed_sample": 0.0015 * steps,
              "moe.route": 0.001 * steps, "moe.experts": 0.008 * steps,
              "moe.shared": 0.001 * steps}
    monkeypatch.setitem(pt._TABLES, "table", {**table, "scopes": scopes})
    with open(os.path.join(HERE, "configs", "trinity-mini-pp8.json")) as f:
        config = json.load(f)
    rows = {"window_rows_live": 150_000,
            "window_rows_capacity": 96 * 129 * 16}
    return {"trace": {"modules": {"jit__batched_window_step_jit": {
                "runs": 100, "seconds": 2.0}}},
            "config": config, "device_kind": "TPU v5 lite",
            "pool_live_share": 0.5, "token_capacity": 96 * 12288,
            "report0": {"steps": 0, "slot_util_mean": 0.0, **rows},
            "report1": {"steps": 100, "slot_util_mean": 1.0, **rows}}


@pytest.mark.parametrize("name", AFMOE_READERS)
def test_afmoe_reader_on_the_recorded_events(name, table, afmoe_record):
    from benchmark import rooflines_afmoe as r

    steps = pt.span_count(table, pt.STEP_SPAN)
    c = afmoe_record["config"]
    assert r.param_count(c) == 4_241_534_720       # the issue's table
    want = {
        "dense_mlp_dev_ms": 1e3 * table["scopes"]["mlp"] / steps,
        "unembed_sample_dev_ms": 1.5,
        # 4 x 811,860,096 parameters x 2 B at 819 GB/s, of 10 ms
        "afmoe_experts_hbm_share": 100 * (4 * 811_860_096 * 2 / 819e9)
        / 10e-3,
        "afmoe_step_hbm_share": 100 * (r.step_bytes(
            c, 0.5 * 96 * 12288, 150_000, 96) / 819e9) / 20e-3}[name]
    got = _read(name, afmoe_record)
    assert got == pytest.approx(want) and (
        "share" not in name or 0 < got < 100)
    # an untraced run, and a program without the scope or the counters
    assert _read(name, {**afmoe_record, "trace": None}) is None
    pt._TABLES["table"] = {**table, "scopes": {"attn.decode": 1.0}}
    bare = {k: {"steps": v["steps"], "slot_util_mean": v["slot_util_mean"]}
            for k, v in afmoe_record.items() if k.startswith("report")}
    assert _read(name, {**afmoe_record, **bare}) is None


def test_the_afmoe_cell_lists_its_readers_and_the_ones_it_joins():
    names = {m.name for m in load_cell(AFMOE_CELL).per_layer}
    assert names >= set(AFMOE_READERS) | {
        "moe_experts_dev_ms", "attn_decode_dev_ms", "attn_window_dev_ms",
        "attn_window_hbm_share", "attn_full_hbm_share", "window_pool_live",
        "attend_walk_share", "step_dev_ms", "device_idle"}
    assert not names & {"mellum_step_hbm_share", "moe_experts_hbm_share",
                        "attn_latent_dev_ms", "ssm_step_dev_ms"}
    assert {m.name for m in load_cell(AFMOE_CELL).end_to_end} == {
        "gap_mean_ms", "setup_s"}


# ---------------------------------------------------------------------------
# PR 37: the prefill's grouped products under their own scope
# ---------------------------------------------------------------------------

EXPERT_CELLS = ("granite-4.0-h-small-ep2.decode-sat",
                "mellum2-12b-a2.5b-pp4.decode-sat-mixed",
                "mistral-small-4-119b-ep4.decode-sat-deep", AFMOE_CELL,
                "longcat-flash-chat-ep32.decode-sat-reason",
                "lfm2-8b-a1b-pp2.decode-sat-docs")
# (the keye_vl2 cell routes experts too, but admits in a few bursts a window:
# a traced 6 s often holds no admission, so it does not list the reader)


def test_grouped_reader_is_the_new_scope_per_admission(table, monkeypatch):
    """``moe_grouped_dev_ms`` on the recorded table with the scope put in by
    hand: its device seconds over the window's ``batch.admit`` spans, apart
    from ``moe.experts`` (an operation counts under its innermost registered
    scope); nothing on a program without the scope (the parent) and nothing
    untraced."""
    from edgellm_tpu.obs.names import SCOPE_NAMES

    assert "moe.experts.grouped" in SCOPE_NAMES
    path = "jit(prefill)/moe.experts/moe.experts.grouped/grouped_matmul"
    assert pt.innermost_scope(path, tuple(SCOPE_NAMES)) == \
        "moe.experts.grouped"
    admits = pt.span_count(table, pt.ADMIT_SPAN)
    assert admits > 0
    scopes = {**table["scopes"], "moe.experts": 0.5,
              "moe.experts.grouped": 0.004 * admits}
    monkeypatch.setitem(pt._TABLES, "table", {**table, "scopes": scopes})
    record = _record(True)
    assert _read("moe_grouped_dev_ms", record) == pytest.approx(4.0)
    assert _read("moe_grouped_dev_ms", {**record, "trace": None}) is None
    pt._TABLES["table"] = table                      # the parent's program
    assert _read("moe_grouped_dev_ms", record) is None


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_the_expert_cells_and_no_other_list_the_grouped_reader(cell):
    names = {m.name: m for m in load_cell(cell).per_layer}
    assert ("moe_grouped_dev_ms" in names) == (cell in EXPERT_CELLS)
    if cell in EXPERT_CELLS:
        assert names["moe_grouped_dev_ms"].unit == "ms"
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        entry, = (m for m in json.load(f)["per_layer"]
                  if m["name"] == "moe_grouped_dev_ms")
    assert entry == {
        "name": "moe_grouped_dev_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "model step",
        "moves": "gap_mean_ms", "workloads": list(EXPERT_CELLS)}


# ---------------------------------------------------------------------------
# PR 38: the longcat_flash cell's four readers, on the recorded events and by
# hand
# ---------------------------------------------------------------------------

LONGCAT_CELL = "longcat-flash-chat-ep32.decode-sat-reason"
LONGCAT_READERS = ("zero_expert_share", "longcat_step_hbm_share",
                   "longcat_experts_hbm_share",
                   "longcat_attn_latent_hbm_share")
LONGCAT_LIVE = 0.45 * 96 * 3072


@pytest.fixture
def longcat_record(table, monkeypatch):
    """A traced window of 100 steps of 20 ms over the recorded table, the
    scopes the recorded Qwen2 step lacks put in by hand."""
    steps = pt.span_count(table, pt.STEP_SPAN)
    scopes = {**table["scopes"], "attn.latent": 0.005 * steps,
              "moe.route": 0.001 * steps, "moe.experts": 0.007 * steps}
    monkeypatch.setitem(pt._TABLES, "table", {**table, "scopes": scopes})
    with open(os.path.join(HERE, "configs",
                           "longcat-flash-chat-ep32.json")) as f:
        config = json.load(f)
    rows = {"latent_rows_live": LONGCAT_LIVE,
            "latent_rows_capacity": 18432 * 16, "kv_row_bytes": 1280}
    return {"trace": {"modules": {"jit__batched_hybrid_step_jit": {
                "runs": 100, "seconds": 2.0}}},
            "config": config, "device_kind": "TPU v5 lite",
            "report0": {"steps": 0, "slot_util_mean": 0.0, **rows,
                        "routed_assignments": 1000, "zero_assignments": 300},
            "report1": {"steps": 100, "slot_util_mean": 1.0, **rows,
                        "routed_assignments": 461_800,
                        "zero_assignments": 153_900}}


@pytest.mark.parametrize("name", LONGCAT_READERS)
def test_longcat_reader_on_the_recorded_events(name, table, longcat_record):
    from benchmark import rooflines_longcat_flash as r

    c = longcat_record["config"]
    assert r.param_count(c) == 5_172_749_312       # the issue's arithmetic
    want = {
        "zero_expert_share": 100 * 153_600 / 460_800,
        # 4 x 608,699,136 parameters x 2 B at 819 GB/s, of 8 ms
        "longcat_experts_hbm_share": 100 * (4 * 608_699_136 * 2 / 819e9)
        / 8e-3,
        # 8 sublayers' live rows at 1280 B at 819 GB/s, of 5 ms
        "longcat_attn_latent_hbm_share":
            100 * (8 * LONGCAT_LIVE * 1280 / 819e9) / 5e-3,
        "longcat_step_hbm_share": 100 * (r.step_bytes(
            c, LONGCAT_LIVE, 1280, 96) / 819e9) / 20e-3}[name]
    got = _read(name, longcat_record)
    assert got == pytest.approx(want) and 0 < got < 100
    # a program without the scopes or the counters (another family's, or an
    # older one), and, for the three device shares, an untraced run
    if name != "zero_expert_share":
        assert _read(name, {**longcat_record, "trace": None}) is None
    pt._TABLES["table"] = {**table, "scopes": {"attn.decode": 1.0}}
    bare = {k: {"steps": v["steps"], "slot_util_mean": v["slot_util_mean"]}
            for k, v in longcat_record.items() if k.startswith("report")}
    assert _read(name, {**longcat_record, **bare}) is None


def test_the_longcat_cell_lists_its_readers_and_the_ones_it_joins():
    names = {m.name for m in load_cell(LONGCAT_CELL).per_layer}
    assert names >= set(LONGCAT_READERS) | {
        "attn_latent_dev_ms", "latent_pool_live", "attend_walk_share",
        "moe_experts_dev_ms", "moe_grouped_dev_ms", "dense_mlp_dev_ms",
        "unembed_sample_dev_ms", "step_dev_ms", "admit_dev_ms",
        "device_idle", "compiles_in_window"} | set(FOLD_READERS)
    # other families' byte counts, and what moves ``out_tok_s``
    assert not names & {"attn_latent_hbm_share", "mistral4_step_hbm_share",
                        "moe_experts_hbm_share", "afmoe_step_hbm_share",
                        "expert_load_skew", "routed_local_share",
                        "slot_util", "pool_live", "evictions",
                        "attn_decode_dev_ms", "ssm_step_dev_ms"}
    assert {m.name for m in load_cell(LONGCAT_CELL).end_to_end} == {
        "gap_mean_ms", "setup_s"}
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index(LONGCAT_READERS[0])
    assert names[at:at + 4] == list(LONGCAT_READERS)
    assert spec["workloads"][7]["name"] == LONGCAT_CELL
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1


# ---------------------------------------------------------------------------
# PR 42: the share of the launches that ran ahead of the host's read
# ---------------------------------------------------------------------------


def test_launch_ahead_share_is_the_windows_steps_ahead_over_its_steps():
    r0 = {"steps": 40, "steps_ahead": 30}
    r1 = {"steps": 240, "steps_ahead": 228}
    assert _read("launch_ahead_share", _record(False, r0, r1)) == \
        pytest.approx(99.0)
    # a window that launched nothing divides by nothing
    assert _read("launch_ahead_share", _record(False, r0, dict(r0))) is None
    # the parent's report() has no such counter: nothing, and no exception
    assert _read("launch_ahead_share", _record(
        False, {"steps": 40}, {"steps": 240})) is None


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_every_cell_lists_launch_ahead_share(cell):
    per_layer = {m.name: m for m in load_cell(cell).per_layer}
    assert per_layer["launch_ahead_share"].unit == "%"
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [m for m in spec["per_layer"]
                if m["name"] == "launch_ahead_share"]
    assert (entry["layer"], entry["source"], entry["better"],
            entry["moves"]) == ("batcher", "program_counter", "higher",
                                "gap_mean_ms")
    assert cell in entry["workloads"]


def test_admit_ahead_share_is_the_windows_admits_ahead_over_its_admitted():
    r0 = {"admitted": 192, "admits_ahead": 190}
    r1 = {"admitted": 392, "admits_ahead": 388}
    assert _read("admit_ahead_share", _record(False, r0, r1)) == \
        pytest.approx(99.0)
    # a window that admitted nothing divides by nothing
    assert _read("admit_ahead_share", _record(False, r0, dict(r0))) is None
    # the parent's report() has no such counter: nothing, and no exception
    assert _read("admit_ahead_share", _record(
        False, {"admitted": 192}, {"admitted": 392})) is None


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_every_cell_lists_admit_ahead_share(cell):
    per_layer = {m.name: m for m in load_cell(cell).per_layer}
    assert per_layer["admit_ahead_share"].unit == "%"
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [m for m in spec["per_layer"]
                if m["name"] == "admit_ahead_share"]
    assert (entry["layer"], entry["source"], entry["better"],
            entry["moves"]) == ("batcher", "program_counter", "higher",
                                "gap_mean_ms")
    assert cell in entry["workloads"]


# ---------------------------------------------------------------------------
# PR 44: the lfm2_moe cell's four readers, on events made by hand
# ---------------------------------------------------------------------------

LFM2_CELL = "lfm2-8b-a1b-pp2.decode-sat-docs"
LFM2_READERS = ("shortconv_dev_ms", "shortconv_hbm_share",
                "lfm2_experts_hbm_share", "lfm2_step_hbm_share")


@pytest.fixture
def lfm2_record(monkeypatch):
    """Two runs of the step executable with a prefill between them, under the
    same scopes: a window of 1 ms on one device plane, times in ns."""
    from benchmark import rooflines_lfm2_moe as r

    step = "jit__batched_hybrid_step_jit(5)"
    events = {"devices": {"/device:TPU:0": {
        "modules": [[step, 0, 100_000], ["jit__prefill_jit(7)", 100_000,
                                         500_000], [step, 600_000, 100_000]],
        "ops": [["shortconv.proj", 10_000, 20_000],
                ["shortconv.conv", 30_000, 10_000],
                ["moe.experts", 40_000, 50_000],
                ["shortconv.proj", 150_000, 200_000],     # the prefill's
                ["moe.experts", 350_000, 100_000],        # the prefill's
                ["shortconv.proj", 610_000, 22_000],
                ["shortconv.conv", 632_000, 8_000],
                ["moe.route", 640_000, 4_000],
                ["moe.experts", 644_000, 50_000]]}},
        "host": [["bench.window", 0, 1_000_000]]}
    monkeypatch.setitem(r._EVENTS, "events", events)
    with open(os.path.join(HERE, "configs", "lfm2-8b-a1b-pp2.json")) as f:
        config = json.load(f)
    return {"trace": {"modules": {"jit__batched_hybrid_step_jit": {
                "runs": 100, "seconds": 1.6}}},
            "config": config, "device_kind": "TPU v5 lite",
            "pool_live_share": 0.55, "token_capacity": 96 * 4608,
            "report0": {"steps": 0, "slot_util_mean": 0.0},
            "report1": {"steps": 100, "slot_util_mean": 1.0}}


@pytest.mark.parametrize("name", LFM2_READERS)
def test_lfm2_reader_counts_the_steps_own_operations(name, lfm2_record,
                                                     monkeypatch):
    """Only what ran inside a run of the step executable counts, per run (the
    prefills run under the same scopes); the shares are the bytes of
    ``rooflines_lfm2_moe`` at 819 GB/s over that time; nothing untraced, and
    nothing on a program whose trace holds no such scope (the parent)."""
    from benchmark import rooflines_lfm2_moe as r

    c = lfm2_record["config"]
    conv_ms, moe_ms = 0.030, 0.052
    want = {
        "shortconv_dev_ms": conv_ms,
        "shortconv_hbm_share": 100 * ((9 * 16_783_360 * 2
                                       + 2 * 96 * 147_456) / 819e9)
        / (1e-3 * conv_ms),
        "lfm2_experts_hbm_share": 100 * (10 * 352_387_104 * 2 / 819e9)
        / (1e-3 * moe_ms),
        "lfm2_step_hbm_share": 100 * (r.step_bytes(
            c, 0.55 * 96 * 4608, 96) / 819e9) / 16e-3}[name]
    assert _read(name, lfm2_record) == pytest.approx(want)
    assert _read(name, {**lfm2_record, "trace": None}) is None
    if name != "lfm2_step_hbm_share":
        bare = {"devices": {"/device:TPU:0": {
            "modules": [["jit__batched_hybrid_step_jit(5)", 0, 100_000]],
            "ops": [["attn.decode", 10_000, 20_000]]}},
            "host": [["bench.window", 0, 1_000_000]]}
        monkeypatch.setitem(r._EVENTS, "events", bare)
        assert _read(name, lfm2_record) is None
        monkeypatch.setitem(r._EVENTS, "events", None)   # no profile left
        assert _read(name, lfm2_record) is None


def test_the_lfm2_cell_lists_its_readers_and_the_ones_it_joins():
    from edgellm_tpu.obs.names import SCOPE_NAMES

    assert {"shortconv.proj", "shortconv.conv"} <= set(SCOPE_NAMES)
    names = {m.name for m in load_cell(LFM2_CELL).per_layer}
    assert names >= set(LFM2_READERS) | {
        "attn_decode_dev_ms", "attend_walk_share", "moe_experts_dev_ms",
        "moe_grouped_dev_ms", "dense_mlp_dev_ms", "unembed_sample_dev_ms",
        "step_dev_ms", "admit_dev_ms", "device_idle", "compiles_in_window",
        "launch_ahead_share"} | set(FOLD_READERS)
    # other families' scopes and byte counts, and what moves ``out_tok_s``
    assert not names & {"ssm_step_dev_ms", "ssm_step_hbm_share",
                        "hybrid_step_hbm_share", "moe_experts_hbm_share",
                        "afmoe_step_hbm_share", "attn_window_dev_ms",
                        "attn_latent_dev_ms", "expert_load_skew",
                        "routed_local_share", "slot_util", "pool_live",
                        "evictions"}
    assert {m.name for m in load_cell(LFM2_CELL).end_to_end} == {
        "gap_mean_ms", "setup_s"}
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    # (found by name: later PRs append their own entries after these)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert all(by_name[n]["workloads"] == [LFM2_CELL] for n in LFM2_READERS)
    assert LFM2_CELL in by_name["attend_run_share"]["workloads"]
    order = [m["name"] for m in spec["per_layer"]]
    at = order.index(LFM2_READERS[0])
    assert order[at:at + 5] == [*LFM2_READERS, "attend_run_share"]
    assert LFM2_CELL in [w["name"] for w in spec["workloads"]]
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1


# ---------------------------------------------------------------------------
# PR 50: the stall count, the CPU clocks and the host's counters over a window
# ---------------------------------------------------------------------------

STALL_READERS = ("stalls_in_window", "stall_ms_in_window",
                 "stall_off_cpu_ms", "steps_judged_share",
                 "host_step_cpu_ms", "host_admit_cpu_ms")


def _stall_report(steps, judged, stalls, excess, off, step_cpu, admit_cpu):
    return {"steps": steps, "steps_judged": judged, "stalls": stalls,
            "stall_excess_s": excess, "stall_off_cpu_s": off,
            "step_cpu_s": step_cpu, "admit_cpu_s": admit_cpu,
            "host": {"nr_throttled": None, "cpu_throttled_s": None}}


#: two windows on one report0: three stalls of 0.36 s, 0.27 of it off the
#: CPU; and a window with no stall. The machine shows no ``cpu.stat``, as the
#: benchmark's do
_R0 = _stall_report(100, 90, 1, 0.1, 0.1, 2.0, 0.5)
_STALLED = _stall_report(300, 280, 4, 0.46, 0.37, 2.8, 0.9)
_SOUND = _stall_report(300, 290, 1, 0.1, 0.1, 3.0, 0.5)


@pytest.mark.parametrize("name, stalled, sound", [
    ("stalls_in_window", 3, 0),
    ("stall_ms_in_window", 360.0, 0.0),
    ("stall_off_cpu_ms", 270.0, 0.0),
    ("steps_judged_share", 95.0, 100.0),
    ("host_step_cpu_ms", 4.0, 5.0),
    ("host_admit_cpu_ms", 2.0, 0.0)])
def test_stall_reader_on_two_hand_made_windows(name, stalled, sound):
    assert _read(name, _record(False, _R0, _STALLED)) == pytest.approx(stalled)
    assert _read(name, _record(False, _R0, _SOUND)) == pytest.approx(sound)


@pytest.mark.parametrize("window", ["stalled", "sound"])
@pytest.mark.parametrize("name", STALL_READERS)
def test_stall_reader_speaks_in_every_window_of_a_program_that_counts(
        name, window):
    # a cell that lists a reader has to print it in every traced run: with a
    # stall or with none, and on a machine that shows no cpu.stat
    r1 = _STALLED if window == "stalled" else _SOUND
    assert isinstance(_read(name, _record(False, _R0, r1)), (int, float))


@pytest.mark.parametrize("name", STALL_READERS)
def test_stall_reader_gives_nothing_on_a_program_without_its_counters(name):
    # the parent's report(): steps and the older clocks, no stall count, no
    # CPU clock, no "host"
    old = _record(False, {"steps": 5, "step_wall_s": 1.0, "admit_s": 0.2},
                  {"steps": 25, "step_wall_s": 3.0, "admit_s": 0.4})
    assert _read(name, old) is None


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_every_cell_lists_the_stall_readers(cell):
    names = {m.name for m in load_cell(cell).per_layer}
    assert set(STALL_READERS) <= names
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in STALL_READERS:
        m = entries[name]
        assert (m["source"], m["layer"], m["moves"]) == (
            "program_counter", "batcher", "gap_mean_ms")
        assert m["workloads"] == list(ALL_CELLS)


# ---------------------------------------------------------------------------
# PR 51: the share of a sparse layer's index pages that went as part of a run
# ---------------------------------------------------------------------------

KEYE_CELL = "keye-vl-2.0-30b-a3b-ep4.decode-sat-context"


def _index_report(walked, in_runs):
    return {"index_pages_walked": walked, "index_pages_in_runs": in_runs}


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("r0, r1, want", [
    (_index_report(10, 8), _index_report(1010, 968), 96.0),   # most in runs
    (_index_report(0, 0), _index_report(500, 0), 0.0),        # every page alone
    (_index_report(7, 0), _index_report(7, 0), None),         # nothing walked
    ({"index_rows_scored": 5}, {"index_rows_scored": 9}, None)])   # the parent
def test_index_run_share_is_the_window_difference_of_the_two_counters(
        traced, r0, r1, want):
    # a program counter: the same number traced or not; None where the
    # program keeps no such counter or scores its index keys by the gather
    got = _read("index_run_share", _record(traced, r0, r1))
    assert got == (None if want is None else pytest.approx(want))


def test_the_cells_that_walk_index_keys_alone_list_index_run_share():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    # (found by name: later PRs append their own entries after it, and a
    # later cell whose pool holds index keys its name to the list: PR 52,
    # PR 54)
    sparse = [KEYE_CELL, *ALL_CELLS[-2:]]
    assert entries["index_run_share"] == {"name": "index_run_share", "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "cache",
                 "moves": "gap_mean_ms", "workloads": sparse}
    for cell in sparse:
        assert "index_run_share" in {
            m.name for m in load_cell(cell).per_layer}
