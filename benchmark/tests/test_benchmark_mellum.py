"""The ``mellum`` architecture hooks under ``closed_loop_arch``, driven on the
CPU at a toy size (``testdata/toys.d/``): a run comes out ``correct`` and its
record has every key ``serving_run.run``'s has; it does not when the window
layers are served with full history, when a ring row is masked by its place in
the ring instead of the position it holds, or under the float8 control."""
import dataclasses
import json
import os
import time

import pytest

from benchmark.cell import HERE, load_cell
from benchmark.run import result_object

TD = os.path.join(HERE, "testdata")
CELL = "mellum2-12b-a2.5b-pp4.decode-sat-mixed"
with open(os.path.join(TD, "toys.d", CELL + ".json")) as f:
    TOY = {cell: tuple(toy) for cell, toy in json.load(f).items()}
NAME = TOY[CELL][0]
PLAIN = "qwen2-0.5b.decode-sat"
NEW = {"attn_window_dev_ms", "attn_window_hbm_share", "attn_full_hbm_share",
       "window_pool_live", "mellum_step_hbm_share"}


@pytest.fixture(scope="module")
def toy_json(tmp_path_factory):
    """``BENCHMARK.json`` cut to the new cell and the one-chip closed-loop
    cell it is compared with, each replaced by its toy."""
    toys = {CELL: TOY[CELL], PLAIN: ("tiny.sat", "tiny", "sat")}
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"] = [w for w in spec["workloads"] if w["name"] in toys]
    for w in spec["workloads"]:
        w["name"], w["config"], w["traffic"] = toys[w["name"]]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [toys[c][0] for c in m["workloads"]
                              if c in toys]
    path = tmp_path_factory.mktemp("toy_mellum") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return str(path)


@pytest.fixture
def cell(toy_json):
    return load_cell(NAME, bench_json=toy_json, data_dir=TD)


def _env(**kw):
    return {"t_start": time.monotonic(), "trace": False, "trace_dir": None,
            "control": False, "dump": lambda name, obj: None, **kw}


def _over(numbers, limits):
    return (numbers["gap_max"] > limits["gap_max"]
            or numbers["gap_mean"] > limits["gap_mean"])


def test_a_run_is_correct_and_its_record_has_serving_runs_keys(cell,
                                                               toy_json):
    record = cell.kind.run(cell, 2**31 + 11, 1.0, _env(control=True))
    line = result_object(cell, record, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    # not out_tok_s: in this closed loop it is 95 slots / gap_mean_ms, the
    # same measurement under a bound five times tighter than its runs' spread
    # on a busy host allows (PERF.md section 2)
    assert set(line["metrics"]) == {"gap_mean_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert record["compiles_in_window"] == 0
    assert line["attempted"] >= cell.config["serving"]["max_slots"]
    plain = load_cell("tiny.sat", bench_json=toy_json, data_dir=TD)
    other = plain.kind.run(plain, 5, 0.3, _env())
    assert set(record) == set(other)
    # the control: one precision below the stated bf16 is not correct
    nums = record["numbers"]
    assert nums["control_gap_max"] > cell.limits["gap_max"] \
        or nums["control_gap_mean"] > cell.limits["gap_mean"]
    assert not _over(nums, cell.limits)
    # the untraced readers of the new metrics read the same record
    layer = result_object(cell, record, trace=True)["metrics"]
    assert 0.0 < layer["window_pool_live"]["value"] <= 100.0
    r1 = record["report1"]
    assert r1["routed_local"] == r1["routed_assignments"] > 0   # all held
    assert r1["evicted"] == 0
    for name in NEW - {"window_pool_live"}:     # no trace, no device time
        assert name not in layer
    assert record["report1"]["window_rows_capacity"] == 8 * 4 * 4


def test_the_new_readers_read_nothing_from_a_program_without_the_counters(
        cell):
    """The parent's record: no ``window_rows_*`` in ``report()``, no
    ``attn.window`` scope. Every new reader returns None and does not raise."""
    record = cell.kind.run(cell, 7, 0.3, _env())
    for r in (record["report0"], record["report1"]):
        r.pop("window_rows_live"), r.pop("window_rows_capacity")
    for m in cell.per_layer:
        if m.name in NEW:
            assert m.reader(record) is None, m.name


def _served_with(monkeypatch, cell, seed, **change):
    """A run whose SERVED model differs from the configuration's (the
    reference keeps the configuration's)."""
    arch = cell.kind.architecture(cell.config)
    real = arch.model_config
    monkeypatch.setattr(arch, "model_config", lambda config:
                        dataclasses.replace(real(config), **change))
    monkeypatch.setattr(cell.kind, "architecture", lambda config: arch)
    return cell.kind.run(cell, seed, 1.0, _env())


def test_window_layers_served_with_full_history_are_not_correct(monkeypatch,
                                                                cell):
    span = (cell.config["serving"]["pages_per_slot"]
            * cell.config["serving"]["page_size"])
    record = _served_with(monkeypatch, cell, 5, sliding_window=span)
    assert record["correct"] is False
    assert record["numbers"]["tokens_missing"] == 0
    assert _over(record["numbers"], cell.limits)


def test_a_ring_row_masked_by_its_place_is_not_correct(monkeypatch, cell):
    import jax.numpy as jnp

    from edgellm_tpu.models import paged_kv

    def by_place(lengths, entries, page_size):
        rows = jnp.arange(entries * page_size, dtype=jnp.int32)
        return jnp.broadcast_to(rows, (lengths.shape[0], rows.shape[0]))

    import jax

    monkeypatch.setattr(paged_kv, "ring_positions", by_place)
    jax.clear_caches()      # the step compiled by an earlier test is sound
    try:
        record = cell.kind.run(cell, 5, 1.0, _env())
    finally:
        monkeypatch.undo()
        jax.clear_caches()  # ... and this one is not: leave none behind
    assert record["correct"] is False
    assert record["numbers"]["tokens_missing"] == 0
    assert _over(record["numbers"], cell.limits)


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_mellum.py")) as f:
        src = f.read()
    assert "edgellm_tpu" not in src.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in src


def test_rooflines_by_hand():
    from benchmark import rooflines_mellum as r

    with open(os.path.join(HERE, "configs",
                           "mellum2-12b-a2.5b-pp4.json")) as f:
        c = json.load(f)
    # q and o 2304 x 4096, k and v 2304 x 512, the input norm
    attn = 2 * 9_437_184 + 2 * 1_179_648 + 2304
    assert r.attention_layer_params(c) == attn == 21_235_968
    # router 2304 x 64, 64 experts of 3 x 2304 x 896, the input norm
    moe = 147_456 + 64 * 6_193_152 + 2304
    assert r.moe_layer_params(c) == moe == 396_511_488
    assert attn + moe == 417_747_456
    assert r.param_count(c) == (8 * 417_747_456 + 2 * 226_492_416
                                + 2304) == 3_794_966_784
    assert r.kv_row_bytes(c, 2) == 2 * 4 * 128 * 2 == 2048
    # 2 full layers read every live position, 6 sliding ones the window's
    assert r.full_rows_bytes(c, 1000) == 2 * 1000 * 2048
    assert r.window_rows_bytes(c, 1000) == 6 * 1000 * 2048
    need = r.step_bytes(c, 300_000, 70_000, 96)
    assert need == (2 * (3_794_966_784 - 226_492_416 + 96 * 2304)
                    + 2 * 300_000 * 2048 + 6 * 70_000 * 2048
                    + 96 * 8 * 2048)
    assert 11e-3 < need / 819e9 < 12e-3      # the step's floor on a v5e
    # the deployment's memory, as the configuration's note counts it
    s = c["serving"]
    assert s["num_pages"] == s["max_slots"] * s["pages_per_slot"] + 1
    full = s["num_pages"] * s["page_size"] * 2 * 2048
    ring = (s["max_slots"] * 65 + 1) * s["page_size"] * 6 * 2048
    assert round(full / 1e9, 2) == 2.42 and round(ring / 1e9, 2) == 1.23


def test_the_cell_and_its_files_keep_to_the_issue():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    w = [w for w in spec["workloads"] if w["name"] == CELL][0]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "mellum2-12b-a2.5b-pp4", "decode-sat-mixed", 1)
    with open(os.path.join(HERE, "traffic", "decode-sat-mixed.json")) as f:
        t = json.load(f)
    assert t == {"kind": "closed_loop_arch", "callers": "max_slots",
                 "prompt": {"values": [512, 4096]},
                 "answer": {"values": [512, 1024, 2048]},
                 "temperature": {"values": [0.0, 0.7]}}
    with open(os.path.join(HERE, "configs",
                           "mellum2-12b-a2.5b-pp4.json")) as f:
        c = json.load(f)
    assert c["reduced"] == ["num_hidden_layers", "layer_types",
                            "mlp_layer_types"]
    assert c["layer_types"] == (["sliding_attention"] * 3
                                + ["full_attention"]) * 2
    assert (c["hidden_size"], c["head_dim"], c["num_experts"],
            c["moe_intermediate_size"], c["vocab_size"],
            c["sliding_window"]) == (2304, 128, 64, 896, 98304, 1024)
    assert max(t["prompt"]["values"]) + max(t["answer"]["values"]) <= \
        c["serving"]["pages_per_slot"] * c["serving"]["page_size"]
    reported = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [])}
    assert NEW <= reported and "moe_experts_hbm_share" not in reported
    e2e = {m["name"] for m in spec["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"gap_mean_ms", "setup_s"}
