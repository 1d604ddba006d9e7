"""The ``afmoe`` architecture hooks under ``closed_loop_arch``, driven on the
CPU at a toy size (``testdata/toys.d/``): a run comes out ``correct`` and its
record has every key ``serving_run.run``'s has; it does not under the float8
control, nor when the served path ignores the router's selection bias, ignores
the attention's output gate, rotates the full layers, or masks a ring row by
its place in the ring instead of the position it holds. The shapes'
arithmetic by hand, and the four new readers on recorded events."""
import json
import os
import time

import pytest

from benchmark import program_trace as pt
from benchmark.cell import HERE, load_cell, load_module
from benchmark.run import result_object

TD = os.path.join(HERE, "testdata")
CELL = "trinity-mini-pp8.decode-sat-long"
with open(os.path.join(TD, "toys.d", CELL + ".json")) as f:
    TOY = {cell: tuple(toy) for cell, toy in json.load(f).items()}
NAME = TOY[CELL][0]
PLAIN = "qwen2-0.5b.decode-sat"
NEW = {"afmoe_step_hbm_share", "afmoe_experts_hbm_share", "dense_mlp_dev_ms",
       "unembed_sample_dev_ms"}
JOINED = {"moe_experts_dev_ms", "attn_decode_dev_ms", "attn_window_dev_ms",
          "attn_window_hbm_share", "attn_full_hbm_share", "window_pool_live",
          "attend_walk_share"}


def _config():
    with open(os.path.join(HERE, "configs", "trinity-mini-pp8.json")) as f:
        return json.load(f)


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
    path = tmp_path_factory.mktemp("toy_afmoe") / "BENCHMARK.json"
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
    # the untraced readers read the same record; no trace, no device time
    layer = result_object(cell, record, trace=True)["metrics"]
    assert 0.0 < layer["window_pool_live"]["value"] <= 100.0
    assert not NEW & set(layer)
    r0, r1 = record["report0"], record["report1"]
    assert r1["routed_local"] == r1["routed_assignments"] > 0   # all held
    # a row an EXPERT layer (4 of the 5), 3 assignments a token a layer
    assert len(r1["expert_tokens"]) == 4
    assert (r1["routed_assignments"] - r0["routed_assignments"]) % (3 * 4) == 0
    assert r1["evicted"] == 0
    assert r1["window_rows_capacity"] == 8 * 4 * 4     # slots x pages x rows


def test_the_new_readers_read_nothing_without_a_trace_or_their_scope(
        cell, monkeypatch):
    """An untraced run, and a traced one of a program whose table has no
    ``mlp`` / ``moe.*`` / ``unembed_sample`` scope and no window counters
    (the parent under these files): every new reader returns None and does
    not raise."""
    record = cell.kind.run(cell, 7, 0.3, _env())
    readers = {m.name: m.reader for m in cell.per_layer if m.name in NEW}
    assert set(readers) == NEW
    for name, read in readers.items():
        assert read(record) is None, name
    record["trace"] = {"modules": {}}
    monkeypatch.setitem(pt._TABLES, "table",
                        {"spans": {pt.STEP_SPAN: {"count": 10.0}},
                         "scopes": {"attn.decode": 0.1}})
    for r in (record["report0"], record["report1"]):
        r.pop("window_rows_live"), r.pop("window_rows_capacity")
    for name, read in readers.items():
        assert read(record) is None, name


def test_the_new_readers_on_recorded_events(monkeypatch):
    """The v5e events recorded under ``testdata``: their table has the
    scope ``mlp`` (a Qwen2 step) and its ``batch.step`` spans; the other
    scopes' seconds are put beside it by hand. The two scope readers read
    scope seconds over spans; the two shares are bytes at the HBM peak over a
    time, by hand."""
    with open(os.path.join(TD, "program_events_v5e.json")) as f:
        table = pt.reduce_program(json.load(f))
    steps = pt.span_count(table, pt.STEP_SPAN)
    assert steps > 0 and table["scopes"]["mlp"] > 0
    table["scopes"].update({"unembed_sample": 0.0015 * steps,
                            "moe.route": 0.001 * steps,
                            "moe.experts": 0.008 * steps,
                            "moe.shared": 0.001 * steps})
    monkeypatch.setitem(pt._TABLES, "table", table)
    c = _config()
    record = {"trace": {"modules": {"jit__batched_window_step_jit": {
                  "runs": 100, "seconds": 2.0}}},
              "config": c, "device_kind": "TPU v5 lite",
              "pool_live_share": 0.5, "token_capacity": 96 * 12288,
              "report0": {"steps": 0, "slot_util_mean": 0.0,
                          "window_rows_live": 150_000,
                          "window_rows_capacity": 96 * 129 * 16},
              "report1": {"steps": 100, "slot_util_mean": 1.0,
                          "window_rows_live": 150_000,
                          "window_rows_capacity": 96 * 129 * 16}}

    def read(name):
        return load_module(os.path.join(HERE, "metrics", name + ".py"),
                           "afmoe_" + name).read(record)

    assert read("dense_mlp_dev_ms") == pytest.approx(
        1e3 * table["scopes"]["mlp"] / steps)
    assert read("unembed_sample_dev_ms") == pytest.approx(1.5)
    # 4 x 811,860,096 parameters x 2 B at 819 GB/s = 7.93 ms of 10 ms
    assert read("afmoe_experts_hbm_share") == pytest.approx(
        100 * (4 * 811_860_096 * 2 / 819e9) / 10e-3)
    from benchmark import rooflines_afmoe as r
    need = r.step_bytes(c, 0.5 * 96 * 12288, 150_000, 96)
    assert read("afmoe_step_hbm_share") == pytest.approx(
        100 * (need / 819e9) / 20e-3)
    assert 0 < read("afmoe_step_hbm_share") < 100


def _broken(monkeypatch, cell, seed, patch):
    """A run whose SERVED model is broken by ``patch()`` (the reference keeps
    the configuration's), compiled afresh and leaving no executable behind."""
    import jax

    patch()
    jax.clear_caches()      # the step compiled by an earlier test is sound
    try:
        record = cell.kind.run(cell, seed, 1.0, _env())
    finally:
        monkeypatch.undo()
        jax.clear_caches()  # ... and this one is not: leave none behind
    assert record["correct"] is False
    assert record["numbers"]["tokens_missing"] == 0
    assert _over(record["numbers"], cell.limits)


def _serving_other_weights(monkeypatch, cell, change):
    """The batcher is built over ``change(weights)``; the reference keeps the
    weights the run made."""
    arch = cell.kind.architecture(cell.config)
    real = arch.build_batcher
    monkeypatch.setattr(arch, "build_batcher", lambda config, weights:
                        real(config, change(weights)))
    monkeypatch.setattr(cell.kind, "architecture", lambda config: arch)


def test_the_selection_bias_ignored_is_not_correct(monkeypatch, cell):
    def zero_bias(w):
        return {**w, "moe": [
            {**mp, "router_bias": 0 * mp["router_bias"]}
            if "router_bias" in mp else mp for mp in w["moe"]]}

    _broken(monkeypatch, cell, 5, lambda: _serving_other_weights(
        monkeypatch, cell, zero_bias))


def test_the_attention_gate_ignored_is_not_correct(monkeypatch, cell):
    def no_gate(w):
        return {**w, **{stack: {k: v for k, v in w[stack].items()
                                if k != "wg"} for stack in ("attn", "window")}}

    _broken(monkeypatch, cell, 5, lambda: _serving_other_weights(
        monkeypatch, cell, no_gate))


def test_full_layers_rotated_are_not_correct(monkeypatch, cell):
    from edgellm_tpu.models.configs import ModelConfig

    # one full layer of five over a window of 10: the least of the four
    # mistakes here, read at the seed the toy's limits name
    _broken(monkeypatch, cell, 11, lambda: monkeypatch.setattr(
        ModelConfig, "position_free", property(lambda self: ())))


def test_a_ring_row_masked_by_its_place_is_not_correct(monkeypatch, cell):
    import jax.numpy as jnp

    from edgellm_tpu.models import paged_kv

    def by_place(lengths, entries, page_size):
        rows = jnp.arange(entries * page_size, dtype=jnp.int32)
        return jnp.broadcast_to(rows, (lengths.shape[0], rows.shape[0]))

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        paged_kv, "ring_positions", by_place))


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_afmoe.py")) as f:
        src = f.read()
    assert "edgellm_tpu" not in src.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in src


def test_rooflines_by_hand():
    from benchmark import rooflines_afmoe as r
    from benchmark import rooflines_mellum as rm

    c = _config()
    # q, o and the gate 2048 x 4096, k and v 2048 x 512, two head norms of 128
    attn = 3 * 8_388_608 + 2 * 1_048_576 + 256
    assert r.attention_params(c) == attn == 27_263_232
    assert r.norm_params(c) == 8_192
    # router 2048 x 128, bias 128, 128 experts of 3 x 2048 x 1024, the shared
    ffn = 262_144 + 128 + 128 * 6_291_456 + 6_291_456
    assert r.expert_ffn_params(c) == ffn == 811_860_096
    assert r.expert_layer_params(c) == attn + 8_192 + ffn == 839_131_520
    assert r.dense_layer_params(c) == (attn + 8_192 + 3 * 2048 * 6144) \
        == 65_020_160
    assert 2 * 409_993_216 + 2048 == 819_988_480
    assert r.param_count(c) == (4 * 839_131_520 + 65_020_160
                                + 819_988_480) == 4_241_534_720
    assert round(2 * r.param_count(c) / 1e9, 2) == 8.48
    assert r.kv_row_bytes(c, 2) == 2 * 4 * 128 * 2 == 2048
    # the pools: a page of one layer is 16 rows x 2048 B = 32 KiB
    s = c["serving"]
    assert s["num_pages"] == s["max_slots"] * s["pages_per_slot"] + 1 == 73_729
    full, ring = r.pool_bytes(c)
    assert full == 73_729 * 32_768 and round(full / 1e9, 2) == 2.42
    assert ring == (96 * 129 + 1) * 4 * 32_768 and round(ring / 1e9, 2) == 1.62
    held = 2 * r.param_count(c) + full + ring
    assert round(held / 1e9, 2) == 12.52 and held / 16e9 > 0.78
    # the experts' read of a step, and the whole step at given live rows
    assert r.experts_step_bytes(c) == 4 * 811_860_096 * 2
    assert round(r.experts_step_bytes(c) / 1e9, 2) == 6.49
    need = r.step_bytes(c, 500_000, 150_000, 96)
    assert need == (2 * (4_241_534_720 - 409_993_216 + 96 * 2048)
                    + 1 * 500_000 * 2048 + 4 * 150_000 * 2048
                    + 96 * 5 * 2048)
    assert 11e-3 < need / 819e9 < 13e-3      # the step's floor on a v5e
    # the row counts the joined mellum readers use read only layer_types, the
    # kv heads, head_dim and the dtype: right for this file's keys
    assert rm.kv_row_bytes(c, 2) == 2048
    assert rm.full_rows_bytes(c, 1000) == 1 * 1000 * 2048
    assert rm.window_rows_bytes(c, 1000) == 4 * 1000 * 2048


def test_the_cell_and_its_files_keep_to_the_issue():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    w = [w for w in spec["workloads"] if w["name"] == CELL][0]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "trinity-mini-pp8", "decode-sat-long", 1)
    assert len(w["why"]) <= 200 and spec["workloads"][-1] is w
    entry = spec["configs"][-1]
    assert entry["name"] == "trinity-mini-pp8" and len(entry["why"]) <= 200
    with open(os.path.join(HERE, "traffic", "decode-sat-long.json")) as f:
        t = json.load(f)
    assert t == {"kind": "closed_loop_arch", "callers": "max_slots",
                 "prompt": {"values": [1024, 8192]},
                 "answer": {"values": [1024, 2048, 4096]},
                 "temperature": {"values": [0.0, 0.7]}}
    c = _config()
    assert c["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    assert c["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert (c["num_hidden_layers"], c["num_dense_layers"]) == (5, 1)
    assert c["published"]["num_hidden_layers"] == 32 \
        and c["published"]["num_dense_layers"] == 2
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["sliding_window"],
            c["num_experts"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["num_shared_experts"],
            c["intermediate_size"], c["vocab_size"]) == (
        2048, 32, 4, 128, 2048, 128, 1024, 8, 1, 6144, 200192)
    assert max(t["prompt"]["values"]) + max(t["answer"]["values"]) == \
        c["serving"]["pages_per_slot"] * c["serving"]["page_size"] == 12288
    reported = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [])}
    assert NEW | JOINED <= reported
    assert not {"mellum_step_hbm_share", "moe_experts_hbm_share",
                "slot_util", "pool_live"} & reported
    assert [m["name"] for m in spec["per_layer"][-4:]] == [
        "afmoe_step_hbm_share", "afmoe_experts_hbm_share",
        "dense_mlp_dev_ms", "unembed_sample_dev_ms"]
    e2e = {m["name"] for m in spec["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"gap_mean_ms", "setup_s"}
