"""The ``lfm2_moe`` architecture hooks under ``closed_loop_arch``, driven on
the CPU at a toy size (``testdata/toys.d/``): a run comes out ``correct`` and
its record has every key ``serving_run.run``'s has; it does not under the
float8 control, nor when the served path reverses the convolution's taps,
leaves a slot's window where it was, drops the window a prefill hands on,
drops the router's selection bias, drops the per-head q/k norms or leaves the
attention layers unrotated. The shapes' arithmetic by hand, and the four new
readers on events made by hand."""
import json
import os
import time

import pytest

from benchmark.cell import HERE, load_cell, load_module
from benchmark.run import result_object

TD = os.path.join(HERE, "testdata")
CELL = "lfm2-8b-a1b-pp2.decode-sat-docs"
with open(os.path.join(TD, "toys.d", CELL + ".json")) as f:
    TOY = {cell: tuple(toy) for cell, toy in json.load(f).items()}
NAME = TOY[CELL][0]
PLAIN = "qwen2-0.5b.decode-sat"
NEW = {"shortconv_dev_ms", "shortconv_hbm_share", "lfm2_experts_hbm_share",
       "lfm2_step_hbm_share"}
JOINED = {"attn_decode_dev_ms", "attend_walk_share", "moe_experts_dev_ms",
          "moe_grouped_dev_ms", "dense_mlp_dev_ms", "unembed_sample_dev_ms",
          "step_dev_ms", "admit_dev_ms", "device_idle", "prefill_tok_s",
          "between_steps_ms", "launch_ahead_share", "compiles_in_window"}


def _config():
    with open(os.path.join(HERE, "configs", "lfm2-8b-a1b-pp2.json")) as f:
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
    path = tmp_path_factory.mktemp("toy_lfm2") / "BENCHMARK.json"
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
    # the control: a precision below the one stated is not correct. (It
    # would read 0 too if the streams repeated one token: a tied table's own
    # row wins every logit where the layers add next to nothing, which is
    # what matrices of std 0.02 do at width 48: the toy's ``matrix_std``.)
    nums = record["numbers"]
    assert nums["control_gap_mean"] > cell.limits["gap_mean"]
    assert nums["control_gap_max"] > cell.limits["gap_max"]
    assert not _over(nums, cell.limits)
    # no trace, no device time: the new readers read nothing
    layer = result_object(cell, record, trace=True)["metrics"]
    assert not NEW & set(layer)
    assert layer["launch_ahead_share"]["value"] > 0
    r0, r1 = record["report0"], record["report1"]
    # a row an EXPERT layer (4 of the toy's 6), a column an expert (all held)
    assert [len(row) for row in r1["expert_tokens"]] == [8] * 4
    made = r1["routed_assignments"] - r0["routed_assignments"]
    assert made > 0 and made % (3 * 4) == 0        # top-3, four layers
    assert r1["routed_local"] == r1["routed_assignments"]
    assert r1["evicted"] == 0
    # the state store: one leaf, the 5 conv layers' windows of 2 rows a slot
    assert r1["state_leaf_bytes"] == {"conv": 5 * 8 * 2 * 48 * 4}
    assert r1["state_bytes"] == 5 * 8 * 2 * 48 * 4


def test_the_new_readers_read_nothing_without_a_trace(cell):
    record = cell.kind.run(cell, 7, 0.3, _env())
    readers = {m.name: m.reader for m in cell.per_layer if m.name in NEW}
    assert set(readers) == NEW
    for name, read in readers.items():
        assert read(record) is None, name


def _events(ops, modules):
    """One device plane and a window of 1 ms, times in ns."""
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": [["bench.window", 0, 1_000_000]]}


def test_the_new_readers_on_events_made_by_hand(monkeypatch):
    """Two runs of the step executable and a prefill between them: only the
    operations inside the step's runs count, per run; a program whose table
    has no such scope (the parent) reads None; the shares are bytes at the
    chip's peak over that time, by hand."""
    from benchmark import rooflines_lfm2_moe as r

    step = "jit__batched_hybrid_step_jit(123)"
    modules = [[step, 0, 100_000], ["jit__prefill_jit(7)", 100_000, 500_000],
               [step, 600_000, 100_000]]
    ops = [["shortconv.proj", 10_000, 20_000],
           ["shortconv.conv", 30_000, 10_000],
           ["moe.experts", 40_000, 50_000],
           # a prefill's: the same scopes, outside the step's runs
           ["shortconv.proj", 150_000, 200_000],
           ["moe.experts", 350_000, 100_000],
           ["shortconv.proj", 610_000, 22_000],
           ["shortconv.conv", 632_000, 8_000],
           ["moe.route", 640_000, 4_000],
           ["moe.experts", 644_000, 50_000]]
    ev = _events(ops, modules)
    name = "_batched_hybrid_step_jit"
    assert r.scope_ms_in_step(ev, r.SHORTCONV_SCOPES, name) == \
        pytest.approx(1e-6 * (20_000 + 10_000 + 22_000 + 8_000) / 2)
    assert r.scope_ms_in_step(ev, r.MOE_SCOPES, name) == \
        pytest.approx(1e-6 * (50_000 + 4_000 + 50_000) / 2)
    # another program under these files: no such scope, no such executable
    assert r.scope_ms_in_step(ev, ("ssm.step",), name) is None
    assert r.scope_ms_in_step(ev, r.MOE_SCOPES, "_batched_step_jit") is None
    assert r.scope_ms_in_step(_events([], modules), r.MOE_SCOPES,
                              name) is None

    c = _config()
    live = 0.55 * 96 * 4608
    record = {"trace": {"modules": {"jit__batched_hybrid_step_jit": {
                  "runs": 100, "seconds": 1.6}}},
              "config": c, "device_kind": "TPU v5 lite",
              "pool_live_share": 0.55, "token_capacity": 96 * 4608,
              "report0": {"steps": 0, "slot_util_mean": 0.0},
              "report1": {"steps": 100, "slot_util_mean": 1.0}}
    monkeypatch.setitem(r._EVENTS, "events", ev)

    def read(name):
        return load_module(os.path.join(HERE, "metrics", name + ".py"),
                           "lfm2_" + name).read(record)

    assert read("shortconv_dev_ms") == pytest.approx(0.030)
    # 9 x 16,783,360 parameters x 2 B + 96 slots x 147,456 B twice
    need = 9 * 16_783_360 * 2 + 2 * 96 * 147_456
    assert read("shortconv_hbm_share") == pytest.approx(
        100 * (need / 819e9) / 0.030e-3)
    # 10 x 352,387,104 parameters x 2 B at 819 GB/s over 52 us: far over
    # 100% (the events are made up); on the chip the bytes take 8.6 ms
    assert read("lfm2_experts_hbm_share") == pytest.approx(
        100 * (10 * 352_387_104 * 2 / 819e9) / 0.052e-3)
    need = r.step_bytes(c, live, 96)
    assert read("lfm2_step_hbm_share") == pytest.approx(
        100 * (need / 819e9) / 16e-3)
    assert 0 < read("lfm2_step_hbm_share") < 100
    # an untraced run, or a process that left no profile
    monkeypatch.setitem(r._EVENTS, "events", None)
    for name in NEW - {"lfm2_step_hbm_share"}:
        assert read(name) is None, name
    record["trace"] = None
    for name in NEW:
        assert read(name) is None, name


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
    return record["numbers"]


def _served(monkeypatch, cell, change):
    """The batcher built from ``change(weights)``; the reference keeps the
    seeded ones."""
    arch = cell.kind.architecture(cell.config)
    real = arch.build_batcher

    def patch():
        monkeypatch.setattr(arch, "build_batcher", lambda config, weights:
                            real(config, change(weights)))
        monkeypatch.setattr(cell.kind, "architecture", lambda config: arch)
    return patch


def test_the_taps_reversed_are_not_correct(monkeypatch, cell):
    _broken(monkeypatch, cell, 5, _served(monkeypatch, cell, lambda w: {
        **w, "conv": {**w["conv"], "conv_w": w["conv"]["conv_w"][..., ::-1]}}))


def test_a_window_left_where_it_was_is_not_correct(monkeypatch, cell):
    """The step reads the window a prefill handed on and never advances it."""
    from edgellm_tpu.models import hybrid, shortconv

    real = shortconv.shortconv_step
    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        hybrid, "shortconv_step", lambda cfg, lp, u, window: (
            real(cfg, lp, u, window)[0], window)))


def test_a_window_dropped_at_admission_is_not_correct(monkeypatch, cell):
    """A prefill's windows are not handed on: the slot starts from zeros."""
    from edgellm_tpu.models import paged_kv

    real = paged_kv.PagedKVCache.adopt_state
    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        paged_kv.PagedKVCache, "adopt_state",
        lambda self, slot, window: real(self, slot, 0.0)))


def test_the_selection_bias_dropped_is_not_correct(monkeypatch, cell):
    _broken(monkeypatch, cell, 5, _served(monkeypatch, cell, lambda w: {
        **w, "moe": [{**mp, "router_bias": 0 * mp["router_bias"]}
                     if "router_bias" in mp else mp for mp in w["moe"]]}))


def test_the_head_norms_dropped_are_not_correct(monkeypatch, cell):
    _broken(monkeypatch, cell, 5, _served(monkeypatch, cell, lambda w: {
        **w, "attn": {k: v for k, v in w["attn"].items()
                      if k not in ("q_norm", "k_norm")}}))


def test_attention_left_unrotated_is_not_correct(monkeypatch, cell):
    """Granite's attention beside recurrent state is position-free; this
    family's rotates."""
    from edgellm_tpu.models.configs import ModelConfig

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        ModelConfig, "position_free",
        property(lambda self: ("attention",))))


def test_the_reference_is_literal_and_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_lfm2_moe.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]
    assert "edgellm_tpu" not in code
    assert 'default_matmul_precision("highest")' in code
    # no window is kept between positions: the convolution reads the padded
    # sequence, tap j at position t - (taps - 1) + j
    assert "window" not in code and "padded[j:j + s]" in code
    assert "+ 1e-6" in code and "jax.nn.sigmoid" in code


def test_the_parent_fails_the_cell_before_any_weight():
    """``make_weights`` asks the program for the family first: a program
    without it raises ``unsupported model_type`` at once."""
    arch = load_module(os.path.join(HERE, "architectures", "lfm2_moe.py"),
                       "arch_lfm2")
    c = _config()
    cfg = arch.model_config(c)
    assert (cfg.family, cfg.conv_layers, cfg.kv_layers, cfg.conv_window,
            cfg.experts_held) == ("lfm2_moe", 9, 3, 3, 0)
    assert cfg.layer_types.count("attention") == 3 and cfg.recurrent_state
    with pytest.raises(ValueError, match="unsupported model_type: "
                                         "lfm2_moe_next"):
        arch.make_weights({**c, "model_type": "lfm2_moe_next"}, 1)
    plan = arch.weight_plan(c)
    assert plan[0][0] == ("embed",)
    from benchmark import rooflines_lfm2_moe as r
    assert sum(_size(shape) for _, shape, _ in plan) == r.param_count(c)
    # the published widths seed at 0.02; only a toy's file widens a matrix
    assert "matrix_std" not in c["seeding"]


def _size(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def test_rooflines_by_hand():
    from benchmark import rooflines_lfm2_moe as r

    c = _config()
    # W_in 2048 x 6144, the taps 2048 x 3, W_out 2048 x 2048
    assert r.conv_mixer_params(c) == 12_582_912 + 6144 + 4_194_304 \
        == 16_783_360
    # wq and wo 2048 x 2048, wk and wv 2048 x 512, two norms of 64
    assert r.attention_params(c) == 2 * 4_194_304 + 2 * 1_048_576 + 128 \
        == 10_485_888
    # the router 2048 x 32, its 32 biases, 32 experts of 3 x 2048 x 1792
    assert r.expert_ffn_params(c) == 65_536 + 32 + 32 * 11_010_048 \
        == 352_387_104
    assert r.param_count(c) == (
        9 * 16_783_360 + 3 * 10_485_888 + 2 * 3 * 2048 * 7168
        + 10 * 352_387_104 + 24 * 2048 + 65536 * 2048 + 2048) \
        == 3_928_728_256
    assert round(2 * r.param_count(c) / 1e9, 2) == 7.86
    # whole: the 24 layers as published (18 conv + 6 attention, 22 routed)
    whole = {**c, "layer_types": c["layer_types"] + [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"]}
    assert round(r.param_count(whole) / 1e9, 2) == 8.34
    # K and V of a position of a layer: 2 x 8 heads x 64 lanes x 2 B
    assert r.kv_row_bytes(c, 2) == 2048
    s = c["serving"]
    assert s["num_pages"] == s["max_slots"] * s["pages_per_slot"] + 1 == 27649
    assert r.pool_bytes(c) == 27649 * 16 * 3 * 2048
    assert round(r.pool_bytes(c) / 1e9, 2) == 2.72
    # 9 layers x 2 rows x 2048 lanes x 4 B a slot
    assert r.window_bytes_per_slot(c) == 147_456
    assert r.shortconv_step_bytes(c, 96) == (
        9 * 16_783_360 * 2 + 2 * 96 * 147_456)
    assert r.experts_step_bytes(c) == 10 * 352_387_104 * 2
    assert round(r.experts_step_bytes(c) / 819e9 * 1e3, 1) == 8.6
    need = r.step_bytes(c, 250_000, 96)
    assert need == (2 * 3_928_728_256 + 2 * 96 * 147_456
                    + 250_000 * 3 * 2048 + 96 * 3 * 2048)
    assert 11.4e-3 < need / 819e9 < 11.6e-3      # the step's floor on a v5e
    held = 2 * r.param_count(c) + r.pool_bytes(c) + 96 * 147_456
    assert round(held / 1e9, 2) == 10.59 and held / 16e9 > 0.66


def test_the_cell_and_its_files_keep_to_the_issue():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    w = [w for w in spec["workloads"] if w["name"] == CELL][0]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "lfm2-8b-a1b-pp2", "decode-sat-docs", 1)
    assert len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    entry = [e for e in spec["configs"] if e["name"] == "lfm2-8b-a1b-pp2"][0]
    assert len(entry["why"]) <= 200
    with open(os.path.join(HERE, "traffic", "decode-sat-docs.json")) as f:
        t = json.load(f)
    assert t == {"kind": "closed_loop_arch", "callers": "max_slots",
                 "prompt": {"values": [1024, 4096]},
                 "answer": {"values": [128, 256, 512]},
                 "temperature": {"values": [0.0, 0.7]}}
    c = _config()
    assert entry["source"] == c["source"]
    assert c["reduced"] == entry["reduced"] == ["num_hidden_layers",
                                                "layer_types"]
    assert c["published"]["num_hidden_layers"] == 24
    published = ["conv", "conv", "full_attention", "conv", "conv", "conv",
                 "full_attention", "conv", "conv", "conv", "full_attention",
                 "conv", "conv", "conv", "full_attention", "conv", "conv",
                 "conv", "full_attention", "conv", "conv", "full_attention",
                 "conv", "conv"]
    assert c["layer_types"] == published[:12] and c["num_hidden_layers"] == 12
    # every number of the catalog row's config that is not reduced stands
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    assert {k: c[k] for k in catalog} == catalog
    # the harness's keys, repeated beside the published names
    assert c["rms_norm_eps"] == c["norm_eps"] and c["tie_word_embeddings"]
    assert "share" not in c                       # all 32 experts held
    assert max(t["prompt"]["values"]) + max(t["answer"]["values"]) == \
        c["serving"]["pages_per_slot"] * c["serving"]["page_size"] == 4608
    reported = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [])}
    assert NEW | JOINED <= reported
    assert not {"ssm_step_dev_ms", "attn_window_dev_ms", "attn_latent_dev_ms",
                "afmoe_step_hbm_share", "moe_experts_hbm_share",
                "expert_load_skew", "routed_local_share", "slot_util",
                "pool_live", "evictions"} & reported
    assert {m["name"] for m in spec["per_layer"]
            if m.get("workloads") == [CELL]} == NEW
    e2e = {m["name"] for m in spec["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"gap_mean_ms", "setup_s"}
