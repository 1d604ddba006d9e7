"""The ``dots3_note`` architecture hooks under ``closed_loop_arch``, driven on
the CPU at a toy size (``testdata/toys.d/``): a run comes out ``correct`` and
its record has every key ``serving_run.run``'s has; it does not under the
float8 control, nor when the served path lets a window layer attend every
row, drops either gate, drops the rescale, rotates the window layers by the
full layers' theta, or attends the newest ``topk`` rows in place of the
chosen. The shapes' arithmetic by hand, and the five new readers on events
and counters made by hand."""
import json
import os
import time

import pytest

from benchmark.cell import HERE, load_cell, load_module
from benchmark.run import result_object

TD = os.path.join(HERE, "testdata")
CELL = "dots3-note-prev-ep8.decode-sat-context"
CONFIG = "dots3-note-prev-ep8"
with open(os.path.join(TD, "toys.d", CELL + ".json")) as f:
    TOY = {cell: tuple(toy) for cell, toy in json.load(f).items()}
NAME = TOY[CELL][0]
PLAIN = "qwen2-0.5b.decode-sat"
NEW = {"attn_window_latent_dev_ms", "attn_window_latent_hbm_share",
       "dots3_attn_sparse_latent_hbm_share", "dots3_experts_hbm_share",
       "dots3_step_hbm_share"}
JOINED = {"attn_sparse_latent_dev_ms", "window_pool_live",
          "moe_experts_dev_ms", "unembed_sample_dev_ms", "dense_mlp_dev_ms",
          "attn_index_dev_ms", "attn_select_dev_ms", "sparse_selected_share",
          "index_run_share", "attend_walk_share", "attend_run_share",
          "latent_pool_live", "step_dev_ms", "device_idle", "prefill_tok_s",
          "between_steps_ms", "launch_ahead_share", "compiles_in_window",
          "step_host_ms", "step_wall_ms", "host_admit_ms",
          "stalls_in_window", "host_step_cpu_ms"}


def _config():
    with open(os.path.join(HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy_json(tmp_path_factory):
    """``BENCHMARK.json`` cut to the new cell and the one-chip closed-loop
    cell it is compared with, each replaced by its toy (found by name)."""
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
    path = tmp_path_factory.mktemp("toy_dots3") / "BENCHMARK.json"
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
    # the control: a precision below the one stated is not correct
    nums = record["numbers"]
    assert nums["control_gap_mean"] > cell.limits["gap_mean"]
    assert nums["control_gap_max"] > cell.limits["gap_max"]
    assert not _over(nums, cell.limits)
    # no trace, no device time: the traced readers read nothing, the
    # counters' readers do
    layer = result_object(cell, record, trace=True)["metrics"]
    assert not NEW & set(layer)
    assert 0 < layer["sparse_selected_share"]["value"] < 100
    assert 0 < layer["latent_pool_live"]["value"] < 100
    assert 0 < layer["window_pool_live"]["value"] <= 100
    r0, r1 = record["report0"], record["report1"]
    assert r1["sparse_read"].startswith("xla row gather")
    assert r1["window_read"] == r1["decode_read"] == "xla page gather"
    # a ring of ceil(20 / 4) + 1 = 6 pages of 4 rows a slot, 8 slots
    assert r1["window_rows_capacity"] == 8 * 6 * 4
    assert 0 < r1["window_rows_live"] <= 8 * 21
    # a row an EXPERT layer (the leading dense layer routes nothing), a
    # column a HELD expert (8 of the router's 16)
    assert [len(row) for row in r1["expert_tokens"]] == [8] * 4
    made = r1["routed_assignments"] - r0["routed_assignments"]
    assert made > 0 and made % (3 * 4) == 0        # top-3, four layers
    assert 0 < r1["routed_local"] < r1["routed_assignments"]
    assert r1["evicted"] == 0
    # a position's stored bytes a full layer: the latent row's and the index
    # key's one lane tile each, float32
    assert r1["kv_row_bytes"] == (128 + 128) * 4


def _events(ops, modules):
    """One device plane and a window of 1 ms, times in ns."""
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": [["bench.window", 0, 1_000_000]]}


def test_the_new_readers_on_events_made_by_hand(monkeypatch):
    """Two runs of the step executable and a prefill between them: only the
    operations inside the step's runs count, per run; the ring's write
    stands in the window layers' time and not in the full layers'; a program
    whose table has no such scope reads None; the shares are the need at the
    chip's peaks over that time, by hand, and a floor."""
    from benchmark import rooflines_dots3_note as r
    from benchmark import rooflines_lfm2_moe as shared

    step = "jit__batched_window_step_jit(123)"
    modules = [[step, 0, 100_000], ["jit__prefill_jit(7)", 100_000, 500_000],
               [step, 600_000, 100_000]]
    ops = [["attn.sparse_latent", 5_000, 10_000],
           ["attn.sparse.index", 15_000, 8_000],
           ["attn.sparse.select", 23_000, 2_000],
           ["paged_kv.write", 25_000, 1_000],
           ["attn.window_latent", 26_000, 14_000],
           ["attn.window_latent.write", 40_000, 1_000],
           ["moe.experts", 42_000, 50_000],
           # a prefill's: outside the step's runs
           ["attn.window_latent.prefill", 150_000, 200_000],
           ["attn.sparse_latent.prefill", 350_000, 100_000],
           ["moe.experts", 450_000, 100_000],
           ["attn.sparse_latent", 605_000, 12_000],
           ["attn.sparse.index", 617_000, 7_000],
           ["attn.sparse.select", 624_000, 1_000],
           ["paged_kv.write", 625_000, 1_000],
           ["attn.window_latent", 626_000, 16_000],
           ["attn.window_latent.write", 642_000, 1_000],
           ["moe.route", 643_000, 1_000],
           ["moe.experts", 644_000, 46_000],
           ["moe.shared", 690_000, 4_000]]
    ev = _events(ops, modules)
    name = "_batched_window_step_jit"
    assert shared.scope_ms_in_step(ev, r.WINDOW_LATENT_SCOPES, name) == \
        pytest.approx(1e-6 * (15_000 + 17_000) / 2)
    assert shared.scope_ms_in_step(ev, r.SPARSE_LATENT_SCOPES, name) == \
        pytest.approx(1e-6 * (21_000 + 21_000) / 2)
    # the parent's program under these files: no such scope
    older = [op for op in ops if op[0].startswith("moe.")]
    assert shared.scope_ms_in_step(_events(older, modules),
                                   r.WINDOW_LATENT_SCOPES, name) is None

    c = _config()
    # 32 riders a step, 14,000 live rows each, 2048 attended, 513 in a ring
    counters = {"steps": 100, "slot_util_mean": 1.0,
                "index_rows_scored": 100 * 32 * 14_000,
                "sparse_rows_live": 100 * 32 * 14_000,
                "sparse_rows_attended": 100 * 32 * 2048,
                "window_rows_live": 32 * 513,
                "window_rows_capacity": 32 * 33 * 16}
    record = {"trace": {"modules": {"jit__batched_window_step_jit": {
                  "runs": 100, "seconds": 2.0}}},
              "config": c, "device_kind": "TPU v5 lite",
              "report0": dict.fromkeys(counters, 0) | {
                  "slot_util_mean": 0.0, "window_rows_live": 32 * 513},
              "report1": counters}
    monkeypatch.setitem(shared._EVENTS, "events", ev)

    def read(name):
        return load_module(os.path.join(HERE, "metrics", name + ".py"),
                           "dots3_" + name).read(record)

    assert read("attn_window_latent_dev_ms") == pytest.approx(0.016)
    assert read("attn_sparse_latent_dev_ms") == pytest.approx(0.021)
    assert read("window_pool_live") == pytest.approx(100 * 513 / 528)
    need, ops_ = r.window_latent_step_need(c, 32 * 513, 32)
    # 3 layers x (90.83M parameters x 2 B + (16,416 ring rows read + 32
    # written) x 2304 B)
    assert need == 3 * (90_834_944 * 2 + (16_416 + 32) * 2304)
    assert ops_ == 3 * 2.0 * (16_416 * 64 * (2 * 1024 + 64)
                              + 32 * 90_834_944)
    assert need / 819e9 > ops_ / 197e12          # bytes bind, not the MXU
    assert read("attn_window_latent_hbm_share") == pytest.approx(
        100 * (need / 819e9) / 0.016e-3)
    need, ops_ = r.sparse_latent_step_need(c, 32 * 14_000, 32 * 2048, 32)
    # 2 layers x (144.05M parameters x 2 B + 448,000 index keys x 256 B +
    # 65,536 latent rows x 1280 B + 32 rows of each written)
    assert need == 2 * ((134_678_016 + 9_371_904) * 2 + 448_000 * 256
                        + 65_536 * 1280 + 32 * (1280 + 256))
    assert read("dots3_attn_sparse_latent_hbm_share") == pytest.approx(
        100 * max(need / 819e9, ops_ / 197e12) / 0.021e-3)
    inside = r.experts_in_scope_bytes(c, "TPU v5 lite")
    assert inside == 4 * (5120 * 256 + 256 + 3 * 33 * 5120 * 1536) * 2 \
        - 4 * 128 * 2 ** 20
    assert read("dots3_experts_hbm_share") == pytest.approx(
        100 * (inside / 819e9) / 0.0505e-3)
    with pytest.raises(KeyError, match="vector memory"):
        r.experts_in_scope_bytes(c, "TPU v9")
    whole = r.step_bytes(c, 32 * 14_000, 32 * 2048, 32 * 513, 32)
    assert read("dots3_step_hbm_share") == pytest.approx(
        100 * (whole / 819e9) / 20e-3)
    assert 0 < read("dots3_step_hbm_share") < 100
    # an untraced run, or a process that left no profile
    monkeypatch.setitem(shared._EVENTS, "events", None)
    for name in NEW - {"dots3_step_hbm_share"}:
        assert read(name) is None, name
    record["trace"] = None
    for name in NEW:
        assert read(name) is None, name


def _broken(monkeypatch, cell, patch, seed=5):
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


def _every_row(monkeypatch):
    """A window layer that attends every row it holds and sees: the band
    dropped from the prefill and from the ring's read."""
    from edgellm_tpu.models import hybrid, paged_kv

    real = hybrid._attention_blocks
    monkeypatch.setattr(hybrid, "_attention_blocks",
                        lambda q, k, v, window: real(q, k, v, 0))
    monkeypatch.setattr(
        paged_kv, "window_valid",
        lambda pos, lengths, window: (pos >= 0) & (
            pos <= lengths[:, None] - 1))


def _no_gate(kind):
    """A kind's gate dropped from both forms (the toy's window kind has 2
    heads, its full kind 4: a layer's ``wg`` says which it is)."""
    def patch(monkeypatch):
        from edgellm_tpu.models import hybrid, mla, paged_kv, sparse_mla

        def ours(lp):
            return (lp["wg"].shape[-1] == 2) == (kind == "window")

        real, real_gated = mla.head_gate, paged_kv.gated
        monkeypatch.setattr(
            mla, "head_gate",
            lambda lp, x: None if ours(lp) else real(lp, x))
        for mod in (hybrid, paged_kv, sparse_mla):
            monkeypatch.setattr(
                mod, "gated", lambda lp, x, ctx: ctx if ours(lp)
                else real_gated(lp, x, ctx))
    return patch


def _no_rescale(monkeypatch):
    from edgellm_tpu.models.configs import ModelConfig

    monkeypatch.setattr(ModelConfig, "rank_scale", lambda self, rank: 1.0)


def _one_theta(monkeypatch):
    from edgellm_tpu.models import hybrid, mla

    real = mla.plain_rope
    monkeypatch.setattr(hybrid.mla, "plain_rope", lambda geo, n: real(
        type(geo)(**{**geo.__dict__, "rope_theta": 10000.0}), n))


def _newest(monkeypatch):
    import jax.numpy as jnp

    from edgellm_tpu.models import sparse_attn

    def newest(scores, lengths, k):
        idx = lengths[:, None] - 1 - jnp.arange(k)[None, :]
        return (jnp.maximum(idx, 0).astype(jnp.int32),
                jnp.minimum(lengths, k).astype(jnp.int32))

    monkeypatch.setattr(sparse_attn, "select", newest)


@pytest.mark.parametrize("name, patch", [
    ("a window layer attends every row", _every_row),
    ("the full layers' gate dropped", _no_gate("full")),
    ("the window layers' gate dropped", _no_gate("window")),
    ("the rescale dropped", _no_rescale),
    ("the window layers rotated by the full layers' theta", _one_theta),
    ("the newest topk rows in place of the chosen", _newest),
])
def test_a_broken_served_path_is_not_correct(monkeypatch, cell, name, patch):
    _broken(monkeypatch, cell, lambda: patch(monkeypatch))


def test_the_reference_is_literal_and_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_dots3_note.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]
    assert "edgellm_tpu" not in code and "reference_deepseek" not in code
    assert "import" not in code.replace(
        "from __future__ import annotations", "").replace(
        "import functools", "").replace("import math", "").replace(
        "import jax.numpy as jnp", "").replace("import jax", "")
    assert 'default_matmul_precision("highest")' in code
    # the selection is top_k over each row's visible scores, literally; the
    # band is explicit; both kinds are the expanded form, never absorbed
    assert "jax.lax.top_k(jnp.where(visible, scores, -jnp.inf)" in code
    assert 'at[None, :] > at[:, None] - k["band"]' in code
    assert "approx" not in code and "absorb" not in code
    assert 'jnp.einsum("qhd,thd->hqt", q_nope, k_nope)' in code
    assert "jax.nn.relu(dots)" in code and "jax.nn.sigmoid" in code
    assert 'math.sqrt(k["hidden"] / rank)' in code


def _size(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def test_the_parent_fails_the_cell_before_any_weight():
    """``make_weights`` asks the program for the family first: a program
    without it raises ``unsupported model_type`` at once."""
    arch = load_module(os.path.join(HERE, "architectures", "dots3_note.py"),
                       "arch_dots3")
    c = _config()
    cfg = arch.model_config(c)
    assert (cfg.family, cfg.sparse_layers, cfg.latent_layers, cfg.kv_layers,
            cfg.window_layers, cfg.window_latent_layers, cfg.num_experts,
            cfg.experts_held, cfg.expert_offset, cfg.vocab_size,
            cfg.num_dense_layers) == (
        "dots3_note", 2, 2, 2, 3, 3, 256, 32, 0, 19008, 1)
    assert (cfg.kv_row_lanes, cfg.window_row_lanes, cfg.sliding_window,
            cfg.window_pages(16)) == (640, 1152, 513, 33)
    assert cfg.rank_scales and cfg.head_gate
    with pytest.raises(ValueError, match="unsupported model_type: "
                                         "dots4_note"):
        arch.make_weights({**c, "model_type": "dots4_note"}, 1)
    plan = arch.weight_plan(c)
    assert plan[0][0] == ("embed",)
    from benchmark import rooflines_dots3_note as r
    assert sum(_size(shape) for _, shape, _ in plan) == r.param_count(c)
    assert "matrix_std" not in c["seeding"]
    # g_q lies around 0.977 on BOTH kinds: under the rank factors every lane
    # is sqrt(hidden) wide, and a head's logits then have the std 2.0
    for kind in ("full_attention", "sliding_attention"):
        assert arch.q_norm_centre(c, 0.02, kind) == pytest.approx(
            2.0 / (0.02 ** 2 * 5120), rel=1e-6)
    # (without the factors the lanes are sqrt(rank) wide and g_q 3.5x that)
    off = {**c, "apply_mla_qkv_lora_rescale": False}
    assert arch.q_norm_centre(off, 0.02, "full_attention") == pytest.approx(
        2.0 / (192 ** -0.5 * 0.02 ** 2 * (1024 * (128 * 512 + 64 * 5120))
               ** 0.5))


def test_rooflines_by_hand():
    from benchmark import rooflines_dots3_note as r

    c = _config()
    assert r.layers(c) == (2, 3)
    # full: W_qa 5120 x 1024, W_qb 1024 x 24576, W_kva 5120 x 576, W_kvb
    # 512 x 32768, W_o 16384 x 5120, W_g 5120 x 128, the two latent norms
    assert r.attention_params(c) == (5_242_880 + 25_165_824 + 2_949_120
                                     + 16_777_216 + 83_886_080 + 655_360
                                     + 1024 + 512)
    # window: W_qa 5120 x 1024, W_qb 1024 x 16384, W_kva 5120 x 1088, W_kvb
    # 1024 x 20480, W_o 8192 x 5120, W_g 5120 x 64, the two latent norms
    assert r.attention_params(c, "swa_") == (
        5_242_880 + 16_777_216 + 5_570_560 + 20_971_520 + 41_943_040
        + 327_680 + 1024 + 1024)
    # W_qI 1024 x 8192, W_kI 5120 x 128, W_w 5120 x 64, the LayerNorm's
    assert r.indexer_params(c) == 8_388_608 + 655_360 + 327_680 + 256
    # the router 5120 x 256 and its bias, 32 + 1 experts of 3 x 5120 x 1536
    assert r.expert_ffn_params(c) == 1_310_720 + 256 + 33 * 23_592_960
    assert r.dense_ffn_params(c) == 3 * 5120 * 13824 == 212_336_640
    assert round(r.param_count(c) / 1e6) == 4087
    assert round(2 * r.param_count(c) / 1e9, 2) == 8.17
    whole = {**c, "num_hidden_layers": 46, "n_routed_experts": 256,
             "vocab_size": 152064,
             "layer_types": ["full_attention"] * 2 + (
                 ["sliding_attention"] * 3 + ["full_attention"]) * 11}
    assert 279 < r.param_count(whole) / 1e9 < 280.2     # the issue's 279.6
    assert (r.latent_row_bytes(c, 2), r.latent_row_bytes(c, 2, "swa_"),
            r.index_row_bytes(c, 2)) == (1280, 2304, 256)
    s = c["serving"]
    assert s["num_pages"] == s["max_slots"] * s["pages_per_slot"] + 1 == 40961
    assert r.pool_bytes(c) == 40961 * 16 * 2 * (1280 + 256) \
        + (32 * 33 + 1) * 16 * 3 * 2304
    assert round(r.pool_bytes(c) / 1e9, 2) == 2.13
    held = 2 * r.param_count(c) + r.pool_bytes(c)
    assert round(held / 1e9, 1) == 10.3 and held / 16e9 > 0.64
    # the needs count the rows the selection names and the band holds
    base = r.sparse_latent_step_need(c, 448_000, 65_536, 32)
    assert r.sparse_latent_step_need(c, 448_001, 65_536, 32)[0] - base[0] \
        == 2 * 256
    assert r.sparse_latent_step_need(c, 448_000, 65_537, 32)[0] - base[0] \
        == 2 * 1280
    ring = r.window_latent_step_need(c, 16_416, 32)
    assert r.window_latent_step_need(c, 16_417, 32)[0] - ring[0] == 3 * 2304
    assert 9.9e-3 < r.step_bytes(c, 448_000, 65_536, 16_416, 32) / 819e9 \
        < 10.5e-3


def test_the_cell_and_its_files_keep_to_the_issue():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    w = [w for w in spec["workloads"] if w["name"] == CELL][0]
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "decode-sat-context", 1)
    assert len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    entry = [e for e in spec["configs"] if e["name"] == CONFIG][0]
    assert len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    c = _config()
    assert entry["source"] == c["source"]
    assert c["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (5, 32, 19008)
    assert c["layer_types"] == ["full_attention"] * 2 \
        + ["sliding_attention"] * 3
    # the floors: four layers after the dense one that are a whole period,
    # 32 >= 8 experts, an eighth of the vocabulary
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert sorted(c["layer_types"][1:]) == ["full_attention"] \
        + ["sliding_attention"] * 3
    assert c["n_routed_experts"] >= 8 and c["vocab_size"] * 8 == 152064
    assert (c["share"]["router_experts"], c["share"]["chips_per_layer"],
            c["published"]["n_routed_experts"]) == (256, 8, 256)
    assert set(c["not_built"]) == {"vision_tower", "audio_encoder", "mtp"}
    assert {"rescale", "gate", "band", "one_routing_group", "lineage",
            "no_indexer_on_window_layers", "tensor_names"} <= set(
        c["assumed"])
    assert "deployment" in c
    # every number of the catalog row's config that is not reduced stands:
    # the widths of BOTH kinds unchanged
    catalog = {
        "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
        "attention_gate_type": "headwise", "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
        "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
        "kv_lora_rank": 512, "max_position_embeddings": 524288,
        "model_type": "dots3_note", "moe_intermediate_size": 1536,
        "moe_layer_freq": 1, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 128, "q_lora_rank": 1024,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
        "routed_scaling_factor": 1, "scoring_func": "sigmoid",
        "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
        "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
        "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
        "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
        "swa_rope_theta": 50000, "swa_v_head_dim": 128,
        "tie_word_embeddings": False, "topk_method": "noaux_tc",
        "v_head_dim": 128}
    assert {k: c[k] for k in catalog} == catalog
    assert c["torch_dtype"] == "bfloat16" and c["chips"] == 1
    assert c["seeding"]["gate_logit_std"] == 1.0
    with open(os.path.join(HERE, "traffic", "decode-sat-context.json")) as f:
        t = json.load(f)
    assert max(t["prompt"]["values"]) + max(t["answer"]["values"]) == \
        c["serving"]["pages_per_slot"] * c["serving"]["page_size"] == 20480
    assert min(t["prompt"]["values"]) > c["index_topk"] \
        > c["sliding_window_size"]
    reported = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [])}
    assert NEW | JOINED <= reported
    # (the cell admits in a few bursts a window: a traced 6 s often holds
    # none, so the two readers that need an admission in the trace are left;
    # deepseek's three count deepseek's layers and stay its own)
    assert not {"moe_grouped_dev_ms", "admit_dev_ms",
                "attn_sparse_latent_hbm_share", "dsv32_experts_hbm_share",
                "dsv32_step_hbm_share", "attn_window_dev_ms",
                "attn_latent_dev_ms", "mellum_step_hbm_share", "slot_util",
                "pool_live", "evictions"} & reported
    assert {m["name"] for m in spec["per_layer"]
            if m.get("workloads") == [CELL]} == NEW
    for m in spec["per_layer"]:
        if m["name"] in NEW:
            assert (m["moves"], m["layer"]) == ("gap_mean_ms", "kernels")
            assert os.path.exists(os.path.join(HERE, "metrics",
                                               m["name"] + ".py"))
    e2e = {m["name"] for m in spec["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"gap_mean_ms", "setup_s"}
    assert os.path.exists(os.path.join(HERE, "limits", CELL + ".json"))
    toy = TOY[CELL]
    assert os.path.exists(os.path.join(TD, "configs", toy[1] + ".json"))
    assert os.path.exists(os.path.join(TD, "traffic", toy[2] + ".json"))
    assert os.path.exists(os.path.join(TD, "limits", toy[0] + ".json"))
