"""The ``deepseek_v32`` architecture hooks under ``closed_loop_arch``, driven
on the CPU at a toy size (``testdata/toys.d/``): a run comes out ``correct``
and its record has every key ``serving_run.run``'s has; it does not under the
float8 control, nor when the served path attends the newest ``topk`` rows in
place of the selected ones, routes without its groups, leaves the indexer
unrotated, or makes the indexer's query from ``x``. The shapes' arithmetic by
hand, and the four new readers on events and counters made by hand."""
import json
import os
import time

import pytest

from benchmark.cell import HERE, load_cell, load_module
from benchmark.run import result_object

TD = os.path.join(HERE, "testdata")
CELL = "deepseek-v3.2-exp-ep16.decode-sat-context"
CONFIG = "deepseek-v3.2-exp-ep16"
with open(os.path.join(TD, "toys.d", CELL + ".json")) as f:
    TOY = {cell: tuple(toy) for cell, toy in json.load(f).items()}
NAME = TOY[CELL][0]
PLAIN = "qwen2-0.5b.decode-sat"
NEW = {"attn_sparse_latent_dev_ms", "attn_sparse_latent_hbm_share",
       "dsv32_experts_hbm_share", "dsv32_step_hbm_share"}
JOINED = {"moe_experts_dev_ms", "unembed_sample_dev_ms", "dense_mlp_dev_ms",
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
    path = tmp_path_factory.mktemp("toy_dsv32") / "BENCHMARK.json"
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
    r0, r1 = record["report0"], record["report1"]
    assert r1["sparse_read"].startswith("xla row gather")
    assert r1["index_rows_scored"] == r1["sparse_rows_live"]
    assert r1["sparse_rows_attended"] - r0["sparse_rows_attended"] < \
        r1["sparse_rows_live"] - r0["sparse_rows_live"]
    # a row an EXPERT layer (the leading dense layer routes nothing), a
    # column a HELD expert (8 of the router's 16)
    assert [len(row) for row in r1["expert_tokens"]] == [8] * 2
    made = r1["routed_assignments"] - r0["routed_assignments"]
    assert made > 0 and made % (3 * 2) == 0        # top-3, two layers
    assert 0 < r1["routed_local"] < r1["routed_assignments"]
    assert r1["evicted"] == 0
    # a position's stored bytes a layer: the latent row's and the index
    # key's one lane tile each, float32
    assert r1["kv_row_bytes"] == (128 + 128) * 4


def _events(ops, modules):
    """One device plane and a window of 1 ms, times in ns."""
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": [["bench.window", 0, 1_000_000]]}


def test_the_new_readers_on_events_made_by_hand(monkeypatch):
    """Two runs of the step executable and a prefill between them: only the
    operations inside the step's runs count, per run; a program whose table
    has no such scope reads None; the shares are the need at the chip's
    peaks over that time, by hand, and a floor."""
    from benchmark import rooflines_deepseek_v32 as r
    from benchmark import rooflines_lfm2_moe as shared

    step = "jit__batched_hybrid_step_jit(123)"
    modules = [[step, 0, 100_000], ["jit__prefill_jit(7)", 100_000, 500_000],
               [step, 600_000, 100_000]]
    ops = [["attn.sparse_latent", 5_000, 10_000],
           ["attn.sparse.index", 15_000, 20_000],
           ["attn.sparse.select", 35_000, 6_000],
           ["paged_kv.write", 41_000, 1_000],
           ["moe.experts", 42_000, 50_000],
           # a prefill's: the same inner scopes, outside the step's runs
           ["attn.sparse.index", 150_000, 200_000],
           ["attn.sparse_latent.prefill", 350_000, 100_000],
           ["moe.experts", 450_000, 100_000],
           ["attn.sparse_latent", 605_000, 12_000],
           ["attn.sparse.index", 617_000, 18_000],
           ["attn.sparse.select", 635_000, 4_000],
           ["paged_kv.write", 639_000, 1_000],
           ["moe.route", 640_000, 4_000],
           ["moe.experts", 644_000, 46_000],
           ["moe.shared", 690_000, 4_000]]
    ev = _events(ops, modules)
    name = "_batched_hybrid_step_jit"
    assert shared.scope_ms_in_step(ev, r.SPARSE_LATENT_SCOPES, name) == \
        pytest.approx(1e-6 * (37_000 + 35_000) / 2)
    # the parent's program under these files: no such scope
    older = [op for op in ops if op[0].startswith("moe.")]
    assert shared.scope_ms_in_step(_events(older, modules),
                                   r.SPARSE_LATENT_SCOPES, name) is None

    c = _config()
    # 16 riders a step, 14,000 live rows each, 2048 attended
    counters = {"steps": 100, "slot_util_mean": 1.0,
                "index_rows_scored": 100 * 16 * 14_000,
                "sparse_rows_live": 100 * 16 * 14_000,
                "sparse_rows_attended": 100 * 16 * 2048}
    record = {"trace": {"modules": {"jit__batched_hybrid_step_jit": {
                  "runs": 100, "seconds": 2.2}}},
              "config": c, "device_kind": "TPU v5 lite",
              "report0": dict.fromkeys(counters, 0) | {"slot_util_mean": 0.0},
              "report1": counters}
    monkeypatch.setitem(shared._EVENTS, "events", ev)

    def read(name):
        return load_module(os.path.join(HERE, "metrics", name + ".py"),
                           "dsv32_" + name).read(record)

    assert read("attn_sparse_latent_dev_ms") == pytest.approx(0.036)
    assert read("attn_index_dev_ms") == pytest.approx(0.019)
    assert read("sparse_selected_share") == pytest.approx(100 * 2048 / 14_000)
    need, ops_ = r.sparse_latent_step_need(c, 16 * 14_000, 16 * 2048, 16)
    # 5 layers x (201.1M parameters x 2 B + 224,000 index keys x 256 B +
    # 32,768 latent rows x 1280 B + 16 rows of each written)
    assert need == 5 * ((187_107_328 + 13_959_424) * 2 + 224_000 * 256
                        + 32_768 * 1280 + 16 * (1280 + 256))
    assert need / 819e9 > ops_ / 197e12          # bytes bind, not the MXU
    assert read("attn_sparse_latent_hbm_share") == pytest.approx(
        100 * (need / 819e9) / 0.036e-3)
    inside = r.experts_in_scope_bytes(c, "TPU v5 lite")
    assert inside == 4 * (7168 * 256 + 256 + 3 * 17 * 7168 * 2048) * 2 \
        - 4 * 128 * 2 ** 20
    assert read("dsv32_experts_hbm_share") == pytest.approx(
        100 * (inside / 819e9) / 0.052e-3)
    with pytest.raises(KeyError, match="vector memory"):
        r.experts_in_scope_bytes(c, "TPU v9")
    whole = r.step_bytes(c, 16 * 14_000, 16 * 2048, 16)
    assert read("dsv32_step_hbm_share") == pytest.approx(
        100 * (whole / 819e9) / 22e-3)
    assert 0 < read("dsv32_step_hbm_share") < 100
    # an untraced run, or a process that left no profile
    monkeypatch.setitem(shared._EVENTS, "events", None)
    for name in NEW - {"dsv32_step_hbm_share"}:
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


def test_the_newest_rows_in_place_of_the_selected_are_not_correct(
        monkeypatch, cell):
    import jax.numpy as jnp

    from edgellm_tpu.models import sparse_attn

    def newest(scores, lengths, k):
        idx = lengths[:, None] - 1 - jnp.arange(k)[None, :]
        return (jnp.maximum(idx, 0).astype(jnp.int32),
                jnp.minimum(lengths, k).astype(jnp.int32))

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        sparse_attn, "select", newest))


def test_ungrouped_routing_is_not_correct(monkeypatch, cell):
    from edgellm_tpu.models import moe

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        moe, "_group_limited", lambda cfg, biased: biased))


def test_the_indexer_left_unrotated_is_not_correct(monkeypatch, cell):
    from edgellm_tpu.models import sparse_mla

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        sparse_mla, "index_rotation_rows",
        lambda cfg, cos, sin: (lambda t: t)))


def test_the_indexers_query_from_x_is_not_correct(monkeypatch, cell):
    import jax.numpy as jnp

    from edgellm_tpu.models import sparse_attn, sparse_mla

    real = sparse_attn.project_index

    def from_x(cfg, lp, x, rotate, query=None):
        tall = {**lp, "wq_index": jnp.resize(
            lp["wq_index"], (x.shape[-1], lp["wq_index"].shape[-1]))}
        return real(cfg, tall, x, rotate)

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        sparse_mla, "project_index", from_x))


def test_the_reference_is_literal_and_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_deepseek_v32.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]
    assert "edgellm_tpu" not in code
    assert 'default_matmul_precision("highest")' in code
    # the selection is top_k over each row's visible scores, literally; the
    # attention is the expanded form (keys rebuilt per head), never absorbed
    assert "jax.lax.top_k(jnp.where(visible, scores, -jnp.inf)" in code
    assert "approx" not in code and "absorb" not in code
    assert 'jnp.einsum("qhd,thd->hqt", q_nope, k_nope)' in code
    assert "jax.nn.relu(dots)" in code and "jax.nn.sigmoid" in code


def _size(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def test_the_parent_fails_the_cell_before_any_weight():
    """``make_weights`` asks the program for the family first: a program
    without it raises ``unsupported model_type`` at once."""
    arch = load_module(os.path.join(HERE, "architectures", "deepseek_v32.py"),
                       "arch_dsv32")
    c = _config()
    cfg = arch.model_config(c)
    assert (cfg.family, cfg.sparse_layers, cfg.latent_layers, cfg.kv_layers,
            cfg.num_experts, cfg.experts_held, cfg.expert_offset,
            cfg.vocab_size, cfg.num_dense_layers) == (
        "deepseek_v32", 5, 5, 5, 256, 16, 0, 16160, 1)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk,
            cfg.route_groups, cfg.route_groups_kept) == (64, 128, 2048, 8, 4)
    with pytest.raises(ValueError, match="unsupported model_type: "
                                         "deepseek_v33"):
        arch.make_weights({**c, "model_type": "deepseek_v33"}, 1)
    plan = arch.weight_plan(c)
    assert plan[0][0] == ("embed",)
    from benchmark import rooflines_deepseek_v32 as r
    assert sum(_size(shape) for _, shape, _ in plan) == r.param_count(c)
    assert "matrix_std" not in c["seeding"]
    # g_q lies around 1.303: a head's logits then have the std 2.0
    assert arch.q_norm_centre(c, 0.02) == pytest.approx(1.303, abs=1e-3)


def test_rooflines_by_hand():
    from benchmark import rooflines_deepseek_v32 as r

    c = _config()
    # W_qa 7168 x 1536, W_qb 1536 x 24576, W_kva 7168 x 576, W_kvb 512 x
    # 32768, W_o 16384 x 7168, the two latent norms
    assert r.attention_params(c) == (11_010_048 + 37_748_736 + 4_128_768
                                     + 16_777_216 + 117_440_512 + 1536 + 512)
    # W_qI 1536 x 8192, W_kI 7168 x 128, W_w 7168 x 64, the LayerNorm's
    assert r.indexer_params(c) == 12_582_912 + 917_504 + 458_752 + 256
    # the router 7168 x 256 and its bias, 16 + 1 experts of 3 x 7168 x 2048
    assert r.expert_ffn_params(c) == 1_835_008 + 256 + 17 * 44_040_192
    assert r.dense_ffn_params(c) == 3 * 7168 * 18432 == 396_361_728
    assert round(r.param_count(c) / 1e6) == 4636
    assert round(2 * r.param_count(c) / 1e9, 2) == 9.27
    whole = {**c, "num_hidden_layers": 61, "first_k_dense_replace": 3,
             "n_routed_experts": 256, "vocab_size": 129280}
    assert 671 < r.param_count(whole) / 1e9 < 672.5   # the indexers' 0.85
    assert (r.latent_row_bytes(c, 2), r.index_row_bytes(c, 2)) == (1280, 256)
    s = c["serving"]
    assert s["num_pages"] == s["max_slots"] * s["pages_per_slot"] + 1 == 20481
    assert r.pool_bytes(c) == 20481 * 16 * 5 * (1280 + 256)
    assert round(r.pool_bytes(c) / 1e9, 2) == 2.52
    held = 2 * r.param_count(c) + r.pool_bytes(c)
    assert round(held / 1e9, 2) == 11.79 and held / 16e9 > 0.73
    # the need counts the rows the selection names: a walk of every live row
    # of the same slots would read 5 x 224,000 x 1280 B = 1.43 GB
    base = r.sparse_latent_step_need(c, 224_000, 32_768, 16)
    assert r.sparse_latent_step_need(c, 224_001, 32_768, 16)[0] - base[0] \
        == 5 * 256
    assert r.sparse_latent_step_need(c, 224_000, 32_769, 16)[0] - base[0] \
        == 5 * 1280
    assert 11.4e-3 < r.step_bytes(c, 224_000, 32_768, 16) / 819e9 < 11.9e-3


def test_the_cell_and_its_files_keep_to_the_issue():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    w = [w for w in spec["workloads"] if w["name"] == CELL][0]
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "decode-sat-context", 1)
    assert len(w["why"]) <= 200 and spec["workloads"][-1] is w
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    entry = [e for e in spec["configs"] if e["name"] == CONFIG][0]
    assert len(entry["why"]) <= 200 and spec["configs"][-1] is entry
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    c = _config()
    assert entry["source"] == c["source"]
    assert c["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 61,
                              "first_k_dense_replace": 3,
                              "n_routed_experts": 256, "vocab_size": 129280}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["vocab_size"]) == (5, 1, 16, 16160)
    # the floors: a leading dense layer and four expert layers, 16 >= 8
    # experts, an eighth of the vocabulary
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["n_routed_experts"] >= 8 and c["vocab_size"] * 8 == 129280
    assert (c["share"]["router_experts"], c["share"]["router_groups"],
            c["share"]["chips_per_layer"]) == (256, 8, 16)
    assert "mtp" in c["not_built"] and "deployment" in c and "assumed" in c
    # every number of the catalog row's config that is not reduced stands,
    # nested groups whole
    catalog = {
        "attention_bias": False, "ep_size": 1, "hidden_act": "silu",
        "hidden_size": 7168, "index_head_dim": 128, "index_n_heads": 64,
        "index_topk": 2048, "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "model_type": "deepseek_v32",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: c[k] for k in catalog} == catalog
    assert c["torch_dtype"] == "bfloat16" and c["chips"] == 1
    with open(os.path.join(HERE, "traffic", "decode-sat-context.json")) as f:
        t = json.load(f)
    assert max(t["prompt"]["values"]) + max(t["answer"]["values"]) == \
        c["serving"]["pages_per_slot"] * c["serving"]["page_size"] == 20480
    assert min(t["prompt"]["values"]) > c["index_topk"]
    reported = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [])}
    assert NEW | JOINED <= reported
    # (the cell admits in a few bursts a window: a traced 6 s often holds
    # none, so the two readers that need an admission in the trace are left)
    assert not {"moe_grouped_dev_ms", "admit_dev_ms"} & reported
    assert not {"attn_sparse_dev_ms", "attn_latent_dev_ms",
                "attn_decode_dev_ms", "keye_step_hbm_share",
                "mistral4_step_hbm_share", "slot_util", "pool_live",
                "evictions"} & reported
    assert {m["name"] for m in spec["per_layer"]
            if m.get("workloads") == [CELL]} == NEW
    assert [m["name"] for m in spec["per_layer"][-4:]] == [
        "attn_sparse_latent_dev_ms", "attn_sparse_latent_hbm_share",
        "dsv32_experts_hbm_share", "dsv32_step_hbm_share"]
    for m in spec["per_layer"][-4:]:
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
