"""The ``KeyeVL2`` architecture hooks under ``closed_loop_arch``, driven on
the CPU at a toy size (``testdata/toys.d/``): a run comes out ``correct`` and
its record has every key ``serving_run.run``'s has; it does not under the
float8 control, nor when the served path attends the newest ``topk`` rows in
place of the selected ones, drops the index key's LayerNorm bias, leaves the
indexer unrotated, or drops the per-head q/k norms. The shapes' arithmetic by
hand, and the eight new readers on events and counters made by hand."""
import json
import os
import time

import pytest

from benchmark.cell import HERE, load_cell, load_module
from benchmark.run import result_object

TD = os.path.join(HERE, "testdata")
CELL = "keye-vl-2.0-30b-a3b-ep4.decode-sat-context"
CONFIG = "keye-vl-2.0-30b-a3b-ep4"
with open(os.path.join(TD, "toys.d", CELL + ".json")) as f:
    TOY = {cell: tuple(toy) for cell, toy in json.load(f).items()}
NAME = TOY[CELL][0]
PLAIN = "qwen2-0.5b.decode-sat"
NEW = {"attn_sparse_dev_ms", "attn_index_dev_ms", "attn_select_dev_ms",
       "attn_sparse_hbm_share", "keye_experts_dev_ms",
       "keye_experts_hbm_share", "keye_step_hbm_share",
       "sparse_selected_share"}
TRACED = NEW - {"sparse_selected_share"}
JOINED = {"moe_experts_dev_ms", "unembed_sample_dev_ms",
          "step_dev_ms", "device_idle", "prefill_tok_s",
          "between_steps_ms", "launch_ahead_share", "compiles_in_window",
          "step_host_ms", "step_wall_ms", "host_admit_ms"}


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
    path = tmp_path_factory.mktemp("toy_keye") / "BENCHMARK.json"
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
    # counter's reader does
    layer = result_object(cell, record, trace=True)["metrics"]
    assert not TRACED & set(layer)
    assert 0 < layer["sparse_selected_share"]["value"] < 100
    assert layer["launch_ahead_share"]["value"] > 0
    r0, r1 = record["report0"], record["report1"]
    # the toy's prompts of 8 and 40 pass its topk of 8 at once: every step
    # selects, and scores every live row (on the CPU by the row gather)
    assert r1["sparse_read"].startswith("xla row gather")
    assert r1["index_rows_scored"] == r1["sparse_rows_live"]
    assert r1["sparse_rows_attended"] - r0["sparse_rows_attended"] < \
        r1["sparse_rows_live"] - r0["sparse_rows_live"]
    # a row a layer, a column a HELD expert (4 of the router's 8)
    assert [len(row) for row in r1["expert_tokens"]] == [4] * 2
    made = r1["routed_assignments"] - r0["routed_assignments"]
    assert made > 0 and made % (3 * 2) == 0        # top-3, two layers
    assert 0 < r1["routed_local"] < r1["routed_assignments"]
    assert r1["evicted"] == 0
    # a position's stored bytes a layer: K | V of 2 x 16 lanes and the index
    # key's one lane tile, float32
    assert r1["kv_row_bytes"] == (2 * 32 + 128) * 4


def test_the_traced_readers_read_nothing_without_a_trace(cell):
    record = cell.kind.run(cell, 7, 0.3, _env())
    readers = {m.name: m.reader for m in cell.per_layer if m.name in NEW}
    assert set(readers) == NEW
    for name in TRACED:
        assert readers[name](record) is None, name
    # ... nor the counter's on another program's report
    for r in (record["report0"], record["report1"]):
        for k in ("sparse_rows_live", "sparse_rows_attended",
                  "index_rows_scored"):
            del r[k]
    assert readers["sparse_selected_share"](record) is None


def _events(ops, modules):
    """One device plane and a window of 1 ms, times in ns."""
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": [["bench.window", 0, 1_000_000]]}


def test_the_new_readers_on_events_made_by_hand(monkeypatch):
    """Two runs of the step executable and a prefill between them: only the
    operations inside the step's runs count, per run; a program whose table
    has no such scope reads None; the shares are the need at the chip's
    peaks over that time, by hand, and a floor."""
    from benchmark import rooflines_keye_vl2 as r
    from benchmark import rooflines_lfm2_moe as shared

    step = "jit__batched_hybrid_step_jit(123)"
    modules = [[step, 0, 100_000], ["jit__prefill_jit(7)", 100_000, 500_000],
               [step, 600_000, 100_000]]
    ops = [["attn.sparse", 5_000, 10_000],
           ["attn.sparse.index", 15_000, 20_000],
           ["attn.sparse.select", 35_000, 6_000],
           ["paged_kv.write", 41_000, 1_000],
           ["moe.experts", 42_000, 50_000],
           # a prefill's: the same inner scopes, outside the step's runs
           ["attn.sparse.index", 150_000, 200_000],
           ["attn.sparse.select", 350_000, 100_000],
           ["moe.experts", 450_000, 100_000],
           ["attn.sparse", 605_000, 12_000],
           ["attn.sparse.index", 617_000, 18_000],
           ["attn.sparse.select", 635_000, 4_000],
           ["paged_kv.write", 639_000, 1_000],
           ["moe.route", 640_000, 4_000],
           ["moe.experts", 644_000, 50_000]]
    ev = _events(ops, modules)
    name = "_batched_hybrid_step_jit"
    assert shared.scope_ms_in_step(ev, r.SPARSE_SCOPES, name) == \
        pytest.approx(1e-6 * (37_000 + 35_000) / 2)
    assert shared.scope_ms_in_step(ev, r.INDEX_SCOPES, name) == \
        pytest.approx(1e-6 * (20_000 + 18_000) / 2)
    assert shared.scope_ms_in_step(ev, r.SELECT_SCOPES, name) == \
        pytest.approx(1e-6 * (6_000 + 4_000) / 2)
    # the parent's program under these files: no such scope
    older = [op for op in ops if not op[0].startswith("attn.sparse")]
    assert shared.scope_ms_in_step(_events(older, modules), r.INDEX_SCOPES,
                                   name) is None

    c = _config()
    # 32 riders a step, 14,000 live rows each, 2048 attended
    counters = {"steps": 100, "slot_util_mean": 1.0,
                "index_rows_scored": 100 * 32 * 14_000,
                "sparse_rows_live": 100 * 32 * 14_000,
                "sparse_rows_attended": 100 * 32 * 2048}
    record = {"trace": {"modules": {"jit__batched_hybrid_step_jit": {
                  "runs": 100, "seconds": 1.2}}},
              "config": c, "device_kind": "TPU v5 lite",
              "report0": dict.fromkeys(counters, 0) | {"slot_util_mean": 0.0},
              "report1": counters}
    monkeypatch.setitem(shared._EVENTS, "events", ev)

    def read(name):
        return load_module(os.path.join(HERE, "metrics", name + ".py"),
                           "keye_" + name).read(record)

    assert read("attn_sparse_dev_ms") == pytest.approx(0.036)
    assert read("attn_index_dev_ms") == pytest.approx(0.019)
    assert read("attn_select_dev_ms") == pytest.approx(0.005)
    assert read("sparse_selected_share") == pytest.approx(100 * 2048 / 14_000)
    need, ops_ = r.sparse_step_need(c, 32 * 14_000, 32 * 2048, 32)
    # 6 layers x (21.1M parameters x 2 B + 448,000 index keys x 256 B +
    # 65,536 K/V rows x 2 KB + 32 rows of each written)
    assert need == 6 * ((18_874_624 + 2_261_120) * 2 + 448_000 * 256
                        + 65_536 * 2048 + 32 * (2048 + 256))
    assert need / 819e9 > ops_ / 197e12          # bytes bind, not the MXU
    assert read("attn_sparse_hbm_share") == pytest.approx(
        100 * (need / 819e9) / 0.036e-3)
    # the in-step time of the two expert scopes alone, and the share of it
    # that the bytes need which the chip's vector memory cannot hold ahead
    # (six layers of routers and 32 experts less 6 x 128 MiB: on the chip
    # a third of each layer's expert bytes is staged there under the
    # attention ahead, and every byte over this time read 125.5%)
    assert read("keye_experts_dev_ms") == pytest.approx(0.052)
    inside = r.experts_in_scope_bytes(c, "TPU v5 lite")
    assert inside == 6 * (2048 * 128 + 3 * 32 * 2048 * 768) * 2 \
        - 6 * 128 * 2 ** 20
    assert 0.55 < inside / r.experts_step_bytes(c) < 0.56
    assert read("keye_experts_hbm_share") == pytest.approx(
        100 * (inside / 819e9) / 0.052e-3)
    with pytest.raises(KeyError, match="vector memory"):
        r.experts_in_scope_bytes(c, "TPU v9")
    whole = r.step_bytes(c, 32 * 14_000, 32 * 2048, 32)
    assert read("keye_step_hbm_share") == pytest.approx(
        100 * (whole / 819e9) / 12e-3)
    assert 0 < read("keye_step_hbm_share") < 100
    # an untraced run, or a process that left no profile
    monkeypatch.setitem(shared._EVENTS, "events", None)
    for name in TRACED - {"keye_step_hbm_share"}:
        assert read(name) is None, name
    record["trace"] = None
    for name in TRACED:
        assert read(name) is None, name
    assert read("sparse_selected_share") is not None


def test_every_share_is_a_floor_by_construction():
    """Each byte and each multiply-add once: the need of a step grows with
    every row it is told of and never counts a row a query did not attend,
    so a share of measured time cannot pass 100% unless the time leaves work
    out. Against the peaks: the cell's step needs at least 3.5 ms."""
    from benchmark import rooflines_keye_vl2 as r

    c = _config()
    base = r.sparse_step_need(c, 448_000, 65_536, 32)
    assert r.sparse_step_need(c, 448_001, 65_536, 32)[0] - base[0] == 6 * 256
    assert r.sparse_step_need(c, 448_000, 65_537, 32)[0] - base[0] == 6 * 2048
    # a full read of the same slots would be 6 x 448,000 x 2 KB = 5.5 GB:
    # the floor counts the selected rows alone
    assert base[0] < 0.35 * (6 * 448_000 * 2048)
    whole = r.step_bytes(c, 448_000, 65_536, 32)
    assert whole > r.experts_step_bytes(c) + base[0] - 1
    assert 4.4e-3 < whole / 819e9 < 4.7e-3


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


def test_the_newest_rows_in_place_of_the_selected_are_not_correct(
        monkeypatch, cell):
    """The cheap wrong answer: a step that attends the newest ``topk``
    positions and scores nothing. (At the toy's widths, seeded at
    ``matrix_std``, which rows are attended moves the logits; at the
    published widths it hardly does, which is why ``chip_smoke.py`` compares
    the chosen row ids themselves.)"""
    import jax.numpy as jnp

    from edgellm_tpu.models import sparse_attn

    def newest(scores, lengths, k):
        idx = lengths[:, None] - 1 - jnp.arange(k)[None, :]
        return (jnp.maximum(idx, 0).astype(jnp.int32),
                jnp.minimum(lengths, k).astype(jnp.int32))

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        sparse_attn, "select", newest))


def test_the_index_keys_bias_dropped_is_not_correct(monkeypatch, cell):
    _broken(monkeypatch, cell, 5, _served(monkeypatch, cell, lambda w: {
        **w, "sparse": {**w["sparse"], "index_norm_bias":
                        0 * w["sparse"]["index_norm_bias"] + 1.0}}))


def test_the_indexer_left_unrotated_is_not_correct(monkeypatch, cell):
    from edgellm_tpu.models import sparse_attn

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        sparse_attn, "rotate_rows", lambda cos, sin: (lambda t: t)))


def test_the_head_norms_dropped_are_not_correct(monkeypatch, cell):
    _broken(monkeypatch, cell, 5, _served(monkeypatch, cell, lambda w: {
        **w, "sparse": {k: v for k, v in w["sparse"].items()
                        if k not in ("q_norm", "k_norm")}}))


def test_the_reference_is_literal_and_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_keye_vl2.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]
    assert "edgellm_tpu" not in code
    assert 'default_matmul_precision("highest")' in code
    # the selection is top_k over each row's visible scores, literally, and
    # the rotation is made of the three streams
    assert "jax.lax.top_k(jnp.where(visible, scores, -jnp.inf)" in code
    assert "approx" not in code
    assert 'k["mrope_section"] * 2' in code and "i % 3" in code
    assert "jax.nn.relu(dots)" in code


def test_the_parent_fails_the_cell_before_any_weight():
    """``make_weights`` asks the program for the family first: a program
    without it raises ``unsupported model_type`` at once."""
    arch = load_module(os.path.join(HERE, "architectures", "KeyeVL2.py"),
                       "arch_keye")
    c = _config()
    cfg = arch.model_config(c)
    assert (cfg.family, cfg.sparse_layers, cfg.kv_layers, cfg.num_experts,
            cfg.experts_held, cfg.expert_offset, cfg.vocab_size) == (
        "keye_vl2", 6, 6, 128, 32, 0, 37984)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk,
            cfg.mrope_section) == (16, 64, 2048, (16, 24, 24))
    with pytest.raises(ValueError, match="unsupported model_type: "
                                         "KeyeVL3"):
        arch.make_weights({**c, "model_type": "KeyeVL3"}, 1)
    plan = arch.weight_plan(c)
    assert plan[0][0] == ("embed",)
    from benchmark import rooflines_keye_vl2 as r
    assert sum(_size(shape) for _, shape, _ in plan) == r.param_count(c)
    # the published widths seed at 0.02; only a toy's file widens a matrix
    assert "matrix_std" not in c["seeding"]


def _size(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def test_rooflines_by_hand():
    from benchmark import rooflines_keye_vl2 as r

    c = _config()
    # wq and wo 2048 x 4096, wk and wv 2048 x 512, two norms of 128
    assert r.attention_params(c) == 2 * 8_388_608 + 2 * 1_048_576 + 256 \
        == 18_874_624
    # WqI 2048 x 1024, WkI 2048 x 64, Ww 2048 x 16, the LayerNorm's 2 x 64
    assert r.indexer_params(c) == 2_097_152 + 131_072 + 32_768 + 128 \
        == 2_261_120
    # the router 2048 x 128, 32 experts of 3 x 2048 x 768
    assert r.expert_ffn_params(c) == 262_144 + 32 * 4_718_592 == 151_257_088
    assert r.layer_params(c) == 172_396_928
    assert r.param_count(c) == (6 * 172_396_928 + 2 * 37_984 * 2048
                                + 2048) == 1_189_966_080
    assert round(2 * r.param_count(c) / 1e9, 2) == 2.38
    # whole: 48 layers, 128 experts held, the published table and head
    whole = {**c, "num_hidden_layers": 48, "num_experts": 128,
             "vocab_size": 151936}
    assert round(r.param_count(whole) / 1e9, 1) == 30.6
    # a position of a layer: K and V 2 x 4 heads x 128 lanes x 2 B; the
    # index key 64 lanes stored in 128
    assert (r.kv_row_bytes(c, 2), r.index_row_bytes(c, 2)) == (2048, 256)
    s = c["serving"]
    assert s["num_pages"] == s["max_slots"] * s["pages_per_slot"] + 1 == 40961
    assert r.pool_bytes(c) == 40961 * 16 * 6 * (2048 + 256)
    assert round(r.pool_bytes(c) / 1e9, 2) == 9.06
    assert r.experts_step_bytes(c) == 6 * 151_257_088 * 2
    assert round(r.experts_step_bytes(c) / 819e9 * 1e3, 2) == 2.22
    held = 2 * r.param_count(c) + r.pool_bytes(c)
    assert round(held / 1e9, 2) == 11.44 and held / 16e9 > 0.71


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
    with open(os.path.join(HERE, "traffic", "decode-sat-context.json")) as f:
        t = json.load(f)
    assert t == {"kind": "closed_loop_arch", "callers": "max_slots",
                 "prompt": {"values": [8192, 16384]},
                 "answer": {"values": [2048, 4096]},
                 "temperature": {"values": [0.0, 0.7]}}
    c = _config()
    assert entry["source"] == c["source"]
    assert c["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                              "num_local_experts": 128, "vocab_size": 151936}
    assert (c["num_hidden_layers"], c["num_experts"], c["num_local_experts"],
            c["vocab_size"]) == (6, 32, 32, 37984)
    assert c["share"]["router_experts"] == 128
    assert (c["share"]["experts_held"], c["share"]["chips_per_layer"],
            c["share"]["layers_per_host"], c["share"]["hosts"]) == (32, 4, 6,
                                                                    8)
    # every number of the catalog row's config that is not reduced stands,
    # nested groups whole
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: c[k] for k in catalog} == catalog
    assert c["torch_dtype"] == "bfloat16" and c["chips"] == 1
    assert max(t["prompt"]["values"]) + max(t["answer"]["values"]) == \
        c["serving"]["pages_per_slot"] * c["serving"]["page_size"] == 20480
    assert c["serving"]["max_slots"] == 32
    # every slot of the mix is past topk from its first step
    assert min(t["prompt"]["values"]) > c["sa_config"]["topk"]
    reported = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [])}
    assert NEW | JOINED <= reported
    # (the masked walk walks pages and counts them: both counters' readers)
    assert {"attend_walk_share", "attend_run_share"} <= reported
    # (the cell admits in a few bursts a window: a traced 6 s often holds
    # none, so the two readers that need an admission in the trace are left)
    assert not {"moe_grouped_dev_ms", "admit_dev_ms"} & reported
    assert not {"ssm_step_dev_ms", "attn_window_dev_ms", "attn_latent_dev_ms",
                "attn_decode_dev_ms", "dense_mlp_dev_ms", "shortconv_dev_ms", "expert_load_skew",
                "routed_local_share", "slot_util", "pool_live",
                "evictions"} & reported
    assert {m["name"] for m in spec["per_layer"]
            if m.get("workloads") == [CELL]} == NEW
    for m in spec["per_layer"]:
        if m["name"] in NEW:
            assert m["moves"] == "gap_mean_ms"
            assert m["layer"] == {"sparse_selected_share": "cache",
                                  "keye_experts_dev_ms": "model step"}.get(
                m["name"], "kernels")
            assert os.path.exists(os.path.join(HERE, "metrics",
                                               m["name"] + ".py"))
    e2e = {m["name"] for m in spec["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"gap_mean_ms", "setup_s"}
    for part in ("limits", ):
        assert os.path.exists(os.path.join(HERE, part, CELL + ".json"))
    toy = TOY[CELL]
    assert os.path.exists(os.path.join(TD, "configs", toy[1] + ".json"))
    assert os.path.exists(os.path.join(TD, "traffic", toy[2] + ".json"))
    assert os.path.exists(os.path.join(TD, "limits", toy[0] + ".json"))
    with open(os.path.join(TD, "configs", toy[1] + ".json")) as f:
        tiny = json.load(f)
    assert tiny["sa_config"]["topk"] == 8
    assert len(tiny["rope_scaling"]["mrope_section"]) == 3
