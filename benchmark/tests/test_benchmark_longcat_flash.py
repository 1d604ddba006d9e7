"""The ``longcat_flash`` architecture hooks under ``closed_loop_arch``, driven
on the CPU at a toy size (``testdata/toys.d/``): a run comes out ``correct``
and its record has every key ``serving_run.run``'s has; it does not under the
float8 control, nor when the served path drops the identity experts' part,
applies the identity to ``h`` and not to ``u0``, joins the shortcut before the
second attention sublayer, renormalises the weights over the chosen, lets the
selection bias into the weights (or drops it), caches the latent without its
rank scale, or holds a share offset by one expert. The shapes' arithmetic by
hand, and the four new readers on recorded events."""
import dataclasses
import json
import os
import time

import pytest

from benchmark import program_trace as pt
from benchmark.cell import HERE, load_cell, load_module
from benchmark.run import result_object

TD = os.path.join(HERE, "testdata")
CELL = "longcat-flash-chat-ep32.decode-sat-reason"
with open(os.path.join(TD, "toys.d", CELL + ".json")) as f:
    TOY = {cell: tuple(toy) for cell, toy in json.load(f).items()}
NAME = TOY[CELL][0]
PLAIN = "qwen2-0.5b.decode-sat"
NEW = {"zero_expert_share", "longcat_step_hbm_share",
       "longcat_experts_hbm_share", "longcat_attn_latent_hbm_share"}
JOINED = {"attn_latent_dev_ms", "latent_pool_live", "attend_walk_share",
          "moe_experts_dev_ms", "moe_grouped_dev_ms", "dense_mlp_dev_ms",
          "unembed_sample_dev_ms", "step_dev_ms", "admit_dev_ms",
          "device_idle", "prefill_tok_s", "between_steps_ms"}


def _config():
    with open(os.path.join(HERE, "configs",
                           "longcat-flash-chat-ep32.json")) as f:
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
    path = tmp_path_factory.mktemp("toy_longcat") / "BENCHMARK.json"
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
    assert nums["control_gap_mean"] > cell.limits["gap_mean"]
    assert not _over(nums, cell.limits)
    # the untraced readers read the same record; no trace, no device time
    layer = result_object(cell, record, trace=True)["metrics"]
    assert 0.0 < layer["latent_pool_live"]["value"] <= 100.0
    # 8 of the router's 24 outputs are identity experts: a third of the
    # seeded router's choices
    assert 20.0 < layer["zero_expert_share"]["value"] < 45.0
    assert not (NEW - {"zero_expert_share"}) & set(layer)
    r0, r1 = record["report0"], record["report1"]
    # a row a PUBLISHED layer (2), a column a HELD expert (4 of the 16)
    assert [len(row) for row in r1["expert_tokens"]] == [4, 4]
    made = r1["routed_assignments"] - r0["routed_assignments"]
    assert made > 0 and made % (5 * 2) == 0        # top-5, two layers
    assert 0 < r1["routed_local"] < r1["routed_assignments"]
    assert r1["evicted"] == 0 and r1["kv_row_bytes"] == 128 * 4   # float32
    # the pages hold rows of all FOUR sublayers
    assert r1["latent_rows_capacity"] == 192 * 4


def test_the_new_readers_read_nothing_without_a_trace_or_their_counters(
        cell, monkeypatch):
    """An untraced run, and a traced one of a program whose table has no
    ``attn.latent`` / ``moe.*`` scope and whose report has no latent or
    identity counters (another program under these files): every new reader
    returns None and does not raise."""
    record = cell.kind.run(cell, 7, 0.3, _env())
    readers = {m.name: m.reader for m in cell.per_layer if m.name in NEW}
    assert set(readers) == NEW
    for name, read in readers.items():
        if name != "zero_expert_share":
            assert read(record) is None, name
    record["trace"] = {"modules": {}}
    monkeypatch.setitem(pt._TABLES, "table",
                        {"spans": {pt.STEP_SPAN: {"count": 10.0}},
                         "scopes": {"attn.decode": 0.1}})
    for r in (record["report0"], record["report1"]):
        for key in ("latent_rows_live", "latent_rows_capacity",
                    "zero_assignments"):
            r.pop(key)
    for name, read in readers.items():
        assert read(record) is None, name


def test_the_new_readers_on_recorded_events(monkeypatch):
    """The v5e events recorded under ``testdata``: their table has its
    ``batch.step`` spans; the latent and expert scopes' seconds are put
    beside them by hand. The three shares are bytes (or operations) at the
    chip's peak over a time, by hand; the counter's share a ratio of two
    deltas."""
    with open(os.path.join(TD, "program_events_v5e.json")) as f:
        table = pt.reduce_program(json.load(f))
    steps = pt.span_count(table, pt.STEP_SPAN)
    assert steps > 0
    table["scopes"].update({"attn.latent": 0.005 * steps,
                            "moe.route": 0.001 * steps,
                            "moe.experts": 0.007 * steps})
    monkeypatch.setitem(pt._TABLES, "table", table)
    c = _config()
    live = 0.45 * 96 * 3072
    record = {"trace": {"modules": {"jit__batched_hybrid_step_jit": {
                  "runs": 100, "seconds": 2.0}}},
              "config": c, "device_kind": "TPU v5 lite",
              "report0": {"steps": 0, "slot_util_mean": 0.0,
                          "latent_rows_live": live,
                          "routed_assignments": 1000, "zero_assignments": 300},
              "report1": {"steps": 100, "slot_util_mean": 1.0,
                          "latent_rows_live": live,
                          "latent_rows_capacity": 18432 * 16,
                          "kv_row_bytes": 1280,
                          "routed_assignments": 461_800,
                          "zero_assignments": 153_900}}

    def read(name):
        return load_module(os.path.join(HERE, "metrics", name + ".py"),
                           "longcat_" + name).read(record)

    assert read("zero_expert_share") == pytest.approx(
        100 * 153_600 / 460_800)
    # 4 x (768 x 6145 + 16 x 37,748,736) parameters x 2 B at 819 GB/s
    assert read("longcat_experts_hbm_share") == pytest.approx(
        100 * (4 * 608_699_136 * 2 / 819e9) / 8e-3)
    # 8 sublayers x the live rows x 1280 B at 819 GB/s against their
    # 2 x 64 x 1088 operations a row at 197 TFLOP/s: the bytes bind
    rows_ms = 1e3 * 8 * live * 1280 / 819e9
    assert rows_ms > 1e3 * 2 * 8 * live * 64 * 1088 / 197e12
    assert read("longcat_attn_latent_hbm_share") == pytest.approx(
        100 * rows_ms / 5.0)
    from benchmark import rooflines_longcat_flash as r
    need = r.step_bytes(c, live, 1280, 96)
    assert read("longcat_step_hbm_share") == pytest.approx(
        100 * (need / 819e9) / 20e-3)
    assert 0 < read("longcat_step_hbm_share") < 100


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


def _identity_dropped(real):
    import jax.numpy as jnp

    def moe_layer(cfg, mp, u, active=None):
        out, counts = real(dataclasses.replace(cfg, zero_experts=0), mp, u,
                           active)
        return out, jnp.concatenate([counts, jnp.zeros((1,), jnp.int32)])
    return moe_layer


def test_the_identity_part_dropped_is_not_correct(monkeypatch, cell):
    from edgellm_tpu.models import hybrid, moe

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        hybrid, "moe_layer", _identity_dropped(moe.moe_layer)))


def test_the_identity_applied_to_h_and_not_to_u0_is_not_correct(monkeypatch,
                                                                cell):
    """``E_e = identity`` of the residual stream the sublayer read, not of
    its normalised input."""
    import jax.numpy as jnp

    from edgellm_tpu.models import hybrid, moe

    real = hybrid._shortcut

    def shortcut(cfg, mp, h, g, term, counts=None, active=None):
        g, term, counts = real(cfg, mp, h, g, term, counts, active)
        if "shortcut" in mp:
            u = hybrid._rms(cfg, h, mp["ln2_scale"])
            sc = mp["shortcut"]
            idx, w = moe.route(cfg, sc["router"], u.reshape(-1, u.shape[-1]),
                               sc["router_bias"])
            share = jnp.sum(jnp.where(idx >= cfg.num_experts, w, 0.0), -1)
            term = (term + (h - u) * share.reshape(h.shape[:-1] + (1,))
                    ).astype(term.dtype)
        return g, term, counts

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        hybrid, "_shortcut", shortcut))


def test_the_shortcut_joined_before_the_second_attention_is_not_correct(
        monkeypatch, cell):
    from edgellm_tpu.models import hybrid

    real = hybrid._shortcut

    def shortcut(cfg, mp, h, g, term, counts=None, active=None):
        g, term, counts = real(cfg, mp, h, g, term, counts, active)
        return (g, None, counts) if term is None else (g + term, 0.0 * term,
                                                       counts)

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        hybrid, "_shortcut", shortcut))


def _route(monkeypatch, change):
    from edgellm_tpu.models import moe

    real = moe.route
    return lambda: monkeypatch.setattr(
        moe, "route", lambda cfg, router_w, u, bias=None: change(
            cfg, bias, *real(cfg, router_w, u, bias)))


def test_weights_renormalised_over_the_chosen_are_not_correct(monkeypatch,
                                                              cell):
    import jax.numpy as jnp

    _broken(monkeypatch, cell, 5, _route(
        monkeypatch, lambda cfg, bias, idx, w: (
            idx, w / jnp.sum(w, -1, keepdims=True) * cfg.route_scale)))


def test_the_bias_let_into_the_weights_is_not_correct(monkeypatch, cell):
    _broken(monkeypatch, cell, 5, _route(
        monkeypatch, lambda cfg, bias, idx, w: (
            idx, w + cfg.route_scale * bias[idx])))


def test_the_selection_bias_dropped_is_not_correct(monkeypatch, cell):
    def zero_bias(w):
        return {**w, "moe": [
            {**mp, "shortcut": {**mp["shortcut"], "router_bias":
                                0 * mp["shortcut"]["router_bias"]}}
            if "shortcut" in mp else mp for mp in w["moe"]]}

    arch = cell.kind.architecture(cell.config)
    real = arch.build_batcher

    def patch():
        monkeypatch.setattr(arch, "build_batcher", lambda config, weights:
                            real(config, zero_bias(weights)))
        monkeypatch.setattr(cell.kind, "architecture", lambda config: arch)

    _broken(monkeypatch, cell, 5, patch)


def test_the_latent_cached_without_its_rank_scale_is_not_correct(monkeypatch,
                                                                 cell):
    import jax.numpy as jnp

    from edgellm_tpu.models import mla

    real = mla.project

    def project(cfg, lp, x, rotate, scale):
        q_nope, q_rope, row = real(cfg, lp, x, rotate, scale)
        return q_nope, q_rope, row.at[..., :cfg.kv_lora_rank].divide(
            jnp.asarray(cfg.kv_rank_scale, row.dtype))

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        mla, "project", project))


def test_the_held_share_offset_by_one_expert_is_not_correct(monkeypatch,
                                                            cell):
    arch = cell.kind.architecture(cell.config)
    real = arch.model_config

    def patch():
        monkeypatch.setattr(arch, "model_config", lambda config: (
            lambda c: dataclasses.replace(
                c, expert_offset=c.expert_offset + 1))(real(config)))
        monkeypatch.setattr(cell.kind, "architecture", lambda config: arch)

    _broken(monkeypatch, cell, 5, patch)


def test_the_reference_is_literal_and_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_longcat_flash.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]
    assert "edgellm_tpu" not in code
    assert 'default_matmul_precision("highest")' in code
    assert "absorb" not in code                  # the EXPANDED form only
    # the identity experts are written as such: no weights, no multiplies
    assert "E_e(u) = u" in code and "h + s" in code


def test_the_parent_fails_the_cell_before_any_weight():
    """``make_weights`` asks the program for the family first: a program
    without it raises ``unsupported model_type`` at once."""
    import types

    from edgellm_tpu.models import hf_loader

    arch = load_module(os.path.join(HERE, "architectures",
                                    "longcat_flash.py"), "arch_longcat")
    c = _config()
    assert arch.model_config(c).experts_held == 16
    with pytest.raises(ValueError, match="unsupported model_type: "
                                         "longcat_flash_next"):
        arch.make_weights({**c, "model_type": "longcat_flash_next"}, 1)
    cfg = hf_loader.config_from_hf(types.SimpleNamespace(**c))
    assert (cfg.num_experts, cfg.zero_experts, cfg.sublayers) == (16, 256, 2)
    plan = arch.weight_plan(c)
    assert [p[0] for p in plan[:2]] == [("embed",), ("lm_head",)]
    total = sum(_size(shape) for _, shape, _ in plan)
    from benchmark import rooflines_longcat_flash as r
    assert total == r.param_count(c)


def _size(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def test_rooflines_by_hand():
    from benchmark import rooflines_longcat_flash as r

    c = _config()
    # q_a 6144 x 1536 + its norm, q_b 1536 x 64 x 192, kv_a 6144 x 576 + the
    # latent's norm, kv_b 512 x 64 x 256, o 8192 x 6144
    attn = (9_437_184 + 1536 + 18_874_368 + 3_538_944 + 512 + 8_388_608
            + 50_331_648)
    assert r.attention_sublayer_params(c) == attn == 90_572_800
    assert r.dense_ffn_params(c) == 3 * 6144 * 12288 == 226_492_416
    # router 6144 x 768 + its 768 biases, 16 held experts of 3 x 6144 x 2048
    routed = 4_718_592 + 768 + 16 * 37_748_736
    assert r.routed_params(c) == routed == 608_699_136
    # the issue's count: 638,874,368 outside the experts a published layer
    assert 2 * attn + 2 * 226_492_416 + 4_719_360 + 24_576 == 638_874_368
    assert r.layer_params(c) == 638_874_368 + 16 * 37_748_736 \
        == 1_242_854_144
    assert r.param_count(c) == (4 * 1_242_854_144 + 2 * 16384 * 6144
                                + 6144) == 5_172_749_312
    assert round(2 * r.param_count(c) / 1e9, 2) == 10.35
    # whole: 28 layers of 512 experts and the 131072-row table and head
    whole = {**c, "num_layers": 28, "n_routed_experts": 512,
             "vocab_size": 131072}
    assert round(r.param_count(whole) / 1e9, 2) == 560.66
    # 8 sublayers read every live row at its stored 1280 B
    assert r.latent_rows_bytes(c, 1000, 1280) == 8 * 1000 * 1280
    # a head scores 512 + 64 lanes and sums 512, 2 operations a product
    assert r.latent_attend_flops(c, 1000) == 2 * 8 * 1000 * 64 * 1088
    assert r.experts_step_bytes(c) == 4 * 608_699_136 * 2
    assert round(r.experts_step_bytes(c) / 1e9, 2) == 4.87
    need = r.step_bytes(c, 130_000, 1280, 96)
    assert need == (2 * (5_172_749_312 - 16384 * 6144 + 96 * 6144)
                    + 8 * 130_000 * 1280 + 96 * 8 * 1280)
    assert 13.9e-3 < need / 819e9 < 14.1e-3      # the step's floor on a v5e
    record = {"config": c, "device_kind": "TPU v5 lite"}
    assert r.attend_floor_ms(record, 130_000, 1280) == pytest.approx(
        1e3 * 8 * 130_000 * 1280 / 819e9)
    # the deployment's memory, as the configuration's note counts it
    s = c["serving"]
    assert s["num_pages"] == s["max_slots"] * s["pages_per_slot"] + 1 == 18433
    pool = s["num_pages"] * s["page_size"] * 8 * 1280
    assert round(pool / 1e9, 2) == 3.02
    held = 2 * r.param_count(c) + pool
    assert round(held / 1e9, 2) == 13.37 and held / 16e9 > 0.83


def test_the_cell_and_its_files_keep_to_the_issue():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    w = [w for w in spec["workloads"] if w["name"] == CELL][0]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "longcat-flash-chat-ep32", "decode-sat-reason", 1)
    assert len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    entry = [e for e in spec["configs"]
             if e["name"] == "longcat-flash-chat-ep32"][0]
    assert len(entry["why"]) <= 200
    with open(os.path.join(HERE, "traffic", "decode-sat-reason.json")) as f:
        t = json.load(f)
    assert t == {"kind": "closed_loop_arch", "callers": "max_slots",
                 "prompt": {"values": [256, 1024]},
                 "answer": {"values": [1024, 2048]},
                 "temperature": {"values": [0.0, 0.7]}}
    c = _config()
    assert entry["source"] == c["source"]
    assert c["reduced"] == entry["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    assert c["published"] == {"num_layers": 28, "n_routed_experts": 512,
                              "vocab_size": 131072}
    assert (c["num_layers"], c["n_routed_experts"], c["vocab_size"]) == (
        4, 16, 16384)
    # every number of the catalog row's config that is not reduced stands
    catalog = {
        "attention_bias": False, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "routed_scaling_factor": 6, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    assert {k: c[k] for k in catalog} == catalog
    # the harness's keys, repeated beside the published names
    assert (c["num_hidden_layers"], c["intermediate_size"],
            c["num_key_value_heads"], c["tie_word_embeddings"]) == (
        c["num_layers"], c["ffn_hidden_size"], c["num_attention_heads"],
        False)
    assert c["share"]["router_experts"] == 512 \
        and c["share"]["experts_held"] == 16
    assert max(t["prompt"]["values"]) + max(t["answer"]["values"]) == \
        c["serving"]["pages_per_slot"] * c["serving"]["page_size"] == 3072
    reported = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [])}
    assert NEW | JOINED <= reported
    assert not {"attn_latent_hbm_share", "mistral4_step_hbm_share",
                "moe_experts_hbm_share", "expert_load_skew",
                "routed_local_share", "slot_util", "pool_live",
                "evictions"} & reported
    assert {m["name"] for m in spec["per_layer"]
            if m.get("workloads") == [CELL]} == NEW
    e2e = {m["name"] for m in spec["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"gap_mean_ms", "setup_s"}
