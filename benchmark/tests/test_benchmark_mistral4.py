"""The ``mistral4`` architecture hooks under ``closed_loop_arch``, driven on
the CPU at a toy size (``testdata/toys.d/``): a run comes out ``correct`` and
its record has every key ``serving_run.run``'s has; it does not when the
absorbed scores lose their rope term, when a row is not written at a page's
first position (a stale row read after a page boundary), when the held share
is offset by one expert, or under the float8 control."""
import dataclasses
import json
import os
import time

import pytest

from benchmark.cell import HERE, load_cell
from benchmark.run import result_object

TD = os.path.join(HERE, "testdata")
CELL = "mistral-small-4-119b-ep4.decode-sat-deep"
with open(os.path.join(TD, "toys.d", CELL + ".json")) as f:
    TOY = {cell: tuple(toy) for cell, toy in json.load(f).items()}
NAME = TOY[CELL][0]
PLAIN = "qwen2-0.5b.decode-sat"
NEW = {"attn_latent_dev_ms", "attn_latent_hbm_share",
       "mistral4_step_hbm_share", "latent_pool_live"}


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
    path = tmp_path_factory.mktemp("toy_mistral4") / "BENCHMARK.json"
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
    # as the mellum cell: out_tok_s is 95 slots / gap_mean_ms here, the same
    # measurement under a bound five times tighter (PERF.md section 2)
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
    assert 0.0 < layer["latent_pool_live"]["value"] <= 100.0
    r1 = record["report1"]
    # 4 of the router's 8 experts are held: some assignments, not all
    assert 0 < r1["routed_local"] < r1["routed_assignments"]
    assert r1["evicted"] == 0
    for name in NEW - {"latent_pool_live"}:     # no trace, no device time
        assert name not in layer
    assert r1["latent_rows_capacity"] == 128 * 4
    assert r1["kv_row_bytes"] == 128 * 2      # 32 + 16 lanes -> one tile


def test_the_new_readers_read_nothing_from_a_program_without_the_counters(
        cell):
    """The parent's record: no ``latent_rows_*`` / ``kv_row_bytes`` in
    ``report()``, no ``attn.latent`` scope. Every new reader returns None
    and does not raise."""
    record = cell.kind.run(cell, 7, 0.3, _env())
    for r in (record["report0"], record["report1"]):
        for key in ("latent_rows_live", "latent_rows_capacity",
                    "kv_row_bytes"):
            r.pop(key)
    for m in cell.per_layer:
        if m.name in NEW:
            assert m.reader(record) is None, m.name


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


def test_absorbed_scores_without_the_rope_term_are_not_correct(monkeypatch,
                                                               cell):
    import jax.numpy as jnp

    from edgellm_tpu.models import mla

    real = mla.absorb_query
    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        mla, "absorb_query", lambda cfg, lp, q_nope, q_rope:
        real(cfg, lp, q_nope, jnp.zeros_like(q_rope))))


def test_a_stale_row_read_after_a_page_boundary_is_not_correct(monkeypatch,
                                                               cell):
    import jax.numpy as jnp

    from edgellm_tpu.models import paged_kv

    real = paged_kv.write_rows

    def write(pool, layer, table, lengths, k, v, ring=False):
        # a page's first row goes to the trash page: what is read there
        # later is what the page held before
        keep = (lengths % pool.page_size != 0)[:, None]
        return real(pool, layer, jnp.where(keep, table, 0), lengths, k, v,
                    ring)

    _broken(monkeypatch, cell, 5, lambda: monkeypatch.setattr(
        paged_kv, "write_rows", write))


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


def test_the_reference_is_expanded_and_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_mistral4.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]
    assert "edgellm_tpu" not in code
    assert 'default_matmul_precision("highest")' in code
    assert "absorb" not in code      # keys and values per head, everywhere


def test_rooflines_by_hand():
    from benchmark import rooflines_mistral4 as r

    with open(os.path.join(HERE, "configs",
                           "mistral-small-4-119b-ep4.json")) as f:
        c = json.load(f)
    # q_a 4096 x 1024, q_b 1024 x 4096, kv_a 4096 x 320, kv_b 256 x 6144,
    # o 4096 x 4096, the two latent norms 1024 + 256, the input norm
    attn = (4_194_304 + 4_194_304 + 1_310_720 + 1_572_864 + 16_777_216
            + 1280 + 4096)
    assert r.attention_layer_params(c) == attn == 28_054_784
    # router 4096 x 128, 32 held experts and the shared one of 3 x 4096 x
    # 2048, the input norm
    moe = 524_288 + 32 * 25_165_824 + 25_165_824 + 4096
    assert r.moe_layer_params(c) == moe == 831_000_576
    # the issue's count: 53,748,992 outside the routed experts a layer
    assert attn + moe - 32 * 25_165_824 == 53_748_992
    assert r.param_count(c) == (4 * 859_055_360 + 2 * 134_217_728
                                + 4096) == 3_704_660_992
    # 4 layers read every live row at its stored 768 B
    assert r.latent_rows_bytes(c, 1000, 768) == 4 * 1000 * 768
    # a head scores 256 + 64 lanes and sums 256, 2 operations a product
    assert r.latent_attend_flops(c, 1000) == 2 * 4 * 1000 * 32 * 576
    need = r.step_bytes(c, 600_000, 768, 96)
    assert need == (2 * (3_704_660_992 - 134_217_728 + 96 * 4096)
                    + 4 * 600_000 * 768 + 96 * 4 * 768)
    assert 10.9e-3 < need / 819e9 < 11.1e-3      # the step's floor on a v5e
    record = {"config": c, "device_kind": "TPU v5 lite"}
    # bytes bind: 768 B a row at 819 GB/s against 36,864 operations at 197
    # TFLOP/s
    assert r.attend_floor_ms(record, 600_000, 768) == pytest.approx(
        1e3 * 4 * 600_000 * 768 / 819e9)
    # the deployment's memory, as the configuration's note counts it
    s = c["serving"]
    assert s["num_pages"] == s["max_slots"] * s["pages_per_slot"] + 1
    pool = s["num_pages"] * s["page_size"] * 4 * 768
    assert round(pool / 1e9, 2) == 3.62
    assert round(2 * r.param_count(c) / 1e9, 2) == 7.41


def test_the_cell_and_its_files_keep_to_the_issue():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    w = [w for w in spec["workloads"] if w["name"] == CELL][0]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "mistral-small-4-119b-ep4", "decode-sat-deep", 1)
    assert len(spec["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    with open(os.path.join(HERE, "traffic", "decode-sat-deep.json")) as f:
        t = json.load(f)
    assert t == {"kind": "closed_loop_arch", "callers": "max_slots",
                 "prompt": {"values": [2048, 8192]},
                 "answer": {"values": [2048, 4096]},
                 "temperature": {"values": [0.0, 0.7]}}
    with open(os.path.join(HERE, "configs",
                           "mistral-small-4-119b-ep4.json")) as f:
        c = json.load(f)
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 36,
                              "n_routed_experts": 128, "vocab_size": 131072}
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (4, 32, 32768)
    assert (c["share"]["router_experts"], c["num_experts_per_tok"]) == (
        128, 4)
    assert (c["hidden_size"], c["q_lora_rank"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["moe_intermediate_size"], c["num_attention_heads"]) == (
        4096, 1024, 256, 64, 64, 128, 2048, 32)
    # every number of the catalog row's config that is not reduced stands
    assert c["rope_parameters"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}
    assert max(t["prompt"]["values"]) + max(t["answer"]["values"]) == \
        c["serving"]["pages_per_slot"] * c["serving"]["page_size"]
    reported = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [])}
    assert NEW <= reported and "moe_experts_dev_ms" in reported
    assert "attn_decode_dev_ms" not in reported
    e2e = {m["name"] for m in spec["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"gap_mean_ms", "setup_s"}
