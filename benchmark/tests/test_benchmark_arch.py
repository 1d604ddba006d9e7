"""The ``closed_loop_arch`` kind and the ``granitemoehybrid`` architecture
hooks, driven on the CPU at a toy size: a run comes out ``correct``, its record
has every key ``serving_run.run``'s has, the float8 control does not, and a
run whose state hand-off is broken (the prefill's recurrent state never
reaches the step) does not either."""
import json
import os
import time

import pytest

from benchmark.cell import HERE, load_cell
from benchmark.run import result_object

TD = os.path.join(HERE, "testdata")
CELL = "granite-4.0-h-small-ep2.decode-sat"
with open(os.path.join(TD, "toys.json")) as f:
    TOY = {cell: tuple(toy) for cell, toy in json.load(f).items()}
NAME = TOY[CELL][0]
PLAIN = "qwen2-0.5b.decode-sat"


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
    path = tmp_path_factory.mktemp("toy_arch") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return str(path)


@pytest.fixture
def cell(toy_json):
    return load_cell(NAME, bench_json=toy_json, data_dir=TD)


def _env(**kw):
    return {"t_start": time.monotonic(), "trace": False, "trace_dir": None,
            "control": False, "dump": lambda name, obj: None, **kw}


def test_a_run_is_correct_and_its_record_has_serving_runs_keys(cell,
                                                               toy_json):
    record = cell.kind.run(cell, 2**31 + 11, 1.0, _env(control=True))
    line = result_object(cell, record, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"gap_mean_ms", "out_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert record["compiles_in_window"] == 0
    assert line["attempted"] >= cell.config["serving"]["max_slots"]
    # every key a serving_run.run record has, as the readers expect them
    plain = load_cell("tiny.sat", bench_json=toy_json, data_dir=TD)
    other = plain.kind.run(plain, 5, 0.3, _env())
    assert set(record) == set(other)
    # the control: one precision below the stated bf16 is not correct
    nums = record["numbers"]
    assert nums["control_gap_max"] > cell.limits["gap_max"] \
        or nums["control_gap_mean"] > cell.limits["gap_mean"]
    assert nums["gap_max"] <= cell.limits["gap_max"]
    # the untraced readers of the new metrics read the same record
    layer = result_object(cell, record, trace=True)["metrics"]
    assert 40.0 <= layer["routed_local_share"]["value"] <= 60.0
    assert layer["expert_load_skew"]["value"] >= 1.0
    assert "ssm_step_dev_ms" not in layer       # no trace, no device time
    assert "hybrid_step_hbm_share" not in layer


def test_state_not_carried_from_prefill_to_the_step_is_not_correct(
        monkeypatch, cell):
    """The broken path: admission drops the recurrent state a prefill hands
    on, so every stream decodes from a zeroed state."""
    from edgellm_tpu.models.paged_kv import PagedKVCache

    real = PagedKVCache.adopt_state

    def dropped(self, slot, conv, ssm):
        return real(self, slot, 0.0, 0.0)

    monkeypatch.setattr(PagedKVCache, "adopt_state", dropped)
    record = cell.kind.run(cell, 5, 1.0, _env())
    assert record["correct"] is False
    assert record["numbers"]["tokens_missing"] == 0
    assert result_object(cell, record, False)["correct"] is False


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_granitemoehybrid.py")) as f:
        src = f.read()
    assert "edgellm_tpu" not in src.split('"""', 2)[2]


def test_rooflines_by_hand():
    from benchmark import rooflines_granitemoehybrid as r

    with open(os.path.join(HERE, "configs",
                           "granite-4.0-h-small-ep2.json")) as f:
        c = json.load(f)
    # in 4096 x (8192 z + 8448 xBC + 128 dt), out 8192 x 4096, conv, norms
    mamba = (4096 * 16768 + 8192 * 4096 + 8448 * 4 + 8448 + 3 * 128 + 8192
             + 4096)
    assert r.mamba_layer_params(c) == mamba == 102_291_072
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 4096
    assert r.attention_layer_params(c) == attn
    moe = 4096 * 72 + 36 * 3 * 4096 * 768 + 3 * 4096 * 1536 + 4096
    assert r.moe_layer_params(c) == moe
    assert r.param_count(c) == (9 * mamba + attn + 10 * moe + 50176 * 4096
                                + 4096) == 4_757_211_776
    # 128 x 64 x 128 float32 of state + 3 x 8448 of window, 9 layers
    assert r.state_bytes_per_slot(c) == 9 * 4 * (128 * 64 * 128 + 3 * 8448)
    assert r.ssm_step_bytes(c, 60) == 2 * 60 * r.state_bytes_per_slot(c)
    assert r.kv_bytes_per_token(c, 2) == 2 * 8 * 128 * 2
    need = r.hybrid_step_bytes(c, 60_000, 60)
    assert need == (2 * r.param_count(c) + r.ssm_step_bytes(c, 60)
                    + 60_000 * 4096 + 60 * 4096)
    assert 17e-3 < need / 819e9 < 18e-3      # the step's floor on a v5e
