"""The harness's own functions driven on the CPU at a toy size, by calling
them: the command itself runs on a TPU or not at all. Also the two tests the
comparison that decides ``correct`` has to pass: the lower-precision control
comes out as not correct, and so does a run whose timed path is broken."""
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import reference, serving_run
from benchmark.cell import HERE, load_cell
from benchmark.run import result_object
from benchmark.weights import make_weights

TD = os.path.join(HERE, "testdata")
#: the toy cells stand in for the real ones and report what those report
TOY = {"qwen2-0.5b.chat-steady": ("tiny.chat", "tiny", "chat"),
       "qwen2-0.5b.decode-sat": ("tiny.sat", "tiny", "sat"),
       "qwen2-1.5b-split4.decode-sat": ("tiny-split4.sat", "tiny-split4",
                                        "sat")}


@pytest.fixture(scope="module")
def toy_json(tmp_path_factory):
    """The real ``BENCHMARK.json`` with each cell replaced by its toy."""
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        w["name"], w["config"], w["traffic"] = TOY[w["name"]]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TOY[c][0] for c in m["workloads"]]
    path = tmp_path_factory.mktemp("toy") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return str(path)


@pytest.fixture
def _cell(toy_json):
    return lambda name: load_cell(name, bench_json=toy_json, data_dir=TD)


def _env(**kw):
    return {"t_start": time.monotonic(), "trace": False, "trace_dir": None,
            "control": False, "dump": lambda name, obj: None, **kw}


@pytest.mark.parametrize("name,e2e", [
    ("tiny.chat", {"ttft_mean_ms", "gap_mean_ms", "setup_s"}),
    ("tiny.sat", {"gap_mean_ms", "out_tok_s", "setup_s"}),
    ("tiny-split4.sat", {"gap_mean_ms", "out_tok_s", "setup_s"}),
])
def test_a_run_of_each_kind_builds_the_result_object(name, e2e, _cell):
    cell = _cell(name)
    record = cell.kind.run(cell, 2**31 + 11, 1.0, _env())
    line = result_object(cell, record, trace=False)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == e2e
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"  # named, whatever it is
    assert record["compiles_in_window"] == 0
    assert record["window_s"] > 0.9
    if name == "tiny.chat":
        n = round(cell.traffic["rate"] * 1.0)
        assert line["attempted"] == n
        assert sum(r.counted for r in record["requests"]) == n
        # the streams set-up put in flight are in the record: their tokens
        # count in the window's gaps like any other stream's
        assert len(record["preload"]) == cell.traffic["inflight_at_open"]
        assert any(0 < t for r in record["preload"] for t in r.stamps)
    else:
        assert line["attempted"] >= cell.config["serving"]["max_slots"]
    # the per-layer readers that need no trace read the same record
    layer = result_object(cell, record, trace=True)["metrics"]
    assert "step_host_ms" in layer and "device_idle" not in layer
    if name == "tiny-split4.sat":
        assert layer["wire_bytes_step"]["value"] > 0


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch,
                                                             _cell):
    """The rest of a run with the timed path broken underneath: every step
    hands back a wrong token for one running stream."""
    from edgellm_tpu.serve.batching import ContinuousBatcher

    real = ContinuousBatcher.step

    def broken(self):
        out = real(self)
        for st in self._running():
            if st.temperature == 0.0 and len(st.tokens) > 1:
                st.tokens[-1] = (st.tokens[-1] + 1) % self.cfg.vocab_size
                break
        return out

    monkeypatch.setattr(ContinuousBatcher, "step", broken)
    cell = _cell("tiny.sat")
    record = cell.kind.run(cell, 5, 1.0, _env())
    assert record["correct"] is False
    assert record["numbers"]["gap_max"] > cell.limits["gap_max"]
    assert result_object(cell, record, False)["correct"] is False


def test_a_step_that_leaves_out_part_of_the_batch_is_not_correct(monkeypatch,
                                                                 _cell):
    """A finished request with fewer tokens than it asked for fails the exact
    comparison, whatever its logits say."""
    from edgellm_tpu.serve.batching import ContinuousBatcher

    real = ContinuousBatcher._finish

    def short(self, st):
        st.tokens = st.tokens[:-1] or st.tokens
        return real(self, st)

    monkeypatch.setattr(ContinuousBatcher, "_finish", short)
    cell = _cell("tiny.sat")
    record = cell.kind.run(cell, 6, 1.0, _env())
    assert record["numbers"]["tokens_missing"] > 0
    assert record["correct"] is False


TOY_MODEL = {"hidden_size": 128, "intermediate_size": 256,
             "num_hidden_layers": 6, "num_attention_heads": 4,
             "num_key_value_heads": 2, "vocab_size": 8192,
             "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
             "rope_theta": 1e6, "tie_word_embeddings": True}


@pytest.mark.parametrize("hops", [(), ((1, "int8_per_token"),
                                       (3, "int4_per_token"),
                                       (4, "int8_per_token"))])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_comes_out_as_not_correct(seed, hops, _cell):
    """The reference in the program's place, one precision below the stated
    bfloat16: it has to fail a limit, where the float32 reference's own
    tokens pass. (On the chip, at the cells' sizes: PERF.md section 2.)"""
    import jax.numpy as jnp

    limits = _cell("tiny.sat").limits
    w = make_weights(TOY_MODEL, seed, "bfloat16")
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(1, 8192, size=448).astype(np.int32))
    key = reference.model_key(TOY_MODEL)
    anything = jnp.zeros((384,), jnp.int32)
    _, first = reference.logit_gaps(key, w, ids, 63, anything, hops=hops,
                                    with_control=True)
    control = np.asarray(first)
    numbers = {"gap_max": float(control.max()),
               "gap_mean": float(control.mean()), "tokens_missing": 0.0}
    assert serving_run.judge(numbers, limits) is False
    assert numbers["gap_max"] > 2 * limits["gap_max"]
    assert numbers["gap_mean"] > 2 * limits["gap_mean"]
    sound = {"gap_max": 0.0, "gap_mean": 0.0, "tokens_missing": 0.0}
    assert serving_run.judge(sound, limits) is True


def test_reference_applies_the_hop_codecs_round_trip():
    import jax.numpy as jnp

    h = jnp.asarray(np.random.default_rng(0).normal(size=(5, 64)),
                    jnp.float32)
    q8 = np.asarray(reference.HOP_CODECS["int8_per_token"](h))
    q4 = np.asarray(reference.HOP_CODECS["int4_per_token"](h))
    span = np.asarray(h.max(-1) - h.min(-1))
    assert np.abs(q8 - np.asarray(h)).max() <= (span / 255 * 0.51).max()
    amax = np.abs(np.asarray(h)).max(-1, keepdims=True)
    codes = q4 / amax * 7
    assert np.allclose(codes, np.round(codes), atol=1e-4)
    assert codes.min() >= -8 and codes.max() <= 7
    # and matches the program's own codecs on the same input
    from edgellm_tpu.codecs.packing import get_wire_codec

    for name, ours in (("int8_per_token", q8), ("int4_per_token", q4)):
        c = get_wire_codec(name)
        assert np.allclose(np.asarray(c.decode(c.encode(h[None])))[0], ours,
                           atol=1e-6)


def test_adding_a_cell_needs_new_files_and_one_entry_only(tmp_path):
    """A copy of the benchmark under a temporary directory gains a
    configuration, a mix, a per-layer metric and a cell: four new files and
    entries in ``BENCHMARK.json``, and no file that was there is touched."""
    bench = tmp_path / "benchmark"
    shutil.copytree(HERE, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.load(open(bench / "testdata" / "configs" / "tiny.json"))
    cfg["num_hidden_layers"] = 2
    (bench / "configs" / "tiny2.json").write_text(json.dumps(cfg))
    mix = json.load(open(bench / "testdata" / "traffic" / "sat.json"))
    mix["answer"]["values"] = [4]
    (bench / "traffic" / "short.json").write_text(json.dumps(mix))
    (bench / "limits" / "tiny2.short.json").write_text(json.dumps(
        {"gap_max": 0.02, "gap_mean": 0.001, "tokens_missing": 0}))
    (bench / "metrics" / "steps.short.py").write_text(
        '"""Steps in the window."""\n\n\ndef read(record):\n'
        '    return float(record["report1"]["steps"]'
        ' - record["report0"]["steps"])\n')
    spec = json.load(open(os.path.join(os.path.dirname(HERE),
                                       "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny2", "source": "none", "reduced": [],
                            "file": "benchmark/configs/tiny2.json",
                            "why": "toy"})
    spec["workloads"].append({"name": "tiny2.short", "config": "tiny2",
                              "traffic": "short", "chips": 1, "why": "toy"})
    for m in spec["end_to_end"]:
        if m["name"] in ("gap_mean_ms", "out_tok_s"):
            m["workloads"].append("tiny2.short")
    spec["per_layer"].append({"name": "steps.short", "unit": "count",
                              "better": "higher", "source": "program_counter",
                              "layer": "batcher", "moves": "out_tok_s",
                              "workloads": ["tiny2.short"]})
    bj = tmp_path / "BENCHMARK.json"
    bj.write_text(json.dumps(spec))
    cell = load_cell("tiny2.short", bench_json=str(bj), bench_dir=str(bench))
    assert [m.name for m in cell.per_layer] == ["steps.short"]
    assert {m.name for m in cell.end_to_end} == {"gap_mean_ms", "out_tok_s",
                                                 "setup_s"}
    record = cell.kind.run(cell, 3, 0.5, _env())
    line = result_object(cell, record, trace=True)
    assert line["correct"] and line["metrics"]["steps.short"]["value"] > 0
    after = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()
             and "__pycache__" not in p.parts}
    assert all(after[p] == b for p, b in before.items())
    assert len(after) == len(before) + 4
