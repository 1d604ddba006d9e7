"""``BENCHMARK.json`` against the contract's own rules, every file a cell names
is there, and the command refuses every platform but a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.cell import HERE, ROOT, load_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmark"]
    assert 1 <= spec["run_seconds"] <= 51
    cells = len(spec["workloads"])
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, cells // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_lines(spec):
    names = []
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert c["file"].startswith("benchmark/") and os.path.exists(
            os.path.join(ROOT, c["file"]))
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        names.append(w["name"])
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and 1 <= len(m["layer"]) <= 200
    metrics = spec["end_to_end"] + spec["per_layer"]
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    assert "setup_s" in [m["name"] for m in spec["end_to_end"]]


def test_every_moves_is_an_end_to_end_metric_of_every_cell_that_lists_it(spec):
    cells = [w["name"] for w in spec["workloads"]]
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", sorted(e2e[m["moves"]])):
            assert cell in cells and cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:  # setup_s, one more end-to-end, one per-layer
        assert sum(cell in v for v in e2e.values()) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in spec["per_layer"])


def test_every_cell_loads_from_its_files(spec):
    used = set()
    for w in spec["workloads"]:
        cell = load_cell(w["name"])
        used.add(w["config"])
        assert cell.chips == w["chips"] == cell.config["chips"]
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert set(cell.limits) >= {"tokens_missing"} or cell.limits
        assert all(callable(m.reader) for m in cell.end_to_end
                   + cell.per_layer)
    assert used == {c["name"] for c in spec["configs"]}


def test_configuration_files_state_their_source_and_reductions(spec):
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert cfg["torch_dtype"] == "bfloat16" and "assumed" in cfg


@pytest.mark.parametrize("cell", ["qwen2-0.5b.chat-steady",
                                  "qwen2-1.5b-split4.decode-sat"])
def test_command_refuses_a_platform_that_is_no_tpu(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="x")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs" in p.stderr and "TPU" in p.stderr
