"""A cell, found by name: its entry in ``BENCHMARK.json`` names a configuration
and a traffic mix, and everything else about it sits in files of its own that
are looked up by those names. A later PR adds a cell by adding files and one
entry; nothing here knows any cell, configuration, mix or metric by name.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: the HF ``config.json`` keys the harness and the reference read
MODEL_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "vocab_size",
              "max_position_embeddings", "rms_norm_eps", "rope_theta",
              "tie_word_embeddings")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """A module from a file whose name may hold dots (``dispatch_ms.serve``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Metric:
    name: str
    unit: str
    kind: str            # "end_to_end" | "per_layer"
    reader: object       # read(record) -> float | None


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    kind: object         # the traffic kind's module
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)

    @property
    def model(self) -> dict:
        return {k: self.config[k] for k in MODEL_KEYS}


def _reports(metric: dict, cell_name: str, moved: set) -> bool:
    """Whether ``cell_name`` reports this metric: it is listed, or the metric
    lists no cells and (for a per-layer metric) the cell reports what it
    moves."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return "moves" not in metric or metric["moves"] in moved


def load_cell(name: str, *, bench_json: str | None = None,
              bench_dir: str = HERE, data_dir: str | None = None) -> Cell:
    """Read the cell ``name`` from ``BENCHMARK.json`` and the files it names:
    traffic kinds and metric readers under ``bench_dir``, configurations,
    mixes and limits under ``data_dir`` (the same directory unless a test
    keeps toy data apart)."""
    data_dir = data_dir or bench_dir
    spec = _load_json(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json (cells: "
                         f"{[w['name'] for w in spec['workloads']]})")
    entry = entries[0]
    config = _load_json(os.path.join(data_dir, "configs",
                                     entry["config"] + ".json"))
    traffic = _load_json(os.path.join(data_dir, "traffic",
                                      entry["traffic"] + ".json"))
    limits = _load_json(os.path.join(data_dir, "limits", name + ".json"))
    kind = load_module(os.path.join(bench_dir, "traffic_kinds",
                                    traffic["kind"] + ".py"),
                       "bench_kind_" + traffic["kind"])

    def metrics(section, moved):
        out = []
        for m in spec[section]:
            if _reports(m, name, moved):
                mod = load_module(
                    os.path.join(bench_dir, "metrics", m["name"] + ".py"),
                    "bench_metric_" + m["name"].replace(".", "_"))
                out.append(Metric(m["name"], m["unit"], section, mod.read))
        return out

    e2e = metrics("end_to_end", set())
    layer = metrics("per_layer", {m.name for m in e2e})
    return Cell(name, int(entry["chips"]), config, traffic, limits, kind,
                e2e, layer)
