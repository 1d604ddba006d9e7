"""``trace_reduce`` on hand-made events and on the events recorded on a v5e
that are kept under ``benchmark/testdata/``."""
import json
import os

import numpy as np
import pytest

from benchmark import trace_reduce as tr
from benchmark.cell import HERE

MS = 1_000_000


def _events():
    ops = [["while.1 s32[] while", 10 * MS, 60 * MS],      # encloses two ops
           ["fusion.1 bf16[8] fusion", 10 * MS, 20 * MS],
           ["copy.2 bf16[8] copy", 40 * MS, 30 * MS],
           ["fusion.1 bf16[8] fusion", 100 * MS, 20 * MS],
           ["fusion.9 f32[2] fusion", 190 * MS, 30 * MS]]  # runs past the window
    modules = [["jit_step(1)", 10 * MS, 60 * MS], ["jit_step(1)", 100 * MS, 20 * MS],
               ["jit_other(2)", 190 * MS, 30 * MS]]
    host = [["bench.window", 0, 200 * MS], ["bench.step", 5 * MS, 85 * MS],
            ["bench.submit", 92 * MS, 4 * MS], ["bench.step", 98 * MS, 30 * MS]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_busy_union_idle_share_and_self_times():
    r = tr.reduce_events(_events())
    assert np.isclose(r["window_s"], 0.200) and r["chips"] == 1
    # busy: [10,70) + [100,120) + [190,200) clipped = 90 ms
    assert np.isclose(r["busy_s"], 0.090)
    ops = r["ops"]
    assert np.isclose(ops["while.1 s32[] while"], 0.010)   # 60 - 20 - 30
    assert np.isclose(ops["fusion.1 bf16[8] fusion"], 0.040)
    assert np.isclose(ops["copy.2 bf16[8] copy"], 0.030)
    assert np.isclose(ops["fusion.9 f32[2] fusion"], 0.010)  # clipped
    assert np.isclose(sum(ops.values()), r["busy_s"])
    assert r["modules"]["jit_step(1)"] == {"runs": 2.0, "seconds": 0.080}
    assert np.isclose(r["modules"]["jit_other(2)"]["seconds"], 0.010)


def test_idle_gaps_go_to_the_span_that_was_open():
    g = tr.reduce_events(_events())["idle_gaps"]
    # idle: [0,10) [70,100) [120,190); step spans [5,90) and [98,128)
    assert np.isclose(g["inside_bench.step"], 0.005 + 0.020 + 0.002 + 0.008)
    assert np.isclose(g["inside_bench.submit"], 0.004)
    assert np.isclose(g["between_spans"], 0.110 - 0.035 - 0.004)
    b = tr.breakdown(tr.reduce_events(_events()))
    assert b["device_ops"][0] == ["fusion.1 bf16[8] fusion", pytest.approx(0.04)]
    assert b["idle_gaps"][0][0] == "between_spans"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_means_are_over_the_device_planes():
    ev = _events()
    ev["devices"]["/device:TPU:1"] = {"ops": [["fusion.1 bf16[8] fusion", 0,
                                               30 * MS]], "modules": []}
    r = tr.reduce_events(ev)
    assert r["chips"] == 2 and np.isclose(r["busy_s"], (0.090 + 0.030) / 2)
    assert np.isclose(r["ops"]["fusion.1 bf16[8] fusion"], (0.040 + 0.030) / 2)


def test_a_trace_without_window_or_device_is_an_error():
    ev = _events()
    with pytest.raises(ValueError):
        tr.reduce_events({"devices": ev["devices"], "host": ev["host"][1:]})
    with pytest.raises(ValueError):
        tr.reduce_events({"devices": {}, "host": ev["host"]})


def test_short_name_keeps_name_shape_and_opcode():
    hlo = ("%fusion.222 = bf16[393216,2,64]{2,1,0:T(2,128)(2,1)} fusion(bf16"
           "[393232,2,64]{2,1,0:T(2,128)(2,1)} %fusion.221), kind=kCustom")
    assert tr.short_name(hlo) == "fusion.222 bf16[393216,2,64] fusion"
    call = ('%_attn_packed.5 = bf16[1,1024,896]{2,1,0:T(8,128)(2,1)S(1)} '
            'custom-call(bf16[1,1024,896]{2,1,0} %x), custom_call_target="t"')
    assert tr.short_name(call) == "_attn_packed.5 bf16[1,1024,896] custom-call"
    assert tr.short_name("bench.step") == "bench.step"


def test_recorded_v5e_trace():
    with open(os.path.join(HERE, "testdata", "trace_events_v5e.json")) as f:
        ev = json.load(f)
    r = tr.reduce_events(ev)
    assert np.isclose(r["window_s"], 1.25) and r["chips"] == 1
    assert np.isclose(r["busy_s"], 0.584956785)
    assert np.isclose(sum(r["ops"].values()), r["busy_s"])
    assert np.isclose(sum(r["idle_gaps"].values()), 1.25 - r["busy_s"])
    step = [m for k, m in r["modules"].items() if "_batched_step_jit" in k]
    assert step == [{"runs": 2.0, "seconds": pytest.approx(0.584485619)}]
    top = tr.breakdown(r)["device_ops"]
    assert top[0][0] == "fusion.222 bf16[393216,2,64] fusion"
    assert top[0][1] == pytest.approx(0.209151389)
    # the host blocks inside step() while the device idles
    assert r["idle_gaps"]["inside_bench.step"] > 0.6
    # the trace-reading metric readers on the same reduction
    from benchmark.cell import load_module

    with open(os.path.join(HERE, "configs", "qwen2-0.5b.json")) as f:
        config = json.load(f)
    rec = {"trace": r, "config": config, "model": config,
           "pool_live_share": 0.38, "token_capacity": 393216,
           "device_kind": "TPU v5 lite"}

    def read(name):
        return load_module(os.path.join(HERE, "metrics", name + ".py"),
                           "t_" + name.replace(".", "_")).read(rec)

    assert read("step_dev_ms") == pytest.approx(292.2428095)
    assert read("device_idle") == pytest.approx(100 * (1 - 0.584956785 / 1.25))
    need = 494_032_768 * 2 + 192 * 896 * 2 + (0.38 * 393216 + 192) * 12288
    assert read("step_hbm_share") == pytest.approx(
        100 * need / 819e9 / 0.2922428095)
    assert read("prefill_pallas_share") == 0.0  # no admission in this slice
    assert read("hop_dev_ms") is None
