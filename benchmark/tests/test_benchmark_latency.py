"""TTFT and gap arithmetic on a hand-made stamp list."""
import numpy as np

from benchmark import latency
from benchmark.cell import HERE, load_module
from benchmark.serving import Request
import os


def _req(due, stamps, counted=True, submit=None):
    r = Request(0, np.zeros(4, np.int32), len(stamps), 0.0, 0, due,
                counted=counted)
    r.stamps, r.submit_t = list(stamps), submit if submit is not None else due
    return r


def _record():
    reqs = [
        _req(0.10, [0.50, 0.50, 1.00, 1.50], submit=0.30),  # ttft 400
        _req(1.20, [2.00, 2.00, 2.50], submit=1.50),        # ttft 800
        _req(-0.50, [0.50, 1.00], counted=False),           # lead-in
        _req(2.80, [3.40, 3.40], submit=3.00),  # first token after the close
    ]
    pre = [_req(-1e9, [-2.0, 0.50, 1.00, 3.40], counted=False)]
    return {"requests": reqs, "preload": pre, "t0": 0.0, "t1": 3.0,
            "window_s": 3.0}


def test_ttft_is_from_due_time_over_requests_due_in_the_window():
    ttft = latency.ttfts_ms(_record())
    assert np.allclose(ttft, [400.0, 800.0, 600.0])
    assert np.isclose(latency.mean(ttft), 600.0)


def test_gaps_cover_every_token_stamped_in_the_window_with_a_predecessor():
    gaps = sorted(latency.gaps_ms(_record()))
    # r0: 0, 500, 500; r1: 0, 500; lead-in: 500; r3: stamps past t1;
    # preload: 2500 (from set-up's token), 500; its 3.40 is past t1
    assert np.allclose(gaps, [0, 0, 500, 500, 500, 500, 500, 2500])


def test_tokens_in_window_counts_stamps_in_the_half_open_window():
    # r0 4, r1 3, lead-in 2, r3 0, preload 2
    assert latency.tokens_in_window(_record()) == 11


def test_generator_lateness_and_percentiles():
    late = latency.late_ms(_record())
    assert np.allclose(late, [200.0, 300.0, 200.0])
    assert latency.pct([1, 2, 3, 4, 5], 50) == 3.0
    assert latency.mean([]) is None and latency.pct([], 90) is None


def test_metric_readers_read_the_record():
    rec = _record()
    rec.update(setup_s=12.5, report0={"prefill_s": 1.0, "decode_s": 2.0,
                                      "steps": 10, "evicted": 0,
                                      "slot_util_mean": 0.5},
               report1={"prefill_s": 1.3, "decode_s": 4.0, "steps": 15,
                        "evicted": 2, "slot_util_mean": 0.6},
               pool_live_share=0.4)

    def read(name):
        return load_module(os.path.join(HERE, "metrics", name + ".py"),
                           "m_" + name.replace(".", "_")).read(rec)

    assert np.isclose(read("ttft_mean_ms"), 600.0)
    assert np.isclose(read("out_tok_s"), 11 / 3.0)
    assert np.isclose(read("prefill_share"), 10.0)
    assert np.isclose(read("step_host_ms"), 400.0)
    assert np.isclose(read("slot_util"), 100 * (0.6 * 15 - 0.5 * 10) / 5)
    assert read("evictions") == 2.0 and read("setup_s") == 12.5
    assert np.isclose(read("pool_live"), 40.0)
    assert read("device_idle") is None and read("step_dev_ms") is None
