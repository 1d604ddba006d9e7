"""bench.py end-to-end on CPU with a tiny preset: the driver-recorded artifact
must never die on a plain Python error (a NameError in the FLOPs block once
slipped past unit tests because only the TPU path ran it)."""
import json
import sys

import pytest


def test_bench_main_headline_is_final_compact_line(monkeypatch, capsys, tmp_path):
    sys.modules.pop("bench", None)
    import bench

    monkeypatch.setenv("BENCH_DETAIL_PATH", str(tmp_path / "detail.json"))
    monkeypatch.setenv("BENCH_MODEL", "tiny-qwen2")
    monkeypatch.setenv("BENCH_CHUNKS", "2")
    monkeypatch.setenv("BENCH_WINDOW_BATCH", "2")
    monkeypatch.setenv("BENCH_RELEVANCE", "0")
    monkeypatch.setenv("BENCH_MEASURE_PEAK", "0")
    bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["unit"] == "s/chunk" and line["value"] > 0
    assert line["vs_baseline"] is None  # anchor is qwen2-0.5b only
    assert line["window_batch"] == 2
    assert "mfu" not in line  # a CPU run has no device utilization
    assert "tiny-qwen2" in line["metric"]
    # the FINAL line is the compact headline (the driver's tail capture
    # truncates giant lines); verbose blocks ride the preceding detail line
    # and the sidecar. A closed key set keeps future verbose additions out.
    assert len(out[-1]) < 1024
    assert set(line) <= {
        "metric", "value", "unit", "vs_baseline", "tokens_per_s",
        "window_batch", "model_tflops_per_s", "mfu", "measured_peak_tflops",
        "mfu_vs_measured", "relevance_it_per_s", "relevance_vs_baseline"}
    detail = json.loads(out[-2])["detail"]
    assert detail["requested_window_batch"] == 2
    assert json.load(open(tmp_path / "detail.json")) == detail


def test_bench_decode_headline(monkeypatch, capsys, tmp_path):
    """BENCH_DECODE=1 flips the bench to the KV-cached decode workload with
    the same stdout contract: compact headline as the FINAL line, verbose
    decode block (incl. split hop bytes/token) on the detail line/sidecar."""
    sys.modules.pop("bench", None)
    import bench

    monkeypatch.setenv("BENCH_DETAIL_PATH", str(tmp_path / "detail.json"))
    monkeypatch.setenv("BENCH_DECODE", "1")
    monkeypatch.setenv("BENCH_MODEL", "tiny-qwen2")
    monkeypatch.setenv("BENCH_DECODE_PROMPT", "8")
    monkeypatch.setenv("BENCH_DECODE_TOKENS", "8")
    monkeypatch.setenv("BENCH_DECODE_BATCH", "2")
    monkeypatch.setenv("BENCH_DECODE_SPLIT", "1")
    monkeypatch.setenv("BENCH_DTYPE", "float32")
    monkeypatch.setenv("BENCH_REPEATS", "1")
    bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["unit"] == "decode tokens/s" and line["value"] > 0
    assert line["vs_baseline"] is None
    assert line["batch"] == 2
    assert "decode" in line["metric"]
    assert line["decode_step_cache_misses"] == 1  # compiled once, ever
    assert len(out[-1]) < 1024
    assert set(line) <= {"metric", "value", "unit", "vs_baseline",
                         "tokens_per_s", "prefill_s", "batch",
                         "decode_step_cache_misses", "ttft_s",
                         "token_latency_p50_s", "token_latency_p95_s",
                         "token_latency_p99_s"}
    # the SLO acceptance surface: TTFT + per-token p50/p95/p99 in the artifact
    assert line["ttft_s"] > 0
    assert 0 < line["token_latency_p50_s"] <= line["token_latency_p95_s"]
    assert line["token_latency_p95_s"] <= line["token_latency_p99_s"]
    detail = json.loads(out[-2])["detail"]
    dec = detail["decode"]
    assert dec["prompt"] == 8 and dec["batch"] == 2
    assert dec["split_hop_bytes_per_token"] > 0
    assert dec["obs_overhead_frac"] >= 0  # instrumented-vs-clean delta
    assert dec["slo"]["token_latency_p50_s"] > 0
    # conftest spoofs 8 CPU devices, so the split section must have run
    assert dec["split"]["tokens_per_s"] > 0
    assert dec["split"]["hop_bytes_per_token"] == [
        b / 2 for b in dec["split"]["measured_hop_bytes_per_step"]]
    # the meta provenance block is stamped centrally on every artifact
    meta = detail["meta"]
    assert meta["schema_version"] == bench.BENCH_SCHEMA_VERSION
    assert meta["jax_version"] and meta["backend"] == "cpu"
    assert json.load(open(tmp_path / "detail.json")) == detail


def test_bench_fec_headline(monkeypatch, capsys, tmp_path):
    """BENCH_FEC=1: the self-healing-link sweep with the same stdout
    contract — headline carries the repaired-vs-retried split and the
    declared parity wire overhead."""
    sys.modules.pop("bench", None)
    import bench

    monkeypatch.setenv("BENCH_DETAIL_PATH", str(tmp_path / "detail.json"))
    monkeypatch.setenv("BENCH_FEC", "1")
    monkeypatch.setenv("BENCH_MODEL", "tiny-qwen2")
    monkeypatch.setenv("BENCH_DTYPE", "float32")
    monkeypatch.setenv("BENCH_FEC_RATES", "0,0.0002")
    monkeypatch.setenv("BENCH_FAULT_CHUNKS", "2")
    monkeypatch.setenv("BENCH_MAX_LENGTH", "64")
    monkeypatch.setenv("BENCH_STRIDE", "32")
    bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["unit"] == "ppl" and line["value"] > 0
    assert line["vs_baseline"] is None
    assert "FEC" in line["metric"]
    assert line["wire_overhead"] > 0
    assert len(out[-1]) < 1024
    assert set(line) <= {
        "metric", "value", "unit", "vs_baseline", "ppl_clean", "ppl_ratio",
        "wire_overhead", "detected", "repaired", "retried", "hedge_wins",
        "substituted", "decode_tokens_per_s_clean",
        "decode_tokens_per_s_faulty"}
    detail = json.loads(out[-2])["detail"]
    fec = detail["fec"]
    assert fec["sweep"][0]["rate"] == 0  # exact fault-free baseline point
    assert fec["sweep"][0]["link_counters"] is None
    assert "repaired" in fec["sweep"][-1]["link_counters"]
    # the decode leg ran (8 spoofed devices) with all three link builds
    assert {"clean", "faulty_retry_only", "faulty_fec"} <= set(fec["decode"])
    assert json.load(open(tmp_path / "detail.json")) == detail


def test_bench_obs_headline(monkeypatch, capsys, tmp_path):
    """BENCH_OBS=1: the observability smoke arms the full obs stack, runs an
    instrumented decode, and writes the two promised artifacts — a metrics
    snapshot and a Perfetto-loadable Chrome trace — while the detail sidecar
    carries the registry snapshot via _emit's enabled-registry hook."""
    sys.modules.pop("bench", None)
    import bench
    from edgellm_tpu import obs

    metrics_path = tmp_path / "metrics.json"
    trace_path = tmp_path / "trace.json"
    monkeypatch.setenv("BENCH_DETAIL_PATH", str(tmp_path / "detail.json"))
    monkeypatch.setenv("BENCH_OBS", "1")
    monkeypatch.setenv("BENCH_MODEL", "tiny-qwen2")
    monkeypatch.setenv("BENCH_DTYPE", "float32")
    monkeypatch.setenv("BENCH_OBS_PROMPT", "8")
    monkeypatch.setenv("BENCH_OBS_TOKENS", "8")
    monkeypatch.setenv("BENCH_OBS_BATCH", "2")
    monkeypatch.setenv("BENCH_OBS_METRICS_PATH", str(metrics_path))
    monkeypatch.setenv("BENCH_OBS_TRACE_PATH", str(trace_path))
    try:
        bench.main()
    finally:
        obs.disable()  # never leak an armed registry into other tests
    assert not obs.enabled()  # obs_main's own finally already disarmed it
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["unit"] == "decode tokens/s (obs on)" and line["value"] > 0
    assert line["n_metrics"] > 0 and line["n_spans"] > 0
    assert line["ttft_s"] > 0 and line["token_latency_p99_s"] > 0
    assert len(out[-1]) < 1024
    detail = json.loads(out[-2])["detail"]
    # _emit folded the enabled registry's snapshot into the sidecar
    assert "edgellm_decode_steps_total" in detail["metrics"]
    assert "edgellm_decode_ttft_seconds" in detail["metrics"]
    assert detail["obs"]["split"]["decode_tokens_per_s"] > 0
    # the on-disk artifacts: JSON snapshot + valid Chrome trace-event JSON
    snap = json.load(open(metrics_path))
    assert "edgellm_decode_token_latency_seconds" in snap
    trace = json.load(open(trace_path))
    assert trace["traceEvents"], "trace must contain spans"
    ev = trace["traceEvents"][0]
    assert ev["ph"] == "X" and {"name", "ts", "dur", "pid", "tid"} <= set(ev)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "generate.decode_loop" in names


def test_bench_backend_outage_exits_nonzero(monkeypatch, capsys, tmp_path):
    """A backend that cannot initialize fails the bench: the error
    propagates (the process exits non-zero) and NO artifact or headline is
    emitted — an outage must never read as a result with rc 0."""
    sys.modules.pop("bench", None)
    import bench
    import jax

    def _dead_backend():
        raise RuntimeError(
            "Unable to initialize backend 'tpu': UNAVAILABLE: no device")

    monkeypatch.setenv("BENCH_DETAIL_PATH", str(tmp_path / "detail.json"))
    monkeypatch.setenv("BENCH_FEC", "1")
    monkeypatch.setattr(jax, "devices", _dead_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        bench.main()
    assert capsys.readouterr().out == ""
    assert not (tmp_path / "detail.json").exists()

    # and so does any other error
    def _real_bug():
        raise RuntimeError("shape mismatch in decode step")

    monkeypatch.setattr(jax, "devices", _real_bug)
    with pytest.raises(RuntimeError, match="shape mismatch"):
        bench.main()


def test_mfu_peak_is_looked_up_by_device_kind():
    """mfu's denominator comes from a table keyed by device_kind; a kind that
    is not in it is an error, never a default peak — and a CPU sweep emits no
    mfu at all (test_bench_main_headline... runs one)."""
    sys.modules.pop("bench", None)
    import bench

    assert bench.peak_bf16_tflops("TPU v5 lite") == 197.0
    with pytest.raises(KeyError, match="no published peak"):
        bench.peak_bf16_tflops("TPU v9000")
    with pytest.raises(KeyError, match="no published peak"):
        bench.peak_bf16_tflops("cpu")
