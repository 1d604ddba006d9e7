"""Bring-up contracts: the chip smoke refuses anything but a TPU, the compile
cache is placed from outside, and a serve soak whose batcher died cannot exit
0. What only a chip can show lives in ``chip_smoke.py`` itself."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("lone", [False, True])
def test_chip_smoke_refuses_cpu(tmp_path, lone):
    """No accelerator -> exit != 0, a message naming the platform found, and
    NOTHING on stdout (no result line to mistake for a pass) — from the
    checkout, and from a directory holding the script alone."""
    script = os.path.join(REPO, "chip_smoke.py")
    if lone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          cwd=os.path.dirname(script), capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout == ""
    assert "platform 'cpu'" in proc.stderr and "TPU" in proc.stderr


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set -> nothing is set in code (JAX reads the
    variable itself); unset -> the fixed in-checkout path, never a temp name."""
    import jax

    from edgellm_tpu.utils import startup

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv(startup.CACHE_ENV, str(tmp_path / "placed"))
    assert startup.configure_compile_cache() == str(tmp_path / "placed")
    assert updates == []
    monkeypatch.delenv(startup.CACHE_ENV)
    want = os.path.join(REPO, ".jax_cache")
    assert startup.configure_compile_cache() == want
    assert startup.configure_compile_cache() == want  # fixed, not per-call
    assert updates == [("jax_compilation_cache_dir", want)] * 2


def _serve_argv(tmp_path):
    params = {"experiment": "serve",
              "serving": {"soak": {"n_requests": 3, "prompt_len": 6,
                                   "max_new_tokens": 4}},
              "batching": {"page_size": 4, "num_pages": 17, "max_slots": 2,
                           "pages_per_slot": 4}}
    return ["--params", json.dumps(params), "--model", "tiny-qwen2",
            "--output-dir", str(tmp_path / "out")]


def test_serve_stamps_the_device(tmp_path, capsys):
    from edgellm_tpu.run import main

    assert main(_serve_argv(tmp_path)) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    rep = json.load(open(tmp_path / "out" / "serve_report.json"))
    for d in (line, rep):
        assert (d["platform"], d["device_kind"]) == ("cpu", "cpu")
        assert d["device_count"] >= 1
    assert rep["outcomes"] == {"completed": 3}
    assert rep["warmup_s"] > 0 and rep["drain_s"] > 0
    assert [len(t) for t in rep["tokens"]] == [4, 4, 4]


def test_serve_exits_nonzero_when_the_batcher_raises(tmp_path, capsys,
                                                     monkeypatch):
    """``drain_batched`` turns any exception out of ``batcher.run`` into
    ``failed`` records and keeps going; ``run.py`` must not then print the
    outcome table and exit 0 — on a chip that is how a compiler refusal on
    the first un-warmed shape would read as ``{"failed": N}``, rc 0."""
    from edgellm_tpu.run import main
    from edgellm_tpu.serve.batching import ContinuousBatcher

    real_run = ContinuousBatcher.run
    calls = []

    def run(self, *a, **kw):
        calls.append(self)
        if len(calls) == 1:  # the warm-up batcher: let it through
            return real_run(self, *a, **kw)
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(ContinuousBatcher, "run", run)
    assert main(_serve_argv(tmp_path)) == 1
    cap = capsys.readouterr()
    assert "Mosaic failed to compile TPU kernel" in cap.err
    assert "batcher:RuntimeError" in cap.err
    rep = json.load(open(tmp_path / "out" / "serve_report.json"))
    assert rep["outcomes"] == {"failed": 3}


def test_chip_smoke_phases_follow_the_paged_step():
    """What of ``chip_smoke.py`` calls the paged step runs here at a toy size:
    the decode site names its one path, and prefill -> adopt -> paged step
    matches the dense forward."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    from edgellm_tpu.models import tiny_config

    cfg = tiny_config("qwen2", num_layers=2, hidden_size=32, num_heads=4,
                      vocab_size=128)
    batching = {"page_size": 8, "pages_per_slot": 4, "max_slots": 2,
                "num_pages": 9}
    sites = chip_smoke.dispatch_phase(cfg, "float32", prompt_lens=(12,),
                                      batching=batching, sweep_len=16)
    assert sites["attention"][-1] == {"site": "serve.decode[paged]",
                                      "seq": 32, "plan": "xla page gather"}
    ref = chip_smoke.reference_phase(cfg, batching=batching, prompt_len=12,
                                     n_steps=3)
    assert len(ref["logit_max_abs_err"]) == 4   # the prefill's and 3 steps'
    assert max(ref["logit_max_abs_err"]) <= chip_smoke.LOGIT_ATOL
