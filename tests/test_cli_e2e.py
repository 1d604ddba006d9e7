"""End-to-end CLI runs from a real on-disk checkpoint (HF safetensors layout).

This is the "real weights + real corpus readiness" contract:
the moment actual Qwen2/Pythia artifacts appear, ``run.py --weights <dir>
--corpus <ids.npy>`` must execute the reference's experiments end to end. The
environment has no pretrained checkpoints, so these tests synthesize a
bit-exact HF-style model directory (config.json + model.safetensors) and drive
``edgellm_tpu.run.main`` through every dispatch branch the reference has
(token sweep ``Qwen2-0.5B/main.py:100-207``, channel sweep ``channel_wise.py``,
initial sweep ``initial_exp.py``, mesh-split eval), checking artifacts land and
that the loaded weights actually produced the numbers (vs. random init).
"""
import json

import numpy as np
import pytest

from edgellm_tpu.run import main
from test_safetensors_io import write_safetensors, _qwen_state_dict

TINY_HF_CONFIG = {
    "model_type": "qwen2",
    "vocab_size": 256,
    "hidden_size": 64,
    "num_hidden_layers": 6,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "intermediate_size": 256,
    "max_position_embeddings": 512,
    "rms_norm_eps": 1e-6,
    "rope_theta": 1000000.0,
    "tie_word_embeddings": True,
}


@pytest.fixture(scope="module")
def ckpt_dir(tmp_path_factory):
    """Synthesized HF-style checkpoint directory + token corpus."""
    from edgellm_tpu.models import tiny_config

    root = tmp_path_factory.mktemp("ckpt")
    cfg = tiny_config("qwen2", num_layers=6)
    rng = np.random.default_rng(7)
    sd = _qwen_state_dict(cfg, rng)
    model_dir = root / "model"
    model_dir.mkdir()
    (model_dir / "config.json").write_text(json.dumps(TINY_HF_CONFIG))
    write_safetensors(str(model_dir / "model.safetensors"), sd)
    corpus = rng.integers(0, cfg.vocab_size, 600).astype(np.int64)
    np.save(root / "corpus.npy", corpus)
    return {"model_dir": str(model_dir), "corpus": str(root / "corpus.npy"),
            "cfg": cfg, "sd": sd, "corpus_ids": corpus}


def _params(tmp_path, body):
    p = tmp_path / "params.json"
    p.write_text(json.dumps(body))
    return str(p)


def _run(argv):
    assert main(argv) in (0, None)


def test_token_sweep_from_checkpoint_dir(ckpt_dir, tmp_path):
    params = _params(tmp_path, {
        "ratios": [0, 0.5, 1], "layers_of_interest": [2],
        "max_length": 64, "stride": 32,
        "methods": ["regular_importance", "last_row"]})
    out = tmp_path / "out"
    _run(["--params", params, "--weights", ckpt_dir["model_dir"],
          "--corpus", ckpt_dir["corpus"], "--output-dir", str(out),
          "--window-batch", "4"])
    result = json.load(open(out / "avg_ppl_results.json"))
    ppl = np.asarray(result["ppl"])
    assert ppl.shape == (2, 1, 3) and np.isfinite(ppl).all()

    # the numbers must come from the checkpoint weights: the same sweep driven
    # directly through the library with the loaded pytree agrees exactly
    from edgellm_tpu.models.safetensors_io import load_checkpoint
    from edgellm_tpu.eval import run_token_sweep

    cfg, pt = load_checkpoint(ckpt_dir["model_dir"])
    direct = run_token_sweep(
        cfg, pt, ckpt_dir["corpus_ids"], methods=["regular_importance", "last_row"],
        layers_of_interest=[2], ratios=[0, 0.5, 1], max_length=64, stride=32,
        window_batch=4)
    np.testing.assert_allclose(ppl, direct.ppl(), rtol=1e-6)


def test_channel_sweep_from_checkpoint_dir(ckpt_dir, tmp_path):
    params = _params(tmp_path, {
        "layers_of_interest": [3], "max_length": 64, "stride": 32,
        "methods": ["channel_8", "channel_1_mean"], "ratios": []})
    out = tmp_path / "out"
    _run(["--params", params, "--weights", ckpt_dir["model_dir"],
          "--corpus", ckpt_dir["corpus"], "--output-dir", str(out),
          "--max-chunks", "4"])
    result = json.load(open(out / "avg_ppl_results.json"))
    assert np.isfinite(result["ppl"]).all()


def test_initial_sweep_from_checkpoint_dir(ckpt_dir, tmp_path):
    params = _params(tmp_path, {
        "experiment": "initial",
        "ratios": [0, 5], "layers_of_interest": [1, "upto ratio"],
        "max_length": 64, "stride": 32})
    out = tmp_path / "out"
    _run(["--params", params, "--weights", ckpt_dir["model_dir"],
          "--corpus", ckpt_dir["corpus"], "--output-dir", str(out),
          "--max-chunks", "4"])
    result = json.load(open(out / "avg_ppl_results.json"))
    assert np.isfinite(result["ppl"]).all()


def test_split_eval_from_checkpoint_dir(ckpt_dir, tmp_path):
    params = _params(tmp_path, {
        "experiment": "split", "cuts": [2],
        "hop_codecs": ["int8_per_token"], "max_length": 64, "stride": 32})
    out = tmp_path / "out"
    _run(["--params", params, "--weights", ckpt_dir["model_dir"],
          "--corpus", ckpt_dir["corpus"], "--output-dir", str(out),
          "--max-chunks", "4"])
    result = json.load(open(out / "split_eval_results.json"))
    assert np.isfinite(result["ppl"])
    assert result["bytes_per_token_per_hop"][0] > 0


def test_ring_long_context_split_cli(ckpt_dir, tmp_path):
    """The stage x seq long-context path end to end from the CLI (the shape of
    configs/split5_qwen_ring_long.json on the synthesized checkpoint): seq
    sharded within each stage, windows right-padded to a shardable length."""
    out = tmp_path / "out_ring"
    params = _params(tmp_path, {
        "experiment": "split", "cuts": [2], "hop_codecs": ["int4_per_token"],
        "max_length": 44, "stride": 22, "n_seq": 3})
    main(["--params", params, "--weights", ckpt_dir["model_dir"],
          "--corpus", ckpt_dir["corpus"], "--output-dir", str(out),
          "--max-chunks", "4"])
    result = json.load(open(out / "split_eval_results.json"))
    assert np.isfinite(result["ppl"])
    assert result["mesh"] == {"stage": 2, "seq": 3}
    assert result["pad_fraction"] > 0  # 44 % 3 != 0: the padding path ran
