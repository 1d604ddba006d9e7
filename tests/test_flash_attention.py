"""Whole-S-in-VMEM attention kernel: parity vs the dense formulation (the
kernel runs in interpret mode on CPU; on TPU it is the default hot path for
S <= 1024 — measured ~2.4x XLA's fused attention at the flagship shapes)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edgellm_tpu.models import flash_attention
from edgellm_tpu.models.flash_attention import (causal_attention,
                                                causal_attention_stats,
                                                kernel_plan,
                                                _shape_plan)


def _dense(q, k, v):
    b, s, h, hd = q.shape
    rep = h // k.shape[2]
    k = np.repeat(k, rep, axis=2)
    v = np.repeat(v, rep, axis=2)
    scores = np.einsum("bshd,bthd->bhst", q, k, dtype=np.float32) / np.sqrt(hd)
    mask = np.tril(np.ones((s, s), bool))
    scores = np.where(mask[None, None], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.einsum("bhst,bthd->bshd", p, v)
    return out, p


@pytest.mark.parametrize("b,h,kv,s,hd", [
    (2, 4, 4, 64, 32),    # MHA
    (2, 4, 2, 64, 32),    # GQA rep=2
    (1, 14, 2, 32, 64),   # the flagship head layout
    (3, 8, 8, 24, 16),    # s not a power of two
])
def test_kernel_matches_dense(rng, b, h, kv, s, hd):
    q = rng.normal(size=(b, s, h, hd)).astype(np.float32)
    k = rng.normal(size=(b, s, kv, hd)).astype(np.float32)
    v = rng.normal(size=(b, s, kv, hd)).astype(np.float32)
    want, _ = _dense(q, k, v)
    got = causal_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


def test_stats_kernel_matches_full_probs(rng):
    b, h, s, hd = 2, 4, 64, 32
    q = rng.normal(size=(b, s, h, hd)).astype(np.float32)
    k = rng.normal(size=(b, s, 2, hd)).astype(np.float32)
    v = rng.normal(size=(b, s, 2, hd)).astype(np.float32)
    want_out, p = _dense(q, k, v)
    out, (col, last) = causal_attention_stats(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), interpret=True)
    np.testing.assert_allclose(np.asarray(out), want_out, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(col), p.sum(axis=2) / s, atol=1e-6)
    np.testing.assert_allclose(np.asarray(last), p[:, :, -1, :], atol=1e-6)


def test_model_attention_same_under_either_backend(rng, monkeypatch):
    """The kernel in transformer.attention (the chooser told it is on a TPU;
    the kernel itself still reads the real backend and runs interpreted)
    reproduces the XLA path's block output and stats."""
    from edgellm_tpu.models import tiny_config, init_params
    from edgellm_tpu.models.transformer import forward, run_layers_from_ids

    # hd must be in VALIDATED_HD (64) or the chooser would answer the XLA
    # path on a TPU too and this test would compare XLA against XLA
    cfg = tiny_config("qwen2", num_layers=3, hidden_size=256, num_heads=4,
                      vocab_size=128)
    params = init_params(cfg, jax.random.key(0))
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)))

    assert kernel_plan(32, 4, 4, 64, itemsize=4) is None
    base, _ = forward(cfg, params, ids)
    _, aux = run_layers_from_ids(cfg, params, ids, capture_stats=True)
    jax.clear_caches()  # attention() asks the chooser at trace time

    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    assert kernel_plan(32, 4, 4, 64, itemsize=4) == ("whole", None)
    got, _ = forward(cfg, params, ids)
    _, aux_p = run_layers_from_ids(cfg, params, ids, capture_stats=True)
    jax.clear_caches()
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(aux_p["stats"].col_mean),
                               np.asarray(aux["stats"].col_mean), atol=1e-5)
    np.testing.assert_allclose(np.asarray(aux_p["stats"].last_row),
                               np.asarray(aux["stats"].last_row), atol=1e-5)


@pytest.mark.parametrize("shape,plan", [
    ((512, 14, 2, 64), ("whole", None)),            # flagship
    ((512, 12, 2, 128), ("whole", None)),           # qwen2-1.5b
    # S=2048 — the reference's Pythia window: query-blocked kernel
    ((2048, 8, 8, 64), ("blocked", (512, 8))),
    ((2048, 14, 2, 64), ("blocked", (512, 14))),
    # llama-1b: packed row 2048 > whole-kernel envelope -> head-group split
    ((512, 32, 8, 64), ("blocked", (512, 16))),
    ((2048, 32, 8, 64), ("blocked", (512, 16))),
    # beyond the blocked envelope, unvalidated hd, ragged GQA: XLA
    ((4096, 8, 8, 64), None),
    ((512, 8, 8, 80), None),                        # ADVICE r4: hd gate
    ((512, 14, 4, 64), None),                       # H % KV != 0
    ((1536, 8, 8, 64), ("blocked", (512, 8))),
    ((1100, 8, 8, 64), None),                       # S not qb-aligned
    ((1024, 14, 2, 64), ("whole", None)),           # the whole-S edge
])
def test_kernel_plan(monkeypatch, shape, plan):
    """On a TPU the plan is the shape's; everywhere else there is none
    (interpret mode would be slow, XLA is fine)."""
    assert kernel_plan(*shape) is None
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    assert kernel_plan(*shape) == plan


def test_shape_plan_scales_whole_s_by_itemsize():
    """ADVICE r5 #1: the whole-S VMEM envelope assumes bf16 rows; wider
    dtypes shrink the eligible S/packed-dh and fall through to the blocked
    plan (whose K/V budget is already itemsize-aware)."""
    assert _shape_plan(1024, 12, 2, 128) == ("whole", None)          # bf16
    assert _shape_plan(1024, 12, 2, 128, itemsize=4) != ("whole", None)
    assert _shape_plan(512, 12, 2, 64, itemsize=4) == ("whole", None)
    # packed-dh gate: fp32 halves the 1536-lane row budget too
    assert _shape_plan(512, 14, 2, 96)[0] == "whole"                 # dh=1344
    assert _shape_plan(512, 14, 2, 96, itemsize=4)[0] != "whole"


@pytest.mark.parametrize("b,h,kv,s,hd,qb,hps", [
    (2, 4, 4, 128, 32, 32, 4),   # query-blocked, all heads per step
    (2, 4, 2, 128, 32, 64, 2),   # query-blocked + GQA head-group split
    (1, 8, 2, 64, 32, 64, 4),    # head-group split only (qb == S)
    (2, 4, 4, 96, 16, 32, 2),    # both splits, MHA
])
def test_blocked_kernel_matches_dense(rng, b, h, kv, s, hd, qb, hps):
    q = rng.normal(size=(b, s, h, hd)).astype(np.float32)
    k = rng.normal(size=(b, s, kv, hd)).astype(np.float32)
    v = rng.normal(size=(b, s, kv, hd)).astype(np.float32)
    want, p = _dense(q, k, v)
    plan = ("blocked", (qb, hps))
    got = causal_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           interpret=True, plan=plan)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)
    out, (col, last) = causal_attention_stats(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        interpret=True, plan=plan)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(col), p.sum(axis=2) / s, atol=1e-5)
    np.testing.assert_allclose(np.asarray(last), p[:, :, -1, :], atol=1e-6)


def test_blocked_plan_is_auto_resolved(rng):
    """At a shape outside the whole-S envelope, causal_attention resolves the
    blocked plan itself (what the model's TPU dispatch relies on)."""
    assert _shape_plan(128, 4, 2, 32) == ("whole", None)
    b, s, h, kv, hd = 1, 1536, 4, 2, 32
    # force the blocked path by shape: s > MAX_WHOLE_S
    q = rng.normal(size=(b, s, h, hd)).astype(np.float32)
    k = rng.normal(size=(b, s, kv, hd)).astype(np.float32)
    v = rng.normal(size=(b, s, kv, hd)).astype(np.float32)
    want, _ = _dense(q, k, v)
    got = causal_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)