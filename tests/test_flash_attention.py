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

# ---------------------------------------------------------------------------
# The masked prefill kernel (flash_attention.masked_attention) against the
# XLA attend it replaces: float32 scores over every key, a `where`, a two-pass
# softmax, the probabilities cast to the operands' dtype, a second einsum.
# ---------------------------------------------------------------------------

#: |kernel - oracle| <= tol x max |oracle|, by dtype: a running softmax orders
#: its float32 sums differently (a few roundings of 2^-24 a key block), and a
#: bfloat16 output is rounded once more on either side (half an ulp of 2^-8
#: each, and a probability rounded to bfloat16 before the second dot may fall
#: on the other side of a rounding boundary)
MASKED_TOL = {jnp.float32: 2e-6, jnp.bfloat16: 1.2e-2}

#: (G, rep, Q, C, dk, dv): the layouts the sparse families send (a latent
#: layer's heads expanded, a head a group; sparse_attn's KV groups), and one
#: group of many heads
MASKED_LAYOUTS = {
    "multi_query": (1, 8, 32, 640, 256, 128),
    "expanded": (3, 1, 64, 640, 192, 128),
    "gqa": (4, 8, 32, 640, 128, 128),
}


def _xla_attend(q, k, v, mask, scale):
    groups = q.shape[0] // mask.shape[0]
    s = jnp.einsum("grqd,gcd->grqc", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(jnp.repeat(mask, groups, axis=0)[:, None], s,
                  jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("grqc,gcd->grqd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _interpreted(*args, **kw):
    from jax.experimental.pallas import tpu as pltpu
    return jax.block_until_ready(flash_attention.masked_attention(
        *args, **kw, interpret=pltpu.InterpretParams()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["causal", "ties", "traced_start",
                                  "skipped_blocks", "leftover_rows"])
@pytest.mark.parametrize("layout", list(MASKED_LAYOUTS))
def test_masked_attention_matches_the_xla_blocks(rng, monkeypatch, layout,
                                                 case, dtype):
    from edgellm_tpu.models import sparse_attn

    g, rep, n, c, dk, dv = MASKED_LAYOUTS[layout]
    start, topk = 500, 48
    if case == "leftover_rows":     # rows that fill no mask tile, keys no block
        n, c, start = 20, 300, 270
    if case == "skipped_blocks":
        # a budget that leaves row tiles of 32 of the block's 64 rows, three
        # key blocks of 512: the third lies past every row, the second past
        # the rows of a head's first tile (positions 480-511) alone
        n, c, start = 64, 1536, 480
        monkeypatch.setattr(flash_attention, "MASKED_VMEM_BYTES", 1 << 19)
        monkeypatch.setattr(flash_attention, "MASKED_KEY_BLOCK", 512)
        assert flash_attention.masked_attention_plan(
            rep, n, c, dk, dv, jnp.dtype(dtype).itemsize) == (32, 512)
    q = jnp.asarray(rng.normal(size=(g, rep, n, dk)), dtype)
    k = jnp.asarray(rng.normal(size=(g, c, dk)), dtype)
    v = jnp.asarray(rng.normal(size=(g, c, dv)), dtype)
    at = start + jnp.arange(n)
    visible = (jnp.arange(c)[None, :] <= at[:, None])[None]       # (1, Q, C)
    mask = visible
    if case != "causal":
        # index scores in eighths: a row's k-th largest visible score is
        # shared by many positions, of which the earliest fill the selection
        scores = jnp.asarray(rng.integers(0, 8, (2 if g % 2 == 0 else 1, n,
                                                 c)) / 8.0, jnp.float32)
        mask = sparse_attn.selection_mask(scores, visible, topk)
        assert (np.asarray(mask.sum(-1)) == np.minimum(
            np.asarray(at) + 1, topk)).all()
    scale = dk ** -0.5
    want = _xla_attend(q, k, v, mask, scale)
    if case == "skipped_blocks":
        # a skipped block is neither fetched nor multiplied: what it holds
        # (NaN here) cannot reach the output, where a block that was
        # multiplied under the mask would carry 0 x NaN into the sums
        last = start + n - 1
        poison = jnp.arange(c)[None, :, None] >= -(-(last + 1) // 512) * 512
        k, v = jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v)
    if case == "traced_start":
        # as a prefill's body hands it over: under ``lax.map``, a tracer
        firsts = jnp.asarray([start, start - 64], jnp.int32)
        masks = jnp.stack([mask, jnp.roll(mask, -64, axis=-1)
                           & (jnp.arange(c)[None, :] <= at[:, None] - 64)])
        got = jax.block_until_ready(jax.jit(lambda *a: jax.lax.map(
            lambda xs: _interpreted(q, k, v, xs[1], xs[0], scale=scale),
            a))(firsts, masks))
        want = jnp.stack([want, _xla_attend(q, k, v, masks[1], scale)])
    else:
        got = _interpreted(q, k, v, mask, jnp.int32(start), scale=scale)
    assert got.shape == want.shape and got.dtype == want.dtype
    want = np.asarray(want, np.float32)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert np.max(np.abs(np.asarray(got, np.float32) - want)) <= \
        MASKED_TOL[dtype] * np.max(np.abs(want))


def test_masked_attention_plan_reads_the_tiles_off_the_shapes():
    """The key block is 2048 keys, a short key set one block of whole lane
    tiles; the row tile is whole copies of the block's rows, or whole mask
    tiles that divide them, the largest whose step fits the budget."""
    plan = flash_attention.masked_attention_plan
    # keye's block: 8 heads a group x 512 rows of 128 lanes
    assert plan(8, 512, 16384, 128, 128, 2) == (512, 2048)
    # deepseek's body, one head a group, 4096 rows: a divisor of the rows in
    # whole mask tiles
    assert plan(1, 4096, 16384, 192, 128, 2) == (512, 2048)
    # whole copies of a short block's rows, its keys in one block
    assert plan(4, 32, 300, 128, 128, 4) == (128, 384)
