"""Ring-attention sequence-parallelism tests on the spoofed CPU mesh: the
sharded-sequence forward must match the dense single-device forward for both
families, any ring size, and sequence lengths that stress the blockwise causal
mask."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edgellm_tpu.models import tiny_config, init_params, forward
from edgellm_tpu.parallel.ring import make_seq_mesh, forward_sp, ring_attention
from jax import shard_map
from jax.sharding import PartitionSpec as P

QWEN = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4, vocab_size=128)
NEOX = tiny_config("gpt_neox", num_layers=3, hidden_size=32, num_heads=4, vocab_size=128)


def _dense_reference(q, k, v):
    """Naive causal attention, fp32."""
    b, s, h, hd = q.shape
    scores = np.einsum("bshd,bthd->bhst", q, k) / np.sqrt(hd)
    mask = np.tril(np.ones((s, s), bool))
    scores = np.where(mask[None, None], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhst,bthd->bshd", p, v)


@pytest.mark.parametrize("n_ring", [2, 4, 8])
def test_ring_attention_matches_dense(rng, n_ring):
    b, s, h, hd = 2, 32, 3, 8
    q = rng.normal(size=(b, s, h, hd)).astype(np.float32)
    k = rng.normal(size=(b, s, h, hd)).astype(np.float32)
    v = rng.normal(size=(b, s, h, hd)).astype(np.float32)
    mesh = make_seq_mesh(n_ring)
    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq"),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
    )
    got = np.asarray(ring(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(got, _dense_reference(q, k, v), atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("cfg", [QWEN, NEOX], ids=["qwen2", "gpt_neox"])
def test_forward_sp_matches_dense_forward(cfg):
    params = init_params(cfg, jax.random.key(2))
    ids = jnp.asarray(np.random.default_rng(8).integers(0, cfg.vocab_size, (2, 32)))
    base, _ = forward(cfg, params, ids)
    mesh = make_seq_mesh(4)
    got = forward_sp(cfg, params, ids, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), atol=2e-4, rtol=2e-4)


def test_forward_sp_rejects_indivisible_seq():
    params = init_params(QWEN, jax.random.key(2))
    ids = jnp.zeros((1, 30), jnp.int32)
    with pytest.raises(ValueError, match="divisible"):
        forward_sp(QWEN, params, ids, make_seq_mesh(4))


def test_ring_nll_long_sequence():
    """Longer-than-window sequence across 8 devices stays finite and causal:
    perturbing a late token must not change earlier logits."""
    cfg = QWEN
    params = init_params(cfg, jax.random.key(4))
    rng = np.random.default_rng(12)
    ids = rng.integers(0, cfg.vocab_size, (1, 64))
    mesh = make_seq_mesh(8)
    out = np.asarray(forward_sp(cfg, params, jnp.asarray(ids), mesh))
    assert np.isfinite(out).all()
    ids2 = ids.copy()
    ids2[0, -1] = (ids2[0, -1] + 1) % cfg.vocab_size
    out2 = np.asarray(forward_sp(cfg, params, jnp.asarray(ids2), mesh))
    np.testing.assert_allclose(out[0, :-1], out2[0, :-1], atol=1e-5)
    assert not np.allclose(out[0, -1], out2[0, -1])


def test_stage_seq_composition_fp32_matches_dense():
    """stage=2 x seq=4 on the 8-device mesh: pipeline-split layers + ring-
    sharded sequence == the dense single-device forward (the composability
    claim in ring.py, backed by execution)."""
    from edgellm_tpu.parallel import SplitRingRuntime, make_sp_stage_mesh

    cfg = QWEN
    params = init_params(cfg, jax.random.key(3))
    ids = jnp.asarray(np.random.default_rng(9).integers(0, cfg.vocab_size, (1, 32)))
    base, _ = forward(cfg, params, ids)
    rt = SplitRingRuntime(cfg, cuts=(1,), hop_codecs=("fp32",),
                          mesh=make_sp_stage_mesh(2, 4))
    out = rt.forward(rt.place_params(params), ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               atol=2e-4, rtol=2e-4)


def test_stage_seq_composition_quantized_hop():
    """A per-token packed hop composes with ring sharding: encoding each
    sequence shard locally == the single-device simulated boundary."""
    from edgellm_tpu.codecs import per_token_affine_int8
    from edgellm_tpu.parallel import SplitRingRuntime, make_sp_stage_mesh

    cfg = QWEN
    cut = 1
    params = init_params(cfg, jax.random.key(3))
    ids = jnp.asarray(np.random.default_rng(9).integers(0, cfg.vocab_size, (1, 32)))
    rt = SplitRingRuntime(cfg, cuts=(cut,), hop_codecs=("int8_per_token",),
                          mesh=make_sp_stage_mesh(2, 4))
    out = rt.forward(rt.place_params(params), ids)

    def bfn(idx, h):
        return jnp.where(idx == cut, per_token_affine_int8(h), h)

    ref_logits, _ = forward(cfg, params, ids, boundary_fn=bfn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_logits),
                               atol=2e-4, rtol=2e-4)


def test_stage_seq_rejects_non_per_token_codecs():
    from edgellm_tpu.parallel import SplitRingRuntime, make_sp_stage_mesh

    with pytest.raises(ValueError, match="per-token"):
        SplitRingRuntime(QWEN, cuts=(1,), hop_codecs=("int4_global",),
                         mesh=make_sp_stage_mesh(2, 4))


def test_long_context_ring_matches_dense_forward():
    """The long-context claim at scale: a 2048-token sequence ring-sharded over
    8 devices (256 tokens per shard) matches the dense single-device forward.
    The ring path never materializes the full S x S score matrix on one device."""
    cfg = QWEN
    params = init_params(cfg, jax.random.key(4))
    ids = jnp.asarray(np.random.default_rng(12).integers(
        0, cfg.vocab_size, (1, 2048)))
    dense, _ = forward(cfg, params, ids)
    mesh = make_seq_mesh(8)
    sharded = forward_sp(cfg, params, ids, mesh, "seq")
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(dense),
                               atol=3e-4, rtol=3e-4)
