"""Fused quantize->DMA boundary hops: the wire-mode fused hop must be
BIT-identical to the separate encode/ppermute/decode ladder, the gate must
refuse everywhere fusion could regress or lie, and the disabled build must
trace the byte-identical pre-fusion graph.

The load-bearing claims, each asserted here:
- a fused "wire" hop (encode -> seal -> ONE flat uint8 ppermute -> verify ->
  decode) delivers the receiver the exact bytes-and-bits the unfused ladder
  would — for every FUSED_CAPABLE base codec;
- the gating ladder refuses: CPU default (no measured win), remote off-TPU,
  an active FaultyLink, importance-carrying codecs, and EDGELLM_FUSED_HOP=0;
- a forced-wire SplitRuntime is bitwise-identical to the default build at
  forward, decode prefill/step, paged decode step, and whole-generation
  (generate_split) granularity;
- fault injection and FEC repair operate on the SAME flat wire stream the
  fused hop ships (codecs.wire_format owns the layout): a corrupted fused
  buffer fails verification, and FEC parity repairs it back to bit-exact;
- the remote-DMA kernel traces (abstract eval) under shard_map even on CPU,
  so its graph structure is CI-checkable without a TPU.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from edgellm_tpu.codecs.packing import get_wire_codec
from edgellm_tpu.codecs.pallas_kernels import (FUSED_CAPABLE, REMOTE_CAPABLE,
                                               FusedHopPlan, fused_hop_plan,
                                               fused_remote_hop,
                                               fused_wire_hop)
from edgellm_tpu.codecs.wire_format import (WireFormat, flatten_bytes,
                                            seal_payload, unflatten_bytes,
                                            verify_payload)
from edgellm_tpu.models import init_params, tiny_config
from edgellm_tpu.parallel import SplitConfig, SplitRuntime, make_stage_mesh
from jax import shard_map

CFG = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4,
                  vocab_size=128)
SPLIT = SplitConfig(cuts=(2,), hop_codecs=("int8_per_token",))


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(3))


@pytest.fixture(scope="module")
def ids():
    rng = np.random.default_rng(11)
    return jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 8)))


@pytest.fixture(scope="module")
def mesh():
    return make_stage_mesh(2)


@pytest.fixture(scope="module")
def runtimes(mesh):
    """(default build, forced-wire build, forced-off build) — the env gate
    resolves at construction time, so set it around each __init__."""
    saved = os.environ.get("EDGELLM_FUSED_HOP")
    try:
        os.environ.pop("EDGELLM_FUSED_HOP", None)
        rt = SplitRuntime(CFG, SPLIT, mesh)
        os.environ["EDGELLM_FUSED_HOP"] = "wire"
        rt_wire = SplitRuntime(CFG, SPLIT, mesh)
        os.environ["EDGELLM_FUSED_HOP"] = "0"
        rt_off = SplitRuntime(CFG, SPLIT, mesh)
    finally:
        if saved is None:
            os.environ.pop("EDGELLM_FUSED_HOP", None)
        else:
            os.environ["EDGELLM_FUSED_HOP"] = saved
    return rt, rt_wire, rt_off


# ---------- the wire hop itself: bit-parity vs the separate ladder ----------


def _hop_pair(codec, hidden, fused: bool):
    """Run one 0->1 hop on a 2-stage mesh; returns the (2, ...) per-stage
    results (row 0 = sender, untouched; row 1 = receiver)."""
    mesh = make_stage_mesh(2)

    def body(h):
        idx = jax.lax.axis_index("stage")
        mine = h[0]
        if fused:
            out = fused_wire_hop(codec, mine, 0, "stage", idx)
        else:
            sealed = seal_payload(codec.encode(mine))
            moved = jax.tree_util.tree_map(
                lambda a: jax.lax.ppermute(a, "stage", [(0, 1)]), sealed)
            ok = verify_payload(moved)
            dec = codec.decode(moved["p"]).astype(mine.dtype)
            out = jnp.where(idx == 1, jnp.where(ok, dec, mine), mine)
        return out[None]

    fn = shard_map(body, mesh=mesh, in_specs=P("stage"), out_specs=P("stage"))
    stacked = jnp.broadcast_to(hidden[None], (2,) + hidden.shape)
    return np.asarray(jax.jit(fn)(stacked))


@pytest.mark.parametrize("base", sorted(FUSED_CAPABLE))
def test_wire_hop_bit_identical_to_separate_ladder(base):
    codec = get_wire_codec(base)
    rng = np.random.default_rng(7)
    hidden = jnp.asarray(rng.standard_normal((1, 4, 32)), jnp.float32)
    fused = _hop_pair(codec, hidden, fused=True)
    plain = _hop_pair(codec, hidden, fused=False)
    # sender row untouched, receiver row decoded — and both BIT-equal
    np.testing.assert_array_equal(fused[0], np.asarray(hidden))
    np.testing.assert_array_equal(fused, plain)
    assert not np.array_equal(fused[1], np.asarray(hidden)), \
        "receiver row identical to raw hidden: quantization never happened"


def test_wire_format_roundtrip_is_the_sealed_tree():
    codec = get_wire_codec("int8_per_token")
    hidden = jnp.asarray(np.random.default_rng(0).standard_normal((1, 4, 32)),
                         jnp.float32)
    sealed = seal_payload(codec.encode(hidden))
    wf = WireFormat.for_codec(codec, hidden.shape, hidden.dtype)
    back = wf.from_wire(wf.to_wire(sealed))
    for a, b in zip(jax.tree_util.tree_leaves(sealed),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert wf.wire_nbytes == wf.payload_nbytes + 8


# ---------- the gating ladder ----------


def test_gate_default_refuses_on_cpu(monkeypatch):
    monkeypatch.delenv("EDGELLM_FUSED_HOP", raising=False)
    assert fused_hop_plan(get_wire_codec("int8_per_token")) is None


def test_gate_forced_wire(monkeypatch):
    monkeypatch.setenv("EDGELLM_FUSED_HOP", "wire")
    plan = fused_hop_plan(get_wire_codec("int8_per_token"))
    assert plan == FusedHopPlan("wire", "int8_per_token",
                                "forced: EDGELLM_FUSED_HOP=wire")


def test_gate_remote_needs_tpu(monkeypatch):
    monkeypatch.setenv("EDGELLM_FUSED_HOP", "remote")
    assert fused_hop_plan(get_wire_codec("int8_per_token")) is None
    plan = fused_hop_plan(get_wire_codec("int8_per_token"), backend="tpu")
    assert plan is not None and plan.mode == "remote"


def test_gate_best_mode_picks_remote_only_where_capable(monkeypatch):
    monkeypatch.setenv("EDGELLM_FUSED_HOP", "1")
    assert fused_hop_plan(get_wire_codec("int8_per_token")).mode == "wire"
    assert fused_hop_plan(get_wire_codec("int8_per_token"),
                          backend="tpu").mode == "remote"
    assert "ternary_mean" not in REMOTE_CAPABLE
    assert fused_hop_plan(get_wire_codec("ternary_mean"),
                          backend="tpu").mode == "wire"


def test_gate_refusals(monkeypatch):
    monkeypatch.setenv("EDGELLM_FUSED_HOP", "wire")
    codec = get_wire_codec("int8_per_token")
    assert fused_hop_plan(None) is None
    # an active FaultyLink owns the hop (injection/retries/FEC would be
    # bypassed by fusion)
    assert fused_hop_plan(codec, link_active=True) is None
    # importance sidecars don't fit the fused payload
    from edgellm_tpu.codecs.packing import selective_int4

    sel = selective_int4(0.5)
    assert sel.needs_importance and fused_hop_plan(sel) is None
    monkeypatch.setenv("EDGELLM_FUSED_HOP", "0")
    assert fused_hop_plan(codec) is None


def test_gate_default_requires_probe_cache_win(monkeypatch):
    from edgellm_tpu.codecs import probe_cache

    monkeypatch.delenv("EDGELLM_FUSED_HOP", raising=False)
    codec = get_wire_codec("int8_per_token")
    monkeypatch.setattr(probe_cache, "measured_win", lambda name: None)
    assert fused_hop_plan(codec, backend="tpu") is None
    monkeypatch.setattr(probe_cache, "measured_win", lambda name: False)
    assert fused_hop_plan(codec, backend="tpu") is None
    monkeypatch.setattr(probe_cache, "measured_win", lambda name: True)
    plan = fused_hop_plan(codec, backend="tpu")
    assert plan is not None and "measured win" in plan.reason


# ---------- runtime threading: forced-wire == default, bit for bit ----------


def test_runtime_plans_and_provenance(runtimes):
    rt, rt_wire, rt_off = runtimes
    assert all(p is None for p in rt.fused_plans)  # CPU: no measured win
    assert all(p is not None and p.mode == "wire"
               for p in rt_wire.fused_plans)
    assert all(p is None for p in rt_off.fused_plans)
    rows = rt_wire.wire_summary(1, 8)
    assert all(r["fused"] == {"mode": "wire",
                              "reason": "forced: EDGELLM_FUSED_HOP=wire"}
               for r in rows)
    assert all(r["fused"] is None for r in rt.wire_summary(1, 8))


def test_forward_bitwise_parity(runtimes, params, ids):
    rt, rt_wire, _ = runtimes
    out = rt.forward(rt.place_params(params), ids)
    out_f = rt_wire.forward(rt_wire.place_params(params), ids)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_f))


def test_decode_step_bitwise_parity(runtimes, params, ids):
    rt, rt_wire, _ = runtimes
    placed = rt.place_params(params)
    cap = 16
    logits0, cache0 = rt.prefill_decode(placed, ids, cap)
    logits1, cache1 = rt_wire.prefill_decode(placed, ids, cap)
    np.testing.assert_array_equal(np.asarray(logits0), np.asarray(logits1))
    tok = jnp.argmax(logits0[:, -1], axis=-1).astype(jnp.int32)
    step0, cache0 = rt.decode_step(placed, cache0, tok)
    step1, cache1 = rt_wire.decode_step(placed, cache1, tok)
    np.testing.assert_array_equal(np.asarray(step0), np.asarray(step1))
    np.testing.assert_array_equal(np.asarray(cache0["k"]),
                                  np.asarray(cache1["k"]))


def test_paged_decode_step_bitwise_parity(runtimes, params, ids):
    rt, rt_wire, _ = runtimes
    placed = rt.place_params(params)
    npages, psize = 5, 8
    out = []
    for r in (rt, rt_wire):
        pool = r.init_paged_pool(npages, psize)
        table = jnp.zeros((2, 2), jnp.int32).at[0].set(jnp.asarray([1, 2]))
        lengths = jnp.asarray([ids.shape[1], 0], jnp.int32)
        toks = jnp.asarray([int(ids[0, -1]), 0], jnp.int32)
        out.append(r.decode_step_paged(placed, pool, table, lengths, toks))
    logits0, logits1 = np.asarray(out[0][0]), np.asarray(out[1][0])
    np.testing.assert_array_equal(logits0, logits1)


def test_generate_split_token_identical(runtimes, params, ids):
    from edgellm_tpu.serve import generate_split

    rt, rt_wire, _ = runtimes
    out = generate_split(rt, rt.place_params(params), ids, 6)
    out_f = generate_split(rt_wire, rt_wire.place_params(params), ids, 6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_f))


def test_fused_disabled_graph_identity(runtimes, params, ids):
    from edgellm_tpu.lint.contracts import graph_fingerprint

    rt, rt_wire, rt_off = runtimes
    placed = rt.place_params(params)
    imps = jnp.zeros((len(rt.codecs), ids.shape[1]), jnp.float32)
    fp_default = graph_fingerprint(rt._forward, placed, ids, imps)
    fp_off = graph_fingerprint(rt_off._forward, placed, ids, imps)
    fp_wire = graph_fingerprint(rt_wire._forward, placed, ids, imps)
    assert fp_off == fp_default  # =0 build IS the pre-fusion graph
    assert fp_wire != fp_default  # the fused build genuinely differs


def test_faulty_link_build_never_fuses(mesh):
    from edgellm_tpu.codecs.faults import FaultConfig, LinkPolicy

    saved = os.environ.get("EDGELLM_FUSED_HOP")
    try:
        os.environ["EDGELLM_FUSED_HOP"] = "wire"
        rt_fault = SplitRuntime(CFG, SPLIT, mesh,
                                faults=FaultConfig(bitflip_rate=0.01, seed=0),
                                policy=LinkPolicy(max_retries=1))
    finally:
        if saved is None:
            os.environ.pop("EDGELLM_FUSED_HOP", None)
        else:
            os.environ["EDGELLM_FUSED_HOP"] = saved
    assert all(p is None for p in rt_fault.fused_plans)


# ---------- faults + FEC through the fused wire stream ----------


def _sealed_payload():
    codec = get_wire_codec("int8_per_token")
    hidden = jnp.asarray(np.random.default_rng(1).standard_normal((1, 4, 32)),
                         jnp.float32)
    return codec, hidden, seal_payload(codec.encode(hidden))


def test_corrupted_fused_buffer_fails_verification():
    codec, hidden, sealed = _sealed_payload()
    wf = WireFormat.for_codec(codec, hidden.shape, hidden.dtype)
    buf = np.asarray(wf.to_wire(sealed))
    assert bool(verify_payload(wf.from_wire(jnp.asarray(buf))))
    for pos in (0, 7, 8, buf.size // 2, buf.size - 1):  # seal AND payload
        bad = buf.copy()
        bad[pos] ^= 0x40
        assert not bool(verify_payload(wf.from_wire(jnp.asarray(bad)))), \
            f"flipped byte {pos} slipped through the fused wire format"


def test_fec_repairs_the_fused_wire_stream():
    from edgellm_tpu.codecs.fec import FECConfig, fec_decode, fec_encode

    _, _, sealed = _sealed_payload()
    cfg = FECConfig(group_size=4, n_groups=4)
    wire = fec_encode(sealed, cfg)
    chunks = np.asarray(wire["chunks"]).copy()
    chunks[2, 1] ^= 0xA5  # one corrupted data chunk: XOR parity territory
    got, any_bad, repaired = fec_decode(
        {"chunks": jnp.asarray(chunks), "words": wire["words"]}, cfg, sealed)
    assert bool(any_bad) and bool(repaired)
    assert bool(verify_payload(got))
    np.testing.assert_array_equal(np.asarray(flatten_bytes(got)),
                                  np.asarray(flatten_bytes(sealed)))


def test_flat_stream_is_shared_by_fec_and_fused_hop():
    # the FEC chunker and the fused hop must serialize the SAME byte order
    codec, hidden, sealed = _sealed_payload()
    wf = WireFormat.for_codec(codec, hidden.shape, hidden.dtype)
    np.testing.assert_array_equal(np.asarray(wf.to_wire(sealed)),
                                  np.asarray(flatten_bytes(sealed)))
    spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), sealed)
    back = unflatten_bytes(wf.to_wire(sealed), spec)
    assert bool(verify_payload(back))


# ---------- remote kernel: trace-only on CPU ----------


def test_remote_hop_traces_under_shard_map():
    """The remote-DMA kernel can't EXECUTE off-TPU, but its graph must
    still build (CI checks structure without a TPU)."""
    codec = get_wire_codec("int8_per_token")
    mesh = make_stage_mesh(2)

    def body(h):
        idx = jax.lax.axis_index("stage")
        return fused_remote_hop(codec, h[0], 0, "stage", idx, n_dev=2)[None]

    # check_vma=False matches the production shard_maps in parallel/split.py
    # (pallas_call has no replication rule)
    fn = shard_map(body, mesh=mesh, in_specs=P("stage"),
                   out_specs=P("stage"), check_vma=False)
    hidden = jnp.zeros((2, 1, 4, 32), jnp.float32)
    out = jax.eval_shape(fn, hidden)
    assert out.shape == hidden.shape and out.dtype == jnp.float32
