"""Ring-attention sequence parallelism: long contexts sharded across devices.

The reference handles its 299k-token corpus by clipping to 512/2048-token windows
(``Qwen2-0.5B/main.py:151-156``) — the window *is* the context limit. Here the
sequence axis itself shards across a ``"seq"`` mesh axis: every device holds the
full weights and 1/n of the tokens; attention is computed blockwise with K/V
blocks rotating around the ring via ``lax.ppermute`` (one hop per step, overlapped
by XLA with the local matmuls), with flash-style online-softmax accumulation so
no device ever materializes the full S x S score matrix. This is the standard
ring-attention construction (Liu et al.; see PAPERS.md) on XLA collectives
instead of NCCL P2P.

Composability: the "seq" axis is orthogonal to the split runtime's "stage" axis —
:class:`SplitRingRuntime` below pipeline-splits the layer stack AND ring-shards
the sequence on a ("stage", "seq") mesh, with per-token-compressed boundary hops
(tested equal to the dense forward in ``tests/test_ring.py``).

Everything is jit-safe: the ring loop is a ``lax.fori_loop`` with static block
shapes; the causal mask is computed from global block offsets.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models.configs import ModelConfig
from ..models.transformer import (
    apply_rotary, embed, precompute_rope, mlp, unembed, _layernorm, _rmsnorm,
)

NEG_INF = -1e30  # finite mask value: keeps exp() well-defined for empty blocks


def make_seq_mesh(n_seq: int, devices=None) -> Mesh:
    devices = np.asarray(devices if devices is not None else jax.devices())
    if devices.size < n_seq:
        raise ValueError(f"need {n_seq} devices, have {devices.size}")
    return Mesh(devices.reshape(-1)[:n_seq], ("seq",))


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str = "seq", capture_stats: bool = False,
                   kv_codec=None):
    """Causal ring attention over locally-sharded (B, S_loc, H, hd) query blocks.

    Must run inside ``shard_map`` with the sequence sharded on ``axis_name``.
    K/V blocks circulate the ring (device i sends to i+1); after n steps every
    query block has seen every key block once. Online softmax keeps running
    (max, denominator, accumulator) per query — the flash-attention recurrence.

    K/V may carry fewer (grouped-query) heads than Q: the unexpanded
    (B, S_loc, KV, hd) blocks are what circulates — h/kv times less ring
    traffic — and the head broadcast happens locally per step. The ring is
    statically unrolled (n is a trace-time constant), so XLA can overlap each
    hop's ppermute with the previous block's matmuls, and the last iteration
    sends nothing.

    ``capture_stats``: also return the reduced attention statistics the
    importance metrics consume (``AttnStats`` semantics, but sequence-sharded:
    each device ends holding the (B, H, S_loc) slice for ITS key block) —
    ``(col_sum / S, last_row)``. The column sums are accumulated during a
    second K rotation: exact per-key probabilities need the FINAL softmax max
    and denominator of every query row, which only exist after the first full
    rotation (a running column sum cannot be corrected retroactively — the
    per-query corrections collapse when summed over queries). The stats
    accumulators travel WITH the circulating K block and arrive back at its
    home device after n hops; the extra pass reuses the pass-1 scores math but
    skips the value matmul (~half an attention pass, only when stats are
    requested). Returns ``(out, (col_sum/S, last_row))`` with stats on,
    plain ``out`` otherwise (a bare array composes with shard_map out_specs).

    ``kv_codec`` (a batch-invariant :class:`~edgellm_tpu.codecs.packing.
    WireCodec`, opt-in) is the fused-quantized-collective trick applied to
    the ring's all-gather: each device encodes its home K/V blocks ONCE,
    the two packed payloads circulate as a single flat uint8 buffer (one
    ppermute per rotation step instead of one per K/V leaf), and every
    step dequantizes the arrived payload locally. Quantization happens
    exactly once per block — no per-hop re-encode, so error does not
    compound around the ring (EQuARX-style). Lossy by construction; None
    (the default) leaves the graph byte-identical to the uncompressed
    ring.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_loc, h, hd = q.shape
    rep = h // k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    q_pos = idx * s_loc + jnp.arange(s_loc)  # global positions of local queries

    m = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s_loc), jnp.float32)
    acc = jnp.zeros((b, h, s_loc, hd), jnp.float32)
    k_blk, v_blk = k, v
    ring = [(i, (i + 1) % n) for i in range(n)]

    if kv_codec is not None:
        from ..codecs.wire_format import flatten_bytes, unflatten_bytes

        kv = k.shape[2]
        k_payload = kv_codec.encode(k.reshape(b, s_loc, kv * hd))
        v_payload = kv_codec.encode(v.reshape(b, s_loc, kv * hd))
        kv_spec = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            {"k": k_payload, "v": v_payload})
        kv_wire = flatten_bytes({"k": k_payload, "v": v_payload})

        def kv_decode(buf):
            p = unflatten_bytes(buf, kv_spec)
            dk = kv_codec.decode(p["k"]).reshape(b, s_loc, kv, hd)
            dv = kv_codec.decode(p["v"]).reshape(b, s_loc, kv, hd)
            return dk.astype(k.dtype), dv.astype(v.dtype)

    def scores_for(k_blk, src):
        k_pos = src * s_loc + jnp.arange(s_loc)
        k_t = jnp.repeat(k_blk, rep, axis=2) if rep > 1 else k_blk
        scores = jnp.einsum("bshd,bthd->bhst", q, k_t,
                            preferred_element_type=jnp.float32) * scale
        mask = q_pos[:, None] >= k_pos[None, :]  # global causal
        return jnp.where(mask[None, None], scores, NEG_INF), mask

    for t in range(n):
        src = (idx - t) % n  # which global block this K/V is
        if kv_codec is not None:
            # every device decodes the payload that just arrived; blocks were
            # quantized exactly once, at home, before the first rotation
            k_blk, v_blk = kv_decode(kv_wire)
        scores, mask = scores_for(k_blk, src)
        v_t = jnp.repeat(v_blk, rep, axis=2) if rep > 1 else v_blk
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None]) * mask[None, None]
        correction = jnp.exp(m - m_new)
        l = l * correction + jnp.sum(p, axis=-1)
        acc = acc * correction[..., None] + jnp.einsum(
            "bhst,bthd->bhsd", p, v_t.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m = m_new
        if t < n - 1:
            if kv_codec is not None:
                # ONE ppermute per step over the packed buffer instead of one
                # per K/V leaf — the quantized-collective trick on the ring
                kv_wire = jax.lax.ppermute(kv_wire, axis_name, ring)
            else:
                k_blk = jax.lax.ppermute(k_blk, axis_name, ring)
                v_blk = jax.lax.ppermute(v_blk, axis_name, ring)

    out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B, H, S_loc, hd)
    out = jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
    if not capture_stats:
        return out

    # second rotation: exact probabilities from the now-final (m, l); the
    # (B, H, S_loc) column-sum / last-row accumulators ride the ring with
    # their K block and land home after n hops
    l_safe = jnp.maximum(l, 1e-30)
    k_blk = k
    if kv_codec is not None:
        from ..codecs.wire_format import flatten_bytes, unflatten_bytes
        kv = k.shape[2]
        k_spec = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), k_payload)
        # rebuild a K-only wire buffer from the home payload saved in pass 1;
        # the f32 accumulators stay raw — they carry exact statistics
        k_wire = flatten_bytes(k_payload)
    col_acc = jnp.zeros((b, h, s_loc), jnp.float32)
    last_acc = jnp.zeros((b, h, s_loc), jnp.float32)
    is_last = (idx == n - 1)  # device holding the globally-last query row
    for t in range(n):
        src = (idx - t) % n
        if kv_codec is not None:
            k_blk = kv_codec.decode(unflatten_bytes(k_wire, k_spec)) \
                .reshape(b, s_loc, kv, hd).astype(k.dtype)
        scores, mask = scores_for(k_blk, src)
        probs = jnp.exp(scores - m[..., None]) * mask[None, None] \
            / l_safe[..., None]  # (B, H, S_loc_q, S_loc_k), exact
        col_acc = col_acc + jnp.sum(probs, axis=2)
        last_acc = last_acc + jnp.where(is_last, probs[:, :, -1, :], 0.0)
        # permute on EVERY step (unlike pass 1) so block and accumulators
        # complete the full circle back to the block's home device
        if kv_codec is not None:
            k_wire = jax.lax.ppermute(k_wire, axis_name, ring)
        else:
            k_blk = jax.lax.ppermute(k_blk, axis_name, ring)
        col_acc = jax.lax.ppermute(col_acc, axis_name, ring)
        last_acc = jax.lax.ppermute(last_acc, axis_name, ring)
    s_total = n * s_loc
    return out, (col_acc / s_total, last_acc)


def _sp_attention(cfg: ModelConfig, lp: dict, x, cos_loc, sin_loc, axis_name,
                  capture_stats: bool = False, kv_codec=None):
    """Per-layer attention with ring communication; x is (B, S_loc, D)."""
    b, s_loc, d = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ lp["wq"]).reshape(b, s_loc, h, hd)
    k = (x @ lp["wk"]).reshape(b, s_loc, kv, hd)
    v = (x @ lp["wv"]).reshape(b, s_loc, kv, hd)
    if "bq" in lp:
        q = q + lp["bq"].reshape(h, hd)
        k = k + lp["bk"].reshape(kv, hd)
        v = v + lp["bv"].reshape(kv, hd)
    q = apply_rotary(q, cos_loc, sin_loc, cfg.rotary_dim)
    k = apply_rotary(k, cos_loc, sin_loc, cfg.rotary_dim)
    # GQA: the unexpanded KV-head blocks circulate the ring; ring_attention
    # broadcasts heads locally per step
    if capture_stats:
        out, stats = ring_attention(q, k, v, axis_name, capture_stats=True,
                                    kv_codec=kv_codec)
    else:
        out, stats = ring_attention(q, k, v, axis_name,
                                    kv_codec=kv_codec), None
    out = out.reshape(b, s_loc, h * hd) @ lp["wo"]
    if "bo" in lp:
        out = out + lp["bo"]
    return out, stats


def _sp_block(cfg: ModelConfig, lp: dict, hidden, cos_loc, sin_loc, axis_name,
              capture_stats: bool = False, kv_codec=None):
    """Decoder block with ring attention; norms/MLP are per-token (trivially SP).
    Returns ``(hidden, stats)`` — stats None unless ``capture_stats``."""
    if cfg.family == "gpt_neox":
        attn_in = _layernorm(hidden, lp["ln1_scale"], lp["ln1_bias"], cfg.norm_eps)
        attn_out, stats = _sp_attention(cfg, lp, attn_in, cos_loc, sin_loc,
                                        axis_name, capture_stats, kv_codec)
        mlp_in = _layernorm(hidden, lp["ln2_scale"], lp["ln2_bias"], cfg.norm_eps)
        return hidden + attn_out + mlp(cfg, lp, mlp_in), stats
    attn_in = _rmsnorm(hidden, lp["ln1_scale"], cfg.norm_eps)
    attn_out, stats = _sp_attention(cfg, lp, attn_in, cos_loc, sin_loc,
                                    axis_name, capture_stats, kv_codec)
    hidden = hidden + attn_out
    mlp_in = _rmsnorm(hidden, lp["ln2_scale"], cfg.norm_eps)
    return hidden + mlp(cfg, lp, mlp_in), stats


@functools.lru_cache(maxsize=None)
def _sp_forward(cfg: ModelConfig, mesh: Mesh, axis_name: str, kv_codec=None):
    @jax.jit
    def fn(params, input_ids):
        seq = input_ids.shape[1]
        if seq % mesh.shape[axis_name]:
            raise ValueError(f"sequence length {seq} not divisible by "
                             f"{axis_name} axis size {mesh.shape[axis_name]}")
        cos, sin = precompute_rope(cfg, seq)

        def body(params, ids_loc, cos_loc, sin_loc):
            hidden = embed(params, ids_loc)  # already ring-varying via ids_loc

            def scan_body(h, lp):
                out, _ = _sp_block(cfg, lp, h, cos_loc, sin_loc, axis_name,
                                   kv_codec=kv_codec)
                return out, None

            hidden, _ = jax.lax.scan(scan_body, hidden, params["layers"])
            return unembed(cfg, params, hidden)

        return shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(None, axis_name), P(axis_name), P(axis_name)),
            out_specs=P(None, axis_name),
        )(params, input_ids, cos, sin)

    return fn


def forward_sp(cfg: ModelConfig, params, input_ids, mesh: Mesh,
               axis_name: str = "seq", kv_codec=None) -> jnp.ndarray:
    """Sequence-parallel forward: ids (B, S) with S sharded over ``axis_name`` ->
    full fp32 logits. Weights replicated, activations 1/n per device, attention
    via the K/V ring. ``kv_codec`` (opt-in, lossy) compresses the circulating
    K/V blocks into a single packed wire buffer per rotation step — see
    :func:`ring_attention`."""
    return _sp_forward(cfg, mesh, axis_name, kv_codec)(
        params, jnp.asarray(input_ids))


@functools.lru_cache(maxsize=None)
def _sp_importance(cfg: ModelConfig, mesh: Mesh, method: str, axis_name: str):
    from ..models.transformer import AttnStats
    from ..importance import importance_per_layer

    @jax.jit
    def fn(params, input_ids, head_weights):
        seq = input_ids.shape[1]
        if seq % mesh.shape[axis_name]:
            raise ValueError(f"sequence length {seq} not divisible by "
                             f"{axis_name} axis size {mesh.shape[axis_name]}")
        cos, sin = precompute_rope(cfg, seq)

        def body(params, hw, ids_loc, cos_loc, sin_loc):
            hidden = embed(params, ids_loc)

            def scan_body(h, lp):
                out, stats = _sp_block(cfg, lp, h, cos_loc, sin_loc, axis_name,
                                       capture_stats=True)
                return out, stats

            _, (col, last) = jax.lax.scan(scan_body, hidden, params["layers"])
            stats = AttnStats(col_mean=col, last_row=last)  # (L, B, H, S_loc)
            # every metric is per-token over reduced stats, so the local
            # shard's importance slice is computable entirely locally
            return importance_per_layer(stats, method, hw)  # (L, B, S_loc)

        return shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(None, axis_name), P(axis_name), P(axis_name)),
            out_specs=P(None, None, axis_name),
        )(params, head_weights, input_ids, cos, sin)

    return fn


def importance_sp(cfg: ModelConfig, params, input_ids, mesh: Mesh,
                  method: str, head_weights=None,
                  axis_name: str = "seq") -> jnp.ndarray:
    """Sequence-parallel importance: the (L, B, S) scores of
    ``importance_per_layer``, computed WITHOUT any device ever holding the
    full sequence — the attention statistics (column sums, last query row) are
    accumulated inside ``ring_attention``'s K rotation and stay sequence-
    sharded; so does the returned importance (a global array sharded on S).

    This is the long-context replacement for the dense stats forward the
    simulate harness uses (``eval/harness.py:_stats_forward``): same methods,
    same values (up to flash-vs-dense softmax roundoff), no O(S^2) buffer and
    no full-S activation anywhere.
    """
    if method == "weighted_importance" and head_weights is None:
        raise ValueError("weighted_importance requires head_weights (L, H)")
    hw = jnp.zeros((cfg.num_layers, cfg.num_heads), jnp.float32) \
        if head_weights is None else jnp.asarray(head_weights)
    return _sp_importance(cfg, mesh, method, axis_name)(
        params, jnp.asarray(input_ids), hw)


# ---------- stage x seq composition ----------


def make_sp_stage_mesh(n_stages: int, n_seq: int, devices=None) -> Mesh:
    """("stage", "seq") mesh: pipeline stages x ring-attention sequence shards."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    need = n_stages * n_seq
    if devices.size < need:
        raise ValueError(f"need {need} devices, have {devices.size}")
    return Mesh(devices.reshape(-1)[:need].reshape(n_stages, n_seq),
                ("stage", "seq"))


class SplitRingRuntime:
    """Pipeline-split forward with each stage's sequence ring-sharded.

    The composition claimed at the top of this module, made concrete: the layer
    stack is cut into stages along "stage" (stage-sharded parameter groups,
    boundary activations crossing by ``ppermute`` exactly like
    ``split.SplitRuntime``) while WITHIN every stage the sequence axis is
    sharded over "seq" and attention runs as the K/V ring. Boundary hops move
    each device's local 1/n_seq sequence shard — with a per-token wire codec,
    the compressed payload — so long contexts never gather onto one device at
    the cut either.

    Hop codecs must be per-token (``batch_invariant``) — their scales reduce
    only over the feature axis, so encoding a sequence shard locally is
    identical to encoding the full sequence — OR explicitly ring-aware
    (:class:`~edgellm_tpu.codecs.ring_codecs.RingWireCodec`): the selective
    mixed-precision codec runs under "seq" by agreeing on ordering and global
    scale across shards with small collectives (an all_gather of the per-token
    importance scalars + a pmax of the scale). Other batch/sequence-reducing
    codecs are rejected.
    """

    def __init__(self, cfg: ModelConfig, cuts, hop_codecs, mesh: Mesh,
                 faults=None, policy=None, fec=None, hedge=None):
        from .split import SplitConfig, apply_default_codec_backend
        from ..codecs.ring_codecs import RingWireCodec
        from ..codecs.faults import FaultConfig, FaultyLink, LinkPolicy

        self.cfg = cfg
        self.mesh = mesh
        self.faults = faults
        self.policy = policy if policy is not None else LinkPolicy()
        self.fec = fec
        self.hedge = hedge
        # same activation rule as SplitRuntime: zero rates build the exact
        # fault-free graph (a disabled FEC/hedge config traces the PR 2 hop)
        self._link = (FaultyLink(faults, self.policy, fec=fec, hedge=hedge)
                      if faults is not None and faults.enabled else None)
        self._counter_accum: list = []
        self._lost_stage = None
        self.split = SplitConfig(cuts=tuple(cuts), hop_codecs=tuple(hop_codecs))
        self.codecs = apply_default_codec_backend(list(self.split.hop_codecs))
        bad = [c.name for c in self.codecs
               if not c.batch_invariant and not isinstance(c, RingWireCodec)]
        if bad:
            raise ValueError(
                f"stage x seq hops need per-token or ring-aware codecs; {bad} "
                f"reduce over batch/sequence and would disagree across "
                f"sequence shards")
        missing = [a for a in ("stage", "seq") if a not in mesh.shape]
        if missing:
            raise ValueError(f"SplitRingRuntime needs a mesh with 'stage' and "
                             f"'seq' axes (got {tuple(mesh.shape)}, missing "
                             f"{missing}); build a ('stage', 'seq') mesh")
        if mesh.shape["stage"] != self.split.n_stages:
            raise ValueError(f"mesh has {mesh.shape['stage']} stages, split "
                             f"needs {self.split.n_stages}")
        for c in self.codecs:
            if isinstance(c, RingWireCodec) and (c.ring_axis != "seq"
                                                 or c.n_seq != mesh.shape["seq"]):
                raise ValueError(
                    f"ring codec {c.name} was built for axis "
                    f"{c.ring_axis!r} x{c.n_seq}, mesh has 'seq' "
                    f"x{mesh.shape['seq']}")
        self.bounds = self.split.stage_bounds(cfg.num_layers)
        self.stage_size = max(stop - start for start, stop in self.bounds)
        self._forward = self._build_forward()

    def mark_stage_lost(self, stage: int) -> None:
        """Same contract as ``SplitRuntime.mark_stage_lost``: subsequent
        forwards raise the typed ``StageLostError``. (Failover re-planning
        for the stage x seq composition is not implemented — the eval driver
        rejects ``stage_failure`` with ``n_seq > 1`` up front.)"""
        if not 0 <= stage < self.split.n_stages:
            raise ValueError(f"stage {stage} out of range for "
                             f"{self.split.n_stages} stages")
        self._lost_stage = stage

    def place_params(self, params: dict) -> dict:
        """Stage-shard the stacked layer groups, replicate the rest (same
        regrouping as the split runtime; no "model"/"data" axes here)."""
        from jax.sharding import NamedSharding

        from .split import regroup_layers

        groups, valid = regroup_layers(params["layers"], self.bounds, self.stage_size)
        stage_spec = NamedSharding(self.mesh, P("stage"))
        repl = NamedSharding(self.mesh, P())
        placed = {
            "layers": {k: jax.device_put(v, stage_spec) for k, v in groups.items()},
            "layers_valid": jax.device_put(valid, stage_spec),
        }
        for k, v in params.items():
            if k != "layers":
                placed[k] = jax.device_put(v, repl)
        return placed

    def _build_forward(self):
        from .split import run_pipeline_stages

        cfg, n_stages = self.cfg, self.split.n_stages
        codecs, mesh = self.codecs, self.mesh
        link = self._link

        def body(local_layers, local_valid, other, ids_loc, cos_loc, sin_loc,
                 hop_imps, fault_step=None):
            lv = {k: v[0] for k, v in local_layers.items()}
            valid = local_valid[0]
            hidden = embed(other, ids_loc)  # (B, S_loc, D), seq-sharded

            def scan_body(h, xs):
                lp, ok = xs
                out, _ = _sp_block(cfg, lp, h, cos_loc, sin_loc, "seq")
                return jnp.where(ok, out, h), None

            def run_stage(h):
                computed, _ = jax.lax.scan(scan_body, h, (lv, valid))
                return computed

            # the shared hop protocol moves each device's local seq shard
            # (per-token codecs encode shard-locally == full-sequence encode;
            # ring-aware selective codecs agree on ordering/scale via their
            # own small collectives over "seq")
            if link is None:
                hidden = run_pipeline_stages(n_stages, codecs, run_stage,
                                             hidden, hop_imps)
                return unembed(cfg, other, hidden)
            # each seq shard ships its OWN payload across the cut, so each
            # gets its own fault stream (fold the shard index into the key);
            # counters then sum over both axes — stage hops x seq shards
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.key(link.faults.seed),
                                   fault_step),
                jax.lax.axis_index("seq"))
            hidden, counters = run_pipeline_stages(
                n_stages, codecs, run_stage, hidden, hop_imps,
                link=link, fault_key=key)
            counters = {k: jax.lax.psum(v, "seq") for k, v in counters.items()}
            return unembed(cfg, other, hidden), counters

        @jax.jit
        def fn(placed, input_ids, hop_imps, fault_step=None):
            seq = input_ids.shape[1]
            if seq % mesh.shape["seq"]:
                raise ValueError(f"sequence length {seq} not divisible by seq "
                                 f"axis size {mesh.shape['seq']}")
            cos, sin = precompute_rope(cfg, seq)
            other = {k: v for k, v in placed.items()
                     if k not in ("layers", "layers_valid")}
            lspecs = jax.tree_util.tree_map(lambda _: P("stage"), placed["layers"])
            # importance shards ride the seq axis on the token dimension, like
            # the hidden: (n_hops, B, S) or (n_hops, S)
            imp_spec = P(None, None, "seq") if hop_imps.ndim == 3 else P(None, "seq")
            if link is None:
                return shard_map(
                    body, mesh=mesh,
                    in_specs=(lspecs, P("stage"), P(), P(None, "seq"), P("seq"),
                              P("seq"), imp_spec),
                    out_specs=P(None, "seq"),
                    check_vma=False,
                )(placed["layers"], placed["layers_valid"], other, input_ids,
                  cos, sin, hop_imps)
            return shard_map(
                body, mesh=mesh,
                in_specs=(lspecs, P("stage"), P(), P(None, "seq"), P("seq"),
                          P("seq"), imp_spec, P()),
                out_specs=(P(None, "seq"), P()),
                check_vma=False,
            )(placed["layers"], placed["layers_valid"], other, input_ids,
              cos, sin, hop_imps, fault_step)

        return fn

    def hop_bytes(self, batch: int, seq: int) -> list:
        """Measured payload bytes per hop for one (batch, seq, D) activation
        (sum over the ``n_seq`` local-shard payloads; see
        ``split.hop_payload_bytes``)."""
        from .split import hop_payload_bytes

        return hop_payload_bytes(self.codecs, self.cfg, batch, seq)

    def bytes_per_token(self, seq: int) -> list:
        """Per-hop boundary bytes per token (the BASELINE.json metric)."""
        return [b / seq for b in self.hop_bytes(1, seq)]

    def decode_hop_bytes(self, batch: int) -> list:
        """No per-token decode surface on the ring runtime (it is a
        whole-window forward) — nothing crosses a wire per decode step.
        Present so the runtime satisfies the
        :class:`~edgellm_tpu.obs.metrics.CounterSource` protocol."""
        return []

    def time_hops(self, batch: int, seq: int, iters: int = 20) -> list:
        """Per-hop transfer time (ms) with the probe activation seq-sharded the
        way the runtime's hops actually move it (each device sends its local
        shard in parallel)."""
        from .split import measure_hop_times

        if seq % self.mesh.shape["seq"]:
            raise ValueError(f"seq {seq} not divisible by the seq axis "
                             f"({self.mesh.shape['seq']})")
        return measure_hop_times(self.mesh, self.codecs, self.cfg, batch, seq,
                                 iters=iters, hidden_spec=P(None, "seq"))

    def forward(self, placed_params: dict, input_ids,
                hop_importance: Optional[list] = None,
                fault_step: int = 0) -> jnp.ndarray:
        """ids (B, S) -> full fp32 logits; layers stage-split, sequence
        ring-sharded, boundary hops carry packed per-token payload shards.

        ``hop_importance``: one (S,) / (B, S) entry per hop for ring-aware
        selective codecs (``needs_importance``); arrays may be global
        seq-sharded outputs of :func:`importance_sp` — the runtime shards them
        over "seq" alongside the hidden, and the codec's own collectives
        reconstruct the global ordering.

        ``fault_step``: per-call fault-PRNG fold (see
        ``SplitRuntime.forward``); each sequence shard additionally folds its
        shard index, so shards draw independent faults. Counters accumulate on
        the runtime — read with :meth:`link_counters`."""
        if self._lost_stage is not None:
            from ..serve.recovery import StageLostError

            raise StageLostError(self._lost_stage)
        input_ids = jnp.asarray(input_ids)
        batch, seq = input_ids.shape
        n_hops = len(self.codecs)
        imps = list(hop_importance) if hop_importance is not None \
            else [None] * n_hops
        if len(imps) != n_hops:
            raise ValueError(f"expected {n_hops} hop_importance entries, "
                             f"got {len(imps)}")
        for c, imp in zip(self.codecs, imps):
            if c.needs_importance and imp is None:
                raise ValueError(f"hop codec {c.name} requires an importance "
                                 f"vector")
            if c.needs_importance and batch > 1 and (
                    jnp.ndim(imp) != 2 or jnp.shape(imp)[0] != batch):
                raise ValueError(
                    f"hop codec {c.name} with batch {batch} needs per-row "
                    f"({batch}, S) importance (got shape {jnp.shape(imp)})")
        per_row = any(i is not None and jnp.ndim(i) == 2 for i in imps) or (
            batch > 1 and any(c.needs_importance for c in self.codecs))
        blank = jnp.zeros((batch, seq) if per_row else (seq,), jnp.float32)
        stacked = (jnp.zeros((0,) + blank.shape, jnp.float32) if not imps else
                   jnp.stack([blank if i is None
                              else jnp.broadcast_to(jnp.asarray(i, jnp.float32),
                                                    blank.shape)
                              for i in imps]))
        if self._link is None:
            return self._forward(placed_params, input_ids, stacked)
        logits, counters = self._forward(placed_params, input_ids, stacked,
                                         jnp.asarray(fault_step, jnp.int32))
        self._counter_accum.append(counters)
        return logits

    def link_counters(self, reset: bool = False) -> Optional[dict]:
        """Per-hop fault counters summed over all forward calls and all
        sequence shards: {name: (n_hops,) int64}. None when faults are off."""
        from ..codecs.faults import sum_counters

        if self._link is None:
            return None
        tot = sum_counters(self._counter_accum)
        if tot is None:
            n_hops = len(self.codecs)
            tot = {k: np.zeros((n_hops,), np.int64)
                   for k in self._link.init_counters(n_hops)}
        if reset:
            self._counter_accum = []
        return tot
