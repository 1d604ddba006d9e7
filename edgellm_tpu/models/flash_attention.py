"""Whole-sequence-in-VMEM causal attention kernel for small-head models.

Why this exists: the flagship qwen2-0.5b has ``head_dim=64`` — half the MXU
lane width — and at the sweep's shapes (S=512, B*R up to 256 rows) XLA's
fused ``jax.nn.dot_product_attention`` measures ~18 TF/s on the v5e while the
same chip does 194 TF/s on big matmuls; the generic Pallas flash/splash
kernels (built for long S, hd>=128) measure slower still. This kernel takes
the opposite design point: at S <= 1024 the ENTIRE (S, S) score matrix of one
(batch, head) pair fits VMEM, so each grid step computes
scores -> causal mask -> softmax -> PV in one pass with zero HBM traffic for
intermediates — no flash blocking, no online-softmax recurrence.

Every rate below was taken in rounds 2-5 (2026-07) on older code and has NOT
been re-measured since; PERF.md holds what the chip has shown since, with its
origin. What PR 21's first run on a v5e did establish, at the shapes serving
actually sends: float32 weights put Qwen2-0.5B on the BLOCKED kernel with
``qb = s`` for every prompt length <= 1024 (not the whole-S bf16 kernel timed
below), and it compiles and matches the dense fp32 formulation at
qb in {12, 100, 512, 1024}, plain and stats (``tools/attn_probe.parity_shape``).

Measured design notes (differential-scan timings on the v5e, round 4):

- the big (S, hd) x (hd, S) ops are what the MXU wants: in-kernel fori flash
  tiling measured 27 TF/s (T=2) / 14 TF/s (T=4), and a 2-way causal split
  (25% fewer flops but 2x smaller matmuls) measured 33 TF/s — all SLOWER
  than the 43-46 TF/s untiled full square, so the causal upper triangle is
  deliberately computed and masked;
- all ``rep = H // KV`` query heads of one KV group run per grid step: K/V
  are fetched once per group (the GQA broadcast costs no HBM traffic) and
  the longer step amortizes grid overhead (43.5 -> 45.9 TF/s);
- per-matmul anatomy: QK alone 34 TF/s, PV alone 31 TF/s, both overlap to
  ~45-50 — the kernel is MXU-bound at the hd=64 padding limit, softmax adds
  only ~15%;
- q and the output stay PACKED as (B, S, H*hd) — the natural projection
  layout — with heads as static column slices of the block, so the two big
  (B, S, H, hd) <-> (B, H, S, hd) transposes never exist (38.3 -> 43.5 TF/s
  end-to-end at the sweep's 256-row batches); only the KV/H-fold smaller K/V
  are transposed.

Net: ~2.4x XLA's fused attention at the flagship shapes (43.5 TF/s vs 18.4
at B=256), measured end-to-end from the model's layout.

Round 5 extends the envelope with a second, BLOCKED kernel (same design
language, two independent splits — see ``_attn_blocked_kernel``) covering
the reference's own Pythia evaluation window (S=2048,
``Experiments/Pythia-70M/initial_exp.py:86``) and wide packed rows
(llama-1b's 2048). Measured on the v5e (``tools/attn_probe.py``,
interleaved-pair median vs XLA's fused attention, bf16):

===================  ======================  ========  =======  =========
shape                plan                    Pallas    XLA      speedup
===================  ======================  ========  =======  =========
pythia-70m  S=2048   blocked (qb512, hps8)   59 TF/s   21 TF/s  2.81x
qwen2-0.5b  S=2048   blocked (qb512, hps14)  56 TF/s   22 TF/s  2.51x
llama-1b    S=512    blocked (qb512, hps16)  52 TF/s   20 TF/s  2.65x
qwen2-0.5b  S=512    whole-S (regression)    54 TF/s   20 TF/s  2.77x
qwen2-1.5b  S=512    whole-S (regression)    88 TF/s   20 TF/s  4.31x
===================  ======================  ========  =======  =========

The stats variants measure within 3-5% of the plain kernels at every shape
(fused stats capture stays ~free); blocked-kernel outputs match the dense
formulation to bf16 tolerance and its stats to <=2e-9 on silicon.

The stats variant additionally emits the column-sum and last-query-row
statistics the importance metrics consume (``AttnStats``), read directly off
the in-VMEM probability matrix — the fused replacement for the blocked-scan
stats capture in ``transformer.attention`` (reference constraint: a SECOND
eager model instance just to get attention maps,
``Experiments/Pythia-70M/last_row_exp.py:66-70``).
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: one head's in-flight score/prob matrices must fit VMEM alongside the
#: double-buffered blocks; S=1024 (4 MB fp32 scores) compile- and run-checked
#: on the v5e (only one head's matrices are live at a time — Mosaic schedules
#: the rest), S=2048 (16 MB) cannot fit — longer sequences take the
#: query-blocked kernel instead
MAX_WHOLE_S = 1024
#: widest packed q/out row validated on silicon for the whole-S all-heads
#: kernel: dh=896 (flagship, 2.4x XLA) and dh=1536 (qwen2-1.5b hd=128,
#: 3.45x XLA); wider rows (llama-1b's 2048) take the head-group-split
#: blocked kernel, which keeps only ``hps*hd`` packed columns live per step
MAX_PACKED_DH = 1536
#: query-block rows for the blocked kernel at S > MAX_WHOLE_S: a 512-row
#: block's scores are 512 x S fp32 = 4 MB at S=2048 — same VMEM budget the
#: whole-S kernel was validated at. Rows stay COMPLETE (every key visible),
#: so per-row softmax is exact and stats capture needs no online rescaling.
QBLOCK = 512
#: longest sequence for the blocked kernel (S=2048 covers the reference's
#: own Pythia evaluation window, Experiments/Pythia-70M/initial_exp.py:86,
#: and the repo's long-context ring config)
MAX_BLOCKED_S = 2048
#: head dims compile- and run-checked on silicon (ADVICE r4: an unvalidated
#: hd such as 80 must fall back to XLA, not silently take the kernel)
VALIDATED_HD = (64, 128)
#: largest per-step resident K (and V) block for the blocked kernel —
#: kvps * S * hd * 2 bytes. 2 MB is the silicon-validated worst case
#: (pythia-70m MHA at S=2048: 8 KV heads x 2048 x 64 bf16); wider MHA
#: groups shrink hps until the K/V blocks fit, rather than compiling a
#: never-validated VMEM footprint on the default path
MAX_KV_BYTES = 2 * 1024 * 1024


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _shape_plan(s: int, h: int, kv: int, hd: int, itemsize: int = 2):
    """Which kernel handles an (S, H, KV, hd) attention shape, ignoring
    backend/eligibility gating: ``("whole", None)`` — the all-heads-per-step
    whole-S kernel; ``("blocked", (qb, hps))`` — the query-blocked,
    head-group-split kernel with ``qb`` query rows and ``hps`` heads per grid
    step; ``None`` — no kernel covers the shape (XLA fused path).
    ``itemsize`` is the activation dtype's bytes (2 = bf16, the validated
    default); fp32 halves the K/V budget so the gate tracks the REAL
    resident footprint, not a bf16 assumption.

    Raises on ragged GQA (``h % kv``): both kernels iterate whole KV groups,
    so a ragged layout would silently leave head columns unwritten — callers
    that want a soft fallback gate through :func:`kernel_plan`."""
    if h % kv:
        raise ValueError(f"kernels need head-aligned GQA, got H={h}, KV={kv}")
    dh = h * hd
    # the whole-S envelope constants were validated at bf16; wider activation
    # dtypes double the resident score/probs and packed-row bytes, so the
    # eligibility window shrinks with itemsize (ADVICE r5 #1) — shapes that
    # fall out land on the blocked branch, whose hps search already budgets
    # the resident K/V blocks by itemsize
    scale = max(itemsize, 2) // 2
    if s <= MAX_WHOLE_S // scale and dh <= MAX_PACKED_DH // scale:
        return ("whole", None)
    if s > MAX_BLOCKED_S:
        return None
    qb = s if s <= MAX_WHOLE_S else QBLOCK
    if s % qb:
        return None
    rep = h // kv
    # largest head group that divides H, keeps KV groups whole (multiple of
    # rep), fits the validated packed width, AND keeps the per-step resident
    # K/V blocks inside the silicon-validated footprint
    hps = next((c for c in range(h, 0, -1)
                if h % c == 0 and c % rep == 0 and c * hd <= MAX_PACKED_DH
                and (c // rep) * s * hd * itemsize <= MAX_KV_BYTES),
               None)
    if hps is None:
        return None
    return ("blocked", (qb, hps))


def kernel_plan(s: int, h: int, kv: int, hd: int, itemsize: int = 2):
    """The kernel plan for this shape where the Pallas path handles it, else
    None (XLA fused path): a TPU, a silicon-validated head_dim, head-aligned
    GQA, and a shape one of the two kernels covers."""
    if not _on_tpu() or hd not in VALIDATED_HD or h % kv:
        return None
    return _shape_plan(s, h, kv, hd, itemsize)


def _head_attn(q, k, v, row0=0):
    """One head's causal attention for a (possibly partial) block of query
    rows against the FULL key set, entirely in VMEM -> (out, probs).
    ``row0`` is the global position of the first query row; every row is
    complete (all keys present), so the per-row softmax is exact."""
    sq, hd = q.shape
    sk = k.shape[0]
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (1.0 / np.sqrt(hd))
    row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + row0
    col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    scores = jnp.where(row >= col, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jax.lax.dot_general(
        p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return out.astype(q.dtype), p


def _attn_packed_kernel(q_ref, k_ref, v_ref, o_ref, *, hd):
    """Grid (B,): one batch row, every head, PACKED (S, H*hd) q/out layout.

    NOTE: the whole-S family (this kernel + its stats twin) is the qb=S,
    hps=H special case of the blocked family below — any fix to masking,
    dtype casting, or stats capture must land in BOTH. They stay separate
    until a silicon probe confirms the blocked kernel's 3-D grid costs
    nothing at the validated whole-S shapes (the round-4 measurements that
    earned this kernel were taken on the 1-D grid; collapsing without that
    probe would silently re-litigate them).

    The packed layout is the natural shape of the QKV projection output, so
    the (B, S, H, hd) -> (B, H, S, hd) transpose of q and of the output —
    hundreds of MB each way per layer at the sweep's 256-row batches — never
    exists; each head is a STATIC column slice of the block. K/V still use
    the (B, KV, S, hd) layout (their transpose is KV/H-fold smaller)."""
    kv = k_ref.shape[1]
    rep = (q_ref.shape[2] // hd) // kv
    for j in range(kv):
        k = k_ref[0, j]
        v = v_ref[0, j]
        for g in range(rep):
            c0 = (j * rep + g) * hd
            out, _ = _head_attn(q_ref[0, :, c0:c0 + hd], k, v)
            o_ref[0, :, c0:c0 + hd] = out.astype(o_ref.dtype)


def _attn_packed_stats_kernel(q_ref, k_ref, v_ref, o_ref, col_ref, last_ref,
                              *, hd):
    kv = k_ref.shape[1]
    rep = (q_ref.shape[2] // hd) // kv
    s = k_ref.shape[2]
    for j in range(kv):
        k = k_ref[0, j]
        v = v_ref[0, j]
        for g in range(rep):
            c0 = (j * rep + g) * hd
            out, p = _head_attn(q_ref[0, :, c0:c0 + hd], k, v)
            o_ref[0, :, c0:c0 + hd] = out.astype(o_ref.dtype)
            col_ref[0, j * rep + g, 0] = jnp.sum(p, axis=0) * (1.0 / s)
            last_ref[0, j * rep + g, 0] = p[s - 1, :]


@functools.partial(jax.jit, static_argnames=("hd", "interpret"))
def _attn_packed(q2, kt, vt, hd: int, interpret: bool):
    """q2 (B, S, H*hd) packed; kt/vt (B, KV, S, hd) -> out (B, S, H*hd)."""
    b, s, dh = q2.shape
    kv = kt.shape[1]
    spec_q = pl.BlockSpec((1, s, dh), lambda i: (i, 0, 0))
    spec_kv = pl.BlockSpec((1, kv, s, hd), lambda i: (i, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_attn_packed_kernel, hd=hd),
        grid=(b,),
        in_specs=[spec_q, spec_kv, spec_kv],
        out_specs=spec_q,
        out_shape=jax.ShapeDtypeStruct((b, s, dh), q2.dtype),
        interpret=interpret,
    )(q2, kt, vt)


@functools.partial(jax.jit, static_argnames=("hd", "interpret"))
def _attn_packed_stats(q2, kt, vt, hd: int, interpret: bool):
    b, s, dh = q2.shape
    kv = kt.shape[1]
    h = dh // hd
    spec_q = pl.BlockSpec((1, s, dh), lambda i: (i, 0, 0))
    spec_kv = pl.BlockSpec((1, kv, s, hd), lambda i: (i, 0, 0, 0))
    spec_s = pl.BlockSpec((1, h, 1, s), lambda i: (i, 0, 0, 0))
    out, col, last = pl.pallas_call(
        functools.partial(_attn_packed_stats_kernel, hd=hd),
        grid=(b,),
        in_specs=[spec_q, spec_kv, spec_kv],
        out_specs=[spec_q, spec_s, spec_s],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, dh), q2.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
        interpret=interpret,
    )(q2, kt, vt)
    return out, col[:, :, 0, :], last[:, :, 0, :]


def _attn_blocked_kernel(q_ref, k_ref, v_ref, o_ref, *, hd):
    """Grid (B, H//hps, S//qb): one query block x one head group per step.

    Two independent splits extend the whole-S kernel's envelope:

    - query blocking (qb < S): only a (qb, S) score slab is live — 4 MB fp32
      at the validated qb=512/S=2048 point — while the FULL K/V of the head
      group stays resident, so every query row still sees all its keys and
      the per-row softmax is exact (no online-softmax recurrence, no
      flash-style rescaling);
    - head-group splitting (hps < H): only ``hps*hd`` packed q/out columns
      ride per step, bringing wide rows (llama-1b's 2048) inside the
      envelope. Groups are KV-aligned (hps a multiple of rep), so K/V are
      still fetched once per GQA group.

    The causal upper triangle is computed and masked, exactly like the
    whole-S kernel — measured on the v5e (round 4): the big (qb, hd) x
    (hd, S) ops beat any in-kernel tiling that skips masked work."""
    t = pl.program_id(2)
    qb = q_ref.shape[1]
    kvps = k_ref.shape[1]
    rep = (q_ref.shape[2] // hd) // kvps
    for j in range(kvps):
        k = k_ref[0, j]
        v = v_ref[0, j]
        for g in range(rep):
            c0 = (j * rep + g) * hd
            out, _ = _head_attn(q_ref[0, :, c0:c0 + hd], k, v, row0=t * qb)
            o_ref[0, :, c0:c0 + hd] = out.astype(o_ref.dtype)


def _attn_blocked_stats_kernel(q_ref, k_ref, v_ref, o_ref, col_ref, last_ref,
                               *, hd, nt):
    """Blocked kernel + stats. col/last blocks are indexed (i, j) — constant
    in the innermost grid dim t — so the same VMEM block is revisited across
    consecutive query blocks: col accumulates (init at t=0), last_row is
    written by the final block (global row S-1 lives there). Rows are
    complete per block, so both stats are exact, not rescaled estimates."""
    t = pl.program_id(2)
    qb = q_ref.shape[1]
    kvps = k_ref.shape[1]
    s = k_ref.shape[2]
    rep = (q_ref.shape[2] // hd) // kvps
    for j in range(kvps):
        k = k_ref[0, j]
        v = v_ref[0, j]
        for g in range(rep):
            c0 = (j * rep + g) * hd
            out, p = _head_attn(q_ref[0, :, c0:c0 + hd], k, v, row0=t * qb)
            o_ref[0, :, c0:c0 + hd] = out.astype(o_ref.dtype)
            hl = j * rep + g
            part = jnp.sum(p, axis=0) * (1.0 / s)

            @pl.when(t == 0)
            def _init():
                col_ref[0, hl, 0] = part

            @pl.when(t > 0)
            def _accum():
                col_ref[0, hl, 0] = col_ref[0, hl, 0] + part

            @pl.when(t == nt - 1)
            def _last():
                last_ref[0, hl, 0] = p[qb - 1, :]


@functools.partial(jax.jit, static_argnames=("hd", "qb", "hps", "interpret"))
def _attn_blocked(q2, kt, vt, hd: int, qb: int, hps: int, interpret: bool):
    b, s, dh = q2.shape
    kv = kt.shape[1]
    rep = (dh // hd) // kv
    kvps = hps // rep
    grid = (b, (dh // hd) // hps, s // qb)
    spec_q = pl.BlockSpec((1, qb, hps * hd), lambda i, j, t: (i, t, j))
    spec_kv = pl.BlockSpec((1, kvps, s, hd), lambda i, j, t: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_attn_blocked_kernel, hd=hd),
        grid=grid,
        in_specs=[spec_q, spec_kv, spec_kv],
        out_specs=spec_q,
        out_shape=jax.ShapeDtypeStruct((b, s, dh), q2.dtype),
        interpret=interpret,
    )(q2, kt, vt)


@functools.partial(jax.jit, static_argnames=("hd", "qb", "hps", "interpret"))
def _attn_blocked_stats(q2, kt, vt, hd: int, qb: int, hps: int,
                        interpret: bool):
    b, s, dh = q2.shape
    kv = kt.shape[1]
    h = dh // hd
    rep = h // kv
    kvps = hps // rep
    nt = s // qb
    grid = (b, h // hps, nt)
    spec_q = pl.BlockSpec((1, qb, hps * hd), lambda i, j, t: (i, t, j))
    spec_kv = pl.BlockSpec((1, kvps, s, hd), lambda i, j, t: (i, j, 0, 0))
    spec_s = pl.BlockSpec((1, hps, 1, s), lambda i, j, t: (i, j, 0, 0))
    out, col, last = pl.pallas_call(
        functools.partial(_attn_blocked_stats_kernel, hd=hd, nt=nt),
        grid=grid,
        in_specs=[spec_q, spec_kv, spec_kv],
        out_specs=[spec_q, spec_s, spec_s],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, dh), q2.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
        interpret=interpret,
    )(q2, kt, vt)
    return out, col[:, :, 0, :], last[:, :, 0, :]


def _resolve(q, k, plan):
    b, s, h, hd = q.shape
    if plan is None:
        plan = _shape_plan(s, h, k.shape[2], hd,
                           itemsize=jnp.dtype(q.dtype).itemsize)
        if plan is None:
            raise ValueError(
                f"no kernel covers S={s}, H={h}, KV={k.shape[2]}, hd={hd}")
    return plan


def causal_attention(q, k, v, *, interpret: bool | None = None, plan=None):
    """Causal attention from the model's (B, S, H, hd) layout; K/V may carry
    fewer (grouped-query) heads. Returns (B, S, H, hd).

    q rides through the kernel PACKED as (B, S, H*hd) — a free reshape of the
    projection output, no transpose; only the small K/V get transposed.
    ``plan`` (from :func:`kernel_plan`) picks whole-S vs blocked; resolved
    from the shape when omitted."""
    if interpret is None:
        interpret = _use_interpret()
    kind, args = _resolve(q, k, plan)
    b, s, h, hd = q.shape
    q2 = q.reshape(b, s, h * hd)
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if kind == "whole":
        out = _attn_packed(q2, kt, vt, hd, interpret)
    else:
        out = _attn_blocked(q2, kt, vt, hd, args[0], args[1], interpret)
    return out.reshape(b, s, h, hd)


def causal_attention_stats(q, k, v, *, interpret: bool | None = None,
                           plan=None):
    """Causal attention + (col_sum/S, last_row) stats, from (B, S, H, hd).
    Returns (out (B, S, H, hd), (col_sum (B, H, S), last_row (B, H, S)))."""
    if interpret is None:
        interpret = _use_interpret()
    kind, args = _resolve(q, k, plan)
    b, s, h, hd = q.shape
    q2 = q.reshape(b, s, h * hd)
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if kind == "whole":
        out, col, last = _attn_packed_stats(q2, kt, vt, hd, interpret)
    else:
        out, col, last = _attn_blocked_stats(q2, kt, vt, hd, args[0], args[1],
                                             interpret)
    return out.reshape(b, s, h, hd), (col, last)


# ---------------------------------------------------------------------------
# Decode attention: q_len=1 against a length-masked KV cache.
# ---------------------------------------------------------------------------


#: KV-at-rest storage tiers for the paged pool (models/paged_kv.py): pages
#: hold packed int codes plus one fp32 scale per (token row, KV head), the
#: same per-channel shapes the wire codecs compress — applied at rest.
#: "fp" is the uncompressed tier and builds the exact pre-quantization graph.
KV_REST_TIERS = ("fp", "int8_per_channel", "int4_per_channel")


def _kv_quant_spec(kv_codec: str) -> float:
    """Integer span of a quantized KV tier (codes live in [-qmax, qmax])."""
    if kv_codec == "int8_per_channel":
        return 127.0
    if kv_codec == "int4_per_channel":
        return 7.0
    raise ValueError(f"unknown KV-at-rest tier {kv_codec!r}; quantized "
                     f"options: {[t for t in KV_REST_TIERS if t != 'fp']}")


def quantize_kv_rows(x, kv_codec: str):
    """Quantize K or V rows per (token, KV head) over the ``hd`` lanes:
    x (..., KV, hd) -> (codes, scales (..., KV) fp32).

    The scale is each row's absmax — one fp32 per row per head, so a page
    append touches only its own row's codes and scale (whole-page scales
    would force a page requantize on every decode write). int8 codes are
    (..., KV, hd) int8; int4 codes pack lane ``i`` with lane ``i + hd/2``
    into one uint8 (..., KV, hd//2), the contiguous-half pairing the wire
    codecs use. An all-zero row quantizes to zero codes with scale 0, which
    dequantizes back to exact zeros (the trash page stays finite)."""
    qmax = _kv_quant_spec(kv_codec)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    safe = jnp.where(amax > 0, amax, 1.0)
    codes = jnp.round(xf / safe[..., None] * qmax).astype(jnp.int8)
    if kv_codec == "int4_per_channel":
        half = x.shape[-1] // 2
        u = (codes + 8).astype(jnp.uint8)  # [-8, 7] -> [0, 15]
        codes = u[..., :half] | (u[..., half:] << 4)
    return codes, amax


def dequantize_kv_rows(codes, scales, kv_codec: str, dtype=jnp.float32):
    """Invert :func:`quantize_kv_rows`: codes (..., KV, hdc) + scales
    (..., KV) -> (..., KV, hd) in ``dtype``. The paged read and the
    reference path of the numerical-equivalence contract both run exactly
    this expression, so gather-then-dequantize equals dequantize-then-gather
    bit for bit (the op is elementwise per row)."""
    qmax = _kv_quant_spec(kv_codec)
    if kv_codec == "int4_per_channel":
        lo = (codes & 0xF).astype(jnp.int8) - 8
        hi = ((codes >> 4) & 0xF).astype(jnp.int8) - 8
        c = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
    else:
        c = codes.astype(jnp.float32)
    return (c * (scales[..., None] / qmax)).astype(dtype)


def decode_attention(q, k_cache, v_cache, length, window: int = 0):
    """Single-position attention against a cache: q (B, 1, H, hd) vs
    k/v_cache (B, capacity, KV, hd) of which the first ``length`` positions
    are valid — the last ``window`` of them where ``window`` (static) is not
    0: a sliding layer's band (``length`` is a traced scalar — one executable per capacity,
    one fill level for the whole batch: the contiguous decode path; the
    paged pool's ragged twin, over rows as the pool stores them, is
    ``models.paged_kv.attend_rows``).
    Returns (B, 1, H, hd) in q's dtype; softmax in fp32.

    GQA broadcasting happens here, not in the cache: the per-group einsum
    reads each KV head once and applies it to its ``rep`` query heads, so
    the cache stays at num_kv_heads width (the whole point of GQA at decode
    time — the cache read IS the bottleneck).
    """
    b, s1, h, hd = q.shape
    kv = k_cache.shape[2]
    rep = h // kv
    if s1 != 1:
        raise ValueError(f"decode_attention is q_len=1 only, got q_len={s1}")
    if h % kv:
        raise ValueError(f"ragged GQA: H={h}, KV={kv}")
    # no kernel plan to consult: one query row leaves the MXU idle and the
    # step is bound by the K/V read, where XLA's fused path is at the
    # bandwidth roofline
    # head j*rep+g attends KV group j — the same packing convention as the
    # prefill kernels' column slices (c0 = (j*rep+g)*hd)
    qg = q[:, 0].reshape(b, kv, rep, hd)
    scores = jnp.einsum("bgrd,bcgd->bgrc", qg, k_cache,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / np.sqrt(hd))
    valid = jnp.arange(k_cache.shape[1]) < length  # (capacity,)
    if window:
        valid &= jnp.arange(k_cache.shape[1]) >= length - window
    scores = jnp.where(valid[None, None, None, :], scores,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrc,bcgd->bgrd", probs.astype(q.dtype), v_cache,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out.reshape(b, 1, h, hd)


def verify_attention(q, k_cache, v_cache, length):
    """q_len=k attention against a cache for speculative verify: q
    (B, K, H, hd) holds K consecutive positions whose K/V were just written
    at cache rows ``length .. length+K-1``, so query row j attends cache
    positions ``[0, length + j]`` — the per-query causal mask is the only
    difference from :func:`decode_attention`, whose einsum/mask/softmax
    structure this clones with the K axis kept. ``length`` is a traced
    scalar (the pre-write fill level). At K=1 this reduces exactly to
    ``decode_attention(q, k_cache, v_cache, length + 1)``.
    Returns (B, K, H, hd) in q's dtype; softmax in fp32.
    """
    b, kq, h, hd = q.shape
    kv = k_cache.shape[2]
    rep = h // kv
    if h % kv:
        raise ValueError(f"ragged GQA: H={h}, KV={kv}")
    # no kernel plan to consult: the verify shape is (tiny K) x (cache read),
    # the same HBM-bound regime as decode_attention's
    qg = q.reshape(b, kq, kv, rep, hd)
    scores = jnp.einsum("bqgrd,bcgd->bqgrc", qg, k_cache,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / np.sqrt(hd))
    # query row j sees positions < length + j + 1 (its own row included)
    valid = (jnp.arange(k_cache.shape[1])[None, :]
             < (length + jnp.arange(kq)[:, None] + 1))  # (K, capacity)
    scores = jnp.where(valid[None, :, None, None, :], scores,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bqgrc,bcgd->bqgrd", probs.astype(q.dtype), v_cache,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out.reshape(b, kq, h, hd)


# ---------------------------------------------------------------------------
# Paged decode attention: q_len=1 per slot against pages read where they lie.
# ---------------------------------------------------------------------------

#: rows one block of the page walk holds in VMEM: the unit a slot's pages are
#: fetched, waited for and attended in. PERF.md §6 "PR 33" has the v5e's
#: timings from 64 to 1024 rows.
WALK_BLOCK_ROWS = 512


def paged_walk_pages_per_block(page_size: int, width: int,
                               itemsize: int) -> int:
    """Pages a block of :func:`paged_decode_walk` holds over a K/V pool whose
    K lanes are ``width`` wide (a row is ``2 * width``): ``WALK_BLOCK_ROWS``
    rows, fewer where the row is wide, so that the two buffers stay inside
    4 MB of VMEM."""
    rows = min(WALK_BLOCK_ROWS, (1 << 20) // (width * itemsize))
    return max(rows // page_size, 1)


def ring_walk_pages_per_block(entries: int, page_size: int, width: int,
                              itemsize: int) -> int:
    """Pages a block of a RING walk (:func:`paged_decode_walk`, ``window``)
    holds. A ring that has turned is fetched whole, every step, and a ring
    is ``window / page_size + 1`` entries: one over a multiple of
    :func:`paged_walk_pages_per_block`'s answer wherever both are powers of
    two, which would end every slot on a block of one page and a full pair
    of dots. So the ring is cut into EQUAL blocks, as many as blocks of
    twice that answer give to the nearest: 129 entries two blocks of 65, 65
    entries one (4.3 MB in the two buffers at 512 K lanes of bf16). On a v5e
    at the two cells' shapes 33 / 43 / 65 / 86 / 129 pages a block take
    0.75 / 0.71 / 0.69 / 0.73 / 0.66 ms a layer over rings of 129 (an
    all-idle batch 0.24 / 0.20 / 0.22 / 0.23 / 0.25, rings half filled
    0.42 / 0.43 / 0.38 / 0.40 / 0.47) and 33 / 43 / 65 take 0.39 / 0.41 /
    0.36 over rings of 65 (PERF.md §6 "PR 40", K and V in two leaves)."""
    ppb = paged_walk_pages_per_block(page_size, width, itemsize)
    return -(-entries // max((entries + ppb) // (2 * ppb), 1))


#: A page of this many bytes is a fetch by itself: its one DMA moves at
#: 83-87% of a v5e's HBM peak, and a run of two such pages was timed 2.5%
#: BEHIND. Smaller pages go as many together as make ``WALK_RUN_BYTES``, at
#: most ``WALK_RUN_MAX_PAGES``: at 8 KB a page moves at 27% of the peak
#: alone, 38% in runs of four and 41% in runs of eight; at 16 KB 49 / 55 /
#: 60% alone, in twos and in fours (PERF.md §6 "PR 45", "PR 46").
WALK_PAGE_ALONE_BYTES = 32 * 1024
WALK_RUN_BYTES = 64 * 1024
WALK_RUN_MAX_PAGES = 8


def walk_run_pages(page_bytes: int, pages_per_slot: int) -> int:
    """Pages of a RUN: what ``PagedKVCache`` hands out and takes back
    adjacent, and what :func:`paged_decode_walk` fetches with one DMA where
    the groups that lead a block of a slot's table name adjacent pages. Read
    off the bytes of ONE page of one layer, by nobody's choice: 1 for a page
    of ``WALK_PAGE_ALONE_BYTES`` or more (the allocator and the kernel then
    do what they did before runs); else the smallest power of two that makes
    a fetch of ``WALK_RUN_BYTES``, at most ``WALK_RUN_MAX_PAGES`` and no more
    than a slot's table holds: 8 and 12 KB pages go eight together, 16 and
    20 KB ones four."""
    run = 1
    while (page_bytes < WALK_PAGE_ALONE_BYTES
           and run * page_bytes < WALK_RUN_BYTES
           and run < WALK_RUN_MAX_PAGES and 2 * run <= pages_per_slot):
        run *= 2
    return run


def page_runs(page_ids, run: int):
    """(B, E // run) bool: which table-aligned groups of ``run`` entries of
    ``page_ids`` (B, E) name adjacent pages, ``ids[j + i] == ids[j] + i``
    (numpy or jax arrays in, the same out); a group of trash-page entries is
    no run."""
    b, e = page_ids.shape
    grp = page_ids[:, :e // run * run].reshape(b, e // run, run)
    return (grp[..., 1:] == grp[..., :-1] + 1).all(-1)


def leading_runs(page_ids, run: int, pages_per_block: int):
    """(B, blocks) int32: of each block of ``pages_per_block`` entries of
    ``page_ids`` (B, E), how many of its groups of ``run`` entries, from the
    first on, are runs (:func:`page_runs`). What the kernel is handed behind
    its lengths, and what the host counts the pages that went in runs by: of
    a block, ``min(this, live pages // run)`` groups go a DMA each."""
    runs = page_runs(page_ids, run)
    b, groups = runs.shape
    per = pages_per_block // run
    # a block a ``pages_per_block`` entries, the last one short or not
    pad = -(-page_ids.shape[1] // pages_per_block) * per - groups
    if pad:
        runs = (jnp if isinstance(runs, jax.Array) else np).concatenate(
            [runs, np.zeros((b, pad), bool)], axis=1)
    blocks = runs.reshape(b, -1, per).astype(np.int32)
    return blocks.cumprod(-1).sum(-1).astype(np.int32)


def _walk_fetch(ids_ref, len_ref, hbm, rbuf, sem, nslots, *, window=0,
                run=1):
    """The FETCH half of a page walk, which every kernel that walks a slot's
    pages inlines: (``live_pages(slot)``, ``start(slot, blk, buf)``,
    ``wait(slot, blk, buf)``) over the scalar-prefetched table ``ids_ref``
    (B, entries) and lengths ``len_ref`` (behind them, where ``run`` > 1, the
    table of each block's leading runs), the leaf ``hbm`` viewed as pages and
    the two buffers ``rbuf`` (2, pages a block, ps, lanes) with a DMA
    semaphore each. What is done with a block that has landed is the
    kernel's own."""
    _, ppb, ps, _ = rbuf.shape
    entries = ids_ref.shape[1]              # of a ring, where ``window``

    def live_pages(slot):
        pages = jnp.maximum(pl.cdiv(len_ref[slot], ps), 1)
        # a ring that has not turned yet is a prefix of its table; one that
        # has is fetched whole
        return jnp.minimum(pages, entries) if window else pages

    def num_pages(slot, blk):              # live pages of a slot's block
        return jnp.minimum(live_pages(slot) - blk * ppb, ppb)

    def start(slot, blk, buf):
        """A DMA a live page. What bounds a saturated step is how fast these
        are ISSUED (PERF.md §6 "PR 33", "PR 45"), so a page is one DMA, K and
        V lanes together, and the loop is unrolled by two. Where pages go in
        runs (``run`` > 1), the block's LEADING groups of ``run`` live entries
        that name adjacent pages are ONE DMA each: adjacent in HBM, they land
        adjacent in the buffer, so the bytes in VMEM are the same. How many
        lead is read off a table, not tested a group: a conditional a group
        costs the scalar core more than the DMAs it saves (PERF.md §6 "PR
        46"). The pages after them go one by one."""
        cnt = num_pages(slot, blk)

        def page(j):
            pltpu.make_async_copy(hbm.at[ids_ref[slot, blk * ppb + j]],
                                  rbuf.at[buf, j], sem.at[buf]).start()

        def pages(n, at=lambda j: j):       # n of them, from position at(0)
            def pair(g, _):
                page(at(2 * g))
                page(at(2 * g + 1))
                return 0

            jax.lax.fori_loop(0, n // 2, pair, 0)

            @pl.when(n % 2 == 1)
            def _odd():
                page(at(n - 1))

        if run == 1:
            return pages(cnt)
        # (the table of leading runs lies behind the lengths, a row a slot)
        lead = jnp.minimum(
            len_ref[nslots + slot * -(-entries // ppb) + blk], cnt // run)

        def group(g, _):
            pltpu.make_async_copy(
                hbm.at[pl.ds(ids_ref[slot, blk * ppb + g * run], run)],
                rbuf.at[buf, pl.ds(g * run, run)], sem.at[buf]).start()
            return 0

        jax.lax.fori_loop(0, lead, group, 0)
        pages(cnt - lead * run, lambda j: lead * run + j)

    def wait(slot, blk, buf):
        """A DMA semaphore counts bytes, so the block's pages are waited for
        by the binary digits of their count: at most ``log2(ppb) + 1`` waits
        in place of one a page (0.82 -> 0.73 ms a layer)."""
        cnt = num_pages(slot, blk)
        for bit in (1 << i for i in range(ppb.bit_length())):
            @pl.when((cnt & bit) != 0)
            def _wait(bit=bit):
                pltpu.make_async_copy(
                    hbm.at[pl.ds(0, bit)], rbuf.at[buf, pl.ds(0, bit)],
                    sem.at[buf]).wait()

    return live_pages, start, wait


def _walk_block(b, n, nblk, base, nslots, start, wait):
    """The buffer that holds block ``n`` of slot ``b``, landed: the next
    block's pages are put in flight first (this slot's, or the NEXT slot's
    first, so that no slot starts cold), then this one's are waited for.
    ``base``: the buffer the slot's first block landed in."""
    buf = (base + n) % 2
    more = n + 1 < nblk
    nxt = jnp.where(more, b, jnp.minimum(b + 1, nslots - 1))

    @pl.when(more | (b + 1 < nslots))
    def _prefetch():
        start(nxt, jnp.where(more, n + 1, 0), 1 - buf)

    wait(b, n, buf)
    return buf


def _paged_walk_kernel(ids_ref, len_ref, q_ref, hbm, o_ref, rbuf, sem,
                       par_ref, *, scale, window=0, run=1, keep_ref=None):
    """Grid (B,): slot ``b`` of the step, every head. See
    :func:`paged_decode_walk`."""
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    _, ppb, ps, _ = rbuf.shape
    rows = ppb * ps
    w = q_ref.shape[-1]
    entries = ids_ref.shape[1]              # of a ring, where ``window``
    # a row of w lanes is key and value both (a latent row); one of 2 w holds
    # the K lanes, then the V lanes
    v_at = rbuf.shape[-1] - w
    live_pages, start, wait = _walk_fetch(ids_ref, len_ref, hbm, rbuf, sem,
                                          nslots, window=window, run=run)

    @pl.when(b == 0)
    def _first():
        start(0, 0, 0)

    # which buffer this slot's first block landed in: the blocks before it,
    # over all slots, mod 2 (scratch keeps it from one grid step to the next)
    base = jnp.where(b == 0, 0, par_ref[0])
    length = len_ref[b]
    nblk = pl.cdiv(live_pages(b), ppb)
    # a query and a pool of two dtypes meet in the wider, as the einsums of
    # ``paged_kv.attend_rows`` promote them
    wide = jnp.promote_types(q_ref.dtype, rbuf.dtype)
    q = q_ref[0].astype(wide)                               # (H, W)
    h = q.shape[0]
    if window:
        # a ring's rows by the POSITION each holds (``paged_kv.ring_positions``
        # + ``window_valid``), from scalars of the slot off the scalar core.
        # The newest position t lies in entry ``turn`` of the ring; ring row R
        # holds position base + R up to that entry's last row and base + R -
        # entries * ps past it (the lap before). So the positions (t - window,
        # t] that exist (>= 0) are the ring rows [first, newest] and [lap,
        # entries * ps): four compares a row, no vector division or modulo.
        t = length - 1
        turn = jax.lax.rem(jax.lax.div(t, ps), entries)
        newest = turn * ps + jax.lax.rem(t, ps)         # t's ring row
        first = newest - jnp.minimum(window - 1, t)
        lap = jnp.maximum(first + entries * ps, (turn + 1) * ps)

        def ring_rows(n, shape, axis):
            r = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            at = n * rows
            return (((r >= first - at) & (r <= newest - at))
                    | ((r >= lap - at) & (r < entries * ps - at)))

    def body(n, carry):
        m, l, acc = carry
        # (the next block's pages are in flight while this one is attended)
        buf = _walk_block(b, n, nblk, base, nslots, start, wait)
        k = rbuf[buf, :, :, pl.ds(0, w)].reshape(rows, w).astype(wide)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if window:
            attended = functools.partial(ring_rows, n)
        else:
            live = length - n * rows       # rows of this block a length covers

            def attended(shape, axis):
                return jax.lax.broadcasted_iota(jnp.int32, shape, axis) < live

        if keep_ref is None:
            s = jnp.where(attended((h, rows), 1), s, -1e30)
        else:
            # a SELECTION of the live rows (``keep``): a row it leaves out is
            # fetched with its page and takes no part; a block may hold none
            # of it, so a row's weight is selected to zero, not left to the
            # exponent of a maximum no row of the block has raised
            chosen = attended((h, rows), 1) & (
                keep_ref[0, :, pl.ds(pl.multiple_of(n * rows, rows), rows)]
                != 0)
            s = jnp.where(chosen, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if keep_ref is not None:
            p = jnp.where(chosen, p, 0.0)
        # rows no DMA filled are stale VMEM, a ring's rows of the lap before
        # whatever the stream left there: 0 x NaN must not reach the sum
        v = rbuf[buf, :, :, pl.ds(v_at, w)].reshape(rows, w)
        v = jnp.where(attended((rows, w), 0), v, jnp.zeros_like(v))
        pv = jax.lax.dot_general(p.astype(q_ref.dtype).astype(wide),
                                 v.astype(wide),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
                alpha * acc + pv)

    m, l, acc = jax.lax.fori_loop(
        0, nblk, body,
        (jnp.full((h, 1), -1e30, jnp.float32), jnp.zeros((h, 1), jnp.float32),
         jnp.zeros((h, w), jnp.float32)))
    par_ref[0] = (base + nblk) % 2
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "pages_per_block", "interpret", "window", "run_pages"))
def paged_decode_walk(qz, pages, page_ids, lengths, *,
                      scale: float, pages_per_block: int | None = None,
                      interpret=False, window: int = 0,
                      run_pages: int | None = None, lead=None, keep=None):
    """Single-position attention of every slot over ITS OWN live pages, read
    out of the pool where they lie: ONE kernel in place of "gather every
    slot's whole span into a copy, then two dots over the copy".

    qz (B, H, W): a slot's query heads, each in the lanes of its KV group and
    zero elsewhere (``paged_kv.attend_rows`` builds it: scores are one dot
    over the whole lane-dense row). pages (N, ps, R): a pool's ONE leaf viewed
    as pages, every layer's; it stays in HBM. R = 2 W: a row is a position's
    K lanes, then its V lanes (``paged_kv.PagePool``). R = W: the row is both
    key and value (a latent row, ``paged_kv.LatentPool``). Either way a page
    is one fetch. page_ids (B, pages_per_slot)
    int32: slot i's pages in position order, as indices into N; lengths (B,)
    int32: the positions slot i attends, >= 1. Returns (B, H, W) in qz's
    dtype: ``softmax(scale * qz . K^T) . V`` in float32, of which a head
    keeps its group's lanes.

    Slot i fetches ``ceil(lengths[i] / ps)`` pages and no more, a block of
    ``pages_per_block`` at a time through two VMEM buffers (a DMA a page),
    with a running (max, sum, accumulator) softmax over the blocks.
    The pipeline runs ACROSS slots: a slot's last block is attended while the
    next slot's first is in flight. Rows past ``lengths[i]`` in the last
    block are masked before the exponent and their V rows selected to zero,
    so what the output holds does not depend on any page or row a length
    does not cover.

    ``window`` (static; 0: none of the following is traced): ``page_ids`` is a
    window layer's RING of ``E = page_ids.shape[1]`` entries (entry ``(p //
    ps) % E`` holds position p's page, ``paged_kv.write_rows(ring=True)``)
    and ``lengths`` still counts the positions written, the newest included.
    Slot i fetches ``min(ceil(lengths[i] / ps), E)`` pages in table order (a
    ring that has not turned is a prefix of its table, one that has is
    fetched whole), ``ring_walk_pages_per_block`` a block, and attends a row
    iff the position it holds lies in ``(t - window, t]``, ``t = lengths[i]
    - 1``: ``paged_kv.ring_positions`` + ``window_valid``. Rows of the lap
    before, rows not written yet and rows no DMA filled are masked before
    the exponent and their V rows selected to zero, as above.

    ``run_pages`` (static; None: :func:`walk_run_pages` of a page's bytes and
    the table's width, cut to what divides a block): where it is more than 1,
    the table-aligned groups of that many LIVE entries that LEAD a block and
    name adjacent pages (:func:`leading_runs`, taken of ``page_ids`` here, on
    the device) are fetched with ONE DMA each; the block's pages from its
    first group that is no run, and the live pages past a slot's last whole
    group, a DMA a page. The bytes that reach the buffer, and so the output,
    are the same bit for bit. At 1 the body is the one above. ``lead``
    (None: made here, of ``page_ids``): that table, where the caller has
    made it already, of the page TABLE its ids come from: adjacency does not
    change with a layer's offset, so a step's layers can share one
    (``paged_kv.attend_pages``).

    ``keep`` (None: nothing of the following is traced; no ``window``): (B,
    E * ps) int32, nonzero at the positions of a slot's table, in table
    order, that its query ATTENDS (a sparse-attention layer's selection,
    ``models/sparse_attn.py``): the walk fetches every live page as before
    and a live row the selection leaves out takes no part in the softmax.
    The mask rides in VMEM a slot at a time, a row of ``E * ps`` lanes.

    Scalar prefetch puts ``page_ids`` and ``lengths`` (with, behind them,
    the table of each block's leading runs) in SMEM before the body runs."""
    b, h, w = qz.shape
    _, ps, r = pages.shape
    if r not in (w, 2 * w):
        raise ValueError(f"a page row of {r} lanes is neither a query's {w} "
                         f"(key and value both) nor K then V ({2 * w})")
    itemsize = pages.dtype.itemsize
    ppb = pages_per_block or (
        ring_walk_pages_per_block(page_ids.shape[1], ps, w, itemsize)
        if window else paged_walk_pages_per_block(ps, w, itemsize))
    run = math.gcd(ppb, walk_run_pages(ps * r * itemsize, page_ids.shape[1])
                   if run_pages is None else run_pages)
    if run > 1:
        # behind the lengths, in the one array: a third table in SMEM costs
        # every launch 2 us, a fifth of an idle batch's layer
        lead = leading_runs(page_ids, run, ppb) if lead is None else lead
        lengths = jnp.concatenate([lengths, lead.reshape(-1)])
    slot = pl.BlockSpec((1, h, w), lambda i, ids, lens: (i, 0, 0))
    kernel = functools.partial(_paged_walk_kernel, scale=scale, window=window,
                               run=run)
    masked = ()
    if keep is not None:
        if window or keep.shape != (b, page_ids.shape[1] * ps):
            raise ValueError(
                f"keep {keep.shape} must mark every position of a prefix's "
                f"table ({b}, {page_ids.shape[1] * ps}); a ring takes none")
        # padded to whole blocks: the last block's slice stays inside
        span = -(-keep.shape[1] // (ppb * ps)) * ppb * ps
        masked = (jnp.pad(keep.astype(jnp.int32),
                          ((0, 0), (0, span - keep.shape[1])))[:, None],)
        inner = kernel

        def kernel(ids_ref, len_ref, q_ref, hbm, keep_ref, *rest):
            return inner(ids_ref, len_ref, q_ref, hbm, *rest,
                         keep_ref=keep_ref)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[slot, pl.BlockSpec(memory_space=pl.ANY)] + [
                pl.BlockSpec((1, 1, a.shape[-1]),
                             lambda i, ids, lens: (i, 0, 0)) for a in masked],
            out_specs=slot,
            scratch_shapes=[pltpu.VMEM((2, ppb, ps, r), pages.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, w), qz.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_walk",
    )(page_ids, lengths, qz, pages, *masked)


# ---------------------------------------------------------------------------
# The index walk: a sparse-attention layer's index keys scored where they lie.
# ---------------------------------------------------------------------------

#: rows one block of the index walk holds in VMEM. An index key is a narrow
#: row (128 lanes), so a block is many more rows than ``WALK_BLOCK_ROWS``
#: inside the same vector memory; PERF.md §6 "PR 51" has the v5e's timings
#: from 512 to 4096 rows.
INDEX_WALK_BLOCK_ROWS = 2048


def index_walk_pages_per_block(page_size: int, lanes: int,
                               itemsize: int) -> int:
    """Pages a block of :func:`paged_index_walk` holds over a leaf of rows
    ``lanes`` wide: ``INDEX_WALK_BLOCK_ROWS`` rows, fewer where the row is
    wide, so that the two buffers stay inside 2 MB of VMEM."""
    rows = min(INDEX_WALK_BLOCK_ROWS, (1 << 20) // (lanes * itemsize))
    return max(rows // page_size, 1)


def _index_walk_kernel(ids_ref, len_ref, q_ref, w_ref, hbm, o_ref, rbuf, sem,
                       par_ref, *, run=1):
    """Grid (B,): slot ``b`` of the step, every indexer head. The fetch is
    the page walk's (:func:`_walk_fetch`, :func:`_walk_block`); a block that
    has landed is SCORED. See :func:`paged_index_walk`."""
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    _, ppb, ps, lanes = rbuf.shape
    rows = ppb * ps
    live_pages, start, wait = _walk_fetch(ids_ref, len_ref, hbm, rbuf, sem,
                                          nslots, run=run)

    @pl.when(b == 0)
    def _first():
        start(0, 0, 0)

    base = jnp.where(b == 0, 0, par_ref[0])
    length = len_ref[b]
    nblk = pl.cdiv(live_pages(b), ppb)
    wide = jnp.promote_types(q_ref.dtype, rbuf.dtype)
    q = q_ref[0].astype(wide)                               # (Hi, lanes)
    w = w_ref[0]                                            # (Hi, 1) float32
    # the blocks no live page reaches are no DMA's and no dot's: zeros
    o_ref[...] = jnp.zeros_like(o_ref)

    def body(n, _):
        buf = _walk_block(b, n, nblk, base, nslots, start, wait)
        keys = rbuf[buf].reshape(rows, lanes).astype(wide)
        dots = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        s = jnp.sum(jnp.maximum(dots, 0.0) * w, axis=0, keepdims=True)
        # rows past the length are stale VMEM (whatever the stream left
        # there, NaN too): selected to zero, as a score of -0.0 is
        covered = jax.lax.broadcasted_iota(
            jnp.int32, (1, rows), 1) < length - n * rows
        o_ref[0, :, pl.ds(pl.multiple_of(n * rows, rows), rows)] = jnp.where(
            covered & (s != 0.0), s, 0.0)
        return 0

    jax.lax.fori_loop(0, nblk, body, 0)
    par_ref[0] = (base + nblk) % 2


@functools.partial(jax.jit, static_argnames=(
    "pages_per_block", "interpret", "run_pages"))
def paged_index_walk(qi, wi, pages, page_ids, lengths, *,
                     pages_per_block: int | None = None, interpret=False,
                     run_pages: int | None = None, lead=None):
    """A sparse-attention layer's index scores of every slot over ITS OWN
    live index keys, read out of the pool's index-key leaf where they lie:
    ONE kernel in place of "gather every slot's whole span of index keys
    into a copy, then a dot over the copy" (``sparse_attn.index_scores`` of
    ``paged_kv._gather_pages``, the oracle).

    qi (B, Hi, lanes): a slot's indexer queries, zeros after their own
    lanes; wi (B, Hi) float32: the heads' weights; pages (N, ps, lanes): the
    leaf viewed as pages, every layer's; it stays in HBM. page_ids (B,
    pages_per_slot) int32: slot i's pages in position order, as indices into
    N; lengths (B,) int32: the positions slot i scores, >= 1. Returns (B,
    pages_per_slot * ps) float32: at position p < ``lengths[i]`` of slot i,
    ``sum_j wi[i, j] relu(qi[i, j] . row p)``, float32 sums of the operands'
    products, a score of -0.0 made +0.0; at every other position 0.0,
    whatever the pages and rows no length covers hold.

    The fetch is :func:`paged_decode_walk`'s: slot i fetches ``ceil(
    lengths[i] / ps)`` pages and no more, a block of ``pages_per_block``
    (None: :func:`index_walk_pages_per_block`) at a time through two VMEM
    buffers, the pipeline running ACROSS slots; where ``run_pages`` (None:
    :func:`walk_run_pages` of a page's bytes and the table's width, cut to
    what divides a block) is more than 1, the table-aligned groups of that
    many live entries that LEAD a block and name adjacent pages
    (:func:`leading_runs`; ``lead``: that table where the caller has made it
    of the page table) are ONE DMA each, every other page a DMA of its own.
    An index page is a few KB, and a walk is bound by how fast its DMAs are
    issued: a page a DMA this is no faster than the gather (PERF.md §6 "PR
    51")."""
    b, hi, lanes = qi.shape
    _, ps, r = pages.shape
    if r != lanes or wi.shape != (b, hi):
        raise ValueError(f"queries {qi.shape} and weights {wi.shape} against "
                         f"index rows of {r} lanes")
    entries = page_ids.shape[1]
    ppb = pages_per_block or index_walk_pages_per_block(
        ps, lanes, pages.dtype.itemsize)
    run = math.gcd(ppb, walk_run_pages(ps * r * pages.dtype.itemsize, entries)
                   if run_pages is None else run_pages)
    if run > 1:
        lead = leading_runs(page_ids, run, ppb) if lead is None else lead
        lengths = jnp.concatenate([lengths, lead.reshape(-1)])
    span = -(-entries // ppb) * ppb * ps        # whole blocks
    scores = pl.pallas_call(
        functools.partial(_index_walk_kernel, run=run),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, hi, lanes),
                                   lambda i, ids, lens: (i, 0, 0)),
                      pl.BlockSpec((1, hi, 1), lambda i, ids, lens: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, span),
                                   lambda i, ids, lens: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, ppb, ps, r), pages.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, 1, span), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_index_walk",
    )(page_ids, lengths, qi, wi.astype(jnp.float32)[:, :, None], pages)
    return scores[:, 0, :entries * ps]


# ---------------------------------------------------------------------------
# Masked prefill attention: a block of query rows under a selection's mask.
# ---------------------------------------------------------------------------

#: keys a grid step of :func:`masked_attention` holds. A row's maximum and sum
#: are reduced across lanes and its accumulator rescaled ONCE A KEY BLOCK,
#: whatever the block's width, and at a key's 128 + 128 or 192 + 128 lanes
#: that fixed part is most of a narrow block's step: the vector units bound
#: the kernel, not the MXU (PERF.md section 6 "PR 53" has the sweep)
MASKED_KEY_BLOCK = 2048
#: what a step's blocks, scratch and score tiles may take of VMEM (a v5e has
#: 128 MiB): the row tile is the largest the plan finds under it
MASKED_VMEM_BYTES = 24 << 20
MASKED_VMEM_LIMIT_BYTES = 64 << 20
#: a mask is int8: whole (32, 128) tiles of it
MASK_SUBLANES = 32


def masked_attention_plan(rep: int, rows: int, keys: int, dk: int, dv: int,
                          itemsize: int):
    """(row tile, key block) of :func:`masked_attention` for ``rep`` copies
    of ``rows`` query rows (a multiple of :data:`MASK_SUBLANES`) against
    ``keys`` keys: the key block is :data:`MASKED_KEY_BLOCK` (a short key set
    one block of whole lane tiles); the row tile is the largest of whole
    copies of the rows (a divisor of ``rep``), or of whole mask tiles that
    divide them, whose step fits :data:`MASKED_VMEM_BYTES`: q, k, v, mask
    and output blocks twice (the pipeline's two buffers), the float32
    accumulator, maximum and sum, and three (row tile, key block) float32
    score tiles (scores, exponents, the cast)."""
    tc = min(MASKED_KEY_BLOCK, -(-keys // 128) * 128)

    def step_bytes(tr):
        blocks = (tr * dk + tc * dk + tc * dv + tr * dv) * itemsize \
            + min(tr, rows) * tc
        return 2 * blocks + tr * (dv + 2 * 128) * 4 + 3 * tr * tc * 4

    tiles = [rows * d for d in range(1, rep + 1) if rep % d == 0] + [
        t for t in range(MASK_SUBLANES, rows, MASK_SUBLANES) if rows % t == 0]
    fits = [t for t in tiles if step_bytes(t) <= MASKED_VMEM_BYTES]
    return (max(fits) if fits else min(tiles)), tc


def _masked_attn_kernel(start_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, scale, rows):
    """Grid (group, row tile, key block), the key block innermost. See
    :func:`masked_attention`."""
    t, j = pl.program_id(1), pl.program_id(2)
    tr, tc = q_ref.shape[1], k_ref.shape[1]

    @pl.when(j == 0)
    def _first():
        # a maximum every real score lies above and a masked one below: a
        # key block none of whose keys a row attends leaves the row's sum
        # and accumulator as they were (exp(-1e30 + 1e29) is 0)
        m_ref[...] = jnp.full_like(m_ref, -1e29)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * tc <= start_ref[0] + _tile_last(t, tr, rows))
    def _attend():
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        keep = mask_ref[0].astype(jnp.int32) != 0
        if tr > rows:       # whole copies of the rows: one mask tile for all
            s = jnp.where(keep[None], s.reshape(tr // rows, rows, tc),
                          -1e30).reshape(tr, tc)
        else:
            s = jnp.where(keep, s, -1e30)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(q_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _last():
        # (a row the mask leaves nothing, a padded one, divides by one)
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _tile_last(t, tr: int, rows: int):
    """The last of a block's ``rows`` query rows that row tile ``t`` of
    ``tr`` rows holds: a tile of whole copies holds them all, a smaller one
    the rows ``[(t mod rows/tr) tr, + tr)``."""
    if tr >= rows:
        return rows - 1
    return jax.lax.rem(t, rows // tr) * tr + tr - 1


def masked_attention(q, k, v, mask, start, *, scale: float, interpret=False):
    """Attention of a BLOCK of query rows over keys under a mask, key block
    by key block with a running maximum, sum and accumulator (float32): no
    (heads, rows, keys) score or probability tensor leaves vector memory.

    q (G, rep, Q, dk): ``rep`` query heads a group, the SAME ``Q`` positions
    each; k (G, C, dk); v (G, C, dv); mask (M, Q, C) bool or int8, M = 1 or a
    divisor of G (group ``g`` reads mask ``g // (G / M)``): query row ``i``
    of every head attends key ``c`` iff ``mask[., i, c]``, which holds the
    row's visibility too (``c <= start + i``); start () int32, the block's
    first position, traced or not. Returns (G, rep, Q, dv) in q's dtype:
    ``softmax(scale * q . k^T) . v`` over the kept keys, scores float32, the
    probabilities cast to q's dtype before the second dot.

    A group's queries ride as ONE (rep * Q, dk) matrix, row ``r`` reading
    mask row ``r mod Q``: a row tile of whole copies of the Q rows takes one
    (Q, keys) mask tile for all of them (a broadcast over the leading axis,
    no row of the mask repeated in VMEM), and every tile ends at the block's
    last position. A key block that lies wholly past a row tile's last
    position is neither fetched (its block index is clamped to the last one
    the tile reads, which the pipeline sees as a repeat) nor multiplied.
    Tiles: :func:`masked_attention_plan`. Q is padded to whole mask tiles and
    C to whole key blocks here; a padded row attends nothing and is cut."""
    g, rep, n, dk = q.shape
    c, dv = k.shape[1], v.shape[-1]
    rows = -(-n // MASK_SUBLANES) * MASK_SUBLANES
    tr, tc = masked_attention_plan(rep, rows, c, dk, dv, q.dtype.itemsize)
    span = -(-c // tc) * tc
    mask = jnp.pad(mask.astype(jnp.int8),
                   ((0, 0), (0, rows - n), (0, span - c)))
    if rows != n:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, rows - n), (0, 0)))
    if span != c:
        k, v = (jnp.pad(a, ((0, 0), (0, span - c), (0, 0))) for a in (k, v))
    per = g // mask.shape[0]

    def key_block(t, j, at):        # the last block a tile reads, repeated
        return jnp.minimum(j, (at[0] + _tile_last(t, tr, rows)) // tc)

    keys = [pl.BlockSpec((1, tc, lanes),
                         lambda i, t, j, at: (i, key_block(t, j, at), 0))
            for lanes in (dk, dv)]
    out = pl.pallas_call(
        functools.partial(_masked_attn_kernel, scale=scale, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(g, rep * rows // tr, span // tc),
            in_specs=[pl.BlockSpec((1, tr, dk), lambda i, t, j, at: (i, t, 0)),
                      *keys,
                      pl.BlockSpec(
                          (1, min(tr, rows), tc), lambda i, t, j, at: (
                              i // per, jax.lax.rem(t, max(rows // tr, 1)),
                              key_block(t, j, at)))],
            out_specs=pl.BlockSpec((1, tr, dv), lambda i, t, j, at: (i, t, 0)),
            scratch_shapes=[pltpu.VMEM((tr, 1), jnp.float32),
                            pltpu.VMEM((tr, 1), jnp.float32),
                            pltpu.VMEM((tr, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((g, rep * rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=MASKED_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="masked_attention",
    )(jnp.reshape(start, (1,)).astype(jnp.int32),
      q.reshape(g, rep * rows, dk), k, v, mask)
    return out.reshape(g, rep, rows, dv)[:, :, :n]
