"""The ``granitemoehybrid`` family (Mamba-2 + NoPE attention layers, routed +
shared experts) against its plain reference, on the CPU at toy widths with
seeded float32 weights.

The reference is ``benchmark/reference_granitemoehybrid.py``: float32 at
``highest``, the Mamba layer as the literal per-token recurrence, nothing
imported from the program. Both sides compute in float32 here, so they differ
by summation order alone.

TOL: logits are compared as ``max |system - reference| <= TOL * max
|reference|``. 2e-5 is ~100 float32 roundings of a four-layer stack whose
sums run over at most 128 terms; the readings are 7e-7 to 2e-6. Every named
mistake below moves the logits by far more of their size — a bfloat16
recurrent state 7e-4, rotary applied 4e-3, 1/sqrt(d) scaling 0.12, a dropped
multiplier 0.3 to 15, a lost state hand-off more — and
``test_a_named_mistake_fails`` holds each to twenty tolerances.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_granitemoehybrid as ref  # noqa: E402
from edgellm_tpu.models import (grouped_matmul, hybrid, mamba2,  # noqa: E402
                                moe, paged_kv, transformer)
from edgellm_tpu.models.configs import (GRANITE_4_0_H_SMALL,  # noqa: E402
                                        ModelConfig, tiny_config,
                                        tiny_hybrid_config)
from edgellm_tpu.models.hybrid import RecurrentStateUnsupported  # noqa: E402
from edgellm_tpu.serve import batching  # noqa: E402
from edgellm_tpu.serve.batching import (BatchingConfig,  # noqa: E402
                                        ContinuousBatcher)
from edgellm_tpu.serve.decode import generate  # noqa: E402

TOL = 2e-5
CFG = tiny_hybrid_config()                       # chunk 8: M M A M
BCFG = BatchingConfig(page_size=4, num_pages=60, max_slots=3,
                      pages_per_slot=12)


def ref_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, from a ModelConfig."""
    return {
        "hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "rms_norm_eps": cfg.norm_eps,
        "layer_types": list(cfg.layer_types),
        "num_local_experts": cfg.local_experts,
        "num_experts_per_tok": cfg.experts_per_tok,
        "share": {"router_experts": cfg.num_experts,
                  "expert_offset": cfg.expert_offset},
        "mamba_n_heads": cfg.mamba_heads, "mamba_d_head": cfg.mamba_head_dim,
        "mamba_d_state": cfg.mamba_d_state, "mamba_d_conv": cfg.mamba_d_conv,
        "mamba_n_groups": cfg.mamba_n_groups,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling}


def make_params(cfg, seed=0):
    """Seeded weights with every matrix at std 0.08 instead of 0.02 and norm
    scales off one: at width 64 that makes the mixers' outputs, the state and
    the experts each a visible part of the logits."""
    params = transformer.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 512))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("_scale"):
            return a + 0.1 * jax.random.normal(next(keys), a.shape)
        if name == "dt_bias":     # dt of 0.01-1: each position moves the state
            return a + 2.5
        if name == "D":           # ... and the skip does not drown it
            return a * 0.1
        if name in ("embed", "A_log", "conv_w"):
            return a
        return a * 4.0

    return jax.tree_util.tree_map_with_path(shake, params)


@pytest.fixture(scope="module")
def params():
    return make_params(CFG)


def ref_logits(cfg, params, ids):
    return np.asarray(ref.logits(ref.model_key(ref_config(cfg)), params,
                                 jnp.asarray(ids)))


def rel_err(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


def _ids(n, seed=1, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, size=n).astype(
        np.int32)


def _forward(cfg, params, ids):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, i: transformer.forward(cfg, p, i)[0])(
            params, jnp.asarray(ids)[None])[0]


# -- forward ----------------------------------------------------------------

@pytest.mark.parametrize("cfg", [
    CFG,
    tiny_hybrid_config(experts_held=4, expert_offset=4),
    tiny_hybrid_config(layer_types=("attention", "mamba", "mamba",
                                    "attention", "mamba"), mamba_chunk=5),
    dataclasses.replace(tiny_hybrid_config(), mamba_n_groups=2),
], ids=["whole", "share-upper-half", "attention-first-chunk5", "two-groups"])
@pytest.mark.parametrize("length", [24, 19])
def test_forward_matches_the_reference(cfg, length):
    p = make_params(cfg)
    ids = _ids(length)
    assert rel_err(_forward(cfg, p, ids), ref_logits(cfg, p, ids)) < TOL


def test_forward_takes_a_batch(params):
    ids = np.stack([_ids(17, 3), _ids(17, 4)])
    with jax.default_matmul_precision("highest"):
        logits, aux = transformer.forward(CFG, params, jnp.asarray(ids))
    assert aux == {} and logits.shape == (2, 17, CFG.vocab_size)
    for b in range(2):
        assert rel_err(logits[b], ref_logits(CFG, params, ids[b])) < TOL


# -- prefill, then decode, through the batcher -------------------------------

class LogitTap:
    """``ContinuousBatcher`` with its step executable replaced by one that
    also hands the logits out: the same ``paged_decode_step_hybrid``, the same
    sampler, the batcher's own admission, adoption and tables around it."""

    def __init__(self, monkeypatch, cfg):
        self.rows = []        # (lengths, logits) per step

        @jax.jit
        def step(params, kv, state, cnt, table, lengths, toks,
                 key_data, steps, temps):
            with jax.default_matmul_precision("highest"):
                logits, kv, state, cnt = (
                    hybrid.paged_decode_step_hybrid(
                        cfg, params, kv, state, cnt, table, lengths,
                        toks))
            return (logits, batching._batched_sample(logits, key_data, steps,
                                                     temps),
                    kv, state, cnt)

        def tapped(cfg_, params, kv, state, cnt, table, lengths,
                   toks, key_data, steps, temps, compute_dtype):
            logits, *rest = step(params, kv, state, cnt, table,
                                 lengths, toks, key_data, steps, temps)
            # copies: on the CPU a device array made from numpy may alias the
            # pool's own table, which the batcher goes on to overwrite
            self.rows.append((np.array(lengths), np.array(logits)))
            return tuple(rest)

        tapped._cache_size = lambda: 0
        monkeypatch.setattr(batching, "_batched_hybrid_step_jit", tapped)

    def of_slot(self, slot):
        """{cache length before the step: that slot's logits row}."""
        return {int(lengths[slot]): logits[slot]
                for lengths, logits in self.rows if lengths[slot] > 0}


def _check_stream(tap, slot, cfg, params, prompt, tokens, tol=TOL,
                  reference=None):
    """Every decode step's logits of a stream against the reference's full
    forward (this family's, or ``reference``) over prompt + served tokens."""
    seq = np.concatenate([prompt, tokens])
    want = (reference or ref_logits)(cfg, params, seq)
    got = tap.of_slot(slot)
    assert len(got) >= len(tokens) - 1
    for pos, row in got.items():
        # the step that starts with `pos` cached positions feeds token `pos`
        # of the sequence and predicts position pos + 1
        if pos < len(seq):
            assert rel_err(row, want[pos]) < tol, pos


@pytest.mark.parametrize("plen", [16, 8, 13, 5, 21])
def test_prefill_then_decode_through_the_batcher_matches_the_full_forward(
        monkeypatch, params, plen):
    """Chunk 8: prompts that are and are not multiples of it, 14 decode
    steps, so a wrong hand-off of the convolution window or the SSM state
    between the chunked prefill and the one-step recurrence shows at once
    and a drift shows later."""
    tap = LogitTap(monkeypatch, CFG)
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(CFG, params, BCFG)
        prompt = _ids(plen, plen)
        sid = b.submit(prompt, 15, rng_seed=0)
        toks = b.run()[sid]
    b.pool.check_invariants()
    _check_stream(tap, 0, CFG, params, prompt, toks)
    # token 0 came from the prefill's last position
    want0 = ref_logits(CFG, params, prompt)[-1]
    assert want0[toks[0]] >= want0.max() - TOL * np.abs(want0).max()


@pytest.fixture(params=["granitemoehybrid", "lfm2_moe"])
def recurrent(request):
    """(config, seeded weights, reference logits) of each family whose
    layers keep recurrent state: what the state store does for one it does
    for the other, whatever leaves its kinds keep."""
    if request.param == "lfm2_moe":
        import test_lfm2_moe as fam   # imports this module's helpers: late
    else:
        fam = sys.modules[__name__]
    return fam.CFG, fam.make_params(fam.CFG), fam.ref_logits


def test_evict_then_readmit_reproduces_the_undisturbed_stream(monkeypatch,
                                                             recurrent):
    CFG, params, _ = recurrent
    leaves = sorted(hybrid.state_shapes(CFG, 1))
    prompt = _ids(11, 7)
    with jax.default_matmul_precision("highest"):
        tap0 = LogitTap(monkeypatch, CFG)
        calm = ContinuousBatcher(CFG, params, BCFG)
        sid = calm.submit(prompt, 12, rng_seed=3, temperature=0.7)
        want = calm.run()[sid]
        tap1 = LogitTap(monkeypatch, CFG)
        b = ContinuousBatcher(CFG, params, BCFG)
        other = b.submit(_ids(6, 8), 20, rng_seed=4)   # takes slot 0
        sid = b.submit(prompt, 12, rng_seed=3, temperature=0.7)
        for _ in range(4):
            b.step()
        st = b._streams[sid]
        assert st.status == "running" and st.slot == 1
        b.evict(sid)
        assert set(st.resume) == {"k", "v", "length", *leaves}
        assert all(st.resume[leaf].dtype == np.float32 for leaf in leaves)
        # a stream's own state comes back: not zeros, not its neighbour's
        assert all(np.abs(st.resume[leaf]).max() > 0 for leaf in leaves)
        b.pool.check_invariants()
        got = b.run()[sid]
        assert b.report()["evicted"] == 1 and other in b.results
    np.testing.assert_array_equal(got, want)
    a, c = tap0.of_slot(0), tap1.of_slot(1)
    assert len(c) == len(a) == 11
    for pos, row in a.items():        # byte copies out and back: the same
        np.testing.assert_allclose(c[pos], row, rtol=0, atol=1e-7)


def test_adjacent_slots_do_not_read_each_others_state_and_a_reused_slot_starts_from_zero(
        monkeypatch, recurrent):
    CFG, params, reference = recurrent
    prompt = _ids(9, 21)
    with jax.default_matmul_precision("highest"):
        alone = ContinuousBatcher(CFG, params, BCFG)
        sid = alone.submit(prompt, 10, rng_seed=1)
        want = alone.run()[sid]
        tap = LogitTap(monkeypatch, CFG)
        b = ContinuousBatcher(CFG, params, BCFG)
        first = b.submit(_ids(14, 22), 3, rng_seed=2)     # slot 0, ends early
        sid = b.submit(prompt, 10, rng_seed=1)            # slot 1
        third = b.submit(_ids(7, 23), 12, rng_seed=5)     # slot 2
        for _ in range(3):
            b.step()
        assert first in b.results and not b.pool.active[0]
        # the slot a stream left keeps its stale state until it is reused...
        assert all(float(jnp.abs(a[:, 0]).max()) > 0
                   for a in b.pool.state.values())
        again = b.submit(prompt, 10, rng_seed=1)          # ...reuses slot 0
        res = b.run()
    np.testing.assert_array_equal(res[sid], want)
    np.testing.assert_array_equal(res[again], want)
    _check_stream(tap, 1, CFG, params, prompt, res[sid], reference=reference)
    assert third in res
    # and allocation itself zeroes the slot's rows
    pool = b.pool
    slot = pool.alloc_slot()
    assert all(float(jnp.abs(a[:, slot]).max()) == 0.0
               for a in pool.state.values())
    pool.free_slot(slot)
    pool.check_invariants()


def test_batcher_tokens_equal_generate(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    prompts = [_ids(n, n) for n in (5, 8, 13, 16)]
    temps = [0.0, 0.7, 0.0, 0.7]
    sids = [b.submit(p, 7, temperature=t, rng_seed=i)
            for i, (p, t) in enumerate(zip(prompts, temps))]
    res = b.run()
    for i, (sid, p, t) in enumerate(zip(sids, prompts, temps)):
        want = np.asarray(generate(CFG, params, p[None], 7, temperature=t,
                                   rng_key=jax.random.key(i)))[0]
        np.testing.assert_array_equal(res[sid], want)
    assert batching.batched_step_cache_size() >= 1


# -- the share ----------------------------------------------------------------

def _granite_shares():
    whole = tiny_hybrid_config()
    return (whole, 4, lambda off: tiny_hybrid_config(experts_held=4,
                                                     expert_offset=off),
            lambda mp, u: ref._moe(dict(ref.model_key(ref_config(whole))),
                                   mp, u, False))


def _mellum_shares():
    """The mellum family's layer: 64 experts top-8, 16 held a chip, none
    shared, at toy widths."""
    from benchmark import reference_mellum
    from edgellm_tpu.models.configs import tiny_mellum_config

    def cfg(**kw):
        return tiny_mellum_config(num_experts=64, experts_per_tok=8, **kw)

    return (cfg(), 16, lambda off: cfg(experts_held=16, expert_offset=off),
            lambda mp, u: reference_mellum._moe(
                {"top_k": 8, "offset": 0, "held": 64}, mp, u, False))


@pytest.mark.parametrize("family", [_granite_shares, _mellum_shares],
                         ids=["2x4-of-8-and-a-shared-expert",
                              "4x16-of-64-none-shared"])
def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        family):
    """Expert parallelism's arithmetic: each chip routes over all the
    experts, computes its own for the tokens routed to them plus the shared
    expert where the family has one; the routed parts and the shared expert
    counted once are the whole layer, as the uncut reference computes it."""
    whole, held_n, share_cfg, want_fn = family()
    mp = make_params(whole)["moe"][1]
    u = jax.random.normal(jax.random.key(5), (37, whole.hidden_size))
    shared = 0.0
    if whole.shared_width:
        shared = (jax.nn.silu(u @ mp["shared_gate"])
                  * (u @ mp["shared_up"])) @ mp["shared_down"]
    parts, counts = [], []
    with jax.default_matmul_precision("highest"):
        for off in range(0, whole.num_experts, held_n):
            held = {**mp, **{k: mp[k][off:off + held_n]
                             for k in ("w_gate", "w_up", "w_down")}}
            out, cnt = moe.moe_layer(share_cfg(off), held, u)
            parts.append(out - shared)
            counts.append(cnt)
        want = want_fn(mp, u)
    assert rel_err(sum(parts) + shared, np.asarray(want)) < TOL
    # no token dropped: every one of the assignments landed somewhere
    assert int(sum(c.sum() for c in counts)) == 37 * whole.experts_per_tok
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)


@pytest.mark.parametrize("tokens", [7, moe.DENSE_MAX_TOKENS + 44])
def test_dense_and_grouped_expert_paths_agree(tokens):
    cfg = tiny_hybrid_config(experts_held=4, expert_offset=2)
    mp = make_params(tiny_hybrid_config())["moe"][0]
    mp = {**mp, **{k: mp[k][2:6] for k in ("w_gate", "w_up", "w_down")}}
    u = jax.random.normal(jax.random.key(6), (tokens, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        idx, w = moe.route(cfg, mp["router"], u)
        dense = moe._experts_dense(cfg, mp, u, idx, w)
        grouped = moe._experts_grouped(cfg, mp, u, idx, w)
        out, counts = moe.moe_layer(cfg, mp, u)
    assert rel_err(grouped, np.asarray(dense)) < TOL
    local = np.asarray(idx) - 2
    want = np.bincount(local[(local >= 0) & (local < 4)], minlength=4)
    np.testing.assert_array_equal(np.asarray(counts), want)
    active = jnp.arange(tokens) % 2 == 0
    _, half = moe.moe_layer(cfg, mp, u, active)
    assert 0 < int(half.sum()) < int(counts.sum())


def _poison_ragged_dot(monkeypatch):
    real = jax.lax.ragged_dot

    def poisoned(lhs, rhs, sizes):
        out = real(lhs, rhs, sizes)
        past = jnp.arange(out.shape[0])[:, None] >= jnp.sum(sizes)
        return jnp.where(past, jnp.nan, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)


def _poison_kernel(monkeypatch):
    """The kernel path as a TPU takes it, run by the interpreter (each call
    waited for: its host callbacks deadlock against a dispatching thread),
    with NaN in every row past the last group, tiles it never visited and
    the visited tile's tail alike."""
    from jax.experimental.pallas import tpu as pltpu

    def poisoned(real):
        def call(rows, *rest):
            *_, sizes = rest
            out = jax.block_until_ready(
                real(rows, *rest, interpret=pltpu.InterpretParams()))
            past = jnp.arange(out.shape[0])[:, None] >= jnp.sum(sizes)
            return jnp.where(past, jnp.nan, out)
        return call

    monkeypatch.setattr(grouped_matmul, "_on_tpu", lambda: True)
    for name in ("grouped_matmul", "grouped_swiglu"):
        monkeypatch.setattr(grouped_matmul, name,
                            poisoned(getattr(grouped_matmul, name)))


@pytest.mark.parametrize("widths, path, poison", [
    (dict(), grouped_matmul.XLA_RAGGED, _poison_ragged_dot),
    (dict(hidden_size=128), grouped_matmul.PALLAS_GROUPED, _poison_kernel)],
    ids=["ragged-dot", "kernel"])
def test_rows_past_the_held_groups_are_selected_out_not_weighted(
        monkeypatch, widths, path, poison):
    """What a grouped product leaves in the rows that belong to no group is
    not defined: the CPU leaves zeros, a TPU at the published sizes left NaN
    (PERF.md, PR 26), the kernel never writes them, and NaN times a zero
    weight is NaN. Poison those rows the way the chip did, on either path;
    the layer's result must not move off the clean ``ragged_dot``'s."""
    # the kernel's case at whole lane tiles: D = F = 128
    wide = dict(expert_width=widths["hidden_size"]) if widths else {}
    cfg = dataclasses.replace(tiny_hybrid_config(
        experts_held=4, expert_offset=2, **widths), **wide)
    mp = make_params(dataclasses.replace(tiny_hybrid_config(**widths),
                                         **wide))["moe"][0]
    mp = {**mp, **{k: mp[k][2:6] for k in ("w_gate", "w_up", "w_down")}}
    u = jax.random.normal(jax.random.key(9), (moe.DENSE_MAX_TOKENS + 3,
                                              cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        idx, w = moe.route(cfg, mp["router"], u)
        want = moe._experts_grouped(cfg, mp, u, idx, w)
        poison(monkeypatch)
        assert moe.grouped_product(cfg) == path
        got = moe._experts_grouped(cfg, mp, u, idx, w)
    assert int(jnp.sum(jnp.any(idx < 2, axis=-1))) > 0     # some rows are past
    assert bool(jnp.isfinite(got).all())
    if path == grouped_matmul.XLA_RAGGED:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert rel_err(got, np.asarray(want)) < TOL


# -- the named mistakes ---------------------------------------------------------

def _bf16_state(monkeypatch):
    real = mamba2.mamba2_step

    def rounded(cfg, lp, u, conv, ssm):
        out, conv, ssm = real(cfg, lp, u, conv, ssm)
        return out, conv, ssm.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(hybrid, "mamba2_step", rounded)
    return CFG


def _rotary(monkeypatch):
    real = hybrid._qkv

    def rotated(cfg, lp, x):
        q, k, v = real(cfg, lp, x)
        rope = dataclasses.replace(tiny_config("qwen2"), num_heads=cfg.num_heads,
                                   hidden_size=cfg.hidden_size)
        cos, sin = transformer.precompute_rope(rope, x.shape[1])
        return (transformer.apply_rotary(q, cos, sin, rope.rotary_dim),
                transformer.apply_rotary(k, cos, sin, rope.rotary_dim), v)

    monkeypatch.setattr(hybrid, "_qkv", rotated)
    return CFG


MISTAKES = {
    "bf16-state": _bf16_state,
    "rotary-applied": _rotary,
    "inv-sqrt-d-scaling": lambda mp: dataclasses.replace(
        CFG, attention_multiplier=None),
    "no-embedding-multiplier": lambda mp: dataclasses.replace(
        CFG, embedding_multiplier=1.0),
    "no-residual-multiplier": lambda mp: dataclasses.replace(
        CFG, residual_multiplier=1.0),
    "no-logits-scaling": lambda mp: dataclasses.replace(
        CFG, logits_scaling=1.0),
}


@pytest.mark.parametrize("name", sorted(MISTAKES))
def test_a_named_mistake_fails(monkeypatch, params, name):
    """The comparison above is tight enough: the same prefill-then-decode
    with one mistake made in the program is twenty tolerances off the
    reference (which is given the right configuration)."""
    wrong = MISTAKES[name](monkeypatch)
    prompt, n = _ids(13, 31), 12
    with jax.default_matmul_precision("highest"):
        logits, cache = transformer.prefill(wrong, params,
                                            jnp.asarray(prompt)[None], 40)
        seq = list(prompt)
        rows = [np.asarray(logits[0, -1])]
        for _ in range(n):
            seq.append(int(np.argmax(rows[-1])))
            step, cache = transformer.decode_step(
                wrong, params, cache, jnp.asarray(seq[-1:], jnp.int32))
            rows.append(np.asarray(step[0]))
    want = ref_logits(CFG, params, np.asarray(seq, np.int32))[len(prompt) - 1:]
    worst = max(rel_err(r, w) for r, w in zip(rows, want))
    assert worst > 20 * TOL, worst


def test_a_lost_state_hand_off_fails(monkeypatch, params):
    real = paged_kv.PagedKVCache.adopt_state
    monkeypatch.setattr(paged_kv.PagedKVCache, "adopt_state",
                        lambda self, slot, conv, ssm: real(self, slot, 0., 0.))
    tap = LogitTap(monkeypatch, CFG)
    with jax.default_matmul_precision("highest"):
        b = ContinuousBatcher(CFG, params, BCFG)
        prompt = _ids(13, 13)
        sid = b.submit(prompt, 6, rng_seed=0)
        toks = b.run()[sid]
    with pytest.raises(AssertionError):
        _check_stream(tap, 0, CFG, params, prompt, toks, tol=20 * TOL)


# -- refusals -------------------------------------------------------------------

def _refuse_prefix(params):
    ContinuousBatcher(CFG, params, dataclasses.replace(
        BCFG, prefix_cache=paged_kv.PrefixCacheConfig()))


def _refuse_kv_codec(params):
    ContinuousBatcher(CFG, params, dataclasses.replace(
        BCFG, kv_codec="int8_per_channel"))


def _refuse_checkpoint_dir(params):
    ContinuousBatcher(CFG, params, dataclasses.replace(
        BCFG, checkpoint_dir="/nonexistent"))


def _refuse_checkpoint_stream(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    sid = b.submit(_ids(5), 4)
    b.step()
    b.checkpoint_stream(sid, "/nonexistent/x.ckpt")


def _refuse_restore_stream(params):
    ContinuousBatcher(CFG, params, BCFG).restore_stream("/nonexistent/x.ckpt")


def _refuse_speculation(params):
    from edgellm_tpu.serve.speculative import SpecConfig, draft_from_params

    draft_from_params(CFG, params, SpecConfig(enabled=True, k=2))


def _refuse_disagg(params):
    from edgellm_tpu.serve.disagg import DisaggServer

    DisaggServer(CFG, params, BCFG)


def _refuse_prefill_hold(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    b.prefill_hold(b.submit(_ids(5), 4))


def _refuse_split(params):
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, make_stage_mesh

    SplitRuntime(CFG, SplitConfig(cuts=(1,), hop_codecs=("int8_per_token",)),
                 make_stage_mesh(2))


def _refuse_recovery(params):
    from edgellm_tpu.serve.recovery import RecoveryConfig

    generate(CFG, params, _ids(5)[None], 3, recovery=RecoveryConfig())


def _refuse_local_runtime(params):
    from edgellm_tpu.serve.recovery import LocalRuntime

    LocalRuntime(CFG)


def _refuse_boundary_hook(params):
    transformer.forward(CFG, params, jnp.asarray(_ids(5))[None],
                        boundary_fn=lambda i, h: h)


def _refuse_bookkeeping_pool(params):
    paged_kv.PagedKVCache(CFG, num_pages=9, page_size=4, max_slots=2,
                          pages_per_slot=4, materialize=False)


REFUSALS = {f.__name__[len("_refuse_"):]: f for f in (
    _refuse_prefix, _refuse_kv_codec, _refuse_checkpoint_dir,
    _refuse_checkpoint_stream, _refuse_restore_stream, _refuse_speculation,
    _refuse_disagg, _refuse_prefill_hold, _refuse_split, _refuse_recovery,
    _refuse_local_runtime, _refuse_boundary_hook, _refuse_bookkeeping_pool)}


@pytest.mark.parametrize("name", sorted(REFUSALS))
def test_what_needs_a_state_snapshot_refuses_the_family_by_name(params, name):
    with pytest.raises(RecurrentStateUnsupported) as e:
        REFUSALS[name](params)
    msg = str(e.value)
    assert "recurrent state" in msg and "granitemoehybrid" in msg
    assert "no fallback" in msg


# -- the configuration, the manager, the counters, the scopes -------------------

def test_the_preset_is_the_published_model():
    c = GRANITE_4_0_H_SMALL
    assert (c.num_layers, c.kv_layers, c.mamba_layers) == (40, 4, 36)
    assert c.layer_types[5] == c.layer_types[35] == "attention"
    assert (c.head_dim, c.mamba_d_inner, c.mamba_conv_dim) == (128, 8192,
                                                               8448)
    assert (c.num_experts, c.local_experts, c.experts_per_tok) == (72, 72, 10)
    assert c.attention_multiplier == 1 / 128 and c.nope
    assert abs(c.q_prescale - 128 ** -0.5) < 1e-12
    assert tiny_config("granitemoehybrid").is_hybrid
    assert hash(c) == hash(dataclasses.replace(c))


@pytest.mark.parametrize("bad", [
    dict(layer_types=("mamba", "window")),
    dict(layer_types=("mamba",)),
    dict(experts_per_tok=9),
    dict(experts_held=6, expert_offset=4),
    dict(mamba_n_groups=3),
], ids=["unknown-kind", "wrong-depth", "top-k-over-width", "share-outside",
        "groups-not-dividing"])
def test_a_hybrid_config_that_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(tiny_hybrid_config(), **bad)


def test_the_old_families_take_no_hybrid_field():
    with pytest.raises(ValueError, match="granitemoehybrid"):
        dataclasses.replace(tiny_config("qwen2"), layer_types=("mamba",) * 4)
    q = tiny_config("qwen2")
    assert (q.kv_layers, q.is_hybrid, q.q_prescale) == (q.num_layers, False,
                                                        1.0)
    pool = paged_kv.PagedKVCache(q, num_pages=9, page_size=4, max_slots=2,
                                 pages_per_slot=4)
    assert pool.state is None and pool.state_bytes == 0
    assert pool.gather_state(0) == {}
    pool.check_invariants()


def test_one_manager_for_both_kinds_of_state(recurrent):
    CFG, params, _ = recurrent
    b = ContinuousBatcher(CFG, params, BCFG)
    pool = b.pool
    assert pool.pool.kv.shape[0] == CFG.kv_layers == 1
    shapes = hybrid.state_shapes(CFG, BCFG.max_slots)
    assert {leaf: a.shape for leaf, a in pool.state.items()} == shapes
    assert pool.state_leaf_bytes == {leaf: 4 * np.prod(shape)
                                     for leaf, shape in shapes.items()}
    assert pool.state_bytes == sum(pool.state_leaf_bytes.values()) > 0
    sid = b.submit(_ids(9), 5)
    b.step()
    pool.check_invariants()
    snap = pool.state_dict()
    got = pool.gather_state(b._streams[sid].slot)
    assert sorted(got) == sorted(shapes)
    for leaf, shape in shapes.items():
        assert snap["state_" + leaf].shape == shape
        assert got[leaf].shape == shape[:1] + shape[2:]
        assert np.abs(got[leaf]).max() > 0
    pool.load_state_dict(snap)
    pool.check_invariants()
    zeros = [0.0] * len(shapes)
    with pytest.raises(ValueError, match="not active"):
        pool.adopt_state(2, *zeros)
    with pytest.raises(ValueError, match="the state store holds"):
        pool.adopt_state(0, *zeros, 0.0)
    last = sorted(shapes)[-1]
    pool.state = {**pool.state, last: pool.state[last].astype(jnp.bfloat16)}
    with pytest.raises(AssertionError, match="float32"):
        pool.check_invariants()


def test_report_counts_routing_on_the_device_and_reads_it_only_when_asked():
    cfg = tiny_hybrid_config(experts_held=4, expert_offset=0)
    b = ContinuousBatcher(cfg, make_params(cfg), BCFG)
    r0 = b.report()
    assert r0["routed_assignments"] == r0["routed_local"] == 0
    for i in range(3):
        b.submit(_ids(6 + i, i), 9, rng_seed=i)
    b.run()
    r = b.report()
    steps, per = r["steps"], cfg.experts_per_tok * cfg.num_layers
    assert r["state_bytes"] == b.pool.state_bytes > 0
    assert r["routed_assignments"] == 3 * 8 * per       # 8 steps x 3 streams
    tokens = np.asarray(r["expert_tokens"])
    assert tokens.shape == (cfg.num_layers, 4)
    assert r["routed_local"] == tokens.sum()
    assert 0.25 < r["routed_local"] / r["routed_assignments"] < 0.75
    assert steps == 8
    # a one-block family's report has none of these keys
    q = tiny_config("qwen2")
    plain = ContinuousBatcher(q, transformer.init_params(q, jax.random.key(0)),
                              BCFG).report()
    assert not {"state_bytes", "expert_tokens", "routed_local"} & set(plain)


def test_the_step_carries_the_new_scopes_and_donates_four_buffers(params):
    b = ContinuousBatcher(CFG, params, BCFG)
    table, lengths = b.pool.device_tables()
    n = BCFG.max_slots
    args = (CFG, params, b.pool.pool.kv, b.pool.state,
            b._expert_tokens, table, lengths,
            jnp.zeros((n,), jnp.int32), jnp.asarray(b._free_key_rows),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32), None)
    lowered = batching._batched_hybrid_step_jit.lower(*args)
    text = lowered.as_text(debug_info=True)
    for scope in ("ssm.proj", "ssm.step", "moe.route", "moe.experts",
                  "moe.shared", "attn.decode", "paged_kv.write",
                  "unembed_sample"):
        assert scope in text, scope
    assert "ssm.scan" not in text
    # the pool's one leaf, conv, ssm, the expert counter
    assert text.count("tf.aliasing_output") == 4
    from edgellm_tpu.serve.decode import _prefill_jit

    pre = _prefill_jit.lower(CFG, params, jnp.zeros((1, 16), jnp.int32),
                             BCFG.span, None).as_text(debug_info=True)
    assert "ssm.scan" in pre and "ssm.step" not in pre
    from edgellm_tpu.lint.contracts import GRAPH_CONTRACTS

    assert "paged.decode_step_hybrid" in GRAPH_CONTRACTS
