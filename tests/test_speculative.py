"""Speculative split decode: stage-0 draft, k-token batched verify (PR 11).

The headline contract is LOSSLESS acceptance: at temperature 0 every token a
speculative ``generate_split`` emits is the argmax the vanilla loop would
have produced — token-identical on the same seed/plan at any k, because the
accept rule emits the verify pass's own argmax whether or not the draft
agreed. Also covered here:

- ``verify_step`` logits == k sequential ``decode_step`` logits (the one
  quantized (1, k, D) boundary block carries the same information as k
  single-token hops);
- the verify wire-byte contract: one burst's hop bytes == k x the
  single-token hop bytes;
- kill-between-draft-and-verify checkpoint/resume: the resumed stream is
  token-identical to the uninterrupted run at k in {1, 4, 8} (burst
  boundaries depend only on the committed prefix);
- jit discipline: one draft executable and one verify executable per
  (capacity, k) — the second same-shape run compiles nothing;
- a disabled SpecConfig is pure host-side dispatch (no verify executables
  built, vanilla tokens out), and the ``run.py`` params validator accepts
  the shipped spec config while refusing the documented foot-guns;
- greedy identity survives a faulty boundary wire when retries are allowed
  to recover corrupt payloads (substitution would legitimately diverge).
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edgellm_tpu.codecs.faults import FaultConfig, LinkPolicy
from edgellm_tpu.models import init_params, tiny_config
from edgellm_tpu.parallel import SplitConfig, SplitRuntime, make_stage_mesh
from edgellm_tpu.serve import (CheckpointError, RecoveryConfig,
                               generate_split, resume_split)
from edgellm_tpu.serve.speculative import (MAX_SPEC_K, SpecConfig,
                                           draft_from_params,
                                           generate_speculative,
                                           spec_capacity)

CFG = tiny_config("qwen2", num_layers=6, hidden_size=32, num_heads=4,
                  vocab_size=128)
SPLIT = SplitConfig(cuts=(2,), hop_codecs=("int8_per_token",))
PROMPT, MAX_NEW = 10, 9
KS = [1, 4, 8]
#: one shared capacity, big enough for the widest verify window, so the
#: vanilla and every spec leg trace against the same cache geometry
CAP = spec_capacity(PROMPT, MAX_NEW, max(KS))


def _ids(batch=1, seed=11):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, CFG.vocab_size, (batch, PROMPT)))


@pytest.fixture(scope="module")
def setup():
    params = init_params(CFG, jax.random.key(1))
    rt = SplitRuntime(CFG, SPLIT, make_stage_mesh(2))
    placed = rt.place_params(params)
    ids = _ids()
    vanilla = np.asarray(generate_split(rt, placed, ids, MAX_NEW,
                                        capacity=CAP))
    return dict(params=params, rt=rt, placed=placed, ids=ids,
                vanilla=vanilla)


# ---------------------------------------------------------------------------
# lossless greedy acceptance: token-identical to vanilla at every k
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", KS)
def test_greedy_token_identical_to_vanilla(setup, k):
    s = setup
    stats = {}
    toks = generate_split(s["rt"], s["placed"], s["ids"], MAX_NEW,
                          capacity=CAP, speculative=SpecConfig(k=k),
                          raw_params=s["params"], stats=stats)
    assert toks.shape == (1, MAX_NEW)
    assert np.array_equal(np.asarray(toks), s["vanilla"])
    sp = stats["speculative"]
    assert sp["k"] == k
    assert sp["bursts"] >= 1
    # every burst is one boundary round-trip for 1..k emitted tokens
    assert 0.0 < sp["hops_per_token"] <= 1.0
    if k == 1:
        # the degenerate window drafts nothing and must cost exactly the
        # vanilla one-hop-per-token rate
        assert sp["drafted"] == 0
        assert sp["hops_per_token"] == 1.0


def test_spec_stats_account_every_draft(setup):
    s = setup
    stats = {}
    generate_split(s["rt"], s["placed"], s["ids"], MAX_NEW, capacity=CAP,
                   speculative=SpecConfig(k=4), raw_params=s["params"],
                   stats=stats)
    sp = stats["speculative"]
    assert sp["accepted"] + sp["rejected"] == sp["drafted"]
    assert sp["drafted"] == sp["bursts"] * 3  # k-1 drafts per burst
    assert sp["acceptance_rate"] == pytest.approx(
        sp["accepted"] / sp["drafted"] if sp["drafted"] else 0.0)
    assert stats["decode_steps"] == MAX_NEW - 1  # emitted after token 0


def test_temperature_sampling_runs_with_spec_stats(setup):
    """temperature > 0 uses residual resampling — distribution-identical,
    not bitwise, so assert shape/range and the bookkeeping, not parity."""
    s = setup
    stats = {}
    toks = generate_split(s["rt"], s["placed"], s["ids"], MAX_NEW,
                          capacity=CAP, temperature=0.8,
                          rng_key=jax.random.key(5),
                          speculative=SpecConfig(k=4),
                          raw_params=s["params"], stats=stats)
    out = np.asarray(toks)
    assert out.shape == (1, MAX_NEW)
    assert (0 <= out).all() and (out < CFG.vocab_size).all()
    assert stats["speculative"]["bursts"] >= 1


# ---------------------------------------------------------------------------
# the verify pass itself: k positions in one hop == k single-token steps
# ---------------------------------------------------------------------------


def test_verify_step_matches_stepwise_decode(setup):
    s = setup
    rt, placed, ids = s["rt"], s["placed"], s["ids"]
    k = 4
    rng = np.random.default_rng(3)
    feed = rng.integers(0, CFG.vocab_size, (k,))

    _, cache_a = rt.prefill_decode(placed, ids, CAP)
    step_logits = []
    for t in feed:
        logits, cache_a = rt.decode_step(placed, cache_a,
                                         jnp.asarray([t], jnp.int32))
        step_logits.append(np.asarray(logits))

    _, cache_b = rt.prefill_decode(placed, ids, CAP)
    vlogits, cache_b = rt.verify_step(placed, cache_b,
                                      jnp.asarray(feed[None, :], jnp.int32))
    assert vlogits.shape == (1, k, CFG.vocab_size)
    assert int(cache_b["length"]) == PROMPT + k
    for j in range(k):
        np.testing.assert_allclose(np.asarray(vlogits[:, j]), step_logits[j],
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("k", KS)
def test_verify_hop_bytes_scale_linearly(setup, k):
    """ONE verify burst moves exactly k single-token payloads' worth of
    bytes per hop — the amortization claim is in round-trips, not bytes."""
    rt = setup["rt"]
    (per_burst,) = rt.verify_hop_bytes(1, k)
    (per_step,) = rt.decode_hop_bytes(1)
    assert per_burst == k * per_step


def test_jit_miss_free_after_first_burst(setup):
    """Second same-shape run compiles nothing: the fill level rides as a
    traced scalar through one draft executable and one verify executable
    per (capacity, k)."""
    s = setup
    spec = SpecConfig(k=4)
    kw = dict(capacity=CAP, speculative=spec, raw_params=s["params"])
    generate_split(s["rt"], s["placed"], s["ids"], MAX_NEW, **kw)  # warm
    n_verify = len(s["rt"]._verify_fns_cache)
    stats = {}
    generate_split(s["rt"], s["placed"], s["ids"], MAX_NEW, stats=stats, **kw)
    assert stats["speculative"]["draft_step_cache_misses"] == 0
    assert len(s["rt"]._verify_fns_cache) == n_verify


def test_disabled_spec_is_pure_dispatch(setup):
    """SpecConfig(enabled=False) must run the vanilla loop untouched: same
    tokens, and the runtime never builds a verify executable (the jaxpr
    half of this contract — fingerprint identity — is graphlint's
    split.decode_step.spec-disabled-identity check)."""
    s = setup
    rt2 = SplitRuntime(CFG, SPLIT, make_stage_mesh(2))
    placed2 = rt2.place_params(s["params"])
    toks = generate_split(rt2, placed2, s["ids"], MAX_NEW, capacity=CAP,
                          speculative=SpecConfig(enabled=False, k=4),
                          raw_params=s["params"])
    assert np.array_equal(np.asarray(toks), s["vanilla"])
    assert len(rt2._verify_fns_cache) == 0


# ---------------------------------------------------------------------------
# config / argument validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kwargs, msg", [
    ({"k": 0}, "k must be in"),
    ({"k": MAX_SPEC_K + 1}, "k must be in"),
    ({"k": True}, "k must be an int"),
    ({"k": "4"}, "k must be an int"),
    ({"draft_source": "ngram"}, "unknown draft_source"),
    ({"draft_layers": 0}, "draft_layers must be"),
    ({"draft_layers": False}, "draft_layers must be"),
])
def test_spec_config_rejects_bad_fields(kwargs, msg):
    with pytest.raises(ValueError, match=msg):
        SpecConfig(**kwargs)


def test_draft_layers_bounded_by_stage0(setup):
    """The draft must run hop-free on stage 0: draft_layers is capped at
    cut + 1 layers, and defaults to exactly that."""
    params = setup["params"]
    cut = SPLIT.cuts[0]
    dcfg, dparams = draft_from_params(CFG, params, SpecConfig(), cut=cut)
    assert dcfg.num_layers == cut + 1
    assert jax.tree_util.tree_leaves(dparams["layers"])[0].shape[0] == cut + 1
    with pytest.raises(ValueError, match="stage 0 owns"):
        draft_from_params(CFG, params, SpecConfig(draft_layers=cut + 2),
                          cut=cut)


def test_generate_speculative_guards(setup):
    s = setup
    spec = SpecConfig(k=4)
    with pytest.raises(ValueError, match="enabled"):
        generate_speculative(s["rt"], s["placed"], s["ids"], MAX_NEW,
                             spec=SpecConfig(enabled=False),
                             raw_params=s["params"])
    with pytest.raises(ValueError, match="raw_params"):
        generate_speculative(s["rt"], s["placed"], s["ids"], MAX_NEW,
                             spec=spec)
    with pytest.raises(ValueError, match="batch"):
        generate_speculative(s["rt"], s["placed"], _ids(batch=2), MAX_NEW,
                             spec=spec, raw_params=s["params"])
    with pytest.raises(ValueError, match="cache overflow"):
        generate_speculative(s["rt"], s["placed"], s["ids"], MAX_NEW,
                             spec=spec, raw_params=s["params"],
                             capacity=PROMPT + MAX_NEW)
    from edgellm_tpu.serve.recovery import StageFailure
    with pytest.raises(ValueError, match="failover drills"):
        generate_speculative(
            s["rt"], s["placed"], s["ids"], MAX_NEW, spec=spec,
            raw_params=s["params"],
            recovery=RecoveryConfig(stage_failure=StageFailure(stage=1,
                                                               at_step=2)))


def test_spec_capacity_math():
    assert spec_capacity(10, 9, 1) == 19
    assert spec_capacity(10, 9, 4) == 21  # last burst writes k-2 rows past
    assert spec_capacity(10, 9, 8) == 25


# ---------------------------------------------------------------------------
# checkpoint / resume: kill between draft and verify, resume, same stream
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", KS)
def test_kill_between_draft_and_verify_resume_identical(setup, tmp_path, k):
    """The ISSUE's mid-verify-burst drill: the process dies AFTER the draft
    proposed its tokens but BEFORE the verify hop committed anything. The
    checkpoint on disk is the last burst boundary; the resumed run must
    re-draft from the committed prefix and emit the exact uninterrupted
    stream (which at temperature 0 is the vanilla stream)."""
    s = setup
    rt = SplitRuntime(CFG, SPLIT, make_stage_mesh(2))  # isolated: patched
    placed = rt.place_params(s["params"])
    spec = SpecConfig(k=k)
    ckpt = str(tmp_path / f"spec_{k}.ckpt")
    # 0-indexed verify call to kill: a run has at least ceil(8/k) bursts
    # (full acceptance emits k per burst), so this is always reached; at
    # k=8 the very first verify dies and resume starts from the prefill
    # checkpoint (token 0 only)
    fail_at = {1: 2, 4: 1, 8: 0}[k]
    orig = rt.verify_step
    calls = {"n": 0}

    def dying_verify(placed_params, cache, token_ids):
        if calls["n"] == fail_at:
            raise RuntimeError("simulated kill between draft and verify")
        calls["n"] += 1
        return orig(placed_params, cache, token_ids)

    rt.verify_step = dying_verify
    try:
        with pytest.raises(RuntimeError, match="simulated kill"):
            generate_split(rt, placed, s["ids"], MAX_NEW, capacity=CAP,
                           speculative=spec, raw_params=s["params"],
                           recovery=RecoveryConfig(checkpoint_path=ckpt,
                                                   checkpoint_every=1))
    finally:
        rt.verify_step = orig
    assert os.path.exists(ckpt)

    rstats = {}
    full = resume_split(rt, placed, ckpt, speculative=spec,
                        raw_params=s["params"], stats=rstats)
    assert rstats["resumed_from_step"] < MAX_NEW - 1
    assert rstats["recovery_counters"]["resume_ok"] == 1
    assert np.array_equal(np.asarray(full), s["vanilla"])


def test_resume_refuses_spec_window_mismatch(setup, tmp_path):
    s = setup
    ckpt = str(tmp_path / "spec.ckpt")
    stats = {}
    generate_split(s["rt"], s["placed"], s["ids"], MAX_NEW, capacity=CAP,
                   speculative=SpecConfig(k=4), raw_params=s["params"],
                   recovery=RecoveryConfig(checkpoint_path=ckpt,
                                           halt_at_step=3),
                   stats=stats)
    assert stats["halted_at_step"] >= 3  # halts on the next burst boundary
    with pytest.raises(CheckpointError, match="speculative"):
        resume_split(s["rt"], s["placed"], ckpt, speculative=SpecConfig(k=8),
                     raw_params=s["params"])
    # the matching window resumes to the full vanilla stream
    full = resume_split(s["rt"], s["placed"], ckpt, speculative=SpecConfig(k=4),
                        raw_params=s["params"])
    assert np.array_equal(np.asarray(full), s["vanilla"])


# ---------------------------------------------------------------------------
# faulty boundary wire: greedy identity survives when retries recover
# ---------------------------------------------------------------------------


def test_greedy_identity_on_retrying_faulty_link(setup):
    """Corrupt verify payloads retried to recovery leave the accepted tokens
    untouched — the spec loop rides the sealed/verified hop ladder
    unchanged. (A substitute-on-fail policy would legitimately diverge:
    vanilla and spec see different fault streams.)"""
    s = setup
    faults = FaultConfig(bitflip_rate=2e-4, seed=3)
    policy = LinkPolicy(max_retries=4)
    rt_f = SplitRuntime(CFG, SPLIT, make_stage_mesh(2), faults=faults,
                        policy=policy)
    placed_f = rt_f.place_params(s["params"])
    vanilla = np.asarray(generate_split(rt_f, placed_f, s["ids"], MAX_NEW,
                                        capacity=CAP))
    stats = {}
    toks = generate_split(rt_f, placed_f, s["ids"], MAX_NEW, capacity=CAP,
                          speculative=SpecConfig(k=4),
                          raw_params=s["params"], stats=stats)
    assert np.array_equal(np.asarray(toks), vanilla)
    # spec made fewer boundary round-trips than the vanilla leg
    assert stats["link_counters"]["hops"][0] < MAX_NEW


# ---------------------------------------------------------------------------
# run.py params validation: the shipped config and the refusals
# ---------------------------------------------------------------------------


def _spec_params():
    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "configs", "split11_qwen_spec.json")) as f:
        return json.load(f)


def test_params_validation_accepts_spec_config():
    from edgellm_tpu.run import _validate_params_json

    _validate_params_json(_spec_params())  # must not raise


@pytest.mark.parametrize("patch, msg", [
    ({"experiment": "split", "max_length": 64, "stride": 32},
     "only applies to experiment 'serve'"),
    ({"cuts": None}, "add 'cuts'"),
    ({"speculative": [4]}, "object of SpecConfig fields"),
    ({"speculative": {"k": 4, "window": 2}}, "unknown field"),
    ({"speculative": {"k": 0}}, "k must be in"),
    ({"speculative": {"k": 4, "draft_source": "ngram"}}, "draft_source"),
    ({"fused_hops": "remote"}, "unknown key"),
    ({"batching": {"page_size": 8, "num_pages": 17, "max_slots": 4,
                   "pages_per_slot": 4}}, "drop"),
])
def test_params_validation_rejects_spec_footguns(patch, msg):
    from edgellm_tpu.run import _validate_params_json

    p = _spec_params()
    p.update(patch)
    if p.get("cuts") is None:
        p.pop("cuts", None)
        p.pop("hop_codecs", None)
    with pytest.raises(SystemExit, match=msg):
        _validate_params_json(p)


def test_params_validation_disabled_spec_allows_batching():
    from edgellm_tpu.run import _validate_params_json

    p = _spec_params()
    p["speculative"] = {"enabled": False, "k": 4}
    p["batching"] = {"page_size": 8, "num_pages": 17, "max_slots": 4,
                     "pages_per_slot": 4}
    _validate_params_json(p)  # must not raise


def test_soak_identity_replay_uses_the_spec_loop():
    """A speculative front soaked at temperature > 0 must still pass the
    soak's bit-identical replay: residual resampling draws a different
    stream than vanilla sampling, so the reference must run the same spec
    loop (with the front's capacity bump) — not the vanilla one."""
    from edgellm_tpu.serve import ServeFront
    from edgellm_tpu.serve.soak import SoakConfig, run_soak
    from edgellm_tpu.utils.clock import FakeClock

    params = init_params(CFG, jax.random.key(1))
    rt = SplitRuntime(CFG, SPLIT, make_stage_mesh(2))
    clk = FakeClock()
    front = ServeFront(CFG, params, split_runtime=rt,
                       speculative=SpecConfig(k=4), clock=clk)
    soak = SoakConfig(n_requests=3, arrival_rate=1.0, prompt_len=8,
                      max_new_tokens=6, deadline_s=120.0)
    art = run_soak(front, soak, clock=clk)
    assert art["outcomes"].get("completed") == 3
    identity = art["token_identity"]
    assert identity["checked"] == 3 and identity["ok"], identity
