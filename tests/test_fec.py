"""Self-healing links: in-band FEC repair, hedged hops, and the LinkHealth
SLO controller.

The load-bearing claims, each asserted here:
- ANY single corrupted byte of the FEC wire tree — every byte position of the
  chunk matrix and of the checksum words — is repaired in band: one decode,
  zero retransmissions, reconstruction bit-identical (non-finite and huge
  payload values included);
- two bad chunks in one parity group exceed XOR parity and fall through to
  the PR 2 retry ladder (the outer seal stays the authority);
- a clean link with FEC + hedging armed is bit-exact with the plain runtime,
  and a faulted build with both *disabled* traces the exact PR 2 graph
  (fingerprint identity — the no-cost-when-off contract);
- hedged routes win on drop-dominated links (hedge_wins counted);
- LinkHealth degrades on budget burn and RE-PROMOTES when the budget
  recovers, with full-window re-measure + clock dwell hysteresis (fake clock).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from edgellm_tpu.codecs.faults import FaultConfig, LinkPolicy, verify_payload
from edgellm_tpu.codecs import fec as fec_mod
from edgellm_tpu.codecs.fec import (FECConfig, HedgeConfig, LinkHealth,
                                    LinkHealthConfig, fec_decode, fec_encode)
from edgellm_tpu.codecs.faults import seal_payload
from edgellm_tpu.codecs.packing import get_wire_codec
from edgellm_tpu.codecs.wire_format import (WireFormat, flatten_bytes,
                                            unflatten_bytes)
from edgellm_tpu.models import init_params, tiny_config
from edgellm_tpu.parallel import SplitConfig, SplitRuntime, make_stage_mesh
from edgellm_tpu.utils.clock import FakeClock

CFG = tiny_config("qwen2", num_layers=6, hidden_size=32, num_heads=4,
                  vocab_size=128)
SPLIT = SplitConfig(cuts=(2,), hop_codecs=("int8_per_token",))
FEC = FECConfig(group_size=2, n_groups=2)  # small geometry: exhaustive sweeps


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(1))


@pytest.fixture(scope="module")
def ids():
    rng = np.random.default_rng(5)
    return jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 24)))


@pytest.fixture(scope="module")
def mesh():
    return make_stage_mesh(2)


def _counters(rt):
    return {k: v.tolist() for k, v in rt.link_counters().items()}


def _payload():
    return {"packed": jnp.arange(-12, 11, dtype=jnp.int8).reshape(23),
            "scale": jnp.asarray([1.5, -2.25, 3e-9], jnp.float32)}


def _tree_equal(a, b):
    """Bit-exact tree equality (byte compare — NaN == NaN by bit pattern)."""
    return all(np.asarray(x).tobytes() == np.asarray(y).tobytes()
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _flip(wire, leaf, pos, bit=1):
    """Flip one bit of one byte of a wire-tree leaf."""
    arr = np.asarray(wire[leaf])
    raw = bytearray(arr.tobytes())
    raw[pos] ^= 1 << bit
    new = np.frombuffer(bytes(raw), arr.dtype).reshape(arr.shape)
    return dict(wire, **{leaf: jnp.asarray(new)})


# ---------- config validation ----------


def test_config_validation():
    assert FECConfig().enabled and FECConfig().n_data_chunks == 16
    with pytest.raises(ValueError):
        FECConfig(group_size=0)
    with pytest.raises(ValueError):
        FECConfig(n_groups=-1)
    with pytest.raises(ValueError):
        FECConfig(enabled="yes")
    with pytest.raises(ValueError):
        HedgeConfig(routes=1)
    with pytest.raises(ValueError):
        LinkHealthConfig(window=0)
    with pytest.raises(ValueError):
        LinkHealthConfig(error_budget=0.0)
    with pytest.raises(ValueError):  # no hysteresis band
        LinkHealthConfig(promote_burn=1.0, degrade_burn=1.0)


def test_wire_accounting_matches_encode():
    from edgellm_tpu.codecs.faults import tree_nbytes

    sealed = seal_payload(_payload())
    n = tree_nbytes(sealed)
    for cfg in (FEC, FECConfig(group_size=4, n_groups=4),
                FECConfig(group_size=1, n_groups=3)):
        wire = fec_encode(sealed, cfg)
        assert tree_nbytes(wire) == cfg.wire_nbytes(n)
        assert cfg.overhead(n) == cfg.wire_nbytes(n) / n - 1.0


# ---------- FEC codec: exhaustive repair ----------


def test_clean_roundtrip_bit_exact():
    sealed = seal_payload(_payload())
    out, bad, fixed = fec_decode(fec_encode(sealed, FEC), FEC, sealed)
    assert _tree_equal(out, sealed)
    assert not bool(bad) and not bool(fixed)
    assert bool(verify_payload(out))


def test_every_single_corrupted_byte_is_repaired_without_retry():
    """The acceptance sweep: one flipped bit at EVERY byte position of the
    wire tree (data chunks, parity chunks, checksum words) must come back
    verified and bit-identical from ONE decode — in-band repair, zero
    retransmissions involved."""
    sealed = seal_payload(_payload())
    wire = fec_encode(sealed, FEC)
    for leaf in ("chunks", "words"):
        nbytes = np.asarray(wire[leaf]).nbytes
        for pos in range(nbytes):
            for bit in (0, 7):
                out, bad, _ = fec_decode(_flip(wire, leaf, pos, bit), FEC,
                                         sealed)
                assert bool(bad), f"{leaf} byte {pos} bit {bit} undetected"
                assert _tree_equal(out, sealed), \
                    f"{leaf} byte {pos} bit {bit} not repaired"
                assert bool(verify_payload(out))


def test_nonfinite_and_huge_values_repair_bit_exact():
    """Repair is pure byte algebra: NaN/Inf/huge payloads reconstruct to the
    exact original bit patterns (a value-space repair would laundering NaNs)."""
    weird = {"x": jnp.asarray([np.nan, np.inf, -np.inf, 3.4e38, -0.0, 1e-45],
                              jnp.float32),
             "y": jnp.asarray([np.float16("nan"), np.float16(65504)],
                              jnp.float16)}
    sealed = seal_payload(weird)
    wire = fec_encode(sealed, FEC)
    for pos in range(np.asarray(wire["chunks"]).nbytes):
        out, _, _ = fec_decode(_flip(wire, "chunks", pos), FEC, sealed)
        assert _tree_equal(out, sealed), f"byte {pos} not bit-exact"
        assert bool(verify_payload(out))


def test_two_bad_chunks_same_group_falls_through():
    """XOR parity repairs one chunk per group; two in the same group must be
    left corrupted so the outer seal fails and the retry ladder takes over."""
    sealed = seal_payload(_payload())
    wire = fec_encode(sealed, FEC)
    L = np.asarray(wire["chunks"]).shape[1]
    # data chunks 0 and n_groups share group 0 (c % n_groups)
    corrupt = _flip(_flip(wire, "chunks", 0), "chunks", FEC.n_groups * L)
    out, bad, _ = fec_decode(corrupt, FEC, sealed)
    assert bool(bad)
    assert not bool(verify_payload(out))  # retry ladder's cue


def test_two_bad_chunks_different_groups_both_repaired():
    sealed = seal_payload(_payload())
    wire = fec_encode(sealed, FEC)
    L = np.asarray(wire["chunks"]).shape[1]
    # chunks 0 and 1 are adjacent -> distinct groups (burst tolerance)
    out, bad, fixed = fec_decode(_flip(_flip(wire, "chunks", 0),
                                       "chunks", L + 1), FEC, sealed)
    assert bool(bad) and bool(fixed)
    assert _tree_equal(out, sealed)


def test_dropped_wire_is_unrepairable():
    sealed = seal_payload(_payload())
    wire = jax.tree.map(jnp.zeros_like, fec_encode(sealed, FEC))
    out, bad, _ = fec_decode(wire, FEC, sealed)
    assert bool(bad)
    assert not bool(verify_payload(out))


# ---------- the healing hop on the real split runtime ----------


def test_clean_link_fec_and_hedge_bit_exact(params, ids, mesh):
    """The whole FEC + hedge machinery on a clean (but active) link changes
    NOTHING: logits bit-identical to the plain runtime, zero repair work."""
    base = SplitRuntime(CFG, SPLIT, mesh)
    out0 = np.asarray(base.forward(base.place_params(params), ids))
    rt = SplitRuntime(CFG, SPLIT, mesh,
                      faults=FaultConfig(byte_budget=10**9),
                      policy=LinkPolicy(max_retries=1),
                      fec=FECConfig(group_size=2, n_groups=2),
                      hedge=HedgeConfig(routes=2))
    out1 = rt.forward(rt.place_params(params), ids, fault_step=3)
    np.testing.assert_array_equal(out0, np.asarray(out1))
    c = _counters(rt)
    assert c["hops"] == [1] and c["detected"] == [0]
    assert c["repaired"] == [0] and c["hedge_wins"] == [0]
    assert c["retried"] == [0] and c["substituted"] == [0]


def test_counter_keys_follow_config(mesh):
    from edgellm_tpu.codecs.faults import COUNTER_KEYS, FaultyLink

    plain = FaultyLink(FaultConfig(byte_budget=1), LinkPolicy())
    assert plain.counter_keys == COUNTER_KEYS and not plain.healing
    fec_link = FaultyLink(FaultConfig(byte_budget=1), LinkPolicy(),
                          fec=FECConfig())
    assert "repaired" in fec_link.counter_keys
    assert "hedge_wins" not in fec_link.counter_keys
    both = FaultyLink(FaultConfig(byte_budget=1), LinkPolicy(),
                      fec=FECConfig(), hedge=HedgeConfig())
    assert {"repaired", "hedge_wins"} <= set(both.counter_keys)
    off = FaultyLink(FaultConfig(byte_budget=1), LinkPolicy(),
                     fec=FECConfig(enabled=False),
                     hedge=HedgeConfig(enabled=False))
    assert off.counter_keys == COUNTER_KEYS and not off.healing


def test_single_flip_repaired_in_band_with_zero_retries(params, ids, mesh,
                                                        monkeypatch):
    """Hop-level proof of the headline property: exactly one corrupted wire
    byte on the first transmission is repaired with NO retransmission — the
    retried counter stays zero and the logits stay bit-exact."""
    base = SplitRuntime(CFG, SPLIT, mesh)
    out0 = np.asarray(base.forward(base.place_params(params), ids))

    calls = []  # transmissions are statically unrolled: trace-time state works
    real_inject = fec_mod.inject_faults

    def inject_one_flip(wire, key, cfg):
        calls.append(1)
        if len(calls) == 1 and isinstance(wire, dict) and "chunks" in wire:
            flipped = wire["chunks"].at[0, 0].set(wire["chunks"][0, 0] ^ 1)
            return dict(wire, chunks=flipped)
        return real_inject(wire, key, cfg)

    monkeypatch.setattr(fec_mod, "inject_faults", inject_one_flip)
    rt = SplitRuntime(CFG, SPLIT, mesh,
                      faults=FaultConfig(byte_budget=10**9),
                      policy=LinkPolicy(max_retries=2),
                      fec=FECConfig(group_size=2, n_groups=2))
    out1 = rt.forward(rt.place_params(params), ids, fault_step=0)
    np.testing.assert_array_equal(out0, np.asarray(out1))
    c = _counters(rt)
    assert c["detected"] == [1] and c["repaired"] == [1]
    assert c["retried"] == [0] and c["recovered"] == [0]
    assert c["substituted"] == [0]


def test_double_flip_same_group_falls_to_retry(params, ids, mesh, monkeypatch):
    """Two bad chunks in one parity group on the first transmission defeat
    XOR parity: the hop must fall through to a retry and recover there."""
    base = SplitRuntime(CFG, SPLIT, mesh)
    out0 = np.asarray(base.forward(base.place_params(params), ids))

    calls = []
    real_inject = fec_mod.inject_faults
    geometry = FECConfig(group_size=2, n_groups=2)

    def inject_two_flips(wire, key, cfg):
        calls.append(1)
        if len(calls) == 1 and isinstance(wire, dict) and "chunks" in wire:
            # chunks 0 and n_groups are both in group 0
            flipped = wire["chunks"].at[0, 0].set(wire["chunks"][0, 0] ^ 1)
            g = geometry.n_groups
            flipped = flipped.at[g, 0].set(flipped[g, 0] ^ 1)
            return dict(wire, chunks=flipped)
        return real_inject(wire, key, cfg)

    monkeypatch.setattr(fec_mod, "inject_faults", inject_two_flips)
    rt = SplitRuntime(CFG, SPLIT, mesh,
                      faults=FaultConfig(byte_budget=10**9),
                      policy=LinkPolicy(max_retries=2), fec=geometry)
    out1 = rt.forward(rt.place_params(params), ids, fault_step=0)
    np.testing.assert_array_equal(out0, np.asarray(out1))  # retry recovered
    c = _counters(rt)
    assert c["detected"] == [1] and c["repaired"] == [0]
    assert c["retried"] == [1] and c["recovered"] == [1]


def test_hedge_wins_on_drop_dominated_link(params, ids, mesh):
    """Parity can't fix a drop (every chunk zeroed); a second staggered route
    can. Over seeded drops the hedged link must log wins, and seeded runs
    must reproduce exactly. The seed is pinned under the installed default
    PRNG stream (jax 0.9.0, partitionable threefry): seed 4 draws 6 dropped
    hops of 8, 4 of them won by the hedge route."""
    rt = SplitRuntime(CFG, SPLIT, mesh,
                      faults=FaultConfig(drop_rate=0.4, seed=4),
                      policy=LinkPolicy(max_retries=2),
                      hedge=HedgeConfig(routes=2))
    placed = rt.place_params(params)
    for step in range(8):
        out = rt.forward(placed, ids, fault_step=step)
    assert np.isfinite(np.asarray(out)).all()
    c = _counters(rt)
    assert c["hops"] == [8] and c["hedge_wins"][0] > 0
    assert c["detected"][0] >= c["hedge_wins"][0]

    rt2 = SplitRuntime(CFG, SPLIT, mesh,
                       faults=FaultConfig(drop_rate=0.4, seed=4),
                       policy=LinkPolicy(max_retries=2),
                       hedge=HedgeConfig(routes=2))
    placed2 = rt2.place_params(params)
    for step in range(8):
        rt2.forward(placed2, ids, fault_step=step)
    assert _counters(rt2) == c


def test_fec_repairs_bitflips_on_live_link(params, ids, mesh):
    """Seeded low-rate bitflips over many steps: the FEC link repairs some
    hops in band, and every detected hop is accounted exactly once as
    repaired-or-clean / recovered / substituted."""
    rt = SplitRuntime(CFG, SPLIT, mesh,
                      faults=FaultConfig(bitflip_rate=0.0005, seed=2),
                      policy=LinkPolicy(max_retries=3),
                      fec=FECConfig(group_size=4, n_groups=4))
    placed = rt.place_params(params)
    for step in range(16):
        out = rt.forward(placed, ids, fault_step=step)
    assert np.isfinite(np.asarray(out)).all()
    c = _counters(rt)
    assert c["hops"] == [16]
    assert c["detected"][0] > 0 and c["repaired"][0] > 0
    assert c["repaired"][0] <= c["detected"][0]
    # hops that needed MORE than in-band repair either recovered via retry or
    # were substituted; none may be silently dropped
    assert c["retried"][0] >= c["recovered"][0]


def test_disabled_fec_fingerprint_identical_to_pre_feature_graph(params, ids,
                                                                 mesh):
    """The no-cost-when-off contract: a faulted build with FEC and hedging
    disabled hashes to the EXACT same jaxpr as a build that never heard of
    fec.py (same check graphlint enforces in CI)."""
    from edgellm_tpu.lint.contracts import graph_fingerprint

    faults = FaultConfig(bitflip_rate=0.01, seed=0)
    policy = LinkPolicy(max_retries=1)
    rt_pre = SplitRuntime(CFG, SPLIT, mesh, faults=faults, policy=policy)
    rt_off = SplitRuntime(CFG, SPLIT, mesh, faults=faults, policy=policy,
                          fec=FECConfig(enabled=False),
                          hedge=HedgeConfig(enabled=False))
    placed = rt_pre.place_params(params)
    imps = jnp.zeros((1, ids.shape[1]), jnp.float32)
    step = jnp.asarray(0, jnp.int32)
    fp_pre = graph_fingerprint(rt_pre._forward, placed, ids, imps, step)
    fp_off = graph_fingerprint(rt_off._forward, placed, ids, imps, step)
    assert fp_pre == fp_off
    # and an ENABLED build must differ (the identity test has teeth)
    rt_on = SplitRuntime(CFG, SPLIT, mesh, faults=faults, policy=policy,
                         fec=FECConfig(group_size=2, n_groups=2))
    assert graph_fingerprint(rt_on._forward, placed, ids, imps, step) != fp_pre


# ---------- LinkHealth SLO controller ----------


def _obs(hops=4, detected=0, repaired=0, retried=0):
    return {"hops": [hops], "detected": [detected], "repaired": [repaired],
            "retried": [retried]}


def test_link_health_degrades_on_burn_and_repromotes():
    clk = FakeClock()
    lh = LinkHealth(3, LinkHealthConfig(window=4, error_budget=0.1,
                                        degrade_burn=1.0, promote_burn=0.25),
                    clock=clk)
    # burn = unrepaired corruption rate / budget: 2/4 hops corrupted = 5x
    for _ in range(3):
        assert lh.observe(_obs(detected=2)) == 0  # window not full yet
    assert lh.observe(_obs(detected=2)) == 1      # full window, burn 5 >= 1
    assert len(lh._window) == 0                   # full re-measure at tier 1
    # tier 1 still burning -> degrade to the floor
    for _ in range(3):
        assert lh.observe(_obs(detected=2)) == 1
    assert lh.observe(_obs(detected=2)) == 2
    assert lh.observe(_obs(detected=2)) == 2      # floor holds
    # budget recovers -> re-promote one tier per full clean window
    for _ in range(4):
        lh.observe(_obs())
    assert lh.tier == 1
    for _ in range(4):
        lh.observe(_obs())
    assert lh.tier == 0 and lh.switches == 4


def test_link_health_repair_discounts_burn():
    """In-band repaired corruption does NOT burn the budget — only the
    unrepaired remainder does."""
    lh = LinkHealth(2, LinkHealthConfig(window=4, error_budget=0.1))
    for _ in range(8):
        lh.observe(_obs(detected=2, repaired=2))
    assert lh.tier == 0 and lh.burn_rate == 0.0
    assert lh.repair_rate == 1.0 and lh.corruption_rate == 0.5


def test_link_health_dwell_hysteresis_under_fake_clock():
    """min_dwell_s is a wall-clock floor between switches: a clean window
    inside the dwell may NOT re-promote; after the dwell it must."""
    clk = FakeClock()
    lh = LinkHealth(2, LinkHealthConfig(window=2, error_budget=0.1,
                                        min_dwell_s=10.0), clock=clk)
    lh.observe(_obs(detected=2))
    assert lh.observe(_obs(detected=2)) == 1      # degrade at t=0
    for _ in range(6):                            # clean, but inside dwell
        assert lh.observe(_obs()) == 1
    clk.set_time(9.9)
    assert lh.observe(_obs()) == 1                # still inside
    clk.set_time(10.0)
    assert lh.observe(_obs()) == 0                # dwell elapsed -> promote
    # and the switch re-arms the dwell: an immediately-burning window cannot
    # flap back down before t=20
    lh.observe(_obs(detected=4))
    assert lh.observe(_obs(detected=4)) == 0
    clk.set_time(20.0)
    lh.observe(_obs(detected=4))
    assert lh.observe(_obs(detected=4)) == 1


def test_link_health_summary_shape():
    lh = LinkHealth(2, LinkHealthConfig(window=2))
    lh.observe(_obs(detected=1, repaired=1, retried=1))
    s = lh.summary()
    assert {"tier", "switches", "observations", "window", "error_budget",
            "burn_rate", "corruption_rate", "repair_rate", "retry_rate",
            "hedge_win_rate"} <= set(s)
    assert s["observations"] == 1 and s["tier"] == 0


# ---------- eval + CLI integration ----------


def test_split_eval_healing_requires_enabled_faults(params):
    from edgellm_tpu.eval.split_eval import run_split_eval

    toks = np.random.default_rng(0).integers(0, CFG.vocab_size, (256,))
    kw = dict(cuts=(2,), hop_codecs=["int8_per_token"], max_length=64,
              stride=32, time_hops=False)
    with pytest.raises(ValueError, match="enabled faults"):
        run_split_eval(CFG, params, toks, fec={"group_size": 2}, **kw)
    with pytest.raises(ValueError, match="enabled faults"):
        run_split_eval(CFG, params, toks, hedge={"routes": 2}, **kw)
    with pytest.raises(ValueError, match="enabled faults"):
        run_split_eval(CFG, params, toks, link_health={"window": 2}, **kw)


def test_split_eval_full_healing_ladder(params):
    """The chaos-config shape end to end: faults + retries + FEC + hedge +
    LinkHealth over the tier ladder, with the health blocks in the result."""
    from edgellm_tpu.eval.split_eval import run_split_eval

    toks = np.random.default_rng(0).integers(0, CFG.vocab_size, (1024,))
    res = run_split_eval(
        CFG, params, toks, cuts=(2,), hop_codecs=["int8_per_token"],
        max_length=64, stride=32, time_hops=False,
        faults={"bitflip_rate": 0.002, "drop_rate": 0.1, "seed": 0},
        link_policy={"max_retries": 2,
                     "tiers": ["int4_per_token", "ternary_per_token"]},
        fec={"group_size": 2, "n_groups": 2}, hedge={"routes": 2},
        link_health={"window": 2, "error_budget": 0.05})
    assert np.isfinite(res["ppl"])
    c = res["link_counters"]
    assert c["detected"][0] > 0
    assert "repaired" in c and "hedge_wins" in c
    assert res["fec"]["group_size"] == 2 and res["hedge"]["routes"] == 2
    assert res["link_health"]["observations"] == res["chunks"]
    assert res["final_tier"] == res["link_health"]["tier"]


def test_run_fault_sweep_passes_healing_only_to_faulted_points(params):
    from edgellm_tpu.eval.split_eval import run_fault_sweep, run_split_eval

    toks = np.random.default_rng(0).integers(0, CFG.vocab_size, (512,))
    kw = dict(cuts=(2,), hop_codecs=["int8_per_token"], max_length=64,
              stride=32, time_hops=False)
    base = run_split_eval(CFG, params, toks, **kw)
    sweep = run_fault_sweep(CFG, params, toks, rates=[0.0, 0.3],
                            knob="drop_rate", link_policy={"max_retries": 2},
                            hedge={"routes": 2}, **kw)
    # rate 0: healing kwargs withheld, the exact fault-free baseline
    assert sweep[0]["ppl"] == base["ppl"]
    assert "link_counters" not in sweep[0]
    assert sweep[1]["link_counters"]["hedge_wins"][0] >= 0
    assert sweep[1]["hedge"]["routes"] == 2


def test_params_json_validates_healing_keys(tmp_path):
    """run.py must die fast, naming the bad key, before any model loads."""
    import json

    from edgellm_tpu.run import main

    def run_with(body):
        p = tmp_path / "params.json"
        p.write_text(json.dumps(body))
        return main(["--params", str(p), "--model", "qwen2-0.5b"])

    split = {"experiment": "split", "cuts": [2],
             "hop_codecs": ["int8_per_token"], "max_length": 64, "stride": 32,
             "faults": {"drop_rate": 0.1}}
    with pytest.raises(SystemExit, match="fec"):
        run_with({**split, "fec": {"group_sizes": 4}})  # typo'd field
    with pytest.raises(SystemExit, match="hedge"):
        run_with({**split, "hedge": {"routes": 1}})  # constructor rejects
    with pytest.raises(SystemExit, match="link_health"):
        run_with({**split, "link_health": ["not", "a", "dict"]})
    with pytest.raises(SystemExit, match="faults"):
        run_with({**split, "faults": {}, "fec": {"group_size": 4}})
    with pytest.raises(SystemExit, match="split"):  # split-only keys
        run_with({"ratios": [0], "layers_of_interest": [1], "max_length": 64,
                  "stride": 32, "methods": ["last_row"],
                  "fec": {"group_size": 4}})


def test_fault_report_prints_counters_and_health(capsys):
    from edgellm_tpu.run import _print_fault_report

    _print_fault_report({
        "link_counters": {"hops": [4, 4], "detected": [2, 1],
                          "repaired": [1, 1], "retried": [1, 0],
                          "hedge_wins": [0, 1], "substituted": [1, 0]},
        "tier_switches": [[3, 1], [9, 0]],
        "link_health": {"tier": 0, "burn_rate": 0.5, "corruption_rate": 0.375,
                        "repair_rate": 0.667, "retry_rate": 0.125,
                        "hedge_win_rate": 0.125, "error_budget": 0.05,
                        "observations": 12, "switches": 2, "window": 2},
    })
    out = capsys.readouterr().out
    # one unified obs-registry table: per-hop counters, totals, health gauges
    assert "edgellm_link_detected_total" in out
    assert "edgellm_link_repaired_total" in out
    assert "edgellm_link_hedge_wins_total" in out
    assert 'hop="0"' in out and 'hop="1"' in out and 'hop="total"' in out
    assert "edgellm_link_health_burn_rate" in out
    _print_fault_report({})
    assert "no link counters" in capsys.readouterr().out


# ---------- the flat wire stream the seal and the FEC framing stand on ----------


def _sealed_payload(name="int8_per_token"):
    codec = get_wire_codec(name)
    hidden = jnp.asarray(np.random.default_rng(1).standard_normal((1, 4, 32)),
                         jnp.float32)
    return codec, hidden, seal_payload(codec.encode(hidden))


def test_wire_format_roundtrip_is_the_sealed_tree():
    codec, hidden, sealed = _sealed_payload()
    wf = WireFormat.for_codec(codec, hidden.shape, hidden.dtype)
    back = wf.from_wire(wf.to_wire(sealed))
    for a, b in zip(jax.tree_util.tree_leaves(sealed),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert wf.wire_nbytes == wf.payload_nbytes + 8


@pytest.mark.parametrize("name", [
    "int8_per_token", "int8_per_channel", "int4_per_token",
    "int4_per_channel", "ternary_mean", "ternary_max"])
def test_wire_format_carries_every_packed_codec(name):
    """Each packed codec's payload tree (int8 codes, nibbles, crumbs, their
    scales and minima) through one flat uint8 buffer and back: the seal
    verifies and the decode is the bits of a decode that never left."""
    codec, hidden, sealed = _sealed_payload(name)
    wf = WireFormat.for_codec(codec, hidden.shape, hidden.dtype)
    buf = wf.to_wire(sealed)
    assert buf.dtype == jnp.uint8 and buf.shape == (wf.wire_nbytes,)
    assert wf.payload_nbytes == codec.payload_bytes(hidden.shape)
    back = wf.from_wire(buf)
    assert bool(verify_payload(back))
    want = np.asarray(codec.decode(codec.encode(hidden)))
    np.testing.assert_array_equal(np.asarray(codec.decode(back["p"])), want)
    assert not np.array_equal(want, np.asarray(hidden)), \
        "decode identical to the raw hidden: quantization never happened"


def test_corrupted_wire_buffer_fails_verification():
    codec, hidden, sealed = _sealed_payload()
    wf = WireFormat.for_codec(codec, hidden.shape, hidden.dtype)
    buf = np.asarray(wf.to_wire(sealed))
    assert bool(verify_payload(wf.from_wire(jnp.asarray(buf))))
    for pos in (0, 7, 8, buf.size // 2, buf.size - 1):  # seal AND payload
        bad = buf.copy()
        bad[pos] ^= 0x40
        assert not bool(verify_payload(wf.from_wire(jnp.asarray(bad)))), \
            f"flipped byte {pos} slipped through the wire format"


def test_fec_repairs_the_flat_wire_stream():
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


def test_flat_stream_is_shared_by_fec_and_wire_format():
    # the FEC chunker and the wire format must serialize the SAME byte order
    codec, hidden, sealed = _sealed_payload()
    wf = WireFormat.for_codec(codec, hidden.shape, hidden.dtype)
    np.testing.assert_array_equal(np.asarray(wf.to_wire(sealed)),
                                  np.asarray(flatten_bytes(sealed)))
    spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), sealed)
    back = unflatten_bytes(wf.to_wire(sealed), spec)
    assert bool(verify_payload(back))
