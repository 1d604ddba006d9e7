"""Disaggregated prefill/decode: token identity by construction, the
migration ladder (detect -> repair -> retry -> degrade), the failure
matrix (prefill kill mid-migration, decode kill, dead link), cross-tier
adoption refusals, and the wire-byte contract.

The load-bearing claim: for every COMPLETED request, disagg serving emits
BIT-IDENTICAL tokens to colocated serving — greedy and sampled, fp and
quantized tiers, under corruption and under mid-workload worker kills —
because the handoff is a verified byte move of the staged pool rows
(never a requantize) injected before any decode step runs.
"""
import dataclasses
import os

import numpy as np
import pytest

import jax

from edgellm_tpu.codecs.faults import FaultConfig
from edgellm_tpu.codecs.fec import FECConfig, HedgeConfig
from edgellm_tpu.codecs.wire_format import seal_payload, tree_nbytes
from edgellm_tpu.models import init_params, tiny_config
from edgellm_tpu.models.paged_kv import KVTierMismatchError, PagedKVCache
from edgellm_tpu.obs.flight import FlightRecorder, configure_flight
from edgellm_tpu.serve.batching import BatchingConfig, ContinuousBatcher
from edgellm_tpu.serve.disagg import (DisaggConfig, DisaggServer,
                                      MigrationError, MigrationLink,
                                      PrefillWorkerLost,
                                      migration_wire_nbytes)
from edgellm_tpu.serve.recovery import (CheckpointError,
                                        CheckpointTierMismatchError)

CFG = tiny_config("qwen2", num_layers=2, hidden_size=32, num_heads=4,
                  vocab_size=128)
BCFG = BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                      pages_per_slot=4)
QCFG = dataclasses.replace(BCFG, kv_codec="int8_per_channel")


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(1))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, size=n).astype(np.int32)


#: a mixed workload: multi-page prompts, greedy + sampled, a 1-token
#: degenerate, different seeds
REQS = [(_prompt(5, 1), 6, 0.0, 0),
        (_prompt(11, 2), 8, 0.7, 3),
        (_prompt(9, 4), 5, 1.1, 9),
        (_prompt(3, 3), 1, 0.0, 7)]


def _colocated(params, bcfg, reqs=REQS):
    ref = ContinuousBatcher(CFG, params, bcfg)
    sids = [ref.submit(p, m, temperature=t, rng_seed=s)
            for p, m, t, s in reqs]
    res = ref.run()
    return [res[s] for s in sids]


def _assert_identical(server, expected, reqs=REQS):
    sids = [server.submit(p, m, temperature=t, rng_seed=s)
            for p, m, t, s in reqs]
    res = server.run()
    for want, s in zip(expected, sids):
        assert np.array_equal(want, res[s]), (want, res[s])


# ---------------------------------------------------------------------------
# token identity by construction: disagg == colocated, fp + quantized
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bcfg", [BCFG, QCFG], ids=["fp", "int8"])
def test_disagg_token_identity(params, bcfg):
    expected = _colocated(params, bcfg)
    srv = DisaggServer(CFG, params, bcfg, DisaggConfig())
    _assert_identical(srv, expected)
    rep = srv.report()["disagg"]
    assert rep["migrations"] == 3          # the 1-token request never ships
    assert rep["migrated_pages"] >= 4
    assert not rep["degraded"]
    assert rep["link"]["failed"] == 0
    assert rep["recompute_tokens"] == 0


def test_disagg_identity_with_fec_and_hedge(params):
    expected = _colocated(params, QCFG)
    srv = DisaggServer(CFG, params, QCFG, DisaggConfig(
        fec=FECConfig(enabled=True), hedge=HedgeConfig(enabled=True)))
    _assert_identical(srv, expected)
    assert srv.report()["disagg"]["link"]["failed"] == 0


# ---------------------------------------------------------------------------
# the ladder: FEC heals a single corrupt chunk in band, zero retries
# ---------------------------------------------------------------------------


def test_fec_heals_single_corrupt_chunk_without_retry(params):
    expected = _colocated(params, QCFG)
    srv = DisaggServer(CFG, params, QCFG,
                       DisaggConfig(fec=FECConfig(enabled=True)))
    srv.link.corrupt_chunk_once = 0
    _assert_identical(srv, expected)
    c = srv.link.counters
    assert c["detected"] == 1
    assert c["repaired"] == 1
    assert c["retried"] == 0            # healed in band, no re-send
    assert c["failed"] == 0


def test_corruption_beyond_repair_is_never_adopted(params):
    """A hot link without FEC: every transfer arrives corrupt, the ladder
    exhausts, and the request falls back to a COLOCATED prefill — tokens
    stay identical, the corrupt bytes never reach the decode pool."""
    expected = _colocated(params, QCFG)
    srv = DisaggServer(CFG, params, QCFG, DisaggConfig(
        max_retries=1, degrade_after=2,
        faults=FaultConfig(bitflip_rate=0.5, seed=9)))
    _assert_identical(srv, expected)
    rep = srv.report()["disagg"]
    assert rep["link"]["failed"] >= 1
    assert rep["link"]["detected"] >= 2     # every attempt detected
    assert rep["migrations"] == 0           # nothing corrupt was adopted
    assert rep["colocated_fallbacks"] >= 1
    assert rep["degraded"] and rep["degrade_reason"] == "migration_failures"


def test_link_send_raises_after_exhaustion():
    link = MigrationLink(faults=FaultConfig(bitflip_rate=0.5, seed=3),
                         max_retries=1)
    with pytest.raises(MigrationError, match="never adopted"):
        link.send({"k": np.ones((2, 4, 2), np.float32)}, sid=0, page=0)
    assert link.counters["failed"] == 1
    assert link.counters["transmissions"] == 2
    assert link.counters["pages"] == 0


def test_dead_link_refuses_immediately():
    link = MigrationLink()
    link.fail()
    with pytest.raises(MigrationError, match="link is down"):
        link.send({"k": np.ones((1, 2, 2), np.float32)}, sid=0, page=0)


# ---------------------------------------------------------------------------
# wire-byte contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fec", [None, FECConfig(enabled=True)],
                         ids=["sealed", "fec"])
def test_migration_wire_bytes_match_declared(fec):
    import jax.numpy as jnp
    payload = {"k": np.ones((2, 8, 2, 4), np.float32),
               "v": np.ones((2, 8, 2, 4), np.float32)}
    link = MigrationLink(fec=fec)
    link.send(payload, sid=0, page=0)
    declared = migration_wire_nbytes(tree_nbytes(
        jax.tree_util.tree_map(jnp.asarray, payload)), fec)
    assert link.counters["wire_bytes"] == declared
    sealed = seal_payload(jax.tree_util.tree_map(jnp.asarray, payload))
    assert tree_nbytes(sealed) == tree_nbytes(
        jax.tree_util.tree_map(jnp.asarray, payload)) + 8


def test_disagg_accounts_wire_bytes_per_request(params):
    srv = DisaggServer(CFG, params, BCFG, DisaggConfig())
    sid = srv.submit(_prompt(11, 2), 4, temperature=0.0, rng_seed=0)
    srv.run()
    srv.pop_result(sid)
    rep = srv.report()["disagg"]
    assert rep["wire_bytes"] == rep["link"]["wire_bytes"] > 0
    # 11 rows over page_size=8 -> 2 page transfers
    assert rep["migrated_pages"] == 2 == rep["link"]["pages"]


# ---------------------------------------------------------------------------
# failure matrix: prefill worker dies mid-migration
# ---------------------------------------------------------------------------


def test_prefill_kill_mid_migration_redrives_from_checkpoint(params):
    """The worker dies BETWEEN page transfers; the server-held prefill
    checkpoint re-drives the remaining pages — zero recompute, identical
    tokens."""
    expected = _colocated(params, BCFG)
    srv = DisaggServer(CFG, params, BCFG,
                       DisaggConfig(num_prefill_workers=2))
    armed = {"done": False}

    def hook(wid, sid, page):
        # fire after page 0 of a MULTI-page migration, so the kill lands
        # with the handoff genuinely in flight
        if not armed["done"] and page == 0 and REQS[sid][0].size > 8:
            armed["done"] = True
            srv.kill_prefill_worker(wid)

    srv.page_hook = hook
    _assert_identical(srv, expected)
    rep = srv.report()["disagg"]
    assert armed["done"]
    assert rep["live_prefill_workers"] == 1
    assert rep["redriven_pages"] > 0
    assert rep["recompute_tokens"] == 0     # nothing accepted was lost
    assert not rep["degraded"]


def test_prefill_kill_without_checkpoint_reprefills(params):
    """prefill_checkpoint=False: the dead worker's staged rows are gone, so
    the prompt re-prefills on the surviving worker — counted recompute,
    still identical tokens."""
    expected = _colocated(params, BCFG)
    srv = DisaggServer(CFG, params, BCFG, DisaggConfig(
        num_prefill_workers=2, prefill_checkpoint=False))
    armed = {"done": False}

    def hook(wid, sid, page):
        if not armed["done"] and page == 0 and REQS[sid][0].size > 8:
            armed["done"] = True
            srv.kill_prefill_worker(wid)

    srv.page_hook = hook
    _assert_identical(srv, expected)
    rep = srv.report()["disagg"]
    assert rep["recompute_tokens"] > 0
    assert rep["redriven_pages"] == 0
    assert not rep["degraded"]


def test_all_prefill_workers_dead_degrades_to_colocated(params):
    expected = _colocated(params, BCFG)
    srv = DisaggServer(CFG, params, BCFG,
                       DisaggConfig(num_prefill_workers=2))
    srv.kill_prefill_worker(0)
    srv.kill_prefill_worker(1)
    _assert_identical(srv, expected)
    rep = srv.report()["disagg"]
    assert rep["degraded"]
    assert rep["degrade_reason"] == "prefill_workers_lost"
    assert rep["live_prefill_workers"] == 0


# ---------------------------------------------------------------------------
# failure matrix: decode worker dies
# ---------------------------------------------------------------------------


def test_decode_kill_readmits_via_checkpoint(params, tmp_path):
    expected = _colocated(params, BCFG)
    bcfg = dataclasses.replace(BCFG, checkpoint_dir=str(tmp_path))
    srv = DisaggServer(CFG, params, bcfg, DisaggConfig())
    sids = [srv.submit(p, m, temperature=t, rng_seed=s)
            for p, m, t, s in REQS]
    for _ in range(3):
        srv.step()
    srv.kill_decode_worker()
    res = srv.run()
    for want, s in zip(expected, sids):
        assert np.array_equal(want, res[s])
    assert srv.report()["disagg"]["readmitted"] >= 1


def test_decode_kill_replays_handoff_without_checkpoint_dir(params):
    expected = _colocated(params, BCFG)
    srv = DisaggServer(CFG, params, BCFG, DisaggConfig())
    sids = [srv.submit(p, m, temperature=t, rng_seed=s)
            for p, m, t, s in REQS]
    for _ in range(3):
        srv.step()
    srv.kill_decode_worker()
    res = srv.run()
    for want, s in zip(expected, sids):
        assert np.array_equal(want, res[s])
    rep = srv.report()["disagg"]
    assert rep["readmitted"] >= 1


# ---------------------------------------------------------------------------
# failure matrix: dead link -> typed graceful degrade
# ---------------------------------------------------------------------------


def test_link_death_degrades_with_typed_reason(params):
    expected = _colocated(params, BCFG)
    srv = DisaggServer(CFG, params, BCFG, DisaggConfig())
    srv.fail_link()
    _assert_identical(srv, expected)
    rep = srv.report()["disagg"]
    assert rep["degraded"]
    assert rep["degrade_reason"] == "migration_link_dead"
    assert rep["migrations"] == 0


def test_link_death_mid_workload_loses_nothing(params):
    """The link dies AFTER some requests migrated: completed handoffs still
    adopt and finish; later prompts fall back colocated. Identity holds for
    every request."""
    expected = _colocated(params, BCFG)
    srv = DisaggServer(CFG, params, BCFG, DisaggConfig())
    first = REQS[:2]
    rest = REQS[2:]
    sids = [srv.submit(p, m, temperature=t, rng_seed=s)
            for p, m, t, s in first]
    srv.step()   # migrate the first wave
    srv.fail_link()
    sids += [srv.submit(p, m, temperature=t, rng_seed=s)
             for p, m, t, s in rest]
    res = srv.run()
    for want, s in zip(expected, sids):
        assert np.array_equal(want, res[s])
    assert srv.degraded


# ---------------------------------------------------------------------------
# bounded handoff queue: decode pulls, prefill back-pressures
# ---------------------------------------------------------------------------


def test_handoff_queue_is_bounded(params):
    srv = DisaggServer(CFG, params, BCFG, DisaggConfig(queue_bound=1))
    reqs = [(_prompt(5, i), 3, 0.0, i) for i in range(6)]
    sids = [srv.submit(p, m, temperature=t, rng_seed=s)
            for p, m, t, s in reqs]
    max_depth = 0
    for _ in range(200):
        srv.step()
        max_depth = max(max_depth, len(srv.queue))
        if not srv._unfinished():
            break
    assert max_depth <= 1
    assert all(s in srv.results for s in sids)


# ---------------------------------------------------------------------------
# exactly one flight-recorder dump per migration-fatal failure
# ---------------------------------------------------------------------------


def test_exactly_one_flight_dump_on_migration_fatal(params, tmp_path):
    rec = FlightRecorder(str(tmp_path))
    configure_flight(rec)
    try:
        srv = DisaggServer(CFG, params, QCFG, DisaggConfig(
            max_retries=0, degrade_after=10,
            faults=FaultConfig(bitflip_rate=0.5, seed=5)))
        sid = srv.submit(_prompt(5, 1), 3, temperature=0.0, rng_seed=0)
        srv.run()
        srv.pop_result(sid)
        dumps = rec.dumps()
        assert len(dumps) == 1          # one fatal failure, one post-mortem
        assert os.path.exists(dumps[0])
    finally:
        configure_flight(None)


# ---------------------------------------------------------------------------
# cross-tier adoption refusals: every path, typed
# ---------------------------------------------------------------------------


def _pool(kv_codec):
    return PagedKVCache(CFG, num_pages=9, page_size=4, max_slots=2,
                        pages_per_slot=2, kv_codec=kv_codec)


def test_adopt_packed_refuses_on_fp_pool_typed():
    pool = _pool("fp")
    z = np.zeros((2, 4, 2, 2), np.int8)
    s = np.zeros((2, 4, 2), np.float32)
    with pytest.raises(KVTierMismatchError) as ei:
        pool.adopt_packed(0, z, z, s, s, 4)
    assert ei.value.offered == "quantized"
    assert ei.value.pool == "fp"
    assert ei.value.where == "adopt_packed"


def test_load_state_dict_refuses_cross_tier_typed():
    pool = _pool("int8_per_channel")
    state = pool.state_dict()
    other = _pool("fp")
    with pytest.raises(KVTierMismatchError) as ei:
        other.load_state_dict(state)
    assert ei.value.offered == "int8_per_channel"
    assert ei.value.pool == "fp"
    assert ei.value.where == "load_state_dict"


def test_gather_rows_packed_refuses_on_fp_pool():
    pool = _pool("fp")
    with pytest.raises(ValueError, match="quantized tiers"):
        pool.gather_slot_rows_packed(0, 0, 1)


def test_restore_stream_refuses_cross_tier_typed(params, tmp_path):
    bat = ContinuousBatcher(CFG, params, QCFG)
    sid = bat.submit(_prompt(5, 1), 6, temperature=0.0, rng_seed=0)
    bat.step()
    path = bat.checkpoint_stream(sid, str(tmp_path / "s.ckpt"))
    fbat = ContinuousBatcher(CFG, params, BCFG)
    with pytest.raises(CheckpointTierMismatchError) as ei:
        fbat.restore_stream(path)
    # one typed error serves both audiences
    assert isinstance(ei.value, KVTierMismatchError)
    assert isinstance(ei.value, CheckpointError)
    assert ei.value.offered == "int8_per_channel"
    assert ei.value.pool == "fp"


def test_split_packed_adopt_refusals_are_typed():
    # the tier gate fires before any mesh work, so an uninitialized
    # runtime exercises the refusal without needing >= 2 devices
    from edgellm_tpu.parallel.split import SplitRuntime
    rt = SplitRuntime.__new__(SplitRuntime)
    from edgellm_tpu.models.paged_kv import PagePool
    fake_pool = PagePool(np.zeros((2, 3, 4, 2, 2 * 2), np.float32))
    with pytest.raises(KVTierMismatchError) as ei:
        rt.gather_paged_packed(fake_pool, np.zeros(2, np.int32))
    assert ei.value.where == "gather_paged_packed"
    z = np.zeros((2, 3, 4, 2, 2), np.int8)
    s = np.zeros((2, 3, 4, 2), np.float32)
    with pytest.raises(KVTierMismatchError) as ei2:
        rt.adopt_paged_rows_packed(fake_pool, z, z, s, s,
                                   np.zeros(2, np.int32))
    assert ei2.value.where == "adopt_paged_rows_packed"
    assert ei2.value.pool == "fp"


# ---------------------------------------------------------------------------
# migration holds: a held slot survives frees and defrag
# ---------------------------------------------------------------------------


def test_held_slot_refuses_free_and_defers_defrag():
    pool = _pool("fp")
    slot = pool.alloc_slot()
    pool.ensure(slot, 4)
    pool.hold_slot(slot)
    assert pool.held_slots == [slot]
    with pytest.raises(ValueError, match="held for an in-flight migration"):
        pool.free_slot(slot)
    assert pool.defrag() == 0
    assert pool.deferred_defrags == 1
    pool.release_slot_hold(slot)
    pool.free_slot(slot)            # now fine
    with pytest.raises(ValueError, match="hold"):
        pool.release_slot_hold(slot)
    pool.check_invariants()


def test_release_handoff_frees_staging_state(params):
    bat = ContinuousBatcher(CFG, params, BCFG)
    sid = bat.submit(_prompt(5, 1), 4, temperature=0.0, rng_seed=0)
    st = bat.prefill_hold(sid)
    assert st is not None and st.status == "running"
    assert bat.pool.held_slots == [st.slot]
    bat.release_handoff(sid)
    assert bat.pool.held_slots == []
    assert sid not in bat._streams
    bat.pool.check_invariants()


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [
    {"num_prefill_workers": 0},
    {"prefill_batch": 0},
    {"queue_bound": 0},
    {"max_retries": -1},
    {"degrade_after": 0},
    {"enabled": "yes"},
    {"fec": "on"},
    {"hedge": 2},
    {"faults": {"bitflip_rate": 0.1}},
    {"link_seed": 1.5},
])
def test_disagg_config_validation(kw):
    with pytest.raises(ValueError):
        DisaggConfig(**kw)


def test_disagg_server_validates_submissions(params):
    srv = DisaggServer(CFG, params, BCFG, DisaggConfig())
    with pytest.raises(ValueError, match="empty prompt"):
        srv.submit(np.array([], np.int32), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        srv.submit(_prompt(4), 0)
    with pytest.raises(ValueError, match="temperature"):
        srv.submit(_prompt(4), 4, temperature=-1.0)
    with pytest.raises(ValueError, match="cache positions"):
        srv.submit(_prompt(4), BCFG.span + 1)


def test_disagg_disabled_config_routes_colocated(params):
    expected = _colocated(params, BCFG)
    srv = DisaggServer(CFG, params, BCFG, DisaggConfig(enabled=False))
    _assert_identical(srv, expected)
    assert srv.report()["disagg"]["migrations"] == 0


# ---------------------------------------------------------------------------
# chaos soak: every worker class killed mid-workload, corruption burst,
# zero accepted loss, full identity
# ---------------------------------------------------------------------------


def test_disagg_chaos_soak_all_legs(params):
    from edgellm_tpu.serve.soak import DisaggSoakConfig, run_disagg_soak
    srv = DisaggServer(CFG, params, BCFG, DisaggConfig(
        num_prefill_workers=3, queue_bound=4, degrade_after=50,
        fec=FECConfig(enabled=True)))
    soak = DisaggSoakConfig(
        n_requests=12, seed=7, vocab_size=CFG.vocab_size,
        min_prompt_len=3, max_prompt_len=14, max_new_tokens=5,
        kills=((0.2, "prefill"), (0.8, "decode")),
        burst_start_frac=0.4, burst_end_frac=0.6,
        burst_bitflip_rate=0.01)
    art = run_disagg_soak(
        srv, soak,
        reference_factory=lambda: ContinuousBatcher(CFG, params, BCFG))
    assert art["accepted_lost"] == 0            # nothing accepted was lost
    assert art["completed"] == 12
    assert art["token_identity"]["ok"]
    assert art["token_identity"]["checked"] == 12
    assert any(k["target"].startswith("prefill") and k["mid_migration"]
               for k in art["kills"])
    assert any(k["target"] == "decode" for k in art["kills"])


def test_disagg_soak_link_kill_degrades_cleanly(params):
    from edgellm_tpu.serve.soak import DisaggSoakConfig, run_disagg_soak
    srv = DisaggServer(CFG, params, BCFG, DisaggConfig())
    soak = DisaggSoakConfig(n_requests=8, seed=3,
                            vocab_size=CFG.vocab_size,
                            kills=((0.5, "link"),))
    art = run_disagg_soak(
        srv, soak,
        reference_factory=lambda: ContinuousBatcher(CFG, params, BCFG))
    assert art["accepted_lost"] == 0
    assert art["token_identity"]["ok"]
    assert art["disagg"]["degraded"]
    assert art["disagg"]["degrade_reason"] == "migration_link_dead"


def test_disagg_soak_config_validation():
    from edgellm_tpu.serve.soak import DisaggSoakConfig
    with pytest.raises(ValueError, match="kill target"):
        DisaggSoakConfig(kills=((0.5, "gpu"),))
    with pytest.raises(ValueError, match="burst_end_frac"):
        DisaggSoakConfig(burst_start_frac=0.8, burst_end_frac=0.2)
    with pytest.raises(ValueError, match="prompt_len"):
        DisaggSoakConfig(min_prompt_len=9, max_prompt_len=3)


# ---------------------------------------------------------------------------
# run.py params validation: the shipped config and the refusals
# ---------------------------------------------------------------------------


def _disagg_params():
    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "configs", "split16_qwen_disagg.json")) as f:
        import json

        return json.load(f)


def test_params_validation_accepts_disagg_config():
    from edgellm_tpu.run import _validate_params_json

    _validate_params_json(_disagg_params())  # must not raise


def test_params_validation_disagg_is_serve_only():
    from edgellm_tpu.run import _validate_params_json

    p = {"experiment": "split", "max_length": 512, "stride": 32,
         "cuts": [1], "hop_codecs": ["int8_per_token"],
         "disagg": {"num_prefill_workers": 2}}
    with pytest.raises(SystemExit, match="only applies to experiment "
                                         "'serve'"):
        _validate_params_json(p)


def test_params_validation_disagg_requires_batching():
    from edgellm_tpu.run import _validate_params_json

    p = _disagg_params()
    del p["batching"]
    with pytest.raises(SystemExit, match="add a 'batching' block"):
        _validate_params_json(p)


@pytest.mark.parametrize("patch, msg", [
    ({"speculative": {"k": 4}}, "speculative"),
    ({"disagg": [2]}, "object of DisaggConfig fields"),
    ({"disagg": {"num_prefill_workerz": 2}}, "disagg: unknown field"),
    ({"disagg": {"fec": {"chunkz": 4}}}, "disagg.fec: unknown field"),
    ({"disagg": {"hedge": 3}}, "disagg.hedge must be an object"),
    ({"disagg": {"num_prefill_workers": 0}}, "num_prefill_workers"),
    ({"disagg": {"queue_bound": 0}}, "queue_bound"),
    ({"disagg": {"max_retries": -1}}, "max_retries"),
])
def test_params_validation_rejects_disagg_footguns(patch, msg):
    from edgellm_tpu.run import _validate_params_json

    p = _disagg_params()
    p.update(patch)
    with pytest.raises(SystemExit, match=msg):
        _validate_params_json(p)


def test_disagg_config_builder_nests_the_ladder_configs():
    from edgellm_tpu.run import _disagg_config

    dcfg = _disagg_config({"num_prefill_workers": 3,
                           "fec": {"enabled": True},
                           "hedge": {"enabled": True, "routes": 2},
                           "faults": {"bitflip_rate": 0.01}})
    assert dcfg.num_prefill_workers == 3
    assert isinstance(dcfg.fec, FECConfig) and dcfg.fec.enabled
    assert isinstance(dcfg.hedge, HedgeConfig) and dcfg.hedge.routes == 2
    assert isinstance(dcfg.faults, FaultConfig)


# ---------------------------------------------------------------------------
# front + router surfacing: disagg state rides the serve report and demotes
# degraded replicas in placement
# ---------------------------------------------------------------------------


def test_serve_front_drains_a_disagg_batcher(params):
    from edgellm_tpu.serve import Request, ServeFront
    from edgellm_tpu.utils.clock import FakeClock

    srv = DisaggServer(CFG, params, BCFG, DisaggConfig())
    front = ServeFront(CFG, params, batcher=srv, clock=FakeClock())
    for i, (prompt, mnt, temp, seed) in enumerate(REQS[:2]):
        front.submit(Request(prompt_ids=prompt, max_new_tokens=mnt,
                             temperature=temp, rng_seed=seed))
    recs = front.drain_batched()
    assert len(recs) == 2
    assert all(r.outcome == "completed" for r in recs)
    assert recs[0].plan["mode"] == "disagg"
    assert recs[0].plan["disagg"]["degraded"] is False
    rep = front.report()
    assert rep["disagg"] == {"degraded": False, "degrade_reason": None}
    assert front.disagg_state() == {"degraded": False,
                                    "degrade_reason": None}
    # degrade surfaces through the same probe (what the router reads)
    srv.fail_link()
    assert front.disagg_state() == {
        "degraded": True, "degrade_reason": "migration_link_dead"}


def test_serve_front_disagg_state_is_none_for_colocated(params):
    from edgellm_tpu.serve import ServeFront
    from edgellm_tpu.utils.clock import FakeClock

    front = ServeFront(CFG, params, batcher=ContinuousBatcher(
        CFG, params, BCFG), clock=FakeClock())
    assert front.disagg_state() is None
    assert "disagg" not in front.report()


def test_cluster_demotes_degraded_disagg_replicas():
    from edgellm_tpu.serve import Request
    from edgellm_tpu.serve.cluster import (ClusterConfig, ClusterFront,
                                           SimReplicaConfig, SimReplicaFront)
    from edgellm_tpu.utils.clock import FakeClock

    class DisaggSimFront(SimReplicaFront):
        degraded = False

        def disagg_state(self):
            return {"degraded": self.degraded,
                    "degrade_reason": ("migration_link_dead"
                                       if self.degraded else None)}

    clock = FakeClock()
    fronts = {}

    def factory(rid, gen):
        f = DisaggSimFront(SimReplicaConfig(), clock=clock, replica_id=rid)
        fronts[rid] = f
        return f

    cluster = ClusterFront(factory, ClusterConfig(num_replicas=2),
                           clock=clock)
    # equal load: the (disagg_penalty, queue_depth, id) key demotes the
    # degraded replica 0 even though the plain tiebreak would pick it
    fronts[0].degraded = True
    prompt = np.random.default_rng(5).integers(
        1, 50_000, size=16).astype(np.int32)
    crid = cluster.submit(Request(prompt_ids=prompt, max_new_tokens=4))
    assert cluster._placements[crid].replica_id == 1
    # the replica summary carries the typed reason for the fleet report
    summaries = {r.id: r.summary() for r in cluster.replicas.values()}
    assert summaries[0]["disagg"]["degrade_reason"] == "migration_link_dead"
    assert summaries[1]["disagg"]["degraded"] is False
    # healthy again: the deterministic tiebreak returns to lowest id
    fronts[0].degraded = False
    crid2 = cluster.submit(Request(prompt_ids=prompt[::-1].copy(),
                                   max_new_tokens=4))
    assert cluster._placements[crid2].replica_id == 0
