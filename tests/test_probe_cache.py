"""The probe-derived substitution policy (codecs/probe_cache.py): cache
hit / miss / fallback, and how pallas_variant's measured_wins_only gate
consumes it. The policy must come from measurement on
THIS chip, with the frozen constant only as the no-data fallback."""
import json

import pytest

from edgellm_tpu.codecs import probe_cache
from edgellm_tpu.codecs.pallas_kernels import (PALLAS_DEFAULT_WINS,
                                               default_substituted,
                                               pallas_variant)
from edgellm_tpu.codecs.packing import get_wire_codec, selective_int4


@pytest.fixture
def cache(tmp_path, monkeypatch):
    path = tmp_path / "wins.json"
    monkeypatch.setenv("EDGELLM_PROBE_CACHE", str(path))
    return path


def _probe_rows(**speedups):
    return [{"codec": k, "roundtrip_speedup_vs_jnp": v}
            for k, v in speedups.items()]


def test_record_and_load_roundtrip(cache):
    assert probe_cache.load_speedups() is None  # miss: no file yet
    wrote = probe_cache.record(_probe_rows(int4_per_token=1.33,
                                           int8_per_token=0.79))
    assert wrote == str(cache)
    got = probe_cache.load_speedups()
    assert got == {"int4_per_token": 1.33, "int8_per_token": 0.79}
    # merge, not replace: a later run updates one codec, keeps the rest
    probe_cache.record(_probe_rows(int8_per_token=1.1))
    got = probe_cache.load_speedups()
    assert got == {"int4_per_token": 1.33, "int8_per_token": 1.1}


def test_fingerprint_keys_are_isolated(cache):
    probe_cache.record(_probe_rows(int4_per_token=1.5), fp="tpu:TPU v99")
    # current (cpu) fingerprint has no data -> miss
    assert probe_cache.load_speedups() is None
    assert probe_cache.load_speedups("tpu:TPU v99") == {"int4_per_token": 1.5}


def test_measured_win_hit_miss(cache):
    assert probe_cache.measured_win("int4_per_token") is None  # no data
    probe_cache.record(_probe_rows(int4_per_token=1.33, int8_per_token=0.79))
    assert probe_cache.measured_win("int4_per_token") is True
    assert probe_cache.measured_win("int8_per_token") is False
    assert probe_cache.measured_win("ternary_mean") is None  # unprobed codec
    # the selective family maps onto one policy key
    probe_cache.record(_probe_rows(**{"selective_int4_r0.5_bf16": 1.2}))
    assert probe_cache.measured_win("selective_int4_r0.25_bf16") is True
    # break-even readings do NOT flap a codec into the default path: the win
    # must clear WIN_MARGIN, not 1.0
    probe_cache.record(_probe_rows(int8_per_channel=1.02))
    assert probe_cache.measured_win("int8_per_channel") is False


def test_record_prefers_unrounded_ratio(cache):
    """ADVICE r5 #3: a 1.046x reading display-rounds to 1.05 — WIN_MARGIN
    must see the raw ratio, or the rounding manufactures a win."""
    probe_cache.record([{"codec": "int8_per_token",
                         "roundtrip_speedup_vs_jnp": 1.05,
                         "roundtrip_speedup_vs_jnp_raw": 1.046}])
    assert probe_cache.load_speedups() == {"int8_per_token": 1.046}
    assert probe_cache.measured_win("int8_per_token") is False
    # rows without the raw field (older probe output) still load
    probe_cache.record(_probe_rows(int4_per_token=1.33))
    assert probe_cache.measured_win("int4_per_token") is True


def test_no_data_falls_back_to_frozen_set(cache):
    for base in ("int4_per_token", "int8_per_token", "selective_int4"):
        assert default_substituted(base) == (base in PALLAS_DEFAULT_WINS)


def test_corrupt_cache_degrades_to_fallback(cache):
    cache.write_text("{not json")
    assert probe_cache.load_speedups() is None
    assert default_substituted("int4_per_token")  # fallback set decides
    # and record() recovers the file
    probe_cache.record(_probe_rows(int4_per_token=1.2))
    assert probe_cache.load_speedups() == {"int4_per_token": 1.2}
    json.loads(cache.read_text())  # valid JSON again


def test_pallas_variant_consults_cache_over_constant(cache):
    int4 = get_wire_codec("int4_per_token")
    # no data: the frozen fallback substitutes int4_per_token
    assert pallas_variant(int4, measured_wins_only=True) is not None
    # a measured LOSS on this chip overrides the constant (the r03->r04
    # int8_per_token 2.12x -> 0.79x flip can never silently ship again)
    probe_cache.record(_probe_rows(int4_per_token=0.8))
    assert pallas_variant(int4, measured_wins_only=True) is None
    # a measured WIN enables a codec the constant excludes
    probe_cache.record(_probe_rows(int8_per_token=1.2))
    got = pallas_variant(get_wire_codec("int8_per_token"),
                         measured_wins_only=True)
    assert got is not None and got.name.endswith("_pallas")
    # explicit *_pallas pins are honored regardless of the cache
    pinned = pallas_variant(got, measured_wins_only=True)
    assert pinned is got
    # the selective codec can never be substituted — its twin was DELETED on
    # measurement, and even a (stale) cache win cannot resurrect it
    sel = selective_int4(0.25, "bf16")
    probe_cache.record(_probe_rows(**{"selective_int4_r0.5_bf16": 1.15}))
    assert pallas_variant(sel, measured_wins_only=True) is None
    assert pallas_variant(sel) is None
