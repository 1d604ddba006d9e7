"""The on-silicon Pallas probe's parity logic, exercised on CPU (interpret
mode). On the real chip ``chip_smoke.py`` runs the same probe — this pins the
comparison machinery (ulp math, leaf checks, codec pairing) without a TPU."""
import numpy as np

import pytest

from edgellm_tpu.tools.pallas_probe import (PROBE_CODECS, _float_leaf_ok,
                                            _ulp_diff, probe_all)


def test_ulp_diff():
    a = np.float32(1.0)
    assert _ulp_diff(np.asarray([a]), np.asarray([np.nextafter(a, 2.0)])) == 1
    assert _ulp_diff(np.asarray([a]), np.asarray([a])) == 0
    # sign crossing: -eps to +eps is two representable steps apart at most
    tiny = np.float32(1e-45)
    assert _ulp_diff(np.asarray([-tiny]), np.asarray([tiny])) == 2
    assert _ulp_diff(np.zeros((0,), np.float32), np.zeros((0,), np.float32)) == 0


def test_float_leaf_criterion():
    """<= max_ulp elementwise, or — for a near-zero value, where ulp says
    nothing — an absolute bound; past both it raises naming the leaf."""
    want = np.asarray([2.0e-5, 0.05], np.float32)
    got = want + np.float32(1.3e-8)  # ~7000 ulp of 2e-5, nothing next to 5.0
    assert _ulp_diff(got, want) > 2
    assert _float_leaf_ok(got, want, 2, 2 * float(np.spacing(np.float32(5))),
                          "ternary_mean.scale") > 2
    with pytest.raises(AssertionError, match="ternary_mean.scale"):
        _float_leaf_ok(got, want, 2, 0.0, "ternary_mean.scale")
    assert _float_leaf_ok(want, want, 2, 0.0, "x") == 0


def test_probe_all_parity_small():
    out = probe_all(batch=2, seq=32, dim=64)
    assert out["interpret"] is True
    # every kernel-twinned codec, plus the recorded selective exclusion (the
    # measured round-5 deletion travels in every probe artifact)
    assert [c["codec"] for c in out["codecs"]] == \
        list(PROBE_CODECS) + ["selective_int4"]
    assert "gather-bound" in out["codecs"][-1]["excluded"]
    for c in out["codecs"][:-1]:
        assert c["encode_max_ulp"] <= 2 and c["decode_max_ulp"] <= 2
        assert c["int_leaves_bit_identical"] >= 1


def test_attention_parity_probe_checks_the_kernel(monkeypatch):
    """attn_probe.parity_shape — the correctness half chip_smoke.py runs on
    silicon — passes on the interpreted kernel and raises past its bound."""
    from edgellm_tpu.tools import attn_probe

    res = attn_probe.parity_shape(2, 4, 2, 24, 64, stats=True)
    assert res["plan"][0] in ("whole", "blocked") and res["interpret"]
    assert res["out_max_abs_err"] <= res["atol"]
    assert res["col_max_abs_err"] <= res["stats_atol"]
    monkeypatch.setitem(attn_probe.PARITY_REL, "float32", 0.0)
    with pytest.raises(AssertionError, match="parity failed"):
        attn_probe.parity_shape(1, 4, 2, 20, 64)
