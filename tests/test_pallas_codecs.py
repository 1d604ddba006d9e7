"""Pallas codec kernels (interpret mode on CPU): must be bit-identical to the
jnp wire codecs — same packed bytes, same reconstruction."""
import numpy as np
import pytest

import jax.numpy as jnp

from edgellm_tpu.codecs import pallas_kernels
from edgellm_tpu.codecs.packing import (WIRE_CODECS, get_wire_codec,
                                        selective_int4)
from edgellm_tpu.codecs.pallas_kernels import (
    SELECTIVE_EXCLUSION, int4_encode_pallas, int4_decode_pallas,
    pallas_wire_codec, pallas_variant,
)


@pytest.fixture
def hidden(rng):
    return jnp.asarray(rng.normal(size=(2, 16, 64)).astype(np.float32))


def test_encode_matches_jnp_codec_bitwise(hidden):
    jnp_codec = get_wire_codec("int4_per_token")
    want = jnp_codec.encode(hidden)
    b, s, d = hidden.shape
    packed, scale = int4_encode_pallas(hidden.reshape(b * s, d))
    np.testing.assert_array_equal(np.asarray(packed).reshape(b, s, -1),
                                  np.asarray(want["packed"]))
    np.testing.assert_allclose(np.asarray(scale).reshape(b, s, 1),
                               np.asarray(want["scale"]), rtol=1e-7)


def test_roundtrip_matches_jnp_roundtrip(hidden):
    jnp_codec = get_wire_codec("int4_per_token")
    want = jnp_codec.decode(jnp_codec.encode(hidden))
    codec = pallas_wire_codec()
    got = codec.decode(codec.encode(hidden))
    # payload bytes are bit-identical (previous test); reconstruction may differ
    # by 1 ulp from XLA fusing (c/7)*s differently
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_ragged_token_counts(rng):
    """Token counts that don't hit the preferred tile sizes still work."""
    for n in (8, 24, 40, 72):
        x = jnp.asarray(rng.normal(size=(n, 32)).astype(np.float32))
        packed, scale = int4_encode_pallas(x)
        out = int4_decode_pallas(packed, scale)
        err = np.abs(np.asarray(out) - np.asarray(x)).max()
        assert err <= np.abs(np.asarray(x)).max() / 7.0 + 1e-6


def _assert_payload_equal(got: dict, want: dict):
    assert set(got) == set(want)
    for key in want:
        g, w = np.asarray(got[key]), np.asarray(want[key])
        assert g.dtype == w.dtype and g.shape == w.shape, key
        if np.issubdtype(w.dtype, np.integer) or w.dtype == np.uint8:
            np.testing.assert_array_equal(g, w, err_msg=key)
        else:
            np.testing.assert_allclose(g, w, rtol=1e-7, err_msg=key)


@pytest.mark.parametrize("name", ["int4_per_channel", "ternary_mean",
                                  "ternary_max"])
def test_pallas_twins_bit_identical(hidden, name):
    jnp_codec = get_wire_codec(name)
    pallas_codec = get_wire_codec(name + "_pallas")
    assert pallas_codec.name == name + "_pallas"
    want = jnp_codec.encode(hidden)
    got = pallas_codec.encode(hidden)
    _assert_payload_equal(got, want)
    np.testing.assert_allclose(np.asarray(pallas_codec.decode(got)),
                               np.asarray(jnp_codec.decode(want)), atol=1e-6)


def test_selective_has_no_kernel_twin_by_measurement(monkeypatch):
    """The selective codec's Pallas twin was DELETED in round 5 on silicon
    measurement (gather-bound; the kernel boundary broke XLA's gather->quant
    fusion, 0.96-0.97x across rounds). The exclusion is a recorded decision:
    pallas_variant returns None on a TPU too and the runtimes fall back to
    the jnp codec, which IS the TPU-native implementation."""
    jnp_codec = selective_int4(0.5, "bf16")
    assert pallas_variant(jnp_codec) is None
    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)
    assert pallas_variant(jnp_codec) is None
    assert not hasattr(pallas_kernels, "pallas_selective_int4")
    assert "gather-bound" in SELECTIVE_EXCLUSION


def test_registry_exposes_pallas_names():
    """An explicit ``*_pallas`` name is a codec on any backend; the int8
    twins, which no default ever picked, are no codec any more."""
    for name in WIRE_CODECS:
        assert get_wire_codec(name).name == name
    assert sorted(n for n in WIRE_CODECS if n.endswith("_pallas")) == [
        "int4_per_channel_pallas", "int4_per_token_pallas",
        "ternary_max_pallas", "ternary_mean_pallas"]
    for gone in ("int8_per_token_pallas", "int8_per_channel_pallas"):
        with pytest.raises(ValueError, match="unknown wire codec"):
            get_wire_codec(gone)


#: every codec a hop can ask for by name, and what the hop runs on a TPU
_TWINNED = ("int4_per_token", "int4_per_channel", "ternary_mean",
            "ternary_max")
_ASKED = [n for n in WIRE_CODECS if not n.endswith("_pallas")] + [
    "selective_int4:0.5:bf16", "int4_per_token_pallas"]


@pytest.mark.parametrize("on_tpu", [False, True], ids=["cpu", "tpu"])
@pytest.mark.parametrize("asked", _ASKED)
def test_hop_codec_is_chosen_from_the_table_and_the_backend(monkeypatch,
                                                            asked, on_tpu):
    """The one place a hop's codec is chosen: the twin's name where the table
    holds the base codec AND the chooser sees a TPU, the codec asked for
    everywhere else; an explicit ``*_pallas`` name is honoured on both."""
    from edgellm_tpu.eval.split_eval import parse_hop_codec
    from edgellm_tpu.parallel.split import apply_default_codec_backend

    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: on_tpu)
    spec = parse_hop_codec(asked)
    own = spec if isinstance(spec, str) else spec.name
    (got,) = apply_default_codec_backend([spec])
    want = own + "_pallas" if on_tpu and own in _TWINNED else own
    assert got.name == want
    if got.name != own:
        assert got.batch_invariant == get_wire_codec(own).batch_invariant


def test_split_cell_dispatch_at_a_toy_size(rng, monkeypatch):
    """The split cell's three hops (int8 / int4 / int8 per token over four
    stages) on a TPU: cuts 0 and 2 cross as the jnp codec, cut 1 as the int4
    twin, and the forward is the all-jnp runtime's."""
    import jax
    from edgellm_tpu.models import tiny_config, init_params
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, make_stage_mesh

    cfg = tiny_config("qwen2", num_layers=8, hidden_size=32, num_heads=4,
                      vocab_size=128)
    split = SplitConfig(cuts=(1, 3, 5), hop_codecs=(
        "int8_per_token", "int4_per_token", "int8_per_token"))
    params = init_params(cfg, jax.random.key(1))
    ids = jnp.asarray(rng.integers(0, 128, (1, 16)))
    rt_j = SplitRuntime(cfg, split, make_stage_mesh(4))
    assert [c.name for c in rt_j.codecs] == list(split.hop_codecs)
    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)
    rt = SplitRuntime(cfg, split, make_stage_mesh(4))
    assert [c.name for c in rt.codecs] == [
        "int8_per_token", "int4_per_token_pallas", "int8_per_token"]
    out_p = rt.forward(rt.place_params(params), ids)
    out_j = rt_j.forward(rt_j.place_params(params), ids)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_j),
                               atol=1e-6, rtol=1e-6)


#: the five variables and the file that once steered a dispatch, with every
#: value they took
_OLD_SWITCHES = [
    ("EDGELLM_ATTN", "pallas"), ("EDGELLM_ATTN", "xla"),
    ("EDGELLM_PALLAS", "0"), ("EDGELLM_PALLAS", "1"),
    ("EDGELLM_FUSED_HOP", "0"), ("EDGELLM_FUSED_HOP", "1"),
    ("EDGELLM_FUSED_HOP", "wire"), ("EDGELLM_FUSED_HOP", "remote"),
    ("EDGELLM_PROBE_ALL", "1"), ("EDGELLM_PROBE_CACHE", "<file>"),
]


@pytest.mark.parametrize("var,value", _OLD_SWITCHES)
def test_old_dispatch_switches_are_inert(monkeypatch, tmp_path, var, value):
    """With an old variable set, and a probe cache under ``$HOME`` that calls
    int8_per_token a win and int4_per_token a loss, the prefill's kernel, a
    hop's codec and the graph of a cut are what they are without them, on a
    TPU and off it."""
    import json

    import jax
    from edgellm_tpu.lint.contracts import graph_fingerprint
    from edgellm_tpu.models import flash_attention, init_params, tiny_config
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, make_stage_mesh
    from edgellm_tpu.parallel.split import apply_default_codec_backend

    cfg = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4,
                      vocab_size=128)
    split = SplitConfig(cuts=(1,), hop_codecs=("int8_per_token",))
    asked = ["int8_per_token", "int4_per_token", "int8_per_channel"]
    params = init_params(cfg, jax.random.key(0))
    ids, imps = jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8), jnp.float32)

    def answers():
        rt = SplitRuntime(cfg, split, make_stage_mesh(2))
        return (flash_attention.kernel_plan(512, 14, 2, 64),
                [c.name for c in apply_default_codec_backend(asked)],
                graph_fingerprint(rt._forward, rt.place_params(params), ids,
                                  imps))

    def both_backends():
        out = []
        for on_tpu in (False, True):
            monkeypatch.setattr(flash_attention, "_on_tpu", lambda: on_tpu)
            monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: on_tpu)
            out.append(answers())
        return out

    for name, _ in _OLD_SWITCHES:
        monkeypatch.delenv(name, raising=False)
    want = both_backends()
    assert want[0][:2] == (None, asked)
    assert want[1][:2] == (("whole", None), [
        "int8_per_token", "int4_per_token_pallas", "int8_per_channel"])

    cache = tmp_path / ".cache" / "edgellm_tpu" / "pallas_wins.json"
    cache.parent.mkdir(parents=True)
    wins = {"speedups": {"int8_per_token": 2.0, "int4_per_token": 0.5,
                         "fused_hop:int8_per_token": 2.0}}
    cache.write_text(json.dumps({f"{jax.default_backend()}:"
                                 f"{jax.devices()[0].device_kind}": wins,
                                 "tpu:TPU v5 lite": wins}))
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setenv(var, str(cache) if value == "<file>" else value)
    assert both_backends() == want


def test_pallas_codec_in_split_runtime(rng):
    """Pallas hop codec through ppermute == jnp hop codec, end to end."""
    import jax
    from edgellm_tpu.models import tiny_config, init_params
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, make_stage_mesh

    cfg = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4, vocab_size=128)
    params = init_params(cfg, jax.random.key(1))
    ids = jnp.asarray(rng.integers(0, 128, (1, 16)))
    rt_p = SplitRuntime(cfg, SplitConfig(cuts=(1,), hop_codecs=(pallas_wire_codec(),)),
                        make_stage_mesh(2))
    rt_j = SplitRuntime(cfg, SplitConfig(cuts=(1,), hop_codecs=("int4_per_token",)),
                        make_stage_mesh(2))
    out_p = rt_p.forward(rt_p.place_params(params), ids)
    out_j = rt_j.forward(rt_j.place_params(params), ids)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_j),
                               atol=1e-6, rtol=1e-6)
