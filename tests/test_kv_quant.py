"""KV-at-rest compression: quantized paged pools, packed round-trips.

The load-bearing claims: (1) the ``fp`` tier IS the pre-quantization data
path — same pool type, same compiled step, token-identical output; (2) on
quantized tiers every page movement (COW fork, defrag, eviction, adopt,
checkpoint) is a BYTE move of packed codes + scales, never a requantize,
so gather -> adopt round-trips are bit-exact across any pool geometry and
a stream's tokens survive eviction/restore unchanged; (3) cross-tier
restore is REFUSED, not transcoded. Capacity math: the same byte budget
buys proportionally more pages at a packed tier, which is the whole point.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edgellm_tpu.models import init_params, tiny_config
from edgellm_tpu.models.flash_attention import (dequantize_kv_rows,
                                                quantize_kv_rows)
from edgellm_tpu.models.paged_kv import (KV_PAGE_CODECS, OutOfPages,
                                         PagedKVCache, PagePool,
                                         PrefixCacheConfig, QuantPagePool,
                                         join_kv, kv_page_bytes,
                                         num_pages_for_bytes,
                                         paged_decode_attention,
                                         resolve_kv_codec)
from edgellm_tpu.serve.batching import BatchingConfig, ContinuousBatcher
from edgellm_tpu.serve.decode import generate
from edgellm_tpu.serve.recovery import CheckpointError

CFG = tiny_config("qwen2", num_layers=4, hidden_size=32, num_heads=4,
                  vocab_size=128)
# fp geometry shared with tests/test_batching.py; quantized twins differ
# ONLY in the kv_codec field, so admission/span math is identical
BCFG = BatchingConfig(page_size=8, num_pages=17, max_slots=4,
                      pages_per_slot=4)
BCFG8 = dataclasses.replace(BCFG, kv_codec="int8_per_channel")
BCFG4 = dataclasses.replace(BCFG, kv_codec="int4_per_channel")

# pool-level tests use a 2-layer model: tier bookkeeping is layer-count
# independent and the materialized pages stay tiny
CFG2 = tiny_config("qwen2", num_layers=2, hidden_size=32, num_heads=4,
                   vocab_size=128)
PROMPT = list(range(100, 110))
TIERS = ("int8_per_channel", "int4_per_channel")


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(1))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, size=n).astype(np.int32)


def _solo(params, prompt, max_new, temp=0.0, seed=0):
    out = generate(CFG, params, jnp.asarray(prompt)[None], max_new,
                   capacity=BCFG.span, temperature=temp,
                   rng_key=jax.random.key(seed))
    return np.asarray(out)[0]


def _seq(n, seed):
    r = np.random.default_rng(seed)
    shape = (CFG2.num_layers, n, CFG2.num_kv_heads, CFG2.head_dim)
    return (jnp.asarray(r.standard_normal(shape), jnp.float32),
            jnp.asarray(r.standard_normal(shape), jnp.float32))


def _qcache(kv_codec, **kw):
    kw.setdefault("num_pages", 13)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_slots", 3)
    kw.setdefault("pages_per_slot", 4)
    return PagedKVCache(CFG2, kv_codec=kv_codec, **kw)


def _packed_equal(a, b, rows=None):
    for key in ("k_codes", "v_codes", "k_scale", "v_scale"):
        x, y = np.asarray(a[key]), np.asarray(b[key])
        if rows is not None:
            x, y = x[:, :rows], y[:, :rows]
        np.testing.assert_array_equal(x, y, err_msg=key)


# ---------------------------------------------------------------------------
# codec registry + capacity math
# ---------------------------------------------------------------------------


def test_codec_registry_refuses_unknown_tiers():
    with pytest.raises(ValueError, match="unknown kv_codec"):
        resolve_kv_codec("int2_per_galaxy")
    assert resolve_kv_codec("fp").quantized is False
    for t in TIERS:
        assert resolve_kv_codec(t).quantized
    with pytest.raises(ValueError, match="even head_dim"):
        KV_PAGE_CODECS["int4_per_channel"].code_lanes(7)


def test_page_bytes_and_budget_capacity_ratio():
    hd = CFG2.head_dim
    fp_row = hd * 4
    assert KV_PAGE_CODECS["fp"].row_bytes(hd) == fp_row
    assert KV_PAGE_CODECS["int8_per_channel"].row_bytes(hd) == hd + 4
    assert KV_PAGE_CODECS["int4_per_channel"].row_bytes(hd) == hd // 2 + 4
    fp_page = kv_page_bytes(CFG2, 4, "fp")
    assert fp_page == 2 * CFG2.num_layers * 4 * CFG2.num_kv_heads * fp_row
    # a fixed byte budget must buy >= 2x the pages at the packed tiers —
    # the acceptance-gate concurrency multiplier comes straight from here
    budget = 8 * fp_page
    n_fp = num_pages_for_bytes(CFG2, budget, 4, "fp")
    assert n_fp == 8
    for t in TIERS:
        assert num_pages_for_bytes(CFG2, budget, 4, t) >= 2 * n_fp
    with pytest.raises(ValueError, match="page 0 is reserved"):
        num_pages_for_bytes(CFG2, kv_page_bytes(CFG2, 4, "int4_per_channel"),
                            4, "int4_per_channel")


def test_out_of_pages_math_with_shrunken_pages():
    # same budget, same request: the fp pool refuses what int4 admits
    budget = 5 * kv_page_bytes(CFG2, 4, "fp")
    geo = dict(page_size=4, max_slots=2, pages_per_slot=8,
               materialize=False)
    fp = PagedKVCache(CFG2, num_pages=num_pages_for_bytes(
        CFG2, budget, 4, "fp"), kv_codec="fp", **geo)
    q4 = PagedKVCache(CFG2, num_pages=num_pages_for_bytes(
        CFG2, budget, 4, "int4_per_channel"), kv_codec="int4_per_channel",
        **geo)
    s = fp.alloc_slot()
    with pytest.raises(OutOfPages):
        fp.ensure(s, 20)          # 5 pages > the 4 the budget buys
    fp.check_invariants()
    for _ in range(2):            # int4: BOTH slots fit at the same bytes
        q4.ensure(q4.alloc_slot(), 20)
    q4.check_invariants()


# ---------------------------------------------------------------------------
# quantize / dequantize rows
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tier", TIERS)
def test_quantize_roundtrip_error_bound_and_idempotence(tier):
    qmax = {"int8_per_channel": 127, "int4_per_channel": 7}[tier]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 9, 3, CFG2.head_dim)) * 3.0,
                    jnp.float32)
    x = x.at[0, 4].set(0.0)       # an all-zero row must survive exactly
    codes, scales = quantize_kv_rows(x, tier)
    y = dequantize_kv_rows(codes, scales, tier)
    assert y.shape == x.shape and y.dtype == jnp.float32
    # per-row absmax scaling: error <= half a quantization step, per row
    step = np.asarray(scales)[..., None] / qmax
    assert (np.abs(np.asarray(x - y)) <= 0.5 * step + 1e-6).all()
    np.testing.assert_array_equal(np.asarray(y[0, 4]), 0.0)
    assert float(scales[0, 4].max()) == 0.0
    # requantizing the dequantized rows reproduces the SAME bytes — the
    # property every byte-move path (COW, defrag, checkpoint) leans on
    codes2, scales2 = quantize_kv_rows(y, tier)
    np.testing.assert_array_equal(np.asarray(codes2), np.asarray(codes))
    np.testing.assert_allclose(np.asarray(scales2), np.asarray(scales),
                               rtol=1e-6)


@pytest.mark.parametrize("tier", TIERS)
def test_paged_quant_fallback_matches_dequantized_pool(tier):
    # the attend over a quantized pool == dequantize the WHOLE pool then
    # the attend over an fp pool, exactly (same contract graphlint executes)
    npg, pgs, ms, pps = 5, 8, 2, 2
    rng = np.random.default_rng(3)
    kv = (npg * pgs, CFG2.num_kv_heads, CFG2.head_dim)
    kq, ks = quantize_kv_rows(
        jnp.asarray(rng.standard_normal(kv), jnp.float32), tier)
    vq, vs = quantize_kv_rows(
        jnp.asarray(rng.standard_normal(kv), jnp.float32), tier)
    hdc = kq.shape[-1]
    q = jnp.asarray(rng.standard_normal(
        (ms, 1, CFG2.num_heads, CFG2.head_dim)), jnp.float32)
    tab = jnp.asarray(rng.permutation(np.arange(1, npg))[:ms * pps]
                      .reshape(ms, pps).astype(np.int32))
    lens = jnp.asarray([pgs + 3, pgs - 2], jnp.int32)
    nkv = CFG2.num_kv_heads
    # the stored form: one layer of (P, ps, KV*lanes) codes, (P, ps, KV)
    # scales
    got = paged_decode_attention(
        q, QuantPagePool(kq.reshape(1, npg, pgs, nkv * hdc),
                         vq.reshape(1, npg, pgs, nkv * hdc),
                         ks.reshape(1, npg, pgs, nkv),
                         vs.reshape(1, npg, pgs, nkv)),
        0, tab, lens)
    kf = dequantize_kv_rows(kq, ks, tier)
    vf = dequantize_kv_rows(vq, vs, tier)
    ref = paged_decode_attention(
        q, PagePool(join_kv(kf.reshape(1, npg, pgs, nkv * CFG2.head_dim),
                            vf.reshape(1, npg, pgs, nkv * CFG2.head_dim))),
        0, tab, lens)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("kv,hd,ps", [(2, 64, 16), (2, 128, 16),
                                      (8, 128, 16), (2, 64, 8)])
@pytest.mark.parametrize("tier", TIERS)
def test_quant_page_gather_equals_flat_row_gather_bitwise(tier, kv, hd, ps):
    # the quantized twin of test_batching's page-gather case: codes and both
    # scale pools fetched a page at a time equal the flat-row fetch (the
    # three old lines, kept below as the oracle) to the bit — ragged
    # lengths, an all-trash slot, a shared page named twice
    from edgellm_tpu.models.flash_attention import decode_attention

    rng = np.random.default_rng(kv + hd + ps)
    pn, pps, h = 11, 4, 2 * kv
    pt = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 0, 0, 0],
                      [0, 0, 0, 0], [1, 7, 1, 8]], jnp.int32)
    lens = jnp.asarray([2 * ps + 3, 2 * ps, 1, 1, pps * ps], jnp.int32)
    b, span = pt.shape[0], pps * ps
    rows = (pn * ps, kv, hd)
    kq, ks = quantize_kv_rows(
        jnp.asarray(rng.standard_normal(rows), jnp.float32), tier)
    vq, vs = quantize_kv_rows(
        jnp.asarray(rng.standard_normal(rows), jnp.float32), tier)
    hdc = kq.shape[-1]
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.bfloat16)
    from edgellm_tpu.models import paged_kv as pk

    # layer 1 of 2 in the stored form; layer 0 holds other bytes under the
    # same page ids
    def stored(x, width):
        leaf = x.reshape(1, pn, ps, width)
        return jnp.concatenate([leaf[:, ::-1], leaf])

    pool = QuantPagePool(stored(kq, kv * hdc), stored(vq, kv * hdc),
                         stored(ks, kv), stored(vs, kv))
    idx = (pt[:, :, None] * ps
           + jnp.arange(ps)[None, None, :]).reshape(b, span)
    kg = dequantize_kv_rows(kq[idx], ks[idx], tier, q.dtype)
    vg = dequantize_kv_rows(vq[idx], vs[idx], tier, q.dtype)
    # the rows handed to the attend are the flat-row fetch's to the bit ...
    got_k, got_v = pk.read_span(pool, 1, pt, q.dtype)
    np.testing.assert_array_equal(np.asarray(got_k).reshape(kg.shape),
                                  np.asarray(kg))
    np.testing.assert_array_equal(np.asarray(got_v).reshape(vg.shape),
                                  np.asarray(vg))
    # ... and the attend over them as they lie is decode_attention over
    # their (KV, hd) view, to a reordered bf16 sum's tolerance
    got = paged_decode_attention(q, pool, 1, pt, lens)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(jnp.concatenate([
            decode_attention(q[i:i + 1], kg[i:i + 1], vg[i:i + 1], lens[i])
            for i in range(b)]), np.float32),
        rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# quantized pool surgery: adopt / gather / COW / defrag / state_dict
# ---------------------------------------------------------------------------


ALL_TIERS = ("fp",) + TIERS


def _zero_pool(axes, pn, ps, kv, hd, tier):
    """An all-zero pool with leading ``axes`` ((L,) on a chip, (n_stages,
    stage_size) staged) at ``tier``."""
    codec = resolve_kv_codec(tier)
    rows = tuple(axes) + (pn, ps)
    if not codec.quantized:   # one leaf: a row's K lanes, then its V lanes
        return PagePool(jnp.zeros(rows + (2 * kv * hd,), jnp.float32))
    codes = rows + (kv * codec.code_lanes(hd),)
    return QuantPagePool(jnp.zeros(codes, codec.code_dtype),
                         jnp.zeros(codes, codec.code_dtype),
                         jnp.zeros(rows + (kv,), jnp.float32),
                         jnp.zeros(rows + (kv,), jnp.float32))


def _host(pool):
    return [np.asarray(a) for a in pool]


@pytest.mark.parametrize("tier", ALL_TIERS)
@pytest.mark.parametrize("lead", [1, 2], ids=["one-chip", "staged"])
def test_pool_surgery_is_one_body_at_every_rank_and_tier(lead, tier):
    # the surgery of models/paged_kv.py over a chip's (L, ...) pool and over
    # the split runtime's (n_stages, stage_size, ...): adopt -> gather
    # returns the rows, packed gather -> packed adopt is a byte move, copy
    # and permute move every leaf (codes AND scales) together, and a staged
    # pool's stage s holds what the one-chip code makes of stage s's inputs
    from edgellm_tpu.models import paged_kv as pk
    from edgellm_tpu.parallel import split as split_mod

    axes = (3,) if lead == 1 else (2, 3)
    pn, ps, kv, hd, n = 7, 4, 2, 8, 7
    rng = np.random.default_rng(lead * 10 + len(tier))
    k = jnp.asarray(rng.standard_normal(axes + (n, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal(axes + (n, kv, hd)), jnp.float32)
    dest = jnp.asarray(np.r_[2 * ps:3 * ps, 3 * ps:3 * ps + 3], jnp.int32)
    adopt = pk._adopt_impl if lead == 1 else split_mod._adopt_paged_impl

    def run(k_, v_, lead_, adopt_):
        """Every surgery step once; host copies of every result."""
        out = {}
        pool = adopt_(_zero_pool(k_.shape[:lead_], pn, ps, kv, hd, tier),
                      k_, v_, dest)
        assert type(pool) is (PagePool if tier == "fp" else QuantPagePool)
        out["adopted"] = _host(pool)
        out["gathered"] = _host(pk._gather_impl(pool, dest, lead=lead_,
                                                kv=kv))
        if tier != "fp":     # the packed form is a quantized pool's
            out["packed"] = _host(pk._gather_packed_impl(pool, dest,
                                                         lead=lead_))
        # COW fork of pages 2, 3 into 4, 5 (the pool is donated: host copies)
        pool = pk._copy_pages_impl(pool, jnp.asarray([2, 3]),
                                   jnp.asarray([4, 5]), lead=lead_)
        out["copied"] = _host(pool)
        src = jnp.asarray([0, 4, 5, 1, 2, 3, 6], jnp.int32)
        out["permuted"] = _host(pk._permute_impl(pool, src, lead=lead_))
        return out, np.asarray(src)

    got, src = run(k, v, lead, adopt)
    page = (slice(None),) * lead
    # a row of layer l (of stage s) landed in layer l (of stage s) at its
    # page and offset and nowhere else: the (..., P, ps, KV, lanes) oracle,
    # written with the indices the flat (layer, page, row) index replaces
    stored_k = (np.asarray(k) if tier == "fp"
                else np.asarray(quantize_kv_rows(k, tier)[0]))
    want = np.zeros(axes + (pn, ps) + stored_k.shape[-2:], stored_k.dtype)
    want[page + (np.asarray(dest) // ps, np.asarray(dest) % ps)] = stored_k
    if tier == "fp":          # the one leaf: K lanes [0, W), V lanes [W, 2W)
        (joined,) = got["adopted"]
        assert joined.shape == axes + (pn, ps, 2 * kv * hd)
        adopted_k, adopted_v = joined[..., :kv * hd], joined[..., kv * hd:]
        want_v = np.zeros_like(want)
        want_v[page + (np.asarray(dest) // ps, np.asarray(dest) % ps)] = v
        np.testing.assert_array_equal(adopted_v.reshape(want.shape), want_v)
    else:
        adopted_k = got["adopted"][0]
    assert adopted_k.shape == axes + (
        pn, ps, kv * stored_k.shape[-1])                 # the stored row
    np.testing.assert_array_equal(adopted_k.reshape(want.shape), want)
    # adopt -> gather returns the rows (to the tier's quantization error)
    gk, gv = got["gathered"]
    if tier == "fp":
        np.testing.assert_array_equal(gk, np.asarray(k))
        np.testing.assert_array_equal(gv, np.asarray(v))
    else:
        codes, scales = quantize_kv_rows(k, tier)
        np.testing.assert_allclose(
            gk, np.asarray(dequantize_kv_rows(codes, scales, tier)),
            rtol=1e-6, atol=1e-7)
        step = np.abs(np.asarray(k)).max(-1, keepdims=True) / (
            7.0 if tier == "int4_per_channel" else 127.0)
        assert (np.abs(gk - np.asarray(k)) <= step / 2 + 1e-6).all()
        # packed gather -> packed adopt elsewhere -> packed gather: the bytes
        dest2 = jnp.asarray(np.r_[5 * ps:5 * ps + n], jnp.int32)
        fresh = _zero_pool(axes, pn, ps, kv, hd, tier)
        moved = pk._adopt_packed_impl(
            fresh, *(jnp.asarray(a) for a in got["packed"]), dest2, lead=lead)
        back = pk._gather_packed_impl(moved, dest2, lead=lead)
        for a, b in zip(back, got["packed"]):
            assert np.asarray(a).dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), b)
    # copy: every leaf's pages 4, 5 are its pages 2, 3; nothing else moved
    for before, after in zip(got["adopted"], got["copied"]):
        np.testing.assert_array_equal(after[page + ([4, 5],)],
                                      before[page + ([2, 3],)])
        keep = [0, 1, 2, 3, 6]
        np.testing.assert_array_equal(after[page + (keep,)],
                                      before[page + (keep,)])
    # permute: new[p] = old[src[p]] for every leaf, scales with their codes
    for before, after in zip(got["copied"], got["permuted"]):
        np.testing.assert_array_equal(after, before[page + (src,)])
    assert got["adopted"][0][page + (2,)].any()      # something was written
    if lead == 2:
        # stage by stage, the staged pool is the one-chip code's result
        for s in range(axes[0]):
            one, _ = run(k[s], v[s], 1, pk._adopt_impl)
            for name, leaves in one.items():
                for a, b in zip(leaves, got[name]):
                    np.testing.assert_array_equal(a, b[s], err_msg=name)


@pytest.mark.parametrize("tier", ALL_TIERS)
@pytest.mark.parametrize("lead", [1, 2], ids=["one-chip", "staged"])
@pytest.mark.parametrize("start,n", [(0, 11), (2, 7), (3, 14), (1, 2)])
def test_adopt_by_whole_pages_equals_adopt_by_rows(start, n, lead, tier):
    # an adopt whose rows fill whole pages scatters those a PAGE a slice
    # (head says where the first page boundary is) and the rows before and
    # after one by one: every leaf must equal the all-rows scatter's, over
    # pages that are not neighbours in the pool, from a start inside a page
    from edgellm_tpu.models import paged_kv as pk
    from edgellm_tpu.parallel import split as split_mod

    axes = (3,) if lead == 1 else (2, 3)
    pn, ps, kv, hd = 9, 4, 2, 8
    rng = np.random.default_rng(start * 100 + n)
    k = jnp.asarray(rng.standard_normal(axes + (n, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal(axes + (n, kv, hd)), jnp.float32)
    pos = np.arange(start, start + n)
    dest = (np.asarray([5, 2, 7, 1, 8])[pos // ps] * ps
            + pos % ps).astype(np.int32)
    head = pk.page_head(dest, ps)
    assert head == -start % ps
    adopt = pk._adopt_impl if lead == 1 else split_mod._adopt_paged_impl
    by_rows = _host(adopt(_zero_pool(axes, pn, ps, kv, hd, tier), k, v,
                          jnp.asarray(dest)))
    by_pages = _host(adopt(_zero_pool(axes, pn, ps, kv, hd, tier), k, v,
                           jnp.asarray(dest), head=head))
    assert by_rows[0].any()
    for a, b in zip(by_rows, by_pages):
        np.testing.assert_array_equal(a, b)
    if tier != "fp":
        packed = pk._gather_packed_impl(
            type(_zero_pool(axes, pn, ps, kv, hd, tier))(
                *(jnp.asarray(a) for a in by_rows)),
            jnp.asarray(dest), lead=lead)
        again = _host(pk._adopt_packed_impl(
            _zero_pool(axes, pn, ps, kv, hd, tier), *packed,
            jnp.asarray(dest), lead=lead, head=head))
        for a, b in zip(by_rows, again):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("tier", ALL_TIERS)
def test_one_chip_and_staged_step_share_one_write_and_one_read(
        tier, params, monkeypatch):
    # the layout of a K/V row is known in two functions of models/paged_kv.py:
    # the one-chip step and the split runtime's step both trace through
    # them, each with its whole carried pool of the tier's type
    from edgellm_tpu.models import paged_kv as pk
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, \
        make_stage_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    seen = []
    write, read = pk.write_rows, pk.read_span

    def recording_write(pool, *a):
        seen.append(("write", type(pool), pool[0].shape))
        return write(pool, *a)

    def recording_read(pool, *a):
        seen.append(("read", type(pool), pool[0].shape))
        return read(pool, *a)

    monkeypatch.setattr(pk, "write_rows", recording_write)
    monkeypatch.setattr(pk, "read_span", recording_read)
    npg, ps, slots, pps = BCFG.num_pages, BCFG.page_size, BCFG.max_slots, 4
    table = jnp.zeros((slots, pps), jnp.int32)
    ints = jnp.zeros((slots,), jnp.int32)
    codec = resolve_kv_codec(tier)
    # a page of the pool's first leaf: K codes, or the fp tier's K | V rows
    layer = (npg, ps, CFG.num_kv_heads * codec.code_lanes(CFG.head_dim)
             * (1 if codec.quantized else 2))
    kind = QuantPagePool if codec.quantized else PagePool

    def want(layers):   # the pool WITH its layer axis, one write, one read
        return [(op, kind, (layers,) + layer) for op in ("write", "read")]

    pool = _zero_pool((CFG.num_layers,), npg, ps, CFG.num_kv_heads,
                      CFG.head_dim, tier)
    jax.make_jaxpr(lambda *a: pk.paged_decode_step(CFG, *a))(
        params, pool, table, ints, ints)
    # the layer scan traces its body once, over the carried whole pool
    assert seen == want(CFG.num_layers)
    del seen[:]

    rt = SplitRuntime(CFG, SplitConfig(cuts=(2,), hop_codecs=("fp32",)),
                      make_stage_mesh(2))
    staged = rt.init_paged_pool(npg, ps, kv_codec=tier)
    assert type(staged) is kind and pk.pool_tier(staged) == tier
    assert staged[0].shape == (2, rt.stage_size) + layer
    jax.make_jaxpr(rt._paged_decode_fns(npg, ps, kv_codec=tier))(
        rt.place_params(params), staged, table, ints, ints)
    # one stage body, scanned by every stage, over the stage's carried pool
    assert seen == want(rt.stage_size)


def test_packed_gather_adopt_roundtrip_across_geometry():
    cache = _qcache("int8_per_channel")
    s = cache.alloc_slot()
    k, v = _seq(10, 0)
    cache.adopt(s, k, v, 10)
    cache.check_invariants()
    packed = cache.gather_slot_packed(s)
    # the dequantized view agrees with dequantizing the packed bytes
    # (to fp rounding — XLA may fuse the scale multiply differently)
    g = cache.gather_slot(s)
    np.testing.assert_allclose(
        g["k"], np.asarray(dequantize_kv_rows(
            jnp.asarray(packed["k_codes"]), jnp.asarray(packed["k_scale"]),
            "int8_per_channel")), rtol=1e-6, atol=1e-7)
    # adopt_packed into a DIFFERENT pool geometry: bytes land unchanged
    other = _qcache("int8_per_channel", num_pages=5, page_size=8,
                    max_slots=2, pages_per_slot=2)
    s2 = other.alloc_slot()
    other.adopt_packed(s2, packed["k_codes"], packed["v_codes"],
                       packed["k_scale"], packed["v_scale"],
                       int(packed["length"]))
    other.check_invariants()
    _packed_equal(other.gather_slot_packed(s2), packed)
    # the packed API is tier-gated in both directions
    fp = _qcache("fp")
    sf = fp.alloc_slot()
    fp.adopt(sf, k, v, 10)
    with pytest.raises(ValueError, match="quantized tiers"):
        fp.gather_slot_packed(sf)
    with pytest.raises(ValueError, match="quantized tiers"):
        fp.adopt_packed(sf, packed["k_codes"], packed["v_codes"],
                        packed["k_scale"], packed["v_scale"], 10)


def test_quant_cow_fork_is_a_byte_move():
    pcfg = PrefixCacheConfig(enabled=True, min_shared_block=1)
    cache = _qcache("int4_per_channel", prefix_cache=pcfg)
    s0 = cache.alloc_slot()
    k0, v0 = _seq(10, 0)
    cache.adopt(s0, k0, v0, 10)
    assert cache.register_prefix(s0, PROMPT) == 3
    donor = cache.gather_slot_packed(s0)
    s1 = cache.alloc_slot()
    assert cache.share_prefix(s1, PROMPT + [111, 112], max_tokens=11) == 10
    k1, v1 = _seq(2, 1)
    cache.adopt_rows(s1, k1, v1, 10, 12)   # forks the shared partial page
    cache.check_invariants()
    assert cache.prefix_counters["cow_forks"] == 1
    # the fork copied codes AND scales: the sharer's first 10 rows are
    # byte-identical to the donor's, and the donor is untouched
    _packed_equal(cache.gather_slot_packed(s1), donor, rows=10)
    _packed_equal(cache.gather_slot_packed(s0), donor)


def test_defrag_with_packed_pages_preserves_bytes():
    cache = _qcache("int8_per_channel")
    slots, snaps = [], {}
    for i, n in enumerate((10, 7, 12)):
        s = cache.alloc_slot()
        k, v = _seq(n, i)
        cache.adopt(s, k, v, n)
        slots.append(s)
    cache.free_slot(slots[1])     # punch holes mid-pool
    for s in (slots[0], slots[2]):
        snaps[s] = cache.gather_slot_packed(s)
    assert cache.defrag() > 0
    cache.check_invariants()
    for s, snap in snaps.items():
        _packed_equal(cache.gather_slot_packed(s), snap)


def test_state_dict_roundtrip_and_tier_refusal():
    cache = _qcache("int8_per_channel")
    s = cache.alloc_slot()
    k, v = _seq(9, 4)
    cache.adopt(s, k, v, 9)
    state = cache.state_dict()
    assert state["kv_codec"] == "int8_per_channel"
    assert {"k_codes", "v_codes", "k_scale", "v_scale"} <= set(state)
    twin = _qcache("int8_per_channel")
    twin.load_state_dict(state)
    twin.check_invariants()
    _packed_equal(twin.gather_slot_packed(s), cache.gather_slot_packed(s))
    np.testing.assert_array_equal(np.asarray(twin.pool.k),
                                  np.asarray(cache.pool.k))
    # cross-tier restore is refused in BOTH directions, never transcoded
    with pytest.raises(ValueError, match="transcoding is refused"):
        _qcache("fp").load_state_dict(state)
    fp = _qcache("fp")
    sf = fp.alloc_slot()
    fp.adopt(sf, k, v, 9)
    fp_state = fp.state_dict()
    # fp checkpoints keep the pre-quantization key set
    assert "kv_codec" not in fp_state and {"k", "v"} <= set(fp_state)
    with pytest.raises(ValueError, match="transcoding is refused"):
        _qcache("int8_per_channel").load_state_dict(fp_state)


# ---------------------------------------------------------------------------
# quantized continuous batching
# ---------------------------------------------------------------------------


def test_fp_tier_is_default_and_token_identical(params):
    assert BatchingConfig().kv_codec == "fp"
    with pytest.raises(ValueError, match="unknown kv_codec"):
        BatchingConfig(kv_codec="float13")
    bat = ContinuousBatcher(CFG, params, dataclasses.replace(
        BCFG, kv_codec="fp"))
    assert not hasattr(bat.pool.pool, "k_scale")   # plain fp PagePool
    p = _prompt(7, 40)
    sid = bat.submit(p, 5, temperature=0.7, rng_seed=3)
    np.testing.assert_array_equal(bat.run()[sid],
                                  _solo(params, p, 5, 0.7, 3))


def test_mixed_tiers_coexist_in_process(params):
    # one process, three batchers at three tiers over the SAME geometry:
    # jit caches are keyed by tier, pools never mix, everything drains
    streams = [dict(prompt=_prompt(6, 50), max_new=5, temp=0.0, seed=7),
               dict(prompt=_prompt(11, 51), max_new=4, temp=0.8, seed=8)]
    for bcfg in (BCFG, BCFG8, BCFG4):
        bat = ContinuousBatcher(CFG, params, bcfg)
        sids = [bat.submit(s["prompt"], s["max_new"],
                           temperature=s["temp"], rng_seed=s["seed"])
                for s in streams]
        results = bat.run()
        for sid, s in zip(sids, streams):
            assert len(results[sid]) == s["max_new"]
        rep = bat.report()
        assert rep["finished"] == len(streams) and rep["evicted"] == 0
        if bcfg.kv_codec == "fp":   # fp tier stays bit-identical to solo
            for sid, s in zip(sids, streams):
                np.testing.assert_array_equal(
                    results[sid], _solo(params, s["prompt"], s["max_new"],
                                        s["temp"], s["seed"]))


def test_quant_eviction_readmit_bit_identical(params):
    # pool too small for all three quant streams: the evicted stream's
    # pages leave as PACKED bytes and come back as the same bytes, so its
    # tokens match the uncontended run of the SAME tier exactly
    streams = [dict(prompt=_prompt(15, 60), max_new=8, temp=0.0, seed=1),
               dict(prompt=_prompt(14, 61), max_new=8, temp=0.9, seed=2),
               dict(prompt=_prompt(13, 62), max_new=8, temp=0.0, seed=3)]
    ref = {}
    roomy = ContinuousBatcher(CFG, params, BCFG8)
    for i, s in enumerate(streams):
        sid = roomy.submit(s["prompt"], s["max_new"],
                           temperature=s["temp"], rng_seed=s["seed"])
        ref[i] = roomy.run()[sid]
    tight = ContinuousBatcher(CFG, params, dataclasses.replace(
        BCFG8, num_pages=8))          # 7 allocatable pages
    sids = [tight.submit(s["prompt"], s["max_new"], temperature=s["temp"],
                         rng_seed=s["seed"]) for s in streams]
    results = tight.run()
    assert tight.report()["evicted"] > 0
    for i, sid in enumerate(sids):
        np.testing.assert_array_equal(results[sid], ref[i])


def test_quant_checkpoint_restore_across_geometry(params, tmp_path):
    p = _prompt(7, 70)
    ref = ContinuousBatcher(CFG, params, BCFG8)
    ref_sid = ref.submit(p, 8, temperature=0.6, rng_seed=42)
    want = ref.run()[ref_sid]
    bat = ContinuousBatcher(CFG, params, BCFG8)
    sid = bat.submit(p, 8, temperature=0.6, rng_seed=42)
    for _ in range(4):
        bat.step()
    path = bat.checkpoint_stream(sid, str(tmp_path / "q.ckpt"))
    # a DIFFERENT pool geometry at the same tier: the payload is packed
    # rows, not pages, so the restored stream finishes bit-identically
    other = ContinuousBatcher(CFG, params, dataclasses.replace(
        BCFG8, page_size=4, num_pages=33, max_slots=2, pages_per_slot=8))
    rid = other.restore_stream(path)
    np.testing.assert_array_equal(other.run()[rid], want)


def test_quant_checkpoint_cross_tier_restore_refused(params, tmp_path):
    bat = ContinuousBatcher(CFG, params, BCFG8)
    sid = bat.submit(_prompt(5, 80), 4)
    bat.step()
    qpath = bat.checkpoint_stream(sid, str(tmp_path / "q.ckpt"))
    with pytest.raises(CheckpointError, match="transcoding is refused"):
        ContinuousBatcher(CFG, params, BCFG).restore_stream(qpath)
    with pytest.raises(CheckpointError, match="transcoding is refused"):
        ContinuousBatcher(CFG, params, BCFG4).restore_stream(qpath)
    fbat = ContinuousBatcher(CFG, params, BCFG)
    fsid = fbat.submit(_prompt(5, 81), 4)
    fbat.step()
    fpath = fbat.checkpoint_stream(fsid, str(tmp_path / "f.ckpt"))
    with pytest.raises(CheckpointError, match="transcoding is refused"):
        ContinuousBatcher(CFG, params, BCFG8).restore_stream(fpath)


# ---------------------------------------------------------------------------
# split runtime: per-stage quant pools move the same bytes
# ---------------------------------------------------------------------------


def test_split_quant_pool_packed_roundtrip(params):
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, \
        make_stage_mesh

    rt = SplitRuntime(CFG, SplitConfig(cuts=(2,), hop_codecs=("fp32",)),
                      make_stage_mesh(2))
    placed = rt.place_params(params)
    ps, npg = 8, 9
    host = PagedKVCache(CFG, num_pages=npg, page_size=ps, max_slots=2,
                        pages_per_slot=4, materialize=False,
                        kv_codec="int8_per_channel")
    pool = rt.init_paged_pool(npg, ps, kv_codec="int8_per_channel")
    prompt = _prompt(9, 90)
    _, cache = rt.prefill_decode(placed, jnp.asarray(prompt)[None], 32)
    slot = host.alloc_slot()
    host.ensure(slot, len(prompt))
    dest = host._flat_indices(slot, len(prompt))
    pool = rt.adopt_paged(pool, cache, 0, dest, len(prompt))
    host.lengths[slot] = len(prompt)
    packed = rt.gather_paged_packed(pool, dest)
    # readmit the SAME bytes at a different placement in a fresh pool
    pool2 = rt.init_paged_pool(npg, ps, kv_codec="int8_per_channel")
    host2 = PagedKVCache(CFG, num_pages=npg, page_size=ps, max_slots=2,
                         pages_per_slot=4, materialize=False,
                         kv_codec="int8_per_channel")
    host2.alloc_slot()
    s2 = host2.alloc_slot()       # slot 1: different page placement
    host2.ensure(s2, len(prompt))
    dest2 = host2._flat_indices(s2, len(prompt))
    pool2 = rt.adopt_paged_rows_packed(pool2, *packed, dest2)
    back = rt.gather_paged_packed(pool2, dest2)
    for a, b in zip(packed, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the dequantized gather form stays finite (suffix-prefill compute path)
    rows_k, rows_v = rt.gather_paged(pool, dest)
    assert np.isfinite(rows_k).all() and np.isfinite(rows_v).all()
    # the packed APIs are tier-gated on fp pools
    fpool = rt.init_paged_pool(npg, ps)
    with pytest.raises(ValueError, match="quantized"):
        rt.gather_paged_packed(fpool, dest)
    with pytest.raises(ValueError, match="quantized"):
        rt.adopt_paged_rows_packed(fpool, *packed, dest2)


# ---------------------------------------------------------------------------
# eval harness
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_kv_tier_eval_sweep_bounds(params):
    from edgellm_tpu.eval.split_eval import run_kv_tier_sweep

    corpus = np.random.default_rng(0).integers(
        1, CFG.vocab_size, size=256).astype(np.int32)
    rows = run_kv_tier_sweep(CFG, params, corpus,
                             tiers=("fp", "int8_per_channel"),
                             max_length=32, stride=32, page_size=8,
                             window_batch=2, max_chunks=2)
    by = {r["kv_codec"]: r for r in rows}
    assert by["fp"]["ppl_delta_vs_fp"] == 0.0
    assert abs(by["int8_per_channel"]["ppl_delta_vs_fp"]) < 0.01
    assert (by["int8_per_channel"]["kv_page_bytes"]
            < by["int8_per_channel"]["kv_page_bytes_fp"])
    assert all(np.isfinite(r["ppl"]) for r in rows)
