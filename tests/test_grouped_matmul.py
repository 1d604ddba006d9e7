"""The grouped-matmul kernel against ``jax.lax.ragged_dot``:
``models/grouped_matmul.py`` in TPU-interpret mode on the CPU, held to the
XLA grouped product (which stays the oracle and every other backend's path)
at the four expert cells' shape families scaled down.

What a chip does with the kernel is ``tests/test_chip_compile.py``'s (it
compiles) and the benchmark's (it is timed); here is what it computes: every
row of a group times its group's weights, whatever the groups' layout over
the row tiles, and nothing of a row that belongs to no group.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from edgellm_tpu.models import grouped_matmul as gm

TILE = gm.ROW_TILE

#: name -> (K = D, N = F, experts held Eh of E, their offset, k, dtype): the
#: cells' families with whole lane tiles kept whole (mellum's F = 7 x 128 as
#: published) and the held share of each (half, all, a quarter, all)
FAMILIES = {
    "granite-36-of-72-top10": (256, 128, 3, 6, 0, 10, jnp.float32),
    "mellum-64-of-64-top8-f896": (128, 896, 4, 4, 0, 8, jnp.float32),
    "mistral4-32-of-128-top4-bf16": (256, 256, 2, 8, 4, 4, jnp.bfloat16),
    "trinity-128-of-128-top8": (128, 128, 8, 8, 0, 8, jnp.float32),
}


def _routed(tokens):
    """Group sizes as a prefill's routing makes them: the family's top-k of E
    by seeded logits, the held ones counted (``moe._experts_grouped``)."""
    def sizes(m, eh, e, offset, k):
        t = tokens or m // k
        idx = np.argsort(np.random.default_rng(3).standard_normal((t, e)),
                         axis=-1)[:, :k] - offset
        return np.bincount(idx[(idx >= 0) & (idx < eh)], minlength=eh)
    return sizes


def _split(total_of_m, hole=None):
    """``total_of_m(m)`` rows cut into Eh uneven groups, group ``hole``
    emptied into its neighbour."""
    def sizes(m, eh, e, offset, k):
        total = total_of_m(m)
        cuts = np.sort(np.random.default_rng(4).integers(0, total + 1, eh - 1))
        out = np.diff(np.concatenate([[0], cuts, [total]]))
        if hole is not None:
            out[(hole + 1) % eh] += out[hole % eh]
            out[hole % eh] = 0
        return out
    return sizes


#: name -> (tokens T, or None for 4 row tiles' worth; sizes (M, Eh, E, offset,
#: k) -> (Eh,)): the layouts that break grouped kernels
LAYOUTS = {
    "as-routed": (None, _routed(None)),
    "an-empty-group": (None, _split(lambda m: m - TILE - 5, hole=1)),
    "a-boundary-inside-a-row-tile": (None, _split(lambda m: 2 * TILE + 9)),
    "every-row-absent": (None, lambda m, eh, *_: np.zeros(eh, np.int64)),
    "no-row-absent": (None, _split(lambda m: m)),
    "whole-tiles-past-the-last-group": (None, _split(lambda m: TILE - 3)),
    # 40 tokens where 33 came: GROUPED_TOKEN_MULTIPLE's padding, and a row
    # count (40 k) that is no whole row tile for any k here
    "padded-tokens": (40, _routed(33)),
}


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("family", FAMILIES)
def test_kernel_computes_what_ragged_dot_computes(family, layout):
    """Both entries — one product, and gate + up with the activation in the
    epilogue — equal the oracle on every row of a group; no group's rows
    hold anything that is not finite."""
    k_dim, n, eh, e, offset, k, dtype = FAMILIES[family]
    tokens, sizes_of = LAYOUTS[layout]
    m = tokens * k if tokens else 4 * TILE
    sizes = jnp.asarray(sizes_of(m, eh, e, offset, k), jnp.int32)
    total = int(sizes.sum())
    assert total <= m
    keys = jax.random.split(jax.random.key(11), 3)
    rows = jax.random.normal(keys[0], (m, k_dim), jnp.float32).astype(dtype)
    w_a, w_b = ((jax.random.normal(key, (eh, k_dim, n), jnp.float32)
                 * k_dim ** -0.5).astype(dtype) for key in keys[1:])
    run = dict(interpret=pltpu.InterpretParams())
    one = jax.block_until_ready(gm.grouped_matmul(rows, w_a, sizes, **run))
    two = jax.block_until_ready(gm.grouped_swiglu(rows, w_a, w_b, sizes,
                                                  **run))
    assert one.shape == two.shape == (m, n) and one.dtype == dtype
    want_a = jax.lax.ragged_dot(rows, w_a, sizes)
    want = (jax.nn.silu(want_a.astype(jnp.float32))
            * jax.lax.ragged_dot(rows, w_b, sizes).astype(jnp.float32))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for got, ref in ((one, want_a), (two, want)):
        got, ref = (np.asarray(x[:total], np.float32) for x in (got, ref))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("sizes, tiles, want", [
    # (sizes, row tiles of 4) -> (group, tile) of each visit in row order
    ((4, 4, 4), 3, [(0, 0), (1, 1), (2, 2)]),
    ((3, 0, 6), 3, [(0, 0), (2, 0), (2, 1), (2, 2)]),
    ((0, 0, 0), 3, []),
    ((9, 1, 0), 3, [(0, 0), (0, 1), (0, 2), (1, 2)]),
    ((1, 1, 1), 4, [(0, 0), (1, 0), (2, 0)]),
], ids=["aligned", "an-empty-group-and-a-straddled-tile", "no-rows",
        "a-group-over-three-tiles", "three-groups-in-one-tile"])
def test_visits_walk_each_tile_once_a_group_and_stop_at_the_last_group(
        sizes, tiles, want):
    offsets, group, tile, later, total = gm.visits(
        jnp.asarray(sizes, jnp.int32), tiles, 4)
    assert int(total) == len(want)
    assert group.shape == tile.shape == (tiles + len(sizes) - 1,)
    got = list(zip(np.asarray(group).tolist(), np.asarray(tile).tolist()))
    assert got[:len(want)] == want
    # what lies past the count repeats the last visit: no block is fetched
    # for it, and none out of range is ever named
    assert all(pair == (want[-1] if want else (len(sizes) - 1, 0))
               for pair in got[len(want):])
    np.testing.assert_array_equal(np.asarray(offsets),
                                  np.concatenate([[0], np.cumsum(sizes)]))
    # each group's next group with rows: whose weights its first visit fetches
    live = [g for g, size in enumerate(sizes) if size]
    assert np.asarray(later).tolist() == [
        next((h for h in live if h > g), -1) for g in range(len(sizes))]


@pytest.mark.parametrize("k, n, itemsize, weights, want", [
    (4096, 768, 2, 2, 768), (768, 4096, 2, 1, 4096),     # granite
    (2304, 896, 2, 2, 896), (896, 2304, 2, 1, 2304),     # mellum: 7 x 128
    (4096, 2048, 2, 2, 1024), (2048, 4096, 2, 1, 4096),  # mistral4
    (2048, 1024, 2, 2, 1024), (1024, 2048, 2, 1, 2048),  # trinity
    (4096, 2048, 4, 2, 512), (128, 128, 4, 1, 128),
])
def test_column_tile_divides_the_width_and_fits_the_budget(k, n, itemsize,
                                                           weights, want):
    tn = gm.column_tile(k, n, itemsize, weights)
    assert tn == want and n % tn == 0 and tn % gm.LANE_TILE == 0
    assert (2 * weights * k * tn * itemsize <= gm.WEIGHT_BLOCK_BYTES
            or tn == gm.LANE_TILE)


@pytest.mark.parametrize("k, n, tpu, want", [
    (4096, 768, True, gm.PALLAS_GROUPED), (2304, 896, True, gm.PALLAS_GROUPED),
    (64, 32, True, gm.XLA_RAGGED), (128, 96, True, gm.XLA_RAGGED),
    (4096, 768, False, gm.XLA_RAGGED),
])
def test_the_path_is_read_off_the_backend_and_the_widths(monkeypatch, k, n,
                                                         tpu, want):
    monkeypatch.setattr(gm, "_on_tpu", lambda: tpu)
    assert gm.grouped_product_path(k, n) == want
