"""The prefill's grouped expert products as one tiled kernel on the MXU.

``rows (M, K)`` sorted by group, ``weights (Eh, K, N)``, ``sizes (Eh,)``:
row ``r`` of group ``g`` (the rows ``[sum(sizes[:g]), sum(sizes[:g + 1]))``)
times ``weights[g]``, float32 accumulation, the result in the rows' type:
what ``jax.lax.ragged_dot`` computes, which stays the oracle and the path of
every backend but a TPU (:func:`grouped_product_path`).

The rows are cut into tiles of ``tm``. A VISIT is one (row tile, group) pair
that share a row: a tile that straddles a group boundary is visited once a
group and stores under a row mask. The grid's length is the number of visits,
a traced value: row tiles that lie wholly past the last group (the
assignments to absent experts and the padding tokens, which sort last) are
never visited, neither read nor multiplied nor written. What the result holds
in a row of no group is not defined, as with ``ragged_dot`` on a TPU: the
caller selects it out.

The weights stay in HBM and the kernel fetches a group's block itself (the
whole contraction by a column tile) into one of two VMEM buffers: at a
group's FIRST visit it waits for that block and starts the next live group's
into the other buffer, so a group's weights are read once however many row
tiles it spans, an empty group's never, and the fetch runs under ALL of the
current group's visits. (The grid's own pipeline looks one step ahead: with
~1.5 visits a group at granite's widths a 12.6 MB block then hides under one
8 us visit and the kernel waits: this form takes 16-26% less there on a v5e,
8-16% at the other cells' widths; PERF.md section 6 "PR 37".)

:func:`grouped_swiglu` is the gate and up products in one call with
``silu(gate) * up`` in the epilogue: a row tile is read once for both and the
two (M, F) intermediates are never written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .configs import LANE_TILE

PALLAS_GROUPED = "pallas grouped matmul"
XLA_RAGGED = "xla ragged dot"

#: rows a visit: one pass of a v5e's 128 x 128 MXU. A visit's masked rows
#: are multiplied for nothing, at most one tile's worth a group, so the tile
#: is the smallest that fills the unit
ROW_TILE = 128
#: what a call's two buffers a weight may take of VMEM (a v5e has 128 MiB):
#: the column tile is the widest whole-lane divisor of N under it
WEIGHT_BLOCK_BYTES = 40 << 20
VMEM_LIMIT_BYTES = 100 << 20


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def grouped_product_path(k: int, n: int) -> str:
    """Which grouped product a call with contraction ``k`` and ``n`` columns
    is built with, read off what it is handed: the kernel on a TPU where both
    are whole lane tiles (the rows are padded to whole row tiles here), the
    XLA ``ragged_dot`` (the oracle) everywhere else."""
    whole = k % LANE_TILE == 0 and n % LANE_TILE == 0
    return PALLAS_GROUPED if whole and _on_tpu() else XLA_RAGGED


def column_tile(k: int, n: int, itemsize: int, weights: int) -> int:
    """The widest divisor of ``n`` in whole lane tiles whose ``weights``
    double-buffered (k, tile) blocks fit :data:`WEIGHT_BLOCK_BYTES` (F = 896
    = 7 x 128 gives 896 or 128, never a 256 or 512 it does not divide)."""
    lanes = n // LANE_TILE
    fits = [d for d in range(1, lanes + 1) if lanes % d == 0
            and 2 * weights * k * d * LANE_TILE * itemsize
            <= WEIGHT_BLOCK_BYTES]
    return LANE_TILE * max(fits, default=1)


def visits(sizes, tiles_m: int, tm: int):
    """The walk over ``sizes (Eh,)`` rows sorted by group, cut into
    ``tiles_m`` tiles of ``tm``: (group offsets (Eh + 1,), the group of each
    visit (V,), its row tile (V,), for each group the next one that has rows
    or -1 (Eh,), the number of visits ()), V = ``tiles_m + Eh - 1`` the most
    there can be. Visits are in row order, so a tile's are consecutive; an
    empty group has none; entries past the count repeat the last visit's."""
    eh = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    v_end = jnp.cumsum(count)
    total = v_end[-1]
    v = jnp.minimum(jnp.arange(tiles_m + eh - 1, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    # the group of visit v: the first whose visits end past it
    group = jnp.minimum(jnp.sum(v[:, None] >= v_end[None, :], axis=1),
                        eh - 1).astype(jnp.int32)
    tile = first[group] + v - (v_end - count)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    ids = jnp.arange(eh, dtype=jnp.int32)
    later = jnp.min(jnp.where((sizes > 0)[None, :] & (ids[None, :]
                                                      > ids[:, None]),
                              ids[None, :], eh), axis=1)
    return (offsets.astype(jnp.int32), group,
            jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32),
            jnp.where(later == eh, -1, later).astype(jnp.int32),
            total.astype(jnp.int32))


def _kernel(offsets, groups, tiles, later, x_ref, *refs, tm: int, tn: int):
    n = (len(refs) - 3) // 2
    w_hbm, o_ref, bufs = refs[:n], refs[n], refs[n + 1:2 * n + 1]
    sem, slot_ref = refs[2 * n + 1:]
    j, i = pl.program_id(0), pl.program_id(1)
    g = groups[i]

    def fetch(group, column, slot):
        """A group's (K, tn) block of each weight, HBM -> buffer ``slot``."""
        at = pl.ds(pl.multiple_of(column * tn, LANE_TILE), tn)
        return [pltpu.make_async_copy(w.at[group, :, at], buf.at[slot],
                                      sem.at[slot, k])
                for k, (w, buf) in enumerate(zip(w_hbm, bufs))]

    @pl.when((i == 0) & (j == 0))
    def _():                       # nobody fetched ahead for the first visit
        slot_ref[0] = 1
        for copy in fetch(g, j, 0):
            copy.start()

    @pl.when((i == 0) | (groups[jnp.maximum(i - 1, 0)] != g))
    def _():                                   # a group's first visit
        slot = 1 - slot_ref[0]
        slot_ref[0] = slot
        for copy in fetch(g, j, slot):
            copy.wait()
        ahead = later[g]

        @pl.when(ahead >= 0)
        def _():
            for copy in fetch(ahead, j, 1 - slot):
                copy.start()

        @pl.when((ahead < 0) & (j + 1 < pl.num_programs(0)))
        def _():                   # the next column tile's first group
            for copy in fetch(groups[0], j + 1, 1 - slot):
                copy.start()

    slot = slot_ref[0]
    row = tiles[i] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= offsets[g]) & (row < offsets[g + 1])
    x = x_ref[...]
    # each product rounded to the rows' type, as ragged_dot hands it back
    y = [jnp.dot(x, buf[slot], preferred_element_type=jnp.float32)
         .astype(o_ref.dtype).astype(jnp.float32) for buf in bufs]
    out = jax.nn.silu(y[0]) * y[1] if n == 2 else y[0]
    # rows of the tile's other groups keep what their visit stored
    o_ref[...] = jnp.where(mine, out, o_ref[...].astype(jnp.float32)
                           ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped(rows, weights: tuple, sizes, *, interpret=False):
    m, k = rows.shape
    n = weights[0].shape[-1]
    tm = ROW_TILE
    tn = column_tile(k, n, rows.dtype.itemsize, len(weights))
    pad = -m % tm
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))       # rows of no group
    tiles_m = (m + pad) // tm
    *walk, total = visits(sizes.astype(jnp.int32), tiles_m, tm)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n // tn, total),
            in_specs=[pl.BlockSpec((tm, k), lambda j, i, o, g, t, a: (t[i], 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(weights),
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, o, g, t, a: (t[i], j)),
            scratch_shapes=[pltpu.VMEM((2, k, tn), w.dtype) for w in weights]
            + [pltpu.SemaphoreType.DMA((2, len(weights))),
               pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((m + pad, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="grouped_matmul",
    )(*walk, rows, *weights)
    return out[:m] if pad else out


def grouped_matmul(rows, w, sizes, *, interpret=False):
    """rows (M, K) sorted by group @ w (Eh, K, N) by ``sizes`` (Eh,) -> (M,
    N) in the rows' type; a row of no group holds anything."""
    return _grouped(rows, (w,), sizes, interpret=interpret)


def grouped_swiglu(rows, w_gate, w_up, sizes, *, interpret=False):
    """``silu(rows @ w_gate[g]) * (rows @ w_up[g])`` by group -> (M, F): each
    product rounded to the rows' type, the activation and the product of the
    two in float32, rounded once."""
    return _grouped(rows, (w_gate, w_up), sizes, interpret=interpret)
