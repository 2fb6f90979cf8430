"""Grouped expert FFN (dropless MoE): the SwiGLU of each row through the
expert its group names, over rows sorted by expert.

Rows are grouped: group g owns rows [start_g, end_g) of ``x`` (M, D), in
group order, and rows past the last group are not computed. The work is a
list of (group, row tile) items in that order, one per row tile a group
touches, so a tile shared by two groups is visited twice in a row and a
group with no rows is not visited at all: an expert's weights are read
once per tile of its rows and never for an expert nothing was routed to.
The item list is made on the device from the group sizes and prefetched
(``PrefetchScalarGridSpec``); the grid has room for the most items M rows
can need, and the items past the real count repeat the last one's blocks,
so they issue no DMA, and skip their compute.

Grid (items, F tiles), the F axis innermost: each step computes
silu(x @ wg[:, f]) * (x @ wi[:, f]) for one (block_m, block_f) tile and adds
its product with wo[f, :] into the float32 output tile, masked to the
group's rows. The output tile's block index only changes between items on
different tiles, so it stays in VMEM while it accumulates, and it is
zeroed at the first visit of its tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def work_items(group_sizes, rows: int, block_m: int):
    """(item_group, item_tile, starts, ends, count) for rows grouped by
    ``group_sizes``: every (group, row tile) pair a group's rows touch, in
    group order, padded to the most items ``rows`` rows can need by
    repeating the last real item."""
    sizes = group_sizes.astype(jnp.int32)
    G = sizes.shape[0]
    n_items = -(-rows // block_m) + G - 1
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // block_m
    last = (jnp.maximum(ends, 1) - 1) // block_m
    per = jnp.where(sizes > 0, last - first + 1, 0)
    upto = jnp.cumsum(per)
    count = upto[-1]
    i = jnp.minimum(jnp.arange(n_items, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    g = jnp.minimum(jnp.searchsorted(upto, i, side="right"), G - 1)
    t = first[g] + i - (upto[g] - per[g])
    return (g.astype(jnp.int32), t.astype(jnp.int32), starts, ends,
            count.reshape((1,)))


def _kernel(grp_ref, tile_ref, start_ref, end_ref, count_ref, x_ref, wg_ref,
            wi_ref, wo_ref, o_ref, *, block_m: int):
    i = pl.program_id(0)
    f = pl.program_id(1)
    live = i < count_ref[0]
    t = tile_ref[i]
    first_visit = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != t)

    @pl.when(live & (f == 0) & first_visit)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _compute():
        g = grp_ref[i]
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wi_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        part = jnp.dot(h, wo_ref[0], preferred_element_type=jnp.float32)
        rows = t * block_m + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_m, 1), 0)
        mine = (rows >= start_ref[g]) & (rows < end_ref[g])
        o_ref[...] += jnp.where(mine, part, 0.0)


def grouped_ffn(x, wg, wi, wo, group_sizes, *, block_m: int = 128,
                block_f: int = 128, interpret: bool = False):
    """x: (M, D) rows sorted by group; wg, wi: (G, D, F); wo: (G, F, D);
    group_sizes: (G,) int32 rows per group. Returns (M, D) float32; rows
    past sum(group_sizes) are undefined (the caller masks them)."""
    M, D = x.shape
    G, _, F = wg.shape
    block_f = min(block_f, F)
    assert F % block_f == 0, (F, block_f)
    if M <= block_m:
        block_m = M
    Mp = -(-M // block_m) * block_m
    xp = jnp.pad(x, ((0, Mp - M), (0, 0)))
    items = work_items(group_sizes, Mp, block_m)
    n_items = items[0].shape[0]

    def rows_of(i, f, g, t, *_):
        return (t[i], 0)

    out = pl.pallas_call(
        functools.partial(_kernel, block_m=block_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_items, F // block_f),
            in_specs=[
                pl.BlockSpec((block_m, D), rows_of),
                pl.BlockSpec((1, D, block_f),
                             lambda i, f, g, *_: (g[i], 0, f)),
                pl.BlockSpec((1, D, block_f),
                             lambda i, f, g, *_: (g[i], 0, f)),
                pl.BlockSpec((1, block_f, D),
                             lambda i, f, g, *_: (g[i], f, 0)),
            ],
            out_specs=pl.BlockSpec((block_m, D), rows_of),
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, D), jnp.float32),
        interpret=interpret,
        name="grouped_ffn",
    )(*items, xp, wg, wi, wo)
    return out[:M]
