"""Grouped matmul for FEW rows a group: a Pallas TPU kernel whose row
tile is sized to the groups and whose weight tile is megabytes, so that
a product's time is the bytes of the groups touched.

``xs`` (m, k) holds rows sorted by group; group ``e`` is the contiguous
range ``[sum(sizes[:e]), sum(sizes[:e + 1]))`` and multiplies
``w[e]`` of the stacked ``(n, k, f)`` kernels; rows past the last group
belong to none. The scheme is megablox's (``jax.experimental.pallas.ops.
tpu.megablox``): the rows are cut into tiles of ``row_tile``, and the
grid walks a WORK LIST of (group, row tile) pairs, one pair for every
tile a non-empty group overlaps — a group larger than the tile takes
several, and a tile that several groups share is visited once by each,
every visit storing its own rows alone. What differs is the shape of a
step, which is made for decode: whole-``k`` weight tiles of
``WEIGHT_TILE_BYTES`` read where the stacked kernels lie (a grid step is
one DMA of megabytes, not 32 KB), the list padded to its static bound by
repeating its last pair (the pipeline elides a fetch whose block does
not move, and the step is skipped), and an activation taken in f32
before the one rounding: optionally TWO kernels a call with ``silu(x w0)
* (x w1)``, or ``relu(x w0)^2`` of one.

A group with no row is in no pair: its kernel is never fetched.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from rafiki_tpu.ops.attention import _pad_to, _resolve_interpret

#: bytes of ONE weight tile (whole k x a slice of the columns); the
#: pipeline holds two of each kernel's
WEIGHT_TILE_BYTES = 4 * 1024 * 1024
#: what the call tells Mosaic it may use of VMEM: two kernels' double
#: buffers at the tile above, the rows and the f32 products beside them
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


class GroupVisits(NamedTuple):
    """The work list of one set of ``sizes`` at one ``row_tile``: int32
    vectors the kernel takes by scalar prefetch."""

    group: jnp.ndarray   # (W,) the group of visit w
    tile: jnp.ndarray    # (W,) its row tile
    starts: jnp.ndarray  # (n,) first row of each group
    ends: jnp.ndarray    # (n,) one past its last
    count: jnp.ndarray   # (1,) visits that are real; the rest repeat


def group_visits(sizes: jnp.ndarray, m: int, row_tile: int) -> GroupVisits:
    """The (group, row tile) pairs to visit for ``m`` sorted rows, in
    order. ``W = ceil(m / row_tile) + n - 1`` bounds their number: each
    tile once, and once more for every group that starts inside one."""
    n = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    first = starts // row_tile
    tiles = jnp.where(sizes > 0, (ends - 1) // row_tile - first + 1, 0)
    upto = jnp.cumsum(tiles, dtype=jnp.int32)
    count = upto[-1]
    w = jnp.minimum(jnp.arange(-(-m // row_tile) + n - 1, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    group = jnp.minimum(jnp.searchsorted(upto, w, side="right"), n - 1
                        ).astype(jnp.int32)
    tile = first[group] + w - (upto[group] - tiles[group])
    return GroupVisits(group, tile.astype(jnp.int32), starts, ends,
                       count.reshape(1))


def _kernel(group_ref, tile_ref, starts_ref, ends_ref, count_ref, x_ref,
            *rest, row_tile: int, relu2: bool):
    from jax.experimental import pallas as pl

    w_refs, o_ref = rest[:-1], rest[-1]
    v = pl.program_id(1)

    @pl.when(v < count_ref[0])
    def _visit():
        x = x_ref[...]
        acc = jnp.dot(x, w_refs[0][...], preferred_element_type=jnp.float32)
        if len(w_refs) == 2:
            acc = jax.nn.silu(acc) * jnp.dot(
                x, w_refs[1][...], preferred_element_type=jnp.float32)
        elif relu2:
            acc = jnp.square(jnp.maximum(acc, 0.0))
        g = group_ref[v]
        row = tile_ref[v] * row_tile + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (row >= starts_ref[g]) & (row < ends_ref[g])
        # the tile's other rows are other groups' (stored by their own
        # visits, before or after this one) or nobody's
        o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def column_tile(k: int, f: int, itemsize: int) -> int:
    """Columns of a weight tile: the largest multiple of 128 that
    divides ``f`` with ``k x columns`` within WEIGHT_TILE_BYTES — 896 of
    2688 = 21 x 128 at ``k`` 1024, where a rule that only doubles would
    stop at 128 (all of ``f`` where it is no multiple of 128: a block
    may span a whole dim whatever its size)."""
    if f % 128:
        return f
    fits = [cols for cols in range(128, f + 1, 128)
            if f % cols == 0 and k * cols * itemsize <= WEIGHT_TILE_BYTES]
    return max(fits, default=128)


def grouped_matmul(xs: jnp.ndarray, ws: Sequence[jnp.ndarray],
                   visits: GroupVisits, row_tile: int,
                   interpret: Optional[bool] = None,
                   relu2: bool = False) -> jnp.ndarray:
    """``xs[rows of group e] @ ws[0][e]`` for every group, (m, f) in
    ``xs.dtype`` from an f32 accumulator; with two kernels in ``ws``,
    ``silu(xs @ ws[0][e]) * (xs @ ws[1][e])``; with ``relu2`` (one
    kernel), ``relu(xs @ ws[0][e])^2``. ``visits`` is
    :func:`group_visits` of the groups' sizes at this ``row_tile`` (one
    list serves every product over the same groups). Rows in no group
    come back as whatever the buffer held: the caller does not read them.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = xs.shape
    n, k_w, f = ws[0].shape
    if k_w != k or any(w.shape != ws[0].shape for w in ws) \
            or not 1 <= len(ws) <= 2 - relu2:
        raise ValueError(f"rows are {k} wide, the kernels "
                         f"{[w.shape for w in ws]}")
    col_tile = column_tile(k, f, ws[0].dtype.itemsize)
    xs = _pad_to(xs, 0, row_tile)  # the ROWS; a stacked kernel never is

    def x_map(j, v, group, tile, *_):
        return (tile[v], 0)

    def w_map(j, v, group, tile, *_):
        return (group[v], 0, j)

    def o_map(j, v, group, tile, *_):
        return (tile[v], j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(f // col_tile, visits.group.shape[0]),
        in_specs=[pl.BlockSpec((row_tile, k), x_map)] + [
            pl.BlockSpec((None, k, col_tile), w_map) for _ in ws],
        out_specs=pl.BlockSpec((row_tile, col_tile), o_map),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, row_tile=row_tile, relu2=relu2),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((xs.shape[0], f), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=_resolve_interpret(interpret),
        name="moe_grouped_matmul",  # what a profile calls the kernel
    )(*visits, xs, *ws)
    return out[:m]
