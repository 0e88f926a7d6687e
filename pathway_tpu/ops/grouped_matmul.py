"""Pallas TPU kernel: the grouped matrix product of a prefill's routed experts.

A prefill program of a routed layer sorts its (token, expert) pairs by
expert and multiplies each run of rows by its own expert's matrix
(``parallel/moe.py::moe_serve``).  The chip's compiler lowers
``jax.lax.ragged_dot`` for that, and at the widths of a layer's share of
experts (15 and 21 lanes wide, thousands of pairs over 64-128 experts) its
cost does not follow the bytes it must read: 7.5 ms for a product whose
experts take 0.8 ms to read on a v5e.

This kernel walks the sorted rows in tiles of ``tm`` and gives each tile
of a group one grid step: a tile that two groups share is visited by both,
one after the other, and each keeps the rows that are its own.  The grid
is ``(N tiles, row tiles, K tiles)``, the row tiles innermost but for K, so
for one column tile the steps of a group follow each other and its weight
block ``[tk, tn]`` is fetched once for all of them: each held expert is
read about once.  Empty groups take no step (the grid's middle size is the
number of tiles the groups touch, known on the device), so an expert no
pair met is never read and neither is a layer's share of a stack in which
only that layer's groups are filled.  Products accumulate in float32 in
VMEM and are stored in the rows' dtype.

Rows past the last group are never visited: whatever the output holds
there is not written by the kernel, and the caller masks it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the rows a grid step multiplies: one pass of the MXU's 128 rows
TILE_ROWS = 128
# the widest column tile: a [4096, 1024] bfloat16 weight block, double
# buffered, is the budget below
_MAX_TILE_COLS = 1024
# VMEM for the weight block's two buffers, and the kernel's whole scope
# (the rows' and the output's blocks and the accumulator fit in the rest)
_WEIGHT_VMEM = 16 * 2**20
_VMEM_LIMIT = 32 * 2**20


def _lane_divisor(n: int, most: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most
    ``most``; ``n`` itself where ``n`` is no multiple of 128 (a block as
    wide as the array)."""
    if n % 128:
        return n
    return max(t for t in range(128, min(n, most) + 1, 128) if n % t == 0)


def tiling(k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` for rows ``[M, k]`` times groups' ``[k, n]``, a
    rule of the widths alone: ``tm`` is 128 rows; ``tn`` the widest
    multiple of 128 lanes that divides ``n`` up to 1,024 (1,920 -> 640,
    2,688 -> 896, 1,024 -> 1,024, 4,096 -> 1,024); ``tk`` the whole of
    ``k`` where the weight block ``[k, tn]``, double buffered, fits 16 MiB
    of VMEM, else the widest multiple of 128 dividing ``k`` that does."""
    tn = _lane_divisor(n, _MAX_TILE_COLS)
    fits = _WEIGHT_VMEM // (2 * tn * itemsize)
    tk = k if k <= fits else _lane_divisor(k, max(128, fits))
    return TILE_ROWS, tk, tn


def _group_tiles(group_sizes, tm: int):
    """``(offsets [G + 1]`` of the groups' rows, the row tile each group's
    first row falls in, the row tiles each touches: nought where empty)."""
    ends = jnp.cumsum(group_sizes, dtype=jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = offsets[:-1] // tm
    touched = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first, 0)
    return offsets, first, touched


def row_tiles(group_sizes, tm: int = TILE_ROWS):
    """The row tiles the kernel multiplies for ``group_sizes`` (int32):
    the sum over non-empty groups of the tiles each touches."""
    return jnp.sum(_group_tiles(group_sizes, tm)[2], dtype=jnp.int32)


def tile_plan(group_sizes, tiles: int, tm: int = TILE_ROWS):
    """The grid's walk over ``tiles`` row tiles of rows sorted by group
    (``group_sizes [G]`` int32, summing to at most ``tiles * tm``):
    ``(offsets [G + 1], the group of each step, the row tile of each
    step, steps)``.  A non-empty group takes a step for each tile it
    touches; an empty one takes none.  The step arrays are sized for the
    most steps there can be (``tiles + G - 1``: each group boundary inside
    a tile adds one); ``steps`` of them are walked."""
    G = group_sizes.shape[0]
    offsets, first, touched = _group_tiles(group_sizes, tm)
    most = tiles + G - 1
    group_of = jnp.repeat(
        jnp.arange(G, dtype=jnp.int32), touched, total_repeat_length=most
    )
    before = jnp.cumsum(touched, dtype=jnp.int32) - touched
    tile_of = first[group_of] + jnp.arange(most, dtype=jnp.int32) - before[group_of]
    return offsets, group_of, tile_of, jnp.sum(touched, dtype=jnp.int32)


def _kernel(offsets, group_of, tile_of, x_ref, w_ref, out_ref, acc_ref, *, tm, tiles_k):
    step, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == tiles_k - 1)
    def _():
        # the tile's rows of this step's group take its product; the rest
        # keep what an earlier group of the tile stored
        g = group_of[step]
        rows = tile_of[step] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0
        )
        mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
        out_ref[...] = jnp.where(
            mine, acc_ref[...], out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(x, w, group_sizes, *, interpret: bool = False):
    """``x [M, K]`` rows sorted by group times each group's ``w [G, K,
    N]``: row ``r`` of group ``g`` (the ``group_sizes [G]`` int32 rows
    after those of the groups before it) is ``x[r] @ w[g]``, accumulated
    in float32 and returned ``[M, N]`` in ``x``'s dtype, as
    ``jax.lax.ragged_dot`` returns it.  Rows past the groups are not
    written.  Tiles by :func:`tiling`."""
    M, K = x.shape
    N = w.shape[2]
    tm, tk, tn = tiling(K, N, x.dtype.itemsize)
    tiles = -(-M // tm)
    if tiles * tm != M:
        # rows past the groups, never visited
        x = jnp.pad(x, ((0, tiles * tm - M), (0, 0)))
    offsets, group_of, tile_of, steps = tile_plan(group_sizes, tiles, tm)
    tiles_k = K // tk
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((tiles * tm, N), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, steps, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n, s, k, o, g, t: (t[s], k)),
                pl.BlockSpec((None, tk, tn), lambda n, s, k, o, g, t: (g[s], k, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n, s, k, o, g, t: (t[s], n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="grouped_matmul",
    )(offsets, group_of, tile_of, x, w)
    return out[:M]
