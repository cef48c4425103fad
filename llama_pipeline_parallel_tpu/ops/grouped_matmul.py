"""Pallas TPU grouped matmul: sorted rows times a stack of experts, each run
of rows by its own expert (`models/hybrid_moe/model.py` `moe_block`).

    out[offsets[g] : offsets[g + 1]] = lhs[offsets[g] : offsets[g + 1]] @ rhs[g]

What `jax.lax.ragged_dot` computes, which XLA:TPU runs at a third of the
weights' HBM time when a group has a row or two (34% of its roofline in the
hybrid serving tick on the v5e, PERF.md PR 26 to PR 42). At that load the
product is the experts' bytes and nothing else, so this kernel is built
around reading them: the grid walks the VISITS, the (row tile, group) pairs
in which the group has a row in the tile, and a visit streams that one
expert's matrix through VMEM in whole-width blocks. A group without a row,
and a row tile past the last group's rows, is never visited: the grid's
bound is the number of visits, a traced scalar, and the block index maps
read the visit's group and row tile from scalar-prefetched metadata
(`group_metadata`, built once a layer and shared by the three products).

Schedule: grid (visit, contraction step). A step multiplies the row tile
`[tm, tk]` by the expert's `[tk, n]` block on the MXU into a float32
accumulator `[tm, n]` in VMEM scratch; the last step stores, rounded once,
the rows of the tile that belong to the visit's group and leaves the others
as they are (the output block stays in VMEM while consecutive visits share
the row tile: a tile's groups fill it one after the other). Rows that
belong to no group are never written: they hold whatever the buffer held,
and the caller discards them.

Tiles, from the shapes alone (`row_tile`, `contraction_tile`): the row tile
is 128 rows, one pass of the MXU's weights, so a tick's rows (a few an
expert) lie in one tile and each expert with a row is read exactly once; a
group that straddles two tiles is read twice. The output is never tiled:
a `[tk, n]` block of a row-major `[k, n]` matrix is one contiguous run of
HBM. `tk` is the largest multiple of 128 dividing `k` whose block stays
under `_BLOCK_BYTES`; a width with no such divisor (the tiny test widths)
is one block.

Numerics: operands as stored, float32 accumulation, one rounding at the
store: `ragged_dot`'s arithmetic with `preferred_element_type` float32 and
one cast, in another order of summation.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llama_pipeline_parallel_tpu.ops.pallas_common import (
    compiler_params,
    interpret_mode,
)
from llama_pipeline_parallel_tpu.utils import trace

# visits share output blocks and the contraction carries the accumulator
_COMPILER_PARAMS = compiler_params("arbitrary", "arbitrary")

# preferred row tiles, largest first; the fallback is every row (one tile)
ROW_TILES = (128, 64, 32, 16)

# weights one grid step brings to VMEM (double-buffered by the pipeline)
_BLOCK_BYTES = 4 << 20


class GroupMetadata(NamedTuple):
    """What the grid needs of the group sizes (all int32). `visits` may be
    traced; the two per-visit arrays are sized for the most visits any sizes
    can make, row tiles + groups - 1, and only their first `visits` entries
    are read."""
    offsets: jnp.ndarray     # [groups + 1] first row of each group; [-1]: rows in groups
    group_of: jnp.ndarray    # [row tiles + groups - 1] the visit's group
    tile_of: jnp.ndarray     # [row tiles + groups - 1] the visit's row tile
    visits: jnp.ndarray      # [] (row tile, group) pairs that share a row


def row_tile(m: int) -> int:
    """Rows a visit multiplies: the largest of `ROW_TILES` dividing m, else
    m itself."""
    return next((t for t in ROW_TILES if m % t == 0), m)


def contraction_tile(k: int, n: int, itemsize: int) -> int:
    """Contraction rows of one weight block `[tk, n]`: the largest multiple
    of 128 that divides k and keeps the block under `_BLOCK_BYTES` (at least
    128), else k itself."""
    divisors = [t for t in range(128, k + 1, 128) if k % t == 0]
    if not divisors:
        return k
    return max((t for t in divisors if t * n * itemsize <= _BLOCK_BYTES),
               default=128)


def group_metadata(group_sizes: jnp.ndarray, m: int) -> GroupMetadata:
    """The visits of `group_sizes` (int32[groups], summing to at most m) over
    the row tiles of m sorted rows, in the order the grid walks them: by
    group, and inside a group by row tile, so a row tile's visits are
    consecutive."""
    tm = row_tile(m)
    groups, row_tiles = group_sizes.shape[0], pl.cdiv(m, tm)
    ends = jnp.cumsum(group_sizes, dtype=jnp.int32)
    starts = ends - group_sizes
    first = starts // tm                                   # a group's first tile
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles, dtype=jnp.int32)
    visit = jnp.arange(row_tiles + groups - 1, dtype=jnp.int32)
    # the group whose run of visits holds this one (compared against every
    # group: a handful of integers, one fusion, no loop); the entries past
    # `visits` are never read and only kept inside the operands
    group_of = jnp.minimum(
        jnp.sum(visit[:, None] >= visit_ends[None, :], axis=1,
                dtype=jnp.int32), groups - 1)
    tile_of = first[group_of] + visit - (visit_ends - tiles)[group_of]
    return GroupMetadata(
        offsets=jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]),
        group_of=group_of,
        tile_of=jnp.clip(tile_of, 0, row_tiles - 1),
        visits=visit_ends[-1])


def _kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
            acc_ref, *, tm: int):
    v = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        # the tile's other rows: another visit's, or nobody's
        out_ref[...] = jnp.where(mine, acc_ref[...].astype(out_ref.dtype),
                                 out_ref[...])


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   meta: GroupMetadata) -> jnp.ndarray:
    """lhs: [m, k] rows sorted by group; rhs: [groups, k, n], the stack as it
    is stored (never sliced: a group is a block index); meta:
    `group_metadata(group_sizes, m)`. Returns [m, n] in lhs's dtype:
    `jax.lax.ragged_dot(lhs, rhs, group_sizes)` on the rows that belong to a
    group; the rows past the last group are NOT written and hold anything."""
    m, k = lhs.shape
    groups, _, n = rhs.shape
    if rhs.shape[1] != k or meta.offsets.shape != (groups + 1,):
        raise ValueError(
            f"lhs {lhs.shape}, rhs {rhs.shape} and metadata of "
            f"{meta.offsets.shape[0] - 1} groups do not belong together")
    tm = row_tile(m)
    tk = contraction_tile(k, n, rhs.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(meta.visits, k // tk),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda v, j, o, g, t: (t[v], j)),
                pl.BlockSpec((None, tk, n), lambda v, j, o, g, t: (g[v], j, 0)),
            ],
            out_specs=pl.BlockSpec((tm, n), lambda v, j, o, g, t: (t[v], 0)),
            scratch_shapes=[pltpu.VMEM((tm, n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret_mode(),
        name=trace.KERNEL_GROUPED_MATMUL,
    )(meta.offsets, meta.group_of, meta.tile_of, lhs, rhs)
