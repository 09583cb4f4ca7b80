"""Rodinia Pathfinder (grid DP) as a Pallas TPU kernel.

dst[j] = wall[r, j] + min(prev[j-1], prev[j], prev[j+1]), rows carried
sequentially.  The row recurrence cannot be parallelised, which is exactly the
paper's low-occupancy situation: the win comes from prefetching the *next* row
tile while the current one is folded into the DP state (the paper found this
benchmark amenable only to the Drop-Off pattern, 1.04-1.11x).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.async_pipeline import (PipelineSpec, Strategy, TileStream,
                                   as_spec, compiler_params, emit,
                                   lane_pad, scratch_for)


def _min3(prev, cols: int):
    # prev: (1, width); neighbours clamp at the real edges (col 0 and
    # cols-1); lanes past cols are padding and never feed a real column
    width = prev.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, prev.shape, 1)
    left = jnp.where(col == 0, prev, pltpu.roll(prev, 1, 1))
    right = jnp.where(col == cols - 1, prev, pltpu.roll(prev, width - 1, 1))
    return jnp.minimum(prev, jnp.minimum(left, right))


def _pathfinder_kernel(first_hbm, wall_hbm, o_hbm, state, row_buf, stage,
                       sems, out_sem, *, spec: PipelineSpec, n_tiles: int,
                       tile_rows: int, n_rows: int, cols: int):
    # row 0 initialises the DP state (its own (1, width) array, so the
    # copy is a whole-array DMA)
    init = pltpu.make_async_copy(first_hbm, state, out_sem)
    init.start()
    init.wait()

    stream = TileStream(
        hbm=wall_hbm, vmem=row_buf, sem=sems,
        index=lambda i: (pl.ds(i * tile_rows, tile_rows), slice(None)),
        depth=spec.ring_depth)

    def fold(i, tile):
        for r in range(tile_rows):          # static unroll; carried dependency
            prev = state[...]
            new = tile[r:r + 1, :] + _min3(prev, cols)
            if n_rows % tile_rows:          # the last tile has pad rows
                new = jnp.where(i * tile_rows + r < n_rows, new, prev)
            state[...] = new

    if spec.strategy == Strategy.DROP_OFF:
        emit(spec, [stream], n_tiles, lambda i, vals: fold(i, vals[0]))
    else:
        def compute(i, bufs):
            fold(i, bufs[0][...])
        emit(spec, [stream], n_tiles, compute, staging=[stage])

    out = pltpu.make_async_copy(state, o_hbm, out_sem)
    out.start()
    out.wait()


def pathfinder_tiles(rows: int, tile_rows: int) -> int:
    """Row tiles streamed for a ``rows``-row grid: row 0 seeds the DP state,
    the other rows are padded up to a whole tile."""
    return -(-(rows - 1) // tile_rows)


def pathfinder_scratch(spec: PipelineSpec, tile_rows: int, cols: int,
                       dtype) -> list:
    """The kernel's scratch: the DP state row, the row-tile ring, SYNC
    staging and the DMA semaphores, all at the lane-padded width."""
    width = lane_pad(cols)
    row_buf, sems, stage = scratch_for(spec, (tile_rows, width), dtype)
    return [pltpu.VMEM((1, width), dtype),      # DP state
            row_buf, stage, sems, pltpu.SemaphoreType.DMA]


def pathfinder_flops_bytes(shape, dtype_bytes: int,
                           tile_rows: int) -> Tuple[float, float]:
    """Analytic flops/bytes: ~3 ops per cell; HBM traffic is row 0, the
    padded row tiles and the result row, all at the lane-padded width."""
    rows, cols = shape
    n_rows = 1 + pathfinder_tiles(rows, tile_rows) * tile_rows + 1
    return 3.0 * rows * cols, float(n_rows * lane_pad(cols) * dtype_bytes)


def pathfinder_pallas(wall: jax.Array, *,
                      spec: PipelineSpec = PipelineSpec(Strategy.DROP_OFF),
                      tile_rows: int = 8,
                      interpret: bool = False) -> jax.Array:
    """wall: (rows, cols), any rows >= 2 and cols.  Returns the (1, cols)
    final DP row.  Columns are padded to a lane multiple and the rows after
    the first to a tile multiple; pad rows leave the DP state unchanged."""
    spec = as_spec(spec)
    rows, cols = wall.shape
    if rows < 2:
        raise ValueError(f"pathfinder needs at least 2 rows, got {rows}")
    n_rows = rows - 1
    n_tiles = pathfinder_tiles(rows, tile_rows)
    width = lane_pad(cols)
    first = jnp.pad(wall[:1], ((0, 0), (0, width - cols)))
    body = jnp.pad(wall[1:], ((0, n_tiles * tile_rows - n_rows),
                              (0, width - cols)))
    scratch = pathfinder_scratch(spec, tile_rows, cols, wall.dtype)
    kernel = functools.partial(
        _pathfinder_kernel, spec=spec, n_tiles=n_tiles, tile_rows=tile_rows,
        n_rows=n_rows, cols=cols)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, width), wall.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=compiler_params(dimension_semantics=(),
                                        scratch_shapes=scratch),
    )(first, body)
    return out[:, :cols]
