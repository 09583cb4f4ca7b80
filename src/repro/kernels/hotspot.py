"""Rodinia Hotspot (2D thermal 5-point stencil) as a Pallas TPU kernel.

One kernel call performs one simulation step over an (R, C) grid.  The host
wrapper replicate-pads the temperature field by one (8, 128)-aligned halo
block of rows above and below, and pads columns to a lane multiple; the
kernel streams row bands of ``tile_rows + 16`` rows HBM -> VMEM under the
selected async-copy strategy (the paper finds Overlap the winning pattern
here, 1.12-1.23x on A100) and drains results through an N-deep write-back
ring.  Every slice is tile-aligned: the four neighbours are formed from the
loaded band with ``pltpu.roll``, and the left/right edges clamp by masking.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.async_pipeline import (PipelineSpec, Strategy, TileStream,
                                   WriteBack, as_spec, compiler_params, emit,
                                   lane_pad, scratch_for, writeback_scratch)

#: rows of halo above and below each band: one sublane tile, so every row
#: offset the kernel slices at stays a multiple of 8
HALO = 8


def _hotspot_kernel(tpad_hbm, power_hbm, o_hbm, t_buf, p_buf, out_buf,
                    t_stage, p_stage, t_sems, p_sems, out_sems,
                    *, spec: PipelineSpec, n_tiles: int, tile_rows: int,
                    cols: int, rx: float, ry: float, rz: float, cap: float):
    pid = pl.program_id(0)
    base = pid * n_tiles * tile_rows
    band = tile_rows + 2 * HALO

    # the band for tile i starts HALO rows above the tile in the padded
    # field, i.e. at row base + i*tile_rows of tpad: always 8-aligned
    t_stream = TileStream(
        hbm=tpad_hbm, vmem=t_buf, sem=t_sems,
        index=lambda i: (pl.ds(base + i * tile_rows, band), slice(None)),
        depth=spec.ring_depth)
    p_stream = TileStream(
        hbm=power_hbm, vmem=p_buf, sem=p_sems,
        index=lambda i: (pl.ds(base + i * tile_rows, tile_rows), slice(None)),
        depth=spec.ring_depth)
    wb = WriteBack(
        hbm=o_hbm, vmem=out_buf, sem=out_sems,
        index=lambda i: (pl.ds(base + i * tile_rows, tile_rows), slice(None)),
        depth=spec.out_depth)

    def stencil(tband, power):
        # tband: (tile_rows + 2*HALO, width) halo band; power: (tile_rows,
        # width).  Rolls keep every slice at an aligned offset; the rows
        # they wrap in are the halo rows that get sliced away.
        width = tband.shape[1]
        rows = slice(HALO, HALO + tile_rows)
        t = tband[rows]
        up = pltpu.roll(tband, 1, 0)[rows]
        down = pltpu.roll(tband, band - 1, 0)[rows]
        col = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
        left = jnp.where(col == 0, t, pltpu.roll(t, 1, 1))
        right = jnp.where(col == cols - 1, t, pltpu.roll(t, width - 1, 1))
        delta = cap * (power + (up + down - 2.0 * t) * ry
                       + (left + right - 2.0 * t) * rx
                       + (80.0 - t) * rz)
        return t + delta

    if spec.strategy == Strategy.DROP_OFF:
        def compute_value(i, vals):
            wb.push(i, stencil(vals[0], vals[1]))
        emit(spec, [t_stream, p_stream], n_tiles, compute_value)
    else:
        def compute(i, bufs):
            wb.push(i, stencil(bufs[0][...], bufs[1][...]))
        emit(spec, [t_stream, p_stream], n_tiles, compute,
             staging=[t_stage, p_stage])

    wb.drain(n_tiles)


def hotspot_scratch(spec: PipelineSpec, tile_rows: int, cols: int,
                    dtype) -> list:
    """The kernel's scratch at the lane-padded width: the halo-band and
    power rings, the write-back ring, SYNC staging and DMA semaphores."""
    width = lane_pad(cols)
    t_buf, t_sems, t_stage = scratch_for(
        spec, (tile_rows + 2 * HALO, width), dtype)
    p_buf, p_sems, p_stage = scratch_for(spec, (tile_rows, width), dtype)
    out_buf, out_sems = writeback_scratch(spec, (tile_rows, width), dtype)
    return [t_buf, p_buf, out_buf, t_stage, p_stage,
            t_sems, p_sems, out_sems]


def hotspot_flops_bytes(shape, dtype_bytes: int,
                        tile_rows: int) -> Tuple[float, float]:
    """Analytic flops/bytes of one step: ~10 flops per cell; each tile
    reads a band of ``tile_rows + 2*HALO`` temperature rows and
    ``tile_rows`` power rows and writes ``tile_rows`` rows, all at the
    lane-padded width."""
    rows, cols = shape
    rows_moved = rows * (2.0 + (tile_rows + 2 * HALO) / tile_rows)
    return 10.0 * rows * cols, rows_moved * lane_pad(cols) * dtype_bytes


def hotspot_step_pallas(temp: jax.Array, power: jax.Array, *,
                        spec: PipelineSpec = PipelineSpec(),
                        tile_rows: int = 8,
                        rx: float = 0.1, ry: float = 0.1, rz: float = 0.5,
                        cap: float = 0.5, grid: int = 1,
                        interpret: bool = False) -> jax.Array:
    """One hotspot iteration.  temp/power: (R, C); R divisible by
    grid*tile_rows, tile_rows a multiple of 8; any C."""
    spec = as_spec(spec)
    rows, cols = temp.shape
    block = rows // grid
    if rows % (grid * tile_rows):
        raise ValueError(f"rows={rows} not divisible by grid*tile_rows")
    if tile_rows % HALO:
        raise ValueError(f"tile_rows={tile_rows} must be a multiple of {HALO}")
    n_tiles = block // tile_rows
    width = lane_pad(cols)
    # edge padding: the halo rows replicate the first/last row, which is
    # the clamped boundary; the pad columns are masked in the kernel
    tpad = jnp.pad(temp, ((HALO, HALO), (0, width - cols)), mode="edge")
    ppad = jnp.pad(power.astype(temp.dtype), ((0, 0), (0, width - cols)))

    scratch = hotspot_scratch(spec, tile_rows, cols, temp.dtype)
    kernel = functools.partial(
        _hotspot_kernel, spec=spec, n_tiles=n_tiles,
        tile_rows=tile_rows, cols=cols, rx=rx, ry=ry, rz=rz, cap=cap)
    out = pl.pallas_call(
        kernel,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct((rows, width), temp.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=compiler_params(
            dimension_semantics=("arbitrary",), scratch_shapes=scratch),
    )(tpad, ppad)
    return out[:, :cols]


def hotspot_pallas(temp: jax.Array, power: jax.Array, *, iters: int,
                   **kw) -> jax.Array:
    for _ in range(iters):
        temp = hotspot_step_pallas(temp, power, **kw)
    return temp
