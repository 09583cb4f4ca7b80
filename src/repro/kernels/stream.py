"""Paper §4.1 microbenchmark kernel (Pallas TPU).

Element-wise application of f(x) = 0.5*x + 0.5 for a configurable number of
iterations (= configurable arithmetic intensity), streaming tiles
HBM -> VMEM under one of the four asynchronous-copy strategies and streaming
results VMEM -> HBM through an N-deep write-back ring.

Grid: one program per row-block; each program streams ``n_tiles`` tiles of
``tile_rows`` x ``width`` elements from its slice of the input.  The
pipeline shape (ring depth, wait-group, out-ring depth) comes from a
``PipelineSpec``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.async_pipeline import (PipelineSpec, Strategy, TileStream,
                                   WriteBack, as_spec, compiler_params, emit,
                                   scratch_for, writeback_scratch)


def _apply_f(val, iters: int):
    if iters <= 0:
        return val
    return jax.lax.fori_loop(
        0, iters, lambda _, v: v * 0.5 + 0.5, val, unroll=min(iters, 8))


def _stream_kernel(x_hbm, o_hbm, in_buf, out_buf, stage_buf, in_sems, out_sems,
                   *, spec: PipelineSpec, n_tiles: int, tile_rows: int,
                   iters: int):
    pid = pl.program_id(0)
    base = pid * n_tiles * tile_rows

    stream = TileStream(
        hbm=x_hbm, vmem=in_buf, sem=in_sems,
        index=lambda i: (pl.ds(base + i * tile_rows, tile_rows), slice(None)),
        depth=spec.ring_depth)

    wb = WriteBack(
        hbm=o_hbm, vmem=out_buf, sem=out_sems,
        index=lambda i: (pl.ds(base + i * tile_rows, tile_rows), slice(None)),
        depth=spec.out_depth)

    if spec.strategy == Strategy.DROP_OFF:
        def compute_value(i, vals):
            wb.push(i, _apply_f(vals[0], iters))
        emit(spec, [stream], n_tiles, compute_value)
    else:
        def compute(i, bufs):
            wb.push(i, _apply_f(bufs[0][...], iters))
        emit(spec, [stream], n_tiles, compute, staging=[stage_buf])

    wb.drain(n_tiles)


def stream_pallas(x: jax.Array, *, iters: int = 1,
                  spec: PipelineSpec = PipelineSpec(),
                  tile_rows: int = 8, n_tiles: int = 4,
                  interpret: bool = False) -> jax.Array:
    """Run the microbenchmark kernel.  x: (rows, width); rows must equal
    g * n_tiles * tile_rows for an integer grid g."""
    spec = as_spec(spec)
    rows, width = x.shape
    block = n_tiles * tile_rows
    if rows % block:
        raise ValueError(f"rows={rows} not divisible by n_tiles*tile_rows={block}")
    grid = rows // block
    in_buf, in_sems, stage = scratch_for(spec, (tile_rows, width), x.dtype)
    out_buf, out_sems = writeback_scratch(spec, (tile_rows, width), x.dtype)
    kernel = functools.partial(
        _stream_kernel, spec=spec, n_tiles=n_tiles,
        tile_rows=tile_rows, iters=iters)
    scratch = [in_buf, out_buf, stage, in_sems, out_sems]
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=compiler_params(
            dimension_semantics=("arbitrary",), scratch_shapes=scratch),
    )(x)


def stream_flops_bytes(x_shape: Tuple[int, int], iters: int,
                       dtype_bytes: int = 4) -> Tuple[float, float]:
    """Analytic flops/bytes for the roofline positioning (paper Fig. 3a):
    2 flops per element per iteration; one read + one write per element."""
    n = float(x_shape[0] * x_shape[1])
    return 2.0 * n * iters, 2.0 * n * dtype_bytes
