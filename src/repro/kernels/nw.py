"""Needleman-Wunsch (Rodinia NW) as a Pallas TPU kernel.

Rodinia processes the DP table in 16x16 blocks along anti-diagonals (a GPU
shared-memory shape).  On TPU we instead *vectorise the row recurrence*:

    m[i,j] = max(m[i-1,j-1] + s[i-1,j-1],  m[i,j-1] - p,  m[i-1,j] - p)

Splitting off c[j] = max(m[i-1,j-1] + s[..], m[i-1,j] - p) leaves
m[i,j] = max(c[j], m[i,j-1] - p) = max_{k<=j} (c[k] - (j-k) p), a max-plus
prefix scan: with t = c + j*p, m = cummax(t) - j*p.  The cummax runs as a
log2(n) Hillis-Steele ladder of vector ops — a full row per step on the VPU
instead of a 16-wide anti-diagonal.  This is the "rethink the algorithm for
the memory hierarchy" adaptation: rows stream HBM -> VMEM under the paper's
async strategies (NW favoured Register Bypass on A100, 1.01-1.08x) and the
DP state lives in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.async_pipeline import (PipelineSpec, Strategy, TileStream,
                                   WriteBack, as_spec, compiler_params, emit,
                                   lane_pad, scratch_for, writeback_scratch)

NEG = -1e30


def _shift_right(x, shift: int, lane):
    """x shifted ``shift`` lanes to the right along the last axis, NEG
    shifted in.  A lane rotation plus a mask: no unaligned lane slices."""
    return jnp.where(lane >= shift, pltpu.roll(x, shift, x.ndim - 1), NEG)


def _cummax(x, lane):
    """Hillis-Steele inclusive max-scan along the last axis (static width)."""
    n = x.shape[-1]
    shift = 1
    while shift < n:
        x = jnp.maximum(x, _shift_right(x, shift, lane))
        shift *= 2
    return x


def _nw_kernel(scores_hbm, o_hbm, state, row_buf, stage, sems, out_buf,
               out_sems, init_sem,
               *, spec: PipelineSpec, n_tiles: int, tile_rows: int, n: int,
               width: int, penalty: float):
    # state = DP row of length n+1 (padded to `width`); row 0 is -j*p
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    j = lane.astype(jnp.float32)
    valid = lane <= n
    state[...] = jnp.where(valid, -penalty * j, NEG)

    stream = TileStream(
        hbm=scores_hbm, vmem=row_buf, sem=sems,
        index=lambda i: (pl.ds(i * tile_rows, tile_rows), slice(None)),
        depth=spec.ring_depth)
    wb = WriteBack(
        hbm=o_hbm, vmem=out_buf, sem=out_sems,
        index=lambda i: (pl.ds(i * tile_rows, tile_rows), slice(None)),
        depth=spec.out_depth)

    def fold(i, tile):
        # tile: (tile_rows, width) score rows s[i-1, j-1] pre-aligned to j
        rows = []
        for r in range(tile_rows):                  # carried row recurrence
            row_idx = (i * tile_rows + r + 1)
            prev = state[...]
            prev_shift = _shift_right(prev, 1, lane)
            c = jnp.maximum(prev_shift + tile[r:r + 1, :], prev - penalty)
            c = jnp.where(lane == 0, -penalty * row_idx, c)
            t = jnp.where(valid, c + penalty * j, NEG)
            new = jnp.where(valid, _cummax(t, lane) - penalty * j, NEG)
            state[...] = new
            rows.append(new)
        wb.push(i, jnp.concatenate(rows, axis=0))

    if spec.strategy == Strategy.DROP_OFF:
        emit(spec, [stream], n_tiles, lambda i, vals: fold(i, vals[0]))
    else:
        def compute(i, bufs):
            fold(i, bufs[0][...])
        emit(spec, [stream], n_tiles, compute, staging=[stage])

    wb.drain(n_tiles)


def nw_pallas(seq_scores: jax.Array, penalty: int, *,
              spec: PipelineSpec = PipelineSpec(Strategy.REGISTER_BYPASS),
              tile_rows: int = 8,
              interpret: bool = False) -> jax.Array:
    """seq_scores: (n, n) similarity matrix.  Returns the (n+1, n+1) DP table
    (float32), matching ref.nw_ref."""
    spec = as_spec(spec)
    n = seq_scores.shape[0]
    if n % tile_rows:
        raise ValueError(f"n={n} must divide tile_rows={tile_rows}")
    width = lane_pad(n + 1)
    # align scores so that column j of the padded row holds s[i-1, j-1]
    scores = jnp.pad(seq_scores.astype(jnp.float32),
                     ((0, 0), (1, width - n - 1)))
    n_tiles = n // tile_rows
    row_buf, sems, stage = scratch_for(spec, (tile_rows, width), jnp.float32)
    out_buf, out_sems = writeback_scratch(spec, (tile_rows, width),
                                          jnp.float32)
    scratch = [pltpu.VMEM((1, width), jnp.float32),     # DP row state
               row_buf, stage, sems, out_buf, out_sems,
               pltpu.SemaphoreType.DMA]
    kernel = functools.partial(
        _nw_kernel, spec=spec, n_tiles=n_tiles, tile_rows=tile_rows,
        n=n, width=width, penalty=float(penalty))
    table = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, width), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=compiler_params(dimension_semantics=(),
                                        scratch_shapes=scratch),
    )(scores)
    top = -penalty * jnp.arange(n + 1, dtype=jnp.float32)[None, :]
    return jnp.concatenate([top, table[:, :n + 1]], axis=0)
