"""Blocked LU decomposition (Rodinia LUD) as Pallas TPU kernels.

Keeps Rodinia's three-kernel structure per diagonal step k:
  lud_diagonal   factor the (bs,bs) pivot block (Doolittle, no pivoting)
  lud_perimeter  triangular solves for the block row (L^-1 A) and block
                 column (A U^-1)
  lud_internal   trailing update C -= L @ U  — the matmul hot spot where the
                 paper's async streaming pays (A100: 1.25-1.32x, pattern
                 flips from Register-Bypass to Overlap with input size)

The internal kernel streams (U tile, C tile) pairs HBM -> VMEM under the
selected strategy while the previous pair is in the MXU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.async_pipeline import (LANES, PipelineSpec, Strategy,
                                   TileStream, WriteBack, as_spec,
                                   compiler_params, emit, lane_pad,
                                   scratch_for, writeback_scratch)


_HIGHEST = jax.lax.Precision.HIGHEST


# --- diagonal block factorization ---------------------------------------------
# In-kernel updates are masked ``jnp.where`` selects: a scatter
# (``.at[...].set``) has no Mosaic lowering.

def _diag_kernel(a_ref, o_ref, *, bs: int):
    blk = a_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
    for k in range(bs):
        pivot = blk[k, k]
        colk = blk[:, k:k + 1]
        colk = jnp.where(rows[:, :1] > k, colk / pivot, colk)
        blk = jnp.where(cols == k, colk, blk)
        mask = (rows > k) & (cols > k)
        blk = jnp.where(mask, blk - colk * blk[k:k + 1, :], blk)
    o_ref[...] = blk


def lud_diagonal(block: jax.Array, *, interpret: bool = False) -> jax.Array:
    bs = block.shape[0]
    return pl.pallas_call(
        functools.partial(_diag_kernel, bs=bs),
        out_shape=jax.ShapeDtypeStruct((bs, bs), block.dtype),
        interpret=interpret,
    )(block)


# --- perimeter row: U_kj = L_kk^{-1} A_kj (unit lower, forward substitution) ---

def _perim_row_kernel(d_ref, a_ref, o_ref, *, bs: int, skip: int):
    d = d_ref[...]
    strip = a_ref[...]
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    strip_rows = jax.lax.broadcasted_iota(jnp.int32, strip.shape, 0)
    for r in range(1, bs):
        lrow = jnp.where(cols < r, d[r:r + 1, :], 0.0)      # L[r, :r]
        strip = jnp.where(
            strip_rows == r,
            strip - jnp.dot(lrow, strip, precision=_HIGHEST,
                            preferred_element_type=strip.dtype),
            strip)
    if skip:
        gcol = (pl.program_id(0) * strip.shape[1]
                + jax.lax.broadcasted_iota(jnp.int32, strip.shape, 1))
        strip = jnp.where(gcol < skip, a_ref[...], strip)
    o_ref[...] = strip


def lud_perimeter_row(diag: jax.Array, strip: jax.Array, *, bw: int = 128,
                      skip: int = 0, interpret: bool = False) -> jax.Array:
    """The first ``skip`` columns of ``strip`` pass through unchanged."""
    bs, w = strip.shape
    bw = min(bw, w)
    assert w % bw == 0
    return pl.pallas_call(
        functools.partial(_perim_row_kernel, bs=bs, skip=skip),
        grid=(w // bw,),
        in_specs=[pl.BlockSpec((bs, bs), lambda j: (0, 0)),
                  pl.BlockSpec((bs, bw), lambda j: (0, j))],
        out_specs=pl.BlockSpec((bs, bw), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((bs, w), strip.dtype),
        interpret=interpret,
    )(diag, strip)


# --- perimeter column: L_ik = A_ik U_kk^{-1} (upper, non-unit) -----------------

def _perim_col_kernel(d_ref, a_ref, o_ref, *, bs: int, skip: int):
    d = d_ref[...]
    strip = a_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
    strip_cols = jax.lax.broadcasted_iota(jnp.int32, strip.shape, 1)
    for c in range(bs):
        # U[:c, c] as the only nonzero column: column c of the product is
        # strip @ U[:c, c], a full-width MXU matmul instead of an N=1 one
        ucol = jnp.where((rows < c) & (cols == c), d, 0.0)
        newcol = (strip - jnp.dot(strip, ucol, precision=_HIGHEST,
                                  preferred_element_type=strip.dtype)
                  ) / d[c, c]
        strip = jnp.where(strip_cols == c, newcol, strip)
    if skip:
        grow = (pl.program_id(0) * strip.shape[0]
                + jax.lax.broadcasted_iota(jnp.int32, strip.shape, 0))
        strip = jnp.where(grow < skip, a_ref[...], strip)
    o_ref[...] = strip


def lud_perimeter_col(diag: jax.Array, strip: jax.Array, *, bh: int = 128,
                      skip: int = 0, interpret: bool = False) -> jax.Array:
    """The first ``skip`` rows of ``strip`` pass through unchanged."""
    h, bs = strip.shape
    bh = min(bh, h)
    assert h % bh == 0
    return pl.pallas_call(
        functools.partial(_perim_col_kernel, bs=bs, skip=skip),
        grid=(h // bh,),
        in_specs=[pl.BlockSpec((bs, bs), lambda i: (0, 0)),
                  pl.BlockSpec((bh, bs), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bh, bs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((h, bs), strip.dtype),
        interpret=interpret,
    )(diag, strip)


# --- internal trailing update: C -= L @ U, streamed -----------------------------

def _internal_kernel(l_hbm, u_hbm, c_hbm, o_hbm, l_buf, u_buf, c_buf, out_buf,
                     u_stage, c_stage, l_sem, u_sems, c_sems, out_sems,
                     *, spec: PipelineSpec, n_tiles: int, bi: int, bs: int,
                     bj: int, skip: int):
    ii = pl.program_id(0)
    lc = pltpu.make_async_copy(l_hbm.at[pl.ds(ii * bi, bi), :], l_buf, l_sem)
    lc.start()

    u_stream = TileStream(
        hbm=u_hbm, vmem=u_buf, sem=u_sems,
        index=lambda j: (slice(None), pl.ds(j * bj, bj)),
        depth=spec.ring_depth)
    c_stream = TileStream(
        hbm=c_hbm, vmem=c_buf, sem=c_sems,
        index=lambda j: (pl.ds(ii * bi, bi), pl.ds(j * bj, bj)),
        depth=spec.ring_depth)
    wb = WriteBack(
        hbm=o_hbm, vmem=out_buf, sem=out_sems,
        index=lambda j: (pl.ds(ii * bi, bi), pl.ds(j * bj, bj)),
        depth=spec.out_depth)
    lc.wait()
    l_tile = l_buf[...]
    if skip:
        # rows/columns of the window before ``skip`` are already final:
        # zero their L rows and U columns so C keeps them exactly
        rows = ii * bi + jax.lax.broadcasted_iota(jnp.int32, l_tile.shape, 0)
        l_tile = jnp.where(rows < skip, 0.0, l_tile)

    def update(j, u_tile, c_tile):
        if skip:
            cols = j * bj + jax.lax.broadcasted_iota(jnp.int32,
                                                     u_tile.shape, 1)
            u_tile = jnp.where(cols < skip, 0.0, u_tile)
        wb.push(j, c_tile - jnp.dot(l_tile, u_tile, precision=_HIGHEST,
                                    preferred_element_type=c_tile.dtype))

    if spec.strategy == Strategy.DROP_OFF:
        emit(spec, [u_stream, c_stream], n_tiles,
             lambda j, vals: update(j, vals[0], vals[1]))
    else:
        def compute(j, bufs):
            update(j, bufs[0][...], bufs[1][...])
        emit(spec, [u_stream, c_stream], n_tiles, compute,
             staging=[u_stage, c_stage])
    wb.drain(n_tiles)


def lud_internal(l_strip: jax.Array, u_strip: jax.Array, c: jax.Array, *,
                 spec: PipelineSpec = PipelineSpec(), bi: int = 128,
                 bj: int = 128, skip: int = 0,
                 interpret: bool = False) -> jax.Array:
    """C -= L @ U on rows and columns from ``skip`` on; the first ``skip``
    rows and columns of C pass through unchanged.  l_strip: (H, bs),
    u_strip: (bs, W), c: (H, W).  The contraction dim is zero-padded to a
    lane multiple, so L's tiles are lane-aligned (the zero terms add
    nothing)."""
    spec = as_spec(spec)
    kp = lane_pad(l_strip.shape[1]) - l_strip.shape[1]
    l_strip = jnp.pad(l_strip, ((0, 0), (0, kp)))
    u_strip = jnp.pad(u_strip, ((0, kp), (0, 0)))
    (h, bs), (_, w) = l_strip.shape, u_strip.shape
    bi, bj = min(bi, h), min(bj, w)
    assert h % bi == 0 and w % bj == 0
    u_buf, u_sems, u_stage = scratch_for(spec, (bs, bj), u_strip.dtype)
    c_buf, c_sems, c_stage = scratch_for(spec, (bi, bj), c.dtype)
    out_buf, out_sems = writeback_scratch(spec, (bi, bj), c.dtype)
    scratch = [pltpu.VMEM((bi, bs), l_strip.dtype),
               u_buf, c_buf, out_buf, u_stage, c_stage,
               pltpu.SemaphoreType.DMA, u_sems, c_sems, out_sems]
    kernel = functools.partial(
        _internal_kernel, spec=spec, n_tiles=w // bj, bi=bi, bs=bs, bj=bj,
        skip=skip)
    return pl.pallas_call(
        kernel,
        grid=(h // bi,),
        out_shape=jax.ShapeDtypeStruct((h, w), c.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=compiler_params(
            dimension_semantics=("arbitrary",), scratch_shapes=scratch),
    )(l_strip, u_strip, c)


# --- full blocked LUD ------------------------------------------------------------

def _window_start(n: int, hi: int) -> int:
    """First row/column of the trailing window for a step whose trailing
    blocks start at ``hi``: widened back so its extent is a lane multiple,
    which keeps every tile the strip and update kernels move (8, 128)-
    aligned (``n`` is a lane multiple)."""
    return n - lane_pad(n - hi)


def lud_pallas(a: jax.Array, *, bs: int = 32,
               spec: PipelineSpec = PipelineSpec(),
               interpret: bool = False) -> jax.Array:
    """Blocked LU of (n, n) with n % bs == 0.  Returns the combined LU matrix
    (matches ref.lud_ref).

    When the trailing window is widened (``_window_start``), its first
    ``skip`` rows and columns are already final: every kernel of the step
    passes them through unchanged, so no result is sliced at an unaligned
    offset."""
    spec = as_spec(spec)
    n = a.shape[0]
    if n % bs or bs > n:
        raise ValueError(f"n={n} not divisible by block size bs={bs}")
    step = math.lcm(bs, LANES)
    if n % step:
        # pad with an identity block, which factors as itself and leaves
        # the leading n x n factors unchanged
        m = -(-n // step) * step
        big = jnp.eye(m, dtype=a.dtype).at[:n, :n].set(a)
        return lud_pallas(big, bs=bs, spec=spec, interpret=interpret)[:n, :n]
    nb = n // bs
    for k in range(nb):
        lo, hi = k * bs, (k + 1) * bs
        diag = lud_diagonal(a[lo:hi, lo:hi], interpret=interpret)
        a = a.at[lo:hi, lo:hi].set(diag)
        if k == nb - 1:
            break
        r0 = _window_start(n, hi)
        skip = hi - r0
        row = lud_perimeter_row(diag, a[lo:hi, r0:], skip=skip,
                                interpret=interpret)
        col = lud_perimeter_col(diag, a[r0:, lo:hi], skip=skip,
                                interpret=interpret)
        a = a.at[lo:hi, r0:].set(row)
        a = a.at[r0:, lo:hi].set(col)
        c = lud_internal(col, row, a[r0:, r0:], spec=spec, skip=skip,
                         interpret=interpret)
        # the barrier ends each step's fusion scope: without it the v5e
        # compiler (libtpu 0.0.34) aborts in a custom-call fusion check
        # once the trailing window moves off row 0 (n >= 256 at bs=32)
        a = jax.lax.optimization_barrier(a.at[r0:, r0:].set(c))
    return a
