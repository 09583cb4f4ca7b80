"""MXU-tiled matmul with an explicit overlap-k HBM->VMEM pipeline.

This is the paper's Overlap pattern applied to the TPU's dominant compute
kernel: A (M,K) x B (K,N) accumulates over K tiles while the next K tile of
both operands streams in.  Block shapes default to MXU-aligned 128 multiples;
accumulation is fp32 regardless of input dtype.

Grid: (M//bm, N//bn); the K loop runs inside the kernel under the selected
strategy so the DMA/compute overlap is explicit (not left to the pallas_call
grid pipeliner), mirroring the paper's hand-written pipelines.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.async_pipeline import (PipelineSpec, Strategy, TileStream,
                                   as_spec, compiler_params, emit,
                                   scratch_for)


def _matmul_kernel(a_hbm, b_hbm, o_hbm, a_buf, b_buf, acc, a_stage, b_stage,
                   a_sems, b_sems, out_sem,
                   *, spec: PipelineSpec, n_k: int, bm: int, bk: int, bn: int):
    mi = pl.program_id(0)
    ni = pl.program_id(1)

    a_stream = TileStream(
        hbm=a_hbm, vmem=a_buf, sem=a_sems,
        index=lambda k: (pl.ds(mi * bm, bm), pl.ds(k * bk, bk)),
        depth=spec.ring_depth)
    b_stream = TileStream(
        hbm=b_hbm, vmem=b_buf, sem=b_sems,
        index=lambda k: (pl.ds(k * bk, bk), pl.ds(ni * bn, bn)),
        depth=spec.ring_depth)

    acc[...] = jnp.zeros_like(acc)

    # bf16 products are exact in the f32 accumulator at any precision, and
    # Mosaic rejects a bf16 dot asked for HIGHEST: pin DEFAULT for narrow
    # inputs so a process-wide "highest" setting cannot break the kernel
    precision = (None if a_buf.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)

    def mac(a_tile, b_tile):
        acc[...] += jnp.dot(a_tile, b_tile, precision=precision,
                            preferred_element_type=jnp.float32)

    if spec.strategy == Strategy.DROP_OFF:
        emit(spec, [a_stream, b_stream], n_k,
             lambda k, vals: mac(vals[0], vals[1]))
    else:
        def compute(k, bufs):
            mac(bufs[0][...], bufs[1][...])
        emit(spec, [a_stream, b_stream], n_k, compute,
             staging=[a_stage, b_stage])

    # drain accumulator to HBM
    out = pltpu.make_async_copy(
        acc, o_hbm.at[pl.ds(mi * bm, bm), pl.ds(ni * bn, bn)], out_sem)
    out.start()
    out.wait()


def matmul_pallas(a: jax.Array, b: jax.Array, *,
                  spec: PipelineSpec = PipelineSpec(),
                  bm: int = 128, bk: int = 128, bn: int = 128,
                  interpret: bool = False) -> jax.Array:
    """a: (M, K), b: (K, N) -> fp32 (M, N).  Dims must divide block shapes."""
    spec = as_spec(spec)
    (m, k), (k2, n) = a.shape, b.shape
    assert k == k2, (a.shape, b.shape)
    if m % bm or k % bk or n % bn:
        raise ValueError(f"shape {(m, k, n)} not divisible by blocks {(bm, bk, bn)}")
    n_k = k // bk
    a_buf, a_sems, a_stage = scratch_for(spec, (bm, bk), a.dtype)
    b_buf, b_sems, b_stage = scratch_for(spec, (bk, bn), b.dtype)
    kernel = functools.partial(
        _matmul_kernel, spec=spec, n_k=n_k, bm=bm, bk=bk, bn=bn)
    scratch = [a_buf, b_buf,
               pltpu.VMEM((bm, bn), jnp.float32),   # accumulator
               a_stage, b_stage, a_sems, b_sems, pltpu.SemaphoreType.DMA]
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel"),
            scratch_shapes=scratch),
    )(a, b)


def matmul_vmem_bytes(strategy: Strategy, bm: int, bk: int, bn: int,
                      depth: int, itemsize: int = 2) -> int:
    """VMEM footprint claimed by the block shapes (for the low-occupancy
    analysis: footprint bounds how many programs can co-schedule)."""
    d = 1 if strategy in (Strategy.SYNC, Strategy.REGISTER_BYPASS) else depth
    buf = d * (bm * bk + bk * bn) * itemsize
    stage = (bm * bk + bk * bn) * itemsize if strategy == Strategy.SYNC else 0
    return buf + stage + bm * bn * 4
