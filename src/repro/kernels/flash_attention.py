"""Flash attention (online softmax) with async K/V streaming — the paper's
Overlap pattern applied to the transformer's dominant memory-bound kernel.

The K/V tiles for query block i+A stream HBM -> VMEM while block i is in the
MXU (A = the PipelineSpec's issue-ahead distance); causal/sliding-window
masking prunes the KV loop to the tiles that can contribute (traced loop
bounds).  GQA is handled by mapping each q head to its kv head inside the
grid.

Layout: q, k, v are (heads, seq, head_dim); batching is vmapped in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.async_pipeline import (PipelineSpec, Strategy, TileStream,
                                   as_spec, compiler_params, emit,
                                   lane_pad, scratch_for)

NEG_INF = -1e30


def _flash_kernel(q_hbm, k_hbm, v_hbm, o_hbm, q_buf, k_buf, v_buf, k_stage,
                  v_stage, acc, m_i, l_i, q_sem, k_sems, v_sems, out_sem,
                  *, spec: PipelineSpec, bq: int, bk: int, head_dim: int,
                  q_heads_per_kv: int, causal: bool, window: int,
                  scale: float, n_kv_tiles_max: int):
    qh = pl.program_id(0)
    qi = pl.program_id(1)
    kvh = qh // q_heads_per_kv
    q_start = qi * bq

    # ---- load the q tile (single DMA; it is reused across all KV tiles)
    qc = pltpu.make_async_copy(
        q_hbm.at[qh, pl.ds(q_start, bq), :], q_buf, q_sem)
    qc.start()

    # ---- KV tile range pruned by the mask structure
    if causal:
        hi = (q_start + bq + bk - 1) // bk          # tiles with kv_start <= q_end
        hi = jnp.minimum(hi, n_kv_tiles_max)
    else:
        hi = n_kv_tiles_max
    if window > 0:
        lo = jnp.maximum((q_start - window + 1) // bk, 0)
    else:
        lo = 0
    n_tiles = hi - lo

    k_stream = TileStream(
        hbm=k_hbm, vmem=k_buf, sem=k_sems,
        index=lambda i: (kvh, pl.ds((lo + i) * bk, bk), slice(None)),
        depth=spec.ring_depth)
    v_stream = TileStream(
        hbm=v_hbm, vmem=v_buf, sem=v_sems,
        index=lambda i: (kvh, pl.ds((lo + i) * bk, bk), slice(None)),
        depth=spec.ring_depth)

    acc[...] = jnp.zeros_like(acc)
    m_i[...] = jnp.full_like(m_i, NEG_INF)
    l_i[...] = jnp.zeros_like(l_i)
    qc.wait()
    q = q_buf[...].astype(jnp.float32) * scale

    def online_softmax(i, k_tile, v_tile):
        kv_start = (lo + i) * bk
        logits = jnp.dot(q, k_tile.astype(jnp.float32).T,
                         preferred_element_type=jnp.float32)  # (bq, bk)
        q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kv_idx = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            mask &= kv_idx <= q_idx
        if window > 0:
            mask &= kv_idx > q_idx - window
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m_i[...], jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_i[...] - m_new)
        p = jnp.exp(logits - m_new)
        l_i[...] = l_i[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jnp.dot(
            p, v_tile.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_i[...] = m_new

    if spec.strategy == Strategy.DROP_OFF:
        emit(spec, [k_stream, v_stream], n_tiles,
             lambda i, vals: online_softmax(i, vals[0], vals[1]))
    else:
        emit(spec, [k_stream, v_stream], n_tiles,
             lambda i, bufs: online_softmax(i, bufs[0][...], bufs[1][...]),
             staging=[k_stage, v_stage])

    out = (acc[...] / jnp.maximum(l_i[...], 1e-30)).astype(o_hbm.dtype)
    acc[...] = out
    oc = pltpu.make_async_copy(
        acc, o_hbm.at[qh, pl.ds(q_start, bq), :], out_sem)
    oc.start()
    oc.wait()


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           scale: float | None = None,
                           spec: PipelineSpec = PipelineSpec(),
                           bq: int = 128, bk: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (H, S, D), k/v: (KVH, S, D) -> (H, S, D) fp32.  A head_dim that
    is not a lane multiple is zero-padded to one: the zero columns add
    nothing to q.k and produce zero output columns, which are sliced off."""
    spec = as_spec(spec)
    h, s, d_in = q.shape
    kvh = k.shape[0]
    assert h % kvh == 0, (h, kvh)
    if s % bq or s % bk:
        raise ValueError(f"seq {s} must divide bq={bq}, bk={bk}")
    scale = scale if scale is not None else 1.0 / (d_in ** 0.5)
    d = lane_pad(d_in)
    if d != d_in:
        pad = ((0, 0), (0, 0), (0, d - d_in))
        q, k, v = (jnp.pad(t, pad) for t in (q, k, v))
    k_buf, k_sems, k_stage = scratch_for(spec, (bk, d), k.dtype)
    v_buf, v_sems, v_stage = scratch_for(spec, (bk, d), v.dtype)
    kernel = functools.partial(
        _flash_kernel, spec=spec, bq=bq, bk=bk, head_dim=d,
        q_heads_per_kv=h // kvh, causal=causal, window=window, scale=scale,
        n_kv_tiles_max=s // bk)
    scratch = [
        pltpu.VMEM((bq, d), q.dtype),
        k_buf, v_buf,
        k_stage, v_stage,
        pltpu.VMEM((bq, d), jnp.float32),    # acc
        pltpu.VMEM((bq, 1), jnp.float32),    # running max
        pltpu.VMEM((bq, 1), jnp.float32),    # running denom
        pltpu.SemaphoreType.DMA,
        k_sems, v_sems,
        pltpu.SemaphoreType.DMA,
    ]
    out = pl.pallas_call(
        kernel,
        grid=(h, s // bq),
        out_shape=jax.ShapeDtypeStruct((h, s, d), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
            scratch_shapes=scratch),
    )(q, k, v)
    return out[..., :d_in]
