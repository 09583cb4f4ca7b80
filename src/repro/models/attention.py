"""Attention for the distributed model path (pure JAX, compiles on any mesh).

Chunked online-softmax attention bounds activation memory at (S/chunk) x chunk
logits tiles — the same algorithm as kernels/flash_attention.py but expressed
in lax.scan so pjit can partition it (the Pallas kernel is the TPU-target
fast path, validated against the same oracle).

GQA uses an explicit q-head -> kv-head index map, which stays *exact* under
head padding (padded q heads read some kv head, and their out-projection rows
are zero-sliced).  Decode supports full and rolling-window KV caches.

Multi-head latent attention (MLA, deepseek-v2) caches one latent row per
token in place of K and V: ``kv_lora_rank`` columns of RMS-normed latent and
``qk_rope_dim`` columns of a RoPE'd key shared by every head.  The cache
holds it as one "KV head" of that width (zero-padded to whole 128-lane
tiles, ``mla_row_width``), beside an empty V.  Prefill and
chunks decompress the latent into per-head keys and values (``wkv_b``);
decode absorbs ``wkv_b``'s key half into the query and its value half into
the output, so its query heads attend the latent rows themselves.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.config import ArchConfig
from ..distributed.sharding import Param, logical
from .layers import (linear, linear_init, norm, padded_heads, rope,
                     yarn_inv_freq, yarn_mscale)

NEG = -1e30


def kv_index_map(n_heads: int, n_kv: int, h_pad: int) -> np.ndarray:
    """q head -> kv head (padded q heads clamp to the last kv head)."""
    group = n_heads // n_kv
    idx = np.minimum(np.arange(h_pad) // group, n_kv - 1)
    return idx.astype(np.int32)


def attn_init(key, cfg: ArchConfig, *, cross: bool = False):
    d, hd, nkv = cfg.d_model, cfg.head_dim_, cfg.n_kv_heads
    hp = padded_heads(cfg)
    ks = jax.random.split(key, 4)
    bias = cfg.attn.qkv_bias
    return {
        "wq": linear_init(ks[0], d, hp * hd, ("embed", "heads"), bias=bias,
                          dtype=cfg.param_dtype),
        "wk": linear_init(ks[1], d, nkv * hd, ("embed", "kv"), bias=bias,
                          dtype=cfg.param_dtype),
        "wv": linear_init(ks[2], d, nkv * hd, ("embed", "kv"), bias=bias,
                          dtype=cfg.param_dtype),
        "wo": linear_init(ks[3], hp * hd, d, ("heads", "embed"),
                          scale=1.0 / math.sqrt(hp * hd),
                          dtype=cfg.param_dtype),
    }


def qkv_project(p, x, cfg: ArchConfig, positions, compute_dtype):
    """x: (B,S,d) -> q (B,S,Hp,hd), k/v (B,S,KV,hd), rope applied."""
    b, s, _ = x.shape
    hd, nkv = cfg.head_dim_, cfg.n_kv_heads
    hp = padded_heads(cfg)
    q = linear(p["wq"], x, compute_dtype).reshape(b, s, hp, hd)
    k = linear(p["wk"], x, compute_dtype).reshape(b, s, nkv, hd)
    v = linear(p["wv"], x, compute_dtype).reshape(b, s, nkv, hd)
    q = logical(q, "batch", None, "heads", None)
    k = logical(k, "batch", None, "kv", None)
    v = logical(v, "batch", None, "kv", None)
    if cfg.attn.rope_theta > 0:
        q = rope(q, positions, cfg.attn.rope_theta)
        k = rope(k, positions, cfg.attn.rope_theta)
    return q, k, v


def update_cache_layer(k_layer, v_layer, pos_layer, k_new, v_new, positions):
    """Insert S new entries at slots positions % W (rolling).

    LOCKSTEP assumption: all sequences in the batch share the same position
    (static-batch serving, as in launch/serve.py), so the update is ONE
    contiguous dynamic_update_slice at a scalar start — a per-batch scatter
    here makes XLA SPMD re-gather the sharded cache (16 GB/chip of temps on
    decode_32k).  Writes never wrap: prefill fills [0, S) and decode writes
    a single slot.  positions: (B, S) absolute."""
    w = k_layer.shape[1]
    start = positions[0, 0] % w
    zero = jnp.zeros((), start.dtype)
    # the update must arrive batch-sharded/kv-replicated like the cache —
    # otherwise XLA reshards the whole (kvlen-sharded) cache per layer
    # (an all-to-all of GBs per decode step)
    k_new = logical(k_new, "batch", None, None, None)
    v_new = logical(v_new, "batch", None, None, None)
    k_layer = jax.lax.dynamic_update_slice(
        k_layer, k_new, (zero, start, zero, zero))
    v_layer = jax.lax.dynamic_update_slice(
        v_layer, v_new, (zero, start, zero, zero))
    pos_layer = jax.lax.dynamic_update_slice(
        pos_layer, positions, (zero, start))
    return k_layer, v_layer, pos_layer


# ---------------------------------------------------------------------------
# Paged KV cache (serving): block arena + per-slot block tables
# ---------------------------------------------------------------------------
#
# The serving arena carves one fixed (n_blocks, block_len, KV, hd) region per
# layer out of a global token budget; each batch slot owns an ordered list of
# block ids (its *block table*).  Because a slot fills its blocks strictly in
# order, gathering the table reconstructs a dense (W, KV, hd) view in which
# row p holds the slot's token at position p — so ``attend_decode`` (and its
# ``pos < 0`` empty-slot masking, the same path ragged cohort serving uses)
# works unchanged on the gathered view.  Block id 0 is a scratch block:
# inactive slots' writes land there and table entries < 0 gather it with
# their positions forced to -1, so garbage is never attended.


def gather_paged_view(k_blocks, v_blocks, pos_blocks, block_table, layer):
    """Reassemble per-slot dense cache views of one layer from a block arena.

    k/v_blocks: the whole (L, n_blocks, BL, KV, hd) arena, indexed at
    ``layer`` together with the blocks, so no copy of the layer's arena is
    made; pos_blocks: (n_blocks, BL); block_table: (B, MB) int32 with -1
    marking unused entries.  Returns (k, v, pos) shaped (B, MB*BL, KV, hd)
    / (B, MB*BL); unused entries' positions are -1 so ``attend_decode``
    masks them."""
    bt = jnp.maximum(block_table, 0)
    b, mb = block_table.shape
    bl = pos_blocks.shape[1]
    k = k_blocks[layer, bt]                              # (B, MB, BL, KV, hd)
    v = v_blocks[layer, bt]
    pos = jnp.where((block_table >= 0)[:, :, None], pos_blocks[bt], -1)
    return (k.reshape((b, mb * bl) + k.shape[3:]),
            v.reshape((b, mb * bl) + v.shape[3:]), pos.reshape(b, mb * bl))


def append_paged_layer(k_blocks, v_blocks, k_new, v_new, blk, off, layer):
    """Write each slot's one new KV row into its current block of
    ``layer`` in the whole arena (as ``gather_paged_view``).

    k/v_new: (B, 1, KV, hd); blk/off: (B,) target block id and row within
    it (inactive slots point at the scratch block 0)."""
    k_blocks = k_blocks.at[layer, blk, off].set(k_new[:, 0])
    v_blocks = v_blocks.at[layer, blk, off].set(v_new[:, 0])
    return k_blocks, v_blocks


def attend_paged(q, k_blocks, v_blocks, pos_blocks, block_table, idx_map, *,
                 layer, q_position, window: int = 0,
                 scale: Optional[float] = None, global_flag=None):
    """Decode attention over ``layer`` of a block arena (as
    ``gather_paged_view``): gather the slot's block table into a dense
    view, then run the standard masked decode attention."""
    k, v, pos = gather_paged_view(k_blocks, v_blocks, pos_blocks,
                                  block_table, layer)
    return attend_decode(q, k, v, pos, idx_map, q_position=q_position,
                         window=window, scale=scale, global_flag=global_flag)


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (train / prefill)
# ---------------------------------------------------------------------------

def attend_chunked(q, k, v, idx_map, *, causal: bool, window: int,
                   chunk: int, scale: Optional[float] = None,
                   global_flag=None):
    """q: (B,S,Hp,hd); k/v: (B,S,KV,hd).  Scans KV chunks, carrying
    (m, l, acc) for every query.  ``global_flag`` (scalar bool, may be
    traced) disables the sliding window for this layer (hymba's hybrid
    global/local mix inside one scan)."""
    b, s, hp, hd = q.shape
    nkv, hv = k.shape[2], v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    chunk = min(chunk, s)
    while s % chunk:        # largest divisor of s not exceeding the request
        chunk -= 1
    n_chunks = s // chunk
    # matmuls run at the INPUT dtype (bf16 in the model path) with fp32
    # accumulation — flash-attention numerics; softmax state stays fp32
    qf = q * jnp.asarray(scale, q.dtype)
    q_pos = jnp.arange(s, dtype=jnp.int32)

    kc = k.reshape(b, n_chunks, chunk, nkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, nkv, hv).transpose(1, 0, 2, 3, 4)
    starts = jnp.arange(n_chunks, dtype=jnp.int32) * chunk

    def body(carry, xs):
        m, l, acc = carry
        k_ch, v_ch, start = xs
        k_rep = jnp.take(k_ch, idx_map, axis=2)               # (B,c,Hp,hd)
        v_rep = jnp.take(v_ch, idx_map, axis=2)
        logits = jnp.einsum("bqhd,bchd->bhqc", qf, k_rep,
                            preferred_element_type=jnp.float32)  # (B,Hp,S,c)
        kv_pos = start + jnp.arange(chunk, dtype=jnp.int32)
        mask = jnp.ones((s, chunk), dtype=bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window > 0:
            wmask = kv_pos[None, :] > q_pos[:, None] - window
            if global_flag is not None:
                wmask = wmask | global_flag
            mask &= wmask
        logits = jnp.where(mask[None, None], logits, NEG)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))      # (B,Hp,S)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqc,bchd->bhqd", p.astype(v_rep.dtype), v_rep,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hp, s), NEG, jnp.float32)
    l0 = jnp.zeros((b, hp, s), jnp.float32)
    a0 = jnp.zeros((b, hp, s, hv), jnp.float32)
    # checkpoint the KV-chunk body: the (B,H,S,chunk) logits/probs are
    # recomputed in the backward instead of residual-stacked over chunks
    body = jax.checkpoint(body,
                          policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, starts))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)          # (B,S,Hp,hv)


# ---------------------------------------------------------------------------
# Decode attention (one new token against the cache)
# ---------------------------------------------------------------------------

def group_size(idx_map, n_kv: int) -> Optional[int]:
    """Query heads per KV head when ``idx_map`` is the plain GQA grouping
    ``repeat(arange(n_kv), G)``, else None.  Padded q heads (tensor
    parallelism) clamp to the last KV head, which is no clean grouping."""
    if idx_map.size % n_kv:
        return None
    g = idx_map.size // n_kv
    return g if np.array_equal(idx_map, np.repeat(np.arange(n_kv), g)) \
        else None


def _attend_view(qf, k_cache, v_cache, mask, idx_map):
    """Masked full-softmax attention over a (B,W,KV,hd) cache view.

    qf: (B,Hp,hd) for one query per row (decode) or (B,C,Hp,hd) for C,
    already scaled; mask: (B,W) or (B,C,W) to match.  Returns qf's shape,
    with V's width last, in float32.  Under a plain grouping each KV head is
    contracted once with the G query heads that share it; only a padded
    head map gathers a head-repeated (B,W,Hp,hd) copy of K and V.  Either
    way the cache-length axis of the view and of the logits stays on
    "kvlen" (without it XLA un-shards W and the decode_32k view costs
    8.6 GB/chip)."""
    # decode passes no query axis: a unit one there costs the step a
    # relayout of the logits (a quarter more temporaries at batch 128)
    c = "c" if qf.ndim == 4 else ""
    nkv = k_cache.shape[2]
    g = group_size(idx_map, nkv)
    if g is not None:
        k = logical(k_cache, "batch", "kvlen", None, None)
        v = logical(v_cache, "batch", "kvlen", None, None)
        qg = qf.reshape(qf.shape[:-2] + (nkv, g, qf.shape[-1]))
        logits = jnp.einsum(f"b{c}kgd,bwkd->bkg{c}w", qg, k,
                            preferred_element_type=jnp.float32)
        logits = logical(logits, "batch", *[None] * (logits.ndim - 2),
                         "kvlen")
        logits = jnp.where(mask[:, None, None], logits, NEG)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum(f"bkg{c}w,bwkd->b{c}kgd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.reshape(qf.shape[:-1] + v.shape[-1:])
    k_rep = jnp.take(k_cache, idx_map, axis=2)                # (B,W,Hp,hd)
    v_rep = jnp.take(v_cache, idx_map, axis=2)
    k_rep = logical(k_rep, "batch", "kvlen", None, None)
    v_rep = logical(v_rep, "batch", "kvlen", None, None)
    logits = jnp.einsum(f"b{c}hd,bwhd->bh{c}w", qf, k_rep,
                        preferred_element_type=jnp.float32)
    logits = logical(logits, "batch", *[None] * (logits.ndim - 2), "kvlen")
    logits = jnp.where(mask[:, None], logits, NEG)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(f"bh{c}w,bwhd->b{c}hd", p.astype(v_rep.dtype), v_rep,
                      preferred_element_type=jnp.float32)


def _view_mask(pos_cache, q_positions, window, global_flag):
    """(B,C,W): cache rows in [0, q_position], inside the window unless the
    layer is global.  pos_cache: (B,W); q_positions: (B,C)."""
    pos = pos_cache[:, None, :]
    qp = q_positions[:, :, None]
    mask = (pos >= 0) & (pos <= qp)
    if window > 0:
        wmask = pos > (qp - window)
        if global_flag is not None:
            wmask = wmask | global_flag
        mask &= wmask
    return mask


def attend_decode(q, k_cache, v_cache, pos_cache, idx_map, *,
                  q_position, window: int = 0,
                  scale: Optional[float] = None, global_flag=None):
    """q: (B,1,Hp,hd); caches: (B,W,KV,hd); pos_cache: (B,W) absolute
    positions (-1 empty).  q_position: (B,) absolute position of the query."""
    hd = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qf = q[:, 0] * jnp.asarray(scale, q.dtype)                # (B,Hp,hd)
    mask = _view_mask(pos_cache, q_position[:, None], window, global_flag)
    out = _attend_view(qf, k_cache, v_cache, mask[:, 0], idx_map)
    return out[:, None].astype(q.dtype)                       # (B,1,Hp,hv)


def attend_prefix(q, k_cache, v_cache, pos_cache, idx_map, *,
                  q_positions, window: int = 0,
                  scale: Optional[float] = None, global_flag=None):
    """Prefill-chunk attention: C queries per batch row over a cache view.

    q: (B,C,Hp,hd); caches: (B,W,KV,hd); pos_cache: (B,W) absolute
    positions (-1 empty); q_positions: (B,C) each query's absolute
    position.  Row c attends cache rows whose position is in
    [0, q_positions[c]] — which includes the chunk's own rows, written
    into the cache before this call.

    Deliberately a FULL masked softmax per query (not the online-softmax
    scan of ``attend_chunked``): every query reduces over the same fixed
    W regardless of how prefill was chunked, so per-row outputs are
    bit-identical across chunk sizes and shared-prefix admissions — the
    property the chunked-prefill equivalence tests pin."""
    hd = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qf = q * jnp.asarray(scale, q.dtype)                      # (B,C,Hp,hd)
    mask = _view_mask(pos_cache, q_positions, window, global_flag)
    out = _attend_view(qf, k_cache, v_cache, mask, idx_map)
    return out.astype(q.dtype)                                # (B,C,Hp,hv)


def attn_out(p, attn_heads, cfg: ArchConfig, compute_dtype):
    b, s = attn_heads.shape[:2]
    flat = attn_heads.reshape(b, s, -1)
    out = linear(p["wo"], flat, compute_dtype)
    return logical(out, "batch", None, "residual")


# ---------------------------------------------------------------------------
# Multi-head latent attention (MLA)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ArchConfig):
    a, d, h = cfg.attn, cfg.d_model, cfg.n_heads
    r, dn, dr, dv = a.kv_lora_rank, a.qk_nope_dim, a.qk_rope_dim, a.v_head_dim
    ks = jax.random.split(key, 4)
    dt = cfg.param_dtype
    return {
        "wq": linear_init(ks[0], d, h * (dn + dr), ("embed", "heads"),
                          dtype=dt),
        "wkv_a": linear_init(ks[1], d, r + dr, ("embed", None), dtype=dt),
        "kv_norm": {"scale": Param(jnp.ones((r,), jnp.float32), (None,))},
        "wkv_b": linear_init(ks[2], r, h * (dn + dv), (None, "heads"),
                             dtype=dt),
        "wo": linear_init(ks[3], h * dv, d, ("heads", "embed"), dtype=dt),
    }


def mla_rope_freq(cfg: ArchConfig):
    """RoPE frequencies of the ``qk_rope_dim`` columns: YaRN's where the
    config scales RoPE, else None (the plain ``rope_theta`` ones)."""
    a = cfg.attn
    if a.yarn_factor <= 0:
        return None
    return yarn_inv_freq(a.qk_rope_dim, a.rope_theta, a.yarn_factor,
                         a.yarn_original_max, a.yarn_beta_fast,
                         a.yarn_beta_slow)


def mla_scale(cfg: ArchConfig) -> float:
    """Softmax scale: (qk_nope_dim + qk_rope_dim)^-0.5, times YaRN's
    mscale(factor, mscale_all_dim) squared."""
    a = cfg.attn
    s = 1.0 / math.sqrt(a.qk_nope_dim + a.qk_rope_dim)
    if a.yarn_factor > 0:
        s *= yarn_mscale(a.yarn_factor, a.yarn_mscale_all_dim) ** 2
    return s


def mla_row_width(cfg: ArchConfig) -> int:
    """Width of a cached latent row: kv_lora_rank + qk_rope_dim, padded
    with zeros to whole 128-lane tiles.  A 576-wide arena (deepseek-v2)
    gets a TPU layout with the block axis minor-most, which every gather
    and write of a layer must relayout; a 640-wide one stays row-major."""
    a = cfg.attn
    return -(-(a.kv_lora_rank + a.qk_rope_dim) // 128) * 128


def mla_project(p, x, cfg: ArchConfig, positions, compute_dtype):
    """x: (B,S,d) -> q_nope (B,S,H,dn), q_pe (B,S,H,dr) with RoPE, and the
    latent rows to cache, (B,S,1,``mla_row_width``): RMS-normed latent,
    the RoPE'd key shared by every head, then zeros."""
    a = cfg.attn
    b, s, _ = x.shape
    h, r, dn = cfg.n_heads, a.kv_lora_rank, a.qk_nope_dim
    freq = mla_rope_freq(cfg)
    q = linear(p["wq"], x, compute_dtype).reshape(b, s, h, -1)
    q_nope = q[..., :dn]
    q_pe = rope(q[..., dn:], positions, a.rope_theta, freq)
    kv = linear(p["wkv_a"], x, compute_dtype)                 # (B,S,r+dr)
    c = norm(p["kv_norm"], kv[..., :r])
    k_pe = rope(kv[..., None, r:], positions, a.rope_theta, freq)
    pad = jnp.zeros(k_pe.shape[:-1] + (mla_row_width(cfg) - r
                                       - a.qk_rope_dim,), k_pe.dtype)
    return q_nope, q_pe, jnp.concatenate([c[..., None, :], k_pe, pad],
                                         axis=-1)


def _wkv_b(p, cfg: ArchConfig, compute_dtype):
    """``wkv_b`` as (r, H, dn + dv): each head's key half, then value half."""
    return p["wkv_b"]["w"].astype(compute_dtype).reshape(
        cfg.attn.kv_lora_rank, cfg.n_heads, -1)


def mla_decompress(p, latent, cfg: ArchConfig, compute_dtype):
    """Latent rows (B,W,1,``mla_row_width``) -> per-head keys (B,W,H,dn+dr)
    and values (B,W,H,dv)."""
    a = cfg.attn
    r, dn = a.kv_lora_rank, a.qk_nope_dim
    kv = jnp.einsum("bwr,rhe->bwhe", latent[..., 0, :r],
                    _wkv_b(p, cfg, compute_dtype))
    k_pe = jnp.broadcast_to(latent[..., r:r + a.qk_rope_dim],
                            kv.shape[:-1] + (a.qk_rope_dim,))
    return jnp.concatenate([kv[..., :dn], k_pe], axis=-1), kv[..., dn:]


def mla_attend_self(p, q_nope, q_pe, latent, cfg: ArchConfig,
                    compute_dtype):
    """Causal attention of a whole sequence over its own latent rows,
    decompressed (training and monolithic prefill)."""
    k, v = mla_decompress(p, latent, cfg, compute_dtype)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    return attend_chunked(q, k, v, np.arange(cfg.n_heads, dtype=np.int32),
                          causal=True, window=0, chunk=cfg.attn.chunk,
                          scale=mla_scale(cfg))


def mla_attend_prefix(p, q_nope, q_pe, latent_view, pos_view,
                      cfg: ArchConfig, q_positions, compute_dtype):
    """A prefill chunk over a slot's gathered latent view (B,W,1,width),
    decompressed; masked as ``attend_prefix``."""
    k, v = mla_decompress(p, latent_view, cfg, compute_dtype)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    return attend_prefix(q, k, v, pos_view,
                         np.arange(cfg.n_heads, dtype=np.int32),
                         q_positions=q_positions, scale=mla_scale(cfg))


def mla_attend_decode(p, q_nope, q_pe, latent_view, pos_view,
                      cfg: ArchConfig, q_position, compute_dtype):
    """One query per row over latent views (B,W,1,width), absorbed: the
    query's no-RoPE part goes through ``wkv_b``'s key half, so all H
    query heads attend the one latent "KV head" (values: its first r
    columns), and the result goes through the value half.  The query is
    zero-padded to the view's width.  Equal to the decompressed attention
    up to rounding.  Returns (B,1,H,dv)."""
    r = cfg.attn.kv_lora_rank
    w = _wkv_b(p, cfg, compute_dtype)
    dn = cfg.attn.qk_nope_dim
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w[..., :dn],
                       preferred_element_type=jnp.float32)
    pad = jnp.zeros(q_pe.shape[:-1] + (latent_view.shape[-1] - r
                                       - q_pe.shape[-1],), compute_dtype)
    q = jnp.concatenate([q_lat.astype(compute_dtype), q_pe, pad], axis=-1)
    o = attend_decode(q, latent_view, latent_view[..., :r], pos_view,
                      np.zeros((cfg.n_heads,), np.int32),
                      q_position=q_position, scale=mla_scale(cfg))
    return jnp.einsum("bshr,rhv->bshv", o, w[..., dn:],
                      preferred_element_type=jnp.float32
                      ).astype(compute_dtype)
