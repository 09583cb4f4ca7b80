"""Parameter-dict building blocks shared by every architecture.

Initializers return trees whose leaves are ``Param(value, logical_axes)``;
apply functions take the plain value trees.  Compute runs in ``cfg.dtype``
(bf16 by default) with fp32 norms/softmax and fp32 params.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.config import ArchConfig
from ..distributed.sharding import Param, logical, axis_size


def _dtype(name: str):
    return jnp.dtype(name)


def pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def padded_heads(cfg: ArchConfig) -> int:
    """q heads padded up to a multiple of the TP degree (exactness argument:
    padded heads' out-projection rows are sliced off the result)."""
    tp = axis_size("heads")
    return pad_to(cfg.n_heads, tp) if tp > 1 else cfg.n_heads


def padded_vocab(cfg: ArchConfig) -> int:
    tp = axis_size("vocab")
    return pad_to(cfg.vocab, tp * 128) if tp > 1 else pad_to(cfg.vocab, 128)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def linear_init(key, d_in: int, d_out: int, axes: Tuple, *, bias: bool = False,
                scale: Optional[float] = None, dtype: str = "float32"):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = jax.random.normal(key, (d_in, d_out), _dtype(dtype)) * scale
    p = {"w": Param(w, axes)}
    if bias:
        p["b"] = Param(jnp.zeros((d_out,), _dtype(dtype)), (axes[-1],))
    return p


def linear(p, x, compute_dtype=jnp.bfloat16):
    out = jnp.einsum("...d,df->...f", x.astype(compute_dtype),
                     p["w"].astype(compute_dtype))
    if "b" in p:
        out = out + p["b"].astype(compute_dtype)
    return out


def norm_init(d: int, kind: str = "rmsnorm"):
    p = {"scale": Param(jnp.ones((d,), jnp.float32), ("embed",))}
    if kind == "layernorm":
        p["bias"] = Param(jnp.zeros((d,), jnp.float32), ("embed",))
    return p


def norm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return out.astype(x.dtype)


def embed_init(key, vocab: int, d: int, dtype: str = "float32"):
    # GPT-style 0.02 std — keeps tied-unembedding logits O(1) at init
    w = jax.random.normal(key, (vocab, d), _dtype(dtype)) * 0.02
    return {"emb": Param(w, ("vocab", "embed"))}


def embed(p, tokens, compute_dtype=jnp.bfloat16):
    out = jnp.take(p["emb"], tokens, axis=0).astype(compute_dtype)
    return logical(out, "batch", None, "residual")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float = 10000.0, inv_freq=None):
    """x: (B, S, H, D); positions: (B, S) int32.  ``inv_freq`` (D/2,)
    replaces the plain ``theta`` frequencies (YaRN, ``yarn_inv_freq``)."""
    d = x.shape[-1]
    half = d // 2
    freq = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
            if inv_freq is None else jnp.asarray(inv_freq, jnp.float32))
    ang = positions[..., None].astype(jnp.float32) * freq      # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
        axis=-1).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor: 0.1 * mscale * ln(factor) + 1
    (1 for factor <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's RoPE frequencies (dim/2,), as deepseek-v2 computes them:
    the plain frequencies theta^(-2i/dim) where a dimension turns more than
    ``beta_fast`` times over ``original_max`` positions, the plain ones
    divided by ``factor`` where it turns fewer than ``beta_slow`` times,
    and a linear ramp over the dimension index in between."""
    i = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** i
    inter = 1.0 / (factor * theta ** i)

    def corr(rot):  # dimension index that turns ``rot`` times
        return (dim * math.log(original_max / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(corr(beta_fast)), 0)
    hi = min(math.ceil(corr(beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp                       # 1 => the plain frequency
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ArchConfig, d_ff: Optional[int] = None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"down": linear_init(ks[2], ff, d, ("mlp", "embed"),
                             dtype=cfg.param_dtype)}
    if cfg.act == "swiglu":
        p["gate"] = linear_init(ks[0], d, ff, ("embed", "mlp"),
                                dtype=cfg.param_dtype)
        p["up"] = linear_init(ks[1], d, ff, ("embed", "mlp"),
                              dtype=cfg.param_dtype)
    else:
        p["up"] = linear_init(ks[1], d, ff, ("embed", "mlp"),
                              dtype=cfg.param_dtype)
    return p


def mlp(p, x, act: str = "swiglu", compute_dtype=jnp.bfloat16):
    if act == "swiglu":
        h = jax.nn.silu(linear(p["gate"], x, compute_dtype)) * \
            linear(p["up"], x, compute_dtype)
    elif act == "gelu":
        h = jax.nn.gelu(linear(p["up"], x, compute_dtype))
    else:
        h = jax.nn.relu(linear(p["up"], x, compute_dtype))
    h = logical(h, "batch", None, "mlp")
    out = linear(p["down"], h, compute_dtype)
    return logical(out, "batch", None, "residual")
