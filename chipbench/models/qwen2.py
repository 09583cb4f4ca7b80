"""Plain reference of the Qwen2 decoder, and the weights both sides use.

The architecture as published (Qwen2 technical report, arXiv:2407.10671,
and the model's ``config.json``): pre-norm RMSNorm (eps 1e-6) blocks of
grouped-query attention with biases on q, k and v and rotary position
embeddings on the first and second halves of each head (``rotate_half``),
then a SwiGLU MLP; a final RMSNorm and an output projection tied to the
embedding.

``forward`` runs it in float32 at ``highest`` matmul precision over one
whole sequence, with nothing cached or batched, and returns the logits at
the positions asked for.  With ``fp8=True`` every matmul instead takes its
two inputs rounded to float8_e4m3fn under a per-tensor scale and
accumulates in float32: the control, one precision step below the
configuration's bfloat16.

``make_weights`` makes random weights from a key on the device, in one
jitted call, in the layout the program's dense transformer takes
(``embed`` / stacked ``layers`` / ``ln_f``) and in the dtype the
configuration holds them in.  The reference makes its own copy from the
same key, so it takes nothing the program made.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Mapping

import jax
import jax.numpy as jnp

EPS = 1e-6
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def weight_shapes(c: Mapping) -> Dict:
    L, d, ff, V = c["n_layers"], c["d_model"], c["d_ff"], c["vocab"]
    hq, hkv = c["n_heads"] * c["head_dim"], c["n_kv_heads"] * c["head_dim"]
    return {
        "embed": {"emb": (V, d)},
        "layers": {
            "ln_attn": {"scale": (L, d)},
            "attn": {"wq": {"w": (L, d, hq), "b": (L, hq)},
                     "wk": {"w": (L, d, hkv), "b": (L, hkv)},
                     "wv": {"w": (L, d, hkv), "b": (L, hkv)},
                     "wo": {"w": (L, hq, d)}},
            "ln_mlp": {"scale": (L, d)},
            "mlp": {"gate": {"w": (L, d, ff)}, "up": {"w": (L, d, ff)},
                    "down": {"w": (L, ff, d)}},
        },
        "ln_f": {"scale": (d,)},
    }


def _init(path: str, shape, key, dtype):
    """Embedding std 0.02, matrices std 1/sqrt(fan-in), biases std 0.02,
    norm scales 1 + 0.1 N(0, 1)."""
    x = jax.random.normal(key, shape, jnp.float32)
    if path.endswith("scale"):
        x = 1.0 + 0.1 * x
    elif path.endswith("/b") or path.startswith("embed"):
        x = 0.02 * x
    else:
        x = x / math.sqrt(shape[-2])
    return x.astype(dtype)


def make_weights(c: Mapping, key) -> Dict:
    shapes = weight_shapes(c)
    leaves, tree = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    paths = ["/".join(str(k.key) for k in p) for p, _ in leaves]
    dtype = jnp.dtype(c["param_dtype"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(tree, [
            _init(p, s, k, dtype) for p, (_, s), k in zip(paths, leaves, keys)])
    return make(key)


def _q8(x):
    """Round to float8_e4m3fn under a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(spec, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, theta):
    """x: (S, H, hd), rotate_half convention."""
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta",
                                   "fp8"))
def _forward(w, tokens, at, *, heads, kv_heads, head_dim, theta, fp8):
    H, KV, hd = heads, kv_heads, head_dim
    s = tokens.shape[0]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = w["embed"]["emb"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a = p["attn"]
        h = _rms(x, p["ln_attn"]["scale"])
        q = (_mm("sd,df->sf", h, a["wq"]["w"], fp8) + a["wq"]["b"])
        k = (_mm("sd,df->sf", h, a["wk"]["w"], fp8) + a["wk"]["b"])
        v = (_mm("sd,df->sf", h, a["wv"]["w"], fp8) + a["wv"]["b"])
        q = _rope(q.reshape(s, H, hd), theta)
        k = _rope(k.reshape(s, KV, hd), theta)
        v = v.reshape(s, KV, hd)
        k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
        sc = _mm("qhd,khd->hqk", q, k, fp8) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = _mm("hqk,khd->qhd", pr, v, fp8).reshape(s, H * hd)
        x = x + _mm("sf,fd->sd", o, a["wo"]["w"], fp8)
        m = p["mlp"]
        h = _rms(x, p["ln_mlp"]["scale"])
        g = jax.nn.silu(_mm("sd,df->sf", h, m["gate"]["w"], fp8))
        u = _mm("sd,df->sf", h, m["up"]["w"], fp8)
        return x + _mm("sf,fd->sd", g * u, m["down"]["w"], fp8), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    xf = _rms(x[at], w["ln_f"]["scale"])
    return _mm("kd,vd->kv", xf, w["embed"]["emb"], fp8)


def forward(w, c: Mapping, tokens, at, *, fp8: bool = False):
    """Logits (len(at), vocab) of the sequence ``tokens`` at positions
    ``at``; float32 at ``highest`` precision, or the fp8 control."""
    with jax.default_matmul_precision("highest"):
        return _forward(w, tokens, at, heads=c["n_heads"],
                        kv_heads=c["n_kv_heads"], head_dim=c["head_dim"],
                        theta=float(c["rope_theta"]), fp8=fp8)
