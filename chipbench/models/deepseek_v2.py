"""Plain reference of the DeepSeek-V2 decoder, and the weights both sides use.

The architecture as published (DeepSeek-V2, arXiv:2405.04434, and the
model's ``config.json``; the configuration file's keys are that file's):

- pre-norm RMSNorm blocks (eps ``rms_norm_eps``);
- multi-head latent attention with no query compression: q = W_q h, per
  head ``qk_nope_head_dim`` columns without RoPE then ``qk_rope_head_dim``
  with it; [c, k_pe] = W_kva h, c <- RMSNorm(c), k_pe one RoPE'd row
  shared by every head; [k_nope, v] = W_kvb c per head; scores
  (q_nope.k_nope + q_pe.k_pe) x (qk_nope + qk_rope)^-0.5 x m^2 with
  m = 0.1 x mscale_all_dim x ln(factor) + 1; output W_o over the heads;
- YaRN RoPE (``rope_scaling``): plain frequencies theta^(-2i/d) where a
  dimension turns more than ``beta_fast`` times over
  ``original_max_position_embeddings`` positions, those over ``factor``
  where it turns fewer than ``beta_slow`` times, a linear ramp between;
  cos and sin scaled by mscale(mscale) / mscale(mscale_all_dim);
- the first ``first_k_dense_replace`` layers a dense SwiGLU of
  ``intermediate_size``, the rest ``n_routed_experts`` SwiGLU experts of
  ``moe_intermediate_size`` chosen by a softmax router, greedy top
  ``num_experts_per_tok``, weights renormalised only if
  ``norm_topk_prob``, times ``routed_scaling_factor``; plus
  ``n_shared_experts`` experts fused into one SwiGLU, added with no gate;
- a final RMSNorm and an untied output projection.

This chip's share of the experts: only experts ``first_held_expert`` to
``first_held_expert + n_routed_experts_held - 1`` are computed, for the
tokens that picked them, as one of the chips of the stated expert-parallel
deployment would; what the others would add is left out, here as in the
program, and the partial sum goes on to the next layer.

Departure from the published code: HF's DeepSeek-V2 permutes the RoPE
columns (pairs interleaved into halves) before ``rotate_half``; here both
q_pe and k_pe use ``rotate_half`` on their columns as they are.  With
random weights that is the same computation with W_q's and W_kva's RoPE
columns permuted.

``forward`` runs in float32 at ``highest`` matmul precision over one whole
sequence, with nothing cached or batched and the latent decompressed
(never absorbed), attention in blocks of query rows so that a long
sequence fits; it returns the logits at the positions asked for.  With
``fp8=True`` every matmul takes its two inputs rounded to float8_e4m3fn
under a per-tensor scale: the control, one precision step below the
configuration's bfloat16.

``make_weights`` makes random weights from a key on the device, in one
jitted call, in the layout the program's transformer takes
(``embed`` / ``dense_layers`` / ``layers`` / ``ln_f`` / ``unembed``, the
held experts stacked) and in the dtype the configuration holds them in.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
#: query rows per attention block
Q_BLOCK = 512


class Dims(NamedTuple):
    d: int
    heads: int
    rank: int
    nope: int
    rope: int
    v: int
    ff: int
    ff_expert: int
    experts: int
    top_k: int
    shared: int
    dense: int
    layers: int
    vocab: int
    held: int
    first_held: int
    eps: float
    norm_topk: bool
    routed_scale: float


def dims(c: Mapping) -> Dims:
    return Dims(
        c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
        c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
        c["intermediate_size"], c["moe_intermediate_size"],
        c["n_routed_experts"], c["num_experts_per_tok"],
        c["n_shared_experts"], c["first_k_dense_replace"],
        c["num_hidden_layers"], c["vocab_size"],
        c["n_routed_experts_held"], c["first_held_expert"],
        float(c["rms_norm_eps"]), bool(c["norm_topk_prob"]),
        float(c["routed_scaling_factor"]))


def _attn_shapes(n: int, k: Dims) -> Dict:
    return {"wq": {"w": (n, k.d, k.heads * (k.nope + k.rope))},
            "wkv_a": {"w": (n, k.d, k.rank + k.rope)},
            "kv_norm": {"scale": (n, k.rank)},
            "wkv_b": {"w": (n, k.rank, k.heads * (k.nope + k.v))},
            "wo": {"w": (n, k.heads * k.v, k.d)}}


def weight_shapes(c: Mapping) -> Dict:
    k = dims(c)
    nd, nm = k.dense, k.layers - k.dense
    ffs = k.shared * k.ff_expert

    def block(n):
        return {"ln_attn": {"scale": (n, k.d)}, "attn": _attn_shapes(n, k),
                "ln_mlp": {"scale": (n, k.d)}}
    return {
        "embed": {"emb": (k.vocab, k.d)},
        "dense_layers": dict(block(nd), mlp={
            "gate": {"w": (nd, k.d, k.ff)}, "up": {"w": (nd, k.d, k.ff)},
            "down": {"w": (nd, k.ff, k.d)}}),
        "layers": dict(block(nm), moe={
            "router": {"w": (nm, k.d, k.experts)},
            "w_gate": (nm, k.held, k.d, k.ff_expert),
            "w_up": (nm, k.held, k.d, k.ff_expert),
            "w_down": (nm, k.held, k.ff_expert, k.d),
            "shared": {"gate": {"w": (nm, k.d, ffs)},
                       "up": {"w": (nm, k.d, ffs)},
                       "down": {"w": (nm, ffs, k.d)}}}),
        "ln_f": {"scale": (k.d,)},
        "unembed": {"w": (k.d, k.vocab)},
    }


def _init(path: str, shape, key, dtype):
    """Embedding std 0.02, matrices std 1/sqrt(fan-in), norm scales
    1 + 0.1 N(0, 1)."""
    x = jax.random.normal(key, shape, jnp.float32)
    if path.endswith("scale"):
        x = 1.0 + 0.1 * x
    elif path.startswith("embed"):
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
            _init(p, s, k, dtype)
            for p, (_, s), k in zip(paths, leaves, keys)])
    return make(key)


def yarn(c: Mapping):
    """(inverse frequencies (rope/2,), cos/sin factor, softmax scale) of
    the configuration's YaRN RoPE."""
    s = c["rope_scaling"]
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    factor = float(s["factor"])
    orig = int(s["original_max_position_embeddings"])

    def turns_at(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0
    lo = max(math.floor(turns_at(s["beta_fast"])), 0)
    hi = min(math.ceil(turns_at(s["beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    plain = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    inv = plain / factor * ramp + plain * (1 - ramp)
    m_all = mscale(float(s["mscale_all_dim"]))
    scale = m_all ** 2 / math.sqrt(c["qk_nope_head_dim"] + dim)
    return inv, mscale(float(s["mscale"])) / m_all, scale


def _q8(x):
    """Round to float8_e4m3fn under a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(spec, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, cos, sin):
    """x: (S, H, r); cos/sin: (S, r/2); rotate_half convention."""
    half = x.shape[-1] // 2
    cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, m, fp8):
    g = jax.nn.silu(_mm("sd,df->sf", h, m["gate"]["w"], fp8))
    return _mm("sf,fd->sd", g * _mm("sd,df->sf", h, m["up"]["w"], fp8),
               m["down"]["w"], fp8)


def _attention(x, p, cos, sin, scale, k: Dims, fp8):
    s = x.shape[0]
    a = p["attn"]
    h = _rms(x, p["ln_attn"]["scale"], k.eps)
    q = _mm("sd,df->sf", h, a["wq"]["w"], fp8).reshape(s, k.heads, -1)
    q = jnp.concatenate([q[..., :k.nope], _rope(q[..., k.nope:], cos, sin)],
                        -1)
    kv = _mm("sd,df->sf", h, a["wkv_a"]["w"], fp8)
    c = _rms(kv[:, :k.rank], a["kv_norm"]["scale"], k.eps)
    k_pe = _rope(kv[:, None, k.rank:], cos, sin)               # (S, 1, r)
    kvb = _mm("sr,rf->sf", c, a["wkv_b"]["w"], fp8).reshape(s, k.heads, -1)
    keys = jnp.concatenate(
        [kvb[..., :k.nope], jnp.broadcast_to(k_pe, (s, k.heads, k.rope))], -1)
    vals = kvb[..., k.nope:]
    qb = math.gcd(s, Q_BLOCK)
    kpos = jnp.arange(s)

    def block(i):
        rows = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)     # (qb, H, dq)
        sc = _mm("qhd,khd->hqk", rows, keys, fp8) * scale
        causal = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", pr, vals, fp8)
    o = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, k.heads * k.v)
    return x + _mm("sf,fd->sd", o, a["wo"]["w"], fp8)


def _experts(h, m, k: Dims, fp8):
    """The held routed experts' part of the layer, plus the shared expert."""
    probs = jax.nn.softmax(_mm("sd,de->se", h, m["router"]["w"], fp8), -1)
    top_p, top_i = jax.lax.top_k(probs, k.top_k)
    if k.norm_topk:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    top_p = top_p * k.routed_scale

    def one(acc, e):
        wg, wu, wd, expert = e
        g = jnp.sum(jnp.where(top_i == expert, top_p, 0.0), -1)   # (S,)
        y = _swiglu(h, {"gate": {"w": wg}, "up": {"w": wu},
                        "down": {"w": wd}}, fp8)
        return acc + g[:, None] * y, None
    held = k.first_held + jnp.arange(k.held)
    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (m["w_gate"], m["w_up"], m["w_down"], held))
    return out + _swiglu(h, m["shared"], fp8)


@partial(jax.jit, static_argnames=("k", "scale", "cs", "fp8"))
def _forward(w, tokens, at, inv_freq, *, k: Dims, scale, cs, fp8):
    s = tokens.shape[0]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang) * cs, jnp.sin(ang) * cs
    x = w["embed"]["emb"][tokens]

    def dense_layer(x, p):
        x = _attention(x, p, cos, sin, scale, k, fp8)
        return x + _swiglu(_rms(x, p["ln_mlp"]["scale"], k.eps), p["mlp"],
                           fp8), None

    def moe_layer(x, p):
        x = _attention(x, p, cos, sin, scale, k, fp8)
        return x + _experts(_rms(x, p["ln_mlp"]["scale"], k.eps), p["moe"],
                            k, fp8), None

    x, _ = jax.lax.scan(dense_layer, x, w["dense_layers"])
    x, _ = jax.lax.scan(moe_layer, x, w["layers"])
    xf = _rms(x[at], w["ln_f"]["scale"], k.eps)
    return _mm("kd,dv->kv", xf, w["unembed"]["w"], fp8)


def forward(w, c: Mapping, tokens, at, *, fp8: bool = False):
    """Logits (len(at), vocab) of the sequence ``tokens`` at positions
    ``at``; float32 at ``highest`` precision, or the fp8 control."""
    inv, cs, scale = yarn(c)
    with jax.default_matmul_precision("highest"):
        return _forward(w, tokens, at, jnp.asarray(inv, jnp.float32),
                        k=dims(c), scale=float(scale), cs=float(cs), fp8=fp8)
