"""Decoder-only transformer assembly covering dense / moe / ssm / hybrid /
vlm families with one scan-over-layers implementation.

Modes:
  train    full sequence, teacher forcing, remat-inside-scan
  prefill  full sequence, returns a decode state (KV caches + SSM states)
  decode   one token against the state

Layer heterogeneity (xlstm's mLSTM/sLSTM mix, hymba's global/local attention
mix) is expressed as per-layer flag arrays threaded through the scan, so the
whole depth still compiles as ONE scanned layer (critical for compile time at
95 layers).  The one exception is moe's leading dense layers
(``n_dense_layers``, deepseek-v2's ``first_k_dense_replace``): they hold a
dense MLP where the others hold experts, so they are a stack of their own
(``params["dense_layers"]``), scanned before the expert layers.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.config import ArchConfig
from ..distributed.sharding import Param, logical
from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import (embed, embed_init, linear, linear_init, mlp, mlp_init,
                     norm, norm_init, padded_heads, padded_vocab)


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ArchConfig, dense: bool = False):
    """One layer; ``dense`` makes a moe model's leading dense layer."""
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {}
    fam = cfg.family
    if fam in ("dense", "moe", "vlm", "hybrid"):
        p["ln_attn"] = norm_init(cfg.d_model, cfg.norm)
        p["attn"] = (attn.mla_init(ks[0], cfg) if cfg.attn.mla
                     else attn.attn_init(ks[0], cfg))
        if not cfg.parallel_residual:
            p["ln_mlp"] = norm_init(cfg.d_model, cfg.norm)
        if cfg.moe.enabled and not dense:
            p["moe"] = moe_mod.moe_init(ks[1], cfg)
        elif cfg.d_ff > 0:
            p["mlp"] = mlp_init(ks[1], cfg)
    if fam == "hybrid":
        d_inner = cfg.ssm.expand * cfg.d_model
        p["mamba"] = ssm_mod.mamba_init(ks[2], cfg, d_inner)
    if fam == "ssm":
        p["ln"] = norm_init(cfg.d_model, cfg.norm)
        d_inner = cfg.ssm.expand * cfg.d_model
        p["mlstm"] = ssm_mod.mlstm_init(ks[3], cfg, d_inner, cfg.n_heads)
    return p


def _slstm_layer_init(key, cfg: ArchConfig):
    return {"ln_s": norm_init(cfg.d_model, cfg.norm),
            "slstm": ssm_mod.slstm_init(key, cfg, cfg.n_heads)}


def ssm_layer_counts(cfg: ArchConfig) -> Tuple[int, int]:
    """(n_mlstm, n_slstm) for the xLSTM 7:1-style interleave."""
    L = cfg.n_layers
    if cfg.family != "ssm" or cfg.ssm.slstm_every <= 0:
        return L, 0
    n_s = L // cfg.ssm.slstm_every
    return L - n_s, n_s


def layer_flags(cfg: ArchConfig) -> Dict[str, np.ndarray]:
    """Static per-layer flag arrays threaded through the scan."""
    L = cfg.n_layers
    flags: Dict[str, np.ndarray] = {}
    if cfg.family == "hybrid":
        # hymba: global (full) attention on first / middle / last layer
        g = np.zeros((L,), np.bool_)
        g[[0, L // 2, L - 1]] = True
        flags["global_attn"] = g
    return flags


# ---------------------------------------------------------------------------
# Decode state
# ---------------------------------------------------------------------------

class State(NamedTuple):
    """Stacked-over-layers decode state.  Unused fields hold size-0 arrays so
    the pytree structure is uniform across families."""
    k: jax.Array
    v: jax.Array
    kpos: jax.Array
    mlstm_c: jax.Array
    mlstm_n: jax.Array
    mlstm_m: jax.Array
    slstm: jax.Array          # (4, L, B, H, dh): c, n, m, h
    mamba: jax.Array          # (L, B, D, N)
    pos: jax.Array            # (B,) next absolute position


def _z(*shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def kv_layout(cfg: ArchConfig) -> Tuple[int, int, int]:
    """(heads, K width, V width) of a cached token: latent attention
    caches one latent row (``attn.mla_row_width``) as one "head" beside an
    empty V."""
    if cfg.attn.mla:
        return 1, attn.mla_row_width(cfg), 0
    return cfg.n_kv_heads, cfg.head_dim_, cfg.head_dim_


def init_state(cfg: ArchConfig, batch: int, budget: int,
               dtype=jnp.bfloat16) -> State:
    L, d = cfg.n_layers, cfg.d_model
    has_attn = cfg.family in ("dense", "moe", "vlm", "hybrid", "encdec")
    nkv, hk, hv = kv_layout(cfg)
    w = budget if has_attn else 0
    if cfg.family == "ssm":
        n_m, n_s = ssm_layer_counts(cfg)
        dh = cfg.ssm.expand * d // cfg.n_heads
        dhs = d // cfg.n_heads
        ml_c = _z(n_m, batch, cfg.n_heads, dh, dh)
        ml_n = _z(n_m, batch, cfg.n_heads, dh)
        ml_m = jnp.full((n_m, batch, cfg.n_heads), -1e30, jnp.float32)
        sl = _z(4, n_s, batch, cfg.n_heads, dhs).at[2].set(-1e30)
    else:
        ml_c = _z(L, 0, 0, 0, 0)
        ml_n = _z(L, 0, 0)
        ml_m = _z(L, 0, 0)
        sl = _z(4, L, 0, 0, 0)
    if cfg.family == "hybrid":
        mam = _z(L, batch, cfg.ssm.expand * d, cfg.ssm.d_state)
    else:
        mam = _z(L, 0, 0, 0)
    return State(
        k=_z(L, batch, w, nkv, hk, dtype=dtype),
        v=_z(L, batch, w, nkv, hv, dtype=dtype),
        kpos=jnp.full((L, batch, w), -1, jnp.int32),
        mlstm_c=ml_c, mlstm_n=ml_n, mlstm_m=ml_m, slstm=sl, mamba=mam,
        pos=jnp.zeros((batch,), jnp.int32),
    )


def state_axes() -> State:
    """Logical axes for sharding the decode state."""
    return State(
        k=(None, "batch", "kvlen", "kv", None),
        v=(None, "batch", "kvlen", "kv", None),
        kpos=(None, "batch", "kvlen"),
        mlstm_c=(None, "batch", None, None, None),
        mlstm_n=(None, "batch", None, None),
        mlstm_m=(None, "batch", None),
        slstm=(None, None, "batch", None, None),
        mamba=(None, "batch", "heads", None),
        pos=("batch",),
    )


def _constrain_state(st: State) -> State:
    ax = state_axes()
    return State(*[logical(v, *a) for v, a in zip(st, ax)])


# ---------------------------------------------------------------------------
# Layer apply
# ---------------------------------------------------------------------------

def _attn_block(p, x, cfg, positions, mode, cache, global_flag, cdt):
    """Returns (out, new_cache).  cache = (k, v, kpos) single-layer or None.
    Under latent attention ``k`` caches the latent rows and ``v`` is
    empty."""
    window = cfg.attn.window
    hp = padded_heads(cfg)
    idx_map = attn.kv_index_map(cfg.n_heads, cfg.n_kv_heads, hp)
    mla = cfg.attn.mla
    if mla:
        q_nope, q_pe, k = attn.mla_project(p, x, cfg, positions, cdt)
        v = k[..., :0]
    else:
        q, k, v = attn.qkv_project(p, x, cfg, positions, cdt)
    new_cache = cache
    if mode == "decode":
        ck, cv, cpos = cache
        ck, cv, cpos = attn.update_cache_layer(ck, cv, cpos, k, v, positions)
        if mla:
            out_h = attn.mla_attend_decode(p, q_nope, q_pe, ck, cpos, cfg,
                                           positions[:, 0], cdt)
        else:
            out_h = attn.attend_decode(
                q, ck, cv, cpos, idx_map, q_position=positions[:, 0],
                window=window, global_flag=global_flag)
        new_cache = (ck, cv, cpos)
    else:
        if mla:
            out_h = attn.mla_attend_self(p, q_nope, q_pe, k, cfg, cdt)
        else:
            out_h = attn.attend_chunked(
                q, k, v, idx_map, causal=cfg.attn.kind != "none",
                window=window, chunk=cfg.attn.chunk,
                global_flag=global_flag)
        if mode == "prefill":
            ck, cv, cpos = cache
            w = ck.shape[1]
            s = k.shape[1]
            if s >= w:
                tail = slice(s - w, s)
                ck, cv, cpos = attn.update_cache_layer(
                    ck, cv, cpos, k[:, tail], v[:, tail],
                    positions[:, tail])
            else:
                ck, cv, cpos = attn.update_cache_layer(
                    ck, cv, cpos, k, v, positions)
            new_cache = (ck, cv, cpos)
    out = attn.attn_out(p, out_h, cfg, cdt)
    return out, new_cache


def _ffn_residual(p, x, h, attn_out, cfg: ArchConfig, cdt, valid=None):
    """Residual + FFN tail shared by every attention family (dense / moe /
    vlm), in both the dense-cache and paged decode paths.  ``h`` is the
    pre-attention normed input (reused by parallel-residual archs).
    Returns (x, aux, picks): an expert layer's per-expert count of the
    ``valid`` (B, S) tokens' picks, else None."""
    aux = jnp.zeros((), jnp.float32)
    picks = None

    def ffn(h):
        nonlocal aux, picks
        if "moe" in p:
            ff, aux, picks = moe_mod.moe_apply(p["moe"], h, cfg, cdt, valid)
            return ff
        return mlp(p["mlp"], h, cfg.act, cdt)

    if cfg.parallel_residual:
        x = x + attn_out + ffn(h)
    else:
        x = x + attn_out
        x = x + ffn(norm(p["ln_mlp"], x))
    return logical(x, "batch", "seq", "residual"), aux, picks


def make_layer_fn(cfg: ArchConfig, mode: str):
    cdt = jnp.dtype(cfg.dtype)

    def layer(x, per):
        p, cache, flags = per
        aux = jnp.zeros((), jnp.float32)
        positions = flags["positions"]
        fam = cfg.family

        if fam == "ssm":
            # mLSTM-only layer; sLSTM layers run in the interleaved stack
            # (see _ssm_forward) — no lax.cond, so cost attribution is exact
            st_m = ssm_mod.MLSTMState(cache["mc"], cache["mn"], cache["mm"])
            h = norm(p["ln"], x)
            out, st = ssm_mod.mlstm_block(
                p["mlstm"], h, cfg, st_m, mode=mode,
                n_heads=cfg.n_heads, compute_dtype=cdt)
            x = x + out
            new_cache = dict(cache, mc=st.c, mn=st.n, mm=st.m)
            return x, (new_cache, aux)

        # families with attention
        gflag = flags.get("global_attn")
        h = norm(p["ln_attn"], x)
        attn_out, new_kv = _attn_block(
            p["attn"], h, cfg, positions, mode,
            (cache["k"], cache["v"], cache["kp"]), gflag, cdt)
        new_cache = dict(cache, k=new_kv[0], v=new_kv[1], kp=new_kv[2])

        if fam == "hybrid":
            st = ssm_mod.MambaState(cache["mb"])
            mamba_out, st2 = ssm_mod.mamba_apply(
                p["mamba"], h, cfg, st, mode=mode, compute_dtype=cdt)
            mixed = (attn_out + mamba_out) * 0.5
            new_cache["mb"] = st2.s
            x = x + mixed
            h2 = norm(p["ln_mlp"], x)
            x = x + mlp(p["mlp"], h2, cfg.act, cdt)
            return x, (new_cache, aux)

        x, aux, _ = _ffn_residual(p, x, h, attn_out, cfg, cdt)
        return x, (new_cache, aux)

    return layer


def _cache_tree(cfg: ArchConfig, st: State):
    """Per-layer cache dict (leading L dim) fed to the scan as xs."""
    return {"k": st.k, "v": st.v, "kp": st.kpos,
            "mc": st.mlstm_c, "mn": st.mlstm_n, "mm": st.mlstm_m,
            "sl": jnp.moveaxis(st.slstm, 0, 1),   # (L,4,...)
            "mb": st.mamba}


def _state_from_cache(cfg: ArchConfig, cache, pos) -> State:
    return State(
        k=cache["k"], v=cache["v"], kpos=cache["kp"],
        mlstm_c=cache["mc"], mlstm_n=cache["mn"], mlstm_m=cache["mm"],
        slstm=jnp.moveaxis(cache["sl"], 1, 0),
        mamba=cache["mb"], pos=pos)


def _flags_tree(cfg: ArchConfig, positions):
    """Per-layer flags; ``positions`` is shared (broadcast to every layer)."""
    f = layer_flags(cfg)
    out = {k: jnp.asarray(v) for k, v in f.items()}
    return out


# ---------------------------------------------------------------------------
# Full model init / apply
# ---------------------------------------------------------------------------

def stack_init(fn, key, n: int, cfg: ArchConfig):
    """vmap-stack ``n`` layers of ``fn(key, cfg)``; annotations (strings)
    cannot pass through vmap, so init strips them (capturing the static axes
    tree as a tracing side-channel) and re-annotates after."""
    from ..distributed.sharding import split_tree
    axes_box = {}

    def stripped(k):
        vals, axes = split_tree(fn(k, cfg))
        axes_box["axes"] = axes
        return vals

    stacked_vals = jax.vmap(stripped)(jax.random.split(key, n))
    return jax.tree.map(
        lambda arr, ax: Param(arr, (None,) + ax),
        stacked_vals, axes_box["axes"])


def transformer_init(key, cfg: ArchConfig):
    ks = jax.random.split(key, 5)
    L = cfg.n_layers
    n_m, n_s = ssm_layer_counts(cfg)
    nd = cfg.n_dense_layers
    stacked = stack_init(_layer_init, ks[0], n_m if cfg.family == "ssm"
                         else L - nd, cfg)
    p = {
        "embed": embed_init(ks[1], padded_vocab(cfg), cfg.d_model),
        "layers": stacked,
        "ln_f": norm_init(cfg.d_model, cfg.norm),
    }
    if nd > 0:
        p["dense_layers"] = stack_init(
            functools.partial(_layer_init, dense=True),
            jax.random.fold_in(ks[0], 1), nd, cfg)
    if n_s > 0:
        p["slstm_layers"] = stack_init(_slstm_layer_init, ks[4], n_s, cfg)
    if not cfg.tie_embeddings:
        p["unembed"] = linear_init(ks[2], cfg.d_model, padded_vocab(cfg),
                                   ("embed", "vocab"))
    if cfg.n_patches > 0:
        p["patch_proj"] = linear_init(ks[3], cfg.d_model, cfg.d_model,
                                      ("embed", "embed2"))
    return p


def sinusoid(positions, d: int):
    half = d // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                   / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _embed_inputs(p, cfg: ArchConfig, tokens, patches, positions, cdt):
    x = embed(p["embed"], tokens, cdt)
    if cfg.n_patches > 0 and patches is not None:
        pe = linear(p["patch_proj"], patches.astype(cdt), cdt)
        x = jnp.concatenate([pe, x], axis=1)
    if cfg.attn.rope_theta == 0:
        x = x + sinusoid(positions, cfg.d_model).astype(cdt)
    return logical(x, "batch", "seq", "residual")


def unembed(p, cfg: ArchConfig, x):
    xf = norm(p["ln_f"], x)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", xf.astype(jnp.float32),
                            p["embed"]["emb"].astype(jnp.float32))
    else:
        w = p["unembed"]["w"]
        logits = jnp.einsum("bsd,dv->bsv", xf.astype(jnp.float32),
                            w.astype(jnp.float32))
    # mask vocab-padding slots (vocab padded up for TP divisibility)
    vp = logits.shape[-1]
    if vp != cfg.vocab:
        logits = jnp.where(jnp.arange(vp) < cfg.vocab, logits, -1e30)
    return logical(logits, "batch", "seq", "vocab")


def _ssm_forward(params, cfg: ArchConfig, x, state: State, *, mode: str,
                 positions, remat: bool):
    """Interleaved xLSTM stack: groups of (every-1) mLSTM layers + 1 sLSTM.
    The group scan doubles as hierarchical remat (group inputs saved)."""
    cdt = jnp.dtype(cfg.dtype)
    every = cfg.ssm.slstm_every
    L = cfg.n_layers
    n_m, n_s = ssm_layer_counts(cfg)
    layer_fn = make_layer_fn(cfg, mode)
    do_ckpt = remat and mode == "train"
    if do_ckpt:
        layer_fn = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.nothing_saveable)

    def mlstm_scan(x, stacks):
        def body(x, per):
            p_l, (mc, mn, mm) = per
            cache = {"mc": mc, "mn": mn, "mm": mm}
            x, (nc, _) = layer_fn(x, (p_l, cache, {"positions": positions}))
            return x, (nc["mc"], nc["mn"], nc["mm"])
        return jax.lax.scan(body, x, stacks)

    def slstm_apply(x, p_l, sl):
        st = ssm_mod.SLSTMState(sl[0], sl[1], sl[2], sl[3])
        h = norm(p_l["ln_s"], x)
        out, st2 = ssm_mod.slstm_block(
            p_l["slstm"], h, cfg, st, mode=mode, n_heads=cfg.n_heads,
            compute_dtype=cdt)
        return x + out, jnp.stack(list(st2))
    if do_ckpt:
        slstm_apply = jax.checkpoint(
            slstm_apply, policy=jax.checkpoint_policies.nothing_saveable)

    m_states = (state.mlstm_c, state.mlstm_n, state.mlstm_m)
    if n_s == 0:
        x, new_m = mlstm_scan(x, (params["layers"], m_states))
        new_sl = state.slstm
    else:
        groups = n_s
        per_g = every - 1
        regroup = lambda t: t.reshape(groups, per_g, *t.shape[1:])
        pm = jax.tree.map(regroup, params["layers"])
        sm = jax.tree.map(regroup, m_states)
        sl = jnp.moveaxis(state.slstm, 1, 0)            # (n_s, 4, ...)

        def group_body(x, per):
            pm_g, sm_g, ps_g, sl_g = per
            x, new_sm = mlstm_scan(x, (pm_g, sm_g))
            x, new_sl = slstm_apply(x, ps_g, sl_g)
            return x, (new_sm, new_sl)

        if do_ckpt:
            group_body = jax.checkpoint(
                group_body, policy=jax.checkpoint_policies.nothing_saveable)
        x, (new_m_g, new_sl_g) = jax.lax.scan(
            group_body, x, (pm, sm, params["slstm_layers"], sl))
        new_m = jax.tree.map(
            lambda t: t.reshape(n_m, *t.shape[2:]), new_m_g)
        new_sl = jnp.moveaxis(new_sl_g, 0, 1)           # (4, n_s, ...)

    new_state = State(
        k=state.k, v=state.v, kpos=state.kpos,
        mlstm_c=new_m[0], mlstm_n=new_m[1], mlstm_m=new_m[2],
        slstm=new_sl, mamba=state.mamba, pos=positions[:, -1] + 1)
    return x, new_state


def _remat_group(L: int) -> int:
    """Largest divisor of L not exceeding ~sqrt(L) (hierarchical remat)."""
    limit = max(2, int(math.isqrt(L)) + 1)
    best = 1
    for g in range(2, limit + 1):
        if L % g == 0:
            best = g
    return best if L // best > 1 else 1


def _layer_stacks(params, cfg: ArchConfig):
    """(stacked params, first layer index, layer count) of each stack in
    depth order: a moe model's leading dense layers, then the rest."""
    nd = cfg.n_dense_layers
    rest = jax.tree.leaves(params["layers"])[0].shape[0]
    return ([(params["dense_layers"], 0, nd)] if nd > 0 else []) + [
        (params["layers"], nd, rest)]


def forward(params, cfg: ArchConfig, tokens, *, patches=None,
            mode: str = "train", state: Optional[State] = None,
            remat: bool = True, budget: Optional[int] = None):
    """Returns (logits, new_state_or_None, aux_loss).  ``budget`` sets the
    KV-cache length a prefill allocates (>= prompt + planned new tokens)."""
    cdt = jnp.dtype(cfg.dtype)
    b = tokens.shape[0]
    if mode == "decode":
        assert state is not None
        positions = state.pos[:, None]                 # (B, 1)
    else:
        s_tok = tokens.shape[1]
        extra = cfg.n_patches if patches is not None else 0
        positions = jnp.broadcast_to(
            jnp.arange(s_tok + extra, dtype=jnp.int32)[None], (b, s_tok + extra))
    x = _embed_inputs(params, cfg, tokens, patches, positions, cdt)

    layer_fn = make_layer_fn(cfg, mode)
    if remat and mode == "train":
        layer_fn = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.nothing_saveable)

    if state is None:
        # train needs no KV budget (fresh k/v per layer); prefill caches at
        # least the prompt (callers pass headroom for the decode phase)
        w = 0 if mode == "train" else max(budget or 0, x.shape[1])
        state = init_state(cfg, b, budget=w, dtype=cdt)

    if cfg.family == "ssm":
        x, new_state = _ssm_forward(params, cfg, x, state, mode=mode,
                                    positions=positions, remat=remat)
        logits = unembed(params, cfg, x)
        if mode != "train":
            new_state = _constrain_state(new_state)
        return logits, new_state, jnp.zeros((), jnp.float32)

    flags = _flags_tree(cfg, positions)
    L = cfg.n_layers

    if mode in ("prefill", "decode"):
        # serving: the KV cache is a scan CARRY updated in place (XLA's
        # in-loop dynamic-update-slice aliasing) — stacking it through
        # scan xs/ys would hold 2-3 cache-sized temps per step
        def serve_body(carry, per):
            x, K, V, KP = carry
            p_l, mb_l, f_l, i = per
            c_l = {
                "k": jax.lax.dynamic_index_in_dim(K, i, 0, keepdims=False),
                "v": jax.lax.dynamic_index_in_dim(V, i, 0, keepdims=False),
                "kp": jax.lax.dynamic_index_in_dim(KP, i, 0, keepdims=False),
                "mb": mb_l,
            }
            f_l = dict(f_l, positions=positions)
            x, (nc, aux) = layer_fn(x, (p_l, c_l, f_l))
            K = jax.lax.dynamic_update_index_in_dim(K, nc["k"], i, 0)
            V = jax.lax.dynamic_update_index_in_dim(V, nc["v"], i, 0)
            KP = jax.lax.dynamic_update_index_in_dim(KP, nc["kp"], i, 0)
            return (x, K, V, KP), (nc["mb"], aux)

        carry = (x, state.k, state.v, state.kpos)
        new_mb, auxs = [], []
        for stack, first, n in _layer_stacks(params, cfg):
            part = slice(first, first + n)
            carry, (mb, aux) = jax.lax.scan(serve_body, carry, (
                stack, state.mamba[part],
                {k: v[part] for k, v in flags.items()},
                jnp.arange(first, first + n, dtype=jnp.int32)))
            new_mb.append(mb)
            auxs.append(jnp.sum(aux))
        x, K, V, KP = carry
        logits = unembed(params, cfg, x)
        new_state = State(
            k=K, v=V, kpos=KP,
            mlstm_c=state.mlstm_c, mlstm_n=state.mlstm_n,
            mlstm_m=state.mlstm_m, slstm=state.slstm,
            mamba=jnp.concatenate(new_mb),
            pos=positions[:, -1] + 1)
        return logits, _constrain_state(new_state), sum(auxs)

    # training path
    cache = _cache_tree(cfg, state)

    def scan_body(x, per_layer):
        p_l, c_l, f_l = per_layer
        f_l = dict(f_l, positions=positions)
        return layer_fn(x, (p_l, c_l, f_l))

    aux_dense = jnp.zeros((), jnp.float32)
    nd = cfg.n_dense_layers
    if nd > 0:
        x, (_, aux_d) = jax.lax.scan(scan_body, x, (
            params["dense_layers"], jax.tree.map(lambda t: t[:nd], cache),
            jax.tree.map(lambda t: t[:nd], flags)))
        aux_dense = jnp.sum(aux_d)
        cache = jax.tree.map(lambda t: t[nd:], cache)
        flags = jax.tree.map(lambda t: t[nd:], flags)
        L -= nd

    g = _remat_group(L) if remat else 1
    if g > 1:
        # hierarchical (sqrt-L) remat: only L/g group-boundary activations
        # are saved; layers inside a group recompute from the group input
        # (deepseek-67b train: 6.1 GB of saved layer inputs -> ~1.2 GB).
        def regroup(t):
            return t.reshape(L // g, g, *t.shape[1:])
        xs = jax.tree.map(regroup, (params["layers"], cache, flags))

        @functools.partial(jax.checkpoint,
                           policy=jax.checkpoint_policies.nothing_saveable)
        def group_body(x, per_group):
            return jax.lax.scan(scan_body, x, per_group)

        x, (new_cache, auxs) = jax.lax.scan(group_body, x, xs)
        auxs = auxs.reshape(L)
    else:
        x, (new_cache, auxs) = jax.lax.scan(
            scan_body, x, (params["layers"], cache, flags))
    logits = unembed(params, cfg, x)
    return logits, None, jnp.sum(auxs) + aux_dense


# ---------------------------------------------------------------------------
# Paged decode (continuous batching over a block-arena KV cache)
# ---------------------------------------------------------------------------

#: families the paged decode path supports (attention-only decode state; the
#: recurrent families carry extra per-layer state a block arena doesn't hold)
PAGED_FAMILIES = ("dense", "moe", "vlm")


class PagedState(NamedTuple):
    """Block-arena KV cache shared by all batch slots.  ``k``/``v``:
    (L, n_blocks, block_len, KV, hd); ``pos``: (n_blocks, block_len)
    absolute position of each row (-1 = empty).  Positions are identical
    across layers, so one plane serves the whole stack.  Block 0 is the
    scratch block inactive slots write into (see models.attention).
    Under latent attention ``k`` is the latent arena, one "head" of
    ``attn.mla_row_width`` columns, and ``v`` is empty (``kv_layout``).

    ``picks`` is an output of the paged steps, never held in the arena:
    how many of the step's tokens each expert layer routed to each expert,
    (n_expert_layers, n_experts) int32, or None (no experts, or the
    arena itself)."""
    k: jax.Array
    v: jax.Array
    pos: jax.Array
    picks: Optional[jax.Array] = None


def init_paged_state(cfg: ArchConfig, n_blocks: int, block_len: int,
                     dtype=None) -> PagedState:
    if cfg.family not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"paged decode supports families {PAGED_FAMILIES}, "
            f"not {cfg.family!r}")
    dtype = jnp.dtype(cfg.dtype) if dtype is None else dtype
    nkv, hk, hv = kv_layout(cfg)
    lead = (cfg.n_layers, n_blocks, block_len, nkv)
    return PagedState(k=jnp.zeros(lead + (hk,), dtype),
                      v=jnp.zeros(lead + (hv,), dtype),
                      pos=jnp.full((n_blocks, block_len), -1, jnp.int32))


def _paged_layers(params, cfg: ArchConfig, body, x, paged: PagedState):
    """Run ``body(carry, (p_l, i))`` over every layer stack with the arena
    as the carry; returns (x, K, V, picks of the expert layers)."""
    carry, picks = (x, paged.k, paged.v), None
    for stack, first, n in _layer_stacks(params, cfg):
        carry, picks = jax.lax.scan(
            body, carry, (stack, jnp.arange(first, first + n,
                                            dtype=jnp.int32)))
    return carry + (picks,)


def _paged_attention(p_l, h, cfg: ArchConfig, positions, K, V, write,
                     attend, cdt):
    """Project one layer's queries and new cache rows, ``write`` the rows
    into the whole arena, then ``attend``: (q, K, V) for standard
    attention, or (q_nope, q_pe), K, V under latent attention.  Both index
    the layer's blocks in the whole arena: a slice of the layer and its
    write-back would cost a pass over the layer's arena each.
    Returns (attention heads, K, V)."""
    if cfg.attn.mla:
        q_nope, q_pe, k_new = attn.mla_project(p_l["attn"], h, cfg,
                                               positions, cdt)
        q, v_new = (q_nope, q_pe), k_new[..., :0]
    else:
        q, k_new, v_new = attn.qkv_project(p_l["attn"], h, cfg, positions,
                                           cdt)
    K, V = write(K, V, k_new, v_new)
    return attend(q, K, V), K, V


def forward_paged_decode(params, cfg: ArchConfig, tokens, paged: PagedState,
                         block_table, slot_pos):
    """One decode step for ``B`` independent slots over the block arena.

    tokens: (B, 1) int32 (each slot's previous token); block_table: (B, MB)
    int32 block ids, -1 = unused; slot_pos: (B,) each slot's next absolute
    position.  Unlike the dense-cache decode, slots need NOT share a
    position — each writes at its own (block, row) and attends only rows
    whose gathered position is in [0, its own position].  Returns
    (last-token logits, new PagedState); the state's ``picks`` count the
    routing of the slots whose table row is in use."""
    if cfg.family not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"paged decode supports families {PAGED_FAMILIES}, "
            f"not {cfg.family!r}")
    cdt = jnp.dtype(cfg.dtype)
    b = tokens.shape[0]
    bl = paged.pos.shape[1]
    positions = slot_pos[:, None]                       # (B, 1)
    x = _embed_inputs(params, cfg, tokens, None, positions, cdt)

    # this step's write target per slot; inactive slots (table entry -1)
    # clamp to the scratch block 0, whose rows are never attended
    blk = jnp.take_along_axis(block_table,
                              (slot_pos // bl)[:, None], axis=1)[:, 0]
    blk = jnp.maximum(blk, 0)
    off = slot_pos % bl
    pos_blocks = paged.pos.at[blk, off].set(slot_pos)
    live = (block_table[:, 0] >= 0)[:, None]            # (B, 1)

    hp = padded_heads(cfg)
    idx_map = attn.kv_index_map(cfg.n_heads, cfg.n_kv_heads, hp)

    def body(carry, per):
        x, K, V = carry
        p_l, i = per

        def write(K, V, k_new, v_new):
            return attn.append_paged_layer(K, V, k_new, v_new, blk, off, i)

        def attend(q, K, V):
            if cfg.attn.mla:
                kd, _, pd = attn.gather_paged_view(K, V, pos_blocks,
                                                   block_table, i)
                return attn.mla_attend_decode(p_l["attn"], *q, kd, pd, cfg,
                                              slot_pos, cdt)
            return attn.attend_paged(
                q, K, V, pos_blocks, block_table, idx_map,
                layer=i, q_position=slot_pos, window=cfg.attn.window)
        h = norm(p_l["ln_attn"], x)
        out_h, K, V = _paged_attention(p_l, h, cfg, positions, K, V, write,
                                       attend, cdt)
        attn_o = attn.attn_out(p_l["attn"], out_h, cfg, cdt)
        x, _, picks = _ffn_residual(p_l, x, h, attn_o, cfg, cdt, live)
        return (x, K, V), picks

    x, K, V, picks = _paged_layers(params, cfg, body, x, paged)
    logits = unembed(params, cfg, x)
    return logits[:, -1], PagedState(k=K, v=V, pos=pos_blocks, picks=picks)


def forward_paged_chunk(params, cfg: ArchConfig, tokens, paged: PagedState,
                        block_table, start, n_real):
    """One prefill CHUNK for a single slot over the block arena.

    tokens: (1, C) int32 — rows ``[0, n_real)`` are the real chunk, the
    rest is pow2-bucket padding; block_table: (1, MB) the slot's table
    (-1 = unused); start: () int32 the chunk's first absolute row;
    n_real: () int32 real-row count (1 <= n_real <= C).  Writes the real
    rows' K/V into the slot's blocks (pad rows land in scratch block 0
    with position -1, so they are never attended) and returns
    (logits of row start+n_real-1, shape (1, vocab_p), new PagedState,
    whose ``picks`` count the real rows' routing).

    Numerics: K/V/FFN are per-row and attention goes through
    ``attend_prefix``'s full masked softmax over the gathered MB*BL view,
    so row values do not depend on the chunk decomposition — chunked,
    shared-prefix, and solo prefill agree bit-for-bit (the equivalence
    tests' anchor).  Note this is a *different* decomposition from the
    monolithic ``prefill`` path's online-softmax ``attend_chunked``, so
    chunked mode is only bit-comparable to chunked-mode oracles."""
    if cfg.family not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"paged chunk prefill supports families {PAGED_FAMILIES}, "
            f"not {cfg.family!r}")
    cdt = jnp.dtype(cfg.dtype)
    c = tokens.shape[1]
    bl = paged.pos.shape[1]
    mb = block_table.shape[1]
    offs = jnp.arange(c, dtype=jnp.int32)
    positions = start + offs                            # (C,)
    valid = offs < n_real
    pos_q = positions[None, :]                          # (1, C)
    x = _embed_inputs(params, cfg, tokens, None, pos_q, cdt)

    # per-row write targets; pad rows clamp to scratch block 0 (their
    # position row is forced to -1, so last-wins scatter races among pad
    # rows at (0, 0) are harmless)
    bt = block_table[0]                                 # (MB,)
    bidx = jnp.clip(positions // bl, 0, mb - 1)
    blk = jnp.where(valid, jnp.maximum(bt[bidx], 0), 0)
    off = jnp.where(valid, positions % bl, 0)
    pos_blocks = paged.pos.at[blk, off].set(
        jnp.where(valid, positions, -1))

    hp = padded_heads(cfg)
    idx_map = attn.kv_index_map(cfg.n_heads, cfg.n_kv_heads, hp)

    def body(carry, per):
        x, K, V = carry
        p_l, i = per

        def write(K, V, k_new, v_new):
            return (K.at[i, blk, off].set(k_new[0]),
                    V.at[i, blk, off].set(v_new[0]))

        def attend(q, K, V):
            kd, vd, pd = attn.gather_paged_view(K, V, pos_blocks,
                                                block_table, i)
            if cfg.attn.mla:
                return attn.mla_attend_prefix(p_l["attn"], *q, kd, pd, cfg,
                                              pos_q, cdt)
            return attn.attend_prefix(q, kd, vd, pd, idx_map,
                                      q_positions=pos_q,
                                      window=cfg.attn.window)
        h = norm(p_l["ln_attn"], x)
        out_h, K, V = _paged_attention(p_l, h, cfg, pos_q, K, V, write,
                                       attend, cdt)
        attn_o = attn.attn_out(p_l["attn"], out_h, cfg, cdt)
        x, _, picks = _ffn_residual(p_l, x, h, attn_o, cfg, cdt,
                                    valid[None, :])
        return (x, K, V), picks

    x, K, V, picks = _paged_layers(params, cfg, body, x, paged)
    x_last = jax.lax.dynamic_slice_in_dim(
        x, jnp.maximum(n_real - 1, 0), 1, axis=1)       # (1, 1, d)
    logits = unembed(params, cfg, x_last)
    return logits[:, -1], PagedState(k=K, v=V, pos=pos_blocks, picks=picks)
