"""Mixture-of-Experts with expert parallelism.

Design (scales to qwen3-moe-235b on a 256-chip pod):

* Expert weights are stacked (E, d, ff) and sharded **two ways**: the expert
  dim over the "model" axis (expert parallelism, E/TP experts resident per
  chip) and the ff dim over the data axes (FSDP storage — 908 GB of fp32
  expert params for qwen3-moe would not fit per-chip otherwise).
* The block runs under ``jax.shard_map``: tokens arrive batch-sharded and
  model-replicated; each program all-gathers its local experts' ff shards
  (bf16) — the FSDP weight gather that XLA overlaps with compute — routes
  all local tokens, and dispatches *sort-based* (argsort by expert id +
  capacity clipping) into an (E_local, C, d) buffer: no O(T x E x C)
  one-hot dispatch tensors.
* Partial outputs psum over "model"; the backward pass reverses the gathers
  into reduce-scatters automatically.

Token-choice top-k routing with capacity factor + load-balance aux loss
(Switch-style).  Shared experts (qwen2-moe) fold into one fused dense MLP
(concatenated hidden = exact) with a sigmoid gate, or none (deepseek-v2).

With ``capacity_factor`` 0 no token is dropped: each held expert has room
for every token of the call, so a token's output never depends on which
other tokens share its step.  A layer may hold only some of the routed
experts (``MoEConfig.expert_lo`` / ``n_held``: one chip's share under
expert parallelism): the router still scores all of them and picks its
top-k, and the layer adds only the held experts' part of the result.  It
also returns how many of the call's valid tokens picked each expert.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.config import ArchConfig
from ..distributed import sharding as shd
from ..distributed.sharding import Param, logical
from .layers import linear, linear_init


def moe_init(key, cfg: ArchConfig):
    d = cfg.d_model
    e = cfg.moe
    ks = jax.random.split(key, 6)
    n_e = padded_experts(cfg)
    n_w = held_experts(cfg)[1]
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": {"w": Param(
            jax.random.normal(ks[0], (d, n_e), jnp.float32) * scale,
            ("embed", None))},
        "w_gate": Param(
            jax.random.normal(ks[1], (n_w, d, e.d_ff_expert), jnp.float32)
            * scale, ("experts", "embed", "expert_shard")),
        "w_up": Param(
            jax.random.normal(ks[2], (n_w, d, e.d_ff_expert), jnp.float32)
            * scale, ("experts", "embed", "expert_shard")),
        "w_down": Param(
            jax.random.normal(ks[3], (n_w, e.d_ff_expert, d), jnp.float32)
            / math.sqrt(e.d_ff_expert), ("experts", "expert_shard", "embed")),
    }
    if e.n_shared > 0:
        ff_shared = e.n_shared * e.d_ff_expert
        p["shared"] = {
            "gate": linear_init(ks[4], d, ff_shared, ("embed", "mlp")),
            "up": linear_init(ks[5], d, ff_shared, ("embed", "mlp")),
            "down": linear_init(jax.random.fold_in(ks[5], 1), ff_shared, d,
                                ("mlp", "embed")),
        }
        if e.shared_gate:
            p["shared"]["sgate"] = linear_init(
                jax.random.fold_in(ks[4], 1), d, 1, ("embed", None))
    return p


def held_experts(cfg: ArchConfig) -> Tuple[int, int]:
    """(first, count) of the routed experts whose weights this layer
    holds: the configured share, else every (padded) expert."""
    e = cfg.moe
    if e.n_held:
        if e.expert_lo < 0 or e.expert_lo + e.n_held > e.n_experts:
            raise ValueError(f"held experts [{e.expert_lo}, "
                             f"{e.expert_lo + e.n_held}) outside "
                             f"{e.n_experts}")
        return e.expert_lo, e.n_held
    return 0, padded_experts(cfg)


def capacity(cfg: ArchConfig, n_tokens: int) -> int:
    """Rows each held expert computes for a call over ``n_tokens`` tokens
    (padding included): every token when no token may be dropped."""
    e = cfg.moe
    if e.capacity_factor <= 0:
        return n_tokens
    return max(int(math.ceil(n_tokens * e.top_k / e.n_experts
                             * e.capacity_factor)), 4)


def padded_experts(cfg: ArchConfig) -> int:
    """Pad expert count to the EP degree (qwen2-moe: 60 -> 64 on TP=16);
    padded experts are masked to -inf router logits."""
    ep = shd.axis_size("experts")
    n = cfg.moe.n_experts
    return ((n + ep - 1) // ep) * ep if ep > 1 else n


def _local_moe(x_flat, router_w, w_gate, w_up, w_down, *, cfg: ArchConfig,
               n_experts_total: int, e_local: int, lo, compute_dtype,
               valid=None):
    """Dispatch/compute/combine for the experts [lo, lo+e_local).

    x_flat: (T, d); valid: (T,) bool, the rows that are real tokens (None:
    all).  Returns (partial_out (T, d), aux_loss scalar, picks
    (n_experts_total,) int32: how many valid tokens picked each expert)."""
    e = cfg.moe
    t = x_flat.shape[0]
    k = e.top_k

    # --- routing (replicated across the model axis; fp32)
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), router_w)
    valid_expert = jnp.arange(n_experts_total) < e.n_experts
    logits = jnp.where(valid_expert[None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = jax.lax.top_k(probs, k)                   # (T, k)
    if e.norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    ok = (jnp.ones((t,), jnp.int32) if valid is None
          else valid.astype(jnp.int32))
    picks = jnp.zeros((n_experts_total,), jnp.int32).at[
        top_ids.reshape(-1)].add(jnp.repeat(ok, k))

    # --- aux load-balance loss (Switch): E * sum_e f_e * P_e
    f = jnp.zeros((n_experts_total,), jnp.float32).at[top_ids.reshape(-1)].add(
        1.0) / (t * k)
    pbar = probs.mean(axis=0)
    aux = e.n_experts * jnp.sum(f * pbar)

    # --- sort-based dispatch with capacity
    cap = capacity(cfg, t)
    flat_e = top_ids.reshape(-1)                               # (T*k,)
    flat_w = top_p.reshape(-1)
    flat_tok = jnp.arange(t * k, dtype=jnp.int32) // k
    order = jnp.argsort(flat_e)                                # stable
    counts = jnp.zeros((n_experts_total,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(t * k, dtype=jnp.int32) - starts[flat_e[order]]
    pos = jnp.zeros((t * k,), jnp.int32).at[order].set(pos_sorted)

    in_local = (flat_e >= lo) & (flat_e < lo + e_local) & (pos < cap)
    slot = jnp.where(in_local, (flat_e - lo) * cap + pos, e_local * cap)

    # Inverted dispatch: scatter int32 token ids (T*k of them), then ONE
    # (El*C, d) gather — never materialises a (T*k, d) tensor (4.3 GB for
    # qwen3-moe prefill shards).
    slot_src = jnp.full((e_local * cap + 1,), t, jnp.int32).at[slot].set(
        flat_tok)[:-1]                                         # (El*C,)
    x_pad = jnp.concatenate(
        [x_flat.astype(compute_dtype), jnp.zeros((1, x_flat.shape[1]),
                                                 compute_dtype)])
    buf = x_pad[slot_src].reshape(e_local, cap, -1)            # (El, C, d)

    # --- expert FFN (swiglu)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * \
        jnp.einsum("ecd,edf->ecf", buf, w_up)
    y = jnp.einsum("ecf,efd->ecd", h, w_down)                  # (El, C, d)

    # --- combine, chunked over the k assignments (bounds transients to
    # (T, d) instead of (T*k, d))
    y_pad = jnp.concatenate(
        [y.reshape(e_local * cap, -1),
         jnp.zeros((1, y.shape[-1]), y.dtype)])                # sentinel row
    contrib = jnp.where(in_local, flat_w, 0.0).astype(compute_dtype)
    slot_tk = slot.reshape(t, k)
    w_tk = contrib.reshape(t, k)
    out = jnp.zeros_like(x_flat)
    for j in range(k):
        out = out + y_pad[slot_tk[:, j]] * w_tk[:, j:j + 1]
    return out, aux, picks


def moe_apply(p, x, cfg: ArchConfig, compute_dtype=jnp.bfloat16,
              valid=None):
    """x: (B, S, d); valid: (B, S) bool, the real tokens (None: all) ->
    (out (B, S, d), aux_loss, picks (n_experts,) int32 per expert)."""
    b, s, d = x.shape
    e = cfg.moe
    rules = shd.current_rules()
    n_total = p["router"]["w"].shape[1]
    lo, n_held = held_experts(cfg)
    valid = None if valid is None else valid.reshape(-1)

    if rules is None or rules.rules.get("experts") is None:
        # single-device / unsharded path: the held experts only
        out, aux, picks = _local_moe(
            x.reshape(-1, d), p["router"]["w"],
            p["w_gate"].astype(compute_dtype),
            p["w_up"].astype(compute_dtype),
            p["w_down"].astype(compute_dtype),
            cfg=cfg, n_experts_total=n_total, e_local=n_held, lo=lo,
            compute_dtype=compute_dtype, valid=valid)
        out = out.reshape(b, s, d)
    else:
        if n_held != n_total:
            raise NotImplementedError(
                "expert parallelism over a mesh needs every expert's "
                "weights; a held share runs on one device")
        mesh = rules.mesh
        model_axis = rules.rules["experts"]
        batch_axes = rules.rules.get("batch")
        e_local = n_total // mesh.shape[model_axis]
        P = jax.sharding.PartitionSpec

        xs = P(batch_axes, None, None)
        wspec_g = P(model_axis, None, batch_axes)   # FSDP ff shard
        wspec_d = P(model_axis, batch_axes, None)

        def block(x_l, rw, wg, wu, wd):
            # FSDP all-gather of the local experts' ff shards (bf16)
            if batch_axes is not None:
                gather = functools.partial(
                    jax.lax.all_gather, axis_name=batch_axes, tiled=True)
            else:
                gather = lambda w, axis: w                    # noqa: E731
            wg = gather(wg.astype(compute_dtype), axis=2)
            wu = gather(wu.astype(compute_dtype), axis=2)
            wd = gather(wd.astype(compute_dtype), axis=1)
            rank = jax.lax.axis_index(model_axis)
            out, aux, picks = _local_moe(
                x_l.reshape(-1, d), rw, wg, wu, wd, cfg=cfg,
                n_experts_total=n_total, e_local=e_local,
                lo=rank * e_local, compute_dtype=compute_dtype)
            out = jax.lax.psum(out, model_axis)
            aux = jax.lax.pmean(aux, model_axis)
            if batch_axes is not None:
                picks = jax.lax.psum(picks, batch_axes)
            return out.reshape(x_l.shape), aux, picks

        out, aux, picks = jax.shard_map(
            block, mesh=mesh,
            in_specs=(xs, P(None, None), wspec_g, wspec_g, wspec_d),
            out_specs=(xs, P(), P()),
            check_vma=False,
        )(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])

    if e.n_shared > 0:
        sh = p["shared"]
        hidden = jax.nn.silu(linear(sh["gate"], x, compute_dtype)) * \
            linear(sh["up"], x, compute_dtype)
        hidden = logical(hidden, "batch", None, "mlp")
        shared_out = linear(sh["down"], hidden, compute_dtype)
        if "sgate" in sh:
            sgate = jax.nn.sigmoid(linear(sh["sgate"], x, jnp.float32))
            shared_out = shared_out * sgate.astype(compute_dtype)
        out = out + shared_out
    return logical(out, "batch", None, "residual"), aux, picks
