"""Operations and bytes of a latent-attention expert decoder's serving work
(DeepSeek-V2), from shapes alone.

As in ``counts``, every count reads only the configuration file's sizes
(its published keys: ``hidden_size``, ``num_attention_heads``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``intermediate_size``, ``moe_intermediate_size``,
``n_routed_experts``, ``n_shared_experts``, ``first_k_dense_replace``,
``num_hidden_layers``, ``vocab_size``; and ``kv_bytes``) and the shapes of
the work, never how the program implements it.

Decode is counted as absorbed attention: each query head attends a
token's latent row (kv_lora_rank + qk_rope_head_dim wide) and takes the
first kv_lora_rank columns as its value, so a context row costs
2 x heads x (2 x kv_lora_rank + qk_rope_head_dim) operations a layer,
and ``wkv_b``'s weights multiply each token once (into the query and out
of the value).  A routed expert's weights count once per pick of it that
was computed here.
"""
from __future__ import annotations

from typing import Iterable, Mapping

from chipbench.counts import WEIGHT_BYTES


def attn_params(c: Mapping) -> int:
    """One layer's attention matrices: W_q, W_kva, W_kvb, W_o."""
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def expert_params(c: Mapping) -> int:
    """One routed expert's gate, up and down matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_layers(c: Mapping) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def dense_matmul_params(c: Mapping) -> int:
    """Weights every token multiplies by, routed experts aside: each
    layer's attention, the leading dense layers' MLPs, each expert layer's
    router and shared experts, and the output projection."""
    d = c["hidden_size"]
    shared = c["n_shared_experts"] * expert_params(c)
    return (c["num_hidden_layers"] * attn_params(c)
            + c["first_k_dense_replace"] * 3 * d * c["intermediate_size"]
            + expert_layers(c) * (d * c["n_routed_experts"] + shared)
            + d * c["vocab_size"])


def norm_params(c: Mapping) -> int:
    """RMSNorm scales: two a layer, the latent's, and the final one."""
    d = c["hidden_size"]
    return c["num_hidden_layers"] * (2 * d + c["kv_lora_rank"]) + d


def latent_bytes_per_token(c: Mapping) -> int:
    """One token's cached latent rows over every layer."""
    return (c["num_hidden_layers"] * (c["kv_lora_rank"]
                                      + c["qk_rope_head_dim"])
            * c["kv_bytes"])


def attention_flops(c: Mapping, context: int) -> float:
    """Absorbed attention of one query row over ``context`` latent rows,
    every layer."""
    per_row = 2 * c["num_attention_heads"] * (2 * c["kv_lora_rank"]
                                              + c["qk_rope_head_dim"])
    return float(c["num_hidden_layers"] * per_row * context)


def decode_step_flops(c: Mapping, positions: Iterable[int],
                      held_picks: int) -> float:
    """A decode step over slots at ``positions`` (each attends rows
    ``0..position``) in which ``held_picks`` routed-expert picks fell on
    experts held here: 2 per matmul weight per token, 2 per expert weight
    per held pick, and absorbed attention."""
    positions = list(positions)
    return (2.0 * dense_matmul_params(c) * len(positions)
            + 2.0 * expert_params(c) * held_picks
            + sum(attention_flops(c, p + 1) for p in positions))


def decode_step_bytes(c: Mapping, n_slots: int, distinct_blocks: int,
                      block_len: int, experts_hit: int) -> float:
    """The least a decode step must move: the weights every token uses and
    the norms once, in the compute dtype; the ``experts_hit`` held experts
    (summed over expert layers) that some token picked; the slots'
    embedding rows; the distinct latent blocks attended (a block several
    slots share counts once) and each slot's new latent row.  The rest of
    the embedding table is not read."""
    lat = latent_bytes_per_token(c)
    weights = (dense_matmul_params(c) + norm_params(c)
               + experts_hit * expert_params(c))
    return (WEIGHT_BYTES * (weights + n_slots * c["hidden_size"])
            + lat * block_len * distinct_blocks + lat * n_slots)
