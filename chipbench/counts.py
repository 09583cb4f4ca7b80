"""Operations and bytes of a dense decoder's serving work, from shapes alone.

Every count here reads only the configuration file's sizes and the shapes
of the work (tokens, positions, cache blocks), never how the program
implements it: the same work counts the same whatever tiles, dtypes or
kernels do it.  The kernels' counts are in ``chipbench/kernels/``.

Sizes are the keys of a serving configuration file: ``n_layers``,
``d_model``, ``n_heads``, ``n_kv_heads``, ``head_dim``, ``d_ff``,
``vocab``, ``qkv_bias``, ``tie_embeddings`` and ``kv_bytes`` (bytes of one
cached element in the dtype the configuration states).
"""
from __future__ import annotations

from typing import Iterable, Mapping

#: bytes of one weight in the dtype the configuration computes in (bf16):
#: the least a step must read, whatever dtype the program holds them in
WEIGHT_BYTES = 2


def matmul_params(c: Mapping) -> int:
    """Weights that multiply a token's activations: every layer's q, k, v,
    o and three MLP matrices, and the output projection."""
    d, hd = c["d_model"], c["head_dim"]
    attn = d * c["n_heads"] * hd * 2 + d * c["n_kv_heads"] * hd * 2
    mlp = 3 * d * c["d_ff"]
    return c["n_layers"] * (attn + mlp) + c["vocab"] * d


def param_count(c: Mapping) -> int:
    """Every parameter, as published."""
    d, hd, L = c["d_model"], c["head_dim"], c["n_layers"]
    bias = (c["n_heads"] + 2 * c["n_kv_heads"]) * hd if c["qkv_bias"] else 0
    per_layer = (d * c["n_heads"] * hd * 2 + d * c["n_kv_heads"] * hd * 2
                 + bias + 3 * d * c["d_ff"] + 2 * d)
    emb = c["vocab"] * d * (1 if c["tie_embeddings"] else 2)
    return L * per_layer + emb + d


def kv_bytes_per_token(c: Mapping) -> int:
    """K and V of one token over every layer."""
    return 2 * c["n_layers"] * c["n_kv_heads"] * c["head_dim"] * c["kv_bytes"]


def token_flops(c: Mapping, context: int) -> float:
    """One token through the model, attending ``context`` cached rows
    (itself included): 2 per matmul weight, plus q.k and p.v over the
    context in every layer."""
    attn = 4 * c["n_layers"] * c["n_heads"] * c["head_dim"] * context
    return 2.0 * matmul_params(c) + attn


def decode_step_flops(c: Mapping, positions: Iterable[int]) -> float:
    """A decode step over slots at ``positions`` (each attends rows
    ``0..position``)."""
    return sum(token_flops(c, p + 1) for p in positions)


def decode_step_bytes(c: Mapping, n_slots: int, distinct_blocks: int,
                      block_len: int) -> float:
    """The least a decode step must move: every parameter once in the
    compute dtype, the distinct cache blocks the slots attend (a block that
    several slots share counts once), and each slot's new K/V row."""
    kv = kv_bytes_per_token(c)
    return (WEIGHT_BYTES * param_count(c) + kv * block_len * distinct_blocks
            + kv * n_slots)


def attended_blocks(positions_and_tables, block_len: int) -> int:
    """Distinct cache blocks attended by slots given as ``(position,
    table)`` pairs: a slot at position p reads blocks ``table[:p //
    block_len + 1]``."""
    seen = set()
    for pos, table in positions_and_tables:
        seen.update(int(b) for b in table[:pos // block_len + 1])
    return len(seen)
