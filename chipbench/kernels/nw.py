"""Rodinia Needleman-Wunsch: the (n+1) x (n+1) alignment table over an
n x n similarity matrix with gap penalty ``penalty``:
``M[i, j] = max(M[i-1, j-1] + s[i-1, j-1], M[i, j-1] - p, M[i-1, j] - p)``
with ``M[i, 0] = M[0, i] = -i p``.

The reference fills the table a row at a time.  Within a row the left
neighbour's term is a running maximum: ``M[i, j] + p j`` is the maximum
over ``k <= j`` of ``B[k] + p k``, where ``B[k]`` is the better of the
diagonal and upper terms (``B[0] = M[i, 0]``).  The values are whole
numbers, so this reordering is exact in float32.

Counts per cell: 5 operations (three adds, two maxes); the similarity
matrix read once and the table written once."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(key, spec):
    n = spec["n"]
    return (jax.jit(lambda k: jax.random.randint(k, (n, n), -3, 4)
                    .astype(jnp.float32))(key),)


def call(scores, *, spec):
    from repro.kernels import ops
    return ops.nw(scores, penalty=spec["penalty"])


def reference(scores, *, spec, dtype=jnp.float32):
    p = jnp.asarray(spec["penalty"], dtype)
    s = scores.astype(dtype)
    n = s.shape[0]
    ramp = jnp.arange(n + 1, dtype=dtype) * p

    def row(prev, i):
        b = jnp.maximum(prev[:-1] + s[i - 1], prev[1:] - p)
        b = jnp.concatenate([(-i.astype(dtype) * p)[None], b])
        cur = jax.lax.cummax(b + ramp) - ramp
        return cur, cur

    first = -ramp
    _, rows = jax.lax.scan(row, first, jnp.arange(1, n + 1))
    return jnp.concatenate([first[None], rows]).astype(jnp.float32)


def counts(spec):
    n = spec["n"]
    return 5.0 * n * n, 4.0 * (n * n + (n + 1) * (n + 1))
