"""Rodinia LUD: in-place Doolittle LU without pivoting of an n x n f32
matrix made diagonally dominant; the answer holds U on and above the
diagonal and L (unit diagonal implied) below it.

Counts: 2 n^3 / 3 operations; the matrix read once and written once."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(key, spec):
    n = spec["n"]
    return (jax.jit(lambda k: jax.random.normal(k, (n, n))
                    + n * jnp.eye(n))(key),)


def call(a, *, spec):
    from repro.kernels import ops
    return ops.lud(a)


def reference(a, *, spec, dtype=jnp.float32):
    a = a.astype(dtype)
    n = a.shape[0]
    idx = jnp.arange(n)

    def outer(a, k):
        col = jnp.where(idx > k, a[:, k] / a[k, k], a[:, k])
        a = a.at[:, k].set(col)
        below = (idx[:, None] > k) & (idx[None, :] > k)
        return jnp.where(below, a - jnp.outer(col, a[k, :]), a), None
    a, _ = jax.lax.scan(outer, a, idx)
    return a.astype(jnp.float32)


def counts(spec):
    n = spec["n"]
    return 2.0 * n ** 3 / 3.0, 2.0 * n * n * 4
