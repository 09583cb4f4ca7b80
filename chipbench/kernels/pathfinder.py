"""Rodinia pathfinder: a row-by-row dynamic program over an int32 wall,
``dst[j] = wall[r, j] + min(prev[j-1], prev[j], prev[j+1])``, edges
clamped; the answer is the last row of path costs.

Counts per cell: 3 operations (two mins and an add); the wall read once
and the last row written once."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(key, spec):
    shape = tuple(spec["shape"])
    return (jax.jit(lambda k: jax.random.randint(k, shape, 0, 10,
                                                 jnp.int32))(key),)


def call(wall, *, spec):
    from repro.kernels import ops
    return ops.pathfinder(wall)[0]


def reference(wall, *, spec, dtype=jnp.int32):
    def step(prev, row):
        left = jnp.concatenate([prev[:1], prev[:-1]])
        right = jnp.concatenate([prev[1:], prev[-1:]])
        return row + jnp.minimum(prev, jnp.minimum(left, right)), None
    w = wall.astype(dtype)
    out, _ = jax.lax.scan(step, w[0], w[1:])
    return out.astype(jnp.float32)


def counts(spec):
    rows, cols = spec["shape"]
    return 3.0 * rows * cols, 4.0 * (rows * cols + cols)
