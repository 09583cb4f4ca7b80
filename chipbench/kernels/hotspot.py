"""Rodinia hotspot: a 5-point thermal stencil over an f32 grid, edges
replicated, ``iters`` steps.

Counts per cell per step: 15 operations (the update below); the
temperature and power grids read once and the result written once."""
from __future__ import annotations

import jax
import jax.numpy as jnp

RX = RY = 0.1
RZ = CAP = 0.5


def make(key, spec):
    k1, k2 = jax.random.split(key)
    shape = tuple(spec["shape"])

    @jax.jit
    def mk(k1, k2):
        return (jax.random.uniform(k1, shape) * 100 + 300,
                jax.random.uniform(k2, shape))
    return mk(k1, k2)


def call(temp, power, *, spec):
    from repro.kernels import ops
    return ops.hotspot(temp, power, iters=spec["iters"])


def reference(temp, power, *, spec, dtype=jnp.float32):
    t, p = temp.astype(dtype), power.astype(dtype)
    for _ in range(spec["iters"]):
        up = jnp.concatenate([t[:1], t[:-1]], axis=0)
        down = jnp.concatenate([t[1:], t[-1:]], axis=0)
        left = jnp.concatenate([t[:, :1], t[:, :-1]], axis=1)
        right = jnp.concatenate([t[:, 1:], t[:, -1:]], axis=1)
        delta = CAP * (p + (up + down - 2.0 * t) * RY
                       + (left + right - 2.0 * t) * RX + (80.0 - t) * RZ)
        t = t + delta
    return t.astype(jnp.float32)


def counts(spec):
    n = spec["shape"][0] * spec["shape"][1]
    return 15.0 * n * spec["iters"], 3.0 * n * 4
