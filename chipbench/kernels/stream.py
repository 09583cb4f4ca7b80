"""The paper's async-copy microbenchmark (Fig. 3): x <- 0.5 x + 0.5,
``iters`` times over an f32 array.

Counts: 2 operations per element per iteration; the array read once and
written once (the iterations stay on chip)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(key, spec):
    return (jax.jit(lambda k: jax.random.uniform(
        k, tuple(spec["shape"]), jnp.float32))(key),)


def call(x, *, spec):
    from repro.kernels import ops
    return ops.stream(x, iters=spec["iters"])


def reference(x, *, spec, dtype=jnp.float32):
    x = x.astype(dtype)
    for _ in range(spec["iters"]):
        x = x * 0.5 + 0.5
    return x.astype(jnp.float32)


def counts(spec):
    n = spec["shape"][0] * spec["shape"][1]
    return 2.0 * n * spec["iters"], 2.0 * n * 4
