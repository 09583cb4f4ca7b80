"""Kernel driver: the paper's kernels through the program's ``kernels.ops``
wrappers, called with no configuration arguments, so each runs under the
default the program picks.

The configuration gives each kernel's sizes; the mix gives the sequence
of kernels that make one call.  Each kernel is ``kernels/<name>.py``: its
inputs from a key, its call, its plain reference and its counts.  Each
kernel call goes through a jitted wrapper named ``chipbench_<kernel>``,
by which the trace finds it.

The window issues calls back to back, each kernel waited on before the
next is issued; ``call_ms`` is the window over the number of whole calls.
The outputs of one call drawn from the seed and of the last call are kept
and compared with the reference once the window has closed: the largest
difference relative to the reference's largest magnitude, per kernel.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench.harness import Check, Outcome, memory_peak


def program(kernel: str) -> str:
    return f"chipbench_{kernel}"


def _named(kernel: str, fn, spec):
    import jax

    def call(*args):
        return fn(*args, spec=spec)
    call.__name__ = call.__qualname__ = program(kernel)
    return jax.jit(call)


def rel_err(got, want) -> float:
    import jax.numpy as jnp
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(want)), 1e-30)
    return float(jnp.max(jnp.abs(got - want)) / scale)


def run(cell, control: bool = False) -> Outcome:
    import jax
    import jax.numpy as jnp

    spec = cell.config["kernels"]
    seq = list(cell.traffic["sequence"])
    mods = {k: cell.module("kernels", k) for k in seq}
    keys = jax.random.split(cell.key(), len(seq))
    args = {k: mods[k].make(keys[i], spec[k]) for i, k in enumerate(seq)}
    fns = {k: _named(k, mods[k].call, spec[k]) for k in seq}
    for k in seq:
        jax.block_until_ready(fns[k](*args[k]))
    keep_at = int(np.random.default_rng([cell.seed, 3]).integers(0, 4))
    kept, last = {}, {}

    t0 = cell.window.open()
    n = 0
    while True:
        for k in seq:
            last[k] = jax.block_until_ready(fns[k](*args[k]))
            if n == keep_at:
                kept[k] = last[k]
        n += 1
        t = time.perf_counter()
        if not cell.window.tick(t):
            break
    cell.window.close()
    peak = memory_peak(1)
    cell.say(f"{n} calls in {t - t0!r} s")

    checks = []
    limits = cell.traffic["check"]
    for k in seq:
        want = jax.jit(lambda *a, m=mods[k], s=spec[k]: m.reference(
            *a, spec=s))
        with jax.default_matmul_precision("highest"):
            ref = want(*args[k])
            outs = [kept.get(k, last[k]), last[k]]
            checks.append(Check(f"{k}_rel_err",
                                max(rel_err(o, ref) for o in outs),
                                float(limits[f"{k}_rel_err"])))
            if control:
                low = jax.jit(lambda *a, m=mods[k], s=spec[k]: m.reference(
                    *a, spec=s, dtype=jnp.bfloat16))(*args[k])
                checks.append(Check(f"control_{k}_rel_err", rel_err(low, ref),
                                    float(limits[f"{k}_rel_err"])))
        del ref
    data = {"kernel_counts": {k: mods[k].counts(spec[k]) for k in seq},
            "programs": {k: program(k) for k in seq}}
    return Outcome({"call_ms": (t - t0) / n * 1e3}, attempted=n, failed=0,
                   checks=checks, memory_peak_bytes=peak, data=data)
