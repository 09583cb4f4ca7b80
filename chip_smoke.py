#!/usr/bin/env python3
"""Bring-up check on one TPU chip, in one process.

    python chip_smoke.py

Phases:
  (a) device   the attached device must be a TPU whose ``device_kind`` is
               in ``core.hardware.DEVICE_KINDS``.
  (b) kernels  the seven Pallas kernels, compiled (never interpreted) under
               every async strategy at published problem sizes, each
               compiled program checked for its Mosaic custom call and its
               output checked against the ``kernels.ref`` oracle within
               ``bench.scenario.CHIP_TOL``.
  (c) serving  qwen2-1.5b at its published widths (random weights from a
               seed) serves four requests through ``ServingLoop``, first
               with monolithic prefill, then with chunked prefill and
               prefix sharing; every request must return exactly its
               ``max_new`` in-vocab tokens, and its logits must equal
               those of a batch-1 run of the same scheduler within
               ``LOGIT_TOL`` at every step up to and including the first
               token where the two runs differ.
  (d) end      the last stdout line is one JSON object naming the device.

Any failure raises, so the script exits non-zero and prints no result.  It
also exits non-zero when JAX finds no TPU, and when run from a directory
that holds no ``src/repro``.  No timings are printed: this is a check of
results, not a measurement.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
ARCH = "qwen2-1.5b"
PROMPT_LENS = (128, 320, 448, 512)   # the first has no shared prefix
PREFIX_LEN = 256
MAX_NEW = 32
BATCH = 4
BLOCK_LEN = 16
CHUNK_TOKENS = 128
#: a batched and a batch-1 decode of the same request see the same inputs
#: up to the first token where they differ, but their dispatches have other
#: shapes and may round bf16 activations differently.  This bounds the
#: largest logit difference over the vocabulary at any such step.  Logits
#: here have std ~0.8 (embedding std 0.02 x sqrt(1536)); on v5e, batch
#: noise flipped tokens at top-2 margins of at most 2.8e-2, so it is of
#: order 1e-2.  The slot fault tried, a decode position one row stale in
#: one slot, moved logits by about one std on the CPU smoke preset (0.16
#: against std ~0.14).  The bound, ~0.3 std, sits between the two.  A token
#: that differs therefore differs only where the batch-1 logits of the two
#: tokens were within 2 x LOGIT_TOL: a near-tie, not a fault.
LOGIT_TOL = 0.25


def check_device():
    import jax
    from repro.core import hardware

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's default device is {dev.platform!r}")
    chip = hardware.chip_for_device_kind(dev.device_kind)
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devs)} chip={chip.name}", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def _kernel_cases():
    """kernel -> (make inputs, call(args, strategy), oracle(args)).

    Each call passes a config naming every tunable key, so the wrappers'
    seed fallback has nothing to swap in."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    keys = jax.random.split(jax.random.PRNGKey(SEED), 3)
    cfg = {
        "stream": dict(tile_rows=256, n_tiles=8, depth=2, wait_group=None,
                       out_depth=2),
        "hotspot": dict(tile_rows=64, depth=2, wait_group=None, out_depth=2),
        "pathfinder": dict(tile_rows=8, depth=2, wait_group=None),
        "nw": dict(tile_rows=8, depth=2, wait_group=None, out_depth=2),
        "lud": dict(bs=32, depth=2, wait_group=None, out_depth=2),
        "matmul": dict(bm=128, bk=128, bn=128, depth=2, wait_group=None),
        "flash_attention": dict(bq=128, bk=128, depth=2, wait_group=None),
    }

    def flash_args():
        q = jax.random.normal(keys[0], (12, 2048, 128), jnp.bfloat16)
        k = jax.random.normal(keys[1], (2, 2048, 128), jnp.bfloat16)
        v = jax.random.normal(keys[2], (2, 2048, 128), jnp.bfloat16)
        return q, k, v

    return {
        # 16384 x 8192 f32 = 512 MiB, four times v5e's VMEM
        "stream": (
            lambda: (jax.random.uniform(keys[0], (16384, 8192)),),
            lambda a, s: ops.stream(a[0], iters=4, strategy=s,
                                    interpret=False, **cfg["stream"]),
            lambda a: ref.stream_ref(a[0], 4)),
        "hotspot": (
            lambda: (jax.random.uniform(keys[0], (1024, 1024)) * 100 + 300,
                     jax.random.uniform(keys[1], (1024, 1024))),
            lambda a, s: ops.hotspot(a[0], a[1], iters=1, strategy=s,
                                     interpret=False, **cfg["hotspot"]),
            lambda a: ref.hotspot_ref(a[0], a[1], iters=1)),
        # Rodinia's 100 x 100000 grid; the wrapper pads it as Mosaic needs
        "pathfinder": (
            lambda: (jax.random.randint(keys[0], (100, 100000), 0, 10,
                                        jnp.int32),),
            lambda a, s: ops.pathfinder(a[0], strategy=s, interpret=False,
                                        **cfg["pathfinder"])[0],
            lambda a: ref.pathfinder_ref(a[0])),
        "nw": (
            lambda: (jax.random.randint(keys[0], (2048, 2048), -3, 4)
                     .astype(jnp.float32),),
            lambda a, s: ops.nw(a[0], penalty=10, strategy=s,
                                interpret=False, **cfg["nw"]),
            lambda a: ref.nw_ref(a[0], 10)),
        "lud": (
            lambda: (jax.random.normal(keys[0], (2048, 2048))
                     + 2048 * jnp.eye(2048),),
            lambda a, s: ops.lud(a[0], strategy=s, interpret=False,
                                 **cfg["lud"]),
            lambda a: ref.lud_ref(a[0])),
        "matmul": (
            lambda: (jax.random.normal(keys[0], (4096, 4096), jnp.bfloat16),
                     jax.random.normal(keys[1], (4096, 4096), jnp.bfloat16)),
            lambda a, s: ops.matmul(a[0], a[1], strategy=s, interpret=False,
                                    **cfg["matmul"]),
            lambda a: ref.matmul_ref(a[0], a[1])),
        # qwen2-1.5b's attention: 12 query heads over 2 KV heads, hd 128
        "flash_attention": (
            flash_args,
            lambda a, s: ops.flash_attention(a[0], a[1], a[2], causal=True,
                                             strategy=s, interpret=False,
                                             **cfg["flash_attention"]),
            lambda a: ref.attention_ref(a[0], jnp.repeat(a[1], 6, axis=0),
                                        jnp.repeat(a[2], 6, axis=0))),
    }


def check_kernels():
    import jax
    import jax.numpy as jnp
    from repro.bench.scenario import CHIP_TOL
    from repro.core.async_pipeline import Strategy

    for kernel, (make, call, oracle) in _kernel_cases().items():
        args = make()
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(args).astype(jnp.float32)
        for strategy in Strategy:
            compiled = jax.jit(
                lambda a, s=strategy: call(a, s)).lower(args).compile()
            if "tpu_custom_call" not in compiled.as_text():
                raise RuntimeError(f"{kernel}/{strategy.value}: compiled "
                                   "program holds no Mosaic kernel")
            got = compiled(args)
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
            tol = CHIP_TOL[kernel]
            print(f"kernel {kernel:16s} strategy={strategy.value:16s} "
                  f"max_err={err:.3e} tol={tol:.1e}", flush=True)
            if not err <= tol:
                raise RuntimeError(f"{kernel}/{strategy.value}: max_err "
                                   f"{err:.3e} exceeds {tol:.1e}")
        del args, want


def _requests(vocab):
    import numpy as np
    from repro.launch.serve import Request

    rng = np.random.default_rng(SEED)
    prefix = rng.integers(0, vocab, PREFIX_LEN).astype(np.int32)
    prompts = []
    for i, n in enumerate(PROMPT_LENS):
        if i == 0:
            prompts.append(rng.integers(0, vocab, n).astype(np.int32))
        else:
            tail = rng.integers(0, vocab, n - PREFIX_LEN).astype(np.int32)
            prompts.append(np.concatenate([prefix, tail]))

    def fresh():
        return [Request(uid=i, prompt=p, max_new=MAX_NEW, logits=[])
                for i, p in enumerate(prompts)]
    return fresh


def _serve(label, cfg, params, fresh, **kw):
    """Serve ``fresh()`` at batch BATCH and at batch 1; returns the two
    request lists, which hold their tokens and logits."""
    from repro.launch.serve import ServingLoop, slot_rows

    out = {}
    for batch in (BATCH, 1):
        reqs = fresh()
        loop = ServingLoop(cfg, params, batch=batch, max_new=MAX_NEW,
                           seed=SEED, block_len=BLOCK_LEN,
                           max_seq=slot_rows(reqs, BLOCK_LEN), **kw)
        loop.run(reqs, max_steps=MAX_NEW)
        out[batch] = reqs
        if batch == BATCH and loop.prefix_cache:
            hits = loop.scheduler.cache.hit_tokens
            print(f"serve {label}: prefix-cache hit tokens={hits}",
                  flush=True)
            if hits <= 0:
                raise RuntimeError(f"{label}: the shared prefix was never "
                                   "served from cache")
    return out[BATCH], out[1]


def _compare(label, got, want, vocab):
    """Check one request's batched run ``got`` against its batch-1 run
    ``want``; returns a failure message or None."""
    import jax.numpy as jnp

    for name, r in (("batched", got), ("solo", want)):
        toks = r.out_tokens
        if len(toks) != MAX_NEW or len(r.logits) != MAX_NEW or not all(
                0 <= t < vocab for t in toks):
            return (f"{label} req {r.uid} ({name}): {len(toks)} tokens and "
                    f"{len(r.logits)} logits rows, want {MAX_NEW} in "
                    f"[0, {vocab})")
    diff = next((j for j, (a, b) in enumerate(zip(got.out_tokens,
                                                  want.out_tokens))
                 if a != b), None)
    last = MAX_NEW - 1 if diff is None else diff
    err = float(jnp.max(jnp.abs(
        jnp.stack(got.logits[:last + 1]).astype(jnp.float32)
        - jnp.stack(want.logits[:last + 1]).astype(jnp.float32))))
    line = (f"serve {label} req {got.uid}: prompt={len(got.prompt)} "
            f"max |logit diff| over steps 0..{last} = {err:.4e} "
            f"(tol {LOGIT_TOL})")
    if diff is None:
        line += f"; all {MAX_NEW} tokens equal to batch-1"
    else:
        solo = want.logits[diff].astype(jnp.float32)
        gap = float(solo[want.out_tokens[diff]] - solo[got.out_tokens[diff]])
        line += (f"; first differs at step {diff}, where batch-1 ranks its "
                 f"token {gap:.4f} above the batched one")
    print(line, flush=True)
    if not err <= LOGIT_TOL:
        return (f"{label} req {got.uid}: logits differ from batch-1 by "
                f"{err:.4e} > {LOGIT_TOL}")
    return None


def check_serving():
    from repro.configs import get_config
    from repro.launch.serve import init_params

    cfg = get_config(ARCH)
    params = init_params(cfg, SEED)
    fresh = _requests(cfg.vocab)
    print(f"serve: {ARCH} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab} params={cfg.param_count()}", flush=True)
    failures = []
    for label, kw in (("monolithic", {}),
                      ("chunked+prefix", dict(chunk_tokens=CHUNK_TOKENS,
                                              prefix_cache=True))):
        batched, solo = _serve(label, cfg, params, fresh, **kw)
        for got, want in zip(batched, solo):
            msg = _compare(label, got, want, cfg.vocab)
            if msg:
                failures.append(msg)
    if failures:
        raise RuntimeError("; ".join(failures))


def main() -> int:
    import jax  # noqa: F401  (fails here, before any phase, without jax)

    device = check_device()
    from repro.core.compile_cache import use_persistent_cache
    use_persistent_cache()
    check_kernels()
    check_serving()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
