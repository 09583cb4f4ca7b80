"""Serving driver: a dense decoder behind the program's ``ServingLoop``.

Set-up makes the weights on the device from the seed (``models/<ref>.py``),
builds the program's ``ServingLoop`` with the continuous scheduler and the
mix's ``server`` settings, and warms it up with one request per prefill
shape the mix can reach: each pow2 prefill bucket of its prompt lengths
(monolithic prefill), or each pow2 chunk width up to ``chunk_tokens``
(chunked prefill).  The window then runs ``ServingLoop.run`` over the
mix's request list, which is longer than any window can finish.

Each request's token list records when each token was appended, and
raises once the window has closed: that ends ``run`` at the first token
after the close, so nothing is left running.  From those times and the
program's own ``ttft_ms``:

- ``tokens_per_s``: output tokens appended inside the window over its
  length;
- ``tpot_p95_ms``: 95th percentile of the gaps between a request's
  consecutive output tokens, over every such gap inside the window;
- ``ttft_p95_ms``: 95th percentile of ``ttft_ms`` (timed by the program
  from when the request was due) over requests whose first token came
  inside the window.

Correctness: once the window has closed, a sample of the served requests,
drawn from the seed and holding the one with the most tokens, is run
through the float32 reference over its prompt and the tokens it was
served; ``max_gap`` is the widest margin by which a served token's
reference logit lies below the reference's best at its position.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

from chipbench import counts
from chipbench.harness import Check, Outcome, WindowClosed, memory_peak

#: configuration keys that must equal the program's own config
_SIZES = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff", "vocab",
          "head_dim_")


class _Tokens(list):
    """A request's output tokens, with the time each was appended."""

    def __init__(self, window):
        super().__init__()
        self.times: List[float] = []
        self.window = window

    def append(self, tok):
        t = time.perf_counter()
        if not self.window.tick(t):
            raise WindowClosed
        list.append(self, tok)
        self.times.append(t)


def program_config(c: Dict):
    """The program's ``ArchConfig`` for the configuration file ``c``: its
    published config with the file's sizes, which it must then report."""
    from repro.configs import get_config
    base = get_config(c["arch"])
    cfg = dataclasses.replace(
        base, n_layers=c["n_layers"], d_model=c["d_model"],
        n_heads=c["n_heads"], n_kv_heads=c["n_kv_heads"], d_ff=c["d_ff"],
        vocab=c["vocab"], head_dim=c["head_dim"], dtype=c["dtype"],
        param_dtype=c["param_dtype"], tie_embeddings=c["tie_embeddings"],
        attn=dataclasses.replace(base.attn, rope_theta=c["rope_theta"],
                                 qkv_bias=c["qkv_bias"]))
    if (cfg.family, cfg.act, cfg.norm, cfg.attn.kind, cfg.attn.window) != (
            "dense", "swiglu", "rmsnorm", "full", 0):
        raise ValueError(f"{c['arch']} is not a dense SwiGLU RMSNorm decoder "
                         "with full attention")
    for k in _SIZES:
        want = c[k.rstrip("_")]
        if getattr(cfg, k) != want:
            raise ValueError(f"program config {k}={getattr(cfg, k)} != {want}")
    return cfg


def _check_layout(cfg, params) -> None:
    import jax
    from repro.distributed.sharding import split_tree
    from repro.models import build_model
    want = jax.eval_shape(lambda k: split_tree(build_model(cfg).init(k))[0],
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError("the reference weights do not match the program's "
                         f"parameter layout:\n{got}\n!=\n{want}")


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def warm_prompts(mix: Dict, gen, seed: int, vocab: int) -> List:
    """Set-up's requests: one per prefill program the mix can reach (each
    pow2 chunk width up to ``chunk_tokens`` for chunked prefill; each
    prompt length for monolithic prefill, which the program compiles per
    exact length), then each system prompt once, so the prefix cache holds
    them as it would in steady service."""
    rng = np.random.default_rng([seed, 1])
    chunk = mix["server"].get("chunk_tokens")
    lengths = ([1 << i for i in range(_pow2(chunk).bit_length())] if chunk
               else gen.prompt_lengths(mix))
    out = [rng.integers(0, vocab, n, dtype=np.int32) for n in lengths]
    return out + [np.concatenate([p, rng.integers(0, vocab, 1,
                                                  dtype=np.int32)])
                  for p in gen.system_prompts(mix, seed, vocab)]


def _p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def run(cell, control: bool = False) -> Outcome:
    import jax
    from repro.launch.serve import ServingLoop
    from repro.serve import Request

    c, mix = cell.config, cell.traffic
    srv, chk = mix["server"], mix["check"]
    gen = cell.module("traffic", "gen")
    ref = cell.module("models", c["reference"])
    if c["vocab"] % 128:
        raise ValueError("vocab must be a multiple of 128 (the program pads "
                         "its embedding to one)")
    cfg = program_config(c)
    key = cell.key()
    params = ref.make_weights(c, key)
    _check_layout(cfg, params)
    jax.block_until_ready(params)

    max_out = int(mix["output_len"][1])
    loop = ServingLoop(cfg, params, batch=srv["batch"],
                       seed=cell.seed % (2 ** 31), max_new=max_out,
                       block_len=c["block_len"], max_seq=srv["max_seq"],
                       total_tokens=srv.get("total_tokens"),
                       chunk_tokens=srv.get("chunk_tokens"),
                       prefix_cache=bool(srv.get("prefix_cache")))
    warm = [Request(uid=-1 - i, prompt=p, max_new=2) for i, p in
            enumerate(warm_prompts(mix, gen, cell.seed, c["vocab"]))]
    loop.run(warm, max_steps=max_out)
    cache = loop.scheduler.cache
    reqs = [Request(uid=r["uid"], prompt=r["prompt"], max_new=r["max_new"],
                    arrival=r["arrival"], out_tokens=_Tokens(cell.window))
            for r in gen.requests(mix, cell.seed, c["vocab"])]
    decode_log = _DecodeLog(loop, cell) if cell.trace else None
    hit0, miss0 = cache.hit_tokens, cache.miss_tokens

    t0 = cell.window.open()
    try:
        loop.run(reqs, max_steps=max_out)
    except WindowClosed:
        pass
    else:
        raise RuntimeError("every request finished inside the window: the "
                           "mix must hold more work than a window can do")
    cell.window.close()
    t_end = t0 + cell.seconds
    hits, misses = cache.hit_tokens - hit0, cache.miss_tokens - miss0
    peak = memory_peak(1)

    served = [r for r in reqs if r.out_tokens.times]
    times = [[t for t in r.out_tokens.times if t < t_end] for r in served]
    in_window = sum(len(ts) for ts in times)
    gaps = [(b - a) * 1e3 for ts in times for a, b in zip(ts, ts[1:])]
    ttft = [r.ttft_ms for r in served]
    n_bad = sum(not all(0 <= t < c["vocab"] for t in r.out_tokens)
                for r in served)
    if not gaps:
        raise RuntimeError("no request got a second token inside the window")
    e2e = {"tokens_per_s": in_window / cell.seconds,
           "tpot_p95_ms": _p95(gaps), "ttft_p95_ms": _p95(ttft)}
    cell.say(f"served {len(served)} requests, "
             f"{sum(r.done for r in served)} finished; {in_window} tokens "
             f"and {len(gaps)} gaps between tokens in the window; prefix "
             f"hits {hits} misses {misses}")
    prompt_computed = (misses if srv.get("prefix_cache")
                       else sum(len(r.prompt) for r in served))
    data = {"tokens_computed": in_window - len(served) + prompt_computed,
            "prefix_hit_tokens": hits, "prefix_miss_tokens": misses,
            "decode_steps": decode_log.steps if decode_log else []}

    sample = [(r.prompt, list(r.out_tokens)) for r in
              _sample(served, int(chk["sample"]), cell.seed)]
    # free the program's state before the reference takes the chip
    del loop, params, reqs, served, warm, cache, decode_log
    gc.collect()
    widest, widest_control = _reference_gaps(ref, c, mix, key, sample,
                                             control)
    checks = [Check("max_gap", widest, float(chk["max_gap"]))]
    if control:
        checks.append(Check("control_max_gap", widest_control,
                            float(chk["max_gap"])))
    return Outcome(e2e, attempted=len(ttft), failed=n_bad, checks=checks,
                   memory_peak_bytes=peak, data=data)


class _DecodeLog:
    """Records, for each decode step that runs while the profiler traces,
    the positions its slots write and the distinct cache blocks they
    attend (a slot whose table row is unused does no work)."""

    def __init__(self, loop, cell):
        sched = loop.scheduler
        inner = sched._decode
        self.steps = []
        block_len = cell.config["block_len"]

        def logged(params, paged, tokens, tables, slot_pos):
            if cell.window.tracing:
                pos, tbl = np.asarray(slot_pos), np.asarray(tables)
                live = [(int(pos[i]), tbl[i]) for i in range(len(pos))
                        if tbl[i, 0] >= 0]
                self.steps.append(([p for p, _ in live],
                                   counts.attended_blocks(live, block_len)))
            return inner(params, paged, tokens, tables, slot_pos)
        sched._decode = logged


def _sample(served, k: int, seed: int):
    """The served request with the most tokens, and ``k - 1`` others drawn
    from the seed."""
    longest = max(served, key=lambda r: (len(r.out_tokens), -r.uid))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _widest_gap(logits, toks, picks, n):
    """Largest (row max - logit of the token at that row) over the first
    ``n`` rows, for the served ``toks`` and for ``picks``."""
    import jax.numpy as jnp
    best = jnp.max(logits, axis=-1)
    valid = jnp.arange(logits.shape[0]) < n

    def gap(t):
        g = best - jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.max(jnp.where(valid, g, 0.0))
    return gap(toks), gap(picks)


def _reference_gaps(ref, c, mix, key, pairs, control: bool):
    """Widest margin of a served token below the float32 reference's best,
    over ``pairs`` of (prompt, served tokens); with ``control``, also that
    of the token the fp8 control puts first at each of those positions."""
    import jax
    import jax.numpy as jnp
    w = ref.make_weights(c, key)
    widest_fn = jax.jit(_widest_gap)
    sysp = mix.get("system_prompts")
    longest = mix["prompt_len"][1] + (int(sysp["len"]) if sysp else 0)
    k_pad = int(mix["output_len"][1])
    s_pad = -(-(longest + k_pad) // 256) * 256
    widest = widest_c = 0.0
    for prompt, toks in pairs:
        n, plen = len(toks), len(prompt)
        seq = np.zeros(s_pad, np.int32)
        seq[:plen + n - 1] = np.concatenate([prompt, toks[:-1]])
        at = np.zeros(k_pad, np.int32)
        at[:n] = plen - 1 + np.arange(n)
        served = np.zeros(k_pad, np.int32)
        served[:n] = toks
        seq, at, served = jnp.asarray(seq), jnp.asarray(at), jnp.asarray(served)
        lg = ref.forward(w, c, seq, at)
        picks = (jnp.argmax(ref.forward(w, c, seq, at, fp8=True), axis=-1)
                 .astype(jnp.int32) if control else served)
        g, gc_ = widest_fn(lg, served, picks, n)
        widest, widest_c = max(widest, float(g)), max(widest_c, float(gc_))
    del w
    return widest, widest_c
