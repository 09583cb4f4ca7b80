"""Serving driver: a latent-attention expert decoder (DeepSeek-V2) behind
the program's ``ServingLoop``.

It runs as ``drivers/serve.py`` does and shares its helpers: the same
set-up (weights on the device from the seed, one warm request per chunk
width, each system prompt cached once), the same window, the same
``tokens_per_s``, ``tpot_p95_ms`` and ``ttft_p95_ms``, and the same
``max_gap`` check of a seeded sample against the float32 reference.
What differs:

- the program's ``ArchConfig`` comes from the file's published keys
  (``hidden_size``, ``kv_lora_rank``, ``n_routed_experts``, ...), with
  the file's depth and held share of the experts
  (``n_routed_experts_held`` from ``first_held_expert``); the driver
  refuses to run if a width, the routing or the parameter layout differs;
- in a ``--trace 1`` run each traced decode step also keeps the routing
  counts the step returned, for ``decode_roofline.mla``, and the window's
  ``moe.*`` counters of the program's registry (which move only while the
  tracer records) go to the metric readers.
"""
from __future__ import annotations

import dataclasses
import gc
from typing import Dict

import numpy as np

from chipbench.harness import Check, Outcome, WindowClosed, memory_peak

#: the program's registry counters the readers take, by data key
COUNTERS = {"moe_assignments": "moe.assignments",
            "moe_assignments_here": "moe.assignments_here",
            "moe_expert_rows": "moe.expert_rows"}


def program_config(c: Dict):
    """The program's ``ArchConfig`` for the configuration file ``c``."""
    from repro.configs import get_config
    base = get_config(c["arch"], smoke=bool(c.get("smoke")))
    cfg = dataclasses.replace(
        base, n_layers=c["num_hidden_layers"], dtype=c["dtype"],
        param_dtype=c["param_dtype"],
        moe=dataclasses.replace(base.moe, n_held=c["n_routed_experts_held"],
                                expert_lo=c["first_held_expert"]))
    a, e, y = cfg.attn, cfg.moe, c["rope_scaling"]
    got = {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
        "first_k_dense_replace": cfg.n_dense_layers,
        "tie_word_embeddings": cfg.tie_embeddings,
        "kv_lora_rank": a.kv_lora_rank, "qk_nope_head_dim": a.qk_nope_dim,
        "qk_rope_head_dim": a.qk_rope_dim, "v_head_dim": a.v_head_dim,
        "rope_theta": a.rope_theta,
        "moe_intermediate_size": e.d_ff_expert,
        "n_routed_experts": e.n_experts, "num_experts_per_tok": e.top_k,
        "n_shared_experts": e.n_shared, "norm_topk_prob": e.norm_topk,
        "rope_scaling": {"factor": a.yarn_factor,
                         "original_max_position_embeddings":
                             a.yarn_original_max,
                         "beta_fast": a.yarn_beta_fast,
                         "beta_slow": a.yarn_beta_slow,
                         "mscale_all_dim": a.yarn_mscale_all_dim},
    }
    want = dict({k: c[k] for k in got}, rope_scaling={
        k: y[k] for k in got["rope_scaling"]})
    if got != want:
        raise ValueError(f"program config differs from the file:\n{got}\n"
                         f"!=\n{want}")
    same = {"q_lora_rank": None, "scoring_func": "softmax",
            "topk_method": "greedy", "moe_layer_freq": 1, "n_group": 1,
            "topk_group": 1, "hidden_act": "silu", "attention_bias": False,
            "routed_scaling_factor": 1}
    if (any(c[k] != v for k, v in same.items()) or y["type"] != "yarn"
            or y["mscale"] != y["mscale_all_dim"]):
        raise ValueError(f"the program serves only {same} with YaRN RoPE "
                         "whose mscale equals mscale_all_dim")
    if (cfg.family, cfg.act, cfg.norm, e.shared_gate, e.capacity_factor) != (
            "moe", "swiglu", "rmsnorm", False, 0.0):
        raise ValueError(f"{c['arch']} is not an ungated-shared-expert, "
                         "dropless SwiGLU RMSNorm expert decoder")
    return cfg


class _RoutedDecodeLog:
    """``serve._DecodeLog``'s traced decode steps, each with the routing
    counts (expert layers, experts) the step returned."""

    def __init__(self, loop, cell, serve):
        self.log = serve._DecodeLog(loop, cell)
        self.picks = []
        sched = loop.scheduler
        inner = sched._decode

        def logged(*args):
            out = inner(*args)
            if cell.window.tracing:
                self.picks.append(out[3])
            return out
        sched._decode = logged

    def steps(self, lo: int, n: int):
        """(positions, distinct blocks, picks of held experts, held
        experts picked summed over layers) per traced step."""
        out = []
        for (pos, blocks), pk in zip(self.log.steps, self.picks, strict=True):
            here = np.asarray(pk)[:, lo:lo + n]
            out.append((pos, blocks, int(here.sum()), int((here > 0).sum())))
        return out


def _counters(loop) -> Dict[str, float]:
    rows = {r["name"]: r for r in loop.metrics.snapshot()}
    return {k: float(rows[n]["value"]) if n in rows else 0.0
            for k, n in COUNTERS.items()}


def run(cell, control: bool = False) -> Outcome:
    import jax
    from repro.launch.serve import ServingLoop
    from repro.serve import Request

    serve = cell.module("drivers", "serve")
    c, mix = cell.config, cell.traffic
    srv, chk = mix["server"], mix["check"]
    gen = cell.module("traffic", "gen")
    ref = cell.module("models", c["reference"])
    vocab = c["vocab_size"]
    if vocab % 128:
        raise ValueError("vocab must be a multiple of 128 (the program pads "
                         "its embedding to one)")
    cfg = program_config(c)
    key = cell.key()
    params = ref.make_weights(c, key)
    serve._check_layout(cfg, params)
    jax.block_until_ready(params)

    max_out = int(mix["output_len"][1])
    loop = ServingLoop(cfg, params, batch=srv["batch"],
                       seed=cell.seed % (2 ** 31), max_new=max_out,
                       block_len=c["block_len"], max_seq=srv["max_seq"],
                       total_tokens=srv.get("total_tokens"),
                       chunk_tokens=srv.get("chunk_tokens"),
                       prefix_cache=bool(srv.get("prefix_cache")))
    warm = [Request(uid=-1 - i, prompt=p, max_new=2) for i, p in
            enumerate(serve.warm_prompts(mix, gen, cell.seed, vocab))]
    loop.run(warm, max_steps=max_out)
    cache = loop.scheduler.cache
    reqs = [Request(uid=r["uid"], prompt=r["prompt"], max_new=r["max_new"],
                    arrival=r["arrival"],
                    out_tokens=serve._Tokens(cell.window))
            for r in gen.requests(mix, cell.seed, vocab)]
    decode_log = _RoutedDecodeLog(loop, cell, serve) if cell.trace else None
    hit0, miss0 = cache.hit_tokens, cache.miss_tokens
    moe0 = _counters(loop)

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
    in_use = cache.n_blocks - 1 - cache.free_blocks
    moe = {k: v - moe0[k] for k, v in _counters(loop).items()}
    peak = memory_peak(1)

    served = [r for r in reqs if r.out_tokens.times]
    times = [[t for t in r.out_tokens.times if t < t_end] for r in served]
    in_window = sum(len(ts) for ts in times)
    gaps = [(b - a) * 1e3 for ts in times for a, b in zip(ts, ts[1:])]
    ttft = [r.ttft_ms for r in served]
    n_bad = sum(not all(0 <= t < vocab for t in r.out_tokens)
                for r in served)
    if not gaps:
        raise RuntimeError("no request got a second token inside the window")
    e2e = {"tokens_per_s": in_window / cell.seconds,
           "tpot_p95_ms": serve._p95(gaps), "ttft_p95_ms": serve._p95(ttft)}
    cell.say(f"served {len(served)} requests, "
             f"{sum(r.done for r in served)} finished; {in_window} tokens "
             f"and {len(gaps)} gaps between tokens in the window; prefix "
             f"hits {hits} misses {misses}; arena blocks in use at the "
             f"window's end {in_use} of {cache.n_blocks - 1}; traced "
             f"routing {moe}")
    prompt_computed = (misses if srv.get("prefix_cache")
                       else sum(len(r.prompt) for r in served))
    lo, n_held = c["first_held_expert"], c["n_routed_experts_held"]
    data = dict(moe, tokens_computed=in_window - len(served) + prompt_computed,
                prefix_hit_tokens=hits, prefix_miss_tokens=misses,
                mla_decode_steps=decode_log.steps(lo, n_held)
                if decode_log else [])

    sample = [(r.prompt, list(r.out_tokens)) for r in
              serve._sample(served, int(chk["sample"]), cell.seed)]
    # free the program's state before the reference takes the chip
    del loop, params, reqs, served, warm, cache, decode_log
    gc.collect()
    widest, widest_control = serve._reference_gaps(ref, c, mix, key, sample,
                                                   control)
    checks = [Check("max_gap", widest, float(chk["max_gap"]))]
    if control:
        checks.append(Check("control_max_gap", widest_control,
                            float(chk["max_gap"])))
    return Outcome(e2e, attempted=len(ttft), failed=n_bad, checks=checks,
                   memory_peak_bytes=peak, data=data)
