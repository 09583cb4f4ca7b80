"""Measure end-to-end serving scenarios (``serve/*``) into schema-v2 rows.

A serving row is a ``BenchResult`` like any kernel cell, so the whole
existing toolchain — BENCH_*.json artifacts, ``obs.cli compare`` noise
gating, ``experiments/make_report.py`` — applies unchanged:

  us_median / times_us   median / raw per-step decode latency in µs (the
                         gated quantity: the compare gate keys on
                         ``us_median`` and derives noise from the
                         ``times_us`` IQR)
  tokens_per_s           emitted tokens / measured wall time
  ttft_ms_p50 / p99      time-to-first-token percentiles
  decode_ms_p50 / p99    per-step decode latency percentiles (ms)
  occupancy_mean         mean active-slots / batch over decode steps
  requests / tokens      totals for the measured run

Measurement protocol: build the model once, replay the trace once as
warmup (compiling every prefill bucket and the decode step), then replay
it again on fresh ``Request`` objects with a fresh metrics registry —
the measured run is compile-free, matching ``bench.timing``'s
warmup-then-measure discipline.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from ..obs import metrics as obs_metrics
from ..obs.trace import get_tracer
from .results import BenchResult, now_iso
from .scenario import ServeScenario

__all__ = ["run_serve_scenario"]


def _trace_requests(w: Dict[str, Any], vocab: int):
    from ..serve import make_trace
    return make_trace(
        w.get("arrival", "uniform"), int(w.get("n_requests", 8)),
        vocab=vocab, rate=float(w.get("rate", 0.5)),
        burst=int(w.get("burst", 4)), seed=int(w.get("seed", 0)),
        prompt_lens=tuple(w.get("prompt_lens", (5, 16))),
        max_new=tuple(w.get("max_new", (4, 8))),
        prefix_len=int(w.get("prefix_len", 0)),
        prefix_group=int(w.get("prefix_group", 0)))


def run_serve_scenario(sc: ServeScenario, opts=None) -> BenchResult:
    """Run one serving scenario (warmup replay + measured replay) and
    return its result row.  ``opts`` is a ``runner.RunOptions`` (only
    ``chip`` and ``emit`` apply; serving always runs compiled-for-host)."""
    from ..configs import get_smoke_config
    from ..distributed.sharding import split_tree
    from ..launch.serve import Request, ServingLoop, slot_rows
    from ..models import build_model

    w = sc.workload
    cfg = get_smoke_config(w.get("arch", "qwen2-1.5b"))
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(0)))

    trace = _trace_requests(w, cfg.vocab)
    block_len = int(w.get("block_len", 8))

    def build_loop(prefix_cache, chunk_tokens=None):
        return ServingLoop(
            cfg, params, batch=int(w.get("batch", 2)),
            seed=int(w.get("seed", 0)),
            max_new=max(r.max_new for r in trace),
            scheduler=w.get("scheduler", "continuous"),
            block_len=block_len, max_seq=slot_rows(trace, block_len),
            chunk_tokens=(w.get("chunk_tokens") if chunk_tokens is None
                          else chunk_tokens),
            prefix_cache=prefix_cache)

    loop = build_loop(bool(w.get("prefix_cache", False)))

    def replay(requests, lp=None):
        return (lp or loop).run(requests, temperature=0.0)

    def fresh():
        return [Request(uid=r.uid, prompt=r.prompt, max_new=r.max_new,
                        arrival=r.arrival) for r in trace]

    cache = getattr(loop.scheduler, "cache", None)
    with get_tracer().span(f"scenario:{sc.name}",
                           scheduler=w.get("scheduler", "continuous"),
                           arrival=w.get("arrival", "uniform"),
                           n_requests=len(trace)) as span:
        with get_tracer().span("serve.warmup"):
            replay(fresh())                 # compiles every shape
        if cache is not None and loop.prefix_cache:
            # forget warmup's retained blocks: the measured replay's hit
            # ratio must reflect a cold start, not a pre-seeded cache
            cache.reset_prefix_cache()
        measured = obs_metrics.Registry()
        loop.scheduler.metrics = measured   # fresh counters for the run
        t0 = time.perf_counter()
        results = replay(fresh())
        wall_s = time.perf_counter() - t0

        snap = {row["name"]: row for row in measured.snapshot()}
        dec = snap.get("serve.decode_ms", {})
        ttft = snap.get("serve.ttft_ms", {})
        occ = snap.get("serve.batch_occupancy", {})
        times_us = [v * 1e3 for v in
                    measured.histogram("serve.decode_ms").samples()]
        n_tokens = sum(len(v) for v in results.values())
        metrics: Dict[str, Any] = {
            "us_median": float(np.median(times_us)) if times_us else 0.0,
            "us_mean": dec.get("mean", 0.0) * 1e3,
            "times_us": times_us,
            "tokens_per_s": n_tokens / wall_s if wall_s > 0 else 0.0,
            "wall_s": wall_s,
            "ttft_ms_p50": ttft.get("p50", 0.0),
            "ttft_ms_p99": ttft.get("p99", 0.0),
            "decode_ms_p50": dec.get("p50", 0.0),
            "decode_ms_p99": dec.get("p99", 0.0),
            "occupancy_mean": occ.get("mean", 0.0),
            "requests": snap.get("serve.requests_total", {}).get("value", 0),
            "tokens": n_tokens,
        }
        if loop.chunk_tokens is not None and cache is not None:
            metrics["cache_hit_ratio"] = cache.cache_hit_ratio
            metrics["prefix_hit_tokens"] = cache.hit_tokens
            metrics["prefix_miss_tokens"] = cache.miss_tokens
        if w.get("check_outputs") and loop.prefix_cache:
            # greedy outputs must be bit-identical with sharing disabled:
            # replay the same trace through a fresh non-sharing chunked
            # loop (same chunk settings) and compare token-for-token
            ref_loop = build_loop(False, chunk_tokens=loop.chunk_tokens)
            ref = replay(fresh(), ref_loop)
            equal = (set(ref) == set(results)
                     and all(ref[u] == results[u] for u in ref))
            metrics["outputs_equal"] = bool(equal)
            if not equal:
                diff = [u for u in results
                        if ref.get(u) != results.get(u)]
                raise RuntimeError(
                    f"{sc.name}: prefix sharing changed greedy outputs "
                    f"for requests {diff[:8]}")
        if span is not None:
            span.attrs["us_median"] = metrics["us_median"]
            span.attrs["tokens_per_s"] = metrics["tokens_per_s"]

    from .runner import RunOptions
    chip = (opts or RunOptions()).resolved_chip()
    result = BenchResult(
        scenario=sc.name, kernel=sc.kernel, shape=list(sc.shape),
        dtype=cfg.dtype, strategy=loop.scheduler_kind, chip=chip,
        metrics=metrics, config=dict(w), config_source="scenario",
        trace_id=span.span_id if span is not None else None,
        kind="measured", section=sc.section or "serve", interpret=False,
        backend=jax.default_backend(), jax_version=jax.__version__,
        created_at=now_iso())
    if opts is not None and opts.emit:
        opts.emit(result)
    return result
