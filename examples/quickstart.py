"""Quickstart: build a model from an assigned architecture config, run a
forward pass, take one training step, prefill + decode a few tokens, then
autotune a Pallas kernel and reuse the cached winner.

    PYTHONPATH=src python examples/quickstart.py [--arch qwen2-1.5b]
"""
import argparse
import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_smoke_config
from repro.core.config import RunConfig
from repro.data import synth_batch
from repro.distributed.sharding import split_tree
from repro.launch.train import build_train_step, set_param_axes
from repro.models import build_model
from repro.optim import adamw_init


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=ARCH_NAMES)
    args = ap.parse_args()

    # reduced config of the same family (full configs are dry-run only)
    cfg = get_smoke_config(args.arch)
    print(f"arch={cfg.name} family={cfg.family} layers={cfg.n_layers} "
          f"d_model={cfg.d_model}")

    model = build_model(cfg)
    params, axes = split_tree(model.init(jax.random.PRNGKey(0)))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"params: {n_params:,}")

    batch = {k: jnp.asarray(v) for k, v in
             synth_batch(cfg, batch=2, seq=32, seed=0, step=0).items()}

    # forward
    logits = jax.jit(model.forward)(params, batch)
    print(f"forward logits: {logits.shape}")

    # one training step
    set_param_axes(axes)
    run = RunConfig(microbatches=2, zero1=False, warmup_steps=1,
                    total_steps=10)
    step_fn = jax.jit(build_train_step(model, run))
    params, opt, metrics = step_fn(params, adamw_init(params), batch,
                                   jnp.zeros((), jnp.int32))
    print(f"train step: ce={float(metrics['ce']):.4f} "
          f"grad_norm={float(metrics['grad_norm']):.3f}")

    # prefill + decode 4 tokens greedily
    lg, state = jax.jit(lambda p, b: model.prefill(p, b, budget=40))(params,
                                                                     batch)
    toks = []
    for _ in range(4):
        t = jnp.argmax(lg[..., :cfg.vocab], axis=-1)[:, None]
        toks.append(t)
        lg, state = jax.jit(model.decode_step)(params, state,
                                               t.astype(jnp.int32))
    print("decoded:", jnp.concatenate(toks, 1).tolist())

    # --- Serving (continuous batching) --------------------------------------
    # ServingLoop serves a request queue with slot-level continuous
    # batching over a paged KV cache (src/repro/serve/README.md): a slot
    # is refilled the moment its request finishes instead of waiting for
    # the whole cohort, and every slot shares one block arena sized by a
    # global token budget.  Greedy outputs are bit-identical to solo
    # prefill+decode regardless of arrival order.  make_trace builds
    # deterministic uniform/poisson/bursty arrival traces.
    from repro.launch.serve import ServingLoop
    from repro.serve import make_trace

    loop = ServingLoop(cfg, params, batch=2, max_new=8, block_len=8)
    reqs = make_trace("poisson", 4, vocab=cfg.vocab, rate=0.5, seed=0,
                      prompt_lens=(5, 12), max_new=(4, 8))
    results = loop.run(reqs, max_steps=8)
    served = sum(len(v) for v in results.values())
    occ = loop.metrics.histogram("serve.batch_occupancy").snapshot()
    print(f"serve: [{loop.scheduler_kind}] {len(results)} requests / "
          f"{served} tokens, mean occupancy {occ['mean']:.2f}")
    # CLI equivalent:
    #   python -m repro.launch.serve --smoke --arrival poisson --requests 8 \
    #       --batch 4 --ragged --scheduler continuous --metrics-json m.json

    # Chunked prefill + copy-on-write prefix sharing: prefill runs in
    # chunk_tokens-sized pieces interleaved with decode (bounds TTFT under
    # long prompts), and prefix_cache=True content-addresses finished KV
    # blocks so requests sharing a prompt prefix (a system prompt, a
    # few-shot preamble) map it by reference instead of recomputing it.
    # Greedy outputs stay bit-identical to serving without sharing.
    shared = ServingLoop(cfg, params, batch=2, max_new=8, block_len=8,
                         chunk_tokens=16, prefix_cache=True)
    reqs = make_trace("poisson", 4, vocab=cfg.vocab, rate=0.5, seed=0,
                      prompt_lens=(5, 12), max_new=(4, 8),
                      prefix_len=16, prefix_group=2)
    shared.run(reqs, max_steps=8)
    hit = shared.scheduler.cache.cache_hit_ratio
    print(f"serve: prefix sharing cache-hit ratio {hit:.2f}")
    # CLI equivalent:
    #   python -m repro.launch.serve --smoke --arrival poisson --requests 8 \
    #       --prefix-len 16 --prefix-group 2 --block-len 8 --prefix-cache \
    #       --chunk-tokens 16 --metrics-json m.json

    # --- Autotuning ---------------------------------------------------------
    # The async-copy strategy / ring depth / tile shape of every Pallas
    # kernel are searched empirically (timed with the repo's one canonical
    # protocol, repro.bench.timing) and cached in a persistent registry
    # (schema-versioned JSON).  First call measures; every later run — and
    # serve.py / train.py at startup — reuses the cached winner.
    import tempfile
    from repro.kernels import ops
    from repro.tuning import Autotuner, Registry, default_task, tuned

    registry = Registry(os.path.join(tempfile.mkdtemp(), "registry.json"))
    task = default_task("stream", shape=(64, 128))
    rec = Autotuner(registry, repeats=2).tune(task)
    strat = rec.best["strategy"]
    print(f"autotune: stream best={strat} "
          f"{rec.best_us:.0f}us ({rec.speedup_vs_default:.2f}x vs default, "
          f"{rec.n_candidates} measured / {rec.n_pruned} pruned "
          f"analytically)")
    cfg = tuned("stream", (64, 128), registry=registry)   # cache hit
    x = jax.random.uniform(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    y = ops.stream(x, iters=4, **cfg)
    print(f"autotune: tuned stream call ok, out={y.shape}; registry at "
          f"{registry.path}")
    # CLI equivalent:  python -m repro.tuning.cli tune --kernel stream

    # --- Benchmarking (repro.bench) -----------------------------------------
    # Benchmarks are declarative: a Scenario names one (kernel x shape x
    # dtype x strategy) cell, the runner resolves the config (tuning
    # registry winner when one exists — config_source says which), checks
    # the kernel against its kernels/ref.py oracle, times it, and emits a
    # schema-v2 result row with full provenance.  `sweep` additionally
    # projects every scenario across the whole Chip lineage (the paper's
    # generation study).  See src/repro/bench/README.md to add a workload.
    from repro.bench import runner, scenarios

    sc = scenarios(only="smoke/stream")[0]
    res = runner.run_scenario(sc, runner.RunOptions(
        repeats=2, registry=registry))
    print(f"bench: {res.scenario} strategy={res.strategy} "
          f"config_source={res.config_source} "
          f"us_median={res.metrics['us_median']:.0f} "
          f"max_err={res.metrics['max_err']:.1e}")
    # CLI equivalents:
    #   python -m repro.bench.cli list                    # all scenarios
    #   python -m repro.bench.cli run --only fig3         # one figure
    #   python -m repro.bench.cli sweep --smoke --json BENCH_sweep.json

    # --- N-stage pipelines + the regime map ---------------------------------
    # Every kernel's async pipeline has a first-class shape: ring depth
    # (VMEM slots, not just double-buffering), wait_group (how many copies
    # may still be in flight when compute starts — the TPU analogue of
    # cp.async.wait_group N) and out_depth (write-back ring).  Pass them
    # per call, or as a PipelineSpec to the *_pallas entry points.
    y3 = ops.stream(x, iters=4, strategy="overlap", depth=3, wait_group=1)
    print(f"stream depth=3 wait_group=1 ok, out={y3.shape}")

    # Hopper-style TMA bulk copies are a strategy too: one descriptor per
    # tile, all operands completing on a shared per-slot mbarrier, always
    # the deepest issue-ahead (no wait_group axis).
    y4 = ops.stream(x, iters=4, strategy="tma", depth=3)
    print(f"stream strategy=tma depth=3 ok, out={y4.shape}")

    # The regime/* scenario family measures, per kernel, a sync baseline
    # plus async at ring depths 2/3/4; sweep() folds the measurements into
    # one "async pays / neutral / hurts" verdict row with the measured
    # break-even depth.
    regime_scs = scenarios(tag="regime", kernel="stream")
    report = runner.sweep(regime_scs, chips=["TPUv5e"], opts=runner.RunOptions(
        warmup=0, repeats=1, registry=registry))
    (verdict,) = [r for r in report.results if r.kind == "regime"]
    m = verdict.metrics
    be = m["break_even_depth"]
    print(f"regime: stream async {m['verdict']} "
          f"(break-even depth={be if be is not None else '-'}, "
          f"best=d{m['best_depth']}, {m['speedup']:.2f}x vs sync)")
    # CLI equivalent:
    #   python -m repro.bench.cli sweep --tag regime --json BENCH_regime.json

    # --- Lineage validation (repro.bench.lineage) ---------------------------
    # The paper's §6 expectation model, made predictive: catalog-derived
    # speedups for the K80 -> ... -> H100 arc, judged against committed
    # published numbers (experiments/baselines/LINEAGE_hopper.json).
    from repro.bench import lineage
    from repro.core import balance, hardware

    exp = balance.expect_speedup(hardware.get_chip("A100"),
                                 hardware.get_chip("H100-SXM"))
    verdicts = lineage.validate(lineage.load_reference(
        lineage.default_reference_path()))
    print(f"lineage: A100->H100-SXM expected {exp.expected:.2f}x "
          f"({exp.binds} bind); "
          f"{sum(v.ok for v in verdicts)}/{len(verdicts)} pairs within band")
    # CLI equivalent:  python -m repro.bench.cli lineage --json LINEAGE.json

    # --- Observability (repro.obs) ------------------------------------------
    # Tracing is off by default and free when off.  Enabled, every layer of
    # the measurement stack emits nested spans — sweep -> scenario ->
    # warmup/timed trials (and tune -> candidate in the autotuner) — which
    # export to JSONL or a Chrome trace that https://ui.perfetto.dev loads
    # directly.  The serving loop records TTFT / per-token latency /
    # occupancy into labeled metrics the same way.
    from repro.obs.trace import tracer
    from repro.obs.compare import compare_reports

    t = tracer()
    t.clear()
    t.enable()
    res2 = runner.run_scenario(sc, runner.RunOptions(
        repeats=2, registry=registry))
    t.disable()
    spans = t.spans()
    trace_path = os.path.join(tempfile.mkdtemp(), "trace.jsonl")
    t.save_jsonl(trace_path)
    print(f"obs: {len(spans)} spans "
          f"({', '.join(sorted({s.name for s in spans}))}); "
          f"row trace_id={res2.trace_id}; jsonl at {trace_path}")

    # the regression gate: diff two reports using each cell's own measured
    # spread (median +/- k*IQR of the baseline's kept trials), not a naive
    # percent threshold.  Identical runs gate clean.
    rep_a, rep_b = runner.new_report(), runner.new_report()
    rep_a.add(res)
    rep_b.add(res2)
    cmp_res = compare_reports(rep_a, rep_b)
    print(f"obs: gate {'REGRESSED' if cmp_res.n_regressions else 'ok'} "
          f"({cmp_res.counts()})")
    # CLI equivalents:
    #   python -m repro.bench.cli sweep --smoke --trace t.jsonl \
    #       --chrome-trace t.chrome.json
    #   python -m repro.obs.cli summary --trace t.jsonl
    #   python -m repro.obs.cli compare BENCH_base.json BENCH_new.json
    #   python -m repro.launch.serve --smoke --ragged --metrics-json m.json


if __name__ == "__main__":
    main()
