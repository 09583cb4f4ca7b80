"""Training step construction + the fault-tolerant training driver.

``build_train_step`` returns a jit-able pure function
    (params, opt_state, batch, step) -> (params, opt_state, metrics)
with gradient-accumulation microbatching (activation memory ~ 1/A), optional
bf16 gradient-accumulator compression (the cross-replica reduce then moves
half the bytes), remat-inside-scan, and ZeRO-1 moment sharding constraints.

Run as a script it trains the published config on the local device
(``--smoke`` trains the 2-layer preset instead):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke --steps 50
"""
from __future__ import annotations

import argparse
import functools
import logging
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.compile_cache import use_persistent_cache
from ..core.config import ArchConfig, RunConfig
from ..distributed import sharding as shd
from ..distributed.fault_tolerance import (PreemptionGuard, StepStats,
                                           run_with_retries)
from ..models import build_model
from ..optim import adamw_init, adamw_update, lr_schedule, moment_shardings

log = logging.getLogger("repro.train")


def microbatch_split(batch: Dict[str, jax.Array], n: int):
    """(B, ...) -> (n, B/n, ...), keeping the batch dim data-sharded."""
    def split(x):
        b = x.shape[0]
        assert b % n == 0, (b, n)
        x = x.reshape(n, b // n, *x.shape[1:])
        return shd.logical(x, None, "batch", *([None] * (x.ndim - 2)))
    return jax.tree.map(split, batch)


def build_train_step(model, run: RunConfig, rules=None):
    cfg: ArchConfig = model.cfg
    accum_dtype = jnp.bfloat16 if run.grad_compression == "bf16" \
        else jnp.float32

    def loss_fn(params, mb):
        return model.loss(params, mb, remat=run.remat)

    def train_step(params, opt_state, batch, step):
        with shd.use_rules(rules):
            a = run.microbatches
            if a > 1:
                mbs = microbatch_split(batch, a)

                def acc_body(carry, mb):
                    g_acc, metric_acc = carry
                    (_, metrics), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(params, mb)
                    g_acc = jax.tree.map(
                        lambda acc, g: acc + g.astype(accum_dtype),
                        g_acc, grads)
                    metric_acc = jax.tree.map(
                        lambda acc, m: acc + m.astype(jnp.float32),
                        metric_acc, metrics)
                    return (g_acc, metric_acc), None

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, accum_dtype), params)
                m0 = {"ce": jnp.zeros(()), "aux": jnp.zeros(()),
                      "tokens": jnp.zeros(())}
                (grads, metrics), _ = jax.lax.scan(
                    acc_body, (g0, m0), mbs)
                grads = jax.tree.map(
                    lambda g: (g / a).astype(jnp.float32), grads)
                metrics = jax.tree.map(lambda m: m / a, metrics)
                metrics["tokens"] = metrics["tokens"] * a
            else:
                (_, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)

            lr = lr_schedule(step + 1, lr=run.lr, warmup=run.warmup_steps,
                             total=run.total_steps)
            params2, opt2, gnorm = adamw_update(
                grads, opt_state, params, lr=lr,
                weight_decay=run.weight_decay, clip_norm=run.clip_norm)
            if run.zero1 and rules is not None:
                mshard = _moment_shardings_for(params, rules)
                opt2 = opt2._replace(
                    m=jax.tree.map(jax.lax.with_sharding_constraint,
                                   opt2.m, mshard),
                    v=jax.tree.map(jax.lax.with_sharding_constraint,
                                   opt2.v, mshard))
            metrics = dict(metrics, grad_norm=gnorm, lr=lr)
            return params2, opt2, metrics

    return train_step


_AXES_CACHE: dict = {}


def set_param_axes(params_axes):
    """Register the logical axes tree (from split_tree) for ZeRO-1 specs."""
    _AXES_CACHE["axes"] = params_axes


def _moment_shardings_for(params, rules):
    axes = _AXES_CACHE.get("axes")
    if axes is None:
        raise RuntimeError("call set_param_axes(axes_tree) before building "
                           "a ZeRO-1 train step")
    shapes = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
                          params)
    return moment_shardings(axes, shapes, rules)


# ---------------------------------------------------------------------------
# End-to-end local training driver (examples + integration tests call this)
# ---------------------------------------------------------------------------

def train_loop(cfg: ArchConfig, run: RunConfig, *, steps: int,
               batch: int = 8, seq: int = 64,
               ckpt_dir: Optional[str] = None, resume: bool = False,
               log_every: int = 10, straggler_factor: float = 3.0):
    """Single-host training with checkpoint/restart + preemption handling."""
    from ..checkpoint import Checkpointer
    from ..data import synth_batch

    model = build_model(cfg)
    params_ann = model.init(jax.random.PRNGKey(run.seed))
    params, axes = shd.split_tree(params_ann)
    set_param_axes(axes)
    opt_state = adamw_init(params)
    start_step = 0

    ckpt = Checkpointer(ckpt_dir, async_save=True) if ckpt_dir else None
    if ckpt and resume and ckpt.latest_step() is not None:
        restored = ckpt.restore({"params": params, "opt": opt_state})
        params = jax.tree.map(jnp.asarray, restored["params"])
        opt_state = jax.tree.map(jnp.asarray, restored["opt"])
        start_step = ckpt.latest_step()
        log.info("resumed from step %d", start_step)

    step_fn = jax.jit(build_train_step(model, run))
    stats = StepStats()
    history = []
    with PreemptionGuard() as guard:
        for step in range(start_step, steps):
            data = synth_batch(cfg, batch=batch, seq=seq, seed=run.seed,
                               step=step)
            data = {k: jnp.asarray(v) for k, v in data.items()}

            def do_step():
                return step_fn(params, opt_state, data,
                               jnp.asarray(step, jnp.int32))

            t0 = time.time()
            params, opt_state, metrics = run_with_retries(do_step)
            jax.block_until_ready(metrics["ce"])
            stats.record(step, time.time() - t0,
                         factor=straggler_factor)
            history.append(float(metrics["ce"]))
            if step % log_every == 0:
                log.info("step %d ce=%.4f gnorm=%.3f", step,
                         float(metrics["ce"]), float(metrics["grad_norm"]))
            if ckpt and (guard.requested or step == steps - 1):
                ckpt.save(step + 1, {"params": params, "opt": opt_state})
                if guard.requested:
                    log.warning("preempted at step %d: state saved", step)
                    break
    if ckpt:
        ckpt.wait()
    return params, opt_state, history


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--smoke", action="store_true",
                    help="train the arch's 2-layer smoke preset instead of "
                         "its published config")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--tuning-registry", default=None,
                    help="autotuning registry JSON (default "
                         "./tuning_registry.json)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    use_persistent_cache()
    from ..tuning import apply_tuned_kernel_defaults
    apply_tuned_kernel_defaults(args.tuning_registry)

    from ..configs import get_config
    cfg = get_config(args.arch, smoke=args.smoke)
    run = RunConfig(total_steps=args.steps, warmup_steps=max(args.steps // 10, 1))
    _, _, history = train_loop(cfg, run, steps=args.steps, batch=args.batch,
                               seq=args.seq, ckpt_dir=args.ckpt,
                               resume=args.resume)
    print(f"first-10 ce={sum(history[:10])/max(len(history[:10]),1):.4f} "
          f"last-10 ce={sum(history[-10:])/max(len(history[-10:]),1):.4f}")


if __name__ == "__main__":
    main()
