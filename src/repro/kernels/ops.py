"""Public jit'd wrappers around the Pallas kernels.

These are the entry points the framework / benchmarks / tests use.  Every
wrapper accepts ``strategy`` (the paper's async-copy pattern) plus the
pipeline-shape axes ``depth`` / ``wait_group`` (and ``out_depth`` for the
kernels with a write-back ring), is jitted with the structural arguments
static, and has a matching oracle in ``ref.py``.  The flat keywords are
assembled into a ``core.async_pipeline.PipelineSpec`` inside the jitted
implementation.  ``interpret`` defaults to the backend's mode
(``resolve_interpret``): compiled Mosaic kernels on a TPU, the Pallas
interpreter on the CPU, where nothing else can run them.

Config constants are NOT hard-coded per call site: each kernel's tunable
parameters live in ``KERNEL_DEFAULTS`` and any omitted (None) keyword falls
back to that table.  The autotuner (``repro.tuning``) overwrites the table
via ``set_default_config`` with registry winners, so tuned configs flow to
every caller without touching call sites; explicit keywords still win.
"""
from __future__ import annotations

import functools
import logging
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..core.async_pipeline import PipelineSpec, Strategy
from . import flash_attention as _fa
from . import hotspot as _hs
from . import lud as _lud
from . import matmul as _mm
from . import nw as _nw
from . import pathfinder as _pf
from . import stream as _st

log = logging.getLogger("repro.kernels")

__all__ = [
    "stream", "hotspot", "pathfinder", "nw", "lud", "matmul",
    "flash_attention", "Strategy", "KERNEL_DEFAULTS", "default_config",
    "seed_default_config", "set_default_config", "reset_default_configs",
    "resolve_interpret",
]


#: The single source of per-kernel tunable constants (the seed's hard-coded
#: values).  ``repro.tuning.apply_registry_defaults`` replaces entries with
#: empirically-tuned winners.  ``wait_group=None`` means the deepest safe
#: issue-ahead (depth - 1); ``out_depth`` is the write-back ring depth for
#: the kernels that drain through a WriteBack.
KERNEL_DEFAULTS: Dict[str, Dict[str, Any]] = {
    "stream": dict(strategy=Strategy.OVERLAP, tile_rows=8, n_tiles=4,
                   depth=2, wait_group=None, out_depth=2),
    "hotspot": dict(strategy=Strategy.OVERLAP, tile_rows=8, depth=2,
                    wait_group=None, out_depth=2),
    "pathfinder": dict(strategy=Strategy.DROP_OFF, tile_rows=8, depth=2,
                       wait_group=None),
    "nw": dict(strategy=Strategy.REGISTER_BYPASS, tile_rows=8, depth=2,
               wait_group=None, out_depth=2),
    "lud": dict(strategy=Strategy.OVERLAP, bs=32, depth=2, wait_group=None,
                out_depth=2),
    "matmul": dict(strategy=Strategy.OVERLAP, bm=128, bk=128, bn=128,
                   depth=2, wait_group=None),
    "flash_attention": dict(strategy=Strategy.OVERLAP, bq=128, bk=128,
                            depth=2, wait_group=None),
}

_SEED_DEFAULTS = {k: dict(v) for k, v in KERNEL_DEFAULTS.items()}


def default_config(kernel: str) -> Dict[str, Any]:
    """A copy of the current default config for ``kernel``."""
    return dict(KERNEL_DEFAULTS[kernel])


def seed_default_config(kernel: str) -> Dict[str, Any]:
    """The original hard-coded config, regardless of installed tunings."""
    return dict(_SEED_DEFAULTS[kernel])


def set_default_config(kernel: str, **config: Any) -> Dict[str, Any]:
    """Overwrite default constants for ``kernel`` (tuner integration point).

    Unknown keys are rejected so a stale registry cannot inject parameters
    a kernel does not understand."""
    cur = KERNEL_DEFAULTS[kernel]
    unknown = set(config) - set(cur)
    if unknown:
        raise KeyError(f"unknown config keys for {kernel}: {sorted(unknown)}")
    cur.update(config)
    return dict(cur)


def reset_default_configs() -> None:
    """Restore the seed defaults (tests / benchmark baselines)."""
    for k, v in _SEED_DEFAULTS.items():
        KERNEL_DEFAULTS[k] = dict(v)


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else the backend's mode: compiled on a TPU,
    the Pallas interpreter anywhere else.  The one place that default is
    decided, for the wrappers below and for ``bench.runner.RunOptions``."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _resolve(kernel: str, **given: Any) -> Dict[str, Any]:
    cfg = KERNEL_DEFAULTS[kernel]
    return {k: (cfg[k] if v is None else v) for k, v in given.items()}


def _with_seed_fallback(kernel: str, given: Dict[str, Any],
                        call: Callable[[Dict[str, Any]], Any]):
    """Run ``call`` with defaults-resolved config; if a *tuned* default is
    structurally invalid for this problem (tile does not divide the shape,
    raising ValueError), retry once with the seed constants.

    Tuned installs are per-(large)-shape winners promoted to process-wide
    defaults; a smaller call shape must degrade to the seed config, not
    crash.  Explicitly-passed (non-None) parameters are never overridden —
    a user error still raises."""
    cfg = _resolve(kernel, **given)
    seed = {k: (_SEED_DEFAULTS[kernel][k] if v is None else v)
            for k, v in given.items()}
    try:
        return call(cfg)
    except ValueError:
        if cfg == seed:
            raise
        log.warning("tuned %s config %s invalid for this shape; "
                    "falling back to seed defaults", kernel,
                    {k: v for k, v in cfg.items() if given[k] is None})
        return call(seed)


# ---------------------------------------------------------------------------
# jit'd implementations (explicit static config) + resolving wrappers
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "iters", "strategy", "tile_rows", "n_tiles", "depth", "wait_group",
    "out_depth", "interpret"))
def _stream(x, *, iters, strategy, tile_rows, n_tiles, depth, wait_group,
            out_depth, interpret):
    spec = PipelineSpec(strategy=strategy, depth=depth,
                        wait_group=wait_group, out_depth=out_depth)
    return _st.stream_pallas(x, iters=iters, spec=spec, tile_rows=tile_rows,
                             n_tiles=n_tiles, interpret=interpret)


def stream(x, *, iters=1, strategy=None, tile_rows=None, n_tiles=None,
           depth=None, wait_group=None, out_depth=None, interpret=None):
    interpret = resolve_interpret(interpret)
    return _with_seed_fallback(
        "stream", dict(strategy=strategy, tile_rows=tile_rows,
                       n_tiles=n_tiles, depth=depth, wait_group=wait_group,
                       out_depth=out_depth),
        lambda cfg: _stream(x, iters=iters, interpret=interpret, **cfg))


@functools.partial(jax.jit, static_argnames=(
    "iters", "strategy", "tile_rows", "depth", "wait_group", "out_depth",
    "grid", "interpret"))
def _hotspot(temp, power, *, iters, strategy, tile_rows, depth, wait_group,
             out_depth, grid, interpret):
    spec = PipelineSpec(strategy=strategy, depth=depth,
                        wait_group=wait_group, out_depth=out_depth)
    return _hs.hotspot_pallas(temp, power, iters=iters, spec=spec,
                              tile_rows=tile_rows, grid=grid,
                              interpret=interpret)


def hotspot(temp, power, *, iters=1, strategy=None, tile_rows=None,
            depth=None, wait_group=None, out_depth=None, grid=1,
            interpret=None):
    interpret = resolve_interpret(interpret)
    return _with_seed_fallback(
        "hotspot", dict(strategy=strategy, tile_rows=tile_rows, depth=depth,
                        wait_group=wait_group, out_depth=out_depth),
        lambda cfg: _hotspot(temp, power, iters=iters, grid=grid,
                             interpret=interpret, **cfg))


@functools.partial(jax.jit, static_argnames=(
    "strategy", "tile_rows", "depth", "wait_group", "interpret"))
def _pathfinder(wall, *, strategy, tile_rows, depth, wait_group, interpret):
    spec = PipelineSpec(strategy=strategy, depth=depth,
                        wait_group=wait_group)
    return _pf.pathfinder_pallas(wall, spec=spec, tile_rows=tile_rows,
                                 interpret=interpret)


def pathfinder(wall, *, strategy=None, tile_rows=None, depth=None,
               wait_group=None, interpret=None):
    interpret = resolve_interpret(interpret)
    return _with_seed_fallback(
        "pathfinder", dict(strategy=strategy, tile_rows=tile_rows,
                           depth=depth, wait_group=wait_group),
        lambda cfg: _pathfinder(wall, interpret=interpret, **cfg))


@functools.partial(jax.jit, static_argnames=(
    "penalty", "strategy", "tile_rows", "depth", "wait_group", "out_depth",
    "interpret"))
def _nw_jit(seq_scores, *, penalty, strategy, tile_rows, depth, wait_group,
            out_depth, interpret):
    spec = PipelineSpec(strategy=strategy, depth=depth,
                        wait_group=wait_group, out_depth=out_depth)
    return _nw.nw_pallas(seq_scores, penalty, spec=spec,
                         tile_rows=tile_rows, interpret=interpret)


def nw(seq_scores, *, penalty=10, strategy=None, tile_rows=None, depth=None,
       wait_group=None, out_depth=None, interpret=None):
    interpret = resolve_interpret(interpret)
    return _with_seed_fallback(
        "nw", dict(strategy=strategy, tile_rows=tile_rows, depth=depth,
                   wait_group=wait_group, out_depth=out_depth),
        lambda cfg: _nw_jit(seq_scores, penalty=penalty,
                            interpret=interpret, **cfg))


@functools.partial(jax.jit, static_argnames=(
    "bs", "strategy", "depth", "wait_group", "out_depth", "interpret"))
def _lud_jit(a, *, bs, strategy, depth, wait_group, out_depth, interpret):
    spec = PipelineSpec(strategy=strategy, depth=depth,
                        wait_group=wait_group, out_depth=out_depth)
    return _lud.lud_pallas(a, bs=bs, spec=spec, interpret=interpret)


def lud(a, *, bs=None, strategy=None, depth=None, wait_group=None,
        out_depth=None, interpret=None):
    interpret = resolve_interpret(interpret)
    return _with_seed_fallback(
        "lud", dict(bs=bs, strategy=strategy, depth=depth,
                    wait_group=wait_group, out_depth=out_depth),
        lambda cfg: _lud_jit(a, interpret=interpret, **cfg))


@functools.partial(jax.jit, static_argnames=(
    "strategy", "bm", "bk", "bn", "depth", "wait_group", "interpret"))
def _matmul(a, b, *, strategy, bm, bk, bn, depth, wait_group, interpret):
    spec = PipelineSpec(strategy=strategy, depth=depth,
                        wait_group=wait_group)
    return _mm.matmul_pallas(a, b, spec=spec, bm=bm, bk=bk, bn=bn,
                             interpret=interpret)


def matmul(a, b, *, strategy=None, bm=None, bk=None, bn=None, depth=None,
           wait_group=None, interpret=None):
    interpret = resolve_interpret(interpret)
    return _with_seed_fallback(
        "matmul", dict(strategy=strategy, bm=bm, bk=bk, bn=bn, depth=depth,
                       wait_group=wait_group),
        lambda cfg: _matmul(a, b, interpret=interpret, **cfg))


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "strategy", "bq", "bk", "depth",
    "wait_group", "interpret"))
def _flash_jit(q, k, v, *, causal, window, scale, strategy, bq, bk, depth,
               wait_group, interpret):
    spec = PipelineSpec(strategy=strategy, depth=depth,
                        wait_group=wait_group)
    fn = functools.partial(
        _fa.flash_attention_pallas, causal=causal, window=window,
        scale=scale, spec=spec, bq=bq, bk=bk, interpret=interpret)
    for _ in range(q.ndim - 3):
        fn = jax.vmap(fn)
    return fn(q, k, v)


def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    strategy=None, bq=None, bk=None, depth=None,
                    wait_group=None, interpret=None):
    """q: (..., H, S, D), k/v: (..., KVH, S, D); leading dims are vmapped."""
    interpret = resolve_interpret(interpret)
    return _with_seed_fallback(
        "flash_attention", dict(strategy=strategy, bq=bq, bk=bk,
                                depth=depth, wait_group=wait_group),
        lambda cfg: _flash_jit(q, k, v, causal=causal, window=window,
                               scale=scale, interpret=interpret, **cfg))
