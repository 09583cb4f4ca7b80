"""Candidate enumeration + analytic pruning for the autotuner.

Per kernel we enumerate (strategy x ring depth x tile shape) candidates,
attach an analytic execution-time prediction from the roofline model
(``core.balance`` / ``core.hardware`` peaks, with the per-strategy overlap
terms from the paper's Fig. 3 analysis), and drop candidates that are
*obviously dominated* before any empirical timing:

  * infeasible: tile shapes that do not divide the problem, or whose VMEM
    footprint exceeds the chip's scratch budget;
  * dominated: predicted time worse than ``keep_ratio`` x the best
    prediction (the paper's expectation model is only trusted for coarse
    ordering — the empirical pass decides among the survivors).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core import hardware
from ..core.async_pipeline import (PipelineSpec, Strategy, lane_pad,
                                   vmem_bytes)
from ..kernels import ops
from ..kernels.hotspot import hotspot_flops_bytes, hotspot_scratch
from ..kernels.matmul import matmul_vmem_bytes
from ..kernels.pathfinder import (pathfinder_flops_bytes, pathfinder_scratch,
                                  pathfinder_tiles)
from ..kernels.stream import stream_flops_bytes

#: keep candidates predicted within this factor of the analytic best
DEFAULT_KEEP_RATIO = 2.0

#: per-tile DMA issue overhead used by the strategy model (seconds)
ISSUE_S = 1e-6

#: DMA latency (seconds) before a copy's first byte lands — the 2208.11174
#: Ampere-microbenchmark-style constant the pipeline model amortises against.
#: With issue-ahead A, sustained DMA bandwidth is capped by Little's law at
#: A * t_tile / (latency + t_tile) of peak: a deeper wait group keeps more
#: copies in flight and recovers bandwidth, at the cost of a longer fill.
DMA_LATENCY_S = 2e-6

#: TMA cost terms (Hopper microbenchmark papers, arXiv:2402.13499 /
#: 2501.12084): a single bulk tensor copy has *higher* per-transaction
#: latency than a cp.async group (descriptor parse + mbarrier arrive), but
#: it is issued once by one producer — the per-tile issue overhead is a
#: fraction of the per-copy ISSUE_S a cp.async-style loop pays — and a bulk
#: 2D transaction sustains near-peak HBM bandwidth once the ring covers the
#: latency.
TMA_LATENCY_S = 3e-6
TMA_ISSUE_S = 0.25e-6
TMA_BULK_BW_FRAC = 0.93


def issue_ahead(depth: int, wait_group: Optional[int]) -> int:
    """Issue-ahead distance A for a (depth, wait_group) pipeline shape:
    at most A copies are in flight while tile i computes."""
    d = max(depth, 2)
    return d - 1 if wait_group is None else max(0, min(wait_group, d - 1))


def predict_time(strategy: Strategy, flops: float, nbytes: float, *,
                 depth: int, n_tiles: int,
                 wait_group: Optional[int] = None,
                 chip: Optional[hardware.Chip] = None) -> float:
    """Analytic execution-time model (seconds) for one strategy.

    sync:            t_m * 1.5 + t_c   (staging re-pass through VMEM)
    register_bypass: t_m + t_c         (no overlap, no staging)
    overlap:         max(t_m / bw_frac, t_c) + ring fill, where
                     bw_frac = min(1, A*t_tile / (latency + t_tile)) is the
                     Little's-law bandwidth fraction an issue-ahead of A
                     copies sustains — this is what makes depth an interior
                     optimum: deeper rings recover bandwidth until bw_frac
                     saturates at 1, after which the longer fill only hurts
    drop_off:        same pipeline law at chunk granularity (tile/4), plus
                     chunked issue overhead
    tma:             bulk-copy pipeline at the deepest issue-ahead
                     (depth - 1; the mbarrier has no wait-group axis):
                     max(t_m / bw_frac, t_c) + fill, with the Little's-law
                     fraction against the *higher* TMA per-transaction
                     latency, capped at TMA_BULK_BW_FRAC of peak, and the
                     much smaller single-producer descriptor issue cost
    """
    chip = chip or hardware.TARGET
    t_c = flops / (chip.tflops_f32 * 1e12)
    t_m = nbytes / (chip.mem_bw_gbs * 1e9)
    n_tiles = max(n_tiles, 1)
    issue = ISSUE_S * n_tiles
    if strategy == Strategy.SYNC:
        return t_m * 1.5 + t_c + issue
    if strategy == Strategy.REGISTER_BYPASS:
        return t_m + t_c + issue
    if strategy == Strategy.TMA:
        ahead = max(depth, 2) - 1       # mbarrier: always the deepest ahead
        t_tile = t_m / n_tiles
        bw_frac = TMA_BULK_BW_FRAC * min(
            1.0, ahead * t_tile / (TMA_LATENCY_S + t_tile))
        fill = ahead * t_tile + TMA_LATENCY_S
        return max(t_m / bw_frac, t_c) + fill + TMA_ISSUE_S * n_tiles
    ahead = issue_ahead(depth, wait_group)
    t_tile = t_m / n_tiles
    if strategy == Strategy.OVERLAP:
        if ahead == 0:          # degenerate wait_group=0: no overlap at all
            return t_m + t_c + issue
        bw_frac = min(1.0, ahead * t_tile / (DMA_LATENCY_S + t_tile))
        fill = ahead * t_tile + DMA_LATENCY_S
        return max(t_m / bw_frac, t_c) + fill + issue
    # DROP_OFF: chunk-granularity pipeline, more per-chunk issue overhead
    t_chunk = t_tile / 4
    a_eff = max(ahead, 1)
    bw_frac = min(1.0, a_eff * t_chunk / (DMA_LATENCY_S + t_chunk))
    fill = t_chunk + DMA_LATENCY_S
    return max(t_m / bw_frac, t_c) + fill + 4 * issue


@dataclass
class Candidate:
    """One point of a kernel's search space, with its analytic position."""
    config: Dict[str, Any]
    predicted_us: float = 0.0
    vmem_bytes: int = 0
    feasible: bool = True
    why_pruned: str = ""

    @property
    def strategy(self) -> Strategy:
        return self.config["strategy"]


# ---------------------------------------------------------------------------
# Per-kernel specs: how to build inputs, call the kernel, enumerate tiles,
# and estimate flops/bytes/VMEM for a candidate.
# ---------------------------------------------------------------------------

STRATEGIES: Tuple[Strategy, ...] = tuple(Strategy)
DEPTHS: Tuple[int, ...] = (2, 3, 4)


def strategy_depths(strategy: Strategy) -> Tuple[int, ...]:
    """Ring depths worth searching: SYNC and REGISTER_BYPASS are
    single-buffered (emit ignores depth), so depth variants would be
    duplicate candidates measured twice."""
    if strategy in (Strategy.SYNC, Strategy.REGISTER_BYPASS):
        return (2,)
    return DEPTHS


def strategy_depth_waits(strategy: Strategy
                         ) -> Tuple[Tuple[int, Optional[int]], ...]:
    """(depth, wait_group) pipeline shapes worth searching per strategy.

    ``wait_group=None`` is the deepest safe issue-ahead (depth - 1).  At
    depth 2 that is the only distinct shape (wait_group 1 == None); deeper
    rings add a shallow-wait variant (wait for tile i with only 1 copy in
    flight) — the ``cp.async.wait_group N`` axis where buffering and
    synchronisation depth decouple.

    TMA has no wait-group axis at all: the per-slot mbarrier tracks every
    outstanding byte of its slot, so the only shape parameter is the ring
    depth (issue-ahead is always depth - 1)."""
    if strategy in (Strategy.SYNC, Strategy.REGISTER_BYPASS):
        return ((2, None),)
    if strategy is Strategy.TMA:
        return tuple((d, None) for d in strategy_depths(strategy))
    out = []
    for d in strategy_depths(strategy):
        out.append((d, None))
        if d > 2:
            out.append((d, 1))
    return tuple(out)


def _strategy_depth_pairs():
    return [(s, d, w) for s in STRATEGIES
            for d, w in strategy_depth_waits(s)]


#: row-tile heights for the row-streaming kernels: whole multiples of the
#: 8-row sublane tile, the smallest a 32-bit DMA or slice may be cut at
SUBLANE_TILE_ROWS: Tuple[int, ...] = (8, 16, 32)


def _dtype_bytes(dtype) -> int:
    return jnp.dtype(dtype).itemsize


@dataclass
class KernelSpec:
    name: str
    default_shape: Tuple[int, ...]
    make_args: Callable[[Tuple[int, ...], Any], Tuple]
    call: Callable[..., Any]          # call(args, config, interpret)
    enumerate_configs: Callable[[Tuple[int, ...]], List[Dict[str, Any]]]
    flops_bytes: Callable[[Tuple[int, ...], Any, Dict[str, Any]],
                          Tuple[float, float]]
    n_tiles: Callable[[Tuple[int, ...], Dict[str, Any]], int]
    vmem_bytes: Callable[[Tuple[int, ...], Any, Dict[str, Any]], int]


def _uniform(shape, dtype, seed=0):
    return jax.random.uniform(jax.random.PRNGKey(seed), shape,
                              jnp.dtype(dtype))


# -- stream -----------------------------------------------------------------

STREAM_ITERS = 4          # fixed workload intensity for tuning runs


def _stream_configs(shape):
    rows, _ = shape
    out = []
    for (s, depth, wg), tr, nt in itertools.product(
            _strategy_depth_pairs(), (8, 16, 32), (2, 4, 8)):
        if rows % (tr * nt):
            continue
        out.append(dict(strategy=s, depth=depth, wait_group=wg,
                        out_depth=2, tile_rows=tr, n_tiles=nt))
    return out


def _stream_vmem(shape, dtype, cfg):
    _, width = shape
    isz = _dtype_bytes(dtype)
    tile = cfg["tile_rows"] * width * isz
    d = 1 if cfg["strategy"] in (Strategy.SYNC, Strategy.REGISTER_BYPASS) \
        else cfg["depth"]
    stage = tile if cfg["strategy"] == Strategy.SYNC else 0
    out_d = cfg.get("out_depth", 2)
    return d * tile + out_d * tile + stage      # in ring + out ring + staging


STREAM = KernelSpec(
    name="stream",
    default_shape=(512, 256),
    make_args=lambda shape, dtype: (_uniform(shape, dtype),),
    call=lambda args, cfg, interp: ops.stream(
        args[0], iters=STREAM_ITERS, interpret=interp, **cfg),
    enumerate_configs=_stream_configs,
    flops_bytes=lambda shape, dtype, cfg: stream_flops_bytes(
        shape, STREAM_ITERS, _dtype_bytes(dtype)),
    n_tiles=lambda shape, cfg: cfg["n_tiles"],
    vmem_bytes=_stream_vmem,
)


# -- matmul -----------------------------------------------------------------

def _matmul_configs(shape):
    m, k, n = shape
    out = []
    for (s, depth, wg), bm, bk, bn in itertools.product(
            _strategy_depth_pairs(), (128, 256), (128, 256), (128, 256)):
        if m % bm or k % bk or n % bn:
            continue
        out.append(dict(strategy=s, depth=depth, wait_group=wg,
                        bm=bm, bk=bk, bn=bn))
    return out


def _matmul_flops_bytes(shape, dtype, cfg):
    m, k, n = shape
    isz = _dtype_bytes(dtype)
    flops = 2.0 * m * k * n
    # A streamed once per N-block, B once per M-block, fp32 C written once
    nbytes = (m * k * (n // cfg["bn"]) + k * n * (m // cfg["bm"])) * isz \
        + m * n * 4
    return flops, nbytes


MATMUL = KernelSpec(
    name="matmul",
    default_shape=(256, 256, 256),
    make_args=lambda shape, dtype: (
        _uniform((shape[0], shape[1]), dtype, 0),
        _uniform((shape[1], shape[2]), dtype, 1)),
    call=lambda args, cfg, interp: ops.matmul(
        args[0], args[1], interpret=interp, **cfg),
    enumerate_configs=_matmul_configs,
    flops_bytes=_matmul_flops_bytes,
    n_tiles=lambda shape, cfg: shape[1] // cfg["bk"],
    vmem_bytes=lambda shape, dtype, cfg: matmul_vmem_bytes(
        cfg["strategy"], cfg["bm"], cfg["bk"], cfg["bn"], cfg["depth"],
        _dtype_bytes(dtype)),
)


# -- hotspot ----------------------------------------------------------------

def _hotspot_configs(shape):
    rows, _ = shape
    out = []
    for (s, depth, wg), tr in itertools.product(_strategy_depth_pairs(),
                                                SUBLANE_TILE_ROWS):
        if rows % tr:
            continue
        out.append(dict(strategy=s, depth=depth, wait_group=wg,
                        out_depth=2, tile_rows=tr))
    return out


def _hotspot_vmem(shape, dtype, cfg):
    return vmem_bytes(hotspot_scratch(PipelineSpec.from_config(cfg),
                                      cfg["tile_rows"], shape[1], dtype))


HOTSPOT = KernelSpec(
    name="hotspot",
    default_shape=(256, 256),
    make_args=lambda shape, dtype: (_uniform(shape, dtype, 0),
                                    _uniform(shape, dtype, 1)),
    call=lambda args, cfg, interp: ops.hotspot(
        args[0], args[1], iters=1, interpret=interp, **cfg),
    enumerate_configs=_hotspot_configs,
    flops_bytes=lambda shape, dtype, cfg: hotspot_flops_bytes(
        shape, _dtype_bytes(dtype), cfg["tile_rows"]),
    n_tiles=lambda shape, cfg: max(shape[0] // cfg["tile_rows"], 1),
    vmem_bytes=_hotspot_vmem,
)


# -- lud --------------------------------------------------------------------

def _lud_configs(shape):
    n = shape[0]
    out = []
    for (s, depth, wg), bs in itertools.product(_strategy_depth_pairs(),
                                                (16, 32, 64)):
        if n % bs or bs >= n:
            continue
        out.append(dict(strategy=s, depth=depth, wait_group=wg,
                        out_depth=2, bs=bs))
    return out


LUD = KernelSpec(
    name="lud",
    default_shape=(64,),     # interpret-mode compile cost grows fast with n
    make_args=lambda shape, dtype: (
        (_uniform((shape[0], shape[0]), dtype)
         + shape[0] * jnp.eye(shape[0], dtype=jnp.dtype(dtype))),),
    call=lambda args, cfg, interp: ops.lud(args[0], interpret=interp, **cfg),
    enumerate_configs=_lud_configs,
    flops_bytes=lambda shape, dtype, cfg: (
        (2.0 / 3.0) * shape[0] ** 3,
        2.0 * shape[0] ** 3 / (3.0 * cfg["bs"]) * _dtype_bytes(dtype)),
    n_tiles=lambda shape, cfg: max(shape[0] // cfg["bs"] - 1, 1),
    vmem_bytes=lambda shape, dtype, cfg: (
        (2 + (1 if cfg["strategy"] in (Strategy.SYNC,
                                       Strategy.REGISTER_BYPASS)
          else cfg["depth"]) * 2 + cfg.get("out_depth", 2) + 2)
        * 128 * cfg["bs"] * _dtype_bytes(dtype)),
)


# -- nw ---------------------------------------------------------------------

def _nw_configs(shape):
    n = shape[0]
    out = []
    for (s, depth, wg), tr in itertools.product(_strategy_depth_pairs(),
                                                SUBLANE_TILE_ROWS):
        if n % tr:
            continue
        out.append(dict(strategy=s, depth=depth, wait_group=wg,
                        out_depth=2, tile_rows=tr))
    return out


def _nw_width(n):
    return ((n + 1 + 127) // 128) * 128


NW = KernelSpec(
    name="nw",
    default_shape=(128,),
    make_args=lambda shape, dtype: (
        jax.random.randint(jax.random.PRNGKey(0),
                           (shape[0], shape[0]), -3, 4).astype(jnp.float32),),
    call=lambda args, cfg, interp: ops.nw(
        args[0], penalty=10, interpret=interp, **cfg),
    enumerate_configs=_nw_configs,
    flops_bytes=lambda shape, dtype, cfg: (
        4.0 * shape[0] * _nw_width(shape[0]),
        2.0 * shape[0] * _nw_width(shape[0]) * 4),
    n_tiles=lambda shape, cfg: max(shape[0] // cfg["tile_rows"], 1),
    vmem_bytes=lambda shape, dtype, cfg: (
        ((1 if cfg["strategy"] in (Strategy.SYNC, Strategy.REGISTER_BYPASS)
          else cfg["depth"]) + 1 + cfg.get("out_depth", 2) +
         (1 if cfg["strategy"] == Strategy.SYNC else 0))
        * cfg["tile_rows"] * _nw_width(shape[0]) * 4),
)


# -- pathfinder -------------------------------------------------------------

def _pathfinder_configs(shape):
    # any row count: the wrapper pads rows to a whole tile
    return [dict(strategy=s, depth=depth, wait_group=wg, tile_rows=tr)
            for (s, depth, wg), tr in itertools.product(
                _strategy_depth_pairs(), SUBLANE_TILE_ROWS)]


PATHFINDER = KernelSpec(
    name="pathfinder",
    default_shape=(129, 256),
    make_args=lambda shape, dtype: (
        jax.random.randint(jax.random.PRNGKey(0), shape, 0, 10, jnp.int32),),
    call=lambda args, cfg, interp: ops.pathfinder(
        args[0], interpret=interp, **cfg),
    enumerate_configs=_pathfinder_configs,
    flops_bytes=lambda shape, dtype, cfg: pathfinder_flops_bytes(
        shape, 4, cfg["tile_rows"]),
    n_tiles=lambda shape, cfg: pathfinder_tiles(shape[0], cfg["tile_rows"]),
    vmem_bytes=lambda shape, dtype, cfg: vmem_bytes(pathfinder_scratch(
        PipelineSpec.from_config(cfg), cfg["tile_rows"], shape[1],
        jnp.int32)),
)


# -- flash attention --------------------------------------------------------

def _flash_configs(shape):
    _, s_len, _ = shape
    out = []
    for (s, depth, wg), bq, bk in itertools.product(
            _strategy_depth_pairs(), (128, 256), (128, 256)):
        if s_len % bq or s_len % bk:
            continue
        out.append(dict(strategy=s, depth=depth, wait_group=wg,
                        bq=bq, bk=bk))
    return out


def _flash_flops_bytes(shape, dtype, cfg):
    h, s, d = shape
    isz = _dtype_bytes(dtype)
    flops = 2.0 * 2.0 * h * s * s * d * 0.5          # 2 matmuls, causal half
    nbytes = h * (s // cfg["bq"]) * 2 * s * d * isz * 0.5 \
        + h * s * d * (isz + 4)
    return flops, nbytes


FLASH = KernelSpec(
    name="flash_attention",
    default_shape=(2, 256, 64),
    make_args=lambda shape, dtype: tuple(
        jax.random.normal(jax.random.PRNGKey(i), shape, jnp.dtype(dtype))
        for i in range(3)),
    call=lambda args, cfg, interp: ops.flash_attention(
        args[0], args[1], args[2], causal=True, interpret=interp, **cfg),
    enumerate_configs=_flash_configs,
    flops_bytes=_flash_flops_bytes,
    n_tiles=lambda shape, cfg: max(shape[1] // cfg["bk"], 1),
    vmem_bytes=lambda shape, dtype, cfg: (
        ((1 if cfg["strategy"] in (Strategy.SYNC, Strategy.REGISTER_BYPASS)
          else cfg["depth"]) * 2 * cfg["bk"] * lane_pad(shape[2])
         * _dtype_bytes(dtype))
        + cfg["bq"] * lane_pad(shape[2]) * (_dtype_bytes(dtype) + 4)
        + cfg["bq"] * 8),
)


SPECS: Dict[str, KernelSpec] = {
    s.name: s for s in
    (STREAM, MATMUL, HOTSPOT, LUD, NW, PATHFINDER, FLASH)
}

KERNELS: Tuple[str, ...] = tuple(SPECS)


# ---------------------------------------------------------------------------
# SearchSpace + TuningTask
# ---------------------------------------------------------------------------

class SearchSpace:
    """All candidates for (kernel, shape, dtype) with analytic annotations."""

    def __init__(self, kernel: str, shape: Sequence[int],
                 dtype: str = "float32",
                 chip: Optional[hardware.Chip] = None,
                 vmem_limit: Optional[int] = None):
        if kernel not in SPECS:
            raise KeyError(f"unknown kernel {kernel!r}; known: {KERNELS}")
        self.spec = SPECS[kernel]
        self.kernel = kernel
        self.shape = tuple(int(s) for s in shape)
        self.dtype = dtype
        self.chip = chip or hardware.TARGET
        if vmem_limit is not None:
            self.vmem_limit = vmem_limit
        elif self.chip.vmem_mb:
            self.vmem_limit = int(self.chip.vmem_mb * 2 ** 20)
        else:
            self.vmem_limit = hardware.VMEM_BYTES

    def annotate(self, config: Dict[str, Any]) -> Candidate:
        flops, nbytes = self.spec.flops_bytes(self.shape, self.dtype, config)
        t = predict_time(config["strategy"], flops, nbytes,
                         depth=config["depth"],
                         n_tiles=self.spec.n_tiles(self.shape, config),
                         wait_group=config.get("wait_group"),
                         chip=self.chip)
        vmem = int(self.spec.vmem_bytes(self.shape, self.dtype, config))
        return Candidate(config=dict(config), predicted_us=t * 1e6,
                         vmem_bytes=vmem)

    def candidates(self) -> List[Candidate]:
        return [self.annotate(c)
                for c in self.spec.enumerate_configs(self.shape)]

    def pruned(self, keep_ratio: float = DEFAULT_KEEP_RATIO
               ) -> Tuple[List[Candidate], List[Candidate]]:
        """(survivors, dropped).  Drops VMEM-infeasible candidates, pipeline
        shapes past analytic break-even (issue-ahead covering the whole tile
        stream — the ring fill then costs the entire memory time up front,
        so the async pipeline provably cannot beat the synchronous bound),
        and candidates analytically dominated by more than ``keep_ratio``."""
        cands = self.candidates()
        for c in cands:
            if c.vmem_bytes > self.vmem_limit:
                c.feasible = False
                c.why_pruned = (f"vmem {c.vmem_bytes} > "
                                f"limit {self.vmem_limit}")
        for c in cands:
            if not c.feasible:
                continue
            if c.config["strategy"] in (Strategy.OVERLAP, Strategy.DROP_OFF,
                                        Strategy.TMA):
                ahead = issue_ahead(c.config["depth"],
                                    c.config.get("wait_group"))
                n = max(self.spec.n_tiles(self.shape, c.config), 1)
                if ahead >= n:
                    c.feasible = False
                    c.why_pruned = (
                        f"break-even: issue-ahead {ahead} >= n_tiles {n}; "
                        "ring fill spans the whole stream, cannot beat sync")
        feasible = [c for c in cands if c.feasible]
        if feasible:
            best = min(c.predicted_us for c in feasible)
            for c in feasible:
                if c.predicted_us > keep_ratio * best:
                    c.feasible = False
                    c.why_pruned = (f"predicted {c.predicted_us:.1f}us > "
                                    f"{keep_ratio:g}x best {best:.1f}us")
        survivors = [c for c in cands if c.feasible]
        dropped = [c for c in cands if not c.feasible]
        return survivors, dropped


@dataclass
class TuningTask:
    """One tunable cell: a kernel at a concrete shape/dtype on a chip."""
    kernel: str
    shape: Tuple[int, ...]
    dtype: str = "float32"
    chip: str = hardware.TARGET.name
    interpret: bool = True
    keep_ratio: float = DEFAULT_KEEP_RATIO
    space: SearchSpace = field(init=False)

    def __post_init__(self):
        self.shape = tuple(int(s) for s in self.shape)
        self.space = SearchSpace(self.kernel, self.shape, self.dtype,
                                 chip=hardware.get_chip(self.chip))

    def make_args(self) -> Tuple:
        return self.space.spec.make_args(self.shape, self.dtype)

    def call(self, args: Tuple, config: Dict[str, Any]):
        return self.space.spec.call(args, config, self.interpret)


def default_task(kernel: str, *, shape: Optional[Sequence[int]] = None,
                 dtype: str = "float32", interpret: bool = True,
                 chip: Optional[str] = None) -> TuningTask:
    spec = SPECS[kernel]
    return TuningTask(kernel=kernel,
                      shape=tuple(shape or spec.default_shape), dtype=dtype,
                      chip=chip or hardware.TARGET.name, interpret=interpret)
