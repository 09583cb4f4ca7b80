"""Declarative benchmark scenarios: kernel x shape x dtype x strategy.

A ``Scenario`` names one concrete workload — a Pallas kernel at a shape and
dtype, optionally pinned to one async ``Strategy`` and extra config/workload
parameters — so every paper figure and every ad-hoc experiment is an entry
in one registry: enumerable (``scenarios()``), filterable (``--only``,
``--kernel``, ``--strategy``, ``--tag``) and individually runnable
(``repro.bench.runner`` / ``python -m repro.bench.cli run``).

Input construction and the analytic (flops, bytes, vmem) models are shared
with the autotuner via ``tuning.search_space.SPECS`` — a scenario and a
tuning task of the same cell can never disagree about the workload.  What
this module adds on top is the *call adapter* (workload parameters such as
``iters``/``penalty`` that the tuner holds fixed) and the correctness oracle
from ``kernels.ref``.

Registering a new workload::

    from repro.bench.scenario import Scenario, register

    register(Scenario(name="mine/stream_hot", kernel="stream",
                      shape=(1024, 256), workload={"iters": 64},
                      tags=("mine",)))

``strategy=None`` means "whatever the resolved default is" — the tuning
registry's winner when one exists, the seed constant otherwise — which is
exactly what a production call site would get.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from ..core.async_pipeline import Strategy
from ..kernels import ops, ref
from ..tuning.search_space import KERNELS, SPECS

__all__ = ["Scenario", "ServeScenario", "register", "get_scenario",
           "scenarios", "scenario_names", "call_kernel", "check_output",
           "CHECK_TOL", "KERNELS", "SERVE_KERNEL"]

#: pseudo-kernel name marking end-to-end serving scenarios — they run the
#: model serving loop (repro.bench.serving), not a Pallas kernel, so they
#: bypass SPECS/CALLERS/roofline projection entirely.
SERVE_KERNEL = "serve"


@dataclass(frozen=True)
class Scenario:
    """One runnable benchmark cell."""
    name: str                            # unique, hierarchical: "fig3/..."
    kernel: str                          # key into tuning SPECS / ops
    shape: Tuple[int, ...]               # the SPECS shape convention
    dtype: str = "float32"
    strategy: Optional[Strategy] = None  # None -> resolved default/tuned
    config: Dict[str, Any] = field(default_factory=dict)   # tile overrides
    workload: Dict[str, Any] = field(default_factory=dict) # iters/penalty/..
    tags: Tuple[str, ...] = ()
    smoke: bool = False                  # include in `sweep --smoke`
    section: str = ""                    # paper figure/table it feeds

    def __post_init__(self):
        if self.kernel not in SPECS:
            raise KeyError(f"unknown kernel {self.kernel!r}; "
                           f"known: {tuple(SPECS)}")
        object.__setattr__(self, "shape",
                           tuple(int(s) for s in self.shape))

    def make_args(self) -> Tuple:
        return SPECS[self.kernel].make_args(self.shape, self.dtype)

    @property
    def is_serving(self) -> bool:
        return self.kernel == SERVE_KERNEL

    def matches(self, *, only: Optional[str] = None,
                kernel: Optional[str] = None,
                strategy: Optional[Strategy] = None,
                tag: Optional[str] = None,
                smoke: Optional[bool] = None) -> bool:
        if only is not None and not any(
                tok and tok in self.name for tok in only.split(",")):
            return False
        if kernel is not None and kernel != self.kernel:
            return False
        if strategy is not None and self.strategy not in (None, strategy):
            return False
        if tag is not None and tag not in self.tags:
            return False
        if smoke is not None and self.smoke != smoke:
            return False
        return True


@dataclass(frozen=True)
class ServeScenario(Scenario):
    """An end-to-end serving workload: scheduler x arrival trace.

    ``workload`` carries the trace/scheduler parameters consumed by
    ``repro.bench.serving.run_serve_scenario``: scheduler ("continuous" |
    "cohort"), arrival ("uniform" | "poisson" | "bursty"), n_requests,
    batch, rate, burst, prompt_lens [lo, hi], max_new [lo, hi], seed,
    block_len, arch.  ``shape`` is (batch, n_requests) for display."""
    kernel: str = SERVE_KERNEL
    shape: Tuple[int, ...] = ()

    def __post_init__(self):
        # no SPECS entry: serving scenarios are not kernel cells
        if self.kernel != SERVE_KERNEL:
            raise ValueError(f"ServeScenario.kernel must be "
                             f"{SERVE_KERNEL!r}, got {self.kernel!r}")
        object.__setattr__(self, "shape",
                           tuple(int(s) for s in self.shape))

    def make_args(self):
        raise TypeError("serving scenarios have no kernel args; run them "
                        "via repro.bench.serving.run_serve_scenario")


# ---------------------------------------------------------------------------
# Call adapters + correctness oracles
# ---------------------------------------------------------------------------

#: kernel -> fn(args, config, workload, interpret) -> jax value.  The config
#: dict holds exactly the KERNEL_DEFAULTS keys; workload holds the
#: non-tunable problem parameters a figure sweeps (intensity, penalty, ...).
CALLERS: Dict[str, Callable[..., Any]] = {
    "stream": lambda a, cfg, w, i: ops.stream(
        a[0], iters=w.get("iters", 4), interpret=i, **cfg),
    "hotspot": lambda a, cfg, w, i: ops.hotspot(
        a[0], a[1], iters=w.get("iters", 1), grid=w.get("grid", 1),
        interpret=i, **cfg),
    "pathfinder": lambda a, cfg, w, i: ops.pathfinder(
        a[0], interpret=i, **cfg),
    "nw": lambda a, cfg, w, i: ops.nw(
        a[0], penalty=w.get("penalty", 10), interpret=i, **cfg),
    "lud": lambda a, cfg, w, i: ops.lud(a[0], interpret=i, **cfg),
    "matmul": lambda a, cfg, w, i: ops.matmul(a[0], a[1], interpret=i,
                                              **cfg),
    "flash_attention": lambda a, cfg, w, i: ops.flash_attention(
        a[0], a[1], a[2], causal=w.get("causal", True), interpret=i, **cfg),
}

#: kernel -> fn(args, workload) -> reference output (kernels.ref oracle).
ORACLES: Dict[str, Callable[..., Any]] = {
    "stream": lambda a, w: ref.stream_ref(a[0], iters=w.get("iters", 4)),
    "hotspot": lambda a, w: ref.hotspot_ref(a[0], a[1],
                                            iters=w.get("iters", 1)),
    "pathfinder": lambda a, w: ref.pathfinder_ref(a[0]),
    "nw": lambda a, w: ref.nw_ref(a[0], w.get("penalty", 10)),
    "lud": lambda a, w: ref.lud_ref(a[0]),
    "matmul": lambda a, w: ref.matmul_ref(a[0], a[1]),
    "flash_attention": lambda a, w: ref.attention_ref(
        a[0], a[1], a[2], causal=w.get("causal", True)),
}

#: max |kernel - oracle| each kernel is held to in interpret mode.
CHECK_TOL: Dict[str, float] = {
    "stream": 1e-5, "hotspot": 1e-2, "pathfinder": 0.5, "nw": 1e-3,
    "lud": 1e-2, "matmul": 1e-2, "flash_attention": 2e-2,
}

#: max |kernel - oracle| for the compiled kernels on a TPU, at the sizes
#: ``chip_smoke.py`` runs, against oracles run at "highest" matmul
#: precision.  Reasons:
CHIP_TOL: Dict[str, float] = {
    # 0.5*x + 0.5 in f32 on both sides, the same operations in order
    "stream": 1e-6,
    # f32 stencil on |T| ~ 400: one ulp is 3e-5, and Mosaic and XLA may
    # order the five-term sum differently
    "hotspot": 1e-3,
    # int32 min-plus DP: exact
    "pathfinder": 0.0,
    # integer scores and penalty held in f32 stay exact below 2^24
    "nw": 0.0,
    # blocked and unblocked f32 LU round differently; with diagonal entries
    # near n = 2048 the two orders differ by ~9e-3 (NumPy f32, host)
    "lud": 5e-2,
    # bf16 products are exact in f32; K = 4096 accumulated in a different
    # order on |C| ~ 64 moves results by ~1e-4
    "matmul": 1e-2,
    # the kernel's f32 dots may run at bf16 operand precision (2^-8
    # relative) on the MXU; outputs are convex mixes of v with |v| < ~5
    "flash_attention": 2e-2,
}


def call_kernel(sc: Scenario, args: Tuple, config: Dict[str, Any],
                interpret: bool = True):
    return CALLERS[sc.kernel](args, config, sc.workload, interpret)


def check_output(sc: Scenario, args: Tuple, out) -> float:
    """Max abs error of ``out`` against the pure-jnp oracle.  Pathfinder's
    kernel returns a (1, cols) row; compare the row itself."""
    want = ORACLES[sc.kernel](args, sc.workload)
    got = out
    if sc.kernel == "pathfinder":
        got = jnp.asarray(out)[0]
    return float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32)
                                 - jnp.asarray(want, jnp.float32))))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_SCENARIOS: Dict[str, Scenario] = {}


def register(sc: Scenario) -> Scenario:
    """Add ``sc`` to the global registry; re-registering the same name with
    a different definition is an error (silent shadowing hides typos)."""
    existing = _SCENARIOS.get(sc.name)
    if existing is not None and existing != sc:
        raise ValueError(f"scenario {sc.name!r} already registered "
                         f"with a different definition")
    _SCENARIOS[sc.name] = sc
    return sc


def get_scenario(name: str) -> Scenario:
    try:
        return _SCENARIOS[name]
    except KeyError:
        raise KeyError(f"unknown scenario {name!r}; run "
                       f"`python -m repro.bench.cli list`") from None


def scenarios(*, only: Optional[str] = None, kernel: Optional[str] = None,
              strategy: Optional[Strategy] = None, tag: Optional[str] = None,
              smoke: Optional[bool] = None) -> List[Scenario]:
    return [s for _, s in sorted(_SCENARIOS.items())
            if s.matches(only=only, kernel=kernel, strategy=strategy,
                         tag=tag, smoke=smoke)]


def scenario_names(**filters) -> List[str]:
    return [s.name for s in scenarios(**filters)]


# ---------------------------------------------------------------------------
# Default scenario set
# ---------------------------------------------------------------------------

#: shapes small enough that interpret mode on a CPU stays in milliseconds;
#: chosen to match the shapes the paper-figure benchmarks always used.
_SMOKE_SHAPES: Dict[str, Tuple[int, ...]] = {
    "stream": (256, 256),
    "hotspot": (32, 126),
    "pathfinder": (33, 128),
    "nw": (32,),
    "lud": (64,),
    "matmul": (256, 256, 256),
    "flash_attention": (2, 256, 64),
}

_SMOKE_WORKLOADS: Dict[str, Dict[str, Any]] = {
    "stream": {"iters": 4},
    "hotspot": {"iters": 2},
}


def _register_defaults() -> None:
    # one fast cell per kernel — the CI trajectory sweep
    for kernel, shape in _SMOKE_SHAPES.items():
        register(Scenario(
            name=f"smoke/{kernel}", kernel=kernel, shape=shape,
            workload=dict(_SMOKE_WORKLOADS.get(kernel, {})),
            tags=("smoke",), smoke=True, section="smoke"))

    # paper Fig. 3: the async-copy microbenchmark, strategy x intensity
    for strategy in Strategy:
        for iters in (1, 32):
            register(Scenario(
                name=f"fig3/stream/{strategy.value}/iters={iters}",
                kernel="stream", shape=(256, 256), strategy=strategy,
                config={"tile_rows": 16, "n_tiles": 8},
                workload={"iters": iters},
                tags=("fig3", "paper"), section="fig3"))

    # regime map: per kernel a sync baseline plus the kernel's best async
    # strategy at each ring depth — `sweep` folds the measurements into
    # per-cell "async pays / async hurts" verdict rows (bench.regime)
    for kernel, shape in _SMOKE_SHAPES.items():
        workload = dict(_SMOKE_WORKLOADS.get(kernel, {}))
        register(Scenario(
            name=f"regime/{kernel}/sync", kernel=kernel, shape=shape,
            strategy=Strategy.SYNC, workload=dict(workload),
            tags=("regime",), section="regime"))
        strat = (Strategy.DROP_OFF if kernel == "pathfinder"
                 else Strategy.OVERLAP)
        for depth in (2, 3, 4):
            register(Scenario(
                name=f"regime/{kernel}/{strat.value}/d{depth}",
                kernel=kernel, shape=shape, strategy=strat,
                config={"depth": depth}, workload=dict(workload),
                tags=("regime",), section="regime"))
            # Hopper-style bulk copies: the regime reducer takes the min
            # across async strategies per depth, so TMA rows slot in as a
            # second async contender rather than a new verdict family
            register(Scenario(
                name=f"regime/{kernel}/{Strategy.TMA.value}/d{depth}",
                kernel=kernel, shape=shape, strategy=Strategy.TMA,
                config={"depth": depth}, workload=dict(workload),
                tags=("regime",), section="regime"))

    # paper Fig. 4: the four Rodinia kernels x every async strategy
    fig4 = {
        "hotspot": ((32, 126), {"iters": 2}),
        "pathfinder": ((33, 128), {}),
        "nw": ((32,), {}),
        "lud": ((64,), {}),
    }
    for kernel, (shape, workload) in fig4.items():
        for strategy in Strategy:
            register(Scenario(
                name=f"fig4/{kernel}/{strategy.value}", kernel=kernel,
                shape=shape, strategy=strategy, workload=dict(workload),
                tags=("fig4", "paper"), section="fig4"))

    # serving: continuous batching vs the static-cohort baseline under
    # three deterministic arrival traces.  uniform is the small CI-gated
    # cell; poisson is the acceptance workload (mixed lengths at batch 4,
    # where slot-level refill shows its tokens/s win); bursty stresses
    # admission + queueing.  Not smoke-tagged: the serving CI step runs
    # them explicitly so the kernel trajectory sweep stays fast.
    serve_traces = {
        "uniform": dict(n_requests=6, batch=2, rate=0.5,
                        prompt_lens=[5, 16], max_new=[4, 8]),
        "poisson": dict(n_requests=16, batch=4, rate=0.5,
                        prompt_lens=[5, 24], max_new=[8, 40]),
        "bursty": dict(n_requests=8, batch=2, rate=0.5, burst=4,
                       prompt_lens=[5, 16], max_new=[4, 12]),
    }
    for arrival, wl in serve_traces.items():
        for sched in ("continuous", "cohort"):
            register(ServeScenario(
                name=f"serve/{arrival}/{sched}",
                shape=(wl["batch"], wl["n_requests"]),
                workload={"scheduler": sched, "arrival": arrival,
                          "seed": 0, "block_len": 8,
                          "arch": "qwen2-1.5b", **wl},
                tags=("serve",), section="serve"))

    # shared-prefix family: one poisson trace whose prompts share a
    # 64-token prefix in groups of 4 (system-prompt workload), run three
    # ways — monolithic prefill (the PR 8 baseline), chunked prefill, and
    # chunked + copy-on-write prefix sharing.  ``check_outputs`` on the
    # shared cell replays it without sharing and fails the bench unless
    # greedy outputs are bit-identical; the headline acceptance number is
    # shared vs chunked tokens/s + TTFT p99 on this trace.  chunk 16 =
    # 2 blocks: match length is capped to chunk multiples, so a chunk
    # finer than the prefix lets nearly all of it be shared.
    prefix_wl = dict(n_requests=16, batch=4, rate=0.25, seed=0,
                     block_len=8, arch="qwen2-1.5b", arrival="poisson",
                     prompt_lens=[5, 24], max_new=[8, 24],
                     prefix_len=64, prefix_group=4)
    for variant, extra in (
            ("baseline", {}),
            ("chunked", {"chunk_tokens": 16}),
            ("shared", {"chunk_tokens": 16, "prefix_cache": True,
                        "check_outputs": True})):
        register(ServeScenario(
            name=f"serve/prefix/{variant}",
            shape=(prefix_wl["batch"], prefix_wl["n_requests"]),
            workload={"scheduler": "continuous", **prefix_wl, **extra},
            tags=("serve", "prefix"), section="serve"))


_register_defaults()
