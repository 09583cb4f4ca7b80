"""Execute scenarios: resolve config, measure, check, project, record.

The runner is the only place where a scenario meets the clock.  For each
``Scenario`` it

  1. resolves the kernel config — tuning-registry winner for this
     (kernel, shape, dtype, chip, mode) cell if one exists, the seed
     default otherwise, then scenario-pinned strategy/overrides on top —
     and records *which* of those happened (``config_source``);
  2. verifies the kernel against its ``kernels.ref`` oracle (``max_err``
     goes into the metrics; a benchmark is worthless if it is wrong);
  3. times it with the canonical ``repro.bench.timing`` protocol; and
  4. emits a schema-v2 ``BenchResult`` with full provenance.

``sweep`` additionally performs the paper's generation study: every
scenario is projected through the analytic roofline model
(``tuning.search_space.predict_time``) onto every registered ``Chip``
model, so one sweep yields the measured-on-this-host rows *plus* the
cross-lineage expectation rows the paper's Fig. 2/§6 analysis needs.
"""
from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core import hardware
from ..obs.trace import get_tracer
from ..tuning.autotuner import _default_registry, decode_config
from ..tuning.registry import Registry
from ..tuning.search_space import SPECS, predict_time
from ..kernels import ops
from ..kernels.stream import stream_flops_bytes
from .regime import regime_rows
from .results import BenchReport, BenchResult, now_iso
from .scenario import (CHECK_TOL, Scenario, call_kernel, check_output,
                       scenarios)
from .timing import time_callable

log = logging.getLogger("repro.bench")

__all__ = ["RunOptions", "resolve_config", "run_scenario", "run_scenarios",
           "project_scenario", "sweep", "new_report"]


@dataclass
class RunOptions:
    """Measurement policy for a batch of scenario runs."""
    warmup: int = 1
    repeats: int = 5
    interpret: Optional[bool] = None    # None: compiled on TPU, else the
    #                                     Pallas interpreter
    check: bool = True                  # compare against the ref oracle
    use_tuned: bool = True              # consult the tuning registry
    chip: Optional[str] = None          # provenance chip (default: TARGET)
    registry: Optional[Registry] = None
    emit: Optional[Callable[[BenchResult], None]] = None  # streaming hook

    def __post_init__(self):
        self.interpret = ops.resolve_interpret(self.interpret)

    def resolved_chip(self) -> str:
        """The provenance / tuning-lookup chip: ``chip`` if set, else the
        attached TPU as its ``device_kind`` names it, else (off-chip) the
        target the tuning registry is keyed by."""
        if self.chip:
            return self.chip
        if jax.default_backend() == "tpu":
            return hardware.chip_for_device_kind(
                jax.devices()[0].device_kind).name
        return hardware.TARGET.name


def new_report() -> BenchReport:
    return BenchReport(jax_version=jax.__version__,
                       backend=jax.default_backend(),
                       created_at=now_iso())


def resolve_config(sc: Scenario, opts: RunOptions
                   ) -> Tuple[Dict[str, object], str, Optional[str]]:
    """(config, source, tuned_key) for this scenario on this chip/mode."""
    cfg = ops.default_config(sc.kernel)
    source, tuned_key = "default", None
    if opts.use_tuned:
        # the memoized process-wide registry: a sweep must not re-parse
        # tuning_registry.json once per scenario
        reg = opts.registry if opts.registry is not None \
            else _default_registry()
        rec = reg.get(sc.kernel, sc.shape, sc.dtype, opts.resolved_chip(),
                      opts.interpret)
        if rec is not None:
            cfg = decode_config(rec.best)
            source, tuned_key = "tuned", rec.key
    if sc.strategy is not None or sc.config:
        cfg = dict(cfg)
        if sc.strategy is not None:
            cfg["strategy"] = sc.strategy
        cfg.update(sc.config)
        source += "+scenario"
    return cfg, source, tuned_key


def _flops_bytes(sc: Scenario, cfg: Dict[str, object]) -> Tuple[float, float]:
    """Analytic work/traffic for the scenario's actual workload.  The tuner
    times at a fixed intensity; scenarios sweep it, so honor the scenario's
    ``iters`` where the kernel spec models a single iteration."""
    if sc.kernel == "stream":
        return stream_flops_bytes(sc.shape, sc.workload.get("iters", 4),
                                  jnp.dtype(sc.dtype).itemsize)
    flops, nbytes = SPECS[sc.kernel].flops_bytes(sc.shape, sc.dtype, cfg)
    if sc.kernel == "hotspot":          # spec models iters=1; scale both
        iters = sc.workload.get("iters", 1)
        flops, nbytes = flops * iters, nbytes * iters
    return flops, nbytes


def _strategy_name(cfg: Dict[str, object]) -> str:
    s = cfg.get("strategy")
    return getattr(s, "value", str(s))


def run_scenario(sc: Scenario, opts: Optional[RunOptions] = None, *,
                 resolved: Optional[Tuple] = None) -> BenchResult:
    """Measure one scenario on this host and return its result row.
    ``resolved`` short-circuits config resolution when the caller (sweep)
    already did it for this scenario."""
    opts = opts or RunOptions()
    if sc.is_serving:
        # end-to-end serving cell: no kernel config, oracle, or roofline
        from .serving import run_serve_scenario
        return run_serve_scenario(sc, opts)
    cfg, source, tuned_key = resolved or resolve_config(sc, opts)
    args = sc.make_args()
    fn = lambda: call_kernel(sc, args, cfg, opts.interpret)

    metrics: Dict[str, object] = {}
    # the scenario span carries the full config provenance, so a Perfetto
    # view of a sweep shows *what* ran in each box, not just how long
    with get_tracer().span(
            f"scenario:{sc.name}", kernel=sc.kernel,
            shape="x".join(map(str, sc.shape)), dtype=sc.dtype,
            strategy=_strategy_name(cfg), config_source=source,
            tuned_key=tuned_key, chip=opts.resolved_chip(),
            interpret=opts.interpret, repeats=opts.repeats) as span:
        warmup = opts.warmup
        if opts.check:
            # the oracle call compiles and runs the kernel, so it doubles
            # as one warmup iteration — interpret-mode calls cost seconds
            with get_tracer().span("oracle"):
                out = jax.block_until_ready(fn())
                err = check_output(sc, args, out)
            warmup = max(warmup - 1, 0)
            metrics["max_err"] = err
            metrics["check_ok"] = bool(err <= CHECK_TOL[sc.kernel])
            if not metrics["check_ok"]:
                log.warning("scenario %s: max_err %.3g exceeds tol %.3g",
                            sc.name, err, CHECK_TOL[sc.kernel])
        stats = time_callable(fn, warmup=warmup, repeats=opts.repeats)
        metrics.update(stats.to_metrics())
        if span is not None:
            span.attrs["us_median"] = stats.median

    flops, nbytes = _flops_bytes(sc, cfg)
    metrics["intensity"] = flops / nbytes if nbytes else 0.0
    metrics["predicted_us"] = predict_time(
        cfg["strategy"], flops, nbytes, depth=int(cfg.get("depth", 2)),
        n_tiles=SPECS[sc.kernel].n_tiles(sc.shape, cfg),
        wait_group=cfg.get("wait_group"),
        chip=hardware.get_chip(opts.resolved_chip())) * 1e6

    result = BenchResult(
        scenario=sc.name, kernel=sc.kernel, shape=list(sc.shape),
        dtype=sc.dtype, strategy=_strategy_name(cfg),
        chip=opts.resolved_chip(), metrics=metrics,
        config={k: getattr(v, "value", v) for k, v in cfg.items()},
        config_source=source, tuned_key=tuned_key,
        trace_id=span.span_id if span is not None else None,
        kind="measured", section=sc.section, interpret=opts.interpret,
        backend=jax.default_backend(), jax_version=jax.__version__,
        created_at=now_iso())
    if opts.emit:
        opts.emit(result)
    return result


def project_scenario(sc: Scenario, chip_name: str,
                     opts: Optional[RunOptions] = None, *,
                     resolved: Optional[Tuple] = None) -> BenchResult:
    """Roofline-model expectation row for ``sc`` on ``chip_name`` — the
    paper's cross-generation methodology where the hardware itself is not
    attached to this host."""
    if sc.is_serving:
        raise ValueError(f"serving scenario {sc.name!r} has no roofline "
                         "projection")
    opts = opts or RunOptions()
    cfg, source, tuned_key = resolved or resolve_config(sc, opts)
    chip = hardware.get_chip(chip_name)
    flops, nbytes = _flops_bytes(sc, cfg)
    t_c = flops / (chip.tflops_f32 * 1e12)
    t_m = nbytes / (chip.mem_bw_gbs * 1e9)
    t = predict_time(cfg["strategy"], flops, nbytes,
                     depth=int(cfg.get("depth", 2)),
                     n_tiles=SPECS[sc.kernel].n_tiles(sc.shape, cfg),
                     wait_group=cfg.get("wait_group"), chip=chip)
    metrics = {"predicted_us": t * 1e6, "t_compute_us": t_c * 1e6,
               "t_memory_us": t_m * 1e6,
               "intensity": flops / nbytes if nbytes else 0.0,
               "bound": "compute" if t_c > t_m else "memory"}
    result = BenchResult(
        scenario=sc.name, kernel=sc.kernel, shape=list(sc.shape),
        dtype=sc.dtype, strategy=_strategy_name(cfg), chip=chip_name,
        metrics=metrics,
        config={k: getattr(v, "value", v) for k, v in cfg.items()},
        config_source=source, tuned_key=tuned_key, kind="model",
        section=sc.section or "lineage", interpret=opts.interpret,
        backend="", jax_version=jax.__version__, created_at=now_iso())
    if opts.emit:
        opts.emit(result)
    return result


def run_scenarios(scs: Sequence[Scenario],
                  opts: Optional[RunOptions] = None) -> BenchReport:
    """Measure a batch of scenarios into one report."""
    opts = opts or RunOptions()
    report = new_report()
    for sc in scs:
        report.add(run_scenario(sc, opts))
    return report


def sweep(scs: Optional[Sequence[Scenario]] = None,
          chips: Optional[Sequence[str]] = None,
          opts: Optional[RunOptions] = None) -> BenchReport:
    """The generation sweep: measure every scenario on this host, then
    project each one across the chip lineage (default: every registered
    ``Chip`` model, GPUs and TPUs alike)."""
    opts = opts or RunOptions()
    if scs is None:
        scs = scenarios(smoke=True)
    if chips is None:
        chips = list(hardware.CATALOG)
    for name in chips:
        hardware.get_chip(name)         # fail fast on a typo'd chip
    report = new_report()
    with get_tracer().span("sweep", n_scenarios=len(scs),
                           n_chips=len(chips)):
        for sc in scs:
            if sc.is_serving:
                # serving cells have no roofline model to project
                report.add(run_scenario(sc, opts))
                continue
            resolved = resolve_config(sc, opts)     # once per scenario
            report.add(run_scenario(sc, opts, resolved=resolved))
            for chip_name in chips:
                report.add(project_scenario(sc, chip_name, opts,
                                            resolved=resolved))
    # fold any regime/* depth-sweep measurements into per-cell
    # "async pays / async hurts" verdict rows (kind="regime")
    for row in regime_rows(report.results):
        report.add(row)
        if opts.emit:
            opts.emit(row)
    return report
