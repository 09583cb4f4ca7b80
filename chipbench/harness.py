"""The benchmark's machinery: find a cell's files by name, run its driver
over one measured window, read the per-layer metrics, print the result.

Everything a cell is made of is found by the names ``BENCHMARK.json``
gives it:

- ``configs/<config>.json``: the configuration; its ``kind`` picks
  ``drivers/<kind>.py``;
- ``traffic/<traffic>.json``: the traffic mix, read by that driver;
- ``metrics/<metric>.py``: one reader per per-layer metric, with
  ``read(readings) -> float | None``;
- ``models/<reference>.py`` and ``kernels/<kernel>.py``: the plain
  references and shape counts the drivers use.

Files are looked up in the directories of ``search`` in order, so a cell
made only of new files in another directory is found beside the ones here.

A driver module has ``run(cell) -> Outcome``.  It makes its inputs from
``cell.seed``, warms up, calls ``cell.window.open()`` when set-up is done,
calls ``cell.window.tick()`` as work completes (which starts and stops the
profiler in a ``--trace 1`` run, and says when the window has closed),
and then checks what the window produced against its reference.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
#: seconds of the window a ``--trace 1`` run traces, centred in it
TRACE_SECONDS = 3.0


class NoDevice(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised from inside the measured work once the window has closed."""


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    """``BENCHMARK.json`` and the directories its names are looked up in."""

    def __init__(self, path: Path = CHECKOUT / "BENCHMARK.json",
                 search: Sequence[Path] = (HERE,)):
        self.path = Path(path)
        self.data = load_json(self.path)
        self.search = [Path(p) for p in search]

    def find(self, kind: str, name: str, ext: str) -> Path:
        if not NAME.match(name):
            raise ValueError(f"{kind} name {name!r} is not a valid name")
        for d in self.search:
            p = d / kind / f"{name}{ext}"
            if p.is_file():
                return p
        raise FileNotFoundError(f"no {kind}/{name}{ext} under "
                                f"{[str(d) for d in self.search]}")

    def json(self, kind: str, name: str) -> Dict:
        return load_json(self.find(kind, name, ".json"))

    def module(self, kind: str, name: str):
        path = self.find(kind, name, ".py")
        mod_name = "chipbench_" + re.sub(r"\W", "_", f"{kind}_{name}")
        if mod_name in sys.modules:
            return sys.modules[mod_name]
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        return mod

    def workload(self, name: str) -> Dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}")

    def end_to_end(self, cell: str) -> List[Dict]:
        return [m for m in self.data["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> List[Dict]:
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.data["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def seed_word(seed: int) -> int:
    """A 31-bit word from any whole-number seed (a ``PRNGKey`` takes only
    32 signed bits; the seeds given may be larger)."""
    import numpy as np
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) >> 1


class Window:
    """The measured window, and the profiler trace inside it."""

    def __init__(self, seconds: float, trace: bool):
        self.seconds = float(seconds)
        self.trace = trace
        self.t0 = self.t_end = None
        self.closed = False
        self._dir: Optional[str] = None
        self._trace_at = self._trace_stop = None
        self._tracing = False
        self._compiles = 0
        self.compiles_in_window = 0

    @property
    def tracing(self) -> bool:
        return self._tracing

    def _on_compile(self, event: str, *_a, **_k) -> None:
        if "backend_compile" in event:
            self._compiles += 1

    def open(self) -> float:
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_compile)
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + self.seconds
        length = min(TRACE_SECONDS, self.seconds / 2)
        self._trace_at = self.t0 + (self.seconds - length) / 2
        self._trace_stop = self._trace_at + length
        self._compiles = 0
        return self.t0

    def tick(self, t: Optional[float] = None) -> bool:
        """Start or stop the profiler when due; False once closed."""
        t = time.perf_counter() if t is None else t
        if self.trace and self._dir is None and t >= self._trace_at:
            import jax
            self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("chipbench_window_start"):
                pass
            self._tracing = True
        elif self._tracing and t >= self._trace_stop:
            self._stop_trace()
        if t >= self.t_end:
            self.close()
            return False
        return True

    def _stop_trace(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation("chipbench_window_stop"):
            pass
        jax.profiler.stop_trace()
        self._tracing = False

    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        self.compiles_in_window = self._compiles
        if self._tracing:
            self._stop_trace()

    def reduction(self):
        """The trace's reduction (``devtrace.Reduction``); the trace files
        are deleted once read."""
        if self._dir is None:
            return None
        from chipbench import devtrace
        try:
            paths = list(Path(self._dir).rglob("*.xplane.pb"))
            if len(paths) != 1:
                raise RuntimeError(f"expected one trace file, found {paths}")
            return devtrace.reduce_trace(str(paths[0]))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


@dataclasses.dataclass
class Cell:
    name: str
    workload: Dict
    config: Dict
    traffic: Dict
    seed: int
    seconds: float
    trace: bool
    manifest: Manifest
    window: Window

    def key(self):
        import jax
        return jax.random.PRNGKey(seed_word(self.seed))

    def module(self, kind: str, name: str):
        return self.manifest.module(kind, name)

    def say(self, *parts) -> None:
        print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    #: what the per-layer readers read besides the trace
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Readings:
    """What a per-layer metric reader gets."""
    config: Dict
    trace: Any              # devtrace.Reduction
    peaks: Any              # peaks.Peaks
    window_s: float         # the measured window, on the host clock
    data: Dict[str, Any]    # the driver's ``Outcome.data``


def device_info(chips: int, require_tpu: bool = True) -> Dict:
    import jax
    from chipbench.peaks import peaks_for
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoDevice(f"no TPU: JAX's devices are {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoDevice(f"{len(devs)} chips, the cell asks for {chips}")
        peaks_for(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def use_compile_cache() -> None:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    else a fixed directory in the checkout.  Every program is kept."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             manifest: Optional[Manifest] = None, require_tpu: bool = True,
             t_start: Optional[float] = None, **driver_kw) -> Dict:
    """Run one cell once; returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    import repro  # noqa: F401  (the program under test must be here)
    manifest = manifest or Manifest()
    w = manifest.workload(workload)
    config = manifest.json("configs", w["config"])
    traffic = manifest.json("traffic", w["traffic"])
    device = device_info(int(w["chips"]), require_tpu)
    cell = Cell(workload, w, config, traffic, int(seed), seconds, trace,
                manifest, Window(seconds, trace))
    driver = manifest.module("drivers", config["kind"])
    out: Outcome = driver.run(cell, **driver_kw)
    if cell.window.t0 is None:
        raise RuntimeError("the driver never opened its window")
    setup_s = cell.window.t0 - t_start
    cell.say(f"compiles inside the window: {cell.window.compiles_in_window}")

    metrics: Dict[str, Dict] = {}
    if trace:
        from chipbench.peaks import PEAKS
        red = cell.window.reduction()
        if red is None or red.busy_s <= 0:
            raise RuntimeError("the traced window saw no device work")
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        readings = Readings(config, red, PEAKS.get(device["kind"]), seconds,
                            out.data)
        for prog, pt in sorted(red.programs.items()):
            cell.say(f"program {prog}: executions={pt.count} "
                     f"device_s={pt.device_s!r}")
        for m in manifest.per_layer(workload):
            v = manifest.module("metrics", m["name"]).read(readings)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(out.end_to_end, setup_s=setup_s)
        for m in manifest.end_to_end(workload):
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    device["memory_peak_bytes"] = out.memory_peak_bytes
    line = {"correct": all(c.ok for c in out.checks) and out.failed == 0,
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = {"device_ops": [[n, s] for n, s in red.device_ops],
                             "idle_gaps": [[n, s] for n, s in red.idle_gaps]}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    for c in out.checks:
        cell.say(f"check {c.name}: value={c.value!r} limit={c.limit!r} "
                 f"{'ok' if c.ok else 'FAILED'}")
    return line
