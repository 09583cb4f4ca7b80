"""Reduce a ``jax.profiler`` trace to the numbers the per-layer metrics read.

A TPU trace (``*.xplane.pb``) has one plane per chip, ``/device:TPU:<n>``.
Its ``XLA Modules`` line holds one event per execution of a compiled
program, named ``jit_<python function name>(<fingerprint>)``; its ``XLA
Ops`` line holds the operations each program ran.  The host plane,
``/host:CPU``, holds what the host threads were doing (dispatching a
program, copying, waiting), and its ``python`` line holds the profiler's
window marks (``WINDOW_MARKS``).

From these:

- busy time: the union of the operation intervals inside the window,
  averaged over the chips used;
- device time per program: for each program execution, the union of the
  operations that ran inside it, summed by program name, with the number
  of executions;
- the longest device operations and the longest idle gaps, each gap named
  by the host event that covered its middle and the program that ran next
  (the 256 longest gaps; the rest are summed under one name).

Programs are attributed by the jitted function's name, never by the
names Mosaic or XLA give their kernels and fusions.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_MODULE_NAME = re.compile(r"^jit_(.*?)(\(\d+\))?$")
_OP_NAME = re.compile(r"^%?([^ =]+)")
#: idle gaps named one by one, longest first; the rest are summed
NAMED_GAPS = 256

Interval = Tuple[float, float]


class ProgramTime(NamedTuple):
    count: int          # executions that started inside the window
    device_s: float     # union of their operations, summed


class Reduction(NamedTuple):
    window_s: float
    busy_s: float                       # averaged over chips
    programs: Dict[str, ProgramTime]    # by jitted function name
    device_ops: List[Tuple[str, float]]  # longest first, at most 10
    idle_gaps: List[Tuple[str, float]]   # longest first, at most 10

    def device_s(self, *names: str) -> float:
        return sum(self.programs[n].device_s for n in names
                   if n in self.programs)

    def count(self, name: str) -> int:
        p = self.programs.get(name)
        return p.count if p else 0


def program_name(event_name: str) -> str:
    """``jit__decode(1234)`` -> ``_decode``; other names pass unchanged."""
    m = _MODULE_NAME.match(event_name)
    return m.group(1) if m else event_name


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


#: host events that open and close the window: the markers the benchmark
#: writes right after ``start_trace`` and right before ``stop_trace``, or,
#: where the Python tracer is on, those calls themselves
WINDOW_MARKS = (("chipbench_window_start", "start_trace"),
                ("chipbench_window_stop", "stop_trace"))


def _is_mark(name: str) -> bool:
    return any(m in name for pair in WINDOW_MARKS for m in pair)


def _window(host_lines) -> Optional[Interval]:
    """The end of the opening mark to the start of the closing one, on the
    host clock, where the trace holds both."""
    start = stop = None
    for line in host_lines:
        for name, s, e in _events(line):
            if any(m in name for m in WINDOW_MARKS[0]):
                start = e
            elif any(m in name for m in WINDOW_MARKS[1]):
                stop = s
    if start is None or stop is None or stop <= start:
        return None
    return start, stop


def _clock_offset(mods, host_events) -> float:
    """Device clock minus host clock, estimated by pairing the k-th
    execution of each program with the k-th host dispatch of it
    (``PjitFunction(<name>)``).  It includes the dispatch latency, so a
    device event shifted by it lines up with the dispatch that caused it."""
    diffs = []
    for prog in {program_name(n) for n, _, _ in mods}:
        dev = sorted(s for n, s, _ in mods if program_name(n) == prog)
        host = []
        for s in sorted(s for n, s, _ in host_events
                        if n == f"PjitFunction({prog})"):
            if not host or s - host[-1] > 1e4:   # nested twin events
                host.append(s)
        if len(host) == len(dev):
            diffs += [d - h for d, h in zip(dev, host)]
    diffs.sort()
    return diffs[len(diffs) // 2] if diffs else 0.0


class _HostIndex:
    """Finds the shortest host event covering a time: what the host was
    doing then."""

    def __init__(self, host_events):
        import numpy as np
        self.names = [n for n, _, _ in host_events]
        self.start = np.array([s for _, s, _ in host_events], np.float64)
        self.end = np.array([e for _, _, e in host_events], np.float64)

    def at(self, t: float) -> str:
        import numpy as np
        cover = np.flatnonzero((self.start <= t) & (t < self.end))
        if cover.size == 0:
            return "host python"
        span = self.end[cover] - self.start[cover]
        return self.names[int(cover[np.argmin(span)])]


def reduce_planes(planes) -> Reduction:
    planes = list(planes)
    device_planes = [p for p in planes if DEVICE_PLANE.match(p.name)]
    host_lines = [ln for p in planes if p.name == HOST_PLANE
                  for ln in p.lines]
    if not device_planes:
        raise ValueError("the trace holds no TPU device plane")
    window = _window(host_lines)
    per_chip = []
    for plane in device_planes:
        lines = {ln.name: _events(ln) for ln in plane.lines}
        per_chip.append((lines.get(MODULES_LINE, []),
                         lines.get(OPS_LINE, [])))
    if window is None:
        raise ValueError("the trace holds no marks that open and close its "
                         "window")
    busy = []
    programs: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    op_time: Dict[str, float] = defaultdict(float)
    by_name: Dict[str, float] = defaultdict(float)
    host_events = [ev for ln in host_lines for ev in _events(ln)
                   if not _is_mark(ev[0])]
    host = _HostIndex(host_events)
    for mods, ops in per_chip:
        # the window is on the host clock; move it onto this chip's
        shift = _clock_offset(mods, host_events) if host_events else 0.0
        lo, hi = window[0] + shift, window[1] + shift
        merged = merge(clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        ops_sorted = sorted((s, e, name) for name, s, e in ops)
        starts = [o[0] for o in ops_sorted]
        mods_in = sorted((s, e, program_name(n)) for n, s, e in mods
                         if lo <= s < hi)
        for s, e, prog in mods_in:
            i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
            inside = clip([(a, b) for a, b, _ in ops_sorted[i:j]], s, e)
            programs[prog][0] += 1
            programs[prog][1] += union_length(inside)
            for a, b, name in ops_sorted[i:j]:
                op_time[f"{prog}/{op_name(name)}"] += min(b, e) - max(a, s)
        mod_starts = [m[0] for m in mods_in]
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = sorted(((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])
                       if b > a), reverse=True)
        for length, a, b in gaps[:NAMED_GAPS]:
            k = bisect.bisect_left(mod_starts, b - 1e3)
            nxt = mods_in[k][2] if k < len(mods_in) else "end of window"
            by_name[f"{host.at((a + b) / 2 - shift)} before {nxt}"] += length
        if len(gaps) > NAMED_GAPS:
            cut = gaps[NAMED_GAPS][0] * 1e-3
            by_name[f"gaps under {cut:.1f} us"] += sum(
                g[0] for g in gaps[NAMED_GAPS:])
    n = len(per_chip)
    return Reduction(
        window_s=(window[1] - window[0]) * 1e-9,
        busy_s=sum(busy) / n * 1e-9,
        programs={k: ProgramTime(int(c), t / n * 1e-9)
                  for k, (c, t) in programs.items()},
        device_ops=[(k, v / n * 1e-9) for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:10]],
        idle_gaps=[(k, v / n * 1e-9) for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:10]],
    )


def reduce_trace(path: str) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)
