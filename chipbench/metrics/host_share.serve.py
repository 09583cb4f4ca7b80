"""Share of the serving loop's iterations in which the host, not the
device, held the loop, in percent: over the ``serve.step`` spans the
program recorded in the traced window, the sum of (the step's duration
minus its ``serve.device_wait`` children) over the sum of the steps'
durations.  Steps that straddle the trace's start or stop are not
recorded, so whole iterations are the base, not the window.

It prints to standard error each child span's share of the steps' time,
the steps' own time outside any child as "unspanned", and the device's
idle share of the traced window beside it."""
import sys
from collections import defaultdict

WAIT = "serve.device_wait"


def host_share(spans, idle_pct=None, out=sys.stderr):
    steps = {s.span_id: s for s in spans
             if s.name == "serve.step" and s.t1_us is not None}
    total = sum(s.dur_us for s in steps.values())
    if total <= 0:
        return None
    by_name = defaultdict(float)
    for s in spans:
        if s.parent_id in steps:
            by_name[s.name] += s.dur_us
    by_name["unspanned"] = total - sum(by_name.values())
    split = ", ".join(f"{k} {100.0 * v / total:.2f}%" for k, v in
                      sorted(by_name.items(), key=lambda kv: -kv[1]))
    idle = "" if idle_pct is None else f"; device idle {idle_pct:.2f}%"
    print(f"host_share.serve: {len(steps)} steps, {total / 1e3:.1f} ms: "
          f"{split}{idle}", file=out, flush=True)
    return 100.0 * (total - by_name.get(WAIT, 0.0)) / total


def read(r):
    from repro.obs.trace import tracer
    t = r.trace
    idle = (100.0 * (1.0 - t.busy_s / t.window_s)
            if t is not None and t.window_s > 0 else None)
    return host_share(tracer().spans(), idle)
