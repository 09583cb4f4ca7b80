"""Requests that have arrived and have no first token yet, time-weighted
over the ``serve.step`` spans the program recorded in the traced window:
the sum of (``queued`` + ``prefilling``) x duration over the sum of the
durations.  By Little's law it is the mean time to the first token times
the arrival rate, and it holds steady where a window sees too few first
tokens for a tail.

It prints to standard error the ``queued`` and ``prefilling`` parts, and
the mean ``waited_ms`` (arrival to admission) of the ``serve.admit``
spans inside those steps."""
import sys


def first_token_backlog(spans, out=sys.stderr):
    steps = {s.span_id: s for s in spans
             if s.name == "serve.step" and s.t1_us is not None}
    total = sum(s.dur_us for s in steps.values())
    if total <= 0:
        return None
    queued = sum(s.attrs["queued"] * s.dur_us for s in steps.values()) / total
    prefilling = sum(s.attrs["prefilling"] * s.dur_us
                     for s in steps.values()) / total
    waits = [s.attrs["waited_ms"] for s in spans
             if s.name == "serve.admit" and s.parent_id in steps]
    waited = (f"{sum(waits) / len(waits):.1f} ms over {len(waits)}"
              if waits else "none")
    print(f"first_token_backlog: {len(steps)} steps: queued {queued:.3f}, "
          f"prefilling {prefilling:.3f}; mean wait for admission {waited}",
          file=out, flush=True)
    return queued + prefilling


def read(r):
    from repro.obs.trace import tracer
    return first_token_backlog(tracer().spans())
