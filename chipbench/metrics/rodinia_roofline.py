"""The Rodinia kernels' share of their roofline, in percent: the sum of
the four least times (hotspot, pathfinder, nw, lud; each kernel's counts
at the peaks) over the sum of their device times per call in the trace.
Each kernel's own share is printed to standard error."""
import sys

from chipbench.peaks import least_seconds

KERNELS = ("hotspot", "pathfinder", "nw", "lud")


def read(r):
    kc, progs = r.data.get("kernel_counts"), r.data.get("programs")
    t = r.trace
    if not kc or any(k not in kc or t.count(progs[k]) == 0
                                  for k in KERNELS):
        return None
    least = {k: least_seconds(*kc[k], r.peaks) for k in KERNELS}
    device = {k: t.device_s(progs[k]) / t.count(progs[k]) for k in KERNELS}
    for k in KERNELS:
        print(f"roofline {k}: {100.0 * least[k] / device[k]!r} % "
              f"(least {least[k]!r} s, device {device[k]!r} s per call)",
              file=sys.stderr)
    return 100.0 * sum(least.values()) / sum(device.values())
