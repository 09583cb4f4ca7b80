"""The stream kernel's share of its roofline, in percent: its least time
(``kernels/stream.py`` counts at the peaks) over its device time per call
in the trace."""
from chipbench.peaks import least_seconds


def read(r):
    kc, progs = r.data.get("kernel_counts"), r.data.get("programs")
    t = r.trace
    if not kc or "stream" not in kc or t.count(progs["stream"]) == 0:
        return None
    device = t.device_s(progs["stream"]) / t.count(progs["stream"])
    return 100.0 * least_seconds(*kc["stream"], r.peaks) / device
