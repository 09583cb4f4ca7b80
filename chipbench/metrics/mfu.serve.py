"""The whole serving step's share of the chip's peak compute, in percent:
tokens computed in the window (output tokens decoded, plus prompt tokens
prefilled, not counting prefix-cache hits) times two operations per
matmul weight, over the window times the peak.  Attention's operations
are left out, so it reads low, never high."""
from chipbench import counts


def read(r):
    n = r.data.get("tokens_computed")
    if not n:
        return None
    flops = n * 2.0 * counts.matmul_params(r.config)
    return 100.0 * flops / (r.window_s * r.peaks.flops_per_s)
