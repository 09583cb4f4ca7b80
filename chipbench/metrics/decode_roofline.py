"""The decode step's share of its roofline, in percent: the least time of
the traced decode steps over their device time (``_decode``), per step.

A step's least time is the larger of its operations over the peak
compute rate and its bytes over the peak bandwidth (``counts``): every
parameter once in the compute dtype, the distinct cache blocks its slots
attend, and their new rows."""
from chipbench import counts
from chipbench.peaks import least_seconds

PROGRAM = "_decode"


def read(r):
    steps = r.data.get("decode_steps")
    t = r.trace
    if not steps or t.count(PROGRAM) == 0:
        return None
    c, bl = r.config, r.config["block_len"]
    least = sum(least_seconds(counts.decode_step_flops(c, pos),
                              counts.decode_step_bytes(c, len(pos), blocks, bl),
                              r.peaks)
                for pos, blocks in steps) / len(steps)
    return 100.0 * least / (t.device_s(PROGRAM) / t.count(PROGRAM))
