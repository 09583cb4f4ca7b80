"""The decode step's share of its roofline under latent attention and a
held share of the experts, in percent: the least time of the traced
decode steps over their device time (``_decode``), per step.

A step's least time is the larger of its operations over the peak
compute rate and its bytes over the peak bandwidth (``counts_mla``): the
weights every token uses once in the compute dtype, the held experts the
step's routing counts say were picked, the distinct latent blocks its
slots attend and their new rows; operations 2 per matmul weight per token
(per held pick for the experts) plus absorbed attention over each slot's
context."""
from chipbench import counts_mla
from chipbench.peaks import least_seconds

PROGRAM = "_decode"


def read(r):
    steps = r.data.get("mla_decode_steps")
    t = r.trace
    if not steps or t.count(PROGRAM) == 0:
        return None
    c, bl = r.config, r.config["block_len"]
    least = sum(least_seconds(
        counts_mla.decode_step_flops(c, pos, picks),
        counts_mla.decode_step_bytes(c, len(pos), blocks, bl, hit), r.peaks)
        for pos, blocks, picks, hit in steps) / len(steps)
    return 100.0 * least / (t.device_s(PROGRAM) / t.count(PROGRAM))
