"""The whole serving step's share of the chip's peak compute under a held
share of the experts, in percent: tokens computed in the window (output
tokens decoded, plus prompt tokens prefilled, not counting prefix-cache
hits) times two operations per weight every token multiplies by, plus two
per expert weight for each pick of a held expert, over the window times
the peak.  The window's held picks are the tokens' picks (tokens x top-k
x expert layers) at the share of them that fell on held experts in the
traced steps (the program's ``moe.assignments_here`` over
``moe.assignments``).  Attention's operations are left out, so it reads
low, never high."""
from chipbench import counts_mla


def read(r):
    n = r.data.get("tokens_computed")
    picks, here = r.data.get("moe_assignments"), r.data.get(
        "moe_assignments_here")
    if not n or not picks:
        return None
    c = r.config
    held = (n * c["num_experts_per_tok"] * counts_mla.expert_layers(c)
            * here / picks)
    flops = 2.0 * (n * counts_mla.dense_matmul_params(c)
                   + held * counts_mla.expert_params(c))
    return 100.0 * flops / (r.window_s * r.peaks.flops_per_s)
