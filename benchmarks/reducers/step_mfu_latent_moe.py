"""The whole step's share of the chip's peak for one chip's share of a
latent-attention expert LM (configs/kanana*.json): analytic model FLOPs a
token done here (lib/flops_kanana.py: 3x forward, recompute not counted) x
tokens a second a chip / the chip's bf16 peak, in percent, over the
untraced part of the window. The pairs held here are what the program
counts: the gauge `moe_pairs_here_per_step{layer}` (models/kanana.py, the
mean over every step the model took) summed over the expert layers. None
where the program sets no such gauge."""

from lib import flops_kanana
from reducers.kanana_roofline import gauge_sum


def compute(args, run, measured, trace):
    pairs = gauge_sum("moe_pairs_here_per_step")
    if not measured.get("steps") or not pairs:
        return None
    seq = int(run.traffic["seq_len"])
    tokens_a_step = measured["batch"] * seq / measured["chips"]
    tokens = measured["steps"] * tokens_a_step
    per_token = flops_kanana.train_flops_per_token(
        run.config, seq, pairs / tokens_a_step)
    return 100.0 * per_token * tokens / measured["window_s"] \
        / run.peaks["bf16_flops_per_s"]
