"""The serving path's share of the chip's peak: 2 x matmul parameters x
(prompt + output tokens the engine counted) / seconds / bf16 peak, in
percent, over the untraced part of the window. Decoding is bound by bytes,
so it reads low; it bounds a claim once a kernel is off the path."""

from lib import flops


def compute(args, run, measured, trace):
    a, b = measured["snap_start"], measured["snap_end"]
    tokens = (b["tokens"] - a["tokens"]) \
        + (b["prefill_tokens"] - a["prefill_tokens"])
    seconds = b["t"] - a["t"]
    if tokens <= 0 or seconds <= 0:
        return None
    return 100.0 * 2 * flops.lm_matmul_params(run.config) * tokens \
        / seconds / run.peaks["bf16_flops_per_s"]
