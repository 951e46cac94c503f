"""The share of the chip's peak that serving a sparse-expert model needs:
FLOPs of the tokens the engine counted over the untraced part of the window
(`lib/flops_cohere_moe.serve_flops`: every prompt and output token through
each layer's dense part, every pair the router sent to an expert held here
through that expert, every output token through the head) / seconds / bf16
peak, in percent. The pairs are the `pairs_here` attrs of the program's
`serve.prefill` and `serve.decode` spans that began in that part (the counts
each call also adds to `moe_pairs_total{held="here"}`), so the count follows
the pairs and not what the implementation computes. None where no span
carries the attr or nothing was counted."""

from lib import flops_cohere_moe
from reducers import recorder_stat


def span_values(measured, names, field):
    """`field` of the program's spans `names` that began in the untraced
    part of the window (the driver's snapshots bound it)."""
    from singa_tpu.observability import spans
    if "snap_start" not in measured or "snap_end" not in measured:
        return []
    wall = (measured["snap_start"]["wall"], measured["snap_end"]["wall"])
    return recorder_stat.select(
        spans.recorder().records(),
        {"kind": "span", "name": names, "field": field, "when": "window"},
        None, wall)


def compute(args, run, measured, trace):
    a, b = measured["snap_start"], measured["snap_end"]
    out = b["tokens"] - a["tokens"]
    tokens = out + (b["prefill_tokens"] - a["prefill_tokens"])
    pairs = sum(span_values(measured, ["serve.prefill", "serve.decode"],
                            "pairs_here"))
    seconds = b["t"] - a["t"]
    if tokens <= 0 or pairs <= 0 or seconds <= 0:
        return None
    return 100.0 * flops_cohere_moe.serve_flops(
        run.config, tokens, out, pairs) / seconds \
        / run.peaks["bf16_flops_per_s"]
