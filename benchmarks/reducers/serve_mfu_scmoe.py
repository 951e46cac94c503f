"""The share of the chip's peak that serving a shortcut-connected
sparse-expert model with latent attention needs: FLOPs of the tokens the
engine counted over the untraced part of the window
(`lib/flops_longcat.serve_flops`: every prompt and output token through each
double layer's dense part, every pair the router sent to an expert held here
through that expert, every output token through the head, every cached row a
decode tick attended to, the prompts' causal attention) / seconds / bf16
peak, in percent. The pairs, rows and squared prompt lengths are the
`pairs_here`, `kv_rows` and `tokens_sq` attrs of the program's
`serve.prefill` and `serve.decode` spans that began in that part; picks of
identity experts (`pairs_zero`) need no FLOP and count nothing. None where a
span lacks the attrs or nothing was counted."""

from lib import flops_longcat
from reducers.serve_mfu_moe import span_values


def compute(args, run, measured, trace):
    a, b = measured["snap_start"], measured["snap_end"]
    out = b["tokens"] - a["tokens"]
    tokens = out + (b["prefill_tokens"] - a["prefill_tokens"])
    pairs = span_values(measured, ["serve.prefill", "serve.decode"],
                        "pairs_here")
    zero = span_values(measured, ["serve.prefill", "serve.decode"],
                       "pairs_zero")
    rows = span_values(measured, "serve.decode", "kv_rows")
    sq = span_values(measured, "serve.prefill", "tokens_sq")
    seconds = b["t"] - a["t"]
    if tokens <= 0 or seconds <= 0 or not pairs or not zero or not rows \
            or not sq:
        return None
    return 100.0 * flops_longcat.serve_flops(
        run.config, tokens, out, sum(pairs), sum(rows), sum(sq)) / seconds \
        / run.peaks["bf16_flops_per_s"]
