"""The share of the chip's peak that serving a decoder-hybrid-decoder model
needs: FLOPs of the tokens served over the untraced part of the window
(`lib/flops_phi4flash.serve_flops`: a prompt's tokens through the
self-decoder, its last token through the cross-decoder, every output token
through every layer and the head) / seconds / bf16 peak, in percent. The
prompt rows are the `self_rows` and `cross_rows` attrs of the program's
`serve.prefill` spans that began in that part (the counts each call also
adds to `serve_prefill_rows_total{decoder}`), so a prefill is credited with
what the published model runs of a prompt and not with its padding; the
output tokens are the engine's counter. None where no span carries the
attrs or nothing was counted."""

from lib import flops_phi4flash
from reducers.serve_mfu_moe import span_values


def compute(args, run, measured, trace):
    a, b = measured["snap_start"], measured["snap_end"]
    out = b["tokens"] - a["tokens"]
    self_rows = span_values(measured, "serve.prefill", "self_rows")
    cross_rows = span_values(measured, "serve.prefill", "cross_rows")
    seconds = b["t"] - a["t"]
    if not self_rows or not cross_rows or out <= 0 or seconds <= 0:
        return None
    return 100.0 * flops_phi4flash.serve_flops(
        run.config, sum(self_rows), sum(cross_rows), out) / seconds \
        / run.peaks["bf16_flops_per_s"]
