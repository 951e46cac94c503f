"""The latent decode kernel's share of its roofline: the least time the chip
could take over a tick's kernel calls — the larger of the bytes they need
over the chip's memory bandwidth and the FLOPs they need over its bf16 peak
(`lib/flops_longcat.latent_decode_need`: each cached row that holds a token
read once at its own width for all heads, scored over its whole width and
weighed over its value columns by every head) — divided by the device time
of the trace's ops whose names match, a tick.

The rows are a tick's mean over the LAST `last_ticks` decode ticks of the
untraced part of the window (the `kv_rows` attr of the program's
`serve.decode` spans, which each tick also adds to
`serve_kv_rows_attended_total`; the traced slice follows that part, and the
contexts grow through the window). The time is the device time of the
matching ops in the traced slice over the ticks it holds: their count over
the levels of the cache. What the kernel moves beyond the need — the padding
of a row to whole lane tiles, the rest of a block whose first rows hold a
token — lowers the share, as it should. In percent; None where the program
records no `kv_rows` or the trace holds no such op, never 0.
args: {"pattern": regular expression of the kernel's op name, "itemsize":
bytes a cache element takes, "last_ticks": as above}."""

from lib import flops_longcat, xplane
from reducers.serve_mfu_moe import span_values


def reduce(config, peaks, args, rows, seconds, calls):
    """The share from a tick's rows (a list, one entry a tick) and the
    matching ops' seconds and count in the traced slice."""
    if not rows or not calls or seconds <= 0:
        return None
    rows = rows[-int(args.get("last_ticks", len(rows))):]
    flops, nbytes = flops_longcat.latent_decode_need(
        config, int(args["itemsize"]), sum(rows) / len(rows))
    least = max(nbytes / peaks["hbm_bytes_per_s"],
                flops / peaks["bf16_flops_per_s"])
    ticks = calls / (2 * int(config["num_layers"]))
    return 100.0 * least / (seconds / ticks)


def compute(args, run, measured, trace):
    if trace is None or "num_layers" not in run.config:
        return None
    chip = min(trace["events"])
    seconds, calls = xplane.matching_seconds(trace["events"][chip],
                                             [args["pattern"]])
    return reduce(run.config, run.peaks, args,
                  span_values(measured, "serve.decode", "kv_rows"),
                  seconds, calls)
