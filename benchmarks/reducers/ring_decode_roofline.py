"""The ring decode kernel's share of its memory roofline: the bytes a decode
tick's kernel calls have to move over the chip's memory bandwidth, divided
by the device time of the trace's ops whose names match, a tick.

The bytes are a tick's mean over the untraced part of the window: the blocks
the tick walks (the `kv_blocks` attr of the program's `serve.decode` spans,
which each tick also adds to `serve_kv_blocks_walked_total`) times a block of
K and of V, read, plus what the kernel writes back of the level for every
live slot and level (the `active` attr of the `serve.tick` spans that ran a
decode). The time is the device time of the matching ops in the traced slice
over the ticks it holds: their count over the levels of the cache. In
percent; None where the program records no `kv_blocks`, the trace holds no
such op or the configuration has no entry, never 0.
args: {"pattern": regular expression of the kernel's op name,
"geometry": {<config name>: {"levels": ring levels of the cache,
"block_bytes": bytes of one block of K and one of V,
"write_bytes": bytes written of a level for one live slot}}}."""

from lib import xplane
from reducers.serve_mfu_moe import span_values


def reduce(geometry, peaks, blocks, active, seconds, calls):
    """The share from a tick's counts (lists, one entry a tick), and the
    matching ops' seconds and count in the traced slice."""
    active = [a for a in active if a > 0]
    if geometry is None or not blocks or not active or not calls \
            or seconds <= 0:
        return None
    nbytes = sum(blocks) / len(blocks) * geometry["block_bytes"] \
        + sum(active) / len(active) * geometry["levels"] \
        * geometry["write_bytes"]
    ticks = calls / geometry["levels"]
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / (seconds / ticks)


def compute(args, run, measured, trace):
    if trace is None:
        return None
    chip = min(trace["events"])
    seconds, calls = xplane.matching_seconds(trace["events"][chip],
                                             [args["pattern"]])
    return reduce(args["geometry"].get(run.config["name"]), run.peaks,
                  span_values(measured, "serve.decode", "kv_blocks"),
                  span_values(measured, "serve.tick", "active"),
                  seconds, calls)
