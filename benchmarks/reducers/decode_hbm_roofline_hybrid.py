"""The decode program's share of its memory roofline, for a model that keeps
recurrent state beside its rings: the bytes a decode tick has to move
(`lib/flops_phi4flash.decode_tick_bytes`: every leaf once, the ring rows
that hold a token once for each layer that reads them, each live slot's
state of each state-space layer read and written) over the chip's memory
bandwidth, divided by the device time of one run of the decode program.

The bytes are a tick's mean over the LAST `last_ticks` decode ticks of the
untraced part of the window, from the attrs of the program's `serve.decode`
spans (`kv_rows`, `state_slots`: the counts each tick also adds to
`serve_kv_rows_attended_total` and `serve_state_steps_total`). The time is
read as `decode_hbm_roofline` reads it: the mean duration of the decode
program's events on the device plane's `XLA Modules` line inside the traced
slice, which FOLLOWS the untraced part. An answer of a thousand tokens holds
its slot for most of a 30 s window, so the live slots, and with them the
bytes, grow all through it: a mean over the whole untraced part would hold
the bytes of a half-empty engine against the time of a full one (46.9 %
where the ticks next to the slice read 56 %; my chip run, PR 31). In percent;
None where a span lacks the attrs or the trace holds no such event, never 0.
args: {"itemsize": bytes a weight or ring element takes, "program": regular
expression of the decode program's module name, "last_ticks": how many ticks
before the traced slice the bytes are a mean of}."""

from lib import flops_phi4flash, xplane
from reducers.decode_hbm_roofline import module_events, program_seconds
from reducers.serve_mfu_moe import span_values


def reduce(config, peaks, args, rows, states, seconds):
    """The share from a tick's counts (lists, one entry a decode tick) and
    the program's seconds."""
    if not rows or not states or not seconds:
        return None
    last = int(args.get("last_ticks", len(rows)))
    rows, states = rows[-last:], states[-last:]
    nbytes = flops_phi4flash.decode_tick_bytes(
        config, int(args["itemsize"]), sum(rows) / len(rows),
        sum(states) / len(states))
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / seconds


def compute(args, run, measured, trace):
    if trace is None or not run.trace_dir:
        return None
    try:
        path = xplane.find_xplane(run.trace_dir)
    except FileNotFoundError:
        return None
    return reduce(
        run.config, run.peaks, args,
        span_values(measured, "serve.decode", "kv_rows"),
        span_values(measured, "serve.decode", "state_slots"),
        program_seconds(module_events(path), trace["window"],
                        args["program"]))
