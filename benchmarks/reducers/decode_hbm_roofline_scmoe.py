"""The decode program's share of its memory roofline, for a
shortcut-connected sparse-expert model on latent rings: the bytes a decode
tick has to read (`lib/flops_longcat.decode_tick_bytes`: every leaf outside
the routed experts and the embedding once, three matrices for each held
expert that got a pair, the cached rows that hold a token at their own
width) over the chip's memory bandwidth, divided by the device time of one
run of the decode program.

The bytes are a tick's mean over the LAST `last_ticks` decode ticks of the
untraced part of the window, from the attrs of the program's `serve.decode`
spans (`experts_touched`, `kv_rows`, and `pairs_zero`, which only this
family's program records: a program without it reads None). The time is
read as `decode_hbm_roofline` reads it: the mean duration of the decode
program's events on the device plane's `XLA Modules` line inside the traced
slice, which FOLLOWS the untraced part (the live slots and their contexts
grow through a window that starts on an empty engine, so the ticks next to
the slice are the ones whose bytes go with its time). In percent; None where
a span lacks the attrs or the trace holds no such event, never 0.
args: {"itemsize": bytes a weight or cache element takes, "program": regular
expression of the decode program's module name, "last_ticks": how many ticks
before the traced slice the bytes are a mean of}."""

from lib import flops_longcat, xplane
from reducers.decode_hbm_roofline import module_events, program_seconds
from reducers.serve_mfu_moe import span_values


def reduce(config, peaks, args, touched, rows, seconds):
    """The share from a tick's counts (lists, one entry a decode tick) and
    the program's seconds."""
    if not touched or not rows or not seconds:
        return None
    last = int(args.get("last_ticks", len(rows)))
    touched, rows = touched[-last:], rows[-last:]
    nbytes = flops_longcat.decode_tick_bytes(
        config, int(args["itemsize"]), sum(touched) / len(touched),
        sum(rows) / len(rows))
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / seconds


def compute(args, run, measured, trace):
    if trace is None or not run.trace_dir or \
            not span_values(measured, "serve.decode", "pairs_zero"):
        return None
    try:
        path = xplane.find_xplane(run.trace_dir)
    except FileNotFoundError:
        return None
    return reduce(
        run.config, run.peaks, args,
        span_values(measured, "serve.decode", "experts_touched"),
        span_values(measured, "serve.decode", "kv_rows"),
        program_seconds(module_events(path), trace["window"],
                        args["program"]))
