"""The decode program's share of its memory roofline: the bytes a decode
tick has to read (`lib/flops_cohere_moe.decode_tick_bytes`: every leaf
outside the routed experts once, three matrices for each held expert that
got a pair, the ring rows that hold a token) over the chip's memory
bandwidth, divided by the device time of one run of the decode program.

The bytes are a tick's mean over the untraced part of the window, from the
attrs of the program's `serve.decode` spans (`experts_touched`, `kv_rows`:
the counts each tick also adds to `moe_experts_touched_total{program=
"decode"}` and `serve_kv_rows_attended_total`). The time is the mean
duration of the events of the device plane's `XLA Modules` line whose name
matches (one event a run of a whole program, first op to last) that lie
whole inside the traced slice; read from the run's own trace file, since
the harness's reduction keeps the op line only. In percent; None where no
span carries the attrs or the trace holds no such event, never 0.
args: {"itemsize": bytes a weight or cache element takes,
"program": regular expression of the decode program's module name}."""

import re

from lib import flops_cohere_moe, xplane
from reducers.serve_mfu_moe import span_values

MODULE_LINE = "XLA Modules"


def module_events(path):
    """[[name, start_ns, duration_ns], ...] of the first chip's module
    line in an `.xplane.pb`."""
    from jax.profiler import ProfileData
    planes = {p.name: p for p in ProfileData.from_file(path).planes
              if xplane.DEVICE_PLANE.match(p.name)}
    for name in sorted(planes):
        for line in planes[name].lines:
            if line.name == MODULE_LINE:
                return [[e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
    return []


def program_seconds(events, window, pattern):
    """Mean seconds of the module events matching `pattern` that lie whole
    inside the window (start_ns, end_ns); None where there is none."""
    rx = re.compile(pattern)
    w0, w1 = window
    runs = [d for name, s, d in events
            if rx.search(name) and s >= w0 and s + d <= w1]
    return sum(runs) / len(runs) / 1e9 if runs else None


def reduce(config, peaks, args, touched, rows, seconds):
    """The share from a tick's counts (lists, one entry a decode tick) and
    the program's seconds."""
    if not touched or not rows or not seconds:
        return None
    nbytes = flops_cohere_moe.decode_tick_bytes(
        config, int(args["itemsize"]), sum(touched) / len(touched),
        sum(rows) / len(rows))
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
        span_values(measured, "serve.decode", "experts_touched"),
        span_values(measured, "serve.decode", "kv_rows"),
        program_seconds(module_events(path), trace["window"],
                        args["program"]))
