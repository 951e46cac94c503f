"""Where a serve program's host call and its run on the device meet: for each
decode tick whose `serve.decode` span lies whole inside the traced slice, the
one run of the decode program that its call launched, on the device plane's
`XLA Modules` line (one event a run of a whole program, first op to last;
`decode_hbm_roofline.module_events`), against the engine's annotations of that
tick on the host plane (its span's phases are annotations on the trace's
clock):

- `launch`: the run's start less the start of `serve.decode.call` (the call's
  own host work, the runtime's enqueue and the device's start);
- `completion`: the end of `serve.decode.ready` less the run's end (what the
  host takes to learn that the program is done).

Calls and runs are paired by order: the calls of both programs (prefill and
decode, from their `.call` annotations) against the runs of both, at the shift
between the two sequences that leaves the fewest pairs of two programs, then
the least median distance between a call's start and its run's. Where prefills
break the rhythm, an offset between the host's and the device's clocks of up to
a tick or more still pairs each call with its own run, which "the first run
after the call" would not. A negative reading is returned as it is: it measures
the clocks' disagreement. The median over the ticks, in ms; None where the
trace holds no such pair, never 0.
args: {"edge": "launch" | "completion", "program": regular expression of the
decode program's module name}; `PREFILL` tells the prefill program's runs."""

import bisect
import functools
import re
import statistics

from lib import xplane
from reducers.decode_hbm_roofline import module_events
from reducers.idle_by_annotation import _annotations

PREFILL = r"^jit_prefill_body\("
SPANS = (("serve.decode", "decode"), ("serve.prefill", "prefill"))
MAX_SHIFT = 3


def host_ticks(annotations):
    """[(program, call_start, span_start, span_end, ready_end)] in order of
    the call: one a `serve.decode` / `serve.prefill` annotation that holds a
    `.call` (`ready_end` None where it holds no `.ready`)."""
    by_name = {}
    for name, s, d in annotations:
        by_name.setdefault(name, []).append((s, s + d))
    out = []
    for span, program in SPANS:
        inner = {}
        for phase in ("call", "ready"):
            found = sorted(by_name.get(f"{span}.{phase}", []))
            inner[phase] = (found, [s for s, _ in found])
        for s, e in by_name.get(span, []):
            edges = {}
            for phase, (found, starts) in inner.items():
                i = bisect.bisect_left(starts, s)
                if i < len(found) and found[i][1] <= e:
                    edges[phase] = found[i]
            if "call" in edges:
                ready = edges.get("ready")
                out.append((program, edges["call"][0], s, e,
                            None if ready is None else ready[1]))
    return sorted(out, key=lambda t: t[1])


def module_runs(modules, patterns):
    """[(program, start, end)] of the module events that match a program's
    pattern (`patterns`: {program: regular expression}), in order."""
    rx = {p: re.compile(x) for p, x in patterns.items()}
    out = []
    for name, s, d in modules:
        for program, r in rx.items():
            if r.search(name):
                out.append((program, s, s + d))
                break
    return sorted(out, key=lambda r: r[1])


def pair(ticks, runs):
    """[(tick, run)]: tick i with run i + k, at the shift k that leaves the
    fewest pairs of two programs, then the least median |start gap|; of those
    pairs, the ones of one program."""
    best = None
    for k in range(-MAX_SHIFT, MAX_SHIFT + 1):
        pairs = [(t, runs[i + k]) for i, t in enumerate(ticks)
                 if 0 <= i + k < len(runs)]
        if not pairs:
            continue
        key = (sum(t[0] != r[0] for t, r in pairs),
               statistics.median(abs(r[1] - t[1]) for t, r in pairs))
        if best is None or key < best[0]:
            best = (key, pairs)
    return [] if best is None else [(t, r) for t, r in best[1]
                                    if t[0] == r[0]]


def edges(annotations, modules, window, args):
    """{"launch": [ms, ...], "completion": [ms, ...]}: one reading a decode
    tick whose span and run lie whole inside the window (ns)."""
    patterns = {"decode": args["program"],
                "prefill": PREFILL}
    w0, w1 = window
    out = {"launch": [], "completion": []}
    for t, r in pair(host_ticks(annotations),
                     module_runs(modules, patterns)):
        program, call_start, s, e, ready_end = t
        if program != "decode" or not (w0 <= s and e <= w1
                                       and w0 <= r[1] and r[2] <= w1):
            continue
        out["launch"].append((r[1] - call_start) / 1e6)
        if ready_end is not None:
            out["completion"].append((ready_end - r[2]) / 1e6)
    return out


def reduce(annotations, modules, window, args):
    values = edges(annotations, modules, window, args)[args["edge"]]
    return statistics.median(values) if values else None


@functools.lru_cache(maxsize=2)
def _modules(path):
    """The module line of one trace file (read once for both edges)."""
    return tuple(module_events(path))


def compute(args, run, measured, trace):
    if trace is None or not run.trace_dir:
        return None
    try:
        path = xplane.find_xplane(run.trace_dir)
    except FileNotFoundError:
        return None
    return reduce(list(_annotations(path, "serve.")), _modules(path),
                  trace["window"], args)
