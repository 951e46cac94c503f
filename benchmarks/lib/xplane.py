"""The reduction from a profiler trace to numbers, read with
`jax.profiler.ProfileData` and nothing else.

What a v5e trace holds (read by hand in PR 23, see PERF.md section 6): one
plane per chip named `/device:TPU:<n>` (beside `#Chip<n> Host Interface`,
`#Chip<n> Misc`, `/device:CUSTOM:Megascale Trace`, `/host:metadata` and
`Task Environment`, which hold no op). Its line `XLA Ops` carries one event
per executed HLO op, named by the op's whole HLO text (fusions, Mosaic
custom calls under their kernel name such as `%jvp_flash_fwd_.24`, async
`copy-start`/`slice-start` and their `-done`), nested where a `while` runs a
body. `Async XLA Ops` repeats the async pairs as start-to-done spans that
overlap the compute; `XLA Modules` and `Steps` carry one event per whole
program and cover the gaps between ops. Only `XLA Ops` is counted as work.
Host threads are the lines of the plane `/host:CPU`;
`jax.profiler.TraceAnnotation`s appear there by name (line `python3`), on
the same clock as the device planes.

The reduction works on a neutral form, so it can be tested on a recorded
cut of a chip trace without a chip:

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}
"""

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path, keep_host=("bench.",)):
    """The neutral form of an `.xplane.pb`. Device planes are kept whole;
    of the host plane only the events whose name starts with one of
    `keep_host` (the benchmark's annotations), which keeps it small."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = DEVICE_PLANE.match(plane.name) is not None
        if not (is_dev or plane.name == HOST_PLANE):
            continue
        lines = []
        for line in plane.lines:
            events = [[op_name(e.name), float(e.start_ns),
                       float(e.duration_ns)]
                      for e in line.events
                      if is_dev or e.name.startswith(tuple(keep_host))]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_name(text):
    """The op's own name. The chip's trace names an op by its whole HLO
    line, `%fusion.79 = (...) fusion(... %jvp_flash_fwd_.24 ...)`, operands
    and all; a pattern must not match an operand, so only the head counts."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_kind(name):
    """`jvp_flash_fwd_.24` -> `jvp_flash_fwd_`: the instances of one kernel
    or one kind of fusion read as one line of the breakdown. A bare
    `fusion.N` or `custom-call.N` says nothing without its number."""
    base, dot, num = name.rpartition(".")
    if dot and num.isdigit() and base not in ("fusion", "custom-call"):
        return base
    return name


def load_json(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def device_op_events(trace):
    """{chip index: events of that chip's op line, sorted by start}."""
    out = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        for line in plane["lines"]:
            if line["name"] == OP_LINE:
                out[int(m.group(1))] = sorted(
                    line["events"], key=lambda e: (e[1], -e[2]))
    return out


def host_annotations(trace, prefix="bench."):
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            out.extend(e for e in line["events"] if e[0].startswith(prefix))
    return sorted(out, key=lambda e: e[1])


def union(intervals):
    """Merged, sorted list of (start, end) from any (start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def total(merged):
    return sum(e - s for s, e in merged)


def clip(events, window):
    """Events cut to the window (start_ns, end_ns); those outside go."""
    w0, w1 = window
    out = []
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            out.append([name, a, b - a])
    return out


def subtract(merged, holes):
    """Length of `merged` not covered by the merged list `holes`."""
    left = total(merged)
    j = 0
    for s, e in merged:
        while j < len(holes) and holes[j][1] <= s:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < e:
            left -= min(e, holes[k][1]) - max(s, holes[k][0])
            k += 1
    return left


def self_times(events):
    """{name: [count, self seconds]}: an event's duration minus the part its
    nested events cover, so a `while` and its body are not counted twice."""
    out = {}
    stack = []                                  # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            slot = out.setdefault(name, [0, 0.0])
            slot[0] += 1
            slot[1] += max(self_ns, 0.0) / 1e9

    for name, s, d in events:
        close(s)
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    close(float("inf"))
    return out


def matching_seconds(events, patterns):
    """Union length, in seconds, of the events whose name matches any of
    the regular expressions."""
    rx = [re.compile(p) for p in patterns]
    hit = [(s, s + d) for name, s, d in events
           if any(r.search(name) for r in rx)]
    return total(union(hit)) / 1e9, len(hit)


def exposed_collective_seconds(events):
    """Time in which a collective op runs on the chip and no other op
    does. Ops that contain others (a `while`) are containers, not work."""
    leaves = _leaves(events)
    coll = union((s, s + d) for n, s, d in leaves if COLLECTIVE.search(n))
    comp = union((s, s + d) for n, s, d in leaves
                 if not COLLECTIVE.search(n))
    return subtract(coll, comp) / 1e9


def _leaves(events):
    out = []
    for i, (name, s, d) in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is not None and nxt[1] < s + d and nxt[1] + nxt[2] <= s + d \
                and nxt[2] < d:
            continue                            # contains the next event
        out.append([name, s, d])
    return out


def idle_gaps(events, window, annotations, top=10):
    """The idle time of one chip's op line inside the window, summed by the
    benchmark annotation the middle of each gap falls under (`(none)` where
    no annotation covers it): [[name, seconds], ...], longest first."""
    busy = union((s, s + d) for _, s, d in events)
    w0, w1 = window
    gaps, at = [], w0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if w1 > at:
        gaps.append((at, w1))
    by_name = {}
    for s, e in gaps:
        mid = (s + e) / 2
        name = "(none)"
        for an, a_s, a_d in annotations:
            if a_s <= mid < a_s + a_d and an != "bench.window":
                name = an                       # innermost: latest start
        by_name[name] = by_name.get(name, 0.0) + (e - s) / 1e9
    return sorted(([n, t] for n, t in by_name.items()),
                  key=lambda x: -x[1])[:top]


def reduce(trace, top=10):
    """Everything the harness reports from one trace: the window (the
    `bench.window` annotation, else the span of the device ops), the seconds
    in which an op ran averaged over the chips, per-chip events cut to the
    window, and the breakdown."""
    per_chip = device_op_events(trace)
    if not per_chip:
        raise ValueError("the trace holds no /device:TPU:<n> op line")
    notes = host_annotations(trace)
    win = [e for e in notes if e[0] == "bench.window"]
    if win:
        window = (win[-1][1], win[-1][1] + win[-1][2])
    else:
        window = (min(ev[0][1] for ev in per_chip.values()),
                  max(max(s + d for _, s, d in ev)
                      for ev in per_chip.values()))
    cut = {chip: clip(ev, window) for chip, ev in per_chip.items()}
    busy = {chip: total(union((s, s + d) for _, s, d in ev)) / 1e9
            for chip, ev in cut.items()}
    first = min(cut)
    ops = {}
    for name, (_, seconds) in self_times(cut[first]).items():
        kind = op_kind(name)
        ops[kind] = ops.get(kind, 0.0) + seconds
    device_ops = sorted(([n, t] for n, t in ops.items()),
                        key=lambda x: -x[1])[:top]
    return {
        "window": window,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_s_per_chip": busy,
        "events": cut,
        "breakdown": {"device_ops": device_ops,
                      "idle_gaps": idle_gaps(cut[first], window, notes,
                                             top)},
    }
