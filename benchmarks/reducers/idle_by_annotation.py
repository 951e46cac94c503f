"""The idle time of the chip's op line, given to what the program's host
thread was inside: each instant of the traced window belongs to the
innermost of the program's annotations open at it (the one that began
last), and an annotation's idle time is the part of its instants in which
no op ran. By overlap, not by the middle of a gap: in a serving tick one gap
between two programs spans the read-back's tail, the sampling, the
bookkeeping and the next dispatch. The annotations are read from the run's
own trace file (spans of `singa_tpu.observability.spans` are profiler
annotations); None where the trace holds none under the prefix.
args: {"prefix": "serve.",
"names": [annotation, ...] whose idle time is summed (absent: every name
under the prefix), "complement_of": [annotation, ...] (every name under the
prefix but these), "per": annotation (count only the idle time inside
annotations of this name and divide by how many of them the window holds, one
that the window cuts counting by its share), "share": true (in % of all the
idle time of the window), "scale": multiplier of seconds}."""

import functools

from lib import xplane


def innermost(annotations):
    """[(start, end, name)]: the annotations flattened so that every
    instant belongs to the one that began last among those open at it."""
    segments, stack, at = [], [], 0.0           # stack of [name, end]

    def close(upto):
        nonlocal at
        while stack and stack[-1][1] <= upto:
            name, end = stack.pop()
            if end > at:
                segments.append((at, end, name))
                at = end

    for name, start, duration in sorted(annotations,
                                        key=lambda e: (e[1], -e[2])):
        close(start)
        if stack and start > at:
            segments.append((at, start, stack[-1][0]))
        at = max(at, start) if stack else start
        stack.append([name, start + duration])
    close(float("inf"))
    return segments


def idle_seconds(segments, busy, wanted):
    """Seconds of the segments whose name `wanted` accepts in which no
    interval of the merged list `busy` lies."""
    mine = xplane.union((s, e) for s, e, name in segments if wanted(name))
    return xplane.subtract(mine, busy) / 1e9


def count_in_window(annotations, name, window):
    """How many annotations `name` the window holds; one that the window
    cuts counts by the share of it that lies inside."""
    w0, w1 = window
    return sum(max(0.0, min(s + d, w1) - max(s, w0)) / d
               for n, s, d in annotations if n == name and d > 0)


def reduce(annotations, events, window, args):
    """The metric from neutral-form host annotations and one chip's op
    events (of any extent; both are cut to the window here)."""
    inside = xplane.clip(annotations, window)
    if not inside:
        return None
    busy = xplane.union((s, s + d) for _, s, d in xplane.clip(events,
                                                             window))
    if "names" in args:
        wanted = set(args["names"]).__contains__
    elif "complement_of" in args:
        left_out = set(args["complement_of"])
        wanted = (lambda name: name not in left_out)
    else:
        wanted = (lambda name: True)
    segments = innermost(inside)
    if "per" in args:
        # only what lies inside an annotation `per` counts: the trace
        # holds the phases of a tick it cut but not the tick itself, and
        # such a tick must add to neither side of the division
        cover = xplane.union((s, s + d) for n, s, d in inside
                             if n == args["per"])
        segments = [seg for seg in segments
                    if xplane.subtract([list(seg[:2])], cover) <= 0]
    value = idle_seconds(segments, busy, wanted)
    if args.get("share"):
        idle = xplane.subtract([list(window)], busy) / 1e9
        return 100.0 * value / idle if idle > 0 else None
    value *= float(args.get("scale", 1.0))
    if "per" in args:
        count = count_in_window(annotations, args["per"], window)
        return value / count if count > 0 else None
    return value


@functools.lru_cache(maxsize=2)
def _annotations(path, prefix):
    """The program's annotations of one trace file (parsed once for the
    several metrics that read them)."""
    return tuple(xplane.host_annotations(
        xplane.load(path, keep_host=(prefix,)), prefix))


def compute(args, run, measured, trace):
    if trace is None or not run.trace_dir:
        return None
    try:
        path = xplane.find_xplane(run.trace_dir)
    except FileNotFoundError:
        return None
    chip = min(trace["events"])
    return reduce(list(_annotations(path, args["prefix"])),
                  trace["events"][chip], trace["window"], args)
