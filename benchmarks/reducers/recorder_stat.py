"""A statistic over the program's flight-recorder records
(`singa_tpu.observability.spans.recorder().records()`), None where no
record matches.
args: {"kind": "span" | "event", "name": a name or a list of names,
"where": {attr: value} (optional), "field": "dur_s" | "compile_s" |
"phases.<name>" | any number the record holds,
"when": "setup" (the record ended before set-up did) | "window" (it began
inside the untraced part of the window, by the driver's snapshots),
"stat": "sum" | "mean" | "p95", "scale": multiplier}."""

import time

import numpy as np


def _field(rec, path):
    value = rec
    for key in path.split("."):
        if not isinstance(value, dict) or key not in value:
            return None
        value = value[key]
    return value


def select(records, args, setup_done_wall, window_wall):
    """The numbers the args pick from `records` (wall-clock epoch bounds)."""
    names = args["name"]
    names = {names} if isinstance(names, str) else set(names)
    where = args.get("where", {})
    out = []
    for rec in records:
        if rec.get("kind") != args["kind"] or rec.get("name") not in names:
            continue
        if any(rec.get(k) != v for k, v in where.items()):
            continue
        if args["when"] == "setup":
            if rec["ts"] > setup_done_wall:
                continue
        else:
            if window_wall is None or not (
                    window_wall[0] <= rec.get("ts_start", rec["ts"])
                    < window_wall[1]):
                continue
        value = _field(rec, args["field"])
        if value is not None:
            out.append(float(value))
    return out


def statistic(values, stat):
    if not values:
        return None
    if stat == "sum":
        return float(sum(values))
    if stat == "mean":
        return float(sum(values) / len(values))
    if stat == "p95":
        return float(np.percentile(np.asarray(values, np.float64), 95))
    raise ValueError(f"unknown stat {stat!r}")


def compute(args, run, measured, trace):
    from singa_tpu.observability import spans
    if run.setup_s is None:
        return None
    # the end of set-up on the records' clock (epoch seconds)
    setup_done_wall = time.time() - (
        time.perf_counter() - (run.t_start + run.setup_s))
    window_wall = None
    if "snap_start" in measured and "snap_end" in measured:
        window_wall = (measured["snap_start"]["wall"],
                       measured["snap_end"]["wall"])
    values = select(spans.recorder().records(), args, setup_done_wall,
                    window_wall)
    value = statistic(values, args["stat"])
    return None if value is None else value * float(args.get("scale", 1.0))
