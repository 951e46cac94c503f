"""Measured per-fusion step profiling from ``jax.profiler`` traces.

The reference prints MEASURED per-node times of the graph it actually
runs (src/core/scheduler/scheduler.cc:240-298). In the XLA world the
executed graph is a set of fusions, so the honest equivalent is: capture
a profiler trace of one compiled step and aggregate the per-fusion
durations. This complements the *static* cost analysis (flops/bytes)
captured by ``Model.cost_analysis``.
"""

import glob
import gzip
import json
import os

# host-side runtime/python frames that appear in CPU traces alongside the
# XLA op events; device lanes (TPU) don't need this
_RUNTIME_MARKERS = ("(", "::", " ")


def _is_xla_op_event(name):
    if name.startswith("$"):             # python source frames
        return False
    return not any(m in name for m in _RUNTIME_MARKERS)


def parse_trace_events(logdir):
    """Flat list of complete ('X') events from a ``jax.profiler.trace``
    output directory, one dict per event::

        {"name": <enriched symbol>, "ts": <µs or None>, "dur": <µs>,
         "lane": "device" | "host", "pid": ..., "line": <thread name>,
         "xla_op": bool}

    ``lane`` is resolved per trace file (``/device:...`` process rows
    are device lanes); ``line`` is the row's thread name (a TPU plane's
    ``XLA Ops``, ``XLA Modules``, ``Steps`` …; ``""`` where the trace
    names none); ``xla_op`` records whether the RAW event name
    looked like an XLA op/fusion symbol (the host-fallback filter —
    computed before :func:`_enrich` folds metadata into the name).
    Python source frames (``$...``) and zero-duration events are
    skipped. This is the ONE gzip+json pass both consumers share: the
    per-fusion aggregation (:func:`parse_trace_dir`) and the
    step-timeline bucketizer (``observability.timeline.analyze``)."""
    files = glob.glob(os.path.join(logdir, "**", "*.trace.json.gz"),
                      recursive=True)
    out = []
    for path in files:
        try:
            with gzip.open(path, "rt") as fh:
                trace = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        events = trace.get("traceEvents", [])
        lanes, rows = {}, {}
        for e in events:
            if e.get("ph") != "M":
                continue
            if e.get("name") == "process_name":
                lanes[e["pid"]] = e.get("args", {}).get("name", "")
            elif e.get("name") == "thread_name":
                rows[(e.get("pid"), e.get("tid"))] = \
                    e.get("args", {}).get("name", "")
        device_pids = {pid for pid, name in lanes.items()
                       if name.startswith("/device:")}
        for e in events:
            if e.get("ph") != "X" or not e.get("dur"):
                continue
            name = e.get("name", "")
            if name.startswith("$"):        # python source frames
                continue
            pid = e.get("pid")
            ts = e.get("ts")
            out.append({
                "name": _enrich(name, e.get("args")),
                "ts": float(ts) if ts is not None else None,
                "dur": float(e["dur"]),
                "lane": "device" if pid in device_pids else "host",
                "pid": pid,
                "line": rows.get((pid, e.get("tid")), ""),
                "xla_op": _is_xla_op_event(name)})
    return out


def parse_trace_dir(logdir):
    """Aggregate complete ('X') events from a ``jax.profiler.trace``
    output directory into ``{op_name: (count, total_seconds)}``.

    Prefers device lanes (``/device:...`` processes — real accelerator
    timelines); on backends without device lanes (CPU) falls back to the
    host lane filtered down to XLA op/fusion names.
    """
    return aggregate_events(parse_trace_events(logdir))


def aggregate_events(events):
    """Fold a :func:`parse_trace_events` list into the per-fusion
    ``{name: (count, total_seconds)}`` table (device lanes preferred,
    XLA-op host fallback otherwise — same rule one level up)."""
    has_device = any(e["lane"] == "device" for e in events)
    out = {}
    for e in events:
        if has_device:
            if e["lane"] != "device":
                continue
        elif not e["xla_op"]:
            continue
        cnt, tot = out.get(e["name"], (0, 0.0))
        out[e["name"]] = (cnt + 1, tot + e["dur"] * 1e-6)
    return out


def _enrich(name, args):
    """Fold trace metadata into an uninformative fusion symbol: device
    lanes name events "fusion.NN", but their args often carry the HLO
    long name / source op — without it a banked profile row can't be
    attributed to a model component. Purely additive: events without
    metadata keep their bare name (CPU CI traces are unchanged)."""
    if not isinstance(args, dict):
        return name
    meta = args.get("long_name") or args.get("tf_op") \
        or args.get("hlo_op") or args.get("hlo_category")
    meta = str(meta) if meta else ""
    if meta and meta != name:
        return f"{name}|{meta[:160]}"
    return name


def measure_step_fusions(run_step, logdir=None, events_out=None):
    """Run ``run_step()`` (which must block on its outputs) under a
    profiler trace and return the parsed per-op aggregate. Returns
    ``(result, {name: (count, total_seconds)})``.

    ``events_out``: a list that, when supplied, receives the RAW
    timestamped events (:func:`parse_trace_events`) of the same single
    parse pass — what ``observability.timeline.analyze`` buckets into
    compute/collective/memcpy/host/idle. An out-param so the 2-tuple
    shape every existing caller consumes stays stable.

    PROFILER failures degrade to an empty table; a failure of the step
    itself propagates untouched (re-running an expensive failing step to
    mask a profiling problem would double the damage and bury the real
    traceback). The temporary trace dump is deleted unless the caller
    supplied ``logdir``."""
    import shutil
    import tempfile

    import jax

    d = logdir or tempfile.mkdtemp(prefix="sg_prof_")
    try:
        ctx = None
        try:
            ctx = jax.profiler.trace(d)
            ctx.__enter__()
        except Exception:
            ctx = None
        try:
            result = run_step()
        finally:
            if ctx is not None:
                try:
                    ctx.__exit__(None, None, None)
                except Exception:
                    ctx = None
        table = {}
        if ctx is not None:
            try:
                events = parse_trace_events(d)
                table = aggregate_events(events)
                if events_out is not None:
                    events_out.extend(events)
            except Exception:
                table = {}
        return result, table
    finally:
        # the trace dump can be tens of MB per signature; never leave it
        # behind (including when the step itself raised)
        if logdir is None:
            shutil.rmtree(d, ignore_errors=True)


def summarize_table(table, top=5):
    """Top-``top`` fusions of one measured table by total seconds,
    JSON-able (``[[name, count, seconds], ...]``) — what the sampling
    profiler's ``profile.sample`` flight-recorder event carries so a
    blackbox names the hot fusions without the full table."""
    rows = sorted(table.items(), key=lambda kv: -kv[1][1])[:int(top)]
    return [[name[:120], int(cnt), round(tot, 6)]
            for name, (cnt, tot) in rows]


def record_fusion_metrics(table, registry=None):
    """Publish a measured per-fusion table into the metrics registry
    (gauges labeled by fusion symbol — SET, not accumulated: each
    profile run replaces the previous decomposition). Used by
    ``Model.profile_step``; returns the registry."""
    from .observability import metrics as _metrics
    reg = registry if registry is not None else _metrics.default_registry()
    secs = reg.gauge("profile_fusion_seconds",
                     "measured device seconds per XLA fusion in the "
                     "newest profiled step", labels=("fusion",))
    cnts = reg.gauge("profile_fusion_count",
                     "event count per XLA fusion in the newest "
                     "profiled step", labels=("fusion",))
    for name, (cnt, tot) in table.items():
        secs.set(tot, fusion=name)
        cnts.set(cnt, fusion=name)
    return reg
