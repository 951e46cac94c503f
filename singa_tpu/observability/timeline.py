"""Step-timeline attribution: where did the hardware go?

The sampling profiler (PR 9) already captures a device trace of one
compiled step (``profiling.measure_step_fusions`` — the same capture
``Model.profile_step`` makes; no second tracing mechanism) and sums the
per-fusion costs. This module keeps the TIMELINE that sum used to throw
away and buckets every device-lane event into:

- **compute** — fusions, dot_generals, convolutions: the MXU/VPU doing
  model math;
- **collective** — all-reduce / all-gather / reduce-scatter /
  all-to-all / collective-permute (``ppermute``/``psum`` lower to
  these) / cross-program send/recv: cross-chip communication;
- **memcpy** — HBM↔host traffic: infeed/outfeed, copy-start/done,
  host transfers;
- **host** — device idle while a HOST lane is busy (the runtime
  feeding/blocking the device — the data-stall signature);
- **idle** — device idle with nothing measurable on the host either.

Two numbers fall out that the ROADMAP's MFU push is steered by:

- **exposed communication**: collective time NOT overlapped with
  compute — the quantity DistOpt gradient-bucketing must drive to
  zero. Overlapped collectives are free; exposed ones are the bill.
- the **MFU-loss waterfall**: peak FLOPs → achieved, with the gap
  attributed per bucket (:func:`waterfall`) — so "MFU is 0.31" becomes
  "0.19 of peak went to exposed collectives, 0.08 to input stalls,
  0.42 to compute inefficiency (HBM-bound fusions)".

The bucket fractions are EXACT over the step window: compute +
exposed-collective + exposed-memcpy + host + idle == 1.0 (overlap is
resolved by precedence compute > collective > memcpy; the committed
trace fixture pins this to 1e-6 in tier-1, CPU-only).

Publication: :func:`record_timeline` sets the ``timeline_*`` gauges
(labels ``site=train|serve`` and ``bucket``); the sampling profiler
(``ResilientTrainer(profile_every=N)``) refreshes them continuously
and its ``timeline.sample`` flight-recorder event carries bounded
per-bucket interval lanes that ``trace_export`` renders as extra
Perfetto rows. :func:`classify_cause` turns a rank's fractions into
the ``comm_bound | data_bound | compute_bound | compile_bound`` label
the coordinator's fleet health report attaches to each straggler
(``metrics.aggregate_summaries -> straggler_causes``).

Everything here is host-side stdlib math over already-parsed events —
nothing imports jax, and the compiled step's ``n_traces`` pin is
untouched (the capture wraps the already-compiled dispatch).
"""

from __future__ import annotations

BUCKETS = ("compute", "collective", "memcpy", "host", "idle")

# substring markers over the (lowercased) event symbol — checked on
# each "|"-separated part, so an enriched "fusion.3|all-reduce.1"
# classifies by its HLO long name too. Order matters: collective wins
# over memcpy (a "collective-permute-start" contains neither memcpy
# marker, but be explicit anyway).
_COLLECTIVE_MARKERS = (
    "all-reduce", "allreduce", "all-gather", "allgather",
    "reduce-scatter", "reducescatter", "all-to-all", "alltoall",
    "collective-permute", "collective-broadcast", "ppermute", "psum",
    "send", "recv")
_MEMCPY_MARKERS = ("infeed", "outfeed", "memcpy", "host-transfer",
                   "transfertodevice", "transferfromdevice", "copy-start",
                   "copy-done", "copy.")


def classify_op(name):
    """Bucket one device-lane op symbol: ``collective`` / ``memcpy`` /
    ``compute``. (``host``/``idle`` are gap buckets — they exist only
    relative to a step window, see :func:`analyze`.)"""
    low = str(name).lower()
    for part in low.split("|"):
        for m in _COLLECTIVE_MARKERS:
            if m in part:
                return "collective"
        for m in _MEMCPY_MARKERS:
            if m in part:
                return "memcpy"
        if part == "copy" or part.startswith("copy."):
            return "memcpy"
    return "compute"


# ---------------------------------------------------------------------------
# interval arithmetic (half-open [start, end) µs pairs)
# ---------------------------------------------------------------------------

def merge_intervals(intervals):
    """Sort + merge overlapping/touching intervals."""
    ivs = sorted((float(a), float(b)) for a, b in intervals if b > a)
    out = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def subtract_intervals(base, cut):
    """``base - cut`` (both merged): the parts of ``base`` not covered
    by ``cut``."""
    out = []
    ci = 0
    cut = list(cut)
    for a, b in base:
        pos = a
        while ci < len(cut) and cut[ci][1] <= pos:
            ci += 1
        j = ci
        while j < len(cut) and cut[j][0] < b:
            ca, cb = cut[j]
            if ca > pos:
                out.append((pos, min(ca, b)))
            pos = max(pos, cb)
            if pos >= b:
                break
            j += 1
        if pos < b:
            out.append((pos, b))
    return [iv for iv in out if iv[1] > iv[0]]


def intersect_intervals(a, b):
    """Overlap of two merged interval lists."""
    out = []
    i = j = 0
    a, b = list(a), list(b)
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _span(intervals):
    return sum(b - a for a, b in intervals)


def _clip(intervals, t0, t1):
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if min(b, t1) > max(a, t0)]


# ---------------------------------------------------------------------------
# the analyzer
# ---------------------------------------------------------------------------

_MAX_LANE_INTERVALS = 128

# a TPU plane's rows: `XLA Ops` holds one event an executed op; `Steps`
# and `XLA Modules` hold one event a step or a program, first op to
# last, and so cover the gaps between its ops (they are not work)
OP_LINE = "XLA Ops"
NOT_OP_LINES = ("Steps", "XLA Modules")


def device_ops(events):
    """The device-lane events that are ops: on a device process that
    has an ``XLA Ops`` row, that row alone (as
    ``benchmarks/lib/xplane.py`` counts); elsewhere every row but
    ``Steps`` and ``XLA Modules``."""
    device = [e for e in events if e.get("lane") == "device"]
    with_op_line = {e.get("pid") for e in device
                    if e.get("line") == OP_LINE}
    return [e for e in device
            if (e.get("line") == OP_LINE if e.get("pid") in with_op_line
                else e.get("line") not in NOT_OP_LINES)]


def host_extent(events, name):
    """``(t0_us, t1_us)`` of the last host event called ``name`` (a
    span's annotation, such as the profiled tick's ``serve.tick``), or
    None where the trace holds none."""
    found = [e for e in events if e.get("lane") == "host"
             and e.get("name") == name and e.get("ts") is not None]
    if not found:
        return None
    last = max(found, key=lambda e: e["ts"])
    return last["ts"], last["ts"] + last["dur"]


def analyze(events, window=None, own=()):
    """Bucket a step's trace events (``profiling.parse_trace_events``
    dicts) into the compute/collective/memcpy/host/idle decomposition.

    Device lanes are the op timeline (:func:`device_ops`: a TPU plane's
    ``Steps`` and ``XLA Modules`` rows are no ops); on a backend without
    device lanes (CPU CI) the host lane's XLA-op events stand in (and
    the ``host`` bucket is then empty — it cannot be told apart from
    compute there). ``window`` is an optional ``(t0_us, t1_us)``
    override; by default the window spans the first op start to the
    last op end. ``own``: name prefixes of the program's own host
    annotations (its spans: what the host thread was inside, not the
    runtime feeding or blocking the device); they are left out of the
    host lane, so a gap under them alone is ``idle``.

    Returns None when nothing timestamped was captured, else a dict::

        {"window_s", "compute_s", "collective_s",
         "exposed_collective_s", "memcpy_s", "exposed_memcpy_s",
         "host_s", "idle_s", "fractions": {bucket: f, ...},  # sums to 1
         "overlapped_collective_s", "events": n,
         "lanes": {bucket: [[rel_start_s, dur_s], ...], ...}}

    The ``fractions`` partition the window exactly (precedence
    compute > collective > memcpy over overlapping device time), so
    ``sum(fractions.values()) == 1.0`` to float precision —
    exposed-communication seconds are ``exposed_collective_s``, while
    ``collective_s`` is the TOTAL collective time (overlap included:
    ``collective_s - exposed_collective_s`` is what the DistOpt
    bucketing successfully hid under compute)."""
    evs = [e for e in (events or [])
           if e.get("ts") is not None and e.get("dur")]
    own = tuple(own)
    device = device_ops(evs)
    if device:
        ops = device
        host = [e for e in evs if e.get("lane") == "host"
                and not str(e.get("name", "")).startswith(own)]
    else:
        # CPU fallback: host XLA-op events are the op timeline; there
        # is no separate runtime lane to attribute gaps to
        ops = [e for e in evs if e.get("xla_op", True)
               and not str(e.get("name", "")).startswith(own)]
        host = []
    if not ops:
        return None

    by_bucket = {"compute": [], "collective": [], "memcpy": []}
    for e in ops:
        by_bucket[classify_op(e["name"])].append(
            (e["ts"], e["ts"] + e["dur"]))
    if window is not None:
        t0, t1 = float(window[0]), float(window[1])
    else:
        t0 = min(a for ivs in by_bucket.values() for a, _b in ivs)
        t1 = max(b for ivs in by_bucket.values() for _a, b in ivs)
    if t1 <= t0:
        return None

    compute = merge_intervals(_clip(by_bucket["compute"], t0, t1))
    coll = merge_intervals(_clip(by_bucket["collective"], t0, t1))
    memcpy = merge_intervals(_clip(by_bucket["memcpy"], t0, t1))
    exposed_coll = subtract_intervals(coll, compute)
    busy_cc = merge_intervals(compute + coll)
    exposed_memcpy = subtract_intervals(memcpy, busy_cc)
    busy = merge_intervals(busy_cc + memcpy)
    gaps = subtract_intervals([(t0, t1)], busy)
    host_busy = merge_intervals(
        _clip([(e["ts"], e["ts"] + e["dur"]) for e in host], t0, t1))
    host_iv = intersect_intervals(gaps, host_busy)
    idle_iv = subtract_intervals(gaps, host_iv)

    window_us = t1 - t0
    us = 1e-6

    def lane(ivs):
        return [[round((a - t0) * us, 9), round((b - a) * us, 9)]
                for a, b in ivs[:_MAX_LANE_INTERVALS]]

    secs = {
        "compute_s": _span(compute) * us,
        "collective_s": _span(coll) * us,
        "exposed_collective_s": _span(exposed_coll) * us,
        "memcpy_s": _span(memcpy) * us,
        "exposed_memcpy_s": _span(exposed_memcpy) * us,
        "host_s": _span(host_iv) * us,
        "idle_s": _span(idle_iv) * us,
    }
    w = window_us * us
    fractions = {
        "compute": secs["compute_s"] / w,
        "collective": secs["exposed_collective_s"] / w,
        "memcpy": secs["exposed_memcpy_s"] / w,
        "host": secs["host_s"] / w,
        "idle": secs["idle_s"] / w,
    }
    return dict(
        secs, window_s=w, fractions=fractions,
        overlapped_collective_s=(secs["collective_s"]
                                 - secs["exposed_collective_s"]),
        events=len(ops),
        lanes={"compute": lane(compute), "collective": lane(coll),
               "memcpy": lane(memcpy), "host": lane(host_iv),
               "idle": lane(idle_iv)})


def waterfall(tl, step_flops, peak_flops):
    """The MFU-loss waterfall over one analyzed timeline: peak (1.0)
    → achieved, the gap attributed per bucket. Each non-compute
    bucket's window fraction is directly that fraction of peak lost;
    what remains of the gap happened INSIDE the compute bucket
    (HBM-bound fusions, low-occupancy kernels) and lands in
    ``compute_inefficiency``. Returns None when the FLOP counts are
    unknown (no cost analysis / unknown chip)."""
    if not (tl and step_flops and peak_flops and tl.get("window_s")):
        return None
    achieved = float(step_flops) / float(tl["window_s"]) / \
        float(peak_flops)
    f = tl["fractions"]
    loss = {
        "collective": f["collective"],
        "memcpy": f["memcpy"],
        "host": f["host"],
        "idle": f["idle"],
        "compute_inefficiency": max(0.0, f["compute"] - achieved),
    }
    return {"achieved_mfu": achieved, "loss": loss}


# ---------------------------------------------------------------------------
# gauge publication + readback (heartbeats)
# ---------------------------------------------------------------------------

def record_timeline(tl, registry=None, site="train", waterfall_doc=None):
    """Publish one analyzed timeline as ``timeline_*`` gauges (SET, not
    accumulated — each sample replaces the previous decomposition,
    like the ``profile_fusion_*`` gauges):

    - ``timeline_fraction{site, bucket}`` — the exact partition;
    - ``timeline_seconds{site, bucket}`` — the same in seconds
      (bucket ``collective`` is EXPOSED seconds; the total rides
      ``timeline_collective_total_seconds``);
    - ``timeline_exposed_collective_seconds{site}`` — the headline
      exposed-communication number;
    - ``timeline_window_seconds{site}``;
    - ``timeline_mfu_loss{site, bucket}`` + ``timeline_mfu{site}`` when
      a :func:`waterfall` doc is given.

    Returns the registry."""
    from . import metrics as _metrics
    reg = registry if registry is not None \
        else _metrics.default_registry()
    if tl is None:
        return reg
    frac = reg.gauge(
        "timeline_fraction",
        "step-window fraction per bucket of the newest profiled "
        "step/tick (compute | collective(exposed) | memcpy(exposed) | "
        "host | idle; sums to 1)", labels=("site", "bucket"))
    secs = reg.gauge(
        "timeline_seconds",
        "seconds per bucket over the newest profiled step window "
        "(collective/memcpy are EXPOSED time)",
        labels=("site", "bucket"))
    sec_by_bucket = {
        "compute": tl["compute_s"],
        "collective": tl["exposed_collective_s"],
        "memcpy": tl["exposed_memcpy_s"],
        "host": tl["host_s"], "idle": tl["idle_s"]}
    for bucket in BUCKETS:
        frac.set(tl["fractions"][bucket], site=site, bucket=bucket)
        secs.set(sec_by_bucket[bucket], site=site, bucket=bucket)
    reg.gauge("timeline_exposed_collective_seconds",
              "collective time NOT overlapped with compute in the "
              "newest profiled step — the number DistOpt bucketing "
              "must drive to zero", labels=("site",)).set(
                  tl["exposed_collective_s"], site=site)
    reg.gauge("timeline_collective_total_seconds",
              "TOTAL collective time (overlapped + exposed) in the "
              "newest profiled step", labels=("site",)).set(
                  tl["collective_s"], site=site)
    reg.gauge("timeline_window_seconds",
              "device-active window of the newest profiled step",
              labels=("site",)).set(tl["window_s"], site=site)
    if waterfall_doc:
        reg.gauge("timeline_mfu",
                  "achieved/peak FLOP fraction over the newest "
                  "profiled step's device window",
                  labels=("site",)).set(
                      waterfall_doc["achieved_mfu"], site=site)
        loss = reg.gauge(
            "timeline_mfu_loss",
            "MFU-loss waterfall: fraction of peak lost per bucket "
            "(collective | memcpy | host | idle | "
            "compute_inefficiency)", labels=("site", "bucket"))
        for bucket, v in waterfall_doc["loss"].items():
            loss.set(v, site=site, bucket=bucket)
    return reg


def compact(tl):
    """The ONE compact serialized form of an analyzed timeline —
    rounded bucket fractions + exposed/total collective seconds + the
    window — shared by every emitter (the bench legs' records,
    the ``timeline.sample`` flight-recorder events) so their schemas
    cannot drift. Returns None for None."""
    if not tl:
        return None
    return {
        "fractions": {k: round(v, 4)
                      for k, v in tl["fractions"].items()},
        "exposed_collective_s": round(tl["exposed_collective_s"], 6),
        "collective_total_s": round(tl["collective_s"], 6),
        "window_s": round(tl["window_s"], 6),
    }


def timeline_summary(registry=None, site="train"):
    """The compact per-rank timeline view that rides cluster
    heartbeats: newest bucket fractions + exposed-comm seconds, read
    back off the ``timeline_*`` gauges. None before the first profiled
    sample (the heartbeat then simply omits the field)."""
    from . import metrics as _metrics
    reg = registry if registry is not None \
        else _metrics.default_registry()
    g = reg.get("timeline_fraction")
    if g is None:
        return None
    fractions = {}
    for s in g.to_doc()["series"]:
        labels = s.get("labels") or {}
        if labels.get("site") == site:
            fractions[labels.get("bucket")] = s.get("value")
    if not fractions:
        return None
    out = {"fractions": fractions}
    for key, name in (("exposed_collective_s",
                       "timeline_exposed_collective_seconds"),
                      ("window_s", "timeline_window_seconds")):
        m = reg.get(name)
        if m is not None:
            try:
                out[key] = m.value(site=site)
            except Exception:   # noqa: BLE001 — label-shape drift
                pass
    return out


# ---------------------------------------------------------------------------
# straggler cause classification
# ---------------------------------------------------------------------------

# a bucket must claim at least this fraction of the step window before
# it is blamed for a straggler (below it, "slow compute" is the honest
# default)
CAUSE_THRESHOLD = 0.2
# compile share of step wall-time above which a rank is compile-bound
# (retraces / cold compiles dominating its steps)
COMPILE_BOUND_SHARE = 0.25

CAUSES = ("comm_bound", "data_bound", "compute_bound", "compile_bound")


def classify_cause(fractions, compile_share=None,
                   threshold=CAUSE_THRESHOLD,
                   compile_threshold=COMPILE_BOUND_SHARE):
    """One straggler's cause label from its timeline fractions (and
    compile share of step wall-time):

    - ``compile_bound`` — compiling/retracing ate ≥ ``compile_threshold``
      of its step time (checked FIRST: a retracing rank also looks
      idle on the device timeline);
    - ``comm_bound``   — exposed collectives ≥ ``threshold`` of the
      window and at least as large as the data-stall share;
    - ``data_bound``   — host + idle + exposed memcpy (input pipeline /
      host stalls) ≥ ``threshold``;
    - ``compute_bound`` — everything else: the device is busy doing
      math, just slowly.

    Returns None when there is nothing to judge (no timeline AND no
    compile share) — the aggregation then labels the rank "unknown"."""
    share = float(compile_share or 0.0)
    if share >= compile_threshold:
        return "compile_bound"
    if not fractions:
        return None if not share else "compute_bound"
    comm = float(fractions.get("collective") or 0.0)
    data = float(fractions.get("host") or 0.0) \
        + float(fractions.get("idle") or 0.0) \
        + float(fractions.get("memcpy") or 0.0)
    if comm >= threshold and comm >= data:
        return "comm_bound"
    if data >= threshold:
        return "data_bound"
    return "compute_bound"


__all__ = ["BUCKETS", "CAUSES", "CAUSE_THRESHOLD",
           "COMPILE_BOUND_SHARE", "classify_op", "merge_intervals",
           "subtract_intervals", "intersect_intervals", "device_ops",
           "host_extent", "analyze",
           "waterfall", "record_timeline", "compact",
           "timeline_summary", "classify_cause"]
