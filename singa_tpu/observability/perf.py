"""Continuous performance observability over the telemetry spine.

PR 6 built the *what happened* layer (metrics registry, spans, the
flight recorder); this module is the *why is it slow / where did the
memory go / why did it retrace* layer the MFU push and the cold-start
work are measured with:

- **HBM telemetry** (:func:`hbm_stats`, :func:`record_hbm`): the one
  shared reader of ``jax_device.memory_stats()`` — normalized dict in,
  ``hbm_*`` gauges out — sampled at training step boundaries and
  serving ticks. :func:`live_array_report` is the OOM post-mortem: a
  bounded ``jax.live_arrays()`` allocation breakdown grouped by
  (shape, dtype), dumped into crash blackboxes.
- **Compile/retrace attribution** (:func:`step_signature`,
  :func:`diff_signatures`, :func:`record_compile`): every trace of a
  compiled step/serving program lands its wall-clock in the
  ``compile_seconds{program}`` histogram and a ``compile``/``retrace``
  flight-recorder event carrying the arg-shape/dtype signature — a
  retrace event NAMES the argument whose signature changed (old vs
  new), so "why did it retrace" is answerable from the blackbox.
- **Sampling step profiler** (:class:`SamplingProfiler`): every Nth
  step runs under the existing ``measure_step_fusions`` machinery
  (``Model.profile_step``), refreshing the ``profile_fusion_*`` gauges
  continuously instead of on demand. Non-sample steps pay one integer
  check; the compiled step never retraces (the profiler wraps the
  already-compiled dispatch).
- **Anomaly sentinel** (:class:`AnomalySentinel`): a rolling (EMA)
  per-rank step-time baseline; a sustained spike fires an attributed
  ``step_anomaly`` event and tells the caller to capture a one-shot
  profile and dump the blackbox. Cross-rank straggler attribution
  rides the heartbeat summaries
  (``metrics.aggregate_summaries -> step_time_stragglers``).

Contract unchanged from PR 6: nothing here imports jax at module
level, everything is host-side (dict updates + ``perf_counter``), and
``compiled_step_info()["n_traces"]`` stays 1 with every feature on —
pinned by ``tests/test_perf_observability.py`` together with a
measured non-sample-step overhead bound.
"""

from __future__ import annotations

import time

import numpy as np

from . import metrics as _metrics
from . import spans as _spans

# memory_stats keys promoted to their own named gauge (the three the
# HBM dashboards and the bench legs read); everything else numeric the
# backend reports lands in the labeled ``hbm_stat_bytes{kind}`` gauge
_HBM_NAMED = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
_HBM_EXTRA = ("bytes_reserved", "largest_alloc_size", "pool_bytes",
              "bytes_reservable_limit")

# devices whose memory_stats() came back unusable — probed once, then
# every later sample is a set lookup (the CPU/emulator fast path on the
# per-step and per-tick call sites)
_HBM_UNAVAILABLE = set()


# ---------------------------------------------------------------------------
# HBM telemetry
# ---------------------------------------------------------------------------

def hbm_stats(jax_device, raise_errors=False):
    """Normalized ``memory_stats()`` of one jax device: the known byte
    counters as ints plus a derived ``peak_gib``, or None when the
    backend has no stats (CPU, emulators) or the read fails.

    ``raise_errors=True`` propagates a FAILING ``memory_stats()`` call
    instead of folding it into None — a diagnostic caller must report
    "the TPU runtime errored: <why>", not the same silence a
    stats-less CPU produces.

    NOTE: ``peak_bytes_in_use`` is a process-lifetime high-water mark —
    within one process it is monotonic across workloads. A precise
    per-model peak needs a fresh process; in-process samples are an
    upper bound."""
    ms = getattr(jax_device, "memory_stats", None)
    if ms is None:
        return None
    try:
        stats = ms()
    except Exception:       # noqa: BLE001 — telemetry is best-effort
        if raise_errors:
            raise
        return None
    if not stats:
        return None
    out = {}
    for k in _HBM_NAMED + _HBM_EXTRA:
        v = stats.get(k)
        if v is not None:
            try:
                out[k] = int(v)
            except (TypeError, ValueError):
                continue
    if not out:
        return None
    if out.get("peak_bytes_in_use"):
        out["peak_gib"] = round(out["peak_bytes_in_use"] / 2**30, 3)
    return out


def record_hbm(jax_device, registry=None, site="train"):
    """Sample one device's HBM stats into gauges — the step-boundary /
    serving-tick call site. Returns the stats dict (or None).

    Gauges: ``hbm_bytes_in_use``, ``hbm_peak_bytes_in_use``,
    ``hbm_bytes_limit`` (labels: ``site`` = ``train``/``serve``/...),
    plus ``hbm_stat_bytes{site, kind}`` for any further counter the
    backend reports. A device without stats is probed ONCE and then
    skipped by a set lookup, so off-accelerator call sites cost
    nothing."""
    if jax_device is None or id(jax_device) in _HBM_UNAVAILABLE:
        return None
    stats = hbm_stats(jax_device)
    if stats is None:
        _HBM_UNAVAILABLE.add(id(jax_device))
        return None
    reg = registry if registry is not None else _metrics.default_registry()
    for k in _HBM_NAMED:
        if k in stats:
            reg.gauge(f"hbm_{k}",
                      f"device memory_stats {k} at the newest sample",
                      labels=("site",)).set(stats[k], site=site)
    extra = reg.gauge("hbm_stat_bytes",
                      "further device memory_stats counters",
                      labels=("site", "kind"))
    for k in _HBM_EXTRA:
        if k in stats:
            extra.set(stats[k], site=site, kind=k)
    return stats


def live_array_report(top=15):
    """Bounded ``jax.live_arrays()`` allocation breakdown — the OOM
    post-mortem the crash blackbox carries: arrays grouped by
    (dtype, shape) with per-group count/bytes, biggest first, plus the
    total. Returns None when jax (or the walk) is unavailable; never
    raises — this runs on paths where the process is already dying."""
    try:
        import jax
        arrs = jax.live_arrays()
    except Exception:       # noqa: BLE001 — post-mortem is best-effort
        return None
    groups = {}
    total = 0
    n = 0
    for a in arrs:
        try:
            shape = tuple(int(d) for d in a.shape)
            dtype = str(a.dtype)
            nbytes = int(np.prod(shape or (1,))) * \
                int(np.dtype(a.dtype).itemsize) if shape is not None else 0
        except Exception:   # noqa: BLE001 — skip exotic leaves
            continue
        n += 1
        total += nbytes
        key = (dtype, shape)
        cnt, byt = groups.get(key, (0, 0))
        groups[key] = (cnt + 1, byt + nbytes)
    rows = sorted(groups.items(), key=lambda kv: -kv[1][1])[:int(top)]
    return {"n_arrays": n, "total_bytes": total,
            "total_gib": round(total / 2**30, 3),
            "top": [{"dtype": d, "shape": list(s), "count": c,
                     "bytes": b}
                    for (d, s), (c, b) in rows]}


def first_jax_device(tree):
    """First jax array's device found in a nested structure (the
    serving engines hold their cache/state, not a Device object).
    Returns None when nothing device-backed is found."""
    stack = [tree]
    seen = 0
    while stack and seen < 256:
        obj = stack.pop()
        seen += 1
        if isinstance(obj, dict):
            stack.extend(obj.values())
            continue
        if isinstance(obj, (list, tuple)):
            stack.extend(obj)
            continue
        devs = getattr(obj, "devices", None)
        if callable(devs):
            try:
                ds = devs()
                if ds:
                    return next(iter(ds))
            except Exception:   # noqa: BLE001 — keep walking
                pass
        d = getattr(obj, "device", None)
        if d is not None and not callable(d):
            return d
    return None


# ---------------------------------------------------------------------------
# compile / retrace attribution
# ---------------------------------------------------------------------------

def step_signature(arrays, names=None):
    """JSON-able shape/dtype signature of one call's traced arguments:
    ``[[label, [dims...], dtype], ...]`` — what the retrace event diffs
    against."""
    sig = []
    for i, a in enumerate(arrays):
        label = names[i] if names is not None and i < len(names) \
            else f"arg{i}"
        sig.append([str(label), [int(d) for d in np.shape(a)],
                    str(getattr(a, "dtype", type(a).__name__))])
    return sig


def diff_signatures(old, new):
    """Structured diff of two :func:`step_signature` lists: one entry
    per argument whose shape or dtype changed (or that appeared/
    vanished), each carrying the old and new ``[shape, dtype]``."""
    changed = []
    old = old or []
    new = new or []
    for i in range(max(len(old), len(new))):
        o = old[i] if i < len(old) else None
        n = new[i] if i < len(new) else None
        if o is not None and n is not None and o[1:] == n[1:]:
            continue
        changed.append({
            "arg": (n or o)[0],
            "old": None if o is None else [o[1], o[2]],
            "new": None if n is None else [n[1], n[2]]})
    return changed


def record_compile(program, seconds, signature, prev_signature=None,
                   registry=None, source="fresh", **attrs):
    """Attribute one trace of a compiled program: observe its wall-time
    in the ``compile_seconds{program, source}`` histogram and leave a
    flight-recorder event — ``compile`` for a first trace (or a
    re-lower with an identical signature), ``retrace`` when the
    signature changed, naming the changed argument(s) old vs new.

    ``source`` labels where the executable came from: ``"fresh"`` (XLA
    compiled it now), ``"cache"`` (served whole from the persistent
    compilation cache — ``singa_tpu.aot.cache.classify`` is the
    judge), or ``"aot"`` (a deserialized exported executable; no trace
    happened at all and ``seconds`` is the verify+load cost). The
    cold-start acceptance gate is "zero ``source="fresh"``
    observations on a warm restart".

    ``seconds`` is the dispatch wall-clock of the call that traced
    (trace + XLA compile + the step's own dispatch — on a first call
    compile dominates). Returns the structured diff (empty/None when
    nothing changed)."""
    reg = registry if registry is not None else _metrics.default_registry()
    reg.histogram(
        "compile_seconds",
        "wall-clock of a dispatch that traced+compiled, by program "
        "and executable source (fresh | cache | aot)",
        labels=("program", "source")).observe(
            float(seconds), program=str(program), source=str(source))
    changed = diff_signatures(prev_signature, signature) \
        if prev_signature is not None else None
    if changed:
        _spans.event("retrace", program=str(program),
                     compile_s=round(float(seconds), 4), source=source,
                     changed=changed, signature=signature, **attrs)
    else:
        _spans.event("compile", program=str(program),
                     compile_s=round(float(seconds), 4), source=source,
                     signature=signature, **attrs)
    return changed


def compile_source_counts(registry=None):
    """{source: observation count} over the ``compile_seconds``
    histogram — the warm-restart gate reads this (zero ``fresh`` on a
    warm path). Empty dict when nothing compiled yet."""
    reg = registry if registry is not None \
        else _metrics.default_registry()
    hist = reg.get("compile_seconds")
    if hist is None:
        return {}
    out = {}
    for series in hist.to_doc()["series"]:
        src = series.get("labels", {}).get("source", "fresh")
        out[src] = out.get(src, 0) + int(series.get("count", 0))
    return out


# ---------------------------------------------------------------------------
# sampling step profiler
# ---------------------------------------------------------------------------

class SamplingProfiler:
    """Every-Nth-step measured per-fusion profiling.

    The trainer asks :meth:`should_sample` per step (one int check on
    non-sample steps); on a sample step it routes the step through
    ``Model.profile_step`` (the existing ``measure_step_fusions``
    machinery — ``n_traces`` untouched, one profiler trace per sample)
    and hands the table to :meth:`record`, which refreshes the
    ``profile_fusion_*`` gauges, counts the sample, observes the
    capture cost, and leaves a ``profile.sample`` event with the top
    fusions. ``every=0`` disables sampling; :meth:`force_next` arms a
    one-shot sample regardless (the anomaly sentinel's capture
    trigger)."""

    def __init__(self, every=0, registry=None):
        self.every = int(every or 0)
        self._force = False
        self.last_timeline = None     # newest analyzed step timeline
        self._reg = registry if registry is not None \
            else _metrics.default_registry()
        self._samples = self._reg.counter(
            "profile_samples_total",
            "sampled profiled steps (sampling profiler + one-shot "
            "anomaly captures)")
        self._capture = self._reg.histogram(
            "profile_capture_seconds",
            "wall-clock of one sampled profiled step (profiler trace "
            "+ parse included — the sampling overhead bound)")
        self._last = self._reg.gauge(
            "profile_last_sample_step",
            "global step of the newest profile sample")

    def should_sample(self, step):
        if self._force:
            return True
        return bool(self.every) and step > 0 and \
            step % self.every == 0

    def force_next(self):
        """Arm a one-shot sample (the sentinel's profile capture)."""
        self._force = True

    def record(self, step, table, capture_s=None, events=None,
               step_flops=None, peak_flops=None, site="train"):
        from .. import profiling as _profiling
        from . import timeline as _timeline
        self._force = False
        self._samples.inc()
        self._last.set(step)
        if capture_s is not None:
            self._capture.observe(capture_s)
        _profiling.record_fusion_metrics(table, registry=self._reg)
        _spans.event("profile.sample", step=step, fusions=len(table),
                     top=_profiling.summarize_table(table, top=3),
                     **({"capture_s": round(capture_s, 4)}
                        if capture_s is not None else {}))
        # the step-timeline decomposition rides the SAME capture (no
        # second trace): bucket the raw events, refresh the timeline_*
        # gauges, and leave a timeline.sample event whose bounded
        # per-bucket lanes the Perfetto exporter renders as extra rows
        if events:
            tl = _timeline.analyze(events)
            if tl is not None:
                wf = _timeline.waterfall(tl, step_flops, peak_flops)
                _timeline.record_timeline(tl, registry=self._reg,
                                          site=site, waterfall_doc=wf)
                self.last_timeline = tl
                _spans.event(
                    "timeline.sample", step=step, site=site,
                    lanes=tl["lanes"], **_timeline.compact(tl),
                    **({"achieved_mfu": round(wf["achieved_mfu"], 4),
                        "mfu_loss": {k: round(v, 4)
                                     for k, v in wf["loss"].items()}}
                       if wf else {}))


# ---------------------------------------------------------------------------
# anomaly sentinel
# ---------------------------------------------------------------------------

class AnomalySentinel:
    """Rolling step-time baseline with sustained-spike detection.

    Feed every completed step's wall-clock to :meth:`observe`; it
    maintains an EMA baseline (spike-clipped, so an incident does not
    teach the baseline to expect incidents) and, after ``warmup``
    samples, fires when ``sustain`` consecutive steps exceed
    ``factor``× the baseline: a ``step_anomaly`` flight-recorder event
    (step, measured, baseline, factor), a ``perf_anomalies_total``
    count, and a True return — the caller's cue to capture a one-shot
    profile and dump the blackbox. A ``cooldown`` keeps one incident
    from firing every step while it lasts."""

    def __init__(self, factor=3.0, sustain=3, warmup=10, alpha=0.2,
                 min_baseline_s=1e-4, cooldown=20, registry=None):
        self.factor = float(factor)
        self.sustain = int(sustain)
        self.warmup = int(warmup)
        self.alpha = float(alpha)
        self.min_baseline_s = float(min_baseline_s)
        self.cooldown = int(cooldown)
        self._ema = None
        self._seen = 0
        self._streak = 0
        self._cool = 0
        reg = registry if registry is not None \
            else _metrics.default_registry()
        self._fired = reg.counter(
            "perf_anomalies_total",
            "sustained step-time spikes the sentinel fired on")
        self._baseline = reg.gauge(
            "perf_step_baseline_seconds",
            "the sentinel's rolling step-time baseline (EMA)")

    def observe(self, step, step_s):
        """Returns True when a sustained spike fires this step."""
        step_s = float(step_s)
        base = self._ema
        fired = False
        floor = max(base or 0.0, self.min_baseline_s)
        spike = (base is not None and self._seen >= self.warmup
                 and step_s > self.factor * floor)
        if spike and self._cool == 0:
            self._streak += 1
            if self._streak >= self.sustain:
                fired = True
                self._streak = 0
                self._cool = self.cooldown
                self._fired.inc()
                _spans.event("step_anomaly", step=step,
                             step_s=round(step_s, 6),
                             baseline_s=round(base, 6),
                             factor=self.factor)
        elif not spike:
            self._streak = 0
        if self._cool:
            self._cool -= 1
        # clip the update so a spike streak drags the baseline up only
        # slowly; a genuine regime change still converges
        clip = step_s if base is None \
            else min(step_s, self.factor * floor)
        self._ema = clip if base is None \
            else (1.0 - self.alpha) * base + self.alpha * clip
        self._seen += 1
        self._baseline.set(self._ema)
        return fired


__all__ = ["hbm_stats", "record_hbm", "live_array_report",
           "first_jax_device", "step_signature", "diff_signatures",
           "record_compile", "compile_source_counts",
           "SamplingProfiler", "AnomalySentinel"]
