"""Process-wide metrics registry: counters, gauges, histograms.

The reference scheduler prints MEASURED per-node accounting of the graph
it actually runs (src/core/scheduler/scheduler.cc:240-298); a production
TPU job needs the same honesty one level up — step time, throughput,
MFU, guard skips, checkpoint/restore durations, cluster health — in ONE
place every layer reports through, instead of per-module print
statements that scroll away.

Design constraints (why this is not a prometheus_client dependency):

- **Host-side only, never inside jit.** Every operation here is a dict
  update under a lock — a few microseconds. Nothing in this module may
  import jax or touch device values; callers hand in plain floats they
  already had (the retrace-guard CI pin ``n_traces == 1`` stays the
  step-path invariant).
- **Snapshot-first.** ``MetricsRegistry.snapshot()`` is the canonical
  serialized form (a JSON-able dict, schema ``singa-tpu-metrics/1``);
  the Prometheus text rendering and the CLI/HTTP exporters
  (:mod:`.export`, ``tools/metrics_dump.py``) all work from snapshots,
  so a metrics file written at the end of a run is exactly as
  exportable as a live registry.
- **Get-or-create.** ``registry.counter(name)`` returns the existing
  series on repeat calls (kind-checked), so instrumented layers never
  need to coordinate creation order.

Usage::

    from singa_tpu.observability import metrics
    reg = metrics.default_registry()
    reg.counter("train_steps_total", "completed training steps").inc()
    reg.histogram("train_step_seconds").observe(dt)
    doc = reg.snapshot()             # JSON-able
    text = reg.to_prometheus()       # exposition text
"""

from __future__ import annotations

import math
import os
import threading
import time

SNAPSHOT_SCHEMA = "singa-tpu-metrics/1"

# process start, as close as telemetry can observe it (this module is
# imported by every instrumented layer's first import) — the build
# stamp's "when did this process come up"
_PROCESS_START = time.time()

# Default histogram buckets, tuned for wall-clock seconds spanning a
# sub-millisecond metric op to a minutes-long restore (the upper +inf
# bucket is implicit).
DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                   0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                   120.0, 300.0)

# Published peaks of one chip, keyed by the EXACT ``device_kind`` string
# jax reports for it — the denominators of MFU and roofline shares. One
# table for the benchmark, chip_smoke.py and the trainer's train_mfu
# gauge. A row is added only with the kind string read off that chip and
# the source of its numbers; a TPU that is not here is an error, never a
# neighbouring generation's peak.
DEVICE_PEAKS = {
    # kind as printed by a v5e chip (chip_smoke.py's device phase)
    "TPU v5 lite": {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 819 GB/s HBM (as quoted in /opt/skills/guides/"
                  "on-chip-measurement/SKILL.md section 3)"},
}


def device_peaks(jax_device):
    """The :data:`DEVICE_PEAKS` row of a jax device; None for the CPU
    backend (and for no device at all), where no peak is meaningful.
    Any other device whose kind the table does not know raises."""
    if jax_device is None or jax_device.platform == "cpu":
        return None
    kind = jax_device.device_kind
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {kind!r} (platform "
            f"{jax_device.platform!r}): add a row with its source to "
            "singa_tpu.observability.metrics.DEVICE_PEAKS "
            f"(known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[kind]


def device_peak_flops(jax_device):
    """Peak dense bf16 matmul FLOP/s of a jax device (the MFU
    denominator), by the rule of :func:`device_peaks`."""
    row = device_peaks(jax_device)
    return row["bf16_flops"] if row else None


# resolved once per process (subprocess git call), then cached
_BUILD_STAMP = None


def build_stamp():
    """The build/deploy identity stamped into every snapshot (and so
    into /metrics.json, heartbeat summaries, and blackbox dumps):
    ``{"git": <commit or None>, "start_ts": <process start, epoch s>,
    "pid": ..., "host": ...}`` — what lets a fleet dashboard correlate
    a perf shift with a deploy instead of guessing. ``git`` honors a
    ``SINGA_TPU_BUILD_GIT`` env override (containers deployed without
    a .git directory stamp their image tag there); otherwise one
    cached ``git rev-parse`` of the installed package's tree, None
    when neither exists."""
    global _BUILD_STAMP
    if _BUILD_STAMP is None:
        import socket
        git = os.environ.get("SINGA_TPU_BUILD_GIT") or None
        if git is None:
            try:
                import subprocess
                here = os.path.abspath(__file__)
                pkg_dir = os.path.dirname(here)
                # the repo git walks up to must actually TRACK this
                # package: a venv's site-packages nested inside some
                # unrelated application repo would otherwise stamp
                # that app's HEAD as the library build — worse than
                # the honest None
                tracked = subprocess.run(
                    ["git", "ls-files", "--error-unmatch",
                     os.path.basename(here)],
                    capture_output=True, text=True, timeout=5,
                    cwd=pkg_dir)
                if tracked.returncode == 0:
                    proc = subprocess.run(
                        ["git", "rev-parse", "--short", "HEAD"],
                        capture_output=True, text=True, timeout=5,
                        cwd=pkg_dir)
                    if proc.returncode == 0:
                        git = proc.stdout.strip() or None
            except Exception:   # noqa: BLE001 — stamp is best-effort
                git = None
        try:
            host = socket.gethostname()
        except Exception:       # noqa: BLE001
            host = None
        _BUILD_STAMP = {"git": git, "start_ts": _PROCESS_START,
                        "pid": os.getpid(), "host": host}
    return dict(_BUILD_STAMP)


def _label_key(label_names, labels):
    if set(labels) != set(label_names):
        raise ValueError(
            f"metric labels {sorted(labels)} do not match the declared "
            f"label names {sorted(label_names)}")
    return tuple(str(labels[n]) for n in label_names)


class _Metric:
    """One named metric: a family of series keyed by label values."""

    kind = "untyped"

    def __init__(self, name, help="", label_names=(), lock=None):
        self.name = name
        self.help = help
        self.label_names = tuple(label_names)
        self._series = {}
        # the registry's lock is shared: one lock bounds the whole
        # snapshot, so a snapshot is internally consistent
        self._lock = lock if lock is not None else threading.Lock()

    def _slot(self, labels):
        key = _label_key(self.label_names, labels)
        with self._lock:
            slot = self._series.get(key)
            if slot is None:
                slot = self._new_slot()
                self._series[key] = slot
            return slot

    def _new_slot(self):
        raise NotImplementedError

    def _series_doc(self, key, slot):
        raise NotImplementedError

    def to_doc(self):
        with self._lock:
            series = [dict(self._series_doc(k, s),
                           labels=dict(zip(self.label_names, k)))
                      for k, s in sorted(self._series.items())]
        return {"name": self.name, "kind": self.kind, "help": self.help,
                "labels": list(self.label_names), "series": series}


class Counter(_Metric):
    """Monotonically increasing count (resets only with the process)."""

    kind = "counter"

    def _new_slot(self):
        return [0.0]

    def _series_doc(self, key, slot):
        return {"value": slot[0]}

    def inc(self, amount=1, **labels):
        if amount < 0:
            raise ValueError("counters only go up")
        slot = self._slot(labels)
        with self._lock:
            slot[0] += amount

    def value(self, **labels):
        slot = self._slot(labels)
        with self._lock:
            return slot[0]

    def total(self):
        """Sum over every label combination (the heartbeat summaries
        want one number per rank, not a breakdown)."""
        with self._lock:
            return sum(s[0] for s in self._series.values())


class Gauge(_Metric):
    """A value that goes up and down (loss scale, straggler count). A
    series given a function (:meth:`set_function`) takes its value from
    it whenever the gauge is read."""

    kind = "gauge"

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._functions = {}

    def _new_slot(self):
        return [0.0]

    def _series_doc(self, key, slot):
        return {"value": slot[0]}

    def set(self, value, **labels):
        slot = self._slot(labels)
        with self._lock:
            self._functions.pop(_label_key(self.label_names, labels), None)
            slot[0] = float(value)

    def set_function(self, fn, **labels):
        """The series' value is ``fn()``, called each time the gauge is
        read (outside the registry's lock): a value that lives elsewhere,
        such as on a device, is fetched only when someone asks."""
        self._slot(labels)
        with self._lock:
            self._functions[_label_key(self.label_names, labels)] = fn

    def _refresh(self):
        with self._lock:
            functions = list(self._functions.items())
        values = {key: float(fn()) for key, fn in functions}
        with self._lock:
            for key, value in values.items():
                self._series[key][0] = value

    def to_doc(self):
        self._refresh()
        return super().to_doc()

    def inc(self, amount=1, **labels):
        slot = self._slot(labels)
        with self._lock:
            slot[0] += amount

    def dec(self, amount=1, **labels):
        self.inc(-amount, **labels)

    def value(self, **labels):
        slot = self._slot(labels)
        fn = self._functions.get(_label_key(self.label_names, labels))
        if fn is not None:
            return float(fn())
        with self._lock:
            return slot[0]


class Histogram(_Metric):
    """Cumulative-bucket histogram with exact min/max/sum/count riding
    along (the heartbeat summaries and the fleet aggregation need real
    extrema, not bucket approximations)."""

    kind = "histogram"

    def __init__(self, name, help="", label_names=(), lock=None,
                 buckets=DEFAULT_BUCKETS):
        super().__init__(name, help, label_names, lock)
        self.buckets = tuple(sorted(float(b) for b in buckets))

    def _new_slot(self):
        return {"counts": [0] * (len(self.buckets) + 1), "sum": 0.0,
                "count": 0, "min": math.inf, "max": -math.inf}

    def _series_doc(self, key, slot):
        # lazy import: export renders snapshots (imports this module);
        # the quantile math lives beside the other exposition helpers
        from .export import series_quantiles
        cum, acc = [], 0
        for le, c in zip(self.buckets, slot["counts"]):
            acc += c
            cum.append([le, acc])
        cum.append(["+Inf", slot["count"]])
        doc = {"count": slot["count"], "sum": slot["sum"],
               "min": None if slot["count"] == 0 else slot["min"],
               "max": None if slot["count"] == 0 else slot["max"],
               "buckets": cum}
        doc["quantiles"] = series_quantiles(doc)
        return doc

    def observe(self, value, **labels):
        value = float(value)
        slot = self._slot(labels)
        # linear scan beats bisect at these bucket counts and keeps the
        # hot path allocation-free
        idx = len(self.buckets)
        for i, b in enumerate(self.buckets):
            if value <= b:
                idx = i
                break
        with self._lock:
            slot["counts"][idx] += 1
            slot["sum"] += value
            slot["count"] += 1
            if value < slot["min"]:
                slot["min"] = value
            if value > slot["max"]:
                slot["max"] = value

    def summary(self, **labels):
        """{count, sum, min, max, mean} for one series (all None-safe:
        an empty histogram summarizes to count 0 and None extrema)."""
        slot = self._slot(labels)
        with self._lock:
            n = slot["count"]
            return {"count": n, "sum": slot["sum"],
                    "min": None if n == 0 else slot["min"],
                    "max": None if n == 0 else slot["max"],
                    "mean": None if n == 0 else slot["sum"] / n}


class MetricsRegistry:
    """Named metrics with get-or-create semantics (see module doc)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = {}

    def _get_or_create(self, cls, name, help, labels, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help, labels, lock=self._lock, **kw)
                self._metrics[name] = m
                return m
        if not isinstance(m, cls):
            raise TypeError(
                f"metric {name!r} already registered as {m.kind}, "
                f"requested {cls.kind}")
        if tuple(labels) != m.label_names:
            raise ValueError(
                f"metric {name!r} already registered with labels "
                f"{m.label_names}, requested {tuple(labels)}")
        return m

    def counter(self, name, help="", labels=()):
        return self._get_or_create(Counter, name, help, labels)

    def gauge(self, name, help="", labels=()):
        return self._get_or_create(Gauge, name, help, labels)

    def histogram(self, name, help="", labels=(),
                  buckets=DEFAULT_BUCKETS):
        return self._get_or_create(Histogram, name, help, labels,
                                   buckets=buckets)

    def get(self, name):
        with self._lock:
            return self._metrics.get(name)

    def reset(self):
        """Drop every metric — tests only; live code never resets (a
        counter that restarts mid-scrape reads as a rollback)."""
        with self._lock:
            self._metrics = {}

    def snapshot(self):
        """The canonical JSON-able serialized form (schema
        ``singa-tpu-metrics/1``) every exporter consumes."""
        with self._lock:
            metrics = list(self._metrics.values())
        return {"schema": SNAPSHOT_SCHEMA, "ts": time.time(),
                "build": build_stamp(),
                "metrics": [m.to_doc() for m in metrics]}

    def to_prometheus(self):
        from .export import render_prometheus
        return render_prometheus(self.snapshot())


# The process-wide default registry every instrumented layer reports
# through. Module-level singleton, like logging.root: one fleet-wide
# view needs one process-wide spine.
REGISTRY = MetricsRegistry()


def default_registry():
    return REGISTRY


def heartbeat_summary(registry=None):
    """The compact per-rank summary that rides cluster heartbeats:
    step-time stats from ``train_step_seconds``, this rank's dropped
    corrupt-frame count, the build stamp (git commit + process start —
    so the fleet view can correlate a perf shift with a deploy), and —
    once the sampling profiler has run — the newest step-timeline
    decomposition (``timeline``: bucket fractions + exposed-comm
    seconds) plus the rank's compile share of step wall-time, the two
    inputs of the coordinator's straggler cause labels. A few hundred
    bytes — cheap enough to attach to every beat; None/absent fields
    mean "no data yet"."""
    reg = registry if registry is not None else REGISTRY
    hist = reg.get("train_step_seconds")
    step = hist.summary() if isinstance(hist, Histogram) else None
    if step is not None and step["count"] == 0:
        step = None
    wires = reg.get("cluster_wire_errors_total")
    out = {"step_time": step,
           "wire_errors": int(wires.total())
           if isinstance(wires, Counter) else 0}
    from . import timeline as _timeline   # lazy: timeline imports us
    tl = _timeline.timeline_summary(reg, site="train")
    if tl is not None:
        out["timeline"] = tl
    compile_hist = reg.get("compile_seconds")
    if isinstance(compile_hist, Histogram) and step is not None \
            and step["sum"]:
        compile_sum = sum(float(s.get("sum") or 0.0) for s in
                          compile_hist.to_doc()["series"])
        if compile_sum:
            out["compile_share"] = min(
                1.0, compile_sum / float(step["sum"]))
    # serving KV pool pressure (paged engines only): the fleet view's
    # early-warning that a replica is running out of blocks — queue
    # depth rises AFTER the pool saturates, this shows it before
    kv_total = reg.get("kv_blocks_total")
    mesh_model = reg.get("serve_mesh_model")
    if isinstance(kv_total, Gauge) or isinstance(mesh_model, Gauge):
        kv = {}
        if isinstance(kv_total, Gauge):
            kv["blocks_total"] = kv_total.value()
        in_use = reg.get("kv_blocks_in_use")
        if isinstance(in_use, Gauge):
            kv["blocks_in_use"] = in_use.value()
        cached = reg.get("kv_blocks_cached")
        if isinstance(cached, Gauge):
            kv["blocks_cached"] = cached.value()
        hits = reg.get("prefix_cache_hits_total")
        if isinstance(hits, Counter):
            kv["prefix_cache_hits"] = int(hits.total())
        ratio = reg.get("speculative_accepted_ratio")
        if isinstance(ratio, Gauge):
            kv["speculative_accepted_ratio"] = ratio.value()
        # host-RAM spill tier (evicted cached prefixes parked in host
        # memory): restore-vs-spill movement shows whether the tier is
        # saving prefills or just churning
        for key, name in (("spills", "serve_kv_spill_total"),
                          ("restores", "serve_kv_restore_total")):
            c = reg.get(name)
            if isinstance(c, Counter):
                kv[key] = int(c.total())
        spill_b = reg.get("serve_kv_spill_bytes")
        if isinstance(spill_b, Gauge):
            kv["spill_bytes"] = spill_b.value()
        # sharded engines: the mesh shape + what ONE chip holds — the
        # fleet view's pool-pressure numbers must be per-device, not
        # the global logical pool (a paged pool is replicated across
        # 'batch' with a heads/model slice per chip; a ring shards its
        # slots over 'batch' too)
        if isinstance(mesh_model, Gauge):
            mesh_batch = reg.get("serve_mesh_batch")
            kv["mesh"] = {
                "batch": mesh_batch.value()
                if isinstance(mesh_batch, Gauge) else None,
                "model": mesh_model.value()}
            per_dev = reg.get("serve_kv_per_device_bytes")
            if isinstance(per_dev, Gauge):
                kv["per_device_bytes"] = per_dev.value()
        out["serving_kv"] = kv
    # live-KV handoff (preemption-deadline drains): migrated-out/-in,
    # typed refusals, recompute fallbacks, checkpoint cadence — the
    # fleet-view evidence a preempted replica's work moved instead of
    # being recomputed
    ho_keys = (("out", "serve_handoff_out_total"),
               ("in", "serve_handoff_in_total"),
               ("refused", "serve_handoff_refused_total"),
               ("fallback", "serve_handoff_fallback_total"),
               ("kv_checkpoints", "serve_kv_checkpoint_total"),
               ("prefill_tokens", "serve_prefill_tokens_total"))
    if any(isinstance(reg.get(n), Counter)
           for _k, n in ho_keys[:4]):
        ho = {}
        for key, name in ho_keys:
            c = reg.get(name)
            if isinstance(c, Counter):
                ho[key] = int(c.total())
        out["serving_handoff"] = ho
    # fleet resilience (processes running a FleetRouter): breaker /
    # re-dispatch / shed movement — the coordinator-view evidence that
    # a replica died and the fleet absorbed it
    fleet_sub = reg.get("serve_fleet_submitted_total")
    if isinstance(fleet_sub, Counter):
        fl = {"submitted": int(fleet_sub.total())}
        for key, name in (("failovers", "serve_fleet_failover_total"),
                          ("redispatches",
                           "serve_fleet_redispatch_total"),
                          ("sheds", "serve_fleet_shed_total"),
                          ("rejected", "serve_fleet_rejected_total"),
                          ("breaker_opens",
                           "serve_fleet_breaker_open_total"),
                          ("handoffs", "serve_fleet_handoff_total"),
                          ("resumes", "serve_fleet_resume_total")):
            c = reg.get(name)
            if isinstance(c, Counter):
                fl[key] = int(c.total())
        breaker = reg.get("serve_fleet_breaker_state")
        if isinstance(breaker, Gauge):
            series = breaker.to_doc().get("series", [])
            fl["breakers_open"] = sum(
                1 for s in series if s.get("value") == 2)
            fl["breakers_half_open"] = sum(
                1 for s in series if s.get("value") == 1)
        stranded = reg.get("serve_stranded_requests_total")
        if isinstance(stranded, Counter):
            fl["stranded"] = int(stranded.total())
        out["serving_fleet"] = fl
    # disaggregated prefill/decode pools: this replica's role tag
    # (engine-published gauge) plus, on router processes, per-pool
    # depth, transfer movement, and the affinity hit ratio — the
    # fleet-view evidence that prefix routing is actually keeping
    # decode-side caches warm
    role_g = reg.get("serve_pool_role")
    if isinstance(role_g, Gauge):
        out["pool_role"] = {1: "prefill", 2: "decode"}.get(
            int(role_g.value() or 0), "colocated")
    pool_xfer = reg.get("serve_pool_transfer_total")
    if isinstance(pool_xfer, Counter):
        pl = {"transferred": int(pool_xfer.total())}
        for key, name in (("retries", "serve_pool_transfer_retry_total"),
                          ("colocate_fallback",
                           "serve_pool_colocate_fallback_total"),
                          ("dup_discarded",
                           "serve_pool_dup_discarded_total"),
                          ("brownouts", "serve_pool_brownout_total"),
                          ("saturated", "serve_pool_saturated_total")):
            c = reg.get(name)
            if isinstance(c, Counter):
                pl[key] = int(c.total())
        hits_c = reg.get("serve_pool_affinity_hit_total")
        miss_c = reg.get("serve_pool_affinity_miss_total")
        h = int(hits_c.total()) if isinstance(hits_c, Counter) else 0
        ms = int(miss_c.total()) if isinstance(miss_c, Counter) else 0
        pl["affinity"] = {"hits": h, "misses": ms,
                          "hit_ratio": (h / (h + ms)) if h + ms
                          else 0.0}
        depth = reg.get("serve_pool_depth")
        if isinstance(depth, Gauge):
            pl["depth"] = {s["labels"].get("pool"): s.get("value")
                           for s in depth.to_doc().get("series", [])}
        out["serving_pools"] = pl
    # autoscaler decisions (processes running serving.autoscaler):
    # population movement + the flap-damping evidence — a fleet view
    # where replace_total climbs while quarantine stays 0 is a crash
    # loop the damping never caught
    pop = reg.get("autoscale_population")
    if isinstance(pop, Gauge):
        asc = {"population": pop.value()}
        for key, name in (("up", "autoscale_up_total"),
                          ("down", "autoscale_down_total"),
                          ("replace", "autoscale_replace_total"),
                          ("quarantine", "autoscale_quarantine_total"),
                          ("warm_refused",
                           "autoscale_warm_refused_total"),
                          ("spawn_failed",
                           "autoscale_spawn_failed_total")):
            c = reg.get(name)
            if isinstance(c, Counter):
                asc[key] = int(c.total())
        for key, name in (("pending_spawns",
                           "autoscale_pending_spawns"),
                          ("rung", "autoscale_rung"),
                          ("quarantined", "autoscale_quarantined")):
            g = reg.get(name)
            if isinstance(g, Gauge):
                asc[key] = g.value()
        spawn = reg.get("autoscale_spawn_seconds")
        if isinstance(spawn, Histogram):
            series = spawn.to_doc().get("series") or []
            if series and series[0]["count"]:
                q = series[0].get("quantiles") or {}
                asc["spawn_p50_s"] = q.get("p50")
                asc["spawn_p99_s"] = q.get("p99")
        out["autoscale"] = asc
    stamp = build_stamp()
    out["build"] = {"git": stamp["git"], "start_ts": stamp["start_ts"]}
    return out


# a rank whose mean step time exceeds this multiple of the fleet's
# count-weighted mean is named a straggler in the aggregated view
STRAGGLER_FACTOR = 1.5


def aggregate_summaries(summaries, ages=None, stale_after=None):
    """Fold per-rank heartbeat summaries into ONE fleet view — what the
    coordinator publishes in its health report: min/max of the ranks'
    step-time extrema, a count-weighted mean, total steps and wire
    errors, how many ranks have reported anything at all, and — when
    more than one rank reports step times — cross-rank straggler
    attribution: the ranks whose own mean step time sits more than
    :data:`STRAGGLER_FACTOR`× above the fleet mean, so "which host is
    slow" is answerable straight off the heartbeat-carried summaries.

    Each named straggler additionally gets a CAUSE label in
    ``straggler_causes`` (``{rank: comm_bound | data_bound |
    compute_bound | compile_bound | unknown}``), judged from the
    timeline fractions and compile share its own heartbeat carried
    (``observability.timeline.classify_cause``) — "rank 2 is slow"
    becomes "rank 2 is slow because its collectives are exposed".

    ``ages`` (``{rank: seconds since last heartbeat}``) with
    ``stale_after`` marks ranks whose last beat is older than the
    threshold as STALE: their last-known gauges are dead data, not
    current load, so they are EXCLUDED from every aggregate above and
    surfaced separately as ``stale`` (``{rank: age}``) — an
    autoscaler reading this view must never scale on a silent
    replica's frozen numbers."""
    summaries = dict(summaries or {})
    stale = {}
    if ages and stale_after:
        for r in list(summaries):
            age = ages.get(str(r), ages.get(r))
            if age is not None and float(age) > float(stale_after):
                stale[str(r)] = round(float(age), 3)
                summaries.pop(r)
    vals = [s for s in summaries.values() if isinstance(s, dict)]
    agg = {"ranks_reporting": len(vals),
           "wire_errors": sum(int(s.get("wire_errors") or 0)
                              for s in vals)}
    if stale:
        agg["stale"] = stale
    per_rank = {r: s["step_time"] for r, s in summaries.items()
                if isinstance(s, dict)
                and isinstance(s.get("step_time"), dict)
                and s["step_time"].get("count")}
    steps = list(per_rank.values())
    if steps:
        total = sum(int(s["count"]) for s in steps)
        agg["steps"] = total
        agg["step_time_min"] = min(float(s["min"]) for s in steps)
        agg["step_time_max"] = max(float(s["max"]) for s in steps)
        agg["step_time_mean"] = sum(
            float(s["mean"]) * int(s["count"]) for s in steps) / total
        fleet = agg["step_time_mean"]
        agg["step_time_stragglers"] = sorted(
            (r for r, s in per_rank.items()
             if float(s["mean"]) > STRAGGLER_FACTOR * fleet),
            key=str) if len(per_rank) > 1 and fleet > 0 else []
        if agg["step_time_stragglers"]:
            from . import timeline as _timeline   # lazy (imports us)
            causes = {}
            for r in agg["step_time_stragglers"]:
                s = summaries.get(r) or {}
                tl = s.get("timeline") or {}
                cause = _timeline.classify_cause(
                    tl.get("fractions"), s.get("compile_share"))
                causes[str(r)] = cause or "unknown"
            agg["straggler_causes"] = causes
    return agg


__all__ = ["SNAPSHOT_SCHEMA", "DEFAULT_BUCKETS", "DEVICE_PEAKS",
           "STRAGGLER_FACTOR", "device_peaks", "device_peak_flops",
           "build_stamp",
           "Counter", "Gauge", "Histogram", "MetricsRegistry",
           "REGISTRY", "default_registry", "heartbeat_summary",
           "aggregate_summaries"]
