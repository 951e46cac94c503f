"""Nested trace spans on two clocks, and the crash flight recorder.

Spans are the narrative counterpart of the metrics registry: where a
histogram says "step time p50 is 42 ms", the span stream says "step 317
took 1.9 s, and inside it checkpoint.save took 1.7 s". Each span is one
JSON record::

    {"kind": "span", "name": "step", "ts": <end, epoch s>,
     "ts_start": <start, epoch s>, "dur_s": 0.042,
     "parent": "run", "rank": 0, "step": 317, ...}

- **Attribution** (run id, rank, step) comes from two places: explicit
  keyword attrs on the span, and an ambient :func:`context` carried in a
  ``contextvars.ContextVar`` — so two in-process ranks (threaded tests,
  the in-process cluster suite) stamp their own rank on every record
  even though they share the process-global recorder, and the trainer's
  watchdog worker (which copies its caller's context) inherits it.
- **Nesting** rides the same contextvar mechanism: a span records the
  name of the innermost enclosing span as ``parent``.
- **Two clocks.** Besides its wall-clock record, every span enters a
  ``jax.profiler.TraceAnnotation`` of its own name. With no profiler
  session that is one enabled-check; under ``jax.profiler.trace`` the
  span appears on the host plane (``/host:CPU``) of the trace, on the
  device planes' clock, so a gap in the device's op line can be laid
  against what the host was inside.
- **Phases** (:meth:`span.phase`) split an open span without records of
  their own: each is an annotation ``<span>.<phase>`` and a sum under
  the span's ``phases`` — a 25 ticks/s serving engine names its tick's
  parts without evicting the ring any faster.
- **The flight recorder** is a bounded ring (``deque(maxlen=...)``) of
  the most recent records. It costs one append per span — nothing is
  written anywhere until :meth:`FlightRecorder.dump` is called, which
  the resilient trainer does on every ABNORMAL exit path (preemption,
  divergence, watchdog kill, membership loss, rollback), writing
  ``telemetry/blackbox-<rank>.jsonl``: a dump header naming the reason,
  the ring contents (the last N seconds of spans), and a final metrics
  snapshot. A post-mortem then shows what the job was doing when it
  died, not just an exit code.
- Optionally a live JSONL sink (:meth:`FlightRecorder.attach_jsonl`)
  mirrors every record to disk as it happens — what
  ``examples/train_cnn.py --telemetry`` turns on.

Everything here is host-side: stdlib plus ``jax.profiler``'s
annotation class, bound on the first span (a process whose jax cannot
be imported keeps the wall-clock half). Span cost is a couple of
``perf_counter`` calls, the annotation's enabled-check and a dict build
(~µs); nothing is traced or dispatched, so the compiled step's
``n_traces`` pin is untouched.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import threading
import time
from collections import deque

# ambient attrs merged into every record (rank, run id); per-context so
# in-process multi-rank tests attribute correctly
_CTX = contextvars.ContextVar("singa_tpu_span_ctx", default=None)
# innermost-enclosing-span name, for the ``parent`` field
_STACK = contextvars.ContextVar("singa_tpu_span_stack", default=())

# spans currently INSIDE their ``with`` body, keyed by object id: a
# blackbox written while the process is dying must show what it was
# inside (the hung step, the restore that never returned), not only
# what already finished — FlightRecorder.dump appends these as
# ``span_open`` records
_OPEN_LOCK = threading.Lock()
_OPEN = {}

DEFAULT_CAPACITY = 1024

# jax.profiler.TraceAnnotation, bound by the first span (None: not tried
# yet; False: this process has no jax to import)
_ANNOTATION = None


def _annotate(name):
    """An entered profiler annotation called ``name``, or None."""
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except ImportError:
            _ANNOTATION = False
    if not _ANNOTATION:
        return None
    annotation = _ANNOTATION(name)
    annotation.__enter__()
    return annotation


@contextlib.contextmanager
def context(**attrs):
    """Scope ambient attribution: every record made inside the ``with``
    (in this thread/context, workers that copy it included) carries
    ``attrs``. Nests by merging."""
    merged = dict(_CTX.get() or {})
    merged.update(attrs)
    token = _CTX.set(merged)
    try:
        yield
    finally:
        _CTX.reset(token)


class FlightRecorder:
    """Bounded in-memory ring of telemetry records + optional live
    JSONL sink (see module docstring)."""

    def __init__(self, capacity=DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._sink_lock = threading.Lock()  # serializes sink I/O only
        self._ring = deque(maxlen=int(capacity))
        self._jsonl = None
        self._jsonl_path = None
        # records the bounded ring pushed out (oldest-first): a
        # beheaded blackbox/trace must SAY it is partial, not read as
        # "nothing else happened" — dump() stamps this into its
        # header, and a process-wide counter tracks it
        self._evicted = 0
        self._evict_counter = None      # lazy metrics handle

    @property
    def dropped_records(self):
        """Ring evictions since this recorder was created — how many
        records any dump/trace built from it is missing."""
        with self._lock:
            return self._evicted

    def _count_eviction(self, n=1):
        # lazy get-or-create OUTSIDE the ring lock; metrics is a lazy
        # import here (it never imports spans, but keep the edge soft)
        c = self._evict_counter
        if c is None:
            try:
                from . import metrics as _metrics
                c = self._evict_counter = \
                    _metrics.default_registry().counter(
                        "recorder_evicted_total",
                        "flight-recorder ring records pushed out by "
                        "newer ones (dumps built after evictions are "
                        "partial and say so)")
            except Exception:   # noqa: BLE001 — telemetry of telemetry
                return
        try:
            c.inc(n)
        except Exception:       # noqa: BLE001
            pass

    def record(self, rec):
        evicted = False
        with self._lock:
            if self._ring.maxlen is not None and \
                    len(self._ring) == self._ring.maxlen:
                self._evicted += 1
                evicted = True
            self._ring.append(rec)
        if evicted:
            self._count_eviction()
        if self._jsonl is not None:
            # serialize + write OUTSIDE the ring lock: a slow disk may
            # stall sink writers, never every span-recording thread
            with self._sink_lock:
                try:
                    if self._jsonl is not None:
                        self._jsonl.write(json.dumps(rec) + "\n")
                except (OSError, ValueError, TypeError):
                    # a full disk or closed sink must never take down
                    # training; the ring still holds the record
                    pass

    def records(self):
        with self._lock:
            return list(self._ring)

    def clear(self):
        with self._lock:
            self._ring.clear()

    # -- live JSONL sink ---------------------------------------------------
    def attach_jsonl(self, path):
        """Mirror every record to ``path`` as it is made (line-buffered
        append). Returns the absolute path."""
        path = os.path.abspath(str(path))
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with self._sink_lock:
            if self._jsonl is not None:
                self._jsonl.close()
            self._jsonl = open(path, "a", buffering=1)
            self._jsonl_path = path
        return path

    def detach_jsonl(self):
        with self._sink_lock:
            if self._jsonl is not None:
                self._jsonl.close()
            self._jsonl = None
            self._jsonl_path = None

    @property
    def jsonl_path(self):
        return self._jsonl_path

    # -- the blackbox dump -------------------------------------------------
    def dump(self, path, reason, rank=None, step=None, extra=None,
             registry=None):
        """Write the blackbox: header (reason/rank/step/extra), the ring
        contents, then a final metrics snapshot. Atomic (tmp + rename)
        and OVERWRITING — the newest incident is the one the post-mortem
        wants, and a half-written dump must never pass for a whole one.
        Returns the absolute path."""
        from . import metrics as _metrics
        path = os.path.abspath(str(path))
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with self._lock:
            dropped = self._evicted
            capacity = self._ring.maxlen
        header = {"kind": "dump", "ts": time.time(),
                  "reason": str(reason),
                  # loud partiality: a ring that evicted is a beheaded
                  # blackbox — the post-mortem must know the N records
                  # before this window are gone, not conclude they
                  # never happened
                  "dropped_records": dropped,
                  "ring_capacity": capacity}
        if rank is not None:
            header["rank"] = rank
        if step is not None:
            header["step"] = step
        if extra:
            header["extra"] = extra
        reg = registry if registry is not None \
            else _metrics.default_registry()
        try:
            snap = reg.snapshot()
        except Exception:       # the spans must land even if metrics fail
            snap = None
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(json.dumps(header) + "\n")
            for rec in self.records():
                f.write(json.dumps(rec) + "\n")
            # spans still open at dump time (the hung step, the restore
            # that never returned): without these the blackbox shows
            # everything EXCEPT what the process died inside
            for rec in open_spans():
                try:
                    f.write(json.dumps(rec, default=str) + "\n")
                except (TypeError, ValueError):
                    continue
            if snap is not None:
                f.write(json.dumps({"kind": "metrics",
                                    "snapshot": snap}) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path


# the process-wide default recorder (the trainer, the span context
# manager, and the --telemetry example all share it)
_RECORDER = FlightRecorder()


def recorder():
    return _RECORDER


def configure(capacity=None, jsonl_path=None):
    """Adjust the default recorder: ring capacity and/or a live JSONL
    sink path. Returns the recorder."""
    if capacity is not None:
        with _RECORDER._lock:
            before = len(_RECORDER._ring)
            _RECORDER._ring = deque(_RECORDER._ring,
                                    maxlen=int(capacity))
            # shrinking below the current length drops the OLDEST
            # records — counted like any other eviction (header AND
            # metrics counter, so the two can never disagree)
            dropped = max(0, before - len(_RECORDER._ring))
            _RECORDER._evicted += dropped
        if dropped:
            _RECORDER._count_eviction(dropped)
    if jsonl_path is not None:
        _RECORDER.attach_jsonl(jsonl_path)
    return _RECORDER


class span:
    """Context manager recording one nested wall-clock span::

        with span("checkpoint.save", step=42):
            mgr.save(...)

    On exit a record lands in the default recorder, stamped with the
    ambient :func:`context` attrs, the enclosing span's name, and — when
    the body raised — the exception type under ``error``. ``attrs`` may
    be added to until then. Under a profiler session the span is also a
    host event of the trace, under its name."""

    __slots__ = ("name", "attrs", "_t0", "_token", "_wall0", "_ctx",
                 "_annotation", "_phases")

    def __init__(self, name, **attrs):
        self.name = name
        self.attrs = attrs
        self._phases = None

    def phase(self, name):
        """Context manager for one part of this (open) span: the
        profiler annotation ``<span name>.<name>``, and its elapsed
        time added to the span record's ``phases[name]`` (summed when
        entered more than once). It makes no record of its own."""
        return _Phase(self, name)

    def __enter__(self):
        self._token = _STACK.set(_STACK.get() + (self.name,))
        self._wall0 = time.time()
        self._ctx = _CTX.get()
        with _OPEN_LOCK:
            _OPEN[id(self)] = self
        self._annotation = _annotate(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        with _OPEN_LOCK:
            _OPEN.pop(id(self), None)
        stack = _STACK.get()
        _STACK.reset(self._token)
        rec = {"kind": "span", "name": self.name, "ts": time.time(),
               "ts_start": self._wall0, "dur_s": dur}
        if len(stack) > 1:
            rec["parent"] = stack[-2]
        ctx = _CTX.get()
        if ctx:
            rec.update(ctx)
        if self.attrs:
            rec.update(self.attrs)
        if self._phases:
            rec["phases"] = self._phases
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        _RECORDER.record(rec)
        return False


class _Phase:
    """One entry of :meth:`span.phase` (see there)."""

    __slots__ = ("_span", "_name", "_annotation", "_t0")

    def __init__(self, owner, name):
        self._span = owner
        self._name = name

    def __enter__(self):
        self._annotation = _annotate(f"{self._span.name}.{self._name}")
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        phases = self._span._phases
        if phases is None:
            phases = self._span._phases = {}
        phases[self._name] = phases.get(self._name, 0.0) + dur
        return False


class annotation:
    """The profiler annotation ``name`` and nothing else: no record and
    no clock read. For a wait that has to show on the trace's clock
    without filling the ring (the serve loop's ``serve.idle``: an idle
    engine would otherwise evict every tick record)."""

    __slots__ = ("_name", "_annotation")

    def __init__(self, name):
        self._name = name

    def __enter__(self):
        self._annotation = _annotate(self._name)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        return False


def event(name, **attrs):
    """Record a point-in-time event (no duration) — rollbacks, loss-
    scale backoffs, quarantines."""
    rec = {"kind": "event", "name": name, "ts": time.time()}
    ctx = _CTX.get()
    if ctx:
        rec.update(ctx)
    if attrs:
        rec.update(attrs)
    _RECORDER.record(rec)


def open_spans(now=None):
    """``span_open`` records for every span currently inside its
    ``with`` body (any thread), oldest first: name, start timestamp,
    age, and the attribution it was entered under. What a post-mortem
    reads to learn what the process was INSIDE when it died."""
    now = now if now is not None else time.time()
    with _OPEN_LOCK:
        items = list(_OPEN.values())
    out = []
    for s in items:
        rec = {"kind": "span_open", "name": s.name, "ts": now,
               "ts_start": s._wall0,
               "age_s": max(0.0, now - s._wall0)}
        if s._ctx:
            rec.update(s._ctx)
        if s.attrs:
            rec.update(s.attrs)
        out.append(rec)
    out.sort(key=lambda r: r["ts_start"])
    return out


__all__ = ["FlightRecorder", "context", "span", "annotation", "event",
           "open_spans", "recorder", "configure", "DEFAULT_CAPACITY"]
