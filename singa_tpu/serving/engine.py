"""Continuous-batching inference engines: prefill/decode split, slot
array, exactly-once delivery.

Two engines share one control plane (:class:`_EngineBase`: submit /
background loop / drain / fault handling / SLO metrics / crash
blackbox):

- :class:`ServingEngine` — autoregressive models (transformer LM,
  char-rnn, the sparse-expert LM). TWO fixed-shape compiled programs a
  model, each taking ``(P, state, *host arrays)`` and returning the
  DONATED KV state and, a row, the token its logits put first beside
  the logits themselves (``kv_cache.with_tokens``; a tick reads back
  the tokens, and the ``(rows, V)`` logits only while a request it
  serves samples):

  * **prefill**: a fixed-width batch of padded prompts writes its
    assigned slots' KV rows and returns last-token logits; a ``valid``
    mask covers padding rows, so admitting 1 or B_p requests runs the
    same executable.
  * **decode**: one tick for EVERY slot of the fixed-width slot array;
    finished sequences free their slot mid-batch and new requests
    refill it, so the program NEVER retraces —
    ``compiled_step_info()["n_traces"]`` is pinned at 1 by CI exactly
    like the train step's retrace guard. On a ring, where every live
    slot is greedy and no pass is pending, tick n + 1 is dispatched on
    tick n's tokens, still on the device, before tick n is read, so the
    device runs through the host's work between two ticks
    (``_run_decode``; docs/serving.md, "Decode ticks dispatched ahead").

  **The tick is written once; the KV format is not its business.** The
  engine owns the slot table, the queue, sampling (a greedy row's
  token is the program's argmax; a sampling row draws host-side
  through :mod:`singa_tpu.models.decode`, which is what lets
  per-request temperature/top_k/seed vary without touching a compiled
  program), the spans, fault points, the counters every format has,
  and the device state itself (``_cache``). A *layout* of
  :mod:`.kv_cache` (``RingLayout``, ``PagedLayout``; picked once by
  ``pick_layout`` from ``kv_layout=`` and what the adapter supports,
  declining LOUDLY what it cannot honour) owns how that state is
  built, which of the adapter's programs run on it, what a request
  reserves before it is popped, how each program's host arrays are
  packed from the batch or the slot table, a slot's candidate row
  (one pending token; under ``speculative_k`` n-gram drafts behind it,
  which the one accept walk of ``_run_decode`` verifies against what
  was sampled), and how one slot's rows leave and enter the state for
  a snapshot or a spilled block.

  ``mesh=`` / ``model_shards=N`` runs BOTH programs GSPMD-sharded over
  a named (batch × model) mesh (``parallel/gspmd.py``): params and KV
  state are annotated with NamedSharding, the SAME pure bodies are
  jitted once, and XLA inserts every collective. The sharded programs
  return the tokens alone — the argmax runs over the vocab-sharded
  logits and the full (rows, V) array never exists on any device or
  the host — so sampled requests are a typed submit-time rejection.

- :class:`BatchServingEngine` — stateless models (the CNN/MLP zoo and
  ONNX imports through ``sonnx.SONNXModel``): each tick gathers up to
  ``W`` queued requests, pads the batch to the fixed width, runs ONE
  jitted forward (state threaded functionally, policy scope entered
  inside the trace), and delivers per-row results. Same queue, same
  exactly-once futures, same drain.

Fault handling reuses :class:`~singa_tpu.resilience.faults.FaultPlan`:
``faults.on_step(tick)`` fires BEFORE any tick mutates engine state, so
an injected transient fault is retried with nothing lost and nothing
doubled (chaos-tested). Retries beyond ``max_retries`` crash the loop:
a flight-recorder blackbox (``telemetry/blackbox-serve.jsonl``) is
dumped and every pending future is failed — once each.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
import warnings
from collections import deque

import numpy as np

from .. import integrity as _integrity
from ..observability import metrics as _metrics
from ..observability import perf as _perf
from ..observability import spans as _spans
from ..resilience.faults import NULL_PLAN, FaultInjected
from ..models import decode as _decode
from . import kv_cache as _kvc
from .scheduler import (BlockPoolExhausted, EngineDraining,
                        HandoffRefused, QueueFull, ReplicaCrashed,
                        Request, RequestQueue, RequestTimeout,
                        ServingError, budget_remaining, deadline_in)

# donation is a TPU/accelerator optimisation; on CPU jax warns that the
# donated buffers were unused — expected for OUR two programs, not
# actionable. The suppression is scoped to our own dispatches (warnings
# filters are process-global; a module-level ignore would hide genuine
# donation regressions in the embedding application's unrelated jits).
# The lock keeps concurrent engines from clobbering each other's
# catch_warnings save/restore; dispatch returns before execution, so
# the hold time is microseconds. ``sp`` is the open serve span: the
# call alone is its phase ``call``, so ``dispatch`` less ``call`` is
# this lock, the filter and what ``_dispatch`` does around them.
_WARN_LOCK = threading.Lock()


def _quiet_donation(sp, fn, *args):
    with _WARN_LOCK, warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        with sp.phase("call"):
            return fn(*args)


def _pack_arrays(arrays):
    """``(specs, payload)`` for a list of host arrays: per-array
    dtype/shape specs (frame metadata) plus one concatenated byte
    blob (frame payload). The inverse of :func:`_unpack_arrays`."""
    specs, chunks = [], []
    for arr in arrays:
        arr = np.ascontiguousarray(arr)
        specs.append({"dtype": str(arr.dtype),
                      "shape": [int(d) for d in arr.shape]})
        chunks.append(arr.tobytes())
    return specs, b"".join(chunks)


def _unpack_arrays(specs, payload):
    """Rebuild the packed arrays from a CRC-verified frame. Length
    mismatches raise IntegrityError: the CRC vouched for the bytes,
    so a mismatch against the specs is a protocol bug — still typed,
    still never written into a pool. ``jnp.dtype`` resolves extended
    dtypes (bfloat16, fp8) that plain numpy refuses by name."""
    import jax.numpy as jnp
    payload = bytes(payload)
    out, off = [], 0
    for spec in specs:
        dt = jnp.dtype(str(spec["dtype"]))
        shape = tuple(int(d) for d in spec["shape"])
        n = int(dt.itemsize) * int(np.prod(shape, dtype=np.int64))
        chunk = payload[off:off + n]
        if len(chunk) != n:
            raise _integrity.IntegrityError(
                f"frame payload truncated: array {spec} needs {n}B, "
                f"{len(chunk)}B left")
        out.append(np.frombuffer(chunk, dtype=dt).reshape(shape))
        off += n
    if off != len(payload):
        raise _integrity.IntegrityError(
            f"frame payload has {len(payload) - off} trailing bytes")
    return out


def _cache_counts():
    """Persistent-compile-cache counter snapshot before a dispatch
    that may trace (labels the compile source cache-vs-fresh)."""
    from ..aot import cache as _aot_cache
    return _aot_cache.snapshot()


def _attribute_trace(rec, registry, program, arrays, names, t0,
                     cache_counts0=None):
    """Compile/retrace attribution for ONE serve-program dispatch that
    traced (caller checks the ``n_traces`` delta): wall-clock into
    ``compile_seconds{program, source}``, signature (diffed against
    this program's previous trace) into a compile/retrace event — a
    decode retrace is the broken no-retrace contract, and the event
    names what changed. ``cache_counts0`` (a persistent-compile-cache
    counter snapshot taken before the dispatch) labels the source
    cache-vs-fresh."""
    from ..aot import cache as _aot_cache
    sig = _perf.step_signature(arrays, names=names)
    source = _aot_cache.classify(cache_counts0) \
        if cache_counts0 is not None else "fresh"
    _perf.record_compile(program, time.perf_counter() - t0, sig,
                         prev_signature=rec.get("sig"),
                         source=source, registry=registry)
    rec["sig"] = sig


class _EngineBase:
    """Shared control plane: queue, loop thread, drain, faults, SLOs."""

    def __init__(self, *, queue_capacity=64, faults=None, registry=None,
                 telemetry_dir="telemetry", max_retries=3,
                 trace_requests=True, profile_every=0):
        self._reg = registry if registry is not None \
            else _metrics.default_registry()
        self.queue = RequestQueue(queue_capacity, registry=self._reg)
        self.faults = faults if faults is not None else NULL_PLAN
        self.telemetry_dir = telemetry_dir
        self.max_retries = int(max_retries)
        # per-request flight-recorder events (request.queued →
        # request.prefill → request.decode_tick... → request.delivered,
        # all carrying the request's trace id) — what the Perfetto
        # exporter reconstructs into one timeline lane per request.
        # Each event is a µs-scale dict append; trace_requests=False
        # turns them off for latency-critical deployments.
        self._trace_requests = bool(trace_requests)
        self._hbm_dev = None        # set by subclasses (HBM sampling)
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._idle_evt = threading.Event()
        self._thread = None
        self._running = False
        self._draining = False
        self._stopped = False
        self._crashed = None
        self._tick_count = 0
        # every-Nth-tick profiled decode tick (the trainer's
        # profile_every, serving-side): the tick runs under a profiler
        # trace through the ALREADY-compiled programs (n_traces pin
        # untouched), refreshing this registry's profile_fusion_* and
        # timeline_* gauges with site=serve. 0 disables; non-profiled
        # ticks pay one integer check.
        self._profile_every = int(profile_every or 0)
        self._profiling_now = False
        self._last_timeline = None
        self._retries = self._reg.counter(
            "serve_retries_total",
            "serve-loop ticks retried after an injected/transient fault")
        # submit sequence number: the key the fleet-level wire-error
        # fault fires on (send numbers, like the control plane's)
        self._submit_seq = 0
        # deadline drain: the handoff callable (set per-drain), the
        # absolute budget clock, and an EWMA of tick cost the handoff
        # pass uses to predict whether a request fits the budget
        self._handoff = None
        self._drain_deadline = None
        self._tick_ewma = 0.0
        self._handoff_seq = 0
        self._stranded = self._reg.counter(
            "serve_stranded_requests_total",
            "requests a serve-loop crash failed while admitted "
            "(queued or slotted) — each one is re-dispatchable by a "
            "fleet router with its remaining deadline budget")
        self._ttft = self._reg.histogram(
            "serve_ttft_seconds",
            "request submit to first generated token (queue wait "
            "included — this is what the caller feels)")
        self._tok_lat = self._reg.histogram(
            "serve_token_seconds",
            "the host's part of one delivered decode tick (the "
            "serve.decode span): input packing and the program call, the "
            "read-back of its tokens (of its logits too in a tick that "
            "serves a sampling request) and the per-slot walk that "
            "places them. Where the next tick is dispatched before this "
            "one is read, the span holds that dispatch and this tick's "
            "read; a span that only reads a tick in flight (before a "
            "pass or a serial tick, or the last of a stream) is a tick "
            "too, so the sum is the decode's host time and the count "
            "the ticks delivered")

    # -- admission ---------------------------------------------------------
    def _admit(self, req):
        # fleet fault point: the submit RPC dies on the wire before the
        # engine sees it (raises ConnectionError — what a router's
        # breaker must classify as a replica failure, not a request one)
        self._submit_seq += 1
        self.faults.on_submit(self._submit_seq)
        if self._crashed is not None:
            self.queue.finish("rejected")
            raise ReplicaCrashed(
                f"engine crashed ({self._crashed}); not accepting "
                "requests — see the blackbox dump")
        if self._draining or self._stopped:
            self.queue.finish("rejected")
            raise EngineDraining(
                "engine is draining/stopped; not accepting new requests")
        # the queued event lands BEFORE the put: the loop thread can
        # pop-and-prefill the instant the request is visible, and the
        # per-request timeline must stay causal (queued < prefill)
        if self._trace_requests:
            _spans.event("request.queued", request=req.trace_id,
                         queue_depth=len(self.queue))
        try:
            self.queue.put(req)
        except QueueFull:
            if self._trace_requests:
                _spans.event("request.rejected", request=req.trace_id,
                             reason="queue_full")
            raise
        self._wake.set()
        # fleet fault point: the replica dies the instant after it
        # admitted this request — the stranded-request shape a router's
        # exactly-once re-dispatch exists for (the future comes back
        # already failed with ReplicaCrashed)
        if self.faults.on_admit(req.id):
            self._crash(RuntimeError("injected crash after admit"))
        return req.future

    # -- background loop ---------------------------------------------------
    def start(self):
        """Run the serve loop on a daemon thread. Idempotent."""
        with self._lock:
            if self._thread is not None:
                return self
            self._running = True
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="serve-loop")
            self._thread.start()
        return self

    def _busy(self):
        raise NotImplementedError

    def _tick(self):
        raise NotImplementedError

    def _fail_inflight(self, error):
        raise NotImplementedError

    def _settle(self):
        """Read what a tick left running on the device (nothing here)."""

    def _fault_point(self):
        """The tick's fault hook. It fires BEFORE any state mutates, so
        a retry replays the tick cleanly: nothing delivered twice,
        nothing dropped. A tick still in flight is read first, so the
        retry starts from a settled engine."""
        try:
            self.faults.on_step(self._tick_count)
        except FaultInjected:
            self._settle()
            raise

    def _run_tick(self):
        """One scheduler tick, every Nth one profiled: the profiled
        tick runs THROUGH the compiled dispatch under a jax.profiler
        trace (``measure_step_fusions`` — no retrace, one trace dump)
        and refreshes ``profile_fusion_*`` plus the step-timeline
        decomposition (``timeline_*{site=serve}`` gauges, a
        ``timeline.sample`` event). The profiled tick's inflated
        per-token latency stays OUT of the SLO series (PR 9's
        trainer invariant, serving-side): its true cost lands in
        ``serve_profile_capture_seconds``."""
        if not (self._profile_every and self._tick_count > 0
                and self._tick_count % self._profile_every == 0):
            self._tick()
            return
        from .. import profiling as _profiling
        self._profiling_now = True
        t0 = time.perf_counter()
        events = []
        try:
            # a failure of the TICK itself propagates untouched (the
            # loop's crash path owns it, exactly like an unprofiled
            # tick); measure_step_fusions already degrades profiler
            # breakage to an empty table
            _, table = _profiling.measure_step_fusions(
                self._tick, events_out=events)
        finally:
            self._profiling_now = False
        capture_s = time.perf_counter() - t0
        try:
            self._record_profiled_tick(table, events, capture_s)
        except Exception as e:      # noqa: BLE001 — never a blocker
            # telemetry must not take the serve loop down (a metric
            # name/kind collision in a caller's registry would
            # otherwise crash the engine and fail every inflight
            # request over bookkeeping)
            warnings.warn(
                f"profiled-tick telemetry failed "
                f"({type(e).__name__}: {e})", stacklevel=2)

    def _record_profiled_tick(self, table, events, capture_s):
        from .. import profiling as _profiling
        from ..observability import timeline as _timeline
        self._reg.counter(
            "serve_profile_samples_total",
            "profiled serving ticks (every profile_every-th)").inc()
        self._reg.histogram(
            "serve_profile_capture_seconds",
            "wall-clock of one profiled serving tick (trace dump + "
            "parse included — the sampling overhead bound)").observe(
                capture_s)
        if table:
            _profiling.record_fusion_metrics(table, registry=self._reg)
        # the window is the tick, host work and waits included, and the
        # engine's own annotations are what it was inside, not the
        # runtime's work: a gap under them alone is idle
        tl = _timeline.analyze(
            events, window=_timeline.host_extent(events, "serve.tick"),
            own=("serve.",))
        if tl is not None:
            _timeline.record_timeline(tl, registry=self._reg,
                                      site="serve")
            self._last_timeline = tl
            _spans.event("timeline.sample", site="serve",
                         tick=self._tick_count, lanes=tl["lanes"],
                         **_timeline.compact(tl))

    @property
    def last_timeline(self):
        """The newest profiled tick's step-timeline decomposition
        (None before the first sample) — what the gateway serves at
        ``GET /timeline.json``."""
        return self._last_timeline

    def _fail_batch(self, batch, exc):
        """Fail requests that were popped from the queue but died
        before reaching the slot table / delivery (exactly once).
        Typed ReplicaCrashed: a tick exception takes the whole loop
        down right after this, so these requests are stranded by a
        dying replica — re-dispatchable, not malformed."""
        err = ReplicaCrashed(f"serve tick failed: {exc}")
        err.__cause__ = exc
        for req in batch:
            if not req.future.done():
                req.future.set_error(err)
                self.queue.finish("failed")

    def _loop(self):
        consecutive = 0
        while self._running:
            if not self._busy():
                self._idle_evt.set()
                # on the trace's clock only: device idle time under
                # `serve.idle` is load that was absent, not host cost
                with _spans.annotation("serve.idle"):
                    self._wake.wait(0.02)
                self._wake.clear()
                continue
            self._idle_evt.clear()
            try:
                self._fault_point()
                self._run_tick()
                self._tick_count += 1
                consecutive = 0
            except FaultInjected as e:
                consecutive += 1
                self._retries.inc()
                if consecutive > self.max_retries:
                    self._crash(e)
                    return
            except Exception as e:          # noqa: BLE001 — crash path
                self._crash(e)
                return
        self._idle_evt.set()

    def _crash(self, exc):
        """Serve-loop death: blackbox dump, then fail every pending
        future exactly once."""
        self._crashed = exc
        self._running = False
        # no loop will ever process the queue again: refuse at the
        # door from this instant (exactly-once forbids futures that
        # never resolve)
        self._stopped = True
        try:
            path = os.path.join(self.telemetry_dir,
                                "blackbox-serve.jsonl")
            extra = {"tick": self._tick_count,
                     "error": f"{type(exc).__name__}: {exc}",
                     "queue_depth": len(self.queue)}
            # serve-side OOM post-mortem: where the HBM went
            hbm = _perf.hbm_stats(self._hbm_dev)
            if hbm:
                extra["hbm"] = hbm
            live = _perf.live_array_report()
            if live:
                extra["live_arrays"] = live
            _spans.recorder().dump(
                path, reason="serve_loop_crash", extra=extra,
                registry=self._reg)
            print(f"[serving] loop crashed ({type(exc).__name__}: "
                  f"{exc}); blackbox at {path}")
        except Exception:   # losing the blackbox must not mask the crash
            pass
        err = ReplicaCrashed(f"serve loop crashed: {exc}")
        err.__cause__ = exc
        # stranded-request capture: everything admitted (queued or
        # slotted) dies HERE with a re-dispatchable typed error — the
        # count is the fleet router's recovery workload
        stranded = self.queue.drain_pending(err)
        stranded += self._count_inflight()
        self._fail_inflight(err)
        if stranded:
            self._stranded.inc(stranded)
        self._idle_evt.set()

    def _count_inflight(self):
        """Requests currently holding a slot (subclass-specific)."""
        return 0

    def _sample_hbm(self):
        """HBM gauges on the serving tick cadence (every 16th tick —
        decode ticks can be sub-ms; a CPU run costs one probe ever)."""
        if self._tick_count % 16 == 0:
            _perf.record_hbm(self._hbm_dev, self._reg, site="serve")

    # -- AOT export (cold-start elimination) -------------------------------
    def export_aot(self, store=None):
        """Serialize this engine's compiled executables into an AOT
        store (the engine's own ``aot_store`` when none is given) so
        the next replica spin-up deserializes instead of tracing.
        Returns {program: manifest}."""
        from ..aot import export as _aot_export
        if getattr(self, "sharded", False):
            d = self._part.describe()
            raise ValueError(
                f"export_aot is not supported for sharded serving: "
                f"the compiled programs are bound to this mesh "
                f"(batch={d['batch']} × model={d['model']} over "
                f"{d['devices']} devices) and a deserialized "
                "NamedSharding executable cannot be verified against "
                "another host's topology — the persistent compile "
                "cache is the sharded warm-start path")
        if store is None:
            store = getattr(self, "_aot_store", None)
        if store is None:
            raise ValueError(
                "export_aot needs a store: pass one, or build the "
                "engine with aot_store=")
        if not isinstance(store, _aot_export.AotStore):
            store = _aot_export.AotStore(store, registry=self._reg)
        docs = _aot_export.export_serving(self, store)
        # keep the warm-restart audit truthful: a cold spin-up that
        # just exported must not keep reporting refused:missing on
        # /healthz and /aot.json (a program that WAS deserialized
        # stays "loaded" — exporting beside it changes nothing)
        if getattr(self, "_aot_store", None) is None:
            self._aot_store = store
        src = dict(getattr(self, "_aot_source", None) or {})
        for program in docs:
            if src.get(program) != "loaded":
                src[program] = "exported"
        self._aot_source = src
        return docs

    # -- synchronous stepping (tests, simple callers) ----------------------
    def step(self):
        """Run ONE scheduler tick inline (only valid without the
        background thread). Returns True when there was work."""
        if self._thread is not None:
            raise RuntimeError("step() is for synchronous use; the "
                               "background loop is running")
        if not self._busy():
            return False
        self._fault_point()
        self._run_tick()
        self._tick_count += 1
        return True

    def run_until_idle(self, max_ticks=10_000):
        """Synchronously tick until no work remains (tests). Transient
        injected faults are retried like the background loop would."""
        ticks = 0
        consecutive = 0
        while self._busy():
            try:
                self.step()
                consecutive = 0
            except FaultInjected:
                consecutive += 1
                self._retries.inc()
                if consecutive > self.max_retries:
                    raise
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError("engine did not go idle "
                                   f"within {max_ticks} ticks")
        return ticks

    # -- drain / stop ------------------------------------------------------
    @property
    def draining(self):
        return self._draining

    def ttft_stats(self):
        """Caller-felt TTFT quantiles, ``{"count", "p50_s", "p99_s"}``.

        Reads the ``serve_ttft_seconds`` histogram (queue wait
        included — the number the SLO is written against); quantiles
        are None until at least one request has produced a first
        token. This is the supervisor-facing accessor: an autoscaler
        or dashboard should call this instead of digging through the
        registry snapshot."""
        h = self._ttft
        doc = h._series_doc(None, h._slot({}))
        q = doc.get("quantiles") or {}
        return {"count": int(doc.get("count", 0) or 0),
                "p50_s": q.get("p50"), "p99_s": q.get("p99")}

    def drain(self, timeout=60.0, handoff=None):
        """Graceful drain: refuse new requests, FINISH everything
        in flight and queued, return True once idle. The drainable-
        replica contract: a drained engine dropped nothing.

        ``handoff`` turns ``timeout`` from a wait into a BUDGET
        (preemption-deadline drain): each tick the engine migrates
        queued requests and any in-flight request that cannot finish
        inside the remaining budget through
        ``handoff(request, snapshot_or_None, budget_s) -> bool`` —
        True means a survivor took ownership of delivering the
        response; anything else fails the request typed
        (:class:`EngineDraining`, the fleet's recompute re-dispatch
        rung). Either way drain returns by the deadline with nothing
        unresolved left behind."""
        self._handoff = handoff
        self._drain_deadline = time.monotonic() + float(timeout)
        self._draining = True
        self._wake.set()
        if self._thread is None:
            # synchronous engines drain inline
            self.run_until_idle()
            return True
        deadline = self._drain_deadline
        while True:
            if self._crashed is not None:
                return False
            if not self._busy() and self._idle_evt.wait(0.05):
                if not self._busy():
                    return True
            now = time.monotonic()
            if now >= deadline:
                if handoff is None:
                    return not self._busy()
                # deadline drain: the handoff pass runs at tick
                # boundaries, and a tick already in flight (the first
                # decode compile, say) cannot be interrupted — so past
                # the deadline the budget is simply negative (the next
                # pass migrates EVERYTHING) and we give the loop a
                # bounded grace to reach that boundary rather than
                # abandoning work a survivor could continue
                if now >= deadline + getattr(self, "_drain_grace", 5.0):
                    return not self._busy()
            time.sleep(0.01)

    def stop(self):
        """Hard stop: end the loop; queued/in-flight requests are
        failed (use :meth:`drain` first for a graceful exit)."""
        self._stopped = True
        self._running = False
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        if self._crashed is None:
            err = EngineDraining("engine stopped")
            n = self.queue.drain_pending(err)
            self._fail_inflight(err)
            return n
        return 0


class ServingEngine(_EngineBase):
    """Continuous-batching autoregressive engine (module docstring)."""

    def __init__(self, adapter, *, slots=4, max_len=64, prefill_len=16,
                 prefill_batch=2, policy=None, aot_store=None,
                 kv_layout="ring", kv_block_size=16, kv_blocks=None,
                 speculative_k=0, mesh=None, model_shards=None,
                 spill_bytes=0, snapshot_every=0,
                 pool_role="colocated", **kw):
        super().__init__(**kw)
        import jax

        self.adapter = adapter
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.prefill_len = int(prefill_len)
        self.prefill_batch = max(1, min(int(prefill_batch), self.slots))
        if self.prefill_len > self.max_len:
            raise ValueError(
                f"prefill_len {self.prefill_len} exceeds the ring "
                f"length max_len {self.max_len}: prompt rows must fit "
                "the cache without wrapping over themselves")
        validate = getattr(adapter, "validate", None)
        if validate is not None:
            # model-side limits (e.g. the positional-embedding table)
            # fail HERE, typed, instead of crashing the first compiled
            # prefill with a shape error
            validate(prefill_len=self.prefill_len, max_len=self.max_len)
        self.policy = policy
        self._P = adapter.params()
        self._slots = [None] * self.slots        # host-side slot table
        # live-KV handoff state: validated snapshot injects waiting
        # for a free slot (+ paged blocks), cadence checkpoints a
        # crashed replica's router resumes from, and the drain pass's
        # wall-clock reserve for the final snapshot/transfer
        self._injects = deque()
        self.snapshot_every = int(snapshot_every or 0)
        self._kv_checkpoints = {}       # trace_id -> {"meta","frame"}
        self._drain_reserve = 0.25
        self._drain_grace = 5.0
        # disaggregated prefill/decode pools: the role tag is ROUTING
        # metadata (the fleet router reads it for pool placement and
        # arms a prefill engine's transfer callable); the engine stays
        # fully capable either way — a decode replica can recompute a
        # prompt from scratch and a prefill replica can decode to the
        # end (the colocate-fallback rung of the degradation ladder)
        pool_role = str(pool_role)
        if pool_role not in ("colocated", "prefill", "decode"):
            raise ValueError(
                f"pool_role must be 'colocated', 'prefill' or "
                f"'decode', got {pool_role!r}")
        self.pool_role = pool_role
        if pool_role != "colocated":
            # published so heartbeat_summary (registry-only view) can
            # report the replica's role: 1=prefill 2=decode
            self._reg.gauge(
                "serve_pool_role",
                "this replica's disaggregated-pool role: "
                "1=prefill 2=decode (absent/0 = colocated)").set(
                1 if pool_role == "prefill" else 2)
        self._transfer = None           # armed by FleetRouter
        self._transfer_seq = 0
        self._transfer_out = None
        self._colocated = None

        # -- GSPMD sharded serving (mesh=/model_shards=) ------------------
        # One NamedSharding partitioner over a named (batch × model)
        # mesh (parallel/gspmd.py): params/KV annotated, the SAME pure
        # programs jitted once, XLA inserts every collective. Configs
        # the mesh cannot honor are typed declines at build — never a
        # silently replicated "sharded" serve.
        self._part = None
        if mesh is not None or model_shards:
            from ..parallel import gspmd
            if not getattr(adapter, "supports_sharded", False):
                raise gspmd.ShardingDecline(
                    f"{type(adapter).__name__} has no sharded (GSPMD) "
                    "serve programs: its decode state cannot be "
                    "partitioned over a (batch × model) mesh — serve "
                    "this model single-device")
            part = gspmd.serving_partitioner(
                mesh=mesh, model_shards=model_shards,
                max_batch=self.slots)
            # the slot array (and the ring cache's W axis) shards over
            # 'batch': the decode program's rows must tile the axis
            # (auto-built meshes already fit it; an explicit mesh is
            # the caller's pin and refuses typed here)
            part.require_divisible("slots", self.slots,
                                   part.batch_axis)
            self._part = part
        self.sharded = self._part is not None

        # -- the KV layout (serving/kv_cache.py): picked once, declined
        #    loudly where it cannot be honoured; what it decided is
        #    republished under the names AOT export, the fleet and the
        #    tests read
        layout = self._layout = _kvc.pick_layout(
            kv_layout, adapter, self._reg, slots=self.slots,
            max_len=self.max_len, prefill_len=self.prefill_len,
            prefill_batch=self.prefill_batch,
            kv_block_size=kv_block_size, kv_blocks=kv_blocks,
            speculative_k=speculative_k, spill_bytes=spill_bytes,
            sharded=self.sharded)
        self.kv_layout = layout.name
        self._mgr = layout.mgr
        self.kv_block_size = layout.block_size
        self.kv_blocks = layout.n_blocks
        self._max_blocks = layout.max_blocks
        self._spec_width = layout.spec_width
        self.speculative_k = layout.spec_width \
            if layout.spec_width > 1 else 0
        self.spill_bytes = int(spill_bytes or 0)
        # brownout knob: while set, no drafts are proposed (each tick
        # emits one token through the SAME compiled verify program —
        # rows padded to width 1, no retrace, greedy identity intact).
        # A fleet shed policy flips this before refusing outright.
        self._spec_throttled = False
        self._cache = layout.init_state()
        if layout.spill_tier is not None:
            layout.attach_spill(self._spill_block_read,
                                self._spill_block_write)

        self._prefill_rec = {"n_traces": 0}
        self._decode_rec = {"n_traces": 0}
        prefill_rec, decode_rec = self._prefill_rec, self._decode_rec
        prefill_raw, decode_raw = layout.programs(self.sharded)
        # a Mosaic call cannot be partitioned: under a sharded jit a
        # ring level keeps the XLA path (a pool has no such call)
        rings = _kvc.xla_rings if self.sharded \
            else contextlib.nullcontext

        def prefill_body(P, state, *host):
            prefill_rec["n_traces"] += 1
            return prefill_raw(P, state, *host)

        def decode_body(P, state, *host):
            # host-side trace counter, same contract as
            # Model._build_step: 1 forever (CI-pinned) — slots, block
            # tables and draft rows vary per tick but their SHAPES are
            # fixed, so refills, prefix hits and speculative ticks
            # reuse the one executable
            decode_rec["n_traces"] += 1
            with rings():
                return decode_raw(P, state, *host)

        jit_kw = {"prefill": {}, "decode": {}}
        tok_sh = None
        if self._part is not None:
            # annotate the named state + KV layout once, jit the same
            # pure bodies: XLA's SPMD partitioner inserts the
            # collectives (heads/MLP/vocab over 'model', slots over
            # 'batch'). Explicit out_shardings keep the donated cache's
            # layout identical in and out, so whole-state donation
            # survives sharding.
            pspecs, cspecs = adapter.sharding_specs(
                self._part, self._P, self._cache, self.kv_layout)
            self._P = self._part.shard(self._P, pspecs)
            self._cache = self._part.shard(self._cache, cspecs)
            from ..parallel import gspmd as _gspmd
            io = _gspmd.serving_arg_specs(self._part, self.kv_layout)
            p_sh = self._part.sharding_tree(pspecs)
            c_sh = self._part.sharding_tree(cspecs)
            tok_sh = self._part.sharding(io["tokens_out"])
            for program, kw in jit_kw.items():
                kw.update(
                    in_shardings=(p_sh, c_sh, *(self._part.sharding(s)
                                                for s in io[program])),
                    out_shardings=(c_sh, (tok_sh,)))
        self._hbm_dev = _perf.first_jax_device(self._cache)
        # the record of the decode tick dispatched before the one it
        # follows is read (``_run_decode``). A layout whose decode takes
        # an input from the last call starts it from an array placed as
        # the calls place their tokens, so every call passes one kind of
        # array there: committed to a device only where the programs'
        # other arguments are. A call with one committed argument
        # returns committed arrays, and the state and the tokens it
        # passes on would then give each program a second executable
        self._inflight = None
        held = jax.tree_util.tree_leaves((self._P, self._cache))
        if tok_sh is not None:
            layout.bind_device(lambda a: jax.device_put(a, tok_sh))
        elif any(getattr(a, "committed", False) for a in held):
            layout.bind_device(
                lambda a: jax.device_put(a, self._hbm_dev))
        else:
            layout.bind_device(jax.device_put)
        # the KV state (ring cache or block pool) is DONATED: the one
        # large serving buffer is updated in place by XLA instead of
        # doubling per tick
        self._prefill = jax.jit(prefill_body, donate_argnums=(1,),
                                **jit_kw["prefill"])
        self._decode = jax.jit(decode_body, donate_argnums=(1,),
                               **jit_kw["decode"])
        # warm restart: deserialize previously exported prefill/decode
        # executables (honored-or-refused per artifact — a refused one
        # compiles fresh, loudly). The trace that produced a loaded
        # program happened in the EXPORTING process, so its n_traces
        # counter reads 1 and the no-retrace pin still holds.
        self._aot_store = None
        self._aot_source = None
        if aot_store is not None:
            if self.sharded:
                # a NamedSharding executable is topology-bound: the
                # manifest contract cannot vouch for it across hosts.
                # Refuse typed, naming the mesh — the persistent
                # compile cache is the sharded warm-start path.
                d = self._part.describe()
                warnings.warn(
                    f"aot_store declined: sharded serving programs "
                    f"(mesh batch={d['batch']} × model={d['model']}) "
                    "are not AOT-exportable; compiling fresh (the "
                    "persistent compile cache still warms them)",
                    stacklevel=3)
                reason = (f"refused:sharded_mesh_{d['batch']}x"
                          f"{d['model']}")
                self._aot_source = {"serve_prefill": reason,
                                    "serve_decode": reason}
            else:
                # ring AND paged manifests carry the layout geometry
                # (kv_block_size/kv_blocks/speculative_k), so both
                # round-trip; a layout mismatch refuses typed
                self._load_aot(aot_store)

        self._occupancy = self._reg.gauge(
            "serve_slot_occupancy", "active sequences in the slot array")
        self._reg.gauge("serve_slots",
                        "slot array width (max in-flight sequences)"
                        ).set(self.slots)
        # the host pays for every buffer of every call (a hold, an
        # event, a reference): an adapter that hands a leaf a layer a
        # role shows here, once, at no cost a tick
        arg_buffers = self._reg.gauge(
            "serve_program_arg_buffers", "buffers one call of a serve "
            "program passes: the leaves of the adapter's parameter "
            "tree, of the donated KV state, and the tick's host arrays",
            labels=("program",))
        n_held = len(jax.tree_util.tree_leaves((self._P, self._cache)))
        for program, names in (("prefill", layout.prefill_names),
                               ("decode", layout.decode_names)):
            arg_buffers.set(n_held + len(names), program=program)
        # an adapter whose programs return ``(logits, stats)`` (a small
        # array of per-call counts that rides the tokens' read-back, no
        # sync of its own) publishes them itself: ``stats_recorder(
        # registry)`` gives ``record(program, stats) -> span attrs``;
        # the engine knows neither their names nor their meaning
        make = getattr(adapter, "stats_recorder", None)
        self._record_stats = None if make is None else make(self._reg)
        self._tokens_total = self._reg.counter(
            "serve_tokens_total", "tokens generated")
        self._readbacks = self._reg.counter(
            "serve_readback_total", "serve program calls, by what of "
            "their output came to the host (tokens: every row's "
            "in-graph argmax, 4 bytes a row; logits: the (rows, V) "
            "float32 array as well, because a request the call served "
            "samples)", labels=("program", "what"))
        self._decode_steps = self._reg.counter(
            "serve_decode_steps_total", "continuous-batching decode "
            "ticks executed")
        self._decode_ticks = self._reg.counter(
            "serve_decode_ticks_total", "decode program calls by how "
            "they were dispatched: ahead, before the call they follow "
            "was read, on its tokens on the device (reason none); or "
            "serial, because nothing was in flight to follow (first), a "
            "live request samples on the host (sampling), the layout's "
            "rows hold candidates (candidates), a hand-off, inject, "
            "transfer or checkpoint pass reads the slots (pass) or the "
            "engine drains (drain)", labels=("mode", "reason"))
        self._prefills = self._reg.counter(
            "serve_prefill_total", "prompts prefilled into a slot")
        self._prefill_tok = self._reg.counter(
            "serve_prefill_tokens_total",
            "prompt tokens run through the prefill program (suffix "
            "only under paged prefix hits) — the recompute cost a KV "
            "handoff or spill restore avoids")
        self._handoff_out = self._reg.counter(
            "serve_handoff_out_total",
            "requests a deadline drain migrated to a survivor "
            "(snapshot or recompute handoff, accepted by the receiver)")
        self._handoff_in = self._reg.counter(
            "serve_handoff_in_total",
            "live KV snapshots this engine accepted for injection")
        self._handoff_refused = self._reg.counter(
            "serve_handoff_refused_total",
            "snapshot injects refused typed (CRC failure or geometry/"
            "policy mismatch) — corrupt KV is never written")
        self._handoff_fallback = self._reg.counter(
            "serve_handoff_fallback_total",
            "drain handoffs that fell back to recompute re-dispatch")
        self._ckpt_count = self._reg.counter(
            "serve_kv_checkpoint_total",
            "in-flight KV snapshots checkpointed on the "
            "snapshot_every cadence (crash re-dispatch resumes from "
            "the newest one instead of token zero)")
        if self.sharded:
            # fleet-view honesty: the mesh shape plus what ONE chip
            # actually holds — heartbeat_summary's serving_kv block and
            # /healthz read these so pool-pressure numbers stay
            # per-device, not global, under sharding
            d = self._part.describe()
            self._reg.gauge(
                "serve_mesh_batch",
                "serving mesh 'batch' axis degree (slots shard over "
                "it)").set(d["batch"])
            self._reg.gauge(
                "serve_mesh_model",
                "serving mesh 'model' axis degree (heads/MLP/vocab "
                "shard over it)").set(d["model"])
            self._reg.gauge(
                "serve_kv_per_device_bytes",
                "KV state bytes ONE device holds (ring: slots/batch × "
                "heads/model slice; paged: whole pool × heads/model "
                "slice)").set(self._part.per_device_bytes(self._cache))
            self._reg.gauge(
                "serve_kv_global_bytes",
                "logical (unsharded) KV state bytes across the mesh"
            ).set(self._part.global_bytes(self._cache))

    # -- AOT export / warm restart -----------------------------------------
    def _load_aot(self, store):
        from ..aot import export as _aot_export
        from ..observability import perf as _perf2
        if not isinstance(store, _aot_export.AotStore):
            # the engine's own registry: aot_loads_total and the
            # quarantine counter must land beside the engine's
            # compile_seconds, not in the default registry
            store = _aot_export.AotStore(store, registry=self._reg)
        self._aot_store = store
        prefill_avals, decode_avals = \
            _aot_export.serving_program_avals(self)
        geometry = _aot_export.serving_geometry(self)
        self._aot_source = {}
        for program, avals, rec, attr in (
                (_aot_export.SERVE_PREFILL, prefill_avals,
                 self._prefill_rec, "_prefill"),
                (_aot_export.SERVE_DECODE, decode_avals,
                 self._decode_rec, "_decode")):
            t0 = time.perf_counter()
            fn, _doc = store.try_load_program(
                program, avals=avals, donate_argnums=(1,),
                policy=self.policy, jax_device=self._hbm_dev,
                expect_extra=geometry)
            if fn is None:
                self._aot_source[program] = store.outcomes.get(
                    program, "fresh")
                continue
            setattr(self, attr, fn)
            rec["n_traces"] = 1
            sig = _perf2.step_signature(avals[2:])
            _perf2.record_compile(program,
                                  time.perf_counter() - t0, sig,
                                  source="aot", registry=self._reg)
            rec["sig"] = sig
            self._aot_source[program] = "loaded"

    # -- public API --------------------------------------------------------
    def submit(self, prompt, max_new_tokens=16, temperature=0.0,
               top_k=None, eos_id=None, seed=0, timeout=None,
               trace_id=None):
        """Queue one generation request; returns its
        :class:`~singa_tpu.serving.scheduler.ServeFuture` (``.result()``
        is ``{"tokens": [...], "prompt_len": n, "ttft_s": ...,
        "queue_wait_s": ...}``; its ``token_times`` holds one stamp per
        generated token). Prompts longer than ``prefill_len`` are
        rejected here, typed and synchronous. ``trace_id`` names the
        request in the per-request flight-recorder trace (the gateway
        mints one per HTTP request); defaults to ``req-<n>``."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if int(max_new_tokens) < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1 (got {max_new_tokens}): "
                "the first token is sampled from the prefill logits, "
                "so every accepted request generates at least one")
        if prompt.size > self.prefill_len:
            self.queue.finish("rejected")
            raise ServingError(
                f"prompt of {prompt.size} tokens exceeds this engine's "
                f"prefill_len {self.prefill_len}")
        if self.sharded and (temperature != 0 or top_k):
            # the sharded programs argmax IN GRAPH over vocab-sharded
            # logits (nothing ever gathers the (rows, V) array), so
            # there are no host logits to sample from. Typed and
            # synchronous — never a silent fall-back to greedy.
            self.queue.finish("rejected")
            raise ServingError(
                f"sharded serving is greedy-only: temperature="
                f"{temperature}, top_k={top_k} would need the full "
                "vocab logits on the host, which the sharded decode "
                "program never materialises — submit with "
                "temperature=0, or serve this model unsharded")
        err = self._layout.never_fits(int(prompt.size),
                                      int(max_new_tokens))
        if err is not None:
            self.queue.finish("rejected")
            raise err
        req = Request(prompt, max_new_tokens=max_new_tokens,
                      temperature=temperature, top_k=top_k,
                      eos_id=eos_id, seed=seed, timeout=timeout,
                      trace_id=trace_id)
        return self._admit(req)

    def compiled_step_info(self):
        """Serve-path retrace audit (the train-step audit's sibling):
        the decode program's ``n_traces`` must be 1 across ANY refill
        pattern — that is the continuous-batching invariant CI pins."""
        info = {"n_traces": self._decode_rec["n_traces"],
                "prefill_n_traces": self._prefill_rec["n_traces"],
                "slots": self.slots, "max_len": self.max_len,
                "prefill_len": self.prefill_len,
                "prefill_batch": self.prefill_batch,
                "kv_layout": self.kv_layout,
                "speculative_k": self.speculative_k,
                "policy": self.policy.describe()
                if self.policy is not None else None,
                # warm-restart audit: per-program executable source
                # ("loaded" = deserialized AOT artifact, otherwise the
                # store's refusal outcome / "fresh"); None without a
                # store. The chaos warm-restart gate reads this off
                # /healthz.
                "aot": self._aot_source}
        info.update(self._layout.declined)
        if self.sharded:
            # /healthz honesty under sharding: the mesh shape and what
            # ONE device holds (not the global logical pool)
            info["mesh"] = self._part.describe()
            info["model_shards"] = self._part.model_shards
            info["kv_per_device_bytes"] = \
                self._part.per_device_bytes(self._cache)
            info["kv_global_bytes"] = \
                self._part.global_bytes(self._cache)
        info.update(self._layout.info(self._part))
        if self.snapshot_every:
            info["snapshot_every"] = self.snapshot_every
        return info

    def active_slots(self):
        return sum(1 for s in self._slots if s is not None)

    def throttle_speculation(self, on=True):
        """Brownout: suspend draft proposal (one token per tick through
        the unchanged compiled verify program) while ``on`` — less
        wasted verify compute under pressure, same greedy tokens.
        Idempotent; a fleet ``ShedPolicy`` brownout hook is the
        intended caller. Returns ``self``."""
        self._spec_throttled = bool(on)
        return self

    # -- disaggregated pools (prefill→decode transfer) ---------------------
    def set_transfer(self, cb):
        """Arm the prefill→decode transfer callable (a
        :class:`~singa_tpu.serving.fleet.FleetRouter` wiring its
        pools). ``cb(request, snapshot, resnap) -> bool``: True means a
        decode replica took ownership of delivering the response (the
        slot frees WITHOUT fulfilling the future — the router's relay
        owns it now); False/raise keeps the request here end-to-end
        (colocate fallback). ``resnap()`` re-extracts a FRESH sealed
        snapshot of the same slot — the retry-on-next-peer rung calls
        it so a frame corrupted at extraction is not re-delivered
        verbatim. ``None`` disarms. Returns ``self``."""
        self._transfer = cb
        if cb is not None:
            self._transfer_out = self._reg.counter(
                "serve_pool_transfer_out_total",
                "slots this prefill-role engine migrated to a decode "
                "replica right after prefill (KV transfer accepted)")
            self._colocated = self._reg.counter(
                "serve_pool_colocate_total",
                "requests this prefill-role engine kept end-to-end "
                "because no decode replica could take the transfer "
                "(the colocate-fallback rung)")
        return self

    def _transfer_pass(self):
        """Offer every active slot whose transfer has not been decided
        yet to the armed transfer callable (runs between prefill and
        decode in :meth:`_tick`, so an accepted slot never pays a
        local decode tick). A decline is sticky per request — the
        colocate fallback decodes it here to the end rather than
        re-negotiating every tick."""
        for i, slot in enumerate(list(self._slots)):
            if slot is None:
                continue
            req = slot["req"]
            if req.future.done() or getattr(req, "_xfer_declined",
                                            False):
                continue
            try:
                snap = self.snapshot_slot(i)
            except Exception:   # noqa: BLE001 — sharded/typed decline
                snap = None
            moved = False
            if snap is not None:
                def _resnap(idx=i):
                    return self.snapshot_slot(idx)
                try:
                    moved = bool(self._transfer(req, snap, _resnap))
                except Exception:   # noqa: BLE001 — colocate fallback
                    moved = False
            if moved:
                # mirror the drain pass's migrate-out: the slot frees
                # WITHOUT fulfilling the future (the router's relay
                # delivers the decode replica's response into it)
                self._slots[i] = None
                self._release_blocks(slot)
                self._kv_checkpoints.pop(req.trace_id, None)
                self._transfer_out.inc()
                self.queue.finish("migrated")
                if self._trace_requests:
                    _spans.event("request.transfer_out",
                                 request=req.trace_id,
                                 tokens=len(req.tokens))
            else:
                req._xfer_declined = True
                self._colocated.inc()
                if self._trace_requests:
                    _spans.event("request.colocate_fallback",
                                 request=req.trace_id)
        self._occupancy.set(self.active_slots())

    def transfer_deliveries(self, frame):
        """The transfer-path fault point: the list of frames ONE
        delivery attempt actually lands at the decode peer —
        ``[frame]`` clean, ``[]`` dropped in flight, ``[frame, frame]``
        duplicated (``faults.slow_transfer`` / ``drop_transfer`` /
        ``dup_transfer``). Sequence numbers count deliveries from 1
        per engine, like handoff extraction numbers."""
        self._transfer_seq += 1
        return self.faults.on_transfer_send(self._transfer_seq, frame)

    # -- live KV handoff (extract / inject / checkpoint) -------------------
    def _handoff_geometry(self):
        """What must match EXACTLY between two engines for a KV
        snapshot (or spilled block) to be bit-meaningful in the
        receiver's pool: layout, layer count, cache dtype +
        quantization, head geometry, position space, and the
        quantization policy. Rides every frame's CRC-covered meta."""
        # the first ring level (a cache may hold state levels too)
        level = next(lv for lv in self._cache if "k" in lv)
        shape = tuple(int(d) for d in level["k"].shape)
        g = {"n_layers": len(self._cache),
             "dtype": str(level["k"].dtype),
             "quantized": "k_scale" in level,
             "heads": shape[1], "head_dim": shape[3],
             "max_len": int(self.max_len),
             "policy": self.policy.describe()
             if self.policy is not None else None}
        g.update(self._layout.geometry())
        return g

    @staticmethod
    def _geometry_mismatch(got, want):
        """Canonical-JSON comparison (tuples/lists, key order, and
        int/float JSON round-trips must not create false mismatches)."""
        try:
            return _integrity.frame_meta({"g": got}) != \
                _integrity.frame_meta({"g": want})
        except (TypeError, ValueError):
            return True

    def _snapshot_slot(self, i):
        """Seal slot ``i``'s live state: generated tokens + sampling
        config in the frame meta, the slot's KV rows (ring) or blocks
        (paged block-table walk) as the payload. Pure read — the slot
        keeps running."""
        slot = self._slots[i]
        req = slot["req"]
        specs, payload = _pack_arrays(
            self._layout.read_slot(self._cache, i, slot["alloc"]))
        doc = {"v": 1, "kind": "kv_snapshot",
               "geometry": self._handoff_geometry(),
               "prompt": [int(t) for t in req.prompt],
               "tokens": [int(t) for t in req.tokens],
               "pos": int(slot["pos"]), "tok": int(slot["tok"]),
               "max_new_tokens": int(req.max_new_tokens),
               "temperature": req.temperature, "top_k": req.top_k,
               "eos_id": req.eos_id, "trace_id": req.trace_id,
               # the request's OWN remaining deadline budget (None =
               # unlimited) — the survivor re-arms this clock, so a
               # migration never resets nor shortens a request's life
               "timeout_s": budget_remaining(req.deadline),
               "arrays": specs}
        meta = _integrity.frame_meta(doc)
        return {"meta": meta,
                "frame": _integrity.seal_frame(meta, payload)}

    def snapshot_slot(self, i):
        """Public extract: :meth:`_snapshot_slot` plus the fleet fault
        point (``corrupt_handoff`` / ``slow_handoff`` /
        ``kill_mid_handoff`` fire on the sealed frame here, exactly
        like wire sends). Sharded engines refuse typed — each device
        holds only a KV slice, so recompute re-dispatch is their
        failover path. It reads the tick in flight first, which places
        tokens and may finish slots, so it runs on the serve loop's own
        thread (its passes) or in :meth:`step` mode, never beside a
        running loop."""
        if self._thread is not None and \
                threading.current_thread() is not self._thread:
            raise RuntimeError("snapshot_slot runs on the serve loop or "
                               "in step() mode; the background loop is "
                               "running")
        if self.sharded:
            raise HandoffRefused(
                "sharded engines cannot snapshot a slot: each device "
                "holds only its slice of the KV state — re-dispatch "
                "(recompute) is the sharded failover path")
        # the rows and the request's stream must agree: a tick in
        # flight has run the slot's pending token already
        self._settle()
        if self._slots[i] is None:
            raise ValueError(f"slot {i} is empty")
        snap = self._snapshot_slot(i)
        self._handoff_seq += 1
        frame = self.faults.on_handoff_send(self._handoff_seq,
                                            snap["frame"])
        return {"meta": snap["meta"], "frame": frame}

    def inject_snapshot(self, meta, frame, timeout=None):
        """Validate a sealed KV snapshot and queue it for injection;
        returns the continuation's ServeFuture (same result shape as
        :meth:`submit`). Validation is synchronous and REFUSES typed
        (:class:`HandoffRefused`, counted) on a CRC failure or any
        geometry/policy mismatch — corrupt or wrong-shape KV is never
        written into the pool. A validated snapshot waits for a free
        slot (and, paged, its block reservation) exactly like an
        admitted request; continuation after placement is bitwise
        identical to an uninterrupted greedy run."""
        if self._crashed is not None:
            raise ReplicaCrashed(
                f"engine crashed ({self._crashed}); not accepting "
                "snapshots")
        if self._draining or self._stopped:
            raise EngineDraining(
                "engine is draining/stopped; not accepting snapshots")
        if self.sharded:
            self._handoff_refused.inc()
            raise HandoffRefused(
                "sharded engines do not accept KV snapshots: the pool "
                "is sliced over the mesh")
        try:
            payload = _integrity.open_frame(meta, frame)
            doc = _integrity.parse_frame_meta(meta)
        except _integrity.IntegrityError as e:
            self._handoff_refused.inc()
            raise HandoffRefused(f"snapshot frame refused: {e}")
        if doc.get("kind") != "kv_snapshot":
            self._handoff_refused.inc()
            raise HandoffRefused(
                f"frame kind {doc.get('kind')!r} is not a KV snapshot")
        want = self._handoff_geometry()
        if self._geometry_mismatch(doc.get("geometry"), want):
            self._handoff_refused.inc()
            raise HandoffRefused(
                f"snapshot geometry {doc.get('geometry')} does not "
                f"match this engine's {want}")
        try:
            arrays = _unpack_arrays(doc["arrays"], payload)
            prompt = np.asarray(doc["prompt"], np.int32).reshape(-1)
            pos, tok = int(doc["pos"]), int(doc["tok"])
            max_new = int(doc["max_new_tokens"])
        except (_integrity.IntegrityError, KeyError, TypeError,
                ValueError) as e:
            self._handoff_refused.inc()
            raise HandoffRefused(f"snapshot refused: {e}")
        err = self._layout.never_fits(int(prompt.size), max_new)
        if err is not None:
            self._handoff_refused.inc()
            raise HandoffRefused(
                f"snapshot can never be placed here: {err}")
        # the request keeps ITS deadline (snapshot-carried remainder);
        # `timeout` bounds only how long the snapshot may wait for a
        # slot — a handoff budget must not shorten the request's life
        req = Request(prompt, max_new_tokens=max_new,
                      temperature=doc.get("temperature", 0.0),
                      top_k=doc.get("top_k"),
                      eos_id=doc.get("eos_id"),
                      timeout=doc.get("timeout_s"),
                      trace_id=doc.get("trace_id"))
        req.tokens = [int(t) for t in doc.get("tokens", [])]
        self._handoff_in.inc()
        done = (len(req.tokens) >= req.max_new_tokens or
                (req.eos_id is not None and req.tokens and
                 req.tokens[-1] == req.eos_id))
        if done:
            # the dying replica finished it between snapshot and send
            req.future.set_result({"tokens": list(req.tokens),
                                   "prompt_len": int(prompt.size),
                                   "ttft_s": None,
                                   "queue_wait_s": None})
            self.queue.finish("completed")
            return req.future
        self._injects.append((req, {"pos": pos, "tok": tok}, arrays,
                              deadline_in(timeout)))
        self._wake.set()
        return req.future

    def _place_injects(self, now):
        """Move validated snapshots into free slots, once what the
        layout reserves for them fits (BlockPoolExhausted is
        backpressure: the snapshot stays pending)."""
        while self._injects:
            free = [i for i, s in enumerate(self._slots) if s is None]
            if not free:
                return
            req, state, arrays, place_by = self._injects[0]
            if req.expired(now) or \
                    (place_by is not None and now > place_by):
                self._injects.popleft()
                if not req.future.done():
                    req.future.set_error(RequestTimeout(
                        "deadline passed before the snapshot could "
                        "be placed"))
                    self.queue.finish("timed_out")
                continue
            try:
                alloc = self._layout.reserve(req.prompt,
                                             req.max_new_tokens)
            except BlockPoolExhausted:
                return              # backpressure: retry next tick
            self._injects.popleft()
            try:
                self._cache = self._layout.write_slot(
                    self._cache, arrays, free[0], alloc)
            except Exception as e:  # noqa: BLE001 — typed refusal below
                if alloc is not None:
                    # never cache the partially-written blocks
                    self._layout.release(alloc, req.prompt, cache=False)
                self._handoff_refused.inc()
                if not req.future.done():
                    req.future.set_error(HandoffRefused(
                        f"snapshot write failed: {e}"))
                    self.queue.finish("failed")
                continue
            self._slots[free[0]] = {"req": req, "pos": state["pos"],
                                    "tok": state["tok"],
                                    "alloc": alloc}
            if self._trace_requests:
                _spans.event("request.injected",
                             request=req.trace_id, slot=free[0],
                             tokens=len(req.tokens))

    def _checkpoint_inflight(self):
        """Cadence crash armor: snapshot every active slot to host
        memory, keyed by trace id. Best-effort — a checkpoint failure
        must never take the serve loop down."""
        if self.sharded:
            return
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            try:
                snap = self._snapshot_slot(i)
            except Exception:       # noqa: BLE001 — best-effort
                continue
            self._kv_checkpoints[slot["req"].trace_id] = snap
            self._ckpt_count.inc()

    def take_kv_checkpoint(self, trace_id):
        """Newest cadence checkpoint for ``trace_id`` (None when none
        exists). Host memory, so it survives a serve-loop crash — the
        fleet router's re-dispatch injects it into a survivor and
        resumes mid-stream instead of from token zero."""
        return self._kv_checkpoints.get(str(trace_id))

    # -- host-RAM spill tier plumbing (BlockManager's device access) -------
    def _spill_block_read(self, bid):
        """Pull ONE pool block's rows (every layer, payloads and
        scales) to host for the spill tier."""
        specs, payload = _pack_arrays(
            self._layout.read_block(self._cache, bid))
        doc = {"v": 1, "kind": "kv_block",
               "geometry": self._handoff_geometry(), "arrays": specs}
        return _integrity.frame_meta(doc), payload

    def _spill_block_write(self, bid, meta, payload):
        """Restore one spilled block's rows into pool block ``bid``.
        Raises on any mismatch — the BlockManager catches and degrades
        to re-prefilling the span, never writes a wrong block."""
        doc = _integrity.parse_frame_meta(meta)
        if doc.get("kind") != "kv_block" or self._geometry_mismatch(
                doc.get("geometry"), self._handoff_geometry()):
            raise HandoffRefused(
                "spilled block does not match this engine's pool "
                "geometry")
        self._cache = self._layout.write_block(
            self._cache, bid, _unpack_arrays(doc.get("arrays", ()),
                                             payload))

    # -- deadline drain (handoff pass) -------------------------------------
    def _drain_handoff_pass(self, now):
        """Migrate what cannot finish inside the drain budget: queued
        requests outright (they would cost a full prefill + decode),
        and any active slot whose remaining tokens — at the EWMA tick
        cost, plus a snapshot/transfer reserve — overrun the budget.
        Requests that fit keep decoding here and finish normally."""
        budget = budget_remaining(self._drain_deadline, now)
        for req in self.queue.pop_batch(len(self.queue), now):
            self._handoff_request(req, None, budget)
        per_tick = max(self._tick_ewma, 1e-4)
        for i, slot in enumerate(list(self._slots)):
            if slot is None:
                continue
            budget = budget_remaining(self._drain_deadline)
            req = slot["req"]
            remaining = req.max_new_tokens - len(req.tokens)
            if budget is None or remaining * per_tick \
                    + self._drain_reserve <= budget:
                continue            # it fits: let it finish here
            snap = None
            try:
                snap = self.snapshot_slot(i)
            except Exception:       # noqa: BLE001 — recompute handoff
                snap = None
            self._slots[i] = None
            self._release_blocks(slot)
            self._handoff_request(req, snap, budget)
        self._occupancy.set(self.active_slots())

    def _handoff_request(self, req, snapshot, budget):
        """One rung of the fallback ladder: offer the request (with
        its snapshot when one exists) to the drain's handoff callable;
        a decline or error falls back to failing it typed with
        :class:`EngineDraining` — the fleet router's recompute
        re-dispatch picks it up with the remaining deadline budget."""
        ok = False
        try:
            ok = bool(self._handoff(req, snapshot, budget))
        except Exception:           # noqa: BLE001 — fallback below
            ok = False
        if ok:
            self._handoff_out.inc()
            self.queue.finish("migrated")
            if self._trace_requests:
                _spans.event("request.migrated",
                             request=req.trace_id,
                             snapshot=snapshot is not None,
                             tokens=len(req.tokens))
            return
        self._handoff_fallback.inc()
        if not req.future.done():
            req.future.set_error(EngineDraining(
                "drain deadline: request was not migrated in time — "
                "re-dispatch with the remaining budget"))
            self.queue.finish("failed")

    # -- loop internals ----------------------------------------------------
    def _busy(self):
        return len(self.queue) > 0 or len(self._injects) > 0 or any(
            s is not None for s in self._slots) or \
            self._inflight is not None

    def _release_blocks(self, slot):
        """Return what a finished/failed sequence had reserved to its
        layout (paged: its full prompt blocks enter the prefix
        cache)."""
        alloc = slot.get("alloc")
        if alloc is not None:
            self._layout.release(alloc, slot["req"].prompt)

    def _count_inflight(self):
        return self.active_slots()

    def _fail_inflight(self, error):
        self._inflight = None       # its rows' requests fail just below
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._slots[i] = None
                self._release_blocks(slot)
                if not slot["req"].future.done():
                    slot["req"].future.set_error(error)
                    self.queue.finish("failed")
        # validated-but-unplaced snapshot injects die here too —
        # exactly-once forbids futures that never resolve
        while self._injects:
            req, _state, _arrays, _by = self._injects.popleft()
            if not req.future.done():
                req.future.set_error(error)
                self.queue.finish("failed")
        self._occupancy.set(0)

    def _fail_batch(self, batch, exc):
        # popped-but-never-slotted requests carry what the admit
        # predicate reserved for them: give it back before failing them
        for req in batch:
            if req._alloc is not None:
                self._layout.release(req._alloc, req.prompt)
                req._alloc = None
        super()._fail_batch(batch, exc)

    def _finish_slot(self, i, status="completed"):
        slot = self._slots[i]
        self._slots[i] = None
        self._release_blocks(slot)
        req = slot["req"]
        # a finished request's cadence checkpoint is dead weight
        self._kv_checkpoints.pop(req.trace_id, None)
        if self._trace_requests:
            _spans.event("request.delivered", request=req.trace_id,
                         status=status, tokens=len(req.tokens))
        if status == "completed":
            req.future.set_result({
                "tokens": list(req.tokens),
                "prompt_len": int(req.prompt.size),
                "ttft_s": (req.first_token_at - req.submitted_at
                           if req.first_token_at else None),
                "queue_wait_s": (req.admitted_at - req.submitted_at
                                 if req.admitted_at else None)})
        elif status == "timed_out":
            # same type a queued expiry raises: callers catch ONE
            # timeout error regardless of where the deadline hit
            req.future.set_error(RequestTimeout(
                f"deadline passed mid-generation after "
                f"{len(req.tokens)} tokens"))
        else:
            req.future.set_error(ServingError(status))
        self.queue.finish(status)

    def _sample_and_place(self, req, tokens, logits, row, at):
        """Row ``row`` of a program's output resolved into the request's
        next token and placed on its stream; returns ``(token, done)``.
        ``tokens`` holds every row's argmax as the program took it,
        which IS the token of a greedy request; a sampling request
        draws from its row of ``logits`` (read back only in a tick that
        serves such a request) with its own ``rng``. ``at`` is the
        token's stamp (``ServeFuture.token_times``): the clock as the
        program's output reached the host, one reading for the whole
        batch."""
        tok = int(tokens[row]) if req.temperature == 0 else \
            _decode.sample_logits(logits[row],
                                  temperature=req.temperature,
                                  top_k=req.top_k, rng=req.rng)
        req.tokens.append(tok)
        req.future.token_times.append(at)
        self._tokens_total.inc()
        return tok, (len(req.tokens) >= req.max_new_tokens or
                     (req.eos_id is not None and tok == req.eos_id))

    def _tick(self):
        """One continuous-batching tick, as ONE ``serve.tick`` span
        whose phases name the host work around the two programs;
        ``serve.prefill`` and ``serve.decode`` are spans of their own
        inside it (docs/serving.md has the table)."""
        with _spans.span("serve.tick", tick=self._tick_count,
                         admitted=0, active=0) as tick:
            self._tick_body(tick)

    def _tick_body(self, tick):
        now = time.monotonic()
        tick_t0 = now
        # a pass reads or rewrites the slots' state and their rows, so a
        # decode tick in flight is read before any runs
        checkpoint = bool(self.snapshot_every) and \
            self._tick_count % self.snapshot_every == 0
        passes = self._draining or bool(self._injects) or \
            self._transfer is not None or checkpoint
        if passes:
            self._settle()
        # 0) deadline drain: migrate what the budget cannot cover;
        #    then place validated snapshot injects into free slots
        if self._draining and self._handoff is not None:
            with tick.phase("handoff"):
                self._drain_handoff_pass(now)
        if self._injects:
            with tick.phase("inject"):
                self._place_injects(now)
        # 1) reap deadline-expired in-flight requests (their slot frees
        #    mid-batch — that is the continuous part of the batching)
        with tick.phase("reap"):
            for i, slot in enumerate(self._slots):
                if slot is not None and slot["req"].expired(now):
                    self._finish_slot(i, status="timed_out")

        # 2) admit: fill free slots, a fixed-width prefill batch per
        #    tick; where the layout reserves (its ``admit``), a request
        #    that does not fit right now stays at the head of the queue
        batch = []
        with tick.phase("admit"):
            free = [i for i, s in enumerate(self._slots) if s is None]
            if free and len(self.queue) > 0:
                batch = self.queue.pop_batch(
                    min(len(free), self.prefill_batch), now,
                    admit=self._layout.admit)
            tick.attrs["admitted"] = len(batch)
            tick.attrs["queue_depth"] = len(self.queue)
        if batch:
            try:
                with _spans.span("serve.prefill", n=len(batch)) as sp:
                    self._run_prefill(batch, free, sp)
            except Exception as e:
                # popped-but-not-yet-slotted requests are in
                # neither the queue nor the slot table: the crash
                # path can't see them, so fail them HERE or they
                # hang forever (exactly-once applies to errors too)
                self._fail_batch(batch, e)
                raise

        # 2b) disaggregated pools: offer freshly-prefilled slots to the
        #     decode pool BEFORE paying a local decode tick (an
        #     accepted transfer frees the slot; a declined one decodes
        #     here — the colocate fallback)
        if self._transfer is not None:
            with tick.phase("transfer"):
                self._transfer_pass()

        # 3) decode: one token for EVERY active slot, one fixed program;
        #    where nothing needs the host between two ticks, the next is
        #    dispatched before this one is read
        active = tick.attrs["active"] = self.active_slots()
        if active or self._inflight is not None:
            reason = self._serial_reason(passes)
            if reason is not None:
                self._settle()
            if reason is None or self.active_slots():
                with self._decode_span() as sp:
                    self._run_decode(sp, reason)
        with tick.phase("post"):
            self._occupancy.set(self.active_slots())
            self._sample_hbm()
            # 4) cadence crash armor + the drain pass's tick-cost EWMA
            if checkpoint:
                self._checkpoint_inflight()
            dt = time.monotonic() - tick_t0
            self._tick_ewma = dt if not self._tick_ewma \
                else 0.8 * self._tick_ewma + 0.2 * dt

    def _dispatch(self, fn, rec, program, names, host, sp):
        """One call of a serve program on the DONATED state, which comes
        back threaded into ``self._cache``; returns the program's other
        output. ``sp`` is the open span: the call alone is its phase
        ``call``, and the engine thread's CPU time here goes to its
        attr ``dispatch_cpu_s`` (against the phase's wall time: the
        thread ran, or waited for the interpreter lock or inside the
        call; summed where a span dispatches twice). A call that traced
        (the ``n_traces`` delta) is
        attributed: a first compile, or the retrace that breaks the
        one-trace contract, with what changed."""
        cpu0 = time.thread_time()
        n0 = rec["n_traces"]
        t0 = time.perf_counter()
        cc0 = _cache_counts()
        self._cache, out = _quiet_donation(sp, fn, self._P, self._cache,
                                           *host)
        if rec["n_traces"] > n0:
            _attribute_trace(rec, self._reg, program, list(host), names,
                             t0, cc0)
        sp.attrs["dispatch_cpu_s"] = sp.attrs.get("dispatch_cpu_s", 0.0) \
            + time.thread_time() - cpu0
        return out

    def _reads(self, out, sampling):
        """The parts of a program's ``out`` a tick brings to the host:
        ``(tokens, stats or None, logits or None)``."""
        return (out[0],
                out[-1] if self._record_stats is not None else None,
                out[1] if sampling else None)

    def _read_out(self, out, sp, program, requests):
        """What the tick needs of a program's ``out`` (``(tokens,
        logits[, stats])``, ``kv_cache.with_tokens``), to the host;
        returns ``(tokens, logits or None)``. The tokens always (4
        bytes a row) and with them the adapter's counts
        (``stats_recorder``; what the recorder returns goes on the
        span). The ``(rows, V)`` logits only where one of the
        ``requests`` this call served samples — with ``temperature ==
        0`` a row's token is its argmax whatever ``top_k`` says — and
        otherwise they stay on the device and go with their
        reference.

        Two phases of ``sp`` split it: ``ready``, the wait until the
        program has made what is read (its copies to the host are
        asked for first, so they follow the program as a plain read's
        would), and ``fetch``, those arrays to numpy once they are."""
        import jax
        sampling = any(r.temperature != 0 for r in requests)
        what = sp.attrs["readback"] = "logits" if sampling else "tokens"
        self._readbacks.inc(program=program, what=what)
        read = self._reads(out, sampling)
        with sp.phase("ready"):
            for leaf in jax.tree.leaves(read):
                leaf.copy_to_host_async()
            jax.block_until_ready(read)
        with sp.phase("fetch"):
            if self._record_stats is None:
                tokens = np.asarray(out[0])
            else:
                tokens, stats = jax.device_get(read[:2])
            logits = np.asarray(out[1]) if sampling else None
        if self._record_stats is not None:
            sp.attrs.update(self._record_stats(program, stats))
        return tokens, logits

    def _run_prefill(self, batch, free, sp):
        """``sp`` is the open ``serve.prefill`` span, split into
        ``pack`` (the layout's numpy inputs), ``dispatch`` (the program
        call until it returns; ``call`` inside it), ``readback`` (its
        output to the host; ``ready`` and ``fetch`` inside it) and
        ``place`` (first tokens sampled, slots filled)."""
        layout = self._layout
        with sp.phase("pack"):
            host, placed, n_tokens = layout.pack_prefill(
                batch, free, sp.attrs)
            self._prefill_tok.inc(n_tokens)
        with sp.phase("dispatch"):
            out = self._dispatch(self._prefill, self._prefill_rec,
                                 "serve_prefill", layout.prefill_names,
                                 host, sp)
        with sp.phase("readback"):
            tokens, logits = self._read_out(out, sp, "prefill", batch)
        with sp.phase("place"):
            at = time.monotonic()
            for b, (req, slot_idx, alloc) in enumerate(placed):
                req._alloc = None   # the slot owns the reservation now
                req.first_token_at = at
                self._ttft.observe(at - req.submitted_at)
                self._prefills.inc()
                if self._trace_requests:
                    hit = {} if alloc is None else \
                        {"prefix_hit_tokens": int(alloc.shared_tokens)}
                    _spans.event("request.prefill",
                                 request=req.trace_id, slot=slot_idx,
                                 prompt_len=int(req.prompt.size), **hit)
                # the first generated token sits at position prompt_len;
                # its k/v are written by the NEXT decode tick
                tok, done = self._sample_and_place(
                    req, tokens, logits, b, at)
                self._slots[slot_idx] = {
                    "req": req, "pos": int(req.prompt.size), "tok": tok,
                    "alloc": alloc}
                if done:
                    self._finish_slot(slot_idx)

    def _serial_reason(self, passes):
        """Why the next decode tick cannot be dispatched before the one
        in flight is read, or None: its rows hold candidates the host
        has to walk, a live request samples from logits on the host, the
        engine drains, or a pass (``passes``) reads the slots."""
        if self._layout.candidate_axis:
            return "candidates"
        if any(s is not None and s["req"].temperature != 0
               for s in self._slots):
            return "sampling"
        if self._draining:
            return "drain"
        return "pass" if passes else None

    @contextlib.contextmanager
    def _decode_span(self):
        """One ``serve.decode`` span: the host's share of one delivered
        decode tick, observed into ``serve_token_seconds``."""
        t0 = time.perf_counter()
        with _spans.span("serve.decode", ahead=0) as sp:
            yield sp
        # a PROFILED tick's dispatch runs under an active trace: its
        # inflated latency must not read as an SLO regression (the
        # sampling cost is serve_profile_capture_seconds)
        if not self._profiling_now:
            self._tok_lat.observe(time.perf_counter() - t0)

    def _settle(self):
        """Read the decode tick in flight, if one is, in a span of its
        own (``readback`` and ``sample``): before a pass, a serial tick,
        a snapshot or a retry."""
        rec, self._inflight = self._inflight, None
        if rec is not None:
            with self._decode_span() as sp:
                self._land(rec, sp)

    def _run_decode(self, sp, reason):
        """``sp`` is the open ``serve.decode`` span, split into ``pack``
        (the layout's numpy inputs, n-gram drafting), ``dispatch`` (the
        program call until it returns; ``call`` inside it), ``readback``
        (``ready``: wait for the device; ``fetch``: its output to the
        host) and ``sample`` (the per-slot accept walk, events,
        finishing). It delivers one tick.

        ``reason`` None: the tick in flight is read only after the next
        is dispatched (span attr ``ahead`` 1), which takes each row's
        token from it on the device, so the device runs through the
        host's read, walk and packing. The first tick after an idle or
        serial one is dispatched first. A request whose EOS the read
        shows has run one row more, which nothing reads. Otherwise
        (:meth:`_serial_reason`) the tick is dispatched and read."""
        if reason is None:
            rec = self._inflight or self._launch(sp, "serial", "first")
            self._inflight = self._launch(sp, "ahead", "none", rec)
            sp.attrs["ahead"] = int(self._inflight is not None)
        else:
            rec = self._launch(sp, "serial", reason)
        self._land(rec, sp)

    def _launch(self, sp, mode, reason, chained=None):
        """Pack and dispatch one decode tick over the live slots; its
        record for :meth:`_land`, or None where no row is due.
        ``chained``: the record of the tick in flight, whose tokens the
        rows of its requests take; a request that tick brings to
        ``max_new_tokens`` runs no further row."""
        import jax
        last = chained["reqs"] if chained is not None else {}
        slots = [None if s is None or (
            last.get(i) is s["req"]
            and len(s["req"].tokens) + 1 >= s["req"].max_new_tokens)
            else s for i, s in enumerate(self._slots)]
        if not any(slots):
            return None
        layout = self._layout
        attrs = {}
        with sp.phase("pack"):
            host, rows = layout.pack_decode(
                slots, attrs, not self._spec_throttled, last)
        reqs = {i: s["req"] for i, s in enumerate(slots) if s is not None}
        sampling = any(r.temperature != 0 for r in reqs.values())
        with sp.phase("dispatch"):
            out = self._dispatch(self._decode, self._decode_rec,
                                 "serve_decode", layout.decode_names,
                                 host, sp)
            layout.decoded(out)
            if not (sampling or self.sharded):
                out = (out[0], None, *out[2:])  # logits nothing reads
            # the tokens cross as soon as the device has them
            for leaf in jax.tree.leaves(self._reads(out, sampling)):
                leaf.copy_to_host_async()
        self._decode_steps.inc()
        self._decode_ticks.inc(mode=mode, reason=reason)
        return {"out": out, "reqs": reqs, "rows": rows, "attrs": attrs}

    def _land(self, rec, sp):
        """Read one dispatched tick and place its tokens on the slots
        that still hold the requests it ran; a request that finished
        meanwhile (on EOS, a deadline, a hand-off) has its row dropped.
        The span takes the tick's own attrs (what it read of the
        rings, the adapter's counts).

        Every row has candidates the ONE program scored: its pending
        token and, under speculation, drafts behind it. The walk emits
        the longest prefix of drafts matching what was sampled — each
        emitted token EXACTLY what sequential greedy decoding would have
        produced (the CI parity invariant) — and at one candidate it is
        one token a slot."""
        layout = self._layout
        reqs, rows = rec["reqs"], rec["rows"]
        sp.attrs.update(rec["attrs"])
        with sp.phase("readback"):
            tokens, logits = self._read_out(rec["out"], sp, "decode",
                                            reqs.values())
            if not layout.candidate_axis:
                tokens = tokens[:, None]
                if logits is not None:
                    logits = logits[:, None]
        with sp.phase("sample"):
            at = time.monotonic()
            trace = self._trace_requests
            for i, req in reqs.items():
                slot = self._slots[i]
                if slot is None or slot["req"] is not req:
                    continue
                row = rows.get(i) if rows is not None else None
                cnt = 1 if row is None else len(row)
                emitted = 0
                while True:
                    tok, done = self._sample_and_place(
                        req, tokens, logits, (i, emitted), at)
                    emitted += 1
                    # a draft equal to what was sampled is accepted: its
                    # k/v row is already right, so its score counts too
                    if done or emitted == cnt or row[emitted] != tok:
                        break
                if cnt > 1:
                    layout.note_accepted(emitted - 1)
                slot["pos"] += emitted
                slot["tok"] = tok
                # decimated past the first 16 tokens: a 4-slot engine
                # generating hundreds of tokens per request would
                # otherwise evict the whole flight-recorder ring
                # (capacity 1024) with ticks, beheading every request
                # lane and crash blackbox
                n_tok = len(req.tokens)
                if trace and (n_tok < 16 or n_tok % 16 < emitted):
                    _spans.event("request.decode_tick",
                                 request=req.trace_id, slot=i,
                                 pos=slot["pos"], emitted=emitted)
                if done:
                    self._finish_slot(i)


class BatchServingEngine(_EngineBase):
    """Stateless (non-autoregressive) serving: classifier zoo models
    and ONNX imports. One jitted fixed-width forward per tick over a
    padded batch of queued requests (module docstring)."""

    def __init__(self, model, *, input_shape, batch=8,
                 input_dtype=np.float32, policy=None, aot_store=None,
                 **kw):
        super().__init__(**kw)
        import jax
        from ..autograd_base import CTX
        from ..tensor import Tensor
        from .. import mixed_precision as mp
        from ..device import get_default_device

        self.model = model
        self.batch = int(batch)
        self.input_shape = tuple(int(d) for d in input_shape)
        self.input_dtype = np.dtype(input_dtype)
        self.policy = policy if policy is not None \
            else getattr(model, "_policy", None)
        dev = getattr(model, "dev", None) or get_default_device()

        # materialise lazily-initialised params with ONE eager eval
        # forward (ONNX imports already hold theirs; zoo models may not)
        x0 = Tensor(
            data=np.zeros((self.batch,) + self.input_shape,
                          self.input_dtype),
            device=dev, requires_grad=False)
        from ..quant.core import dequant_params_scope
        prev = CTX.training
        CTX.training = False
        try:
            with mp.policy_scope(self.policy), \
                    dequant_params_scope(model):
                model.forward(x0)
        finally:
            CTX.training = prev
        state_list = model._state_tensors()
        self._state_arrays = [t.data for t in state_list]
        rec = {"n_traces": 0}
        self._rec = rec

        def fwd(state_arrays, x):
            rec["n_traces"] += 1
            backup = [t.data for t in state_list]
            for t, a in zip(state_list, state_arrays):
                t.data = a
            prev = CTX.training
            CTX.training = False
            try:
                # a weight-quantized model (quant.quantize_params)
                # dequantizes IN GRAPH here too: the scope rebinds int8
                # payloads to payload x scale for the traced body only
                with mp.policy_scope(self.policy), \
                        dequant_params_scope(model):
                    out = model.forward(Tensor(data=x, device=dev,
                                               requires_grad=False))
            finally:
                CTX.training = prev
                for t, a in zip(state_list, backup):
                    t.data = a
            outs = out if isinstance(out, (list, tuple)) else (out,)
            leaves = [o.data if isinstance(o, Tensor) else o
                      for o in outs]
            if self.policy is not None:
                leaves = [self.policy.cast_output(x) for x in leaves]
            return leaves

        self._fwd = jax.jit(fwd)
        self._hbm_dev = _perf.first_jax_device(self._state_arrays)
        # warm spin-up: deserialize a previously exported batch
        # forward (honored-or-refused; a refusal compiles fresh).
        # n_traces reads 1: the trace happened in the exporting
        # process, and the no-retrace audit still holds.
        self._aot_store = None
        self._aot_source = None
        if aot_store is not None:
            from ..aot import export as _aot_export
            from ..observability import perf as _perf2
            if not isinstance(aot_store, _aot_export.AotStore):
                aot_store = _aot_export.AotStore(aot_store,
                                                 registry=self._reg)
            self._aot_store = aot_store
            t0 = time.perf_counter()
            avals = _aot_export.batch_program_avals(self)
            fn, _doc = aot_store.try_load_program(
                _aot_export.SERVE_BATCH, avals=avals,
                donate_argnums=(), policy=self.policy,
                jax_device=self._hbm_dev,
                expect_extra=_aot_export.batch_geometry(self))
            if fn is not None:
                self._fwd = fn
                rec["n_traces"] = 1
                sig = _perf2.step_signature([avals[1]])
                _perf2.record_compile(
                    _aot_export.SERVE_BATCH,
                    time.perf_counter() - t0, sig, source="aot",
                    registry=self._reg)
                rec["sig"] = sig
            self._aot_source = {_aot_export.SERVE_BATCH:
                                aot_store.outcomes.get(
                                    _aot_export.SERVE_BATCH, "fresh")}
        self._occupancy = self._reg.gauge(
            "serve_slot_occupancy", "active sequences in the slot array")
        self._reg.gauge("serve_slots",
                        "slot array width (max in-flight sequences)"
                        ).set(self.batch)

    def submit(self, x, timeout=None, trace_id=None):
        """Queue one input array of ``input_shape``; the future's
        result is the model's per-row output (array, or tuple for
        multi-output models)."""
        x = np.asarray(x, self.input_dtype)
        if x.shape != self.input_shape:
            self.queue.finish("rejected")
            raise ServingError(
                f"input shape {x.shape} != engine input_shape "
                f"{self.input_shape}")
        req = Request(None, payload=x, timeout=timeout,
                      trace_id=trace_id)
        return self._admit(req)

    def compiled_step_info(self):
        return {"n_traces": self._rec["n_traces"],
                "slots": self.batch,
                "input_shape": self.input_shape,
                "policy": self.policy.describe()
                if self.policy is not None else None,
                "aot": self._aot_source}

    def _busy(self):
        return len(self.queue) > 0

    def _fail_inflight(self, error):
        pass            # stateless: nothing lives between ticks

    def _tick(self):
        batch = self.queue.pop_batch(self.batch)
        if not batch:
            return
        self._occupancy.set(len(batch))
        x = np.zeros((self.batch,) + self.input_shape, self.input_dtype)
        for i, req in enumerate(batch):
            x[i] = req.payload
        t0 = time.perf_counter()
        n0 = self._rec["n_traces"]
        cc0 = _cache_counts()
        try:
            with _spans.span("serve.batch_forward", n=len(batch)):
                leaves = self._fwd(self._state_arrays, x)
        except Exception as e:
            # popped requests are invisible to the crash path's queue
            # drain — fail them here, exactly once
            self._fail_batch(batch, e)
            raise
        if self._rec["n_traces"] > n0:
            _attribute_trace(self._rec, self._reg, "serve_batch",
                             [x], ("input",), t0, cc0)
        # same rule as the autoregressive decode: a PROFILED tick's
        # trace-inflated latency stays out of the SLO series
        if not self._profiling_now:
            self._tok_lat.observe(time.perf_counter() - t0)
        leaves = [np.asarray(leaf) for leaf in leaves]
        for i, req in enumerate(batch):
            now = time.monotonic()
            req.first_token_at = now
            self._ttft.observe(now - req.submitted_at)
            row = tuple(leaf[i] for leaf in leaves)
            if self._trace_requests:
                _spans.event("request.delivered",
                             request=req.trace_id, status="completed")
            req.future.set_result(row[0] if len(row) == 1 else row)
            self.queue.finish("completed")
        self._occupancy.set(0)
        self._sample_hbm()


def _check_quant_policy(policy, target, *, weights_ok, cache_ok, hint):
    """A quantized policy the target cannot honor must FAIL at build —
    serving full fp32 while the caller believes they deployed int8 is
    the silent no-op this guard exists to prevent. ``hint`` names the
    working route for THIS target."""
    wq = getattr(policy, "weight_quant", None)
    cq = getattr(policy, "cache_quant", None)
    if wq is not None and not weights_ok:
        raise ValueError(
            f"policy {policy.name!r} requests {wq} weight quantization "
            f"but {target} cannot honor it; it would serve full-"
            f"precision weights silently. {hint}")
    if cq is not None and not cache_ok:
        raise ValueError(
            f"policy {policy.name!r} requests an {cq} KV cache but "
            f"{target} has no ring cache to quantize")


def build_engine(model, **kw):
    """The ``Model.compile_serving`` backend: autoregressive models
    (anything exposing ``decode_adapter``) get a :class:`ServingEngine`
    over their ring-cache adapter; everything else — the classifier
    zoo, ONNX imports — serves statelessly through a
    :class:`BatchServingEngine` (pass ``input_shape=``).

    Quantized policies are honored-or-refused: an adapter that does not
    declare ``supports_weight_quant`` / ``supports_cache_quant`` (the
    transformer adapter does, the char-rnn's (h,c) slot state cannot)
    rejects them typed at build, and a stateless engine accepts a
    weight-quant policy only over an already ``quantize_params``'d
    model (the cache axis is inert there — it has no KV cache)."""
    if hasattr(model, "decode_adapter"):
        adapter_kw = {}
        if "policy" in kw:
            adapter_kw["policy"] = kw.get("policy")
        adapter = model.decode_adapter(**adapter_kw)
        if kw.get("policy") is not None:
            _check_quant_policy(
                kw["policy"], f"{type(model).__name__}'s decode adapter",
                weights_ok=getattr(adapter, "supports_weight_quant",
                                   False),
                cache_ok=getattr(adapter, "supports_cache_quant",
                                 False),
                hint="Serve under a non-quantized policy (an in-place-"
                "quantized model's weights are dequantized at engine "
                "build either way)")
        ar_keys = ("slots", "max_len", "prefill_len", "prefill_batch",
                   "policy", "queue_capacity", "faults", "registry",
                   "telemetry_dir", "max_retries", "trace_requests",
                   "aot_store", "profile_every", "kv_layout",
                   "kv_block_size", "kv_blocks", "speculative_k",
                   "mesh", "model_shards", "spill_bytes",
                   "snapshot_every", "pool_role")
        unknown = sorted(set(kw) - set(ar_keys))
        if unknown:
            raise TypeError(
                f"unknown serving option(s) {unknown} for "
                f"autoregressive {type(model).__name__} "
                f"(accepted: {sorted(ar_keys)})")
        return ServingEngine(adapter, **kw)
    if "input_shape" not in kw:
        raise TypeError(
            "stateless serving needs input_shape=(per-sample shape); "
            f"{type(model).__name__} has no decode_adapter")
    bt_keys = ("input_shape", "batch", "input_dtype", "policy",
               "queue_capacity", "faults", "registry", "telemetry_dir",
               "max_retries", "trace_requests", "aot_store",
               "profile_every")
    unknown = sorted(set(kw) - set(bt_keys))
    if unknown:
        raise TypeError(
            f"unknown serving option(s) {unknown} for stateless "
            f"{type(model).__name__} (accepted: {sorted(bt_keys)})")
    if kw.get("policy") is not None:
        _check_quant_policy(
            kw["policy"], f"stateless {type(model).__name__} serving",
            weights_ok=bool(getattr(model, "_quant_pairs", None)),
            cache_ok=True,   # inert: a batch engine has no KV cache
            hint="Run quant.quantize_params(model) first, or use a "
            "non-quantized policy")
    return BatchServingEngine(model, **kw)


__all__ = ["ServingEngine", "BatchServingEngine", "build_engine"]
