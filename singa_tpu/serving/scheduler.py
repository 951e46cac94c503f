"""Request queue, per-request futures, and admission bookkeeping.

The serving engine's control plane is deliberately boring host-side
python: a bounded deque of :class:`Request` records and a
:class:`ServeFuture` per request that is fulfilled EXACTLY ONCE — the
delivery guard is a real invariant (chaos-tested with injected faults),
not a convention. Rejection is synchronous and loud: a full queue or a
draining engine refuses at ``submit`` time with a typed error, so a
load balancer can fail over instead of letting requests rot.

SLO metrics recorded here (all through the PR-6 observability
registry):

- ``serve_queue_depth`` (gauge) — requests waiting for a slot;
- ``serve_requests_total{status=...}`` (counter) — terminal outcome of
  every request: ``completed`` | ``rejected`` | ``timed_out`` |
  ``failed`` | ``cancelled``;
- ``serve_queue_wait_seconds`` (histogram) — submit until
  :meth:`RequestQueue.pop_batch` took the request off the queue; the
  same wait is also inside the engine's TTFT histogram (queue time is
  part of time-to-first-token, which is what the user feels), so TTFT
  splits into waiting and prefill.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque

import numpy as np

from ..observability import metrics as _metrics


class ServingError(RuntimeError):
    """Base class for serve-path failures."""


class QueueFull(ServingError):
    """Admission refused: the bounded request queue is at capacity."""


class EngineDraining(ServingError):
    """Admission refused: the engine is draining (finishing in-flight
    work, accepting nothing new) or already stopped."""


class RequestTimeout(ServingError):
    """The request's deadline passed before a response completed."""


class ReplicaCrashed(ServingError):
    """The replica that held this request died (serve-loop crash, wire
    failure, or a crashed engine refusing at the door). Unlike
    backpressure refusals this is a REPLICA failure, not a request
    failure: the request itself is pure submit args + a fresh id, so a
    fleet router may re-dispatch it to a survivor exactly once —
    deterministic greedy decode makes the retried response
    token-identical to the one the dead replica would have produced."""


class RequestShed(ServingError):
    """The fleet refused this request on purpose: sustained
    backpressure (QueueFull / BlockPoolExhausted across every admitted
    replica) tripped the shed policy. Fast-fail, typed, with a
    ``retry_after`` hint the gateway turns into a ``Retry-After``
    header — degrading loudly beats queueing into a timeout."""

    def __init__(self, message, retry_after=1.0):
        super().__init__(message)
        self.retry_after = float(retry_after)


class PoolSaturated(RequestShed):
    """The disaggregated decode pool refused this request AFTER the
    degradation ladder ran dry: brownout stepped generation down,
    colocate fallback (prefill replicas serving decode end-to-end)
    absorbed what it could, and the fleet still cannot place the
    request. A :class:`RequestShed` subclass, so the gateway's 503 +
    ``Retry-After`` contract applies unchanged — but typed, so tests
    and dashboards can tell pool saturation from generic overload."""


def deadline_in(timeout, now=None):
    """Monotonic deadline for a timeout budget; ``None`` timeout means
    no deadline. The single clock a request lives on: the gateway and
    the fleet router both derive engine-side timeouts AND client-side
    waits from one of these, so a retry inherits the true remainder."""
    if timeout is None:
        return None
    return (now if now is not None else time.monotonic()) \
        + float(timeout)


def budget_remaining(deadline, now=None):
    """Seconds left until ``deadline``, floored at 0.0 (``None``
    deadline → ``None``: unlimited)."""
    if deadline is None:
        return None
    return max(0.0, deadline - (now if now is not None
                                else time.monotonic()))


class HandoffRefused(ServingError):
    """A live-KV snapshot inject was refused, typed: the sealed frame
    failed :func:`integrity.open_frame` (corruption in flight), or its
    geometry/policy metadata does not match this engine's compiled
    programs (layout, dtype, head/block shape, cache quantization).
    Corrupt or wrong-shape KV state is NEVER written into a survivor's
    pool — the caller falls back to plain recompute re-dispatch with
    whatever deadline budget remains."""


class BlockPoolExhausted(ServingError):
    """Admission refused: the paged KV block pool cannot cover the
    request's ``prompt + max_new_tokens`` reservation without evicting
    a LIVE sequence's blocks (which never happens — only unreferenced
    cached prefixes are reclaimable). Raised synchronously at
    ``submit`` when the request could NEVER fit the pool; a request
    that merely has to wait for in-flight sequences to finish stays
    queued instead (backpressure, not failure)."""


class ServeFuture:
    """One request's response slot: fulfilled exactly once.

    ``result(timeout)`` blocks for the response and re-raises the
    request's error. ``deliveries`` counts fulfillment attempts — the
    exactly-once chaos test asserts it is 1 for every request, and a
    second delivery attempt raises instead of silently overwriting.

    ``token_times`` holds one ``time.monotonic()`` stamp per token the
    engine that owns this future generated, in order: the engine reads
    the clock once when a program's output has reached the host and
    gives that reading to every token of that prefill batch or decode
    tick (tokens a speculative tick emits together share it). The first
    entry is the request's ``first_token_at``; consecutive differences
    are the inter-token gaps. The engine's thread appends while the
    request runs; read it once the future is done. A request injected
    or migrated into another engine continues under a NEW future there:
    that list starts empty and holds stamps only for the tokens that
    engine generates (its clock), so it lines up with the tail of
    ``result()["tokens"]``; the tokens the snapshot carried have no
    stamp and the continuation's ``ttft_s`` is None."""

    def __init__(self):
        self._event = threading.Event()
        self._lock = threading.Lock()
        self._result = None
        self._error = None
        self.deliveries = 0
        self.token_times: list = []

    def _fulfill(self, result=None, error=None):
        with self._lock:
            self.deliveries += 1
            if self._event.is_set():
                raise RuntimeError(
                    "double delivery: this request already has a "
                    "response (exactly-once violation)")
            self._result = result
            self._error = error
            self._event.set()

    def set_result(self, result):
        self._fulfill(result=result)

    def set_error(self, error):
        self._fulfill(error=error)

    def done(self):
        return self._event.is_set()

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise RequestTimeout(
                f"no response within {timeout}s (request still "
                "in flight)")
        if self._error is not None:
            raise self._error
        return self._result


class Request:
    """One generation request: prompt token ids + sampling config.

    ``rng`` is per-request (seeded) so a retried/re-ordered schedule
    cannot change what any single request samples. ``trace_id`` names
    the request in the per-request flight-recorder trace (minted at
    the gateway for HTTP traffic; defaults to ``req-<n>``) — every
    span/event the engine records for this request carries it."""

    _ids = itertools.count(1)

    def __init__(self, prompt, max_new_tokens=16, temperature=0.0,
                 top_k=None, eos_id=None, seed=0, timeout=None,
                 payload=None, trace_id=None):
        self.id = next(Request._ids)
        self.trace_id = str(trace_id) if trace_id else f"req-{self.id}"
        self.prompt = np.asarray(prompt, np.int32).reshape(-1) \
            if prompt is not None else None
        self.payload = payload          # stateless-mode input array
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.eos_id = eos_id
        self.rng = np.random.RandomState(int(seed) + self.id)
        self.submitted_at = time.monotonic()
        # `is not None`, not truthiness: timeout=0 means "already due"
        # (a fail-fast probe), the opposite of no deadline
        self.deadline = (self.submitted_at + float(timeout)
                         if timeout is not None else None)
        self.admitted_at = None         # set where pop_batch takes it
        self.first_token_at = None      # set by the engine at prefill
        self.future = ServeFuture()
        self.tokens: list = []          # generated ids (engine-owned)
        # what the engine's KV layout reserved for it when it was
        # popped, until a slot owns it (paged: a SlotAlloc)
        self._alloc = None

    def expired(self, now=None):
        return self.deadline is not None and \
            (now if now is not None else time.monotonic()) > self.deadline


class RequestQueue:
    """Bounded FIFO admission queue with deadline sweeping."""

    def __init__(self, capacity=64, registry=None):
        self.capacity = int(capacity)
        self._q = deque()
        self._lock = threading.Lock()
        self._reg = registry if registry is not None \
            else _metrics.default_registry()
        self._depth = self._reg.gauge(
            "serve_queue_depth", "requests admitted but not yet slotted")
        self._outcomes = self._reg.counter(
            "serve_requests_total",
            "terminal request outcomes", labels=("status",))
        self._wait = self._reg.histogram(
            "serve_queue_wait_seconds",
            "request submit until it was taken off the queue for a "
            "slot (the waiting part of serve_ttft_seconds)")

    def finish(self, status):
        """Record a request's terminal outcome (engine calls this at
        the single point each future is fulfilled)."""
        self._outcomes.inc(status=status)

    def put(self, req):
        """Admit or raise :class:`QueueFull` (counted as rejected)."""
        with self._lock:
            if len(self._q) >= self.capacity:
                full = True
            else:
                self._q.append(req)
                full = False
            depth = len(self._q)
        self._depth.set(depth)
        if full:
            self.finish("rejected")
            raise QueueFull(
                f"request queue at capacity ({self.capacity}); "
                "retry against another replica")

    def pop_batch(self, n, now=None, admit=None):
        """Up to ``n`` non-expired requests, FIFO. Expired requests are
        fulfilled with :class:`RequestTimeout` here (counted
        ``timed_out``) — they never consume a slot. ``admit`` (an
        optional predicate) gates each pop: the first refused request
        STOPS the batch and stays at the head of the queue — the paged
        engine's block-pool backpressure, FIFO-fair by construction
        (nothing behind an unplaceable request jumps it)."""
        taken, expired = [], []
        with self._lock:
            while self._q and len(taken) < n:
                req = self._q[0]
                if req.expired(now):
                    expired.append(self._q.popleft())
                    continue
                if admit is not None and not admit(req):
                    # the blocked head stays — but the deadline sweep
                    # must still reach everything queued BEHIND it, or
                    # a timed-out request would sit unresolved for as
                    # long as the head waits for blocks
                    keep = deque()
                    while self._q:
                        r = self._q.popleft()
                        (expired if r.expired(now)
                         else keep).append(r)
                    self._q.extend(keep)
                    break
                taken.append(self._q.popleft())
            depth = len(self._q)
        self._depth.set(depth)
        if taken:
            # a fresh reading: `now` is the caller's tick start, and a
            # request can have been submitted since
            admitted_at = time.monotonic()
            for req in taken:
                req.admitted_at = admitted_at
                self._wait.observe(admitted_at - req.submitted_at)
        for req in expired:
            req.future.set_error(RequestTimeout(
                "deadline passed while queued"))
            self.finish("timed_out")
        return taken

    def drain_pending(self, error):
        """Fulfill every queued request with ``error`` (hard-stop
        path; graceful drain empties the queue by serving it)."""
        with self._lock:
            pending = list(self._q)
            self._q.clear()
        self._depth.set(0)
        for req in pending:
            if not req.future.done():
                req.future.set_error(error)
                self.finish("failed")
        return len(pending)

    def __len__(self):
        with self._lock:
            return len(self._q)


__all__ = ["ServingError", "QueueFull", "EngineDraining",
           "RequestTimeout", "ReplicaCrashed", "RequestShed",
           "PoolSaturated", "BlockPoolExhausted", "HandoffRefused",
           "ServeFuture", "Request", "RequestQueue", "deadline_in",
           "budget_remaining"]
