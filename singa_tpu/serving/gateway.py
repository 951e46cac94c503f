"""Stdlib HTTP gateway over a serving engine.

Deliberately dependency-free (``http.server``): the gateway is the thin
edge of the engine, not a web framework. Endpoints:

- ``POST /v1/generate`` — autoregressive engines. JSON body
  ``{"prompt": [ids...], "max_new_tokens": n, "temperature": t,
  "top_k": k, "eos_id": id, "seed": s, "timeout": secs}`` (everything
  but ``prompt`` optional); 200 with the completed
  ``{"tokens": [...], "prompt_len": n, "ttft_s": ...,
  "queue_wait_s": ...}``.
- ``POST /v1/predict`` — stateless engines. ``{"input": nested list}``;
  200 with ``{"output": nested list}`` (or ``"outputs"`` for
  multi-output models).
- ``GET /healthz`` — replica health JSON. **503 while draining or
  crashed**, 200 otherwise — this is the load-balancer contract: a
  draining replica stops receiving traffic because it says so here and
  on every refused submit, not because anyone remembered to deregister
  it.
- ``GET /metrics`` / ``GET /metrics.json`` — Prometheus text / snapshot
  JSON of the engine's registry (quantile summaries included).
- ``GET /trace.json`` — the process flight-recorder ring (in-flight
  spans included) rendered as a Chrome-trace document that opens in
  ui.perfetto.dev — per-request timeline lanes keyed by the request
  ids this gateway minted.
- ``GET /timeline.json`` — the newest profiled tick's step-timeline
  decomposition (compute/collective/memcpy/host/idle fractions +
  exposed-communication seconds; engines built with
  ``profile_every=N`` refresh it continuously).
- ``POST /drain`` — begin a graceful drain; 202 immediately (the drain
  finishes in the background; watch ``/healthz``). ``?deadline=2.5``
  (or ``{"deadline": 2.5}``) arms a preemption budget: finish what
  fits, hand off / fail-typed the rest by the deadline.
- ``POST /v1/inject`` — live-KV handoff receive: ``{"meta": b64,
  "frame": b64, "timeout": secs}`` (a sealed snapshot from a draining
  peer); 200 with the continuation's response, **409 on a typed
  refusal** (corrupt frame, geometry mismatch) — the sender falls back
  to recompute re-dispatch, corrupt KV is never injected.

Request tracing: every ``/v1/generate`` / ``/v1/predict`` call gets a
request id (``request_id`` in the body to supply your own, else a
fresh hex id), passed to the engine as its trace id and echoed in the
response — the handle that finds this request's lane in
``/trace.json``.

Refusal mapping: draining/full queue/exhausted block pool → 503 (fail
over), shed under sustained backpressure → 503 with a ``Retry-After``
header (back off, don't hammer), request deadline → 504, malformed
request → 400, oversized/undeclared body → 413 (refused before a byte
is read), serve-loop crash → 500. Every generate/predict request
lives on ONE deadline: the engine-side timeout and the handler's wait
derive from the same clock, so a fleet retry inherits the true
remaining budget. Handler threads are non-daemon and joined at
``server_close()``, so a drained process never exits with a response
half-written.
"""

from __future__ import annotations

import base64
import json
import math
import threading
import uuid
from urllib.parse import parse_qs, urlsplit

from .scheduler import (BlockPoolExhausted, EngineDraining,
                        HandoffRefused, QueueFull, ReplicaCrashed,
                        RequestShed, RequestTimeout, ServingError,
                        budget_remaining, deadline_in)


def _result_doc(res):
    import numpy as np
    if isinstance(res, dict):
        return res
    if isinstance(res, tuple):
        return {"outputs": [np.asarray(r).tolist() for r in res]}
    return {"output": np.asarray(res).tolist()}


def serve_gateway(engine, host="127.0.0.1", port=0, replica=None,
                  default_timeout=120.0, max_body_bytes=8 << 20,
                  retry_after=None):
    """Start the gateway on a daemon thread. Returns ``(server, port)``;
    ``server.shutdown(); server.server_close()`` stops it (close joins
    in-flight handler threads). ``replica`` (a
    :class:`~singa_tpu.serving.fleet.ServingReplica`) upgrades
    ``/healthz`` to the full replica view and routes ``/drain`` through
    the replica's drain contract. ``engine`` may also be a
    :class:`~singa_tpu.serving.fleet.FleetRouter` — a fleet-front
    gateway: ``/healthz`` lists every replica (200 while at least one
    serves), ``/drain`` drains them all, and requests ride the
    router's breaker/re-dispatch/shed machinery. POST bodies larger
    than ``max_body_bytes`` (or with a missing/garbage
    ``Content-Length``) are refused 413 before a byte is read — the
    gateway never buffers unbounded input. Binds localhost by
    default — put a real LB/mesh in front for anything public.

    ``retry_after`` (seconds, or a zero-arg callable returning
    seconds-or-None) sets the ``Retry-After`` on backpressure 503s.
    Wire it to :meth:`Autoscaler.retry_after_hint
    <singa_tpu.serving.autoscaler.Autoscaler.retry_after_hint>` and a
    503 emitted while the fleet is scaling up tells clients when
    capacity actually lands — the rolling median of observed
    spawn-to-ready durations — instead of a constant; None (or no
    hint) falls back to the constant 1s."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from ..observability.export import render_prometheus

    is_fleet = hasattr(engine, "replicas")

    def retry_after_header():
        v = retry_after() if callable(retry_after) else retry_after
        try:
            v = None if v is None else float(v)
        except (TypeError, ValueError):
            v = None
        if v is None or v <= 0:
            return "1"
        return str(max(1, int(math.ceil(v))))

    def health_doc():
        if replica is not None:
            return replica.health()
        if is_fleet:
            docs = engine.health()
            n_ok = sum(1 for d in docs if isinstance(d, dict)
                       and d.get("status") == "serving")
            doc = {"status": "serving" if n_ok else "unavailable",
                   "replicas": docs,
                   "breakers": engine.breaker_states()}
            # disaggregated prefill/decode view: per-pool depth +
            # transfer/affinity counters (absent when pools are off)
            pools = getattr(engine, "pools_summary", lambda: None)()
            if pools is not None:
                doc["pools"] = pools
            return doc
        return {"status": ("crashed" if engine._crashed is not None
                           else "draining" if engine.draining
                           else "serving"),
                "queue_depth": len(engine.queue),
                "compiled": engine.compiled_step_info()}

    def begin_drain(deadline=None):
        if replica is not None:
            replica.request_drain(deadline=deadline)
            # run_until_drained (the replica's main thread) finishes it;
            # a replica-less engine drains on a helper thread instead
            return
        kw = {} if deadline is None else {"timeout": float(deadline)}
        threading.Thread(target=engine.drain, kwargs=kw, daemon=True,
                         name="gateway-drain").start()

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def _reply(self, code, doc, headers=()):
            body = json.dumps(doc).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            for k, v in headers:
                self.send_header(k, v)
            self.send_header("Content-Length", str(len(body)))
            # one request per connection: keep-alive would park handler
            # threads in a blocking read, and server_close() JOINS
            # handler threads (that join is the drain guarantee — it
            # must never wait on an idle keep-alive socket)
            self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(body)
            self.close_connection = True

        def do_GET(self):       # noqa: N802 — stdlib API
            try:
                if self.path.startswith("/healthz"):
                    doc = health_doc()
                    self._reply(200 if doc.get("status") == "serving"
                                else 503, doc)
                elif self.path.startswith("/metrics.json"):
                    self._reply(200, engine._reg.snapshot())
                elif self.path.startswith("/aot.json"):
                    # warm-restart audit: which executables were
                    # deserialized vs compiled fresh, plus the store's
                    # on-disk manifests (None/{} without an AOT store)
                    store = getattr(engine, "_aot_store", None)
                    self._reply(200, {
                        "source": getattr(engine, "_aot_source", None),
                        "manifests": store.inspect()
                        if store is not None else {}})
                elif self.path.startswith("/timeline.json"):
                    # the newest profiled tick's step-timeline
                    # decomposition (engines built with profile_every=N
                    # refresh it continuously); the interval lanes are
                    # dropped from the reply — the fractions and the
                    # exposed-comm number are the dashboard payload,
                    # /trace.json renders the lanes
                    tl = getattr(engine, "last_timeline", None)
                    self._reply(200, {
                        "site": "serve",
                        "timeline": ({k: v for k, v in tl.items()
                                      if k != "lanes"}
                                     if tl else None)})
                elif self.path.startswith("/trace.json"):
                    from ..observability import trace_export as _texp
                    # _reply's own dumps is the single serialization
                    # AND the serializability check (failure → 500)
                    self._reply(200, _texp.validate_chrome_trace(
                        _texp.to_chrome_trace(_texp.live_records(
                            registry=engine._reg)),
                        check_serializable=False))
                elif self.path.startswith("/metrics"):
                    body = render_prometheus(
                        engine._reg.snapshot()).encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(body)))
                    self.send_header("Connection", "close")
                    self.end_headers()
                    self.wfile.write(body)
                    self.close_connection = True
                else:
                    self._reply(404, {"error": "unknown path"})
            except Exception as e:   # noqa: BLE001 — a probe must not kill us
                try:
                    self._reply(500,
                                {"error": f"{type(e).__name__}: {e}"})
                except Exception:
                    pass

        def do_POST(self):      # noqa: N802 — stdlib API
            # body cap BEFORE any read: a missing or garbage
            # Content-Length means "read until the peer hangs up" —
            # unbounded — and an honest oversized one is refused by
            # the declared size alone (never buffered then rejected)
            raw_len = self.headers.get("Content-Length")
            try:
                n = int(raw_len)
                if n < 0:
                    raise ValueError
            except (TypeError, ValueError):
                self._reply(413, {
                    "error": f"missing or unparseable Content-Length "
                             f"{raw_len!r}: the gateway reads exactly "
                             "the declared bytes"})
                return
            if n > max_body_bytes:
                self._reply(413, {
                    "error": f"request body of {n} bytes exceeds the "
                             f"gateway limit of {max_body_bytes}"})
                return
            try:
                raw = self.rfile.read(n) if n else b"{}"
                body = json.loads(raw.decode() or "{}")
            except Exception:
                self._reply(400, {"error": "body is not JSON"})
                return
            self._rid = None
            try:
                if self.path.startswith("/drain"):
                    # ?deadline=2.5 arms a preemption budget: the
                    # drain finishes what fits and migrates/fails the
                    # rest by then instead of waiting out the default
                    q = parse_qs(urlsplit(self.path).query)
                    deadline = body.get("deadline")
                    if deadline is None and q.get("deadline"):
                        deadline = q["deadline"][0]
                    begin_drain(deadline=None if deadline is None
                                else float(deadline))
                    doc = {"status": "draining"}
                    if deadline is not None:
                        doc["deadline_s"] = float(deadline)
                    self._reply(202, doc)
                elif self.path.startswith("/v1/generate"):
                    self._generate(body)
                elif self.path.startswith("/v1/inject"):
                    self._inject(body)
                elif self.path.startswith("/v1/predict"):
                    self._predict(body)
                else:
                    self._reply(404, {"error": "unknown path"})
            except RequestShed as e:
                # typed fast-fail shed: Retry-After is the contract —
                # the client backs off instead of hammering an
                # overloaded fleet into timeouts
                self._reply(503, self._err(
                    e, retryable=True, retry_after=e.retry_after),
                    headers=(("Retry-After",
                              str(max(1, int(e.retry_after)))),))
            except HandoffRefused as e:
                # typed inject refusal (corrupt frame, geometry
                # mismatch): 409 — recompute-redispatch territory, NOT
                # a fail-over-and-retry-the-same-bytes 503
                self._reply(409, self._err(e, retryable=False))
            except (EngineDraining, QueueFull,
                    BlockPoolExhausted) as e:
                # Retry-After rides every backpressure refusal: a
                # draining replica tells the client when to re-probe
                # the fleet instead of hammering this instance; the
                # hint (when wired) is spawn-to-ready derived, so the
                # back-off tracks real warm-up time
                self._reply(503, self._err(e, retryable=True),
                            headers=(("Retry-After",
                                      retry_after_header()),))
            except RequestTimeout as e:
                self._reply(504, self._err(e))
            except ReplicaCrashed as e:
                # serve-loop crash → 500 (the docstring's refusal map);
                # still retryable — a fleet LB fails over on it
                self._reply(500, self._err(e, retryable=True))
            except (ServingError, ValueError, TypeError) as e:
                self._reply(400, self._err(e))
            except Exception as e:   # noqa: BLE001 — crash → 500, once
                self._reply(500, self._err(e, named=True))

        def _err(self, e, named=False, **extra):
            # error replies keep the minted request id — a FAILED
            # request's trace lane is the main /trace.json debugging
            # target, and without the echo a server-minted id is
            # unfindable
            doc = {"error": f"{type(e).__name__}: {e}" if named
                   else str(e), **extra}
            if getattr(self, "_rid", None):
                doc["request_id"] = self._rid
            return doc

        @staticmethod
        def _mint_rid(body):
            # the request id minted here rides every engine span/event
            # for this request — the /trace.json timeline handle
            rid = body.get("request_id")
            return str(rid) if rid else uuid.uuid4().hex[:12]

        def _generate(self, body):
            prompt = body.get("prompt")
            if not isinstance(prompt, list) or not prompt:
                raise ValueError(
                    "generate needs a non-empty integer list 'prompt'")
            kw = {k: body[k] for k in ("max_new_tokens", "temperature",
                                       "top_k", "eos_id", "seed",
                                       "timeout") if k in body}
            # ONE deadline: the engine-side timeout and this handler's
            # wait are the same clock (started here), so a fleet
            # retry inherits the true remainder and the 504 fires in
            # lockstep with the request's own expiry
            wait = float(kw["timeout"]) \
                if kw.get("timeout") is not None else default_timeout
            deadline = deadline_in(wait)
            kw["timeout"] = wait
            rid = self._rid = self._mint_rid(body)
            fut = engine.submit(prompt, trace_id=rid, **kw)
            doc = fut.result(timeout=budget_remaining(deadline))
            if isinstance(doc, dict):
                doc = dict(doc, request_id=rid)
            self._reply(200, doc)

        def _inject(self, body):
            # live-KV handoff receive: a draining/dying peer POSTs a
            # sealed snapshot here; the engine validates (CRC +
            # geometry) before ANY bytes touch the pool — a refusal is
            # 409 and the sender falls back to recompute re-dispatch
            try:
                meta = base64.b64decode(body["meta"])
                frame = base64.b64decode(body["frame"])
            except (KeyError, TypeError, ValueError):
                raise ValueError(
                    "inject needs base64 'meta' and 'frame'")
            eng = replica.engine if replica is not None else \
                engine.engine if hasattr(engine, "engine") else engine
            inject = getattr(eng, "inject_snapshot", None)
            if inject is None:
                raise ValueError(
                    "this endpoint's engine does not accept KV "
                    "snapshots")
            wait = float(body["timeout"]) \
                if body.get("timeout") is not None else default_timeout
            deadline = deadline_in(wait)
            fut = inject(meta, frame, timeout=wait)
            doc = fut.result(timeout=budget_remaining(deadline))
            self._reply(200, doc if isinstance(doc, dict)
                        else {"tokens": doc})

        def _predict(self, body):
            if "input" not in body:
                raise ValueError("predict needs 'input'")
            wait = float(body["timeout"]) \
                if body.get("timeout") is not None else default_timeout
            deadline = deadline_in(wait)
            rid = self._rid = self._mint_rid(body)
            fut = engine.submit(body["input"], timeout=wait,
                                trace_id=rid)
            doc = _result_doc(fut.result(
                timeout=budget_remaining(deadline)))
            self._reply(200, dict(doc, request_id=rid))

        def log_message(self, *a):   # silence per-request stderr spam
            pass

    class Server(ThreadingHTTPServer):
        # joined at server_close(): a drain never abandons a response
        daemon_threads = False
        block_on_close = True

    server = Server((host, int(port)), Handler)
    t = threading.Thread(target=server.serve_forever, daemon=True,
                         name="serve-gateway")
    t.start()
    return server, server.server_address[1]


__all__ = ["serve_gateway"]
