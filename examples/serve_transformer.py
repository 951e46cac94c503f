"""Serve a TransformerLM behind the continuous-batching engine and the
stdlib HTTP gateway — the serving counterpart of train_elastic.py: real
enough to chaos-test, small enough to read.

Modes:

- default: start the engine + gateway, print ``READY port=N``, then
  block until SIGTERM/SIGINT. The signal triggers a **graceful drain**
  (in-flight and queued requests all finish, new ones get 503) and the
  process exits 0 (``serving.EXIT_DRAINED``) — kill -TERM is how a
  supervisor rolls a replica, and exit 0 tells it the drain completed.
- ``--selftest N``: additionally fire N generation requests at the own
  gateway from client threads, assert every one returns exactly once
  with the requested token count and that the decode program traced
  exactly once, print ``SELFTEST OK`` and exit 0 (the CI smoke).
- ``--pool-role prefill --decode-peers P1,P2``: disaggregated pools
  across processes. This replica admits and chunk-prefills only; each
  finished prefill is sealed (CRC-framed KV snapshot) and transferred
  to a decode gateway chosen by prefix affinity (rendezvous hash of
  the prompt's block-aligned chain key over the peer list, so a
  repeated prefix keeps landing where its KV already lives). Failure
  ladder per transfer: typed 409 refusal (corrupt frame) or a dead
  peer → next-best peer → recompute via ``/v1/generate`` on any live
  peer → typed error; no live peers at seal time → colocate (this
  replica decodes it after all). ``--pool-role decode`` marks the
  receiving side (it serves ``/v1/inject`` continuations and plain
  generates). Both sides must share KV geometry.
- ``--autoscale MIN``: fleet mode. MIN in-process replicas (each its
  own engine + metrics registry) behind a ``FleetRouter``, an
  ``Autoscaler`` supervising the population against SLO targets
  (scale-up on sustained breach, drain+handoff retirement on calm,
  crash/stale replacement, flap quarantine), ONE gateway fronting the
  router. With ``--aot-dir`` every spawned replica must pass the
  warm-admission gate (zero fresh compiles); backpressure 503s carry
  a ``Retry-After`` from the scaler's observed spawn-to-ready median.
  ``--selftest`` prints ``AUTOSCALE OK`` instead of ``SELFTEST OK``.

Usage::

    python examples/serve_transformer.py --cpu --port 8901
    curl -d '{"prompt": [1,2,3], "max_new_tokens": 8}' \
        http://127.0.0.1:8901/v1/generate
    curl -X POST http://127.0.0.1:8901/drain     # or: kill -TERM <pid>
"""

import argparse
import base64
import json
import sys
import threading
import time

import numpy as np

sys.path.insert(0, ".")


def _post(port, path, doc, timeout=120.0):
    import http.client
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        c.request("POST", path, json.dumps(doc),
                  {"Content-Type": "application/json"})
        r = c.getresponse()
        return r.status, json.loads(r.read().decode() or "{}")
    finally:
        c.close()


def _make_handoff(peers, timeout):
    """The deadline drain's migration callable: offer each unfinished
    request to the peer gateways — sealed-snapshot inject first (the
    continuation is bitwise-identical, zero recomputed prefill), typed
    409 refusal → recompute via /v1/generate on the same peer, dead
    peer → next peer. Ownership moves to a relay thread (the drain
    must not block on a peer's decode); the thread resolves the
    request's future exactly once, typed on total failure."""

    def handoff(req, snapshot, budget):
        if not peers:
            return False

        def run():
            from singa_tpu.serving import EngineDraining
            doc = None
            for p in peers:
                try:
                    if snapshot is not None:
                        st, d = _post(p, "/v1/inject", {
                            "meta": base64.b64encode(
                                snapshot["meta"]).decode(),
                            "frame": base64.b64encode(
                                snapshot["frame"]).decode(),
                            "timeout": timeout}, timeout=timeout)
                        if st == 200:
                            doc = d
                            break
                        if st != 409:
                            continue    # peer trouble: next peer
                        # 409 = typed refusal: recompute, same peer
                    body = {"prompt": [int(t) for t in req.prompt],
                            "max_new_tokens": req.max_new_tokens,
                            "temperature": req.temperature,
                            "request_id": req.trace_id,
                            "timeout": timeout}
                    if req.top_k is not None:
                        body["top_k"] = req.top_k
                    if req.eos_id is not None:
                        body["eos_id"] = req.eos_id
                    st, d = _post(p, "/v1/generate", body,
                                  timeout=timeout)
                    if st == 200:
                        doc = d
                        break
                except OSError:
                    continue
            if req.future.done():
                return
            if doc is None:
                req.future.set_error(EngineDraining(
                    "handoff failed: no peer accepted the request"))
            else:
                req.future.set_result(doc)

        threading.Thread(target=run, daemon=True,
                         name="handoff-relay").start()
        return True

    return handoff


def _make_pool_transfer(peers, timeout, reg, affinity, block_size):
    """The prefill pool's transfer callable (``engine.set_transfer``):
    route each sealed slot to a decode gateway by prefix affinity and
    walk the failure ladder across processes. Rungs: typed 409
    refusal or a dead socket → next-best peer; all injects refused →
    recompute via ``/v1/generate`` on a live peer (greedy makes the
    recompute bitwise-identical, it just pays prefill again); nothing
    live at seal time → return False, which is the colocate rung (the
    prefill engine keeps the slot and decodes it itself). A relay
    thread owns the request once we return True — the engine tick
    must never block on a peer's decode — and resolves the future
    exactly once, typed on total failure."""
    from singa_tpu.serving import affinity_hash, prefix_chain_key

    dead = set()
    owner = {}              # prefix chain key → port that served it
    hits = reg.counter("serve_pool_affinity_hit_total",
                       "transfers landing on the decode peer that "
                       "already served this prefix chain")
    misses = reg.counter("serve_pool_affinity_miss_total",
                         "transfers landing on a decode peer cold "
                         "for this prefix chain")
    retries = reg.counter("serve_pool_transfer_retry_total",
                          "transfer attempts that moved to the "
                          "next-best decode peer (refused frame or "
                          "dead socket)")

    def transfer(req, snapshot, _resnap):
        live = [p for p in peers if p not in dead]
        if not live:
            return False                    # colocate rung
        key = prefix_chain_key([int(t) for t in req.prompt],
                               block_size)
        if affinity and key is not None:
            order = sorted(live, key=lambda p: affinity_hash(
                key, salt=str(p)), reverse=True)
        else:
            order = live[hash(req.trace_id) % len(live):] + \
                live[:hash(req.trace_id) % len(live)]

        def run():
            import http.client as _hc

            from singa_tpu.serving import ReplicaCrashed
            doc, served_by = None, None
            # a peer SIGKILLed mid-response surfaces as any of these
            wire_dead = (OSError, _hc.HTTPException, ValueError)
            for p in order:
                try:
                    st, d = _post(p, "/v1/inject", {
                        "meta": base64.b64encode(
                            snapshot["meta"]).decode(),
                        "frame": base64.b64encode(
                            snapshot["frame"]).decode(),
                        "timeout": timeout}, timeout=timeout)
                except wire_dead:
                    dead.add(p)
                    retries.inc()
                    continue
                if st == 200:
                    doc, served_by = d, p
                    break
                retries.inc()   # 409: refused typed; the frame is
                                # bad everywhere, the recompute rung
                                # below picks it up
            if doc is None:
                for p in order:
                    if p in dead:
                        continue
                    try:
                        st, d = _post(
                            p, "/v1/generate",
                            {"prompt": [int(t) for t in req.prompt],
                             "max_new_tokens": req.max_new_tokens,
                             "temperature": req.temperature,
                             "request_id": req.trace_id,
                             "timeout": timeout}, timeout=timeout)
                    except wire_dead:
                        dead.add(p)
                        continue
                    if st == 200:
                        doc, served_by = d, p
                        break
            if req.future.done():
                return
            if doc is None:
                req.future.set_error(ReplicaCrashed(
                    "pool transfer failed: no decode peer took the "
                    "request"))
                return
            if key is not None:
                (hits if owner.get(key) == served_by
                 else misses).inc()
                owner[key] = served_by
            req.future.set_result(doc)

        threading.Thread(target=run, daemon=True,
                         name="pool-transfer-relay").start()
        return True

    return transfer


def _selftest(port, n, vocab, new_tokens=8, temperature=0.5):
    rng = np.random.RandomState(0)
    results = [None] * n

    def one(i):
        prompt = rng.randint(1, vocab, (int(rng.randint(1, 8)),)).tolist()
        results[i] = _post(port, "/v1/generate",
                           {"prompt": prompt,
                            "max_new_tokens": new_tokens,
                            "temperature": temperature, "seed": i})

    threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    bad = [(i, r) for i, r in enumerate(results)
           if r is None or r[0] != 200
           or len(r[1].get("tokens", [])) != new_tokens]
    if bad:
        raise SystemExit(f"SELFTEST FAILED: {bad[:3]}")


def _run_autoscale(args, model, serve_kw):
    """Fleet mode: ``--autoscale MIN`` replicas behind a FleetRouter
    with an Autoscaler driving the population (see module docstring).
    Single-device engines only — the sharded flags don't compose with
    in-process fleet replicas."""
    import itertools
    import signal as _signal

    from singa_tpu.observability import metrics as obs_metrics
    from singa_tpu.serving import (Autoscaler, AutoscaleTargets,
                                   FleetRouter, ServingReplica,
                                   ShedPolicy, serve_gateway)

    seq = itertools.count()

    def spawn():
        i = next(seq)
        reg = obs_metrics.MetricsRegistry()
        eng = model.compile_serving(
            slots=args.slots, max_len=args.max_len,
            prefill_len=args.prefill_len, policy=args.policy,
            registry=reg, **serve_kw)
        if args.aot_dir:
            src = dict(eng.compiled_step_info()["aot"] or {})
            if not src or any(v != "loaded" for v in src.values()):
                # cold spin-up exports back: the NEXT spawn (the one
                # the warm-admission gate judges) deserializes
                eng.export_aot()
        return ServingReplica(eng, name=f"r{i}").start()

    fleet_reg = obs_metrics.MetricsRegistry()
    router = FleetRouter([spawn() for _ in range(args.autoscale)],
                         registry=fleet_reg,
                         shed_policy=ShedPolicy(window_s=1.0))
    scaler = Autoscaler(
        router, spawn,
        targets=AutoscaleTargets(min_replicas=args.autoscale,
                                 max_replicas=args.max_replicas),
        registry=fleet_reg, interval=args.autoscale_interval,
        require_warm=bool(args.aot_dir),
        probe_timeout=args.default_timeout)
    scaler.start()
    server, port = serve_gateway(
        router, port=args.port,
        default_timeout=args.default_timeout,
        max_body_bytes=args.max_body_bytes,
        retry_after=scaler.retry_after_hint)
    print(f"READY port={port} replicas={router.population()}",
          flush=True)

    def shutdown():
        scaler.stop()
        ok = router.drain(timeout=args.drain_timeout)
        server.shutdown()
        server.server_close()
        return 0 if ok else 1

    if args.selftest:
        _selftest(port, args.selftest, args.vocab, temperature=0.5)
        st = scaler.status()
        code = shutdown()
        print(f"AUTOSCALE OK n={args.selftest} "
              f"population={st['population']} "
              f"quarantined={st['quarantined_seats']} "
              f"drain_exit={code}", flush=True)
        return code

    stop = threading.Event()
    for s in (_signal.SIGTERM, _signal.SIGINT):
        _signal.signal(s, lambda *_: stop.set())
    stop.wait()
    code = shutdown()
    print(f"DRAINED exit={code}", flush=True)
    return code


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=0,
                    help="gateway port (0 = ephemeral, printed as READY)")
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prefill-len", type=int, default=16)
    ap.add_argument("--policy", default=None,
                    help="mixed-precision policy name (e.g. bf16_mixed)")
    ap.add_argument("--kv-layout", default="ring",
                    choices=("ring", "paged"),
                    help="KV cache layout: the ring (default) or the "
                         "paged block pool with prefix sharing "
                         "(docs/serving.md)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="paged layout: tokens per KV block")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="paged layout: pool size in blocks (default "
                         "slots x ceil(max_len/block_size))")
    ap.add_argument("--speculative-k", type=int, default=0,
                    help="speculative decoding: verify-program width "
                         "(up to K tokens per tick, greedy requests "
                         "only; needs --kv-layout paged)")
    ap.add_argument("--model-shards", type=int, default=0,
                    help="GSPMD sharded serving: tensor-parallel "
                         "degree over a (batch × model) device mesh "
                         "(heads/MLP/vocab sharded, XLA inserts the "
                         "collectives; greedy-only — "
                         "docs/serving.md). 0 = single-device")
    ap.add_argument("--mesh", default=None, metavar="BxM",
                    help="explicit serving mesh shape, e.g. 2x2 "
                         "(batch × model axes over the first B*M "
                         "devices); overrides --model-shards")
    ap.add_argument("--aot-dir", default=None, metavar="DIR",
                    help="cold-start elimination (singa_tpu.aot): "
                         "deserialize matching prefill/decode "
                         "executables from DIR instead of tracing "
                         "(the persistent compile cache goes where "
                         "JAX_COMPILATION_CACHE_DIR says, else "
                         "<checkout>/.jax_compile_cache); programs "
                         "compiled fresh are "
                         "exported back so the NEXT spin-up is warm")
    ap.add_argument("--autoscale", type=int, default=0, metavar="MIN",
                    help="fleet mode: MIN in-process replicas behind "
                         "a FleetRouter with an SLO-driven Autoscaler "
                         "supervising the population (scale-up on "
                         "sustained breach, drain+handoff retirement, "
                         "crash replacement, flap quarantine); with "
                         "--aot-dir spawns must pass the "
                         "warm-admission gate (0 = single-replica "
                         "mode)")
    ap.add_argument("--max-replicas", type=int, default=3,
                    help="autoscale population ceiling")
    ap.add_argument("--autoscale-interval", type=float, default=0.25,
                    help="supervision tick period (seconds)")
    ap.add_argument("--selftest", type=int, default=0, metavar="N",
                    help="fire N requests at the own gateway, verify, "
                         "exit 0")
    ap.add_argument("--drain-timeout", type=float, default=60.0)
    ap.add_argument("--drain-deadline", type=float, default=None,
                    help="preemption budget (seconds) armed on "
                         "SIGTERM/SIGINT: finish what fits, hand off "
                         "(--handoff-peers) or fail-typed the rest by "
                         "the deadline instead of waiting out "
                         "--drain-timeout")
    ap.add_argument("--handoff-peers", default=None, metavar="PORTS",
                    help="comma-separated peer gateway ports: a "
                         "deadline drain migrates unfinished requests "
                         "there (POST /v1/inject with the sealed KV "
                         "snapshot; recompute via /v1/generate when "
                         "the peer refuses typed)")
    ap.add_argument("--pool-role", default=None,
                    choices=("prefill", "decode"),
                    help="disaggregated pools: tag this replica's "
                         "role (prefill seals+transfers finished "
                         "slots to --decode-peers; decode receives "
                         "/v1/inject continuations). Both sides must "
                         "share KV geometry")
    ap.add_argument("--decode-peers", default=None, metavar="PORTS",
                    help="comma-separated decode gateway ports the "
                         "prefill pool transfers sealed KV to "
                         "(prefix-affinity ordered; failure ladder "
                         "in the module docstring)")
    ap.add_argument("--no-affinity", action="store_true",
                    help="order decode peers round-robin instead of "
                         "by prefix affinity (the A/B measurement "
                         "baseline for the affinity hit counters)")
    ap.add_argument("--fault-corrupt-transfer", type=int, default=0,
                    metavar="SEQ",
                    help="chaos: arm FaultPlan.corrupt_handoff(SEQ) — "
                         "flip a bit in the SEQ-th sealed KV frame so "
                         "the receiving decode peer refuses it typed "
                         "(0 = off)")
    ap.add_argument("--spill-bytes", type=int, default=0,
                    help="host-RAM spill tier byte budget for evicted "
                         "cached-prefix KV blocks (paged layout; 0 = "
                         "off)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="checkpoint in-flight KV snapshots every N "
                         "ticks so a crash re-dispatch resumes from "
                         "the last snapshot instead of token zero "
                         "(0 = off)")
    ap.add_argument("--default-timeout", type=float, default=120.0,
                    help="per-request deadline budget (seconds) when "
                         "the body carries no timeout; the engine SLO "
                         "timeout and the gateway's own wait are both "
                         "derived from this ONE clock")
    ap.add_argument("--max-body-bytes", type=int, default=8 << 20,
                    help="refuse request bodies over this size with "
                         "413 before reading them")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    from singa_tpu import device, tensor
    from singa_tpu.models import transformer
    from singa_tpu.serving import ServingReplica, serve_gateway

    dev = device.create_cpu_device() if args.cpu \
        else device.create_tpu_device()
    dev.SetRandSeed(0)
    model = transformer.TransformerLM(
        args.vocab, d_model=args.d_model, n_heads=args.heads,
        n_layers=args.layers, max_len=args.max_len, tp=False)
    model.eval()
    # one eager forward materialises the lazily-initialised params the
    # serving adapter host-gathers
    model(tensor.Tensor(
        data=np.zeros((1, args.prefill_len), np.float32), device=dev,
        requires_grad=False))

    serve_kw = {}
    if args.aot_dir:
        serve_kw["aot_store"] = args.aot_dir
        serve_kw["compile_cache"] = True
    if args.kv_layout != "ring":
        serve_kw.update(kv_layout=args.kv_layout,
                        kv_block_size=args.kv_block_size,
                        kv_blocks=args.kv_blocks)
    if args.speculative_k:
        serve_kw["speculative_k"] = args.speculative_k
    if args.spill_bytes:
        serve_kw["spill_bytes"] = args.spill_bytes
    if args.snapshot_every:
        serve_kw["snapshot_every"] = args.snapshot_every
    if args.pool_role:
        serve_kw["pool_role"] = args.pool_role
    if args.fault_corrupt_transfer:
        from singa_tpu.resilience.faults import FaultPlan
        plan = FaultPlan()
        plan.corrupt_handoff(args.fault_corrupt_transfer, times=1)
        serve_kw["faults"] = plan
    if args.autoscale:
        return _run_autoscale(args, model, serve_kw)
    sharded = bool(args.model_shards or args.mesh)
    if args.mesh:
        import jax
        from singa_tpu.parallel import gspmd
        b, m_ = (int(x) for x in args.mesh.lower().split("x"))
        serve_kw["mesh"] = gspmd.serving_mesh(
            jax.devices()[:b * m_], model_shards=m_, batch_shards=b)
    elif args.model_shards:
        serve_kw["model_shards"] = args.model_shards
    engine = model.compile_serving(
        slots=args.slots, max_len=args.max_len,
        prefill_len=args.prefill_len, policy=args.policy, **serve_kw)
    if sharded:
        info = engine.compiled_step_info()
        print(f"SHARDED mesh=batch{info['mesh']['batch']}x"
              f"model{info['mesh']['model']} "
              f"kv_per_device_bytes={info['kv_per_device_bytes']}",
              flush=True)
    if args.aot_dir:
        src = dict(engine.compiled_step_info()["aot"] or {})
        if not src or any(v != "loaded" for v in src.values()):
            # cold spin-up: leave warm artifacts behind for the next
            # replica (the chaos warm-restart scenario's populate
            # leg); export_aot refreshes the engine's audit state, so
            # /healthz and /aot.json report "exported" too
            engine.export_aot()
            src = dict(engine.compiled_step_info()["aot"] or {})
        print("AOT " + " ".join(
            f"{p.split('serve_', 1)[-1]}={v}"
            for p, v in sorted(src.items())), flush=True)
    if args.decode_peers:
        peers = [int(p) for p in args.decode_peers.split(",") if p]
        engine.set_transfer(_make_pool_transfer(
            peers, args.default_timeout, engine._reg,
            affinity=not args.no_affinity,
            block_size=args.kv_block_size))
    replica = ServingReplica(engine, name=f"serve-{args.port}")
    replica.install_signal_handlers(deadline=args.drain_deadline)
    replica.start()
    server, port = serve_gateway(engine, port=args.port,
                                 replica=replica,
                                 default_timeout=args.default_timeout,
                                 max_body_bytes=args.max_body_bytes)
    print(f"READY port={port}", flush=True)

    if args.selftest:
        # sharded serving is greedy-only (in-graph argmax over the
        # vocab shards): the smoke drives it at temperature 0
        _selftest(port, args.selftest, args.vocab,
                  temperature=0.0 if sharded else 0.5)
        info = engine.compiled_step_info()
        assert info["n_traces"] == 1, \
            f"decode retraced: {info['n_traces']}"
        replica.request_drain()
        code = replica.drain(timeout=args.drain_timeout)
        server.shutdown()
        server.server_close()
        print(f"SELFTEST OK n={args.selftest} n_traces=1 "
              f"drain_exit={code}", flush=True)
        return code

    handoff = None
    if args.handoff_peers:
        peers = [int(p) for p in args.handoff_peers.split(",") if p]
        handoff = _make_handoff(peers, args.default_timeout)
    drain_started = {}

    def _watch():
        replica._drain_evt.wait()
        drain_started["t"] = time.monotonic()

    threading.Thread(target=_watch, daemon=True,
                     name="drain-watch").start()
    # poll=0.05: a preemption deadline is seconds — the gap between
    # the signal and the blocking drain must not eat half the budget
    code = replica.run_until_drained(poll=0.05,
                                     timeout=args.drain_timeout,
                                     handoff=handoff)
    # DRAIN_DONE times the ENGINE drain (the preemption-deadline
    # contract) — printed before server_close(), whose handler-thread
    # join legitimately extends past the deadline while migrated
    # responses relay back from the peers
    if "t" in drain_started:
        print(f"DRAIN_DONE in={time.monotonic() - drain_started['t']:.2f}s",
              flush=True)
    # stop accepting, then join in-flight handler threads: every
    # admitted request's HTTP response is written before exit
    server.shutdown()
    server.server_close()
    print(f"DRAINED exit={code}", flush=True)
    return code


if __name__ == "__main__":
    raise SystemExit(main())
