"""Driver for the serving engine: `model.compile_serving(...)`, `eng.start()`,
requests through `eng.submit(...)` on an open loop of absolute due times.

Tails come from the harness's own clocks: a request's time to first token
is (submit - due) + the engine's `ttft_s` (which starts at submit); its time
per output token is (completion - first token) / (tokens - 1), completion
noted by a collector thread that polls `done()`. A request that fails or is
refused counts as the worst (the grace time). The end-to-end metric is the
95th percentile of the time per output token; the time to first token is
reported under `notes` only (at 264 requests a window its 95th percentile
spreads too widely to carry a bound, PERF.md section 7).

`correct`: once the window has closed, a sample drawn from the seed of the
requests it finished, the longest among them, goes through the plain
reference once (prompt and served tokens, teacher-forced); the number is the
widest gap by which a served token's logit lies below the reference's best.
"""

import gc
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from lib import compare, loadgen, weights
from lib.program import Handle, build_model, load_weights, singa_device

GRACE_S = 60.0


def _submit(h, req):
    """The one call the window makes per request."""
    return h.engine.submit(req["prompt"],
                           max_new_tokens=req["max_new_tokens"],
                           temperature=req["temperature"])


def _result_tokens(result):
    """The served tokens of one answer, as the future delivered them."""
    return [int(t) for t in result["tokens"]]


def setup(run):
    from singa_tpu import tensor
    from singa_tpu.observability import metrics as obs_metrics
    from singa_tpu.observability import spans
    h = Handle()
    config, job = run.config, run.traffic
    eng_kw = dict(job["engine"])
    h.dev = singa_device(run.devices[0].platform)
    h.dev.SetRandSeed(run.seed & 0x7FFFFFFF)
    reference = importlib.import_module(f"lib.references.{config['reference']}")
    specs = reference.param_specs(config)
    model = build_model(config, job)
    ids = tensor.Tensor(data=jnp.zeros((1, int(eng_kw["prefill_len"])),
                                       jnp.float32),
                        device=h.dev, requires_grad=False)
    # shape inference only: the dry run makes the parameters, no forward
    model.compile([ids], is_train=False, use_graph=True,
                  policy=config["precision"])
    model.eval()
    run.phase("model_compiled")
    load_weights(model, config, specs, run.seed)
    spans.configure(capacity=int(job.get("recorder_capacity", 400000)))
    h.registry = obs_metrics.MetricsRegistry()
    h.engine = model.compile_serving(policy=config["precision"],
                                     registry=h.registry, **eng_kw)
    h.model = model
    run.phase("engine_built")
    h.engine.start()
    # warm up the two programs this traffic uses (batched prefill, decode)
    rng = np.random.default_rng(run.seed)
    warm = [h.engine.submit(rng.integers(1, int(config["vocab_size"]), n,
                                         dtype=np.int32),
                            max_new_tokens=4, temperature=0.0)
            for n in (int(eng_kw["prefill_len"]), 16, 16, 16, 16)]
    for f in warm:
        f.result(timeout=1100)
    run.phase("warmed_up")
    h.schedule = loadgen.make_schedule(job, int(config["vocab_size"]),
                                       run.seed, run.seconds)
    return h


def _hist(registry, name):
    m = registry.get(name)
    if m is None:
        return {"count": 0, "sum": 0.0}
    s = m.summary()
    return {"count": s["count"], "sum": s["sum"]}


def _counter(registry, name):
    m = registry.get(name)
    return 0.0 if m is None else float(m.value())


def _snapshot(h):
    return {"t": time.monotonic(), "wall": time.time(),
            "token_seconds": _hist(h.registry, "serve_token_seconds"),
            "tokens": _counter(h.registry, "serve_tokens_total"),
            "prefill_tokens": _counter(h.registry,
                                       "serve_prefill_tokens_total"),
            "decode_steps": _counter(h.registry, "serve_decode_steps_total")}


def window(run, h):
    from jax.profiler import TraceAnnotation
    job = run.traffic
    slice_s = float(job.get("trace_seconds", 2.0)) if run.trace_dir else 0.0
    state = {"tracing": False, "stopped": False}
    snaps = {}

    def on_start(t0):
        run.mark_setup_done()
        snaps["start"] = _snapshot(h)

    def submit(req):
        # the traced slice is the last `slice_s` of the window; counters
        # and clocks are read over the part before it
        if run.trace_dir and not state["tracing"] and \
                req["due_s"] >= run.seconds - slice_s:
            snaps["end"] = _snapshot(h)
            run.start_trace()
            state["tracing"] = True
            state["annot"] = TraceAnnotation("bench.window")
            state["annot"].__enter__()
        with TraceAnnotation("bench.submit"):
            return _submit(h, req)

    def end_slice(force=False):
        # called from the submitting thread, which also opened the slice
        if state["tracing"] and not state["stopped"] and (
                force or time.monotonic()
                >= snaps["start"]["t"] + run.seconds):
            state["annot"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            state["stopped"] = True

    try:
        t0, records = loadgen.run_open_loop(
            h.schedule, submit, lambda fut: fut.done(), grace_s=GRACE_S,
            on_start=on_start, on_idle=end_slice)
    finally:
        end_slice(force=True)
    snaps.setdefault("end", _snapshot(h))
    worst_ms = GRACE_S * 1e3
    ttft_ms, tpot_ms, failed = [], [], 0
    answers = []
    for req, rec in zip(h.schedule, records):
        result = None
        if rec["error"] is None and rec["done_at"] is not None:
            try:
                result = rec["handle"].result(timeout=0)
            except Exception as e:      # noqa: BLE001 — a failure is a result
                rec["error"] = f"{type(e).__name__}: {e}"
        if result is None or result.get("ttft_s") is None:
            failed += 1
            ttft_ms.append(worst_ms)
            tpot_ms.append(worst_ms)
            answers.append(None)
            continue
        tokens = _result_tokens(result)
        first_at = rec["submitted"] + result["ttft_s"]
        ttft_ms.append((first_at - rec["due"]) * 1e3)
        if len(tokens) > 1:
            tpot_ms.append((rec["done_at"] - first_at)
                           / (len(tokens) - 1) * 1e3)
        answers.append(tokens)
    late_med, late_max = loadgen.lateness(records)
    from singa_tpu.observability import spans
    prefill = [r["dur_s"] for r in spans.recorder().records()
               if r.get("kind") == "span" and r.get("name") == "serve.prefill"
               and snaps["start"]["wall"] <= r["ts_start"]
               < snaps["end"]["wall"]]
    h.answers = answers
    done_in_window = sum(1 for r in records if r["done_at"] is not None
                         and r["done_at"] <= t0 + run.seconds)
    return {"attempted": len(records), "failed": failed,
            "ttft_ms": ttft_ms, "tpot_ms": tpot_ms,
            "snap_start": snaps["start"], "snap_end": snaps["end"],
            "prefill_span_s": prefill, "records": records,
            "notes": {"requests": len(records), "failed": failed,
                      "generator_late_ms_median": late_med * 1e3,
                      "generator_late_ms_max": late_max * 1e3,
                      "done_inside_window": done_in_window,
                      "drain_s": max(r["done_at"] or 0 for r in records)
                      - (t0 + run.seconds),
                      "ttft_p50_ms": loadgen.percentile(ttft_ms, 50),
                      "ttft_p95_ms": loadgen.percentile(ttft_ms, 95),
                      "tpot_p50_ms": loadgen.percentile(tpot_ms, 50)}}


def end_to_end(run, m):
    return {"serve_tpot_p95_ms": (loadgen.percentile(m["tpot_ms"], 95), "ms")}


def release(run, h):
    """Stop the engine, free its state, keep the sampled answers."""
    h.engine.stop()
    rng = np.random.default_rng(run.seed + 1)
    done = [i for i, a in enumerate(h.answers) if a is not None]
    n = int(run.traffic.get("check_requests", 6))
    longest = max(done, key=lambda i: len(h.schedule[i]["prompt"])
                  + len(h.answers[i]), default=None)
    picked = [] if longest is None else [longest]
    rest = [i for i in done if i != longest]
    picked += list(rng.choice(rest, size=min(n - 1, len(rest)),
                              replace=False)) if rest else []
    unanswered = sum(
        1 for i in picked
        if len(h.answers[i]) != h.schedule[i]["max_new_tokens"])
    evidence = {"unanswered": unanswered + (0 if done else 1),
                "samples": [(h.schedule[i]["prompt"],
                             np.asarray(h.answers[i], np.int32))
                            for i in picked]}
    model = h.model
    for t in model.get_states().values():
        t.data = None
    model._decode_params_pin = None
    h.__dict__.clear()
    del model
    gc.collect()
    return evidence


def reference_gaps(run, samples, cast=None):
    """Run the plain reference once over each sampled prompt with its served
    tokens. Returns (gaps of the served tokens, gaps of the tokens the
    lower-precision pass `cast` puts first, or None)."""
    config = run.config
    ref = importlib.import_module(f"lib.references.{config['reference']}")
    eng = run.traffic["engine"]
    length = int(eng["max_len"])
    block = int(run.traffic.get("check_block", 2))
    rows = -(-len(samples) // block) * block    # whole blocks: one program
    ids = np.zeros((rows, length), np.int32)
    spans_ = []
    for r, (prompt, tokens) in enumerate(samples):
        seq = np.concatenate([prompt, tokens])[:length]
        ids[r, :len(seq)] = seq
        spans_.append((len(prompt) - 1, len(seq) - 1))
    with jax.default_matmul_precision("highest"):
        params = weights.make(ref.param_specs(config), run.seed)
        served, low = [], []
        for a in range(0, len(samples), block):
            g, lo = ref.served_gaps(params, jnp.asarray(ids[a:a + block]),
                                    config, cast)
            g = np.asarray(g)
            lo = None if lo is None else np.asarray(lo)
            for r in range(min(block, len(samples) - a)):
                s, e = spans_[a + r]
                served.append(g[r, s:e])
                if lo is not None:
                    low.append(lo[r, s:e])
        del params
    cat = (lambda xs: np.concatenate(xs) if xs else np.zeros((0,)))
    return cat(served), (cat(low) if low else None)


def check(run, evidence):
    gaps, _ = reference_gaps(run, evidence["samples"])
    return compare.served_numbers(
        {"gaps": gaps, "unanswered": evidence["unanswered"]},
        run.cell["limits"])
