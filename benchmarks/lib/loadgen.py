"""The one general traffic generator: a traffic file's parameters and a seed
give a schedule of requests on absolute due times, and `run_open_loop`
offers it to anything that takes a `submit(request)`.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps (the quantiles of the distributions the file names, as
many as `rate_rps * seconds` requests) in another order, and other token
ids. So the work of a window is fixed by the traffic file, and the seed
changes only which request meets which.

A traffic file (JSON):

    {"kind": "open_loop", "rate_rps": 8.0,
     "prompt_len": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                    "min": 16, "max": 512},
     "output_len": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                    "min": 8, "max": 256},
     "temperature": 0.0}
"""

import math
import statistics
import threading
import time

import numpy as np

_NORMAL = statistics.NormalDist()


def _quantiles(spec, n):
    """n values at the mid-quantiles of the distribution `spec` names."""
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "lognormal":
        vals = [spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(q))
                for q in qs]
    elif spec["dist"] == "exponential":
        vals = [-spec["mean"] * math.log(1.0 - q) for q in qs]
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    lo, hi = spec.get("min", -math.inf), spec.get("max", math.inf)
    return [min(max(v, lo), hi) for v in vals]


def make_schedule(traffic, vocab, seed, seconds):
    """[{due_s, prompt (int32 array), max_new_tokens, temperature}, ...] in
    due order: every request due inside [0, seconds)."""
    if traffic.get("kind") != "open_loop":
        raise ValueError("make_schedule reads open_loop traffic only")
    rate = float(traffic["rate_rps"])
    n = max(1, round(rate * seconds))
    rng = np.random.default_rng(int(seed))
    gaps = np.array(_quantiles({"dist": "exponential", "mean": 1.0 / rate},
                               n))
    gaps *= (seconds / n) / gaps.mean()   # the set of gaps fills the window
    rng.shuffle(gaps)
    due = np.cumsum(gaps) - gaps[0] * 0.5
    due = [min(float(t), seconds * (1 - 1e-9)) for t in due]
    p_len = np.rint(_quantiles(traffic["prompt_len"], n)).astype(int)
    o_len = np.rint(_quantiles(traffic["output_len"], n)).astype(int)
    rng.shuffle(p_len)
    rng.shuffle(o_len)
    out = []
    for i in range(n):
        prompt = rng.integers(1, vocab, int(p_len[i]), dtype=np.int32)
        out.append({"due_s": due[i], "prompt": prompt,
                    "max_new_tokens": int(o_len[i]),
                    "temperature": float(traffic.get("temperature", 0.0))})
    out.sort(key=lambda r: r["due_s"])
    return out


def run_open_loop(schedule, submit, is_done, *, clock=time.monotonic,
                  sleep=time.sleep, poll_s=0.001, grace_s=60.0,
                  on_start=None, on_idle=None):
    """Offer `schedule` on its absolute due times from one thread and note
    each request's completion from a collector thread that polls
    `is_done(handle)`. A request whose `submit` raises is a failed one.
    Waits up to `grace_s` past the last due time for stragglers; while it
    waits, the submitting thread calls `on_idle()` every few milliseconds
    (the harness ends its traced slice from there). Returns
    (t0, records): records[i] = {due, submitted, done_at, handle, error},
    times on `clock`, `due` absolute."""
    records = [{"due": None, "submitted": None, "done_at": None,
                "handle": None, "error": None} for _ in schedule]
    pending, lock, stop = [], threading.Lock(), threading.Event()

    def collect():
        while True:
            with lock:
                live = list(pending)
            now = clock()
            for rec in live:
                if is_done(rec["handle"]):
                    rec["done_at"] = now
            with lock:
                pending[:] = [r for r in pending if r["done_at"] is None]
                empty = not pending
            if stop.is_set() and empty:
                return
            sleep(poll_s)

    collector = threading.Thread(target=collect, name="bench-collector",
                                 daemon=True)
    t0 = clock()
    if on_start is not None:
        on_start(t0)
    collector.start()
    try:
        for req, rec in zip(schedule, records):
            rec["due"] = t0 + req["due_s"]
            wait = rec["due"] - clock()
            if wait > 0:
                sleep(wait)
            rec["submitted"] = clock()
            try:
                rec["handle"] = submit(req)
            except Exception as e:      # noqa: BLE001 — a refusal is a result
                rec["error"] = f"{type(e).__name__}: {e}"
                rec["done_at"] = clock()
                continue
            with lock:
                pending.append(rec)
        deadline = clock() + grace_s
        while clock() < deadline:
            with lock:
                if not pending:
                    break
            if on_idle is not None:
                on_idle()
            sleep(poll_s * 5)
    finally:
        stop.set()
        with lock:
            for rec in pending:          # never came: left without done_at
                rec["error"] = rec["error"] or "no answer within the grace"
            pending.clear()
        collector.join(timeout=10)
    return t0, records


def lateness(records):
    """How late the generator ran: (median, max) of submitted - due, s."""
    late = [r["submitted"] - r["due"] for r in records
            if r["submitted"] is not None]
    return (statistics.median(late), max(late)) if late else (None, None)


def percentile(values, q):
    """The q-th percentile by linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))
