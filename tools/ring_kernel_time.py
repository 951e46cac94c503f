#!/usr/bin/env python3
"""The ring decode kernel alone, at a level's shape, on the chip, for each
count of KV heads a grid step takes.

    python3 tools/ring_kernel_time.py [--slots 64] [--kv-heads 10]
        [--queries 4] [--head-size 128] [--rings 512,3072] [--live 44]
        [--tokens 800] [--heads-a-step 1,2,5,10] [--calls 50]

A bf16 level ``(slots, kv_heads, ring, head_size)`` for each ring length
(the defaults are Phi-4-mini-flash's window and full rings), ``live``
slots with contexts of about ``tokens`` (log-normal, sigma 0.4) and the
rest dead. For each count of heads a step (each must divide the KV heads)
the kernel is traced again with that count in place of
``ring_decode.heads_per_step``'s, and both passes run ``calls`` times:
``ring_decode`` (through ``kv_cache.decode_token``, the level donated and
written) and ``ring_attend`` (``kv_cache.attend_token``, read-only).
Microseconds a call by the host's clock round a ``block_until_ready``
(``host_us``: where a call is shorter than its dispatch, which a call
that donates its level waits for, this is the host's time) and on the
device (``us``: the mean of the kernel's events on the chip's op line of
a profiler trace of the same calls), the grid steps a call takes, the
bytes of K and V its blocks move, and that share of the HBM bandwidth by
the device's time. One JSON line a (ring, heads a step, pass); not a
benchmark result. ``rule`` names the count the rule picks.
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402

from singa_tpu.observability import metrics         # noqa: E402
from singa_tpu.ops import ring_decode               # noqa: E402
from singa_tpu.serving import kv_cache              # noqa: E402


def _device_s(trace_dir, kernel):
    """Mean seconds of the events named ``kernel`` on the op line of the
    first chip's plane of the trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    durations = [
        e.duration_ns for plane in ProfileData.from_file(path).planes
        if plane.name == "/device:TPU:0" for line in plane.lines
        if line.name == "XLA Ops" for e in line.events
        if e.name.split(" = ", 1)[0].lstrip("%").startswith(kernel)]
    return sum(durations) / len(durations) / 1e9 if durations else None


def _timed(fn, args, calls, kernel, donated=None):
    """Seconds a call by the host's clock and on the device, ``calls``
    calls back to back after one that compiles; ``donated`` is the level
    a writing call hands back."""
    out = fn(donated, *args) if donated is not None else fn(*args)
    jax.block_until_ready(out)
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            t0 = time.perf_counter()
            for _ in range(calls):
                if donated is not None:
                    out = fn(out[1], *args)
                else:
                    out = fn(*args)
            jax.block_until_ready(out)
            host = (time.perf_counter() - t0) / calls
        return host, _device_s(trace_dir, kernel)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--kv-heads", type=int, default=10)
    ap.add_argument("--queries", type=int, default=4,
                    help="query heads a KV head")
    ap.add_argument("--head-size", type=int, default=128)
    ap.add_argument("--rings", default="512,3072")
    ap.add_argument("--live", type=int, default=44)
    ap.add_argument("--tokens", type=int, default=800)
    ap.add_argument("--heads-a-step", default="1,2,5,10")
    ap.add_argument("--calls", type=int, default=50)
    a = ap.parse_args(argv)
    W, n_kv, G, D = a.slots, a.kv_heads, a.queries, a.head_size
    dtype = jnp.bfloat16
    peaks = metrics.device_peaks(jax.devices()[0])
    rng = np.random.default_rng(0)
    pos = np.clip(a.tokens * np.exp(0.4 * rng.normal(size=W)), 1,
                  None).astype(np.int32)
    active = np.arange(W) < a.live
    q = jnp.asarray(rng.normal(size=(W, n_kv * G, 1, D)), dtype)
    k_new, v_new = (jnp.asarray(rng.normal(size=(W, n_kv, D)), dtype)
                    for _ in range(2))
    scale = D ** -0.5
    rule = ring_decode.heads_per_step

    def decode(level, q, k_new, v_new, pos, active):
        out, level = kv_cache.decode_token(level, q, k_new, v_new, pos,
                                           active, scale)
        return out, level

    def attend(level, q, pos, active):
        return kv_cache.attend_token(level, q, pos, active, scale)

    for L in (int(n) for n in a.rings.split(",")):
        def fresh():            # drawn on the device
            keys = jax.random.split(jax.random.key(1))
            return {name: jax.random.normal(key, (W, n_kv, L, D), dtype)
                    for name, key in zip(("k", "v"), keys)}

        level = fresh()
        block = kv_cache.ring_block(level)
        blocks = int(np.asarray(ring_decode.live_blocks(
            jnp.asarray(pos), jnp.asarray(active), L, block)).sum())
        moved = blocks * n_kv * block * D * 2 * jnp.dtype(dtype).itemsize
        for hb in (int(n) for n in a.heads_a_step.split(",")):
            if n_kv % hb:
                continue
            ring_decode.heads_per_step = lambda *_, hb=hb: hb
            jax.clear_caches()
            try:
                args = (jnp.asarray(pos), jnp.asarray(active))
                times = {
                    "ring_decode": _timed(
                        jax.jit(decode, donate_argnums=(0,)),
                        (q, k_new, v_new) + args, a.calls, "ring_decode",
                        donated=fresh()),
                    "ring_attend": _timed(jax.jit(attend),
                                          (level, q) + args, a.calls,
                                          "ring_attend"),
                }
            finally:
                ring_decode.heads_per_step = rule
            for name, (host, s) in times.items():
                line = {
                    "op": name, "level": [W, n_kv, L, D], "queries": G,
                    "block": block, "live": a.live,
                    "blocks_walked": blocks, "heads_a_step": hb,
                    "rule": rule(n_kv, block, D, jnp.dtype(dtype).itemsize),
                    "grid_steps": blocks * n_kv // hb,
                    "host_us": round(host * 1e6, 1), "kv_bytes": moved}
                if s is not None:
                    line.update(
                        us=round(s * 1e6, 1),
                        us_a_step=round(s * 1e6 * hb / (blocks * n_kv), 4),
                        gb_per_s=round(moved / s / 1e9, 1))
                    if peaks is not None:
                        line["hbm_roofline_pct"] = round(
                            100 * moved / s / peaks["hbm_bytes_per_s"], 1)
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
