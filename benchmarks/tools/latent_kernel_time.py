#!/usr/bin/env python3
"""The latent decode kernel alone, at a cell's shape, on the chip.

    python3 benchmarks/tools/latent_kernel_time.py [slots] [heads] [ring] [live] [tokens]

One level `(slots, 1, ring, 640)` bf16 (a row of 512 + 64 numbers), `live`
slots with contexts of about `tokens` (log-normal, sigma 0.4) and the rest
dead, through `kv_cache.decode_token` 50 times: microseconds a call by the
host's clock round a `block_until_ready`, what the call needs (each row that
holds a token read once at 1,152 B, scored and weighed by every head), and
the share of the roofline that is. Beside it the XLA twins on the same
level. One JSON line; not a benchmark result.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402

from lib import peaks                               # noqa: E402


def timed(fn, level, args, n=50):
    out, level = fn(level, *args)                   # compiles
    jax.block_until_ready((out, level))
    t0 = time.perf_counter()
    for _ in range(n):
        out, level = fn(level, *args)
    jax.block_until_ready((out, level))
    return (time.perf_counter() - t0) / n


def main(argv):
    W, H, L, live, tokens = (int(a) for a in argv[1:6]) \
        if len(argv) > 5 else (64, 64, 4096, 40, 2000)
    from singa_tpu.serving import kv_cache
    pk = peaks.peaks_for(jax.devices()[0].device_kind)
    rng = np.random.default_rng(0)

    def fresh():                # the timed call donates its level
        return kv_cache.LatentLevel(jnp.asarray(
            np.random.default_rng(1).normal(size=(W, 1, L, 640)),
            jnp.bfloat16), 576, 512)

    q = jnp.asarray(rng.normal(size=(W, H, 1, 576)), jnp.bfloat16)
    row = jnp.asarray(rng.normal(size=(W, 576)), jnp.bfloat16)
    pos = np.clip(tokens * np.exp(0.4 * rng.normal(size=W)), 64,
                  L - 1).astype(np.int32)
    active = np.arange(W) < live
    rows = int(np.sum((pos + 1)[active]))
    scale = 192 ** -0.5

    def kernel(level, q, row, pos, active):
        return kv_cache.decode_token(level, q, row, None, pos, active, scale)

    def twins(level, q, row, pos, active):
        level = kv_cache.write_token(level, row, None, pos)
        return kv_cache.attend(q, level, pos, scale), level

    args = (q, row, jnp.asarray(pos), jnp.asarray(active))
    out = {"level": [W, 1, L, 640], "heads": H, "live": live,
           "rows_holding_a_token": rows,
           "ring_block": kv_cache.ring_block(fresh())}
    need_s = max(1152 * rows / pk["hbm_bytes_per_s"],
                 2 * H * 1088 * rows / pk["bf16_flops_per_s"])
    for name, fn in (("kernel", kernel), ("xla_twins", twins)):
        s = timed(jax.jit(fn, donate_argnums=(0,)), fresh(), args)
        out[f"{name}_us"] = round(s * 1e6, 1)
        out[f"{name}_roofline_pct"] = round(100 * need_s / s, 1)
    out["need_us"] = round(need_s * 1e6, 1)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv)
