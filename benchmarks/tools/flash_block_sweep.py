#!/usr/bin/env python3
"""The flash kernels' block sizes swept at one shape, on the chip.

    python3 benchmarks/tools/flash_block_sweep.py [B] [H] [S] [D]

For each (block_q, block_k): the forward kernel and the two backward kernels
of `ops/attention.py` (causal, bf16, the heads a grid step that
`_heads_per_step` picks), called 20 times each under the profiler; the
device time a call of each kernel from the trace's op line, and the block
pair the rule `_pick_blocks` takes marked. Defaults: 2 x 16 heads of 128 at
S 4096 (the looped train cell's attention). One JSON line a pair, also
written to chiprun_out/flash_block_sweep.jsonl; not a benchmark result.
"""

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402

from lib import xplane                              # noqa: E402

PAIRS = [(256, 256), (256, 512), (512, 256), (512, 512), (512, 1024),
         (1024, 512), (1024, 1024)]
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
CALLS = 20


def traced_ms(fn, args, calls=CALLS):
    """{kernel: device ms a call} of `calls` calls of `fn` under the
    profiler."""
    jax.block_until_ready(fn(*args))                # compiles
    out_dir = tempfile.mkdtemp(prefix="flash_sweep_")
    jax.profiler.start_trace(out_dir)
    try:
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    events = xplane.device_op_events(xplane.load(
        xplane.find_xplane(out_dir)))
    shutil.rmtree(out_dir, ignore_errors=True)
    chip = min(events)
    return {k: 1e3 * xplane.matching_seconds(events[chip], [k])[0]
            / calls for k in KERNELS}


def main(argv):
    from singa_tpu.ops import attention_mod as A
    B, H, S, D = (int(a) for a in argv[1:5]) if len(argv) > 4 \
        else (2, 16, 4096, 128)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("flash_block_sweep.py times the Pallas kernels on "
                         "a TPU; there is none here.")
    rng = np.random.default_rng(0)
    q, k, v, g = (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.bfloat16)
                  for _ in range(4))
    scale = 1.0 / float(np.sqrt(D))
    rule = A._pick_blocks(S, S)
    os.makedirs("chiprun_out", exist_ok=True)
    for bq, bk in PAIRS:
        if S % bq or S % bk:
            continue

        def fwd(q, k, v, bq=bq, bk=bk):
            return A._pallas_flash_fwd(q, k, v, True, scale, bq, bk)

        out, lse = jax.block_until_ready(fwd(q, k, v))

        def bwd(q, k, v, out, lse, g, bq=bq, bk=bk):
            return A._pallas_flash_bwd(q, k, v, out, lse, g, True, scale,
                                       bq, bk)

        ms = traced_ms(fwd, (q, k, v))
        ms.update({n: t for n, t in traced_ms(
            bwd, (q, k, v, out, lse, g)).items() if n != "flash_fwd"})
        line = json.dumps({
            "shape": [B, H, S, D], "blocks": [bq, bk],
            "rule": [bq, bk] == list(rule),
            "heads_a_step": A._heads_per_step(B * H, bq, bk, D, 2),
            "ms": ms, "total_ms": sum(ms.values())})
        print(line, flush=True)
        with open("chiprun_out/flash_block_sweep.jsonl", "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main(sys.argv)
