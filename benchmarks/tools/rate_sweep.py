#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip: the highest offered rate at
which the backlog at the end of the window is no larger than at its middle.

    python3 benchmarks/tools/rate_sweep.py <cell> <rate,rate,...> [seconds] [seed]

One engine, built as the benchmark builds it, takes each rate in turn for
`seconds` (default 20) of the cell's own traffic and drains before the next.
One JSON line a rate; the cell's traffic file then gets four fifths of the
knee as its `rate_rps`, with these lines beside it. Not a benchmark result.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run                             # noqa: E402
from lib import loadgen                             # noqa: E402


def main(argv):
    cell = argv[1]
    rates = [float(r) for r in argv[2].split(",")]
    seconds = float(argv[3]) if len(argv) > 3 else 20.0
    seed = int(argv[4]) if len(argv) > 4 else 1
    from drivers import serve_engine as D
    from singa_tpu.aot import cache as aot_cache
    aot_cache.install()
    run = bench_run.Run(cell, seed, seconds, 0)
    run.find_devices()
    h = D.setup(run)
    for i, rate in enumerate(rates):
        traffic = dict(run.traffic, rate_rps=rate)
        schedule = loadgen.make_schedule(
            traffic, int(run.config["vocab_size"]), seed + i, seconds)
        h.schedule = schedule
        m = D.window(run, h)
        t0 = m["snap_start"]["t"]
        recs = m["records"]

        def backlog(at):
            return sum(1 for r in recs if r["submitted"] is not None
                       and r["submitted"] <= t0 + at
                       and (r["done_at"] is None or r["done_at"] > t0 + at))
        tokens = m["snap_end"]["tokens"] - m["snap_start"]["tokens"]
        span = m["snap_end"]["t"] - m["snap_start"]["t"]
        print(json.dumps({
            "rate_rps": rate, "requests": m["attempted"],
            "failed": m["failed"],
            "backlog_middle": backlog(seconds / 2),
            "backlog_end": backlog(seconds),
            "ttft_p50_ms": loadgen.percentile(m["ttft_ms"], 50),
            "ttft_p95_ms": loadgen.percentile(m["ttft_ms"], 95),
            "tpot_p50_ms": loadgen.percentile(m["tpot_ms"], 50),
            "tpot_p95_ms": loadgen.percentile(m["tpot_ms"], 95),
            "output_tokens_per_s": tokens / span,
            "drain_s": m["notes"]["drain_s"],
            "decode_tick_ms": 1e3 * (
                m["snap_end"]["token_seconds"]["sum"]
                - m["snap_start"]["token_seconds"]["sum"]) / max(1, (
                    m["snap_end"]["token_seconds"]["count"]
                    - m["snap_start"]["token_seconds"]["count"])),
            "generator_late_ms_max": m["notes"]["generator_late_ms_max"],
        }), flush=True)
    D.release(run, h)


if __name__ == "__main__":
    main(sys.argv)
