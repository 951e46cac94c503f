#!/usr/bin/env python3
"""`tools/rate_sweep.py` for a serving cell whose driver is not
`serve_engine`: the driver comes from the cell's `entry`.

    python3 benchmarks/tools/rate_sweep_entry.py <cell> <rate,rate,...> [seconds] [seed] [runs]

One engine, built as the benchmark builds it, takes each rate `runs` times
(default 2, each with a schedule of its own) for `seconds` (default 30) of
the cell's own traffic and drains before the next. One JSON line a run, also
appended to chiprun_out/sweep_<cell>.jsonl. Not a benchmark result.
"""

import importlib
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
    seconds = float(argv[3]) if len(argv) > 3 else 30.0
    seed = int(argv[4]) if len(argv) > 4 else 1
    runs = int(argv[5]) if len(argv) > 5 else 2
    from singa_tpu.aot import cache as aot_cache
    aot_cache.install()
    run = bench_run.Run(cell, seed, seconds, 0)
    D = importlib.import_module(f"drivers.{run.cell['entry']}")
    run.find_devices()
    h = D.setup(run)
    os.makedirs("chiprun_out", exist_ok=True)
    n = 0
    for rate in rates:
        for _ in range(runs):
            n += 1
            traffic = dict(run.traffic, rate_rps=rate)
            h.schedule = loadgen.make_schedule(
                traffic, int(run.config["vocab_size"]), seed + n, seconds)
            m = D.window(run, h)
            a, b = m["snap_start"], m["snap_end"]
            t0, recs = a["t"], m["records"]

            def backlog(at):
                return sum(1 for r in recs if r["submitted"] is not None
                           and r["submitted"] <= t0 + at
                           and (r["done_at"] is None
                                or r["done_at"] > t0 + at))
            ticks = b["token_seconds"]["count"] - a["token_seconds"]["count"]
            line = json.dumps({
                "rate_rps": rate, "seconds": seconds,
                "requests": m["attempted"], "failed": m["failed"],
                "backlog_middle": backlog(seconds / 2),
                "backlog_end": backlog(seconds),
                "ttft_p50_ms": loadgen.percentile(m["ttft_ms"], 50),
                "ttft_p95_ms": loadgen.percentile(m["ttft_ms"], 95),
                "tpot_p50_ms": loadgen.percentile(m["tpot_ms"], 50),
                "tpot_p95_ms": loadgen.percentile(m["tpot_ms"], 95),
                "output_tokens_per_s": (b["tokens"] - a["tokens"])
                / (b["t"] - a["t"]),
                "drain_s": m["notes"]["drain_s"],
                "decode_tick_ms": 1e3 * (b["token_seconds"]["sum"]
                                         - a["token_seconds"]["sum"])
                / max(1, ticks),
                "prefill_ms": 1e3 * sum(m["prefill_span_s"])
                / max(1, len(m["prefill_span_s"])),
                "generator_late_ms_max": m["notes"]["generator_late_ms_max"],
            })
            print(line, flush=True)
            with open(f"chiprun_out/sweep_{cell}.jsonl", "a") as f:
                f.write(line + "\n")
    D.release(run, h)


if __name__ == "__main__":
    main(sys.argv)
