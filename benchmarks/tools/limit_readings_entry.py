#!/usr/bin/env python3
"""`tools/limit_readings.py` for a serving cell whose driver is not
`serve_engine`: the driver comes from the cell's `entry`, and has to give
`reference_gaps(run, samples, cast, picks_out)` and `numbers(run, gaps,
evidence)`.

    python3 benchmarks/tools/limit_readings_entry.py <cell> <seed,seed,...> [control_seeds] [seconds]

For every seed: set-up as the benchmark makes it, a window of `seconds`
(default 12) at the cell's own load, the program's numbers against the plain
reference. For the first `control_seeds` seeds (default 3) also the
control's (the reference in fp8 in the program's place), and how often a
lower precision moves a token's set of picked experts: the share of
(token, layer) pairs at the compared positions whose pick set differs from
the float32 pass's, for the reference with bf16 operands (the program's own
rounding: what the program is expected to show) and with fp8 operands (the
control). Every set of numbers is held to the limits in the cell's file by
`run.verdict`. One JSON line a seed, also appended to
chiprun_out/limits_<cell>.jsonl; nothing here is a benchmark result.
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

import numpy as np                                  # noqa: E402

import run as bench_run                             # noqa: E402
from tools.limit_readings import _held              # noqa: E402


def picks_differ_share(picks, against, spans):
    """Share of (token, layer) pairs inside `spans` [(row's first, last)]
    whose sorted pick sets differ."""
    differ = total = 0
    for mine, theirs in zip(picks, against):
        for r, (s, e) in enumerate(spans):
            differ += int(np.sum(np.any(mine[r, s:e] != theirs[r, s:e],
                                        axis=-1)))
            total += e - s
    return differ / max(1, total)


def serve_cell(cell, seed, with_control, seconds):
    run = bench_run.Run(cell, seed, seconds, 0)
    D = importlib.import_module(f"drivers.{run.cell['entry']}")
    run.find_devices()
    h = D.setup(run)
    measured = D.window(run, h)
    evidence = D.release(run, h)
    picks = {} if with_control else None
    served, low = D.reference_gaps(
        run, evidence["samples"],
        cast="fp8_e4m3" if with_control else None, picks_out=picks)

    def numbers(gaps):
        # the widest gap beside the held numbers: it is no limit of a
        # sparse-expert cell, and the readings say why
        return dict(_held(D.numbers(run, gaps, evidence)),
                    logit_gap_widest=float(np.max(gaps)))
    out = {"seed": seed, "requests": measured["attempted"],
           "failed": measured["failed"], "tokens_compared": int(len(served)),
           "check_max_context": evidence["max_context"],
           "program": numbers(served), "phases": run.phases}
    if low is not None:
        out["control_fp8"] = numbers(low)
        out["picks_differ_share_fp8"] = picks_differ_share(
            picks["fp8_e4m3"], picks["float32"], picks["spans"])
        _, low16 = D.reference_gaps(run, evidence["samples"], cast="bf16",
                                    picks_out=picks)
        out["reference_bf16_operands"] = numbers(low16)
        out["picks_differ_share_bf16"] = picks_differ_share(
            picks["bf16"], picks["float32"], picks["spans"])
    return out


def main(argv):
    cell = argv[1]
    seeds = [int(s) for s in argv[2].split(",")]
    n_control = int(argv[3]) if len(argv) > 3 else 3
    seconds = float(argv[4]) if len(argv) > 4 else 12.0
    from singa_tpu.aot import cache as aot_cache
    aot_cache.install()
    os.makedirs("chiprun_out", exist_ok=True)
    for i, seed in enumerate(seeds):
        line = json.dumps(serve_cell(cell, seed, i < n_control, seconds))
        print(line, flush=True)
        with open(f"chiprun_out/limits_{cell}.jsonl", "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main(sys.argv)
