#!/usr/bin/env python3
"""Read, on the chip and in one process, what a cell's limits are set from.

    python3 benchmarks/tools/limit_readings.py <cell> <seed,seed,...> [control_seeds]

For every seed: the program's numbers (set-up as the benchmark makes it, a
short window at the cell's own load for a serving cell) against the plain
reference. For the first `control_seeds` seeds (default 3) also the
control's (the reference in fp8 in the program's place) and, for a training
cell, the planted faults' (half of the batch left out; for a cell across
chips, one chip's share of the batch, which is the exchange left out),
planted in the reference put in the program's place, on the share repeated
to the full batch so that no program is compiled anew. A state left unchanged
reads 1 by the measure and needs no run. Every set of numbers is held to the
limits in the cell's file by the benchmark's own rule (`run.verdict`) and
its verdict printed beside it under `correct`: true for the program, false
for the control and for each fault, or the limits do not hold. One JSON line
a seed; nothing here is a benchmark result.
"""

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
from lib import compare                             # noqa: E402


def _held(compared):
    """{name: value} of {name: (value, limit)}, with the verdict."""
    out = {k: v for k, (v, _) in compared.items()}
    out["correct"] = bench_run.verdict(compared)
    return out


def _tiled(batch, parts):
    """The first 1/parts of the rows, repeated to the full batch: the same
    shapes (and compiled programs) as the whole batch, and exactly the
    gradient, batch statistics and loss of the share alone."""
    import jax.numpy as jnp
    n = batch[0].shape[0] // parts
    return tuple(jnp.concatenate([a[:n]] * parts) for a in batch)


def train_cell(cell, seed, with_control):
    from drivers import train_step as D
    run = bench_run.Run(cell, seed, 1.0, 0)
    run.find_devices()
    h = D.setup(run)
    run.mark_setup_done()
    evidence = D.release(run, h)
    batch = int(run.traffic["batch_per_chip"]) * run.chips
    _, ref_batch = D._make_feed(run.config, run.traffic, batch, seed)
    args = (run.config, run.traffic["optimizer"], seed)
    ref = compare.reference_train(*args, ref_batch, D.CHECK_STEPS)
    limits = run.cell["limits"]

    def numbers(prog):
        return _held(compare.train_numbers(prog, ref, limits))
    out = {"seed": seed, "program": numbers(evidence),
           "program_losses": evidence["losses"], "ref_losses": ref["losses"],
           "phases": run.phases}
    if with_control:
        out["fault_half_batch"] = numbers(compare.reference_train(
            *args, _tiled(ref_batch, 2), D.CHECK_STEPS))
        if run.chips > 1:
            out["fault_no_exchange"] = numbers(compare.reference_train(
                *args, _tiled(ref_batch, run.chips), D.CHECK_STEPS))
        out["control_fp8"] = numbers(compare.reference_train(
            *args, ref_batch, D.CHECK_STEPS, cast="fp8_e4m3"))
    return out


def serve_cell(cell, seed, with_control, seconds=8.0):
    from drivers import serve_engine as D
    run = bench_run.Run(cell, seed, seconds, 0)
    run.find_devices()
    h = D.setup(run)
    measured = D.window(run, h)
    evidence = D.release(run, h)
    served, low = D.reference_gaps(
        run, evidence["samples"], cast="fp8_e4m3" if with_control else None)
    def numbers(gaps):
        return dict(_held(compare.served_numbers(
            {"gaps": gaps, "unanswered": evidence["unanswered"]},
            run.cell["limits"])),
            logit_gap_p99=float(np.percentile(gaps, 99)))
    out = {"seed": seed, "requests": measured["attempted"],
           "failed": measured["failed"], "tokens_compared": int(len(served)),
           "program": numbers(served)}
    if low is not None:
        out["control_fp8"] = numbers(low)
    return out


def main(argv):
    cell = argv[1]
    seeds = [int(s) for s in argv[2].split(",")]
    n_control = int(argv[3]) if len(argv) > 3 else 3
    from singa_tpu.aot import cache as aot_cache
    aot_cache.install()
    entry = bench_run.load_json(BENCH, "workloads", f"{cell}.json")["entry"]
    fn = {"train_step": train_cell, "serve_engine": serve_cell}[entry]
    os.makedirs("chiprun_out", exist_ok=True)
    for i, seed in enumerate(seeds):
        line = json.dumps(fn(cell, seed, i < n_control))
        print(line, flush=True)
        with open(f"chiprun_out/limits_{cell}.jsonl", "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main(sys.argv)
