#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run = one new process = one cell: build, warm up the cell's own shapes,
measure for `--seconds`, read the peak memory, free the program, decide
`correct` against the plain reference, print one JSON line, exit. Everything
that belongs to one cell, configuration, traffic mix or per-layer metric is
a file of its own, found by name:

    --workload X -> workloads/X.json -> configs/<config>.json
                                     -> traffic/<traffic>.json
                                     -> drivers/<entry>.py
    --trace 1    -> layer_metrics/<m>.json for each m the cell's file names
                                     -> reducers/<reducer>.py

It needs a TPU whose `device_kind` is in lib/peaks.py and as many chips as
the cell asks for; otherwise it exits non-zero and prints no result.
"""

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import time

T_PROCESS_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
for _p in (CHECKOUT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Run:
    """What one run knows: the cell's files, the seed, the devices."""

    def __init__(self, workload, seed, seconds, trace, root=HERE,
                 require_chip=True, scratch=None):
        self.name = workload
        self.cell = load_json(root, "workloads", f"{workload}.json")
        self.config = load_json(root, "configs",
                                f"{self.cell['config']}.json")
        self.traffic = load_json(root, "traffic",
                                 f"{self.cell['traffic']}.json")
        self.seed, self.seconds, self.trace = int(seed), float(seconds), \
            bool(trace)
        self.require_chip = require_chip
        self.chips = int(self.cell["chips"])
        self.scratch = scratch or os.path.join(CHECKOUT, ".bench_scratch")
        self.trace_dir = os.path.join(self.scratch, "trace") \
            if self.trace else None
        self.t_start = T_PROCESS_START
        self.setup_s = None
        self.phases = []            # [(name, seconds since process start)]
        self.devices = self.peaks = self.device_info = None

    def start_trace(self):
        """Start the profiler for the traced slice (host annotations on,
        the Python tracer off: it slows the host it measures)."""
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def phase(self, name):
        """Note when a phase of the run ended (reported under `notes`)."""
        self.phases.append([name, time.perf_counter() - self.t_start])

    def find_devices(self):
        import jax
        from lib import peaks
        devs = jax.devices()
        d0 = devs[0]
        if self.require_chip:
            if d0.platform != "tpu":
                raise SystemExit(
                    f"benchmarks/run.py needs a TPU; JAX reports platform "
                    f"{d0.platform!r}. No result.")
            self.peaks = peaks.peaks_for(d0.device_kind)
        else:                   # tests only: never a device metric's source
            self.peaks = next(iter(peaks.PEAKS.values()))
        if len(devs) < self.chips:
            raise SystemExit(
                f"cell {self.name} needs {self.chips} chips; JAX reports "
                f"{len(devs)}. No result.")
        self.devices = devs[:self.chips]
        self.device_info = {"platform": d0.platform, "kind": d0.device_kind,
                            "count": len(self.devices)}

    def memory_peak(self):
        """(peak bytes on the fullest chip, that chip's allocator stats).
        The chip's allocator counts live arrays (`peak_bytes_in_use`) and
        the memory a running program reserves for its temporaries
        (`peak_bytes_reserved`) apart; what the chip holds is their sum
        (`bytes_limit` less both is `largest_free_block_bytes`, read on
        the v5e in PR 23)."""
        best = (None, {})
        for d in self.devices:
            stats = {k: int(v) for k, v in (d.memory_stats() or {}).items()}
            if "peak_bytes_in_use" not in stats:
                continue
            peak = stats["peak_bytes_in_use"] \
                + stats.get("peak_bytes_reserved", 0)
            if best[0] is None or peak > best[0]:
                best = (peak, stats)
        return best

    def mark_setup_done(self):
        """Process start -> the first measured step or request; and the
        compile cache's counters as set-up leaves them."""
        from singa_tpu.aot import cache as aot_cache
        self.setup_s = time.perf_counter() - self.t_start
        self.phase("setup_done")
        self.cache_at_setup = aot_cache.snapshot()


def layer_metrics(run, measured, trace):
    """The per-layer metrics the cell's file names, each by its reducer. A
    reducer that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for name in run.cell["layer_metrics"]:
        spec = load_json(HERE, "layer_metrics", f"{name}.json")
        reducer = importlib.import_module(f"reducers.{spec['reducer']}")
        value = reducer.compute(spec.get("args", {}), run, measured, trace)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def verdict(compared):
    """`correct`: something was compared, and every number is a number at
    or under its limit. `compared` is {name: (value, limit)}."""
    return bool(compared) and all(
        v == v and v <= lim for v, lim in compared.values())


def run_cell(run):
    """Drive one run to its result (a dict: the last line's object)."""
    run.find_devices()
    from singa_tpu.aot import cache as aot_cache
    aot_cache.install()
    if run.trace_dir:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        os.makedirs(run.trace_dir, exist_ok=True)
    driver = importlib.import_module(f"drivers.{run.cell['entry']}")

    run.phase("imports_and_devices")
    handle = driver.setup(run)
    measured = driver.window(run, handle)       # marks the end of set-up
    run.phase("window_done")
    after = aot_cache.snapshot()
    measured.setdefault("notes", {})["compiles_in_window"] = sum(
        after[k] - run.cache_at_setup[k] for k in ("hits", "misses"))
    peak, stats = run.memory_peak()
    device = dict(run.device_info, memory_peak_bytes=peak)
    measured["notes"]["memory_stats"] = stats
    evidence = driver.release(run, handle)
    del handle
    gc.collect()

    trace = None
    if run.trace_dir:
        from lib import xplane
        trace = xplane.reduce(xplane.load(xplane.find_xplane(run.trace_dir)))
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        metrics = layer_metrics(run, measured, trace)
    else:
        metrics = {k: {"value": float(v), "unit": u}
                   for k, (v, u) in driver.end_to_end(run, measured).items()}
        metrics["setup_s"] = {"value": run.setup_s, "unit": "s"}

    run.phase("released_and_reduced")
    compared = driver.check(run, evidence)
    run.phase("checked")
    result = {"correct": verdict(compared),
              "attempted": int(measured["attempted"]),
              "failed": int(measured["failed"]),
              "metrics": metrics, "device": device}
    if trace is not None:
        result["breakdown"] = trace["breakdown"]
    result["notes"] = dict(measured.get("notes", {}), phases=run.phases)
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    if run.trace_dir:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    return result


def print_result(result):
    for k, c in result["compared"].items():
        verdict = "ok" if c["value"] == c["value"] and \
            c["value"] <= c["limit"] else "OVER"
        print(f"compared {k}: {c['value']:.6g} limit {c['limit']:.6g} "
              f"{verdict}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(CHECKOUT, "singa_tpu")):
        raise SystemExit("benchmarks/run.py measures the singa_tpu package "
                         "of its checkout; there is none here. No result.")
    run = Run(args.workload, args.seed, args.seconds, args.trace)
    if str(run.cell.get("limits_from", "")).startswith("provisional"):
        raise SystemExit(
            f"cell {run.name}: its limits are provisional (no reading was "
            f"made at its own size; tools/limit_readings.py reads them). "
            f"No result.")
    print_result(run_cell(run))
    return 0


if __name__ == "__main__":
    sys.exit(main())
