#!/usr/bin/env python3
"""Write into BENCHMARK.json what the benchmark's files already say.

    python3 benchmarks/tools/sync_benchmark_json.py [cell ...]

Keeps `command`, `paths`, `run_seconds` and the `end_to_end` entries (names,
bounds, cells) as they stand. Rewrites `configs`, `workloads` and
`per_layer` from configs/, workloads/ and layer_metrics/ for the cells
already named (plus any given as arguments, appended in that order), so the
two cannot disagree (tests/test_files.py holds them to it). A new cell's
name still has to be added by hand to the `workloads` of each end-to-end
metric it reports.
"""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def main(argv):
    path = os.path.join(ROOT, "BENCHMARK.json")
    doc = load(path)
    cells = [w["name"] for w in doc["workloads"]]
    cells += [c for c in argv[1:] if c not in cells]
    workloads, configs, per_layer = [], {}, {}
    for name in cells:
        cell = load(BENCH, "workloads", f"{name}.json")
        workloads.append({k: cell[k] for k in
                          ("name", "config", "traffic", "chips", "why")})
        cfg = load(BENCH, "configs", f"{cell['config']}.json")
        configs[cfg["name"]] = {
            "name": cfg["name"], "source": cfg["source"],
            "file": f"benchmarks/configs/{cfg['name']}.json",
            "reduced": cfg["reduced"], "why": cfg["why"]}
        for m in cell["layer_metrics"]:
            spec = load(BENCH, "layer_metrics", f"{m}.json")
            entry = per_layer.setdefault(m, {
                **{k: spec[k] for k in ("name", "unit", "better", "source",
                                        "layer", "moves")},
                "workloads": []})
            entry["workloads"].append(name)
    doc["configs"] = list(configs.values())
    doc["workloads"] = workloads
    doc["per_layer"] = list(per_layer.values())
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"{len(workloads)} cells, {len(configs)} configs, "
          f"{len(per_layer)} per-layer metrics")


if __name__ == "__main__":
    main(sys.argv)
