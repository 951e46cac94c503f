"""Every data file loads, and BENCHMARK.json agrees with the files."""

import glob
import importlib
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _names(kind):
    return sorted(os.path.basename(p)[:-5]
                  for p in glob.glob(os.path.join(BENCH, kind, "*.json")))


BENCHMARK = _load(CHECKOUT, "BENCHMARK.json")
E2E = {m["name"]: m for m in BENCHMARK["end_to_end"]}
CELLS = {w["name"]: w for w in BENCHMARK["workloads"]}


def _reports(cell_name, metric):
    """Does the cell report this end-to-end metric, by BENCHMARK.json?"""
    cells = E2E[metric].get("workloads")
    return cells is None or cell_name in cells


@pytest.mark.parametrize("name", _names("workloads"))
def test_workload_file(name):
    cell = _load(BENCH, "workloads", f"{name}.json")
    assert cell["name"] == name and cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    config = _load(BENCH, "configs", f"{cell['config']}.json")
    traffic = _load(BENCH, "traffic", f"{cell['traffic']}.json")
    assert os.path.exists(os.path.join(BENCH, "drivers",
                                       f"{cell['entry']}.py"))
    importlib.import_module(f"lib.references.{config['reference']}")
    assert traffic["kind"] in ("train", "open_loop")
    assert cell["limits"] and "bytes" in cell
    for m in cell["layer_metrics"]:
        spec = _load(BENCH, "layer_metrics", f"{m}.json")
        # a metric a cell names moves an end-to-end metric that cell reports
        # (cells not yet in BENCHMARK.json are checked once they are)
        if name in CELLS:
            assert _reports(name, spec["moves"]), (name, m, spec["moves"])


@pytest.mark.parametrize("name", _names("configs"))
def test_config_file(name):
    config = _load(BENCH, "configs", f"{name}.json")
    assert config["name"] == name and config["source"]
    assert isinstance(config["reduced"], list)
    ref = importlib.import_module(f"lib.references.{config['reference']}")
    specs = ref.param_specs(config)
    assert len({n for n, *_ in specs}) == len(specs) > 10


@pytest.mark.parametrize("name", _names("layer_metrics"))
def test_layer_metric_file(name):
    spec = _load(BENCH, "layer_metrics", f"{name}.json")
    assert spec["name"] == name
    assert spec["moves"] in E2E
    assert spec["better"] in ("lower", "higher")
    assert spec["source"] in ("device_trace", "program_span",
                              "program_counter", "host_clock")
    assert hasattr(importlib.import_module(f"reducers.{spec['reducer']}"),
                   "compute")


def test_benchmark_json_agrees_with_the_files():
    assert BENCHMARK["command"] == ["python3", "benchmarks/run.py"]
    assert BENCHMARK["paths"] == ["benchmarks"]
    for c in BENCHMARK["configs"]:
        config = _load(CHECKOUT, c["file"])
        assert config["name"] == c["name"]
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
    used = set()
    for w in BENCHMARK["workloads"]:
        cell = _load(BENCH, "workloads", f"{w['name']}.json")
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        assert cell["why"] == w["why"]
        used.add(w["config"])
    assert used == {c["name"] for c in BENCHMARK["configs"]}
    four = sum(1 for w in BENCHMARK["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCHMARK["workloads"]) // 4)
    per_layer = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name, m in per_layer.items():
        spec = _load(BENCH, "layer_metrics", f"{name}.json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (name, key)
        # BENCHMARK.json lists on the metric exactly the cells whose files
        # name it
        naming = sorted(w for w in CELLS if name in
                        _load(BENCH, "workloads", f"{w}.json")["layer_metrics"])
        assert sorted(m["workloads"]) == naming, name
        for w in m["workloads"]:
            assert _reports(w, m["moves"])
    named = {m for w in CELLS
             for m in _load(BENCH, "workloads", f"{w}.json")["layer_metrics"]}
    assert named == set(per_layer)
    for w in CELLS:                     # every cell: setup_s + another
        assert sum(1 for m in E2E if _reports(w, m)) >= 2


def test_toy_files_are_never_named_in_benchmark_json():
    toy = os.path.join(BENCH, "tests", "toy")
    text = json.dumps(BENCHMARK)
    for kind in ("configs", "workloads", "traffic"):
        for p in glob.glob(os.path.join(toy, kind, "*.json")):
            assert os.path.basename(p)[:-5] not in text
