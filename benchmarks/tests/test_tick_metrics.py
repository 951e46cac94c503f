"""The reducers that read the program's own tracing (PR 24):
`idle_by_annotation` on hand-made traces against a brute-force timeline and
on a recorded cut of a chip trace of the serve cell, `token_gaps` and
`recorder_stat` on hand-made inputs, and the toy rehearsals reporting the
metrics that need no TPU plane. No cell's file names the nine metrics yet
(PR 24 may edit no file the benchmark has): the rehearsals append them to
the cell in memory."""

import os

import numpy as np
import pytest

import run as bench_run
from lib import xplane
from reducers import idle_by_annotation, recorder_stat, token_gaps

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy")
TRAIN_CUT = os.path.join(HERE, "fixtures", "gpt2m_train_trace_cut.json.gz")
SERVE_CUT = os.path.join(HERE, "fixtures", "gpt2m_serve_trace_cut.json.gz")
SERVE_METRICS = ["idle_named_share", "idle_ms_per_tick.sample",
                 "idle_ms_per_tick.readback", "idle_ms_per_tick.other"]


def _metric_args(name):
    return bench_run.load_json(bench_run.HERE, "layer_metrics",
                               f"{name}.json")["args"]


def _brute_force(annotations, events, window, grid_ns=1.0):
    """{name: idle ns}: paint the window on a grid, an op over every cell
    it touches, and give each idle cell to the annotation open at it that
    began last (`(none)` where none is)."""
    w0, w1 = window
    n = int(round((w1 - w0) / grid_ns))
    busy = np.zeros(n, bool)
    for _, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            busy[int(round((a - w0) / grid_ns)):
                 int(round((b - w0) / grid_ns))] = True
    owner = np.full(n, -1)
    names = []
    for name, s, d in sorted(annotations, key=lambda e: (e[1], -e[2])):
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            names.append(name)
            owner[int(round((a - w0) / grid_ns)):
                  int(round((b - w0) / grid_ns))] = len(names) - 1
    out = {}
    for cell in np.flatnonzero(~busy):
        name = names[owner[cell]] if owner[cell] >= 0 else "(none)"
        out[name] = out.get(name, 0.0) + grid_ns
    return out


# two ticks of a serving engine, in whole nanoseconds. Ops run 100-400 and
# 520-800; the idle gap between them (400-520) spans the read-back's tail,
# the sampling, the bookkeeping, and the next tick's packing and dispatch.
TICKS = [
    ["serve.tick", 80, 400],
    ["serve.decode", 90, 370],                  # 90-460
    ["serve.decode.pack", 90, 5],
    ["serve.decode.dispatch", 95, 10],
    ["serve.decode.readback", 105, 315],        # 105-420: tail 400-420
    ["serve.decode.sample", 420, 40],           # 420-460
    ["serve.tick.post", 460, 20],               # 460-480
    ["serve.tick", 490, 400],
    ["serve.tick.reap", 490, 4],
    ["serve.tick.admit", 494, 6],
    ["serve.decode", 500, 380],
    ["serve.decode.pack", 500, 8],
    ["serve.decode.dispatch", 508, 12],         # 508-520
    ["serve.decode.readback", 520, 300],        # 520-820: tail 800-820
    ["serve.decode.sample", 820, 30],
]
OPS = [["fusion.1", 100, 300], ["fusion.2", 520, 280]]


def test_idle_time_goes_to_the_innermost_annotation_by_overlap():
    window = (50.0, 860.0)
    want = _brute_force(TICKS, OPS, window)
    # 50-80 and 480-490 lie under no annotation of the program
    assert want["(none)"] == 30 + 10
    assert want["serve.decode.sample"] == 40 + 30
    assert want["serve.decode.readback"] == 20 + 20
    assert want["serve.tick.post"] == 20
    for name in {n for n, _, _ in TICKS}:
        got = idle_by_annotation.reduce(
            TICKS, OPS, window, {"prefix": "serve.", "names": [name]})
        assert got * 1e9 == pytest.approx(want.get(name, 0.0)), name
    # the middle of the gap 400-520 lies under `post`: by the midpoint all
    # 120 ns would be its, by overlap 20
    mid = dict(xplane.idle_gaps(OPS, window, TICKS))
    assert mid["serve.tick.post"] * 1e9 == pytest.approx(120)


def test_share_per_and_complement():
    window = (50.0, 860.0)
    want = _brute_force(TICKS, OPS, window)
    idle = sum(want.values())
    named = idle - want["(none)"]
    share = idle_by_annotation.reduce(TICKS, OPS, window,
                                      {"prefix": "serve.", "share": True})
    assert share == pytest.approx(100.0 * named / idle)
    # ticks in the window: the first whole, 370 of the second's 400 ns
    ticks = idle_by_annotation.count_in_window(TICKS, "serve.tick", window)
    assert ticks == pytest.approx(1 + 370 / 400)
    parts = {}
    for metric in SERVE_METRICS[1:]:
        args = dict(_metric_args(metric), scale=1e9)
        parts[metric] = idle_by_annotation.reduce(TICKS, OPS, window, args)
    assert parts["idle_ms_per_tick.sample"] == pytest.approx(70 / ticks)
    assert parts["idle_ms_per_tick.readback"] == pytest.approx(40 / ticks)
    # the three close the account of the named idle time
    assert sum(parts.values()) == pytest.approx(named / ticks)
    # the phases of a tick the trace cut (the tick itself is not recorded)
    # are named idle time of the window, but of no tick
    cut = TICKS + [["serve.decode.sample", 55, 20]]
    assert idle_by_annotation.reduce(
        cut, OPS, window, {"prefix": "serve.", "share": True}) == \
        pytest.approx(100.0 * (named + 20) / idle)
    for metric, value in parts.items():
        args = dict(_metric_args(metric), scale=1e9)
        assert idle_by_annotation.reduce(cut, OPS, window, args) == \
            pytest.approx(value), metric


def test_an_annotation_that_outlives_its_parent_keeps_its_instants():
    """Another thread's annotation is not nested: the one that began last
    owns an instant until it ends."""
    notes = [["serve.a", 0, 10], ["serve.b", 5, 10], ["serve.c", 30, 5]]
    segments = idle_by_annotation.innermost(notes)
    assert segments == [(0, 5, "serve.a"), (5, 15, "serve.b"),
                        (30, 35, "serve.c")]
    want = _brute_force(notes, [["op", 8, 4]], (0.0, 40.0))
    for name in ("serve.a", "serve.b", "serve.c"):
        got = idle_by_annotation.reduce(notes, [["op", 8, 4]], (0.0, 40.0),
                                        {"prefix": "serve.", "names": [name]})
        assert got * 1e9 == pytest.approx(want[name])


def test_no_annotation_of_the_program_is_nothing_to_read():
    assert idle_by_annotation.reduce([], OPS, (50.0, 860.0),
                                     {"prefix": "serve.", "share": True}) \
        is None
    bench_only = [["bench.window", 0, 1000]]
    notes = [e for e in bench_only if e[0].startswith("serve.")]
    assert idle_by_annotation.reduce(notes, OPS, (50.0, 860.0),
                                     _metric_args("idle_ms_per_tick.other")) \
        is None


def test_on_a_cut_of_the_serve_cells_chip_trace():
    """A few ticks of gpt2m_serve_chat on a v5e (PR 24): the op line and
    the program's `serve.*` host events."""
    cut = xplane.load_json(SERVE_CUT)
    red = xplane.reduce(cut)
    notes = xplane.host_annotations(cut, "serve.")
    names = {n for n, _, _ in notes}
    assert {"serve.tick", "serve.decode", "serve.decode.pack",
            "serve.decode.dispatch", "serve.decode.readback",
            "serve.decode.sample", "serve.tick.post", "serve.tick.reap",
            "serve.tick.admit"} <= names
    events, window = red["events"][0], red["window"]
    idle_s = red["window_s"] - red["busy_s"]
    assert 0.05 < idle_s / red["window_s"] < 0.4
    # today's breakdown names none of it: it reads only `bench.*`
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps.get("(none)", 0.0) > 0.9 * idle_s
    values = {m: idle_by_annotation.reduce(notes, events, window,
                                           _metric_args(m))
              for m in SERVE_METRICS}
    assert values["idle_named_share"] > 95.0
    ticks = idle_by_annotation.count_in_window(notes, "serve.tick", window)
    assert ticks == pytest.approx(3.0, abs=0.01)
    per_tick = sum(values[m] for m in SERVE_METRICS[1:])
    named_ms = idle_s * 1e3 * values["idle_named_share"] / 100.0 / ticks
    assert per_tick == pytest.approx(named_ms, rel=1e-6)
    # against the brute-force timeline, on a 1 us grid
    brute = _brute_force(notes, events, window, grid_ns=1000.0)
    sample = sum(brute.get(n, 0.0) for n in
                 _metric_args("idle_ms_per_tick.sample")["names"])
    assert values["idle_ms_per_tick.sample"] == pytest.approx(
        sample / 1e6 / ticks, rel=0.02)


def test_token_gaps_reads_the_requests_that_ended_in_the_window():
    a = [10.0, 10.04, 10.08, 10.20]             # ended inside
    b = [9.0, 9.5, 30.0]                        # ended after the window
    c = [10.5]                                  # one token: no gap
    got = token_gaps.gaps([a, b, c], 10.0, 20.0)
    assert np.allclose(got, [0.04, 0.04, 0.12])

    class Future:
        def __init__(self, times):
            self.token_times = times

    measured = {"snap_start": {"t": 10.0}, "snap_end": {"t": 20.0},
                "records": [{"handle": Future(a), "done_at": 10.2},
                            {"handle": Future(b), "done_at": 30.0},
                            {"handle": Future(c), "done_at": 10.5},
                            {"handle": None, "done_at": 11.0},
                            {"handle": Future(a), "done_at": None}]}
    args = {"percentile": 50, "scale": 1000.0}
    assert token_gaps.compute(args, None, measured, None) == \
        pytest.approx(40.0)
    # a program whose futures carry no stamps: nothing to read
    measured["records"] = [{"handle": object(), "done_at": 10.2}]
    assert token_gaps.compute(args, None, measured, None) is None


def test_recorder_stat_selects_by_kind_name_where_and_when():
    records = [
        {"kind": "span", "name": "compile", "ts": 5.0, "ts_start": 1.0,
         "dur_s": 4.0},
        {"kind": "span", "name": "compile", "ts": 50.0, "ts_start": 49.0,
         "dur_s": 1.0},                         # after set-up: not counted
        {"kind": "event", "name": "compile", "ts": 8.0, "compile_s": 2.5,
         "program": "train_step"},
        {"kind": "event", "name": "retrace", "ts": 9.0, "compile_s": 0.5,
         "program": "serve_decode"},
        {"kind": "span", "name": "serve.decode", "ts": 21.0,
         "ts_start": 20.9, "dur_s": 0.1,
         "phases": {"sample": 0.03, "pack": 0.01}},
        {"kind": "span", "name": "serve.decode", "ts": 22.0,
         "ts_start": 21.9, "dur_s": 0.1, "phases": {"sample": 0.05}},
        {"kind": "span", "name": "serve.decode", "ts": 41.0,
         "ts_start": 40.9, "dur_s": 0.1, "phases": {"sample": 9.0}},
    ]
    pick = (lambda args: recorder_stat.select(records, args, 10.0,
                                              (20.0, 30.0)))
    assert pick(_metric_args("setup_model_compile_s")) == [4.0]
    assert pick(_metric_args("setup_program_compile_s")) == [2.5, 0.5]
    assert pick({"kind": "event", "name": "compile", "field": "compile_s",
                 "where": {"program": "serve_decode"},
                 "when": "setup"}) == []
    sample = {"kind": "span", "name": "serve.decode",
              "field": "phases.sample", "when": "window"}
    assert pick(sample) == [0.03, 0.05]
    assert pick(dict(sample, field="phases.pack")) == [0.01]
    assert recorder_stat.statistic([0.03, 0.05], "mean") == \
        pytest.approx(0.04)
    assert recorder_stat.statistic([1.0, 2.0], "sum") == 3.0
    assert recorder_stat.statistic(list(range(101)), "p95") == \
        pytest.approx(95.0)
    assert recorder_stat.statistic([], "sum") is None
    with pytest.raises(ValueError):
        recorder_stat.statistic([1.0], "median")


SERVE_CELL = ["token_gap_p95_ms", "tick_ms", *SERVE_METRICS,
              "setup_model_compile_s", "setup_program_compile_s"]
TRAIN_CELL = ["setup_model_compile_s", "setup_program_compile_s",
              "setup_rehearse_s"]


def _traced(cell, names, seed, tmp_path, monkeypatch, seconds=3.0):
    """A `--trace 1` rehearsal on the CPU with `names` appended to the
    cell's `layer_metrics`, as the `benchmark` PR that switches the metrics
    on will write them into the cells' files (PERF.md section 7: this PR may
    edit no file the benchmark has). The CPU's profiler records no TPU
    plane, so the train cell's recorded cut stands in for the device: it
    holds no annotation of the serving engine."""
    cut = xplane.load_json(TRAIN_CUT)
    monkeypatch.setattr(xplane, "load", lambda path, keep_host=(): cut)
    run = bench_run.Run(cell, seed, seconds, 1, root=TOY,
                        require_chip=False, scratch=str(tmp_path))
    run.cell["layer_metrics"] = run.cell["layer_metrics"] + names
    return bench_run.run_cell(run)


def test_toy_serve_rehearsal_reports_what_needs_no_tpu_plane(tmp_path,
                                                             monkeypatch):
    result = _traced("toy_gpt2_serve", SERVE_CELL, 11, tmp_path,
                     monkeypatch)
    metrics = result["metrics"]
    assert {"token_gap_p95_ms", "tick_ms", "setup_model_compile_s",
            "setup_program_compile_s", "decode_tick_ms",
            "prefill_batch_ms"} <= set(metrics)
    # the device-clock metrics found no `serve.*` event: left out
    assert not set(SERVE_METRICS) & set(metrics)
    assert metrics["tick_ms"]["value"] >= metrics["decode_tick_ms"]["value"]
    assert metrics["token_gap_p95_ms"]["value"] > 0
    assert 0 < metrics["setup_program_compile_s"]["value"] \
        < result["notes"]["phases"][-1][1]
    assert result["correct"], result["compared"]


def test_toy_train_rehearsal_reports_its_set_up_metrics(tmp_path,
                                                        monkeypatch):
    result = _traced("toy_gpt2_train", TRAIN_CELL, 7, tmp_path, monkeypatch,
                     seconds=2.0)
    metrics = result["metrics"]
    assert {"setup_model_compile_s", "setup_program_compile_s",
            "setup_rehearse_s", "setup_cache_misses"} <= set(metrics)
    setup_s = dict(map(tuple, result["notes"]["phases"]))["setup_done"]
    for name in TRAIN_CELL:
        assert 0 < metrics[name]["value"] < setup_s, name


def test_every_new_metric_file_names_a_reducer_of_this_pr():
    """The nine metric files are data for reducers that exist, and move an
    end-to-end metric that `BENCHMARK.json` has."""
    import importlib
    import json
    with open(os.path.join(bench_run.CHECKOUT, "BENCHMARK.json")) as f:
        end_to_end = {m["name"] for m in json.load(f)["end_to_end"]}
    for name in sorted(set(SERVE_CELL) | set(TRAIN_CELL)):
        spec = bench_run.load_json(bench_run.HERE, "layer_metrics",
                                   f"{name}.json")
        assert spec["reducer"] in ("recorder_stat", "token_gaps",
                                   "idle_by_annotation"), name
        assert hasattr(importlib.import_module(
            f"reducers.{spec['reducer']}"), "compute")
        assert spec["moves"] in end_to_end
