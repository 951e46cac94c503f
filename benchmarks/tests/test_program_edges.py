"""The six metrics of PR 35 that split the serving tick's host time:
`program_edges` (launch and completion of the decode program, joined to the
device's `XLA Modules` line) on hand-made traces with known gaps and clock
offsets, `idle_host_share` through `idle_by_annotation`, and a toy rehearsal
that reports the three read from the flight recorder. No cell names the six
yet: a program PR may not edit the cells' files, so the names are appended to
the four serve cells' `layer_metrics` (and BENCHMARK.json's `per_layer`) by a
`benchmark` PR; the tests name them on the cell they load."""

import os

import pytest

import run as bench_run
from lib import xplane
from reducers import idle_by_annotation, program_edges

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy")
TRAIN_CUT = os.path.join(HERE, "fixtures", "gpt2m_train_trace_cut.json.gz")
NEW = ["dispatch_call_ms", "dispatch_cpu_ms", "readback_fetch_ms",
       "decode_launch_ms", "decode_completion_ms", "idle_host_share"]
SERVE = ["gpt2m_serve_chat", "cmdaplus_serve_rag", "phi4flash_serve_reason",
         "longcat_serve_longform"]
MS = 1e6                                        # ns a millisecond


def _args(name):
    return bench_run.load_json(bench_run.HERE, "layer_metrics",
                               f"{name}.json")["args"]


def _ticks(n, launch, completion, offset=0.0, prefill_every=4,
           period=4.0):
    """(annotations, modules) of `n` serving ticks in ns: a tick every
    `period` ms, a prefill ahead of the decode in every `prefill_every`-th.
    Tick i's decode run starts `launch(i)` ms after its call began and the
    host sees it ready `completion(i)` ms after it ended, on the host's
    clock; the device's clock reads `offset` ms ahead of the host's."""
    notes, modules = [], []

    def program(span, module, at, run_ms, lat, comp):
        call = at + 0.2
        start = call + lat
        end = start + run_ms
        ready_end = end + comp
        notes.extend([
            [span, at * MS, (ready_end + 0.1 - at) * MS],
            [f"{span}.dispatch", call * MS, 0.4 * MS],
            [f"{span}.call", call * MS, 0.3 * MS],
            [f"{span}.readback", (call + 0.4) * MS,
             (ready_end + 0.05 - call - 0.4) * MS],
            [f"{span}.ready", (call + 0.4) * MS,
             (ready_end - call - 0.4) * MS],
            [f"{span}.fetch", ready_end * MS, 0.05 * MS]])
        modules.append([module, (start + offset) * MS, run_ms * MS])
        return ready_end + 0.1

    at = 0.0
    for i in range(n):
        t0 = at
        if prefill_every and i % prefill_every == 0:
            at = program("serve.prefill", "jit_prefill_body(7)", at, 6.0,
                         0.2, 0.5)
        at = program("serve.decode", "jit_decode_body(9)", at, 1.5,
                     launch(i), completion(i))
        at = max(at, t0 + period)
    return notes, modules


def _window(notes):
    return (0.0, max(s + d for _, s, d in notes) + 4 * MS)


def _edges(notes, modules, window=None):
    return program_edges.edges(notes, modules, window or _window(notes),
                               _args("decode_launch_ms"))


LAUNCH = (lambda i: 0.1 + 0.05 * (i % 5))      # 0.10-0.30 ms, median 0.2
COMPLETION = (lambda i: 0.6)


def test_launch_and_completion_of_every_tick():
    notes, modules = _ticks(10, LAUNCH, COMPLETION)
    got = _edges(notes, modules)
    assert got["launch"] == pytest.approx([LAUNCH(i) for i in range(10)])
    assert got["completion"] == pytest.approx([0.6] * 10)
    window = _window(notes)
    assert program_edges.reduce(notes, modules, window,
                                _args("decode_launch_ms")) == \
        pytest.approx(0.2)
    assert program_edges.reduce(notes, modules, window,
                                _args("decode_completion_ms")) == \
        pytest.approx(0.6)


@pytest.mark.parametrize("offset", [0.5, -0.5])
def test_a_clock_offset_moves_the_two_edges_apart_and_is_kept(offset):
    """launch + completion does not depend on the offset; each edge moves
    by it, and a reading below 0 is returned as it is."""
    notes, modules = _ticks(12, LAUNCH, COMPLETION, offset=offset)
    got = _edges(notes, modules)
    assert got["launch"] == pytest.approx(
        [LAUNCH(i) + offset for i in range(12)])
    assert got["completion"] == pytest.approx([0.6 - offset] * 12)
    if offset < 0:
        assert sum(v < 0 for v in got["launch"]) == 12 - sum(
            LAUNCH(i) > 0.5 for i in range(12))
    assert [a + b for a, b in zip(got["launch"], got["completion"])] == \
        pytest.approx([LAUNCH(i) + 0.6 for i in range(12)])


def test_an_offset_past_half_a_tick_still_pairs_each_call_with_its_run():
    """2.5 ms on a 4 ms tick: the nearest run to a call is the next tick's,
    and the prefills show which shift is right."""
    notes, modules = _ticks(16, LAUNCH, COMPLETION, offset=2.5)
    got = _edges(notes, modules)
    assert got["launch"] == pytest.approx(
        [LAUNCH(i) + 2.5 for i in range(16)])


def test_a_tick_the_slice_cuts_is_left_out():
    notes, modules = _ticks(10, LAUNCH, COMPLETION)
    spans_ = sorted((s, s + d) for n, s, d in notes if n == "serve.decode")
    # the slice begins inside the first decode span and ends inside the
    # last one
    window = (spans_[0][0] + 1.0, spans_[-1][1] - 1.0)
    got = _edges(notes, modules, window)
    assert got["launch"] == pytest.approx([LAUNCH(i) for i in range(1, 9)])


def test_nothing_to_pair_is_nothing_to_read():
    notes, modules = _ticks(6, LAUNCH, COMPLETION)
    window = _window(notes)
    for edge in ("decode_launch_ms", "decode_completion_ms"):
        args = _args(edge)
        # no module event (a trace without the device's module line)
        assert program_edges.reduce(notes, [], window, args) is None
        # no `.call` annotation (a program that does not split dispatch)
        parent = [e for e in notes if not e[0].endswith((".call",
                                                         ".ready"))]
        assert program_edges.reduce(parent, modules, window, args) is None
        assert program_edges.reduce([], modules, window, args) is None


def test_idle_host_share_leaves_out_serve_idle_and_what_no_annotation_covers():
    notes = [["serve.tick", 0, 100], ["serve.decode", 5, 90],
             ["serve.decode.sample", 60, 20], ["serve.idle", 150, 50]]
    ops = [["fusion.1", 10, 50], ["fusion.2", 210, 40]]
    window = (0.0, 300.0)
    # idle 210 ns: 0-10 and 60-100 under the engine's work (50), 150-200
    # under serve.idle, 100-150, 200-210 and 250-300 under nothing
    share = idle_by_annotation.reduce(notes, ops, window,
                                      _args("idle_host_share"))
    assert share == pytest.approx(100.0 * 50 / 210)
    assert idle_by_annotation.reduce(
        [["serve.idle", 0, 300]], ops, window,
        _args("idle_host_share")) == 0.0


def test_the_six_files_are_the_serving_layers_and_move_the_tpot():
    for name in NEW:
        spec = bench_run.load_json(bench_run.HERE, "layer_metrics",
                                   f"{name}.json")
        assert spec["layer"] == \
            "serving (serving/engine.py, scheduler.py, kv_cache.py)"
        assert spec["moves"] == "serve_tpot_p95_ms"
    # the four serve cells they are meant for report what they move
    e2e = {m["name"]: m for m in bench_run.load_json(
        bench_run.CHECKOUT, "BENCHMARK.json")["end_to_end"]}
    cells = e2e["serve_tpot_p95_ms"].get("workloads")
    assert cells is None or set(SERVE) <= set(cells)


def test_toy_serve_rehearsal_reads_the_split_from_the_recorder(
        tmp_path, monkeypatch):
    """`--trace 1` on the CPU: the three recorder metrics read the engine's
    spans; the CPU's trace has no TPU plane, so the train cell's recorded
    cut stands in and the three device metrics find nothing to read."""
    cut = xplane.load_json(TRAIN_CUT)
    monkeypatch.setattr(xplane, "load", lambda path, keep_host=(): cut)
    run = bench_run.Run("toy_gpt2_serve", 2147483659, 3.0, 1, root=TOY,
                        require_chip=False, scratch=str(tmp_path))
    run.cell["layer_metrics"] = run.cell["layer_metrics"] + NEW
    result = bench_run.run_cell(run)
    metrics = result["metrics"]
    for name in NEW[:3]:
        assert metrics[name]["unit"] == "ms"
        assert metrics[name]["value"] > 0, name
    assert not set(NEW[3:]) & set(metrics)
    assert result["correct"], result["compared"]
