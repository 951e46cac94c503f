"""`ring_decode_roofline` (PR 28; in no cell's list yet): the reducer on
hand-made counts and a hand-made op line, the geometry in its file against
the kernel's own rule at the two serve cells' shapes, and the metric read
from the spans of a toy serve run whose trace is made by hand (the CPU
profiler records no TPU plane)."""

import os

import pytest

import run as bench_run
from reducers import ring_decode_roofline as rdr

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = bench_run.load_json(bench_run.HERE, "layer_metrics",
                           "ring_decode_roofline.json")
PEAKS = {"hbm_bytes_per_s": 819e9}


def test_the_share_is_bytes_over_bandwidth_over_the_kernels_time_a_tick():
    geo = {"levels": 2, "block_bytes": 1000, "write_bytes": 10}
    # a tick walks 30 blocks on average and writes 2 levels of 3 live
    # slots: 30 060 bytes; 8 calls in the slice are 4 ticks of 1 us each
    share = rdr.reduce(geo, {"hbm_bytes_per_s": 60.12e9},
                       [20, 40], [2, 4, 0], 4e-6, 8)
    assert share == pytest.approx(50.0)
    # twice the time, half the share; ticks that ran no decode do not
    # dilute the live slots
    assert rdr.reduce(geo, {"hbm_bytes_per_s": 60.12e9}, [30], [3, 0, 0],
                      8e-6, 8) == pytest.approx(25.0)


@pytest.mark.parametrize("case", ["no_geometry", "no_blocks", "no_ops",
                                  "no_live_slot"])
def test_nothing_to_read_is_none_never_zero(case):
    geo = {"levels": 2, "block_bytes": 1000, "write_bytes": 10}
    args = {"no_geometry": (None, [3], [1], 1e-6, 2),
            "no_blocks": (geo, [], [1], 1e-6, 2),
            "no_ops": (geo, [3], [1], 0.0, 0),
            "no_live_slot": (geo, [3], [0], 1e-6, 2)}[case]
    assert rdr.reduce(args[0], PEAKS, *args[1:]) is None


def test_a_parent_without_the_kernel_reports_nothing():
    """The parent commit names no op `ring_decode` and records no
    `kv_blocks`: the metric is left out, and nothing raises."""
    class Run:
        config = {"name": "gpt2_medium"}
        peaks = PEAKS
    trace = {"events": {0: [["fusion.1", 0.0, 50.0],
                            ["dynamic-update-slice.3", 60.0, 40.0]]}}
    assert rdr.compute(SPEC["args"], Run, {}, trace) is None
    assert rdr.compute(SPEC["args"], Run, {}, None) is None


@pytest.mark.parametrize("config,n_kv,length,D,levels", [
    ("gpt2_medium", 16, 1024, 64, 24),
    ("command_a_plus_tp8ep8", 1, 4096, 128, 4),
    ("command_a_plus_tp8ep8", 1, 5120, 128, 4),
])
def test_the_files_geometry_is_the_kernels(config, n_kv, length, D, levels):
    """Bytes of a block of K and V, and of what a live slot has written of
    a level, as `ops/ring_decode.py` cuts and writes a bf16 level of the
    cell's shape."""
    from singa_tpu.ops import ring_decode
    geo = SPEC["args"]["geometry"][config]
    block = ring_decode.kernel_block(n_kv, length, D)
    assert geo["levels"] == levels
    assert geo["block_bytes"] == 2 * n_kv * block * D * 2
    # ring on the lanes (head size under a lane tile): the 128 ring
    # indices of one lane tile, all heads; row-major: one 16-row tile
    written = 128 if D % 128 else 16
    assert geo["write_bytes"] == 2 * n_kv * written * D * 2


def test_the_metric_file_is_data_for_this_reducer():
    import json
    with open(os.path.join(bench_run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert SPEC["reducer"] == "ring_decode_roofline"
    assert SPEC["unit"] == "%" and SPEC["source"] == "device_trace"
    assert SPEC["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert SPEC["layer"] in {m["layer"] for m in bench["per_layer"]}
    # not switched on: no cell's file names it (a `benchmark` PR's to do)
    for w in bench["workloads"]:
        cell = bench_run.load_json(bench_run.HERE, "workloads",
                                   f"{w['name']}.json")
        assert "ring_decode_roofline" not in cell["layer_metrics"]


def test_on_the_spans_of_a_toy_serve_run(tmp_path, monkeypatch):
    """The toy GPT-2 serve cell on the CPU: its `serve.decode` spans carry
    `kv_blocks`, its `serve.tick` spans `active`; with an op line made by
    hand (one `ring_decode` call a level a tick) the metric reads."""
    run = bench_run.Run("toy_gpt2_serve", 3, 2.0, 0,
                        root=os.path.join(HERE, "toy"), require_chip=False,
                        scratch=str(tmp_path))
    seen = {}
    from drivers import serve_engine
    orig = serve_engine.window

    def window(r, h):
        seen["levels"] = len(h.engine._cache)
        seen["measured"] = orig(r, h)
        return seen["measured"]
    monkeypatch.setattr(serve_engine, "window", window)
    bench_run.run_cell(run)
    m = seen["measured"]
    blocks = rdr.span_values(m, "serve.decode", "kv_blocks")
    active = rdr.span_values(m, "serve.tick", "active")
    assert blocks and len(active) >= len(blocks)
    # the toy rings are shorter than any block: one block a level a slot
    assert all(b % seen["levels"] == 0 for b in blocks)
    args = {"pattern": "ring_decode", "geometry": {run.config["name"]: {
        "levels": seen["levels"], "block_bytes": 4096, "write_bytes": 64}}}
    events = [[f"%ring_decode.{i} = (...) custom-call(...)", 10.0 * i, 5.0]
              for i in range(3 * seen["levels"])]
    value = rdr.compute(args, run, m, {"events": {0: events}})
    live = [a for a in active if a > 0]
    want = (sum(blocks) / len(blocks) * 4096
            + sum(live) / len(live) * seen["levels"] * 64) \
        / run.peaks["hbm_bytes_per_s"] / (5e-9 * seen["levels"]) * 100
    assert value == pytest.approx(want)
