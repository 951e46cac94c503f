"""lib/xplane.py on a recorded cut of a chip trace (two train steps of
gpt2m_train_s1024 on a v5e, PR 23) and on hand-made cases."""

import os

import numpy as np

from lib import xplane

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "gpt2m_train_trace_cut.json.gz")


def _fixture():
    return xplane.load_json(FIXTURE)


def test_only_the_op_line_of_a_device_plane_is_work():
    trace = _fixture()
    per_chip = xplane.device_op_events(trace)
    assert list(per_chip) == [0]
    lines = {l["name"]: l["events"] for p in trace["planes"]
             if p["name"] == "/device:TPU:0" for l in p["lines"]}
    assert set(lines) == {"Steps", "XLA Modules", "XLA Ops", "Async XLA Ops"}
    assert len(per_chip[0]) == len(lines["XLA Ops"])
    # the module row covers the gaps: counting it would hide every idle gap
    module = lines["XLA Modules"][0]
    inside = [e for e in per_chip[0]
              if module[1] <= e[1] and e[1] + e[2] <= module[1] + module[2]]
    busy = xplane.total(xplane.union((s, s + d) for _, s, d in inside))
    assert busy < module[2]


def test_busy_union_and_idle_share_against_a_brute_force_timeline():
    red = xplane.reduce(_fixture())
    w0, w1 = red["window"]
    assert abs(red["window_s"] - 0.25) < 1e-9
    # brute force: paint every op onto a 100 ns grid
    grid = np.zeros(int((w1 - w0) / 100) + 1, bool)
    for _, s, d in red["events"][0]:
        grid[int((s - w0) / 100):int(np.ceil((s + d - w0) / 100))] = True
    painted = grid.sum() * 100e-9
    assert abs(red["busy_s"] - painted) / painted < 2e-3
    idle_share = 1 - red["busy_s"] / red["window_s"]
    assert 0.005 < idle_share < 0.03        # two steps dispatched ahead
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert abs(sum(gaps.values()) - (red["window_s"] - red["busy_s"])) < 1e-6
    assert max(gaps, key=gaps.get) == "bench.train_step"   # first dispatch


def test_kernel_sums_by_name():
    red = xplane.reduce(_fixture())
    ev = red["events"][0]
    pats = ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    seconds, n = xplane.matching_seconds(ev, pats)
    by_hand = [d for name, _, d in ev if "flash_" in name]
    assert n == len(by_hand) == 148
    assert abs(seconds - sum(by_hand) / 1e9) < 1e-9    # they never overlap
    for p, count in zip(pats, (52, 48, 48)):
        assert xplane.matching_seconds(ev, [p])[1] == count
    assert xplane.matching_seconds(ev, ["no_such_kernel"]) == (0.0, 0)
    top = dict(red["breakdown"]["device_ops"])
    assert len(top) <= 10 and "jvp_flash_fwd_" in top


def test_op_names_are_the_heads_of_the_hlo_lines():
    text = ("%fusion.79 = (f32[256]{0}) fusion(bf16[4]{0} %jvp_flash_fwd_.24)"
            ", kind=kOutput")
    assert xplane.op_name(text) == "fusion.79"      # not its operand
    assert xplane.op_name("%jvp_flash_fwd_.24 = (bf16[64]) custom-call()") \
        == "jvp_flash_fwd_.24"
    assert xplane.op_kind("jvp_flash_fwd_.24") == "jvp_flash_fwd_"
    assert xplane.op_kind("fusion.79") == "fusion.79"
    assert xplane.op_kind("multiply_reduce_fusion.3") == \
        "multiply_reduce_fusion"


def test_self_times_do_not_count_a_while_and_its_body_twice():
    ev = [["while.1", 0.0, 100.0], ["fusion.1", 10.0, 30.0],
          ["fusion.2", 50.0, 40.0], ["fusion.3", 120.0, 10.0]]
    st = xplane.self_times(ev)
    assert st["while.1"] == [1, 30e-9]
    assert st["fusion.1"] == [1, 30e-9] and st["fusion.3"] == [1, 10e-9]
    assert abs(sum(v[1] for v in st.values()) - 110e-9) < 1e-15


def test_exposed_collective_on_a_hand_made_two_chip_case():
    """Chip 0: an all-reduce of 40 with nothing else running. Chip 1: an
    async pair, the start 5 and the done 25 long, fully exposed (on one op
    line nothing overlaps them), beside compute."""
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 0.0, 50.0], ["all-reduce.1", 50.0, 40.0],
                ["fusion.2", 90.0, 10.0]]},
            {"name": "XLA Modules", "events": [["jit_step", 0.0, 100.0]]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [
                ["all-reduce-start.1", 0.0, 5.0], ["fusion.1", 5.0, 50.0],
                ["all-reduce-done.1", 55.0, 25.0], ["fusion.2", 80.0, 10.0]]},
            {"name": "Async XLA Ops", "events": [
                ["all-reduce-start.1", 0.0, 80.0]]}]},
    ]}
    red = xplane.reduce(trace)
    assert red["window"] == (0.0, 100.0)
    assert red["busy_s_per_chip"] == {0: 100e-9, 1: 90e-9}
    assert abs(red["busy_s"] - 95e-9) < 1e-15
    exposed = {c: xplane.exposed_collective_seconds(ev)
               for c, ev in red["events"].items()}
    assert abs(exposed[0] - 40e-9) < 1e-15
    assert abs(exposed[1] - 30e-9) < 1e-15


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert xplane.subtract([[0, 10]], [[2, 3], [5, 12]]) == 4
    assert xplane.clip([["a", 0.0, 10.0], ["b", 20.0, 5.0]], (5.0, 21.0)) == \
        [["a", 5.0, 5.0], ["b", 20.0, 1.0]]
