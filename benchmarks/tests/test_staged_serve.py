"""The staged serve driver walked on the CPU at the toy size (a share of a
small cohere2_moe: 4 of 16 experts held, window 8, rings of 8 and 40), its
comparison shown to fail under the control and under each fault, and the
two reducers that read the expert layer's counts on hand-made span records
and on a cut of a chip trace. No number from here is a measurement.
"""

import os

import numpy as np
import pytest

import run as bench_run
from drivers import serve_engine, serve_engine_staged as staged
from lib import flops_cohere_moe, weights_staged, xplane
from lib.references import cohere_moe as ref
from reducers import decode_hbm_roofline, serve_mfu_moe

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
CELL = "toy_cohere_serve"


def _run(seed, seconds=3.0, tmp_path=None):
    return bench_run.Run(CELL, seed, seconds, 0, root=TOY,
                         require_chip=False,
                         scratch=str(tmp_path) if tmp_path else None)


def test_cell_walks_and_is_correct(tmp_path):
    result = bench_run.run_cell(_run(2**31 + 9, tmp_path=tmp_path))
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] == 15
    assert result["metrics"]["serve_tpot_p95_ms"]["value"] > 0
    assert result["notes"]["compiles_in_window"] == 0
    # the sample reached past the window of 8, and says how far
    assert result["notes"]["check_max_context"] > 8
    assert result["compared"]["contexts_within_window"]["value"] == 0


def test_bf16_weights_walk_leaf_by_leaf(tmp_path):
    """The published configuration's precision at the toy size: bf16 leaves
    loaded in their own dtype, bf16 rings (a milder init, under which a
    rounding cannot move a served token's rank at 64 wide)."""
    run = _run(21, tmp_path=tmp_path)
    run.config.update(precision="bfloat16", init={
        "matrix_std": 0.15, "router_std": 0.3, "embedding_std": 1.0,
        "residual_out_std": 0.05})
    seen = {}
    orig = staged.setup

    def setup(r):
        h = orig(r)
        seen["dtypes"] = {str(t.data.dtype)
                          for t in h.model.get_states().values()} \
            | {str(level["k"].dtype) for level in h.engine._cache}
        return h
    staged.setup = setup
    try:
        result = bench_run.run_cell(run)
    finally:
        staged.setup = orig
    assert seen["dtypes"] == {"bfloat16"}
    assert result["correct"], result["compared"]


def test_layer_metrics_read_the_programs_counts(tmp_path, monkeypatch):
    """The reducers of a traced run, on the spans of an untraced one. What
    the spans of the window carry is what the program's counters gained in
    it. The CPU profiler records no TPU plane, so the roofline share has no
    program time to read and is left out; its bytes are read all the same."""
    seen = {}
    orig = staged.window

    def counters(h):
        reg = h.registry
        return {"pairs_here": reg.get("moe_pairs_total").value(held="here"),
                "touched": reg.get("moe_experts_touched_total").value(
                    program="decode"),
                "calls": reg.get("moe_calls_total").value(program="decode"),
                "kv_rows": reg.get("serve_kv_rows_attended_total").value()}

    def window(run, h):
        before = counters(h)
        seen["measured"] = orig(run, h)
        seen["gained"] = {k: v - before[k] for k, v in counters(h).items()}
        return seen["measured"]
    monkeypatch.setattr(staged, "window", window)
    run = _run(5, tmp_path=tmp_path)
    bench_run.run_cell(run)
    m, gained = seen["measured"], seen["gained"]
    metrics = bench_run.layer_metrics(run, m, None)
    assert set(metrics) == set(run.cell["layer_metrics"]) \
        - {"decode_hbm_roofline"}
    assert 0 < metrics["serve_mfu.moe"]["value"] < 100
    pairs = serve_mfu_moe.span_values(
        m, ["serve.prefill", "serve.decode"], "pairs_here")
    touched = serve_mfu_moe.span_values(m, "serve.decode", "experts_touched")
    rows = serve_mfu_moe.span_values(m, "serve.decode", "kv_rows")
    assert sum(pairs) == gained["pairs_here"] > 0
    assert len(touched) == len(rows) == gained["calls"]
    assert sum(touched) == gained["touched"] <= 16 * gained["calls"]
    assert sum(rows) == gained["kv_rows"] > 0
    # top-4 over 16 experts of which 4 are held: about a quarter of the
    # 16 pairs a token makes in 4 layers
    a, b = m["snap_start"], m["snap_end"]
    tokens = (b["tokens"] - a["tokens"]) \
        + (b["prefill_tokens"] - a["prefill_tokens"])
    assert 0 < sum(pairs) < 8 * tokens
    share = decode_hbm_roofline.reduce(
        run.config, run.peaks, {"itemsize": 4}, touched, rows, 1e-4)
    assert share is not None and share > 0


def test_an_altered_token_comes_out_not_correct(tmp_path, monkeypatch):
    orig = serve_engine._result_tokens

    def altered(result):
        tokens = orig(result)
        tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 101) % 256
        return tokens
    monkeypatch.setattr(serve_engine, "_result_tokens", altered)
    result = bench_run.run_cell(_run(11, tmp_path=tmp_path))
    assert result["correct"] is False, result["compared"]
    assert result["compared"]["logit_gap"]["value"] \
        > result["compared"]["logit_gap"]["limit"]


def test_a_ring_that_does_not_wrap_comes_out_not_correct(tmp_path,
                                                         monkeypatch):
    """Window layers given rings as long as the full layer's keep every
    position: attention reaches past the window and the served tokens
    leave the reference's."""
    from singa_tpu.models import cohere_moe
    monkeypatch.setattr(
        cohere_moe._ServeAdapter, "ring_lengths",
        lambda self, max_len: [int(max_len)] * len(self.cfg.layer_types))
    result = bench_run.run_cell(_run(11, tmp_path=tmp_path))
    assert result["correct"] is False, result["compared"]


def test_a_sample_that_never_passes_the_window_is_not_correct(tmp_path):
    run = _run(11, tmp_path=tmp_path)
    run.config["sliding_window"] = 64      # no context of max_len 40 wraps
    result = bench_run.run_cell(run)
    assert result["compared"]["contexts_within_window"]["value"] == 1
    assert result["compared"]["logit_gap"]["value"] \
        <= result["compared"]["logit_gap"]["limit"]
    assert result["correct"] is False


def test_control_reads_above_the_program():
    """The token the fp8 reference puts first lies further below the float32
    best than the limit allows, and fp8 moves more pick sets than the
    program's own operand rounding does."""
    run = _run(13)
    rng = np.random.default_rng(13)
    samples = [(rng.integers(1, 256, n, dtype=np.int32),
                rng.integers(1, 256, 20, dtype=np.int32))
               for n in (16, 9, 12, 5, 14, 7)]
    picks = {}
    served, low = staged.reference_gaps(run, samples, cast="fp8_e4m3",
                                        picks_out=picks)
    assert len(served) == len(low) == 120
    limit = run.cell["limits"]["logit_gap"]
    assert float(np.max(low)) > limit
    staged.reference_gaps(run, samples, cast="bf16", picks_out=picks)
    from tools import limit_readings_entry as tool
    fp8 = tool.picks_differ_share(picks["fp8_e4m3"], picks["float32"],
                                  picks["spans"])
    bf16 = tool.picks_differ_share(picks["bf16"], picks["float32"],
                                   picks["spans"])
    assert 0 <= bf16 < fp8 <= 1


def test_a_leaf_hangs_on_seed_and_name_alone():
    import jax.numpy as jnp
    a = weights_staged.make_leaf(("layers.1.wq", (8, 4), 0.0, 1.0), 7,
                                 jnp.bfloat16)
    again = weights_staged.make(
        [("emb", (3, 3), 0.0, 1.0), ("layers.1.wq", (8, 4), 0.0, 1.0)], 7,
        jnp.bfloat16, jnp.float32)["layers.1.wq"]
    assert a.dtype == jnp.bfloat16 and again.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(again))
    other = weights_staged.make_leaf(("layers.2.wq", (8, 4), 0.0, 1.0), 7,
                                     jnp.bfloat16)
    big = weights_staged.make_leaf(("layers.1.wq", (8, 4), 0.0, 1.0),
                                   2**31 + 7, jnp.bfloat16)
    assert not np.array_equal(np.asarray(a, np.float32),
                              np.asarray(other, np.float32))
    assert not np.array_equal(np.asarray(a, np.float32),
                              np.asarray(big, np.float32))


CONFIG = bench_run.load_json(os.path.dirname(TOY), "..", "configs",
                             "command_a_plus_tp8ep8.json")


def test_need_functions_at_the_published_widths():
    """The issue's arithmetic: 18.4 M + 201.3 M a layer outside the routed
    experts, 50.33 M an expert, 4.23 B parameters in all."""
    assert flops_cohere_moe.expert_params(CONFIG) == 3 * 4096 * 4096
    dense = flops_cohere_moe.dense_params_per_layer(CONFIG)
    assert dense == 17_825_792 + 524_288 + 4 * 50_331_648
    total = sum(int(np.prod(shape)) for _, shape, *_ in
                ref.param_specs(CONFIG))
    assert round(total / 1e9, 2) == 4.23
    # a tick that touches every held expert reads every leaf once
    nbytes = flops_cohere_moe.decode_tick_bytes(CONFIG, 2, 64, 0)
    assert nbytes == 2 * total


class _Peaks:
    config = CONFIG
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def records():
    """Hand-made span records in the program's flight recorder: what the
    engine leaves of 2 prefills and 100 decode ticks between wall-clock 100
    and 110, and a tick on either side of it."""
    from singa_tpu.observability import spans
    rec = spans.recorder()
    kept = rec.records()
    rec.clear()

    def span(name, at, **attrs):
        rec.record(dict(kind="span", name=name, ts=at + 0.01, ts_start=at,
                        dur_s=0.01, **attrs))
    span("serve.decode", 99.5, pairs_here=10**6, experts_touched=10**6,
         kv_rows=10**9)
    for i in range(2):
        span("serve.prefill", 100.5 + i, pairs_here=8000,
             experts_touched=64)
    for i in range(100):
        span("serve.decode", 101.0 + i * 0.05, pairs_here=240,
             experts_touched=60, kv_rows=400_000)
    span("serve.decode", 110.5, pairs_here=10**6, experts_touched=10**6,
         kv_rows=10**9)
    yield
    rec.clear()
    for r in kept:
        rec.record(r)


def _snaps(tokens, prefill):
    zero = {"t": 0.0, "wall": 100.0, "tokens": 0, "prefill_tokens": 0}
    end = {"t": 10.0, "wall": 110.0, "tokens": tokens,
           "prefill_tokens": prefill}
    return {"snap_start": zero, "snap_end": end}


def test_serve_mfu_moe_on_a_fixture(records):
    m = _snaps(tokens=1000, prefill=9000)
    pairs = 2 * 8000 + 100 * 240
    want = 2.0 * (4 * 219_676_672 * 10000 + 50_331_648 * pairs
                  + 4096 * 32768 * 1000) / 10.0 / 197e12 * 100
    assert serve_mfu_moe.compute({}, _Peaks, m, None) == pytest.approx(want)
    assert 1 < want < 100
    m["snap_end"]["wall"] = 100.2           # a window that holds no span
    assert serve_mfu_moe.compute({}, _Peaks, m, None) is None


def test_decode_hbm_roofline_on_a_fixture(records):
    """100 ticks that each touch 60 of the 64 held experts and attend
    400,000 ring rows, under a decode program of 12 ms."""
    m = _snaps(tokens=5000, prefill=0)
    touched = serve_mfu_moe.span_values(m, "serve.decode", "experts_touched")
    rows = serve_mfu_moe.span_values(m, "serve.decode", "kv_rows")
    assert touched == [60.0] * 100 and rows == [400_000.0] * 100
    fixed = 4096 * 32768 + 4096 + 4 * (219_676_672 + 4096)
    nbytes = 2 * (fixed + 50_331_648 * 60 + 2 * 128 * 400_000)
    want = nbytes / 819e9 / 0.012 * 100
    args = {"itemsize": 2, "program": r"^jit_decode_body\("}
    got = decode_hbm_roofline.reduce(CONFIG, _Peaks.peaks, args, touched,
                                     rows, 0.012)
    assert got == pytest.approx(want) and 50 < got < 100
    # no program time in the trace, or no span with the counts: no number
    assert decode_hbm_roofline.reduce(CONFIG, _Peaks.peaks, args, touched,
                                      rows, None) is None
    assert decode_hbm_roofline.reduce(CONFIG, _Peaks.peaks, args, [], [],
                                      0.012) is None
    assert decode_hbm_roofline.compute(args, _Peaks, m, None) is None


def test_decode_program_time_from_a_cut_of_a_chip_trace():
    """Three ticks of `gpt2m_serve_chat` on the v5e (PR 24's fixture): the
    module line holds three runs of the decode program and one of the
    prefill program; a run that the window cuts is not counted."""
    trace = xplane.load_json(os.path.join(
        os.path.dirname(TOY), "fixtures", "gpt2m_serve_trace_cut.json.gz"))
    line, = [ln for p in trace["planes"] for ln in p["lines"]
             if ln["name"] == decode_hbm_roofline.MODULE_LINE]
    runs = sorted((e for e in line["events"]
                   if e[0].startswith("jit_decode_body(")),
                  key=lambda e: e[1])
    assert len(runs) == 3
    everything = (0.0, float("inf"))
    pattern = r"^jit_decode_body\("
    mean = decode_hbm_roofline.program_seconds(line["events"], everything,
                                               pattern)
    assert mean == pytest.approx(sum(e[2] for e in runs) / 3 / 1e9)
    assert 0.030 < mean < 0.040             # 34.5 ms of program a tick
    cut = (runs[0][1] + 1.0, float("inf"))  # the first run began before it
    assert decode_hbm_roofline.program_seconds(
        line["events"], cut, pattern) == pytest.approx(
            sum(e[2] for e in runs[1:]) / 2 / 1e9)
    assert decode_hbm_roofline.program_seconds(
        line["events"], everything, r"^jit_no_such_program\(") is None


def test_gap_statistics_are_held_to_the_limits_the_cell_names():
    """`numbers` gives the mean, the 99th percentile and the share above
    nought for the limits a cell's file names, and no others."""
    run = _run(13)
    run.cell["limits"] = {"logit_gap_mean": 0.035, "logit_gap_p99": 0.7,
                          "logit_gap_over_0": 0.15, "unanswered": 0,
                          "contexts_within_window": 0}
    gaps = np.zeros(1000)
    gaps[:50] = 0.2                          # an expert swapped: 5 %
    evidence = {"unanswered": 0, "max_context": 30}
    got = staged.numbers(run, gaps, evidence)
    assert set(got) == set(run.cell["limits"])
    assert got["logit_gap_mean"][0] == pytest.approx(0.01)
    assert got["logit_gap_p99"] == (pytest.approx(0.2), 0.7)
    assert got["logit_gap_over_0"] == (pytest.approx(0.05), 0.15)
    assert bench_run.verdict(got)
    gaps[:25] = 6.0                          # 25 tokens fully wrong
    assert not bench_run.verdict(staged.numbers(run, gaps, evidence))
    gaps[:] = 0.0
    gaps[:200] = 0.01                        # many tokens a little off
    low = staged.numbers(run, gaps, evidence)
    assert low["logit_gap_mean"][0] < 0.035 and not bench_run.verdict(low)
