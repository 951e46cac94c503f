"""The hybrid serve cell (a phi4flash model: state-space layers, window
rings and one full ring that the cross layers read, Gated Memory Units)
walked on the CPU at the toy size through the staged serve driver, its
comparison shown to fail under the fp8 control and under two faults planted
in the reference put in the program's place, the need functions held to the
published model's parameter counts, and the two reducers on hand-made span
records. (ISSUE 31 asks for these as cases of test_rehearsal.py and
test_flops.py; a PR may edit no file the benchmark has, so they live here.)
No number from here is a measurement.
"""

import os

import numpy as np
import pytest

import run as bench_run
from drivers import serve_engine_staged as staged
from lib import flops_phi4flash
from lib.references import phi4flash as ref
from reducers import decode_hbm_roofline_hybrid, serve_mfu_hybrid
from reducers.serve_mfu_moe import span_values

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
CELL = "toy_phi4flash_serve"
CONFIG = bench_run.load_json(os.path.dirname(TOY), "..", "configs",
                             "phi4_mini_flash.json")


def _run(seed, seconds=3.0, tmp_path=None):
    return bench_run.Run(CELL, seed, seconds, 0, root=TOY,
                         require_chip=False,
                         scratch=str(tmp_path) if tmp_path else None)


@pytest.fixture(scope="module")
def walked(tmp_path_factory):
    """One walk of the whole command, with what its window measured."""
    seen = {}
    orig = staged.window

    def window(run, h):
        reg = h.registry
        names = ("serve_kv_rows_attended_total", "serve_state_steps_total")
        before = {n: reg.get(n).value() for n in names}
        rows = reg.get("serve_prefill_rows_total")
        before.update({d: rows.value(decoder=d) for d in ("self", "cross")})
        seen["measured"] = orig(run, h)
        seen["gained"] = {n: reg.get(n).value() - before[n] for n in names}
        seen["gained"].update({d: rows.value(decoder=d) - before[d]
                               for d in ("self", "cross")})
        return seen["measured"]
    staged.window = window
    run = _run(2**31 + 9, tmp_path=tmp_path_factory.mktemp("walk"))
    try:
        seen["result"] = bench_run.run_cell(run)
    finally:
        staged.window = orig
    seen["run"] = run
    return seen


def test_cell_walks_and_is_correct(walked):
    result = walked["result"]
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] == 15
    assert result["metrics"]["serve_tpot_p95_ms"]["value"] > 0
    assert result["notes"]["compiles_in_window"] == 0
    # the sample reached past the window of 8, and says how far
    assert result["notes"]["check_max_context"] > 8
    assert result["compared"]["contexts_within_window"]["value"] == 0
    assert result["compared"]["logit_gap"]["value"] \
        <= result["compared"]["logit_gap"]["limit"]


def test_layer_metrics_read_the_programs_counts(walked):
    """The reducers of a traced run, on the spans of an untraced one: what
    the spans of the window carry is what the program's counters gained in
    it. The CPU profiler records no TPU plane, so the roofline share has no
    program time to read and is left out; its bytes are read all the same."""
    run, m, gained = walked["run"], walked["measured"], walked["gained"]
    metrics = bench_run.layer_metrics(run, m, None)
    assert set(metrics) == set(run.cell["layer_metrics"]) \
        - {"decode_hbm_roofline.hybrid"}
    assert 0 < metrics["serve_mfu.hybrid"]["value"] < 100
    self_rows = span_values(m, "serve.prefill", "self_rows")
    cross_rows = span_values(m, "serve.prefill", "cross_rows")
    rows = span_values(m, "serve.decode", "kv_rows")
    states = span_values(m, "serve.decode", "state_slots")
    assert sum(self_rows) == gained["self"] > 0
    assert sum(cross_rows) == gained["cross"] == 15
    assert sum(rows) == gained["serve_kv_rows_attended_total"] > 0
    assert sum(states) == gained["serve_state_steps_total"] > 0
    assert len(rows) == len(states)
    # a prompt's tokens went through the self-decoder, one row of it
    # through the cross-decoder
    a, b = m["snap_start"], m["snap_end"]
    assert sum(self_rows) == b["prefill_tokens"] - a["prefill_tokens"]
    share = decode_hbm_roofline_hybrid.reduce(
        run.config, run.peaks, {"itemsize": 4}, rows, states, 1e-4)
    assert share is not None and share > 0


def _samples(seed):
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, 256, n, dtype=np.int32),
             rng.integers(1, 256, 24, dtype=np.int32))
            for n in (16, 9, 12, 5, 14, 7)]


def _numbers_of(run, gaps):
    return staged.numbers(run, gaps, {"unanswered": 0, "max_context": 40})


def test_control_comes_out_not_correct():
    """The token the fp8 reference puts first lies further below the float32
    best than the limit allows; the float32 pass's own best never does."""
    run = _run(13)
    served, low = staged.reference_gaps(run, _samples(13), cast="fp8_e4m3")
    assert len(served) == len(low) == 6 * 24
    assert not bench_run.verdict(_numbers_of(run, low))
    assert float(np.max(low)) > 5 * run.cell["limits"]["logit_gap"]


@pytest.mark.parametrize("fault", ["state_lost", "cross_reads_window"])
def test_planted_faults_come_out_not_correct(fault):
    """The reference with a fault, put in the program's place: the state and
    the convolution's inputs zeroed where prefill hands over to decoding;
    the cross layers given the last WINDOW layer's keys and values. The
    tokens it puts first are not `correct`; without the fault they are."""
    run = _run(14)
    samples = _samples(14)
    ids = np.zeros((len(samples), 40), np.int32)
    spans_ = []
    for r, (prompt, tokens) in enumerate(samples):
        seq = np.concatenate([prompt, tokens])
        ids[r, :len(seq)] = seq
        spans_.append((len(prompt) - 1, len(seq) - 1))
    cut = lambda g: np.concatenate(  # noqa: E731
        [g[r, s:e] for r, (s, e) in enumerate(spans_)])
    _, low = ref.served_gaps(run.config, 14, ids, fault=fault,
                             reset_at=[len(p) for p, _ in samples])
    faulty = _numbers_of(run, cut(low))
    assert not bench_run.verdict(faulty), faulty
    assert faulty["logit_gap"][0] > 2 * faulty["logit_gap"][1]
    _, same = ref.served_gaps(run.config, 14, ids, fault="state_lost",
                              reset_at=[-1] * len(samples))
    assert bench_run.verdict(_numbers_of(run, cut(same)))


def test_a_program_that_loses_its_state_comes_out_not_correct(
        tmp_path, monkeypatch):
    """The same fault in the program itself: a prefill that leaves the
    slots' states as they were (nought, or the last request's)."""
    from singa_tpu.models import phi4flash
    orig = phi4flash._ServeAdapter.prefill_fn

    def prefill_fn(self):
        fn = orig(self)

        def lossy(P, cache, *args):
            new, out = fn(P, cache, *args)
            return [old if "ssm" in old else lv
                    for old, lv in zip(cache, new)], out
        return lossy
    monkeypatch.setattr(phi4flash._ServeAdapter, "prefill_fn", prefill_fn)
    result = bench_run.run_cell(_run(11, tmp_path=tmp_path))
    assert result["correct"] is False, result["compared"]
    assert result["compared"]["logit_gap"]["value"] \
        > result["compared"]["logit_gap"]["limit"]


def test_a_sample_that_never_passes_the_window_is_not_correct(tmp_path):
    run = _run(11, tmp_path=tmp_path)
    run.config["sliding_window"] = 64      # no context of max_len 40 wraps
    result = bench_run.run_cell(run)
    assert result["compared"]["contexts_within_window"]["value"] == 1
    assert result["correct"] is False


# -- the need functions at the published widths -------------------------------

def test_need_functions_at_the_published_widths():
    """ISSUE 31's arithmetic: 1.964 B parameters in layers 0-17 and 1.376 B
    in layers 18-31 (3.340 B), 0.512 B in the tied head, 3.853 B in all,
    7.71 GB; a ring row 5,120 B, a state 358 KB."""
    assert flops_phi4flash.kinds(CONFIG) == ref.kinds(CONFIG)
    assert [flops_phi4flash.kinds(CONFIG).count(k) for k in
            ("mamba", "attention", "gmu", "cross")] == [9, 9, 7, 7]
    p_self, p_cross = flops_phi4flash.decoder_params(CONFIG)
    assert flops_phi4flash.mlp_params(CONFIG) == 3 * 2560 * 10240
    assert flops_phi4flash.mixer_params(CONFIG, "attention") \
        == 2560 * 5120 + 2560 * 2560
    assert flops_phi4flash.mixer_params(CONFIG, "mamba") == 2560 * 10240 \
        + 5120 * 4 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert p_cross == 14 * 78_643_200 + 7 * 26_214_400 + 7 * 13_107_200
    assert round(p_cross / 1e9, 3) == 1.376
    # the issue's 1.964 B counts A_log and the other vectors too: the
    # matrices are 1.963 B
    assert abs(p_self - 1.964e9) < 2e6
    assert round((p_self + p_cross) / 1e9, 2) == 3.34
    assert round(flops_phi4flash.head_params(CONFIG) / 1e9, 3) == 0.512
    total = sum(int(np.prod(shape)) for _, shape, *_ in
                ref.param_specs(CONFIG))
    assert flops_phi4flash.leaf_params(CONFIG) == total
    assert round(total / 1e9, 3) == 3.853 and round(2 * total / 1e9, 2) == 7.71
    assert flops_phi4flash.kv_row_bytes(CONFIG, 2) == 5120
    assert flops_phi4flash.state_bytes(CONFIG, 2) == 358_400
    # a tick with no live slot reads every leaf once
    assert flops_phi4flash.decode_tick_bytes(CONFIG, 2, 0, 0) == 2 * total
    # 40 live slots at 1,500 tokens: the issue's 2.6 GB of rings and state
    rows = 40 * (8 * 512 + 8 * 1500)
    extra = flops_phi4flash.decode_tick_bytes(CONFIG, 2, rows, 40 * 9) \
        - 2 * total
    assert 2.5e9 < extra < 3.6e9
    assert flops_phi4flash.serve_flops(CONFIG, 100, 2, 10) == 2.0 * (
        p_self * 100 + p_cross * 2
        + (p_self + p_cross + 2560 * 200064) * 10)


class _Peaks:
    config = CONFIG
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def records():
    """Hand-made span records in the program's flight recorder: what the
    engine leaves of 3 prefills and 100 decode ticks between wall-clock 100
    and 110, and a tick on either side of it."""
    from singa_tpu.observability import spans
    rec = spans.recorder()
    kept = rec.records()
    rec.clear()

    def span(name, at, **attrs):
        rec.record(dict(kind="span", name=name, ts=at + 0.01, ts_start=at,
                        dur_s=0.01, **attrs))
    span("serve.decode", 99.5, kv_rows=10**9, state_slots=10**6)
    for i in range(3):
        span("serve.prefill", 100.5 + i, self_rows=600, cross_rows=2)
    for i in range(100):
        span("serve.decode", 101.0 + i * 0.05, kv_rows=500_000,
             state_slots=360)
    span("serve.decode", 110.5, kv_rows=10**9, state_slots=10**6)
    yield
    rec.clear()
    for r in kept:
        rec.record(r)


def _snaps(tokens, prefill):
    zero = {"t": 0.0, "wall": 100.0, "tokens": 0, "prefill_tokens": 0}
    end = {"t": 10.0, "wall": 110.0, "tokens": tokens,
           "prefill_tokens": prefill}
    return {"snap_start": zero, "snap_end": end}


def test_serve_mfu_hybrid_on_a_fixture(records):
    m = _snaps(tokens=4000, prefill=1800)
    p_self, p_cross = flops_phi4flash.decoder_params(CONFIG)
    want = 2.0 * (p_self * 1800 + p_cross * 6
                  + (p_self + p_cross + 2560 * 200064) * 4000) \
        / 10.0 / 197e12 * 100
    assert serve_mfu_hybrid.compute({}, _Peaks, m, None) \
        == pytest.approx(want)
    assert 1 < want < 100
    m["snap_end"]["wall"] = 100.2           # a window that holds no span
    assert serve_mfu_hybrid.compute({}, _Peaks, m, None) is None


def test_decode_hbm_roofline_hybrid_on_a_fixture(records):
    """100 ticks of 40 live slots (360 state steps) that attend 500,000 ring
    rows, under a decode program of 14 ms."""
    m = _snaps(tokens=4000, prefill=0)
    rows = span_values(m, "serve.decode", "kv_rows")
    states = span_values(m, "serve.decode", "state_slots")
    assert rows == [500_000.0] * 100 and states == [360.0] * 100
    nbytes = 2 * 3_852_562_944 + 5120 * 500_000 + 2 * 358_400 * 360
    want = nbytes / 819e9 / 0.014 * 100
    args = {"itemsize": 2, "program": r"^jit_decode_body\("}
    reduce = decode_hbm_roofline_hybrid.reduce
    got = reduce(CONFIG, _Peaks.peaks, args, rows, states, 0.014)
    assert got == pytest.approx(want) and 50 < got < 100
    # the bytes are those of the ticks next to the traced slice
    ramp = [r * i / 100 for i, r in enumerate(rows)]
    assert reduce(CONFIG, _Peaks.peaks, dict(args, last_ticks=1), ramp,
                  states, 0.014) == pytest.approx(
        (2 * 3_852_562_944 + 5120 * 495_000 + 2 * 358_400 * 360)
        / 819e9 / 0.014 * 100)
    # no program time in the trace, or a span without the counts: no number
    assert reduce(CONFIG, _Peaks.peaks, args, rows, states, None) is None
    assert reduce(CONFIG, _Peaks.peaks, args, rows, [], 0.014) is None
    assert reduce(CONFIG, _Peaks.peaks, args, [], states, 0.014) is None
    assert decode_hbm_roofline_hybrid.compute(args, _Peaks, m, None) is None


def test_the_new_reducers_read_nothing_from_a_program_without_the_attrs():
    """On a program that puts no `self_rows` / `state_slots` on its spans
    (the parent of this PR, any other model) both return None and raise
    nothing."""
    from singa_tpu.observability import spans
    rec = spans.recorder()
    kept = rec.records()
    rec.clear()
    try:
        rec.record(dict(kind="span", name="serve.decode", ts=101.01,
                        ts_start=101.0, dur_s=0.01, kv_rows=400_000))
        rec.record(dict(kind="span", name="serve.prefill", ts=100.51,
                        ts_start=100.5, dur_s=0.01))
        m = _snaps(tokens=100, prefill=50)
        assert serve_mfu_hybrid.compute({}, _Peaks, m, None) is None
        assert decode_hbm_roofline_hybrid.reduce(
            CONFIG, _Peaks.peaks, {"itemsize": 2},
            span_values(m, "serve.decode", "kv_rows"),
            span_values(m, "serve.decode", "state_slots"), 0.014) is None
    finally:
        rec.clear()
        for r in kept:
            rec.record(r)
