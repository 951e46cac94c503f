"""The latent serve cell (a LongCat-Flash share: shortcut-connected double
layers, latent attention on latent ring levels, a softmax router with a
selection bias over routed and identity experts) walked on the CPU at the toy
size through the staged serve driver, its comparison shown to fail under the
fp8 control and under three faults planted in the reference put in the
program's place, the need functions held to the published model's parameter
counts, and the three reducers on hand-made span records. (ISSUE 33 asks for
these as cases of test_rehearsal.py and test_flops.py; a PR may edit no file
the benchmark has, so they live here.) No number from here is a measurement.
"""

import os

import numpy as np
import pytest

import run as bench_run
from drivers import serve_engine_staged as staged
from lib import flops_longcat
from lib.references import longcat_flash as ref
from reducers import (decode_hbm_roofline_scmoe, latent_decode_roofline,
                      serve_mfu_scmoe)
from reducers.serve_mfu_moe import span_values

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
CELL = "toy_longcat_serve"
CONFIG = bench_run.load_json(os.path.dirname(TOY), "..", "configs",
                             "longcat_flash_omni_ep32.json")


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
        pairs = reg.get("moe_pairs_total")
        before = {k: pairs.value(held=k) for k in ("here", "absent", "zero")}
        before["rows"] = reg.get("serve_kv_rows_attended_total").value()
        seen["measured"] = orig(run, h)
        seen["gained"] = {k: pairs.value(held=k) - before[k]
                          for k in ("here", "absent", "zero")}
        seen["gained"]["rows"] = reg.get(
            "serve_kv_rows_attended_total").value() - before["rows"]
        seen["latent_bytes"] = reg.get("serve_kv_bytes").value(kind="latent")
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
    assert set(result["compared"]) == {"logit_gap", "logit_gap_mean",
                                       "unanswered"}
    # 8 slots x 40 rows x 4 levels of one padded row of 128 float32
    assert walked["latent_bytes"] == 8 * 40 * 4 * 128 * 4


def test_layer_metrics_read_the_programs_counts(walked):
    """The reducers of a traced run, on the spans of an untraced one: what
    the spans of the window carry is what the program's counters gained in
    it. The CPU profiler records no TPU plane, so the two roofline shares
    have no device time to read and are left out."""
    run, m, gained = walked["run"], walked["measured"], walked["gained"]
    metrics = bench_run.layer_metrics(run, m, None)
    assert set(metrics) == set(run.cell["layer_metrics"]) \
        - {"decode_hbm_roofline.scmoe", "latent_decode_roofline"}
    assert 0 < metrics["serve_mfu.scmoe"]["value"] < 100
    both = ["serve.prefill", "serve.decode"]
    here = sum(span_values(m, both, "pairs_here"))
    zero = sum(span_values(m, both, "pairs_zero"))
    rows = span_values(m, "serve.decode", "kv_rows")
    assert here == gained["here"] > 0 and zero == gained["zero"] > 0
    assert sum(rows) == gained["rows"] > 0
    # here + absent + zero = real rows x top-3 x 2 layers (a prompt's
    # tokens and more: every decoded token is a row too), and the identity
    # experts (4 of 12 columns) get about a third of the picks
    a, b = m["snap_start"], m["snap_end"]
    total = sum(gained[k] for k in ("here", "absent", "zero"))
    assert total % 6 == 0
    assert total >= 6 * (b["prefill_tokens"] - a["prefill_tokens"])
    assert 0.2 < gained["zero"] / total < 0.5
    sq = span_values(m, "serve.prefill", "tokens_sq")
    assert len(sq) == 15 and all(16 <= v <= 256 for v in sq)
    touched = span_values(m, "serve.decode", "experts_touched")
    share = decode_hbm_roofline_scmoe.reduce(
        run.config, run.peaks, {"itemsize": 4}, touched, rows, 1e-4)
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
    best than the limits allow; the float32 pass's own best never does."""
    run = _run(13)
    served, low = staged.reference_gaps(run, _samples(13), cast="fp8_e4m3")
    assert len(served) == len(low) == 6 * 24
    assert not bench_run.verdict(_numbers_of(run, low))
    assert float(np.max(low)) > 5 * run.cell["limits"]["logit_gap"]
    assert float(np.mean(low)) > 5 * run.cell["limits"]["logit_gap_mean"]


def _cut_gaps(run, seed, fault):
    samples = _samples(seed)
    ids = np.zeros((len(samples), 40), np.int32)
    spans_ = []
    for r, (prompt, tokens) in enumerate(samples):
        seq = np.concatenate([prompt, tokens])
        ids[r, :len(seq)] = seq
        spans_.append((len(prompt) - 1, len(seq) - 1))
    _, low = ref.served_gaps(run.config, seed, ids, cast="float32",
                             fault=fault)
    return np.concatenate([low[r, s:e] for r, (s, e) in enumerate(spans_)])


@pytest.mark.parametrize("fault", ["zero_experts_out", "bias_in_weights",
                                   "k_r_unrotated"])
def test_planted_faults_come_out_not_correct(fault):
    """The reference with a fault, put in the program's place: the identity
    experts' term left out; the selection bias added to the picks' weights;
    the shared rotary key left unrotated. The tokens it puts first are not
    `correct`, by the widest gap and by the mean."""
    run = _run(14)
    faulty = _numbers_of(run, _cut_gaps(run, 14, fault))
    assert not bench_run.verdict(faulty), faulty
    assert faulty["logit_gap"][0] > 2 * faulty["logit_gap"][1]
    assert faulty["logit_gap_mean"][0] > 2 * faulty["logit_gap_mean"][1]


def test_the_reference_without_a_fault_is_correct():
    run = _run(14)
    assert bench_run.verdict(_numbers_of(run, _cut_gaps(run, 14, None)))


def test_a_program_without_the_identity_experts_comes_out_not_correct(
        tmp_path, monkeypatch):
    """The first fault in the program itself: an expert layer that is not
    told of the experts without weights (their picks count as absent and
    add nothing)."""
    from singa_tpu.models import longcat_flash
    from singa_tpu.parallel import moe

    def lossy(p, h, **kw):
        return moe.expert_share_ffn(p, h, **dict(kw, n_zero=0))
    monkeypatch.setattr(longcat_flash, "expert_share_ffn", lossy)
    result = bench_run.run_cell(_run(11, tmp_path=tmp_path))
    assert result["correct"] is False, result["compared"]
    assert result["compared"]["logit_gap"]["value"] \
        > result["compared"]["logit_gap"]["limit"]


def test_the_bf16_path_walks(tmp_path):
    """The cell's own precision at the toy size: weights and latent rows in
    bf16 through `Model.compile(policy="bfloat16")`; the gaps are those of
    rounding, far under the faults'."""
    run = _run(12, tmp_path=tmp_path)
    run.config["precision"] = "bfloat16"
    run.cell["limits"] = {"logit_gap": 3.0, "logit_gap_mean": 0.2,
                          "unanswered": 0}
    result = bench_run.run_cell(run)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0


# -- the need functions at the published widths -------------------------------

def test_need_functions_at_the_published_widths():
    """ISSUE 33's arithmetic: one latent-attention block 90.57 M, one dense
    FFN 226.49 M, the router 4.72 M, a layer outside its experts 638.8 M, an
    expert 37.75 M, the head 100.7 M; 5.17 B parameters, 10.35 GB; a cached
    row 1,152 B, 2 x 64 x 1088 FLOPs a row a tick."""
    assert flops_longcat.mla_params(CONFIG) == 6144 * 1536 \
        + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 8192 * 6144
    assert round(flops_longcat.mla_params(CONFIG) / 1e6, 2) == 90.57
    assert flops_longcat.dense_ffn_params(CONFIG) == 3 * 6144 * 12288
    assert round(flops_longcat.dense_ffn_params(CONFIG) / 1e6, 2) == 226.49
    assert flops_longcat.expert_params(CONFIG) == 3 * 6144 * 2048
    assert round(flops_longcat.expert_params(CONFIG) / 1e6, 2) == 37.75
    dense = flops_longcat.dense_params_per_layer(CONFIG)
    assert dense == 2 * 90_570_752 + 2 * 226_492_416 + 6144 * 768
    assert round(dense / 1e6, 1) == 638.8
    assert round(flops_longcat.head_params(CONFIG) / 1e6, 1) == 100.7
    total = sum(int(np.prod(shape)) for _, shape, *_ in
                ref.param_specs(CONFIG))
    assert flops_longcat.leaf_params(CONFIG) == total
    assert round(total / 1e9, 2) == 5.17 and round(2 * total / 1e9, 2) == 10.35
    assert 2 * flops_longcat.latent_row_width(CONFIG) == 1152
    assert flops_longcat.latent_row_flops(CONFIG) == 2 * 64 * 1088
    assert flops_longcat.prefill_attention_flops(CONFIG, 1000 ** 2) \
        == 2 * 64 * 320 * 8 * 1000 ** 2 / 2
    # a tick with no live slot and no expert touched reads 5.3 GB
    idle = flops_longcat.decode_tick_bytes(CONFIG, 2, 0, 0)
    assert round(idle / 1e9, 1) == 5.3
    # every held expert touched: 4.8 GB more; 40 slots of 2 k tokens on 8
    # levels: 0.74 GB more
    assert flops_longcat.decode_tick_bytes(CONFIG, 2, 64, 0) - idle \
        == 2 * 64 * 37_748_736
    rows = 40 * 2000 * 8
    assert flops_longcat.decode_tick_bytes(CONFIG, 2, 0, rows) - idle \
        == 1152 * rows
    assert flops_longcat.serve_flops(CONFIG, 100, 10, 7, 50, 9) == 2.0 * (
        4 * dense * 100 + 37_748_736 * 7 + 6144 * 16384 * 10) \
        + 2 * 64 * 1088 * 50 + 2 * 64 * 320 * 8 * 9 / 2
    f, b = flops_longcat.latent_decode_need(CONFIG, 2, rows)
    assert (f, b) == (2 * 64 * 1088 * rows, 1152 * rows)
    assert 100 < f / b < 130            # FLOPs a byte: under the chip's 240


class _Peaks:
    config, trace_dir = CONFIG, None
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
    span("serve.decode", 99.5, kv_rows=10**9, pairs_here=10**6,
         pairs_zero=10**6, experts_touched=64)
    for i in range(3):
        span("serve.prefill", 100.5 + i, pairs_here=400, pairs_zero=6000,
             experts_touched=60, tokens_sq=1500 ** 2)
    for i in range(100):
        span("serve.decode", 101.0 + i * 0.05, kv_rows=640_000,
             pairs_here=16, pairs_zero=250, experts_touched=40)
    span("serve.decode", 110.5, kv_rows=10**9, pairs_here=10**6,
         pairs_zero=10**6, experts_touched=64)
    yield
    rec.clear()
    for r in kept:
        rec.record(r)


def _snaps(tokens, prefill):
    zero = {"t": 0.0, "wall": 100.0, "tokens": 0, "prefill_tokens": 0}
    end = {"t": 10.0, "wall": 110.0, "tokens": tokens,
           "prefill_tokens": prefill}
    return {"snap_start": zero, "snap_end": end}


def test_serve_mfu_scmoe_on_a_fixture(records):
    m = _snaps(tokens=4000, prefill=4500)
    want = flops_longcat.serve_flops(
        CONFIG, 8500, 4000, 3 * 400 + 100 * 16, 100 * 640_000,
        3 * 1500 ** 2) / 10.0 / 197e12 * 100
    assert serve_mfu_scmoe.compute({}, _Peaks, m, None) \
        == pytest.approx(want)
    assert 1 < want < 100
    m["snap_end"]["wall"] = 100.2           # a window that holds no span
    assert serve_mfu_scmoe.compute({}, _Peaks, m, None) is None


def test_decode_hbm_roofline_scmoe_on_a_fixture(records):
    """100 ticks of 40 touched experts that attend 640,000 cached rows,
    under a decode program of 16 ms."""
    m = _snaps(tokens=4000, prefill=0)
    rows = span_values(m, "serve.decode", "kv_rows")
    touched = span_values(m, "serve.decode", "experts_touched")
    assert rows == [640_000.0] * 100 and touched == [40.0] * 100
    nbytes = flops_longcat.decode_tick_bytes(CONFIG, 2, 0, 0) \
        + 2 * 40 * 37_748_736 + 1152 * 640_000
    args = {"itemsize": 2, "program": r"^jit_decode_body\("}
    reduce = decode_hbm_roofline_scmoe.reduce
    got = reduce(CONFIG, _Peaks.peaks, args, touched, rows, 0.016)
    assert got == pytest.approx(nbytes / 819e9 / 0.016 * 100)
    assert 50 < got < 100
    # the bytes are those of the ticks next to the traced slice
    ramp = [r * i / 100 for i, r in enumerate(rows)]
    assert reduce(CONFIG, _Peaks.peaks, dict(args, last_ticks=1), touched,
                  ramp, 0.016) == pytest.approx(
        (nbytes - 1152 * 6400) / 819e9 / 0.016 * 100)
    assert reduce(CONFIG, _Peaks.peaks, args, touched, rows, None) is None
    assert reduce(CONFIG, _Peaks.peaks, args, [], rows, 0.016) is None
    assert decode_hbm_roofline_scmoe.compute(args, _Peaks, m, None) is None


def test_latent_decode_roofline_on_a_fixture():
    """8 kernel calls a tick; 640,000 rows a tick need 0.90 ms of the
    memory system (and 0.45 ms of the MXU), under kernels that took 1.5 ms
    a tick."""
    args = {"pattern": "latent_decode", "itemsize": 2}
    reduce = latent_decode_roofline.reduce
    got = reduce(CONFIG, _Peaks.peaks, args, [640_000.0] * 10, 0.015, 80)
    assert got == pytest.approx(1152 * 640_000 / 819e9 / 0.0015 * 100)
    assert 55 < got < 65
    # FLOPs bound it where the memory system is the faster of the two
    fast = dict(_Peaks.peaks, hbm_bytes_per_s=819e10)
    assert reduce(CONFIG, fast, args, [640_000.0] * 10, 0.015, 80) \
        == pytest.approx(2 * 64 * 1088 * 640_000 / 197e12 / 0.0015 * 100)
    assert reduce(CONFIG, _Peaks.peaks, args, [], 0.015, 80) is None
    assert reduce(CONFIG, _Peaks.peaks, args, [1.0], 0.0, 0) is None
    assert latent_decode_roofline.compute(args, _Peaks, {}, None) is None


def test_the_new_reducers_read_nothing_from_a_program_without_the_attrs():
    """On a program that puts no `pairs_zero` / `tokens_sq` on its spans
    (the parent of this PR, any other model) the three return None and
    raise nothing, whatever the configuration."""
    from singa_tpu.observability import spans
    rec = spans.recorder()
    kept = rec.records()
    rec.clear()
    other = bench_run.load_json(os.path.dirname(TOY), "..", "configs",
                                "command_a_plus_tp8ep8.json")

    class Other:
        config, peaks, trace_dir = other, _Peaks.peaks, "nowhere"
    try:
        rec.record(dict(kind="span", name="serve.decode", ts=101.01,
                        ts_start=101.0, dur_s=0.01, kv_rows=400_000,
                        pairs_here=20, experts_touched=12))
        rec.record(dict(kind="span", name="serve.prefill", ts=100.51,
                        ts_start=100.5, dur_s=0.01, pairs_here=300,
                        experts_touched=16))
        m = _snaps(tokens=100, prefill=50)
        trace = {"events": {0: [["latent_decode.1", 0.0, 5.0]]},
                 "window": (0.0, 10.0)}
        for run in (_Peaks, Other):
            assert serve_mfu_scmoe.compute({}, run, m, None) is None
            assert decode_hbm_roofline_scmoe.compute(
                {"itemsize": 2, "program": "x"}, run, m, trace) is None
        assert latent_decode_roofline.compute(
            {"pattern": "latent_decode", "itemsize": 2}, Other, m,
            trace) is None
    finally:
        rec.clear()
        for r in kept:
            rec.record(r)
