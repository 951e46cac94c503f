"""The whole command walked on the CPU at toy sizes (data under tests/toy,
never named in BENCHMARK.json): build, warm-up, window, comparison, last
line. Then the comparison shown to fail: the control (the reference in fp8
put in the program's place) and, with the timed path broken underneath, each
fault the cell can have.

These drive `run.run_cell` with `require_chip=False`: they skip the
harness's look for a chip and nothing else. No number from here is a
measurement.
"""

import json
import os

import numpy as np
import pytest

import run as bench_run
from drivers import serve_engine, train_step
from lib import compare, xplane

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "gpt2m_train_trace_cut.json.gz")


def _run(cell, seed, seconds=2.0, trace=0, tmp_path=None):
    return bench_run.Run(cell, seed, seconds, trace, root=TOY,
                         require_chip=False,
                         scratch=str(tmp_path) if tmp_path else None)


def _check_line(result, end_to_end):
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device", "compared"}
    assert list(result)[-1] == "compared"
    assert result["device"]["platform"] == "cpu"     # and says so
    json.dumps(result)
    for name in end_to_end:
        assert result["metrics"][name]["value"] > 0


def test_lm_train_cell_walks_and_is_correct(tmp_path):
    result = bench_run.run_cell(_run("toy_gpt2_train", 2**31 + 5,
                                     tmp_path=tmp_path))
    _check_line(result, ["train_tokens_per_s_per_chip", "setup_s"])
    assert result["correct"], result["compared"]
    assert result["notes"]["compiles_in_window"] == 0


def test_serve_cell_walks_and_is_correct(tmp_path):
    result = bench_run.run_cell(_run("toy_gpt2_serve", 11, seconds=3.0,
                                     tmp_path=tmp_path))
    _check_line(result, ["serve_tpot_p95_ms", "setup_s"])
    assert result["notes"]["ttft_p95_ms"] > 0
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] == 18


def test_traced_run_reports_the_cells_layer_metrics(tmp_path, monkeypatch):
    """On the CPU the profiler records no TPU plane, so the recorded chip
    trace stands in for what `xplane.load` would read."""
    cut = xplane.load_json(FIXTURE)
    monkeypatch.setattr(xplane, "load", lambda path, keep_host=(): cut)
    result = bench_run.run_cell(_run("toy_gpt2_train", 7, trace=1,
                                     tmp_path=tmp_path))
    assert {"setup_cache_misses", "step_mfu.tokens",
            "attention_roofline"} <= set(result["metrics"])
    assert "setup_s" not in result["metrics"]
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    assert len(result["breakdown"]["device_ops"]) <= 10


def test_control_fails_the_train_comparison():
    """The reference in fp8 in the program's place is not correct."""
    run = _run("toy_gpt2_train", 3)
    run.find_devices()
    batch = int(run.traffic["batch_per_chip"])
    _, ref_batch = train_step._make_feed(run.config, run.traffic, batch, 3)
    args = (run.config, run.traffic["optimizer"], 3, ref_batch,
            train_step.CHECK_STEPS)
    ref = compare.reference_train(*args)
    control = compare.reference_train(*args, cast="fp8_e4m3")
    numbers = compare.train_numbers(control, ref, run.cell["limits"])
    assert any(v > lim for v, lim in numbers.values()), numbers
    same = compare.train_numbers(ref, ref, run.cell["limits"])
    assert all(v == 0 for v, _ in same.values())


def _frozen_step(monkeypatch):
    import jax.numpy as jnp
    orig = train_step._call_step

    def frozen(h):
        tensors = h.model._state_tensors()
        saved = [jnp.copy(t.data) for t in tensors]
        loss = orig(h)
        for t, a in zip(tensors, saved):
            t.data = a
        return loss
    monkeypatch.setattr(train_step, "_call_step", frozen)


def _half_batch(monkeypatch):
    orig = train_step._make_feed

    def half(config, job, batch, seed):
        (x, y), ref = orig(config, job, batch, seed)
        return (x[:batch // 2], y[:batch // 2]), ref
    monkeypatch.setattr(train_step, "_make_feed", half)


@pytest.mark.parametrize("fault", [_frozen_step, _half_batch])
def test_train_faults_come_out_not_correct(fault, tmp_path, monkeypatch):
    fault(monkeypatch)
    result = bench_run.run_cell(_run("toy_gpt2_train", 5,
                                     tmp_path=tmp_path))
    assert result["correct"] is False, result["compared"]


def test_an_altered_token_comes_out_not_correct(tmp_path, monkeypatch):
    orig = serve_engine._result_tokens

    def altered(result):
        tokens = orig(result)
        tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 101) % 512
        return tokens
    monkeypatch.setattr(serve_engine, "_result_tokens", altered)
    result = bench_run.run_cell(_run("toy_gpt2_serve", 11, seconds=3.0,
                                     tmp_path=tmp_path))
    assert result["correct"] is False, result["compared"]


def test_serve_control_reads_above_the_program():
    """At every position of served prompts and tokens, the token the fp8
    reference puts first lies further below the float32 best than any
    served token does."""
    run = _run("toy_gpt2_serve", 13)
    rng = np.random.default_rng(13)
    samples = []
    for n in (20, 31, 8, 12, 25, 17, 30, 9):
        prompt = rng.integers(1, 512, n, dtype=np.int32)
        samples.append((prompt, rng.integers(1, 512, 20, dtype=np.int32)))
    served, low = serve_engine.reference_gaps(run, samples, cast="fp8_e4m3")
    assert len(served) == len(low) == 160
    assert float(np.max(low)) > run.cell["limits"]["logit_gap"]


@pytest.mark.slow
def test_conv_train_cell_walks(tmp_path):
    result = bench_run.run_cell(_run("toy_resnet_train", 9, seconds=5.0,
                                     tmp_path=tmp_path))
    _check_line(result, ["train_images_per_s_per_chip", "setup_s"])


def _no_exchange(monkeypatch):
    """DistOpt's one reduction chokepoint passes gradients through
    unsummed: every chip steps on its own share's gradient."""
    from singa_tpu import opt
    monkeypatch.setattr(opt.DistOpt, "grad_reduce_stream",
                        lambda self, pairs, wire=None: pairs)


@pytest.mark.slow
@pytest.mark.parametrize("fault", [None, _no_exchange])
def test_four_device_cell_walks_and_its_fault_is_caught(fault, tmp_path,
                                                        monkeypatch):
    import jax
    if jax.device_count() < 4:
        pytest.skip("needs four (virtual) devices")
    if fault is not None:
        fault(monkeypatch)
    result = bench_run.run_cell(_run("toy_resnet_dp4", 9, seconds=3.0,
                                     tmp_path=tmp_path))
    assert result["device"]["count"] == 4
    assert result["correct"] is (fault is None), result["compared"]
