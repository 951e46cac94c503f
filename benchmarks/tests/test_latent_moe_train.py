"""The latent-attention expert train cell (one chip's share of a Kanana-2 /
DeepSeek-V3-style model: MLA with a full-rank query, a dense layer, then
sigmoid top-k experts with a selection bias and a shared expert) walked on
the CPU at the toy size through the train driver, its comparison shown to
fail under the fp8 control and under four faults planted in the reference
put in the program's place, the need functions tied to the reference's
leaves and to the published model's sizes, and the reducers shown to read
nothing where the program gives nothing. No number from here is a
measurement.
"""

import functools
import math
import os

import pytest

import run as bench_run
from drivers import train_step
from lib import compare, flops_kanana, xplane
from lib.references import kanana as ref
from reducers import kanana_roofline, step_mfu_latent_moe

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
CELL = "toy_kanana_train"
CONFIG = bench_run.load_json(os.path.dirname(TOY), "..", "configs",
                             "kanana2_30b_a3b_ep8.json")
TOY_CONFIG = bench_run.load_json(TOY, "configs", "toy_kanana.json")
METRICS = {"setup_cache_misses", "step_mfu.latent_moe",
           "attention_roofline.latent", "moe_roofline.train"}


def _run(seed, trace=0, tmp_path=None):
    return bench_run.Run(CELL, seed, 2.0, trace, root=TOY,
                         require_chip=False,
                         scratch=str(tmp_path) if tmp_path else None)


def test_cell_walks_and_is_correct(tmp_path):
    result = bench_run.run_cell(_run(2**31 + 5, tmp_path=tmp_path))
    assert result["correct"], result["compared"]
    assert result["device"]["platform"] == "cpu"
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0
    assert result["notes"]["compiles_in_window"] == 0
    assert set(result["compared"]) == {"loss_gap", "first_grad_norm_gap",
                                       "change_norm_gap"}


def test_traced_run_reports_the_cells_layer_metrics(tmp_path, monkeypatch):
    """The CPU profiler records no TPU plane, so a made-up device line with
    the flash kernels and the grouped product's kernels stands in for what
    `xplane.load` would read."""
    ops = [["flash_fwd.1", 0, 4_000_000], ["flash_bwd_dq.2", 5e6, 4e6],
           ["gmm.3", 10e6, 1e6], ["tgmm.4", 12e6, 1e6]]
    made = {"planes": [{"name": "/device:TPU:0",
                        "lines": [{"name": xplane.OP_LINE, "events": ops}]}]}
    monkeypatch.setattr(xplane, "load", lambda path, keep_host=(): made)
    result = bench_run.run_cell(_run(7, trace=1, tmp_path=tmp_path))
    assert set(result["metrics"]) == METRICS
    assert 0 < result["metrics"]["step_mfu.latent_moe"]["value"] < 100
    for name in ("attention_roofline.latent", "moe_roofline.train"):
        assert result["metrics"][name]["value"] > 0
    assert result["correct"], result["compared"]


def test_the_reducers_read_nothing_without_the_kernels():
    """No trace, or a trace with neither the flash kernels nor the grouped
    product's, gives None, never 0."""
    run = _run(1)
    measured = {"steps": 5, "batch": 2, "chips": 1, "window_s": 1.0,
                "notes": {"traced_steps": 2}}
    assert step_mfu_latent_moe.compute({}, run, {"steps": 0}, None) is None
    other = {"events": {0: [("fusion.1", 0, 1000), ("copy.2", 2000, 500)]}}
    for args in ({"patterns": ["flash_fwd"], "need": "attention_train_need"},
                 {"patterns": ["gmm"], "need": "moe_train_need",
                  "pairs": "moe_pairs_here"}):
        assert kanana_roofline.compute(args, run, measured, None) is None
        assert kanana_roofline.compute(args, run, measured, other) is None


def _reference(run, seed, cast=None, fault=None, monkeypatch=None):
    batch = int(run.traffic["batch_per_chip"])
    _, ref_batch = train_step._make_feed(run.config, run.traffic, batch, seed)
    if fault is not None:
        monkeypatch.setattr(ref, "stages",
                            functools.partial(ref.stages, fault=fault))
    return compare.reference_train(run.config, run.traffic["optimizer"],
                                   seed, ref_batch, train_step.CHECK_STEPS,
                                   cast=cast)


@pytest.mark.parametrize("cast, fault", [
    ("fp8_e4m3", None), (None, "selection_bias_skipped"),
    (None, "shared_expert_skipped"), (None, "weights_unnormalised"),
    (None, "absent_outputs_learnt")])
def test_control_and_planted_faults_come_out_not_correct(cast, fault,
                                                         monkeypatch):
    """The reference in fp8, and the reference with a fault, each put in
    the program's place: picks made by the scores alone; the shared expert
    left out; the picks' weights not divided by their sum; the router
    learning from the missing outputs of picks held elsewhere. Each is over
    at least one limit."""
    run = _run(3)
    clean = _reference(run, 3)
    faulty = _reference(run, 3, cast, fault, monkeypatch)
    numbers = compare.train_numbers(faulty, clean, run.cell["limits"])
    assert not bench_run.verdict(numbers), numbers
    assert bench_run.verdict(compare.train_numbers(clean, clean,
                                                   run.cell["limits"]))


# -- the need functions -------------------------------------------------------

@pytest.mark.parametrize("config", [CONFIG, TOY_CONFIG],
                         ids=["published", "toy"])
def test_need_functions_count_the_references_leaves(config):
    specs = ref.param_specs(config)
    assert flops_kanana.param_count(config) == sum(
        math.prod(shape) for _, shape, *_ in specs)
    mla = sum(math.prod(shape) for n, shape, *_ in specs
              if n.split(".")[-1] in ("wq", "wkv_a", "wkv_b", "wo")
              and n.startswith("layers.0."))
    assert flops_kanana.mla_params(config) == mla
    bank = [shape for n, shape, *_ in specs if n.endswith(".w_gate")][0]
    assert bank[0] == int(config["n_routed_experts"])
    assert flops_kanana.expert_params(config) == 3 * bank[1] * bank[2]


def test_need_functions_at_the_published_widths():
    """The arithmetic of the cell: MLA 26.35 M a layer; the dense layer
    64.10 M, an expert layer 111.55 M with its 16 held experts, embedding
    and head 65.67 M: 575.96 M held here, 10.37 GB at 18 B; at the pairs an
    even router would send here (6 x 16 / 128 = 0.75 a token a layer, 3
    over the 4 expert layers) a token's forward 0.720 GFLOP, 2.161 GFLOP
    trained, 17.7 TFLOP a step of 2 x 4096; attention 5.15 TFLOP and 5.0
    GB a step."""
    assert flops_kanana.mla_params(CONFIG) == 26_345_472
    assert flops_kanana.param_count(CONFIG) == 575_955_968
    assert 18 * flops_kanana.param_count(CONFIG) == pytest.approx(
        10.37e9, rel=1e-3)
    even = 4 * 6 * 16 / 128
    fwd = flops_kanana.forward_flops_per_token(CONFIG, 4096, even)
    assert fwd == pytest.approx(0.7202e9, rel=1e-3)
    train = flops_kanana.train_flops_per_token(CONFIG, 4096, even)
    assert train * 2 * 4096 == pytest.approx(17.70e12, rel=1e-3)
    f, b = flops_kanana.attention_train_need(CONFIG, 2, 4096)
    assert f == pytest.approx(5.154e12, rel=1e-3)
    assert b == pytest.approx(5.03e9, rel=1e-3)
    # 9 products of 6,144 pairs x 2,048 x 768 x 2 in each of 4 layers
    f, b = flops_kanana.moe_train_need(CONFIG, 2, 4096, 2 * 4096 * even)
    assert f == 4 * 9 * 2 * 6144 * 2048 * 768
    # twice the pairs, twice the products and every byte but the banks'
    f2, b2 = flops_kanana.moe_train_need(CONFIG, 2, 4096, 4 * 4096 * even)
    banks = 4 * 9 * 16 * 2048 * 768 * 2
    assert f2 == 2 * f and b2 - banks == pytest.approx(2 * (b - banks))


def test_the_reducers_count_the_pairs_the_program_counts(monkeypatch):
    """The expert work comes from the gauges the program sets: none in
    the registry, no reading; more pairs counted, a larger share."""
    from singa_tpu.observability import metrics
    monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())
    run = _run(1)
    run.find_devices()
    measured = {"steps": 5, "batch": 2, "chips": 1, "window_s": 1.0,
                "notes": {"traced_steps": 2}}
    trace = {"events": {0: [("gmm.1", 0, 1000), ("tgmm.2", 2000, 500)]}}
    args = {"patterns": ["gmm"], "need": "moe_train_need",
            "pairs": "moe_pairs_here"}
    assert kanana_roofline.compute(args, run, measured, trace) is None
    assert step_mfu_latent_moe.compute({}, run, measured, None) is None
    reg = metrics.default_registry()
    readings = []
    for pairs in (100.0, 200.0):
        for name in ("moe_pairs_here", "moe_pairs_here_per_step"):
            g = reg.gauge(name, labels=("layer",))
            for layer in (1, 2):
                g.set(pairs, layer=layer)
        readings.append((
            kanana_roofline.compute(args, run, measured, trace),
            step_mfu_latent_moe.compute({}, run, measured, None)))
    assert all(b > a > 0 for a, b in zip(*readings))
