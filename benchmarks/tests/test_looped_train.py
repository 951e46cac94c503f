"""The looped train cell (an Ouro LoopLM stage: shared sandwich-norm layers
run several times, gated exits under the entropy-regularised exit loss)
walked on the CPU at the toy size through the train driver, its comparison
shown to fail under the fp8 control and under three faults planted in the
reference put in the program's place, the need functions tied to the
reference's leaves and to the published model's sizes, and the two reducers
shown to read nothing where the program gives nothing. No number from here
is a measurement.
"""

import functools
import os

import pytest

import run as bench_run
from drivers import train_step
from lib import compare, flops_ouro, xplane
from lib.references import ouro as ref
from reducers import attention_roofline_looped, step_mfu_looped

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
CELL = "toy_ouro_train"
CONFIG = bench_run.load_json(os.path.dirname(TOY), "..", "configs",
                             "ouro_2p6b_pp12.json")
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "gpt2m_train_trace_cut.json.gz")
METRICS = {"setup_cache_misses", "step_mfu.looped",
           "attention_roofline.looped"}


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
    """The CPU profiler records no TPU plane, so a recorded chip trace of
    the flash kernels stands in for what `xplane.load` would read."""
    cut = xplane.load_json(FIXTURE)
    monkeypatch.setattr(xplane, "load", lambda path, keep_host=(): cut)
    result = bench_run.run_cell(_run(7, trace=1, tmp_path=tmp_path))
    assert set(result["metrics"]) == METRICS
    assert 0 < result["metrics"]["step_mfu.looped"]["value"] < 100
    assert result["metrics"]["attention_roofline.looped"]["value"] > 0
    assert result["correct"], result["compared"]


def test_the_reducers_read_nothing_from_a_program_without_the_loop(
        monkeypatch):
    """A program that sets no loop gauges (the parent commit's) and a trace
    with no flash kernel give None, never 0."""
    from singa_tpu.observability import metrics
    run = _run(1)
    measured = {"steps": 5, "batch": 2, "chips": 1, "window_s": 1.0,
                "notes": {"traced_steps": 2}}
    monkeypatch.setattr(metrics, "default_registry",
                        lambda: metrics.MetricsRegistry())
    assert step_mfu_looped.compute({}, run, measured, None) is None
    args = {"patterns": ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]}
    assert attention_roofline_looped.compute(args, run, measured,
                                             None) is None
    other = {"events": {0: [("fusion.1", 0, 1000), ("copy.2", 2000, 500)]}}
    assert attention_roofline_looped.compute(args, run, measured,
                                             other) is None


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
    ("fp8_e4m3", None), (None, "last_exit_only"),
    (None, "pass_gradient_stopped"), (None, "loop_norm_skipped")])
def test_control_and_planted_faults_come_out_not_correct(cast, fault,
                                                         monkeypatch):
    """The reference in fp8, and the reference with a fault, each put in
    the program's place: the loss from the last exit alone; the gradient
    stopped at each boundary between passes; the next pass handed the state
    before the loop norm. Each is over at least one limit."""
    run = _run(3)
    clean = _reference(run, 3)
    faulty = _reference(run, 3, cast, fault, monkeypatch)
    numbers = compare.train_numbers(faulty, clean, run.cell["limits"])
    assert not bench_run.verdict(numbers), numbers
    assert bench_run.verdict(compare.train_numbers(clean, clean,
                                                   run.cell["limits"]))


@pytest.mark.parametrize("gate_scale", [1.0, 40.0])
def test_reference_objective_holds_where_the_gates_saturate(gate_scale):
    """Gate logits past about 17 give lam = 1 exactly in float32 and p = 0 for
    every later exit (three Adam steps on the cell reach that). The
    reference's objective and its gradients stay finite there and agree
    with the program's, which forms p in log space: the p it rounds to 0
    is under e^-17, so 1e-5 on the loss and 1e-4 on the gradients is
    float32 rounding."""
    import jax
    import jax.numpy as jnp
    from singa_tpu.models import ouro

    T, N, D, V = 4, 64, 16, 11
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    hs = jax.random.normal(k[0], (T, 1, N, D), jnp.float32)
    targets = jax.random.randint(k[1], (1, N), 0, V)
    p = {"exits.head.W": jax.random.normal(k[2], (D, V), jnp.float32),
         "exits.gate.W": gate_scale / D ** 0.5
         * jax.random.normal(k[3], (D, 1), jnp.float32),
         "exits.gate.b": jnp.full((1,), 0.5, jnp.float32)}

    def reference(p, hs):
        return ref._exits(p, hs, targets, 0.1, 16, "float32", None)

    def program(p, hs):
        flat = hs.reshape(T, N, D)
        logp = jax.nn.log_softmax(flat @ p["exits.head.W"], -1)
        ce = -jnp.take_along_axis(logp, targets.reshape(1, N, 1), -1)[..., 0]
        z = (flat[:-1] @ p["exits.gate.W"])[..., 0] + p["exits.gate.b"][0]
        return ouro.exit_objective(z, ce, 0.1)[0]

    with jax.default_matmul_precision("highest"):
        z = hs[:-1, 0] @ p["exits.gate.W"] + p["exits.gate.b"][0]
        assert bool(jnp.any(jax.nn.sigmoid(z) == 1.0)) == (gate_scale > 1)
        lr, gr = jax.value_and_grad(reference, argnums=(0, 1))(p, hs)
        lp, gp = jax.value_and_grad(program, argnums=(0, 1))(p, hs)
    assert float(lr) == pytest.approx(float(lp), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
        assert bool(jnp.all(jnp.isfinite(a)))
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale


# -- the need functions --------------------------------------------------------

def _matrices(specs, prefix):
    return sum(shape[0] * shape[1] for n, shape, *_ in specs
               if n.startswith(prefix) and len(shape) == 2)


@pytest.mark.parametrize("config", [CONFIG, bench_run.load_json(
    TOY, "configs", "toy_ouro.json")])
def test_need_functions_count_the_references_leaves(config):
    specs = ref.param_specs(config)
    sizes = {n: 1 for n, *_ in specs}
    for n, shape, *_ in specs:
        for s in shape:
            sizes[n] *= s
    assert flops_ouro.param_count(config) == sum(sizes.values())
    assert flops_ouro.layer_matmul_params(config) == _matrices(specs,
                                                               "layers.0.")
    assert flops_ouro.layer_params(config) == sum(
        v for n, v in sizes.items() if n.startswith("layers.0."))


def test_need_functions_at_the_published_widths():
    """The arithmetic of the cell: a layer's matrices 51.38 M (4 x 2048^2 +
    3 x 2048 x 5632), with its biases and norms 51.39 M; 406.91 M
    parameters in all, 6.51 GB of training state at 16 B; a token's
    forward 16 applications x 119.54 M + 4 heads x 201.3 M = 2.718 GFLOP,
    8.154 GFLOP trained, 66.8 TFLOP a step of 2 x 4096; attention 6.60
    TFLOP and 6.44 GB a step."""
    assert flops_ouro.layer_matmul_params(CONFIG) == 51_380_224
    assert flops_ouro.layer_params(CONFIG) == 51_394_560
    assert flops_ouro.param_count(CONFIG) == 406_908_929
    assert 16 * flops_ouro.param_count(CONFIG) == pytest.approx(6.51e9,
                                                                rel=1e-3)
    fwd = flops_ouro.forward_flops_per_token(CONFIG, 4096)
    assert fwd == 16 * (2 * 51_380_224 + 2 * 4096 * 2048) \
        + 4 * 2 * 2048 * 49152
    assert fwd == pytest.approx(2.718e9, rel=1e-3)
    train = flops_ouro.train_flops_per_token(CONFIG, 4096)
    assert train == pytest.approx(8.154e9, rel=1e-3)
    assert train * 2 * 4096 == pytest.approx(66.8e12, rel=1e-3)
    # the exits' share of the counted FLOPs at 4 layers
    assert 4 * 2 * 2048 * 49152 / fwd == pytest.approx(0.296, abs=1e-3)
    f, b = flops_ouro.attention_train_need(CONFIG, 2, 4096)
    assert f == pytest.approx(6.597e12, rel=1e-3)
    assert b == pytest.approx(6.442e9, rel=1e-3)
    # the gauges' counts give the same numbers as the configuration's
    assert flops_ouro.train_flops_per_token(CONFIG, 4096, 16, 4) == train
