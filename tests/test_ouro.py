"""The looped LM (models/ouro.py) and the per-token fused CE head
(ops/losses.fused_ce_rows) against the plain float32 reference the benchmark
keeps (benchmarks/lib/references/ouro.py), at a small size on the CPU:
hidden 64, 2 heads of 32, SwiGLU 96, 2 layers run 3 times, vocabulary 97,
sequences of 16, float32.

Tolerances: everything here is float32 and differs from the reference by
the order of its sums alone (the flash attention's online softmax, the fused
head's chunked logsumexp, the exit distribution formed in log space against
a plain product). So the loss agrees to 1e-5 relative, and each leaf's
gradient to 1e-4 of its norm: a gradient is the sum of 3 passes' backward
through 6 layer applications, and the worst leaf is the key bias, whose
gradient is a difference of nearly equal terms.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(os.path.dirname(HERE), "benchmarks"),):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import weights                                  # noqa: E402
from lib.references import chain                         # noqa: E402
from lib.references import ouro as ref                   # noqa: E402
from singa_tpu import autograd, device, opt, tensor      # noqa: E402
from singa_tpu.models import ouro                        # noqa: E402
from singa_tpu.observability.metrics import (            # noqa: E402
    MetricsRegistry, default_registry)
from singa_tpu.ops import attention_mod, losses          # noqa: E402

DEV = device.create_cpu_device()
PREFIX = "OuroLM"
B, S = 2, 16
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4


def toy_cfg(**over):
    cfg = dict(hidden_size=64, num_attention_heads=2, head_dim=32,
               intermediate_size=96, vocab_size=97, num_hidden_layers=2,
               total_ut_steps=3, rms_norm_eps=1e-6, rope_theta=1e6,
               exit_entropy_beta=0.1, initializer_range=0.1)
    cfg.update(over)
    return cfg


def build(cfg, seed=7, remat=False, chunk=32, S=S):
    """The program holding the reference's weights for `seed`, its params
    made by the abstract dry run of `Model.compile`."""
    m = ouro.OuroLM(cfg["vocab_size"], d_model=cfg["hidden_size"],
                    n_heads=cfg["num_attention_heads"],
                    head_dim=cfg["head_dim"],
                    d_ff=cfg["intermediate_size"],
                    n_layers=cfg["num_hidden_layers"],
                    loop_passes=cfg["total_ut_steps"],
                    rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
                    exit_entropy=cfg["exit_entropy_beta"],
                    fused_head_chunk=chunk, remat=remat)
    m.compile([_tensor(np.zeros((B, S)))], is_train=True, use_graph=True)
    states = m.get_states()
    params = weights.make(ref.param_specs(cfg), seed)
    for name, arr in params.items():
        t = states[f"{PREFIX}.{name}"]
        assert tuple(t.shape) == tuple(arr.shape), name
        t.data = arr
    return m, params


def _tensor(a):
    return tensor.Tensor(data=jnp.asarray(a, jnp.float32), device=DEV,
                         requires_grad=False)


def batch(cfg, seed=3, S=S):
    ids = np.random.default_rng(seed).integers(0, cfg["vocab_size"], (B, S))
    return jnp.asarray(ids, jnp.int32), jnp.roll(jnp.asarray(ids, jnp.int32),
                                                 -1, 1)


def tape_grads(m, ids, targets):
    """(loss, {name: gradient}) of the program by its tape."""
    prev = autograd.is_training()
    autograd.set_training(True)
    try:
        loss, _ = m.objective(_tensor(ids), _tensor(targets))
        by_id = {id(p): g for p, g in autograd.backward(loss)}
    finally:
        autograd.set_training(prev)
    names = {id(t): n[len(PREFIX) + 1:] for n, t in m.get_states().items()}
    return float(loss.data), {names[k]: np.asarray(g.data)
                              for k, g in by_id.items()}


def ref_grads(cfg, params, ids, targets):
    with jax.default_matmul_precision("highest"):
        loss, grads = chain.value_and_grad(ref.stages(cfg), params, ids,
                                           targets)
    return float(loss), {k: np.asarray(v) for k, v in grads.items()}


def assert_leaves_close(got, want, rtol=GRAD_RTOL):
    assert set(got) == set(want)
    for name, g in want.items():
        err = np.linalg.norm(got[name] - g) / np.linalg.norm(g)
        assert err <= rtol, (name, err)


@pytest.fixture(scope="module")
def toy():
    cfg = toy_cfg()
    m, params = build(cfg)
    ids, targets = batch(cfg)
    return cfg, m, params, (ids, targets), ref_grads(cfg, params, ids,
                                                     targets)


def test_state_names_are_the_references_leaves(toy):
    cfg, m, params, _, _ = toy
    assert {n for n, t in m.get_states().items() if t.requires_grad} == \
        {f"{PREFIX}.{n}" for n in params}


def test_loss_and_every_gradient_match_the_reference(toy):
    cfg, m, params, (ids, targets), (loss, grads) = toy
    got_loss, got = tape_grads(m, ids, targets)
    assert abs(got_loss - loss) <= LOSS_RTOL * abs(loss)
    assert_leaves_close(got, grads)


def test_the_compiled_step_applies_the_references_gradient():
    """Through `Model.compile(is_train=True, use_graph=True)` and `model(tx,
    ty)`: one plain SGD step of rate 1 moves each leaf by minus the
    reference's gradient, and the step returns the reference's loss."""
    cfg = toy_cfg()
    m, params = build(cfg, seed=8)
    ids, targets = batch(cfg, seed=4)
    loss, grads = ref_grads(cfg, params, ids, targets)
    before = {n: np.asarray(a) for n, a in params.items()}  # donated
    m.set_optimizer(opt.SGD(lr=1.0))
    out, got_loss = m(_tensor(ids), _tensor(targets))
    assert abs(float(got_loss.data) - loss) <= LOSS_RTOL * abs(loss)
    states = m.get_states()
    moved = {n: before[n] - np.asarray(states[f"{PREFIX}.{n}"].data)
             for n in params}
    # a move of rate 1 is the gradient to the rounding of the weights it
    # is taken from (float32 of magnitude 0.1 against gradients of ~1e-3)
    assert_leaves_close(moved, grads, rtol=1e-3)
    assert m._last_run_rec["n_traces"] == 1


def _untied_loss(cfg, copies, params, ids, targets):
    """The reference with a copy of the layers for every pass."""
    eps, L = cfg["rms_norm_eps"], cfg["num_hidden_layers"]
    x = params["embed.W"][ids]
    hs = []
    for layers in copies:
        for l in range(L):
            x = ref.layer(layers[l], x, cfg["num_attention_heads"], eps,
                          cfg["rope_theta"], "float32")
        x = ref.rms_norm(x, params["norm.scale"], eps)
        hs.append(x)
    exits = {k: v for k, v in params.items() if k.startswith("exits.")}
    return ref._exits(exits, jnp.stack(hs), targets,
                      cfg["exit_entropy_beta"], ref.ROWS, "float32", None)


def test_a_shared_weights_gradient_is_the_sum_over_the_passes(toy):
    cfg, m, params, (ids, targets), _ = toy
    L, T = cfg["num_hidden_layers"], cfg["total_ut_steps"]
    layer = [{k[len(f"layers.{l}."):]: v for k, v in params.items()
              if k.startswith(f"layers.{l}.")} for l in range(L)]
    copies = [[dict(lp) for lp in layer] for _ in range(T)]
    with jax.default_matmul_precision("highest"):
        per_copy = jax.grad(_untied_loss, argnums=1)(cfg, copies, params,
                                                     ids, targets)
    _, got = tape_grads(m, ids, targets)
    summed = {f"layers.{l}.{k}": sum(np.asarray(per_copy[t][l][k])
                                     for t in range(T))
              for l in range(L) for k in layer[l]}
    assert_leaves_close({k: got[k] for k in summed}, summed)
    # each pass's share is a real part of the sum, not all of it
    w = "layers.0.up_proj.W"
    first = np.asarray(per_copy[0][0]["up_proj.W"])
    assert np.linalg.norm(first) < 0.95 * np.linalg.norm(summed[w])


def test_one_pass_without_entropy_is_a_plain_sandwich_decoder():
    """T = 1, beta = 0: one exit of probability 1, whose loss is the mean
    cross-entropy of a plain decoder with one head."""
    cfg = toy_cfg(total_ut_steps=1, exit_entropy_beta=0.0)
    m, params = build(cfg)
    ids, targets = batch(cfg)

    def plain(p):
        x = p["embed.W"][ids]
        for l in range(cfg["num_hidden_layers"]):
            x = ref.layer({k[len(f"layers.{l}."):]: v for k, v in p.items()
                           if k.startswith(f"layers.{l}.")}, x,
                          cfg["num_attention_heads"], cfg["rms_norm_eps"],
                          cfg["rope_theta"], "float32")
        x = ref.rms_norm(x, p["norm.scale"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(x @ p["exits.head.W"], -1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(plain)(params)
    got_loss, got = tape_grads(m, ids, targets)
    assert abs(got_loss - float(loss)) <= LOSS_RTOL * abs(float(loss))
    # the gate feeds nothing with one pass
    assert not np.any(got["exits.gate.W"]) and not np.any(got["exits.gate.b"])
    assert_leaves_close({k: v for k, v in got.items()
                         if not k.startswith("exits.gate")},
                        {k: np.asarray(v) for k, v in grads.items()
                         if not k.startswith("exits.gate")})


@pytest.mark.parametrize("T", [1, 2, 4])
def test_exit_probabilities_sum_to_one_for_every_token(T):
    z = jax.random.normal(jax.random.PRNGKey(T), (T - 1, 50)) * 3.0
    ce = jnp.ones((T, 50))
    loss, stats = ouro.exit_objective(z, ce, 0.0)
    # with every exit's CE 1, the loss is the mean of sum_t p_t
    np.testing.assert_allclose(float(loss), 1.0, rtol=1e-6)
    np.testing.assert_allclose(float(jnp.sum(stats[0])), 1.0, rtol=1e-6)
    p = ref.exit_probs(jax.nn.sigmoid(z))
    np.testing.assert_allclose(np.asarray(jnp.sum(p, 0)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(stats[0]),
                               np.asarray(jnp.mean(p, 1)), rtol=1e-5)


def test_the_step_keeps_the_exit_statistics_and_sets_the_gauges(toy):
    cfg = toy_cfg()
    m, _ = build(cfg, seed=9)
    assert m.exit_stats() is None
    m.set_optimizer(opt.SGD(lr=0.0))
    ids, targets = batch(cfg)
    m(_tensor(ids), _tensor(targets))
    reg = MetricsRegistry()
    stats = m.exit_stats(registry=reg)
    T = cfg["total_ut_steps"]
    assert len(stats["share"]) == len(stats["loss"]) == T
    np.testing.assert_allclose(sum(stats["share"]), 1.0, rtol=1e-5)
    assert all(0 < s < 1 for s in stats["share"])
    for t in range(T):
        assert reg.get("loop_exit_share").value(**{"pass": t + 1}) == \
            pytest.approx(stats["share"][t])
        assert reg.get("loop_exit_loss").value(**{"pass": t + 1}) == \
            pytest.approx(stats["loss"][t])


def test_the_gauges_count_passes_applications_and_exits():
    ouro.OuroLM(97, d_model=64, n_heads=2, head_dim=32, d_ff=96, n_layers=2,
                loop_passes=3)
    reg = default_registry()
    value = {n: reg.get(n).value(model=PREFIX) for n in (
        "model_loop_passes", "model_layer_applications", "model_exit_heads")}
    assert value == {"model_loop_passes": 3, "model_layer_applications": 6,
                     "model_exit_heads": 3}


@pytest.mark.parametrize("kernels", [False, True])
def test_rematerialised_applications_give_the_same_gradients(kernels,
                                                              monkeypatch):
    """Also with the flash kernels (interpreted, S 128: the path the chip
    takes), whose forward rule has no JVP: the block's own ops inside
    `autograd.checkpoint` must take no vjp of their own."""
    cfg, S = toy_cfg(), 128 if kernels else 16
    if kernels:
        monkeypatch.setattr(attention_mod, "FORCE_PALLAS_INTERPRET", True)
    ids, targets = batch(cfg, S=S)
    plain_loss, plain = tape_grads(build(cfg, S=S)[0], ids, targets)
    remat_loss, remat = tape_grads(build(cfg, remat=True, S=S)[0], ids,
                                   targets)
    assert remat_loss == pytest.approx(plain_loss, rel=1e-6)
    # the same maths; the recompute behind its optimization barrier is
    # fused apart from its use, so float32 rounds differently (1.6e-6 of
    # the key bias's norm, the leaf of cancelling terms)
    assert_leaves_close(remat, plain, rtol=1e-5)


def test_the_passes_and_exits_are_named_in_the_compiled_step(toy):
    cfg = toy_cfg()
    m, _ = build(cfg, seed=10, remat=True)
    m.set_optimizer(opt.SGD(lr=0.0))
    ids, targets = batch(cfg)
    for _ in range(3):
        m(_tensor(ids), _tensor(targets))
    rec = m._last_run_rec
    # one trace and one executable: the device's key keeps its placement
    # when a rematerialised application draws inside the dry run
    assert rec["n_traces"] == 1 and rec["jit"]._cache_size() == 1
    text = rec["jit"].lower(*rec["avals"][:2], *rec["avals"][2]).as_text(
        debug_info=True)
    assert "loop_pass" in text and "loop_exit" in text
    assert "checkpoint" in text or "remat" in text


# -- the per-token fused CE head -----------------------------------------------

def _plain_rows(h, W, b, ids):
    logits = h @ W + (0.0 if b is None else b)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, ids[:, None], -1)[:, 0]


@pytest.mark.parametrize("chunk, with_bias", [(32, True), (32, False),
                                              (97, False), (128, True)])
def test_per_token_fused_ce_is_the_unfused_ce(chunk, with_bias):
    """Values and gradients under a per-token cotangent; the vocabulary of
    97 pads the last chunk of 32 and of 128."""
    k = jax.random.split(jax.random.PRNGKey(chunk), 5)
    N, D, V = 24, 16, 97
    h = jax.random.normal(k[0], (N, D))
    W = jax.random.normal(k[1], (D, V)) * 0.3
    b = jax.random.normal(k[2], (V,)) if with_bias else None
    ids = jax.random.randint(k[3], (N,), 0, V)
    w = jax.random.uniform(k[4], (N,))
    with jax.default_matmul_precision("highest"):
        rows = losses.fused_ce_rows(h, W, b, ids, chunk)
        np.testing.assert_allclose(rows, _plain_rows(h, W, b, ids),
                                   rtol=1e-5, atol=1e-6)
        got = jax.grad(lambda *a: jnp.sum(w * losses.fused_ce_rows(
            *a, ids, chunk)), argnums=(0, 1, 2) if with_bias else (0, 1))(
                h, W, *([b] if with_bias else [None]))
        want = jax.grad(lambda *a: jnp.sum(w * _plain_rows(
            *a, ids)), argnums=(0, 1, 2) if with_bias else (0, 1))(
                h, W, *([b] if with_bias else [None]))
        for g, e in zip(got, want):
            np.testing.assert_allclose(g, e, rtol=1e-4, atol=1e-6)
        if with_bias:
            np.testing.assert_allclose(
                float(jnp.mean(rows)),
                float(losses.fused_ce_head(h, W, b, ids, chunk)), rtol=1e-6)
            mean_grads = jax.grad(lambda *a: losses.fused_ce_head(
                *a, ids, chunk), argnums=(0, 1, 2))(h, W, b)
            row_grads = jax.grad(lambda *a: jnp.mean(losses.fused_ce_rows(
                *a, ids, chunk)), argnums=(0, 1, 2))(h, W, b)
            for g, e in zip(row_grads, mean_grads):
                np.testing.assert_allclose(g, e, rtol=1e-5, atol=1e-8)
