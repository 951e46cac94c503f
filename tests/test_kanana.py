"""The latent-attention expert LM (models/kanana.py), the flash kernels with
values narrower than keys (ops/attention.py) and the share-aware expert
layer's reverse (parallel/moe.py), at a small size on the CPU, the Pallas
kernels in interpret mode, against plain float32 forms: the reference the
benchmark keeps (benchmarks/lib/references/kanana.py) and dense layers
written out here.

Tolerances: everything compared here is float32 and differs from its plain
form by the order of its sums alone (online softmax, the fused head's
chunked logsumexp, pairs sorted and summed per expert). So losses agree to
1e-5 relative and each leaf's gradient to 1e-4 of its norm; kernel outputs
to 1e-5 of their largest value.
"""

import importlib
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
from lib.references import kanana as ref                 # noqa: E402
from singa_tpu import autograd, device, opt, tensor      # noqa: E402
from singa_tpu.models import kanana, longcat_flash       # noqa: E402
from singa_tpu.observability.metrics import MetricsRegistry  # noqa: E402
from singa_tpu.parallel import moe                       # noqa: E402

ATTN = importlib.import_module("singa_tpu.ops.attention")
DEV = device.create_cpu_device()
PREFIX = "KananaLM"
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4


# ---------------------------------------------------------------------------
# the flash kernels: keys 24 wide, values 16
# ---------------------------------------------------------------------------

def _plain_attention(q, k, v, causal):
    S = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / np.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                      precision="highest")


@pytest.mark.parametrize("kernels", [True, False], ids=["pallas", "scan"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_with_values_narrower_than_keys(causal, kernels, monkeypatch):
    """Forward and the three gradients of the Pallas kernels (interpreted)
    and of the scan path, at keys 24 and values 16, against plain
    softmax attention: the output and dv are 16 wide, dq and dk 24."""
    monkeypatch.setattr(ATTN, "FORCE_PALLAS_INTERPRET", kernels)
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    q, kk = (jax.random.normal(k[i], (1, 2, 256, 24)) for i in (0, 1))
    v, g = (jax.random.normal(k[i], (1, 2, 256, 16)) for i in (2, 3))
    assert (ATTN._pallas_blocks(q, kk) is not None) == kernels
    out, vjp = jax.vjp(lambda *a: ATTN.flash_attention(*a, causal), q, kk, v)
    want, vjp_want = jax.vjp(lambda *a: _plain_attention(*a, causal), q, kk,
                             v)
    assert out.shape == (1, 2, 256, 16)
    for a, b in zip((out,) + vjp(g), (want,) + vjp_want(g)):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= \
            1e-5 * float(jnp.max(jnp.abs(b))) + 1e-6


def test_heads_per_step_counts_each_width():
    """Equal widths reckon as they did with one width; narrower values
    leave room for more heads a step, never fewer."""
    for n, bq, bk, D in ((64, 512, 512, 64), (32, 512, 512, 128),
                         (64, 512, 512, 192)):
        assert ATTN._heads_per_step(n, bq, bk, D, 2) == \
            ATTN._heads_per_step(n, bq, bk, D, 2, D)
        assert ATTN._heads_per_step(n, bq, bk, D, 2, 128) >= \
            ATTN._heads_per_step(n, bq, bk, D, 2)


# ---------------------------------------------------------------------------
# the share-aware expert layer's reverse
# ---------------------------------------------------------------------------

def _ffn_params(rng, D=16, F=24, E=8, G=4, Fs=32):
    def n(*s):
        return jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    # a router skewed toward the first experts: uneven loads
    router = n(D, E) + jnp.linspace(0.6, -0.6, E)[None, :]
    return {"router": router, "router_bias": n(E) * 0.1,
            "w_gate": n(G, D, F), "w_up": n(G, D, F), "w_down": n(G, F, D),
            "s_gate": n(1, D, Fs), "s_up": n(1, D, Fs), "s_down": n(1, Fs, D)}


def _dense_layer(p, h, top_k, held_from, route, alone=False):
    """The same layer written out densely in float32: every held expert
    on every row, weighted by the routing rule or nought; ``alone``: the
    weights' gradient that of a share trained alone (the reference's),
    else the plain rule's."""
    G = p["w_gate"].shape[0]
    with jax.default_matmul_precision("highest"):
        weight = ref.route(h, p["router"], p["router_bias"], top_k,
                           route.scale, held_from, G,
                           None if alone else "absent_outputs_learnt")

        def mlp(g, u, d, x):
            return (jax.nn.silu(x @ g) * (x @ u)) @ d
        y = sum(weight[:, e:e + 1] * mlp(p["w_gate"][e], p["w_up"][e],
                                         p["w_down"][e], h)
                for e in range(G))
        return y + mlp(p["s_gate"][0], p["s_up"][0], p["s_down"][0], h)


ROUTE = moe.Route("sigmoid", True, 2.448)


@pytest.mark.parametrize("alone", [False, True], ids=["plain", "alone"])
@pytest.mark.parametrize("T", [300, 515])
def test_grouped_layer_gradients_match_a_dense_layer(T, alone):
    """Above DENSE_ROWS the grouped path: the output and the gradients in
    the rows, the router, every held bank and the shared expert agree with
    jax.grad of the dense float32 layer, under the plain rule and under
    that of a share trained alone; the selection bias gets none."""
    assert T > moe.DENSE_ROWS
    rng = np.random.default_rng(T)
    p = _ffn_params(rng)
    h = jnp.asarray(rng.normal(size=(T, 16)), jnp.float32)
    kw = dict(top_k=3, held_from=2, route=ROUTE, alone=alone)

    def program(p, h):
        y, stats = moe.expert_share_ffn(p, h, grouped=True, **kw)
        return jnp.sum(jnp.sin(y)), stats

    def dense(p, h):
        return jnp.sum(jnp.sin(_dense_layer(p, h, 3, 2, ROUTE, alone)))

    (got, stats), g_got = jax.value_and_grad(program, (0, 1),
                                             has_aux=True)(p, h)
    want, g_want = jax.value_and_grad(dense, (0, 1))(p, h)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    loads = int(stats["load_max"])
    assert int(stats["pairs_here"]) < 4 * loads      # uneven routing
    for name in p:
        a, b = np.asarray(g_got[0][name]), np.asarray(g_want[0][name])
        if name == "router_bias":
            assert not np.any(a) and not np.any(b)
            continue
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b), name
    a, b = np.asarray(g_got[1]), np.asarray(g_want[1])
    assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b)


@pytest.mark.parametrize("T", [100, 300])
def test_a_share_alone_does_not_learn_toward_the_experts_it_holds(T):
    """``alone``: the forward is the plain rule's bit for bit; no gradient
    reaches the router's columns of experts held elsewhere (their picks'
    outputs are missing), and the scores of the picks here, weighted by
    themselves, get gradients that sum to nought. Under the plain rule the
    absent columns do learn: the drift the rule removes. On both paths:
    every held expert on every row (T 100) and the grouped product."""
    rng = np.random.default_rng(T + 1)
    p = _ffn_params(rng)
    h = jnp.asarray(rng.normal(size=(T, 16)), jnp.float32)
    kw = dict(top_k=3, held_from=2, route=ROUTE, grouped=True)

    def loss(router, alone):
        y, _ = moe.expert_share_ffn(dict(p, router=router), h,
                                    alone=alone, **kw)
        return jnp.sum(jnp.sin(y)), y

    (_, y_alone), g_alone = jax.value_and_grad(loss, has_aux=True)(
        p["router"], True)
    (_, y_plain), g_plain = jax.value_and_grad(loss, has_aux=True)(
        p["router"], False)
    np.testing.assert_array_equal(np.asarray(y_alone), np.asarray(y_plain))
    held = np.zeros(8, bool)
    held[2:6] = True
    g_alone, g_plain = np.asarray(g_alone), np.asarray(g_plain)
    assert np.abs(g_alone[:, ~held]).max() <= 1e-6 * np.abs(g_alone).max()
    assert np.abs(g_plain[:, ~held]).max() > 0.05 * np.abs(g_plain).max()

    # the picks' own scores: a row's present picks' gradients, weighted
    # by their scores, sum to nought; its absent picks get none
    s = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(T, 6)), jnp.float32))
    here = jnp.asarray(rng.random((T, 6)) < 0.4)
    out = jnp.asarray(rng.normal(size=(T, 6)), jnp.float32)

    def through_scores(s):
        w = moe._weights_of_a_share_alone(
            s / jnp.sum(s, -1, keepdims=True) * 2.448, here)
        return jnp.sum(jnp.sin(jnp.where(here, w * out, 0.0)))
    gs = jax.grad(through_scores)(s)
    np.testing.assert_allclose(np.asarray(jnp.sum(s * gs, -1)), 0.0,
                               atol=1e-6)
    gs = np.asarray(gs)
    assert np.abs(gs[~np.asarray(here)]).max() <= 1e-6 * np.abs(gs).max()


def test_grouped_path_counts_its_tiles():
    """pairs_here + pairs_absent are every pick; the rows the grouped
    product works are whole tiles covering the pairs here, at most one
    tile more than the pairs need for each held expert."""
    rng = np.random.default_rng(3)
    p = _ffn_params(rng)
    T = 400
    h = jnp.asarray(rng.normal(size=(T, 16)), jnp.float32)
    y, st = moe.expert_share_ffn(p, h, top_k=3, held_from=2, route=ROUTE,
                                 grouped=True)
    y_loop, st_loop = moe.expert_share_ffn(p, h, top_k=3, held_from=2,
                                           route=ROUTE)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_loop),
                               atol=1e-5)
    assert int(st["pairs_here"] + st["pairs_absent"]) == 3 * T
    rows, here = int(st["rows_computed"]), int(st["pairs_here"])
    assert rows % moe.GROUPED_TILE == 0
    assert here <= rows <= here + 2 * 4 * moe.GROUPED_TILE
    assert set(st) == set(st_loop) | {"rows_computed", "load_max"}


@pytest.mark.parametrize("M", [256, 384])
def test_grouped_matmul_and_its_reverse(M):
    """Rows of the held groups times their group's matrix; rows of the
    last group (pairs held elsewhere) and of none nought; the reverse
    against the dense form's."""
    rng = np.random.default_rng(M)
    G, K, N = 3, 16, 24
    sizes = jnp.asarray([70, 0, 101, M - 171], jnp.int32)
    lhs = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(G, K, N)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(M, N)), jnp.float32)
    group = np.repeat(np.arange(G + 1), np.asarray(sizes))

    def dense(lhs, rhs):
        W = jnp.concatenate([rhs, jnp.zeros((1, K, N))])[group]
        return jnp.einsum("mk,mkn->mn", lhs, W, precision="highest")

    out, vjp = jax.vjp(lambda a, b: moe.grouped_matmul(a, b, sizes),
                       lhs, rhs)
    want, vjp_want = jax.vjp(dense, lhs, rhs)
    assert not np.any(np.asarray(out)[171:])
    for a, b in zip((out,) + vjp(dy), (want,) + vjp_want(dy)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)


def test_eight_shares_and_the_shared_expert_add_up_to_the_whole_layer():
    """Eight shares of 2 experts each of a 16-expert layer: their routed
    parts, with the shared expert counted once, are the layer with every
    expert held (output and gradients in the rows)."""
    rng = np.random.default_rng(8)
    E, G = 16, 2
    whole = _ffn_params(rng, E=E, G=E)
    h = jnp.asarray(rng.normal(size=(260, 16)), jnp.float32)
    kw = dict(top_k=4, route=ROUTE, grouped=True)
    none = {"s_gate": whole["s_gate"][:0], "s_up": whole["s_up"][:0],
            "s_down": whole["s_down"][:0]}

    def share(i, h):
        p = dict(whole, **none)
        for b in ("w_gate", "w_up", "w_down"):
            p[b] = whole[b][i * G:(i + 1) * G]
        return moe.expert_share_ffn(p, h, held_from=i * G, **kw)[0]

    def shared_once(h):
        p = dict(whole)
        for b in ("w_gate", "w_up", "w_down"):
            p[b] = whole[b][:0]
        return moe.expert_share_ffn(p, h, **kw)[0]

    def summed(h):
        return sum(share(i, h) for i in range(E // G)) + shared_once(h)

    def uncut(h):
        return moe.expert_share_ffn(whole, h, **kw)[0]

    np.testing.assert_allclose(np.asarray(summed(h)), np.asarray(uncut(h)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(uncut(h)), np.asarray(
        _dense_layer(whole, h, 4, 0, ROUTE)), rtol=1e-4, atol=1e-4)
    g1 = jax.grad(lambda h: jnp.sum(jnp.sin(summed(h))))(h)
    g2 = jax.grad(lambda h: jnp.sum(jnp.sin(uncut(h))))(h)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4,
                               atol=1e-5)


def test_the_layer_form_trains():
    """ExpertShareFFN is a tape node with a reverse now: its banks and
    router take gradients through the grouped path."""
    ffn = moe.ExpertShareFFN(8, 24, top_k=2, held_count=3, held_from=4,
                             n_shared=1, init_std=0.3)
    x = tensor.Tensor(data=np.random.default_rng(4).normal(
        size=(2, 100, 16)).astype(np.float32), device=DEV,
        requires_grad=False)
    prev = autograd.is_training()
    autograd.set_training(True)
    try:
        y = ffn(x)
        loss = autograd.reduce_mean(autograd.mul(y, y))
        got = {id(p): g for p, g in autograd.backward(loss)}
    finally:
        autograd.set_training(prev)
    names = {id(t): n for n, t in ffn.get_params().items()}
    assert {names[k].split(".")[-1] for k in got} >= {
        "router", "w_gate", "w_up", "w_down", "s_gate"}
    assert all(np.all(np.isfinite(np.asarray(g.data))) for g in got.values())


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def toy_cfg(**over):
    cfg = dict(hidden_size=32, num_attention_heads=2, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24,
               intermediate_size=48, moe_intermediate_size=12,
               n_shared_experts=2, n_routed_experts=3, router_width=8,
               experts_held_from=2, num_experts_per_tok=3,
               routed_scaling_factor=2.448, norm_topk_prob=True,
               first_k_dense_replace=1, num_hidden_layers=3,
               rms_norm_eps=1e-6, rope_theta=1e6, vocab_size=61,
               init={"matrix_std": 0.15, "norm_std": 0.1,
                     "router_bias_std": 0.05})
    cfg.update(over)
    return cfg


B, S = 2, 96        # 192 rows: above DENSE_ROWS, the grouped path


def build(cfg, seed=7, remat=False, chunk=32):
    m = kanana.KananaLM(
        cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        num_experts=cfg["n_routed_experts"],
        router_width=cfg["router_width"],
        top_k=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        experts_held_from=cfg["experts_held_from"],
        first_k_dense=cfg["first_k_dense_replace"],
        num_layers=cfg["num_hidden_layers"], rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"], fused_head_chunk=chunk,
        remat=remat)
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


def batch(cfg, seed=3):
    ids = np.random.default_rng(seed).integers(0, cfg["vocab_size"], (B, S))
    return jnp.asarray(ids, jnp.int32), jnp.roll(jnp.asarray(ids, jnp.int32),
                                                 -1, 1)


def tape_grads(m, ids, targets):
    prev = autograd.is_training()
    autograd.set_training(True)
    try:
        loss = m.objective(_tensor(ids), _tensor(targets))
        by_id = {id(p): g for p, g in autograd.backward(loss)}
    finally:
        autograd.set_training(prev)
    names = {id(t): n[len(PREFIX) + 1:] for n, t in m.get_states().items()}
    return float(loss.data), {names[k]: np.asarray(g.data)
                              for k, g in by_id.items()}


def ref_grads(cfg, params, ids, targets, fault=None):
    with jax.default_matmul_precision("highest"):
        loss, grads = chain.value_and_grad(ref.stages(cfg, fault=fault),
                                           params, ids, targets)
    return float(loss), {k: np.asarray(v) for k, v in grads.items()}


def assert_leaves_close(got, want, rtol=GRAD_RTOL):
    for name, g in want.items():
        if not np.any(g):
            assert name.endswith("router_bias"), name
            assert not np.any(got.get(name, 0.0)), name
            continue
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


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_every_gradient_match_the_reference(toy, remat):
    cfg, m, params, (ids, targets), (loss, grads) = toy
    m.remat = remat
    try:
        got_loss, got = tape_grads(m, ids, targets)
    finally:
        m.remat = False
    assert abs(got_loss - loss) <= LOSS_RTOL * abs(loss)
    assert_leaves_close(got, grads)


@pytest.mark.parametrize("fault", ["selection_bias_skipped",
                                   "shared_expert_skipped",
                                   "weights_unnormalised",
                                   "absent_outputs_learnt"])
def test_each_planted_fault_moves_the_reference(toy, fault):
    """The faults the benchmark's tests plant are visible at this size."""
    cfg, m, params, (ids, targets), (loss, grads) = toy
    bad_loss, bad = ref_grads(cfg, params, ids, targets, fault)
    worst = max(np.linalg.norm(bad[n] - g) / max(np.linalg.norm(g), 1e-30)
                for n, g in grads.items() if np.any(g))
    assert abs(bad_loss - loss) > LOSS_RTOL * abs(loss) or worst > 1e-3


def test_the_compiled_step_trains_with_adam_and_keeps_the_counts():
    """Model.compile(is_train=True, use_graph=True, policy="bf16_mixed")
    and model(tx, ty) with Adam: the loss falls, the selection bias stays
    as drawn, and the expert layers' counts come back as gauges."""
    cfg = toy_cfg()
    m = kanana.KananaLM(cfg["vocab_size"], hidden_size=32, num_heads=2,
                        qk_nope_head_dim=16, qk_rope_head_dim=8,
                        v_head_dim=16, kv_lora_rank=24, intermediate_size=48,
                        moe_intermediate_size=12, num_experts=3,
                        router_width=8, top_k=3, experts_held_from=2,
                        num_layers=3, fused_head_chunk=32, remat=True)
    m.set_optimizer(opt.Adam(lr=3e-3))
    ids, targets = batch(cfg)
    tx, ty = _tensor(ids), _tensor(targets)
    m.compile([tx], is_train=True, use_graph=True, policy="bf16_mixed")
    bias = np.asarray(m.get_states()[f"{PREFIX}.layers.1.router_bias"].data)
    losses = [float(m(tx, ty)[1].data) for _ in range(6)]
    assert losses[-1] < losses[0]
    np.testing.assert_array_equal(
        np.asarray(m.get_states()[f"{PREFIX}.layers.1.router_bias"].data),
        bias)
    reg = MetricsRegistry()
    st = m.moe_stats(reg)
    assert st["layer"] == [1, 2]
    assert all(0 < p <= B * S * 3 for p in st["pairs_here"])
    assert all(0 <= s < 1 for s in st["padding_share"])
    assert st["load_mean"] == [p / 3 for p in st["pairs_here"]]
    doc = reg.get("moe_load_max").to_doc()
    assert {s["labels"]["layer"] for s in doc["series"]} == {"1", "2"}
    # the process's registry has them from the first step, read from the
    # newest step when read; the mean over the 6 steps and the first
    # (eager) call lies within the steps' own counts
    from singa_tpu.observability.metrics import default_registry
    series = default_registry().get("moe_pairs_here").to_doc()["series"]
    assert sorted(s["value"] for s in series) == sorted(st["pairs_here"])
    mean = st["pairs_here_per_step"]
    assert all(0 < v <= B * S * 3 for v in mean)
    assert reg.get("moe_pairs_here_per_step").value(layer=1) == mean[0]


def test_the_step_names_its_spans_and_kernels():
    """The compiled step's HLO carries the named scopes of the layer and
    the expert path's kernels (interpreted here, so by their scope)."""
    cfg = toy_cfg()
    m, params = build(cfg)
    ids, targets = batch(cfg)

    def loss(p):
        lp = {k[len("layers.1."):]: v for k, v in p.items()
              if k.startswith("layers.1.")}
        x = jnp.zeros((B, S, cfg["hidden_size"]), jnp.float32) + 0.1
        return jnp.sum(kanana.layer_apply(m.cfg, lp, x, False)[0])

    text = jax.jit(jax.grad(loss)).lower(params).as_text(
        debug_info=True)
    for scope in ("mla_project", "latent_attention", "moe_route",
                  "moe_experts", "moe_shared"):
        assert scope in text, scope


def test_mla_project_takes_a_full_rank_query():
    """With q_lora_rank 0 the query is one matrix; the key/value half is
    longcat's, unchanged."""
    cfg = kanana.Config(
        hidden_size=16, num_heads=2, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, kv_lora_rank=12, intermediate_size=8,
        moe_intermediate_size=4, n_shared_experts=1, num_experts=2,
        router_width=4, top_k=2, routed_scaling_factor=1.0,
        norm_topk_prob=True, experts_held_from=0, first_k_dense=1,
        rope_theta=1e4, rms_norm_eps=1e-6)
    rng = np.random.default_rng(0)
    p = {n: jnp.asarray(rng.normal(size=s), jnp.float32)
         for n, s in cfg.leaf_shapes(True).items()}
    h = jnp.asarray(rng.normal(size=(1, 5, 16)), jnp.float32)
    pos = jnp.arange(5)[None]
    q_n, q_r, c, k_r = longcat_flash.mla_project(cfg, p, None, h, pos)
    q = (h @ p["wq"]).reshape(1, 5, 2, 12)
    np.testing.assert_allclose(np.asarray(q_n), np.asarray(q[..., :8]),
                               rtol=1e-5, atol=1e-5)
    assert q_r.shape == (1, 5, 2, 4) and c.shape == (1, 5, 12)
    assert k_r.shape == (1, 5, 4)
