"""The tree ``_LMServeAdapter.params()`` hands the engine (PR 34): the
layers role by role — a vector role ONE leaf stacked over the layers, a
matrix role a leaf a layer — with the matrix products' operands cast
once to the compute dtype; against a reference that runs the package's
own block body on the tree the adapter built before (a float32 leaf a
block a role, gathered through the host, cast at its use site every
tick). The old tree and its walk over a list of blocks live here, not
in the package.

- prefill and decode logits and the served tokens equal the old tree's,
  over {float32, bf16_mixed, int8_weight_only, fp8_serving} × {ring,
  paged};
- a layer adds its six matrices to the weight leaves and nothing else,
  and ``serve_program_arg_buffers`` reads what ``tree_leaves`` counts;
- the pin on the live arrays returns one tree until a train step
  rebinds them, and a cast tree keeps no float32 copy beside it.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import device, mixed_precision as mp, opt
from singa_tpu.models import transformer
from singa_tpu.observability import metrics as obs_metrics
from singa_tpu.quant import core as qcore
from singa_tpu.serving.engine import ServingEngine
from singa_tpu.tensor import Tensor

pytestmark = pytest.mark.serving

DEV = device.create_cpu_device()
POLICIES = (None, "bf16_mixed", "int8_weight_only", "fp8_serving")
LAYOUTS = ("ring", "paged")
GEOMETRY = dict(slots=2, max_len=32, prefill_len=8, prefill_batch=1)
PAGED = dict(kv_layout="paged", kv_block_size=4)


def tiny_lm(layers=2, seed=0):
    np.random.seed(seed)
    m = transformer.TransformerLM(19, d_model=16, n_heads=2,
                                  n_layers=layers, max_len=64, tp=False)
    m.eval()
    m(Tensor(data=np.zeros((1, 4), np.float32), device=DEV,
             requires_grad=False))
    return m


class _OldTreeAdapter(transformer._LMServeAdapter):
    """The adapter with the parameter tree it built before PR 34."""

    def params(self):
        def a(t):
            return jnp.asarray(np.asarray(jax.device_get(t.data)))

        m = self.m
        blocks = [{name: a(t) for name, t in leaves}
                  for leaves in transformer._lm_decode_tensors(m)]
        if getattr(self.policy, "weight_quant", None) == "int8":
            for p in blocks:
                for key in self._QUANT_KEYS:
                    q, s = qcore.quantize_int8(
                        p[key], qcore.channel_axis(p[key].shape))
                    p[key] = {"q": q, "s": s}
        return dict(tok=a(m.tok_emb.W), pos=a(m.pos_emb.W),
                    lnf_s=a(m.ln_f.scale), lnf_b=a(m.ln_f.bias),
                    head_w=a(m.head.W), head_b=a(m.head.b),
                    blocks=blocks)


@contextlib.contextmanager
def old_walk():
    """While this is entered, a program that is traced takes layer
    ``l``'s leaves from a LIST of blocks — the old tree's form."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(transformer, "_layer", lambda blocks, l: blocks[l])
        yield


def _logits(ad, layout):
    """One prefill of a 5-token prompt into slot 0 and one decode tick,
    by the adapter's own programs: (prefill logits, decode logits)."""
    P = ad.params()
    i32 = np.int32
    prompt = np.array([[3, 1, 4, 1, 5, 0, 0, 0]], i32)
    n = np.array([5], i32)
    nxt = np.array([9, 0], i32)
    if layout == "ring":
        cache = ad.init_cache(2, 32)
        cache, pre = jax.jit(ad.prefill_fn())(
            P, cache, prompt, n, np.array([0], i32), np.array([True]))
        cache, dec = jax.jit(ad.decode_fn())(
            P, cache, nxt, np.array([5, 0], i32),
            np.array([True, False]))
        return np.asarray(pre), np.asarray(dec)
    pool = ad.init_pool(17, 4)
    tables = np.zeros((2, 8), i32)
    tables[0] = np.arange(1, 9)
    pool, pre = jax.jit(ad.paged_prefill_fn())(
        P, pool, tables[:1], prompt, np.array([0], i32), n,
        np.array([True]))
    pool, dec = jax.jit(ad.paged_decode_fn())(
        P, pool, tables, nxt[:, None], np.array([5, 0], i32),
        np.array([1, 0], i32))
    return np.asarray(pre), np.asarray(dec)


def _served(engine, prompts, n_new=6):
    futs = [engine.submit(p, max_new_tokens=n_new, temperature=0.0)
            for p in prompts]
    engine.run_until_idle()
    return [f.result(timeout=5)["tokens"] for f in futs]


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("policy", POLICIES)
def test_the_tree_serves_what_the_old_tree_served(policy, layout):
    m = tiny_lm(seed=3)
    pol = None if policy is None else mp.resolve(policy)
    kw = dict(GEOMETRY, **(PAGED if layout == "paged" else {}))
    prompts = [np.random.RandomState(i).randint(0, 19, (n,))
               for i, n in enumerate((6, 3, 8))]

    with old_walk():
        old = _OldTreeAdapter(m, policy=pol)
        want_pre, want_dec = _logits(old, layout)
        want = _served(ServingEngine(
            old, policy=pol, registry=obs_metrics.MetricsRegistry(),
            **kw), prompts)

    ad = m.decode_adapter(policy=pol)
    got_pre, got_dec = _logits(ad, layout)
    engine = m.compile_serving(policy=pol,
                               registry=obs_metrics.MetricsRegistry(),
                               **kw)
    assert _served(engine, prompts) == want
    assert engine.compiled_step_info()["n_traces"] == 1
    if policy is None:
        np.testing.assert_array_equal(got_pre, want_pre)
        np.testing.assert_array_equal(got_dec, want_dec)
    else:
        np.testing.assert_allclose(got_pre, want_pre, rtol=0, atol=1e-6)
        np.testing.assert_allclose(got_dec, want_dec, rtol=0, atol=1e-6)

    # what the programs find: the products' operands in the compute
    # dtype where the policy leaves them to a cast, as they were where
    # it quantizes; LayerNorm's leaves, the tables and the head float32
    blocks = ad.params()["blocks"]
    cdt = ad._compute_dtype()
    assert len(blocks["wq"]) == 2 and blocks["bq"].shape == (2, 16)
    if policy == "int8_weight_only":
        assert blocks["wq"][1]["q"].dtype == jnp.int8
        assert blocks["wq"][1]["q"].shape == (16, 16)
        assert blocks["wq"][1]["s"].shape == (1, 16)
        assert blocks["bq"].dtype == jnp.float32
    else:
        want_dt = jnp.float32 if policy == "fp8_serving" else cdt
        assert blocks["wq"][1].dtype == blocks["b_up"].dtype == want_dt
    assert blocks["ln1_s"].dtype == jnp.float32
    assert ad.params()["head_w"].dtype == jnp.float32


@pytest.mark.parametrize("policy", POLICIES)
def test_a_layer_adds_its_matrices_and_no_other_leaf(policy):
    pol = None if policy is None else mp.resolve(policy)
    # an int8 payload is two leaves, {"q", "s"}
    a_matrix = 2 if policy == "int8_weight_only" else 1
    for layers in (2, 6):
        P = tiny_lm(layers=layers).decode_adapter(policy=pol).params()
        assert P["blocks"]["b_up"].shape == (layers, 64)
        assert len(P["blocks"]["w_up"]) == layers
        # ten vector roles, the six leaves outside the blocks, and six
        # matrices a layer (of a block's 16 leaves)
        assert len(jax.tree_util.tree_leaves(P)) == \
            10 + 6 + 6 * a_matrix * layers


@pytest.mark.parametrize("layout", LAYOUTS)
def test_arg_buffers_gauge_reads_what_tree_leaves_counts(layout):
    reg = obs_metrics.MetricsRegistry()
    kw = dict(GEOMETRY, **(PAGED if layout == "paged" else {}))
    eng = tiny_lm(layers=6).compile_serving(
        policy=mp.resolve("bf16_mixed"), registry=reg, **kw)
    held = len(jax.tree_util.tree_leaves((eng._P, eng._cache)))
    assert held == 16 + 6 * 6 + 2 * 6
    gauge = reg.get("serve_program_arg_buffers")
    assert gauge.value(program="prefill") == \
        held + len(eng._layout.prefill_names)
    assert gauge.value(program="decode") == \
        held + len(eng._layout.decode_names)


def test_pin_returns_one_tree_until_a_train_step_rebinds():
    m = tiny_lm()
    ad = m.decode_adapter(policy=mp.resolve("bf16_mixed"))
    P1 = ad.params()
    assert ad.params() is P1
    assert P1["blocks"]["wq"][0].dtype == jnp.bfloat16
    # the cast tree is the only one the pin keeps: no float32 copy of
    # the blocks stays alive beside it
    assert list(m._decode_params_pin[1]) == [jnp.dtype(jnp.bfloat16)]
    # generate() asks for the uncast tree: a second entry, same pin
    Pg = transformer._lm_decode_params(m)
    assert Pg is not P1 and Pg["blocks"]["wq"][0].dtype == jnp.float32
    assert transformer._lm_decode_params(m) is Pg and ad.params() is P1
    # a float32 policy has nothing to cast and reads generate()'s tree
    assert m.decode_adapter(policy=None).params() is Pg

    ids = np.random.RandomState(0).randint(0, 19, (2, 8))
    tx = Tensor(data=ids.astype(np.float32), device=DEV,
                requires_grad=False)
    m.set_optimizer(opt.SGD(lr=0.1))
    m.compile([tx], is_train=True, use_graph=True)
    m.train()
    m(tx, tx)
    P2 = ad.params()
    assert P2 is not P1
    assert not np.array_equal(np.asarray(P2["head_w"]),
                              np.asarray(P1["head_w"]))
    # the old tree's leaves are its own: the step's donation of the
    # model's arrays has not taken them
    assert np.isfinite(np.asarray(P1["tok"])).all()
