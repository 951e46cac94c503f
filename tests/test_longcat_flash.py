"""The shortcut-connected sparse-expert LM with latent attention
(models/longcat_flash.py), the routing rule as data and the experts without
weights (parallel/moe.py), and latent ring levels in the serving engine
(serving/kv_cache.py, ops/ring_decode.py), against the plain float32
reference the benchmark keeps (benchmarks/lib/references/longcat_flash.py),
at a small size on the CPU: hidden 64, 4 heads of 16 + 8 / 16, ranks 24 /
32, dense FFN 96, 8 routed experts of 48 and 4 identity experts, top-3,
2 double layers, vocabulary 256.

Tolerances: everything here runs in float32, so program and reference
differ by summation order alone — 2e-4 absolute on logits of magnitude ~10
(a float32 sum of a few hundred terms; the absorbed form of the attention
sums in another order than the expanded one), and exact agreement of served
tokens with the reference's best (gap 0) wherever the best leads by more
than that.
"""

import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(os.path.dirname(HERE), "benchmarks"),):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import weights_staged                          # noqa: E402
from lib.references import longcat_flash as ref         # noqa: E402
from singa_tpu import device, tensor                    # noqa: E402
from singa_tpu.models import cohere_moe as cm           # noqa: E402
from singa_tpu.models import longcat_flash as lf        # noqa: E402
from singa_tpu.ops import attention_mod                 # noqa: E402
from singa_tpu.parallel import moe                      # noqa: E402
from singa_tpu.parallel.communicator import collective_context  # noqa: E402
from singa_tpu.serving import kv_cache                  # noqa: E402

DEV = device.create_cpu_device()
ATOL = 2e-4
PREFIX = "LongCatFlashLM"


def toy_cfg(**over):
    cfg = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, ffn_hidden_size=96, expert_ffn_hidden_size=48,
               n_routed_experts=8, router_width=12, zero_expert_num=4,
               moe_topk=3, routed_scaling_factor=6.0, experts_held_from=0,
               rope_theta=1e7, rms_norm_eps=1e-5, mla_scale_q_lora=True,
               mla_scale_kv_lora=True, vocab_size=256, num_layers=2,
               precision="float32",
               init={"matrix_std": 0.1, "router_std": 0.3,
                     "embedding_std": 1.0, "router_bias_std": 0.02})
    cfg.update(over)
    return cfg


KWARGS = {"hidden_size": "hidden_size", "num_layers": "num_layers",
          "num_heads": "num_attention_heads", "q_lora_rank": "q_lora_rank",
          "kv_lora_rank": "kv_lora_rank",
          "qk_nope_head_dim": "qk_nope_head_dim",
          "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
          "ffn_hidden_size": "ffn_hidden_size",
          "expert_ffn_hidden_size": "expert_ffn_hidden_size",
          "num_experts": "n_routed_experts", "router_width": "router_width",
          "zero_expert_num": "zero_expert_num", "top_k": "moe_topk",
          "routed_scaling_factor": "routed_scaling_factor",
          "experts_held_from": "experts_held_from",
          "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps"}


def build(cfg, seed=7, policy=None, S=24):
    """The model compiled as the benchmark compiles it, holding the
    reference's weights for `seed`. Returns (model, {name: array})."""
    m = lf.LongCatFlashLM(cfg["vocab_size"],
                          **{k: cfg[v] for k, v in KWARGS.items()})
    ids = tensor.Tensor(data=jnp.zeros((1, S), jnp.float32), device=DEV,
                        requires_grad=False)
    m.compile([ids], is_train=False, use_graph=True, policy=policy)
    m.eval()
    states = m.get_states()
    params = weights_staged.make(ref.param_specs(cfg), seed, jnp.float32)
    for name, arr in params.items():
        t = states[f"{PREFIX}.{name}"]
        assert tuple(t.shape) == tuple(arr.shape), name
        t.data = arr.astype(t.data.dtype)
    return m, params


@pytest.fixture(scope="module")
def toy():
    cfg = toy_cfg()
    m, params = build(cfg)
    return cfg, m, params


@pytest.fixture
def interpreted():
    prev = attention_mod.FORCE_PALLAS_INTERPRET
    attention_mod.FORCE_PALLAS_INTERPRET = True
    yield
    attention_mod.FORCE_PALLAS_INTERPRET = prev


def _engine(m, **kw):
    from singa_tpu.observability.metrics import MetricsRegistry
    args = dict(slots=4, max_len=40, prefill_len=16, prefill_batch=2,
                registry=MetricsRegistry())
    args.update(kw)
    return m.compile_serving(**args)


def _tensor(tok):
    return tensor.Tensor(data=jnp.asarray(tok, jnp.float32), device=DEV,
                         requires_grad=False)


# -- the model against the reference ------------------------------------------

def test_state_names_are_the_references_leaves(toy):
    cfg, m, params = toy
    assert set(m.get_states()) == {f"{PREFIX}.{n}" for n in params}


@pytest.mark.parametrize("S", [5, 24])
def test_eval_forward_matches_the_reference(toy, S):
    cfg, m, params = toy
    tok = np.random.default_rng(S).integers(0, 256, (2, S))
    want = np.asarray(ref.forward(params, jnp.asarray(tok), cfg))
    np.testing.assert_allclose(np.asarray(m(_tensor(tok)).data), want,
                               atol=ATOL)


def test_training_is_refused_with_the_reason(toy):
    with pytest.raises(NotImplementedError, match="inference-only"):
        toy[1].train_one_batch(None, None)


@pytest.mark.parametrize("fault", ref.FAULTS[1:])
def test_each_planted_fault_moves_the_references_logits(toy, fault):
    """What the rehearsal's comparison has to catch is really another
    function: each fault moves the logits by far more than the tolerance
    (the identity experts get a third of the picks, the bias is a fifth of
    a probability, the rotary key turns with the position)."""
    cfg, _, params = toy
    tok = jnp.asarray(np.random.default_rng(1).integers(0, 256, (1, 24)))
    want = np.asarray(ref.forward(params, tok, cfg))
    got = np.asarray(ref.forward(params, tok, cfg, fault=fault))
    assert np.abs(got - want).max() > 100 * ATOL


# -- serving: prefill then decode through the engine ---------------------------

def test_the_cache_is_latent_levels_two_a_layer(toy):
    cfg, m, _ = toy
    eng = _engine(m)
    assert all(isinstance(lv, kv_cache.LatentLevel) for lv in eng._cache)
    # a row of 32 + 8 numbers is kept once, padded to one lane tile
    assert [tuple(lv["k"].shape) for lv in eng._cache] == [(4, 1, 40, 128)] * 4
    assert all((lv.width, lv.value_width) == (40, 32) for lv in eng._cache)
    reg = eng._reg
    assert reg.get("serve_kv_bytes").value(kind="latent") == \
        4 * 4 * 40 * 128 * 4
    g = eng._handoff_geometry()
    assert g["latent"] == [[i, 40, 32] for i in range(4)]
    assert (g["heads"], g["head_dim"], g["n_layers"]) == (1, 128, 4)
    assert m.decode_adapter().cache_kinds() == ["latent"] * 4


def test_served_tokens_are_the_references_best(toy):
    """Padded and unpadded prompts (16 of 16, and 5, 12, 9), five requests
    on four slots so that a slot is reused by a second request: each
    served token is the reference's best in its full forward."""
    cfg, m, params = toy
    eng = _engine(m)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 256, n) for n in (16, 5, 12, 9, 16)]
    futs = [eng.submit(p, max_new_tokens=20, temperature=0.0)
            for p in prompts]
    eng.run_until_idle()
    assert eng.compiled_step_info()["n_traces"] == 1
    for p, f in zip(prompts, futs):
        tokens = f.result(timeout=0)["tokens"]
        seq = np.concatenate([p, tokens])[None]
        lg = np.asarray(ref.forward(params, jnp.asarray(seq), cfg))[0]
        at = np.arange(len(p) - 1, seq.shape[1] - 1)
        gaps = lg[at].max(-1) - lg[at, seq[0, len(p):]]
        assert gaps.max() <= ATOL, gaps.max()


@pytest.mark.parametrize("prefill_rows,n,kernel",
                         [(512, 14, False), (4, 14, False), (4, 10, False),
                          (512, 16, True)])
def test_prefill_and_decode_logits_match_the_full_forward(
        toy, prefill_rows, n, kernel, monkeypatch):
    """The adapter's two programs, driven by hand: logits after a prefill
    (expanded attention) and after each decoded token (absorbed attention
    over the latent rows) against the reference's forward of the whole
    sequence. With blocks of 4 rows the prefill's loops run over the blocks
    that hold a token; a second slot stays dead; the last case sends the
    decode through the kernel's latent form (interpreted) on rings of 128
    rows."""
    cfg, m, params = toy
    ad = m.decode_adapter()
    monkeypatch.setattr(cm, "PREFILL_ROWS", prefill_rows)
    monkeypatch.setattr(attention_mod, "FORCE_PALLAS_INTERPRET", kernel)
    Pm = ad.params()
    cache = ad.init_cache(2, 128 if kernel else 40)
    assert (kv_cache.ring_block(cache[0]) is not None) == kernel
    prefill, decode = jax.jit(ad.prefill_fn()), jax.jit(ad.decode_fn())
    rng = np.random.default_rng(5)
    seq = rng.integers(1, 256, 26)
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :n] = seq[:n]
    cache, (logits, stats) = prefill(
        Pm, cache, tokens, np.asarray([n], np.int32),
        np.asarray([1], np.int32), np.asarray([True]))
    want = np.asarray(ref.forward(params, jnp.asarray(seq[None]), cfg))[0]
    np.testing.assert_allclose(np.asarray(logits)[0], want[n - 1], atol=ATOL)
    # every real token picked 3 of the 12 columns in each of 2 layers, all
    # routed experts held: here + absent + zero = rows x top-k
    here, absent, zero, touched = (int(v) for v in stats)
    assert (here + zero, absent) == (n * 3 * 2, 0) and zero > 0
    for t in range(n, 26):
        cache, (logits, stats) = decode(
            Pm, cache, np.asarray([0, seq[t]], np.int32),
            np.asarray([0, t], np.int32), np.asarray([False, True]))
        np.testing.assert_allclose(np.asarray(logits)[1], want[t],
                                   atol=ATOL)
        assert int(stats[0] + stats[1] + stats[2]) == 3 * 2


def test_engine_counts_the_three_kinds_of_pairs_and_latent_rows(toy):
    cfg, m, _ = toy
    eng = _engine(m)
    f = eng.submit(np.arange(1, 11), max_new_tokens=6, temperature=0.0)
    eng.run_until_idle()
    f.result(timeout=0)
    reg = eng._reg
    pairs = {h: reg.get("moe_pairs_total").value(held=h)
             for h in ("here", "absent", "zero")}
    # 10 prompt tokens + 5 decoded inputs, top-3, 2 layers
    assert sum(pairs.values()) == 15 * 3 * 2
    assert pairs["absent"] == 0 and pairs["zero"] > 0
    # decode ticks at positions 10..14: each of 4 latent levels holds
    # position + 1 rows
    assert reg.get("serve_kv_rows_attended_total").value() == \
        sum(4 * (pos + 1) for pos in range(10, 15))
    from singa_tpu.observability import spans
    recs = spans.recorder().records()
    decode = [r for r in recs if r.get("name") == "serve.decode"
              and "pairs_zero" in r][-5:]
    prefill = [r for r in recs if r.get("name") == "serve.prefill"
               and "pairs_zero" in r][-1]
    assert sum(r["pairs_zero"] for r in decode) + prefill["pairs_zero"] \
        == pairs["zero"]
    assert [r["kv_rows"] for r in decode] == \
        [4 * (pos + 1) for pos in range(10, 15)]
    # what causal attention's work goes with
    assert prefill["tokens_sq"] == 100
    readback = reg.get("serve_readback_total")
    assert readback.value(program="decode", what="logits") == 0


def test_a_share_of_the_model_matches_the_reference_given_the_same_share():
    """The configuration the benchmark runs in small: 2 of 8 routed experts
    held (from 4), the router 12 wide with its 4 identity experts; picks
    that go to absent experts add nothing on either side, picks of identity
    experts add on both."""
    cfg = toy_cfg(n_routed_experts=2, experts_held_from=4)
    m, params = build(cfg, seed=11)
    tok = np.random.default_rng(11).integers(0, 256, (2, 20))
    want = np.asarray(ref.forward(params, jnp.asarray(tok), cfg))
    np.testing.assert_allclose(np.asarray(m(_tensor(tok)).data), want,
                               atol=ATOL)
    eng = _engine(m)
    f = eng.submit(tok[0, :12], max_new_tokens=8, temperature=0.0)
    eng.run_until_idle()
    f.result(timeout=0)
    pairs = {h: eng._reg.get("moe_pairs_total").value(held=h)
             for h in ("here", "absent", "zero")}
    assert sum(pairs.values()) == (12 + 7) * 3 * 2
    assert all(v > 0 for v in pairs.values())


# -- the two forms of the attention --------------------------------------------

def test_absorbed_attention_equals_expanded_on_the_same_rows(toy):
    """The last token of a sequence, attended in the absorbed form over the
    latent rows a prefill of the others left in a level, equals the same
    token's row of the expanded form over the whole sequence."""
    cfg, m, _ = toy
    c = m.cfg
    p = m.decode_adapter().params()["layers"][1]
    rng = np.random.default_rng(2)
    S = 19
    h = jnp.asarray(rng.normal(size=(2, S, 64)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (2, S))
    q_n, q_r, lat, k_r = lf.mla_project(c, p, 1, h, pos)
    want = lf.mla_expanded(c, p, 1, q_n, q_r, lat, k_r)[:, -1]
    level = kv_cache.init_latent(2, 24, c.latent_width, c.kv_lora_rank)
    level = kv_cache.write_prompts(
        level, jnp.arange(2), jnp.concatenate([lat, k_r], -1)[:, :-1], None,
        jnp.full((2,), S - 1), jnp.ones((2,), bool))

    def attend(q, row):
        return kv_cache.decode_token(level, q, row, None, pos[:, -1],
                                     jnp.ones((2,), bool), c.scale)

    got, new = lf.mla_absorbed(c, p, 1, q_n[:, -1:], q_r[:, -1:],
                               lat[:, -1:], k_r[:, -1:], attend)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                               atol=2e-5)
    # the new row went where the token's position says, as [c; k_r]
    np.testing.assert_array_equal(
        np.asarray(new["k"][:, 0, S - 1, :40]),
        np.asarray(jnp.concatenate([lat[:, -1], k_r[:, -1]], -1)))


def test_attention_takes_values_narrower_than_keys():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 32, 4, 24)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 32, 2, 24)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 32, 2, 16)), jnp.float32)
    one = cm.masked_attention(q, k, v, 0.3)
    assert one.shape == (2, 32, 4, 16)
    wide = cm.masked_attention(q, k, jnp.pad(v, [(0, 0)] * 3 + [(0, 8)]), 0.3)
    np.testing.assert_allclose(np.asarray(one), np.asarray(wide[..., :16]),
                               atol=1e-6)
    for n_blocks in (None, jnp.int32(3)):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(cm, "PREFILL_ROWS", 8)
            blocked = cm.masked_attention(q, k, v, 0.3, n_blocks=n_blocks)
        rows = 32 if n_blocks is None else 24
        np.testing.assert_allclose(np.asarray(blocked[:, :rows]),
                                   np.asarray(one[:, :rows]), atol=1e-5)
        assert not np.asarray(blocked[:, rows:]).any()


# -- the latent level: XLA twins and the kernel --------------------------------

def _latent_and_token(W, H, L, width, vw, dtype, filled, seed=0):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    level = kv_cache.write_prompts(
        kv_cache.init_latent(W, L, width, vw, dtype), jnp.arange(W),
        draw(W, L, width), None, jnp.full((W,), filled),
        jnp.ones((W,), bool))
    return level, draw(W, H, 1, width), draw(W, width)


LATENT_CASES = {
    # W, H, L, width, value width, dtype, positions, active, tolerance
    "short-and-on-a-block-edge": (3, 4, 256, 48, 32, jnp.float32,
                                  [5, 127, 128], [1, 1, 1], 1e-5),
    "wrapped": (3, 4, 256, 48, 32, jnp.float32, [255, 256, 700],
                [1, 1, 1], 1e-5),
    "dead-slots": (4, 8, 1024, 192, 128, jnp.bfloat16, [9, 300, 0, 1023],
                   [1, 0, 0, 1], 2e-2),
    "the-cells-row": (2, 64, 1024, 576, 512, jnp.bfloat16, [700, 2000],
                      [1, 1], 2e-2),
}


@pytest.mark.parametrize("case", LATENT_CASES)
def test_latent_decode_kernel_equals_the_xla_twins(interpreted, case):
    """`decode_token` on a latent level through the kernel's latent form
    (interpreted) against `write_token` + `attend`: the attention within
    rounding of the level's dtype, the written row bit for bit, every
    other row and every dead slot untouched."""
    W, H, L, width, vw, dtype, pos, active, tol = LATENT_CASES[case]
    level, q, row = _latent_and_token(W, H, L, width, vw, dtype, L)
    pos = jnp.asarray(pos, jnp.int32)
    active = np.asarray(active, bool)
    assert kv_cache.ring_block(level) is not None
    want_level = kv_cache.write_token(level, row, None, pos)
    want = kv_cache.attend(q, want_level, pos, 0.2)
    got, got_level = kv_cache.decode_token(level, q, row, None, pos,
                                           jnp.asarray(active), 0.2)
    assert got.shape == (W, H, 1, vw) and got.dtype == q.dtype
    assert isinstance(got_level, kv_cache.LatentLevel)
    assert (got_level.width, got_level.value_width) == (width, vw)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got[active], want[active], atol=tol, rtol=0)
    assert not got[~active].any()
    f32 = lambda lv: np.asarray(lv["k"], np.float32)  # noqa: E731
    assert np.array_equal(f32(got_level)[active], f32(want_level)[active])
    assert np.array_equal(f32(got_level)[~active], f32(level)[~active])


def test_what_the_kernel_cannot_take_keeps_the_xla_path(interpreted,
                                                       monkeypatch):
    """A latent level goes to the kernel only where a block divides its
    ring, its dtype is a float the kernel takes and no sharded jit is
    being traced; off the TPU and outside the hook, never."""
    level = kv_cache.init_latent(2, 256, 48, 32, jnp.float32)
    assert kv_cache.ring_block(level) == 128
    assert kv_cache.ring_block(
        kv_cache.init_latent(2, 40, 48, 32, jnp.float32)) is None
    assert kv_cache.ring_block(
        kv_cache.init_latent(2, 256, 48, 32, jnp.float16)) is None
    with kv_cache.xla_rings():
        assert kv_cache.ring_block(level) is None
    monkeypatch.setattr(attention_mod, "FORCE_PALLAS_INTERPRET", False)
    assert kv_cache.ring_block(level) is None


def test_latent_twins_equal_plain_attention_over_the_rows():
    """`write_token` + `attend` on a latent level are attention with the
    rows as keys and their first columns as values."""
    level, q, row = _latent_and_token(2, 3, 16, 12, 8, jnp.float32, 9)
    pos = jnp.asarray([9, 4], jnp.int32)
    level = kv_cache.write_token(level, row, None, pos)
    got = np.asarray(kv_cache.attend(q, level, pos, 0.3))
    rows = np.asarray(level["k"])[:, 0, :, :12]
    assert not np.asarray(level["k"])[..., 12:].any()
    for w, n in enumerate((10, 5)):
        s = np.asarray(q)[w, :, 0] @ rows[w, :n].T * 0.3
        a = np.exp(s - s.max(-1, keepdims=True))
        want = (a / a.sum(-1, keepdims=True)) @ rows[w, :n, :8]
        np.testing.assert_allclose(got[w, :, 0], want, atol=1e-5)
    np.testing.assert_array_equal(rows[0, 9], np.asarray(row)[0])


def test_a_latent_level_is_a_pytree_that_keeps_its_widths():
    level = kv_cache.init_latent(2, 8, 40, 32, jnp.bfloat16)
    leaves, treedef = jax.tree_util.tree_flatten(level)
    assert [a.shape for a in leaves] == [(2, 1, 8, 128)]
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(back, kv_cache.LatentLevel)
    assert (back.width, back.value_width) == (40, 32)
    out = jax.jit(lambda lv: jax.tree_util.tree_map(lambda a: a + 1, lv))(
        level)
    assert isinstance(out, kv_cache.LatentLevel) and out.value_width == 32
    other = kv_cache.init_latent(2, 8, 40, 24, jnp.bfloat16)
    assert jax.tree_util.tree_structure(other) != treedef


def test_engine_serves_the_same_tokens_through_the_kernel(toy, interpreted):
    cfg, m, params = toy
    eng = _engine(m, max_len=128, slots=2)
    assert kv_cache.ring_block(eng._cache[0]) is not None
    prompt = np.random.default_rng(4).integers(1, 256, 11)
    f = eng.submit(prompt, max_new_tokens=8, temperature=0.0)
    eng.run_until_idle()
    tokens = f.result(timeout=0)["tokens"]
    seq = np.concatenate([prompt, tokens])[None]
    lg = np.asarray(ref.forward(params, jnp.asarray(seq), cfg))[0]
    at = np.arange(len(prompt) - 1, seq.shape[1] - 1)
    assert (lg[at].max(-1) - lg[at, seq[0, len(prompt):]]).max() <= ATOL
    assert eng.compiled_step_info()["n_traces"] == 1


# -- snapshots and declines ----------------------------------------------------

def test_snapshot_of_latent_levels_continues_bitwise(toy):
    cfg, m, _ = toy
    a, b, whole = _engine(m), _engine(m), _engine(m)
    prompt = np.arange(3, 15)
    fw = whole.submit(prompt, max_new_tokens=18, temperature=0.0)
    whole.run_until_idle()
    a.submit(prompt, max_new_tokens=18, temperature=0.0)
    for _ in range(9):
        a.step()
    snap = a.snapshot_slot(0)
    fb = b.inject_snapshot(snap["meta"], snap["frame"])
    b.run_until_idle()
    assert fb.result(timeout=0)["tokens"] == fw.result(timeout=0)["tokens"]
    assert all(isinstance(lv, kv_cache.LatentLevel) for lv in b._cache)


def test_a_snapshot_of_another_geometry_is_refused(toy):
    """Another ring length, and — the shapes being equal — another split of
    the row into values and the rest."""
    cfg, m, _ = toy
    from singa_tpu.serving.engine import HandoffRefused
    a = _engine(m)
    a.submit(np.arange(1, 9), max_new_tokens=8, temperature=0.0)
    a.step()
    snap = a.snapshot_slot(0)
    with pytest.raises(HandoffRefused):
        _engine(m, max_len=48).inject_snapshot(snap["meta"], snap["frame"])
    other = _engine(m)
    other._layout._latent = [[i, 40, 24] for i in range(4)]
    with pytest.raises(HandoffRefused):
        other.inject_snapshot(snap["meta"], snap["frame"])


def test_engine_declines_what_a_latent_level_cannot(toy):
    cfg, m, _ = toy
    from singa_tpu.parallel.gspmd import ShardingDecline
    with pytest.raises(ShardingDecline):
        _engine(m, model_shards=2)
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        eng = _engine(m, kv_layout="paged", speculative_k=3)
    assert eng.kv_layout == "ring" and eng.speculative_k == 0
    assert sum("declined" in str(w.message) for w in seen) == 2
    info = eng.compiled_step_info()
    assert info["kv_layout_declined"] == "latent_level"
    assert info["speculative_declined"] == "latent_level"


def test_params_are_the_models_own_arrays_in_bf16():
    cfg = toy_cfg(precision="bfloat16")
    m, _ = build(cfg, policy="bfloat16")
    Pm = m.decode_adapter().params()
    assert Pm["head"] is m.head.data
    assert Pm["layers"][1]["wkv_b_0"] is m.layers[1].wkv_b_0.data
    leaves = jax.tree_util.tree_leaves(Pm)
    assert len(leaves) == 3 + 29 * 2
    assert all(a.dtype == jnp.bfloat16 for a in leaves)
    eng = _engine(m, policy="bfloat16")
    assert all(lv["k"].dtype == jnp.bfloat16 for lv in eng._cache)
    with pytest.raises(ValueError, match="by reference"):
        _engine(build(toy_cfg())[0], policy="bfloat16")


# -- the routing rule ----------------------------------------------------------

def _router(rng, T=40, D=16, E=12):
    h = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    return h, jnp.asarray(rng.normal(size=(D, E)) * 0.5, jnp.float32)


def test_sigmoid_rule_is_route_sigmoid_topk_bit_for_bit():
    h, router = _router(np.random.default_rng(0))
    idx, w = moe.route_topk(h, router, 4, moe.Route("sigmoid", True, 1.0))
    s = jax.nn.sigmoid(jnp.dot(h, router,
                               precision=jax.lax.Precision.HIGHEST))
    top, want_idx = jax.lax.top_k(s, 4)
    want = top / jnp.sum(top, -1, keepdims=True)
    for got in ((idx, w), moe.route_sigmoid_topk(h, router, 4)):
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want_idx))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want))


def test_softmax_rule_keeps_the_probabilities_and_applies_the_factor_once():
    h, router = _router(np.random.default_rng(1))
    prob = jax.nn.softmax(jnp.dot(h, router,
                                  precision=jax.lax.Precision.HIGHEST), -1)
    idx, w = moe.route_topk(h, router, 3, moe.Route("softmax", False, 6.0))
    top, want_idx = jax.lax.top_k(prob, 3)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(w), 6.0 * np.asarray(top),
                               rtol=1e-6)
    # not renormalised: the picks' weights sum to 6 x their probability
    assert np.all(np.asarray(jnp.sum(w, -1)) < 6.0)
    _, renorm = moe.route_topk(h, router, 3, moe.Route("softmax", True, 6.0))
    np.testing.assert_allclose(np.asarray(jnp.sum(renorm, -1)), 6.0,
                               rtol=1e-6)


def test_the_bias_moves_the_choice_and_not_the_weights():
    h, router = _router(np.random.default_rng(2))
    rule = moe.Route("softmax", False, 1.0)
    prob = np.asarray(jax.nn.softmax(jnp.dot(
        h, router, precision=jax.lax.Precision.HIGHEST), -1))
    bias = jnp.zeros((12,)).at[7].set(1.0)      # column 7 always chosen
    idx0, _ = moe.route_topk(h, router, 3, rule)
    idx, w = moe.route_topk(h, router, 3, rule, bias)
    assert np.all(np.any(np.asarray(idx) == 7, -1))
    assert not np.all(np.any(np.asarray(idx0) == 7, -1))
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(prob, np.asarray(idx), -1),
        rtol=1e-6)
    with pytest.raises(ValueError):
        moe.Route("tanh")


def _zero_params(rng, D=16, F=24, E=8, Z=4, G=8):
    n = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
    return {"router": n(D, E + Z), "router_bias": n(E + Z) * 0.1,
            "w_gate": n(G, D, F), "w_up": n(G, D, F), "w_down": n(G, F, D)}


def _plain_moe(p, h, held_from=0, fault=None):
    cfg = {"moe_topk": 3, "experts_held_from": held_from, "router_width": 12,
           "zero_expert_num": 4, "routed_scaling_factor": 6.0}
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref._moe(p, h, cfg, None, fault)[0])


RULE = moe.Route("softmax", False, 6.0)


@pytest.mark.parametrize("T,dense_rows", [(12, 128), (40, 8), (300, 8)])
def test_identity_experts_on_both_paths_match_plain(T, dense_rows,
                                                    monkeypatch):
    """Few rows and many (the dense and the sorted path), a share of 3 of 8
    routed experts and 4 identity experts behind them: the layer against
    the reference's plain form, with no shared expert leaves at all."""
    monkeypatch.setattr(moe, "DENSE_ROWS", dense_rows)
    monkeypatch.setattr(moe, "SORTED_TILE", 8 if T == 40 else 256)
    rng = np.random.default_rng(T)
    p = _zero_params(rng, G=3)
    h = jnp.asarray(rng.normal(size=(T, 16)), jnp.float32)
    rows = jnp.arange(T) < T - 2
    y, stats = moe.expert_share_ffn(p, h, top_k=3, held_from=2, rows=rows,
                                    route=RULE, n_zero=4)
    np.testing.assert_allclose(np.asarray(y)[:T - 2],
                               _plain_moe(p, h, 2)[:T - 2], atol=1e-4)
    here, absent, zero = (int(stats[k]) for k in
                          ("pairs_here", "pairs_absent", "pairs_zero"))
    assert here + absent + zero == 3 * (T - 2) and min(here, absent, zero) > 0
    # without the term the layer is another function
    assert np.abs(_plain_moe(p, h, 2, "zero_experts_out")
                  - _plain_moe(p, h, 2)).max() > 0.1


def test_on_the_expert_axis_the_identity_term_is_added_once():
    """Four peers, two routed experts each, the same rows on all: the layer
    with its exchange gives what one chip holding all eight gives — the
    routed parts summed over the axis, the identity experts' term outside
    the sum."""
    rng = np.random.default_rng(6)
    p = _zero_params(rng)
    h = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices("cpu")[:4]), ("expert",))
    specs = {k: P("expert") if k.startswith("w_") else P() for k in p}

    def body(p, h):
        with collective_context("expert"):
            return moe.expert_share_ffn(p, h, top_k=3, axis_name="expert",
                                        route=RULE, n_zero=4)[0]

    y = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(specs, P()),
                              out_specs=P()))(p, h)
    np.testing.assert_allclose(np.asarray(y), _plain_moe(p, h), atol=1e-4)


# -- the shares add up ---------------------------------------------------------

@pytest.mark.parametrize("layer", [0, 1])
def test_the_four_shares_add_up_to_the_uncut_layer(toy, layer):
    """Four shares of 2 routed experts each see the same rows. What every
    share computes alike — the two attention blocks, the two dense FFNs and
    the identity experts' term — is counted once, the routed parts are
    added up: that equals the reference's uncut layer."""
    cfg, m, params = toy
    rng = np.random.default_rng(layer)
    x = jnp.asarray(rng.normal(size=(2, 20, 64)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(20), (2, 20))
    whole = {k[len(f"layers.{layer}."):]: v for k, v in params.items()
             if k.startswith(f"layers.{layer}.")}

    def run(held_from, count):
        c = lf.Config(**{k: cfg[v] for k, v in KWARGS.items()
                         if k not in ("num_experts", "experts_held_from")},
                      num_experts=count, experts_held_from=held_from)
        p = dict(whole)
        for k in ("w_gate", "w_up", "w_down"):
            p[k] = whole[k][held_from:held_from + count]

        def attend(i, *projected):
            return lf.mla_expanded(c, p, i, *projected), None

        return lf.layer_apply(c, p, x, pos, [attend, attend])

    # a share that holds no routed expert: everything the shares have alike
    alike, _, st = run(0, 0)
    assert int(st["pairs_here"]) == 0 and int(st["pairs_zero"]) > 0
    routed = jnp.zeros_like(x)
    pairs = 0
    for s in range(4):
        y, _, st = run(2 * s, 2)
        routed = routed + (y - alike)
        pairs += int(st["pairs_here"])
        assert int(st["pairs_here"] + st["pairs_absent"]
                   + st["pairs_zero"]) == 40 * 3
    assert pairs + int(st["pairs_zero"]) == 40 * 3
    want, _ = ref.layer_forward(params, x, layer, cfg)
    np.testing.assert_allclose(np.asarray(alike + routed), np.asarray(want),
                               atol=ATOL)


def test_vocabulary_shares_are_slices_of_the_uncut_logits(toy):
    cfg, m, params = toy
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, 6, 64)),
                    jnp.float32)
    want = np.asarray(ref.logits_of(params, x, cfg))
    for s in range(4):
        rows = slice(64 * s, 64 * (s + 1))
        got = lf.head_logits(m.cfg, {"head": params["head"][rows],
                                     "ln_f": params["ln_f"]}, x)
        np.testing.assert_allclose(np.asarray(got), want[..., rows],
                                   atol=ATOL)


def test_the_named_scopes_are_in_the_decode_program(toy):
    cfg, m, _ = toy
    ad = m.decode_adapter()
    hlo = jax.jit(ad.decode_fn()).lower(
        ad.params(), ad.init_cache(2, 40), np.zeros((2,), np.int32),
        np.zeros((2,), np.int32), np.ones((2,), bool)).as_text(
            debug_info=True)
    for scope in ("mla_project", "mla_absorb", "latent_attention",
                  "dense_ffn", "moe_route", "moe_experts", "moe_zero"):
        assert scope in hlo, scope
