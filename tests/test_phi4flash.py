"""The decoder-hybrid-decoder LM (models/phi4flash.py) and one cache of
three kinds of level in the serving engine, against the plain float32
reference the benchmark keeps (benchmarks/lib/references/phi4flash.py), at
a small size on the CPU: hidden 64, 8 query / 4 KV heads of 8 (4
differential heads on 2 differential KV heads), 8 layers — Mamba, window,
Mamba, window, Mamba (the memory), full, GMU, cross — window 8, state 4,
vocabulary 256.

Tolerances: everything here runs in float32, so program and reference
differ by summation order alone (the program's grouped form of
differential attention, its chunked scan and its last-token prefill against
the reference's equations, token-by-token scan and full forward): 2e-4
absolute on logits of magnitude ~1, and served tokens within that of the
reference's best.
"""

import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(os.path.dirname(HERE), "benchmarks"),):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import weights_staged                          # noqa: E402
from lib.references import phi4flash as ref             # noqa: E402
from singa_tpu import device, tensor                    # noqa: E402
from singa_tpu.models import phi4flash as pf            # noqa: E402
from singa_tpu.observability import spans               # noqa: E402
from singa_tpu.ops import attention_mod                 # noqa: E402
from singa_tpu.serving import kv_cache                  # noqa: E402

pytestmark = pytest.mark.serving

DEV = device.create_cpu_device()
ATOL = 2e-4
WINDOW, MAX_LEN, PREFILL = 8, 40, 16


def toy_cfg(**over):
    cfg = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
               intermediate_size=96, sliding_window=WINDOW,
               layer_norm_eps=1e-5, vocab_size=256, num_hidden_layers=8,
               mamba_d_state=4, mamba_d_conv=4, mamba_expand=2,
               mamba_dt_rank=4, precision="float32",
               init={"matrix_std": 0.15, "embedding_std": 0.15,
                     "dt_bias_mean": -2.0})
    cfg.update(over)
    return cfg


def build(cfg, seed=7, policy=None):
    """The model compiled as the benchmark compiles it, holding the
    reference's weights for `seed`. Returns (model, {name: array})."""
    m = pf.Phi4FlashLM(
        cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        sliding_window=cfg["sliding_window"], d_state=cfg["mamba_d_state"],
        d_conv=cfg["mamba_d_conv"], expand=cfg["mamba_expand"],
        dt_rank=cfg["mamba_dt_rank"])
    ids = tensor.Tensor(data=jnp.zeros((1, PREFILL), jnp.float32),
                        device=DEV, requires_grad=False)
    m.compile([ids], is_train=False, use_graph=True, policy=policy)
    m.eval()
    states = m.get_states()
    params = weights_staged.make(ref.param_specs(cfg), seed, jnp.float32)
    for name, arr in params.items():
        t = states[f"Phi4FlashLM.{name}"]
        assert tuple(t.shape) == tuple(arr.shape), name
        t.data = arr.astype(t.data.dtype)
    return m, params


@pytest.fixture(scope="module")
def toy():
    cfg = toy_cfg()
    m, params = build(cfg)
    return cfg, m, params


def _reference(params, cfg, seq):
    return np.asarray(ref.forward(params, jnp.asarray(seq)[None], cfg))[0]


def _engine(m, **kw):
    from singa_tpu.observability.metrics import MetricsRegistry
    args = dict(slots=4, max_len=MAX_LEN, prefill_len=PREFILL,
                prefill_batch=2, registry=MetricsRegistry())
    args.update(kw)
    return m.compile_serving(**args)


def test_state_names_are_the_references_leaves(toy):
    cfg, m, params = toy
    assert set(m.get_states()) == {f"Phi4FlashLM.{n}" for n in params}
    assert m.cfg.kinds == ("mamba", "attention", "mamba", "attention",
                           "mamba", "attention", "gmu", "cross")
    assert (m.cfg.memory_layer, m.cfg.full_layer) == (4, 5)


def test_the_published_layout_of_32_layers():
    c = pf.Config(hidden_size=2560, num_layers=32, num_heads=40,
                  num_kv_heads=20, intermediate_size=10240,
                  sliding_window=512, layer_norm_eps=1e-5, d_state=16,
                  d_conv=4, expand=2, dt_rank=None)
    assert [c.kinds.count(k) for k in ("mamba", "attention", "gmu",
                                       "cross")] == [9, 9, 7, 7]
    assert (c.memory_layer, c.full_layer, c.dt_rank) == (16, 17, 160)
    assert c.kinds[17] == "attention" and c.kinds[18] == "gmu"
    ad = pf._ServeAdapter.__new__(pf._ServeAdapter)
    ad.cfg = c
    assert ad.cache_kinds() == ["state", "window"] * 8 + ["state", "full"]
    assert ad.cache_readers() == [1] * 17 + [8]


@pytest.mark.parametrize("S", [1, 5, 24])
def test_eval_forward_matches_the_reference(toy, S):
    cfg, m, params = toy
    tok = np.random.default_rng(S).integers(0, 256, (2, S))
    out = m(tensor.Tensor(data=jnp.asarray(tok, jnp.float32), device=DEV,
                          requires_grad=False))
    want = np.asarray(ref.forward(params, jnp.asarray(tok), cfg))
    np.testing.assert_allclose(np.asarray(out.data), want, atol=ATOL)


# -- the adapter's two programs, by hand --------------------------------------

@pytest.fixture(scope="module")
def programs(toy):
    cfg, m, params = toy
    ad = m.decode_adapter()
    return (ad, ad.params(), jax.jit(ad.prefill_fn()),
            jax.jit(ad.decode_fn()))


def _prefill_one(programs, cache, seq, n, slot, row=0, width=2):
    """A prefill batch of `width` rows of which `row` holds seq[:n]."""
    ad, Pm, prefill, _ = programs
    tokens = np.zeros((width, PREFILL), np.int32)
    tokens[row, :n] = seq[:n]
    lengths = np.zeros((width,), np.int32)
    lengths[row] = n
    slots = np.zeros((width,), np.int32)
    slots[row] = slot
    valid = np.zeros((width,), bool)
    valid[row] = True
    cache, logits = prefill(Pm, cache, tokens, lengths, slots, valid)
    return cache, np.asarray(logits)[row]


@pytest.mark.parametrize("n,row", [(5, 0), (14, 1), (16, 0), (8, 1), (1, 0)])
def test_prefill_then_decode_logits_match_the_full_forward(toy, programs, n,
                                                           row):
    """Logits after a prefill of n tokens — shorter than the window of 8,
    the window exactly, longer, and the whole unpadded prefill width of
    16 — and after each decoded token up to a context of 34, where every
    window ring has wrapped three times, against the reference's full
    forward of the whole sequence. The prompt sits in either row of the
    batch; the other row is padding."""
    cfg, m, params = toy
    ad, Pm, _, decode = programs
    seq = np.random.default_rng(n).integers(1, 256, 34)
    want = _reference(params, cfg, seq)
    cache, logits = _prefill_one(programs, ad.init_cache(3, MAX_LEN), seq, n,
                                 slot=2, row=row)
    np.testing.assert_allclose(logits, want[n - 1], atol=ATOL)
    for t in range(n, 34):
        cache, logits = decode(
            Pm, cache, np.asarray([0, 0, seq[t]], np.int32),
            np.asarray([0, 0, t], np.int32),
            np.asarray([False, False, True]))
        np.testing.assert_allclose(np.asarray(logits)[2], want[t], atol=ATOL)


def test_two_prompts_of_a_batch_do_not_mix(toy, programs):
    cfg, m, params = toy
    ad, Pm, prefill, decode = programs
    rng = np.random.default_rng(11)
    a, b = rng.integers(1, 256, 20), rng.integers(1, 256, 20)
    tokens = np.zeros((2, PREFILL), np.int32)
    tokens[0, :13], tokens[1, :4] = a[:13], b[:4]
    cache, logits = prefill(Pm, ad.init_cache(3, MAX_LEN), tokens,
                            np.asarray([13, 4], np.int32),
                            np.asarray([2, 0], np.int32),
                            np.asarray([True, True]))
    wa, wb = _reference(params, cfg, a), _reference(params, cfg, b)
    np.testing.assert_allclose(np.asarray(logits),
                               np.stack([wa[12], wb[3]]), atol=ATOL)
    # the two slots decode side by side, each at its own position
    for i in range(7):
        cache, logits = decode(
            Pm, cache, np.asarray([b[4 + i], 0, a[13 + i]], np.int32),
            np.asarray([4 + i, 0, 13 + i], np.int32),
            np.asarray([True, False, True]))
        np.testing.assert_allclose(np.asarray(logits)[0], wb[4 + i],
                                   atol=ATOL)
        np.testing.assert_allclose(np.asarray(logits)[2], wa[13 + i],
                                   atol=ATOL)


def test_a_reused_slot_gives_the_second_requests_logits_alone(toy, programs):
    """A slot that held a long request (rings wrapped, states full) takes a
    second, shorter one: its logits are those of the second sequence by
    itself — nothing of the first is left in rings, tails or states."""
    cfg, m, params = toy
    ad, Pm, _, decode = programs
    rng = np.random.default_rng(13)
    first, second = rng.integers(1, 256, 30), rng.integers(1, 256, 12)
    cache, _ = _prefill_one(programs, ad.init_cache(2, MAX_LEN), first, 16,
                            slot=1)
    for t in range(16, 30):
        cache, _ = decode(Pm, cache, np.asarray([0, first[t]], np.int32),
                          np.asarray([0, t], np.int32),
                          np.asarray([False, True]))
    want = _reference(params, cfg, second)
    cache, logits = _prefill_one(programs, cache, second, 3, slot=1)
    np.testing.assert_allclose(logits, want[2], atol=ATOL)
    for t in range(3, 12):
        cache, logits = decode(Pm, cache,
                               np.asarray([0, second[t]], np.int32),
                               np.asarray([0, t], np.int32),
                               np.asarray([False, True]))
        np.testing.assert_allclose(np.asarray(logits)[1], want[t], atol=ATOL)


def test_a_dead_slots_state_stands_still(toy, programs):
    cfg, m, params = toy
    ad, Pm, _, decode = programs
    seq = np.random.default_rng(17).integers(1, 256, 12)
    cache, _ = _prefill_one(programs, ad.init_cache(2, MAX_LEN), seq, 6,
                            slot=0)
    after, _ = decode(Pm, cache, np.asarray([9, 9], np.int32),
                      np.asarray([6, 0], np.int32),
                      np.asarray([False, False]))
    states = [(old, new) for old, new in zip(cache, after) if "ssm" in old]
    assert len(states) == 3
    for old, new in states:             # rings are masked, not kept
        for name in old:
            np.testing.assert_array_equal(np.asarray(old[name]),
                                          np.asarray(new[name]))
    assert np.asarray(cache[0]["ssm"])[0].any()      # and slot 0 has one


# -- through the engine ------------------------------------------------------

def test_the_cache_holds_rings_and_states_side_by_side(toy):
    cfg, m, _ = toy
    eng = _engine(m)
    kinds = ["state", "window", "state", "window", "state", "full"]
    assert eng._layout.adapter.cache_kinds() == kinds
    shapes = [{n: tuple(a.shape) for n, a in level.items()}
              for level in eng._cache]
    state = {"conv": (4, 3, 128), "ssm": (4, 4, 128)}
    assert shapes == [state, {"k": (4, 2, 8, 16), "v": (4, 2, 8, 16)}] * 2 \
        + [state, {"k": (4, 2, 40, 16), "v": (4, 2, 40, 16)}]
    gauge = eng._reg.get("serve_kv_bytes")
    assert gauge.value(kind="window") == 2 * 2 * 4 * 2 * 8 * 16 * 4
    assert gauge.value(kind="full") == 2 * 4 * 2 * 40 * 16 * 4
    assert gauge.value(kind="state") == 3 * 4 * (3 + 4) * 128 * 4
    g = eng._handoff_geometry()
    assert g["ring_lengths"] == [8, 8, 40] and g["n_layers"] == 6
    assert g["heads"] == 2 and g["head_dim"] == 16
    assert g["state"] == [["conv", [3, 128], "float32"],
                          ["ssm", [4, 128], "float32"]] * 3


def test_served_tokens_are_the_references_best(toy):
    """Prompts shorter and longer than the window through `submit`, decoded
    until the window rings have wrapped several times: each served token
    is the reference's best in its full forward, to rounding."""
    cfg, m, params = toy
    eng = _engine(m)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 256, n) for n in (16, 5, 12, 9, 16, 3)]
    futs = [eng.submit(p, max_new_tokens=20, temperature=0.0)
            for p in prompts]
    eng.run_until_idle()
    assert eng.compiled_step_info()["n_traces"] == 1
    for p, f in zip(prompts, futs):
        tokens = f.result(timeout=0)["tokens"]
        seq = np.concatenate([p, tokens])
        lg = _reference(params, cfg, seq)
        at = np.arange(len(p) - 1, len(seq) - 1)
        gaps = lg[at].max(-1) - lg[at, seq[len(p):]]
        assert gaps.max() <= ATOL, gaps.max()


def test_a_slot_reused_through_the_engine(toy):
    """One slot: the second request waits for it and is served as if the
    engine were fresh."""
    cfg, m, params = toy
    eng = _engine(m, slots=1, prefill_batch=1)
    rng = np.random.default_rng(19)
    first, second = rng.integers(1, 256, 16), rng.integers(1, 256, 4)
    f1 = eng.submit(first, max_new_tokens=18, temperature=0.0)
    f2 = eng.submit(second, max_new_tokens=10, temperature=0.0)
    eng.run_until_idle()
    assert len(f1.result(timeout=0)["tokens"]) == 18
    fresh = _engine(m, slots=1, prefill_batch=1)
    f3 = fresh.submit(second, max_new_tokens=10, temperature=0.0)
    fresh.run_until_idle()
    assert f2.result(timeout=0)["tokens"] == f3.result(timeout=0)["tokens"]


def test_span_attrs_sum_to_the_counters(toy):
    cfg, m, _ = toy
    spans.recorder().clear()
    eng = _engine(m)
    futs = [eng.submit(np.arange(1, n + 1), max_new_tokens=6,
                       temperature=0.0) for n in (10, 3)]
    eng.run_until_idle()
    for f in futs:
        f.result(timeout=0)
    reg = eng._reg
    recs = spans.recorder().records()
    decode = [r for r in recs if r.get("name") == "serve.decode"]
    prefill = [r for r in recs if r.get("name") == "serve.prefill"]
    # one prefill batch of both prompts: the self-decoder ran every prompt
    # token, the cross-decoder one row a prompt
    assert [(r["self_rows"], r["cross_rows"]) for r in prefill] == [(13, 2)]
    rows = reg.get("serve_prefill_rows_total")
    assert rows.value(decoder="self") == 13 == \
        reg.get("serve_prefill_tokens_total").value()
    assert rows.value(decoder="cross") == 2
    # 5 ticks of two live slots, 3 state levels each
    assert [r["state_slots"] for r in decode] == [6] * 5
    assert reg.get("serve_state_steps_total").value() == 30
    # a tick reads two window rings once and the full ring twice (its
    # owner and the one cross layer): positions 10..14 and 3..7
    want = [sum(2 * min(p + 1, 8) + 2 * (p + 1) for p in (10 + i, 3 + i))
            for i in range(5)]
    assert [r["kv_rows"] for r in decode] == want
    assert reg.get("serve_kv_rows_attended_total").value() == sum(want)
    # no block of the kernel divides these toy rings: each is one block
    assert [r["kv_blocks"] for r in decode] == [2 * (2 + 2)] * 5
    assert reg.get("serve_kv_blocks_walked_total").value() == 40
    assert {r["readback"] for r in decode + prefill} == {"tokens"}
    assert reg.get("serve_readback_total").total() == 6


def test_snapshot_carries_the_state_levels(toy):
    """A request moved between engines in mid-answer continues bitwise:
    rings, convolution tails and states all went with it."""
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
    # and without its states the same request goes another way
    rows = a._layout.read_slot(a._cache, 0, None)
    assert len(rows) == 3 * 2 + 3 * 2
    assert any(np.asarray(r).any() for r in rows if r.shape == (4, 128))


def test_a_snapshot_of_another_geometry_is_refused(toy):
    cfg, m, _ = toy
    from singa_tpu.serving.engine import HandoffRefused
    a = _engine(m)
    a.submit(np.arange(1, 9), max_new_tokens=8, temperature=0.0)
    a.step()
    snap = a.snapshot_slot(0)
    with pytest.raises(HandoffRefused):
        _engine(m, max_len=48).inject_snapshot(snap["meta"], snap["frame"])
    # the same rings around a state of another size
    other, _ = build(toy_cfg(mamba_d_state=8))
    with pytest.raises(HandoffRefused, match="geometry"):
        _engine(other).inject_snapshot(snap["meta"], snap["frame"])


@pytest.mark.parametrize("asked,key", [
    (dict(kv_layout="paged"), "kv_layout_declined"),
    (dict(speculative_k=4), "speculative_declined"),
    (dict(kv_layout="paged", speculative_k=4), "speculative_declined"),
])
def test_paged_and_speculative_builds_decline_typed(toy, asked, key):
    cfg, m, _ = toy
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        eng = _engine(m, **asked)
    assert eng.kv_layout == "ring" and not eng.speculative_k
    assert eng.compiled_step_info()[key] == "recurrent_state"
    assert any("declined" in str(w.message) for w in seen)


def test_sharded_build_declines(toy):
    cfg, m, _ = toy
    from singa_tpu.parallel.gspmd import ShardingDecline
    with pytest.raises(ShardingDecline):
        _engine(m, model_shards=2)


# -- the read-only attend ----------------------------------------------------

@pytest.fixture
def interpreted():
    prev = attention_mod.FORCE_PALLAS_INTERPRET
    attention_mod.FORCE_PALLAS_INTERPRET = True
    yield
    attention_mod.FORCE_PALLAS_INTERPRET = prev


@pytest.mark.parametrize("L,dtype,kernel", [
    (24, jnp.float32, False),      # no block divides: the XLA path
    (256, jnp.float32, True),      # two blocks of 128
    (512, jnp.bfloat16, True),     # the window ring's four blocks
])
def test_attend_token_is_attend_and_writes_nothing(interpreted, L, dtype,
                                                   kernel):
    """`attend_token` against `attend` on a level of 2 KV heads of 128 read
    by 4 query heads each (the differential form), slots at positions
    short of a block, on its edge, wrapped, and one dead."""
    rng = np.random.default_rng(L)
    draw = lambda *s: jnp.asarray(rng.normal(size=s), dtype)  # noqa: E731
    level = {"k": draw(5, 2, L, 128), "v": draw(5, 2, L, 128)}
    q = draw(5, 8, 1, 128)
    pos = jnp.asarray([3, L // 2 - 1, L - 1, 3 * L + 5, 7], jnp.int32)
    active = jnp.asarray([True, True, True, True, False])
    assert (kv_cache.ring_block(level) is not None) == kernel
    held = {n: np.asarray(a, np.float32) for n, a in level.items()}
    got = kv_cache.attend_token(level, q, pos, active, 0.125)
    want = kv_cache.attend(q, level, pos, 0.125)
    assert got.shape == q.shape and got.dtype == q.dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32)[:4],
                               np.asarray(want, np.float32)[:4], atol=tol)
    for n, a in level.items():
        np.testing.assert_array_equal(np.asarray(a, np.float32), held[n])
    if kernel:
        assert not np.asarray(got, np.float32)[4].any()


def test_decode_through_the_kernel_paths_matches_the_twins(interpreted,
                                                           monkeypatch):
    """A model whose rings the kernel takes (2 differential KV heads of
    128, window 128, max_len 256), decoded with the kernel interpreted —
    `ring_decode` on the six levels' rings, `ring_attend` for the cross
    layer — against the same decode on the XLA twins."""
    cfg = toy_cfg(hidden_size=256, num_attention_heads=4,
                  num_key_value_heads=4, sliding_window=128,
                  intermediate_size=64, mamba_expand=1)
    m, _ = build(cfg)
    ad = m.decode_adapter()
    Pm, decode = ad.params(), ad.decode_fn()
    rng = np.random.default_rng(23)
    cache = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape) * 0.3, a.dtype),
        ad.init_cache(3, 256))
    assert [kv_cache.ring_block(lv) for lv in cache if "k" in lv] \
        == [128, 128, 128]
    args = (np.asarray([5, 6, 7], np.int32),
            np.asarray([130, 0, 17], np.int32),
            np.asarray([True, False, True]))
    from singa_tpu.ops import ring_decode
    calls = {"ring_decode": 0, "ring_attend": 0}
    for name in calls:
        def counted(*a, _f=getattr(ring_decode, name), _n=name):
            calls[_n] += 1
            return _f(*a)
        monkeypatch.setattr(ring_decode, name, counted)
    new, logits = decode(Pm, cache, *args)
    assert calls == {"ring_decode": 3, "ring_attend": 1}
    with kv_cache.xla_rings():
        want_new, want = decode(Pm, cache, *args)
    live = np.asarray([0, 2])
    np.testing.assert_allclose(np.asarray(logits)[live],
                               np.asarray(want)[live], atol=ATOL)
    for got_lv, want_lv in zip(new, want_new):
        for n in got_lv:
            np.testing.assert_allclose(np.asarray(got_lv[n])[live],
                                       np.asarray(want_lv[n])[live],
                                       atol=1e-5)


# -- weights once, inference only --------------------------------------------

def test_params_are_the_models_own_arrays_in_bf16():
    m, _ = build(toy_cfg(precision="bfloat16"), policy="bfloat16")
    Pm = m.decode_adapter(policy=None).params()
    assert Pm["emb"] is m.emb.data
    assert Pm["layers"][4]["A_log"] is m.layers[4].A_log.data
    leaves = jax.tree_util.tree_leaves(Pm)
    assert len(leaves) == 3 + 3 * 15 + 3 * 15 + 8 + 15
    assert all(a.dtype == jnp.bfloat16 for a in leaves)
    eng = _engine(m, policy="bfloat16")
    assert {str(a.dtype) for lv in eng._cache for a in lv.values()} == \
        {"bfloat16", "float32"}
    assert all(lv["ssm"].dtype == jnp.float32 for lv in eng._cache
               if "ssm" in lv)


def test_a_policy_the_weights_do_not_fit_is_refused(toy):
    cfg, m, _ = toy                     # float32 weights
    with pytest.raises(ValueError, match="by reference"):
        _engine(m, policy="bfloat16")


def test_training_is_refused_with_the_reason(toy):
    cfg, m, _ = toy
    with pytest.raises(NotImplementedError, match="inference-only"):
        m.train_one_batch(None, None)


def test_layer_counts_that_do_not_pair_up_are_refused():
    with pytest.raises(ValueError, match="layers"):
        pf.Phi4FlashLM(256, hidden_size=64, num_layers=6, num_heads=8,
                       num_kv_heads=4)
    with pytest.raises(ValueError, match="pair up"):
        pf.Phi4FlashLM(256, hidden_size=64, num_layers=8, num_heads=8,
                       num_kv_heads=3)
