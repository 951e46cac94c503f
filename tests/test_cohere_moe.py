"""The parallel-block sparse-expert LM (models/cohere_moe.py), the
share-aware expert layer (parallel/moe.py) and per-layer rings in the
serving engine, against the plain float32 reference the benchmark keeps
(benchmarks/lib/references/cohere_moe.py), at a small size on the CPU:
hidden 64, 8 query / 4 KV heads of 8, 16 experts top-4, 2 shared,
window 8, 4 layers (sliding, sliding, sliding, full), vocabulary 256.

Tolerances: everything here runs in float32, so program and reference
differ by summation order alone — 2e-4 absolute on logits of magnitude
~10 (a float32 sum of a few hundred terms), and exact agreement of served
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
from lib.references import cohere_moe as ref            # noqa: E402
from singa_tpu import device, tensor                    # noqa: E402
from singa_tpu.models import cohere_moe as cm           # noqa: E402
from singa_tpu.parallel import moe                      # noqa: E402
from singa_tpu.parallel.communicator import collective_context  # noqa: E402
from singa_tpu.serving import kv_cache                  # noqa: E402

DEV = device.create_cpu_device()
ATOL = 2e-4
LAYER_TYPES = ["sliding_attention"] * 3 + ["full_attention"]


def toy_cfg(**over):
    cfg = dict(hidden_size=64, head_dim=8, num_attention_heads=8,
               num_key_value_heads=4, intermediate_size=96, num_experts=16,
               router_width=16, num_experts_per_tok=4, num_shared_experts=2,
               experts_held_from=0, sliding_window=8, rope_theta=50000.0,
               layer_norm_eps=1e-5, logit_scale=1, vocab_size=256,
               num_hidden_layers=4, layer_types=LAYER_TYPES,
               precision="float32",
               init={"matrix_std": 0.1, "router_std": 0.3,
                     "embedding_std": 1.0, "residual_out_std": 0.1})
    cfg.update(over)
    return cfg


def build(cfg, seed=7, policy=None, S=24):
    """The model compiled as the benchmark compiles it, holding the
    reference's weights for `seed`. Returns (model, {name: array})."""
    m = cm.CohereMoELM(
        cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        num_experts=cfg["num_experts"], router_width=cfg["router_width"],
        top_k=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        experts_held_from=cfg["experts_held_from"],
        sliding_window=cfg["sliding_window"], layer_types=cfg["layer_types"])
    ids = tensor.Tensor(data=jnp.zeros((1, S), jnp.float32), device=DEV,
                        requires_grad=False)
    m.compile([ids], is_train=False, use_graph=True, policy=policy)
    m.eval()
    states = m.get_states()
    params = weights_staged.make(ref.param_specs(cfg), seed, jnp.float32)
    for name, arr in params.items():
        t = states[f"CohereMoELM.{name}"]
        assert tuple(t.shape) == tuple(arr.shape), name
        t.data = arr.astype(t.data.dtype)
    return m, params


@pytest.fixture(scope="module")
def toy():
    cfg = toy_cfg()
    m, params = build(cfg)
    return cfg, m, params


def test_state_names_are_the_references_leaves(toy):
    cfg, m, params = toy
    names = {n for n, t in m.get_states().items()}
    assert names == {f"CohereMoELM.{n}" for n in params}


@pytest.mark.parametrize("S", [5, 24])
def test_eval_forward_matches_the_reference(toy, S):
    cfg, m, params = toy
    tok = np.random.default_rng(S).integers(0, 256, (2, S))
    out = m(tensor.Tensor(data=jnp.asarray(tok, jnp.float32), device=DEV,
                          requires_grad=False))
    want = np.asarray(ref.forward(params, jnp.asarray(tok), cfg))
    np.testing.assert_allclose(np.asarray(out.data), want, atol=ATOL)


def test_blocked_attention_equals_one_shot(monkeypatch):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 32, 8, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 32, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 32, 2, 8)), jnp.float32)
    for window in (None, 8):
        one = cm.masked_attention(q, k, v, 0.3, window)   # 32 rows <= 512
        monkeypatch.setattr(cm, "PREFILL_ROWS", 8)
        blocked = cm.masked_attention(q, k, v, 0.3, window)
        monkeypatch.undo()
        np.testing.assert_allclose(np.asarray(blocked), np.asarray(one),
                                   atol=1e-5)


def test_ring_attend_reads_grouped_heads():
    """`kv_cache.attend` with 8 query heads on 2 KV heads equals the same
    call with each KV head repeated for its 4 query heads."""
    rng = np.random.default_rng(1)
    level = {"k": jnp.asarray(rng.normal(size=(3, 2, 6, 8)), jnp.float32),
             "v": jnp.asarray(rng.normal(size=(3, 2, 6, 8)), jnp.float32)}
    q = jnp.asarray(rng.normal(size=(3, 8, 1, 8)), jnp.float32)
    pos = jnp.asarray([2, 5, 9])
    got = kv_cache.attend(q, level, pos, 0.35)
    full = {n: jnp.repeat(a, 4, axis=1) for n, a in level.items()}
    want = kv_cache.attend(q, full, pos, 0.35)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# -- serving: prefill then decode through the engine -------------------------

def _engine(m, **kw):
    from singa_tpu.observability.metrics import MetricsRegistry
    args = dict(slots=4, max_len=40, prefill_len=16, prefill_batch=2,
                registry=MetricsRegistry())
    args.update(kw)
    return m.compile_serving(**args)


def test_rings_have_their_layers_own_length(toy):
    cfg, m, _ = toy
    eng = _engine(m)
    shapes = [tuple(level["k"].shape) for level in eng._cache]
    assert shapes == [(4, 4, 8, 8)] * 3 + [(4, 4, 40, 8)]
    reg = eng._reg
    assert reg.get("serve_kv_bytes").value(kind="window") == \
        3 * 2 * 4 * 4 * 8 * 8 * 4
    assert reg.get("serve_kv_bytes").value(kind="full") == \
        2 * 4 * 4 * 40 * 8 * 4
    assert eng._handoff_geometry()["ring_lengths"] == [8, 8, 8, 40]


def test_served_tokens_are_the_references_best(toy):
    """Prefill longer than the window (16 > 8), decode until every window
    ring has wrapped several times (contexts up to 36 of max_len 40): each
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


@pytest.mark.parametrize("prefill_rows,n", [(512, 14), (4, 14), (4, 10)])
def test_prefill_and_decode_logits_match_the_full_forward(toy, prefill_rows,
                                                          n, monkeypatch):
    """The adapter's two programs, driven by hand: logits after a prefill
    of 14 > window tokens and after each of 12 decoded tokens against the
    reference's forward of the whole sequence. With blocks of 4 rows the
    prefill's loops run over the blocks that hold a token (4 of 4 for 14
    tokens, 3 of 4 for 10) and leave the padding's out."""
    cfg, m, params = toy
    ad = m.decode_adapter()
    monkeypatch.setattr(cm, "PREFILL_ROWS", prefill_rows)
    Pm = ad.params()
    cache = ad.init_cache(2, 40)
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
    # every real token routed top-4 over 16 held experts in 4 layers
    assert [int(v) for v in stats][:2] == [n * 4 * 4, 0]
    for t in range(n, 26):
        cache, (logits, stats) = decode(
            Pm, cache, np.asarray([0, seq[t]], np.int32),
            np.asarray([0, t], np.int32), np.asarray([False, True]))
        np.testing.assert_allclose(np.asarray(logits)[1], want[t],
                                   atol=ATOL)
        assert int(stats[0]) == 4 * 4          # the one active row's pairs


def test_engine_counts_pairs_touched_experts_and_ring_rows(toy):
    cfg, m, _ = toy
    eng = _engine(m)
    f = eng.submit(np.arange(1, 11), max_new_tokens=6, temperature=0.0)
    eng.run_until_idle()
    f.result(timeout=0)
    reg = eng._reg
    # 10 prompt tokens + 5 decoded inputs, top-4, 4 layers, all held
    assert reg.get("moe_pairs_total").value(held="here") == 15 * 16
    assert reg.get("moe_pairs_total").value(held="absent") == 0
    assert reg.get("moe_calls_total").value(program="decode") == 5
    assert 0 < reg.get("moe_experts_touched_total").value(
        program="decode") <= 5 * 16
    # decode ticks at positions 10..14: window rings hold 8, the full
    # ring position + 1
    assert reg.get("serve_kv_rows_attended_total").value() == \
        sum(3 * 8 + (pos + 1) for pos in range(10, 15))
    from singa_tpu.observability import spans
    decode = [r for r in spans.recorder().records()
              if r.get("name") == "serve.decode" and "pairs_here" in r]
    assert decode and decode[-1]["pairs_here"] == 16
    # each tick's share of the ring rows rides its span
    assert [r["kv_rows"] for r in decode[-5:]] == \
        [3 * 8 + (pos + 1) for pos in range(10, 15)]
    # no block of the kernel divides these toy rings: each is one block
    assert [r["kv_blocks"] for r in decode[-5:]] == [4] * 5
    assert reg.get("serve_kv_blocks_walked_total").value() == 4 * 5
    # the counts came over with the tokens: no call read its logits
    assert {r["readback"] for r in decode[-5:]} == {"tokens"}
    readback = reg.get("serve_readback_total")
    assert readback.value(program="decode", what="tokens") == 5
    assert readback.value(program="prefill", what="tokens") == 1
    assert readback.total() == 6


def test_the_counts_reach_the_spans_of_a_sampling_tick_too(toy):
    """``(tokens, logits, stats)``: in a tick that serves a sampling
    request the logits are read as well, and the counts still are the
    last output, on the span and in the counters."""
    cfg, m, _ = toy
    eng = _engine(m)
    f = eng.submit(np.arange(1, 11), max_new_tokens=4, temperature=0.7,
                   seed=5)
    eng.run_until_idle()
    assert len(f.result(timeout=0)["tokens"]) == 4
    reg = eng._reg
    assert reg.get("moe_pairs_total").value(held="here") == 13 * 16
    assert reg.get("moe_calls_total").value(program="decode") == 3
    readback = reg.get("serve_readback_total")
    assert readback.value(program="decode", what="logits") == 3
    assert readback.value(program="prefill", what="logits") == 1
    assert readback.total() == 4
    from singa_tpu.observability import spans
    last = [r for r in spans.recorder().records()
            if r.get("name") == "serve.decode"][-1]
    assert last["pairs_here"] == 16 and last["readback"] == "logits"
    assert eng.compiled_step_info()["n_traces"] == 1


def test_the_engine_takes_whatever_counts_an_adapter_publishes(toy,
                                                               monkeypatch):
    """The serving layer knows neither the names nor the meaning of an
    adapter's per-call counts: the adapter registers its own counters and
    the dict it returns goes on the call's span."""
    cfg, m, _ = toy
    seen = []

    def stats_recorder(self, registry):
        calls = registry.counter("my_program_calls_total", "calls",
                                 labels=("program",))

        def record(program, stats):
            calls.inc(program=program)
            seen.append((program, stats.shape))
            return {"first_count": int(stats[0])}
        return record
    monkeypatch.setattr(cm._ServeAdapter, "stats_recorder", stats_recorder)
    eng = _engine(m)
    f = eng.submit(np.arange(1, 11), max_new_tokens=4, temperature=0.0)
    eng.run_until_idle()
    f.result(timeout=0)
    reg = eng._reg
    assert reg.get("moe_pairs_total") is None
    assert reg.get("my_program_calls_total").value(program="prefill") == 1
    assert reg.get("my_program_calls_total").value(program="decode") == 3
    assert seen[0] == ("prefill", (3,))
    from singa_tpu.observability import spans
    last = [r for r in spans.recorder().records()
            if r.get("name") == "serve.decode"][-1]
    assert last["first_count"] == 16 and "pairs_here" not in last


def test_snapshot_of_mixed_rings_continues_bitwise(toy):
    cfg, m, _ = toy
    a, b = _engine(m), _engine(m)
    prompt = np.arange(3, 15)
    whole = _engine(m)
    fw = whole.submit(prompt, max_new_tokens=18, temperature=0.0)
    whole.run_until_idle()
    a.submit(prompt, max_new_tokens=18, temperature=0.0)
    for _ in range(9):
        a.step()
    snap = a.snapshot_slot(0)
    fb = b.inject_snapshot(snap["meta"], snap["frame"])
    b.run_until_idle()
    assert fb.result(timeout=0)["tokens"] == fw.result(timeout=0)["tokens"]


def test_engine_declines_what_the_adapter_cannot(toy):
    cfg, m, _ = toy
    from singa_tpu.parallel.gspmd import ShardingDecline
    with pytest.raises(ShardingDecline):
        _engine(m, model_shards=2)
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        eng = _engine(m, kv_layout="paged")
    assert eng.kv_layout == "ring"
    assert any("declined" in str(w.message) for w in seen)


def test_a_snapshot_of_another_geometry_is_refused(toy):
    cfg, m, _ = toy
    from singa_tpu.serving.engine import HandoffRefused
    a, b = _engine(m), _engine(m, max_len=48)
    a.submit(np.arange(1, 9), max_new_tokens=8, temperature=0.0)
    a.step()
    snap = a.snapshot_slot(0)
    with pytest.raises(HandoffRefused):
        b.inject_snapshot(snap["meta"], snap["frame"])


# -- weights once -------------------------------------------------------------

def test_params_are_the_models_own_arrays_in_bf16():
    cfg = toy_cfg(precision="bfloat16")
    m, _ = build(cfg, policy="bfloat16")
    ad = m.decode_adapter()
    Pm = ad.params()
    assert Pm["emb"] is m.emb.data
    assert Pm["layers"][2]["ffn"]["w_gate"] is m.layers[2].ffn.w_gate.data
    leaves = jax.tree_util.tree_leaves(Pm)
    assert len(leaves) == 2 + 12 * 4
    assert all(a.dtype == jnp.bfloat16 for a in leaves)


def _live_bytes():
    return sum(a.size * a.dtype.itemsize for a in jax.live_arrays())


def test_compile_serving_adds_the_rings_and_no_second_copy():
    import gc
    cfg = toy_cfg(precision="bfloat16")
    m, _ = build(cfg, policy="bfloat16")
    weights = sum(t.data.size * 2 for t in m.get_states().values())
    gc.collect()
    before = _live_bytes()
    eng = _engine(m, policy="bfloat16")
    gc.collect()
    added = _live_bytes() - before
    rings = sum(a.size * a.dtype.itemsize for level in eng._cache
                for a in level.values())
    assert rings <= added < rings + 0.05 * weights, (added, rings, weights)
    assert all(level["k"].dtype == jnp.bfloat16 for level in eng._cache)


def test_a_policy_the_weights_do_not_fit_is_refused(toy):
    cfg, m, _ = toy                     # float32 weights
    with pytest.raises(ValueError, match="by reference"):
        _engine(m, policy="bfloat16")


def test_training_is_refused_with_the_reason(toy):
    cfg, m, _ = toy
    with pytest.raises(NotImplementedError, match="inference-only"):
        m.train_one_batch(None, None)


# -- the expert layer ---------------------------------------------------------

def _ffn_params(rng, D=16, F=24, E=8, G=8, S=2):
    n = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
    return {"router": n(D, E), "w_gate": n(G, D, F), "w_up": n(G, D, F),
            "w_down": n(G, F, D), "s_gate": n(S, D, F), "s_up": n(S, D, F),
            "s_down": n(S, F, D)}


def _plain_ffn(p, h, top_k, held_from=0):
    cfg = {"num_experts_per_tok": top_k, "experts_held_from": held_from}
    flat = {f"ffn.{k}": v for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref._ffn(flat, h, cfg, None)[0])


@pytest.mark.parametrize("T,dense_rows", [(12, 128), (40, 8), (300, 8)])
def test_expert_share_matches_plain_on_both_paths(T, dense_rows, monkeypatch):
    """Few rows: every held expert on every row; many rows: the pairs
    sorted by expert and worked off in tiles (here 8 and 256 rows)."""
    monkeypatch.setattr(moe, "DENSE_ROWS", dense_rows)
    monkeypatch.setattr(moe, "SORTED_TILE", 8 if T == 40 else 256)
    rng = np.random.default_rng(T)
    p = _ffn_params(rng, E=8, G=3)
    h = jnp.asarray(rng.normal(size=(T, 16)), jnp.float32)
    y, stats = moe.expert_share_ffn(p, h, top_k=2, held_from=2)
    np.testing.assert_allclose(np.asarray(y), _plain_ffn(p, h, 2, 2),
                               atol=1e-4)
    assert int(stats["pairs_here"] + stats["pairs_absent"]) == 2 * T
    assert 0 < int(stats["experts_touched"]) <= 3


@pytest.mark.parametrize("dense_rows", [128, 8])
def test_no_pair_is_dropped_under_a_skewed_router(dense_rows, monkeypatch):
    """A router that sends every token to held expert 1 (and its other
    pick wherever): 64 rows on one expert, which a capacity factor of
    1.25 would cut to 20."""
    monkeypatch.setattr(moe, "DENSE_ROWS", dense_rows)
    monkeypatch.setattr(moe, "SORTED_TILE", 16)
    rng = np.random.default_rng(9)
    p = _ffn_params(rng, E=8, G=4)
    p["router"] = p["router"].at[:, 1].set(0.0)
    h = jnp.asarray(np.abs(rng.normal(size=(64, 16))), jnp.float32)
    p["router"] = p["router"].at[:, 1].set(5.0)     # h > 0: score ~ 1
    y, stats = moe.expert_share_ffn(p, h, top_k=2)
    np.testing.assert_allclose(np.asarray(y), _plain_ffn(p, h, 2),
                               atol=1e-4)
    idx, _ = moe.route_sigmoid_topk(h, p["router"], 2)
    assert int(jnp.sum(idx == 1)) == 64
    assert int(stats["pairs_here"]) == int(jnp.sum(idx < 4))


def test_rows_in_blocks_works_the_first_blocks_only():
    x = jnp.arange(24.0).reshape(12, 2)
    y = jax.jit(lambda x, n: moe.rows_in_blocks(
        lambda r: jnp.concatenate([r, r * 2], axis=1), x, n, 4))(x, 2)
    assert y.shape == (12, 4)
    np.testing.assert_array_equal(np.asarray(y[:8, 2:]), np.asarray(x[:8] * 2))
    assert not np.asarray(y[8:]).any()


def test_padding_rows_are_routed_nowhere(monkeypatch):
    rng = np.random.default_rng(2)
    p = _ffn_params(rng)
    h = jnp.asarray(rng.normal(size=(20, 16)), jnp.float32)
    rows = jnp.arange(20) < 13
    monkeypatch.setattr(moe, "SORTED_TILE", 8)
    for dense_rows in (128, 4):
        monkeypatch.setattr(moe, "DENSE_ROWS", dense_rows)
        y, stats = moe.expert_share_ffn(p, h, top_k=2, rows=rows)
        assert int(stats["pairs_here"]) == 26
        np.testing.assert_allclose(np.asarray(y)[:13],
                                   _plain_ffn(p, h, 2)[:13], atol=1e-4)


def test_the_layer_form_holds_its_share():
    ffn = moe.ExpertShareFFN(8, 24, top_k=2, held_count=3, held_from=4,
                             n_shared=2, init_std=0.3)
    x = tensor.Tensor(data=np.random.default_rng(4).normal(
        size=(2, 5, 16)).astype(np.float32), device=DEV, requires_grad=False)
    y = ffn(x)
    p = {n: getattr(ffn, n).data for n in ffn.LEAVES}
    assert p["w_gate"].shape == (3, 16, 24) and p["router"].shape == (16, 8)
    want = _plain_ffn(p, x.data.reshape(10, 16), 2, 4)
    np.testing.assert_allclose(np.asarray(y.data).reshape(10, 16), want,
                               atol=1e-4)
    assert int(ffn.stats["pairs_here"] + ffn.stats["pairs_absent"]) == 20
    with pytest.raises(ValueError):
        moe.ExpertShareFFN(8, 24, top_k=2, held_count=6, held_from=4)


def test_on_the_expert_axis_the_shares_are_summed():
    """Four peers, two experts each, the same rows on all: the layer with
    its exchange gives what one chip holding all eight gives."""
    rng = np.random.default_rng(6)
    p = _ffn_params(rng, E=8, G=8)
    h = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices("cpu")[:4]), ("expert",))
    specs = {k: P("expert") if k.startswith("w_") else P() for k in p}

    def body(p, h):
        with collective_context("expert"):
            return moe.expert_share_ffn(p, h, top_k=2,
                                        axis_name="expert")[0]

    y = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(specs, P()),
                              out_specs=P()))(p, h)
    np.testing.assert_allclose(np.asarray(y), _plain_ffn(p, h, 2), atol=1e-4)


# -- the shares add up --------------------------------------------------------

def _share_of(params, cfg, s, layer):
    """Share s of 4 of one layer's leaves: 2 query heads, 1 KV head,
    4 experts; the norm, the router and the shared experts whole."""
    hd = cfg["head_dim"]
    pre = f"layers.{layer}."
    q = slice(2 * s * hd, 2 * (s + 1) * hd)
    kv = slice(s * hd, (s + 1) * hd)
    ffn = {k: params[pre + "ffn." + k] for k in moe.ExpertShareFFN.LEAVES}
    for k in ("w_gate", "w_up", "w_down"):
        ffn[k] = ffn[k][4 * s:4 * (s + 1)]
    return {"ln": params[pre + "ln"], "wq": params[pre + "wq"][:, q],
            "wk": params[pre + "wk"][:, kv], "wv": params[pre + "wv"][:, kv],
            "wo": params[pre + "wo"][q], "ffn": ffn}


@pytest.mark.parametrize("layer", [0, 3])
def test_the_four_shares_add_up_to_the_uncut_layer(toy, layer):
    """Attention parts + routed parts + the shared experts ONCE + x, over
    four shares of 2 query heads, 1 KV head and 4 experts each, equal the
    reference's uncut layer (a sliding layer and the full one)."""
    cfg, _, params = toy
    rng = np.random.default_rng(layer)
    x = jnp.asarray(rng.normal(size=(2, 20, 64)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(20), (2, 20))
    kind = cfg["layer_types"][layer]
    window = cfg["sliding_window"] if kind == cm.SLIDING else None
    parts = jnp.zeros_like(x)
    for s in range(4):
        c = cm.Config(
            hidden_size=64, num_heads=2, num_kv_heads=1, head_dim=8,
            intermediate_size=96, num_experts=4, router_width=16, top_k=4,
            num_shared_experts=2, experts_held_from=4 * s, sliding_window=8,
            rope_theta=50000.0, layer_norm_eps=1e-5, logit_scale=1,
            layer_types=cfg["layer_types"])
        y, _, _ = cm.block_apply(
            c, kind, _share_of(params, cfg, s, layer), x, pos,
            lambda q, k, v, c=c: (cm.masked_attention(q, k, v, c.scale,
                                                      window), None))
        parts = parts + (y - x)
    # every share added the shared experts' mean: count it once
    none = _share_of(params, cfg, 0, layer)["ffn"]
    none = {k: (v[:0] if k.startswith("w_") else v) for k, v in none.items()}
    h = cm.layer_norm(x, params[f"layers.{layer}.ln"], 1e-5)
    shared, _ = moe.expert_share_ffn(none, h.reshape(40, 64), top_k=4)
    whole = x + parts - 3 * shared.reshape(x.shape)
    want, _ = ref.layer_forward(params, x, layer, cfg)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               atol=ATOL)


def test_vocabulary_shares_are_slices_of_the_uncut_logits(toy):
    cfg, m, params = toy
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, 6, 64)),
                    jnp.float32)
    want = np.asarray(ref.logits_of(params, x, cfg))
    for s in range(4):
        rows = slice(64 * s, 64 * (s + 1))
        got = cm.head_logits(m.cfg, {"emb": params["emb"][rows],
                                     "ln_f": params["ln_f"]}, x)
        np.testing.assert_allclose(np.asarray(got), want[..., rows],
                                   atol=ATOL)


def test_a_share_of_the_model_matches_the_reference_given_the_same_share():
    """The configuration the benchmark runs in small: 4 of 16 experts held
    (from 4), router 16 wide; picks that go elsewhere add nothing on
    either side."""
    cfg = toy_cfg(num_experts=4, experts_held_from=4,
                  num_attention_heads=2, num_key_value_heads=1)
    m, params = build(cfg, seed=11)
    tok = np.random.default_rng(11).integers(0, 256, (2, 20))
    out = m(tensor.Tensor(data=jnp.asarray(tok, jnp.float32), device=DEV,
                          requires_grad=False))
    want = np.asarray(ref.forward(params, jnp.asarray(tok), cfg))
    np.testing.assert_allclose(np.asarray(out.data), want, atol=ATOL)
    eng = _engine(m)
    f = eng.submit(tok[0, :12], max_new_tokens=8, temperature=0.0)
    eng.run_until_idle()
    f.result(timeout=0)
    here = eng._reg.get("moe_pairs_total").value(held="here")
    absent = eng._reg.get("moe_pairs_total").value(held="absent")
    assert here + absent == (12 + 7) * 4 * 4 and 0 < here < absent
