"""GSPMD sharded serving: the CI pins for ISSUE 15's acceptance bar.

Hermetic ≥4-device CPU mesh (conftest forces 8 virtual host devices):
``compile_serving(model_shards=2)`` must produce greedy tokens
BITWISE-identical to the single-device engine for the ring AND paged
layouts (int8 KV included), keep ``n_traces == 1`` across ≥3 slot
refills, never gather the full vocab before argmax, and refuse — typed
— every config the mesh cannot honor.
"""

import warnings

import numpy as np
import pytest
import jax

from singa_tpu import device, tensor
from singa_tpu.models import char_rnn, transformer
from singa_tpu.observability import metrics as obs_metrics
from singa_tpu.parallel import gspmd
from singa_tpu.parallel.gspmd import ShardingDecline
from singa_tpu.serving.scheduler import ServingError
from singa_tpu.tensor import Tensor

DEV = device.create_cpu_device()

pytestmark = pytest.mark.serving


def _reg():
    return obs_metrics.MetricsRegistry()


def tiny_lm(vocab=64, d_model=32, heads=4, layers=2, max_len=64,
            seed=0):
    np.random.seed(seed)
    DEV.SetRandSeed(seed)
    m = transformer.TransformerLM(vocab, d_model=d_model, n_heads=heads,
                                  n_layers=layers, max_len=max_len,
                                  tp=False)
    m.eval()
    m(Tensor(data=np.zeros((1, 8), np.float32), device=DEV,
             requires_grad=False))
    return m


def _prompts(n=8, vocab=64, seed=3, max_len=8, shared_prefix=True):
    rng = np.random.RandomState(seed)
    out = [rng.randint(1, vocab, (int(rng.randint(2, max_len)),))
           for _ in range(n)]
    if shared_prefix and n >= 8:
        # a prefix-cache-hit pair for the paged engines: the sharer
        # arrives LAST so the source prompt has finished (and released
        # its full blocks into the prefix cache) by the time it admits
        out[0] = rng.randint(1, vocab, (7,))
        out[7] = np.concatenate([out[0][:4], [5]])
    return out


def _run(eng, prompts, n_new=6):
    futs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    eng.run_until_idle()
    return [f.result(timeout=5)["tokens"] for f in futs]


class TestShardedParity:
    def test_ring_bitwise_parity_across_refills(self):
        """THE acceptance pin: greedy tokens from the model_shards=2
        engine are token-for-token identical to the single-device
        engine, with slots=2 so 8 prompts force ≥4 slot refills, and
        the decode program still traced exactly once."""
        m = tiny_lm(seed=1)
        prompts = _prompts(8)
        ref = _run(m.compile_serving(slots=2, max_len=48,
                                     prefill_len=8, registry=_reg()),
                   prompts)
        eng = m.compile_serving(slots=2, max_len=48, prefill_len=8,
                                model_shards=2, registry=_reg())
        assert _run(eng, prompts) == ref
        info = eng.compiled_step_info()
        assert info["n_traces"] == 1, info
        assert info["prefill_n_traces"] == 1, info
        assert info["mesh"]["model"] == 2
        assert info["mesh"]["devices"] >= 4
        assert info["slots_per_device"] * info["mesh"]["batch"] == 2

    def test_ring_parity_on_explicit_2x2_mesh(self):
        """The literal acceptance geometry: an explicit 4-device
        (batch=2 × model=2) mesh, bitwise ring parity."""
        m = tiny_lm(seed=2)
        prompts = _prompts(6)
        ref = _run(m.compile_serving(slots=2, max_len=48,
                                     prefill_len=8, registry=_reg()),
                   prompts)
        mesh = gspmd.serving_mesh(jax.devices()[:4], model_shards=2)
        eng = m.compile_serving(slots=2, max_len=48, prefill_len=8,
                                mesh=mesh, registry=_reg())
        assert _run(eng, prompts) == ref
        assert eng.compiled_step_info()["mesh"] == {
            "batch": 2, "model": 2, "devices": 4}

    def test_paged_parity_with_prefix_hits(self):
        m = tiny_lm(seed=3)
        prompts = _prompts(8)
        kw = dict(slots=2, max_len=48, prefill_len=8,
                  kv_layout="paged", kv_block_size=4)
        ref = _run(m.compile_serving(**kw, registry=_reg()), prompts)
        reg = _reg()
        eng = m.compile_serving(**kw, model_shards=2, registry=reg)
        assert _run(eng, prompts) == ref
        info = eng.compiled_step_info()
        assert info["n_traces"] == 1, info
        # the shared-prefix pair actually exercised the prefix cache
        # on the sharded engine (hit → prefill skipped for the span)
        assert reg.get("prefix_cache_hits_total").total() >= 1

    def test_int8_kv_parity_ring_and_paged(self):
        """int8 KV (the quant serving preset) rides the sharded path:
        payload pools shard over heads/slots, the per-row fp32 scale
        planes follow their own specs, and tokens stay bitwise equal
        to the single-device int8 engines."""
        m = tiny_lm(seed=4)
        prompts = _prompts(6)
        for extra in ({}, {"kv_layout": "paged", "kv_block_size": 4}):
            kw = dict(slots=2, max_len=48, prefill_len=8,
                      policy="int8_weight_only", **extra)
            ref = _run(m.compile_serving(**kw, registry=_reg()),
                       prompts)
            eng = m.compile_serving(**kw, model_shards=2,
                                    registry=_reg())
            assert _run(eng, prompts) == ref, extra
            assert eng.compiled_step_info()["n_traces"] == 1

    def test_speculative_sharded_identity(self):
        """The K-token verify program sharded: the accept walk runs on
        in-graph argmax tokens and stays token-identical to sequential
        greedy (the single-device spec engine is itself CI-pinned to
        that)."""
        m = tiny_lm(seed=5)
        prompts = _prompts(6)
        kw = dict(slots=2, max_len=48, prefill_len=8,
                  kv_layout="paged", kv_block_size=4)
        ref = _run(m.compile_serving(**kw, registry=_reg()), prompts)
        eng = m.compile_serving(**kw, model_shards=2, speculative_k=3,
                                registry=_reg())
        assert _run(eng, prompts) == ref
        assert eng.compiled_step_info()["n_traces"] == 1

    def test_bf16_policy_sharded_parity(self):
        m = tiny_lm(seed=6)
        prompts = _prompts(5)
        kw = dict(slots=2, max_len=48, prefill_len=8,
                  policy="bf16_mixed")
        ref = _run(m.compile_serving(**kw, registry=_reg()), prompts)
        eng = m.compile_serving(**kw, model_shards=2, registry=_reg())
        assert _run(eng, prompts) == ref


class TestNoVocabGather:
    def test_decode_jaxpr_has_no_gather_and_token_outputs(self):
        """The sharded decode program's jaxpr: greedy argmax happens
        IN GRAPH (token-shaped outputs, no (W, V) logits output) and
        contains no hand-written collective — XLA inserts whatever the
        sharding needs at compile time, never a full-vocab all-gather
        in the program text."""
        from singa_tpu.aot import export as aot_export
        m = tiny_lm(seed=7)
        eng = m.compile_serving(slots=2, max_len=48, prefill_len=8,
                                model_shards=2, registry=_reg())
        _, decode_avals = aot_export.serving_program_avals(eng)
        raw = eng._layout.programs(eng.sharded)[1]
        jaxpr = jax.make_jaxpr(raw)(*decode_avals)
        text = str(jaxpr)
        for prim in ("all_gather", "psum", "all_to_all",
                     "ppermute"):
            assert prim not in text, prim
        # outputs: the cache levels + (W,) int32 tokens — nothing
        # vocab-sized ever leaves the program
        vocab = m.vocab_size
        tok_aval = jaxpr.out_avals[-1]
        assert tok_aval.shape == (eng.slots,)
        assert str(tok_aval.dtype) == "int32"
        assert all(vocab not in a.shape for a in jaxpr.out_avals)

    def test_paged_decode_jaxpr_token_outputs(self):
        from singa_tpu.aot import export as aot_export
        m = tiny_lm(seed=8)
        eng = m.compile_serving(slots=2, max_len=48, prefill_len=8,
                                kv_layout="paged", kv_block_size=4,
                                model_shards=2, speculative_k=3,
                                registry=_reg())
        _, decode_avals = aot_export.serving_program_avals(eng)
        jaxpr = jax.make_jaxpr(eng._layout.programs(eng.sharded)[1])(
            *decode_avals)
        assert "all_gather" not in str(jaxpr)
        assert jaxpr.out_avals[-1].shape == (eng.slots, 3)
        assert all(m.vocab_size not in a.shape
                   for a in jaxpr.out_avals)

    def test_one_wrap_keeps_or_drops_the_logits(self):
        """The sharded engine's programs are the unsharded engine's with
        the logits dropped — one wrap (``kv_cache.with_tokens``) at the
        one place programs are picked: unsharded, decode returns the
        ``(W,)`` tokens beside the ``(W, V)`` float32 logits; sharded,
        the tokens alone, and every call counts a ``tokens``
        read-back."""
        from singa_tpu.aot import export as aot_export
        m = tiny_lm(seed=7)
        reg = _reg()
        kw = dict(slots=2, max_len=48, prefill_len=8)
        one = m.compile_serving(**kw, registry=_reg())
        eng = m.compile_serving(**kw, model_shards=2, registry=reg)
        _, decode_avals = aot_export.serving_program_avals(one)
        n_state = len(jax.tree_util.tree_leaves(decode_avals[1]))
        for e, want in ((one, [((2,), "int32"),
                               ((2, m.vocab_size), "float32")]),
                        (eng, [((2,), "int32")])):
            jaxpr = jax.make_jaxpr(e._layout.programs(e.sharded)[1])(
                *decode_avals)
            assert [(a.shape, str(a.dtype))
                    for a in jaxpr.out_avals[n_state:]] == want
        assert one._layout.outputs(False) == "tokens+logits"
        assert eng._layout.outputs(True) == "tokens"
        _run(eng, _prompts(3))
        readback = reg.get("serve_readback_total")
        assert readback.value(program="decode", what="tokens") \
            == reg.get("serve_decode_steps_total").total() > 0
        assert readback.value(program="decode", what="logits") == 0
        assert readback.value(program="prefill", what="logits") == 0


class TestTypedDeclines:
    def test_heads_indivisible(self):
        m = tiny_lm(d_model=30, heads=3, seed=9)
        with pytest.raises(ShardingDecline, match="n_heads"):
            m.compile_serving(slots=2, max_len=48, prefill_len=8,
                              model_shards=2, registry=_reg())

    def test_vocab_indivisible(self):
        m = tiny_lm(vocab=65, seed=10)
        with pytest.raises(ShardingDecline, match="vocab"):
            m.compile_serving(slots=2, max_len=48, prefill_len=8,
                              model_shards=2, registry=_reg())

    def test_mesh_smaller_than_model_shards(self):
        m = tiny_lm(seed=11)
        with pytest.raises(ShardingDecline, match="model_shards"):
            m.compile_serving(slots=2, max_len=48, prefill_len=8,
                              model_shards=len(jax.devices()) * 2,
                              registry=_reg())

    def test_slots_indivisible_by_batch_axis(self):
        m = tiny_lm(seed=12)
        mesh = gspmd.serving_mesh(jax.devices()[:4], model_shards=2)
        with pytest.raises(ShardingDecline, match="slots"):
            m.compile_serving(slots=3, max_len=48, prefill_len=8,
                              mesh=mesh, registry=_reg())

    def test_mesh_without_named_axes(self):
        from singa_tpu.parallel import mesh as mesh_mod
        m = tiny_lm(seed=13)
        plain = mesh_mod.make_mesh(jax.devices())   # dp axes, no batch
        with pytest.raises(ShardingDecline, match="named axes"):
            m.compile_serving(slots=2, max_len=48, prefill_len=8,
                              mesh=plain, registry=_reg())

    def test_charrnn_adapter_declines(self):
        np.random.seed(0)
        cm = char_rnn.CharRNN(11, hidden_size=8)
        cm.eval()
        xs = [Tensor(data=np.eye(11, dtype=np.float32)[
            np.random.randint(0, 11, (2,))], device=DEV,
            requires_grad=False) for _ in range(3)]
        cm.forward(xs)
        with pytest.raises(ShardingDecline, match="sharded"):
            cm.compile_serving(slots=2, max_len=16, prefill_len=4,
                               model_shards=2, registry=_reg())

    def test_moe_blocks_decline(self):
        m = tiny_lm(seed=14)
        np.random.seed(14)
        moe = transformer.TransformerLM(64, d_model=32, n_heads=4,
                                        n_layers=1, max_len=64,
                                        tp=False, moe=2)
        moe.eval()
        moe(Tensor(data=np.zeros((1, 8), np.float32), device=DEV,
                   requires_grad=False))
        with pytest.raises(ShardingDecline, match="MoE"):
            moe.compile_serving(slots=2, max_len=48, prefill_len=8,
                                model_shards=2, registry=_reg())
        del m

    def test_sampled_request_rejected_typed(self):
        m = tiny_lm(seed=15)
        eng = m.compile_serving(slots=2, max_len=48, prefill_len=8,
                                model_shards=2, registry=_reg())
        with pytest.raises(ServingError, match="greedy-only"):
            eng.submit([1, 2, 3], max_new_tokens=2, temperature=0.7)
        with pytest.raises(ServingError, match="greedy-only"):
            eng.submit([1, 2, 3], max_new_tokens=2, top_k=4)
        # greedy still serves after the rejections
        assert len(_run(eng, [np.asarray([1, 2, 3])], 3)[0]) == 3

    def test_aot_store_refused_with_mesh_named(self, tmp_path):
        m = tiny_lm(seed=16)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            eng = m.compile_serving(slots=2, max_len=48, prefill_len=8,
                                    model_shards=2,
                                    aot_store=str(tmp_path),
                                    registry=_reg())
        assert any("sharded" in str(x.message) for x in w)
        src = eng.compiled_step_info()["aot"]
        assert all(v.startswith("refused:sharded_mesh")
                   for v in src.values()), src
        with pytest.raises(ValueError, match="mesh"):
            eng.export_aot(str(tmp_path))


class TestFleetView:
    def test_healthz_info_and_heartbeat_mesh(self):
        """/healthz (compiled_step_info) and the heartbeat serving_kv
        block carry the mesh shape and PER-DEVICE pool bytes when
        sharded — the pool-pressure numbers stay honest per chip."""
        m = tiny_lm(seed=17)
        reg = _reg()
        eng = m.compile_serving(slots=2, max_len=48, prefill_len=8,
                                kv_layout="paged", kv_block_size=4,
                                model_shards=2, registry=reg)
        _run(eng, _prompts(3, shared_prefix=False), 3)
        info = eng.compiled_step_info()
        assert info["mesh"]["model"] == 2
        # paged pool: replicated over batch, head-sliced over model
        assert info["kv_per_device_bytes"] * 2 == \
            info["kv_global_bytes"]
        hb = obs_metrics.heartbeat_summary(reg)
        kv = hb["serving_kv"]
        assert kv["mesh"]["model"] == 2
        assert kv["per_device_bytes"] == info["kv_per_device_bytes"]
        assert kv["blocks_total"] == eng.kv_blocks

    def test_ring_per_device_bytes(self):
        m = tiny_lm(seed=18)
        reg = _reg()
        eng = m.compile_serving(slots=4, max_len=48, prefill_len=8,
                                model_shards=2, registry=reg)
        info = eng.compiled_step_info()
        # ring: slots/batch × heads/model → per-device = global / n
        assert info["kv_per_device_bytes"] * info["mesh"]["devices"] \
            == info["kv_global_bytes"]
        hb = obs_metrics.heartbeat_summary(reg)
        assert hb["serving_kv"]["per_device_bytes"] == \
            info["kv_per_device_bytes"]


class TestServeTreeSpecs:
    """The adapter's tree holds its layers role by role (PR 34): a
    vector role is one leaf stacked over the layers and gets the spec
    its role had a block behind a ``None`` for the layer axis; a matrix
    role is a list with that spec a layer."""

    @pytest.mark.parametrize("policy", [None, "int8_weight_only"])
    def test_every_role_keeps_its_spec(self, policy):
        from jax.sharding import PartitionSpec as P
        from singa_tpu import mixed_precision as mp
        m = tiny_lm(seed=20, layers=3)
        pol = None if policy is None else mp.resolve(policy)
        ad = m.decode_adapter(policy=pol)
        tree = ad.params()
        part = gspmd.serving_partitioner(model_shards=2, max_batch=4)
        ax = part.model_axis
        a_block = {
            "ln1_s": P(), "ln1_b": P(), "ln2_s": P(), "ln2_b": P(),
            "wq": P(None, ax), "bq": P(ax), "wk": P(None, ax),
            "bk": P(ax), "wv": P(None, ax), "bv": P(ax),
            "wo": P(ax, None), "bo": P(),
            "w_up": P(None, ax), "b_up": P(ax),
            "w_dn": P(ax, None), "b_dn": P()}
        specs = gspmd.lm_param_specs(part, tree, ad.n_heads)
        assert set(specs["blocks"]) == set(a_block)
        for role, old in a_block.items():
            got, leaf = specs["blocks"][role], tree["blocks"][role]
            if not isinstance(leaf, list):
                assert leaf.shape[0] == 3, role
                assert got == P(None, *old), role
            elif isinstance(leaf[0], dict):
                # the scale (1, out) rides the payload's out axis
                assert got == [{"q": old, "s": P(None, old[1])}] * 3, role
            else:
                assert got == [old] * 3, role
        # and the mesh holds what the table says: half of wq's columns
        # and half of bq's of every layer on a device
        eng = m.compile_serving(slots=4, max_len=48, prefill_len=8,
                                model_shards=2, policy=pol,
                                registry=_reg())
        wq = eng._P["blocks"]["wq"][2]
        wq = wq["q"] if isinstance(wq, dict) else wq
        assert wq.sharding.shard_shape(wq.shape) == (32, 16)
        bq = eng._P["blocks"]["bq"]
        assert bq.sharding.shard_shape(bq.shape) == (3, 16)
        assert _run(eng, _prompts(4, shared_prefix=False)) == _run(
            m.compile_serving(slots=4, max_len=48, prefill_len=8,
                              policy=pol, registry=_reg()),
            _prompts(4, shared_prefix=False))
