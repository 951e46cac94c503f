"""Transformer LM: training under DP / TP / SP on the CPU mesh — all
three parallel modes must match plain DP numerically."""

import numpy as np
import jax
import pytest
from jax.sharding import PartitionSpec as P

from singa_tpu import device, model, opt, tensor
from singa_tpu.tensor import Tensor
from singa_tpu.models import transformer
from singa_tpu.parallel import mesh as mesh_mod
from singa_tpu.parallel.communicator import set_mesh


VOCAB = 31


def lm_data(B=8, S=16, seed=0, vocab=VOCAB):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab, (B, S)).astype(np.float32)
    targets = np.roll(ids, -1, axis=1)
    return ids, targets


def train(mesh_config=None, tp=False, seq_axis=None, reduce_axes=None,
          steps=8, seed=5, use_graph=True, dist=True, seq_mode="ring",
          vocab=VOCAB, fused_head_chunk=None, return_model=False):
    dev = device.create_cpu_device()
    dev.SetRandSeed(seed)
    ids, targets = lm_data(vocab=vocab)
    tx = tensor.Tensor(data=ids, device=dev, requires_grad=False)
    ty = tensor.Tensor(data=targets, device=dev, requires_grad=False)

    m = transformer.TransformerLM(vocab, d_model=32, n_heads=2,
                                  n_layers=2, max_len=64, tp=tp,
                                  seq_axis=seq_axis, seq_mode=seq_mode,
                                  fused_head_chunk=fused_head_chunk)
    if dist:
        d = opt.DistOpt(opt.SGD(lr=0.3, momentum=0.9),
                        reduce_axes=reduce_axes)
        if mesh_config is not None:
            msh = mesh_mod.make_mesh(jax.devices("cpu"), mesh_config)
            d.communicator.mesh = msh
            set_mesh(msh)
        m.set_optimizer(d)
    else:
        m.set_optimizer(opt.SGD(lr=0.3, momentum=0.9))
    if seq_axis is not None:
        m.input_specs = [P("data", "seq"), P("data", "seq")]
        m.output_specs = [P("data", "seq"), P()]
    m.compile([tx], is_train=True, use_graph=use_graph)
    losses = [float(m(tx, ty)[1].data) for _ in range(steps)]
    return (losses, m) if return_model else losses


class TestTransformerLM:
    @pytest.mark.slow
    def test_eager_trains(self):
        losses = train(dist=False, use_graph=False, steps=6)
        assert losses[-1] < losses[0], losses

    def test_dp_trains(self):
        losses = train(mesh_mod.MeshConfig())
        assert losses[-1] < losses[0] * 0.9, losses

    def test_tp_matches_dp(self):
        dp = train(mesh_mod.MeshConfig())
        tp = train(mesh_mod.MeshConfig(model=2), tp=True)
        np.testing.assert_allclose(tp, dp, rtol=5e-3)

    def test_sp_matches_dp(self):
        dp = train(mesh_mod.MeshConfig())
        sp = train(mesh_mod.MeshConfig(seq=2), seq_axis="seq",
                   reduce_axes=("data", "seq"))
        np.testing.assert_allclose(sp, dp, rtol=5e-3)

    @pytest.mark.slow
    def test_sp_ulysses_matches_dp(self):
        """All-to-all sequence parallelism through the full model: one
        head re-shard per attention instead of ring hops; must match the
        dense run like ring does."""
        dp = train(mesh_mod.MeshConfig())
        ul = train(mesh_mod.MeshConfig(seq=2), seq_axis="seq",
                   reduce_axes=("data", "seq"), seq_mode="ulysses")
        np.testing.assert_allclose(ul, dp, rtol=5e-3)

    @pytest.mark.slow
    def test_tp_plus_sp(self):
        dp = train(mesh_mod.MeshConfig())
        both = train(mesh_mod.MeshConfig(model=2, seq=2), tp=True,
                     seq_axis="seq", reduce_axes=("data", "seq"))
        np.testing.assert_allclose(both, dp, rtol=5e-3)

    def test_generation_shapes(self):
        dev = device.create_cpu_device()
        m = transformer.TransformerLM(VOCAB, d_model=32, n_heads=2,
                                      n_layers=1, max_len=64)
        ids, _ = lm_data(B=2, S=8)
        tx = tensor.Tensor(data=ids, device=dev, requires_grad=False)
        logits = m(tx)
        assert logits.shape == (2, 8, VOCAB)


class TestVocabParallel:
    """The vocab ends shard over 'model': embedding rows
    (VocabParallelEmbedding) + head columns (ColumnParallelLinear), and
    the fused CE loss reduces across vocab shards online. vocab=32
    divides model=2 so the specs genuinely shard; the suite's default
    VOCAB=31 exercises the indivisible→replicate fallback instead."""

    def test_tp_vocab32_matches_dp(self):
        dp = train(vocab=32)
        tpl, m = train(mesh_mod.MeshConfig(model=2), tp=True, vocab=32,
                       return_model=True)
        np.testing.assert_allclose(tpl, dp, rtol=2e-4)
        # announced layouts survived spec fitting: rows/columns sharded
        sl = m._state_list
        i_emb = next(j for j, t in enumerate(sl) if t is m.tok_emb.W)
        i_head = next(j for j, t in enumerate(sl) if t is m.head.W)
        assert tuple(m._state_specs[i_emb]) [:1] == ("model",)
        assert tuple(m._state_specs[i_head]) == (None, "model")

    @pytest.mark.parametrize("chunk", [
        8, pytest.param(12, marks=pytest.mark.slow)])
    def test_tp_fused_head_matches_dense_dp(self, chunk):
        # the headline composition: dp×tp mesh, vocab-sharded head, loss
        # through the cross-shard fused CE — must track the dense
        # replicated path step for step. chunk=12 does NOT divide the
        # local vocab (16), so the scan's padded tail overlaps other
        # ranks' target ids: regression for the owned-bound in the hit
        # mask (a miss there adds -1e30 to the loss).
        dp = train(vocab=32)
        fl = train(mesh_mod.MeshConfig(model=2), tp=True, vocab=32,
                   fused_head_chunk=chunk)
        np.testing.assert_allclose(fl, dp, rtol=1e-3)

    def test_fused_head_dp_only_matches(self):
        base = train(vocab=32)
        dp = train(mesh_mod.MeshConfig(), vocab=32, fused_head_chunk=8)
        np.testing.assert_allclose(dp, base, rtol=1e-3)

    def test_decode_weight_cache_reuses_and_invalidates(self):
        """The host-gather of decode weights is cached against live
        param identity: repeated generate() calls reuse it; a train
        step (which rebinds every param array) must invalidate it so
        decoding NEVER uses stale weights."""
        from singa_tpu.models.transformer import _lm_decode_params
        _, m = train(steps=2, return_model=True)
        P1 = _lm_decode_params(m)
        assert _lm_decode_params(m) is P1          # identity: cached
        ids, tgt = lm_data()
        dev = device.create_cpu_device()
        tx = tensor.Tensor(data=ids.astype(np.float32), device=dev,
                           requires_grad=False)
        ty = tensor.Tensor(data=tgt.astype(np.float32), device=dev,
                           requires_grad=False)
        m(tx, ty)                                  # one more train step
        P2 = _lm_decode_params(m)
        assert P2 is not P1                        # regathered
        assert not np.allclose(np.asarray(P2["head_w"]),
                               np.asarray(P1["head_w"]))
        # and a greedy step after the refresh matches the live forward
        out = m.generate(ids[:, :6], max_new_tokens=1, temperature=0)
        m.eval()
        m.graph_mode = False
        want = np.argmax(np.asarray(
            m(tensor.Tensor(data=ids[:, :6].astype(np.float32),
                            device=dev)).data)[:, -1, :], -1)
        np.testing.assert_array_equal(out[:, -1], want)

    def test_generate_after_sharded_training(self):
        # decoding consumes the tp-sharded trained state (host-gathered
        # once): one greedy step must equal the argmax of the model's own
        # full forward logits
        _, m = train(mesh_mod.MeshConfig(model=2), tp=True, vocab=32,
                     fused_head_chunk=8, steps=3, return_model=True)
        ids, _ = lm_data(vocab=32)
        dev = device.create_cpu_device()
        tx = tensor.Tensor(data=ids, device=dev, requires_grad=False)
        out = m.generate(tx, max_new_tokens=1, temperature=0)
        m.eval()
        m.graph_mode = False
        logits = m(tx)
        want = np.argmax(np.asarray(logits.data)[:, -1, :], -1)
        np.testing.assert_array_equal(out[:, -1], want)

    @pytest.mark.slow
    def test_save_load_restores_sharded_momentum(self, tmp_path):
        # load_states creates momentum buffers on the fresh optimizer;
        # they must re-announce their param's layout or the next compiled
        # step collides full-shape buffer with local-shard grad
        import jax
        from singa_tpu import opt as opt_mod
        from singa_tpu.parallel.communicator import set_mesh
        dev = device.create_cpu_device()
        dev.SetRandSeed(7)
        ids, targets = lm_data(vocab=32)
        tx = tensor.Tensor(data=ids, device=dev, requires_grad=False)
        ty = tensor.Tensor(data=targets, device=dev, requires_grad=False)

        def build():
            m = transformer.TransformerLM(32, d_model=32, n_heads=2,
                                          n_layers=2, max_len=64, tp=True,
                                          fused_head_chunk=8)
            d = opt_mod.DistOpt(opt_mod.SGD(lr=0.3, momentum=0.9))
            msh = mesh_mod.make_mesh(jax.devices("cpu"),
                                     mesh_mod.MeshConfig(model=2))
            d.communicator.mesh = msh
            set_mesh(msh)
            m.set_optimizer(d)
            m.compile([tx], is_train=True, use_graph=True)
            return m

        m = build()
        for _ in range(3):
            m(tx, ty)
        p = str(tmp_path / "st.zip")
        m.save_states(p)
        l_ref = float(m(tx, ty)[1].data)
        m2 = build()
        m2.load_states(p)
        l2 = float(m2(tx, ty)[1].data)    # raised pre-fix
        np.testing.assert_allclose(l2, l_ref, rtol=5e-3)

    def test_indivisible_vocab_replicates(self):
        # 31 rows over model=2 cannot shard: the fitted spec must fall
        # back to replication (and training still matches dp — the
        # existing test_tp_matches_dp covers the numerics)
        _, m = train(mesh_mod.MeshConfig(model=2), tp=True, steps=2,
                     return_model=True)
        sl = m._state_list
        i_emb = next(j for j, t in enumerate(sl) if t is m.tok_emb.W)
        i_head = next(j for j, t in enumerate(sl) if t is m.head.W)
        assert m._state_specs[i_emb] == P()
        assert m._state_specs[i_head] == P()


class TestRemat:
    """autograd.checkpoint / TransformerLM(remat=True): rematerialized
    backward matches the stored-activation run exactly (no reference
    counterpart — the TPU-first activation-memory trade)."""

    def _train(self, remat, steps=3):
        dev = device.create_cpu_device()
        dev.SetRandSeed(3)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 23, (4, 10)).astype(np.float32)
        tgt = np.roll(ids, -1, 1)
        m = transformer.TransformerLM(23, d_model=16, n_heads=2,
                                      n_layers=2, max_len=32, tp=False,
                                      remat=remat)
        m.set_optimizer(opt.SGD(lr=0.1))
        ti = Tensor(data=ids, device=dev, requires_grad=False)
        tt = Tensor(data=tgt, device=dev, requires_grad=False)
        m.compile([ti], is_train=True, use_graph=True)
        return [float(m(ti, tt)[1].numpy()) for _ in range(steps)], m, ti, tt

    def test_remat_matches_baseline(self):
        base, _, _, _ = self._train(False)
        rem, _, _, _ = self._train(True)
        np.testing.assert_allclose(base, rem, rtol=1e-5)

    def test_remat_marks_the_jaxpr(self):
        _, m, ti, tt = self._train(True, steps=1)
        table = m.graph_debug(ti, tt, print_out=False)
        assert "remat" in str(table) or "checkpoint" in str(table)

    def test_checkpoint_rejects_batchnorm_state(self):
        from singa_tpu import autograd, layer
        from singa_tpu.autograd_base import CTX

        class BNBlock(layer.Layer):
            def __init__(self):
                super().__init__()
                self.c = layer.Conv2d(4, 3, padding=1)
                self.bn = layer.BatchNorm2d()

            def forward(self, x):
                return self.bn(self.c(x))

        dev = device.create_cpu_device()
        rng = np.random.RandomState(0)
        b = BNBlock()
        x = Tensor(data=rng.randn(2, 3, 8, 8).astype(np.float32),
                   device=dev)
        b(x)
        prev = CTX.training
        CTX.training = True
        try:
            with pytest.raises(ValueError, match="running stat"):
                autograd.checkpoint(b, x)
        finally:
            CTX.training = prev


class TestGeneration:
    """KV-cache autoregressive decoding: greedy decode must EXACTLY
    match the naive strategy of re-running the full forward per token
    (proves the cache math), and sampling respects temperature/top_k."""

    def _model(self, steps=3):
        dev = device.create_cpu_device()
        dev.SetRandSeed(11)
        ids, targets = lm_data(B=2, S=8)
        tx = tensor.Tensor(data=ids, device=dev, requires_grad=False)
        ty = tensor.Tensor(data=targets, device=dev, requires_grad=False)
        m = transformer.TransformerLM(VOCAB, d_model=32, n_heads=2,
                                      n_layers=2, max_len=64, tp=False)
        m.set_optimizer(opt.SGD(lr=0.3))
        m.compile([tx], is_train=True, use_graph=True)
        for _ in range(steps):
            m(tx, ty)
        m.eval()
        return m, dev, ids

    @pytest.mark.slow
    def test_greedy_matches_naive_refoward(self):
        m, dev, ids = self._model()
        prompt = ids[:, :5]
        T = 6
        out = m.generate(prompt, T, temperature=0)
        assert out.shape == (2, 5 + T)

        # naive: re-run the FULL tape forward per emitted token
        cur = prompt.copy()
        for _ in range(T):
            tx = tensor.Tensor(data=cur.astype(np.float32), device=dev,
                               requires_grad=False)
            logits = np.asarray(m(tx).data)
            nxt = logits[:, -1].argmax(-1).astype(np.float32)
            cur = np.concatenate([cur, nxt[:, None]], 1)
        np.testing.assert_array_equal(out, cur.astype(np.int64))

    @pytest.mark.slow
    def test_moe_greedy_matches_naive_reforward(self):
        # MoE decode routes through the training MoE kernel; with a
        # capacity factor high enough that no token drops, greedy decode
        # must EXACTLY reproduce the full-forward-per-token strategy
        dev = device.create_cpu_device()
        dev.SetRandSeed(11)
        ids, targets = lm_data(B=2, S=8)
        tx = tensor.Tensor(data=ids, device=dev, requires_grad=False)
        ty = tensor.Tensor(data=targets, device=dev, requires_grad=False)
        m = transformer.TransformerLM(VOCAB, d_model=32, n_heads=2,
                                      n_layers=2, max_len=64, tp=False,
                                      moe=4, moe_capacity_factor=8.0)
        m.set_optimizer(opt.SGD(lr=0.3))
        m.compile([tx], is_train=True, use_graph=True)
        for _ in range(3):
            m(tx, ty)
        m.eval()
        prompt = ids[:, :5]
        T = 6
        out = m.generate(prompt, T, temperature=0)
        assert out.shape == (2, 5 + T)
        cur = prompt.copy()
        for _ in range(T):
            txc = tensor.Tensor(data=cur.astype(np.float32), device=dev,
                                requires_grad=False)
            logits = np.asarray(m(txc).data)
            nxt = logits[:, -1].argmax(-1).astype(np.float32)
            cur = np.concatenate([cur, nxt[:, None]], 1)
        np.testing.assert_array_equal(out, cur.astype(np.int64))

    @pytest.mark.slow
    def test_sampling_runs_and_respects_topk(self):
        m, dev, ids = self._model(steps=1)
        out = m.generate(ids[:, :4], 5, temperature=0.8, top_k=3, seed=1)
        assert out.shape == (2, 9)
        assert (out >= 0).all() and (out < VOCAB).all()
        # same seed deterministic, different seed differs
        out2 = m.generate(ids[:, :4], 5, temperature=0.8, top_k=3, seed=1)
        np.testing.assert_array_equal(out, out2)
        out3 = m.generate(ids[:, :4], 5, temperature=0.8, top_k=3, seed=2)
        assert not np.array_equal(out, out3)
        # top_k=1 with temperature is exactly greedy: pins the filter
        out_k1 = m.generate(ids[:, :4], 5, temperature=0.8, top_k=1,
                            seed=3)
        greedy = m.generate(ids[:, :4], 5, temperature=0)
        np.testing.assert_array_equal(out_k1, greedy)

    @pytest.mark.parametrize("moe", [0, 4], ids=["dense", "moe"])
    def test_generate_gives_the_tokens_the_old_tree_gave(self, moe,
                                                         monkeypatch):
        """generate() reads the tree that holds its layers role by role
        (PR 34): greedy and sampled, it emits what the same program
        emits over the tree of a float32 leaf a block a role."""
        from test_serve_param_tree import _OldTreeAdapter, old_walk
        dev = device.create_cpu_device()
        dev.SetRandSeed(11)
        np.random.seed(11)
        m = transformer.TransformerLM(VOCAB, d_model=32, n_heads=2,
                                      n_layers=3, max_len=64, tp=False,
                                      moe=moe, moe_capacity_factor=8.0)
        m.eval()
        ids, _ = lm_data(B=2, S=8)
        m(tensor.Tensor(data=ids, device=dev, requires_grad=False))
        asks = (dict(temperature=0), dict(temperature=0.8, top_k=3,
                                          seed=1))
        got = [m.generate(ids[:, :5], 6, **kw) for kw in asks]
        blocks = transformer._lm_decode_params(m)["blocks"]
        assert blocks["ln1_s"].shape == (3, 32) and len(blocks["wq"]) == 3
        m._decode_cache = None      # the programs traced over that tree
        with old_walk():
            monkeypatch.setattr(
                transformer, "_lm_decode_params",
                lambda model: _OldTreeAdapter(model).params())
            want = [m.generate(ids[:, :5], 6, **kw) for kw in asks]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_edge_cases(self):
        m, dev, ids = self._model(steps=1)
        # zero new tokens returns the prompt unchanged
        out = m.generate(ids[:, :4], 0)
        np.testing.assert_array_equal(out, ids[:, :4].astype(np.int64))
        # non-causal models refuse clearly
        m2 = transformer.TransformerLM(VOCAB, d_model=16, n_heads=2,
                                       n_layers=1, max_len=16,
                                       causal=False)
        import pytest as _pytest
        with _pytest.raises(NotImplementedError, match="causal"):
            m2.generate(ids[:, :4], 2)


class TestBF16Compute:
    """compute_dtype=bfloat16: the LM counterpart of the CNN zoo's
    bf16-input training — downstream params follow, embeddings and the
    MoE router stay f32, both loss paths upcast before the softmax."""

    def _train(self, steps=8, **kw):
        import jax.numpy as jnp
        dev = device.create_cpu_device()
        dev.SetRandSeed(5)
        ids, targets = lm_data()
        tx = tensor.Tensor(data=ids, device=dev, requires_grad=False)
        ty = tensor.Tensor(data=targets, device=dev, requires_grad=False)
        m = transformer.TransformerLM(VOCAB, d_model=32, n_heads=2,
                                      n_layers=2, max_len=64, tp=False,
                                      compute_dtype=jnp.bfloat16, **kw)
        m.set_optimizer(opt.SGD(lr=0.3, momentum=0.9))
        m.compile([tx], is_train=True, use_graph=True)
        losses = [float(m(tx, ty)[1].data) for _ in range(steps)]
        return losses, m

    def test_dense_head_trains_with_bf16_params(self):
        losses, m = self._train()
        assert losses[-1] < losses[0]
        assert str(m.blocks[0].attn.q_proj.W.data.dtype) == "bfloat16"
        assert str(m.blocks[0].mlp.up.W.data.dtype) == "bfloat16"
        # master-precision ends stay f32
        assert str(m.tok_emb.W.data.dtype) == "float32"

    def test_fused_head_trains_in_bf16(self):
        losses, m = self._train(fused_head_chunk=16)
        assert losses[-1] < losses[0]
        assert str(m.head.W.data.dtype) == "bfloat16"

    @pytest.mark.slow
    def test_moe_experts_follow_router_stays_f32(self):
        losses, m = self._train(moe=2, steps=6)
        assert losses[-1] < losses[0]
        assert str(m.blocks[0].mlp.w1.data.dtype) == "bfloat16"
        assert str(m.blocks[0].mlp.wg.data.dtype) == "float32"

    def test_save_load_roundtrip_preserves_bf16(self, tmp_path):
        """bf16 params/momentum store as portable f32 inside the .npz
        and cast back on load — same values, same dtypes, same
        next-step loss."""
        import jax.numpy as jnp
        losses, m = self._train()
        dev = device.create_cpu_device()
        ids, targets = lm_data()
        tx = tensor.Tensor(data=ids, device=dev, requires_grad=False)
        ty = tensor.Tensor(data=targets, device=dev, requires_grad=False)
        p = str(tmp_path / "bf16.zip")
        m.save_states(p)
        m2 = transformer.TransformerLM(VOCAB, d_model=32, n_heads=2,
                                       n_layers=2, max_len=64, tp=False,
                                       compute_dtype=jnp.bfloat16)
        m2.set_optimizer(opt.SGD(lr=0.3, momentum=0.9))
        m2.compile([tx], is_train=True, use_graph=True)
        m2.load_states(p)
        W1 = m.blocks[0].attn.q_proj.W.data
        W2 = m2.blocks[0].attn.q_proj.W.data
        assert str(W2.dtype) == "bfloat16"
        np.testing.assert_array_equal(np.asarray(W1, dtype=np.float32),
                                      np.asarray(W2, dtype=np.float32))
        # fresh-optimizer resume path: momentum buffers must come back
        # in their true (attr-recorded) dtype, not the portable f32 the
        # archive stores
        mom_dtypes = {str(t.data.dtype)
                      for k, t in m2.optimizer._aux.items()
                      if k.endswith(":momentum")
                      and "tok_emb" not in k and "pos_emb" not in k
                      and "wg" not in k and "ln" not in k}
        assert "bfloat16" in mom_dtypes, mom_dtypes
        l1 = float(m(tx, ty)[1].data)
        l2 = float(m2(tx, ty)[1].data)
        assert abs(l1 - l2) < 5e-3, (l1, l2)
