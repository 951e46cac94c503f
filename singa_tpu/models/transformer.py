"""Decoder-only Transformer LM — the flagship long-context model.

TPU-first design (no reference equivalent; the reference's only attention
is composed from primitive ops in examples/qabot): pre-norm GPT-style
blocks whose attention is the fused flash kernel (ops/attention.py), with
three composable parallelism modes driven by the mesh:

- data parallel: batch over 'data' (DistOpt psum, like every model here);
- tensor parallel (``tp=True``): qkv and MLP-up as ColumnParallelLinear,
  out-proj and MLP-down as RowParallelLinear — heads shard over 'model',
  two all-reduces per block (Megatron layout); the vocab ends shard too:
  token embedding rows (VocabParallelEmbedding) and LM-head columns
  (ColumnParallelLinear), and with ``fused_head_chunk`` the chunked CE
  loss reduces across vocab shards online so per-rank head memory is
  V/tp without ever materialising logits;
- sequence parallel (``seq_axis='seq'``): tokens shard over 'seq'; the
  attention switches to ring attention (k/v rotate over ICI) and the
  caller sets ``Model.input_specs = [P('data', 'seq'), ...]``.
"""

from __future__ import annotations

import math

import numpy as np

from .. import autograd, layer, model
from ..parallel import tensor_parallel as tp_mod
from ..ops.attention import attention
from ..tensor import Tensor


class _Positions(autograd.Operator):
    """Global position ids for a (possibly sequence-sharded) token block."""

    differentiable = False

    def __init__(self, seq_axis=None):
        super().__init__()
        self.seq_axis = seq_axis

    def forward(self, ids):
        import jax.numpy as jnp
        from jax import lax
        from ..parallel.communicator import active_axis
        S = ids.shape[1]
        pos = jnp.arange(S)
        if self.seq_axis and active_axis(self.seq_axis):
            pos = pos + lax.axis_index(self.seq_axis) * S
        return jnp.broadcast_to(pos[None, :], ids.shape).astype(jnp.float32)


class MultiHeadAttention(layer.Layer):
    """Fused-attention MHA; optionally tensor-parallel over heads and/or
    sequence-parallel (ring) over tokens."""

    def __init__(self, d_model, n_heads, causal=True, tp=True,
                 seq_axis=None, axis_name="model", seq_mode="ring"):
        """``tp`` is accepted for API compatibility but the layout is
        mesh-driven: the parallel layers degrade to plain Linear on a
        size-1 'model' axis (or outside any mesh), so there is exactly one
        code path — and one state-dict layout — for every topology."""
        super().__init__()
        assert d_model % n_heads == 0
        self.d_model = d_model
        self.n_heads = n_heads
        self.head_dim = d_model // n_heads
        self.causal = causal
        self.seq_axis = seq_axis
        self.seq_mode = seq_mode
        # three separate column-parallel projections: a fused qkv matrix
        # would shard its columns across the [q|k|v] boundary
        self.q_proj = tp_mod.ColumnParallelLinear(d_model,
                                                  axis_name=axis_name)
        self.k_proj = tp_mod.ColumnParallelLinear(d_model,
                                                  axis_name=axis_name)
        self.v_proj = tp_mod.ColumnParallelLinear(d_model,
                                                  axis_name=axis_name)
        self.proj = tp_mod.RowParallelLinear(d_model, axis_name=axis_name)

    def forward(self, x):
        B, S = x.shape[0], x.shape[1]
        q = self.q_proj(x)                      # (B, S, d_local)
        k = self.k_proj(x)
        v = self.v_proj(x)
        d_local = q.shape[-1]
        h_local = d_local // self.head_dim      # heads on this shard

        def split_heads(t):
            t = autograd.reshape(t, (B, S, h_local, self.head_dim))
            return autograd.transpose(t, (0, 2, 1, 3))  # (B, H, S, D)

        out = attention(split_heads(q), split_heads(k), split_heads(v),
                        causal=self.causal, seq_axis=self.seq_axis,
                        seq_mode=self.seq_mode)
        out = autograd.transpose(out, (0, 2, 1, 3))
        out = autograd.reshape(out, (B, S, d_local))
        return self.proj(out)


class TransformerBlock(layer.Layer):
    def __init__(self, d_model, n_heads, d_ff=None, causal=True, tp=True,
                 seq_axis=None, moe=None, moe_top_k=None,
                 moe_capacity_factor=1.25, seq_mode="ring"):
        """``moe``: number of experts; replaces the dense FFN with a
        :class:`~singa_tpu.parallel.moe.MoEFFN` sharded over the mesh
        'expert' axis (``self.mlp.aux_loss`` is valid only inside the
        same train_one_batch trace). ``moe_top_k`` defaults to 2 clamped
        to the expert count (so moe=1 means Switch-style top-1)."""
        super().__init__()
        d_ff = d_ff or 4 * d_model
        self.ln1 = layer.LayerNorm()
        self.attn = MultiHeadAttention(d_model, n_heads, causal, tp,
                                       seq_axis, seq_mode=seq_mode)
        self.ln2 = layer.LayerNorm()
        if moe:
            from ..parallel.moe import MoEFFN
            top_k = moe_top_k if moe_top_k is not None else min(2, moe)
            self.mlp = MoEFFN(moe, d_ff, top_k=top_k,
                              capacity_factor=moe_capacity_factor)
        else:
            self.mlp = tp_mod.TPMLP(d_ff, d_model, activation="gelu")

    def forward(self, x):
        x = autograd.add(x, self.attn(self.ln1(x)))
        return autograd.add(x, self.mlp(self.ln2(x)))


class TransformerLM(model.Model):
    """GPT-style language model with next-token loss.

    ``train_one_batch(ids, targets)`` takes float tensors of token ids and
    target ids, both (B, S) ((B, S/n) per shard under sequence parallel).
    """

    def __init__(self, vocab_size, d_model=128, n_heads=4, n_layers=2,
                 max_len=1024, causal=True, tp=True, seq_axis=None,
                 remat=False, moe=None, moe_aux_weight=0.01,
                 moe_top_k=None, moe_capacity_factor=1.25,
                 seq_mode="ring", fused_head_chunk=None,
                 compute_dtype=None):
        """``moe``: experts per block (MoE FFN over the 'expert' mesh
        axis); the blocks' load-balance aux losses join the training loss
        scaled by ``moe_aux_weight``. ``moe_top_k`` defaults to
        min(2, moe).

        ``compute_dtype`` (e.g. ``jnp.bfloat16``): cast the summed
        embeddings to this dtype, so every downstream layer initialises
        its params in it and the whole transformer stack (attention
        matmuls included) runs in the MXU's native precision — the LM
        counterpart of feeding a bf16 input to the CNN zoo. Embedding
        tables stay f32 (the gather is bandwidth-, not MXU-bound), norm
        stats compute in f32 as always, and both loss paths upcast to
        f32 before the softmax."""
        super().__init__()
        self.compute_dtype = compute_dtype
        self.vocab_size = vocab_size
        self.d_model = d_model
        # remat: rematerialize each block in backward (jax.checkpoint) —
        # activation memory O(n_layers * block-boundary) instead of
        # O(n_layers * everything), the standard long-context trade
        self.remat = remat
        self.moe = moe
        self.moe_aux_weight = moe_aux_weight
        self.fused_head_chunk = fused_head_chunk
        # vocab-parallel ends: token embedding rows and head columns
        # shard over 'model' (Megatron layout) — at real vocab sizes the
        # head is the single largest tensor, so it must not replicate.
        # Both degrade to plain layers outside a mesh with the SAME
        # full-shape state dict, so there is one layout everywhere.
        # pos_emb stays replicated: max_len·D is small and every rank
        # reads every row.
        self.tok_emb = tp_mod.VocabParallelEmbedding(vocab_size, d_model)
        self.pos_emb = layer.Embedding(max_len, d_model)
        self._pos = _Positions(seq_axis)
        self.blocks = [TransformerBlock(
            d_model, n_heads, causal=causal, tp=tp, seq_axis=seq_axis,
            moe=moe, moe_top_k=moe_top_k,
            moe_capacity_factor=moe_capacity_factor, seq_mode=seq_mode)
            for i in range(n_layers)]
        self.ln_f = layer.LayerNorm()
        self.head = tp_mod.ColumnParallelLinear(vocab_size,
                                                gather_output=True)
        self.loss_fn = layer.SoftMaxCrossEntropy()

    def _hidden(self, ids):
        pos = self._pos(ids)
        x = autograd.add(self.tok_emb(ids), self.pos_emb(pos))
        if self.compute_dtype is not None:
            x = autograd.astype(x, self.compute_dtype)
        for blk in self.blocks:
            x = autograd.checkpoint(blk, x) if self.remat else blk(x)
        return self.ln_f(x)

    def forward(self, ids):
        return self.head(self._hidden(ids))     # (B, S, vocab)

    def train_one_batch(self, ids, targets):
        if self.fused_head_chunk:
            # large-vocab mode: loss straight from the hidden states via
            # the chunked fused CE head — the (B,S,V) logits are never
            # materialised in the TRAINING step (forward/eval still
            # produces them through the same shared head params).
            from ..ops.losses import fused_softmax_cross_entropy
            h = self._hidden(ids)
            # params only, no forward: running head(h) here would
            # materialise the full (B,S,V) logits the fused mode exists
            # to avoid
            self.head.ensure_initialized(h)
            # the layer's own sharded-check decides whether to turn on
            # the cross-shard reduction (one source of truth)
            ax = self.head.axis_name if self.head._sharded() else None
            loss = fused_softmax_cross_entropy(
                h, self.head.W, self.head.b, targets,
                self.fused_head_chunk, axis_name=ax)
            out = None
        else:
            logits = self.forward(ids)
            if self.compute_dtype is not None:
                # softmax over a 32k vocab needs f32 range
                logits = autograd.astype(logits, np.float32)
            B, S, V = logits.shape
            flat = autograd.reshape(logits, (B * S, V))
            onehot = autograd.onehot(-1, targets, self.vocab_size)
            oh_flat = autograd.reshape(onehot, (B * S, V))
            loss = autograd.softmax_cross_entropy(flat, oh_flat)
            out = logits
        if self.moe:
            w = Tensor(data=np.asarray(self.moe_aux_weight, np.float32),
                       device=ids.device, requires_grad=False)
            for blk in self.blocks:
                loss = autograd.add(loss, autograd.mul(blk.mlp.aux_loss, w))
        self.optimizer(loss)
        # fused mode has no logits to return: the TOTAL loss (incl. moe
        # aux) fills the predictions slot so both outputs agree with
        # what the optimizer stepped on
        if out is None:
            out = loss
        return out, loss


def create_model(vocab_size=256, **kwargs):
    return TransformerLM(vocab_size, **kwargs)


__all__ = ["TransformerLM", "TransformerBlock", "MultiHeadAttention",
           "create_model"]


def _lm_decode_tensors(m):
    """Ordered (name, Tensor) leaves the decode functions need."""
    out = []
    for i, blk in enumerate(m.blocks):
        at = blk.attn
        leaves = [("ln1_s", blk.ln1.scale), ("ln1_b", blk.ln1.bias),
                  ("wq", at.q_proj.W), ("bq", at.q_proj.b),
                  ("wk", at.k_proj.W), ("bk", at.k_proj.b),
                  ("wv", at.v_proj.W), ("bv", at.v_proj.b),
                  ("wo", at.proj.W), ("bo", at.proj.b),
                  ("ln2_s", blk.ln2.scale), ("ln2_b", blk.ln2.bias)]
        if hasattr(blk.mlp, "up"):
            leaves += [("w_up", blk.mlp.up.W), ("b_up", blk.mlp.up.b),
                       ("w_dn", blk.mlp.down.W), ("b_dn", blk.mlp.down.b)]
        else:
            # MoE FFN: all expert groups gathered to host like the rest
            # of the decode state; "wg" flags the MoE path downstream
            leaves += [("wg", blk.mlp.wg), ("w1", blk.mlp.w1),
                       ("b1", blk.mlp.b1), ("w2", blk.mlp.w2),
                       ("b2", blk.mlp.b2)]
        out.append(leaves)
    return out


# the block leaves the serve programs use through ``c()`` — the matrix
# products' operands and their biases. LayerNorm's leaves are used in
# float32 (``_ln``) and the MoE banks go to the MoE op as they are.
_CAST_ROLES = ("wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo",
               "w_up", "b_up", "w_dn", "b_dn")


def _whole(t):
    """A parameter's array where the decode tree can read it as it lies:
    one device holds all of it. An array spread over a mesh is gathered
    through the host, once."""
    import jax
    import jax.numpy as jnp
    a = t.data
    if isinstance(a, jax.Array) and len(a.sharding.device_set) > 1:
        return jnp.asarray(np.asarray(jax.device_get(a)))
    return a


def _lm_decode_params(m, block_dtype=None):
    """The trained weights as the ONE pytree the pure decode functions
    take (``generate()`` and the serve adapter both read it), formed on
    the device from the live arrays: the six leaves outside the blocks,
    and ``blocks`` — one dict that holds, role by role, the layers in
    order (:func:`_layer` takes layer ``l``'s). The two forms a role
    takes are what the chip showed (PERF.md §6, PR 34):

    - a VECTOR role (biases, LayerNorm's scales and biases: 10 of a
      block's 16 leaves) is ONE array with a leading layer axis,
      ``bq (L, D)``. The host pays for every buffer of every call, and
      these are most of the buffers and none of the bytes;
    - a MATRIX role stays a list of ``L`` arrays. XLA prefetches a
      parameter of its own into the fast memory while the ops before
      its use run; of a leaf stacked over the layers it prefetches
      nothing use by use — the products read their slice from HBM
      inside the fusion, and the decode program took 0.6 ms longer
      than with a buffer a matrix, more than the buffers cost.

    ``block_dtype`` casts the matrix products' operands and biases
    (``_CAST_ROLES``) as the tree is formed, so that a serve program
    finds them in its compute dtype and casts nothing a tick;
    LayerNorm's leaves, the tables and the head keep their dtype.
    ``None`` (what ``generate()`` asks for) casts nothing.

    The tree is CACHED against the identity of the live param arrays
    (jax arrays are immutable, so a train step rebinds every leaf): a
    serving loop builds it once, not per call — one tree a
    ``block_dtype`` asked for. It owns its leaves (a train step donates
    the model's arrays; an engine's tree outlives it), and the cache
    holds references to the arrays it was built from, so after a train
    step one stale weight copy lives until the next call refreshes it —
    an inference-convenience tradeoff, documented here. The tree lies
    where the model's arrays lie; mesh-sharded state is gathered through
    the host first (generation is a single-device inference
    convenience)."""
    import jax.numpy as jnp

    per_block = _lm_decode_tensors(m)
    ends = dict(tok=m.tok_emb.W, pos=m.pos_emb.W, lnf_s=m.ln_f.scale,
                lnf_b=m.ln_f.bias, head_w=m.head.W, head_b=m.head.b)
    live = [t.data for leaves in per_block for _, t in leaves] \
        + [t.data for t in ends.values()]
    pin = getattr(m, "_decode_params_pin", None)
    if pin is None or len(pin[0]) != len(live) or \
            not all(a is b for a, b in zip(pin[0], live)):
        pin = m._decode_params_pin = (live, {})
    key = None if block_dtype is None else jnp.dtype(block_dtype)
    if all(t.data.dtype == key for name, t in per_block[0]
           if name in _CAST_ROLES):
        key = None      # nothing to cast: the tree generate() reads
    P = pin[1].get(key)
    if P is None:
        blocks = {}
        for of_role in zip(*per_block):     # one role, layer by layer
            role = of_role[0][0]
            leaves = [_whole(t) for _, t in of_role]
            dt = leaves[0].dtype
            if key is not None and role in _CAST_ROLES and \
                    jnp.issubdtype(dt, jnp.floating):
                dt = key
            blocks[role] = jnp.stack(leaves, dtype=dt) \
                if leaves[0].ndim == 1 else \
                [jnp.array(a, dtype=dt, copy=True) for a in leaves]
        P = pin[1][key] = dict(
            {name: jnp.array(_whole(t), copy=True)
             for name, t in ends.items()},
            blocks=blocks)
    return P


def _layer(blocks, l):
    """Layer ``l``'s leaves of the ``blocks`` tree: every role holds its
    layers in order, as a stacked array's leading axis or as a list."""
    return {role: leaves[l] for role, leaves in blocks.items()}


def _ln(x, s, b, eps=1e-5):
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * s + b).astype(x.dtype)


def _split_heads(t, n_heads):
    B, S, D = t.shape
    return t.reshape(B, S, n_heads, D // n_heads).transpose(0, 2, 1, 3)


def _merge_heads(t):
    B, H, S, hd = t.shape
    return t.transpose(0, 2, 1, 3).reshape(B, S, H * hd)


def _generate(self, ids, max_new_tokens, temperature=1.0, top_k=None,
              seed=0):
    """Autoregressive decoding with a static-shape KV cache.

    One causal prefill pass encodes the prompt and fills per-layer
    key/value caches; a ``lax.scan`` then emits one token per tick,
    attending against the cache — O(L) per new token instead of
    re-running the full O(L²) forward (no reference counterpart; its
    rnn examples re-run full forwards).

    ``ids``: Tensor or array (B, S0) of prompt token ids (float or int).
    ``temperature=0`` is greedy argmax; otherwise softmax sampling with
    optional ``top_k``. Returns a (B, S0 + max_new_tokens) numpy array.
    Single-device inference path: mesh-sharded weights are host-gathered
    per call (so freshly trained values are always used), but the
    compiled decode program is CACHED per shape signature — repeated
    calls pay no retrace. Causal models only (AR decoding is undefined
    for bidirectional attention). MoE models decode through the training
    MoE kernel (same routing/combine math, expert axis inactive) with
    DROP-FREE capacity; greedy decode equals the full forward exactly
    whenever the forward itself drops no tokens.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    if not self.blocks[0].attn.causal:
        raise NotImplementedError(
            "generate() requires a causal model; this TransformerLM was "
            "built with causal=False")
    arr = ids.data if isinstance(ids, Tensor) else ids
    prompt = jnp.asarray(np.asarray(jax.device_get(arr)), jnp.int32)
    if max_new_tokens <= 0:
        return np.asarray(prompt)
    B, S0 = prompt.shape
    P = _lm_decode_params(self)
    n_heads = self.blocks[0].attn.n_heads
    n_layers = len(self.blocks)
    hd = self.d_model // n_heads
    L = S0 + max_new_tokens
    assert L <= P["pos"].shape[0], \
        f"prompt+new tokens ({L}) exceeds max_len {P['pos'].shape[0]}"
    scale = 1.0 / math.sqrt(hd)
    mlp0 = self.blocks[0].mlp
    act = jax.nn.gelu \
        if getattr(mlp0, "activation", "gelu") == "gelu" else jax.nn.relu
    if self.moe:
        # decode reuses the training MoE kernel (same routing/combine
        # math) with the expert axis inactive — the host-gathered params
        # hold every expert — but with DROP-FREE capacity: cf=E makes
        # C = k*T, so no token of the tiny per-step set is ever dropped
        # (training's cf is tuned for joint batches; applied to T=B
        # decode steps it would silently zero some tokens' FFN output).
        # Exact greedy parity with a full forward therefore holds
        # whenever the forward itself drops nothing.
        from ..parallel.moe import _MoEFFN
        moe_op = _MoEFFN(mlp0.n_experts, mlp0.top_k,
                         float(mlp0.n_experts), None, ())

    def mlp_apply(p, h2):
        if "wg" in p:
            Bq, Sq, Dq = h2.shape
            y, _aux = moe_op.forward(h2.reshape(-1, Dq), p["wg"],
                                     p["w1"], p["b1"], p["w2"], p["b2"])
            return y.reshape(h2.shape)
        return act(h2 @ p["w_up"] + p["b_up"]) @ p["w_dn"] + p["b_dn"]

    sig = (B, S0, max_new_tokens, float(temperature), top_k)
    cache = getattr(self, "_decode_cache", None)
    if cache is None:
        cache = self._decode_cache = {}
    run = cache.get(sig)
    if run is None:
        def embed(Pq, tok_ids, pos_ids):
            return (jnp.take(Pq["tok"], tok_ids, axis=0)
                    + jnp.take(Pq["pos"], pos_ids, axis=0))

        def block_prefill(p, x):
            h = _ln(x, p["ln1_s"], p["ln1_b"])
            q = _split_heads(h @ p["wq"] + p["bq"], n_heads)
            k = _split_heads(h @ p["wk"] + p["bk"], n_heads)
            v = _split_heads(h @ p["wv"] + p["bv"], n_heads)
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            mask = jnp.tril(jnp.ones((S0, S0), bool))
            att = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
            o = _merge_heads(jnp.einsum("bhqk,bhkd->bhqd", att, v))
            x = x + (o @ p["wo"] + p["bo"])
            h2 = _ln(x, p["ln2_s"], p["ln2_b"])
            x = x + mlp_apply(p, h2)
            return x, k, v

        def block_decode(p, x, kc, vc, pos):
            h = _ln(x, p["ln1_s"], p["ln1_b"])          # (B, 1, D)
            q = _split_heads(h @ p["wq"] + p["bq"], n_heads)
            k = _split_heads(h @ p["wk"] + p["bk"], n_heads)
            v = _split_heads(h @ p["wv"] + p["bv"], n_heads)
            kc = lax.dynamic_update_slice(kc, k, (0, 0, pos, 0))
            vc = lax.dynamic_update_slice(vc, v, (0, 0, pos, 0))
            s = jnp.einsum("bhqd,bhkd->bhqk", q, kc) * scale
            valid = jnp.arange(L)[None, None, None, :] <= pos
            att = jax.nn.softmax(jnp.where(valid, s, -jnp.inf), -1)
            o = _merge_heads(jnp.einsum("bhqk,bhkd->bhqd", att, vc))
            x = x + (o @ p["wo"] + p["bo"])
            h2 = _ln(x, p["ln2_s"], p["ln2_b"])
            x = x + mlp_apply(p, h2)
            return x, kc, vc

        def sample(logits, key):
            # the ONE shared sampling path (models/decode.py): greedy /
            # temperature / top-k math lives there, tested once, shared
            # with char_rnn.sample and the serving engine
            from .decode import sample_logits_jax
            return sample_logits_jax(logits, temperature, top_k, key)

        @jax.jit
        def run(Pq, prompt, key):
            x = embed(Pq, prompt, jnp.arange(S0)[None, :])
            caches = []
            for l in range(n_layers):
                x, k, v = block_prefill(_layer(Pq["blocks"], l), x)
                kc = jnp.zeros((B, n_heads, L, hd), k.dtype)
                vc = jnp.zeros_like(kc)
                kc = lax.dynamic_update_slice(kc, k, (0, 0, 0, 0))
                vc = lax.dynamic_update_slice(vc, v, (0, 0, 0, 0))
                caches.append((kc, vc))
            hN = _ln(x, Pq["lnf_s"], Pq["lnf_b"])
            logits0 = hN[:, -1] @ Pq["head_w"] + Pq["head_b"]
            key, sub = jax.random.split(key)
            tok0 = sample(logits0, sub)

            def step(carry, _):
                tok, pos, caches, key = carry
                x = embed(Pq, tok[:, None], pos.reshape(1, 1))
                new_caches = []
                for l, (kc, vc) in enumerate(caches):
                    x, kc, vc = block_decode(_layer(Pq["blocks"], l), x,
                                             kc, vc, pos[0])
                    new_caches.append((kc, vc))
                hN = _ln(x, Pq["lnf_s"], Pq["lnf_b"])
                logits = hN[:, -1] @ Pq["head_w"] + Pq["head_b"]
                key, sub = jax.random.split(key)
                nxt = sample(logits, sub)
                return (nxt, pos + 1, tuple(new_caches), key), tok

            init = (tok0, jnp.asarray([S0]), tuple(caches), key)
            (last, _, _, _), toks = lax.scan(
                step, init, None, length=max_new_tokens - 1)
            toks = jnp.concatenate([toks.transpose(1, 0), last[:, None]],
                                   1)
            return toks

        cache[sig] = run

    key = jax.random.PRNGKey(seed)
    new = run(P, prompt, key)
    return np.concatenate([np.asarray(prompt), np.asarray(new)], axis=1)


TransformerLM.generate = _generate


class _LMServeAdapter:
    """Ring-cache prefill/decode adapter: the TransformerLM half of the
    ``singa_tpu.serving.ServingEngine`` contract.

    Exposes the two pure fixed-shape functions the engine AOT-compiles —

    - ``prefill_fn``: ``(P, cache, tokens (B,S), lengths, slot_ids,
      valid) -> (cache, logits (B,V))`` — a fixed-width batch of padded
      prompts runs ONE causal forward and writes each prompt's k/v rows
      into its assigned slot of the ring cache (``valid=False`` rows are
      batch padding: computed, never written);
    - ``decode_fn``: ``(P, cache, tokens (W,), positions (W,),
      active (W,)) -> (cache, logits (W,V))`` — one token for every slot
      in O(1): write the new k/v at ``pos % max_len``, attend over the
      ring (``serving.kv_cache``), return next-token logits.

    Freed-slot hygiene is arithmetic, not bookkeeping: a dead slot's
    stale rows sit at ring indices the position mask only reaches once
    the NEW occupant has overwritten them (prefill covers ``[0, len)``,
    decode writes index ``p`` in the same tick the mask first admits
    ``p``), so no cross-request leakage is possible by construction.

    Mixed precision follows the training policy's contract: embeddings
    and the head stay f32, block weights and the cache run in the
    policy's compute dtype (bf16 serving out of the box), attention
    softmax and the returned logits are f32.

    Quantized serving (``singa_tpu.quant`` presets): under
    ``"int8_weight_only"`` every block matmul weight is quantized ONCE
    at engine build into an int8 payload + per-output-channel fp32
    scale and dequantized in graph at its use site (embeddings and the
    head stay f32 — they are the parity-critical ends); under
    ``"fp8_serving"`` block weights are rounded through the e4m3 grid
    inside the compiled programs. Either way a ``cache_quant`` policy
    runs the ring KV cache in int8 with per-(slot, ring-index) scale
    rows — ``kv_cache`` dequantizes into the unchanged f32 softmax.
    """

    # block weights eligible for int8 weight-only quantization (2-D
    # matmul operands; biases/LN stay f32, MoE expert banks pass
    # through untouched)
    _QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_up", "w_dn")
    # build_engine's honored-or-refused contract for quantized policies
    supports_weight_quant = True
    supports_cache_quant = True
    # the transformer's KV state is pure per-position rows — exactly
    # what the paged block pool holds; the char-rnn's (h,c) carry is
    # not, so ITS adapter leaves this False and the engine declines
    # kv_layout="paged" loudly back to the ring
    supports_paged = True
    # GSPMD sharded serving (parallel/gspmd.py): the adapter can map
    # its param/cache trees to NamedSharding specs; the char-rnn's
    # (h,c) adapter cannot, so compile_serving(model_shards=) on it is
    # a typed decline
    supports_sharded = True

    def __init__(self, m, policy=None):
        self.m = m
        self.policy = policy
        at = m.blocks[0].attn
        if not at.causal:
            raise NotImplementedError(
                "serving needs a causal model; this TransformerLM was "
                "built with causal=False")
        self.n_heads = at.n_heads
        self.head_dim = m.d_model // self.n_heads
        self.scale = 1.0 / math.sqrt(self.head_dim)

    def _compute_dtype(self):
        import jax.numpy as jnp
        if self.policy is not None and \
                self.policy.compute_dtype is not None:
            return jnp.dtype(self.policy.compute_dtype)
        cd = self.m.compute_dtype
        return jnp.dtype(cd) if cd is not None else jnp.dtype(jnp.float32)

    def params(self):
        """The tree both serve programs take as ``P``
        (:func:`_lm_decode_params`: the vector roles one stacked leaf
        each, the matrices a leaf a layer). Where ``c()`` would do
        nothing to a block weight but cast it — no ``weight_quant``, no
        ``compute_quant`` — the cast is made here, once, and the
        programs read the compute dtype; under a quantized policy the
        leaves stay as they are and ``c()`` does the work at the use
        site."""
        from ..quant.core import dequant_params_scope
        weight_quant = getattr(self.policy, "weight_quant", None)
        plain = weight_quant is None and \
            getattr(self.policy, "compute_quant", None) is None
        with dequant_params_scope(self.m):
            # a model already weight-quantized in place hands the
            # engine its DEQUANTIZED weights here (concrete arrays at
            # build time; re-quantized below under an int8 policy)
            P = _lm_decode_params(
                self.m, self._compute_dtype() if plain else None)
        if weight_quant == "int8":
            from ..quant import core as _qcore
            import jax.numpy as jnp

            def quantized(w):
                q, s = _qcore.quantize_int8(
                    w, _qcore.channel_axis(w.shape))
                return {"q": q, "s": s}

            blocks = dict(P["blocks"])
            for key in self._QUANT_KEYS:
                layers = blocks.get(key)
                if layers is not None and layers[0].ndim == 2 and \
                        jnp.issubdtype(layers[0].dtype, jnp.floating):
                    blocks[key] = [quantized(w) for w in layers]
            P = dict(P, blocks=blocks)
        return P

    def _cache_dtype(self):
        import jax.numpy as jnp
        if getattr(self.policy, "cache_quant", None) == "int8":
            return jnp.dtype(jnp.int8)
        return self._compute_dtype()

    def validate(self, prefill_len, max_len):
        """Engine-construction-time limits the engine itself can't see:
        a prompt longer than the positional-embedding table would crash
        the first compiled prefill with a shape error; fail typed and
        early instead. (decode clips positions to the table: past
        ``max_len`` tokens this adapter's rings, all ``max_len`` long,
        have wrapped, and that wrap is the only windowing this model
        has — an adapter whose layers window by design gives those
        layers rings of the window's length, ``models/cohere_moe.py`` —
        but prefill indexes ``pos[:S]`` directly.)"""
        table = int(self.m.pos_emb.input_dim)
        if int(prefill_len) > table:
            raise ValueError(
                f"prefill_len {prefill_len} exceeds this model's "
                f"positional-embedding table ({table} rows): rebuild "
                f"the model with max_len >= {prefill_len} or lower "
                "prefill_len")

    def init_cache(self, slots, max_len):
        from ..serving import kv_cache
        return [kv_cache.init_cache(slots, self.n_heads, max_len,
                                    self.head_dim, self._cache_dtype())
                for _ in self.m.blocks]

    def _mlp_apply(self):
        import jax
        mlp0 = self.m.blocks[0].mlp
        act = jax.nn.gelu \
            if getattr(mlp0, "activation", "gelu") == "gelu" \
            else jax.nn.relu
        if self.m.moe:
            from ..parallel.moe import _MoEFFN
            # drop-free capacity, expert axis inactive — the same decode
            # convention generate() documents
            moe_op = _MoEFFN(mlp0.n_experts, mlp0.top_k,
                             float(mlp0.n_experts), None, ())
        else:
            moe_op = None

        def mlp_apply(p, h2, c):
            if "wg" in p:
                Bq, Sq, Dq = h2.shape
                y, _aux = moe_op.forward(h2.reshape(-1, Dq), p["wg"],
                                         p["w1"], p["b1"], p["w2"],
                                         p["b2"])
                return y.reshape(h2.shape).astype(h2.dtype)
            return (act(h2 @ c(p["w_up"]) + c(p["b_up"]))
                    @ c(p["w_dn"]) + c(p["b_dn"]))

        return mlp_apply

    def _block(self):
        """The ONE transformer-block body both serve programs share
        (LN → QKV → attend → out-proj → LN → MLP). Only the
        attention+cache step differs between prefill and decode, so it
        is injected: ``attend(q, k, v, level) -> (merged_out, level)``.
        One copy means the two compiled programs cannot drift from each
        other."""
        import jax.numpy as jnp
        n_heads = self.n_heads
        cdt = self._compute_dtype()
        mlp_apply = self._mlp_apply()
        fp8_w = getattr(self.policy, "compute_quant", None) \
            if getattr(self.policy, "weight_quant", None) is None else None
        if fp8_w is not None and fp8_w not in ("e4m3", "e5m2"):
            fp8_w = None        # int8 fake-quant policies serve as-is

        def c(a):
            if isinstance(a, dict):
                # int8 weight-only payload from params(): the in-graph
                # dequant XLA fuses into the consuming matmul — the
                # threaded params stay int8, only this use site is fp
                from ..quant import core as _qcore
                return _qcore.dequantize_int8(a["q"], a["s"], cdt)
            if not jnp.issubdtype(a.dtype, jnp.floating):
                return a
            a = a.astype(cdt)
            if fp8_w is not None and a.ndim == 2:
                # fp8_serving: matmul weights rounded through the e4m3
                # grid inside the compiled programs (biases/LN stay in
                # the compute dtype — tiny and fragile)
                from ..quant import core as _qcore
                a = _qcore.fake_cast(a, fp8_w)
            return a

        def block(p, x, level, attend):
            h = _ln(x, p["ln1_s"], p["ln1_b"])
            q = _split_heads(h @ c(p["wq"]) + c(p["bq"]), n_heads)
            k = _split_heads(h @ c(p["wk"]) + c(p["bk"]), n_heads)
            v = _split_heads(h @ c(p["wv"]) + c(p["bv"]), n_heads)
            o, level = attend(q, k, v, level)
            x = x + (o.astype(x.dtype) @ c(p["wo"]) + c(p["bo"]))
            return x + mlp_apply(p, _ln(x, p["ln2_s"], p["ln2_b"]), c), \
                level

        return block, c, cdt

    def prefill_fn(self):
        import jax
        import jax.numpy as jnp
        from ..serving import kv_cache
        scale = self.scale
        block, _c, cdt = self._block()

        def fn(P, cache, tokens, lengths, slot_ids, valid):
            B, S = tokens.shape
            x = (jnp.take(P["tok"], tokens, axis=0)
                 + P["pos"][None, :S]).astype(cdt)
            causal = jnp.tril(jnp.ones((S, S), bool))[None, None]

            def attend(q, k, v, level):
                s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                               k.astype(jnp.float32)) * scale
                att = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
                o = _merge_heads(jnp.einsum("bhqk,bhkd->bhqd", att,
                                            v.astype(jnp.float32)))
                # B is a static prefill-batch width: this unrolls into
                # B masked slot writes inside the ONE compiled program
                for b in range(B):
                    level = kv_cache.write_prompt(
                        level, slot_ids[b], k[b], v[b], valid[b])
                return o, level

            new_cache = []
            for l, level in enumerate(cache):
                x, level = block(_layer(P["blocks"], l), x, level,
                                 attend)
                new_cache.append(level)
            hN = _ln(x, P["lnf_s"], P["lnf_b"])
            h_last = jnp.take_along_axis(
                hN, (lengths - 1).astype(jnp.int32)[:, None, None]
                .clip(0), axis=1)[:, 0]
            logits = (h_last.astype(jnp.float32) @ P["head_w"]
                      + P["head_b"])
            return new_cache, logits

        return fn

    # -- paged block-pool programs ------------------------------------------
    def init_pool(self, n_blocks, block_size):
        from ..serving import kv_cache
        return [kv_cache.init_pool(n_blocks, self.n_heads, block_size,
                                   self.head_dim, self._cache_dtype())
                for _ in self.m.blocks]

    def _paged_core(self):
        """The ONE paged transformer pass both paged programs share:
        embed ``(R, Q)`` tokens at absolute positions ``pos_abs``,
        write each layer's fresh k/v rows into the pool through the
        per-row block tables (``wmask`` drops padding/inactive rows),
        attend position-exactly (``cache position <= query position`` —
        a query sees the cached prefix, earlier fresh tokens, and
        itself), and return the final-LN hidden states. Chunked prefill
        and the K-token speculative verify are the SAME math at
        different (R, Q); one body means they cannot drift."""
        import jax.numpy as jnp
        from ..serving import kv_cache
        scale = self.scale
        block, _c, cdt = self._block()

        def core(P, pool, tables, tokens, pos_abs, wmask):
            pos_ids = jnp.minimum(pos_abs,
                                  P["pos"].shape[0] - 1)
            x = (jnp.take(P["tok"], tokens, axis=0)
                 + jnp.take(P["pos"], pos_ids, axis=0)).astype(cdt)

            def attend(q, k, v, level):
                level = kv_cache.write_rows(level, tables, k, v,
                                            pos_abs, wmask)
                o = kv_cache.attend_pages(q, level, tables, pos_abs,
                                          scale)
                return _merge_heads(o), level

            new_pool = []
            for l, level in enumerate(pool):
                x, level = block(_layer(P["blocks"], l), x, level,
                                 attend)
                new_pool.append(level)
            return new_pool, _ln(x, P["lnf_s"], P["lnf_b"])

        return core

    def paged_prefill_fn(self):
        """Chunked paged prefill: ``(P, pool, tables (B, n_pages),
        tokens (B, S) SUFFIX tokens, starts (B,) prefix-hit lengths,
        lengths (B,) suffix lengths, valid (B,)) -> (pool,
        logits (B, V))`` — a prefix-cache hit enters here with
        ``starts > 0`` and its suffix attending to the shared blocks
        it never recomputed."""
        import jax.numpy as jnp

        core = self._paged_core()

        def fn(P, pool, tables, tokens, starts, lengths, valid):
            B, S = tokens.shape
            pos_abs = starts.astype(jnp.int32)[:, None] \
                + jnp.arange(S, dtype=jnp.int32)[None, :]
            wmask = (jnp.arange(S, dtype=jnp.int32)[None, :]
                     < lengths.astype(jnp.int32)[:, None]) \
                & valid[:, None]
            pool, hN = core(P, pool, tables, tokens, pos_abs, wmask)
            h_last = jnp.take_along_axis(
                hN, (lengths - 1).astype(jnp.int32)[:, None, None]
                .clip(0), axis=1)[:, 0]
            logits = (h_last.astype(jnp.float32) @ P["head_w"]
                      + P["head_b"])
            return pool, logits

        return fn

    def paged_decode_fn(self):
        """Paged decode/verify: ``(P, pool, tables (W, n_pages),
        tokens (W, K), positions (W,) first-token positions,
        counts (W,) real tokens per row) -> (pool,
        logits (W, K, V))``. ``K == 1`` is plain one-token decode;
        ``K > 1`` scores a speculative draft row in ONE tick —
        ``logits[:, i]`` is the exact next-token distribution after
        token ``i``, which is what makes the host accept/reject walk
        token-identical to sequential greedy."""
        import jax.numpy as jnp

        core = self._paged_core()

        def fn(P, pool, tables, tokens, positions, counts):
            W, K = tokens.shape
            pos_abs = positions.astype(jnp.int32)[:, None] \
                + jnp.arange(K, dtype=jnp.int32)[None, :]
            wmask = jnp.arange(K, dtype=jnp.int32)[None, :] \
                < counts.astype(jnp.int32)[:, None]
            pool, hN = core(P, pool, tables, tokens, pos_abs, wmask)
            logits = (hN.astype(jnp.float32) @ P["head_w"]
                      + P["head_b"])
            return pool, logits

        return fn

    def decode_fn(self):
        import jax.numpy as jnp
        from ..serving import kv_cache
        scale = self.scale
        block, _c, cdt = self._block()

        def fn(P, cache, tokens, positions, active):
            positions = positions.astype(jnp.int32)
            # the learned position table is finite; a sequence decoding
            # past it holds the last embedding (every ring here is
            # max_len long and has wrapped by then: attention runs over
            # the last max_len tokens, by the wrap and not by a mask)
            pos_ids = jnp.minimum(positions, P["pos"].shape[0] - 1)
            x = (jnp.take(P["tok"], tokens, axis=0)
                 + jnp.take(P["pos"], pos_ids, axis=0))[:, None, :] \
                .astype(cdt)

            def attend(q, k, v, level):
                o, level = kv_cache.decode_token(
                    level, q, k[:, :, 0], v[:, :, 0], positions, active,
                    scale)
                return _merge_heads(o), level

            new_cache = []
            for l, level in enumerate(cache):
                x, level = block(_layer(P["blocks"], l), x, level,
                                 attend)
                new_cache.append(level)
            hN = _ln(x, P["lnf_s"], P["lnf_b"])[:, 0]
            logits = (hN.astype(jnp.float32) @ P["head_w"]
                      + P["head_b"])
            return new_cache, logits

        return fn


    # -- GSPMD sharded serving ----------------------------------------------
    def sharding_specs(self, part, P, cache, kv_layout):
        """PartitionSpec trees for this adapter's param dict and KV
        state over a (batch × model) partitioner — the ONE gspmd rule
        table; raises a typed
        :class:`~singa_tpu.parallel.gspmd.ShardingDecline` for any
        dimension the mesh cannot split honestly (heads, vocab, MLP
        hidden, MoE expert banks)."""
        from ..parallel import gspmd
        param_specs = gspmd.lm_param_specs(part, P, self.n_heads)
        cache_specs = gspmd.pool_specs(part, cache) \
            if kv_layout == "paged" else \
            gspmd.ring_cache_specs(part, cache)
        return param_specs, cache_specs


def _decode_adapter(self, policy=None):
    """The serving engine's entry point (``Model.compile_serving``
    routes autoregressive models here): a :class:`_LMServeAdapter` over
    a tree formed from this model's live weights
    (:func:`_lm_decode_params`)."""
    return _LMServeAdapter(self, policy=policy)


TransformerLM.decode_adapter = _decode_adapter
