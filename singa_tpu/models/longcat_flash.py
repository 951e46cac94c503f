"""Shortcut-connected sparse-expert LM with latent attention (the
LongCat-Flash family's language model).

A layer holds TWO sub-blocks and ONE expert layer::

    for i in 0, 1:
        x += MLA_i(N1_i x)
        u  = N2_i x
        if i == 0:  s = MoE(u)          # kept aside: the shortcut
        x += FFN_i(u)
    x += s

so the expert layer reads the first sub-block's normed stream and lands
after the second (deployed, its exchange hides under the second
attention and dense FFN). Attention is multi-head LATENT attention: a
token's keys and values are all functions of one row ``[c (kv_lora_rank);
k_r (qk_rope_head_dim)]``, which is what a serving cache keeps — one row
a token for every head (``serving/kv_cache.LatentLevel``). Two forms of
the same attention, which must agree:

- expanded (eval forward, prefill): per-head keys ``[W_K c; k_r]`` and
  values ``W_V c`` are made from the rows and attended to plainly
  (:func:`mla_expanded`);
- absorbed (decode): ``W_K`` goes into the query and ``W_V`` behind the
  attention, so the cached rows ARE the keys and, by their first
  columns, the values (:func:`mla_absorbed`), read once for all heads.

The expert layer is :func:`singa_tpu.parallel.moe.expert_share_ffn` with
a softmax router over the routed experts AND the identity ("zero
compute") experts behind them, a bias for the choice only, weights that
are the picks' own probabilities times a factor (no renormalisation),
and this chip's share of the routed experts. No shared expert; the head
is untied. RMSNorm everywhere; rotary positions (interleaved pairs) on
the ``qk_rope_head_dim`` columns only.

The layer is written ONCE, as pure functions of (config, params): the
eval forward and the serve adapter's prefill and decode call
:func:`layer_apply` and differ only in the ``attend`` they hand it.
Inference only. A model holds ONE chip's share of a stated deployment:
``num_experts`` / ``vocab_size`` count what lives here, the router keeps
its full width ``router_width`` (routed + identity experts) and
``experts_held_from`` says which experts these are.
"""

from __future__ import annotations

import math

from .. import layer, model
from ..layer import _param
from ..parallel.moe import Route, expert_share_ffn, rows_in_blocks
from . import cohere_moe as _cm
from .cohere_moe import (ByReferenceAdapter, DrawnBeforeCompile, EvalForward,
                         embed, masked_attention, moe_stats_recorder,
                         rope_interleaved)

STAT_NAMES = ("pairs_here", "pairs_absent", "pairs_zero", "experts_touched")
_NORMS = ("n1", "n2", "q_norm", "kv_norm")
_BANKS = ("w_gate", "w_up", "w_down")


class Config:
    """The static half of the layer: what :func:`layer_apply` closes
    over. Hashable by identity; every field is a python number."""

    def __init__(self, *, hidden_size, num_layers, num_heads, q_lora_rank,
                 kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                 v_head_dim, ffn_hidden_size, expert_ffn_hidden_size,
                 num_experts, router_width, zero_expert_num, top_k,
                 routed_scaling_factor, experts_held_from, rope_theta,
                 rms_norm_eps, mla_scale_q_lora=True,
                 mla_scale_kv_lora=True):
        self.hidden_size, self.num_layers = int(hidden_size), int(num_layers)
        self.num_heads = int(num_heads)
        self.q_lora_rank, self.kv_lora_rank = int(q_lora_rank), \
            int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.ffn_hidden_size = int(ffn_hidden_size)
        self.expert_ffn_hidden_size = int(expert_ffn_hidden_size)
        self.num_experts, self.router_width = int(num_experts), \
            int(router_width)
        self.zero_expert_num, self.top_k = int(zero_expert_num), int(top_k)
        self.experts_held_from = int(experts_held_from)
        self.rope_theta, self.rms_norm_eps = float(rope_theta), \
            float(rms_norm_eps)
        # softmax scores, the picks' own probabilities times the factor
        self.route = Route("softmax", False, float(routed_scaling_factor))
        # the two low-rank streams are scaled back up to the hidden
        # size's magnitude
        self.q_scale = math.sqrt(self.hidden_size / self.q_lora_rank) \
            if mla_scale_q_lora else 1.0
        self.kv_scale = math.sqrt(self.hidden_size / self.kv_lora_rank) \
            if mla_scale_kv_lora else 1.0
        routed = self.router_width - self.zero_expert_num
        if self.experts_held_from + self.num_experts > routed:
            raise ValueError(
                f"experts {self.experts_held_from}.."
                f"{self.experts_held_from + self.num_experts} do not fit "
                f"the {routed} routed columns of a router "
                f"{self.router_width} wide with {self.zero_expert_num} "
                "identity experts")

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def scale(self):
        return 1.0 / math.sqrt(self.qk_head_dim)

    @property
    def latent_width(self):
        """Numbers a token a block keeps: ``[c; k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def leaf_shapes(self):
        """``{leaf name: shape}`` of one layer, as :func:`layer_apply`
        reads them (``_0`` / ``_1``: the sub-block)."""
        D, H = self.hidden_size, self.num_heads
        F, Fe, G = self.ffn_hidden_size, self.expert_ffn_hidden_size, \
            self.num_experts
        shapes = {}
        for i in (0, 1):
            shapes.update({
                f"n1_{i}": (D,),
                f"wq_a_{i}": (D, self.q_lora_rank),
                f"q_norm_{i}": (self.q_lora_rank,),
                f"wq_b_{i}": (self.q_lora_rank, H * self.qk_head_dim),
                f"wkv_a_{i}": (D, self.latent_width),
                f"kv_norm_{i}": (self.kv_lora_rank,),
                f"wkv_b_{i}": (self.kv_lora_rank, H * (
                    self.qk_nope_head_dim + self.v_head_dim)),
                f"wo_{i}": (H * self.v_head_dim, D),
                f"n2_{i}": (D,),
                f"ffn_gate_{i}": (D, F), f"ffn_up_{i}": (D, F),
                f"ffn_down_{i}": (F, D)})
        shapes.update({
            "router": (D, self.router_width),
            "router_bias": (self.router_width,),
            "w_gate": (G, D, Fe), "w_up": (G, D, Fe), "w_down": (G, Fe, D)})
        return shapes


# ---------------------------------------------------------------------------
# the pure functions
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    """RMSNorm with a learned scale, statistics in float32; returns
    float32."""
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def _on_rows(fn, a, blocks):
    """``fn`` on the rows of ``a`` (its last axis); with ``blocks`` only
    on the blocks of rows that hold a token, the rest left nought."""
    if blocks is None:
        return fn(a)
    flat = a.reshape(-1, a.shape[-1])
    return rows_in_blocks(fn, flat, *blocks).reshape(*a.shape[:-1], -1)


def _mm(a, w, blocks=None):
    """``a @ w`` over the last axis, float32 sums and result."""
    import jax.numpy as jnp
    return _on_rows(lambda r: jnp.matmul(
        r, w, preferred_element_type=jnp.float32), a, blocks)


def mla_project(cfg, p, i, h, positions, blocks=None):
    """What both forms of the attention start from. ``h``: (B, S, D) the
    normed stream in the compute dtype. Returns ``q_n`` (B, S, H, nope),
    ``q_r`` (B, S, H, rope) rotated, ``c`` (B, S, kv_lora_rank) normed
    and scaled, ``k_r`` (B, S, rope) rotated (one head, shared by all),
    each in the compute dtype: ``[c; k_r]`` is the row a cache keeps."""
    import jax
    import jax.numpy as jnp
    B, S, _ = h.shape
    dt, H = h.dtype, cfg.num_heads
    with jax.named_scope("mla_project"):
        c_q = rms_norm(_mm(h, p[f"wq_a_{i}"], blocks), p[f"q_norm_{i}"],
                       cfg.rms_norm_eps).astype(dt)
        q = (_mm(c_q, p[f"wq_b_{i}"], blocks) * cfg.q_scale).reshape(
            B, S, H, cfg.qk_head_dim)
        kv = _mm(h, p[f"wkv_a_{i}"], blocks)
        c = cfg.kv_scale * rms_norm(kv[..., :cfg.kv_lora_rank],
                                    p[f"kv_norm_{i}"], cfg.rms_norm_eps)
        q_r = rope_interleaved(q[..., cfg.qk_nope_head_dim:], positions,
                               cfg.rope_theta)
        k_r = rope_interleaved(kv[..., None, cfg.kv_lora_rank:], positions,
                               cfg.rope_theta)[:, :, 0]
        return (q[..., :cfg.qk_nope_head_dim].astype(dt), q_r.astype(dt),
                c.astype(dt), k_r.astype(dt))


def _kv_b(cfg, p, i):
    """The one stored ``wkv_b`` leaf as (rank, H, nope + v): its first
    ``nope`` columns a head make keys, the rest values."""
    return p[f"wkv_b_{i}"].reshape(
        cfg.kv_lora_rank, cfg.num_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)


def mla_expanded(cfg, p, i, q_n, q_r, c, k_r, n_blocks=None):
    """Causal attention over whole sequences with the keys and values
    written out a head: ``k_h = [W_K,h c; k_r]``, ``v_h = W_V,h c``.
    Returns (B, S, H, v_head_dim)."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("latent_attention"):
        kv = jnp.einsum("bsr,rhn->bshn", c, _kv_b(cfg, p, i),
                        preferred_element_type=jnp.float32).astype(c.dtype)
        k = jnp.concatenate(
            [kv[..., :cfg.qk_nope_head_dim],
             jnp.broadcast_to(k_r[:, :, None, :], q_r.shape)], axis=-1)
        q = jnp.concatenate([q_n, q_r], axis=-1)
        return masked_attention(q, k, kv[..., cfg.qk_nope_head_dim:],
                                cfg.scale, n_blocks=n_blocks)


def mla_absorbed(cfg, p, i, q_n, q_r, c, k_r, attend):
    """One new token a row (``S == 1``) against cached rows, the
    up-projection absorbed: ``q_c = W_K,h^T q_n`` is scored against the
    cached ``c`` and ``q_r`` against the cached ``k_r``, the attention
    weighs the cached ``c`` themselves, and ``W_V,h`` comes after.
    ``attend(q (B, H, 1, width), row (B, width)) -> (o_c (B, H, 1,
    kv_lora_rank), state)`` writes the row and reads the cache. Returns
    ``(o (B, 1, H, v_head_dim), state)``."""
    import jax
    import jax.numpy as jnp
    w = _kv_b(cfg, p, i)
    with jax.named_scope("mla_absorb"):
        q_c = jnp.einsum("bhn,rhn->bhr", q_n[:, 0],
                         w[..., :cfg.qk_nope_head_dim],
                         preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_c.astype(c.dtype), q_r[:, 0]], axis=-1)
        row = jnp.concatenate([c[:, 0], k_r[:, 0]], axis=-1)
    with jax.named_scope("latent_attention"):
        o_c, state = attend(q[:, :, None, :], row)
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("bhr,rhv->bhv", o_c[:, :, 0],
                       w[..., cfg.qk_nope_head_dim:],
                       preferred_element_type=jnp.float32)
    return o.astype(c.dtype)[:, None], state


def dense_ffn(p, i, u, blocks=None):
    """``W_d (silu(W_g u) * W_u u)``: operands in ``u.dtype``, sums and
    the gate in float32; returns float32."""
    import jax

    def ffn(r):
        g = _mm(r, p[f"ffn_gate_{i}"])
        return _mm((jax.nn.silu(g) * _mm(r, p[f"ffn_up_{i}"])).astype(
            r.dtype), p[f"ffn_down_{i}"])

    with jax.named_scope("dense_ffn"):
        return _on_rows(ffn, u, blocks)


def layer_apply(cfg, p, x, positions, attends, rows=None, blocks=None):
    """One double layer. ``p``: the leaves of ``Config.leaf_shapes``;
    ``x``: (B, S, D) in the compute dtype; ``positions``: (B, S);
    ``attends``: one function a sub-block, ``attend(i, q_n, q_r, c,
    k_r) -> (o (B, S, H, v), state)`` — the one thing the callers
    differ in; ``rows``: (B, S) bool, False for padding; ``blocks``:
    ``(n_blocks, block_rows)`` when only the first ``n_blocks`` (a
    device scalar) blocks of the B*S rows hold a token. Returns
    ``(y, [state_0, state_1], stats)``."""
    B, S, D = x.shape
    dt, eps = x.dtype, cfg.rms_norm_eps
    states = []
    for i in (0, 1):
        h = rms_norm(x, p[f"n1_{i}"], eps).astype(dt)
        o, state = attends[i](i, *mla_project(cfg, p, i, h, positions,
                                              blocks))
        states.append(state)
        x = x + _mm(o.reshape(B, S, -1), p[f"wo_{i}"], blocks).astype(dt)
        u32 = rms_norm(x, p[f"n2_{i}"], eps)
        u = u32.astype(dt)
        if i == 0:
            # the shortcut: routed on this stream, added after the
            # second sub-block
            s, stats = expert_share_ffn(
                p, u.reshape(B * S, D), top_k=cfg.top_k,
                held_from=cfg.experts_held_from,
                h_route=u32.reshape(B * S, D),
                rows=None if rows is None else rows.reshape(B * S),
                axis_name=None, blocks=blocks, route=cfg.route,
                n_zero=cfg.zero_expert_num)
        x = x + dense_ffn(p, i, u, blocks).astype(dt)
    return x + s.astype(dt).reshape(B, S, D), states, stats


def head_logits(cfg, P, x):
    """Final RMSNorm, then the untied head: float32 logits over the rows
    of the vocabulary held here."""
    import jax.numpy as jnp
    h = rms_norm(x, P["ln_f"], cfg.rms_norm_eps).astype(x.dtype)
    return jnp.einsum("...d,vd->...v", h, P["head"],
                      preferred_element_type=jnp.float32)


def _sum_stats(stats_list):
    import jax.numpy as jnp
    return jnp.stack([sum(s[n] for s in stats_list) for n in STAT_NAMES])


def forward_logits(cfg, P, tokens):
    """The eval forward: logits (B, S, V) float32 of whole sequences,
    attention in its expanded form."""
    import jax.numpy as jnp
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    x = embed(P, tokens)
    for p in P["layers"]:

        def attend(i, *projected, p=p):
            return mla_expanded(cfg, p, i, *projected), None

        x, _, _ = layer_apply(cfg, p, x, positions, [attend, attend])
    return head_logits(cfg, P, x)


# ---------------------------------------------------------------------------
# the model.Model
# ---------------------------------------------------------------------------

class LongCatFlashLayer(layer.Layer):
    """One double layer's parameters (``Config.leaf_shapes``)."""

    def __init__(self, cfg, init_of):
        super().__init__()
        self._cfg, self._init_of = cfg, init_of

    def initialize(self, x):
        import jax.numpy as jnp
        self._names = []
        for name, shape in self._cfg.leaf_shapes().items():
            mean, std = self._init_of(name)
            t = _param(shape, x.device, dtype=x.dtype)
            if name in _BANKS:
                # a bank is drawn a matrix at a time: one draw of a
                # 0.4 GB bank holds several times that in temporaries
                t.data = jnp.stack([
                    _param(shape[1:], x.device, dtype=x.dtype)
                    .gaussian(mean, std).data for _ in range(shape[0])])
            else:
                t.gaussian(mean, std)
            setattr(self, name, t)
            self._names.append(name)

    def _own_params(self):
        return {n: getattr(self, n) for n in self._names}

    leaves = _own_params


class LongCatFlashLM(DrawnBeforeCompile, model.Model):
    """One chip's share of a LongCat-Flash language model.

    ``forward(ids)`` takes a float tensor of token ids (B, S) and gives
    the logits (B, S, vocab) of the whole sequences (eval);
    ``decode_adapter(policy)`` hands the serving engine the same layers
    over latent ring levels, two a layer. Compile under
    ``policy="bfloat16"`` to hold the weights once, at 2 bytes.

    ``router_bias_std``: how a fresh model draws the router's selection
    bias (a served model's comes from its checkpoint)."""

    def __init__(self, vocab_size, hidden_size=6144, num_layers=4,
                 num_heads=64, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 ffn_hidden_size=12288, expert_ffn_hidden_size=2048,
                 num_experts=16, router_width=768, zero_expert_num=256,
                 top_k=12, routed_scaling_factor=6.0, experts_held_from=0,
                 rope_theta=1e7, rms_norm_eps=1e-5, mla_scale_q_lora=True,
                 mla_scale_kv_lora=True, init_std=0.02,
                 router_bias_std=0.0004):
        super().__init__()
        self.vocab_size = int(vocab_size)
        self.cfg = Config(
            hidden_size=hidden_size, num_layers=num_layers,
            num_heads=num_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            ffn_hidden_size=ffn_hidden_size,
            expert_ffn_hidden_size=expert_ffn_hidden_size,
            num_experts=num_experts, router_width=router_width,
            zero_expert_num=zero_expert_num, top_k=top_k,
            routed_scaling_factor=routed_scaling_factor,
            experts_held_from=experts_held_from, rope_theta=rope_theta,
            rms_norm_eps=rms_norm_eps, mla_scale_q_lora=mla_scale_q_lora,
            mla_scale_kv_lora=mla_scale_kv_lora)
        self._init_std = float(init_std)
        self._bias_std = float(router_bias_std)
        self.layers = [LongCatFlashLayer(self.cfg, self._init_of)
                       for _ in range(self.cfg.num_layers)]
        self._ready = False

    def _init_of(self, name):
        """(mean, std) a fresh leaf is drawn with."""
        if name == "router_bias":
            return 0.0, self._bias_std
        if name == "ln_f" or name.rsplit("_", 1)[0] in _NORMS:
            return 1.0, self._init_std
        return 0.0, self._init_std

    def _draw_params(self, dev, dtype):
        from ..tensor import Tensor
        D = self.cfg.hidden_size
        for name, shape in (("emb", (self.vocab_size, D)),
                            ("head", (self.vocab_size, D)), ("ln_f", (D,))):
            t = _param(shape, dev, dtype=dtype)
            t.gaussian(*self._init_of(name))
            setattr(self, name, t)
        probe = Tensor(shape=(1, 1, D), device=dev, dtype=dtype,
                       requires_grad=False)
        for lyr in self.layers:
            lyr.initialize(probe)
            lyr._initialized = True

    def _own_params(self):
        return {"emb": self.emb, "head": self.head, "ln_f": self.ln_f}

    def param_tensors(self):
        """The params tree of the pure functions, as Tensors."""
        return {**self._own_params(),
                "layers": [lyr.leaves() for lyr in self.layers]}

    def forward(self, ids):
        leaves, treedef = self._leaves(ids)
        return EvalForward(self.cfg, treedef, forward_logits)(ids, *leaves)

    def train_one_batch(self, *a, **kw):
        raise NotImplementedError(
            "LongCatFlashLM is inference-only: the routed experts' loop "
            "has no reverse, and at published widths four layers with 8 "
            "experts a layer are 60 GB of training state")

    def decode_adapter(self, policy=None):
        return _ServeAdapter(self, policy)


def create_model(vocab_size=256, **kwargs):
    return LongCatFlashLM(vocab_size, **kwargs)


# ---------------------------------------------------------------------------
# the serve adapter
# ---------------------------------------------------------------------------

class _ServeAdapter(ByReferenceAdapter):
    """What ``ServingEngine`` needs of the model (docs/serving.md, "The
    adapter contract"): the model's own arrays by reference, two latent
    ring levels a layer (one a sub-block: ``max_len`` rows of ``[c;
    k_r]``, kept once for all heads), a prefill program in the
    attention's expanded form and a decode program in its absorbed form,
    both built on :func:`layer_apply`, and the expert layer's counts
    riding each program's read-back (:meth:`stats_recorder`)."""

    def stats_recorder(self, registry):
        return moe_stats_recorder(registry, STAT_NAMES)

    def cache_kinds(self):
        return ["latent"] * (2 * self.cfg.num_layers)

    def init_cache(self, slots, max_len):
        from ..serving import kv_cache
        c = self.cfg
        return [kv_cache.init_latent(slots, max_len, c.latent_width,
                                     c.kv_lora_rank, self._cache_dtype())
                for _ in range(2 * c.num_layers)]

    def prefill_fn(self):
        import jax.numpy as jnp
        from ..serving import kv_cache
        cfg = self.cfg

        def fn(P, cache, tokens, lengths, slot_ids, valid):
            B, S = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                         (B, S))
            lengths = lengths.astype(jnp.int32)
            rows = (positions < lengths[:, None]) & valid[:, None]
            # one prompt a call: the blocks of R rows that hold a token
            # are worked, the padding behind them is not
            R = min(_cm.PREFILL_ROWS, S)     # masked_attention's blocks
            blocks = None
            if B == 1 and S % R == 0:
                blocks = (jnp.where(valid[0], -(-lengths[0] // R), 0), R)
            x = embed(P, tokens)
            new_cache, stats = [], []
            for n, p in enumerate(P["layers"]):

                def attend(i, q_n, q_r, c, k_r, p=p, n=n):
                    o = mla_expanded(
                        cfg, p, i, q_n, q_r, c, k_r,
                        n_blocks=None if blocks is None else blocks[0])
                    return o, kv_cache.write_prompts(
                        cache[2 * n + i], slot_ids,
                        jnp.concatenate([c, k_r], axis=-1), None, lengths,
                        valid)

                x, levels, st = layer_apply(cfg, p, x, positions,
                                            [attend, attend], rows, blocks)
                new_cache += levels
                stats.append(st)
            x_last = jnp.take_along_axis(
                x, (lengths - 1)[:, None, None].clip(0), axis=1)[:, 0]
            return new_cache, (head_logits(cfg, P, x_last),
                               _sum_stats(stats))

        return fn

    def decode_fn(self):
        import jax.numpy as jnp
        from ..serving import kv_cache
        cfg = self.cfg

        def fn(P, cache, tokens, positions, active):
            positions = positions.astype(jnp.int32)
            x = embed(P, tokens)[:, None, :]
            new_cache, stats = [], []
            for n, p in enumerate(P["layers"]):

                def attend(i, q_n, q_r, c, k_r, p=p, n=n):
                    return mla_absorbed(
                        cfg, p, i, q_n, q_r, c, k_r,
                        lambda q, row: kv_cache.decode_token(
                            cache[2 * n + i], q, row, None, positions,
                            active, cfg.scale))

                x, levels, st = layer_apply(cfg, p, x, positions[:, None],
                                            [attend, attend],
                                            active[:, None])
                new_cache += levels
                stats.append(st)
            return new_cache, (head_logits(cfg, P, x[:, 0]),
                               _sum_stats(stats))

        return fn


__all__ = ["LongCatFlashLM", "LongCatFlashLayer", "Config", "rms_norm",
           "mla_project", "mla_expanded", "mla_absorbed", "dense_ffn",
           "layer_apply", "forward_logits", "create_model"]
