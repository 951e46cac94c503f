"""Parallel-block sparse-expert LM (the ``cohere2_moe`` family: Command A+).

One bias-free LayerNorm feeds attention and the expert FFN side by side
(``y = x + attn(LN x) + ffn(LN x)``); grouped KV heads; three layers in
four attend inside a sliding window with rotary positions (interleaved
pairs), the fourth attends to everything with no positional encoding at
all; the FFN is :func:`singa_tpu.parallel.moe.expert_share_ffn` — sigmoid
top-k over all experts of which this chip holds a share, shared experts
averaged beside them; the output head is the token embedding transposed.

The block is written ONCE, as the pure function :func:`block_apply` of
(config, params, state): the ``Layer`` forward (eval) and the serve
adapter's prefill and decode all call it and differ only in the
``attend`` they hand it (plain masked attention; the same plus the ring
write; the ring read). Inference only: at published widths the smallest
cut the benchmark allows has 2.6 B parameters, 42 GB of training state.

A model holds ONE chip's share of a stated deployment: ``num_heads`` /
``num_kv_heads`` / ``num_experts`` / ``vocab_size`` count what lives
here; the router keeps its full width ``router_width`` and
``experts_held_from`` says which experts these are.
"""

from __future__ import annotations

import math

from .. import layer, model
from ..autograd_base import Operator
from ..layer import _param
from ..parallel.moe import ExpertShareFFN, expert_share_ffn, rows_in_blocks

SLIDING, FULL = "sliding_attention", "full_attention"
STAT_NAMES = ("pairs_here", "pairs_absent", "experts_touched")
_ATTN_LEAVES = ("wq", "wk", "wv", "wo")


class Config:
    """The static half of the block: what :func:`block_apply` closes
    over. Hashable by identity; every field is a python number."""

    def __init__(self, *, hidden_size, num_heads, num_kv_heads, head_dim,
                 intermediate_size, num_experts, router_width, top_k,
                 num_shared_experts, experts_held_from, sliding_window,
                 rope_theta, layer_norm_eps, logit_scale, layer_types):
        self.hidden_size = int(hidden_size)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.intermediate_size = int(intermediate_size)
        self.num_experts, self.router_width = int(num_experts), \
            int(router_width)
        self.top_k, self.num_shared_experts = int(top_k), \
            int(num_shared_experts)
        self.experts_held_from = int(experts_held_from)
        self.sliding_window = int(sliding_window)
        self.rope_theta = float(rope_theta)
        self.layer_norm_eps = float(layer_norm_eps)
        self.logit_scale = float(logit_scale)
        self.layer_types = tuple(layer_types)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads do not group over "
                f"{self.num_kv_heads} KV heads")
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")

    @property
    def scale(self):
        return 1.0 / math.sqrt(self.head_dim)


# ---------------------------------------------------------------------------
# the pure functions
# ---------------------------------------------------------------------------

def layer_norm(x, scale, eps):
    """Bias-free LayerNorm, statistics in float32; returns float32."""
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    return (xf - mean) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope_interleaved(t, positions, theta):
    """Rotary positions on the whole head, pairs ``(2i, 2i+1)``
    (``rope_gptj``). ``t``: (B, S, H, D); ``positions``: (B, S)."""
    import jax.numpy as jnp
    D = t.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[..., None, None] * inv   # B,S,1,D/2
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    tf = t.astype(jnp.float32)
    a, b = tf[..., 0::2], tf[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(t.shape).astype(t.dtype)


# rows a block of the prefill's loops (attention's query blocks, the
# shared experts' row blocks): a padded prompt pays for the blocks that
# hold a token
PREFILL_ROWS = 512


def masked_attention(q, k, v, scale, window=None, n_blocks=None):
    """Causal attention of grouped heads over whole sequences, queries in
    blocks of ``PREFILL_ROWS`` so that the scores of S = 4096 never exist
    for all rows at once. ``q``/``k``: (B, S, Hq | Hkv, D); ``v``:
    (B, S, Hkv, Dv), as wide as the keys or not; returns (B, S, Hq, Dv);
    ``window``: keep ``i - j < window`` besides ``j <= i``; ``n_blocks``
    (a device scalar): only the first ``n_blocks`` query blocks hold a
    token, the rest come out nought. Operands in their own dtype, sums
    and the softmax in float32."""
    import jax
    import jax.numpy as jnp
    B, S, Hq, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    block_q = PREFILL_ROWS
    qg = q.reshape(B, S, Hkv, Hq // Hkv, D)
    cols = jnp.arange(S)

    def rows_of(qb, first):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k,
                       preferred_element_type=jnp.float32) * scale
        i = first + jnp.arange(qb.shape[1])
        keep = cols[None, :] <= i[:, None]
        if window is not None:
            keep &= i[:, None] - cols[None, :] < window
        a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", a.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    if S <= block_q or S % block_q:
        return rows_of(qg, 0).reshape(B, S, Hq, Dv)
    n = S // block_q
    blocks = qg.reshape(B, n, block_q, Hkv, Hq // Hkv, D).swapaxes(0, 1)
    if n_blocks is None:
        out = jax.lax.map(lambda a: rows_of(a[0], a[1]),
                          (blocks, jnp.arange(n) * block_q))
    else:
        out = jax.lax.fori_loop(
            0, n_blocks,
            lambda i, acc: acc.at[i].set(rows_of(blocks[i], i * block_q)),
            jnp.zeros(blocks.shape[:-1] + (Dv,), q.dtype))
    return out.swapaxes(0, 1).reshape(B, S, Hq, Dv)


def block_apply(cfg, kind, p, x, positions, attend, rows=None, blocks=None):
    """One layer. ``p``: ``ln`` (D,), ``wq`` (D, Hq*hd), ``wk``/``wv``
    (D, Hkv*hd), ``wo`` (Hq*hd, D) and ``ffn`` (the leaves of
    :func:`expert_share_ffn`). ``x``: (B, S, D) in the compute dtype;
    ``positions``: (B, S) token positions (rotary, sliding layers only);
    ``attend(q, k, v) -> (o (B, S, Hq, hd), state)`` is the one thing the
    callers differ in; ``rows``: (B, S) bool, False for padding;
    ``blocks``: ``(n_blocks, block_rows)`` when only the first
    ``n_blocks`` (a device scalar) blocks of the B*S rows hold a token
    (a padded prompt: the projections and the shared experts then run
    on those blocks alone). Returns ``(y, state, stats)``."""
    import jax
    B, S, D = x.shape
    hd = cfg.head_dim

    def matmul(a, w):
        return a @ w if blocks is None \
            else rows_in_blocks(lambda r: r @ w, a, *blocks)

    with jax.named_scope("block_norm"):
        h32 = layer_norm(x, p["ln"], cfg.layer_norm_eps).reshape(B * S, D)
        h = h32.astype(x.dtype)
    with jax.named_scope("block_attention"):
        q = matmul(h, p["wq"]).reshape(B, S, cfg.num_heads, hd)
        k = matmul(h, p["wk"]).reshape(B, S, cfg.num_kv_heads, hd)
        v = matmul(h, p["wv"]).reshape(B, S, cfg.num_kv_heads, hd)
        if kind == SLIDING:
            q = rope_interleaved(q, positions, cfg.rope_theta)
            k = rope_interleaved(k, positions, cfg.rope_theta)
        o, state = attend(q, k, v)
        attn = matmul(o.reshape(B * S, -1).astype(x.dtype), p["wo"])
    ffn, stats = expert_share_ffn(
        p["ffn"], h, top_k=cfg.top_k, held_from=cfg.experts_held_from,
        h_route=h32, rows=None if rows is None else rows.reshape(B * S),
        axis_name=None, blocks=blocks)
    return x + (attn + ffn.astype(x.dtype)).reshape(B, S, D), state, stats


def embed(P, tokens):
    import jax.numpy as jnp
    return jnp.take(P["emb"], tokens, axis=0)


def head_logits(cfg, P, x):
    """Final LayerNorm, then the tied embedding as head: float32 logits
    over the rows of the vocabulary held here."""
    import jax.numpy as jnp
    h = layer_norm(x, P["ln_f"], cfg.layer_norm_eps).astype(x.dtype)
    return jnp.einsum("...d,vd->...v", h, P["emb"],
                      preferred_element_type=jnp.float32) * cfg.logit_scale


def _sum_stats(stats_list):
    import jax.numpy as jnp
    return jnp.stack([sum(s[n] for s in stats_list) for n in STAT_NAMES])


def forward_logits(cfg, P, tokens):
    """The eval forward: logits (B, S, V) float32 of whole sequences."""
    import jax.numpy as jnp
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = embed(P, tokens)
    for kind, p in zip(cfg.layer_types, P["layers"]):
        window = cfg.sliding_window if kind == SLIDING else None

        def attend(q, k, v, window=window):
            return masked_attention(q, k, v, cfg.scale, window), None

        x, _, _ = block_apply(cfg, kind, p, x, positions, attend)
    return head_logits(cfg, P, x)


# ---------------------------------------------------------------------------
# the model.Model
# ---------------------------------------------------------------------------

class EvalForward(Operator):
    """A whole eval forward as one tape node over a model's leaves:
    ``logits_fn(cfg, P, tokens)`` with ``P`` the params tree the leaves
    unflatten to."""

    differentiable = False

    def __init__(self, cfg, treedef, logits_fn):
        super().__init__()
        self.cfg, self.treedef, self.logits_fn = cfg, treedef, logits_fn

    def forward(self, ids, *leaves):
        import jax
        import jax.numpy as jnp
        P = jax.tree_util.tree_unflatten(self.treedef, leaves)
        return self.logits_fn(self.cfg, P, ids.astype(jnp.int32))


class CohereMoEBlock(layer.Layer):
    """One layer's parameters: ``ln.scale``, the four attention
    projections, and the expert share (``ffn``)."""

    def __init__(self, cfg, init_std, out_std):
        super().__init__()
        self._cfg, self._init_std, self._out_std = cfg, init_std, out_std
        self.ffn = ExpertShareFFN(
            cfg.router_width, cfg.intermediate_size, cfg.top_k,
            held_count=cfg.num_experts, held_from=cfg.experts_held_from,
            n_shared=cfg.num_shared_experts, init_std=init_std,
            out_std=out_std)

    def initialize(self, x):
        c, dev = self._cfg, x.device
        D, hd = c.hidden_size, c.head_dim
        self.ln = _param((D,), dev, init="ones", dtype=x.dtype)
        shapes = {"wq": (D, c.num_heads * hd), "wk": (D, c.num_kv_heads * hd),
                  "wv": (D, c.num_kv_heads * hd), "wo": (c.num_heads * hd, D)}
        for name, shape in shapes.items():
            t = _param(shape, dev, dtype=x.dtype)
            t.gaussian(0.0, self._out_std if name == "wo"
                       else self._init_std)
            setattr(self, name, t)

    def _own_params(self):
        return {"ln": self.ln, **{n: getattr(self, n) for n in _ATTN_LEAVES}}

    def leaves(self):
        """The params tree :func:`block_apply` reads, as Tensors."""
        return {**self._own_params(), "ffn": self.ffn._own_params()}


class DrawnBeforeCompile:
    """For a ``model.Model`` of billions of parameters (mixed in before
    it): ``compile`` draws the parameters BEFORE the dry run — inside
    its trace a random draw is folded into the executable (a 1 GB
    constant a 1 GB leaf, minutes of compiling); out here it is one
    small program a leaf on the device. The model writes
    ``_draw_params(dev, dtype)`` and starts with ``_ready = False``."""

    def compile(self, inputs, is_train=True, use_graph=False,
                sequential=False, policy=None, **kw):
        from .. import mixed_precision as mp
        pol = mp.resolve(policy)
        self._ensure_params(inputs[0].device, pol.param_dtype
                            if pol is not None else None)
        return super().compile(inputs, is_train=is_train,
                               use_graph=use_graph, sequential=sequential,
                               policy=policy, **kw)

    def _ensure_params(self, dev, dtype=None, traced=False):
        if self._ready:
            return
        import contextlib
        import jax
        import jax.numpy as jnp
        # under a trace (eager use without compile) the draws have to be
        # evaluated at compile time; outside one that scope is what turns
        # a draw into a constant of its executable, so it is left out
        scope = jax.ensure_compile_time_eval() if traced \
            else contextlib.nullcontext()
        with scope:
            self._draw_params(dev, jnp.dtype(dtype or jnp.float32))
        self._ready = True

    def _leaves(self, ids):
        """``(leaves, treedef)`` of ``param_tensors()`` for the forward's
        one tape node, drawn first if this is eager use without
        compile."""
        import jax
        self._ensure_params(ids.device,
                            traced=isinstance(ids.data, jax.core.Tracer))
        return jax.tree_util.tree_flatten(
            self.param_tensors(), is_leaf=lambda t: hasattr(t, "data"))


class CohereMoELM(DrawnBeforeCompile, model.Model):
    """One chip's share of a ``cohere2_moe`` language model.

    ``forward(ids)`` takes a float tensor of token ids (B, S) and gives
    the logits (B, S, vocab) of the whole sequences (eval);
    ``decode_adapter(policy)`` hands the serving engine the same block
    with per-layer rings. Compile under ``policy="bfloat16"`` to hold
    the weights once, at 2 bytes."""

    def __init__(self, vocab_size, hidden_size=4096, num_layers=4,
                 num_heads=16, num_kv_heads=1, head_dim=128,
                 intermediate_size=4096, num_experts=16, router_width=128,
                 top_k=8, num_shared_experts=4, experts_held_from=0,
                 sliding_window=4096, rope_theta=50000.0,
                 layer_norm_eps=1e-5, logit_scale=1.0, layer_types=None,
                 init_std=0.02, out_std=None, emb_std=None):
        super().__init__()
        if layer_types is None:
            layer_types = [FULL if (i + 1) % 4 == 0 else SLIDING
                           for i in range(num_layers)]
        self.vocab_size = int(vocab_size)
        self.cfg = Config(
            hidden_size=hidden_size, num_heads=num_heads,
            num_kv_heads=num_kv_heads, head_dim=head_dim,
            intermediate_size=intermediate_size, num_experts=num_experts,
            router_width=router_width, top_k=top_k,
            num_shared_experts=num_shared_experts,
            experts_held_from=experts_held_from,
            sliding_window=sliding_window, rope_theta=rope_theta,
            layer_norm_eps=layer_norm_eps, logit_scale=logit_scale,
            layer_types=list(layer_types)[:int(num_layers)])
        self._emb_std = init_std if emb_std is None else emb_std
        out_std = init_std if out_std is None else out_std
        self.layers = [CohereMoEBlock(self.cfg, init_std, out_std)
                       for _ in self.cfg.layer_types]
        self._ready = False

    def _draw_params(self, dev, dtype):
        from ..tensor import Tensor
        self.emb = _param((self.vocab_size, self.cfg.hidden_size), dev,
                          dtype=dtype)
        self.emb.gaussian(0.0, self._emb_std)
        self.ln_f = _param((self.cfg.hidden_size,), dev, init="ones",
                           dtype=dtype)
        probe = Tensor(shape=(1, 1, self.cfg.hidden_size), device=dev,
                       dtype=dtype, requires_grad=False)
        for blk in self.layers:
            for lyr in (blk.ffn, blk):
                lyr.initialize(probe)
                lyr._initialized = True

    def _own_params(self):
        return {"emb": self.emb, "ln_f": self.ln_f}

    def param_tensors(self):
        """The params tree of the pure functions, as Tensors."""
        return {"emb": self.emb, "ln_f": self.ln_f,
                "layers": [blk.leaves() for blk in self.layers]}

    def forward(self, ids):
        leaves, treedef = self._leaves(ids)
        return EvalForward(self.cfg, treedef, forward_logits)(ids, *leaves)

    def train_one_batch(self, *a, **kw):
        raise NotImplementedError(
            "CohereMoELM is inference-only: the routed experts' loop has "
            "no reverse, and at published widths one period of layers "
            "with 8 experts a layer is 42 GB of training state")

    def decode_adapter(self, policy=None):
        return _ServeAdapter(self, policy)


def create_model(vocab_size=256, **kwargs):
    return CohereMoELM(vocab_size, **kwargs)


# ---------------------------------------------------------------------------
# the serve adapter
# ---------------------------------------------------------------------------

class ByReferenceAdapter:
    """The part of a serve adapter that hands the engine the model's own
    device arrays (``m.param_tensors()``), for a model too large to hold
    twice: ring layout only, one device, no quantized second copy."""

    supports_paged = False
    supports_sharded = False

    def __init__(self, m, policy=None):
        self.m, self.policy, self.cfg = m, policy, m.cfg

    def _cache_dtype(self):
        import jax.numpy as jnp
        return jnp.dtype(self.m.emb.data.dtype)

    def validate(self, prefill_len, max_len):
        want = getattr(self.policy, "compute_dtype", None)
        have = self._cache_dtype()
        if want is not None and have != want:
            raise ValueError(
                f"the model holds {have} weights and the serving policy "
                f"computes in {want}: compile the model under the policy "
                "it is served with (weights are handed over by reference, "
                "never cast into a second copy)")
        if getattr(self.policy, "weight_quant", None) or \
                getattr(self.policy, "cache_quant", None):
            raise ValueError(
                f"{type(self.m).__name__} serves its own arrays: "
                "quantized serving policies are not supported")

    def params(self):
        """The model's own device arrays: no host round trip, no cast, no
        copy (``Model.compile(policy="bfloat16")`` made them 2 bytes)."""
        import jax
        return jax.tree_util.tree_map(
            lambda t: t.data, self.m.param_tensors(),
            is_leaf=lambda t: hasattr(t, "data"))



def moe_stats_recorder(registry, names):
    """The expert layer's counters in the engine's registry, and the
    function the engine hands each program call's counts (the array
    ``names`` beside the logits: ``pairs_here``, ``pairs_absent``,
    ``experts_touched``, and ``pairs_zero`` where the layer has experts
    without weights): it feeds the counters and returns the attrs of
    the call's span."""
    pairs = registry.counter(
        "moe_pairs_total", "(token, expert) picks the router made for "
        "real tokens, by where the expert is (here: computed on this "
        "chip; absent: add nothing here; zero: an expert without "
        "weights, its pick adds the token's own row)", labels=("held",))
    touched = registry.counter(
        "moe_experts_touched_total", "held experts that got any pair, "
        "summed over layers and program calls (each one's matrices had "
        "to be read)", labels=("program",))
    calls = registry.counter(
        "moe_calls_total", "program calls the expert counts were read "
        "from", labels=("program",))

    def record(program, stats):
        st = dict(zip(names, (int(v) for v in stats)))
        for held in ("here", "absent", "zero"):
            if f"pairs_{held}" in st:
                pairs.inc(st[f"pairs_{held}"], held=held)
        touched.inc(st["experts_touched"], program=program)
        calls.inc(program=program)
        return {k: st[k] for k in ("pairs_here", "pairs_zero",
                                   "experts_touched") if k in st}

    return record


class _ServeAdapter(ByReferenceAdapter):
    """What ``ServingEngine`` needs of the model (docs/serving.md, "The
    adapter contract"): the model's own arrays by reference, per-layer
    rings (``min(window, max_len)`` positions for a window layer,
    ``max_len`` for a full one, one row of KV heads each), a prefill and
    a decode program built on :func:`block_apply`, and the expert
    layer's counts riding each program's read-back
    (:meth:`stats_recorder`)."""

    def stats_recorder(self, registry):
        return moe_stats_recorder(registry, STAT_NAMES)

    def ring_lengths(self, max_len):
        c = self.cfg
        return [min(c.sliding_window, int(max_len)) if kind == SLIDING
                else int(max_len) for kind in c.layer_types]

    def cache_kinds(self):
        return ["window" if kind == SLIDING else "full"
                for kind in self.cfg.layer_types]

    def init_cache(self, slots, max_len):
        from ..serving import kv_cache
        c = self.cfg
        return [kv_cache.init_cache(slots, c.num_kv_heads, length,
                                    c.head_dim, self._cache_dtype())
                for length in self.ring_lengths(max_len)]

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
            R = min(PREFILL_ROWS, S)
            blocks = None
            if B == 1 and S % R == 0:
                blocks = (jnp.where(valid[0], -(-lengths[0] // R), 0), R)
            x = embed(P, tokens)
            new_cache, stats = [], []
            for kind, p, level in zip(cfg.layer_types, P["layers"], cache):
                window = cfg.sliding_window if kind == SLIDING else None

                def attend(q, k, v, level=level, window=window):
                    o = masked_attention(
                        q, k, v, cfg.scale, window,
                        n_blocks=None if blocks is None else blocks[0])
                    return o, kv_cache.write_prompts(
                        level, slot_ids, k, v, lengths, valid)

                x, level, st = block_apply(cfg, kind, p, x, positions,
                                           attend, rows, blocks)
                new_cache.append(level)
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
            for kind, p, level in zip(cfg.layer_types, P["layers"], cache):

                def attend(q, k, v, level=level):
                    o, level = kv_cache.decode_token(
                        level, q.swapaxes(1, 2), k[:, 0], v[:, 0],
                        positions, active, cfg.scale)
                    return o.swapaxes(1, 2), level

                x, level, st = block_apply(cfg, kind, p, x,
                                           positions[:, None], attend,
                                           active[:, None])
                new_cache.append(level)
                stats.append(st)
            return new_cache, (head_logits(cfg, P, x[:, 0]),
                               _sum_stats(stats))

        return fn


__all__ = ["CohereMoELM", "CohereMoEBlock", "Config", "block_apply",
           "forward_logits", "create_model", "DrawnBeforeCompile",
           "ByReferenceAdapter", "EvalForward", "moe_stats_recorder"]
