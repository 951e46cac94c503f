"""Decoder-hybrid-decoder LM (the ``phi4flash`` family: Phi-4-mini-flash-
reasoning; the model's paper is arXiv:2507.06607, "SambaY").

Every layer is ``x += mix(LN1 x); x += MLP(LN2 x)`` (LayerNorm with scale
and bias, a gated SiLU MLP, no positional encoding anywhere) and the
layers differ in their mixer, of four kinds:

- **Mamba** (the even layers of the first half, the self-decoder): a
  selective state-space layer (``ops/ssm.py``). Per sequence it keeps a
  state of ``(d_state, d_inner)`` and the last ``d_conv - 1`` inputs of
  its convolution — a fixed size, no row a position. The LAST of them
  also hands its scan output (before the gate) down as the memory ``m``.
- **Differential attention** (the odd layers of the first half): within a
  window but for the last, which attends to everything. Query heads pair
  up as ``(q1, q2)`` and KV heads as ``(k1, k2)``, ``(v1, v2)``; a head's
  output is ``(1 - l0) RMSNorm(a1 - lam a2)`` with ``a_i = softmax(q_i
  k_i^T) [v1; v2]``. Computed as ordinary grouped attention over KV heads
  ``[k1; k2]`` / ``[v1; v2]`` of twice the head size with queries ``[q1;
  0]`` and ``[0; q2]`` — the form the ring functions and the ring decode
  kernel of ``serving/kv_cache.py`` take as it is.
- **Gated Memory Unit** (the even layers of the second half, the
  cross-decoder): ``W2(m * silu(W1 h))``, elementwise in the token, no
  state.
- **Cross attention** (the odd layers of the second half): its own
  queries against the LAST self-decoder layer's keys and values; nothing
  of its own is cached.

The four mixers and the MLP are pure functions written once; the eval
forward, the serve adapter's prefill and its decode call them and differ
in the ``attend`` and the state they hand in. A prompt's prefill runs the
self-decoder over all of it (filling rings, convolution tails and states)
and the cross-decoder on its LAST token alone, as published.

Layers are unrolled, not scanned over stacked pairs: a scan would carry
each ring level through its ``xs -> ys`` (a copy of the level a tick),
where the unrolled decode program updates one tile of it in place.

Precision under ``policy="bfloat16"``: weights, rings, convolution tails
and matmul operands bf16; LayerNorm and sub-norm statistics, softmax, the
step size, ``exp(dt A)`` and the state, the lambdas, the gates'
activations and the logits float32. Inference only.
"""

from __future__ import annotations

import math

from .. import layer, model
from ..layer import _param
from .cohere_moe import (ByReferenceAdapter, DrawnBeforeCompile, EvalForward,
                         embed, layer_norm, masked_attention, PREFILL_ROWS)

MAMBA, ATTN, GMU, CROSS = "mamba", "attention", "gmu", "cross"
_NORM_SCALES = ("ln1_w", "ln2_w", "ln_f", "subln")
_BIASES = ("ln1_b", "ln2_b", "ln_f_b", "conv_b", "bqkv", "bq", "bo")


class Config:
    """The static half: what the pure functions close over."""

    def __init__(self, *, hidden_size, num_layers, num_heads, num_kv_heads,
                 intermediate_size, sliding_window, layer_norm_eps,
                 d_state, d_conv, expand, dt_rank):
        self.hidden_size = D = int(hidden_size)
        self.num_layers = L = int(num_layers)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.intermediate_size = int(intermediate_size)
        self.sliding_window = int(sliding_window)
        self.layer_norm_eps = float(layer_norm_eps)
        self.d_state, self.d_conv = int(d_state), int(d_conv)
        self.d_inner = int(expand) * D
        self.dt_rank = int(dt_rank) if dt_rank else -(-D // 16)
        if L % 4 or L < 8:
            raise ValueError(
                f"{L} layers: the self-decoder and the cross-decoder are "
                "half of the layers each, in (Mamba, attention) and (GMU, "
                "cross) pairs")
        if D % self.num_heads or self.num_heads % 2 or \
                self.num_kv_heads % 2 or \
                self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads / {self.num_kv_heads} KV "
                f"heads of {D} do not pair up into differential heads")
        self.head_dim = D // self.num_heads
        half = L // 2
        # the self-decoder ends with a Mamba layer (the memory) and the
        # one full-attention layer (the keys and values the cross layers
        # read); the cross-decoder follows
        self.memory_layer, self.full_layer = half, half + 1
        self.kinds = tuple(
            (MAMBA if i % 2 == 0 else ATTN) if i <= half + 1 else
            (GMU if i % 2 == 0 else CROSS) for i in range(L))

    @property
    def n_self(self):
        return self.full_layer + 1

    @property
    def scale(self):
        return 1.0 / math.sqrt(self.head_dim)

    def lambda_init(self, i):
        return 0.8 - 0.6 * math.exp(-0.3 * i)

    def leaf_shapes(self, i):
        """{name: shape} of layer ``i``'s leaves."""
        D, F, C = self.hidden_size, self.intermediate_size, self.d_inner
        hd, Hq, Hkv = self.head_dim, self.num_heads, self.num_kv_heads
        shapes = {"ln1_w": (D,), "ln1_b": (D,), "ln2_w": (D,), "ln2_b": (D,),
                  "fc1": (D, 2 * F), "fc2": (F, D)}
        kind = self.kinds[i]
        if kind == MAMBA:
            shapes.update(
                in_proj=(D, 2 * C), conv_w=(C, self.d_conv), conv_b=(C,),
                x_proj=(C, self.dt_rank + 2 * self.d_state),
                dt_proj=(self.dt_rank, C), dt_bias=(C,),
                A_log=(C, self.d_state), D=(C,), out_proj=(C, D))
        elif kind == GMU:
            shapes.update(w1=(D, C), w2=(C, D))
        else:
            lams = {n: (hd,) for n in ("lam_q1", "lam_k1", "lam_q2",
                                       "lam_k2")}
            shapes.update(lams, subln=(2 * hd,), wo=(Hq * hd, D), bo=(D,))
            if kind == ATTN:
                shapes.update(wqkv=(D, (Hq + 2 * Hkv) * hd),
                              bqkv=((Hq + 2 * Hkv) * hd,))
            else:
                shapes.update(wq=(D, Hq * hd), bq=(Hq * hd,))
        return shapes


# ---------------------------------------------------------------------------
# the pure functions
# ---------------------------------------------------------------------------

def _norm(x, w, b, eps):
    """LayerNorm with scale and bias, statistics in float32, cast back."""
    import jax.numpy as jnp
    return (layer_norm(x, w, eps) + b.astype(jnp.float32)).astype(x.dtype)


def _mm(a, w):
    """``a @ w`` with float32 accumulation and a float32 result."""
    import jax.numpy as jnp
    return jnp.matmul(a, w, preferred_element_type=jnp.float32)


def mlp(cfg, p, h):
    """``fc2(silu(g) * u)``, ``[g, u] = fc1(h)``: the first half of
    ``fc1`` gates."""
    import jax
    gu = _mm(h, p["fc1"])
    g, u = gu[..., :cfg.intermediate_size], gu[..., cfg.intermediate_size:]
    return _mm((jax.nn.silu(g) * u).astype(h.dtype), p["fc2"])


def mamba_mix(cfg, p, h, state, lengths):
    """The Mamba mixer on ``h`` (B, S, D) from ``state`` (``conv`` (B,
    d_conv - 1, C), ``ssm`` (B, N, C) float32), rows true up to
    ``lengths`` (B,) — one token a row (S = 1, a decode tick: ``lengths``
    is 1 for a live slot, 0 for a dead one) or whole prompts. Returns
    ``(out (B, S, D) float32, y (B, S, C) float32 — the scan's output
    before the gate, the memory — , state')``."""
    import jax
    import jax.numpy as jnp
    from ..ops import ssm
    C, N, R = cfg.d_inner, cfg.d_state, cfg.dt_rank
    xz = _mm(h, p["in_proj"])
    x, z = xz[..., :C].astype(h.dtype), xz[..., C:]
    x, conv = ssm.causal_conv(x, p["conv_w"], p["conv_b"], state["conv"],
                              lengths)
    x = jax.nn.silu(x).astype(h.dtype)
    dbc = _mm(x, p["x_proj"])
    dt = jax.nn.softplus(
        _mm(dbc[..., :R].astype(h.dtype), p["dt_proj"])
        + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    Bm, Cm = dbc[..., R:R + N], dbc[..., R + N:]
    if h.shape[1] == 1:
        y, s = ssm.selective_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                  p["D"], state["ssm"], lengths > 0)
        y = y[:, None]
    else:
        y, s = ssm.selective_scan(x, dt, A, Bm, Cm, p["D"], state["ssm"],
                                  lengths)
    out = _mm((y * jax.nn.silu(z)).astype(h.dtype), p["out_proj"])
    return out, y, {"conv": conv, "ssm": s}


def _paired_queries(q):
    """Query heads ``(B, S, Hq, hd)`` as the ``[q1; 0]``, ``[0; q2]`` of
    twice the head size: head ``2i`` scores against ``k1`` alone, head
    ``2i + 1`` against ``k2``, of the paired KV head ``[k1; k2]``."""
    import jax.numpy as jnp
    B, S, Hq, hd = q.shape
    q = q.reshape(B, S, Hq // 2, 2, 1, hd) \
        * jnp.eye(2, dtype=q.dtype)[:, :, None]
    return q.reshape(B, S, Hq, 2 * hd)


def _differential_out(cfg, p, i, o):
    """``o`` (B, S, Hq, 2 hd): each pair's ``(a1, a2)`` to ``(1 - l0)
    RMSNorm(a1 - lam a2) * subln``, then the output projection."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    B, S, Hq, w = o.shape
    lam0 = cfg.lambda_init(i)
    lam = jnp.exp(jnp.sum(p["lam_q1"].astype(f32) * p["lam_k1"].astype(f32))) \
        - jnp.exp(jnp.sum(p["lam_q2"].astype(f32) * p["lam_k2"].astype(f32))) \
        + lam0
    a = o.astype(f32).reshape(B, S, Hq // 2, 2, w)
    d = a[..., 0, :] - lam * a[..., 1, :]
    d = d * jax.lax.rsqrt(jnp.mean(jnp.square(d), -1, keepdims=True)
                          + cfg.layer_norm_eps)
    d = (1.0 - lam0) * d * p["subln"].astype(f32)
    return _mm(d.reshape(B, S, -1).astype(o.dtype), p["wo"]) \
        + p["bo"].astype(f32)


def attention_mix(cfg, p, i, h, attend):
    """Differential self-attention. ``attend(q, k, v) -> (o, state)``
    with ``q`` (B, S, Hq, 2 hd), ``k``/``v`` (B, S, Hkv / 2, 2 hd) in the
    grouped form, scale ``cfg.scale``. Returns ``(out float32, state)``."""
    import jax
    import jax.numpy as jnp
    B, S, _ = h.shape
    hd, Hq, Hkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    with jax.named_scope("diff_attention"):
        qkv = (_mm(h, p["wqkv"]) + p["bqkv"].astype(jnp.float32)) \
            .astype(h.dtype)
        q = _paired_queries(qkv[..., :Hq * hd].reshape(B, S, Hq, hd))
        k = qkv[..., Hq * hd:(Hq + Hkv) * hd].reshape(B, S, Hkv // 2, 2 * hd)
        v = qkv[..., (Hq + Hkv) * hd:].reshape(B, S, Hkv // 2, 2 * hd)
        o, state = attend(q, k, v)
        return _differential_out(cfg, p, i, o), state


def cross_mix(cfg, p, i, h, attend):
    """Differential cross attention: own queries, ``attend(q) -> o``
    against another layer's keys and values. Returns ``out`` float32."""
    import jax
    import jax.numpy as jnp
    B, S, _ = h.shape
    with jax.named_scope("cross_attention"):
        q = (_mm(h, p["wq"]) + p["bq"].astype(jnp.float32)).astype(h.dtype)
        q = _paired_queries(q.reshape(B, S, cfg.num_heads, cfg.head_dim))
        return _differential_out(cfg, p, i, attend(q))


def gmu_mix(p, h, m):
    """The Gated Memory Unit: ``W2(m * silu(W1 h))`` with ``m`` the
    memory (B, S, C) float32."""
    import jax
    with jax.named_scope("gmu"):
        return _mm((m * jax.nn.silu(_mm(h, p["w1"]))).astype(h.dtype),
                   p["w2"])


def layer_apply(cfg, i, p, x, mix):
    """Layer ``i`` around its mixer: ``mix(h) -> (out, aux)``. Returns
    ``(y, aux)``."""
    eps = cfg.layer_norm_eps
    out, aux = mix(_norm(x, p["ln1_w"], p["ln1_b"], eps))
    x = x + out.astype(x.dtype)
    x = x + mlp(cfg, p, _norm(x, p["ln2_w"], p["ln2_b"], eps)) \
        .astype(x.dtype)
    return x, aux


def self_decoder(cfg, P, x, cache, lengths, attend_for):
    """Layers 0 .. ``full_layer`` on ``x`` (B, S, D). ``cache``: one
    level a layer, a state for a Mamba layer and whatever ``attend_for(i,
    level)`` — which returns the layer's ``attend`` — makes of the other.
    Returns ``(x, memory, cache')``."""
    new_cache, memory = [], None
    for i in range(cfg.n_self):
        p, level = P["layers"][i], cache[i]
        if cfg.kinds[i] == MAMBA:
            def mix(h, p=p, level=level):
                out, y, state = mamba_mix(cfg, p, h, level, lengths)
                return out, (y, state)

            x, (y, level) = layer_apply(cfg, i, p, x, mix)
            if i == cfg.memory_layer:
                memory = y
        else:
            x, level = layer_apply(
                cfg, i, p, x, lambda h, p=p, i=i, level=level:
                attention_mix(cfg, p, i, h, attend_for(i, level)))
        new_cache.append(level)
    return x, memory, new_cache


def cross_decoder(cfg, P, x, memory, attend):
    """Layers ``full_layer + 1`` .. on ``x`` (B, S, D) with the memory
    (B, S, C) and ``attend(q) -> o`` over the full layer's keys and
    values."""
    for i in range(cfg.n_self, cfg.num_layers):
        p = P["layers"][i]
        if cfg.kinds[i] == GMU:
            x, _ = layer_apply(cfg, i, p, x,
                               lambda h, p=p: (gmu_mix(p, h, memory), None))
        else:
            x, _ = layer_apply(
                cfg, i, p, x, lambda h, p=p, i=i:
                (cross_mix(cfg, p, i, h, attend), None))
    return x


def head_logits(cfg, P, x):
    """Final LayerNorm, then the tied embedding as head: float32."""
    import jax.numpy as jnp
    h = _norm(x, P["ln_f"], P["ln_f_b"], cfg.layer_norm_eps)
    return jnp.einsum("...d,vd->...v", h, P["emb"],
                      preferred_element_type=jnp.float32)


def zero_state(cfg, rows, dtype):
    """What a Mamba layer keeps of ``rows`` fresh sequences."""
    import jax.numpy as jnp
    return {"conv": jnp.zeros((rows, cfg.d_conv - 1, cfg.d_inner), dtype),
            "ssm": jnp.zeros((rows, cfg.d_state, cfg.d_inner), jnp.float32)}


def _window_of(cfg, i):
    return None if i == cfg.full_layer else cfg.sliding_window


def forward_logits(cfg, P, tokens):
    """The eval forward: logits (B, S, V) float32 of whole sequences,
    every layer on every position (no cache, no last-token shortcut)."""
    import jax.numpy as jnp
    B, S = tokens.shape
    x = embed(P, tokens)
    lengths = jnp.full((B,), S, jnp.int32)
    fresh = zero_state(cfg, B, x.dtype)
    kept = {}

    def attend_for(i, _):
        def attend(q, k, v):
            kept[i] = (k, v)
            return masked_attention(q, k, v, cfg.scale, _window_of(cfg, i)), \
                None
        return attend

    x, memory, _ = self_decoder(cfg, P, x, [fresh] * cfg.n_self, lengths,
                                attend_for)
    k, v = kept[cfg.full_layer]
    x = cross_decoder(cfg, P, x, memory,
                      lambda q: masked_attention(q, k, v, cfg.scale))
    return head_logits(cfg, P, x)


# ---------------------------------------------------------------------------
# the model.Model
# ---------------------------------------------------------------------------

class Phi4FlashBlock(layer.Layer):
    """One layer's parameters, by its kind (``Config.leaf_shapes``)."""

    def __init__(self, cfg, i, init):
        super().__init__()
        self._cfg, self._i, self._init = cfg, i, init

    def initialize(self, x):
        self._names = []
        for name, shape in self._cfg.leaf_shapes(self._i).items():
            mean, std = self._init(name)
            t = _param(shape, x.device, dtype=x.dtype)
            t.gaussian(mean, std)
            setattr(self, name, t)
            self._names.append(name)

    def _own_params(self):
        return {n: getattr(self, n) for n in self._names}

    leaves = _own_params


class Phi4FlashLM(DrawnBeforeCompile, model.Model):
    """A ``phi4flash`` language model, whole.

    ``forward(ids)`` takes a float tensor of token ids (B, S) and gives
    the logits (B, S, vocab) of the whole sequences (eval);
    ``decode_adapter(policy)`` hands the serving engine the same layers
    over one cache of rings and states. Compile under
    ``policy="bfloat16"`` to hold the weights once, at 2 bytes.

    ``init``: ``{leaf name or "matrix" | "norm" | "lam": (mean, std)}``
    — how a fresh model draws its leaves (a served model's come from its
    checkpoint). ``A_log`` and ``dt_bias`` decide how long the state
    remembers: the defaults spread ``A`` over about 1..16 and the step
    over about 0.001..0.1."""

    INIT = {"matrix": (0.0, 0.02), "norm": (1.0, 0.02), "bias": (0.0, 0.02),
            "lam": (0.0, 0.1), "D": (1.0, 0.02), "conv_w": (0.0, 0.3),
            "A_log": (1.9, 0.75), "dt_bias": (-4.6, 1.3)}

    def __init__(self, vocab_size, hidden_size=2560, num_layers=32,
                 num_heads=40, num_kv_heads=20, intermediate_size=10240,
                 sliding_window=512, layer_norm_eps=1e-5, d_state=16,
                 d_conv=4, expand=2, dt_rank=None, init=None):
        super().__init__()
        self.vocab_size = int(vocab_size)
        self.cfg = Config(
            hidden_size=hidden_size, num_layers=num_layers,
            num_heads=num_heads, num_kv_heads=num_kv_heads,
            intermediate_size=intermediate_size,
            sliding_window=sliding_window, layer_norm_eps=layer_norm_eps,
            d_state=d_state, d_conv=d_conv, expand=expand, dt_rank=dt_rank)
        self._init = dict(self.INIT, **(init or {}))
        self.layers = [Phi4FlashBlock(self.cfg, i, self._init_of)
                       for i in range(self.cfg.num_layers)]
        self._ready = False

    def _init_of(self, name):
        if name in self._init:
            return self._init[name]
        if name.startswith("lam_"):
            return self._init["lam"]
        if name in _NORM_SCALES:
            return self._init["norm"]
        return self._init["bias" if name in _BIASES else "matrix"]

    def _draw_params(self, dev, dtype):
        from ..tensor import Tensor
        D = self.cfg.hidden_size
        for name, shape in (("emb", (self.vocab_size, D)), ("ln_f", (D,)),
                            ("ln_f_b", (D,))):
            t = _param(shape, dev, dtype=dtype)
            t.gaussian(*self._init_of(name))
            setattr(self, name, t)
        probe = Tensor(shape=(1, 1, D), device=dev, dtype=dtype,
                       requires_grad=False)
        for blk in self.layers:
            blk.initialize(probe)
            blk._initialized = True

    def _own_params(self):
        return {"emb": self.emb, "ln_f": self.ln_f, "ln_f_b": self.ln_f_b}

    def param_tensors(self):
        """The params tree of the pure functions, as Tensors."""
        return {**self._own_params(),
                "layers": [blk.leaves() for blk in self.layers]}

    def forward(self, ids):
        leaves, treedef = self._leaves(ids)
        return EvalForward(self.cfg, treedef, forward_logits)(ids, *leaves)

    def train_one_batch(self, *a, **kw):
        raise NotImplementedError(
            "Phi4FlashLM is inference-only: the selective scan has no "
            "reverse here, and at published widths the whole model is "
            "62 GB of training state")

    def decode_adapter(self, policy=None):
        return _ServeAdapter(self, policy)


def create_model(vocab_size=256, **kwargs):
    return Phi4FlashLM(vocab_size, **kwargs)


# ---------------------------------------------------------------------------
# the serve adapter
# ---------------------------------------------------------------------------

class _ServeAdapter(ByReferenceAdapter):
    """What ``ServingEngine`` needs of the model (docs/serving.md, "The
    adapter contract"): the model's own arrays by reference and ONE cache
    of a level a self-decoder layer — a state for a Mamba layer, a ring
    of ``min(window, max_len)`` positions for a window layer, of
    ``max_len`` for the full one, which every cross layer reads beside
    its owner. The cross-decoder's layers own no level."""

    def cache_kinds(self):
        c = self.cfg
        return ["state" if c.kinds[i] == MAMBA else
                "full" if i == c.full_layer else "window"
                for i in range(c.n_self)]

    def cache_readers(self):
        """Layers that read each level a decode tick: its owner, and for
        the full ring every cross layer too."""
        c = self.cfg
        cross = sum(k == CROSS for k in c.kinds)
        return [1 + cross * (i == c.full_layer) for i in range(c.n_self)]

    def prefill_rows(self, lengths):
        """Rows each decoder runs of a prefill batch of these prompt
        lengths: the self-decoder all of them, the cross-decoder each
        prompt's last."""
        return {"self": int(sum(lengths)), "cross": len(lengths)}

    def init_cache(self, slots, max_len):
        from ..serving import kv_cache
        c, dtype = self.cfg, self._cache_dtype()
        return [zero_state(c, slots, dtype) if kind == "state" else
                kv_cache.init_cache(
                    slots, c.num_kv_heads // 2,
                    int(max_len) if kind == "full"
                    else min(c.sliding_window, int(max_len)),
                    2 * c.head_dim, dtype)
                for kind in self.cache_kinds()]

    def prefill_fn(self):
        import jax.numpy as jnp
        from ..serving import kv_cache
        cfg = self.cfg

        def fn(P, cache, tokens, lengths, slot_ids, valid):
            B, S = tokens.shape
            lengths = jnp.where(valid, lengths.astype(jnp.int32), 0)
            x = embed(P, tokens)
            R = min(PREFILL_ROWS, S)
            n_blocks = -(-jnp.max(lengths) // R) if S % R == 0 else None
            kept = {}

            def attend_for(i, level):
                def attend(q, k, v):
                    if i == cfg.full_layer:
                        kept["kv"] = (k, v)
                    o = masked_attention(q, k, v, cfg.scale,
                                         _window_of(cfg, i), n_blocks)
                    return o, kv_cache.write_prompts(
                        level, slot_ids, k, v, lengths, valid)
                return attend

            # every prompt starts from nought: a reused slot inherits
            # nothing of the request that held it
            fresh = zero_state(cfg, B, x.dtype)
            x, memory, levels = self_decoder(
                cfg, P, x, [fresh if "ssm" in lv else lv for lv in cache],
                lengths, attend_for)
            # the fresh rows' final states into their slots (a row that
            # is padding goes past the end and is dropped)
            at = jnp.where(valid, slot_ids, cache[0]["ssm"].shape[0])
            new_cache = [
                {n: old[n].at[at].set(new[n].astype(old[n].dtype),
                                      mode="drop") for n in old}
                if "ssm" in old else new
                for old, new in zip(cache, levels)]
            # the cross-decoder on each prompt's last token alone
            last = jnp.maximum(lengths - 1, 0)
            pick = lambda a: jnp.take_along_axis(   # noqa: E731
                a, last[:, None, None], axis=1)
            k, v = (a.swapaxes(1, 2) for a in kept["kv"])    # B, H, S, D
            x = cross_decoder(
                cfg, P, pick(x), pick(memory),
                lambda q: kv_cache.attend(
                    q.swapaxes(1, 2), {"k": k, "v": v}, last,
                    cfg.scale).swapaxes(1, 2))
            return new_cache, head_logits(cfg, P, x[:, 0])

        return fn

    def decode_fn(self):
        import jax.numpy as jnp
        from ..serving import kv_cache
        cfg = self.cfg

        def fn(P, cache, tokens, positions, active):
            positions = positions.astype(jnp.int32)
            x = embed(P, tokens)[:, None, :]

            def attend_for(i, level):
                def attend(q, k, v):
                    o, new = kv_cache.decode_token(
                        level, q.swapaxes(1, 2), k[:, 0], v[:, 0],
                        positions, active, cfg.scale)
                    return o.swapaxes(1, 2), new
                return attend

            x, memory, new_cache = self_decoder(
                cfg, P, x, cache, active.astype(jnp.int32), attend_for)
            full = new_cache[cfg.full_layer]
            x = cross_decoder(
                cfg, P, x, memory,
                lambda q: kv_cache.attend_token(
                    full, q.swapaxes(1, 2), positions, active,
                    cfg.scale).swapaxes(1, 2))
            return new_cache, head_logits(cfg, P, x[:, 0])

        return fn


__all__ = ["Phi4FlashLM", "Phi4FlashBlock", "Config", "forward_logits",
           "mamba_mix", "attention_mix", "cross_mix", "gmu_mix", "mlp",
           "create_model"]
