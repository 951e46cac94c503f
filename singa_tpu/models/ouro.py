"""Looped language model (the Ouro LoopLM family): ONE stack of layers run
several times, with an exit after every pass.

A pass runs the same ``n_layers`` decoder layers; the stack runs
``loop_passes`` (T) times a forward, so every weight of a layer is used T
times in one step and its gradient is the sum of T uses::

    h_0 = E[id]
    for t in 1..T:
        x = h_{t-1}
        for each layer l:                         # the SAME layers each pass
            x = x + N2_l Attn_l(N1_l x)           # "sandwich" norms: one
            x = x + N4_l MLP_l(N3_l x)            # before, one after each
        h_t = N_loop x                            # one shared norm; feeds
                                                  # exit t AND pass t + 1
    lam_t = sigmoid(w_g . h_t + b_g)              # the exit gate
    ce_t  = CE(h_t W_head, target)
    p_t = lam_t prod_{j<t} (1 - lam_j)  (t < T),   p_T = prod_{j<T} (1 - lam_j)
    loss = mean_tokens( sum_t p_t ce_t - beta H(p) ),  H(p) = -sum_t p_t log p_t

Attention is full causal multi-head attention (as many KV heads as query
heads) with rotary positions (rotate-half, on the whole head, the same
positions every pass) and biases on q, k and v; the MLP is SwiGLU; every
norm is an RMSNorm. Each pass attends to its own keys and values only.

Train path: the layer is written ONCE as a pure function
(:func:`sandwich_layer`) run by one tape op a layer application; with
``remat=True`` each application goes through ``autograd.checkpoint`` and
is recomputed in the backward. The exits use the per-token fused CE head
(``ops/losses.fused_ce_rows``), so the (tokens x vocab) logits never
exist. A pass's ops trace under ``jax.named_scope("loop_pass")`` and an
exit's under ``"loop_exit"``.

Observability: the gauges ``model_loop_passes``, ``model_layer_applications``
(T x L, per token per forward) and ``model_exit_heads`` are set at build;
the step keeps each pass's mean exit probability and mean CE on the device
(no host sync a step), and :meth:`OuroLM.exit_stats` reads them on demand
into ``loop_exit_share{pass}`` and ``loop_exit_loss{pass}``.
"""

from __future__ import annotations

import math

import numpy as np

from .. import autograd, layer, model
from ..autograd_base import Operator
from ..layer import _param
from ..mixed_precision import cast_compute
from ..tensor import Tensor
from .longcat_flash import rms_norm

# a fresh model's matrices, embedding, head and gate: N(0, INIT_STD) (the
# family's initializer_range); norm scales 1, biases 0
INIT_STD = 0.02
_NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
          "post_attention_layernorm_2")


def leaf_shapes(d_model, n_heads, head_dim, d_ff):
    """One layer's parameters: name -> shape. Matrices are (in, out)."""
    inner = n_heads * head_dim
    shapes = {n: (d_model,) for n in _NORMS}
    for proj in ("q_proj", "k_proj", "v_proj"):
        shapes[f"{proj}.W"] = (d_model, inner)
        shapes[f"{proj}.b"] = (inner,)
    shapes["o_proj.W"] = (inner, d_model)
    shapes["gate_proj.W"] = (d_model, d_ff)
    shapes["up_proj.W"] = (d_model, d_ff)
    shapes["down_proj.W"] = (d_ff, d_model)
    return shapes


def _mm(a, w):
    """``a @ w`` with both operands in the policy's compute dtype, float32
    sums and result."""
    import jax.numpy as jnp
    a, w = cast_compute(a, w)
    return jnp.matmul(a, w, preferred_element_type=jnp.float32)


def rope_half(t, theta):
    """Rotary positions 0..S-1 on the whole head, halves rotated against
    each other (``rotate_half``). ``t``: (B, S, H, D) float32."""
    import jax.numpy as jnp
    S, D = t.shape[1], t.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv        # S, D/2
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    rot = jnp.concatenate([-t[..., D // 2:], t[..., :D // 2]], -1)
    return t * cos + rot * sin


def sandwich_layer(x, p, *, n_heads, head_dim, eps, theta):
    """One application of one layer: ``x`` (B, S, D) float32 -> the same.
    ``p``: the layer's leaves by :func:`leaf_shapes`' names."""
    import jax
    import jax.numpy as jnp
    from ..ops.attention import flash_attention
    B, S, _ = x.shape
    with jax.named_scope("attention"):
        h = rms_norm(x, p["input_layernorm"], eps)

        def heads(name):
            t = _mm(h, p[f"{name}.W"]) + p[f"{name}.b"].astype(jnp.float32)
            return t.reshape(B, S, n_heads, head_dim)

        q, k = rope_half(heads("q_proj"), theta), rope_half(heads("k_proj"),
                                                            theta)
        q, k, v = (t.transpose(0, 2, 1, 3)
                   for t in cast_compute(q, k, heads("v_proj")))
        o = flash_attention(q, k, v, True, 1.0 / math.sqrt(head_dim))
        o = o.transpose(0, 2, 1, 3).reshape(B, S, n_heads * head_dim)
        x = x + rms_norm(_mm(o, p["o_proj.W"]), p["input_layernorm_2"], eps)
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["post_attention_layernorm"], eps)
        m = jax.nn.silu(_mm(h, p["gate_proj.W"])) * _mm(h, p["up_proj.W"])
        x = x + rms_norm(_mm(m, p["down_proj.W"]),
                         p["post_attention_layernorm_2"], eps)
    return x


def exit_objective(z, ce, beta):
    """The entropy-regularised exit loss. ``z``: (T - 1, N) gate logits of
    the first T - 1 exits; ``ce``: (T, N) each exit's cross-entropy.
    Returns (loss, stats (2, T): each exit's mean probability and mean
    cross-entropy). The exit distribution is formed in log space:
    ``log p_t = log lam_t + sum_{j<t} log(1 - lam_j)``."""
    import jax
    import jax.numpy as jnp
    zero = jnp.zeros_like(ce[:1])
    log_stay = jax.nn.log_sigmoid(-z)                    # log(1 - lam)
    log_p = jnp.concatenate([zero, jnp.cumsum(log_stay, 0)]) \
        + jnp.concatenate([jax.nn.log_sigmoid(z), zero])
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, 0)
    loss = jnp.mean(jnp.sum(p * ce, 0) - beta * entropy)
    stats = jax.lax.stop_gradient(jnp.stack([jnp.mean(p, 1),
                                             jnp.mean(ce, 1)]))
    return loss, stats


class _Apply(Operator):
    """One layer application on the tape: ``fn(x, {name: leaf})``."""

    def __init__(self, fn, names):
        super().__init__()
        self._fn, self._names = fn, names

    def forward(self, x, *leaves):
        return self._fn(x, dict(zip(self._names, leaves)))


class _Lookup(Operator):
    """Rows of the float32 table (no compute cast: the stream stays
    float32 from the first pass to the last exit)."""

    def forward(self, ids, W):
        import jax
        import jax.numpy as jnp
        return jnp.take(W, jax.lax.stop_gradient(ids).astype(jnp.int32), 0)


class _Exits(Operator):
    """Every exit and the objective: (targets, head W, gate W, gate b,
    h_1 .. h_T) -> (loss, stats)."""

    def __init__(self, beta, chunk):
        super().__init__()
        self._beta, self._chunk = beta, chunk

    def forward(self, targets, W, gate_w, gate_b, *hs):
        import jax
        import jax.numpy as jnp
        from ..ops.losses import fused_ce_rows
        ids = targets.reshape(-1)
        ces, zs = [], []
        for t, h in enumerate(hs):
            with jax.named_scope("loop_exit"):
                flat = h.reshape(-1, h.shape[-1])
                ces.append(fused_ce_rows(flat, W, None, ids, self._chunk))
                if t < len(hs) - 1:     # the last exit takes what is left
                    zs.append(_mm(flat, gate_w)[:, 0]
                              + gate_b[0].astype(jnp.float32))
        z = jnp.stack(zs) if zs else jnp.zeros((0, ids.shape[0]),
                                               jnp.float32)
        return exit_objective(z, jnp.stack(ces), self._beta)


class SandwichLayer(layer.Layer):
    """One decoder layer's parameters (:func:`leaf_shapes`); applying it
    runs :func:`sandwich_layer` as one tape op."""

    def __init__(self, d_model, n_heads, head_dim, d_ff, rms_eps, rope_theta):
        super().__init__()
        self._shapes = leaf_shapes(d_model, n_heads, head_dim, d_ff)
        self._statics = dict(n_heads=n_heads, head_dim=head_dim, eps=rms_eps,
                             theta=rope_theta)

    def initialize(self, x):
        self._leaves = {}
        for name, shape in self._shapes.items():       # biases: zeros
            t = _param(shape, x.device,
                       init="ones" if name in _NORMS else "zeros")
            if name.endswith(".W"):
                t.gaussian(0.0, INIT_STD)
            self._leaves[name] = t

    def _own_params(self):
        return dict(self._leaves)

    def forward(self, x):
        statics = self._statics
        names = tuple(self._leaves)
        op = _Apply(lambda x, p: sandwich_layer(x, p, **statics), names)
        return op(x, *self._leaves.values())


class RMSNorm(layer.Layer):
    """RMSNorm with a learned scale; statistics and result in float32."""

    def __init__(self, eps):
        super().__init__()
        self.eps = eps

    def initialize(self, x):
        self.scale = _param((x.shape[-1],), x.device, init="ones")

    def forward(self, x):
        eps = self.eps
        return _Apply(lambda x, p: rms_norm(x, p["scale"], eps),
                      ("scale",))(x, self.scale)

    def _own_params(self):
        return {"scale": self.scale}


class LoopExits(layer.Layer):
    """The untied head ``head.W`` (D, V) shared by every exit, and the
    exit gate ``gate`` (Linear(D, 1) with a bias)."""

    def __init__(self, vocab_size, beta, chunk):
        super().__init__()
        self.vocab_size, self.beta, self.chunk = vocab_size, beta, chunk

    def initialize(self, h):
        d, dev = h.shape[-1], h.device
        self.head_W = _param((d, self.vocab_size), dev)
        self.head_W.gaussian(0.0, INIT_STD)
        self.gate_W = _param((d, 1), dev)
        self.gate_W.gaussian(0.0, INIT_STD)
        self.gate_b = _param((1,), dev)

    def _own_params(self):
        return {"head.W": self.head_W, "gate.W": self.gate_W,
                "gate.b": self.gate_b}

    def forward(self, hs, targets):
        return _Exits(self.beta, self.chunk)(
            targets, self.head_W, self.gate_W, self.gate_b, *hs)

    def logits(self, h):
        return autograd.matmul(h, self.head_W)


class OuroLM(model.Model):
    """A looped decoder LM trained on its exits' entropy-regularised loss.

    ``train_one_batch(ids, targets)`` takes float tensors of token ids and
    target ids, both (B, S), and returns ``(loss, loss)`` after the
    optimizer's step (no logits: the exits never form them).
    ``forward(ids)`` (eval) gives the last exit's logits (B, S, V).
    ``fused_head_chunk``: vocabulary columns a chunk of the fused head
    (None: the whole vocabulary in one). ``remat``: recompute each layer
    application in the backward."""

    def __init__(self, vocab_size, d_model=2048, n_heads=16, head_dim=128,
                 d_ff=5632, n_layers=4, loop_passes=4, rope_theta=1e6,
                 rms_eps=1e-6, exit_entropy=0.1, fused_head_chunk=None,
                 remat=False):
        super().__init__()
        self.vocab_size, self.d_model = int(vocab_size), int(d_model)
        self.loop_passes = int(loop_passes)
        self.remat = remat
        self.embed = layer.Embedding(self.vocab_size, d_model)
        self.layers = [SandwichLayer(d_model, n_heads, head_dim, d_ff,
                                     rms_eps, rope_theta)
                       for _ in range(n_layers)]
        self.norm = RMSNorm(rms_eps)
        self.exits = LoopExits(self.vocab_size, float(exit_entropy),
                               int(fused_head_chunk or vocab_size))
        self._exit_stats = None
        from ..observability.metrics import default_registry
        reg = default_registry()
        for name, value, what in (
                ("model_loop_passes", self.loop_passes,
                 "passes of the layer stack a forward"),
                ("model_layer_applications", self.loop_passes * n_layers,
                 "layer applications a token a forward"),
                ("model_exit_heads", self.loop_passes,
                 "output heads a token a forward")):
            reg.gauge(name, what, labels=("model",)).set(
                value, model=type(self).__name__)

    def compile(self, inputs, *args, **kwargs):
        """``Model.compile``, with the parameters drawn first, out of its
        dry run: inside that trace a draw is folded into its executable as
        a constant (0.8 GB for the embedding or the head at the published
        widths) and compiles for minutes."""
        probe = Tensor(shape=(1, 1, self.d_model), device=inputs[0].device,
                       requires_grad=False)
        for lyr in (self.embed, *self.layers, self.norm, self.exits):
            if not lyr._initialized:
                lyr.initialize(probe)
                lyr._initialized = True
        return super().compile(inputs, *args, **kwargs)

    def _own_states(self):
        if self._exit_stats is None:
            return {}
        return {"exit_stats": self._exit_stats}

    def _passes(self, ids):
        """[h_1 .. h_T]: the normed state after each pass."""
        import jax
        self.embed.ensure_initialized(ids)
        x = _Lookup()(ids, self.embed.W)
        hs = []
        for _ in range(self.loop_passes):
            with jax.named_scope("loop_pass"):
                for lyr in self.layers:
                    x = autograd.checkpoint(lyr, x) if self.remat \
                        else lyr(x)
                x = self.norm(x)
            hs.append(x)
        return hs

    def forward(self, ids):
        hs = self._passes(ids)
        self.exits.ensure_initialized(hs[-1])
        return self.exits.logits(hs[-1])

    def objective(self, ids, targets):
        """(loss, stats) Tensors of one batch, on the tape in training."""
        hs = self._passes(ids)
        self.exits.ensure_initialized(hs[-1])
        return self.exits(hs, targets)

    def train_one_batch(self, ids, targets):
        loss, stats = self.objective(ids, targets)
        if self._exit_stats is None:
            self._exit_stats = Tensor(data=stats.data, device=ids.device,
                                      requires_grad=False)
        self._exit_stats.data = stats.data
        self.optimizer(loss)
        return loss, loss

    def exit_stats(self, registry=None):
        """The last step's exits, read from the device now: {"share": each
        exit's mean probability, "loss": each exit's mean cross-entropy},
        also set as the gauges ``loop_exit_share{pass}`` and
        ``loop_exit_loss{pass}`` (passes counted from 1). None before the
        first step."""
        if self._exit_stats is None:
            return None
        import jax
        from ..observability.metrics import default_registry
        share, loss = np.asarray(jax.device_get(self._exit_stats.data),
                                 np.float64)
        reg = registry or default_registry()
        for name, values, what in (
                ("loop_exit_share", share, "mean exit probability"),
                ("loop_exit_loss", loss, "mean cross-entropy at the exit")):
            g = reg.gauge(name, what, labels=("pass",))
            for t, v in enumerate(values):
                g.set(v, **{"pass": t + 1})
        return {"share": share.tolist(), "loss": loss.tolist()}


def create_model(vocab_size=256, **kwargs):
    return OuroLM(vocab_size, **kwargs)


__all__ = ["OuroLM", "SandwichLayer", "RMSNorm", "LoopExits",
           "sandwich_layer", "exit_objective", "rope_half", "leaf_shapes",
           "create_model"]
