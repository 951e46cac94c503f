"""DeepSeek-V3-style sparse-expert LM with latent attention and a full-rank
query (the Kanana-2 family's language model), trained.

A pre-norm layer::

    x += W_o MLA(N1 x)
    u  = N2 x
    x += FFN_l(u)       l < first_k_dense: one SiLU-gated MLP
                        else: the routed experts held here + the shared one
    loss = mean_tokens CE(N_f(x) W_head, target)

Attention is multi-head LATENT attention with a full-rank query: ``q = W_q
h`` (H heads of ``nope + rope``); ``[c~; k~_r] = W_kva h``, ``c =
RMSNorm(c~)`` and one rotated ``k_r`` shared by every head; ``[k_n,h; v_h]
= W_kvb,h c``; causal softmax of ``[q_n; q_r] . [k_n; k_r] / sqrt(nope +
rope)`` over ``v_h``. Keys are ``nope + rope`` = 192 wide and values 128:
the flash kernels take them as they are (``ops/attention.flash_attention``),
V is not padded. The projections are ``models/longcat_flash.mla_project``'s
(its full-rank query path), rotary positions are interleaved pairs.

The expert layer is :func:`singa_tpu.parallel.moe.expert_share_ffn` on its
grouped path (megablox's Pallas kernels, with a reverse): sigmoid scores over all of the
router's columns in float32, the top ``top_k`` of the scores plus a
selection bias (the choice only), weights the picks' own scores divided by
their sum times a factor, the pairs routed to the experts held here worked
off with none dropped, and the shared expert (a bank of one) added once.

Train path: each layer is written ONCE as a pure function
(:func:`layer_apply`) run by one tape op; with ``remat=True`` each layer
goes through ``autograd.checkpoint`` and is recomputed in the backward. The
head is the per-token fused CE head (``ops/losses.fused_ce_rows``): the
(tokens x vocabulary) logits never exist. A model holds ONE chip's share of
a stated deployment: ``num_experts`` / ``vocab_size`` count what lives here,
the router keeps its full width ``router_width`` and ``experts_held_from``
says which experts these are.

Observability: the named scopes ``mla_project``, ``latent_attention``,
``dense_ffn``, ``moe_route``, ``moe_experts`` and ``moe_shared``; the
grouped product's kernels are megablox's ``gmm`` (forward and the rows'
gradient) and ``tgmm`` (the banks' gradient). Each expert layer's counts of
the newest step, and their sums over the steps taken, stay on the device
(no host sync a step); the gauges ``moe_pairs_here{layer}``,
``moe_pairs_here_per_step{layer}``, ``moe_experts_touched{layer}``,
``moe_load_max{layer}``, ``moe_load_mean{layer}`` and
``moe_padding_share{layer}`` read them when they are read
(:data:`GAUGES`, :meth:`KananaLM.moe_stats`).

One chip's share is trained ALONE (``expert_share_ffn(..., alone=True)``):
the outputs of the picks routed to experts held elsewhere never come back,
and the router's gradient is taken as if each had the picks here on
average, so the router does not learn to prefer the experts held here.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from .. import autograd, layer, model
from ..autograd_base import Operator
from ..layer import _param
from ..mixed_precision import cast_compute
from ..parallel.moe import Route, expert_share_ffn
from ..tensor import Tensor
from .longcat_flash import (_mm, dense_ffn, expanded_qkv, mla_project,
                            rms_norm)
from .ouro import RMSNorm, _Apply, _Lookup

# a fresh model's matrices, embedding and head: N(0, INIT_STD)
# (initializer_range); norm scales 1
INIT_STD = 0.02
NORMS = ("attn_norm", "kv_norm", "ffn_norm")
# leaves that stay float32 under a mixed policy: the norms' scales and the
# router, whose scores are formed in float32 at the highest precision
KEPT_FLOAT32 = NORMS + ("router", "router_bias")
# per expert layer, in this order: pairs routed to held experts, held
# experts with a pair, the most pairs one got, rows the grouped product
# worked
STAT_NAMES = ("pairs_here", "experts_touched", "load_max", "rows_computed")
# the gauges moe_<name>{layer}, each read from the device when it is read
GAUGES = (
    ("pairs_here", "pairs routed to the experts held here, the last step"),
    ("pairs_here_per_step", "pairs routed to the experts held here, mean "
     "of every step the model has taken"),
    ("experts_touched", "held experts that got a pair, the last step"),
    ("load_max", "most pairs a held expert got, the last step"),
    ("load_mean", "mean pairs a held expert got, the last step"),
    ("padding_share", "rows of the grouped product that held no pair, "
     "over its rows, the last step"))


class Config:
    """The static half of a layer: what :func:`layer_apply` closes over.
    Every field is a python number; the attribute names are those
    ``longcat_flash.mla_project`` reads."""

    def __init__(self, *, hidden_size, num_heads, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, kv_lora_rank,
                 intermediate_size, moe_intermediate_size, n_shared_experts,
                 num_experts, router_width, top_k, routed_scaling_factor,
                 norm_topk_prob, experts_held_from, first_k_dense,
                 rope_theta, rms_norm_eps):
        self.hidden_size, self.num_heads = int(hidden_size), int(num_heads)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim, self.kv_lora_rank = int(v_head_dim), \
            int(kv_lora_rank)
        self.q_lora_rank = 0                # the query is full-rank
        self.q_scale = self.kv_scale = 1.0
        self.intermediate_size = int(intermediate_size)
        self.shared_width = int(moe_intermediate_size) \
            * int(n_shared_experts)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_experts, self.router_width = int(num_experts), \
            int(router_width)
        self.top_k, self.experts_held_from = int(top_k), \
            int(experts_held_from)
        self.first_k_dense = int(first_k_dense)
        self.rope_theta, self.rms_norm_eps = float(rope_theta), \
            float(rms_norm_eps)
        self.route = Route("sigmoid", bool(norm_topk_prob),
                           float(routed_scaling_factor))
        if self.experts_held_from + self.num_experts > self.router_width:
            raise ValueError(
                f"experts {self.experts_held_from}.."
                f"{self.experts_held_from + self.num_experts} do not fit a "
                f"router {self.router_width} wide")

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def scale(self):
        return 1.0 / math.sqrt(self.qk_head_dim)

    def leaf_shapes(self, dense):
        """``{leaf name: shape}`` of one layer, matrices (in, out); banks
        (experts, in, out)."""
        D, H = self.hidden_size, self.num_heads
        shapes = {
            "attn_norm": (D,),
            "wq": (D, H * self.qk_head_dim),
            "wkv_a": (D, self.kv_lora_rank + self.qk_rope_head_dim),
            "kv_norm": (self.kv_lora_rank,),
            "wkv_b": (self.kv_lora_rank,
                      H * (self.qk_nope_head_dim + self.v_head_dim)),
            "wo": (H * self.v_head_dim, D),
            "ffn_norm": (D,)}
        if dense:
            F = self.intermediate_size
            shapes.update({"ffn_gate": (D, F), "ffn_up": (D, F),
                           "ffn_down": (F, D)})
            return shapes
        G, Fe, Fs = self.num_experts, self.moe_intermediate_size, \
            self.shared_width
        shapes.update({
            "router": (D, self.router_width),
            "router_bias": (self.router_width,),
            "w_gate": (G, D, Fe), "w_up": (G, D, Fe), "w_down": (G, Fe, D),
            "s_gate": (1, D, Fs), "s_up": (1, D, Fs), "s_down": (1, Fs, D)})
        return shapes


# ---------------------------------------------------------------------------
# the pure functions
# ---------------------------------------------------------------------------

def mla_flash(cfg, p, q_n, q_r, c, k_r):
    """Causal latent attention over whole sequences through the flash
    kernels, the keys (nope + rope wide) and values (v_head_dim wide)
    written out a head (``longcat_flash.expanded_qkv``). Returns (B, S,
    H, v)."""
    import jax
    from ..ops.attention import flash_attention
    with jax.named_scope("latent_attention"):
        q, k, v = (t.transpose(0, 2, 1, 3) for t in expanded_qkv(
            cfg, p, None, q_n, q_r, c, k_r))
        o = flash_attention(q, k, v, True, cfg.scale)
        return o.transpose(0, 2, 1, 3)


def layer_apply(cfg, p, x, dense):
    """One layer on ``x`` (B, S, D) float32. ``p``: the leaves of
    ``Config.leaf_shapes(dense)``, matrices in the compute dtype, norms
    and router float32. Returns ``(x, stats)``: ``stats`` (4,) float32,
    :data:`STAT_NAMES` of the expert layer (nought for a dense one)."""
    import jax
    import jax.numpy as jnp
    B, S, D = x.shape
    dt, eps = p["wq"].dtype, cfg.rms_norm_eps
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    h = rms_norm(x, p["attn_norm"], eps).astype(dt)
    o = mla_flash(cfg, p, *mla_project(cfg, p, None, h, positions))
    x = x + _mm(o.reshape(B, S, -1), p["wo"])
    u32 = rms_norm(x, p["ffn_norm"], eps)
    u = u32.astype(dt)
    if dense:
        return x + dense_ffn(p, None, u), jnp.zeros((len(STAT_NAMES),),
                                                     jnp.float32)
    y, stats = expert_share_ffn(
        p, u.reshape(B * S, D), top_k=cfg.top_k,
        held_from=cfg.experts_held_from, h_route=u32.reshape(B * S, D),
        route=cfg.route, grouped=True, alone=True)
    stats = jax.lax.stop_gradient(jnp.stack(
        [stats[n].astype(jnp.float32) for n in STAT_NAMES]))
    return x + y.reshape(B, S, D), stats


# ---------------------------------------------------------------------------
# the model.Model
# ---------------------------------------------------------------------------

class _Head(Operator):
    """The mean cross-entropy of the fused head: (targets, h, W) ->
    scalar."""

    def __init__(self, chunk):
        super().__init__()
        self._chunk = chunk

    def forward(self, targets, h, W):
        import jax.numpy as jnp
        from ..ops.losses import fused_ce_rows
        flat = h.reshape(-1, h.shape[-1])
        return jnp.mean(fused_ce_rows(flat, W, None, targets.reshape(-1),
                                      self._chunk))


class KananaLayer(layer.Layer):
    """One layer's parameters (``Config.leaf_shapes``); applying it runs
    :func:`layer_apply` as one tape op. An expert layer leaves its counts
    in ``aux_loss`` (the channel by which ``autograd.checkpoint`` carries
    a sublayer's side output out of the recomputed block); they take no
    gradient."""

    def __init__(self, cfg, dense, bias_std):
        super().__init__()
        self._cfg, self._dense, self._bias_std = cfg, dense, bias_std
        self._shapes = cfg.leaf_shapes(dense)
        self.aux_loss = None

    def initialize(self, x):
        self._leaves = {}
        for name, shape in self._shapes.items():
            t = _param(shape, x.device,
                       init="ones" if name in NORMS else "zeros")
            if name not in NORMS:
                t.gaussian(0.0, self._bias_std if name == "router_bias"
                           else INIT_STD)
            self._leaves[name] = t

    def _own_params(self):
        return dict(self._leaves)

    def forward(self, x):
        cfg, dense = self._cfg, self._dense
        names = tuple(self._leaves)

        def fn(x, p):
            p = {n: v if n in KEPT_FLOAT32 else cast_compute(v)
                 for n, v in p.items()}
            return layer_apply(cfg, p, x, dense)

        y, stats = _Apply(fn, names)(x, *self._leaves.values())
        self.aux_loss = stats
        return y


class KananaLM(model.Model):
    """One chip's share of a Kanana-2 (DeepSeek-V3-style) language model,
    trained on the mean cross-entropy over the vocabulary held here.

    ``train_one_batch(ids, targets)`` takes float tensors of token ids and
    target ids, both (B, S), and returns ``(loss, loss)`` after the
    optimizer's step (no logits: the fused head never forms them).
    ``forward(ids)`` (eval) gives the logits (B, S, vocab_size).
    ``fused_head_chunk``: vocabulary columns a chunk of the fused head
    (None: the whole vocabulary in one). ``remat``: recompute each layer
    in the backward. ``router_bias_std``: how a fresh model draws the
    selection bias, a fixed buffer in effect (it takes part in the choice
    only, so its gradient is nought and the optimizer leaves it)."""

    def __init__(self, vocab_size, hidden_size=2048, num_heads=32,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 kv_lora_rank=512, intermediate_size=6144,
                 moe_intermediate_size=768, n_shared_experts=2,
                 num_experts=16, router_width=128, top_k=6,
                 routed_scaling_factor=2.448, norm_topk_prob=True,
                 experts_held_from=0, first_k_dense=1, num_layers=5,
                 rope_theta=1e6, rms_norm_eps=1e-6, router_bias_std=0.002,
                 fused_head_chunk=None, remat=False):
        super().__init__()
        self.vocab_size = int(vocab_size)
        self.cfg = Config(
            hidden_size=hidden_size, num_heads=num_heads,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            kv_lora_rank=kv_lora_rank, intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            n_shared_experts=n_shared_experts, num_experts=num_experts,
            router_width=router_width, top_k=top_k,
            routed_scaling_factor=routed_scaling_factor,
            norm_topk_prob=norm_topk_prob,
            experts_held_from=experts_held_from,
            first_k_dense=first_k_dense, rope_theta=rope_theta,
            rms_norm_eps=rms_norm_eps)
        self.remat = remat
        self.chunk = int(fused_head_chunk or vocab_size)
        self.embed = layer.Embedding(self.vocab_size, hidden_size)
        self.layers = [KananaLayer(self.cfg, i < self.cfg.first_k_dense,
                                   float(router_bias_std))
                       for i in range(int(num_layers))]
        self.norm = RMSNorm(rms_norm_eps)
        self._moe_stats = self._moe_totals = None
        self._moe_read = {}
        self._moe_gauges = False

    def compile(self, inputs, *args, **kwargs):
        """``Model.compile``, with the parameters drawn first, out of its
        dry run (inside its trace a draw is folded into the executable as
        a constant)."""
        dev = inputs[0].device
        probe = Tensor(shape=(1, 1, self.cfg.hidden_size), device=dev,
                       requires_grad=False)
        for lyr in (self.embed, *self.layers, self.norm):
            if not lyr._initialized:
                lyr.initialize(probe)
                lyr._initialized = True
        self._draw_head(dev)
        return super().compile(inputs, *args, **kwargs)

    def _draw_head(self, dev):
        if getattr(self, "head_W", None) is None:
            self.head_W = _param((self.cfg.hidden_size, self.vocab_size),
                                 dev)
            self.head_W.gaussian(0.0, INIT_STD)

    def _own_params(self):
        return {"head.W": self.head_W}

    def _own_states(self):
        if self._moe_stats is None:
            return self._own_params()
        return {**self._own_params(), "moe_stats": self._moe_stats,
                "moe_totals": self._moe_totals}

    def _final(self, ids):
        self.embed.ensure_initialized(ids)
        x = _Lookup()(ids, self.embed.W)
        for lyr in self.layers:
            x = autograd.checkpoint(lyr, x) if self.remat else lyr(x)
        self._draw_head(ids.device)
        return self.norm(x)

    def forward(self, ids):
        return autograd.matmul(self._final(ids), self.head_W)

    def objective(self, ids, targets):
        """The loss Tensor of one batch, on the tape in training."""
        return _Head(self.chunk)(targets, self._final(ids), self.head_W)

    def train_one_batch(self, ids, targets):
        import jax.numpy as jnp
        loss = self.objective(ids, targets)
        stats = jnp.stack([lyr.aux_loss.data for lyr in self.layers
                           if not lyr._dense])
        # the counts' sums over the steps taken, and the steps, a column
        # more
        step = jnp.concatenate([stats, jnp.ones((len(stats), 1),
                                                stats.dtype)], 1)
        if self._moe_stats is None:
            self._moe_stats = Tensor(data=stats, device=ids.device,
                                     requires_grad=False)
            self._moe_totals = Tensor(data=jnp.zeros_like(step),
                                      device=ids.device, requires_grad=False)
        self._moe_stats.data = stats
        self._moe_totals.data = self._moe_totals.data + step
        self.optimizer(loss)
        return loss, loss

    def __call__(self, *args, **kwargs):
        out = super().__call__(*args, **kwargs)
        if self._train and self._moe_stats is not None:
            # the newest step's counts, for the gauges: arrays on the
            # device, read only when a gauge is read
            self._moe_read["stats"] = self._moe_stats.data
            self._moe_read["totals"] = self._moe_totals.data
            if not self._moe_gauges:
                self._moe_gauges = True
                self._gauges(None)
        return out

    def _gauges(self, registry):
        """Each of :data:`GAUGES` as ``moe_<name>{layer}`` of ``registry``
        (default: the process's), read from the newest step whenever it is
        read."""
        from ..observability.metrics import default_registry
        reg = registry or default_registry()
        read, layers = self._moe_read, self._expert_layers()
        for name, what in GAUGES:
            g = reg.gauge(f"moe_{name}", what, labels=("layer",))
            for row, lyr in enumerate(layers):
                g.set_function(functools.partial(
                    _stat, read, name, row, self.cfg.num_experts), layer=lyr)

    def _expert_layers(self):
        return [i for i, lyr in enumerate(self.layers) if not lyr._dense]

    def moe_stats(self, registry=None):
        """The expert layers' counts, read from the device now: one entry a
        layer (counted from 0 over the whole model) of each of
        :data:`GAUGES`. Also sets them up as gauges ``moe_<name>{layer}``
        of ``registry`` (the process's own has them from the first step).
        None before the first step."""
        if self._moe_stats is None:
            return None
        self._moe_read.update(stats=self._moe_stats.data,
                              totals=self._moe_totals.data)
        self._gauges(registry)
        out = {"layer": self._expert_layers()}
        for name, _ in GAUGES:
            out[name] = [_stat(self._moe_read, name, row,
                               self.cfg.num_experts)
                         for row in range(len(out["layer"]))]
        return out


def _stat(read, name, row, held):
    """One of :data:`GAUGES` for the expert layer in row ``row`` of the
    newest step's counts ``read``; NaN while they cannot be read (before
    a step, or while a step that took them as its input is under way)."""
    try:
        stats = np.asarray(read["stats"], np.float64)[row]
        totals = np.asarray(read["totals"], np.float64)[row]
    except (KeyError, RuntimeError):
        return float("nan")
    pairs, touched, load_max, rows = stats
    return {"pairs_here": pairs,
            "pairs_here_per_step": totals[0] / max(totals[-1], 1.0),
            "experts_touched": touched,
            "load_max": load_max,
            "load_mean": pairs / held,
            "padding_share": 1.0 - pairs / rows if rows > 0 else 0.0}[name]


def create_model(vocab_size=256, **kwargs):
    return KananaLM(vocab_size, **kwargs)


__all__ = ["KananaLM", "KananaLayer", "Config", "layer_apply", "mla_flash",
           "create_model", "STAT_NAMES"]
