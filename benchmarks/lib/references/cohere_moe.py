"""One chip's share of a `cohere2_moe` model (CohereLabs Command A+,
huggingface.co/CohereLabs/command-a-plus-05-2026 `config.json`) in plain
float32 at the highest matmul precision: no kernel, no cache, no batching
trick. For a layer with input x (T x D):

    h    = LN(x)                       (x - mean) / sqrt(var + eps) * gamma
    q,k,v = h Wq, h Wk, h Wv           Hq query heads, Hkv KV heads of hd;
                                       query head i reads KV head i // (Hq/Hkv)
    sliding layers: rotary positions on q and k, interleaved pairs
                    (2i, 2i+1), theta; mask j <= i and i - j < window
    full layers:    no positional encoding; mask j <= i
    attn = concat(softmax(q k^T / sqrt(hd) + mask) v) Wo
    s    = sigmoid(h Wr)               over ALL experts (router_width)
    I    = top-k(s);  w_i = s_i / sum_{j in I} s_j
    E(h) = (silu(h W_gate) * (h W_up)) W_down
    ffn  = sum_{i in I, i held here} w_i E_i(h) + mean_j S_j(h)
    y    = x + attn + ffn
    logits = LN(x_last) E^T * logit_scale      (tied embedding)

The share: the query heads, KV heads, routed experts and vocabulary rows
the configuration's file counts live here; picks that go to experts held
elsewhere add nothing, in the program and here alike; the weights w_i are
normalised over all k picks. The routed sum is computed plainly: every held
expert on every token, weighted by w_i or nought. A configuration that holds
every head, expert and row IS the uncut model (the tests' use).

The served comparison goes layer by layer: a layer's weights are drawn from
the seed (lib/weights_staged.py), used on every sampled sequence and freed,
so never more than one layer's float32 weights are alive; queries go in
blocks.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import weights_staged
from . import lowprec

HIGHEST = lax.Precision.HIGHEST
SLIDING = "sliding_attention"
QUERY_BLOCK = 1024


def bf16_operands(x):
    """The program's own operand rounding (for reading how often it moves a
    token's pick set; never a control)."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


CASTS = dict(lowprec.CASTS, bf16=bf16_operands)


def _init(cfg):
    init = cfg.get("init", {})
    std = float(init.get("matrix_std", 0.02))
    return {"matrix": std, "out": float(init.get("residual_out_std", std)),
            "emb": float(init.get("embedding_std", std)),
            "router": float(init.get("router_std", std)),
            "norm": float(init.get("norm_std", 0.02))}


def layer_specs(cfg, i):
    """[(name, shape, mean, std)] of layer i, named as the program names its
    state (without the model's own prefix)."""
    s = _init(cfg)
    D, hd, F = int(cfg["hidden_size"]), int(cfg["head_dim"]), \
        int(cfg["intermediate_size"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    G, S = int(cfg["num_experts"]), int(cfg["num_shared_experts"])
    p = f"layers.{i}."
    return [(p + "ln", (D,), 1.0, s["norm"]),
            (p + "wq", (D, hq * hd), 0.0, s["matrix"]),
            (p + "wk", (D, hkv * hd), 0.0, s["matrix"]),
            (p + "wv", (D, hkv * hd), 0.0, s["matrix"]),
            (p + "wo", (hq * hd, D), 0.0, s["out"]),
            (p + "ffn.router", (D, int(cfg["router_width"])), 0.0,
             s["router"]),
            (p + "ffn.w_gate", (G, D, F), 0.0, s["matrix"]),
            (p + "ffn.w_up", (G, D, F), 0.0, s["matrix"]),
            (p + "ffn.w_down", (G, F, D), 0.0, s["out"]),
            (p + "ffn.s_gate", (S, D, F), 0.0, s["matrix"]),
            (p + "ffn.s_up", (S, D, F), 0.0, s["matrix"]),
            (p + "ffn.s_down", (S, F, D), 0.0, s["out"])]


def end_specs(cfg):
    s = _init(cfg)
    D = int(cfg["hidden_size"])
    return [("emb", (int(cfg["vocab_size"]), D), 0.0, s["emb"]),
            ("ln_f", (D,), 1.0, s["norm"])]


def param_specs(cfg):
    specs = end_specs(cfg)
    for i in range(int(cfg["num_hidden_layers"])):
        specs += layer_specs(cfg, i)
    return specs


def store_dtype(cfg):
    """The dtype the configuration keeps its weights in."""
    return {"bfloat16": jnp.bfloat16}.get(cfg["precision"], jnp.float32)


def _mm(a, b, cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _ln(x, scale, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale


def _rope(t, theta):
    """t: (L, H, hd); positions 0..L-1; pairs (2i, 2i+1)."""
    L, _, hd = t.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None, None] * inv
    a, b = t[..., 0::2], t[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return out.reshape(t.shape)


def _attention(p, h, kind, cfg, cast):
    """h: (L, D) of ONE sequence."""
    L = h.shape[0]
    hd = int(cfg["head_dim"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    q = _mm(h, p["wq"], cast).reshape(L, hq, hd)
    k = _mm(h, p["wk"], cast).reshape(L, hkv, hd)
    v = _mm(h, p["wv"], cast).reshape(L, hkv, hd)
    if kind == SLIDING:
        theta = float(cfg["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, hq // hkv, axis=1)        # query head i <- i // group
    v = jnp.repeat(v, hq // hkv, axis=1)
    j = jnp.arange(L)
    outs = []
    for a in range(0, L, QUERY_BLOCK):
        i = jnp.arange(a, min(a + QUERY_BLOCK, L))
        keep = j[None, :] <= i[:, None]
        if kind == SLIDING:
            keep &= i[:, None] - j[None, :] < int(cfg["sliding_window"])
        s = _mm(q[i].transpose(1, 0, 2), k.transpose(1, 2, 0), cast) \
            / math.sqrt(hd)                                  # hq, block, L
        pr = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        outs.append(_mm(pr, v.transpose(1, 0, 2), cast).transpose(1, 0, 2))
    o = jnp.concatenate(outs, axis=0).reshape(L, hq * hd)
    return _mm(o, p["wo"], cast)


def _expert(h, wg, wu, wd, cast):
    return _mm(jax.nn.silu(_mm(h, wg, cast)) * _mm(h, wu, cast), wd, cast)


def _ffn(p, h, cfg, cast):
    """(ffn (L, D), picks (L, k) sorted expert ids)."""
    k = int(cfg["num_experts_per_tok"])
    held_from = int(cfg.get("experts_held_from", 0))
    s = jax.nn.sigmoid(_mm(h, p["ffn.router"], cast))
    top, idx = lax.top_k(s, k)
    w = top / jnp.sum(top, -1, keepdims=True)
    out = jnp.zeros_like(h)
    for g in range(p["ffn.w_gate"].shape[0]):
        weight = jnp.sum(jnp.where(idx == held_from + g, w, 0.0), axis=-1)
        out = out + weight[:, None] * _expert(
            h, p["ffn.w_gate"][g], p["ffn.w_up"][g], p["ffn.w_down"][g], cast)
    S = p["ffn.s_gate"].shape[0]
    for j in range(S):
        out = out + _expert(h, p["ffn.s_gate"][j], p["ffn.s_up"][j],
                            p["ffn.s_down"][j], cast) / S
    return out, jnp.sort(idx, axis=-1)


def _layer(p, x, kind, cfg, cast):
    """One layer on ONE sequence x (L, D): (y, picks)."""
    cast = CASTS[cast]
    h = _ln(x, p["ln"], float(cfg["layer_norm_eps"]))
    ffn, picks = _ffn(p, h, cfg, cast)
    return x + _attention(p, h, kind, cfg, cast) + ffn, picks


_layer_jit = jax.jit(_layer, static_argnames=("kind", "cfg", "cast"))


class _Frozen(dict):
    """A configuration as a static argument of a jitted function."""

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


def _local(params, i):
    prefix = f"layers.{i}."
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def layer_forward(params, x, i, cfg, cast="float32"):
    """Layer i of `params` ({model name: float32 array}) on x (N, L, D):
    (y (N, L, D), picks (N, L, k))."""
    frozen = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    kind = cfg["layer_types"][i]
    p = params if "ln" in params else _local(params, i)
    with jax.default_matmul_precision("highest"):
        outs = [_layer_jit(p, x[n], kind=kind, cfg=frozen, cast=cast)
                for n in range(x.shape[0])]
    return jnp.stack([o[0] for o in outs]), jnp.stack([o[1] for o in outs])


def logits_of(params, x, cfg, cast="float32"):
    """LN then the tied embedding as head: (N, L, V) float32."""
    with jax.default_matmul_precision("highest"):
        h = _ln(x, params["ln_f"], float(cfg["layer_norm_eps"]))
        return _mm(h, params["emb"].T, CASTS[cast]) \
            * float(cfg.get("logit_scale", 1))


def forward(params, ids, cfg, cast="float32"):
    """The whole forward of sequences ids (N, L) with every weight given:
    logits (N, L, V). For the CPU tests; the chip's comparison goes layer
    by layer (`served_gaps`)."""
    frozen = _Frozen(cfg)
    x = params["emb"][ids]
    for i in range(int(cfg["num_hidden_layers"])):
        x, _ = layer_forward(params, x, i, frozen, cast)
    return logits_of(params, x, cfg, cast)


def _hidden(cfg, seed, ids, cast, keep_picks):
    """Final hidden states of `ids` (N, L) with weights drawn layer by layer
    from the seed: (x (N, L, D), emb, ln_f, [picks per layer])."""
    store = store_dtype(cfg)
    frozen = _Frozen(cfg)
    ends = weights_staged.make(end_specs(cfg), seed, store, jnp.float32)
    x = ends["emb"][ids]
    picks = []
    for i in range(int(cfg["num_hidden_layers"])):
        p = _local(weights_staged.make(layer_specs(cfg, i), seed, store,
                                       jnp.float32), i)
        x, pk = layer_forward(p, x, i, frozen, cast)
        if keep_picks:
            picks.append(np.asarray(pk))
        del p
    return x, ends, picks


def _gaps(ends, x, ids, x_low, cfg, cast):
    """One sequence at a time (the logits of one are L x V float32)."""
    served, low = [], []
    for n in range(ids.shape[0]):
        full = logits_of(ends, x[n, :-1], cfg)
        best = jnp.max(full, axis=-1)
        nxt = jnp.take_along_axis(full, ids[n, 1:, None], axis=-1)[..., 0]
        served.append(np.asarray(best - nxt))
        if x_low is not None:
            pick = jnp.argmax(logits_of(ends, x_low[n, :-1], cfg, cast),
                              axis=-1)
            low.append(np.asarray(best - jnp.take_along_axis(
                full, pick[..., None], axis=-1)[..., 0]))
    return np.stack(served), (np.stack(low) if low else None)


def served_gaps(cfg, seed, ids, cast=None, picks_out=None):
    """For sequences `ids` (N, L): at every position t, how far the logit of
    the token that follows lies below the best logit in this float32 pass;
    and, when `cast` names a lower precision, the same for the token that
    pass puts first, read in the float32 logits. Returns (gap_of_next
    (N, L-1), gap_of_lowprec_best or None). `picks_out`, a dict, receives
    under "float32" and `cast` each pass's picks per layer."""
    ids = jnp.asarray(ids)
    keep = picks_out is not None
    x, ends, picks = _hidden(cfg, seed, ids, "float32", keep)
    x_low = None
    if keep:
        picks_out["float32"] = picks
    if cast is not None:
        x_low, _, picks_low = _hidden(cfg, seed, ids, cast, keep)
        if keep:
            picks_out[cast] = picks_low
    return _gaps(ends, x, ids, x_low, cfg, cast or "float32")
