"""GPT-2 (Radford et al. 2019; huggingface.co/openai-community/gpt2-medium
`config.json`) in plain float32: pre-norm blocks, learned positions, full
causal multi-head attention, `gelu_new` MLP. The model is a chain of stages
(chain.py): the embeddings, one stage a block, the head with the loss.

Two departures of the program, reproduced here and listed in the
configuration's file: the output head is a matrix of its own (not the token
embedding transposed) and has a bias.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import lowprec

HIGHEST = lax.Precision.HIGHEST
_BLOCK_LEAVES = (
    ["ln1.scale", "ln1.bias", "ln2.scale", "ln2.bias"]
    + [f"attn.{p}.{s}" for p in ("q_proj", "k_proj", "v_proj", "proj")
       for s in ("W", "b")]
    + [f"mlp.{p}.{s}" for p in ("up", "down") for s in ("W", "b")])


def param_specs(cfg):
    """[(name, shape, mean, std)], named as the program names its state
    (without the model's own prefix)."""
    d, V = int(cfg["n_embd"]), int(cfg["vocab_size"])
    ff = int(cfg.get("n_inner") or 4 * d)
    s = float(cfg.get("initializer_range", 0.02))
    shapes = {"W": {"q_proj": (d, d), "k_proj": (d, d), "v_proj": (d, d),
                    "proj": (d, d), "up": (d, ff), "down": (ff, d)}}
    specs = [("tok_emb.W", (V, d), 0.0, s),
             ("pos_emb.W", (int(cfg["n_positions"]), d), 0.0, s)]
    for i in range(int(cfg["n_layer"])):
        for leaf in _BLOCK_LEAVES:
            layer, kind = leaf.split(".")[-2:]
            if layer in ("ln1", "ln2"):
                shape, mean = (d,), (1.0 if kind == "scale" else 0.0)
            else:
                w = shapes["W"][layer]
                shape, mean = (w if kind == "W" else (w[1],)), 0.0
            specs.append((f"blocks.{i}.{leaf}", shape, mean, s))
    specs += [("ln_f.scale", (d,), 1.0, s), ("ln_f.bias", (d,), 0.0, s),
              ("head.W", (d, V), 0.0, s), ("head.b", (V,), 0.0, s)]
    return specs


def _mm(a, b, cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _ln(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _embed(p, ids, cast):
    return p["tok_emb.W"][ids] + p["pos_emb.W"][:ids.shape[1]][None]


def _block(p, x, n_head, eps, cast):
    cast = lowprec.CASTS[cast]
    B, S, d = x.shape
    hd = d // n_head
    h = _ln(x, p["ln1.scale"], p["ln1.bias"], eps)

    def heads(name):
        t = _mm(h, p[f"attn.{name}.W"], cast) + p[f"attn.{name}.b"]
        return t.reshape(B, S, n_head, hd).transpose(0, 2, 1, 3)

    q, k, v = heads("q_proj"), heads("k_proj"), heads("v_proj")
    scores = _mm(q, k.transpose(0, 1, 3, 2), cast) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    o = _mm(probs, v, cast).transpose(0, 2, 1, 3).reshape(B, S, d)
    x = x + _mm(o, p["attn.proj.W"], cast) + p["attn.proj.b"]
    h = _ln(x, p["ln2.scale"], p["ln2.bias"], eps)
    h = _gelu_new(_mm(h, p["mlp.up.W"], cast) + p["mlp.up.b"])
    return x + _mm(h, p["mlp.down.W"], cast) + p["mlp.down.b"]


def _logits(p, x, eps, cast):
    cast = lowprec.CASTS[cast]
    x = _ln(x, p["ln_f.scale"], p["ln_f.bias"], eps)
    return _mm(x, p["head.W"], cast) + p["head.b"]


def _head(p, x, targets, eps, cast):
    """Mean next-token cross-entropy over every position."""
    logp = jax.nn.log_softmax(_logits(p, x, eps, cast), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


_HEAD_NAMES = {k: k for k in ("ln_f.scale", "ln_f.bias", "head.W", "head.b")}


def stages(cfg, cast="float32"):
    """The chain: [(fn, {local name: model name}, statics)]. It takes a
    batch (ids (B,S) int32, targets (B,S) int32)."""
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    cast_s = (("cast", cast),)
    out = [(_embed, {"tok_emb.W": "tok_emb.W", "pos_emb.W": "pos_emb.W"},
            cast_s)]
    for i in range(int(cfg["n_layer"])):
        out.append((_block, {k: f"blocks.{i}.{k}" for k in _BLOCK_LEAVES},
                    (("n_head", int(cfg["n_head"])), ("eps", eps)) + cast_s))
    out.append((_head, _HEAD_NAMES, (("eps", eps),) + cast_s))
    return out


def _gaps(p, x, ids, x_low, eps, cast):
    full = _logits(p, x, eps, "float32")[:, :-1]
    best = jnp.max(full, axis=-1)
    nxt = jnp.take_along_axis(full, ids[:, 1:, None], axis=-1)[..., 0]
    if x_low is None:
        return best - nxt, None
    pick = jnp.argmax(_logits(p, x_low, eps, cast)[:, :-1], axis=-1)
    low = jnp.take_along_axis(full, pick[..., None], axis=-1)[..., 0]
    return best - nxt, best - low


def served_gaps(params, ids, cfg, cast=None):
    """For sequences `ids` (N, L): at every position t, how far the logit
    of the token that follows (ids[:, t+1]) lies below the best logit, in
    this float32 forward pass; and, when `cast` names a lower precision,
    the same for the token that pass puts first, read in the float32
    logits. Returns (gap_of_next (N, L-1), gap_of_lowprec_best or None)."""
    from . import chain
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    x = chain.forward(stages(cfg), params, ids)
    x_low = None if cast is None else \
        chain.forward(stages(cfg, cast), params, ids)
    head = {k: params[v] for k, v in _HEAD_NAMES.items()}
    fn = jax.jit(_gaps, static_argnames=("eps", "cast"))
    return fn(head, x, ids, x_low, eps=eps, cast=cast or "float32")
