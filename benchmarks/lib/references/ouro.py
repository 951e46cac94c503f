"""A looped language model (ByteDance Ouro, huggingface.co/ByteDance/
Ouro-2.6B `config.json`; "Scaling Latent Reasoning via Looped Language
Models", 2025) trained on its exits, in plain float32 at the highest matmul
precision: no kernel, no fused head, no cache. With T = total_ut_steps
passes over the same L layers:

    h_0 = E[id]
    for t in 1..T:
        x = h_{t-1}
        for l in 0..L-1:
            a = Attn_l(N1_l x)       full causal MHA, scale 1/sqrt(head_dim),
            x = x + N2_l a           rotary (rotate-half, rope_theta) on the
            m = Wd_l (silu(Wg_l N3_l x) * Wu_l N3_l x)     whole head; biases
            x = x + N4_l m           on q, k, v only
        h_t = N_loop x               one shared RMSNorm; feeds exit t and
                                     pass t + 1
    lam_t = sigmoid(h_t w_g + b_g);  ce_t = CE(h_t W_head, target)
    p_t = lam_t prod_{j<t}(1 - lam_j) (t < T),  p_T = prod_{j<T}(1 - lam_j)
    loss = mean_tokens(sum_t p_t ce_t - beta H(p)),  H(p) = -sum_t p_t log p_t

N1..N4 are input_layernorm, input_layernorm_2, post_attention_layernorm,
post_attention_layernorm_2; every norm is an RMSNorm (rms_norm_eps). Each
pass attends to its own keys and values only. What the configuration's file
lists under `assumed` (the sandwich order, the gate, beta, the biases, the
weights) is assumed here too.

The chain (chain.py) has three stages and every parameter lies in exactly
one: the embedding; the loop (all layers and the loop norm, run T times,
each layer application under `jax.checkpoint`; it gives the T normed states
stacked (T, B, S, D)); the exits and the objective. Attention goes one
(sequence, head) at a time and the exits in blocks of ROWS tokens, each
under `jax.checkpoint`, so that the reference fits beside its float32 Adam
state at S = 4096.

`fault` plants one of three faults for the tests (the reference put in the
program's place has to come out not correct): "last_exit_only" takes the
loss from the last exit alone, "pass_gradient_stopped" stops the gradient at
each boundary between passes, "loop_norm_skipped" hands the next pass the
state before the loop norm.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import lowprec

HIGHEST = lax.Precision.HIGHEST
FAULTS = (None, "last_exit_only", "pass_gradient_stopped",
          "loop_norm_skipped")
ROWS = 1024        # tokens a block of the exits: 201 MB of float32 logits
NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")


def _dims(cfg):
    return (int(cfg["hidden_size"]), int(cfg["num_attention_heads"]),
            int(cfg["head_dim"]), int(cfg["intermediate_size"]),
            int(cfg["vocab_size"]))


def layer_shapes(cfg):
    """One layer's leaves: {name: shape}, matrices (in, out)."""
    d, H, hd, ff, _ = _dims(cfg)
    shapes = {n: (d,) for n in NORMS}
    for proj in ("q_proj", "k_proj", "v_proj"):
        shapes[f"{proj}.W"], shapes[f"{proj}.b"] = (d, H * hd), (H * hd,)
    shapes.update({"o_proj.W": (H * hd, d), "gate_proj.W": (d, ff),
                   "up_proj.W": (d, ff), "down_proj.W": (ff, d)})
    return shapes


def param_specs(cfg):
    """[(name, shape, mean, std)], named as the program names its state
    (without the model's own prefix): matrices, embedding, head, gate and
    biases N(0, s), norm scales N(1, s), s = initializer_range."""
    d, _, _, _, V = _dims(cfg)
    s = float(cfg.get("initializer_range", 0.02))
    specs = [("embed.W", (V, d), 0.0, s)]
    for i in range(int(cfg["num_hidden_layers"])):
        for name, shape in layer_shapes(cfg).items():
            specs.append((f"layers.{i}.{name}", shape,
                          1.0 if name in NORMS else 0.0, s))
    return specs + [("norm.scale", (d,), 1.0, s),
                    ("exits.head.W", (d, V), 0.0, s),
                    ("exits.gate.W", (d, 1), 0.0, s),
                    ("exits.gate.b", (1,), 0.0, s)]


def _mm(a, b, cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _rope(t, theta):
    """t (B, S, H, D): position s rotates the pair (i, i + D/2) by
    s * theta^(-2i/D)."""
    S, D = t.shape[1], t.shape[-1]
    freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = t[..., :D // 2], t[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(q, k, v, cast):
    """Causal softmax attention of (B, S, H, D) heads, one (sequence, head)
    at a time; (B, S, H * D)."""
    B, S, H, D = q.shape
    mask = jnp.tril(jnp.ones((S, S), bool))

    def one(qkv):
        q1, k1, v1 = qkv
        scores = _mm(q1, k1.T, cast) / math.sqrt(D)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return _mm(probs, v1, cast)

    def heads(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    o = lax.map(jax.checkpoint(one), (heads(q), heads(k), heads(v)))
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3).reshape(B, S, H * D)


def layer(p, x, n_head, eps, theta, cast):
    """One application of one sandwich layer; `p` by layer_shapes' names."""
    cast = lowprec.CASTS[cast]
    B, S, _ = x.shape
    h = rms_norm(x, p["input_layernorm"], eps)

    def proj(name):
        t = _mm(h, p[f"{name}.W"], cast) + p[f"{name}.b"]
        return t.reshape(B, S, n_head, -1)

    a = _attention(_rope(proj("q_proj"), theta), _rope(proj("k_proj"), theta),
                   proj("v_proj"), cast)
    x = x + rms_norm(_mm(a, p["o_proj.W"], cast), p["input_layernorm_2"], eps)
    h = rms_norm(x, p["post_attention_layernorm"], eps)
    m = jax.nn.silu(_mm(h, p["gate_proj.W"], cast)) \
        * _mm(h, p["up_proj.W"], cast)
    return x + rms_norm(_mm(m, p["down_proj.W"], cast),
                        p["post_attention_layernorm_2"], eps)


def _embed(p, ids):
    return p["embed.W"][ids]


def _loop(p, x, n_layer, passes, n_head, eps, theta, cast, fault):
    layers = [{k[len(f"layers.{i}."):]: v for k, v in p.items()
               if k.startswith(f"layers.{i}.")} for i in range(n_layer)]
    apply = jax.checkpoint(functools.partial(
        layer, n_head=n_head, eps=eps, theta=theta, cast=cast))

    def one_pass(x, _):
        for lp in layers:
            x = apply(lp, x)
        h = rms_norm(x, p["norm.scale"], eps)
        nxt = x if fault == "loop_norm_skipped" else h
        if fault == "pass_gradient_stopped":
            nxt = lax.stop_gradient(nxt)
        return nxt, h

    return lax.scan(one_pass, x, None, length=passes)[1]


def exit_probs(lam):
    """(T, N) exit probabilities from the (T - 1, N) gates, as a product."""
    out, stay = [], jnp.ones(lam.shape[1:], lam.dtype)
    for t in range(lam.shape[0]):
        out.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(out + [stay])


def entropy(prob):
    """H(p) = -sum_t p_t log p_t over the (T, N) exit probabilities, with
    0 log 0 = 0. A gate saturates in float32 (lam = 1 exactly past a logit
    of about 17, which a few Adam steps at lr 3e-4 reach), and every later
    exit's p is then 0; the inner `where` keeps log(0) out of the gradient."""
    live = prob > 0
    plogp = prob * jnp.log(jnp.where(live, prob, 1.0))
    return -jnp.sum(jnp.where(live, plogp, 0.0), 0)


def _exits(p, hs, targets, beta, rows, cast, fault):
    """The objective of the T normed states hs (T, B, S, D)."""
    T, B, S, D = hs.shape
    N = B * S
    rows = min(rows, N)
    cast_fn = lowprec.CASTS[cast]
    flat = hs.reshape(T, N // rows, rows, D)
    ids = targets.reshape(N // rows, rows)

    def ce_block(hb):
        h, t = hb
        logp = jax.nn.log_softmax(_mm(h, p["exits.head.W"], cast_fn), -1)
        return -jnp.take_along_axis(logp, t[:, None], -1)[:, 0]

    ce = jnp.stack([lax.map(jax.checkpoint(ce_block), (flat[t], ids))
                    .reshape(N) for t in range(T)])
    if fault == "last_exit_only":
        return jnp.mean(ce[-1])
    z = _mm(hs[:-1].reshape(T - 1, N, D), p["exits.gate.W"], cast_fn)[..., 0]
    prob = exit_probs(jax.nn.sigmoid(z + p["exits.gate.b"][0]))
    return jnp.mean(jnp.sum(prob * ce, 0) - beta * entropy(prob))


def _names(keys):
    return {k: k for k in keys}


def stages(cfg, cast="float32", fault=None):
    """The chain: [(fn, {local name: model name}, statics)]. It takes a
    batch (ids (B, S) int32, targets (B, S) int32)."""
    assert fault in FAULTS, fault
    eps = float(cfg["rms_norm_eps"])
    specs = [n for n, *_ in param_specs(cfg)]
    loop = [n for n in specs if n.startswith("layers.")] + ["norm.scale"]
    return [
        (_embed, _names(["embed.W"]), ()),
        (_loop, _names(loop),
         (("n_layer", int(cfg["num_hidden_layers"])),
          ("passes", int(cfg["total_ut_steps"])),
          ("n_head", int(cfg["num_attention_heads"])), ("eps", eps),
          ("theta", float(cfg["rope_theta"])), ("cast", cast),
          ("fault", fault))),
        (_exits, _names([n for n in specs if n.startswith("exits.")]),
         (("beta", float(cfg["exit_entropy_beta"])), ("rows", ROWS),
          ("cast", cast), ("fault", fault))),
    ]
