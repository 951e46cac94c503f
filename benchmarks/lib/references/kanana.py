"""A DeepSeek-V3-style sparse-expert LM with latent attention and a
full-rank query (Kakao Kanana-2-30B-A3B, huggingface.co/kakaocorp/
kanana-2-30b-a3b-instruct-2601 `config.json`, model_type deepseek_v3)
trained on one chip's share of its deployment, in plain float32 at the
highest matmul precision: no kernel, no fused head, no grouped product.

    x = E[id]
    for each layer l:
        h = N1 x
        q = h W_q  (H heads of nope + rope);   [c~; k~_r] = h W_kva
        c = N_kv c~;  k_r = rope(k~_r) (one head);  q_r = rope(q_r)
        [k_n; v] = c W_kvb (H heads of nope + v)
        a = softmax_causal([q_n; q_r] . [k_n; k_r] / sqrt(nope + rope)) v
        x = x + a W_o
        u = N2 x
        l < first_k_dense_replace:  x = x + W_d(silu(W_g u) * W_u u)
        else:  s = sigmoid(u W_r) over all n_routed columns
               picks = top num_experts_per_tok of s + b   (b: the choice)
               w = s[picks] / sum(s[picks]) * routed_scaling_factor
               x = x + sum_{picks held here} w_j E_j(u) + Shared(u)
               (w formed as s_j / sum(s[picks held here]) times the
               constant sum(s[picks held here]) / sum(s[picks]) times
               the factor: the same value, the gradient of a share
               trained alone, see `route`)
    loss = mean_tokens CE(N_f(x) W_head, target)

Every norm is an RMSNorm (rms_norm_eps) with a learned scale; rotary
positions rotate the interleaved pairs (2i, 2i+1) of the rope columns by
s * theta^(-2i/rope) (rope_interleave, no scaling); experts and the shared
expert are SiLU-gated MLPs without biases. The share is the program's:
experts `experts_held_from` .. + `n_routed_experts` of a router
`router_width` wide, the vocabulary's first `vocab_size` rows; picks of
experts held elsewhere add nothing. The routing rule is written out here,
not taken from the program.

The chain (chain.py) has a stage a layer and every parameter lies in
exactly one: the embedding; the dense layer; the expert layers (one
program for all, as they have one shape); the final norm, the head and
the loss. Attention goes one (sequence, head) at a time, an expert's MLP
over all tokens one expert at a time, and the head in blocks of ROWS
tokens, each under `jax.checkpoint`, so that the reference fits beside its
float32 Adam state.

`fault` plants one of four faults for the tests (the reference put in the
program's place has to come out not correct): "selection_bias_skipped"
picks by the scores alone, "shared_expert_skipped" leaves the shared expert
out, "weights_unnormalised" weighs the picks by their scores times the
factor without dividing by their sum, "absent_outputs_learnt" lets the
router learn from the missing outputs of the picks held elsewhere (the
weights' gradient through the sum of all the picks' scores).
"""

import jax
import jax.numpy as jnp
from jax import lax

from . import lowprec

HIGHEST = lax.Precision.HIGHEST
FAULTS = (None, "selection_bias_skipped", "shared_expert_skipped",
          "weights_unnormalised", "absent_outputs_learnt")
ROWS = 1024        # tokens a block of the head: 66 MB of float32 logits
NORMS = ("attn_norm", "kv_norm", "ffn_norm")


def _dims(cfg):
    return {k: int(cfg[k]) for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "intermediate_size", "moe_intermediate_size", "n_shared_experts",
        "n_routed_experts", "router_width", "vocab_size",
        "num_hidden_layers", "first_k_dense_replace")}


def layer_shapes(cfg, dense):
    """One layer's leaves: {name: shape}, matrices (in, out), banks
    (experts, in, out)."""
    d = _dims(cfg)
    D, H = d["hidden_size"], d["num_attention_heads"]
    nope, rope, v = d["qk_nope_head_dim"], d["qk_rope_head_dim"], \
        d["v_head_dim"]
    rank = d["kv_lora_rank"]
    shapes = {"attn_norm": (D,), "wq": (D, H * (nope + rope)),
              "wkv_a": (D, rank + rope), "kv_norm": (rank,),
              "wkv_b": (rank, H * (nope + v)), "wo": (H * v, D),
              "ffn_norm": (D,)}
    if dense:
        F = d["intermediate_size"]
        shapes.update({"ffn_gate": (D, F), "ffn_up": (D, F),
                       "ffn_down": (F, D)})
        return shapes
    G, Fe = d["n_routed_experts"], d["moe_intermediate_size"]
    Fs = Fe * d["n_shared_experts"]
    shapes.update({"router": (D, d["router_width"]),
                   "router_bias": (d["router_width"],),
                   "w_gate": (G, D, Fe), "w_up": (G, D, Fe),
                   "w_down": (G, Fe, D), "s_gate": (1, D, Fs),
                   "s_up": (1, D, Fs), "s_down": (1, Fs, D)})
    return shapes


def param_specs(cfg):
    """[(name, shape, mean, std)], named as the program names its state
    (without the model's own prefix): matrices, banks, router, embedding
    and head N(0, matrix_std), norm scales N(1, norm_std), the selection
    bias N(0, router_bias_std)."""
    d, init = _dims(cfg), cfg["init"]
    D, V = d["hidden_size"], d["vocab_size"]
    s = float(init["matrix_std"])
    specs = [("embed.W", (V, D), 0.0, s)]
    for i in range(d["num_hidden_layers"]):
        dense = i < d["first_k_dense_replace"]
        for name, shape in layer_shapes(cfg, dense).items():
            if name in NORMS:
                mean, std = 1.0, float(init["norm_std"])
            elif name == "router_bias":
                mean, std = 0.0, float(init["router_bias_std"])
            else:
                mean, std = 0.0, s
            specs.append((f"layers.{i}.{name}", shape, mean, std))
    return specs + [("norm.scale", (D,), 1.0, float(init["norm_std"])),
                    ("head.W", (D, V), 0.0, s)]


def _mm(a, b, cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _rope(t, theta):
    """t (B, S, H, R): position s rotates the pair (2i, 2i + 1) by
    s * theta^(-2i/R)."""
    S, R = t.shape[1], t.shape[-1]
    freq = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = t[..., 0::2], t[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(t.shape)


def _attention(q, k, v, cast):
    """Causal softmax attention, one (sequence, head) at a time. q, k (B,
    S, H, Dk), v (B, S, H, Dv); returns (B, S, H * Dv)."""
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    mask = jnp.tril(jnp.ones((S, S), bool))

    def one(qkv):
        q1, k1, v1 = qkv
        scores = _mm(q1, k1.T, cast) / jnp.sqrt(jnp.float32(Dk))
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return _mm(probs, v1, cast)

    def heads(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, S, t.shape[-1])

    o = lax.map(jax.checkpoint(one), (heads(q), heads(k), heads(v)))
    return o.reshape(B, H, S, Dv).transpose(0, 2, 1, 3).reshape(
        B, S, H * Dv)


def _mlp(u, gate, up, down, cast):
    return _mm(jax.nn.silu(_mm(u, gate, cast)) * _mm(u, up, cast), down,
               cast)


def route(u, router, bias, top_k, scale, held_from, held, fault=None):
    """The routing rule, written out: (T, held) the weight each token
    gives each expert held here (0 where it did not pick it).

    Its value is a pick's score over the sum of the token's picks' scores,
    times the factor. The share is trained alone, so the outputs of the
    picks held elsewhere are missing; the weight is formed as a held
    pick's score over the held picks' sum, times the held picks' share of
    the whole sum taken as a constant: the same value, and a gradient
    that reaches no score of a pick held elsewhere and does not move the
    held picks' share."""
    s = jax.nn.sigmoid(jnp.matmul(u, router, precision=HIGHEST))
    choice = s if fault == "selection_bias_skipped" else s + bias
    _, idx = lax.top_k(choice, top_k)
    w = jnp.take_along_axis(s, idx, -1)
    mine = idx[:, :, None] == held_from + jnp.arange(held)   # (T, k, held)
    total = 1.0 if fault == "weights_unnormalised" \
        else jnp.sum(w, -1, keepdims=True)
    if fault == "absent_outputs_learnt":
        w = w / total
    else:
        here = jnp.sum(jnp.where(jnp.any(mine, -1), w, 0.0), -1,
                       keepdims=True)
        w = w / jnp.where(here > 0, here, 1.0) \
            * lax.stop_gradient(here / total)
    return jnp.sum(jnp.where(mine, w[:, :, None] * scale, 0.0), 1)


def _experts(p, u, cfg_s, cast, fault):
    """The routed experts held here and the shared expert on u (T, D)."""
    top_k, scale, held_from = cfg_s
    G = p["w_gate"].shape[0]
    weight = route(u, p["router"], p["router_bias"], top_k, scale,
                   held_from, G, fault)

    def one(acc, e):
        y = _mlp(u, p["w_gate"][e], p["w_up"][e], p["w_down"][e], cast)
        return acc + lax.dynamic_slice_in_dim(weight, e, 1, 1) * y, None

    y, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(u), jnp.arange(G))
    if fault != "shared_expert_skipped":
        y = y + _mlp(u, p["s_gate"][0], p["s_up"][0], p["s_down"][0], cast)
    return y


def layer(p, x, dense, n_head, nope, rope, eps, theta, top_k, scale,
          held_from, cast, fault):
    """One pre-norm layer; `p` by layer_shapes' names."""
    cast_fn = lowprec.CASTS[cast]
    B, S, D = x.shape
    rank = p["kv_norm"].shape[0]
    h = rms_norm(x, p["attn_norm"], eps)
    q = _mm(h, p["wq"], cast_fn).reshape(B, S, n_head, nope + rope)
    kv = _mm(h, p["wkv_a"], cast_fn)
    c = rms_norm(kv[..., :rank], p["kv_norm"], eps)
    k_r = _rope(kv[..., None, rank:], theta)
    kvb = _mm(c, p["wkv_b"], cast_fn).reshape(B, S, n_head, -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_r, (B, S, n_head, rope))], -1)
    a = _attention(q, k, kvb[..., nope:], cast_fn)
    x = x + _mm(a, p["wo"], cast_fn)
    u = rms_norm(x, p["ffn_norm"], eps)
    if dense:
        return x + _mlp(u, p["ffn_gate"], p["ffn_up"], p["ffn_down"],
                        cast_fn)
    y = _experts(p, u.reshape(B * S, D), (top_k, scale, held_from),
                 cast_fn, fault)
    return x + y.reshape(B, S, D)


def _embed(p, ids):
    return p["embed.W"][ids]


def _head(p, x, targets, eps, rows, cast):
    B, S, D = x.shape
    N = B * S
    rows = min(rows, N)
    cast_fn = lowprec.CASTS[cast]
    h = rms_norm(x, p["norm.scale"], eps).reshape(N // rows, rows, D)

    def ce_block(hb):
        hh, t = hb
        logp = jax.nn.log_softmax(_mm(hh, p["head.W"], cast_fn), -1)
        return -jnp.take_along_axis(logp, t[:, None], -1)[:, 0]

    ce = lax.map(jax.checkpoint(ce_block),
                 (h, targets.reshape(N // rows, rows)))
    return jnp.mean(ce)


def stages(cfg, cast="float32", fault=None):
    """The chain: [(fn, {local name: model name}, statics)]. It takes a
    batch (ids (B, S) int32, targets (B, S) int32)."""
    assert fault in FAULTS, fault
    d = _dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    out = [(_embed, {"embed.W": "embed.W"}, ())]
    for i in range(d["num_hidden_layers"]):
        dense = i < d["first_k_dense_replace"]
        names = {n: f"layers.{i}.{n}" for n in layer_shapes(cfg, dense)}
        out.append((layer, names, (
            ("dense", dense), ("n_head", d["num_attention_heads"]),
            ("nope", d["qk_nope_head_dim"]), ("rope", d["qk_rope_head_dim"]),
            ("eps", eps), ("theta", float(cfg["rope_theta"])),
            ("top_k", int(cfg["num_experts_per_tok"])),
            ("scale", float(cfg["routed_scaling_factor"])),
            ("held_from", int(cfg["experts_held_from"])), ("cast", cast),
            ("fault", fault))))
    out.append((_head, {"norm.scale": "norm.scale", "head.W": "head.W"},
                (("eps", eps), ("rows", ROWS), ("cast", cast))))
    return out
