"""One chip's share of LongCat-Flash's language model (meituan-longcat
LongCat-Flash-Omni `config.json`; the layer equations of the LongCat-Flash
Technical Report, arXiv:2509.01322) in plain float32 at the highest matmul
precision: no kernel, no cache, no batching, no absorption. One layer holds
two sub-blocks i = 0, 1 and one expert layer; with x (T x D):

    for i in 0, 1:
        h    = RMS(x, n1_i)
        c_q  = RMS(h Wqa_i, q_norm_i)                     q_lora_rank wide
        q    = s_q (c_q Wqb_i)      H heads of [q_n (nope); q_r (rope)]
        [c'; k_r] = h Wkva_i        kv_lora_rank + rope; ONE k_r for all heads
        c    = s_kv RMS(c', kv_norm_i)
        q_r, k_r rotated by position, interleaved pairs (2j, 2j+1), theta
        [k_n; v] = c Wkvb_i         H heads of [k_n (nope); v (v_head_dim)]
        a    = softmax((q_n k_n^T + q_r k_r^T) / sqrt(nope + rope) + causal)
        x    = x + concat_h(a_h v_h) Wo_i
        u    = RMS(x, n2_i)
        if i == 0:                                        the shortcut
            p  = softmax(u Wr)      over E routed + Z identity experts
            I  = top-k(p + b);  w_e = f p_e               b: choice only
            s  = sum_{e in I, e held here} w_e Expert_e(u)
                 + (sum_{e in I, e >= E} w_e) u           identity experts
        x    = x + Wd_i (silu(Wg_i u) * Wu_i u)
    y = x + s
    logits = RMS(x_last, ln_f) Head^T                     untied head

s_q = sqrt(D / q_lora_rank), s_kv = sqrt(D / kv_lora_rank) where the
configuration's mla_scale_* keys are true; f = routed_scaling_factor; the
weights are NOT renormalised; Expert_e(u) = (silu(u W_gate) * (u W_up))
W_down. The share: the routed experts and vocabulary rows the
configuration's file counts live here; picks that go to routed experts held
elsewhere add nothing, in the program and here alike; the identity experts'
term is the same on every share. A configuration that holds every routed
expert and row IS the uncut model (the tests' use).

The served comparison goes layer by layer: a layer's weights are drawn from
the seed (lib/weights_staged.py), used on every sampled sequence and freed;
queries go in blocks.

`fault` plants one of three faults for the tests (the reference put in the
program's place has to come out not correct): "zero_experts_out" leaves the
identity experts' term out, "bias_in_weights" weighs the picks by p + b,
"k_r_unrotated" leaves the shared rotary key unrotated.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import weights_staged
# what every layer-by-layer reference needs and one already has: the casts
# (float32, fp8_e4m3, bf16), the matrix product under a cast, a
# configuration as a static argument, a layer's leaves by their local names
from .cohere_moe import (CASTS, HIGHEST, QUERY_BLOCK, _Frozen, _local, _mm,
                         _rope, store_dtype)

FAULTS = (None, "zero_experts_out", "bias_in_weights", "k_r_unrotated")
LOGIT_BLOCK = 1024


def _sizes(cfg):
    names = ("hidden_size", "num_attention_heads", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "ffn_hidden_size", "expert_ffn_hidden_size",
             "n_routed_experts", "router_width", "zero_expert_num")
    return tuple(int(cfg[n]) for n in names)


def _init(cfg):
    init = cfg.get("init", {})
    std = float(init.get("matrix_std", 0.02))
    return {"matrix": std, "emb": float(init.get("embedding_std", std)),
            "router": float(init.get("router_std", std)),
            "norm": float(init.get("norm_std", 0.02)),
            "bias": float(init["router_bias_std"])}


def layer_specs(cfg, i):
    """[(name, shape, mean, std)] of layer i, named as the program names its
    state (without the model's own prefix)."""
    s = _init(cfg)
    D, H, rq, rkv, nope, rope, dv, F, Fe, G, Rw, _ = _sizes(cfg)
    p = f"layers.{i}."
    specs = []
    for b in (0, 1):
        specs += [
            (p + f"n1_{b}", (D,), 1.0, s["norm"]),
            (p + f"wq_a_{b}", (D, rq), 0.0, s["matrix"]),
            (p + f"q_norm_{b}", (rq,), 1.0, s["norm"]),
            (p + f"wq_b_{b}", (rq, H * (nope + rope)), 0.0, s["matrix"]),
            (p + f"wkv_a_{b}", (D, rkv + rope), 0.0, s["matrix"]),
            (p + f"kv_norm_{b}", (rkv,), 1.0, s["norm"]),
            (p + f"wkv_b_{b}", (rkv, H * (nope + dv)), 0.0, s["matrix"]),
            (p + f"wo_{b}", (H * dv, D), 0.0, s["matrix"]),
            (p + f"n2_{b}", (D,), 1.0, s["norm"]),
            (p + f"ffn_gate_{b}", (D, F), 0.0, s["matrix"]),
            (p + f"ffn_up_{b}", (D, F), 0.0, s["matrix"]),
            (p + f"ffn_down_{b}", (F, D), 0.0, s["matrix"])]
    return specs + [
        (p + "router", (D, Rw), 0.0, s["router"]),
        (p + "router_bias", (Rw,), 0.0, s["bias"]),
        (p + "w_gate", (G, D, Fe), 0.0, s["matrix"]),
        (p + "w_up", (G, D, Fe), 0.0, s["matrix"]),
        (p + "w_down", (G, Fe, D), 0.0, s["matrix"])]


def end_specs(cfg):
    s = _init(cfg)
    D, V = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    return [("emb", (V, D), 0.0, s["emb"]), ("head", (V, D), 0.0, s["emb"]),
            ("ln_f", (D,), 1.0, s["norm"])]


def param_specs(cfg):
    specs = end_specs(cfg)
    for i in range(int(cfg["num_layers"])):
        specs += layer_specs(cfg, i)
    return specs


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _attention(p, b, h, cfg, cast, fault):
    """Sub-block b's latent attention on h (L, D) of ONE sequence, keys and
    values written out for every head."""
    D, H, rq, rkv, nope, rope, dv, *_ = _sizes(cfg)
    L = h.shape[0]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s_q = math.sqrt(D / rq) if cfg.get("mla_scale_q_lora") else 1.0
    s_kv = math.sqrt(D / rkv) if cfg.get("mla_scale_kv_lora") else 1.0
    c_q = _rms(_mm(h, p[f"wq_a_{b}"], cast), p[f"q_norm_{b}"], eps)
    q = s_q * _mm(c_q, p[f"wq_b_{b}"], cast).reshape(L, H, nope + rope)
    kv = _mm(h, p[f"wkv_a_{b}"], cast)
    c = s_kv * _rms(kv[:, :rkv], p[f"kv_norm_{b}"], eps)
    k_r = kv[:, None, rkv:]                               # L, 1, rope
    if fault != "k_r_unrotated":
        k_r = _rope(k_r, theta)
    q_r = _rope(q[..., nope:], theta)
    kvb = _mm(c, p[f"wkv_b_{b}"], cast).reshape(L, H, nope + dv)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_r, (L, H, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    v = kvb[..., nope:]
    j = jnp.arange(L)
    outs = []
    for a in range(0, L, QUERY_BLOCK):
        i = jnp.arange(a, min(a + QUERY_BLOCK, L))
        s = _mm(q[i].transpose(1, 0, 2), k.transpose(1, 2, 0), cast) \
            / math.sqrt(nope + rope)                         # H, block, L
        pr = jax.nn.softmax(
            jnp.where((j[None, :] <= i[:, None])[None], s, -jnp.inf), axis=-1)
        outs.append(_mm(pr, v.transpose(1, 0, 2), cast).transpose(1, 0, 2))
    o = jnp.concatenate(outs, axis=0).reshape(L, H * dv)
    return _mm(o, p[f"wo_{b}"], cast)


def _gated(u, wg, wu, wd, cast):
    return _mm(jax.nn.silu(_mm(u, wg, cast)) * _mm(u, wu, cast), wd, cast)


def _moe(p, u, cfg, cast, fault):
    """(s (L, D), picks (L, k) sorted expert ids)."""
    k = int(cfg["moe_topk"])
    held_from = int(cfg.get("experts_held_from", 0))
    routed = int(cfg["router_width"]) - int(cfg["zero_expert_num"])
    prob = jax.nn.softmax(_mm(u, p["router"], cast), axis=-1)
    biased = prob + p["router_bias"]
    _, idx = lax.top_k(biased, k)
    w = float(cfg["routed_scaling_factor"]) * jnp.take_along_axis(
        biased if fault == "bias_in_weights" else prob, idx, axis=-1)
    out = jnp.zeros_like(u)
    for g in range(p["w_gate"].shape[0]):
        weight = jnp.sum(jnp.where(idx == held_from + g, w, 0.0), axis=-1)
        out = out + weight[:, None] * _gated(
            u, p["w_gate"][g], p["w_up"][g], p["w_down"][g], cast)
    if fault != "zero_experts_out":
        out = out + jnp.sum(jnp.where(idx >= routed, w, 0.0), axis=-1,
                            keepdims=True) * u
    return out, jnp.sort(idx, axis=-1)


def _layer(p, x, cfg, cast, fault):
    """One double layer on ONE sequence x (L, D): (y, picks)."""
    cast = CASTS[cast]
    eps = float(cfg["rms_norm_eps"])
    for b in (0, 1):
        x = x + _attention(p, b, _rms(x, p[f"n1_{b}"], eps), cfg, cast,
                           fault)
        u = _rms(x, p[f"n2_{b}"], eps)
        if b == 0:
            s, picks = _moe(p, u, cfg, cast, fault)
        x = x + _gated(u, p[f"ffn_gate_{b}"], p[f"ffn_up_{b}"],
                       p[f"ffn_down_{b}"], cast)
    return x + s, picks


_layer_jit = jax.jit(_layer, static_argnames=("cfg", "cast", "fault"))


def layer_forward(params, x, i, cfg, cast="float32", fault=None):
    """Layer i of `params` ({model name: float32 array}) on x (N, L, D):
    (y (N, L, D), picks (N, L, k))."""
    assert fault in FAULTS, fault
    frozen = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    p = params if "router" in params else _local(params, i)
    with jax.default_matmul_precision("highest"):
        outs = [_layer_jit(p, x[n], cfg=frozen, cast=cast, fault=fault)
                for n in range(x.shape[0])]
    return jnp.stack([o[0] for o in outs]), jnp.stack([o[1] for o in outs])


def logits_of(params, x, cfg, cast="float32"):
    """RMSNorm then the untied head: (..., V) float32."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["ln_f"], float(cfg["rms_norm_eps"]))
        return _mm(h, params["head"].T, CASTS[cast])


def forward(params, ids, cfg, cast="float32", fault=None):
    """The whole forward of sequences ids (N, L) with every weight given:
    logits (N, L, V). For the CPU tests; the chip's comparison goes layer
    by layer (`served_gaps`)."""
    frozen = _Frozen(cfg)
    x = params["emb"][ids]
    for i in range(int(cfg["num_layers"])):
        x, _ = layer_forward(params, x, i, frozen, cast, fault)
    return logits_of(params, x, cfg, cast)


def _hidden(cfg, seed, ids, cast, keep_picks, fault=None):
    """Final hidden states of `ids` (N, L) with weights drawn layer by layer
    from the seed: (x (N, L, D), the end leaves, [picks per layer])."""
    store = store_dtype(cfg)
    frozen = _Frozen(cfg)
    ends = weights_staged.make(end_specs(cfg), seed, store, jnp.float32)
    x = ends["emb"][ids]
    picks = []
    for i in range(int(cfg["num_layers"])):
        p = _local(weights_staged.make(layer_specs(cfg, i), seed, store,
                                       jnp.float32), i)
        x, pk = layer_forward(p, x, i, frozen, cast, fault)
        if keep_picks:
            picks.append(np.asarray(pk))
        del p
    return x, ends, picks


def _gaps(ends, x, ids, x_low, cfg, cast):
    """One sequence at a time, positions in blocks (the logits of a block
    are LOGIT_BLOCK x V float32)."""
    served, low = [], []
    for n in range(ids.shape[0]):
        g, lo = [], []
        for a in range(0, ids.shape[1] - 1, LOGIT_BLOCK):
            b = min(a + LOGIT_BLOCK, ids.shape[1] - 1)
            full = logits_of(ends, x[n, a:b], cfg)
            best = jnp.max(full, axis=-1)
            nxt = jnp.take_along_axis(full, ids[n, a + 1:b + 1, None],
                                      axis=-1)[..., 0]
            g.append(np.asarray(best - nxt))
            if x_low is not None:
                pick = jnp.argmax(logits_of(ends, x_low[n, a:b], cfg, cast),
                                  axis=-1)
                lo.append(np.asarray(best - jnp.take_along_axis(
                    full, pick[..., None], axis=-1)[..., 0]))
        served.append(np.concatenate(g))
        if lo:
            low.append(np.concatenate(lo))
    return np.stack(served), (np.stack(low) if low else None)


def served_gaps(cfg, seed, ids, cast=None, picks_out=None, fault=None):
    """For sequences `ids` (N, L): at every position t, how far the logit of
    the token that follows lies below the best logit in this float32 pass;
    and, when `cast` names a lower precision or `fault` a planted fault, the
    same for the token THAT pass puts first, read in the float32 logits.
    Returns (gap_of_next (N, L-1), gap_of_the_other_pass's_best or None).
    `picks_out`, a dict, receives under "float32" and `cast` each pass's
    picks per layer."""
    ids = jnp.asarray(ids)
    keep = picks_out is not None
    x, ends, picks = _hidden(cfg, seed, ids, "float32", keep)
    x_low = None
    if keep:
        picks_out["float32"] = picks
    if cast is not None or fault is not None:
        x_low, _, picks_low = _hidden(cfg, seed, ids, cast or "float32", keep,
                                      fault)
        if keep:
            picks_out[cast] = picks_low
    return _gaps(ends, x, ids, x_low, cfg, cast or "float32")
