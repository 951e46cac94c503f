"""Phi-4-mini-flash-reasoning (huggingface.co/microsoft/Phi-4-mini-flash-
reasoning `config.json`, model_type `phi4flash`; the model's paper is
arXiv:2507.06607, "SambaY": a self-decoder, then a cross-decoder that
reuses the self-decoder's last keys and values and a memory) in plain
float32 at the highest matmul precision: no kernel, no cache, no batching
trick, every layer on every position, the recurrence a `lax.scan` a token.
For layer l with input x (T x D), every layer alike:

    x = x + mix_l(LN1(x));  x = x + fc2(silu(g) * u),  [g, u] = fc1(LN2(x))
    LN(x) = (x - mean) / sqrt(var + eps) * w + b

and mix_l by the layer's kind (L layers; the first L/2 + 2 are the
self-decoder, even layers Mamba and odd ones attention; the rest the
cross-decoder, even layers Gated Memory Units and odd ones cross
attention):

  Mamba (C = expand * D channels, N = d_state, K = d_conv, R = dt_rank):
    [x, z] = h W_in;  x = silu(b_c + sum_k w_c[:, k] x_{t-K+1+k})
    [d, B, C_] = x W_x;  dt = softplus(d W_dt + b_dt);  A = -exp(A_log)
    s_t = exp(dt_t A) * s_{t-1} + (dt_t x_t) (x) B_t;  y_t = s_t C_t + D x_t
    out = (y * silu(z)) W_out.      Layer L/2 also hands m = y down.
  Differential attention (Hq query / Hkv KV heads of hd; window W but for
  layer L/2 + 1, which sees everything; no positional encoding):
    [q, k, v] = h W_qkv + b_qkv; differential head i has q1, q2 = query
    heads 2i, 2i+1 and reads differential KV head j = i // 2: k1, k2 = KV
    heads 2j, 2j+1, v = [v_2j ; v_2j+1] (2 hd wide)
    a1 = softmax(q1 k1^T / sqrt(hd) + mask) v;  a2 likewise with q2, k2
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + l0,  l0 = 0.8 - 0.6 exp(-0.3 l)
    head_i = (1 - l0) * RMSNorm(a1 - lam a2) * g   (over 2 hd, eps)
    out = concat_i(head_i) W_o + b_o
  Gated Memory Unit:  out = (m * silu(h W_1)) W_2
  Cross attention: the same with its own q = h W_q + b_q and layer
    L/2 + 1's k and v (causal mask alone).

    logits = LN_f(x) E^T      (tied embedding, no head bias)

The served comparison goes layer by layer: a layer's weights are drawn from
the seed (lib/weights_staged.py), used on every sampled sequence and freed;
between layers the sequences' hidden states, the memory and the full
layer's keys and values are kept. Queries go in blocks.

Two faults can be planted (`fault=`), for the tests that show the
comparison catching them: "state_lost" zeroes every Mamba layer's state and
convolution inputs at position `reset_at` of each sequence (a state that
prefill did not hand to decoding); "cross_reads_window" gives the cross
layers the LAST WINDOW layer's keys and values, inside its window.
"""

import functools
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
                         store_dtype)

MAMBA, ATTN, GMU, CROSS = "mamba", "attention", "gmu", "cross"
FAULTS = (None, "state_lost", "cross_reads_window")


def kinds(cfg):
    L = int(cfg["num_hidden_layers"])
    n_self = L // 2 + 2
    return [(MAMBA if i % 2 == 0 else ATTN) if i < n_self else
            (GMU if i % 2 == 0 else CROSS) for i in range(L)]


def _sizes(cfg):
    D = int(cfg["hidden_size"])
    return dict(
        D=D, F=int(cfg["intermediate_size"]),
        Hq=int(cfg["num_attention_heads"]),
        Hkv=int(cfg["num_key_value_heads"]),
        hd=D // int(cfg["num_attention_heads"]),
        C=int(cfg.get("mamba_expand", 2)) * D,
        N=int(cfg.get("mamba_d_state", 16)),
        K=int(cfg.get("mamba_d_conv", 4)),
        R=int(cfg.get("mamba_dt_rank") or -(-D // 16)))


def _init(cfg):
    """{kind of leaf: (mean, std)}."""
    init = cfg.get("init", {})
    std = float(init.get("matrix_std", 0.02))
    pair = lambda key, mean, s: (  # noqa: E731
        float(init.get(key + "_mean", mean)), float(init.get(key + "_std", s)))
    return {"matrix": (0.0, std),
            "emb": (0.0, float(init.get("embedding_std", std))),
            "norm": (1.0, float(init.get("norm_std", 0.02))),
            "bias": (0.0, float(init.get("bias_std", 0.02))),
            "lam": (0.0, float(init.get("lambda_std", 0.1))),
            "conv": (0.0, float(init.get("conv_std", 0.3))),
            "D": pair("D", 1.0, 0.02), "A_log": pair("A_log", 1.9, 0.75),
            "dt_bias": pair("dt_bias", -4.6, 1.3)}


def layer_specs(cfg, i):
    """[(name, shape, mean, std)] of layer i, named as the program names its
    state (without the model's own prefix)."""
    s, z = _init(cfg), _sizes(cfg)
    D, F, C, hd, Hq, Hkv = (z[k] for k in ("D", "F", "C", "hd", "Hq", "Hkv"))
    kind = kinds(cfg)[i]
    leaves = [("ln1_w", (D,), "norm"), ("ln1_b", (D,), "bias"),
              ("ln2_w", (D,), "norm"), ("ln2_b", (D,), "bias"),
              ("fc1", (D, 2 * F), "matrix"), ("fc2", (F, D), "matrix")]
    if kind == MAMBA:
        leaves += [("in_proj", (D, 2 * C), "matrix"),
                   ("conv_w", (C, z["K"]), "conv"),
                   ("conv_b", (C,), "bias"),
                   ("x_proj", (C, z["R"] + 2 * z["N"]), "matrix"),
                   ("dt_proj", (z["R"], C), "matrix"),
                   ("dt_bias", (C,), "dt_bias"),
                   ("A_log", (C, z["N"]), "A_log"), ("D", (C,), "D"),
                   ("out_proj", (C, D), "matrix")]
    elif kind == GMU:
        leaves += [("w1", (D, C), "matrix"), ("w2", (C, D), "matrix")]
    else:
        leaves += [(n, (hd,), "lam") for n in ("lam_q1", "lam_k1", "lam_q2",
                                               "lam_k2")]
        leaves += [("subln", (2 * hd,), "norm"),
                   ("wo", (Hq * hd, D), "matrix"), ("bo", (D,), "bias")]
        if kind == ATTN:
            leaves += [("wqkv", (D, (Hq + 2 * Hkv) * hd), "matrix"),
                       ("bqkv", ((Hq + 2 * Hkv) * hd,), "bias")]
        else:
            leaves += [("wq", (D, Hq * hd), "matrix"),
                       ("bq", (Hq * hd,), "bias")]
    return [(f"layers.{i}.{n}", shape, *s[k]) for n, shape, k in leaves]


def end_specs(cfg):
    s, D = _init(cfg), int(cfg["hidden_size"])
    return [("emb", (int(cfg["vocab_size"]), D), *s["emb"]),
            ("ln_f", (D,), *s["norm"]), ("ln_f_b", (D,), *s["bias"])]


def param_specs(cfg):
    specs = end_specs(cfg)
    for i in range(int(cfg["num_hidden_layers"])):
        specs += layer_specs(cfg, i)
    return specs


def _ln(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _conv(x, w, b, cast):
    """Depthwise, causal: y_t = b + sum_k w[:, k] x_{t-K+1+k}; operands
    rounded like a matrix product's."""
    T, K = x.shape[0], w.shape[1]
    if cast is not None:
        x, w = cast(x), cast(w)
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return b + sum(xp[k:k + T] * w[:, k] for k in range(K))


def _mamba(p, h, cfg, cast, reset_at):
    """h: (T, D) of ONE sequence. Returns (out, y): y the scan's output
    before the gate. `reset_at`: the position whose step starts from a
    zero state and a zero convolution history (the fault), or -1."""
    z_ = _sizes(cfg)
    C, N, R = z_["C"], z_["N"], z_["R"]
    T = h.shape[0]
    t = jnp.arange(T)
    xz = _mm(h, p["in_proj"], cast)
    x, z = xz[:, :C], xz[:, C:]
    conv = _conv(x, p["conv_w"], p["conv_b"], cast)
    lost = _conv(jnp.where((t >= reset_at)[:, None], x, 0.0), p["conv_w"],
                 p["conv_b"], cast)
    x = jax.nn.silu(jnp.where(((t >= reset_at) & (reset_at >= 0))[:, None],
                              lost, conv))
    dbc = _mm(x, p["x_proj"], cast)
    dt = jax.nn.softplus(_mm(dbc[:, :R], p["dt_proj"], cast) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])                                    # (C, N)

    def step(s, inp):
        i, dt_t, x_t, b_t, c_t = inp
        s = jnp.where(i == reset_at, 0.0, s)
        s = jnp.exp(dt_t[:, None] * A) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], axis=-1) + p["D"] * x_t

    _, y = lax.scan(step, jnp.zeros((C, N), jnp.float32),
                    (t, dt, x, dbc[:, R:R + N], dbc[:, R + N:]))
    return _mm(y * jax.nn.silu(z), p["out_proj"], cast), y


def _differential(p, lam0, q, k, v, window, cfg, cast):
    """q: (T, Hq, hd); k, v: (T, Hkv, hd) of ONE sequence, from the
    equations: differential head n uses query heads 2n, 2n+1 and KV heads
    2(n // 2), 2(n // 2) + 1."""
    z = _sizes(cfg)
    T, Hq, hd = q.shape
    nd = Hq // 2
    kv_of = jnp.arange(nd) // (Hq // z["Hkv"])      # differential KV head
    q1, q2 = q[:, 0::2].transpose(1, 0, 2), q[:, 1::2].transpose(1, 0, 2)
    k1 = k[:, 0::2][:, kv_of].transpose(1, 2, 0)               # nd, hd, T
    k2 = k[:, 1::2][:, kv_of].transpose(1, 2, 0)
    vv = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)[:, kv_of] \
        .transpose(1, 0, 2)                                    # nd, T, 2hd
    lam = jnp.exp(jnp.sum(p["lam_q1"] * p["lam_k1"])) \
        - jnp.exp(jnp.sum(p["lam_q2"] * p["lam_k2"])) + lam0
    j = jnp.arange(T)
    heads = []
    for a in range(0, T, QUERY_BLOCK):
        r = jnp.arange(a, min(a + QUERY_BLOCK, T))
        keep = j[None, :] <= r[:, None]
        if window is not None:
            keep &= r[:, None] - j[None, :] < window

        def half(qh, kh):
            s = _mm(qh[:, r], kh, cast) / math.sqrt(hd)
            pr = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
            return _mm(pr, vv, cast)                           # nd, rows, 2hd

        d = half(q1, k1) - lam * half(q2, k2)
        d = d / jnp.sqrt(jnp.mean(jnp.square(d), -1, keepdims=True)
                         + float(cfg["layer_norm_eps"]))
        heads.append(((1.0 - lam0) * d * p["subln"]).transpose(1, 0, 2))
    o = jnp.concatenate(heads, axis=0).reshape(T, nd * 2 * hd)
    return _mm(o, p["wo"], cast) + p["bo"]


def _layer(p, x, memory, k_in, v_in, reset_at, lam0, *, kind, window, cfg,
           cast):
    """One layer on ONE sequence x (T, D); `lam0` is its l0 (data, so that
    layers of one kind share a program). Returns (y, memory or None, k, v
    or None) — what the layer hands down."""
    cast = CASTS[cast]
    z, eps = _sizes(cfg), float(cfg["layer_norm_eps"])
    Hq, Hkv, hd, F = z["Hq"], z["Hkv"], z["hd"], z["F"]
    h = _ln(x, p["ln1_w"], p["ln1_b"], eps)
    T = h.shape[0]
    m = k = v = None
    if kind == MAMBA:
        out, m = _mamba(p, h, cfg, cast, reset_at)
    elif kind == GMU:
        out = _mm(memory * jax.nn.silu(_mm(h, p["w1"], cast)), p["w2"], cast)
    elif kind == ATTN:
        qkv = _mm(h, p["wqkv"], cast) + p["bqkv"]
        q = qkv[:, :Hq * hd].reshape(T, Hq, hd)
        k = qkv[:, Hq * hd:(Hq + Hkv) * hd].reshape(T, Hkv, hd)
        v = qkv[:, (Hq + Hkv) * hd:].reshape(T, Hkv, hd)
        out = _differential(p, lam0, q, k, v, window, cfg, cast)
    else:
        q = (_mm(h, p["wq"], cast) + p["bq"]).reshape(T, Hq, hd)
        out = _differential(p, lam0, q, k_in, v_in, window, cfg, cast)
    x = x + out
    gu = _mm(_ln(x, p["ln2_w"], p["ln2_b"], eps), p["fc1"], cast)
    return x + _mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], p["fc2"], cast), \
        m, k, v


_layer_jit = jax.jit(_layer, static_argnames=("kind", "window", "cfg",
                                              "cast"))


class _Carry:
    """What the layers hand down, a list entry a sequence: the memory and
    the keys and values the cross layers read."""

    def __init__(self, n):
        self.memory = [None] * n
        self.kv = [(None, None)] * n


def layer_forward(params, x, i, cfg, carry, cast="float32", fault=None,
                  reset_at=None):
    """Layer i of `params` ({name: float32 array}, the model's or the
    layer's own) on x (n, T, D), one sequence at a time; `carry` is read
    and updated. Returns y (n, T, D)."""
    assert fault in FAULTS, fault
    frozen = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    kind = kinds(cfg)[i]
    L = int(cfg["num_hidden_layers"])
    full = L // 2 + 1
    # the layer whose keys and values the cross layers get
    source = full - 2 if fault == "cross_reads_window" else full
    window = None if i == full or (kind == CROSS and source == full) \
        else int(cfg["sliding_window"])
    p = params if "ln1_w" in params else _local(params, i)
    out = []
    with jax.default_matmul_precision("highest"):
        for n in range(x.shape[0]):
            at = -1 if fault != "state_lost" else int(reset_at[n])
            k_in, v_in = carry.kv[n]
            y, m, k, v = _layer_jit(
                p, x[n], carry.memory[n], k_in, v_in, jnp.int32(at),
                jnp.float32(0.8 - 0.6 * math.exp(-0.3 * i)), kind=kind,
                window=window, cfg=frozen, cast=cast)
            if i == L // 2:
                carry.memory[n] = m
            if i == source:
                carry.kv[n] = (k, v)
            out.append(y)
    return jnp.stack(out)


@jax.jit
def _final_norm(x, w, b, eps):
    return _ln(x, w, b, eps)


def _head(params, cast):
    """The tied embedding as the head's operand, rounded once a pass (one
    program: a 0.5 G-element matrix is not rounded op by op)."""
    cast = CASTS[cast]
    return params["emb"] if cast is None else jax.jit(cast)(params["emb"])


@functools.partial(jax.jit, static_argnames=("cast",))
def _head_logits(h, head, *, cast):
    cast = CASTS[cast]
    h = h if cast is None else cast(h)
    return jnp.matmul(h, head.T, precision=HIGHEST)


def logits_of(params, x, cfg, cast="float32", head=None):
    """LN then the tied embedding as head: (..., V) float32. `head`: the
    embedding already rounded for `cast` (`_head`), where a caller makes
    many calls."""
    with jax.default_matmul_precision("highest"):
        h = _final_norm(x, params["ln_f"], params["ln_f_b"],
                        float(cfg["layer_norm_eps"]))
        return _head_logits(h, _head(params, cast) if head is None else head,
                            cast=cast)


def forward(params, ids, cfg, cast="float32", fault=None, reset_at=None):
    """The whole forward of sequences ids (n, T) with every weight given:
    logits (n, T, V). For the CPU tests; the chip's comparison goes layer
    by layer (`served_gaps`)."""
    frozen = _Frozen(cfg)
    x = params["emb"][ids]
    carry = _Carry(x.shape[0])
    for i in range(int(cfg["num_hidden_layers"])):
        x = layer_forward(params, x, i, frozen, carry, cast, fault, reset_at)
    return logits_of(params, x, cfg, cast)


def _hidden(cfg, seed, ids, cast, fault=None, reset_at=None):
    """Final hidden states of `ids` (n, T) with weights drawn layer by layer
    from the seed: (x (n, T, D), the end leaves)."""
    store = store_dtype(cfg)
    frozen = _Frozen(cfg)
    ends = weights_staged.make(end_specs(cfg), seed, store, jnp.float32)
    x = ends["emb"][ids]
    carry = _Carry(x.shape[0])
    for i in range(int(cfg["num_hidden_layers"])):
        p = _local(weights_staged.make(layer_specs(cfg, i), seed, store,
                                       jnp.float32), i)
        x = layer_forward(p, x, i, frozen, carry, cast, fault, reset_at)
        del p
    return x, ends


LOGIT_BLOCK = 1024


def _gaps(ends, x, ids, x_low, cfg, cast):
    """One sequence at a time, positions in blocks (the logits of a block
    are LOGIT_BLOCK x V float32)."""
    served, low = [], []
    head_low = None if x_low is None else _head(ends, cast)
    for n in range(ids.shape[0]):
        g, lo = [], []
        for a in range(0, ids.shape[1] - 1, LOGIT_BLOCK):
            b = min(a + LOGIT_BLOCK, ids.shape[1] - 1)
            full = logits_of(ends, x[n, a:b], cfg, head=ends["emb"])
            best = jnp.max(full, axis=-1)
            nxt = jnp.take_along_axis(full, ids[n, a + 1:b + 1, None],
                                      axis=-1)[..., 0]
            g.append(np.asarray(best - nxt))
            if x_low is not None:
                pick = jnp.argmax(logits_of(ends, x_low[n, a:b], cfg, cast,
                                            head_low), axis=-1)
                lo.append(np.asarray(best - jnp.take_along_axis(
                    full, pick[..., None], axis=-1)[..., 0]))
        served.append(np.concatenate(g))
        if lo:
            low.append(np.concatenate(lo))
    return np.stack(served), (np.stack(low) if low else None)


def served_gaps(cfg, seed, ids, cast=None, picks_out=None, fault=None,
                reset_at=None):
    """For sequences `ids` (n, T): at every position t, how far the logit of
    the token that follows lies below the best logit in this float32 pass;
    and, when `cast` names a lower precision or `fault` a planted fault
    (`reset_at`: (n,) positions, for "state_lost"), the same for the token
    THAT pass puts first, read in the float32 logits. Returns (gap_of_next
    (n, T-1), gap_of_the_other_pass's_best or None). `picks_out` is the
    staged driver's hook for a router's picks per layer: this model has no
    router, so each pass's list is empty."""
    ids = jnp.asarray(ids)
    if picks_out is not None:
        picks_out["float32"] = []
        picks_out[cast] = []
    x, ends = _hidden(cfg, seed, ids, "float32")
    x_low = None
    if cast is not None or fault is not None:
        x_low, _ = _hidden(cfg, seed, ids, cast or "float32", fault, reset_at)
    return _gaps(ends, x, ids, x_low, cfg, cast or "float32")
