"""Operations and bytes a phi4flash model (self-decoder of Mamba and
differential-attention layers, cross-decoder of Gated Memory Units and cross
attention) needs, from the configuration's shapes. A multiply-add is 2
FLOPs. Counted are the matrices a token is multiplied by; attention's scores
and values, the recurrence's elementwise work, norms and biases are left out
(as `reducers/serve_mfu.py` leaves attention out). What an implementation
computes beyond that (padding rows of a prefill) is never counted.
"""


def _widths(cfg):
    D = int(cfg["hidden_size"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return dict(D=D, F=int(cfg["intermediate_size"]), hq=hq, hkv=hkv,
                hd=D // hq, C=int(cfg.get("mamba_expand", 2)) * D,
                N=int(cfg.get("mamba_d_state", 16)),
                K=int(cfg.get("mamba_d_conv", 4)),
                R=int(cfg.get("mamba_dt_rank") or -(-D // 16)))


def kinds(cfg):
    """The layers' kinds: the first L/2 + 2 are the self-decoder."""
    L = int(cfg["num_hidden_layers"])
    n_self = L // 2 + 2
    return [("mamba" if i % 2 == 0 else "attention") if i < n_self else
            ("gmu" if i % 2 == 0 else "cross") for i in range(L)]


def mixer_params(cfg, kind):
    w = _widths(cfg)
    D, C, hd = w["D"], w["C"], w["hd"]
    if kind == "mamba":
        return D * 2 * C + C * w["K"] + C * (w["R"] + 2 * w["N"]) \
            + w["R"] * C + C * D
    if kind == "attention":
        return D * (w["hq"] + 2 * w["hkv"]) * hd + w["hq"] * hd * D
    if kind == "gmu":
        return 2 * D * C
    return 2 * D * w["hq"] * hd                 # cross: Wq and Wo


def mlp_params(cfg):
    w = _widths(cfg)
    return w["D"] * 2 * w["F"] + w["F"] * w["D"]


def decoder_params(cfg):
    """(self-decoder, cross-decoder): the matrices of the layers a prompt's
    every token goes through, and of those its last token alone does."""
    per = [mixer_params(cfg, k) + mlp_params(cfg) for k in kinds(cfg)]
    n_self = int(cfg["num_hidden_layers"]) // 2 + 2
    return sum(per[:n_self]), sum(per[n_self:])


def head_params(cfg):
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def serve_flops(cfg, self_rows, cross_rows, output_tokens):
    """FLOPs the served tokens need: a prompt's tokens through the
    self-decoder (`self_rows`), its last token through the cross-decoder
    (`cross_rows`), every output token through all layers and the head."""
    p_self, p_cross = decoder_params(cfg)
    return 2.0 * (p_self * self_rows + p_cross * cross_rows
                  + (p_self + p_cross + head_params(cfg)) * output_tokens)


def kv_row_bytes(cfg, itemsize):
    """One position's keys and values of every KV head."""
    w = _widths(cfg)
    return 2 * w["hkv"] * w["hd"] * itemsize


def state_bytes(cfg, itemsize):
    """What one Mamba layer keeps of one sequence: the float32 state and
    the convolution's last inputs."""
    w = _widths(cfg)
    return w["C"] * w["N"] * 4 + (w["K"] - 1) * w["C"] * itemsize


def leaf_params(cfg):
    """Every parameter of the model: the matrices, the tied embedding, and
    the norms, biases, lambdas and per-channel vectors beside them."""
    w = _widths(cfg)
    D, C, hd = w["D"], w["C"], w["hd"]
    small = {"mamba": 3 * C + C * w["N"],       # conv_b, dt_bias, D, A_log
             "attention": (w["hq"] + 2 * w["hkv"]) * hd + D + 6 * hd,
             "gmu": 0, "cross": w["hq"] * hd + D + 6 * hd}
    total = head_params(cfg) + 2 * D
    for kind in kinds(cfg):
        total += mixer_params(cfg, kind) + mlp_params(cfg) + 4 * D \
            + small[kind]
    return total


def decode_tick_bytes(cfg, itemsize, kv_rows, state_slots):
    """Bytes one decode tick has to move: every leaf once, the ring rows
    that hold a token as often as a layer reads them (`kv_rows`, summed over
    the layers that read a ring and the live slots), each live slot's state
    of each Mamba layer read and written (`state_slots`)."""
    return itemsize * leaf_params(cfg) \
        + kv_row_bytes(cfg, itemsize) * kv_rows \
        + 2 * state_bytes(cfg, itemsize) * state_slots
