"""Operations and bytes the cohere2_moe share needs, from the configuration's
shapes. A multiply-add is 2 FLOPs; what an implementation computes beyond
the pairs routed here (every held expert on every row of a decode tick) is
never counted.
"""


def _widths(cfg):
    D, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    return (D, hd, int(cfg["num_attention_heads"]),
            int(cfg["num_key_value_heads"]), int(cfg["intermediate_size"]))


def expert_params(cfg):
    """One gated expert: gate, up and down."""
    D, _, _, _, F = _widths(cfg)
    return 3 * D * F


def dense_params_per_layer(cfg):
    """What multiplies every token in a layer whatever the router says:
    the four attention projections, the router, the shared experts."""
    D, hd, hq, hkv, _ = _widths(cfg)
    attn = 2 * D * hq * hd + 2 * D * hkv * hd
    return attn + D * int(cfg["router_width"]) \
        + int(cfg["num_shared_experts"]) * expert_params(cfg)


def head_params(cfg):
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def serve_flops(cfg, tokens, output_tokens, pairs_here):
    """FLOPs the served tokens need: every token through every layer's
    dense part, each pair routed here through one expert, each output
    token through the head. Attention's scores and values are left out
    (as `reducers/serve_mfu.py` leaves them out)."""
    layers = int(cfg["num_hidden_layers"])
    return 2.0 * (layers * dense_params_per_layer(cfg) * tokens
                  + expert_params(cfg) * pairs_here
                  + head_params(cfg) * output_tokens)


def decode_tick_bytes(cfg, itemsize, experts_touched, kv_rows):
    """Bytes one decode tick has to read: every leaf outside the routed
    experts once (the tied embedding as head, the norms, attention, the
    router, the shared experts), three matrices for each held expert that
    got a pair (`experts_touched`, summed over layers), and the ring rows
    that hold a token (`kv_rows`, summed over layers and slots; a row is
    one position's keys and values of the KV heads held here)."""
    D, hd, _, hkv, _ = _widths(cfg)
    layers = int(cfg["num_hidden_layers"])
    fixed = head_params(cfg) + D + layers * (dense_params_per_layer(cfg) + D)
    return itemsize * (fixed + expert_params(cfg) * experts_touched
                       + 2 * hkv * hd * kv_rows)
