"""Operations and bytes one chip's share of a LongCat-Flash language model
needs, from the configuration's shapes. A multiply-add is 2 FLOPs. What an
implementation computes beyond the need is never counted: every held expert
on every row of a decode tick, the padding of a cached row to whole lane
tiles, the blocks of a ring beyond the rows that hold a token. A pick of an
identity ("zero-compute") expert needs no FLOP and no byte of weights.
"""


def _w(cfg):
    names = ("hidden_size", "num_attention_heads", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "ffn_hidden_size", "expert_ffn_hidden_size")
    return tuple(int(cfg[n]) for n in names)


def mla_params(cfg):
    """One latent-attention block: q_a, q_b, kv_a, kv_b, o."""
    D, H, rq, rkv, nope, rope, dv, _, _ = _w(cfg)
    return D * rq + rq * H * (nope + rope) + D * (rkv + rope) \
        + rkv * H * (nope + dv) + H * dv * D


def dense_ffn_params(cfg):
    D, *_, F, _ = _w(cfg)
    return 3 * D * F


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    D, *_, Fe = _w(cfg)
    return 3 * D * Fe


def dense_params_per_layer(cfg):
    """What multiplies every token in a double layer whatever the router
    says: two attention blocks, two dense FFNs, the router."""
    return 2 * mla_params(cfg) + 2 * dense_ffn_params(cfg) \
        + int(cfg["hidden_size"]) * int(cfg["router_width"])


def head_params(cfg):
    """The untied head over the rows of the vocabulary held here (the
    embedding, as large, is gathered from and multiplies nothing)."""
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def small_params_per_layer(cfg):
    """The vectors of a double layer: four stream norms, the two low-rank
    norms of each block, the router's bias."""
    D, _, rq, rkv, *_ = _w(cfg)
    return 4 * D + 2 * (rq + rkv) + int(cfg["router_width"])


def leaf_params(cfg):
    """Every parameter held here."""
    layers = int(cfg["num_layers"])
    return layers * (dense_params_per_layer(cfg) + small_params_per_layer(cfg)
                     + int(cfg["n_routed_experts"]) * expert_params(cfg)) \
        + 2 * head_params(cfg) + int(cfg["hidden_size"])


def latent_row_width(cfg):
    """Numbers a token a block keeps: [c; k_r], for every head."""
    return int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])


def latent_row_flops(cfg):
    """FLOPs one cached row costs one decode query of every head, in the
    absorbed form: scored over its whole width, weighed over its first
    kv_lora_rank columns."""
    H = int(cfg["num_attention_heads"])
    return 2 * H * (latent_row_width(cfg) + int(cfg["kv_lora_rank"]))


def prefill_attention_flops(cfg, tokens_sq):
    """Causal attention of whole prompts in the expanded form, every block
    of every layer: a (query, key) pair of a head is scored over nope + rope
    and weighed over v_head_dim columns; `tokens_sq` is the sum of the
    prompts' squared lengths, of which causality keeps half."""
    _, H, _, _, nope, rope, dv, _, _ = _w(cfg)
    blocks = 2 * int(cfg["num_layers"])
    return 2 * H * (nope + rope + dv) * blocks * tokens_sq / 2.0


def serve_flops(cfg, tokens, output_tokens, pairs_here, kv_rows, tokens_sq):
    """FLOPs the served tokens need: every token through every layer's dense
    part, each pair routed to an expert held here through that expert, each
    output token through the head, each cached row a decode tick attends to
    (`kv_rows`, summed over the 2 x layers levels and the live slots), and
    the prompts' causal attention."""
    layers = int(cfg["num_layers"])
    return 2.0 * (layers * dense_params_per_layer(cfg) * tokens
                  + expert_params(cfg) * pairs_here
                  + head_params(cfg) * output_tokens) \
        + latent_row_flops(cfg) * kv_rows \
        + prefill_attention_flops(cfg, tokens_sq)


def decode_tick_bytes(cfg, itemsize, experts_touched, kv_rows):
    """Bytes one decode tick has to read: every leaf outside the routed
    experts and the embedding once (the embedding gives up one row a live
    slot, which is not counted), three matrices for each held expert that
    got a pair (`experts_touched`, summed over layers), and the cached rows
    that hold a token (`kv_rows`, summed over levels and slots)."""
    layers = int(cfg["num_layers"])
    fixed = head_params(cfg) + int(cfg["hidden_size"]) + layers * (
        dense_params_per_layer(cfg) + small_params_per_layer(cfg))
    return itemsize * (fixed + expert_params(cfg) * experts_touched
                       + latent_row_width(cfg) * kv_rows)


def latent_decode_need(cfg, itemsize, kv_rows):
    """(FLOPs, bytes) the latent decode kernel's calls of one tick need:
    each cached row that holds a token read once, at its own width, for all
    heads, and scored and weighed by every head."""
    return latent_row_flops(cfg) * kv_rows, \
        itemsize * latent_row_width(cfg) * kv_rows
