"""Operations and bytes one chip's share of a Kanana-2 (DeepSeek-V3-style)
language model needs in training, from the configuration's shapes, under
lib/flops.py's rules: a multiply-add is 2 FLOPs, training is 3x the forward
pass, recomputation (remat, the flash backward's scores) is never counted.

The work counted is the work done HERE: the pairs routed to the experts
held on this chip, as the program counts them (the gauges
`moe_pairs_here{layer}` and `moe_pairs_here_per_step{layer}` of
models/kanana.py, summed over the expert layers), not the deployed top_k
and not what a router that spreads its picks evenly would send here. The
padding of the grouped product's tiles is never counted.
"""


def _w(cfg):
    names = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
             "intermediate_size", "moe_intermediate_size", "vocab_size")
    return tuple(int(cfg[n]) for n in names)


def _layers(cfg):
    """(dense layers, expert layers)."""
    n, dense = int(cfg["num_hidden_layers"]), \
        int(cfg["first_k_dense_replace"])
    return dense, n - dense


def mla_params(cfg):
    """One latent-attention block with a full-rank query: q, kv_a, kv_b,
    o."""
    D, H, nope, rope, dv, rank, *_ = _w(cfg)
    return D * H * (nope + rope) + D * (rank + rope) \
        + rank * H * (nope + dv) + H * dv * D


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    D, *_, Fe, _ = _w(cfg)
    return 3 * D * Fe


def shared_params(cfg):
    D, *_, Fe, _ = _w(cfg)
    return 3 * D * Fe * int(cfg["n_shared_experts"])


def param_count(cfg):
    """Every parameter held here: embedding, layers (their norms, the
    router and its selection bias), the final norm, the head."""
    D, H, nope, rope, dv, rank, F, _, V = _w(cfg)
    dense, moe = _layers(cfg)
    per_layer = mla_params(cfg) + 2 * D + rank
    router = D * int(cfg["router_width"]) + int(cfg["router_width"])
    return 2 * V * D + D + dense * (per_layer + 3 * D * F) \
        + moe * (per_layer + router + shared_params(cfg)
                 + int(cfg["n_routed_experts"]) * expert_params(cfg))


def forward_flops_per_token(cfg, context, pairs):
    """Forward FLOPs of one token attending causally within sequences of
    `context` tokens (on average half the context): 2 x the matrix
    parameters that multiply it (attention, the dense FFN or the router,
    the shared expert, the head) + scores and values, 2 x context / 2 x
    heads x (key + value width) a layer, + 2 x an expert's parameters for
    each of its `pairs`: the pairs held here a token, summed over the
    expert layers."""
    D, H, nope, rope, dv, rank, F, _, V = _w(cfg)
    dense, moe = _layers(cfg)
    attn = 2 * mla_params(cfg) + 2 * context * 0.5 * H * (nope + rope + dv)
    ffn_moe = 2 * (D * int(cfg["router_width"]) + shared_params(cfg))
    return (dense + moe) * attn + dense * 2 * 3 * D * F + moe * ffn_moe \
        + 2 * pairs * expert_params(cfg) + 2 * D * V


def train_flops_per_token(cfg, seq, pairs):
    return 3 * forward_flops_per_token(cfg, seq, pairs)


def attention_train_need(cfg, batch, seq):
    """What causal latent attention needs in one training step of `batch`
    sequences over every layer, whatever kernel does it: forward the
    scores (key width) and the values (value width), backward dV and dP
    (value width), dQ and dK (key width), each over the causal half; q, k,
    v, o read or written forward and q, k, v, o, do read and dq, dk, dv
    written backward, each at its own width, in the 2-byte compute type.
    Returns (flops, bytes)."""
    _, H, nope, rope, dv, *_ = _w(cfg)
    dk = nope + rope
    layers = int(cfg["num_hidden_layers"])
    flops = layers * 3 * 2 * batch * seq * seq * 0.5 * H * (dk + dv)
    nbytes = layers * batch * seq * H * (6 * dk + 6 * dv) * 2
    return flops, nbytes


def moe_train_need(cfg, batch, seq, pairs):
    """What the routed experts' products need in one training step for the
    `pairs` held here (summed over the expert layers): forward gate, up and
    down; backward each one's rows' and matrix's gradients (9 products of
    2 x pairs x hidden x expert width). Bytes: each layer's held experts'
    three matrices read forward, read again for the rows' gradient and
    their gradients written (bf16); the rows of every product read and
    written once at their own width, the forward's outputs in float32, the
    rest bf16. `batch` and `seq` are not read: the pairs count the work.
    Returns (flops, bytes)."""
    D, *_, Fe, _ = _w(cfg)
    _, moe = _layers(cfg)
    P = pairs
    G = int(cfg["n_routed_experts"])
    flops = 9 * 2 * P * D * Fe
    weights = moe * 9 * G * D * Fe * 2
    forward = 2 * (P * D * 2 + P * Fe * 4) + P * Fe * 2 + P * D * 4
    rows_grad = 2 * (P * Fe * 2 + P * D * 2) + P * D * 2 + P * Fe * 2
    weights_grad = 3 * (P * D * 2 + P * Fe * 2)
    return flops, weights + forward + rows_grad + weights_grad
