"""Operations and bytes a looped LM (configs/ouro_*.json) needs, from its
shapes, under lib/flops.py's rules: a multiply-add is 2 FLOPs, training is 3x
the forward pass, recomputation (remat, the flash backward's scores) is never
counted.

A token's forward is `applications` layer applications (passes x layers: the
same weights run once a pass) and `exit_heads` output heads (one an exit).
The exit gate's 2 x hidden FLOPs an exit are left out (0.0002 % of a token).
"""


def _dims(cfg):
    return (int(cfg["hidden_size"]),
            int(cfg["num_attention_heads"]) * int(cfg["head_dim"]),
            int(cfg["intermediate_size"]), int(cfg["vocab_size"]),
            int(cfg["num_hidden_layers"]), int(cfg["total_ut_steps"]))


def layer_matmul_params(cfg):
    """Parameters of one layer that multiply a token's activations: the
    four attention projections and the three SwiGLU matrices."""
    d, inner, ff, _, _, _ = _dims(cfg)
    return 4 * d * inner + 3 * d * ff


def layer_params(cfg):
    """All of one layer's parameters: with the q, k, v biases and the four
    norm scales."""
    d, inner, _, _, _, _ = _dims(cfg)
    return layer_matmul_params(cfg) + 3 * inner + 4 * d


def param_count(cfg):
    """The model's parameters: embedding, layers, loop norm, head, gate."""
    d, _, _, V, L, _ = _dims(cfg)
    return V * d + L * layer_params(cfg) + d + d * V + d + 1


def forward_flops_per_token(cfg, context, applications=None,
                            exit_heads=None):
    """Forward FLOPs of one token attending causally within sequences of
    `context` tokens (on average half the context): each layer application
    2 x its matrix parameters + 4 x context x width / 2, each exit head 2 x
    hidden x vocabulary. By default the configuration's passes x layers
    applications and one head a pass."""
    d, inner, _, V, L, T = _dims(cfg)
    apps = T * L if applications is None else applications
    heads = T if exit_heads is None else exit_heads
    return apps * (2 * layer_matmul_params(cfg) + 4 * context * inner * 0.5) \
        + heads * 2 * d * V


def train_flops_per_token(cfg, seq, applications=None, exit_heads=None):
    return 3 * forward_flops_per_token(cfg, seq, applications, exit_heads)


def attention_train_need(cfg, batch, seq, applications=None):
    """What causal self-attention needs in one training step of `batch`
    sequences, over every layer application, whatever kernel does it:
    forward two matrix products, backward four, each over the causal half;
    q, k, v, o read or written once forward and q, k, v, o, do read and dq,
    dk, dv written backward (12 tensors), in the 2-byte compute type.
    Returns (flops, bytes)."""
    _, inner, _, _, L, T = _dims(cfg)
    apps = T * L if applications is None else applications
    flops = apps * 6 * 2 * batch * seq * seq * inner * 0.5
    nbytes = apps * 12 * batch * seq * inner * 2
    return flops, nbytes
