"""Operations and bytes the algorithms need, from a configuration's shapes.

Model FLOPs: a multiply-add is 2 FLOPs; training is 3x the forward pass
(backward with respect to activations and to weights); recomputation (the
flash backward's score recompute, remat) is never counted.
"""

RESNET_STAGES = {18: (2, 2, 2, 2), 34: (3, 4, 6, 3), 50: (3, 4, 6, 3),
                 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def _out(size, kernel, stride, pad):
    return (size + 2 * pad - kernel) // stride + 1


def resnet_convs(cfg):
    """(name, cin, cout, kernel, stride, out_hw) of every convolution of the
    bottleneck ResNet `cfg` describes, in forward order, and the feature
    width entering the classifier. Names are the parameter names."""
    depth, image = int(cfg["depth"]), int(cfg["image_size"])
    if depth < 50:
        raise ValueError("only bottleneck depths (>= 50) are described")
    convs = []
    hw = _out(image, 7, 2, 3)
    convs.append(("conv1", int(cfg["num_channels"]), 64, 7, 2, hw))
    hw = _out(hw, 3, 2, 1)                       # 3x3/2 max pool
    cin, block = 64, 0
    for stage, n_blocks in enumerate(RESNET_STAGES[depth]):
        planes = 64 * 2 ** stage
        for b in range(n_blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            name = f"block.{block}"
            out_hw = _out(hw, 3, stride, 1)
            convs.append((f"{name}.conv1", cin, planes, 1, 1, hw))
            convs.append((f"{name}.conv2", planes, planes, 3, stride,
                          out_hw))
            convs.append((f"{name}.conv3", planes, planes * 4, 1, 1,
                          out_hw))
            if b == 0:
                convs.append((f"{name}.downsample.conv", cin, planes * 4,
                              1, stride, out_hw))
            cin, hw, block = planes * 4, out_hw, block + 1
    return convs, cin


def resnet_forward_flops_per_image(cfg):
    convs, features = resnet_convs(cfg)
    macs = sum(cin * cout * k * k * hw * hw
               for _, cin, cout, k, _, hw in convs)
    macs += features * int(cfg["num_classes"])
    return 2 * macs


def resnet_train_flops_per_image(cfg):
    return 3 * resnet_forward_flops_per_image(cfg)


def lm_matmul_params(cfg):
    """Parameters that multiply a token's activations: the four attention
    projections and the two MLP matrices of every layer, and the output
    head. Embedding lookups multiply nothing."""
    d, layers = int(cfg["n_embd"]), int(cfg["n_layer"])
    ff = int(cfg.get("n_inner") or 4 * d)
    return layers * (4 * d * d + 2 * d * ff) + d * int(cfg["vocab_size"])


def lm_forward_flops_per_token(cfg, context):
    """Forward FLOPs of one token that attends causally within sequences of
    `context` tokens: on average half the context (arithmetic copied from
    bench._lm_train_flops_per_token: scores and values are 4*S*d per layer,
    halved when causal)."""
    d, layers = int(cfg["n_embd"]), int(cfg["n_layer"])
    return 2 * lm_matmul_params(cfg) + layers * 4 * context * d * 0.5


def lm_train_flops_per_token(cfg, seq):
    return 3 * lm_forward_flops_per_token(cfg, seq)


def attention_train_need(cfg, batch, seq):
    """What causal self-attention needs for one training step of `batch`
    sequences, over all layers, whatever kernel does it: forward two matrix
    products (scores, values), backward four (dV, dP, dQ, dK), each over the
    causal half; q, k, v, o read or written once forward (4 tensors), and
    q, k, v, o, do read and dq, dk, dv written backward (8 tensors), in the
    2-byte compute type. Returns (flops, bytes)."""
    d, layers = int(cfg["n_embd"]), int(cfg["n_layer"])
    per_product = 2 * batch * seq * seq * d * 0.5
    flops = layers * 6 * per_product
    nbytes = layers * 12 * batch * seq * d * 2
    return flops, nbytes


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which bound holds."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
