"""ResNet (He et al. 2015, arXiv:1512.03385, table 1, bottleneck columns;
the stride sits on the 3x3 convolution, as in the program and torchvision)
in plain float32: forward pass in training mode (batch statistics) and the
mean softmax cross-entropy. NCHW activations, OIHW weights. The model is a
chain of stages (chain.py): the stem, one stage a bottleneck, the head.

Left out, because no compared number depends on it: the running mean and
variance that batch normalisation keeps for inference.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from .. import flops
from . import lowprec

BN_EPS = 1e-5
HIGHEST = lax.Precision.HIGHEST


def param_specs(cfg):
    """[(name, shape, mean, std)] of every trainable leaf, named as the
    program names its state (without the model's own prefix)."""
    convs, features = flops.resnet_convs(cfg)
    classes = int(cfg["num_classes"])
    specs = []
    for name, cin, cout, k, _, _ in convs:
        std = math.sqrt(2.0 / (cin * k * k + cout))
        specs.append((f"{name}.W", (cout, cin, k, k), 0.0, std))
        bn = name.replace("conv", "bn") if "downsample" not in name \
            else name.replace(".conv", ".bn")
        last = name.endswith(".conv3")
        specs.append((f"{bn}.scale", (cout,),
                      float(cfg.get("residual_bn_scale", 1.0)) if last
                      else 1.0, 0.05 * (float(cfg.get("residual_bn_scale",
                                                      1.0)) if last else 1.0)))
        specs.append((f"{bn}.bias", (cout,), 0.0, 0.05))
    specs.append(("fc.W", (features, classes), 0.0,
                  math.sqrt(2.0 / (features + classes))))
    specs.append(("fc.b", (classes,), 0.0, 0.05))
    return specs


def _conv(x, w, stride, pad, cast):
    if cast is not None:
        x, w = cast(x), cast(w)
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)


def _bn(x, scale, bias):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    return (x - mean) * lax.rsqrt(var + BN_EPS) * scale[None, :, None, None] \
        + bias[None, :, None, None]


def _stem(p, x, cast):
    cast = lowprec.CASTS[cast]
    x = jax.nn.relu(_bn(_conv(x, p["conv1.W"], 2, 3, cast),
                        p["bn1.scale"], p["bn1.bias"]))
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                             (1, 1, 2, 2), ((0, 0), (0, 0), (1, 1), (1, 1)))


def _bottleneck(p, x, stride, has_down, cast):
    cast = lowprec.CASTS[cast]

    def cb(x, conv, bn, s, pad):
        return _bn(_conv(x, p[f"{conv}.W"], s, pad, cast),
                   p[f"{bn}.scale"], p[f"{bn}.bias"])
    out = jax.nn.relu(cb(x, "conv1", "bn1", 1, 0))
    out = jax.nn.relu(cb(out, "conv2", "bn2", stride, 1))
    out = cb(out, "conv3", "bn3", 1, 0)
    if has_down:
        x = cb(x, "downsample.conv", "downsample.bn", stride, 0)
    return jax.nn.relu(out + x)


def _head(p, x, labels, cast):
    """Global average pool, classifier, mean softmax cross-entropy against
    integer labels."""
    cast = lowprec.CASTS[cast]
    x = jnp.mean(x, axis=(2, 3))
    w = p["fc.W"]
    if cast is not None:
        x, w = cast(x), cast(w)
    logits = jnp.matmul(x, w, precision=HIGHEST) + p["fc.b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def stages(cfg, cast="float32"):
    """The chain: [(fn, {local name: model name}, statics)]. It takes a
    batch (images (B,3,H,W) float32, integer labels (B,))."""
    cast_s = (("cast", cast),)
    out = [(_stem, {k: k for k in ("conv1.W", "bn1.scale", "bn1.bias")},
            cast_s)]
    block = 0
    for stage, n_blocks in enumerate(flops.RESNET_STAGES[int(cfg["depth"])]):
        for b in range(n_blocks):
            local = [f"{c}.W" for c in ("conv1", "conv2", "conv3")] + \
                [f"{n}.{s}" for n in ("bn1", "bn2", "bn3")
                 for s in ("scale", "bias")]
            if b == 0:
                local += ["downsample.conv.W", "downsample.bn.scale",
                          "downsample.bn.bias"]
            out.append((_bottleneck,
                        {k: f"block.{block}.{k}" for k in local},
                        (("stride", 2 if (stage > 0 and b == 0) else 1),
                         ("has_down", b == 0)) + cast_s))
            block += 1
    out.append((_head, {"fc.W": "fc.W", "fc.b": "fc.b"}, cast_s))
    return out
