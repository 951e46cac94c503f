"""Misc helpers: progress bar, ONNX-style padding math, tape walking.

Capability parity with the reference utils (python/singa/utils.py): the
``update_progress`` console bar, odd/SAME padding helpers used by
Conv/Pool layers for ONNX ``auto_pad`` semantics, and a post-order tape
traversal. The odd-pad forward/backward pair is unnecessary here — our
conv/pool handles take explicit ((top, bottom), (left, right)) pad pairs
and XLA differentiates through them — so ``handle_odd_pad_fwd`` reduces to
a plain asymmetric pad.
"""

from __future__ import annotations

import sys

import jax.numpy as jnp

from .tensor import Tensor


def update_progress(progress: float, info: str = "") -> None:
    """Render a textual progress bar (reference utils.update_progress:27).

    progress in [0, 1]; 1.0 appends Done.
    """
    length = 20
    progress = max(0.0, min(1.0, float(progress)))
    filled = int(round(length * progress))
    bar = "#" * filled + "-" * (length - filled)
    status = " Done." if progress >= 1.0 else ""
    sys.stdout.write(f"\r[{bar}] {progress * 100:3.1f}% {info}{status}")
    sys.stdout.flush()
    if progress >= 1.0:
        sys.stdout.write("\n")


def get_padding_shape(pad_mode, input_spatial_shape, kernel_spatial_shape,
                      strides_spatial):
    """ONNX auto_pad ('SAME_UPPER'/'SAME_LOWER') -> per-dim (begin, end)
    pads (reference utils.get_padding_shape:159)."""
    pads = []
    for i, (d, k, s) in enumerate(zip(input_spatial_shape,
                                      kernel_spatial_shape,
                                      strides_spatial)):
        out = (d + s - 1) // s
        total = max(0, (out - 1) * s + k - d)
        small, big = total // 2, total - total // 2
        if pad_mode == "SAME_LOWER":
            pads.append((big, small))
        else:  # SAME_UPPER
            pads.append((small, big))
    return pads


def get_output_shape(auto_pad, input_spatial_shape, kernel_spatial_shape,
                     strides_spatial):
    """Spatial output shape under an ONNX auto_pad mode
    (reference utils.get_output_shape:189)."""
    if auto_pad in ("SAME_UPPER", "SAME_LOWER"):
        return [(d + s - 1) // s
                for d, s in zip(input_spatial_shape, strides_spatial)]
    if auto_pad == "VALID":
        return [(d - k) // s + 1
                for d, k, s in zip(input_spatial_shape,
                                   kernel_spatial_shape, strides_spatial)]
    raise ValueError(f"unsupported auto_pad {auto_pad}")


def handle_odd_pad_fwd(x, odd_padding, is_pool=False):
    """Apply an asymmetric (top, bottom, left, right) pad to NCHW data
    (reference utils.handle_odd_pad_fwd:56). Tensor inputs go through the
    taped Pad op so gradients flow; the reference's explicit backward twin
    (handle_odd_pad_bwd) is therefore unnecessary."""
    t, b, l, r = odd_padding
    fill = float("-inf") if is_pool else 0.0
    if isinstance(x, Tensor):
        from . import autograd
        # pads layout: begin per dim, then end per dim (N,C,H,W)
        return autograd.pad(x, "constant", [0, 0, t, l, 0, 0, b, r], fill)
    return jnp.pad(jnp.asarray(x), ((0, 0), (0, 0), (t, b), (l, r)),
                   constant_values=fill)


def same_pad_shape_check(handle, pad_mode, x):
    """Validate that the handle's explicit pads equal the auto_pad-derived
    ones (reference utils.same_pad_shape_check:110).

    ConvHandle stores ((t, b), (l, r)) pairs in ``padding``; PoolingHandle
    exposes the same as ``pad_pairs``.
    """
    spatial = x.shape[2:]
    expect = get_padding_shape(pad_mode, spatial, handle.kernel_size,
                               handle.stride)
    got = getattr(handle, "pad_pairs", None)
    if got is None:
        got = handle.padding  # ConvHandle: already pair-of-pairs
    return tuple(map(tuple, got)) == tuple(map(tuple, expect))


def force_unicode(s):
    """bytes -> str passthrough (reference utils.force_unicode:219)."""
    if isinstance(s, bytes):
        return s.decode("utf-8", errors="replace")
    return str(s)


def post_order_recursive(root, visit):
    """Post-order walk over a tape from a root op, calling ``visit(op)``
    per op (reference utils.post_order_recursive:234). Iterative, so deep
    tapes don't hit the recursion limit."""
    seen = set()
    stack = [(root, False)]
    while stack:
        op, expanded = stack.pop()
        if op is None:
            continue
        if expanded:
            visit(op)
            continue
        if id(op) in seen:
            continue
        seen.add(id(op))
        stack.append((op, True))
        for (src_op, _x, _t, _r) in getattr(op, "src", []):
            if src_op is not None and id(src_op) not in seen:
                stack.append((src_op, False))
