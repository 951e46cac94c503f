"""Fused conv epilogue: inference BN scale/shift + ReLU in one pass.

At inference/serving the BN that follows a conv is a frozen per-channel
affine: ``y = x * scale' + shift'`` with ``scale' = scale *
rsqrt(running_var + eps)`` and ``shift' = bias - running_mean * scale'``
— the folding math stays f32 (the mixed-precision contract for norm
statistics) and only the final elementwise pass touches the activation
dtype. The Pallas kernel applies that affine AND the ReLU that follows
in ONE HBM pass over the conv output, instead of BN and ReLU each
re-reading the full activation. The RESIDUAL tail
(conv→BN→add→ReLU — every ResNet block's exit) fuses the same way:
``autograd.add`` tags a sum whose operand is a tagged BN output, and
the consuming ReLU emits the scale/shift + skip-add + relu as one
pass (two full-size tiles per block, so the VMEM budget halves the
row block).

Wiring is a peephole, not a graph rewrite: the inference BN op tags its
output Tensor with the folding ingredients (``ops/batchnorm.py``), and
``autograd.relu`` — when the module is :func:`enabled`, the pass is
traced (serving programs, compiled eval; eager eval skips it so nothing
computes twice), training is off, and the kernel-eligibility gate
accepts — consumes the tag and emits the fused kernel on the conv
output directly. Everything else falls through to the reference ops.

House pattern as ``ops/attention.py``/``ops/fused_optim.py``:
``FORCE_PALLAS_INTERPRET`` runs the exact kernel on CPU for the
``pallas`` CI tier; OFF by default (``enable`` / ``enabled_scope``,
bench's ``BENCH_CONV_EPILOGUE`` pin) until a chip A/B says otherwise
(ROADMAP.md D3).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import fused_optim

_ENABLED = False


def enable(on=True):
    """Process-wide opt-in (bench/serving set it from the measured A/B
    winner; never on by default). Returns the previous value."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(on)
    return prev


@contextlib.contextmanager
def enabled_scope(on=True):
    prev = enable(on)
    try:
        yield
    finally:
        enable(prev)


def enabled():
    return _ENABLED


def _interpret():
    return fused_optim.FORCE_PALLAS_INTERPRET or \
        jax.default_backend() != "tpu"


def _available(n_elems):
    # one eligibility policy for every fused kernel (backend, force-
    # reference scope, interpret hook, min size) — fused_optim owns it
    return fused_optim.available(n_elems)


def _affine_relu_cols_kernel(x_ref, s_ref, b_ref, o_ref):
    """Channels-last rows: scale/shift broadcast over rows."""
    y = x_ref[...].astype(jnp.float32) * s_ref[...] + b_ref[...]
    o_ref[...] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


def _affine_relu_rows_kernel(x_ref, s_ref, b_ref, o_ref):
    """Channel-per-row (NCHW collapsed to (N*C, H*W)): scale/shift are
    per-row columns."""
    y = x_ref[...].astype(jnp.float32) * s_ref[...] + b_ref[...]
    o_ref[...] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


def _affine_add_relu_cols_kernel(x_ref, r_ref, s_ref, b_ref, o_ref):
    """Residual tail, channels-last: scale/shift + residual add + relu
    in the one pass."""
    y = x_ref[...].astype(jnp.float32) * s_ref[...] + b_ref[...] \
        + r_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


def _affine_add_relu_rows_kernel(x_ref, r_ref, s_ref, b_ref, o_ref):
    """Residual tail, channel-per-row (NCHW collapsed)."""
    y = x_ref[...].astype(jnp.float32) * s_ref[...] + b_ref[...] \
        + r_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


# per-block VMEM budget: input + output tiles must fit comfortably in
# the ~16 MB of VMEM alongside scratch; 4 MB for the input block keeps
# the pair under half of it
_BLOCK_BYTE_BUDGET = 4 << 20


def _block_rows(rows, row_elems, itemsize=4, n_inputs=1):
    """Largest row-block that tiles ``rows`` AND fits the VMEM budget
    (a (32, 64, 112, 112) NCHW activation has 12544-element rows — an
    uncapped 256-row block would be 12.8 MB and fail Mosaic on real
    hardware even though interpret-mode CI accepts it). ``n_inputs``
    counts the FULL-SIZE input tiles resident at once (2 for the
    residual-tail kernel: activation + residual), so the budget stays
    honest when the kernel reads two big arrays. None when even the
    minimum legal block exceeds the budget — the caller falls back to
    the reference elementwise math."""
    for b in (256, 128, 64, 32, 16, 8):
        if rows % b == 0 and n_inputs * b * row_elems * itemsize <= \
                _BLOCK_BYTE_BUDGET:
            return b
    return None


def _pad_axis0(arr, rows):
    pad = rows - arr.shape[0]
    if pad:
        arr = jnp.concatenate(
            [arr, jnp.zeros((pad,) + arr.shape[1:], arr.dtype)])
    return arr


def _reference(x, scale, shift, layout, residual=None):
    b = (1, x.shape[1], 1, 1) if layout == "NCHW" \
        else (1, 1, 1, x.shape[-1])
    y = x.astype(jnp.float32) * scale.reshape(b) + shift.reshape(b)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return jnp.maximum(y, 0.0).astype(x.dtype)


def _scale_shift_relu_impl(x, scale, shift, layout, residual):
    """One tiling for both tails: ``max(x*s + b [+ residual], 0)`` in a
    single Pallas pass. ``residual`` (same shape as ``x``) turns the
    plain affine+relu into the conv→BN→add→ReLU residual tail; the
    VMEM budget then accounts for TWO full-size tiles per block."""
    N = x.shape[0]
    scale = jnp.asarray(scale, jnp.float32)
    shift = jnp.asarray(shift, jnp.float32)
    n_inputs = 1 if residual is None else 2
    if layout == "NHWC":
        C = x.shape[-1]
        m = x.size // C
        rows = -(-m // 8) * 8
        br = _block_rows(rows, C, x.dtype.itemsize, n_inputs)
        if br is None:
            return _reference(x, scale, shift, layout, residual)
        # a custom call cost analysis can't count — the step_flops
        # reference twin keys off this mark, same as the optimizer
        # kernels
        fused_optim._mark("epilogue")
        xr = _pad_axis0(x.reshape(m, C), rows)
        blk = pl.BlockSpec((br, C), lambda i: (i, 0))
        vec = pl.BlockSpec((1, C), lambda i: (0, 0))
        args = [xr]
        specs = [blk]
        kernel = _affine_relu_cols_kernel
        if residual is not None:
            args.append(_pad_axis0(residual.reshape(m, C), rows))
            specs.append(blk)
            kernel = _affine_add_relu_cols_kernel
        out = pl.pallas_call(
            kernel,
            grid=(rows // br,),
            in_specs=specs + [vec, vec],
            out_specs=blk,
            out_shape=jax.ShapeDtypeStruct((rows, C), x.dtype),
            interpret=_interpret(),
            name="conv_epilogue_nhwc",
        )(*args, scale.reshape(1, C), shift.reshape(1, C))
        return out[:m].reshape(x.shape)
    # NCHW: collapse to one row per (image, channel); the per-row
    # scale/shift columns are a tiny (N*C, 1) tile
    C = x.shape[1]
    L = x.size // (N * C)
    rows = -(-(N * C) // 8) * 8
    br = _block_rows(rows, L, x.dtype.itemsize, n_inputs)
    if br is None:
        return _reference(x, scale, shift, layout, residual)
    fused_optim._mark("epilogue")
    xr = _pad_axis0(x.reshape(N * C, L), rows)
    s_rows = _pad_axis0(jnp.tile(scale, N).reshape(N * C, 1), rows)
    b_rows = _pad_axis0(jnp.tile(shift, N).reshape(N * C, 1), rows)
    blk = pl.BlockSpec((br, L), lambda i: (i, 0))
    vec = pl.BlockSpec((br, 1), lambda i: (i, 0))
    args = [xr]
    specs = [blk]
    kernel = _affine_relu_rows_kernel
    if residual is not None:
        args.append(_pad_axis0(residual.reshape(N * C, L), rows))
        specs.append(blk)
        kernel = _affine_add_relu_rows_kernel
    out = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=specs + [vec, vec],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((rows, L), x.dtype),
        interpret=_interpret(),
        name="conv_epilogue_nchw",
    )(*args, s_rows, b_rows)
    return out[:N * C].reshape(x.shape)


def scale_shift_relu(x, scale, shift, layout="NCHW"):
    """``max(x * scale + shift, 0)`` with per-channel f32 scale/shift in
    one Pallas pass over a 4-D activation. ``layout`` names where the
    channel axis lives. Returns an array of x's shape/dtype. Shapes
    whose minimum legal block would blow the VMEM budget compute the
    same math with plain XLA ops instead."""
    return _scale_shift_relu_impl(x, scale, shift, layout, None)


def scale_shift_add_relu(x, scale, shift, residual, layout="NCHW"):
    """The residual tail: ``max(x * scale + shift + residual, 0)`` in
    ONE pass over the conv output — BN fold, skip-connection add, and
    ReLU without re-reading the activation three times. ``residual``
    must match ``x``'s shape; same decline-to-reference rules as
    :func:`scale_shift_relu` (the block budget counts both tiles)."""
    if tuple(residual.shape) != tuple(x.shape):
        return _reference(x, jnp.asarray(scale, jnp.float32),
                          jnp.asarray(shift, jnp.float32), layout,
                          residual)
    return _scale_shift_relu_impl(x, scale, shift, layout, residual)


def fold_bn(scale, bias, rmean, rvar, eps):
    """Frozen-BN folding in f32 (the norm-statistics precision
    contract): returns per-channel ``(scale', shift')`` such that
    ``bn(x) == x * scale' + shift'``."""
    scale = jnp.asarray(scale, jnp.float32)
    inv = jax.lax.rsqrt(jnp.asarray(rvar, jnp.float32) + eps)
    s2 = scale * inv
    b2 = jnp.asarray(bias, jnp.float32) \
        - jnp.asarray(rmean, jnp.float32) * s2
    return s2, b2


def try_relu_epilogue(x_tensor):
    """ReLU peephole: when ``x_tensor`` is a tagged inference-BN output
    — or a tagged ``bn_out + residual`` sum (the conv→BN→add→ReLU
    residual tail, ``autograd.add`` sets the tag) — and the fused
    epilogue is both enabled and eligible, return the tail computed by
    the one-pass kernel on the BN's INPUT (+ the residual); else None
    (caller runs the reference ReLU op). Only fires inside a trace —
    in eager evaluation the BN output already exists concretely, so
    recomputing it fused would double the work; under a jit the
    reference BN/add outputs this peephole bypasses are dead code XLA
    eliminates."""
    residual = None
    tag = getattr(x_tensor, "_bn_epilogue", None)
    if tag is None:
        add_tag = getattr(x_tensor, "_bn_add_epilogue", None)
        if add_tag is None:
            return None
        tag, residual = add_tag
    if not _ENABLED:
        return None
    from ..autograd_base import is_training
    if is_training():
        # a frozen-stats BN (use_global_stats) still BACKPROPS through
        # scale/bias in training — and the residual branch backprops
        # too; the fused output carries no tape creator, so fusing
        # here would silently drop those gradients
        return None
    xin, scale, bias, rmean, rvar, eps, layout = tag
    arr = getattr(xin, "data", xin)
    if arr.ndim != 4 or not _available(arr.size):
        return None
    if not isinstance(arr, jax.core.Tracer):
        return None
    res_arr = None
    if residual is not None:
        res_arr = getattr(residual, "data", residual)
        if tuple(res_arr.shape) != tuple(arr.shape):
            # a broadcasting skip-connection is not the tail this
            # kernel fuses — decline to the reference add+relu
            return None
    s2, b2 = fold_bn(getattr(scale, "data", scale),
                     getattr(bias, "data", bias),
                     getattr(rmean, "data", rmean),
                     getattr(rvar, "data", rvar), eps)
    from ..tensor import Tensor
    if res_arr is not None:
        out = scale_shift_add_relu(arr, s2, b2, res_arr, layout=layout)
    else:
        out = scale_shift_relu(arr, s2, b2, layout=layout)
    return Tensor(data=out, device=getattr(x_tensor, "device", None),
                  requires_grad=False)
