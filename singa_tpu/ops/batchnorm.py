"""Batch normalization with functional running-stat state.

Capability parity with the reference BN operation
(src/model/operation/batchnorm.h:49-115): training mode normalises by batch
statistics and updates the running mean/var "in place" (the reference mutates
the running blocks on device; here the update rebinds the state Tensors'
values, which the Model layer threads through jit as donated state), and
inference mode normalises by the running statistics.

Training mode is written in closed form in both directions, so a step
passes over the activation as rarely as BN's mathematics allows. The
batch statistics are Σx and Σx² in one read (float32 accumulation,
var = max(Σx²/N − mean², 0)); the backward is dβ = Σdy, dγ = Σdy·x̂ in
one read of (x, dy), then dx = γ·inv·(dy − dβ/N − x̂·dγ/N) elementwise —
the same math as cudnnBatchNormalizationBackward. Each pair of sums is
two siblings of one fusion; on the TPU XLA hangs them on the neighbouring
convolution fusion as its epilogue, so no reduction kernel of BN's own is
left in a conv net's step (PERF.md §5).
Inside a data-parallel shard_map step the statistics are the global
batch's: one stacked pmean forward, one stacked psum backward per BN.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..autograd_base import Operator, is_training
from ..tensor import Tensor


class BatchNormHandle:
    """Static BN config (reference BatchNormHandle batchnorm.h:49-73).

    Supports 2D (N, C) and 4D (N, C, H, W) inputs like the reference.
    """

    def __init__(self, momentum, x, eps: float = 1e-5, layout=None):
        from .layout import resolve as _resolve_layout
        self.factor = float(momentum)
        self.layout = _resolve_layout(layout)
        xs = x.shape if hasattr(x, "shape") else tuple(x)
        self.is_2d = len(xs) == 2
        self.channels = int(xs[-1]) \
            if self.layout == "NHWC" and not self.is_2d else int(xs[1])
        self.eps = eps
        self.batchsize = int(xs[0])

    def _axes(self, ndim):
        if ndim == 2:
            return (0,)
        return (0, 1, 2) if self.layout == "NHWC" else (0, 2, 3)

    def _bshape(self, ndim):
        if ndim == 2:
            return (1, self.channels)
        return (1, 1, 1, self.channels) if self.layout == "NHWC" \
            else (1, self.channels, 1, 1)


def _batch_axes():
    """Mesh axes the step's batch is sharded over (empty outside a mesh
    context); imported here because ``parallel`` imports ``layer``."""
    from ..parallel.communicator import active_batch_axes
    return active_batch_axes()


def _global_moments(xb, axes):
    """Batch mean and biased variance in ONE read of the activation: Σx
    and Σx² are sibling float32 reductions of the same operand (XLA
    emits them as one multi-output fusion), mean = Σx/N and
    var = max(Σx²/N − mean², 0) — Flax BatchNorm's default
    (``use_fast_variance``). A bf16 input is accumulated in f32 (the
    cast fuses into the reduction: the "stats stay f32" contract at
    zero cost; a bf16 sum over N·H·W ≈ 1.6M elements at the bench
    shapes would lose most of its mantissa), and a bf16 value's square
    is exact in f32, so what the raw moments lose is f32 summation
    error × (1 + mean²/var): on the TPU's reductions 2e-4 of the
    variance at a mean of 30 standard deviations and 3e-3 at 100
    (PERF.md §6, PR 32; XLA:CPU sums in one accumulator and loses about
    ten times that).

    Inside a shard_map'd step each replica sees only its local batch
    shard; the two raw moments go through ONE stacked pmean over every
    mesh axis the batch is sharded over (identity outside a mesh
    context), so normalisation and the running-stat update use GLOBAL
    batch statistics and, with equal-sized shards, the sharded step is
    numerically a single-device full-batch step (the SPMD-correct form
    of the reference's in-place running stats,
    src/model/operation/batchnorm.h:103-115). The axes come from the
    Model step's declared input batch sharding, NOT a hardcoded 'data'
    (the batch may shard over ('data','expert') or a renamed axis)."""
    x32 = xb.astype(jnp.float32)
    moments = jnp.stack([jnp.mean(x32, axis=axes),
                         jnp.mean(jnp.square(x32), axis=axes)])
    paxes = _batch_axes()
    if paxes:
        moments = jax.lax.pmean(moments, paxes)
    mean, mean_sq = moments
    return mean, jnp.maximum(mean_sq - jnp.square(mean), 0.0)


class _BatchNorm2d(Operator):
    """Training-mode BN over batch stats; grads for (x, scale, bias).

    Forward and backward are both written in closed form, so a training
    step passes over the activation as rarely as BN's mathematics
    allows: one read for the statistics, one elementwise pass for y,
    one read of (x, dy) for the backward's two sums, one elementwise
    pass for dx. (The vjp of a mean-then-deviation forward costs two
    dependent reductions each way: autodiff does not know Σ(x − μ) = 0.)
    After ``forward`` the op holds ``batch_mean`` / ``batch_var`` for
    the wrapper's running-stat update.
    """

    def __init__(self, handle: BatchNormHandle):
        super().__init__()
        self.handle = handle

    def forward(self, x, scale, bias):
        h = self.handle
        bshape = h._bshape(x.ndim)
        mean, var = _global_moments(x, h._axes(x.ndim))
        inv = jax.lax.rsqrt(var + h.eps)
        self.batch_mean, self.batch_var = mean, var
        # residuals: x in its own dtype and per-channel f32 vectors — no
        # activation-sized f32 copy is kept for backward
        self._saved = (x, scale, bias, inv)
        # stats/params stay f32 for stability; activations keep the
        # input's precision class (bf16 nets must not upcast here)
        a = scale.astype(jnp.float32) * inv
        b = bias.astype(jnp.float32) - mean * a
        y = x.astype(jnp.float32) * a.reshape(bshape) + b.reshape(bshape)
        return y.astype(x.dtype)

    def backward(self, dy):
        """dβ = Σdy, dγ = Σdy·x̂, dx = γ·inv·(dy − dβ/N − x̂·dγ/N): the
        math of cudnnBatchNormalizationBackward, one level of
        reductions. Under sync-BN the sums inside dx are the GLOBAL
        batch's (one stacked psum) while the returned dγ, dβ stay this
        shard's — what the vjp of the pmean'd forward gave; the
        optimizer's all-reduce sums them afterwards."""
        h = self.handle
        x, scale, bias, inv = self._saved
        mean = self.batch_mean
        axes, bshape = h._axes(x.ndim), h._bshape(x.ndim)
        dy32 = dy.astype(jnp.float32)
        xhat = (x.astype(jnp.float32) - mean.reshape(bshape)) \
            * inv.reshape(bshape)
        sums = jnp.stack([jnp.sum(dy32, axis=axes),
                          jnp.sum(dy32 * xhat, axis=axes)])
        dbias, dscale = sums
        count = x.size // h.channels
        paxes = _batch_axes()
        if paxes:
            sums = jax.lax.psum(sums, paxes)
            count *= jax.lax.psum(1, paxes)
        m1, m2 = (sums / count).reshape((2,) + bshape)
        k = (scale.astype(jnp.float32) * inv).reshape(bshape)
        dx = k * (dy32 - m1 - xhat * m2)
        return (dx.astype(x.dtype), dscale.astype(scale.dtype),
                dbias.astype(bias.dtype))


class _BatchNorm2dInference(Operator):
    """Inference-mode BN with frozen running stats
    (reference GpuBatchNormForwardInference batchnorm.h:103-115)."""

    def __init__(self, handle: BatchNormHandle):
        super().__init__()
        self.handle = handle

    def forward(self, x, scale, bias, rmean, rvar):
        h = self.handle
        bshape = h._bshape(x.ndim)
        rmean = jax.lax.stop_gradient(rmean)
        rvar = jax.lax.stop_gradient(rvar)
        inv = jax.lax.rsqrt(rvar + h.eps).reshape(bshape)
        y = (x - rmean.reshape(bshape)) * inv * scale.reshape(bshape) \
            + bias.reshape(bshape)
        return y.astype(x.dtype)


def batchnorm_2d(handle: BatchNormHandle, x, scale, bias,
                 running_mean: Tensor, running_var: Tensor,
                 freeze_stats=False):
    """Functional wrapper (parity: reference autograd.batchnorm_2d:1740).

    In training mode the running statistics are updated in place (rebinding
    the state Tensors), exactly mirroring the reference's in-place block
    mutation semantics. ``freeze_stats`` forces the frozen-stats inference
    path even in training (caffe's use_global_stats).
    """
    training = is_training() and not freeze_stats
    if training:
        op, args = _BatchNorm2d(handle), (x, scale, bias)
    else:
        op, args = _BatchNorm2dInference(handle), \
            (x, scale, bias, running_mean, running_var)
    # keep references for ONNX export (BatchNormalization's mean/var inputs)
    op.running_mean, op.running_var = running_mean, running_var
    out = op(*args)
    if training:
        # the moments the op normalised by, computed once: running stats
        # keep their own (f32) dtype under EVERY precision mode — the
        # moments are accumulated f32, and the astype pins the threaded
        # state's dtype so a precision policy (or a stat tensor restored
        # from an older checkpoint) can never flip it mid-training and
        # break step donation
        m = handle.factor
        for running, batch in ((running_mean, op.batch_mean),
                               (running_var, op.batch_var)):
            running.data = (m * running.data.astype(jnp.float32)
                            + (1 - m) * batch).astype(running.data.dtype)
    if not training and not handle.is_2d:
        # tag the frozen-stats output with its folding ingredients: a
        # ReLU consuming it may fuse the whole scale/shift+relu epilogue
        # into one pass over the conv output (ops/fused_epilogue.py —
        # opt-in, traced inference only; the tag itself is one attr)
        out._bn_epilogue = (x, scale, bias, running_mean, running_var,
                            handle.eps, handle.layout)
    return out
