"""Define-by-run autograd engine + the ~120-op surface, on XLA.

Capability parity with the reference engine (python/singa/autograd.py):

- ``Operator._do_forward`` records ``src`` links exactly like
  autograd.py:270-314;
- ``infer_dependency`` ref-counts the upstream graph (autograd.py:71-102);
- ``backward(y, dy)`` is a lazy generator yielding ``(param, grad)`` in
  reverse-topological order (autograd.py:128-224) so optimizers can overlap
  update (and, distributed, all-reduce) with the rest of backward.

TPU-first redesign: every ``forward`` is a pure ``jax.numpy`` function, so a
whole train step (forward + this tape + optimizer) traces under ``jax.jit``
into one XLA computation — the reference's buffered C++ Graph
(src/core/scheduler/scheduler.cc) becomes XLA scheduling/fusion for free.
Backward rules default to ``jax.vjp`` of the op's own forward, which is both
exactly consistent with forward and XLA-fused; ops override ``backward`` only
when vjp semantics are not what the reference specifies.
"""

from __future__ import annotations

import sys
import types

import numpy as np
import jax
import jax.numpy as jnp

from .tensor import Tensor
from .autograd_base import (CTX, Operator, Dummy, backward, gradients,
                            infer_dependency, is_training, set_training,
                            _raw)
# ops cast compute operands / upcast fragile reductions through the ONE
# precision-contract module (f32-accumulate discipline lives there)
from .mixed_precision import cast_compute as _cast_compute
from .mixed_precision import accum_f32 as _f32a


class _AutogradModule(types.ModuleType):
    """Lets reference-style ``autograd.training = True`` toggle the shared
    engine context (CTX) that ops and the Model layer consult."""

    @property
    def training(self):
        return CTX.training

    @training.setter
    def training(self, flag):
        CTX.training = bool(flag)


sys.modules[__name__].__class__ = _AutogradModule


# ===========================================================================
# Op library. Classes mirror reference names; snake_case functional wrappers
# below. Forward bodies are jax.numpy; backwards default to vjp.
# ===========================================================================

# ---- arithmetic -----------------------------------------------------------

class Add(Operator):
    def forward(self, a, b):
        return a + b


class Sub(Operator):
    def forward(self, a, b):
        return a - b


class Mul(Operator):
    def forward(self, a, b):
        return a * b


class Div(Operator):
    def forward(self, a, b):
        return a / b


class Pow(Operator):
    def forward(self, a, b):
        return a ** b


class Negative(Operator):
    def forward(self, x):
        return -x


class Reciprocal(Operator):
    def forward(self, x):
        return 1.0 / x


class AddBias(Operator):
    """y = x + b broadcast along an axis (reference autograd.AddBias)."""

    def __init__(self, axis=0):
        super().__init__()
        self.axis = axis

    def forward(self, x, b):
        # policy discipline: a bias is not numerically fragile — under a
        # 16-bit policy it joins the activation's precision instead of
        # silently upcasting the whole activation back to its own
        x, b = _cast_compute(x, b)
        if self.axis == 0:
            return x + b.reshape((1,) + b.shape)
        return x + b.reshape(b.shape + (1,) * (x.ndim - 1 - self.axis))


class Matmul(Operator):
    def forward(self, a, b):
        # under an active precision policy both operands enter the MXU
        # in the compute dtype (fp32 masters are cast at the use site;
        # the vjp casts the weight gradient back up automatically)
        a, b = _cast_compute(a, b)
        return jnp.matmul(a, b)


class Gemm(Operator):
    """alpha*A'@B' + beta*C (reference autograd.Gemm, onnx Gemm)."""

    def __init__(self, alpha=1.0, beta=1.0, transA=0, transB=0):
        super().__init__()
        self.alpha, self.beta = alpha, beta
        self.transA, self.transB = transA, transB

    def forward(self, A, B, C=None):
        A, B, C = _cast_compute(A, B, C)
        a = A.T if self.transA else A
        b = B.T if self.transB else B
        y = self.alpha * (a @ b)
        if C is not None:
            y = y + self.beta * C
        return y


class Sum(Operator):
    """Elementwise sum of N tensors (reference autograd.Sum)."""

    def forward(self, *xs):
        out = xs[0]
        for x in xs[1:]:
            out = out + x
        return out


# ---- unary math -----------------------------------------------------------

def _unary_op(name, fn):
    return type(name, (Operator,), {"forward": staticmethod(fn)})


Abs = _unary_op("Abs", jnp.abs)
Exp = _unary_op("Exp", jnp.exp)
Log = _unary_op("Log", jnp.log)
Sqrt = _unary_op("Sqrt", jnp.sqrt)
Sin = _unary_op("Sin", jnp.sin)
Cos = _unary_op("Cos", jnp.cos)
Tan = _unary_op("Tan", jnp.tan)
Sinh = _unary_op("Sinh", jnp.sinh)
Cosh = _unary_op("Cosh", jnp.cosh)
Asin = _unary_op("Asin", jnp.arcsin)
Acos = _unary_op("Acos", jnp.arccos)
Atan = _unary_op("Atan", jnp.arctan)
Asinh = _unary_op("Asinh", jnp.arcsinh)
Acosh = _unary_op("Acosh", jnp.arccosh)
Atanh = _unary_op("Atanh", jnp.arctanh)
Tanh = _unary_op("Tanh", jnp.tanh)
Erf = _unary_op("Erf", jax.scipy.special.erf)


class Ceil(Operator):
    differentiable = True

    def forward(self, x):
        return jnp.ceil(x)

    def backward(self, dy):
        return jnp.zeros_like(dy)


class Floor(Operator):
    def forward(self, x):
        return jnp.floor(x)

    def backward(self, dy):
        return jnp.zeros_like(dy)


class Round(Operator):
    def forward(self, x):
        return jnp.trunc(x + jnp.sign(x) * 0.5)  # round-half-away like ref

    def backward(self, dy):
        return jnp.zeros_like(dy)


class Rounde(Operator):
    """Round half to even (reference autograd.Rounde)."""

    def forward(self, x):
        return jnp.round(x)

    def backward(self, dy):
        return jnp.zeros_like(dy)


class Sign(Operator):
    def forward(self, x):
        return jnp.sign(x)

    def backward(self, dy):
        return jnp.zeros_like(dy)


# ---- activations ----------------------------------------------------------

class ReLU(Operator):
    def forward(self, x):
        return jnp.maximum(x, 0)


class LeakyRelu(Operator):
    def __init__(self, a=0.01):
        super().__init__()
        self.a = a

    def forward(self, x):
        return jnp.where(x >= 0, x, self.a * x)


class Elu(Operator):
    def __init__(self, alpha=1.0):
        super().__init__()
        self.alpha = alpha

    def forward(self, x):
        return jnp.where(x > 0, x, self.alpha * (jnp.exp(jnp.minimum(x, 0)) - 1))


class SeLU(Operator):
    def __init__(self, alpha=1.67326, gamma=1.0507):
        super().__init__()
        self.alpha, self.gamma = alpha, gamma

    def forward(self, x):
        return self.gamma * jnp.where(
            x > 0, x, self.alpha * (jnp.exp(jnp.minimum(x, 0)) - 1))


class Sigmoid(Operator):
    def forward(self, x):
        return jax.nn.sigmoid(x)


class SoftPlus(Operator):
    def forward(self, x):
        return jax.nn.softplus(x)


class SoftSign(Operator):
    def forward(self, x):
        return x / (1 + jnp.abs(x))


class HardSigmoid(Operator):
    def __init__(self, alpha=0.2, gamma=0.5):
        super().__init__()
        self.alpha, self.gamma = alpha, gamma

    def forward(self, x):
        return jnp.clip(self.alpha * x + self.gamma, 0.0, 1.0)


class PRelu(Operator):
    def forward(self, x, slope):
        return jnp.where(x >= 0, x, slope * x)


class SoftMax(Operator):
    def __init__(self, axis=1):
        super().__init__()
        self.axis = axis

    def forward(self, x):
        # logsumexp accumulation stays f32 for 16-bit inputs (an 8-bit
        # mantissa sum over a wide axis loses the tail); the activation
        # keeps its precision class
        return jax.nn.softmax(_f32a(x), axis=self.axis).astype(x.dtype)


class GELU(Operator):
    """TPU extension (used by transformer models; not in reference op set)."""

    def forward(self, x):
        return jax.nn.gelu(x)


class LRN(Operator):
    """Across-channel local response normalisation on NCHW
    (reference src/model/layer/lrn.cc; AlexNet-era caffe semantics):
    y = x / (k + alpha/n * sum_{window n}(x^2))^beta."""

    def __init__(self, size=5, alpha=1e-4, beta=0.75, k=1.0):
        super().__init__()
        self.size = int(size)
        self.alpha = float(alpha)
        self.beta = float(beta)
        self.k = float(k)

    def forward(self, x):
        half = self.size // 2
        win = jax.lax.reduce_window(
            x * x, 0.0, jax.lax.add,
            window_dimensions=(1, self.size, 1, 1),
            window_strides=(1, 1, 1, 1),
            padding=((0, 0), (half, self.size - 1 - half), (0, 0), (0, 0)))
        return x * jnp.power(self.k + self.alpha / self.size * win,
                             -self.beta)


def lrn(x, size=5, alpha=1e-4, beta=0.75, k=1.0):
    return LRN(size, alpha, beta, k)(x)


# ---- losses ---------------------------------------------------------------

class CrossEntropy(Operator):
    """-mean(sum(t * log(p))) with probabilities input
    (reference autograd.py cross_entropy:1212)."""

    def forward(self, x, t):
        t = jax.lax.stop_gradient(t)
        eps = 1e-10
        batch = x.shape[0]
        # loss reduction in f32 regardless of the net's compute dtype
        x, t = _f32a(x), _f32a(t)
        return -jnp.sum(t * jnp.log(x + eps)) / batch


class SoftMaxCrossEntropy(Operator):
    """Fused softmax + CE over logits (reference softmax_cross_entropy:1306).

    Targets may be one-hot (same shape) or integer class ids.
    """

    def forward(self, x, t):
        t = jax.lax.stop_gradient(t)
        # logsumexp + mean in f32: the fragile-op contract of 16-bit
        # policies (and of the plain bf16 input path)
        x = _f32a(x)
        logp = jax.nn.log_softmax(x, axis=-1)
        if t.shape == x.shape:
            ce = -jnp.sum(t * logp, axis=-1)
        else:
            tt = t.reshape(t.shape[0:1]) if t.ndim > 1 else t
            ce = -jnp.take_along_axis(
                logp, tt.astype(jnp.int32)[:, None], axis=-1)[:, 0]
        return jnp.mean(ce)


class MeanSquareError(Operator):
    """0.5 * mean over batch of ||x-t||^2 (reference mse_loss:1334)."""

    def forward(self, x, t):
        t = jax.lax.stop_gradient(t)
        batch = x.shape[0]
        return jnp.sum(jnp.square(_f32a(x) - _f32a(t))) / (2.0 * batch)


class BinaryCrossEntropy(Operator):
    def forward(self, x, t):
        t = jax.lax.stop_gradient(t)
        eps = 1e-10
        x, t = _f32a(x), _f32a(t)
        per = -(t * jnp.log(x + eps) + (1 - t) * jnp.log(1 - x + eps))
        return jnp.mean(jnp.sum(per.reshape(per.shape[0], -1), axis=-1))


class RankingLoss(Operator):
    """Margin ranking loss over (pos, neg) scores (reference
    ranking_loss:1266)."""

    def __init__(self, M=0.2):
        super().__init__()
        self.M = M

    def forward(self, pos, neg):
        return jnp.mean(jnp.maximum(self.M - (pos - neg), 0.0))


# ---- reductions / comparisons ---------------------------------------------

class ReduceSum(Operator):
    def __init__(self, axes=None, keepdims=1):
        super().__init__()
        self.axes = tuple(axes) if axes is not None else None
        self.keepdims = bool(keepdims)

    def forward(self, x):
        return jnp.sum(x, axis=self.axes, keepdims=self.keepdims)


class ReduceMean(Operator):
    def __init__(self, axes=None, keepdims=1):
        super().__init__()
        self.axes = tuple(axes) if axes is not None else None
        self.keepdims = bool(keepdims)

    def forward(self, x):
        return jnp.mean(x, axis=self.axes, keepdims=self.keepdims)


class ReduceMax(Operator):
    def __init__(self, axes=None, keepdims=1):
        super().__init__()
        self.axes = tuple(axes) if axes is not None else None
        self.keepdims = bool(keepdims)

    def forward(self, x):
        return jnp.max(x, axis=self.axes, keepdims=self.keepdims)


class ReduceProd(Operator):
    """Product reduction (ONNX ReduceProd — the reference reaches it only
    through its ONNX backend; no composition of sum/log covers negative
    or zero values, so it is a first-class op with a vjp backward)."""

    def __init__(self, axes=None, keepdims=1):
        super().__init__()
        self.axes = tuple(axes) if axes is not None else None
        self.keepdims = bool(keepdims)

    def forward(self, x):
        return jnp.prod(x, axis=self.axes, keepdims=self.keepdims)


class Mean(Operator):
    """Elementwise mean of N tensors (reference autograd.Mean)."""

    def forward(self, *xs):
        out = xs[0]
        for x in xs[1:]:
            out = out + x
        return out / len(xs)


class Max(Operator):
    def forward(self, *xs):
        out = xs[0]
        for x in xs[1:]:
            out = jnp.maximum(out, x)
        return out


class Min(Operator):
    def forward(self, *xs):
        out = xs[0]
        for x in xs[1:]:
            out = jnp.minimum(out, x)
        return out


class Clip(Operator):
    def __init__(self, min=None, max=None):  # noqa: A002
        super().__init__()
        self.min, self.max = min, max

    def forward(self, x):
        return jnp.clip(x, self.min, self.max)


def _cmp_op(name, fn):
    cls = type(name, (Operator,), {
        "forward": staticmethod(lambda *a, _f=fn: _f(*a).astype(jnp.float32))})
    cls.differentiable = False
    return cls


Less = _cmp_op("Less", jnp.less)
Greater = _cmp_op("Greater", jnp.greater)
Equal = _cmp_op("Equal", jnp.equal)
And = _cmp_op("And", lambda a, b: jnp.logical_and(a > 0, b > 0))
Or = _cmp_op("Or", lambda a, b: jnp.logical_or(a > 0, b > 0))
Xor = _cmp_op("Xor", lambda a, b: jnp.logical_xor(a > 0, b > 0))
Not = _cmp_op("Not", lambda a: jnp.logical_not(a > 0))


# ---- shape ops ------------------------------------------------------------

class Reshape(Operator):
    def __init__(self, shape):
        super().__init__()
        self.shape = tuple(int(s) for s in shape)

    def forward(self, x):
        return jnp.reshape(x, self.shape)


class Flatten(Operator):
    def __init__(self, axis=1):
        super().__init__()
        self.axis = axis

    def forward(self, x):
        lead = int(np.prod(x.shape[:self.axis])) if self.axis else 1
        return jnp.reshape(x, (lead, -1))


class Transpose(Operator):
    def __init__(self, perm=None):
        super().__init__()
        self.perm = tuple(perm) if perm is not None else None

    def forward(self, x):
        return jnp.transpose(x, self.perm)


class Squeeze(Operator):
    def __init__(self, axis=None):
        super().__init__()
        self.axis = tuple(axis) if isinstance(axis, (list, tuple)) else axis

    def forward(self, x):
        return jnp.squeeze(x, self.axis)


class Unsqueeze(Operator):
    def __init__(self, axis):
        super().__init__()
        self.axis = axis if isinstance(axis, (list, tuple)) else [axis]

    def forward(self, x):
        for a in sorted(self.axis):
            x = jnp.expand_dims(x, a)
        return x


class Concat(Operator):
    def __init__(self, axis=0):
        super().__init__()
        self.axis = axis

    def forward(self, *xs):
        return jnp.concatenate(xs, axis=self.axis)


class Split(Operator):
    def __init__(self, axis, parts=None, num_output=None):
        super().__init__()
        self.axis = axis
        self.parts = parts
        self.num_output = num_output

    def forward(self, x):
        if self.parts is not None:
            idx = np.cumsum(self.parts)[:-1].tolist()
            return tuple(jnp.split(x, idx, axis=self.axis))
        return tuple(jnp.split(x, self.num_output, axis=self.axis))


class Slice(Operator):
    def __init__(self, starts, ends, axes=None, steps=None):
        super().__init__()
        self.starts, self.ends = list(starts), list(ends)
        self.axes = list(axes) if axes is not None else None
        self.steps = list(steps) if steps is not None else None

    def forward(self, x):
        axes = self.axes if self.axes is not None else list(range(len(self.starts)))
        steps = self.steps if self.steps is not None else [1] * len(self.starts)
        idx = [builtins_slice(None)] * x.ndim
        for s, e, a, st in zip(self.starts, self.ends, axes, steps):
            idx[a] = builtins_slice(s, e, st)
        return x[tuple(idx)]


builtins_slice = slice  # keep builtin reachable; `slice` fn below shadows it


class Gather(Operator):
    def __init__(self, axis=0):
        super().__init__()
        self.axis = axis

    def forward(self, x, indices):
        return jnp.take(x, indices.astype(jnp.int32), axis=self.axis)


class ScatterElements(Operator):
    def __init__(self, axis=0):
        super().__init__()
        self.axis = axis

    def forward(self, x, indices, updates):
        idx = indices.astype(jnp.int32)
        # build full index grids along every axis, replace on self.axis
        grids = jnp.meshgrid(*[jnp.arange(s) for s in idx.shape],
                             indexing="ij")
        grids[self.axis] = idx
        return x.at[tuple(grids)].set(updates)


class Tile(Operator):
    def __init__(self, repeats):
        super().__init__()
        self.repeats = repeats

    def forward(self, x):
        return jnp.tile(x, self.repeats)


class Expand(Operator):
    def __init__(self, shape):
        super().__init__()
        self.shape = tuple(int(s) for s in shape)

    def forward(self, x):
        return jnp.broadcast_to(x, jnp.broadcast_shapes(x.shape, self.shape))


class Pad(Operator):
    def __init__(self, mode, pads, constant=0.0):
        super().__init__()
        self.mode = mode
        self.pads = list(pads)
        self.constant = constant

    def forward(self, x):
        n = x.ndim
        width = [(self.pads[i], self.pads[i + n]) for i in range(n)]
        if self.mode == "constant":
            return jnp.pad(x, width, constant_values=self.constant)
        return jnp.pad(x, width, mode={"reflect": "reflect",
                                       "edge": "edge"}[self.mode])


class UpSample(Operator):
    """Nearest-neighbour upsample by integer scales (reference
    autograd.UpSample:5263)."""

    def __init__(self, mode="nearest", scales=None):
        super().__init__()
        assert mode.lower() == "nearest"
        self.scales = scales

    def forward(self, x):
        for axis, s in enumerate(self.scales):
            s = int(s)
            if s != 1:
                x = jnp.repeat(x, s, axis=axis)
        return x


class DepthToSpace(Operator):
    def __init__(self, blocksize, mode="DCR"):
        super().__init__()
        self.b = blocksize
        self.mode = mode

    def forward(self, x):
        N, C, H, W = x.shape
        b = self.b
        if self.mode == "DCR":
            y = x.reshape(N, b, b, C // (b * b), H, W)
            y = jnp.transpose(y, (0, 3, 4, 1, 5, 2))
        else:  # CRD
            y = x.reshape(N, C // (b * b), b, b, H, W)
            y = jnp.transpose(y, (0, 1, 4, 2, 5, 3))
        return y.reshape(N, C // (b * b), H * b, W * b)


class SpaceToDepth(Operator):
    def __init__(self, blocksize):
        super().__init__()
        self.b = blocksize

    def forward(self, x):
        N, C, H, W = x.shape
        b = self.b
        y = x.reshape(N, C, H // b, b, W // b, b)
        y = jnp.transpose(y, (0, 3, 5, 1, 2, 4))
        return y.reshape(N, C * b * b, H // b, W // b)


# ---- indexing / generation ------------------------------------------------

class Where(Operator):
    def forward(self, cond, a, b):
        return jnp.where(jax.lax.stop_gradient(cond) > 0, a, b)


class OneHot(Operator):
    def __init__(self, axis=-1, depth=None, values=(0.0, 1.0)):
        super().__init__()
        self.axis = axis
        self.depth = depth
        self.values = values

    differentiable = False

    def forward(self, indices):
        off, on = self.values
        oh = jax.nn.one_hot(indices.astype(jnp.int32), self.depth,
                            axis=self.axis)
        return oh * (on - off) + off


class Embedding(Operator):
    """Lookup rows of W by integer ids (reference autograd.Embedding:5648)."""

    def forward(self, x, W):
        y = jnp.take(W, jax.lax.stop_gradient(x).astype(jnp.int32), axis=0)
        # policy cast on the GATHERED rows, not the table: casting W
        # itself would materialise a full 16-bit copy of the (possibly
        # vocab-sized) table; ids are index-valued and never cast
        return _cast_compute(y)


class CosSim(Operator):
    def forward(self, a, b):
        num = jnp.sum(a * b, axis=-1)
        den = jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1)
        return num / (den + 1e-12)


class Shape(Operator):
    differentiable = False

    def forward(self, x):
        return jnp.asarray(x.shape, dtype=jnp.int32)


class ConstantOfShape(Operator):
    differentiable = False

    def __init__(self, value=0.0):
        super().__init__()
        self.value = value

    def forward(self, x):
        shape = tuple(int(v) for v in np.asarray(x))
        return jnp.full(shape, self.value, dtype=jnp.float32)


class NonZero(Operator):
    """Indices of nonzero entries. Dynamic-shaped ⇒ eager/host only (cannot
    run under jit; reference computes it on host too)."""

    differentiable = False

    def forward(self, x):
        idx = np.nonzero(np.asarray(jax.device_get(x)))
        return jnp.asarray(np.stack(idx), dtype=jnp.int64)


class Cast(Operator):
    differentiable = False

    def __init__(self, to):
        super().__init__()
        self.to = to

    def forward(self, x):
        return x.astype(self.to)


class Identity(Operator):
    def forward(self, x):
        return x


class AsType(Operator):
    """Differentiable dtype cast — the mixed-precision boundary op
    (bf16 activations below, f32 above). Unlike :class:`Cast` (which is
    for integer/config casts and blocks gradients), jax's vjp through
    ``astype`` casts the cotangent back to the source dtype, which is
    exactly the master-dtype accumulation semantics wanted here."""

    def __init__(self, to):
        super().__init__()
        self.to = to

    def forward(self, x):
        return x.astype(self.to)


class _LayerNorm(Operator):
    """Normalise over the trailing dim, then scale+shift (TPU extension
    used by the transformer family)."""

    def __init__(self, eps=1e-5):
        super().__init__()
        self.eps = eps

    def forward(self, x, scale, bias):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps) * scale + bias
        # norm math in f32; activations keep the input's precision class
        return y.astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    return _LayerNorm(eps)(x, scale, bias)


class Dropout(Operator):
    def __init__(self, ratio=0.5):
        super().__init__()
        self.ratio = ratio

    def forward(self, x):
        if not is_training() or self.ratio <= 0.0:
            return x
        key = self.dev.rand_key()
        keep = 1.0 - self.ratio
        mask = jax.random.bernoulli(key, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0)


# ===========================================================================
# functional wrappers (parity with reference snake_case API)
# ===========================================================================

def add(a, b):
    out = Add()(a, b)
    # residual-tail peephole tag (ops/fused_epilogue.py): a sum whose
    # operand is a tagged inference-BN output may fuse the whole
    # scale/shift + add + relu tail into one pass over the conv output
    # when a ReLU consumes it. One getattr per operand — the tag
    # itself costs one attribute; eligibility is decided at the ReLU.
    ta = getattr(a, "_bn_epilogue", None)
    tb = getattr(b, "_bn_epilogue", None)
    if ta is not None or tb is not None:
        # both-tagged (a downsample block adds two BN outputs): fuse
        # around ONE of them, the other's reference output is the
        # residual input
        tag, res = (ta, b) if ta is not None else (tb, a)
        out._bn_add_epilogue = (tag, res)
    return out


def sub(a, b):
    return Sub()(a, b)


def mul(a, b):
    return Mul()(a, b)


def div(a, b):
    return Div()(a, b)


def pow(a, b):  # noqa: A001
    return Pow()(a, b)


def negative(x):
    return Negative()(x)


def reciprocal(x):
    return Reciprocal()(x)


def add_bias(x, b, axis=0):
    return AddBias(axis)(x, b)


def matmul(a, b):
    return Matmul()(a, b)


def gemm(A, B, C=None, alpha=1.0, beta=1.0, transA=0, transB=0):
    if C is None:
        return Gemm(alpha, beta, transA, transB)(A, B)
    return Gemm(alpha, beta, transA, transB)(A, B, C)


def add_all(*xs):
    return Sum()(*xs)


def sum(*xs):  # noqa: A001  (reference autograd.sum = elementwise N-ary sum)
    return Sum()(*xs)


def abs(x):  # noqa: A001
    return Abs()(x)


def exp(x):
    return Exp()(x)


def log(x):
    return Log()(x)


def sqrt(x):
    return Sqrt()(x)


def sin(x):
    return Sin()(x)


def cos(x):
    return Cos()(x)


def tan(x):
    return Tan()(x)


def sinh(x):
    return Sinh()(x)


def cosh(x):
    return Cosh()(x)


def asin(x):
    return Asin()(x)


def acos(x):
    return Acos()(x)


def atan(x):
    return Atan()(x)


def asinh(x):
    return Asinh()(x)


def acosh(x):
    return Acosh()(x)


def atanh(x):
    return Atanh()(x)


def tanh(x):
    return Tanh()(x)


def erf(x):
    return Erf()(x)


def ceil(x):
    return Ceil()(x)


def floor(x):
    return Floor()(x)


def round(x):  # noqa: A001
    return Round()(x)


def rounde(x):
    return Rounde()(x)


def sign(x):
    return Sign()(x)


def relu(x):
    if getattr(x, "_bn_epilogue", None) is not None or \
            getattr(x, "_bn_add_epilogue", None) is not None:
        # a tagged inference-BN output (or a BN-output + residual sum)
        # may fuse scale/shift[+add]+relu into one pass over the conv
        # output (ops/fused_epilogue.py peephole; opt-in +
        # eligibility-gated — returns None to decline)
        from .ops import fused_epilogue
        fused = fused_epilogue.try_relu_epilogue(x)
        if fused is not None:
            return fused
    return ReLU()(x)


def leakyrelu(x, a=0.01):
    return LeakyRelu(a)(x)


def elu(x, alpha=1.0):
    return Elu(alpha)(x)


def selu(x, alpha=1.67326, gamma=1.0507):
    return SeLU(alpha, gamma)(x)


def sigmoid(x):
    return Sigmoid()(x)


def softplus(x):
    return SoftPlus()(x)


def softsign(x):
    return SoftSign()(x)


def hardsigmoid(x, alpha=0.2, gamma=0.5):
    return HardSigmoid(alpha, gamma)(x)


def prelu(x, slope):
    return PRelu()(x, slope)


def softmax(x, axis=1):
    return SoftMax(axis)(x)


def gelu(x):
    return GELU()(x)


def cross_entropy(y, t):
    return CrossEntropy()(y, t)


def softmax_cross_entropy(x, t):
    return SoftMaxCrossEntropy()(x, t)


def mse_loss(x, t):
    return MeanSquareError()(x, t)


def binary_cross_entropy(x, t):
    return BinaryCrossEntropy()(x, t)


def ranking_loss(pos, neg, M=0.2):
    return RankingLoss(M)(pos, neg)


def reduce_sum(x, axes=None, keepdims=1):
    return ReduceSum(axes, keepdims)(x)


def reduce_mean(x, axes=None, keepdims=1):
    return ReduceMean(axes, keepdims)(x)


def reduce_max(x, axes=None, keepdims=1):
    return ReduceMax(axes, keepdims)(x)


def reduce_prod(x, axes=None, keepdims=1):
    return ReduceProd(axes, keepdims)(x)


def mean(*xs):
    return Mean()(*xs)


def max(*xs):  # noqa: A001
    return Max()(*xs)


def min(*xs):  # noqa: A001
    return Min()(*xs)


def clip(x, min=None, max=None):  # noqa: A002
    return Clip(min, max)(x)


def less(a, b):
    return Less()(a, b)


def greater(a, b):
    return Greater()(a, b)


def equal(a, b):
    return Equal()(a, b)


def _and(a, b):
    return And()(a, b)


def _or(a, b):
    return Or()(a, b)


def _xor(a, b):
    return Xor()(a, b)


def _not(a):
    return Not()(a)


def reshape(x, shape):
    return Reshape(shape)(x)


def flatten(x, axis=1):
    return Flatten(axis)(x)


def transpose(x, shape=None):
    return Transpose(shape)(x)


def squeeze(x, axis=None):
    return Squeeze(axis)(x)


def unsqueeze(x, axis):
    return Unsqueeze(axis)(x)


def cat(xs, axis=0):
    return Concat(axis)(*xs)


def split(x, axis, parts=None, num_output=None):
    return Split(axis, parts, num_output)(x)


def slice(x, starts, ends, axes=None, steps=None):  # noqa: A001
    return Slice(starts, ends, axes, steps)(x)


def make_slice(x, axis, idx):
    """Take index ``idx`` along ``axis`` keeping dims (reference helper)."""
    return Slice([idx], [idx + 1], [axis])(x)


def gather(x, axis, indices):
    if isinstance(indices, (list, tuple, np.ndarray)):
        indices = Tensor(data=np.asarray(indices, dtype=np.int32),
                         requires_grad=False)
    return Gather(axis)(x, indices)


def scatter_elements(x, indices, updates, axis=0):
    return ScatterElements(axis)(x, indices, updates)


def tile(x, repeats):
    return Tile(repeats)(x)


def expand(x, shape):
    return Expand(shape)(x)


def pad(x, mode, pads, constant=0.0):
    return Pad(mode, pads, constant)(x)


def upsample(x, mode="nearest", scales=None):
    return UpSample(mode, scales)(x)


def depth_to_space(x, blocksize, mode="DCR"):
    return DepthToSpace(blocksize, mode)(x)


def space_to_depth(x, blocksize):
    return SpaceToDepth(blocksize)(x)


def where(cond, a, b):
    return Where()(cond, a, b)


def onehot(axis, indices, depth, values=(0.0, 1.0)):
    return OneHot(axis, depth, values)(indices)


def embedding(x, W):
    return Embedding()(x, W)


def cossim(a, b):
    return CosSim()(a, b)


def shape(x):
    return Shape()(x)


def constant_of_shape(x, value=0.0):
    return ConstantOfShape(value)(x)


def nonzero(x):
    return NonZero()(x)


def cast(x, to):
    return Cast(to)(x)


def astype(x, to):
    return AsType(to)(x)


def axis_helper(y_shape, x_shape):
    """Axes along which ``x_shape`` was broadcast to produce
    ``y_shape`` — the sum-reduction set for a broadcast backward
    (reference autograd.py:34)."""
    res = []
    j = len(x_shape) - 1
    for i in range(len(y_shape) - 1, -1, -1):
        if j < 0 or x_shape[j] != y_shape[i]:
            res.append(i)
        j -= 1
    return tuple(res[::-1])


def back_broadcast(y_shape, x_shape, x):
    """Reduce a broadcast result (cotangent) back to ``x_shape``: sum
    over the broadcast axes, then reshape (reference autograd.py:52).
    Accepts a Tensor or array; returns the same kind, preserving the
    Tensor's device and requires_grad metadata."""
    if tuple(y_shape) == tuple(x_shape):
        return x
    arr = x.data if isinstance(x, Tensor) else jnp.asarray(x)
    arr = jnp.sum(arr, axis=axis_helper(y_shape, x_shape)) \
        .reshape(tuple(x_shape))
    if isinstance(x, Tensor):
        return Tensor(data=arr, device=x.device,
                      requires_grad=x.requires_grad)
    return arr


def identity(x):
    return Identity()(x)


def dropout(x, ratio=0.5):
    return Dropout(ratio)(x)


def ctensor2numpy(x):
    return np.asarray(jax.device_get(_raw(x)))


class _Checkpointed(Operator):
    """Run a sub-network under ``jax.checkpoint``: its activations are NOT
    saved for backward — the block is recomputed from its inputs during the
    gradient pass. The TPU-first answer to activation memory on long
    sequences / deep stacks (trade FLOPs for HBM); no reference counterpart
    (SINGA recycles block buffers in its Graph scheduler instead,
    src/core/scheduler/scheduler.cc:671-688, which cannot help with
    autograd residuals).

    Params enter as explicit operator inputs so their gradients ride the
    ordinary tape; the device RNG is re-seeded from an input key inside the
    wrapped function so dropout masks agree between the forward and the
    recompute pass.
    """

    def __init__(self, run):
        super().__init__()
        self._run = run          # (x_arr, *param_arrs) -> out_arr, via ops
        self._ck = jax.checkpoint(self._pure)

    def _pure(self, key, x, *params):
        dev = self.dev
        saved = dev._get_rng_state()
        dev._set_rng_state(key)
        try:
            return self._run(x, *params)
        finally:
            dev._set_rng_state(saved)

    def forward(self, key, x, *params):
        return self._ck(key, x, *params)


def _aux_layers(block):
    """Layers in ``block``'s tree that stash an ``aux_loss`` Tensor during
    forward (MoE load-balance losses), in deterministic traversal order."""
    found = []

    def walk(l):
        if hasattr(l, "aux_loss"):
            found.append(l)
        for _name, sub in sorted(l._sublayers()):
            walk(sub)

    walk(block)
    return found


def checkpoint(block, x):
    """Apply ``block`` (a Layer) to Tensor ``x`` with rematerialized
    backward: ``y = checkpoint(blk, x)`` is numerically ``blk(x)`` but
    stores only the block's inputs, recomputing its inside during the
    gradient pass (``jax.checkpoint``).

    Auxiliary losses stashed by sublayers during forward (``aux_loss``
    attributes, e.g. MoE load-balance terms) are threaded out of the
    rematerialized region as extra op outputs and re-stashed, so
    ``blk.mlp.aux_loss`` stays usable in the surrounding loss.

    On the first call (shape-inferring initialization) the block runs
    un-checkpointed so its parameters materialize; every later call —
    including under jit/graph mode — is rematerialized.
    """
    from .layer import Layer
    if not isinstance(block, Layer):
        raise TypeError("checkpoint() wraps a Layer; for plain functions "
                        "use jax.checkpoint directly")
    if not block._initialized:
        return block(x)
    params = block.get_params()
    if len(block.get_states()) != len(params):
        # running statistics (BatchNorm) are updated in the forward pass;
        # under recompute they would be written from a closed-over inner
        # trace — unsound. LayerNorm-style blocks are the supported shape.
        raise ValueError(
            "checkpoint() cannot wrap blocks holding non-parameter state "
            "(e.g. BatchNorm running stats); use normalization without "
            "running statistics (LayerNorm) inside checkpointed blocks")
    names = sorted(params)
    tensors = [params[n] for n in names]
    aux_layers = _aux_layers(block)

    def run(x_arr, *param_arrs):
        backup = [(t.data, t.requires_grad) for t in tensors]
        for t, a in zip(tensors, param_arrs):
            # the block's own ops take no vjp: the checkpointed op's vjp
            # differentiates the whole block, and a vjp nested in it would
            # differentiate a custom_vjp's forward rule (a Pallas call
            # has no JVP)
            t.data, t.requires_grad = a, False
        try:
            xin = Tensor(data=x_arr, device=x.device, requires_grad=False)
            out = block(xin)
            if not isinstance(out, Tensor):
                raise TypeError(
                    "checkpoint() supports single-Tensor-output blocks; "
                    f"{type(block).__name__}.forward returned "
                    f"{type(out).__name__}")
            auxs = tuple(l.aux_loss.data for l in aux_layers
                         if l.aux_loss is not None)
            if auxs:
                return (out.data,) + auxs
            return out.data
        finally:
            for t, (a, grad) in zip(tensors, backup):
                t.data, t.requires_grad = a, grad

    op = _Checkpointed(run)
    key = x.device.rand_key()
    kt = Tensor(data=key, device=x.device, requires_grad=False)
    res = op(kt, x, *tensors)
    if isinstance(res, (tuple, list)):
        y, auxs = res[0], list(res[1:])
        live = [l for l in aux_layers if l.aux_loss is not None]
        for l, a in zip(live, auxs):
            l.aux_loss = a
        return y
    return res


# ---- conv/bn/pool/rnn ops live in singa_tpu.ops; re-export here for parity
from .ops.conv import (ConvHandle, _Conv2d, conv2d)  # noqa: E402
from .ops.batchnorm import (BatchNormHandle, _BatchNorm2d,  # noqa: E402
                            batchnorm_2d)
from .ops.pooling import (PoolingHandle, _Pooling2d, pooling_2d,  # noqa: E402
                          globalaveragepool, GlobalAveragePool)
from .ops.rnn import (CudnnRNNHandle, _RNN, rnn_op)  # noqa: E402
