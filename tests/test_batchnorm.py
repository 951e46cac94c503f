"""Training-mode BatchNorm in closed form (``ops/batchnorm.py``): values
and gradients against ``jax.vjp`` of a plain two-pass float32 BN written
here, the numerics of the one-pass variance, and a structural pin on the
number and depth of the reductions a conv -> BN -> ReLU train step makes
over the activation."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import autograd, device, layer, model, opt
from singa_tpu.ops.batchnorm import (BatchNormHandle, _BatchNorm2d,
                                     batchnorm_2d)
from singa_tpu.tensor import Tensor

EPS = 1e-5
MOMENTUM = 0.9


# (input shape, layout, reduced axes, broadcast shape of a per-channel vector)
CASES = {
    "NCHW": ((8, 6, 5, 5), "NCHW", (0, 2, 3), (1, 6, 1, 1)),
    "NHWC": ((8, 5, 5, 6), "NHWC", (0, 1, 2), (1, 1, 1, 6)),
    "2D": ((32, 6), "NCHW", (0,), (1, 6)),
}


def _two_pass(x, scale, bias, axes, bshape):
    """Plain float32 BN: mean, then mean squared deviation around it."""
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x - mean.reshape(bshape)), axis=axes)
    y = (x - mean.reshape(bshape)) * jax.lax.rsqrt(var + EPS).reshape(bshape)
    return y * scale.reshape(bshape) + bias.reshape(bshape), (mean, var)


def _run_op(x, scale, bias, dy, layout):
    """y, (dx, dscale, dbias) and the updated running stats through the
    repo's wrapper and tape."""
    tx, ts, tb = (Tensor(data=a, requires_grad=True, stores_grad=True)
                  for a in (x, scale, bias))
    c = scale.shape[0]
    rmean = Tensor(data=jnp.zeros(c, jnp.float32), requires_grad=False)
    rvar = Tensor(data=jnp.ones(c, jnp.float32), requires_grad=False)
    handle = BatchNormHandle(MOMENTUM, x, eps=EPS, layout=layout)
    y = batchnorm_2d(handle, tx, ts, tb, rmean, rvar)
    grads = {id(p): g.data for p, g in autograd.backward(y, dy)}
    return (y.data, tuple(grads[id(t)] for t in (tx, ts, tb)),
            (rmean.data, rvar.data))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_closed_form_matches_two_pass_vjp(case, dtype, training_mode):
    shape, layout, axes, bshape = CASES[case]
    rs = np.random.RandomState(7)
    x = jnp.asarray(rs.randn(*shape) * 2 + 1, dtype)
    scale = jnp.asarray(rs.rand(6) + 0.5, jnp.float32)
    bias = jnp.asarray(rs.randn(6), jnp.float32)
    dy = jnp.asarray(rs.randn(*shape), dtype)

    y, (dx, dscale, dbias), (rmean, rvar) = _run_op(x, scale, bias, dy,
                                                    layout)
    (y_ref, (mean, var)), vjp = jax.vjp(
        lambda *a: _two_pass(*a, axes, bshape), x, scale, bias)
    zeros = (jnp.zeros_like(mean), jnp.zeros_like(var))
    dx_ref, dscale_ref, dbias_ref = vjp((dy.astype(jnp.float32), zeros))

    # gradients and activations come back in each input's dtype
    assert y.dtype == dx.dtype == dtype
    assert dscale.dtype == dbias.dtype == rmean.dtype == rvar.dtype \
        == jnp.float32
    # float32: the two forms agree to rounding; bf16 input: y and dx are
    # rounded to bf16 once (2**-8 of their size), the float32 sums are not
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    pairs = {"y": (y, y_ref), "dx": (dx, dx_ref),
             "dscale": (dscale, dscale_ref), "dbias": (dbias, dbias_ref),
             "running_mean": (rmean, (1 - MOMENTUM) * mean),
             "running_var": (rvar, MOMENTUM + (1 - MOMENTUM) * var)}
    for name, (got, ref) in pairs.items():
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        np.testing.assert_allclose(
            got, ref, rtol=tol, atol=tol * np.abs(ref).max(), err_msg=name)


def test_variance_of_a_far_off_mean(training_mode):
    """mean = 30 standard deviations: the raw moments cancel 900 parts in
    901 and the one-pass variance still holds to 1e-2 (float32 sums)."""
    rs = np.random.RandomState(11)
    x = (rs.randn(64, 4, 16, 16) * 0.5 + 15.0).astype(np.float32)
    op = _BatchNorm2d(BatchNormHandle(MOMENTUM, x, eps=EPS))
    y = op.forward(jnp.asarray(x), jnp.ones(4), jnp.zeros(4))
    var = np.asarray(op.batch_var)
    assert (var >= 0).all()
    np.testing.assert_allclose(var, x.var(axis=(0, 2, 3)), rtol=1e-2)
    np.testing.assert_allclose(np.asarray(op.batch_mean),
                               x.mean(axis=(0, 2, 3)), rtol=1e-5)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y).std(axis=(0, 2, 3)), 1.0,
                               rtol=1e-2)


def test_constant_channel_is_finite(training_mode):
    """Variance 0 (and a raw-moment difference that may round below 0):
    y and dx stay finite, y is the bias."""
    rs = np.random.RandomState(13)
    x = rs.randn(8, 3, 4, 4).astype(np.float32)
    x[:, 1] = 3.1415927
    dy = jnp.asarray(rs.randn(*x.shape), jnp.float32)
    bias = jnp.asarray([0.5, -1.5, 2.0], jnp.float32)
    y, (dx, dscale, dbias), (_, rvar) = _run_op(
        jnp.asarray(x), jnp.ones(3, jnp.float32), bias, dy, "NCHW")
    for name, a in (("y", y), ("dx", dx), ("dscale", dscale),
                    ("dbias", dbias), ("running_var", rvar)):
        assert np.isfinite(np.asarray(a)).all(), name
    # the batch variance did not come out negative
    assert rvar[1] >= np.float32(MOMENTUM)
    np.testing.assert_allclose(np.asarray(y)[:, 1], -1.5, atol=1e-2)


# -- the structural pin ------------------------------------------------------

class _ConvBNReLU(model.Model):
    def __init__(self):
        super().__init__()
        self.conv = layer.Conv2d(16, 3, padding=1, bias=False)
        self.bn = layer.BatchNorm2d()
        self.relu = layer.ReLU()
        self.flat = layer.Flatten()
        self.fc = layer.Linear(4)
        self.loss_fn = layer.SoftMaxCrossEntropy()

    def forward(self, x):
        return self.fc(self.flat(self.relu(self.bn(self.conv(x)))))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.loss_fn(out, y)
        self.optimizer(loss)
        return out, loss


_SSA = re.compile(r"%[\w.]+")
_REDUCE = re.compile(r"stablehlo\.reduce\(.*?:\s*\(tensor<([0-9x]+)x\w+>")


def activation_reduce_depths(mlir: str, n_elements: int):
    """For every ``stablehlo.reduce`` in ``mlir`` whose first operand has
    at least ``n_elements`` elements: how many such reductions lie on the
    longest chain of data dependencies that ends in it (1: it waits for
    none). A call's results depend on all its arguments; SSA names are a
    function's own, so each ``func.func`` is walked by itself."""
    depths = []
    for body in mlir.split("func.func")[1:]:
        depth = {}
        for line in body.splitlines():
            lhs, eq, rhs = line.partition(" = ")
            if not eq or not lhs.strip().startswith("%"):
                continue
            d = max((depth.get(v.split("#")[0], 0)
                     for v in _SSA.findall(rhs)), default=0)
            m = _REDUCE.search(rhs)
            if m and np.prod([int(n) for n in m.group(1).split("x")]) \
                    >= n_elements:
                d += 1
                depths.append(d)
            for v in _SSA.findall(lhs):
                depth[v.split(":")[0]] = d
    return sorted(depths)


def test_activation_reduce_depths_reads_a_chain():
    """The analyser itself, on the two-pass form it exists to refuse."""
    x = jnp.ones((8, 16, 12, 12))
    f = lambda x: jnp.sum(_two_pass(x, jnp.ones(16), jnp.zeros(16),
                                    (0, 2, 3), (1, 16, 1, 1))[0] ** 2)
    text = jax.jit(jax.grad(f)).lower(x).as_text()
    depths = activation_reduce_depths(text, x.size)
    assert depths[:2] == [1, 2] and max(depths) >= 4, depths


@pytest.mark.parametrize("policy", [None, "bf16_mixed"],
                         ids=["float32", "bf16_mixed"])
def test_train_step_reduces_the_activation_four_times_in_two_levels(policy):
    """One conv -> BN -> ReLU train step through ``Model.compile``: two
    sibling reductions forward (Σx, Σx²), two backward (Σdy, Σdy·x̂), none
    waiting for another of its direction. The vjp of a two-pass forward
    lowers to 9-10 of them, two dependent levels each way; this keeps a
    later edit from quietly bringing the third pass back."""
    dev = device.create_cpu_device()
    dev.SetRandSeed(1)
    rs = np.random.RandomState(0)
    x = rs.randn(8, 3, 12, 12).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rs.randint(0, 4, 8)]
    m = _ConvBNReLU()
    m.set_optimizer(opt.SGD(lr=0.1))
    tx = Tensor(data=x, device=dev, requires_grad=False)
    ty = Tensor(data=y, device=dev, requires_grad=False)
    m.compile([tx], is_train=True, use_graph=True,
              **({"policy": policy} if policy else {}))
    assert np.isfinite(float(m(tx, ty)[1].data))
    rec = m._last_run_rec
    state_avals, rng_aval, in_avals = rec["avals"]
    text = rec["jit"].lower(state_avals, rng_aval, *in_avals).as_text()
    # the BN input: (8, 16, 12, 12); nothing else in the step is as large
    depths = activation_reduce_depths(text, 8 * 16 * 12 * 12)
    assert depths == [1, 1, 2, 2], depths
