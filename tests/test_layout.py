"""NHWC (channels-last) layout mode: parity with the NCHW reference path.

The reference stack is NCHW-only (cuDNN's native layout,
src/model/operation/convolution.h:43-90). The TPU build adds an NHWC
activation mode (ops/layout.py) because the MXU wants channels in the
128-lane minor dim; weights stay OIHW so checkpoints are identical.
These tests pin the invariant that makes a layout A/B on the chip a
fair comparison: both layouts compute the SAME function.
"""

import os
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from singa_tpu import device, opt, tensor
from singa_tpu.ops.conv import (ConvHandle, ConvTransposeHandle, conv2d,
                                conv_transpose2d)
from singa_tpu.ops.pooling import PoolingHandle, pooling_2d
from singa_tpu.ops.batchnorm import BatchNormHandle, batchnorm_2d
from singa_tpu.ops import layout as L


@pytest.fixture
def dev():
    return device.create_cpu_device()


def _nchw_to_nhwc(x):
    return np.transpose(x, (0, 2, 3, 1))


def test_layout_scope_is_per_thread():
    """ADVICE r5 #1 regression: an NHWC scope on one thread must not
    leak into handle construction on another (training alongside
    serving) — the scope stack is a ContextVar, not a process global."""
    import threading

    seen = {}
    entered = threading.Event()
    release = threading.Event()

    def other_thread():
        seen["before"] = L.current_layout()
        with L.use_layout("NHWC" if seen["before"] == "NCHW" else "NCHW"):
            pass
        entered.wait(5)
        # main thread is INSIDE use_layout("NHWC") right now
        seen["during"] = L.current_layout()
        release.set()

    th = threading.Thread(target=other_thread)
    th.start()
    with L.use_layout("NHWC"):
        entered.set()
        release.wait(5)
        assert L.current_layout() == "NHWC"
    th.join(5)
    assert seen["before"] == "NCHW"
    assert seen["during"] == "NCHW"     # no cross-thread leak
    assert L.current_layout() == "NCHW"


def test_layout_stack_and_validation():
    assert L.current_layout() == "NCHW"
    with L.use_layout("nhwc"):
        assert L.current_layout() == "NHWC"
        assert L.channel_axis(4) == 3
        assert L.channel_axis(2) == 1
        with L.use_layout("NCHW"):
            assert L.current_layout() == "NCHW"
        assert L.current_layout() == "NHWC"
    assert L.current_layout() == "NCHW"
    with pytest.raises(ValueError):
        with L.use_layout("NWHC"):
            pass


def test_conv2d_nhwc_matches_nchw(dev):
    rng = np.random.RandomState(0)
    x = rng.randn(2, 5, 9, 9).astype(np.float32)
    W = rng.randn(4, 5, 3, 3).astype(np.float32)
    b = rng.randn(4).astype(np.float32)
    tx = tensor.Tensor(data=x, device=dev)
    tW = tensor.Tensor(data=W, device=dev)
    tb = tensor.Tensor(data=b, device=dev)
    h = ConvHandle(x, 3, 2, 1, 5, 4)
    ref = tensor.to_numpy(conv2d(h, tx, tW, tb))

    xt = _nchw_to_nhwc(x)
    h2 = ConvHandle(xt, 3, 2, 1, 5, 4, layout="NHWC")
    assert h2.dimension_numbers == ("NHWC", "OIHW", "NHWC")
    assert h2.output_shape(xt.shape) == tuple(
        np.transpose(ref, (0, 2, 3, 1)).shape)
    txt = tensor.Tensor(data=xt, device=dev)
    got = tensor.to_numpy(conv2d(h2, txt, tW, tb))
    np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), ref,
                               rtol=1e-5, atol=1e-5)


def test_conv2d_nhwc_grouped(dev):
    rng = np.random.RandomState(1)
    x = rng.randn(2, 4, 8, 8).astype(np.float32)
    W = rng.randn(6, 2, 3, 3).astype(np.float32)
    tx = tensor.Tensor(data=x, device=dev)
    tW = tensor.Tensor(data=W, device=dev)
    ref = tensor.to_numpy(conv2d(ConvHandle(x, 3, 1, 1, 4, 6, group=2),
                                 tx, tW))
    xt = _nchw_to_nhwc(x)
    got = tensor.to_numpy(conv2d(
        ConvHandle(xt, 3, 1, 1, 4, 6, group=2, layout="NHWC"),
        tensor.Tensor(data=xt, device=dev), tW))
    np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), ref,
                               rtol=1e-5, atol=1e-5)


def test_conv_transpose_nhwc_matches_nchw(dev):
    rng = np.random.RandomState(2)
    x = rng.randn(2, 3, 5, 5).astype(np.float32)
    W = rng.randn(3, 4, 3, 3).astype(np.float32)  # (Cin, Cout, kh, kw)
    tx = tensor.Tensor(data=x, device=dev)
    tW = tensor.Tensor(data=W, device=dev)
    h = ConvTransposeHandle(x, 3, 2, 1, 3, 4, output_padding=1)
    ref = tensor.to_numpy(conv_transpose2d(h, tx, tW))
    xt = _nchw_to_nhwc(x)
    h2 = ConvTransposeHandle(xt, 3, 2, 1, 3, 4, output_padding=1,
                             layout="NHWC")
    assert h2.output_shape(xt.shape) == tuple(
        np.transpose(ref, (0, 2, 3, 1)).shape)
    got = tensor.to_numpy(conv_transpose2d(
        h2, tensor.Tensor(data=xt, device=dev), tW))
    np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), ref,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("is_max", [True, False])
def test_pooling_nhwc_matches_nchw(dev, is_max):
    rng = np.random.RandomState(3)
    x = rng.randn(2, 3, 8, 8).astype(np.float32)
    tx = tensor.Tensor(data=x, device=dev)
    ref = tensor.to_numpy(pooling_2d(
        PoolingHandle(x, 3, 2, 1, is_max=is_max), tx))
    xt = _nchw_to_nhwc(x)
    h = PoolingHandle(xt, 3, 2, 1, is_max=is_max, layout="NHWC")
    assert h.channels == 3 and h.height == 8
    got = tensor.to_numpy(pooling_2d(
        h, tensor.Tensor(data=xt, device=dev)))
    np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), ref,
                               rtol=1e-5, atol=1e-5)


def test_batchnorm_nhwc_matches_nchw(dev, training_mode):
    rng = np.random.RandomState(4)
    x = rng.randn(4, 3, 6, 6).astype(np.float32)
    scale = rng.rand(3).astype(np.float32) + 0.5
    bias = rng.randn(3).astype(np.float32)

    def run(xin, layout):
        tx = tensor.Tensor(data=xin, device=dev)
        ts = tensor.Tensor(data=scale, device=dev)
        tb = tensor.Tensor(data=bias, device=dev)
        rm = tensor.Tensor(data=np.zeros(3, np.float32), device=dev,
                           requires_grad=False)
        rv = tensor.Tensor(data=np.ones(3, np.float32), device=dev,
                           requires_grad=False)
        h = BatchNormHandle(0.9, xin, layout=layout)
        y = batchnorm_2d(h, tx, ts, tb, rm, rv)
        return tensor.to_numpy(y), np.asarray(rm.data), np.asarray(rv.data)

    ref, rm_ref, rv_ref = run(x, "NCHW")
    got, rm_got, rv_got = run(_nchw_to_nhwc(x), "NHWC")
    np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), ref,
                               rtol=1e-5, atol=1e-5)
    # running-stat updates must agree too (same per-channel moments)
    np.testing.assert_allclose(rm_got, rm_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(rv_got, rv_ref, rtol=1e-5, atol=1e-6)


def test_resnet_layout_train_parity(dev):
    """End-to-end: same seed, same data — the NHWC ResNet's losses track
    the NCHW ones step for step (same function, same init, same update)."""
    from singa_tpu.models import resnet

    def losses(lay):
        d = device.create_cpu_device()
        d.SetRandSeed(0)
        m = resnet.create_model(depth=18, num_classes=10, layout=lay)
        m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
        rng = np.random.RandomState(0)
        x = rng.randn(2, 3, 32, 32).astype(np.float32)
        y = np.eye(10)[rng.randint(0, 10, 2)].astype(np.float32)
        tx = tensor.Tensor(data=x, device=d, requires_grad=False)
        ty = tensor.Tensor(data=y, device=d, requires_grad=False)
        m.compile([tx], is_train=True, use_graph=True)
        out = []
        for _ in range(2):
            _, loss = m(tx, ty)
            out.append(float(loss.data))
        return out

    a, b = losses("NCHW"), losses("NHWC")
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class TestSpaceToDepthStem:
    """The exact stride-2 stem reformulation (ops/conv.py
    _space_to_depth_conv): same weights, same math, C*4 channels at
    stride 1 — so the MXU's lane dim isn't 97% padding on C_in=3."""

    def test_exact_vs_plain_conv_7x7(self, dev):
        rng = np.random.RandomState(0)
        x = rng.randn(2, 3, 16, 16).astype(np.float32)
        W = rng.randn(8, 3, 7, 7).astype(np.float32)
        tx = tensor.Tensor(data=x, device=dev)
        tW = tensor.Tensor(data=W, device=dev)
        ref = tensor.to_numpy(conv2d(ConvHandle(x, 7, 2, 3, 3, 8),
                                     tx, tW))
        got = tensor.to_numpy(conv2d(
            ConvHandle(x, 7, 2, 3, 3, 8, space_to_depth=True), tx, tW))
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)

    def test_exact_nhwc(self, dev):
        rng = np.random.RandomState(1)
        x = rng.randn(2, 3, 12, 12).astype(np.float32)
        W = rng.randn(4, 3, 7, 7).astype(np.float32)
        ref = tensor.to_numpy(conv2d(
            ConvHandle(x, 7, 2, 3, 3, 4),
            tensor.Tensor(data=x, device=dev),
            tensor.Tensor(data=W, device=dev)))
        xt = _nchw_to_nhwc(x)
        got = tensor.to_numpy(conv2d(
            ConvHandle(xt, 7, 2, 3, 3, 4, space_to_depth=True,
                       layout="NHWC"),
            tensor.Tensor(data=xt, device=dev),
            tensor.Tensor(data=W, device=dev)))
        np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), ref,
                                   rtol=1e-4, atol=1e-4)

    def test_gradients_match_plain(self, dev, training_mode):
        sys.path.insert(0, os.path.dirname(__file__))
        from test_gradcheck import gradcheck
        rng = np.random.RandomState(2)
        x = rng.randn(1, 2, 8, 8).astype(np.float32)
        W = rng.randn(3, 2, 3, 3).astype(np.float32)
        h = ConvHandle(x, 3, 2, 1, 2, 3, space_to_depth=True)
        gradcheck(lambda xx, ww: conv2d(h, xx, ww), [x, W])

    def test_invalid_geometry_rejected(self):
        x = np.zeros((1, 3, 16, 16), np.float32)
        with pytest.raises(ValueError, match="space_to_depth"):
            ConvHandle(x, 7, 1, 3, 3, 8, space_to_depth=True)  # stride 1
        with pytest.raises(ValueError, match="space_to_depth"):
            ConvHandle(x, 4, 2, 1, 3, 8, space_to_depth=True)  # even K
        with pytest.raises(ValueError, match="space_to_depth"):
            ConvHandle(np.zeros((1, 3, 15, 16), np.float32),
                       7, 2, 3, 3, 8, space_to_depth=True)     # odd H

    def test_resnet_stem_train_parity(self, dev):
        """Same seed, same data: the s2d-stem ResNet's losses track the
        plain-stem run (same function, same init, same update), and the
        checkpoint stays layout/stem-independent."""
        from singa_tpu.models import resnet

        def losses(stem):
            d = device.create_cpu_device()
            d.SetRandSeed(0)
            m = resnet.create_model(depth=18, num_classes=10, stem=stem)
            m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
            rng = np.random.RandomState(0)
            x = rng.randn(2, 3, 32, 32).astype(np.float32)
            y = np.eye(10)[rng.randint(0, 10, 2)].astype(np.float32)
            tx = tensor.Tensor(data=x, device=d, requires_grad=False)
            ty = tensor.Tensor(data=y, device=d, requires_grad=False)
            m.compile([tx], is_train=True, use_graph=True)
            return [float(m(tx, ty)[1].data) for _ in range(2)]

        np.testing.assert_allclose(losses("conv7"),
                                   losses("space_to_depth"),
                                   rtol=1e-4, atol=1e-5)


def test_layout_env_default(monkeypatch):
    from contextvars import ContextVar
    monkeypatch.setattr(
        L, "_stack", ContextVar("test_layout", default=("NCHW",)))
    x = np.zeros((1, 2, 4, 4), np.float32)
    assert ConvHandle(x, 3, 1, 1, 2, 2).layout == "NCHW"
    with L.use_layout("NHWC"):
        xt = np.zeros((1, 4, 4, 2), np.float32)
        h = ConvHandle(xt, 3, 1, 1, 2, 2)
        assert h.layout == "NHWC"
        # explicit beats ambient
        assert ConvHandle(x, 3, 1, 1, 2, 2, layout="NCHW").layout == "NCHW"


def test_onnx_export_nhwc_raises_clearly(dev):
    """ONNX Conv is NCHW-only: exporting an NHWC-mode model must fail
    loudly, not emit silently wrong nodes."""
    from singa_tpu import sonnx
    from singa_tpu.models import resnet
    m = resnet.create_model(depth=18, num_classes=4, layout="NHWC")
    x = tensor.Tensor(data=np.random.randn(1, 3, 32, 32)
                      .astype(np.float32), device=dev)
    m.compile([x], is_train=True, use_graph=False)
    m.eval()
    with pytest.raises(NotImplementedError, match="NCHW"):
        sonnx.to_onnx(m, [x], "nhwc")


def test_onnx_export_s2d_stem_roundtrips(dev):
    """The space-to-depth stem is the SAME function as the 7x7/s2 conv,
    so it exports as a plain ONNX Conv and the reimport matches."""
    from singa_tpu import sonnx
    from singa_tpu.models import resnet
    d = device.create_cpu_device()
    d.SetRandSeed(2)
    m = resnet.create_model(depth=18, num_classes=4,
                            stem="space_to_depth")
    x = tensor.Tensor(data=np.random.RandomState(0)
                      .randn(1, 3, 32, 32).astype(np.float32), device=d)
    m.compile([x], is_train=True, use_graph=False)
    m.eval()
    want = tensor.to_numpy(m(x))
    om = sonnx.to_onnx(m, [x], "s2d")
    rep = sonnx.prepare(om, device="CPU")
    got = np.asarray(rep.run([x])[0].data)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
