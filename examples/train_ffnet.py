"""Sequential FeedForwardNet training — the convenience-trainer path
(reference examples/cpp/cifar10/alexnet.cc drives
FeedForwardNet::Train/Evaluate, include/singa/model/feed_forward_net.h:
63-116; here the same capability through singa_tpu.net on synthetic
CIFAR-shaped data: add layers, compile with loss+metric, fit/evaluate).
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--bs", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--size", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend (hermetic runs)")
    args = ap.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    from singa_tpu import device, layer, metric, net, opt, tensor

    dev = device.create_cpu_device() if args.cpu \
        else device.create_tpu_device()
    dev.SetRandSeed(7)

    # synthetic separable data: class = argmax of a fixed projection
    rng = np.random.RandomState(0)
    x = rng.randn(args.n, 3, args.size, args.size).astype(np.float32)
    w = rng.randn(3 * args.size * args.size, 10)
    yi = np.argmax(x.reshape(args.n, -1) @ w, axis=1)
    y = np.eye(10, dtype=np.float32)[yi]

    model = net.FeedForwardNet()
    model.add(layer.Conv2d(16, 3, padding=1))
    model.add(layer.ReLU())
    model.add(layer.MaxPool2d(2, 2))
    model.add(layer.Conv2d(32, 3, padding=1))
    model.add(layer.ReLU())
    model.add(layer.MaxPool2d(2, 2))
    model.add(layer.Flatten())
    model.add(layer.Linear(10))

    tx = tensor.Tensor(data=x[:args.bs], device=dev, requires_grad=False)
    model.compile_net(opt.SGD(lr=args.lr, momentum=0.9), [tx],
                      loss=layer.SoftMaxCrossEntropy(),
                      metric=metric.Accuracy())
    model.fit(x, y, batch_size=args.bs, epochs=args.epochs, dev=dev)
    loss, acc = model.evaluate(x, y, batch_size=args.bs, dev=dev)
    print(f"final eval: loss {loss:.4f} accuracy {acc:.3f}")


if __name__ == "__main__":
    main()
