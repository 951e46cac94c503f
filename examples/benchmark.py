"""ResNet-50 synthetic-data training throughput benchmark
(reference examples/cnn/benchmark.py:40-90, same metric:
``throughput = niters * batch * world / (end - start)``).

This is the interactive form of the harness; the repo-root ``bench.py``
runs the same measurement beside its other legs, on a TPU only, and
prints one JSON line.

Usage: python examples/benchmark.py [--bs 32] [--iters 100]
           [--warmup 8] [--depth 50] [--size 224] [-p float32|bfloat16]
           [--dist] [--verbosity 0] [--cpu]
"""

import argparse
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bs", type=int, default=32)
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--warmup", type=int, default=8)
    ap.add_argument("--depth", type=int, default=50,
                    choices=[18, 34, 50, 101, 152])
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("-p", "--precision", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--dist", action="store_true")
    ap.add_argument("--verbosity", "-v", type=int, default=0)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--layout", default="NCHW",
                    choices=["NCHW", "NHWC"])
    ap.add_argument("--stem", default="conv7",
                    choices=["conv7", "space_to_depth"])
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    from singa_tpu import device, opt, tensor
    from singa_tpu.models import resnet

    dev = device.create_cpu_device() if args.cpu \
        else device.create_tpu_device()
    dev.SetRandSeed(0)
    dev.SetVerbosity(args.verbosity)
    dev.SetSkipIteration(5)

    world = 1
    m = resnet.create_model(depth=args.depth, num_classes=1000,
                            num_channels=3, layout=args.layout,
                            stem=args.stem)
    sgd = opt.SGD(lr=0.1, momentum=0.9, weight_decay=1e-5)
    if args.dist:
        d = opt.DistOpt(sgd)
        world = d.world_size
        m.set_optimizer(d)
    else:
        m.set_optimizer(sgd)

    rng = np.random.RandomState(0)
    x = rng.randn(args.bs, 3, args.size, args.size).astype(np.float32)
    y = np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, args.bs)]
    tx = tensor.Tensor(data=x, device=dev, requires_grad=False)
    if args.precision == "bfloat16":
        tx = tx.as_type(jnp.bfloat16)
    ty = tensor.Tensor(data=y, device=dev, requires_grad=False)

    m.compile([tx], is_train=True, use_graph=True)

    def sync(t):
        # dispatch is asynchronous: the clock may only stop once the
        # last step's output exists
        jax.block_until_ready(t.data)

    # always at least one untimed step: it includes trace+compile, which
    # must not land inside the timed region
    for _ in range(max(1, args.warmup)):
        out, loss = m(tx, ty)
    sync(loss)

    start = time.time()
    for _ in range(args.iters):
        out, loss = m(tx, ty)
    sync(loss)
    end = time.time()

    titer = (end - start) / args.iters
    throughput = args.iters * args.bs * world / (end - start)
    print(f"\nThroughput = {throughput:.2f} per second", flush=True)
    print(f"TotalTime={end - start:.4f}", flush=True)
    print(f"Total={titer:.6f}", flush=True)
    dev.PrintTimeProfiling()


if __name__ == "__main__":
    main()
