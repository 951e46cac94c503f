"""Training from an on-disk record dataset that does not fit the model's
input pipeline in memory (reference examples/largedataset_cnn: data is
pre-encoded into record shards, then streamed through the prefetching
reader during training).

Phase 1 writes CIFAR-like samples into BinFile shards (the native
``SGTPREC0`` record runtime, native/singa_native.cc); phase 2 streams
them back with the C++ prefetch thread, batches, and trains a CNN —
multi-epoch, exercising reader rewind with prefetch intact.
"""

import argparse
import os
import struct
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def write_shards(root, n, shards, shape, rng):
    from singa_tpu.io import BinFileWriter
    c, h, w = shape
    paths = []
    per = n // shards
    for s in range(shards):
        path = os.path.join(root, f"shard-{s:03d}.bin")
        with BinFileWriter(path) as wtr:
            for i in range(per):
                label = rng.randint(0, 10)
                img = (rng.rand(c, h, w) * 255).astype(np.uint8)
                # record: 1 label byte + raw CHW bytes
                wtr.Write(f"s{s}-{i}",
                          struct.pack("B", label) + img.tobytes())
        paths.append(path)
    return paths


def stream_batches(paths, bs, shape, epochs):
    """Generator over (x, y) batches, streaming every shard per epoch
    through the native prefetching reader."""
    from singa_tpu.io import BinFileReader
    c, h, w = shape
    readers = [BinFileReader(p, prefetch=64) for p in paths]
    try:
        for _ in range(epochs):
            xs, ys = [], []
            for r in readers:
                r.SeekToFirst()
                while True:
                    rec = r.Read()
                    if rec is None:
                        break
                    _, value = rec
                    ys.append(value[0])
                    xs.append(np.frombuffer(value[1:], np.uint8)
                              .reshape(c, h, w))
                    if len(xs) == bs:
                        x = np.stack(xs).astype(np.float32) / 255.0 - 0.5
                        y = np.eye(10, dtype=np.float32)[ys]
                        xs, ys = [], []
                        yield x, y
            yield None, None          # epoch boundary
    finally:
        for r in readers:
            r.Close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--bs", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--size", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend (hermetic runs)")
    args = ap.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    from singa_tpu import device, opt, tensor
    from singa_tpu.models import cnn

    shape = (3, args.size, args.size)
    rng = np.random.RandomState(0)
    with tempfile.TemporaryDirectory() as root:
        paths = write_shards(root, args.n, args.shards, shape, rng)
        total = sum(os.path.getsize(p) for p in paths)
        print(f"wrote {args.shards} shards, {total / 1e6:.2f} MB")

        dev = device.create_cpu_device() if args.cpu \
            else device.create_tpu_device()
        dev.SetRandSeed(7)
        model = cnn.create_model(num_channels=3)
        model.set_optimizer(opt.SGD(lr=args.lr, momentum=0.9))
        x0 = np.zeros((args.bs, *shape), np.float32)
        tx0 = tensor.Tensor(data=x0, device=dev, requires_grad=False)
        model.compile([tx0], is_train=True, use_graph=True)

        epoch, losses, t0 = 0, [], time.time()
        for x, y in stream_batches(paths, args.bs, shape, args.epochs):
            if x is None:
                dt = time.time() - t0
                print(f"epoch {epoch}: loss {np.mean(losses):.4f} "
                      f"({len(losses) * args.bs / dt:.1f} img/s)")
                epoch, losses, t0 = epoch + 1, [], time.time()
                continue
            tx = tensor.Tensor(data=x, device=dev, requires_grad=False)
            ty = tensor.Tensor(data=y, device=dev, requires_grad=False)
            out, loss = model(tx, ty)
            losses.append(float(loss.data))


if __name__ == "__main__":
    main()
