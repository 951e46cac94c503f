"""Train a CNN-family model on CIFAR-10/100, MNIST, or synthetic data.

Parity with the reference's north-star command (examples/cnn/
train_cnn.py:97-263): ``python examples/train_cnn.py resnet cifar10``
trains with shuffling + batched random-crop/flip augmentation and prints
training loss/accuracy and evaluation accuracy per epoch. Differences
are TPU-idiomatic: augmentation and resize are vectorized over the batch
(no per-sample PIL loops), and training runs the traced/compiled graph
path.

Datasets are read from local files (no egress): see singa_tpu/datasets.py
for the accepted locations/formats. ``synthetic`` needs no files.

Usage: python examples/train_cnn.py [cnn|alexnet|resnet|xceptionnet|mlp]
           [cifar10|cifar100|mnist|synthetic] [--data-dir DIR]
           [--bs 64] [--epochs 10] [--lr 0.05]
           [-p float32|bfloat16|bf16_mixed] [--layout NCHW|NHWC]
           [--dist] [--dist-option plain|half|partialUpdate|
            sparseTopK|sparseThreshold] [--spars 0.05] [--cpu]
           [--mesh DxM] [--fsdp]
           [--bucket-mb 0] [--no-overlap] [--fused-optim]
           [--verbosity 0] [--npz path.npz]
           [--resilient] [--ckpt-dir ckpts_cnn] [--save-every 50]
           [--profile-every 0] [--anomaly-factor F]

``-p bf16_mixed`` trains under the mixed-precision compile policy
(``Model.compile(policy="bf16_mixed")``): fp32 master weights (what
checkpoints store) with bf16 conv/matmul compute and dynamic loss
scaling — the TPU production setting.

``--resilient`` runs the fault-tolerant driver instead of the bare
epoch loop: NaN/divergence guards (singa_tpu/resilience/guards.py)
skip bad steps on-device, training checkpoints every ``--save-every``
steps, SIGTERM/SIGINT preemption checkpoints synchronously and exits
75 for the restart supervisor, and a relaunched command resumes from
the newest restorable checkpoint automatically.
"""

import argparse
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("model", nargs="?", default="cnn",
                    choices=["cnn", "alexnet", "resnet", "xceptionnet",
                             "mlp"])
    ap.add_argument("data", nargs="?", default="synthetic",
                    choices=["cifar10", "cifar100", "mnist", "synthetic"])
    ap.add_argument("--data-dir", default=None,
                    help="directory holding the standard dataset files")
    ap.add_argument("--bs", "-b", type=int, default=64)
    ap.add_argument("--epochs", "-m", type=int, default=10)
    ap.add_argument("--iters", type=int, default=20,
                    help="synthetic-data batches per epoch")
    ap.add_argument("--max-batches", type=int, default=0,
                    help="cap train batches per epoch (0 = all); "
                         "lets CI run a real epoch quickly")
    ap.add_argument("--lr", "-l", type=float, default=0.05)
    ap.add_argument("-p", "--precision", default="float32",
                    choices=["float32", "bfloat16", "bf16_mixed"],
                    help="bf16_mixed compiles the model under the "
                         "mixed-precision policy (fp32 masters + loss "
                         "scaling, bf16 compute); bfloat16 is the "
                         "legacy pure-bf16 input cast")
    ap.add_argument("--dist", action="store_true")
    ap.add_argument("--dist-option", default="plain")
    ap.add_argument("--spars", type=float, default=0.05)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="GSPMD train mesh 'DxM' (data x model degrees, "
                         "e.g. 8x1) — the train step compiles as ONE "
                         "jitted program with NamedSharding in/out "
                         "(Model.compile(mesh=...)); XLA inserts the "
                         "grad collectives. Mirrors serve_transformer's "
                         "--mesh")
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO/FSDP on the GSPMD path: optimizer state "
                         "+ fp32 masters sharded over 'data', gathered "
                         "just-in-time inside the step (~Nx per-chip "
                         "optimizer-state headroom). Implies a default "
                         "data mesh when --mesh is not given")
    ap.add_argument("--bucket-mb", type=float, default=0.0,
                    help="with --dist: gradient-psum bucket size target "
                         "in MiB (DistOpt bucket_mb) — gradients "
                         "coalesce into size-targeted buckets, one "
                         "collective each, issued as backward produces "
                         "them so XLA hides them under remaining "
                         "backward compute; 0 = per-gradient streaming "
                         "psums (default). Read the win off "
                         "timeline_exposed_collective_seconds")
    ap.add_argument("--no-overlap", action="store_true",
                    help="with --dist: pin every gradient collective "
                         "behind the FULL backward (the measured "
                         "no-overlap baseline an A/B compares against)")
    ap.add_argument("--fused-optim", action="store_true",
                    help="route eligible optimizer updates through the "
                         "one-HBM-pass Pallas kernels "
                         "(ops/fused_optim.py; declines to the "
                         "reference path off-TPU)")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--no-augment", action="store_true")
    ap.add_argument("--verbosity", "-v", type=int, default=0)
    ap.add_argument("--layout", default="NCHW",
                    choices=["NCHW", "NHWC"],
                    help="conv-trunk activation layout (resnet only; "
                         "NHWC is the TPU lane-friendly form, applied "
                         "via ops.layout.use_layout inside the model)")
    ap.add_argument("--stem", default="conv7",
                    choices=["conv7", "space_to_depth"],
                    help="resnet stem: plain 7x7/s2 conv or its exact "
                         "space-to-depth reformulation")
    ap.add_argument("--npz", default=None,
                    help="npz with arrays x,y (overrides the data arg)")
    ap.add_argument("--telemetry", default=None, metavar="DIR",
                    help="write run telemetry into DIR: spans.jsonl "
                         "(live trace spans), metrics.json (registry "
                         "snapshot; feed to tools/metrics_dump.py) and "
                         "metrics.prom (Prometheus text)")
    ap.add_argument("--resilient", action="store_true",
                    help="train through the fault-tolerant driver "
                         "(checkpoint-restart + NaN guards + retry)")
    ap.add_argument("--ckpt-dir", default="ckpts_cnn",
                    help="checkpoint directory for --resilient")
    ap.add_argument("--save-every", type=int, default=50,
                    help="checkpoint interval (steps) for --resilient")
    ap.add_argument("--profile-every", type=int, default=0,
                    help="with --resilient: run every Nth step under a "
                         "profiler trace and refresh the "
                         "profile_fusion_* gauges (0 = off)")
    ap.add_argument("--anomaly-factor", type=float, default=None,
                    help="with --resilient: arm the step-time anomaly "
                         "sentinel at this spike factor (e.g. 3.0)")
    return ap


def _dump_telemetry(args, model):
    """End-of-run telemetry dump for --telemetry DIR: the metrics
    snapshot as JSON (the form tools/metrics_dump.py validates and
    converts) plus its Prometheus rendering; spans.jsonl has been
    streaming live since startup."""
    if not args.telemetry:
        return
    import json

    from singa_tpu.observability import export, metrics
    try:
        # enrich the snapshot with the step's XLA flop count (one AOT
        # re-lower, end of run — never on the step path)
        flops = model.step_flops(compute=True)
        if flops:
            metrics.default_registry().gauge(
                "train_step_flops",
                "XLA-counted FLOPs of one compiled step").set(flops)
    except Exception:
        pass
    snap = metrics.default_registry().snapshot()
    with open(f"{args.telemetry}/metrics.json", "w") as f:
        json.dump(snap, f)
    with open(f"{args.telemetry}/metrics.prom", "w") as f:
        f.write(export.render_prometheus(snap))
    print(f"telemetry written to {args.telemetry} "
          "(spans.jsonl, metrics.json, metrics.prom)", flush=True)


def main():
    args = build_parser().parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    from singa_tpu import datasets, device, metric, opt, tensor
    from singa_tpu import models

    if args.telemetry:
        import os

        from singa_tpu.observability import spans as obs_spans
        os.makedirs(args.telemetry, exist_ok=True)
        obs_spans.configure(jsonl_path=f"{args.telemetry}/spans.jsonl")

    dev = device.create_cpu_device() if args.cpu \
        else device.create_tpu_device()
    dev.SetRandSeed(0)
    dev.SetVerbosity(args.verbosity)

    # ---- data -----------------------------------------------------------
    num_classes = 10
    augment = False
    if args.npz:  # npz escape hatch
        blob = np.load(args.npz)
        x, y = blob["x"].astype(np.float32), blob["y"].astype(np.int32)
        n_val = max(1, len(x) // 10)
        train_x, train_y = x[:-n_val], y[:-n_val]
        val_x, val_y = x[-n_val:], y[-n_val:]
        num_classes = int(y.max()) + 1
    elif args.data == "synthetic":
        chans = 1 if args.model in ("cnn", "mlp") else 3
        size = {"cnn": 28, "mlp": 28, "alexnet": 224, "resnet": 224,
                "xceptionnet": 299}[args.model]
        rng = np.random.RandomState(0)
        n = args.bs * args.iters
        train_x = rng.randn(n, chans, size, size).astype(np.float32)
        train_y = rng.randint(0, 10, n).astype(np.int32)
        val_x, val_y = train_x[:args.bs], train_y[:args.bs]
    else:
        train_x, train_y, val_x, val_y = datasets.load(args.data,
                                                       args.data_dir)
        if args.data.startswith("cifar"):
            train_x, val_x = datasets.normalize_cifar(train_x, val_x)
            num_classes = 100 if args.data == "cifar100" else 10
            augment = not args.no_augment
        else:  # mnist
            train_x = np.asarray(train_x, np.float32) / 255.0
            val_x = np.asarray(val_x, np.float32) / 255.0

    chans = train_x.shape[1]

    # ---- model ----------------------------------------------------------
    factory = getattr(models, args.model)
    if args.model == "mlp":
        train_x = train_x.reshape(len(train_x), -1)
        val_x = val_x.reshape(len(val_x), -1)
        model = factory.create_model(data_size=train_x.shape[1],
                                     num_classes=num_classes)
        augment = False
    else:
        kw = {}
        if args.model == "resnet":
            kw = {"layout": args.layout, "stem": args.stem}
        model = factory.create_model(num_channels=chans,
                                     num_classes=num_classes, **kw)
    sgd = opt.SGD(lr=args.lr, momentum=0.9, weight_decay=1e-5,
                  fused=args.fused_optim)
    opt_obj = opt.DistOpt(sgd, bucket_mb=args.bucket_mb,
                          overlap=not args.no_overlap) \
        if args.dist else sgd
    if not args.dist and (args.bucket_mb or args.no_overlap):
        print("note: --bucket-mb/--no-overlap shape the gradient "
              "collectives and need --dist; ignored on a single "
              "replica", flush=True)
    if args.resilient:
        from singa_tpu.resilience import GuardedOptimizer
        # (Without --resilient, compile(policy="bf16_mixed") wraps a
        # GuardedOptimizer itself — this explicit wrap keeps the
        # trainer's rollback hooks on the same object.)
        if args.precision == "bf16_mixed":
            # same configuration compile() would pick for the policy
            from singa_tpu.mixed_precision import Policy
            opt_obj = GuardedOptimizer.for_policy(opt_obj,
                                                  Policy("bf16_mixed"))
        else:
            # legacy pure-bf16 keeps its underflow shield; f32 runs
            # pure-guard
            opt_obj = GuardedOptimizer(
                opt_obj, init_scale=2.0 ** 15
                if args.precision == "bfloat16" else 1.0)
    model.set_optimizer(opt_obj)

    # Under --dist every process feeds the FULL global batch and the
    # mesh shards it (shard_map splits dim 0; multi-process placement
    # assumes an SPMD-identical host copy) — so unlike the reference's
    # NCCL ranks (train_cnn.py:58-72) the dataset is NOT partitioned
    # per rank here. datasets.partition remains for host-local loaders.
    rank = model.optimizer.global_rank if args.dist else 0

    input_size = getattr(model, "input_size", None)
    need_resize = (getattr(model, "dimension", 4) == 4
                   and input_size is not None
                   and train_x.shape[-1] != input_size)

    def stage(x):
        if need_resize:
            # stays a device array: no host roundtrip before the step
            x = datasets.resize_batch(x, input_size)
        else:
            x = np.ascontiguousarray(x, np.float32)
        t = tensor.Tensor(data=x, device=dev, requires_grad=False)
        if args.precision == "bfloat16":
            # legacy pure-bf16: params follow the input dtype. Under
            # bf16_mixed the input stays f32 — the policy casts at the
            # op boundary inside the compiled step.
            import jax.numpy as jnp
            t = t.as_type(jnp.bfloat16)
        return t

    mesh_obj = None
    if args.mesh or args.fsdp:
        from singa_tpu.parallel import gspmd
        if args.mesh:
            d_, m_ = (int(v) for v in args.mesh.lower().split("x"))
        else:
            import jax
            d_, m_ = len(jax.devices()), 1
        mesh_obj = gspmd.train_mesh(data=d_, model=m_)
        print(f"GSPMD train mesh=data{d_}xmodel{m_}"
              f"{' fsdp=data' if args.fsdp else ''}", flush=True)

    tx = stage(train_x[:args.bs])
    model.compile([tx], is_train=True, use_graph=True,
                  policy="bf16_mixed" if args.precision == "bf16_mixed"
                  else None,
                  mesh=mesh_obj,
                  fsdp_axis="data" if args.fsdp else None)

    eye = np.eye(num_classes, dtype=np.float32)
    acc = metric.Accuracy()
    n_train = len(train_x) // args.bs
    if n_train == 0:
        sys.exit(f"dataset too small: {len(train_x)} train samples "
                 f"(per rank) < batch size {args.bs}")
    if args.max_batches:
        n_train = min(n_train, args.max_batches)
    n_val = len(val_x) // args.bs or 1

    if args.resilient:
        from singa_tpu.data import NumpyBatchIter
        from singa_tpu.resilience import ResilientTrainer

        class StagedBatches:
            """Checkpointable CNN input pipeline: sample selection via
            the stateless-shuffle NumpyBatchIter (its ``{epoch,
            position}`` state rides every --resilient checkpoint, so a
            preempted/rolled-back run resumes the EXACT sample stream),
            augmentation seeded by that state (the resumed stream
            reproduces the exact augmented batches too), device staging
            last."""

            def __init__(self, inner):
                self.inner = inner

            def state_dict(self):
                return self.inner.state_dict()

            def load_state_dict(self, state):
                self.inner.load_state_dict(state)

            def __iter__(self):
                for bx, by in self.inner:
                    if augment:
                        st = self.inner.state_dict()
                        arng = np.random.RandomState(
                            (st["epoch"] * 1_000_003 + st["position"])
                            % (2 ** 31))
                        bx = datasets.augment_crop_flip(bx, rng=arng)
                    yield (stage(bx),
                           tensor.Tensor(data=eye[by], device=dev,
                                         requires_grad=False))

        # --max-batches caps the EPOCH by slicing the sample set, so
        # the deterministic permutation stays over a fixed population
        pipeline = StagedBatches(NumpyBatchIter(
            train_x[:n_train * args.bs], train_y[:n_train * args.bs],
            args.bs, seed=1))
        model.train()
        trainer = ResilientTrainer(model, args.ckpt_dir,
                                   save_interval_steps=args.save_every,
                                   verbose=(rank == 0),
                                   profile_every=args.profile_every,
                                   anomaly_factor=args.anomaly_factor)
        summary = trainer.run(pipeline,
                              num_steps=args.epochs * n_train)
        if rank == 0:
            print(f"resilient run summary: {summary}", flush=True)
        model.eval()
        vaccs = [acc.evaluate(model(stage(val_x[b*args.bs:(b+1)*args.bs])),
                              val_y[b*args.bs:(b+1)*args.bs])
                 for b in range(n_val)]
        if rank == 0:
            print(f"Evaluation accuracy = {np.mean(vaccs):.6f}",
                  flush=True)
        dev.PrintTimeProfiling()
        _dump_telemetry(args, model)
        return

    from singa_tpu.observability import metrics as obs_metrics
    from singa_tpu.observability import spans as obs_spans
    m_step = obs_metrics.default_registry().histogram(
        "train_step_seconds", "wall-clock duration of one step")
    rng = np.random.RandomState(1)
    for epoch in range(args.epochs):
        if rank == 0:
            print(f"Starting Epoch {epoch}:", flush=True)
        idx = rng.permutation(len(train_x))
        t0, losses, accs = time.time(), [], []
        model.train()
        for b in range(n_train):
            sel = idx[b * args.bs:(b + 1) * args.bs]
            bx = train_x[sel]
            if augment:
                bx = datasets.augment_crop_flip(bx, rng=rng)
            tbx = stage(bx)
            tby = tensor.Tensor(data=eye[train_y[sel]], device=dev,
                                requires_grad=False)
            ts = time.perf_counter()
            with obs_spans.span("step", step=epoch * n_train + b):
                if args.dist and args.dist_option != "plain":
                    out, loss = model(tbx, tby, args.dist_option,
                                      args.spars)
                else:
                    out, loss = model(tbx, tby)
            m_step.observe(time.perf_counter() - ts)
            losses.append(float(loss.data))
            accs.append(acc.evaluate(out, train_y[sel]))
        if rank == 0:
            print(f"Training loss = {np.mean(losses):.6f}, "
                  f"training accuracy = {np.mean(accs):.6f}", flush=True)

        model.eval()
        vaccs = []
        for b in range(n_val):
            bx = val_x[b * args.bs:(b + 1) * args.bs]
            by = val_y[b * args.bs:(b + 1) * args.bs]
            out = model(stage(bx))
            vaccs.append(acc.evaluate(out, by))
        if rank == 0:
            print(f"Evaluation accuracy = {np.mean(vaccs):.6f}, "
                  f"Elapsed Time = {time.time() - t0:.3f}s", flush=True)

    dev.PrintTimeProfiling()
    _dump_telemetry(args, model)


if __name__ == "__main__":
    main()
