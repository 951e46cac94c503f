"""Multi-process data-parallel training — one OS process per host,
bootstrapped with ``jax.distributed`` (the reference launches one process
per GPU with python multiprocessing + an NcclIdHolder,
examples/cnn/train_multiprocess.py, or mpirun, examples/cnn/train_mpi.py;
here the coordinator address plays the NCCL-id role and XLA collectives
replace the NCCL ring).

Run standalone (spawns the workers itself):

    python examples/train_multiprocess.py --procs 2 --steps 5

or launch one rank per host, SPMD-style:

    python examples/train_multiprocess.py --rank 0 --procs 2 \
        --coordinator host0:29500 &
    python examples/train_multiprocess.py --rank 1 --procs 2 \
        --coordinator host0:29500

On machines without accelerators each process simulates a host with
``--devices-per-proc`` CPU devices, so the full multi-host code path —
coordination service, global mesh, cross-process psum — runs anywhere.

The standalone launcher is CPU-only: it starts every rank on THIS host,
and on a TPU host each rank would claim every chip — a chip belongs to
one process, so all ranks but the first fail or hang. One process drives
all the chips of a host (``opt.DistOpt`` / ``compile(mesh=...)``); with
``--platform tpu`` start one rank per host yourself with ``--rank``.
"""

import argparse
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_rank(args):
    if args.platform == "cpu":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count="
            f"{args.devices_per_proc}")
        import jax
        jax.config.update("jax_platforms", "cpu")
    else:
        import jax

    import numpy as np
    from jax.sharding import PartitionSpec as P
    from singa_tpu import device, layer, model as model_mod, opt, tensor
    from singa_tpu.models import cnn
    from singa_tpu.parallel import communicator, mesh as mesh_mod

    # rank exchange / process bootstrap (reference communicator.cc:73-103)
    communicator.init_process(
        communicator.NcclIdHolder(args.coordinator),
        rank=args.rank, world=args.procs)
    n_local = jax.local_device_count()
    n_global = jax.device_count()
    print(f"rank {args.rank}/{args.procs}: {n_local} local / "
          f"{n_global} global devices", flush=True)

    rng = np.random.RandomState(0)
    gb = args.bs * n_global
    if args.moe:
        # expert-parallel across HOSTS: the 'expert' axis is made the
        # OUTERMOST mesh axis so (with process-major device order) each
        # process owns one expert group — expert weights genuinely shard
        # cross-process, and save_states gathers them over the process
        # group
        from singa_tpu.parallel.moe import MoEFFN

        class MoENet(model_mod.Model):
            def __init__(self):
                super().__init__()
                self.ffn = MoEFFN(args.moe, 32, top_k=2,
                                  capacity_factor=4.0)
                self.loss_fn = layer.MeanSquareError()

            def forward(self, xx):
                return self.ffn(xx)

            def train_one_batch(self, xx, yy):
                o = self.forward(xx)
                ls = self.loss_fn(o, yy)
                self.optimizer(ls)
                return o, ls

        mesh_cfg = mesh_mod.MeshConfig(
            expert=args.procs,
            axis_order=("expert", "data", "seq", "pipe", "model"))
        dist_kw = {"reduce_axes": ("data", "expert")}
        make_model = MoENet
        x = rng.randn(gb, 16).astype(np.float32)
        y = rng.randn(gb, 16).astype(np.float32)
    else:
        mesh_cfg = mesh_mod.MeshConfig()
        dist_kw = {"world_size": n_global}
        make_model = lambda: cnn.create_model(num_channels=1)  # noqa: E731
        x = rng.randn(gb, 1, 28, 28).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, gb)]

    mesh = mesh_mod.make_mesh(jax.devices(), mesh_cfg)
    communicator.set_mesh(mesh)
    dev = device.Device(jax.local_devices()[0])
    dev.SetRandSeed(7)
    model = make_model()
    dist = opt.DistOpt(opt.SGD(lr=args.lr, momentum=0.9), **dist_kw)
    dist.communicator.mesh = mesh
    model.set_optimizer(dist)
    if args.moe:
        model.input_specs = [P(("data", "expert")),
                             P(("data", "expert"))]

    # SPMD convention: every process feeds the same GLOBAL batch; the
    # placement inside the compiled step keeps only the local shard
    tx = tensor.Tensor(data=x, device=dev, requires_grad=False)
    ty = tensor.Tensor(data=y, device=dev, requires_grad=False)

    model.compile([tx], is_train=True, use_graph=True)
    model(tx, ty)                       # materialise + compile
    t0 = time.time()
    loss = None
    for _ in range(args.steps):
        out, loss = model(tx, ty)
    lv = float(np.asarray(jax.device_get(loss.data)))
    dt = time.time() - t0
    print(f"rank {args.rank}: {args.steps} steps, loss {lv:.4f}, "
          f"{args.steps * gb / dt:.1f} img/s global", flush=True)

    if args.save:
        # collective: every rank participates in the cross-process gather
        # of host-sharded state; each writes its own (identical) copy
        path = f"{args.save}.rank{args.rank}.zip"
        model.save_states(path)
        print(f"rank {args.rank}: saved {path}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rank", type=int, default=None,
                    help="this process's rank; omit to spawn all ranks")
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--coordinator", default=None,
                    help="host:port of rank 0's coordination service; "
                         "launcher mode defaults to an ephemeral free port")
    ap.add_argument("--devices-per-proc", type=int, default=2)
    ap.add_argument("--platform", default="cpu",
                    choices=["cpu", "tpu"])
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--bs", type=int, default=8,
                    help="per-device batch size")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--moe", type=int, default=0,
                    help="experts for a cross-host expert-parallel MoE "
                         "run (0 = data-parallel CNN)")
    ap.add_argument("--save", default="",
                    help="checkpoint path prefix written after "
                         "training (collective across ranks)")
    args = ap.parse_args()

    if args.rank is not None:
        if args.coordinator is None:
            args.coordinator = "127.0.0.1:29512"
        run_rank(args)
        return

    if args.platform != "cpu":
        raise SystemExit(
            "train_multiprocess: the standalone launcher starts all "
            f"{args.procs} ranks on this one host, and with --platform "
            f"{args.platform} each of them would claim every chip (a "
            "chip belongs to one process). Start one rank per host "
            "with --rank/--coordinator, or drive all of one host's "
            "chips from a single process (opt.DistOpt, "
            "compile(mesh=...)).")

    if args.coordinator is None:
        # ephemeral free port so concurrent runs / stale workers on the
        # default port can't collide
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        args.coordinator = f"127.0.0.1:{s.getsockname()[1]}"
        s.close()

    # launcher mode: one subprocess per rank (the reference's
    # multiprocessing.Process loop, train_multiprocess.py)
    procs = []
    for r in range(args.procs):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--rank", str(r)]
        for k in ("procs", "coordinator", "devices_per_proc", "platform",
                  "steps", "bs", "lr", "moe", "save"):
            cmd += [f"--{k.replace('_', '-')}", str(getattr(args, k))]
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(cmd, env=env))
    rcs = [p.wait() for p in procs]
    if any(rcs):
        raise SystemExit(f"worker failure: rcs={rcs}")


if __name__ == "__main__":
    main()
