"""Elastic multi-host training: cluster health + two-phase checkpoints +
world-size-elastic resume.

The reference's failure handling is print-and-exit
(include/singa/io/communicator.h:40-67). This example runs the full
elastic contract instead:

- every rank joins a control-plane cluster (heartbeats, failing-fast
  barriers — ``singa_tpu/resilience/cluster.py``);
- checkpoints are TWO-PHASE: each rank writes its shard, ACKs, and only
  after every ACK does the coordinator publish the commit marker — a
  rank that dies mid-save can never leave a checkpoint that only looks
  committed;
- a lost rank exits the survivors with code 75 (the supervisor
  contract); relaunching with a SMALLER ``--world`` resumes from the
  last *committed* step, optimizer momentum included, with the batch
  accounting rescaled from the manifest (per-replica batch kept).

Try it (single host — world of one, same code path)::

    python examples/train_elastic.py --cpu --steps 40 --crash-at 17
    python examples/train_elastic.py --cpu --steps 40      # resumes

Two hosts, then lose one and restart smaller::

    python examples/train_elastic.py --cpu --world 2 --steps 40 \
        --die-at 11 --die-rank 1            # rank 1 hard-dies at step 11
    # survivors exit 75; restart at the surviving size:
    python examples/train_elastic.py --cpu --world 1 --steps 40

``tools/chaos_smoke.py`` drives these scenarios end-to-end under a
wall-clock budget.

The ``--world N`` launcher starts every rank on THIS host, so it is for
``--cpu`` runs: on an accelerator host each rank would claim every chip
(a chip belongs to one process) and the launcher refuses. Across real
hosts, start one rank per host with ``--rank`` and ``--coordinator``.
"""

import argparse
import json
import os
import subprocess
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_model(lr):
    from singa_tpu import layer, model, opt

    class MLP(model.Model):
        def __init__(self):
            super().__init__()
            self.fc1 = layer.Linear(64)
            self.relu = layer.ReLU()
            self.fc2 = layer.Linear(10)
            self.loss_fn = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.fc2(self.relu(self.fc1(x)))

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = self.loss_fn(out, y)
            self.optimizer(loss)
            return out, loss

    m = MLP()
    m.set_optimizer(opt.SGD(lr=lr, momentum=0.9))
    return m


def dump_state(model, path):
    """Host-copy every model + optimizer state to one npz — the
    bit-identity probe the chaos suite compares across restarts."""
    states = {f"model/{k}": np.asarray(getattr(v, "data", v))
              for k, v in model.get_states().items()}
    for k, v in model.optimizer.get_states().items():
        states[f"optimizer/{k}"] = np.asarray(getattr(v, "data", v))
    np.savez(path, **states)


def run_rank(args):
    from singa_tpu import device, tensor
    from singa_tpu.checkpoint import latest_manifest
    from singa_tpu.data import NumpyBatchIter
    from singa_tpu.parallel import communicator, mesh as mesh_mod
    from singa_tpu.resilience import ClusterConfig, FaultPlan, make_cluster
    from singa_tpu.resilience.runtime import ResilientTrainer

    # -- elastic accounting: manifest first, shapes second ---------------
    manifest = latest_manifest(args.dir)
    per_bs, global_bs = args.bs, args.bs * args.world
    if manifest is not None:
        per, gb = communicator.rescale_batch(manifest, args.world)
        if per is not None:
            per_bs, global_bs = per, gb
        if int(manifest.get("world", args.world)) != args.world:
            print(f"rank {args.rank}: elastic restart — checkpoint world "
                  f"{manifest.get('world')} -> {args.world}, global "
                  f"batch {manifest.get('global_batch')} -> {global_bs}",
                  flush=True)

    # the data axis absorbs any device-count change; axis NAMES stay
    # fixed so checkpointed shardings re-land on the new degrees. The
    # CLUSTER world change is reported above — elastic_mesh's
    # saved_world compares per-process DEVICE degrees, a different
    # quantity (1 per process here), so it is not passed.
    mesh = mesh_mod.elastic_mesh()
    if args.mesh:
        # explicit GSPMD train mesh (data x model): same axis names as
        # the elastic mesh, so checkpoint shardings re-land unchanged
        from singa_tpu.parallel import gspmd
        d_, m_ = (int(v) for v in args.mesh.lower().split("x"))
        mesh = gspmd.train_mesh(data=d_, model=m_)
    communicator.set_mesh(mesh)
    use_gspmd = bool(args.mesh or args.fsdp)

    faults = FaultPlan()
    if args.die_at >= 0 and args.rank == args.die_rank:
        faults.kill_rank(args.die_at)
    if args.kill_before_ack >= 0 and args.rank == args.die_rank:
        faults.kill_before_ack(args.kill_before_ack)
    if args.diverge_at >= 0 and args.rank == args.diverge_rank:
        # silent SDC on this rank: state forks with no exception — only
        # the cross-replica fingerprint can see it
        faults.diverge_at(args.diverge_at, times=args.diverge_times)

    cluster = make_cluster(
        args.rank, args.world, args.coordinator,
        ClusterConfig(heartbeat_interval=args.hb_interval,
                      straggler_after=3 * args.hb_interval,
                      dead_after=args.dead_after),
        faults=faults)

    dev = device.create_cpu_device() if args.cpu \
        else device.create_tpu_device()
    dev.SetRandSeed(0)
    rng = np.random.RandomState(0)
    n = max(global_bs * 4, 64)
    x = rng.randn(n, 32).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]
    tx = tensor.Tensor(data=x[:global_bs], device=dev,
                       requires_grad=False)

    m = build_model(args.lr)
    m.compile([tx], is_train=True, use_graph=True,
              mesh=mesh if use_gspmd else None,
              fsdp_axis="data" if args.fsdp else None)
    if use_gspmd:
        print(f"rank {args.rank}: GSPMD train "
              f"mesh=data{mesh.shape['data']}xmodel{mesh.shape['model']}"
              f"{' fsdp=data' if args.fsdp else ''}", flush=True)

    trainer = ResilientTrainer(
        m, args.dir, max_to_keep=args.keep,
        save_interval_steps=args.save_every, cluster=cluster,
        faults=faults, commit_timeout=args.commit_timeout,
        start_barrier_timeout=args.start_timeout,
        fingerprint_every=args.fingerprint_every,
        max_divergence_rollbacks=args.max_divergence_rollbacks,
        manifest_extra={"per_replica_batch": per_bs,
                        "global_batch": global_bs},
        aot=args.aot_dir or None)

    if args.dump_restored:
        # bit-identity probe: what does the last COMMITTED checkpoint
        # restore to? (run() restores again itself — deterministic)
        start = trainer.mgr.restore_latest(m)
        dump_state(m, args.dump_restored)
        print(f"rank {args.rank}: dumped restored state of step "
              f"{start - 1} to {args.dump_restored}", flush=True)

    if args.dump_sample_ids:
        os.makedirs(args.dump_sample_ids, exist_ok=True)

    def on_step(step, out):
        if args.dump_on_save and trainer.mgr.latest_step() == step:
            dump_state(m, os.path.join(args.dump_on_save,
                                       f"state_step{step}.npz"))
        if args.dump_sample_ids and batches.last_batch_ids is not None:
            # one file per step, overwritten on a re-run: the dir holds
            # the FINAL timeline's per-step sample ids — what the
            # data-resume chaos scenario asserts bit-identical to a
            # fault-free run's
            np.save(os.path.join(args.dump_sample_ids,
                                 f"ids_step{step}.npy"),
                    batches.last_batch_ids)
        if step == args.crash_at:
            trainer.mgr.wait()
            print(f"simulated crash at step {step}", flush=True)
            sys.exit(42)

    # checkpointable stream: state ({epoch, position}) rides every
    # checkpoint, so kills/rollbacks/elastic restarts rewind it in
    # lockstep with the tensors (exactly-once sample consumption)
    batches = NumpyBatchIter(x, y, batch_size=global_bs, seed=0)
    try:
        summary = trainer.run(batches, num_steps=args.steps,
                              step_callback=on_step)
    finally:
        cluster.close()
    print(f"rank {args.rank}: summary "
          f"{json.dumps({k: v for k, v in summary.items() if k != 'cluster'})}",
          flush=True)
    print("training complete", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="ckpts")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--save-every", type=int, default=5)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--bs", type=int, default=32,
                    help="PER-REPLICA batch size (the elastic invariant)")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="GSPMD train mesh 'DxM' (data x model): compile "
                         "the step as ONE jitted NamedSharding program "
                         "instead of the shard_map driver")
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO/FSDP over 'data' on the GSPMD path "
                         "(optimizer state + masters sharded, gathered "
                         "just-in-time)")
    ap.add_argument("--world", type=int, default=1)
    ap.add_argument("--rank", type=int, default=None,
                    help="this process's rank; omit to spawn all ranks")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of rank 0's cluster listener")
    ap.add_argument("--aot-dir", default="",
                    help="cold-start elimination (singa_tpu.aot): "
                         "persistent compile cache + exported train-"
                         "step executable under this dir; a restart "
                         "deserializes instead of retracing")
    ap.add_argument("--hb-interval", type=float, default=0.25)
    ap.add_argument("--dead-after", type=float, default=2.5)
    ap.add_argument("--commit-timeout", type=float, default=30.0)
    ap.add_argument("--start-timeout", type=float, default=30.0)
    ap.add_argument("--crash-at", type=int, default=-1,
                    help="soft crash (exit 42) after this step commits")
    ap.add_argument("--die-at", type=int, default=-1,
                    help="hard-kill --die-rank just before this step")
    ap.add_argument("--die-rank", type=int, default=1)
    ap.add_argument("--kill-before-ack", type=int, default=-1,
                    help="hard-kill --die-rank after this step's shard "
                         "is written but before its commit ACK")
    ap.add_argument("--fingerprint-every", type=int, default=0,
                    help="cross-replica state fingerprint cadence "
                         "(0 = off, the zero-overhead default)")
    ap.add_argument("--max-divergence-rollbacks", type=int, default=2,
                    help="quarantine-rollbacks before exit 76")
    ap.add_argument("--diverge-at", type=int, default=-1,
                    help="silently perturb --diverge-rank's params at "
                         "this step's fingerprint check (SDC injection)")
    ap.add_argument("--diverge-rank", type=int, default=1)
    ap.add_argument("--diverge-times", type=int, default=1,
                    help="how many times the divergence re-fires "
                         "(>max-divergence-rollbacks forces exit 76)")
    ap.add_argument("--dump-on-save", default="",
                    help="dir for per-committed-step state npz dumps")
    ap.add_argument("--dump-restored", default="",
                    help="npz path for the state right after restore")
    ap.add_argument("--dump-sample-ids", default="",
                    help="dir for per-step consumed-sample-id npy dumps "
                         "(the exactly-once probe)")
    args = ap.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    if args.world > 1 and args.coordinator is None:
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        args.coordinator = f"127.0.0.1:{s.getsockname()[1]}"
        s.close()

    if args.rank is not None or args.world <= 1:
        args.rank = args.rank or 0
        run_rank(args)
        return

    # launcher mode: one subprocess per rank; exit code is rank 0's
    # (the supervisor contract — 75 means "restart me, maybe smaller")
    if not args.cpu:
        raise SystemExit(
            f"train_elastic: the launcher starts all {args.world} ranks "
            "on this one host; without --cpu each of them would claim "
            "every accelerator chip (a chip belongs to one process). "
            "Pass --cpu, or start one rank per host with "
            "--rank/--coordinator.")
    procs = []
    for r in range(args.world):
        cmd = [sys.executable, os.path.abspath(__file__), "--rank",
               str(r)]
        for k, v in vars(args).items():
            if k == "rank" or isinstance(v, bool) or v is None:
                continue
            cmd += [f"--{k.replace('_', '-')}", str(v)]
        if args.cpu:
            cmd.append("--cpu")
        if args.fsdp:   # bools are skipped above; forward explicitly
            cmd.append("--fsdp")
        procs.append(subprocess.Popen(cmd))
    rcs = [p.wait() for p in procs]
    print(f"launcher: rank exit codes {rcs}", flush=True)
    sys.exit(rcs[0])


if __name__ == "__main__":
    main()
