#!/usr/bin/env python
"""Operate the cold-start machinery (``singa_tpu.aot``): prebuild a
warm cache + AOT artifacts for a model spec, inspect artifact
manifests, GC the persistent compile cache, scrub artifacts at rest.

Commands::

    python tools/aot_cache.py prebuild --aot-dir DIR --spec lm \
        [--vocab 64 --d-model 32 --heads 2 --layers 1 \
         --slots 4 --max-len 64 --prefill-len 16] [--policy NAME]
    python tools/aot_cache.py prebuild --aot-dir DIR --spec mlp \
        [--bs 8 --features 32 --classes 10]
    python tools/aot_cache.py inspect --aot-dir DIR
    python tools/aot_cache.py scrub --aot-dir DIR [--delete]
    python tools/aot_cache.py stats --cache-dir DIR
    python tools/aot_cache.py gc --cache-dir DIR --budget-mb N
    python tools/aot_cache.py --selftest

``prebuild`` is the replica-fleet warm-up: compile the spec's programs
ONCE on a build box (serialized executables + digest-verified
manifests under ``<aot-dir>``; the persistent compile cache is
populated where ``singa_tpu.aot.cache``'s rule puts it — set
``JAX_COMPILATION_CACHE_DIR`` on the build box and on the replicas to
a directory that ships with the checkpoint), and every restart/spin-up
deserializes in seconds instead of recompiling. ``spec lm`` prebuilds the serving prefill/decode
programs of a TransformerLM (mirrors ``examples/serve_transformer.py``
's flags); ``spec mlp`` prebuilds a train step.

``--selftest`` proves the whole contract on CPU: export → inspect →
warm reload → corrupt a byte → digest refusal + quarantine → version
refusal on a doctored manifest → cache LRU GC round-trip. Exit 0 and
``selftest: OK`` on success (wired into ``tests/test_examples.py``
like the other tool selftests).

Exit codes: 0 clean; 1 corrupt artifacts found by ``scrub`` (cron-able
like ``tools/scrub_checkpoints.py``); 2 usage/spec errors.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cpu():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _build_lm_engine(args):
    import numpy as np

    from singa_tpu import device, tensor
    from singa_tpu.models import transformer
    dev = device.create_cpu_device() if args.cpu \
        else device.create_tpu_device()
    dev.SetRandSeed(0)
    model = transformer.TransformerLM(
        args.vocab, d_model=args.d_model, n_heads=args.heads,
        n_layers=args.layers, max_len=args.max_len, tp=False)
    model.eval()
    model(tensor.Tensor(
        data=np.zeros((1, args.prefill_len), np.float32), device=dev,
        requires_grad=False))
    return model.compile_serving(
        slots=args.slots, max_len=args.max_len,
        prefill_len=args.prefill_len, policy=args.policy,
        compile_cache=True)


def _build_mlp_step(args):
    import numpy as np

    from singa_tpu import device, layer, model as model_mod, opt, tensor

    class MLP(model_mod.Model):
        def __init__(self):
            super().__init__()
            self.fc1 = layer.Linear(args.features)
            self.relu = layer.ReLU()
            self.fc2 = layer.Linear(args.classes)
            self.loss_fn = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.fc2(self.relu(self.fc1(x)))

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = self.loss_fn(out, y)
            self.optimizer(loss)
            return out, loss

    dev = device.create_cpu_device() if args.cpu \
        else device.create_tpu_device()
    dev.SetRandSeed(0)
    rng = np.random.RandomState(0)
    tx = tensor.Tensor(data=rng.randn(args.bs, args.features)
                       .astype(np.float32), device=dev,
                       requires_grad=False)
    ty = tensor.Tensor(
        data=np.eye(args.classes, dtype=np.float32)[
            rng.randint(0, args.classes, args.bs)],
        device=dev, requires_grad=False)
    m = MLP()
    m.set_optimizer(opt.SGD(lr=0.05, momentum=0.9))
    m.compile([tx], is_train=True, use_graph=True,
              policy=args.policy, compile_cache=True)
    m(tx, ty)       # materialise + compile the step
    return m


def cmd_prebuild(args):
    from singa_tpu.aot import export as aot_export
    aot_dir = os.path.abspath(args.aot_dir)
    store = aot_export.AotStore(aot_dir)
    if args.spec == "lm":
        engine = _build_lm_engine(args)
        docs = engine.export_aot(store)
        engine.stop()
    elif args.spec == "mlp":
        model = _build_mlp_step(args)
        docs = {"train_step":
                aot_export.export_train_step(model, store)}
    else:
        print(f"unknown --spec {args.spec!r} (lm | mlp)",
              file=sys.stderr)
        return 2
    from singa_tpu.aot import cache as aot_cache
    st = aot_cache.stats()
    if getattr(args, "json", False):
        # machine-readable doc: an autoscaler's spawn path (or CI)
        # parses this to assert the artifacts it will warm-admit
        # against actually exist before a replica ever boots
        print(json.dumps({
            "aot_dir": aot_dir, "spec": args.spec,
            "programs": {p: {"digest": d["digest"], "env": d["env"]}
                         for p, d in docs.items()},
            "cache": {"entries": st["entries"], "bytes": st["bytes"],
                      "directory": st["directory"]},
        }, indent=1, sort_keys=True))
        return 0
    for program, doc in docs.items():
        print(f"[aot] exported {program}: {doc['digest']} "
              f"(jax {doc['env']['jax']}, "
              f"{doc['env']['platform']}/{doc['env']['device_kind']})")
    print(f"[aot] compile cache: {st['entries']} entries, "
          f"{st['bytes']} bytes under {st['directory']}")
    return 0


def cmd_inspect(args):
    from singa_tpu.aot.export import AotStore
    docs = AotStore(os.path.abspath(args.aot_dir)).inspect()
    if args.json:
        print(json.dumps(docs, indent=1, sort_keys=True))
        return 0
    if not docs:
        print("[aot] no artifacts")
        return 0
    for program, doc in sorted(docs.items()):
        if "error" in doc:
            print(f"[aot] {program}: UNREADABLE ({doc['error']})")
            continue
        env = doc.get("env", {})
        print(f"[aot] {program}: {doc.get('digest')} | jax "
              f"{env.get('jax')}/{env.get('jaxlib')} | "
              f"{env.get('platform')}/{env.get('device_kind')} x"
              f"{env.get('n_devices')} | policy "
              f"{(doc.get('policy') or {}).get('name', None)} | "
              f"donation {doc.get('donation')}")
    return 0


def cmd_scrub(args):
    from singa_tpu.aot.export import AotStore
    report = AotStore(os.path.abspath(args.aot_dir)).scrub(
        delete=args.delete)
    bad = sum(1 for s in report.values() if s != "ok")
    if args.json:
        print(json.dumps({"report": report, "bad": bad,
                          "deleted": args.delete}))
    else:
        for program, status in sorted(report.items()):
            print(f"[aot] {program}: {status}")
        print(f"[aot] {bad} corrupt/unreadable artifact(s)"
              + (" (quarantined)" if args.delete and bad else ""))
    return 1 if bad else 0


def cmd_stats(args):
    from singa_tpu.aot import cache as aot_cache
    print(json.dumps(aot_cache.stats(os.path.abspath(args.cache_dir))))
    return 0


def cmd_gc(args):
    from singa_tpu.aot import cache as aot_cache
    rep = aot_cache.gc(
        aot_cache.CachePolicy(os.path.abspath(args.cache_dir)),
        budget_bytes=int(args.budget_mb * (1 << 20)))
    print(json.dumps(rep))
    return 0


def selftest():
    """export → inspect → warm reload → corrupt → detect+quarantine →
    version refusal → GC round-trip, all on CPU."""
    import tempfile
    import warnings

    _cpu()
    # hermetic: the GC leg prunes its own temp cache, never the
    # directory the environment placed (jax reads this at import)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from singa_tpu.aot import cache as aot_cache
    from singa_tpu.aot import manifest as aot_manifest
    from singa_tpu.aot.export import AotStore
    from singa_tpu.aot.manifest import AotMismatch

    root = tempfile.mkdtemp(prefix="aot_selftest_")
    ok = lambda what: print(f"  ok: {what}")         # noqa: E731

    # 1) export a compiled program + inspect its manifest
    store = AotStore(os.path.join(root, "aot"))

    def step(state, x):
        return [s + x.sum() for s in state], x * 2.0

    avals = ([jax.ShapeDtypeStruct((8,), np.float32)],
             jax.ShapeDtypeStruct((8,), np.float32))
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        *avals).compile()
    doc = store.save_program("train_step", compiled, avals=avals,
                             donate_argnums=(0,))
    assert doc["digest"].startswith("crc32:"), doc
    shown = store.inspect()["train_step"]
    assert shown["env"]["jax"] == jax.__version__, shown
    ok("export + manifest inspect")

    # 2) warm reload runs, bit-equal to the live program
    fn, _ = store.load_program("train_step", avals=avals,
                               donate_argnums=(0,))
    x = jnp.arange(8.0)
    (live_state, live_y) = jax.jit(step, donate_argnums=(0,))(
        [jnp.ones(8)], x)
    (aot_state, aot_y) = fn([jnp.ones(8)], x)
    assert np.array_equal(np.asarray(live_y), np.asarray(aot_y))
    assert np.array_equal(np.asarray(live_state[0]),
                          np.asarray(aot_state[0]))
    ok("warm reload, bit-equal output")

    # 3) corrupt one payload byte → digest refusal + quarantine
    p = store._bin_path("train_step")
    blob = bytearray(open(p, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(p, "wb").write(bytes(blob))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        refused, _ = store.try_load_program(
            "train_step", avals=avals, donate_argnums=(0,))
    assert refused is None
    assert store.outcomes["train_step"] == "refused:digest", \
        store.outcomes
    assert "train_step" not in store.programs()
    qdir = os.path.join(store.directory, store.QUARANTINE_DIR)
    assert any("digest" in n for n in os.listdir(qdir))
    ok("corrupt byte → digest refusal, artifact quarantined")

    # 4) wrong jax version stamp → typed version refusal
    doc2 = store.save_program("train_step", compiled, avals=avals,
                              donate_argnums=(0,))
    doc2 = dict(doc2)
    doc2["env"] = dict(doc2["env"], jax="0.0.0-selftest")
    aot_manifest.write(store._manifest_path("train_step"), doc2)
    try:
        store.load_program("train_step", avals=avals,
                           donate_argnums=(0,))
        raise SystemExit("selftest FAILED: stale version accepted")
    except AotMismatch as e:
        assert e.reason == "version", e
    ok("doctored version stamp → typed refusal")

    # 5) persistent-cache GC: populate, then LRU-prune to a budget
    cdir = os.path.join(root, "xla-cache")
    aot_cache.install(aot_cache.CachePolicy(cdir))
    try:
        for k in range(3):
            jax.jit(lambda v, k=k: jnp.sin(v) * (k + 1))(
                jnp.ones(4)).block_until_ready()
        st = aot_cache.stats(cdir)
        assert st["entries"] >= 3, st
        rep = aot_cache.gc(aot_cache.CachePolicy(cdir),
                           budget_bytes=st["bytes"] // 2)
        assert rep["removed"] >= 1 and rep["bytes"] <= st["bytes"] // 2, \
            rep
        ok(f"cache GC pruned {rep['removed']} entries to budget")
    finally:
        aot_cache.uninstall()

    print("selftest: OK")
    return 0


def main():
    ap = argparse.ArgumentParser(
        description="prebuild / inspect / gc / scrub the AOT "
                    "cold-start artifacts")
    ap.add_argument("--selftest", action="store_true",
                    help="CPU round-trip proof of the whole contract")
    sub = ap.add_subparsers(dest="cmd")

    pb = sub.add_parser("prebuild", help="compile a spec and export "
                        "its executables + warm the compile cache")
    pb.add_argument("--aot-dir", required=True)
    pb.add_argument("--spec", default="lm", choices=("lm", "mlp"))
    pb.add_argument("--policy", default=None)
    pb.add_argument("--cpu", action="store_true")
    pb.add_argument("--vocab", type=int, default=64)
    pb.add_argument("--d-model", type=int, default=32)
    pb.add_argument("--heads", type=int, default=2)
    pb.add_argument("--layers", type=int, default=1)
    pb.add_argument("--slots", type=int, default=4)
    pb.add_argument("--max-len", type=int, default=64)
    pb.add_argument("--prefill-len", type=int, default=16)
    pb.add_argument("--bs", type=int, default=8)
    pb.add_argument("--features", type=int, default=32)
    pb.add_argument("--classes", type=int, default=10)
    pb.add_argument("--json", action="store_true",
                    help="print a machine-readable export doc "
                         "(digests + cache stats) instead of prose")

    ins = sub.add_parser("inspect", help="print artifact manifests")
    ins.add_argument("--aot-dir", required=True)
    ins.add_argument("--json", action="store_true")

    sc = sub.add_parser("scrub", help="verify artifacts at rest")
    sc.add_argument("--aot-dir", required=True)
    sc.add_argument("--delete", action="store_true",
                    help="quarantine corrupt artifacts")
    sc.add_argument("--json", action="store_true")

    st = sub.add_parser("stats", help="compile-cache size/entries")
    st.add_argument("--cache-dir", required=True)

    gc_p = sub.add_parser("gc", help="LRU-prune the compile cache")
    gc_p.add_argument("--cache-dir", required=True)
    gc_p.add_argument("--budget-mb", type=float, required=True)

    args = ap.parse_args()
    if args.selftest:
        return selftest()
    if args.cmd is None:
        ap.print_help()
        return 2
    return {"prebuild": cmd_prebuild, "inspect": cmd_inspect,
            "scrub": cmd_scrub, "stats": cmd_stats,
            "gc": cmd_gc}[args.cmd](args)


if __name__ == "__main__":
    raise SystemExit(main())
