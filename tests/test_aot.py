"""Cold-start elimination (``singa_tpu/aot``): persistent compile
cache policy, AOT export/restore round trips, and — the heart of the
contract — the artifact-mismatch REFUSAL matrix: corrupted digest,
wrong version stamp, changed avals/donation, changed precision policy
each land on the typed fallback-and-recompile path with the stale
artifact quarantined. CPU-only; one manifest is a committed fixture
(tests/data/aot_fixture)."""

import json
import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from singa_tpu import device, layer, opt, tensor
from singa_tpu import model as model_mod
from singa_tpu.aot import cache as aot_cache
from singa_tpu.aot import export as aot_export
from singa_tpu.aot import manifest as aot_manifest
from singa_tpu.aot.export import AotStore
from singa_tpu.aot.manifest import AotMismatch
from singa_tpu.observability import metrics as obs_metrics
from singa_tpu.observability import perf

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "aot_fixture")


REAL_DEFAULT_DIR = aot_cache.default_dir


@pytest.fixture(autouse=True)
def _no_global_cache(monkeypatch, tmp_path):
    """Every test leaves the PROCESS-GLOBAL persistent cache off, so
    later tests' compile_seconds classifications stay 'fresh' — and
    starts hermetic: no directory placed from outside, and the default
    one (``ResilientTrainer(aot=True)`` installs it) moved off the
    checkout's shared cache, whose XLA:CPU executables other processes
    wrote."""
    monkeypatch.delenv(aot_cache.ENV_DIR, raising=False)
    monkeypatch.setattr(aot_cache, "default_dir",
                        lambda: str(tmp_path / "default-cache"))
    yield
    aot_cache.uninstall()


@pytest.fixture()
def dev():
    d = device.create_cpu_device()
    d.SetRandSeed(0)
    return d


# ---------------------------------------------------------------------------
# cache policy
# ---------------------------------------------------------------------------

class TestCachePolicy:
    def test_resolve_forms(self, tmp_path):
        p = aot_cache.resolve(str(tmp_path))
        assert p.enabled and p.directory == str(tmp_path)
        assert aot_cache.resolve(p) is p
        assert aot_cache.resolve(False).enabled is False
        assert aot_cache.resolve(True).enabled is True

    def test_install_hits_and_classify(self, tmp_path):
        aot_cache.install(aot_cache.CachePolicy(str(tmp_path)))
        # drop jax's in-memory executable cache: programs compiled
        # BEFORE the install (earlier tests) would otherwise skip
        # compilation on the first pass and never be persisted —
        # making their post-clear recompile a spurious cache miss
        jax.clear_caches()

        def f(x):
            return jnp.sin(x) * 2 + 1

        s0 = aot_cache.snapshot()
        jax.jit(f)(jnp.ones(5)).block_until_ready()
        assert aot_cache.classify(s0) == "fresh"
        assert aot_cache.stats(str(tmp_path))["entries"] > 0
        jax.clear_caches()
        s1 = aot_cache.snapshot()
        jax.jit(f)(jnp.ones(5)).block_until_ready()
        assert aot_cache.classify(s1) == "cache"
        # counters landed on the registry too
        reg = obs_metrics.default_registry()
        assert reg.get("compile_cache_hits_total").total() >= 1

    def test_directory_rule_env_unset(self, tmp_path):
        """No JAX_COMPILATION_CACHE_DIR: the cache is the default
        directory — the fixed <checkout>/.jax_compile_cache outside
        this module's hermetic fixture — and an explicit directory —
        CachePolicy or Model.compile(compile_cache=) spelling — is
        honoured."""
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        assert REAL_DEFAULT_DIR() == os.path.join(
            repo, ".jax_compile_cache")
        pol = aot_cache.install()
        assert pol.directory == aot_cache.default_dir()
        assert jax.config.jax_compilation_cache_dir == pol.directory
        explicit = str(tmp_path / "mine")
        assert aot_cache.install(explicit).directory == explicit
        assert jax.config.jax_compilation_cache_dir == explicit
        aot_cache.uninstall()
        assert jax.config.jax_compilation_cache_dir is None

    def test_directory_rule_env_set(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR set: a fresh process keeps its
        cache there — filled, counted, no other directory created — and
        never sets a directory in code, not even an explicit one."""
        import subprocess
        import sys
        env_dir, other = tmp_path / "from_env", tmp_path / "explicit"
        code = f"""
import jax, jax.numpy as jnp
calls = []
real = jax.config.update
jax.config.update = lambda k, v: (calls.append(k), real(k, v))[1]
from singa_tpu.aot import cache
pol = cache.install({str(other)!r})
assert pol.directory == {str(env_dir)!r}, pol
assert "jax_compilation_cache_dir" not in calls, calls
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
assert cache.stats()["entries"] > 0, cache.stats()
assert cache.snapshot()["misses"] > 0
cache.uninstall()
assert jax.config.jax_compilation_cache_dir == {str(env_dir)!r}
print("RULE_OK")
"""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(env_dir))
        proc = subprocess.run(
            [sys.executable, "-c", code], env=env, text=True,
            capture_output=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        assert proc.returncode == 0 and "RULE_OK" in proc.stdout, \
            proc.stderr[-2000:]
        assert any(n.endswith("-cache") for n in os.listdir(env_dir))
        assert not other.exists()

    def test_only_the_cache_module_names_the_directory_option(self):
        """The rule is implemented once: no other file of the program
        touches jax's cache-directory option."""
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        hits = []
        for root, dirs, files in os.walk(repo):
            dirs[:] = [d for d in dirs
                       if not d.startswith(".") and d != "tests"]
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(root, f)
                    with open(path) as fh:
                        if "jax_compilation_cache_dir" in fh.read():
                            hits.append(os.path.relpath(path, repo))
        assert hits == [os.path.join("singa_tpu", "aot", "cache.py")], \
            hits

    def test_classify_without_cache_is_fresh(self):
        s = aot_cache.snapshot()
        assert aot_cache.classify(s) == "fresh"

    def test_gc_prunes_lru_to_budget(self, tmp_path):
        # three fake entries with distinct last-use stamps
        sizes = {}
        for i, name in enumerate(["a", "b", "c"]):
            p = tmp_path / f"jit_{name}-0-cache"
            p.write_bytes(b"x" * 1000)
            at = tmp_path / f"jit_{name}-0-atime"
            at.write_bytes(b"")
            t = 1_000_000 + i * 100
            os.utime(at, (t, t))
            sizes[name] = 1000
        rep = aot_cache.gc(aot_cache.CachePolicy(str(tmp_path)),
                           budget_bytes=2100)
        assert rep["removed"] == 1
        # oldest-last-use entry (a) went first
        assert not (tmp_path / "jit_a-0-cache").exists()
        assert (tmp_path / "jit_c-0-cache").exists()

    def test_stats_missing_dir_is_empty(self, tmp_path):
        st = aot_cache.stats(str(tmp_path / "nope"))
        assert st["entries"] == 0 and st["bytes"] == 0


# ---------------------------------------------------------------------------
# manifest verify matrix
# ---------------------------------------------------------------------------

def _compiled_toy():
    def f(state, x):
        return [s + x.sum() for s in state], x * 2.0

    avals = ([jax.ShapeDtypeStruct((4,), np.float32)],
             jax.ShapeDtypeStruct((4,), np.float32))
    return jax.jit(f).lower(*avals).compile(), avals


class TestManifestMatrix:
    def test_build_and_verify_roundtrip(self):
        doc = aot_manifest.build("p", b"bytes", avals=[jnp.ones(3)],
                                 donate_argnums=(0,))
        aot_manifest.verify(doc, payload=b"bytes",
                            avals=[jnp.ones(3)], donate_argnums=(0,))

    @pytest.mark.parametrize("mutate, reason", [
        (lambda d: d.update(digest="crc32:00000000:5"), "digest"),
        (lambda d: d["env"].update(jax="0.0.1"), "version"),
        (lambda d: d["env"].update(jaxlib="0.0.1"), "version"),
        (lambda d: d["env"].update(platform="tpu",
                                   device_kind="TPU v9"), "backend"),
        (lambda d: d["env"].update(n_devices=4096), "topology"),
        (lambda d: d.update(format=99), "format"),
    ])
    def test_refusal_names_the_axis(self, mutate, reason):
        doc = aot_manifest.build("p", b"bytes", avals=[jnp.ones(3)])
        mutate(doc)
        with pytest.raises(AotMismatch) as ei:
            aot_manifest.verify(doc, payload=b"bytes",
                                avals=[jnp.ones(3)])
        assert ei.value.reason == reason

    def test_aval_and_donation_and_policy_refusals(self):
        from singa_tpu import mixed_precision as mp
        doc = aot_manifest.build("p", b"x", avals=[jnp.ones(3)],
                                 donate_argnums=(0,),
                                 policy=mp.resolve("bf16_mixed"))
        with pytest.raises(AotMismatch) as ei:
            aot_manifest.verify(doc, avals=[jnp.ones(4)])
        assert ei.value.reason == "avals"
        with pytest.raises(AotMismatch) as ei:
            aot_manifest.verify(doc, avals=[jnp.ones(3)],
                                donate_argnums=())
        assert ei.value.reason == "donation"
        with pytest.raises(AotMismatch) as ei:
            aot_manifest.verify(doc, avals=[jnp.ones(3)],
                                donate_argnums=(0,),
                                policy=mp.resolve("float32"))
        assert ei.value.reason == "policy"
        # policy stamped but live has none: refused too
        with pytest.raises(AotMismatch) as ei:
            aot_manifest.verify(doc, avals=[jnp.ones(3)],
                                donate_argnums=(0,), policy=None)
        assert ei.value.reason == "policy"

    def test_committed_fixture_refuses_on_version(self):
        """The committed fixture manifest was stamped by a fictitious
        jax build — ANY real runtime must refuse it, typed."""
        doc = aot_manifest.read(os.path.join(FIXTURE,
                                             "train_step.json"))
        with pytest.raises(AotMismatch) as ei:
            aot_manifest.verify(doc)
        assert ei.value.reason == "version"
        assert "0.0.0-fixture" in str(ei.value)

    def test_missing_and_unparseable(self, tmp_path):
        with pytest.raises(AotMismatch) as ei:
            aot_manifest.read(str(tmp_path / "none.json"))
        assert ei.value.reason == "missing"
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        with pytest.raises(AotMismatch) as ei:
            aot_manifest.read(str(bad))
        assert ei.value.reason == "format"


# ---------------------------------------------------------------------------
# store: round trip, quarantine, scrub
# ---------------------------------------------------------------------------

class TestAotStore:
    def test_roundtrip_and_bit_equal(self, tmp_path):
        compiled, avals = _compiled_toy()
        store = AotStore(str(tmp_path))
        doc = store.save_program("p", compiled, avals=avals)
        assert doc["digest"].startswith("crc32:")
        fn, _ = store.load_program("p", avals=avals)
        state, y = fn([jnp.ones(4)], jnp.arange(4.0))
        ref_state, ref_y = compiled([jnp.ones(4)], jnp.arange(4.0))
        assert np.array_equal(np.asarray(y), np.asarray(ref_y))
        assert np.array_equal(np.asarray(state[0]),
                              np.asarray(ref_state[0]))

    def test_corrupt_payload_quarantined(self, tmp_path):
        compiled, avals = _compiled_toy()
        store = AotStore(str(tmp_path))
        store.save_program("p", compiled, avals=avals)
        path = store._bin_path("p")
        blob = bytearray(open(path, "rb").read())
        blob[len(blob) // 3] ^= 0x5A
        open(path, "wb").write(bytes(blob))
        with pytest.warns(UserWarning, match="REFUSED"):
            fn, _ = store.try_load_program("p", avals=avals)
        assert fn is None
        assert store.outcomes["p"] == "refused:digest"
        assert store.programs() == []     # out of the load path
        qdir = os.path.join(store.directory, store.QUARANTINE_DIR)
        assert any("digest" in n for n in os.listdir(qdir))

    def test_missing_is_quiet_no_quarantine(self, tmp_path):
        store = AotStore(str(tmp_path))
        with warnings.catch_warnings():
            warnings.simplefilter("error")    # a warn would raise
            fn, _ = store.try_load_program(
                "absent", avals=[jnp.ones(2)])
        assert fn is None
        assert store.outcomes["absent"] == "refused:missing"

    def test_scrub_digest_only_and_delete(self, tmp_path):
        compiled, avals = _compiled_toy()
        store = AotStore(str(tmp_path))
        store.save_program("good", compiled, avals=avals)
        store.save_program("bad", compiled, avals=avals)
        p = store._bin_path("bad")
        open(p, "ab").write(b"rot")
        with pytest.warns(UserWarning, match="FAILED"):
            rep = store.scrub()
        assert rep == {"good": "ok", "bad": "corrupt"}
        with pytest.warns(UserWarning):
            rep = store.scrub(delete=True)
        assert store.programs() == ["good"]

    def test_out_tree_and_layout_roundtrip(self):
        tree = ("U", [("T", 0),
                      ("D", {"a": ("L", [("T", 1), ("T", 2)])})])
        enc = aot_export.encode_tree(tree)
        assert aot_export.decode_tree(json.loads(json.dumps(enc))) \
            == tree
        from singa_tpu.model import _TENSOR
        layout = (_TENSOR, "plain", None, 3, _TENSOR)
        doc = aot_export.encode_layout(layout)
        assert json.loads(doc) == [["T"], ["V", "plain"], ["V", None],
                                   ["V", 3], ["T"]]
        with pytest.raises(aot_export.AotExportError):
            aot_export.encode_layout((object(),))


# ---------------------------------------------------------------------------
# train-step export / warm restart
# ---------------------------------------------------------------------------

class _MLP(model_mod.Model):
    def __init__(self):
        super().__init__()
        self.fc1 = layer.Linear(12)
        self.relu = layer.ReLU()
        self.fc2 = layer.Linear(4)
        self.loss_fn = layer.SoftMaxCrossEntropy()

    def forward(self, x):
        return self.fc2(self.relu(self.fc1(x)))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.loss_fn(out, y)
        self.optimizer(loss)
        return out, loss


def _mlp_and_batch(dev, policy=None):
    dev.SetRandSeed(0)
    rng = np.random.RandomState(0)
    tx = tensor.Tensor(data=rng.randn(8, 6).astype(np.float32),
                       device=dev, requires_grad=False)
    ty = tensor.Tensor(
        data=np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)],
        device=dev, requires_grad=False)
    m = _MLP()
    m.set_optimizer(opt.SGD(lr=0.05, momentum=0.9))
    m.compile([tx], is_train=True, use_graph=True, policy=policy)
    return m, tx, ty


def _host_states(m):
    return {k: np.asarray(jax.device_get(t.data))
            for k, t in m.get_states().items()}


class TestTrainStepAot:
    def test_export_load_bitwise_parity(self, dev, tmp_path):
        store = AotStore(str(tmp_path))
        m1, tx, ty = _mlp_and_batch(dev)
        m1(tx, ty)
        aot_export.export_train_step(m1, store)
        assert store.outcomes["train_step"] == "exported"

        # a "restarted" twin loads the artifact instead of tracing
        m2, tx2, ty2 = _mlp_and_batch(dev)
        m2._aot_store = store
        m2(tx2, ty2)
        rec = m2._last_run_rec
        assert rec.get("aot") is True
        assert rec["n_traces"] == 1
        assert store.outcomes["train_step"] == "loaded"
        # both models step identically from identical seeds
        m1(tx, ty)
        m2(tx2, ty2)
        s1, s2 = _host_states(m1), _host_states(m2)
        assert set(s1) == set(s2)
        for k in s1:
            assert np.array_equal(s1[k], s2[k]), k

    def test_compile_seconds_source_aot(self, dev, tmp_path):
        store = AotStore(str(tmp_path))
        m1, tx, ty = _mlp_and_batch(dev)
        m1(tx, ty)
        aot_export.export_train_step(m1, store)
        before = perf.compile_source_counts()
        m2, tx2, ty2 = _mlp_and_batch(dev)
        m2._aot_store = store
        m2(tx2, ty2)
        after = perf.compile_source_counts()
        assert after.get("aot", 0) == before.get("aot", 0) + 1
        assert after.get("fresh", 0) == before.get("fresh", 0)

    def test_export_refuses_before_any_step(self, dev, tmp_path):
        m, _tx, _ty = _mlp_and_batch(dev)
        with pytest.raises(aot_export.AotExportError):
            aot_export.export_train_step(m, AotStore(str(tmp_path)))

    def test_skip_if_current(self, dev, tmp_path):
        store = AotStore(str(tmp_path))
        m, tx, ty = _mlp_and_batch(dev)
        m(tx, ty)
        assert aot_export.export_train_step(m, store) is not None
        mtime = os.path.getmtime(store._bin_path("train_step"))
        assert aot_export.export_train_step(
            m, store, skip_if_current=True) is None
        assert os.path.getmtime(store._bin_path("train_step")) == mtime

    @pytest.mark.parametrize("corrupt, reason", [
        ("digest", "digest"), ("version", "version"),
        ("avals", "avals"), ("donation", "donation"),
        ("policy", "policy"), ("layout", "signature"),
    ])
    def test_mismatch_matrix_falls_back_and_quarantines(
            self, dev, tmp_path, corrupt, reason):
        """THE acceptance matrix: every corrupted/mismatched axis lands
        on the typed refusal, the artifact is quarantined, and the
        model falls back to a fresh compile — training proceeds."""
        store = AotStore(str(tmp_path))
        m1, tx, ty = _mlp_and_batch(dev)
        m1(tx, ty)
        aot_export.export_train_step(m1, store)
        mpath = store._manifest_path("train_step")
        doc = aot_manifest.read(mpath)
        if corrupt == "digest":
            blob = bytearray(open(store._bin_path("train_step"),
                                  "rb").read())
            blob[len(blob) // 2] ^= 0xFF
            open(store._bin_path("train_step"), "wb").write(bytes(blob))
        elif corrupt == "version":
            doc["env"]["jax"] = "0.0.0-stale"
            aot_manifest.write(mpath, doc)
        elif corrupt == "avals":
            doc["avals"]["leaves"][0][0] = [999, 999]
            aot_manifest.write(mpath, doc)
        elif corrupt == "donation":
            doc["donation"] = [0, 1]
            aot_manifest.write(mpath, doc)
        elif corrupt == "policy":
            doc["policy"] = {"name": "bf16_mixed"}
            aot_manifest.write(mpath, doc)
        elif corrupt == "layout":
            doc["layout"] = json.dumps([["T"], ["T"], ["V", "spars"]])
            aot_manifest.write(mpath, doc)

        m2, tx2, ty2 = _mlp_and_batch(dev)
        m2._aot_store = store
        with pytest.warns(UserWarning, match="REFUSED"):
            out = m2(tx2, ty2)          # falls back to a fresh compile
        assert out is not None
        assert m2._last_run_rec.get("aot") is None
        assert m2._last_run_rec["n_traces"] == 1
        assert store.outcomes["train_step"] == f"refused:{reason}"
        assert "train_step" not in store.programs()   # quarantined
        qdir = os.path.join(store.directory, store.QUARANTINE_DIR)
        assert any(reason in n for n in os.listdir(qdir))
        # the fallback really trains: a second step runs compiled
        m2(tx2, ty2)
        assert m2._last_run_rec["n_traces"] == 1

    def test_changed_policy_live_side_refuses(self, dev, tmp_path):
        """Exported under no policy, loaded under bf16_mixed: the live
        policy axis refuses (never a silently-wrong-precision step)."""
        store = AotStore(str(tmp_path))
        m1, tx, ty = _mlp_and_batch(dev)
        m1(tx, ty)
        aot_export.export_train_step(m1, store)
        m2, tx2, ty2 = _mlp_and_batch(dev, policy="bf16_mixed")
        m2._aot_store = store
        with pytest.warns(UserWarning, match="REFUSED"):
            m2(tx2, ty2)
        assert store.outcomes["train_step"].startswith("refused:")

    def test_trainer_roundtrip_and_summary(self, dev, tmp_path):
        """ResilientTrainer(aot=True): run 1 exports, run 2 (fresh
        model, restored checkpoint — aux materialises in CHECKPOINT
        order, exercising the state-name reorder) loads with zero
        fresh compiles in its summary."""
        from singa_tpu.resilience.runtime import ResilientTrainer
        rng = np.random.RandomState(1)
        x = rng.randn(32, 6).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 32)]

        def batches(d):
            return [(tensor.Tensor(data=x[i:i + 8], device=d,
                                   requires_grad=False),
                     tensor.Tensor(data=y[i:i + 8], device=d,
                                   requires_grad=False))
                    for i in range(0, 32, 8)]

        ck = str(tmp_path / "ck")
        m1, _tx, _ty = _mlp_and_batch(dev)
        t1 = ResilientTrainer(m1, ck, save_interval_steps=1,
                              exit_on_preempt=False, verbose=False,
                              aot=True)
        s1 = t1.run(batches(dev), num_steps=3)
        t1.close()
        assert s1["aot"]["train_step"] == "exported"
        assert s1["n_traces"] == 1

        m2, _tx, _ty = _mlp_and_batch(dev)
        t2 = ResilientTrainer(m2, ck, save_interval_steps=1,
                              exit_on_preempt=False, verbose=False,
                              aot=True)
        s2 = t2.run(batches(dev), num_steps=6)
        t2.close()
        assert s2["start"] == 3
        assert s2["aot"]["train_step"] == "loaded"
        assert s2["n_traces"] == 1
        assert "compile_sources" in s2


# ---------------------------------------------------------------------------
# serving export / warm spin-up
# ---------------------------------------------------------------------------

@pytest.mark.serving
class TestServingAot:
    def _model(self, dev):
        from singa_tpu.models import transformer
        dev.SetRandSeed(0)
        m = transformer.TransformerLM(32, d_model=16, n_heads=2,
                                      n_layers=1, max_len=48,
                                      tp=False)
        m.eval()
        m(tensor.Tensor(data=np.zeros((1, 8), np.float32),
                        device=dev, requires_grad=False))
        return m

    def test_export_load_parity_and_no_retrace(self, dev, tmp_path):
        store = AotStore(str(tmp_path))
        e1 = self._model(dev).compile_serving(
            slots=2, max_len=48, prefill_len=8)
        e1.export_aot(store)
        f1 = e1.submit([1, 2, 3], max_new_tokens=6)
        e1.run_until_idle()
        r1 = f1.result()
        # export lowered FRESH jits: the engine's pins are untouched
        assert e1.compiled_step_info()["n_traces"] == 1

        e2 = self._model(dev).compile_serving(
            slots=2, max_len=48, prefill_len=8, aot_store=store)
        info = e2.compiled_step_info()
        assert info["aot"] == {"serve_prefill": "loaded",
                               "serve_decode": "loaded"}
        # ≥3 refills through the DESERIALIZED programs, zero retraces
        results = []
        for k in range(3):
            f = e2.submit([1, 2, 3], max_new_tokens=6)
            e2.run_until_idle()
            results.append(f.result()["tokens"])
        assert results[0] == r1["tokens"]
        assert results[0] == results[1] == results[2]
        info = e2.compiled_step_info()
        assert info["n_traces"] == 1
        assert info["prefill_n_traces"] == 1

    def test_decode_artifact_takes_the_chained_inputs(self, dev, tmp_path):
        """The ring decode program's call, as the artifact records it:
        the last call's tokens (on the device), the host's tokens, the
        ``fresh`` mask, positions, active. Loaded, the one deserialized
        executable serves ticks dispatched ahead and serial ones (a
        drain), token for token what the exporting engine served."""
        store = AotStore(str(tmp_path))
        geometry = dict(slots=2, max_len=48, prefill_len=8)
        e1 = self._model(dev).compile_serving(
            registry=obs_metrics.MetricsRegistry(), **geometry)
        e1.export_aot(store)
        leaves = store.read_manifest("serve_decode")["avals"]["leaves"]
        assert leaves[-5:] == [[[2], "int32"], [[2], "int32"],
                               [[2], "bool"], [[2], "int32"],
                               [[2], "bool"]]

        def serve(eng):
            futs = [eng.submit(p, max_new_tokens=7)
                    for p in ([1, 2, 3], [4, 5], [6, 7, 8, 9])]
            for _ in range(4):
                eng.step()
            assert eng.drain(timeout=30)
            ticks = eng._reg.get("serve_decode_ticks_total")
            assert ticks.value(mode="ahead", reason="none") > 0
            assert ticks.value(mode="serial", reason="drain") > 0
            return [f.result()["tokens"] for f in futs]

        e2 = self._model(dev).compile_serving(
            aot_store=store, registry=obs_metrics.MetricsRegistry(),
            **geometry)
        assert e2.compiled_step_info()["aot"] == {
            "serve_prefill": "loaded", "serve_decode": "loaded"}
        assert serve(e2) == serve(e1)
        info = e2.compiled_step_info()
        assert (info["prefill_n_traces"], info["n_traces"]) == (1, 1)

    def test_batch_engine_roundtrip(self, dev, tmp_path):
        """The stateless batch forward exports/loads too: same
        honored-or-refused contract, parity, n_traces reads 1."""
        store = AotStore(str(tmp_path))
        m1, tx, _ty = _mlp_and_batch(dev)
        m1.eval()
        e1 = m1.compile_serving(input_shape=(6,), batch=4)
        e1.export_aot(store)
        f1 = e1.submit(np.ones(6, np.float32))
        e1.run_until_idle()
        r1 = np.asarray(f1.result())
        assert e1.compiled_step_info()["n_traces"] == 1

        m2, _tx, _ty = _mlp_and_batch(dev)
        m2.eval()
        e2 = m2.compile_serving(input_shape=(6,), batch=4,
                                aot_store=store)
        info = e2.compiled_step_info()
        assert info["aot"] == {"serve_batch": "loaded"}
        f2 = e2.submit(np.ones(6, np.float32))
        e2.run_until_idle()
        assert np.array_equal(r1, np.asarray(f2.result()))
        assert e2.compiled_step_info()["n_traces"] == 1
        # changed geometry refuses, typed + quarantined, serves fresh
        m3, _tx, _ty = _mlp_and_batch(dev)
        m3.eval()
        with pytest.warns(UserWarning, match="REFUSED"):
            e3 = m3.compile_serving(input_shape=(6,), batch=8,
                                    aot_store=store)
        assert e3.compiled_step_info()["aot"]["serve_batch"] \
            .startswith("refused:")
        f3 = e3.submit(np.ones(6, np.float32))
        e3.run_until_idle()
        assert np.asarray(f3.result()).shape == r1.shape

    def test_geometry_change_refuses(self, dev, tmp_path):
        store = AotStore(str(tmp_path))
        e1 = self._model(dev).compile_serving(
            slots=2, max_len=48, prefill_len=8)
        e1.export_aot(store)
        with pytest.warns(UserWarning, match="REFUSED"):
            e3 = self._model(dev).compile_serving(
                slots=4, max_len=48, prefill_len=8, aot_store=store)
        src = e3.compiled_step_info()["aot"]
        assert all(v.startswith("refused:") for v in src.values())
        # ...and the refused engine still serves (fresh programs)
        f = e3.submit([1, 2, 3], max_new_tokens=4)
        e3.run_until_idle()
        assert len(f.result()["tokens"]) == 4

    def test_artifact_without_the_output_form_is_refused(self, dev,
                                                         tmp_path):
        """The programs return ``(tokens, logits)`` beside the state,
        and the geometry stamp says so. An artifact of a build whose
        programs returned the bare logits carries no such stamp: it is
        refused with reason ``signature`` and compiled fresh, never
        loaded and unpacked wrongly — and the refused engine serves the
        tokens the exporting one served."""
        store = AotStore(str(tmp_path))
        e1 = self._model(dev).compile_serving(
            slots=2, max_len=48, prefill_len=8)
        e1.export_aot(store)
        f1 = e1.submit([1, 2, 3], max_new_tokens=6)
        e1.run_until_idle()
        for program in ("serve_prefill", "serve_decode"):
            path = os.path.join(str(tmp_path), program + ".json")
            with open(path) as f:
                doc = json.load(f)
            assert doc["engine"].pop("outputs") == "tokens+logits"
            with open(path, "w") as f:
                json.dump(doc, f)
        with pytest.warns(UserWarning, match="REFUSED.*outputs"):
            e2 = self._model(dev).compile_serving(
                slots=2, max_len=48, prefill_len=8, aot_store=store)
        assert e2.compiled_step_info()["aot"] == {
            "serve_prefill": "refused:signature",
            "serve_decode": "refused:signature"}
        f2 = e2.submit([1, 2, 3], max_new_tokens=6)
        e2.run_until_idle()
        assert f2.result()["tokens"] == f1.result()["tokens"]

    def test_artifact_of_the_old_parameter_tree_is_refused(self, dev,
                                                           tmp_path):
        """The adapter's tree holds its layers role by role (PR 34: a
        vector role one stacked leaf) and the manifest's avals are of
        that tree. An artifact exported from the tree of a leaf a block
        a role is refused typed and compiled fresh — the refused engine
        serves what the exporting one served — and a fresh export
        round-trips."""
        from singa_tpu.serving.engine import ServingEngine
        from test_serve_param_tree import _OldTreeAdapter, old_walk
        geometry = dict(slots=2, max_len=48, prefill_len=8)
        store = AotStore(str(tmp_path))
        with old_walk():
            e1 = ServingEngine(_OldTreeAdapter(self._model(dev)),
                               **geometry)
            e1.export_aot(store)
            f1 = e1.submit([1, 2, 3], max_new_tokens=6)
            e1.run_until_idle()
        with pytest.warns(UserWarning, match="REFUSED"):
            e2 = self._model(dev).compile_serving(aot_store=store,
                                                  **geometry)
        assert e2.compiled_step_info()["aot"] == {
            "serve_prefill": "refused:avals",
            "serve_decode": "refused:avals"}
        f2 = e2.submit([1, 2, 3], max_new_tokens=6)
        e2.run_until_idle()
        assert f2.result()["tokens"] == f1.result()["tokens"]
        e2.export_aot(store)
        e3 = self._model(dev).compile_serving(aot_store=store,
                                              **geometry)
        assert e3.compiled_step_info()["aot"] == {
            "serve_prefill": "loaded", "serve_decode": "loaded"}
        f3 = e3.submit([1, 2, 3], max_new_tokens=6)
        e3.run_until_idle()
        assert f3.result()["tokens"] == f1.result()["tokens"]
        assert e3.compiled_step_info()["n_traces"] == 1


# ---------------------------------------------------------------------------
# checkpoint scrub covers the aot sidecar
# ---------------------------------------------------------------------------

class TestScrubIntegration:
    def test_scrub_reports_and_quarantines_aot(self, dev, tmp_path):
        from singa_tpu.checkpoint import CheckpointManager
        ck = str(tmp_path / "ck")
        m, tx, ty = _mlp_and_batch(dev)
        m(tx, ty)
        mgr = CheckpointManager(ck, save_interval_steps=1)
        mgr.save(0, m)
        mgr.wait()
        store = AotStore(os.path.join(ck, "aot"))
        aot_export.export_train_step(m, store)
        rep = mgr.scrub()
        assert rep[0] == "ok"
        assert rep["aot/train_step"] == "ok"
        # rot the artifact: scrub flags it; delete quarantines it
        # WITHOUT touching the (healthy) checkpoint step
        open(store._bin_path("train_step"), "ab").write(b"rot")
        with pytest.warns(UserWarning):
            rep = mgr.scrub(delete=True)
        assert rep["aot/train_step"] == "corrupt"
        assert rep[0] == "ok"
        assert store.programs() == []
        mgr2 = CheckpointManager(ck, save_interval_steps=1)
        assert mgr2.scrub()[0] == "ok"    # step survived the demotion
        mgr2.close()
        mgr.close()
