"""Smoke tests: every example script trains for a few tiny steps end-to-end
(VERDICT r1 weak #8 — the examples were never exercised by CI). Each runs
in a subprocess with --cpu so compile caches and platform pinning stay
isolated."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow   # subprocess smoke runs: --full tier

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(args, timeout=420, expect_returncode=0):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable] + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == expect_returncode, \
        f"{args}:\nstdout:{proc.stdout[-2000:]}\nstderr:{proc.stderr[-2000:]}"
    return proc.stdout


class TestExamples:
    def test_train_mlp(self):
        out = run_example(["examples/train_mlp.py", "--cpu", "--epochs", "1",
                           "--bs", "32"])
        assert "loss" in out.lower() or "accuracy" in out.lower(), out[-500:]

    def test_train_cnn(self):
        out = run_example(["examples/train_cnn.py", "cnn", "--cpu",
                           "--epochs", "1", "--iters", "2", "--bs", "8"])
        assert "loss" in out.lower(), out[-500:]

    def test_train_cnn_dist_half(self):
        """The reference calling convention model(tx, ty, dist_option,
        spars) through the compiled path (the round-1 crash repro)."""
        out = run_example(["examples/train_cnn.py", "cnn", "--cpu",
                           "--epochs", "1", "--iters", "3", "--bs", "8",
                           "--dist", "--dist-option", "half"])
        assert "loss" in out.lower(), out[-500:]

    def test_train_cnn_overlap_fused_flags(self):
        """The MFU-push knobs through the user CLI: gradient-psum
        bucketing + the no-overlap baseline + the fused-optimizer flag
        (which declines to the reference path on CPU) all train on the
        forced multi-device mesh."""
        out = run_example(["examples/train_cnn.py", "cnn", "--cpu",
                           "--epochs", "1", "--iters", "3", "--bs", "8",
                           "--dist", "--bucket-mb", "4",
                           "--fused-optim"])
        assert "loss" in out.lower(), out[-500:]
        out = run_example(["examples/train_cnn.py", "cnn", "--cpu",
                           "--epochs", "1", "--iters", "2", "--bs", "8",
                           "--dist", "--no-overlap"])
        assert "loss" in out.lower(), out[-500:]

    def test_train_cnn_resilient(self, tmp_path):
        """The fault-tolerant driver through the user CLI: trains,
        checkpoints, and a relaunch resumes instead of restarting."""
        args = ["examples/train_cnn.py", "mlp", "--cpu", "--epochs", "1",
                "--iters", "2", "--bs", "8", "--resilient",
                "--save-every", "1", "--ckpt-dir", str(tmp_path / "ck")]
        out = run_example(args)
        assert "resilient run summary" in out, out[-500:]
        out = run_example(args[:4] + ["2"] + args[5:])   # 2 epochs now
        assert "resumed from checkpoint" in out, out[-500:]

    def test_train_cnn_gspmd_mesh_fsdp(self):
        """The GSPMD train-step migration through the user CLI: --mesh
        2x1 compiles the single-jit sharded step on the hermetic CPU
        mesh, --fsdp shards optimizer state over the data axis
        (mirrors test_serve_transformer_explicit_mesh for training)."""
        out = run_example(["examples/train_cnn.py", "mlp", "synthetic",
                           "--cpu", "--epochs", "1", "--iters", "2",
                           "--bs", "8", "--mesh", "2x1"])
        assert "GSPMD train mesh=data2xmodel1" in out, out[-500:]
        assert "loss" in out.lower(), out[-500:]
        out = run_example(["examples/train_cnn.py", "mlp", "synthetic",
                           "--cpu", "--epochs", "1", "--iters", "2",
                           "--bs", "8", "--mesh", "2x1", "--fsdp"])
        assert "GSPMD train mesh=data2xmodel1 fsdp=data" in out, out[-500:]
        assert "loss" in out.lower(), out[-500:]

    def test_train_resnet_perf_modes(self):
        """The round-5 perf modes through the user CLI: channels-last
        trunk + space-to-depth stem on the resnet family."""
        out = run_example(["examples/train_cnn.py", "resnet", "--cpu",
                           "--epochs", "1", "--iters", "2", "--bs", "2",
                           "--layout", "NHWC",
                           "--stem", "space_to_depth"], timeout=900)
        assert "loss" in out.lower(), out[-500:]

    def test_train_resnet_bf16_mixed_policy(self):
        """The mixed-precision compile policy end-to-end through the
        user CLI (acceptance: Model.compile(policy="bf16_mixed") trains
        the resnet example): fp32 masters + loss scaling, bf16
        compute."""
        out = run_example(["examples/train_cnn.py", "resnet", "--cpu",
                           "--epochs", "1", "--iters", "2", "--bs", "2",
                           "-p", "bf16_mixed"], timeout=900)
        assert "loss" in out.lower(), out[-500:]

    def test_train_charrnn(self):
        out = run_example(["examples/train_charrnn.py", "--cpu",
                           "--epochs", "1", "--seq", "8", "--hidden", "16",
                           "--bs", "4"])
        assert "loss" in out.lower(), out[-500:]

    def test_train_transformer(self):
        # batch shards over the 'data' mesh axis (8 virtual CPU devices)
        out = run_example(["examples/train_transformer.py", "--cpu",
                           "--steps", "2", "--seq", "16", "--d-model", "32",
                           "--heads", "2", "--layers", "1", "--bs", "8"])
        assert "loss" in out.lower(), out[-500:]

    def test_train_transformer_fused_tp_generate(self):
        # the round's headline path end-to-end as a user would run it:
        # vocab-sharded head + cross-shard fused CE under tp, then a
        # greedy KV-cache decode off the sharded trained state
        out = run_example(["examples/train_transformer.py", "--cpu",
                           "--steps", "2", "--seq", "16", "--d-model",
                           "32", "--heads", "2", "--layers", "1",
                           "--bs", "8", "--tp", "2", "--vocab", "64",
                           "--fused-head-chunk", "16",
                           "--generate", "4"])
        assert "loss" in out.lower(), out[-500:]
        assert "generated:" in out, out[-500:]

    def test_train_gan(self):
        out = run_example(["examples/train_gan.py", "vanilla", "--cpu",
                           "--iters", "2", "--bs", "8"])
        assert "loss" in out.lower() or "d_loss" in out.lower(), out[-500:]

    def test_onnx_finetune(self):
        out = run_example(["examples/onnx_finetune.py", "--cpu",
                           "--steps", "3"])
        assert "fine-tuned imported model" in out, out[-500:]

    def test_train_rbm(self):
        out = run_example(["examples/train_rbm.py", "--cpu", "--epochs",
                           "1", "--bs", "16", "--hdim", "32"])
        assert "err" in out.lower() or "loss" in out.lower(), out[-500:]

    def test_train_qabot(self):
        out = run_example(["examples/train_qabot.py", "--cpu",
                           "--epochs", "2", "--n", "32", "--bs", "8", "--hidden", "16",
                           "--seq-len", "6", "--embed", "16"])
        assert "top1" in out, out[-500:]

    def test_train_largedataset(self):
        out = run_example(["examples/train_largedataset.py", "--cpu",
                           "--n", "64", "--shards", "2", "--bs", "8", "--epochs", "2",
                           "--size", "12"])
        assert "epoch 1" in out, out[-500:]

    def test_train_transformer_moe(self):
        out = run_example(["examples/train_transformer.py", "--cpu",
                           "--steps", "2", "--seq", "16", "--d-model",
                           "32", "--heads", "2", "--layers", "1",
                           "--bs", "8", "--moe", "4", "--ep", "2"])
        assert "'expert': 2" in out and "loss" in out, out[-500:]

    def test_train_ffnet(self):
        out = run_example(["examples/train_ffnet.py", "--cpu", "--n", "64",
                           "--epochs", "1", "--size", "12", "--bs", "16"])
        assert "final eval" in out, out[-500:]

    def test_train_imdb(self):
        out = run_example(["examples/train_imdb.py", "--cpu", "--epochs",
                           "1", "--bs", "16", "--seq", "16", "--vocab",
                           "200", "--hidden", "16"])
        assert "val_acc" in out, out[-500:]

    def test_onnx_zoo_roundtrip(self, tmp_path):
        """Export one of our zoo models to a .onnx FILE, reload it from
        disk, run inference, and fine-tune — the reference's
        examples/onnx/*.py loop without the download."""
        p = str(tmp_path / "m.onnx")
        out = run_example(["examples/onnx_zoo.py", "--export", p,
                           "--arch", "mlp", "--cpu", p,
                           "--finetune", "2"])
        assert "output" in out and "finetune step 1" in out, out[-800:]

    def test_benchmark(self):
        out = run_example(["examples/benchmark.py", "--cpu", "--bs", "4",
                           "--iters", "2", "--warmup", "1", "--depth",
                           "18", "--size", "64"])
        assert "Throughput" in out, out[-500:]

    def test_train_elastic_resumes(self, tmp_path):
        """Crash-and-restart: second run resumes from the newest
        committed checkpoint and completes."""
        d = str(tmp_path / "ck")
        args = ["examples/train_elastic.py", "--cpu", "--dir", d,
                "--steps", "12", "--save-every", "2", "--bs", "8"]
        out1 = run_example(args + ["--crash-at", "5"],
                           expect_returncode=42)
        assert "simulated crash at step 5" in out1
        out2 = run_example(args)
        # crash happened at step 5 with saves on even steps: the last
        # committed checkpoint is step 4, so the rerun repeats step 5
        assert "continuing at step 5" in out2, out2
        assert "training complete" in out2


class TestTelemetryExample:
    """--telemetry DIR: the end-of-run dump contract — live span JSONL,
    a schema-valid metrics snapshot, and its Prometheus rendering, all
    consumable by tools/metrics_dump.py."""

    def test_train_cnn_telemetry_dump(self, tmp_path):
        import json

        tel = str(tmp_path / "tel")
        out = run_example(["examples/train_cnn.py", "mlp", "synthetic",
                           "--cpu", "--epochs", "1", "--iters", "2",
                           "--bs", "8", "--telemetry", tel])
        assert "telemetry written" in out, out[-500:]

        # metrics.json is a valid singa-tpu-metrics/1 snapshot with the
        # step histogram populated
        from singa_tpu.observability import export
        with open(os.path.join(tel, "metrics.json")) as f:
            snap = json.load(f)
        export.validate_snapshot(snap)
        by_name = {m["name"]: m for m in snap["metrics"]}
        assert "train_step_seconds" in by_name
        (series,) = by_name["train_step_seconds"]["series"]
        assert series["count"] >= 2

        # the Prometheus rendering exists and names the same metric
        with open(os.path.join(tel, "metrics.prom")) as f:
            prom = f.read()
        assert "# TYPE train_step_seconds histogram" in prom

        # spans.jsonl streamed live: compile + per-step spans
        with open(os.path.join(tel, "spans.jsonl")) as f:
            recs = [json.loads(ln) for ln in f]
        names = [r["name"] for r in recs]
        assert "compile" in names and "step" in names

        # and the CLI converts the snapshot (the post-mortem workflow)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "tools/metrics_dump.py",
             os.path.join(tel, "metrics.json")],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=120)
        assert proc.returncode == 0, proc.stderr[-500:]
        assert "train_step_seconds_count" in proc.stdout


class TestQuantizeCheckpointTool:
    """The offline fp32 -> int8 checkpoint converter's CI smoke (like
    metrics_dump's): save, convert, dequantized restore parity, >=3x
    shrink, clean scrub, and the corrupt-source digest-mismatch path —
    all inside the tool's own --selftest."""

    def test_selftest_is_green(self):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "tools/quantize_checkpoint.py",
             "--selftest"],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=300)
        assert proc.returncode == 0, proc.stderr[-800:]
        assert "selftest: OK" in proc.stdout, proc.stdout[-300:]


class TestAotCacheTool:
    """The cold-start tool's CI smoke (like the other tool selftests):
    export → inspect → warm reload (bit-equal) → corrupt a byte →
    digest refusal + quarantine → doctored version stamp → typed
    refusal → persistent-cache LRU GC round-trip — all inside the
    tool's own --selftest."""

    def test_selftest_is_green(self):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "tools/aot_cache.py", "--selftest"],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=300)
        assert proc.returncode == 0, proc.stderr[-800:]
        assert "selftest: OK" in proc.stdout, proc.stdout[-300:]


class TestBenchReportTool:
    """The bench-trajectory report's CI smoke (like the other tool
    selftests): a synthetic 4-round BENCH_r*.json trajectory through
    the real load/extract/delta path, same-platform comparison, the
    timeline columns, and a known 20% bf16 regression flagged — all
    inside the tool's own --selftest."""

    def test_selftest_is_green(self):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "tools/bench_report.py", "--selftest"],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=120)
        assert proc.returncode == 0, proc.stderr[-800:]
        assert "selftest: OK" in proc.stdout, proc.stdout[-300:]


class TestTraceExportTool:
    """The Perfetto exporter's CI smoke (like metrics_dump's): a
    synthetic recorder ring exported through the real file path,
    Chrome-trace schema round-trip, rank rows + per-request lanes —
    all inside the tool's own --selftest."""

    def test_selftest_is_green(self):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "tools/trace_export.py", "--selftest"],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=120)
        assert proc.returncode == 0, proc.stderr[-800:]
        assert "selftest ok" in proc.stdout, proc.stdout[-300:]

    def test_converts_telemetry_spans_jsonl(self, tmp_path):
        """End to end on REAL recorder output: a --telemetry training
        run's spans.jsonl renders into a schema-valid trace."""
        import json

        tel = str(tmp_path / "tel")
        run_example(["examples/train_cnn.py", "mlp", "synthetic",
                     "--cpu", "--epochs", "1", "--iters", "2",
                     "--bs", "8", "--telemetry", tel])
        out = str(tmp_path / "run.trace.json")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "tools/trace_export.py",
             os.path.join(tel, "spans.jsonl"), "-o", out],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=120)
        assert proc.returncode == 0, proc.stderr[-800:]
        from singa_tpu.observability import trace_export
        with open(out) as f:
            doc = json.load(f)
        trace_export.validate_chrome_trace(doc)
        names = {e["name"] for e in doc["traceEvents"]}
        assert "step" in names and "compile" in names, names


class TestServeGatewayExample:
    """The serving gateway smoke: engine + stdlib HTTP gateway + drain,
    end to end in one subprocess (the chaos serve-drain scenario's
    building block)."""

    def test_serve_transformer_selftest(self):
        out = run_example(["examples/serve_transformer.py", "--cpu",
                           "--selftest", "4"])
        assert "READY port=" in out, out[-500:]
        assert "SELFTEST OK" in out, out[-500:]
        assert "n_traces=1" in out, out[-500:]
        assert "drain_exit=0" in out, out[-500:]

    def test_serve_transformer_sharded_cpu_mesh(self):
        """GSPMD sharded serving through the example: --model-shards 2
        on the hermetic 8-device CPU mesh (XLA_FLAGS inherited from
        conftest), greedy selftest requests, no-retrace pin, clean
        drain."""
        out = run_example(["examples/serve_transformer.py", "--cpu",
                           "--model-shards", "2", "--slots", "4",
                           "--selftest", "4"])
        assert "SHARDED mesh=batch" in out, out[-500:]
        assert "SELFTEST OK" in out, out[-500:]
        assert "n_traces=1" in out, out[-500:]
        assert "drain_exit=0" in out, out[-500:]

    def test_serve_transformer_explicit_mesh(self):
        out = run_example(["examples/serve_transformer.py", "--cpu",
                           "--mesh", "2x2", "--slots", "4",
                           "--selftest", "3"])
        assert "SHARDED mesh=batch2xmodel2" in out, out[-500:]
        assert "SELFTEST OK" in out, out[-500:]

    def test_serve_transformer_autoscale(self):
        """The supervised-fleet mode: a 2-replica floor behind a
        FleetRouter with the Autoscaler owning the population, selftest
        traffic through the gateway, clean drain of every replica."""
        out = run_example(["examples/serve_transformer.py", "--cpu",
                           "--autoscale", "2", "--selftest", "4"])
        assert "READY port=" in out, out[-500:]
        assert "replicas=2" in out, out[-500:]
        assert "AUTOSCALE OK" in out, out[-500:]
        assert "drain_exit=0" in out, out[-500:]

    @pytest.mark.chaos
    def test_serve_autoscale_lifecycle_drill(self, tmp_path):
        """The autoscaler drill, end to end in real subprocesses: AOT
        prebuild, warm scale-up under sustained load (zero fresh
        compiles fleet-wide), crash replacement with re-dispatch,
        calm scale-down through the drain path, and flap quarantine
        stopping the respawn loop (shared with ``tools/chaos_smoke.py
        --only serve-autoscale`` — one source of truth)."""
        import importlib.util as _ilu
        spec = _ilu.spec_from_file_location(
            "chaos_smoke", os.path.join(ROOT, "tools", "chaos_smoke.py"))
        chaos_smoke = _ilu.module_from_spec(spec)
        spec.loader.exec_module(chaos_smoke)
        chaos_smoke.scenario_serve_autoscale(
            str(tmp_path), chaos_smoke.Budget(300))

    @pytest.mark.chaos
    def test_serve_preempt_live_kv_handoff(self, tmp_path):
        """The preemption drill, end to end in real subprocesses: a
        two-replica fleet, SIGTERM one mid-request under a 2s-class
        deadline — zero failed responses, migrated continuations
        token-identical to an uninterrupted run, and STRICTLY fewer
        re-prefilled tokens than the forced-recompute baseline (the
        scenario is shared with ``tools/chaos_smoke.py
        --only serve-preempt`` — one source of truth)."""
        import importlib.util as _ilu
        spec = _ilu.spec_from_file_location(
            "chaos_smoke", os.path.join(ROOT, "tools", "chaos_smoke.py"))
        chaos_smoke = _ilu.module_from_spec(spec)
        spec.loader.exec_module(chaos_smoke)
        chaos_smoke.scenario_serve_preempt(
            str(tmp_path), chaos_smoke.Budget(300))

    @pytest.mark.chaos
    def test_serve_disagg_pool_drill(self, tmp_path):
        """The disaggregated-pool drill, end to end in real
        subprocesses: a prefill gateway transferring sealed KV to two
        decode gateways by prefix affinity, one decode peer SIGKILLed
        holding injected work plus one corrupted frame — zero failed
        responses, every answer bitwise identical to colocated
        greedy, and the affinity leg's hit counter strictly above a
        round-robin baseline (shared with ``tools/chaos_smoke.py
        --only serve-disagg`` — one source of truth)."""
        import importlib.util as _ilu
        spec = _ilu.spec_from_file_location(
            "chaos_smoke", os.path.join(ROOT, "tools", "chaos_smoke.py"))
        chaos_smoke = _ilu.module_from_spec(spec)
        spec.loader.exec_module(chaos_smoke)
        chaos_smoke.scenario_serve_disagg(
            str(tmp_path), chaos_smoke.Budget(300))
