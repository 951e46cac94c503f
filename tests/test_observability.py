"""Unified telemetry (singa_tpu/observability): the metrics registry,
trace spans, the crash flight recorder, and the exporters.

The three load-bearing invariants from the PR contract:

- **Chaos**: an injected preemption (exit 75) and an injected
  divergence (exit 76) both leave ``telemetry/blackbox-<rank>.jsonl``
  behind, containing the final step's spans with correct step/rank
  attribution.
- **Off the compiled step path**: ``compiled_step_info()["n_traces"]``
  stays 1 with telemetry enabled, and the measured per-step host cost
  of the full instrumentation bundle is bounded (loosely) at a few
  hundred microseconds.
- **Fleet view**: heartbeat-carried worker summaries aggregate into one
  coordinator-published view (the in-process cluster half lives in
  tests/test_cluster.py; the pure aggregation math is pinned here).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request
import warnings

import numpy as np
import pytest

from singa_tpu.observability import export, metrics, spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def reg():
    """A private registry — unit tests never touch the process-global
    one (the trainer/cluster suites share it)."""
    return metrics.MetricsRegistry()


@pytest.fixture(autouse=True)
def _clean_recorder():
    """The flight-recorder ring is process-global by design; start each
    test from an empty ring so span assertions see only their own
    records."""
    spans.recorder().clear()
    yield
    spans.recorder().clear()
    spans.recorder().detach_jsonl()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_monotonic(self, reg):
        c = reg.counter("c", "help")
        c.inc()
        c.inc(2.5)
        assert c.value() == 3.5
        with pytest.raises(ValueError, match="only go up"):
            c.inc(-1)

    def test_gauge_up_down(self, reg):
        g = reg.gauge("g")
        g.set(10)
        g.dec(4)
        g.inc()
        assert g.value() == 7.0

    def test_histogram_summary_and_extrema(self, reg):
        h = reg.histogram("h")
        for v in (0.01, 0.2, 5.0):
            h.observe(v)
        s = h.summary()
        assert s["count"] == 3
        assert s["min"] == 0.01 and s["max"] == 5.0
        assert s["mean"] == pytest.approx((0.01 + 0.2 + 5.0) / 3)

    def test_empty_histogram_summary_is_none_safe(self, reg):
        s = reg.histogram("h").summary()
        assert s["count"] == 0
        assert s["min"] is None and s["max"] is None and s["mean"] is None

    def test_labels_partition_series(self, reg):
        c = reg.counter("c", labels=("kind",))
        c.inc(2, kind="a")
        c.inc(kind="b")
        assert c.value(kind="a") == 2 and c.value(kind="b") == 1
        assert c.total() == 3

    def test_label_mismatch_refused(self, reg):
        c = reg.counter("c", labels=("kind",))
        with pytest.raises(ValueError, match="label"):
            c.inc(other="x")
        with pytest.raises(ValueError, match="label"):
            c.inc()                         # missing the declared label

    def test_get_or_create_returns_same_series(self, reg):
        reg.counter("c").inc(5)
        assert reg.counter("c").value() == 5

    def test_kind_conflict_refused(self, reg):
        reg.counter("c")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("c")

    def test_label_conflict_refused(self, reg):
        reg.counter("c", labels=("kind",))
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("c", labels=("other",))

    def test_snapshot_is_json_roundtrippable(self, reg):
        reg.counter("c", "a counter").inc()
        reg.gauge("g").set(2)
        reg.histogram("h").observe(0.5)
        doc = json.loads(json.dumps(reg.snapshot()))
        assert doc["schema"] == metrics.SNAPSHOT_SCHEMA
        export.validate_snapshot(doc)
        assert {m["name"] for m in doc["metrics"]} == {"c", "g", "h"}

    def test_histogram_buckets_cumulative(self, reg):
        h = reg.histogram("h", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 0.5, 100.0):
            h.observe(v)
        (series,) = h.to_doc()["series"]
        assert series["buckets"] == [[0.1, 1], [1.0, 3], ["+Inf", 4]]

    def test_device_peak_flops_table(self):
        """Exact ``device_kind`` keys: the v5e row, None for the CPU
        backend, and an error — never a neighbouring generation's peak
        — for a TPU the table does not know."""
        import types

        def dev(platform, kind):
            return types.SimpleNamespace(platform=platform,
                                         device_kind=kind)

        v5e = dev("tpu", "TPU v5 lite")
        assert metrics.device_peak_flops(v5e) == 197e12
        assert metrics.device_peaks(v5e)["hbm_bytes_per_s"] == 819e9
        assert metrics.device_peak_flops(dev("cpu", "cpu")) is None
        assert metrics.device_peak_flops(None) is None
        for kind in ("TPU v5", "TPU v5p", "TPU v5 lite pod", "TPU v9"):
            with pytest.raises(KeyError, match="no published peaks"):
                metrics.device_peak_flops(dev("tpu", kind))
        assert all(row["source"] for row in
                   metrics.DEVICE_PEAKS.values())


class TestHeartbeatSummaries:
    def test_summary_shape(self, reg):
        reg.histogram("train_step_seconds").observe(0.1)
        reg.counter("cluster_wire_errors_total").inc(3)
        s = metrics.heartbeat_summary(reg)
        assert s["step_time"]["count"] == 1
        assert s["wire_errors"] == 3

    def test_summary_empty_registry(self, reg):
        s = metrics.heartbeat_summary(reg)
        assert s["step_time"] is None and s["wire_errors"] == 0
        # no profile sample yet: the timeline/compile fields are absent
        # (not None-valued noise on every beat); the build stamp rides
        # every summary so the fleet view can correlate with deploys
        assert "timeline" not in s and "compile_share" not in s
        assert "git" in s["build"] and "start_ts" in s["build"]

    def test_aggregation_weighted_mean_and_extrema(self):
        def one(count, mn, mx, mean, wires=0):
            return {"step_time": {"count": count, "sum": mean * count,
                                  "min": mn, "max": mx, "mean": mean},
                    "wire_errors": wires}
        agg = metrics.aggregate_summaries(
            {0: one(10, 0.01, 0.05, 0.02, wires=1),
             1: one(30, 0.02, 0.90, 0.04),
             2: None,                       # a rank with no data yet
             3: {"step_time": None, "wire_errors": 2}})
        assert agg["ranks_reporting"] == 3  # None doesn't count
        assert agg["steps"] == 40
        assert agg["wire_errors"] == 3
        assert agg["step_time_min"] == 0.01
        assert agg["step_time_max"] == 0.90
        assert agg["step_time_mean"] == pytest.approx(
            (0.02 * 10 + 0.04 * 30) / 40)

    def test_aggregation_empty(self):
        agg = metrics.aggregate_summaries({})
        assert agg["ranks_reporting"] == 0 and "steps" not in agg


# ---------------------------------------------------------------------------
# spans + flight recorder
# ---------------------------------------------------------------------------

class TestSpans:
    def test_span_records_duration_and_name(self):
        with spans.span("step", step=3):
            time.sleep(0.002)
        (rec,) = spans.recorder().records()
        assert rec["kind"] == "span" and rec["name"] == "step"
        assert rec["step"] == 3 and rec["dur_s"] >= 0.002

    def test_nesting_records_parent(self):
        with spans.span("step"):
            with spans.span("checkpoint.save"):
                pass
        inner, outer = spans.recorder().records()
        assert inner["name"] == "checkpoint.save"
        assert inner["parent"] == "step"
        assert "parent" not in outer

    def test_context_attribution_merges_and_nests(self):
        with spans.context(rank=2, run="r1"):
            with spans.context(run="r2"):
                spans.event("inner")
            spans.event("outer")
        inner, outer = spans.recorder().records()
        assert inner["rank"] == 2 and inner["run"] == "r2"
        assert outer["rank"] == 2 and outer["run"] == "r1"

    def test_context_is_per_thread(self):
        done = threading.Event()

        def other():
            spans.event("other-thread")
            done.set()

        with spans.context(rank=7):
            t = threading.Thread(target=other)
            t.start()
            assert done.wait(5)
            t.join()
        recs = spans.recorder().records()
        # a fresh thread does NOT inherit the caller's contextvar
        assert "rank" not in recs[0]

    def test_error_captured(self):
        with pytest.raises(RuntimeError):
            with spans.span("step"):
                raise RuntimeError("boom")
        (rec,) = spans.recorder().records()
        assert rec["error"] == "RuntimeError"

    def test_ring_is_bounded(self):
        rec = spans.FlightRecorder(capacity=4)
        for i in range(10):
            rec.record({"i": i})
        got = [r["i"] for r in rec.records()]
        assert got == [6, 7, 8, 9]

    def test_jsonl_sink_mirrors_live(self, tmp_path):
        path = spans.recorder().attach_jsonl(str(tmp_path / "s.jsonl"))
        spans.event("a", x=1)
        with spans.span("step", step=1):
            pass
        spans.recorder().detach_jsonl()
        lines = [json.loads(ln) for ln in open(path)]
        assert [ln["name"] for ln in lines] == ["a", "step"]

    def test_dump_format_and_attribution(self, tmp_path, reg):
        reg.counter("c").inc()
        rec = spans.FlightRecorder(capacity=8)
        rec.record({"kind": "span", "name": "step", "step": 11, "rank": 2,
                    "ts": 0.0, "dur_s": 0.1})
        path = rec.dump(str(tmp_path / "bb.jsonl"), reason="test",
                        rank=2, step=11, extra={"why": "x"}, registry=reg)
        lines = [json.loads(ln) for ln in open(path)]
        assert lines[0]["kind"] == "dump"
        assert lines[0]["reason"] == "test"
        assert lines[0]["rank"] == 2 and lines[0]["step"] == 11
        assert lines[0]["extra"] == {"why": "x"}
        assert lines[1]["name"] == "step"
        assert lines[-1]["kind"] == "metrics"
        export.validate_snapshot(lines[-1]["snapshot"])

    def test_dump_overwrites_previous_incident(self, tmp_path, reg):
        rec = spans.FlightRecorder(capacity=8)
        p1 = rec.dump(str(tmp_path / "bb.jsonl"), "first", registry=reg)
        rec.record({"kind": "event", "name": "later", "ts": 0.0})
        p2 = rec.dump(str(tmp_path / "bb.jsonl"), "second", registry=reg)
        assert p1 == p2
        lines = [json.loads(ln) for ln in open(p2)]
        assert lines[0]["reason"] == "second"
        assert any(ln.get("name") == "later" for ln in lines)

    def test_configure_resizes_ring(self):
        spans.configure(capacity=2)
        try:
            for i in range(5):
                spans.event("e", i=i)
            assert len(spans.recorder().records()) == 2
        finally:
            spans.configure(capacity=spans.DEFAULT_CAPACITY)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

class TestExport:
    def test_prometheus_rendering(self, reg):
        reg.counter("steps", "completed steps").inc(5)
        g = reg.gauge("scale", labels=("kind",))
        g.set(8, kind='lo"ss')             # label escaping
        reg.histogram("lat", buckets=(1.0,)).observe(0.5)
        text = reg.to_prometheus()
        assert "# HELP steps completed steps" in text
        assert "# TYPE steps counter" in text
        assert "steps 5.0" in text
        assert 'scale{kind="lo\\"ss"} 8.0' in text
        assert 'lat_bucket{le="1.0"} 1' in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "lat_sum 0.5" in text and "lat_count 1" in text

    @pytest.mark.parametrize("mutate, match", [
        (lambda d: d.update(schema="bogus/9"), "schema"),
        (lambda d: d.update(metrics="nope"), "not a list"),
        (lambda d: d["metrics"][0].pop("name"), "without a name"),
        (lambda d: d["metrics"][0].update(kind="exotic"), "unknown kind"),
        (lambda d: d["metrics"][0]["series"][0].pop("value"),
         "missing value"),
    ])
    def test_validate_names_the_problem(self, reg, mutate, match):
        reg.counter("c").inc()
        doc = reg.snapshot()
        mutate(doc)
        with pytest.raises(ValueError, match=match):
            export.validate_snapshot(doc)

    def test_validate_catches_noncumulative_buckets(self, reg):
        reg.histogram("h", buckets=(0.1, 1.0)).observe(0.5)
        doc = reg.snapshot()
        doc["metrics"][0]["series"][0]["buckets"][0][1] = 99
        with pytest.raises(ValueError, match="cumulative"):
            export.validate_snapshot(doc)

    def test_http_endpoint_serves_both_forms(self, reg):
        reg.counter("hits").inc(3)
        server, port = export.serve_metrics(reg)
        try:
            base = f"http://127.0.0.1:{port}"
            text = urllib.request.urlopen(
                f"{base}/metrics", timeout=10).read().decode()
            assert "hits 3.0" in text
            doc = json.loads(urllib.request.urlopen(
                f"{base}/metrics.json", timeout=10).read())
            export.validate_snapshot(doc)
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{base}/nope", timeout=10)
        finally:
            server.shutdown()


class TestMetricsDumpCLI:
    def test_selftest_is_green(self):
        """The tier-1 CI gate: the CLI's --selftest round-trips every
        format end to end in a fresh interpreter."""
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_dump.py"),
             "--selftest"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stdout + out.stderr
        assert "selftest ok" in out.stdout

    def test_converts_snapshot_file(self, tmp_path, reg):
        reg.counter("c", "a counter").inc(2)
        snap = str(tmp_path / "m.json")
        with open(snap, "w") as f:
            json.dump(reg.snapshot(), f)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_dump.py"), snap],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        assert "c 2.0" in out.stdout

    def test_rejects_invalid_snapshot(self, tmp_path):
        snap = str(tmp_path / "bad.json")
        with open(snap, "w") as f:
            json.dump({"schema": "wrong"}, f)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_dump.py"), snap],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode != 0


# ---------------------------------------------------------------------------
# the trainer: chaos flight-recorder proof + step-path invariants
# ---------------------------------------------------------------------------

from singa_tpu import device, layer, model, opt, tensor  # noqa: E402
from singa_tpu import network as net                     # noqa: E402
from singa_tpu.resilience import (EXIT_DIVERGED,         # noqa: E402
                                  EXIT_PREEMPTED, FaultPlan,
                                  GuardedOptimizer, ResilientTrainer)
from singa_tpu.resilience.cluster import (ClusterConfig,  # noqa: E402
                                          make_cluster)


class MLP(model.Model):
    def __init__(self):
        super().__init__()
        self.fc1 = layer.Linear(16)
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


def _compiled_mlp(seed=7, guard=False, **guard_kw):
    dev = device.create_cpu_device()
    dev.SetRandSeed(seed)
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 16)]
    tx = tensor.Tensor(data=x, device=dev, requires_grad=False)
    ty = tensor.Tensor(data=y, device=dev, requires_grad=False)
    m = MLP()
    sgd = opt.SGD(lr=0.1, momentum=0.9)
    m.set_optimizer(GuardedOptimizer(sgd, **guard_kw) if guard else sgd)
    m.compile([tx], is_train=True, use_graph=True)
    return m, tx, ty


def _blackbox_lines(ckpt_dir, rank):
    path = os.path.join(str(ckpt_dir), "telemetry",
                        f"blackbox-{rank}.jsonl")
    assert os.path.exists(path), f"no blackbox dump at {path}"
    with open(path) as f:
        return [json.loads(ln) for ln in f]


class TestFlightRecorderChaos:
    def test_preemption_exit75_leaves_blackbox(self, tmp_path):
        """The contract's first half: a preemption (exit 75) leaves
        ``telemetry/blackbox-<rank>.jsonl`` containing the final step's
        spans with correct step/rank attribution."""
        ck = str(tmp_path / "run")
        m, tx, ty = _compiled_mlp(guard=True)
        plan = FaultPlan().preempt_at(step=4, sig=signal.SIGTERM)
        tr = ResilientTrainer(m, ck, save_interval_steps=2, faults=plan,
                              verbose=False)
        try:
            with pytest.raises(SystemExit) as e:
                tr.run([(tx, ty)], num_steps=10)
            assert e.value.code == EXIT_PREEMPTED == 75
        finally:
            tr.close()

        lines = _blackbox_lines(ck, rank=0)
        head = lines[0]
        assert head["kind"] == "dump" and head["reason"] == "preempted"
        assert head["rank"] == 0
        # guard stats ride the dump header for the post-mortem
        assert "loss_scale" in head["extra"]["guard"]
        # the final completed step's span is in the ring, attributed
        step_spans = [ln for ln in lines if ln.get("kind") == "span"
                      and ln.get("name") == "step"]
        assert step_spans, "no step spans in the blackbox"
        final = step_spans[-1]
        assert final["step"] == 4 and final["rank"] == 0
        # the dump closes with a validating metrics snapshot
        assert lines[-1]["kind"] == "metrics"
        export.validate_snapshot(lines[-1]["snapshot"])
        # checkpoint/restore narrative spans are present too
        names = {ln.get("name") for ln in lines
                 if ln.get("kind") == "span"}
        assert "checkpoint.save" in names and "restore" in names

    @pytest.mark.skipif(not net.available(),
                        reason="native network layer unavailable")
    def test_divergence_exit76_leaves_blackbox_per_rank(self, tmp_path):
        """The contract's second half: repeated replica divergence
        (exit 76) dumps a blackbox on EVERY rank, each stamped with its
        own rank even though the recorder ring is process-global."""
        addr = None
        import socket as _socket
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        addr = f"127.0.0.1:{s.getsockname()[1]}"
        s.close()
        fast = ClusterConfig(heartbeat_interval=0.05, straggler_after=0.2,
                             dead_after=10.0, connect_timeout=10.0)
        td = str(tmp_path / "run")
        codes = [None, None]

        def run_rank(r):
            m, tx, ty = _compiled_mlp()
            faults = FaultPlan()
            if r == 1:
                faults.diverge_at(5, times=10)   # diverges again after
            cluster = make_cluster(r, 2, addr, fast, faults=faults)
            trainer = ResilientTrainer(
                m, td, save_interval_steps=2, cluster=cluster,
                faults=faults, fingerprint_every=3,
                max_divergence_rollbacks=1, exit_on_preempt=True,
                install_signal_handlers=False, commit_timeout=20,
                start_barrier_timeout=20, verbose=False)
            try:
                trainer.run([(tx, ty)] * 4, num_steps=12)
            except SystemExit as e:
                codes[r] = e.code
            finally:
                trainer.close()
                cluster.close()

        ts = [threading.Thread(target=run_rank, args=(r,))
              for r in (0, 1)]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for t in ts:
                t.start()
            for t in ts:
                t.join(120)
        # the coordinator always learns the verdict and exits 76; the
        # other rank may instead observe its peer's death first (75)
        assert codes[0] == EXIT_DIVERGED == 76, codes
        assert codes[1] in (EXIT_DIVERGED, EXIT_PREEMPTED), codes

        for r in (0, 1):
            lines = _blackbox_lines(td, rank=r)
            head = lines[0]
            assert head["kind"] == "dump" and head["rank"] == r
            # rank 0 certainly died of divergence; rank 1 may have died
            # of membership loss after rank 0 exited
            if r == 0:
                assert head["reason"] in ("diverged", "quarantine")
            own = [ln for ln in lines if ln.get("kind") == "span"
                   and ln.get("name") == "step" and ln.get("rank") == r]
            assert own, f"rank {r}: no own step spans in the blackbox"
            # the quarantined step is the last thing this rank ran
            assert own[-1]["step"] >= 5
            assert lines[-1]["kind"] == "metrics"

    def test_rollback_dumps_blackbox_and_recovers(self, tmp_path):
        """The guard-rollback abnormal path dumps too — and because the
        run then RECOVERS, the summary still carries the dump path."""
        ck = str(tmp_path / "run")
        m, tx, ty = _compiled_mlp(guard=True, init_scale=128.0)
        plan = (FaultPlan().poison_batch(step=3).poison_batch(step=4)
                .poison_batch(step=5))
        tr = ResilientTrainer(m, ck, save_interval_steps=1, faults=plan,
                              rollback_after=3, verbose=False)
        try:
            with pytest.warns(UserWarning, match="rolled back"):
                s = tr.run([(tx, ty)], num_steps=8)
        finally:
            tr.close()
        assert s["rollbacks"] == 1
        assert s["blackbox"] == os.path.join(ck, "telemetry",
                                             "blackbox-0.jsonl")
        lines = _blackbox_lines(ck, rank=0)
        assert lines[0]["reason"] == "rollback"
        assert any(ln.get("name") == "rollback" for ln in lines)


class TestStepPathInvariants:
    def test_n_traces_stays_one_with_telemetry_on(self, tmp_path):
        """Telemetry must live OUTSIDE the compiled step: after a
        telemetry-instrumented training run, the compiled step traced
        exactly once."""
        m, tx, ty = _compiled_mlp(guard=True)
        tr = ResilientTrainer(m, str(tmp_path / "run"),
                              save_interval_steps=2, verbose=False)
        try:
            s = tr.run([(tx, ty)], num_steps=6)
        finally:
            tr.close()
        assert s["steps_run"] == 6
        assert m.compiled_step_info()["n_traces"] == 1

    def test_summary_reports_first_step_latency(self, tmp_path):
        m, tx, ty = _compiled_mlp()
        tr = ResilientTrainer(m, str(tmp_path / "run"),
                              save_interval_steps=2, verbose=False)
        try:
            s = tr.run([(tx, ty)], num_steps=3)
        finally:
            tr.close()
        lat = s["first_step_latency_s"]
        assert lat is not None and 0 < lat < 300
        # the gauge carries the same number for scrapes
        g = metrics.default_registry().get("restart_to_first_step_seconds")
        assert g.value() == pytest.approx(lat, abs=1e-6)

    def test_step_metrics_populated_by_training(self, tmp_path):
        m, tx, ty = _compiled_mlp()
        tr = ResilientTrainer(m, str(tmp_path / "run"),
                              save_interval_steps=2, verbose=False)
        reg = metrics.default_registry()
        before = reg.counter("train_steps_total").value()
        h_before = reg.histogram("train_step_seconds").summary()["count"]
        try:
            tr.run([(tx, ty)], num_steps=4)
        finally:
            tr.close()
        assert reg.counter("train_steps_total").value() == before + 4
        assert reg.histogram(
            "train_step_seconds").summary()["count"] == h_before + 4
        assert reg.gauge(
            "train_throughput_samples_per_sec").value() > 0
        # checkpoint instrumentation fired too (saves at steps 0 and 2)
        assert reg.counter("checkpoint_saves_total").value() >= 2
        assert reg.histogram(
            "checkpoint_restore_seconds").summary()["count"] >= 1

    def test_instrumentation_overhead_bounded(self):
        """The PR contract's loose bound: the ENTIRE per-step telemetry
        bundle (counter + histogram + 2 gauges + 2 spans under an
        ambient context) must cost well under a few hundred µs per
        step on the host."""
        reg = metrics.MetricsRegistry()
        c = reg.counter("train_steps_total")
        h = reg.histogram("train_step_seconds")
        g1 = reg.gauge("train_throughput_samples_per_sec")
        g2 = reg.gauge("guard_bad_streak")
        n = 300
        with spans.context(rank=0):
            t0 = time.perf_counter()
            for i in range(n):
                with spans.span("data.next", step=i):
                    pass
                with spans.span("step", step=i):
                    pass
                c.inc()
                h.observe(0.001)
                g1.set(123.0)
                g2.set(0)
            per_step = (time.perf_counter() - t0) / n
        # generous even for a loaded CI box; real cost is ~10 µs
        assert per_step < 500e-6, f"{per_step * 1e6:.1f} µs per step"


# ---------------------------------------------------------------------------
# histogram quantile summaries (the serving SLOs read p99 off these)
# ---------------------------------------------------------------------------

class TestQuantiles:
    def test_known_uniform_distribution(self, reg):
        """20k U(0,1) observations: p50/p95/p99 land within bucket
        resolution of the true quantiles."""
        h = reg.histogram("lat_seconds")
        rng = np.random.RandomState(0)
        for v in rng.uniform(0, 1, 20000):
            h.observe(v)
        q = reg.snapshot()["metrics"][0]["series"][0]["quantiles"]
        assert abs(q["p50"] - 0.5) < 0.06, q
        assert abs(q["p95"] - 0.95) < 0.06, q
        assert abs(q["p99"] - 0.99) < 0.06, q

    def test_known_exponential_distribution(self, reg):
        """Skewed tail: quantiles of Exp(λ=10) vs the closed form
        −ln(1−q)/λ, within the (coarser, log-spaced) bucket error."""
        h = reg.histogram("exp_seconds")
        rng = np.random.RandomState(1)
        lam = 10.0
        for v in rng.exponential(1.0 / lam, 50000):
            h.observe(v)
        q = reg.snapshot()["metrics"][0]["series"][0]["quantiles"]
        for name, p in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99)):
            true = -np.log(1 - p) / lam
            assert abs(q[name] - true) / true < 0.5, (name, q[name], true)

    def test_single_observation_is_exact(self, reg):
        """min/max clamping makes degenerate series EXACT, not
        bucket-approximate."""
        h = reg.histogram("one_seconds")
        h.observe(0.0042)
        q = reg.snapshot()["metrics"][0]["series"][0]["quantiles"]
        assert all(abs(v - 0.0042) < 1e-12 for v in q.values()), q

    def test_empty_series_quantiles_are_none(self):
        q = export.series_quantiles(
            {"count": 0, "buckets": [["+Inf", 0]],
             "min": None, "max": None})
        assert q == {"p50": None, "p95": None, "p99": None}

    def test_quantiles_clamped_to_observed_extrema(self, reg):
        """All mass in one bucket: interpolation may not stray outside
        the exact [min, max] actually observed."""
        h = reg.histogram("narrow_seconds")
        for v in (0.030, 0.031, 0.032):
            h.observe(v)                  # all inside the (0.025, 0.05] bucket
        q = reg.snapshot()["metrics"][0]["series"][0]["quantiles"]
        for v in q.values():
            assert 0.030 <= v <= 0.032, q

    def test_prometheus_text_carries_quantiles(self, reg):
        h = reg.histogram("lat_seconds")
        for v in (0.001, 0.002, 0.5):
            h.observe(v)
        text = export.render_prometheus(reg.snapshot())
        assert "lat_seconds_p50" in text
        assert "lat_seconds_p95" in text
        assert "lat_seconds_p99" in text

    def test_bucket_quantile_math_direct(self):
        # 10 observations, cumulative over edges [1, 2, +Inf]
        buckets = [[1.0, 4], [2.0, 8], ["+Inf", 10]]
        # p50 → target 5 → inside (1, 2]: 1 + (5-4)/(8-4) * 1 = 1.25
        assert abs(export.bucket_quantile(buckets, 10, 0.5) - 1.25) < 1e-9
        # p99 → target 9.9 → overflow bucket → exact max when known
        assert export.bucket_quantile(buckets, 10, 0.99, hi=7.5) == 7.5
        # ... else the last finite edge
        assert export.bucket_quantile(buckets, 10, 0.99) == 2.0
        assert export.bucket_quantile(buckets, 0, 0.5) is None

    def test_validate_accepts_and_checks_quantiles(self, reg):
        reg.histogram("h").observe(1.0)
        doc = reg.snapshot()
        export.validate_snapshot(doc)     # quantiles present: fine
        doc["metrics"][0]["series"][0]["quantiles"] = "nope"
        with pytest.raises(ValueError, match="quantiles"):
            export.validate_snapshot(doc)
