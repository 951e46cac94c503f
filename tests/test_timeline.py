"""Step-timeline attribution (observability/timeline + its wiring
through the profiler, the trainer, the serving engine, heartbeats, and
the Perfetto exporter).

The PR's load-bearing acceptance criteria, pinned here:

- on the COMMITTED trace fixture the bucket fractions are
  deterministic, sum to 1.0 ± 1e-6 of the step window, and the
  overlapped collective is attributed as overlapped while the exposed
  one lands in ``timeline_exposed_collective_seconds`` — CPU-only;
- with ``profile_every`` on, ``n_traces`` stays pinned at 1, profiled
  steps stay out of the step-time series (the PR 9 invariant), and the
  measured non-sample-step overhead stays bounded;
- a comm-heavy straggler gets a ``comm_bound`` cause label in the
  coordinator's aggregated health report;
- flight-recorder evictions are counted and stamped into dumps;
- registry snapshots carry a build stamp.
"""

import json
import os
import time

import numpy as np
import pytest

from singa_tpu import profiling as prof
from singa_tpu.observability import (metrics, perf, spans, timeline,
                                     trace_export)

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "data", "trace_fixture")


@pytest.fixture
def reg():
    return metrics.MetricsRegistry()


@pytest.fixture(autouse=True)
def _clean_recorder():
    spans.recorder().clear()
    yield
    spans.recorder().clear()
    spans.recorder().detach_jsonl()


# ---------------------------------------------------------------------------
# classification + interval math (unit)
# ---------------------------------------------------------------------------

class TestClassify:
    @pytest.mark.parametrize("name, bucket", [
        ("fusion.1", "compute"),
        ("fusion.1|convolution.3", "compute"),
        ("dot_general.5", "compute"),
        ("all-reduce.1", "collective"),
        ("all-reduce-start.2", "collective"),
        ("all-gather.3", "collective"),
        ("reduce-scatter.7", "collective"),
        ("all-to-all.1", "collective"),
        ("collective-permute.4", "collective"),
        ("fusion.9|all-reduce.2", "collective"),   # enriched symbol
        ("send.1", "collective"),
        ("recv-done.1", "collective"),
        ("infeed.7", "memcpy"),
        ("outfeed.2", "memcpy"),
        ("copy.4", "memcpy"),
        ("copy-start.1", "memcpy"),
        ("copy-done.9", "memcpy"),
        ("MemcpyD2H", "memcpy"),
        ("TransferToDevice", "memcpy"),
    ])
    def test_buckets(self, name, bucket):
        assert timeline.classify_op(name) == bucket


class TestIntervals:
    def test_merge(self):
        assert timeline.merge_intervals(
            [(5, 15), (0, 10), (20, 30), (30, 31)]) == \
            [(0.0, 15.0), (20.0, 31.0)]
        assert timeline.merge_intervals([]) == []
        assert timeline.merge_intervals([(5, 5)]) == []   # empty iv

    def test_subtract(self):
        assert timeline.subtract_intervals(
            [(0, 10)], [(3, 5)]) == [(0, 3), (5, 10)]
        assert timeline.subtract_intervals(
            [(0, 10), (20, 30)], [(5, 25)]) == [(0, 5), (25, 30)]
        assert timeline.subtract_intervals([(0, 10)], []) == [(0, 10)]
        assert timeline.subtract_intervals([(0, 10)], [(0, 10)]) == []

    def test_intersect(self):
        assert timeline.intersect_intervals(
            [(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
        assert timeline.intersect_intervals([(0, 10)], [(10, 20)]) == []


# ---------------------------------------------------------------------------
# the committed fixture: deterministic CPU-only decomposition
# ---------------------------------------------------------------------------

class TestFixtureDecomposition:
    """Fixture layout (µs): compute fusion.1 [0,120)+[130,160),
    dot_general.5 [170,220); all-reduce.1 [20,100) fully OVERLAPPED by
    compute; all-gather.3 [220,260) EXPOSED; infeed.7 [260,280);
    host-lane TransferHostToDevice [280,340); window (0,400)."""

    def _analyze(self, window=(0, 400)):
        events = prof.parse_trace_events(FIXTURE)
        return timeline.analyze(events, window=window)

    def test_fractions_deterministic_and_partition(self):
        tl = self._analyze()
        assert tl["fractions"] == {
            "compute": pytest.approx(0.5),
            "collective": pytest.approx(0.1),
            "memcpy": pytest.approx(0.05),
            "host": pytest.approx(0.15),
            "idle": pytest.approx(0.2)}
        # acceptance: an exact partition of the step window
        assert sum(tl["fractions"].values()) == pytest.approx(
            1.0, abs=1e-6)
        assert tl["window_s"] == pytest.approx(400e-6)

    def test_overlapped_vs_exposed_collective(self):
        """The all-reduce under compute is free; the all-gather in the
        gap is the exposed-communication bill."""
        tl = self._analyze()
        assert tl["collective_s"] == pytest.approx(120e-6)
        assert tl["exposed_collective_s"] == pytest.approx(40e-6)
        assert tl["overlapped_collective_s"] == pytest.approx(80e-6)

    def test_host_vs_idle_gap_split(self):
        """A gap where the HOST lane is busy is a host stall; a gap
        where nothing runs anywhere is idle."""
        tl = self._analyze()
        assert tl["host_s"] == pytest.approx(60e-6)
        assert tl["idle_s"] == pytest.approx(80e-6)

    def test_default_window_spans_device_ops(self):
        tl = self._analyze(window=None)
        assert tl["window_s"] == pytest.approx(280e-6)
        assert sum(tl["fractions"].values()) == pytest.approx(
            1.0, abs=1e-6)

    def test_lanes_are_bounded_relative_intervals(self):
        tl = self._analyze()
        assert tl["lanes"]["collective"] == [
            [pytest.approx(20e-6), pytest.approx(80e-6)],
            [pytest.approx(220e-6), pytest.approx(40e-6)]]
        assert tl["lanes"]["host"] == [
            [pytest.approx(280e-6), pytest.approx(60e-6)]]
        for ivs in tl["lanes"].values():
            assert len(ivs) <= 128

    def test_empty_and_unplaceable_events(self):
        assert timeline.analyze([]) is None
        assert timeline.analyze(
            [{"name": "f", "ts": None, "dur": 5, "lane": "device",
              "xla_op": True}]) is None

    def test_cpu_fallback_uses_host_xla_ops(self):
        """No device lanes (CPU CI): host XLA-op events become the op
        timeline; runtime frames are excluded and the host bucket is
        empty (indistinguishable from compute there)."""
        evs = [
            {"name": "fusion.1", "ts": 0, "dur": 50, "lane": "host",
             "xla_op": True},
            {"name": "all-reduce.1", "ts": 60, "dur": 40,
             "lane": "host", "xla_op": True},
            {"name": "PjRtCpuExecutable::Execute", "ts": 0, "dur": 100,
             "lane": "host", "xla_op": False},
        ]
        tl = timeline.analyze(evs)
        assert tl["window_s"] == pytest.approx(100e-6)
        assert tl["fractions"]["compute"] == pytest.approx(0.5)
        assert tl["fractions"]["collective"] == pytest.approx(0.4)
        assert tl["fractions"]["idle"] == pytest.approx(0.1)
        assert tl["fractions"]["host"] == 0.0


def _write_chip_trace(tmp_path, device_rows, host_rows):
    """A `.trace.json.gz` as the profiler writes it for a TPU: one process
    a plane, one thread a row, named by metadata events; rows are
    ``{row name: [(name, ts_us, dur_us), ...]}``."""
    import gzip
    events, tid = [], 0
    for pid, (plane, rows) in enumerate(
            (("/device:TPU:0", device_rows), ("/host:CPU", host_rows)), 1):
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "args": {"name": plane}})
        for row, evs in rows.items():
            tid += 1
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": row}})
            events += [{"ph": "X", "name": n, "pid": pid, "tid": tid,
                        "ts": ts, "dur": dur} for n, ts, dur in evs]
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    return prof.parse_trace_events(str(tmp_path))


# a decode tick on a v5e, in µs: the `Steps` and `XLA Modules` rows cover
# the gaps between the ops of the `XLA Ops` row
CHIP_DEVICE = {
    "Steps": [("0", 0, 1000)],
    "XLA Modules": [("jit_decode_body(1)", 100, 300),
                    ("jit_decode_body(2)", 600, 300)],
    "XLA Ops": [("fusion.1", 100, 150), ("ring_decode.3", 260, 140),
                ("fusion.1", 600, 300)]}


class TestChipTraceRows:
    """ROADMAP D12: the repair that lets `analyze` read a chip trace —
    the device's `Steps` and `XLA Modules` rows are no ops."""

    def test_rows_are_parsed_and_only_ops_are_work(self, tmp_path):
        events = _write_chip_trace(tmp_path, CHIP_DEVICE, {
            "python3": [("PjitFunction(decode_body)", 40, 50)]})
        rows = {e["line"] for e in events if e["lane"] == "device"}
        assert rows == {"Steps", "XLA Modules", "XLA Ops"}
        assert {e["name"] for e in timeline.device_ops(events)} == \
            {"fusion.1", "ring_decode.3"}
        tl = timeline.analyze(events)
        # first op to last: 590 of 800 µs ran an op; Steps alone would
        # have read compute 1.0, idle 0.0
        assert tl["window_s"] == pytest.approx(800e-6)
        assert tl["compute_s"] == pytest.approx(590e-6)
        assert tl["idle_s"] == pytest.approx(210e-6)
        assert tl["fractions"]["idle"] > 0
        assert sum(tl["fractions"].values()) == pytest.approx(1.0,
                                                              abs=1e-6)

    def test_a_serving_tick_reads_its_idle_time_over_the_tick(self,
                                                              tmp_path):
        """The serve site's window is the profiled tick; its own
        annotations are what the host was inside, so a gap under them
        alone is idle, and under the runtime's work it is host."""
        events = _write_chip_trace(tmp_path, CHIP_DEVICE, {
            "python3": [("serve.tick", 0, 1000),
                        ("serve.decode.sample", 400, 120),
                        ("PjitFunction(decode_body)", 560, 50)]})
        window = timeline.host_extent(events, "serve.tick")
        assert window == (0.0, 1000.0)
        tl = timeline.analyze(events, window=window, own=("serve.",))
        # gaps: 0-100, 250-260, 400-600, 900-1000 = 410 µs, of which
        # 560-600 lie under the runtime's dispatch
        assert tl["host_s"] == pytest.approx(40e-6)
        assert tl["idle_s"] == pytest.approx(370e-6)
        assert tl["fractions"]["idle"] == pytest.approx(0.37)
        # without `own` the tick's annotation would hide every gap
        every = timeline.analyze(events, window=window)
        assert every["idle_s"] == 0.0
        assert every["host_s"] == pytest.approx(410e-6)
        assert timeline.host_extent(events, "serve.nothing") is None

    def test_a_device_without_an_op_row_keeps_its_other_rows(self):
        evs = [{"name": n, "ts": ts, "dur": 10.0, "lane": "device",
                "pid": 7, "line": row}
               for n, ts, row in (("step", 0.0, "Steps"),
                                  ("module", 0.0, "XLA Modules"),
                                  ("fusion.2", 0.0, "Stream #1"),
                                  ("fusion.3", 20.0, ""))]
        assert [e["name"] for e in timeline.device_ops(evs)] == \
            ["fusion.2", "fusion.3"]


class TestWaterfall:
    def test_attributes_the_gap(self):
        tl = timeline.analyze(prof.parse_trace_events(FIXTURE),
                              window=(0, 400))
        # 1e6 flops over 400µs against a 1e10 peak: achieved 0.25
        wf = timeline.waterfall(tl, step_flops=1e6, peak_flops=1e10)
        assert wf["achieved_mfu"] == pytest.approx(0.25)
        assert wf["loss"] == {
            "collective": pytest.approx(0.1),
            "memcpy": pytest.approx(0.05),
            "host": pytest.approx(0.15),
            "idle": pytest.approx(0.2),
            "compute_inefficiency": pytest.approx(0.25)}
        # achieved + every loss = 1.0: the waterfall closes
        assert wf["achieved_mfu"] + sum(wf["loss"].values()) == \
            pytest.approx(1.0)

    def test_unknown_flops_is_none(self):
        tl = timeline.analyze(prof.parse_trace_events(FIXTURE))
        assert timeline.waterfall(tl, None, 1e10) is None
        assert timeline.waterfall(tl, 1e6, None) is None
        assert timeline.waterfall(None, 1e6, 1e10) is None


# ---------------------------------------------------------------------------
# gauge publication + heartbeat readback
# ---------------------------------------------------------------------------

class TestRecordTimeline:
    def test_gauges_and_summary_roundtrip(self, reg):
        tl = timeline.analyze(prof.parse_trace_events(FIXTURE),
                              window=(0, 400))
        wf = timeline.waterfall(tl, 1e6, 1e10)
        timeline.record_timeline(tl, registry=reg, site="train",
                                 waterfall_doc=wf)
        g = reg.get("timeline_fraction")
        assert g.value(site="train", bucket="compute") == \
            pytest.approx(0.5)
        assert g.value(site="train", bucket="collective") == \
            pytest.approx(0.1)
        assert reg.get("timeline_exposed_collective_seconds").value(
            site="train") == pytest.approx(40e-6)
        assert reg.get("timeline_collective_total_seconds").value(
            site="train") == pytest.approx(120e-6)
        assert reg.get("timeline_mfu").value(site="train") == \
            pytest.approx(0.25)
        assert reg.get("timeline_mfu_loss").value(
            site="train", bucket="compute_inefficiency") == \
            pytest.approx(0.25)
        # the heartbeat-compact readback
        s = timeline.timeline_summary(reg, site="train")
        assert s["fractions"]["idle"] == pytest.approx(0.2)
        assert s["exposed_collective_s"] == pytest.approx(40e-6)
        assert s["window_s"] == pytest.approx(400e-6)
        # a site nobody recorded reads as None, not zeros
        assert timeline.timeline_summary(reg, site="serve") is None

    def test_empty_registry_summary_is_none(self, reg):
        assert timeline.timeline_summary(reg) is None


class TestClassifyCause:
    def test_comm_bound(self):
        assert timeline.classify_cause(
            {"compute": 0.4, "collective": 0.4, "memcpy": 0.0,
             "host": 0.1, "idle": 0.1}) == "comm_bound"

    def test_data_bound(self):
        assert timeline.classify_cause(
            {"compute": 0.5, "collective": 0.05, "memcpy": 0.1,
             "host": 0.2, "idle": 0.15}) == "data_bound"

    def test_compute_bound(self):
        assert timeline.classify_cause(
            {"compute": 0.9, "collective": 0.02, "memcpy": 0.02,
             "host": 0.03, "idle": 0.03}) == "compute_bound"

    def test_compile_bound_wins(self):
        """A retracing rank also looks idle on the device timeline —
        the compile share is checked FIRST."""
        assert timeline.classify_cause(
            {"compute": 0.1, "collective": 0.0, "memcpy": 0.0,
             "host": 0.0, "idle": 0.9},
            compile_share=0.6) == "compile_bound"

    def test_nothing_to_judge(self):
        assert timeline.classify_cause(None) is None
        assert timeline.classify_cause({}, compile_share=0.1) == \
            "compute_bound"


class TestStragglerCauses:
    @staticmethod
    def _rank(mean, count=20, **extra):
        return dict({"step_time": {"count": count, "sum": mean * count,
                                   "min": mean, "max": mean,
                                   "mean": mean},
                     "wire_errors": 0}, **extra)

    def test_comm_bound_straggler_labeled(self):
        """Acceptance: the slow rank's own heartbeat carried a
        comm-heavy timeline — the aggregated fleet view labels it
        comm_bound (and the straggler list itself is unchanged)."""
        comm_heavy = {"fractions": {
            "compute": 0.4, "collective": 0.45, "memcpy": 0.0,
            "host": 0.05, "idle": 0.1}, "exposed_collective_s": 0.02}
        agg = metrics.aggregate_summaries({
            0: self._rank(0.010), 1: self._rank(0.011),
            2: self._rank(0.050, timeline=comm_heavy),
            3: self._rank(0.012)})
        assert agg["step_time_stragglers"] == [2]
        assert agg["straggler_causes"] == {"2": "comm_bound"}

    def test_data_and_compile_bound_labels(self):
        agg = metrics.aggregate_summaries({
            0: self._rank(0.010),
            1: self._rank(0.050, timeline={"fractions": {
                "compute": 0.5, "collective": 0.0, "memcpy": 0.05,
                "host": 0.25, "idle": 0.2}}),
            2: self._rank(0.060, compile_share=0.7),
            3: self._rank(0.010)})
        assert sorted(agg["step_time_stragglers"]) == [1, 2]
        assert agg["straggler_causes"] == {
            "1": "data_bound", "2": "compile_bound"}

    def test_straggler_without_timeline_is_unknown(self):
        agg = metrics.aggregate_summaries(
            {0: self._rank(0.010), 1: self._rank(0.011),
             2: self._rank(0.050)})
        assert agg["straggler_causes"] == {"2": "unknown"}

    def test_no_stragglers_no_causes(self):
        agg = metrics.aggregate_summaries(
            {0: self._rank(0.010), 1: self._rank(0.011)})
        assert agg["step_time_stragglers"] == []
        assert "straggler_causes" not in agg


class TestHeartbeatCarriesTimeline:
    def test_timeline_and_build_ride_the_summary(self, reg):
        reg.histogram("train_step_seconds").observe(0.1)
        tl = timeline.analyze(prof.parse_trace_events(FIXTURE),
                              window=(0, 400))
        timeline.record_timeline(tl, registry=reg, site="train")
        s = metrics.heartbeat_summary(reg)
        assert s["timeline"]["fractions"]["collective"] == \
            pytest.approx(0.1)
        assert s["timeline"]["exposed_collective_s"] == \
            pytest.approx(40e-6)
        assert "start_ts" in s["build"] and "git" in s["build"]

    def test_compile_share_rides_when_observed(self, reg):
        reg.histogram("train_step_seconds").observe(1.0)
        reg.histogram("compile_seconds",
                      labels=("program", "source")).observe(
            0.5, program="train_step", source="fresh")
        s = metrics.heartbeat_summary(reg)
        assert s["compile_share"] == pytest.approx(0.5)

    def test_summary_without_samples_has_no_timeline(self, reg):
        s = metrics.heartbeat_summary(reg)
        assert "timeline" not in s and "compile_share" not in s


# ---------------------------------------------------------------------------
# build stamp in snapshots
# ---------------------------------------------------------------------------

class TestBuildStamp:
    def test_snapshot_carries_build(self, reg):
        snap = reg.snapshot()
        b = snap["build"]
        assert b["pid"] == os.getpid()
        assert b["start_ts"] <= time.time()
        assert "git" in b and "host" in b
        # stable across calls (cached), and JSON-able
        assert metrics.build_stamp() == metrics.build_stamp()
        json.dumps(snap)

    def test_snapshot_still_validates_and_renders(self, reg):
        from singa_tpu.observability import export
        reg.counter("x_total").inc()
        export.validate_snapshot(reg.snapshot())
        assert "x_total" in export.render_prometheus(reg.snapshot())


# ---------------------------------------------------------------------------
# flight-recorder eviction visibility
# ---------------------------------------------------------------------------

class TestRecorderEvictions:
    def test_evictions_counted_and_stamped_in_dump(self, tmp_path,
                                                   reg):
        before = metrics.default_registry().counter(
            "recorder_evicted_total").value()
        rec = spans.FlightRecorder(capacity=4)
        for i in range(10):
            rec.record({"kind": "event", "name": f"e{i}",
                        "ts": float(i)})
        assert rec.dropped_records == 6
        assert metrics.default_registry().counter(
            "recorder_evicted_total").value() == before + 6
        path = rec.dump(str(tmp_path / "bb.jsonl"), reason="test",
                        registry=reg)
        with open(path) as f:
            head = json.loads(f.readline())
        assert head["dropped_records"] == 6
        assert head["ring_capacity"] == 4

    def test_no_evictions_dump_says_zero(self, tmp_path, reg):
        rec = spans.FlightRecorder(capacity=16)
        rec.record({"kind": "event", "name": "only", "ts": 1.0})
        path = rec.dump(str(tmp_path / "bb.jsonl"), reason="test",
                        registry=reg)
        head = json.loads(open(path).readline())
        assert head["dropped_records"] == 0

    def test_live_records_carry_partiality_marker(self, reg):
        rec = spans.FlightRecorder(capacity=2)
        for i in range(5):
            rec.record({"kind": "event", "name": f"e{i}",
                        "ts": float(i)})
        recs = trace_export.live_records(recorder=rec, registry=reg)
        (marker,) = [r for r in recs
                     if r.get("name") == "recorder.dropped"]
        assert marker["dropped_records"] == 3
        # and a full ring leaves no marker
        rec2 = spans.FlightRecorder(capacity=8)
        rec2.record({"kind": "event", "name": "e", "ts": 1.0})
        assert not [r for r in trace_export.live_records(
            recorder=rec2, registry=reg)
            if r.get("name") == "recorder.dropped"]

    def test_configure_shrink_counts_dropped(self):
        rec = spans.recorder()
        for i in range(8):
            spans.event(f"e{i}")
        before = rec.dropped_records
        counter_before = metrics.default_registry().counter(
            "recorder_evicted_total").value()
        spans.configure(capacity=2)
        try:
            assert rec.dropped_records >= before + 6
            # header total and metrics counter move in lockstep — a
            # dashboard alerting on the counter must see the shrink
            assert metrics.default_registry().counter(
                "recorder_evicted_total").value() >= \
                counter_before + 6
        finally:
            spans.configure(capacity=spans.DEFAULT_CAPACITY)


# ---------------------------------------------------------------------------
# Perfetto: timeline lanes
# ---------------------------------------------------------------------------

class TestTimelineLanes:
    def _sample_event(self):
        return {
            "kind": "event", "name": "timeline.sample", "rank": 0,
            "ts": 10.0, "step": 5, "site": "train",
            "window_s": 0.0004,
            "fractions": {"compute": 0.5, "collective": 0.1,
                          "memcpy": 0.05, "host": 0.15, "idle": 0.2},
            "exposed_collective_s": 4e-5,
            "lanes": {
                "compute": [[0.0, 0.00012], [0.00013, 3e-05],
                            [0.00017, 5e-05]],
                "collective": [[2e-05, 8e-05], [0.00022, 4e-05]],
                "memcpy": [[0.00026, 2e-05]],
                "host": [[0.00028, 6e-05]],
                "idle": [[0.00012, 1e-05], [0.00016, 1e-05],
                         [0.00034, 6e-05]]}}

    def test_lanes_render_as_named_rows(self):
        doc = trace_export.to_chrome_trace(
            [{"kind": "span", "name": "step", "rank": 0, "ts": 10.0,
              "ts_start": 9.999, "dur_s": 0.001},
             self._sample_event()])
        trace_export.validate_chrome_trace(doc)
        lanes = {e["args"]["name"]: (e["pid"], e["tid"])
                 for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "thread_name"
                 and e["args"]["name"].startswith("timeline ")}
        assert set(lanes) == {"timeline compute", "timeline collective",
                              "timeline memcpy", "timeline host",
                              "timeline idle"}
        coll = [e for e in doc["traceEvents"]
                if e.get("cat") == "timeline"
                and e["name"] == "collective"]
        assert len(coll) == 2
        assert coll[1]["dur"] == pytest.approx(40.0)    # 4e-5 s in µs
        # the two collective intervals keep their relative offset
        assert coll[1]["ts"] - coll[0]["ts"] == pytest.approx(200.0)
        # the instant event survives WITHOUT the raw interval list
        (inst,) = [e for e in doc["traceEvents"]
                   if e["name"] == "timeline.sample"]
        assert "lanes" not in inst["args"]
        assert inst["args"]["fractions"]["compute"] == 0.5

    def test_sample_without_lanes_is_plain_event(self):
        ev = self._sample_event()
        del ev["lanes"]
        doc = trace_export.to_chrome_trace([ev])
        trace_export.validate_chrome_trace(doc)
        assert not [e for e in doc["traceEvents"]
                    if e.get("cat") == "timeline"]


# ---------------------------------------------------------------------------
# trainer wiring: gauges refresh, series exclusion, overhead
# ---------------------------------------------------------------------------

class TestTrainerTimeline:
    def _compiled_mlp(self, batch=16):
        from singa_tpu import device, layer, model, opt, tensor

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

        dev = device.create_cpu_device()
        dev.SetRandSeed(7)
        rng = np.random.RandomState(0)
        x = rng.randn(batch, 8).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, batch)]
        tx = tensor.Tensor(data=x, device=dev, requires_grad=False)
        ty = tensor.Tensor(data=y, device=dev, requires_grad=False)
        m = MLP()
        m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
        m.compile([tx], is_train=True, use_graph=True)
        return m, tx, ty

    def test_profile_every_refreshes_timeline_gauges(self, tmp_path):
        """Acceptance (training half): profile_every=2 on — timeline_*
        gauges refresh continuously, fractions partition the window,
        n_traces stays 1, and timeline.sample events carry the lanes
        the exporter renders."""
        from singa_tpu.resilience import ResilientTrainer
        reg = metrics.default_registry()
        m, tx, ty = self._compiled_mlp()
        tr = ResilientTrainer(m, str(tmp_path / "run"),
                              save_interval_steps=3, verbose=False,
                              profile_every=2)
        try:
            s = tr.run([(tx, ty)], num_steps=6)
        finally:
            tr.close()
        assert s["steps_run"] == 6
        assert m.compiled_step_info()["n_traces"] == 1
        g = reg.get("timeline_fraction")
        assert g is not None
        fr = {b: g.value(site="train", bucket=b)
              for b in timeline.BUCKETS}
        assert sum(fr.values()) == pytest.approx(1.0, abs=1e-6)
        assert fr["compute"] > 0            # the MLP computed SOMETHING
        assert reg.get("timeline_window_seconds").value(
            site="train") > 0
        # exposed-comm exists as a series even on a single CPU device
        assert reg.get(
            "timeline_exposed_collective_seconds") is not None
        samples = [r for r in spans.recorder().records()
                   if r["name"] == "timeline.sample"]
        assert samples and samples[-1]["site"] == "train"
        assert samples[-1]["lanes"]["compute"]
        # the profiler kept the newest decomposition for callers
        assert tr._profiler.last_timeline is not None

    def test_non_sample_overhead_still_bounded(self, reg):
        """The timeline work rides ONLY the sampled step: a non-sample
        step still pays one integer check (PR 9's bound, re-measured
        with the timeline layer present)."""
        profiler = perf.SamplingProfiler(every=1000, registry=reg)
        n = 300
        t0 = time.perf_counter()
        for i in range(n):
            profiler.should_sample(i)
        per_step = (time.perf_counter() - t0) / n
        assert per_step < 200e-6, f"{per_step * 1e6:.1f} µs per step"

    def test_profiler_record_without_events_unchanged(self, reg):
        """A caller that passes no events (bench probes, older call
        sites) gets the PR-9 behavior: fusion gauges only, no timeline
        series created."""
        p = perf.SamplingProfiler(every=2, registry=reg)
        p.record(4, {"fusion.1": (1, 0.001)}, capture_s=0.01)
        assert reg.get("timeline_fraction") is None
        assert p.last_timeline is None


# ---------------------------------------------------------------------------
# serving: profiled decode tick
# ---------------------------------------------------------------------------

class TestServingProfiledTick:
    def _tiny_engine(self, **kw):
        from singa_tpu import device, tensor
        from singa_tpu.models import transformer
        dev = device.create_cpu_device()
        np.random.seed(0)
        m = transformer.TransformerLM(19, d_model=16, n_heads=2,
                                      n_layers=2, max_len=64, tp=False)
        m.eval()
        m(tensor.Tensor(data=np.zeros((1, 4), np.float32), device=dev,
                        requires_grad=False))
        return m.compile_serving(slots=2, max_len=32, prefill_len=8,
                                 registry=metrics.MetricsRegistry(),
                                 **kw)

    def test_profiled_tick_records_serve_timeline(self):
        """Acceptance (serving half): every Nth tick profiled — the
        decode program still traced exactly once, the profiled ticks
        stayed out of the SLO latency series, and the engine's registry
        carries the site=serve decomposition."""
        eng = self._tiny_engine(profile_every=3)
        rng = np.random.RandomState(0)
        futs = [eng.submit(rng.randint(1, 19, (3,)), max_new_tokens=6)
                for _ in range(6)]
        eng.run_until_idle()
        for f in futs:
            f.result(timeout=5)
        assert eng.compiled_step_info()["n_traces"] == 1
        reg = eng._reg
        samples = reg.get("serve_profile_samples_total").value()
        assert samples >= 1
        assert reg.get(
            "serve_profile_capture_seconds").summary()["count"] == \
            samples
        # profiled ticks are excluded from the per-token SLO series
        decode_ticks = reg.get("serve_decode_steps_total").value()
        observed = reg.get("serve_token_seconds").summary()["count"]
        assert observed < decode_ticks
        # the decomposition landed (CPU host-fallback lanes)
        assert eng.last_timeline is not None
        g = reg.get("timeline_fraction")
        fr = {b: g.value(site="serve", bucket=b)
              for b in timeline.BUCKETS}
        assert sum(fr.values()) == pytest.approx(1.0, abs=1e-6)
        eng.stop()

    def test_profile_every_off_changes_nothing(self):
        eng = self._tiny_engine()
        fut = eng.submit([1, 2, 3], max_new_tokens=3)
        eng.run_until_idle()
        fut.result(timeout=5)
        assert eng._reg.get("serve_profile_samples_total") is None
        assert eng.last_timeline is None
        eng.stop()

    def test_gateway_serves_timeline_json(self):
        import urllib.request

        from singa_tpu.serving import serve_gateway
        eng = self._tiny_engine(profile_every=2).start()
        server, port = serve_gateway(eng)
        try:
            body = json.dumps({"prompt": [1, 2, 3],
                               "max_new_tokens": 8}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate", data=body,
                headers={"Content-Type": "application/json"})
            doc = json.loads(urllib.request.urlopen(
                req, timeout=30).read())
            assert doc["tokens"]
            tl = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/timeline.json",
                timeout=30).read())
            assert tl["site"] == "serve"
            if tl["timeline"] is not None:      # ≥1 profiled tick ran
                assert "lanes" not in tl["timeline"]
                assert sum(tl["timeline"]["fractions"].values()) == \
                    pytest.approx(1.0, abs=1e-6)
        finally:
            server.shutdown()
            server.server_close()
            eng.stop()


# ---------------------------------------------------------------------------
# cluster end-to-end: the comm-bound straggler in the health report
# ---------------------------------------------------------------------------

class TestClusterCauseLabels:
    """In-process coordinator+workers (the test_cluster pattern): the
    slow rank's heartbeat carries a comm-heavy timeline, and the
    coordinator's aggregated health report names it comm_bound."""

    def _spawn(self, world):
        import socket
        import threading

        from singa_tpu.resilience.cluster import (ClusterConfig,
                                                  make_cluster)
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        addr = f"127.0.0.1:{port}"
        cfg = ClusterConfig(heartbeat_interval=0.1,
                            straggler_after=0.3, dead_after=1.0,
                            connect_timeout=10.0)
        members = [None] * world
        members[0] = make_cluster(0, world, addr, cfg)

        def up(r):
            members[r] = make_cluster(r, world, addr, cfg)

        ts = [threading.Thread(target=up, args=(r,))
              for r in range(1, world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(15)
        assert all(m is not None for m in members)
        return members

    @staticmethod
    def _source(mean, count=20, timeline_doc=None):
        def src():
            s = {"step_time": {"count": count, "sum": mean * count,
                               "min": mean, "max": mean, "mean": mean},
                 "wire_errors": 0}
            if timeline_doc is not None:
                s["timeline"] = timeline_doc
            return s
        return src

    def test_comm_heavy_straggler_labeled_in_health(self):
        from singa_tpu import network as net
        if not net.available():
            pytest.skip("native network layer unavailable")
        members = self._spawn(3)
        try:
            comm_heavy = {"fractions": {
                "compute": 0.35, "collective": 0.45, "memcpy": 0.0,
                "host": 0.05, "idle": 0.15},
                "exposed_collective_s": 0.02}
            members[0].metrics_source = self._source(0.010)
            members[1].metrics_source = self._source(0.011)
            members[2].metrics_source = self._source(
                0.060, timeline_doc=comm_heavy)
            # wait until every rank's POST-injection summary landed
            # (the first beats carry whatever the process registry
            # held — 3 ranks × 20 steps marks the injected set)
            deadline = time.monotonic() + 8
            agg = None
            while time.monotonic() < deadline:
                agg = members[0].health().get("worker_metrics") or {}
                if agg.get("steps") == 60:
                    break
                time.sleep(0.05)
            assert agg.get("step_time_stragglers") == [2], agg
            assert agg.get("straggler_causes") == {"2": "comm_bound"}, \
                agg
            # workers see the cause-labeled view on hb-ack too
            deadline = time.monotonic() + 8
            wagg = None
            while time.monotonic() < deadline:
                wagg = members[1].health().get("worker_metrics") or {}
                if wagg.get("steps") == 60:
                    break
                time.sleep(0.05)
            assert wagg.get("straggler_causes") == \
                {"2": "comm_bound"}, wagg
        finally:
            for m in members:
                try:
                    m.close()
                except Exception:
                    pass
