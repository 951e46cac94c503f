"""The serving tick and the spans, measured from inside (CPU, fast tier).

- a span is also a profiler annotation of its own name, and
  ``span.phase`` splits an open span without a record of its own;
- a serving engine (ring and paged) leaves exactly one ``serve.tick``
  record a tick, with the counts at its boundary, and ``serve.prefill``
  / ``serve.decode`` records whose phases lie inside their duration;
- per-token stamps on the future, the queue wait on the request and in
  ``serve_queue_wait_seconds``.

No number here is a measurement: the clocks are read, not judged.
"""

import glob
import os
import time

import numpy as np
import pytest

from singa_tpu import device
from singa_tpu.models import transformer
from singa_tpu.observability import metrics as obs_metrics
from singa_tpu.observability import spans
from singa_tpu.tensor import Tensor

pytestmark = pytest.mark.serving

DEV = device.create_cpu_device()


def tiny_lm(vocab=19, d_model=16, heads=2, layers=2, max_len=64, seed=0):
    np.random.seed(seed)
    m = transformer.TransformerLM(vocab, d_model=d_model, n_heads=heads,
                                  n_layers=layers, max_len=max_len,
                                  tp=False)
    m.eval()
    m(Tensor(data=np.zeros((1, 4), np.float32), device=DEV,
             requires_grad=False))
    return m


def _engine(layout, registry, **kw):
    paged = dict(kv_layout="paged", kv_block_size=4) \
        if layout == "paged" else {}
    return tiny_lm().compile_serving(slots=3, max_len=32, prefill_len=8,
                                     prefill_batch=2, registry=registry,
                                     **paged, **kw)


def _serve(eng, n=7, seed=0):
    """Submit `n` mixed requests, tick until idle; (futures, ticks)."""
    rng = np.random.RandomState(seed)
    futs = [eng.submit(rng.randint(0, 19, (int(rng.randint(1, 8)),)),
                       max_new_tokens=int(rng.randint(2, 7)),
                       temperature=0.0, seed=i) for i in range(n)]
    return futs, eng.run_until_idle()


def _named(records, name, kind="span"):
    return [r for r in records
            if r.get("kind") == kind and r.get("name") == name]


# in the order they end: `call` inside `dispatch`, `ready` and `fetch`
# inside `readback`
DECODE_PHASES = ["pack", "call", "dispatch", "ready", "fetch", "readback",
                 "sample"]
PREFILL_PHASES = ["pack", "call", "dispatch", "ready", "fetch", "readback",
                  "place"]
NESTED = ("call", "ready", "fetch")
# a decode span that dispatches nothing reads the tick in flight: the last
# of a stream, whose follower no row needs
READ_PHASES = ["ready", "fetch", "readback", "sample"]


def _decode_forms(layout):
    """The phase lists a layout's `serve.decode` spans may carry: a verify
    tick is always dispatched and read in one span."""
    return {tuple(DECODE_PHASES)} if layout == "paged" else \
        {tuple(DECODE_PHASES), tuple(READ_PHASES)}


def _top(record):
    """A program span's phases that do not lie inside another phase."""
    return {k: v for k, v in record["phases"].items() if k not in NESTED}


@pytest.fixture
def ring():
    """The process-wide recorder, emptied and roomy for one test."""
    rec = spans.recorder()
    before = rec._ring.maxlen
    spans.configure(capacity=20000)
    rec.clear()
    yield rec
    rec.clear()
    spans.configure(capacity=before)


class TestSpanPhases:
    def test_phase_sums_into_phases_and_writes_no_record(self, ring):
        with spans.span("outer"):
            with spans.span("work", step=3) as sp:
                with sp.phase("a"):
                    pass
                with sp.phase("b"):
                    pass
                with sp.phase("a"):
                    pass
        records = ring.records()
        assert [r["name"] for r in records] == ["work", "outer"]
        work = records[0]
        assert work["parent"] == "outer" and work["step"] == 3
        assert set(work["phases"]) == {"a", "b"}
        assert all(v >= 0 for v in work["phases"].values())
        assert sum(work["phases"].values()) <= work["dur_s"]
        assert "phases" not in records[1]       # only where used

    def test_a_phase_entered_twice_is_summed(self, ring, monkeypatch):
        ticks = iter([0.0, 1.0, 3.0, 10.0, 14.0, 20.0])
        monkeypatch.setattr(spans.time, "perf_counter",
                            lambda: next(ticks))
        with spans.span("s") as sp:             # t0 = 0
            with sp.phase("a"):                 # 1 -> 3
                pass
            with sp.phase("a"):                 # 10 -> 14
                pass
        rec = ring.records()[-1]                # exit at 20
        assert rec["phases"] == {"a": 6.0} and rec["dur_s"] == 20.0

    def test_a_phase_that_raises_is_still_counted(self, ring):
        with pytest.raises(KeyError):
            with spans.span("s") as sp:
                with sp.phase("bad"):
                    raise KeyError("x")
        rec = ring.records()[-1]
        assert rec["error"] == "KeyError" and "bad" in rec["phases"]

    def test_attrs_added_while_open_land_in_the_record(self, ring):
        with spans.span("s", a=1) as sp:
            sp.attrs["b"] = 2
        assert ring.records()[-1]["a"] == 1
        assert ring.records()[-1]["b"] == 2

    def test_spans_work_where_the_annotation_cannot_be_imported(
            self, ring, monkeypatch):
        monkeypatch.setattr(spans, "_ANNOTATION", False)
        with spans.span("s") as sp:
            with sp.phase("p"):
                pass
        assert list(ring.records()[-1]["phases"]) == ["p"]

    def test_spans_are_host_events_of_a_profiler_trace(self, ring,
                                                       tmp_path):
        """On the trace's clock: between two annotations the test opens
        itself, in order, under their own names."""
        import jax
        from jax.profiler import ProfileData, TraceAnnotation
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with TraceAnnotation("test.before"):
                pass
            with spans.span("probe.span") as sp:
                with sp.phase("one"):
                    pass
                with sp.phase("two"):
                    pass
            with TraceAnnotation("test.after"):
                pass
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                       "*", "*.xplane.pb"))
        assert files, "the profiler wrote no trace"
        data = ProfileData.from_file(files[-1])
        at = {}
        for plane in data.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("test.", "probe.")):
                        at[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
        if "test.before" not in at:
            pytest.skip("this jax's CPU profiler records no host TraceMe "
                        "events; PERF.md shows the spans on the chip's "
                        "trace instead")
        assert set(at) == {"test.before", "probe.span", "probe.span.one",
                           "probe.span.two", "test.after"}
        assert at["test.before"][1] <= at["probe.span"][0]
        assert at["probe.span"][0] <= at["probe.span.one"][0]
        assert at["probe.span.one"][1] <= at["probe.span.two"][0]
        assert at["probe.span.two"][1] <= at["probe.span"][1]
        assert at["probe.span"][1] <= at["test.after"][0]


@pytest.mark.parametrize("layout", ["ring", "paged"])
class TestServeTick:
    def test_one_tick_record_a_tick_with_its_counts(self, ring, layout):
        reg = obs_metrics.MetricsRegistry()
        eng = _engine(layout, reg)
        futs, n_ticks = _serve(eng)
        records = ring.records()
        ticks = _named(records, "serve.tick")
        assert len(ticks) == n_ticks
        assert [t["tick"] for t in ticks] == list(range(n_ticks))
        prefills = _named(records, "serve.prefill")
        decodes = _named(records, "serve.decode")
        assert all(r["parent"] == "serve.tick"
                   for r in prefills + decodes)
        # the counts at the boundary: a prefill batch where one was
        # admitted, a decode where a slot was active
        assert sum(t["admitted"] for t in ticks) == len(futs)
        assert [t["admitted"] for t in ticks if t["admitted"]] == \
            [p["n"] for p in prefills]
        assert sum(1 for t in ticks if t["active"]) == len(decodes)
        assert all(0 <= t["active"] <= 3 and t["queue_depth"] >= 0
                   for t in ticks)
        assert ticks[0]["queue_depth"] == len(futs) - ticks[0]["admitted"]
        for t in ticks:
            assert {"reap", "admit", "post"} <= set(t["phases"])
            assert set(t["phases"]) <= {"reap", "admit", "post"}
            assert sum(t["phases"].values()) <= t["dur_s"]

    def test_decode_and_prefill_phases_lie_inside_their_span(self, ring,
                                                             layout):
        eng = _engine(layout, obs_metrics.MetricsRegistry())
        _serve(eng)
        records = ring.records()
        for r in _named(records, "serve.decode"):
            assert tuple(r["phases"]) in _decode_forms(layout)
            assert sum(_top(r).values()) <= r["dur_s"]
        for r in _named(records, "serve.prefill"):
            assert list(r["phases"]) == PREFILL_PHASES
            assert sum(_top(r).values()) <= r["dur_s"]
        # a tick's own phases and its two child spans fit inside it
        ticks = _named(records, "serve.tick")
        inner = sum(r["dur_s"] for r in records
                    if r.get("parent") == "serve.tick")
        own = sum(sum(t["phases"].values()) for t in ticks)
        assert inner + own <= sum(t["dur_s"] for t in ticks)

    def test_the_ring_grows_by_one_record_a_tick(self, ring, layout):
        """Without per-request events a tick leaves what it left before
        (`serve.decode`, `serve.prefill` where a batch ran) and one
        `serve.tick`: the phases write nothing."""
        eng = _engine(layout, obs_metrics.MetricsRegistry(),
                      trace_requests=False)
        _, n_ticks = _serve(eng)
        records = [r for r in ring.records()
                   if r.get("name") not in ("compile", "retrace")]
        assert not [r for r in records
                    if str(r.get("name", "")).startswith("request.")]
        names = [r["name"] for r in records]
        assert set(names) == {"serve.tick", "serve.prefill", "serve.decode"}
        assert names.count("serve.tick") == n_ticks
        assert len(records) - n_ticks == \
            names.count("serve.prefill") + names.count("serve.decode")
        assert len(records) <= 3 * n_ticks

    def test_token_times_and_queue_wait(self, ring, layout):
        reg = obs_metrics.MetricsRegistry()
        eng = _engine(layout, reg)
        futs, _ = _serve(eng)
        for f in futs:
            res = f.result(timeout=5)
            times = f.token_times
            assert len(times) == len(res["tokens"]) >= 2
            assert all(b >= a for a, b in zip(times, times[1:]))
            assert 0.0 <= res["queue_wait_s"] <= res["ttft_s"]
        wait = reg.get("serve_queue_wait_seconds").summary()
        ttft = reg.get("serve_ttft_seconds").summary()
        assert wait["count"] == ttft["count"] == len(futs)
        assert wait["sum"] <= ttft["sum"]

    def test_first_stamp_is_the_first_token(self, ring, layout):
        eng = _engine(layout, obs_metrics.MetricsRegistry())
        rng = np.random.RandomState(3)
        futs = [eng.submit(rng.randint(0, 19, (5,)), max_new_tokens=4)
                for _ in range(3)]
        held = list(eng.queue._q)
        eng.run_until_idle()
        for req, f in zip(held, futs):
            assert req.future is f
            res = f.result(timeout=5)
            assert f.token_times[0] == req.first_token_at
            assert f.token_times[0] == pytest.approx(
                req.submitted_at + res["ttft_s"], abs=1e-9)
            assert req.admitted_at - req.submitted_at == \
                pytest.approx(res["queue_wait_s"], abs=1e-9)
            assert req.submitted_at <= req.admitted_at \
                <= req.first_token_at

    def test_both_layouts_drive_the_two_programs_by_one_path(self, ring,
                                                             layout):
        """The seam of `serving/kv_cache.py`: whichever layout packs the
        tick, the `serve.prefill` / `serve.decode` spans carry the same
        phase names in the same order, and over 20 ticks with admissions
        all along neither program is traced a second time."""
        reg = obs_metrics.MetricsRegistry()
        eng = _engine(layout, reg)
        rng = np.random.RandomState(3)
        futs, ticks = [], 0
        while ticks < 20:
            if ticks % 2 == 0:      # a new request every other tick
                futs.append(eng.submit(
                    rng.randint(0, 19, (int(rng.randint(1, 8)),)),
                    max_new_tokens=int(rng.randint(3, 9)),
                    temperature=0.0, seed=ticks))
            assert eng.step()
            ticks += 1
        eng.run_until_idle()
        assert all(f.result(timeout=5)["tokens"] for f in futs)
        records = ring.records()
        prefills = _named(records, "serve.prefill")
        decodes = _named(records, "serve.decode")
        assert len(prefills) >= 5 and len(decodes) >= 20
        assert {tuple(r["phases"]) for r in prefills} == \
            {tuple(PREFILL_PHASES)}
        assert tuple(DECODE_PHASES) in \
            {tuple(r["phases"]) for r in decodes} <= _decode_forms(layout)
        info = eng.compiled_step_info()
        assert info["kv_layout"] == layout
        assert (info["prefill_n_traces"], info["n_traces"]) == (1, 1)
        # one compile event a program, no retrace
        compiles = [r for r in records if r.get("name") == "compile"
                    and r.get("kind") == "event"]
        assert sorted(r["program"] for r in compiles) == \
            ["serve_decode", "serve_prefill"]
        assert not _named(records, "retrace", kind="event")


class _Annotations:
    """Stands in for `jax.profiler.TraceAnnotation`: every enter and exit,
    in order, with the names open at it."""

    def __init__(self):
        self.log, self.open = [], []
        book = self

        class Fake:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                book.log.append(("enter", self.name, tuple(book.open)))
                book.open.append(self.name)
                return self

            def __exit__(self, *exc):
                book.log.append(("exit", self.name, tuple(book.open)))
                book.open.remove(self.name)
                return False

        self.cls = Fake

    def entered(self, name):
        """The names open when `name` was entered, one tuple an entry."""
        return [opened for kind, n, opened in self.log
                if kind == "enter" and n == name]


@pytest.fixture
def annotations(monkeypatch):
    book = _Annotations()
    monkeypatch.setattr(spans, "_ANNOTATION", book.cls)
    return book


@pytest.mark.parametrize("layout", ["ring", "paged"])
class TestDispatchAndReadbackSplit:
    """`dispatch` holds the program call as a phase of its own, `readback`
    the wait for the device and the fetch; the engine thread's CPU time in
    `dispatch` rides the span. Host code only: the programs trace once."""

    def test_the_split_phases_lie_inside_the_phase_they_split(self, ring,
                                                             layout):
        eng = _engine(layout, obs_metrics.MetricsRegistry())
        futs, _ = _serve(eng)
        # a sampling request brings the logits through `fetch` too
        futs.append(eng.submit([1, 2, 3], max_new_tokens=4,
                               temperature=0.8, seed=1))
        eng.run_until_idle()
        assert all(f.result(timeout=5)["tokens"] for f in futs)
        records = ring.records()
        spans_ = _named(records, "serve.decode") + \
            _named(records, "serve.prefill")
        assert {r["readback"] for r in spans_} == {"tokens", "logits"}
        for r in spans_:
            ph = r["phases"]
            assert ph["ready"] >= 0 and ph["fetch"] >= 0
            assert ph["ready"] + ph["fetch"] <= ph["readback"]
            if "dispatch" not in ph:
                continue            # it read the tick in flight alone
            assert 0 <= ph["call"] <= ph["dispatch"]
            assert isinstance(r["dispatch_cpu_s"], float)
            assert r["dispatch_cpu_s"] >= 0
        info = eng.compiled_step_info()
        assert (info["prefill_n_traces"], info["n_traces"]) == (1, 1)

    def test_the_annotations_nest_as_the_phases_do(self, ring, layout,
                                                   annotations):
        eng = _engine(layout, obs_metrics.MetricsRegistry())
        _serve(eng, n=3)
        for span in ("serve.decode", "serve.prefill"):
            calls = annotations.entered(f"{span}.call")
            readies = annotations.entered(f"{span}.ready")
            fetches = annotations.entered(f"{span}.fetch")
            assert calls and len(calls) == len(readies) == len(fetches)
            assert all(o[-2:] == (span, f"{span}.dispatch") for o in calls)
            assert all(o[-2:] == (span, f"{span}.readback")
                       for o in readies + fetches)
        # within a read-back the wait comes first, then the fetch
        names = [n for kind, n, _ in annotations.log if kind == "enter"
                 and n.endswith((".ready", ".fetch"))]
        assert names[::2] == [n for n in names if n.endswith(".ready")]
        assert not annotations.open


def test_an_idle_serve_loop_waits_under_serve_idle_and_records_nothing(
        ring, annotations):
    """The loop's wait for work is the annotation `serve.idle` on the
    trace's clock and no record: an idle engine does not evict the ring."""
    eng = _engine("ring", obs_metrics.MetricsRegistry()).start()
    try:
        fut = eng.submit([1, 2, 3], max_new_tokens=3)
        assert fut.result(timeout=60)["tokens"]
        deadline = time.monotonic() + 30
        while len(annotations.entered("serve.idle")) < 3 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        eng.stop()
    idle = annotations.entered("serve.idle")
    assert len(idle) >= 3
    assert all(not any(n.startswith("serve.") for n in o) for o in idle)
    assert not [r for r in ring.records() if r.get("name") == "serve.idle"]
    assert _named(ring.records(), "serve.tick")


def test_an_annotation_alone_makes_no_record(ring, annotations,
                                             monkeypatch):
    with spans.annotation("probe.wait"):
        pass
    assert [(k, n) for k, n, _ in annotations.log] == \
        [("enter", "probe.wait"), ("exit", "probe.wait")]
    assert ring.records() == []
    monkeypatch.setattr(spans, "_ANNOTATION", False)
    with spans.annotation("probe.wait"):
        pass
    assert ring.records() == []


def test_spans_of_a_running_engine_appear_in_a_profiler_trace(tmp_path):
    """The tick's names on the host plane of a trace taken around it."""
    import jax
    from jax.profiler import ProfileData
    eng = _engine("ring", obs_metrics.MetricsRegistry())
    _serve(eng, n=2)                            # compiled before the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _serve(eng, n=3, seed=1)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(files[-1]).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")}
    if not names:
        pytest.skip("this jax's CPU profiler records no host TraceMe "
                    "events; PERF.md shows the spans on the chip's trace")
    assert {"serve.tick", "serve.tick.reap", "serve.tick.admit",
            "serve.tick.post", "serve.prefill", "serve.prefill.pack",
            "serve.prefill.dispatch", "serve.prefill.call",
            "serve.prefill.readback", "serve.prefill.ready",
            "serve.prefill.fetch", "serve.prefill.place", "serve.decode",
            "serve.decode.pack", "serve.decode.dispatch",
            "serve.decode.call", "serve.decode.readback",
            "serve.decode.ready", "serve.decode.fetch",
            "serve.decode.sample"} <= names


def test_handoff_and_inject_phases_appear_only_when_those_passes_run(ring):
    """A draining engine with a handoff callable runs the handoff pass
    inside its tick; an engine that is handed a snapshot runs inject."""
    src = _engine("ring", obs_metrics.MetricsRegistry())
    dst = _engine("ring", obs_metrics.MetricsRegistry())
    fut = src.submit(np.arange(5), max_new_tokens=12)
    src.step()                                  # prefill + one decode
    snap = src.snapshot_slot(0)
    cont = dst.inject_snapshot(snap["meta"], snap["frame"])
    dst.run_until_idle()
    res = cont.result(timeout=5)
    carried = len(res["tokens"]) - len(cont.token_times)
    assert carried >= 1 and res["ttft_s"] is None \
        and res["queue_wait_s"] is None
    assert len(cont.token_times) >= 1           # only this engine's tokens
    dst_ticks = _named(ring.records(), "serve.tick")
    assert any("inject" in t["phases"] for t in dst_ticks)
    assert not any("handoff" in t["phases"] or "transfer" in t["phases"]
                   for t in dst_ticks)
    ring.clear()
    src.drain(timeout=0.0, handoff=lambda req, snapshot, budget: False)
    assert any("handoff" in t["phases"]
               for t in _named(ring.records(), "serve.tick"))
    assert fut.done()


def test_the_first_train_step_records_its_rehearsal_once(ring):
    """`train_step.rehearse` covers the abstract first-step rehearsal;
    the steady-state step records nothing."""
    from singa_tpu import layer, model, opt

    class MLP(model.Model):
        def __init__(self):
            super().__init__()
            self.fc = layer.Linear(4)
            self.loss_fn = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.fc(x)

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = self.loss_fn(out, y)
            self.optimizer(loss)
            return out, loss

    m = MLP()
    m.set_optimizer(opt.SGD(lr=0.1))
    rng = np.random.RandomState(0)
    tx = Tensor(data=rng.randn(8, 6).astype(np.float32), device=DEV)
    ty = Tensor(data=np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)],
                device=DEV)
    m.compile([tx], is_train=True, use_graph=True)
    assert [r["name"] for r in ring.records()
            if r["kind"] == "span"] == ["compile"]
    m(tx, ty)
    spans_now = [r["name"] for r in ring.records() if r["kind"] == "span"]
    assert spans_now == ["compile", "train_step.rehearse"]
    n = len(ring.records())
    for _ in range(3):
        m(tx, ty)
    assert len(ring.records()) == n
