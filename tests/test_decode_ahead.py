"""Greedy decode ticks dispatched ahead (CPU, fast tier).

Where every live slot is greedy with one candidate and no pass is pending,
the ring engine dispatches decode tick n + 1 on tick n's tokens, which stay
on the device, before it reads tick n. These pin what that must not change
and what it must cost:

- greedy streams equal the serial engine's (the same engine with every
  tick read before the next is dispatched), request by request, whether a
  request ends on ``max_new_tokens``, on EOS (one row more is run and
  dropped, and the slot goes to the next prefill) or by its deadline with
  a tick in flight;
- a sampling request turns the ticks serial and its end turns them back;
  a fault's retry, a drain, a snapshot and a hand-off read the tick in
  flight first, and a stop drops it with the requests it fails: nothing is
  delivered twice, nothing dropped;
- ``serve_decode_ticks_total{mode, reason}`` counts each case;
- ONE executable a program: serial and ahead ticks call the decode
  program with arguments of the same kinds, on a ring and a sharded engine.
"""

import time

import numpy as np
import pytest

from singa_tpu import device
from singa_tpu.models import decode as decode_mod, transformer
from singa_tpu.observability import metrics as obs_metrics
from singa_tpu.observability import spans
from singa_tpu.resilience.faults import FaultPlan
from singa_tpu.serving import EngineDraining, RequestTimeout
from singa_tpu.tensor import Tensor

pytestmark = pytest.mark.serving

DEV = device.create_cpu_device()
KINDS = (("ahead", "none"), ("serial", "first"), ("serial", "sampling"),
         ("serial", "candidates"), ("serial", "pass"),
         ("serial", "drain"))


def _reg():
    return obs_metrics.MetricsRegistry()


def tiny_lm(seed=0, vocab=19):
    """Weight-identical for one seed, so two engines can be compared."""
    DEV.set_rand_seed(seed)
    np.random.seed(seed)
    m = transformer.TransformerLM(vocab, d_model=16, n_heads=2, n_layers=2,
                                  max_len=64, tp=False)
    m.eval()
    m(Tensor(data=np.zeros((1, 4), np.float32), device=DEV,
             requires_grad=False))
    return m


def _engine(m, serial=False, **kw):
    """A ring engine; ``serial``: every tick is read before the next is
    dispatched, as for a pass, which is what the engine did before it
    dispatched ticks ahead."""
    kw = dict(dict(slots=2, max_len=48, prefill_len=8, registry=_reg()),
              **kw)
    eng = m.compile_serving(**kw)
    if serial:
        eng._serial_reason = lambda passes: "pass"
    return eng


def _ticks(eng):
    """``{(mode, reason): calls}`` of ``serve_decode_ticks_total``."""
    c = eng._reg.get("serve_decode_ticks_total")
    return {k: int(c.value(mode=k[0], reason=k[1])) for k in KINDS}


def _compiles(reg):
    """``{program: compile events}`` of the registry's ``compile_seconds``
    (whatever their source: a fresh compile or the compile cache)."""
    out = {}
    for m in reg.snapshot()["metrics"]:
        if m["name"] == "compile_seconds":
            for s in m["series"]:
                p = s["labels"]["program"]
                out[p] = out.get(p, 0) + s["count"]
    return out


def _reference_tokens(m, prompt, n_new, temperature=0.0, rng=None):
    """The uncached eager forward's walk, one grown sequence at a time."""
    seq = list(prompt)
    for _ in range(n_new):
        logits = m(Tensor(data=np.asarray(seq, np.float32)[None],
                          device=DEV, requires_grad=False))
        seq.append(decode_mod.sample_logits(
            np.asarray(logits.data)[0, -1], temperature=temperature,
            rng=rng))
    return seq[len(prompt):]


def _work(n=6, seed=7):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, 19, (int(rng.randint(1, 8)),)),
             int(rng.randint(3, 12))) for _ in range(n)]


def _outcome(fut):
    """A future's tokens, or the type of its error."""
    try:
        return fut.result(timeout=5)["tokens"]
    except Exception as e:      # noqa: BLE001 — the outcome is compared
        return type(e).__name__


def _drive(eng, work, eos_id=None, reap=None):
    """Submit ``work``, then tick until idle; ``reap`` = (request index,
    tick): that request's deadline passes at the start of that tick.
    Returns the futures."""
    futs = [eng.submit(p, max_new_tokens=n, eos_id=eos_id)
            for p, n in work]
    reqs = {id(r.future): r for r in eng.queue._q}
    tick = 0
    while eng._busy():
        if reap is not None and tick == reap[1]:
            req = reqs[id(futs[reap[0]])]
            assert not req.future.done()
            req.deadline = time.monotonic() - 1.0
        eng.step()
        tick += 1
    return futs


class TestParity:
    @pytest.mark.parametrize("finish", ["max_new_tokens", "eos", "deadline"])
    def test_greedy_streams_equal_the_serial_engine(self, finish):
        """Ahead against serial on one workload of six requests through
        two slots, each request's outcome the same: its tokens, or the
        deadline's typed error. On EOS the ahead engine runs the finished
        request's next row, which nothing reads, and the slot goes to the
        request waiting for it."""
        m = tiny_lm(seed=1)
        work = _work()
        eos_id = reap = None
        if finish == "eos":
            # a token that ends two of the first four streams early
            plain = [_reference_tokens(m, p, n) for p, n in work]
            counts = np.bincount([t for s in plain[:4] for t in s[1:-1]],
                                 minlength=19)
            eos_id = int(np.argmax(counts))
        if finish == "deadline":
            reap = (0, 3)
        engines = {"ahead": _engine(m), "serial": _engine(m, serial=True)}
        got = {}
        for name, eng in engines.items():
            futs = _drive(eng, work, eos_id=eos_id, reap=reap)
            got[name] = [_outcome(f) for f in futs]
            assert all(f.deliveries == 1 for f in futs)
            assert eng._inflight is None and eng.active_slots() == 0
            assert eng.compiled_step_info()["n_traces"] == 1
            delivered = sum(len(o) for o in got[name]
                            if isinstance(o, list))
            if finish != "deadline":
                assert eng._reg.get("serve_tokens_total").value() == \
                    delivered
        assert got["ahead"] == got["serial"]
        ahead, serial = _ticks(engines["ahead"]), _ticks(engines["serial"])
        assert ahead["ahead", "none"] > 0 and ahead["serial", "pass"] == 0
        assert set(k for k, v in serial.items() if v) == {("serial", "pass")}
        if finish == "max_new_tokens":
            assert got["ahead"] == [_reference_tokens(m, p, n)
                                    for p, n in work]
        if finish == "eos":
            early = [o for o, (_, n) in zip(got["ahead"][:4], work)
                     if len(o) < n]
            assert len(early) >= 2 and all(o[-1] == eos_id for o in early)
        if finish == "deadline":
            assert got["ahead"][0] == RequestTimeout.__name__
            assert all(isinstance(o, list) for o in got["ahead"][1:])


class TestModes:
    def test_a_sampling_request_turns_ticks_serial_and_back(self):
        """A ``temperature=0.8`` request arriving mid-stream: the tick in
        flight is read, the ticks it lives through run serially (its
        logits are read), and the first tick after it dispatches ahead
        again. Every stream is the reference's."""
        m = tiny_lm(seed=5)
        eng = _engine(m, slots=3)
        rec = spans.recorder()
        rec.clear()
        greedy = [([3, 1, 4], 14), ([6, 5], 14)]
        futs = [eng.submit(p, max_new_tokens=n) for p, n in greedy]
        for _ in range(3):
            eng.step()
        assert eng._inflight is not None
        fut = eng.submit([1, 5, 9, 2], max_new_tokens=4, temperature=0.8,
                         seed=11)
        req = eng.queue._q[-1]
        eng.run_until_idle()
        for (p, n), f in zip(greedy, futs):
            assert f.result(timeout=5)["tokens"] == \
                _reference_tokens(m, p, n)
        assert fut.result(timeout=5)["tokens"] == _reference_tokens(
            m, [1, 5, 9, 2], 4, temperature=0.8,
            rng=np.random.RandomState(11 + req.id))
        ticks = _ticks(eng)
        # its prefill gives the first token, three serial ticks the rest
        assert ticks["serial", "sampling"] == 3
        assert ticks["serial", "first"] == 2
        assert ticks["ahead", "none"] > 0
        assert sum(ticks.values()) == \
            eng._reg.get("serve_decode_steps_total").value()
        flags = [r["ahead"] for r in rec.records()
                 if r.get("name") == "serve.decode"]
        # ahead, then the read of the tick in flight and three serial
        # ticks, then ahead again until the streams' last read
        assert flags[:3] == [1, 1, 1] and flags[3:7] == [0] * 4
        assert flags[7] == 1 and flags[-1] == 0
        assert eng.compiled_step_info()["n_traces"] == 1

    def test_fault_retries_read_the_tick_in_flight(self):
        """A fault before tick 3 (twice) and tick 6 (once), each with a
        tick in flight: the in-flight tick is read before the retry, the
        retry starts serially, and every request gets its reference
        tokens exactly once."""
        m = tiny_lm(seed=2)
        faults = FaultPlan()
        faults.fail_step(3, times=2)
        faults.fail_step(6, times=1)
        eng = _engine(m, faults=faults, max_retries=3)
        work = _work(n=5, seed=3)
        futs = [eng.submit(p, max_new_tokens=n) for p, n in work]
        eng.run_until_idle()
        for (p, n), f in zip(work, futs):
            assert f.result(timeout=5)["tokens"] == \
                _reference_tokens(m, p, n)
            assert f.deliveries == 1
        assert eng._reg.get("serve_retries_total").total() == 3
        ticks = _ticks(eng)
        # the first tick, and the tick after each settled retry
        assert ticks["serial", "first"] == 3
        assert ticks["ahead", "none"] > 0

    @pytest.mark.parametrize("how", ["drain", "snapshot", "handoff", "stop"])
    def test_a_pass_reads_the_tick_in_flight(self, how):
        """With a tick in flight: ``drain`` finishes every request on
        serial ticks; ``snapshot_slot`` reads the tick first, so the
        snapshot continues bitwise on another engine; a deadline drain's
        hand-off moves every slot with its rows; ``stop`` fails what is
        live exactly once and leaves nothing in flight."""
        m = tiny_lm(seed=4)
        work = [([2, 7, 1, 8], 10), ([3, 1], 10)]
        want = [_reference_tokens(m, p, n) for p, n in work]
        eng = _engine(m)
        futs = [eng.submit(p, max_new_tokens=n) for p, n in work]
        for _ in range(4):
            eng.step()
        assert eng._inflight is not None
        if how == "drain":
            assert eng.drain(timeout=30)
            assert [f.result(timeout=5)["tokens"] for f in futs] == want
            assert _ticks(eng)["serial", "drain"] > 0
        elif how == "snapshot":
            snap = eng.snapshot_slot(0)
            assert eng._inflight is None
            dst = _engine(m)
            moved = dst.inject_snapshot(snap["meta"], snap["frame"])
            dst.run_until_idle()
            eng.run_until_idle()
            assert moved.result(timeout=5)["tokens"] == want[0]
            assert [f.result(timeout=5)["tokens"] for f in futs] == want
            assert _ticks(eng)["serial", "first"] == 2
            assert _ticks(dst)["ahead", "none"] > 0
        elif how == "handoff":
            dst = _engine(m)
            moved = []

            def handoff(req, snap, budget):
                moved.append(dst.inject_snapshot(snap["meta"],
                                                 snap["frame"]))
                return True

            assert eng.drain(timeout=0.0, handoff=handoff)
            assert eng._reg.get("serve_handoff_out_total").value() == 2
            dst.run_until_idle()
            assert [f.result(timeout=5)["tokens"] for f in moved] == want
            assert not any(f.done() for f in futs)    # the survivor's now
        else:
            eng.stop()
            for f in futs:
                with pytest.raises(EngineDraining):
                    f.result(timeout=5)
                assert f.deliveries == 1
        assert eng._inflight is None and not eng._busy()
        assert eng.compiled_step_info()["n_traces"] == 1

    def test_a_snapshot_beside_a_running_loop_is_refused(self):
        """Reading the tick in flight places tokens, which only the loop
        may do while it runs: ``snapshot_slot`` from another thread is
        refused, and the loop's requests end as the reference's."""
        m = tiny_lm(seed=4)
        eng = _engine(m)
        fut = eng.submit([2, 7, 1, 8], max_new_tokens=6)
        eng.start()
        try:
            with pytest.raises(RuntimeError, match="serve loop"):
                eng.snapshot_slot(0)
            assert fut.result(timeout=30)["tokens"] == \
                _reference_tokens(m, [2, 7, 1, 8], 6)
        finally:
            eng.stop()
        assert fut.deliveries == 1


class TestOneExecutable:
    @pytest.mark.parametrize("form", ["ring", "uncommitted", "sharded"])
    def test_serial_and_ahead_ticks_share_each_program(self, form):
        """Warm-up as the benchmark does it (three requests of 4
        tokens), then a run mixing ahead and serial ticks (a sampling
        request where the engine can sample; a drain): each program
        traced once, one compile event each, and the decode program ONE
        executable — the serial engine has as many of each. Also where
        the weights are on no device in particular, as the benchmark's
        loader leaves them: the tokens passed on must not be either."""
        import jax
        import jax.numpy as jnp
        # the sharded programs split the vocabulary over the model axis
        m = tiny_lm(seed=6, vocab=20)
        sharded = form == "sharded"
        kw = dict(model_shards=2) if sharded else {}
        if form == "uncommitted":
            for t in m.get_states().values():
                t.data = jnp.asarray(np.asarray(t.data))
        regs = {"ahead": _reg(), "serial": _reg()}
        engines = {name: _engine(m, serial=name == "serial",
                                 registry=regs[name], **kw)
                   for name in regs}
        for name, eng in engines.items():
            warm = [eng.submit(np.arange(1, n + 1) % 19, max_new_tokens=4)
                    for n in (8, 4, 4)]
            eng.run_until_idle()
            assert all(len(f.result(timeout=5)["tokens"]) == 4
                       for f in warm)
            futs = [eng.submit(p, max_new_tokens=n) for p, n in _work()]
            for _ in range(4):
                eng.step()
            if not sharded:
                futs.append(eng.submit([4, 4, 2], max_new_tokens=3,
                                       temperature=0.7, seed=3))
                for _ in range(4):
                    eng.step()
            assert eng.drain(timeout=30)
            assert all(f.result(timeout=5)["tokens"] for f in futs)
            info = eng.compiled_step_info()
            assert (info["prefill_n_traces"], info["n_traces"]) == (1, 1)
            assert _compiles(regs[name]) == {"serve_prefill": 1,
                                             "serve_decode": 1}
        ahead, serial = engines["ahead"], engines["serial"]
        ticks = _ticks(ahead)
        assert ticks["ahead", "none"] > 0 and ticks["serial", "drain"] > 0
        assert ticks["serial", "sampling"] == (0 if sharded else 2)
        held = jax.tree_util.tree_leaves(ahead._P)
        assert any(a.committed for a in held) == (form != "uncommitted")
        assert ahead._decode._cache_size() == 1
        assert serial._decode._cache_size() == 1
        assert ahead._prefill._cache_size() == \
            serial._prefill._cache_size()
