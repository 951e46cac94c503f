"""Serving-engine suite (CPU, fast tier): the continuous-batching
invariants the subsystem exists for.

- the decode program NEVER retraces: ≥3 mid-batch slot refills with
  mixed sequence lengths, ``compiled_step_info()["n_traces"] == 1``;
- exactly-once response delivery — including across injected
  serve-loop faults, a crash, and a graceful drain;
- ring-cache wraparound correctness against an uncached reference
  (full causal while the sequence fits, sliding-window after);
- drain semantics (finish everything, refuse loudly, exit 0) and
  fleet failover;
- one decode path: the engine's greedy output equals the uncached
  eager forward's argmax walk, for the transformer AND the char-rnn;
- ONNX imports serve through the same engine (scenario diversity).
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from singa_tpu import device, layer, model, sonnx, tensor
from singa_tpu.models import char_rnn, decode as decode_mod, transformer
from singa_tpu.observability import metrics as obs_metrics
from singa_tpu.resilience.faults import FaultPlan
from singa_tpu.serving import (EXIT_DRAINED, EngineDraining, FleetRouter,
                               QueueFull, RequestTimeout, ServingError,
                               ServingReplica, kv_cache, serve_gateway)
from singa_tpu.tensor import Tensor

pytestmark = pytest.mark.serving

DEV = device.create_cpu_device()


def _reg():
    return obs_metrics.MetricsRegistry()


def tiny_lm(vocab=19, d_model=16, heads=2, layers=2, max_len=64,
            seed=0):
    np.random.seed(seed)
    m = transformer.TransformerLM(vocab, d_model=d_model, n_heads=heads,
                                  n_layers=layers, max_len=max_len,
                                  tp=False)
    m.eval()
    m(Tensor(data=np.zeros((1, 4), np.float32), device=DEV,
             requires_grad=False))
    return m


def tiny_charrnn(vocab=11, hidden=8, seed=0):
    np.random.seed(seed)
    m = char_rnn.CharRNN(vocab, hidden_size=hidden)
    m.eval()
    xs = [Tensor(data=np.eye(vocab, dtype=np.float32)[
        np.random.randint(0, vocab, (2,))], device=DEV,
        requires_grad=False) for _ in range(3)]
    m.forward(xs)
    return m


class TestContinuousBatching:
    def test_refill_never_retraces_and_exactly_once(self):
        """THE acceptance invariant: ≥3 mid-batch slot refills with
        mixed sequence lengths; the decode program traced exactly once;
        every request answered exactly once and completely."""
        m = tiny_lm()
        eng = m.compile_serving(slots=2, max_len=32, prefill_len=8,
                                prefill_batch=1, registry=_reg())
        rng = np.random.RandomState(0)
        want = []
        futs = []
        for i in range(7):
            n_new = int(rng.randint(2, 7))
            prompt = rng.randint(0, 19, (int(rng.randint(1, 8)),))
            futs.append(eng.submit(prompt, max_new_tokens=n_new,
                                   temperature=0.7, seed=i))
            want.append(n_new)
        eng.run_until_idle()
        info = eng.compiled_step_info()
        assert info["n_traces"] == 1, info
        assert info["prefill_n_traces"] == 1, info
        # 7 prompts through 2 slots = at least 5 mid-batch refills
        for f, n_new in zip(futs, want):
            res = f.result(timeout=5)
            assert f.deliveries == 1
            assert len(res["tokens"]) == n_new
            assert res["ttft_s"] is not None

    def test_greedy_matches_uncached_reference_forward(self):
        """Ring-cache decode vs the uncached reference: grow the
        sequence, run the FULL eager forward, argmax — token for
        token."""
        m = tiny_lm(seed=1)
        prompt = np.random.RandomState(1).randint(0, 19, (6,))
        ref = _reference_tokens(m, prompt, 6)

        eng = m.compile_serving(slots=2, max_len=32, prefill_len=8,
                                registry=_reg())
        fut = eng.submit(prompt, max_new_tokens=6, temperature=0.0)
        eng.run_until_idle()
        assert fut.result(timeout=5)["tokens"] == ref

    def test_charrnn_engine_matches_sample(self):
        """The char-rnn serves through the SAME engine; greedy output
        equals the (shared-decode-helper) reference sampler's."""
        m = tiny_charrnn()
        ref = char_rnn.sample(m, [3, 5], 11, nsamples=6, use_max=True)
        eng = m.compile_serving(slots=2, max_len=16, prefill_len=4,
                                registry=_reg())
        fut = eng.submit([3, 5], max_new_tokens=6, temperature=0.0)
        eng.run_until_idle()
        assert fut.result(timeout=5)["tokens"] == ref
        assert eng.compiled_step_info()["n_traces"] == 1

    def test_invalid_request_params_rejected(self):
        """max_new_tokens < 1 and a prefill_len beyond the model's
        positional table fail typed at submit/construction, never as a
        shape error inside the first compiled program."""
        m = tiny_lm(max_len=8)
        with pytest.raises(ValueError, match="positional-embedding"):
            m.compile_serving(slots=2, max_len=32, prefill_len=16,
                              registry=_reg())
        m2 = tiny_lm()
        eng = m2.compile_serving(slots=2, max_len=32, prefill_len=8,
                                 registry=_reg())
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit([1, 2], max_new_tokens=0)

    def test_timeout_zero_means_already_due(self):
        """timeout=0 is a fail-fast probe (immediate deadline), NOT
        'no deadline'."""
        m = tiny_lm()
        eng = m.compile_serving(slots=1, max_len=32, prefill_len=4,
                                registry=_reg())
        fut = eng.submit([1], max_new_tokens=2, timeout=0)
        eng.run_until_idle()
        with pytest.raises(RequestTimeout):
            fut.result(timeout=5)
        assert fut.deliveries == 1

    def test_charrnn_policy_is_honored_not_just_reported(self):
        """compile_serving(policy=bf16) on the char-rnn actually runs
        bf16 state/compute — what healthz reports is what executes."""
        import jax.numpy as jnp
        m = tiny_charrnn()
        eng = m.compile_serving(slots=2, max_len=16, prefill_len=4,
                                policy="bf16_mixed", registry=_reg())
        assert eng._cache["h"].dtype == jnp.bfloat16
        fut = eng.submit([3, 5], max_new_tokens=4, temperature=0.0)
        eng.run_until_idle()
        assert len(fut.result(timeout=5)["tokens"]) == 4
        assert eng.compiled_step_info()["policy"]["name"] == "bf16_mixed"

    def test_unknown_serving_option_raises(self):
        """A typo'd or wrong-engine kwarg fails at construction, never
        silently falls back to defaults."""
        m = tiny_lm()
        with pytest.raises(TypeError, match="prefil_len"):
            m.compile_serving(slots=2, prefil_len=8)   # typo
        with pytest.raises(TypeError, match="batch"):
            m.compile_serving(batch=16)    # stateless-engine option

    def test_eos_and_long_prompt_rejection(self):
        m = tiny_lm()
        eng = m.compile_serving(slots=2, max_len=32, prefill_len=4,
                                registry=_reg())
        with pytest.raises(ServingError):
            eng.submit(np.arange(9), max_new_tokens=2)  # > prefill_len
        # eos stops generation early
        fut = eng.submit([1, 2], max_new_tokens=20, temperature=0.0)
        eng.run_until_idle()
        first = fut.result(timeout=5)["tokens"][0]
        fut2 = eng.submit([1, 2], max_new_tokens=20, temperature=0.0,
                          eos_id=first)
        eng.run_until_idle()
        assert fut2.result(timeout=5)["tokens"] == [first]

    def test_bf16_policy_serving(self):
        """bf16 serving out of the box: cache in compute dtype, logits
        host-side f32, still one trace."""
        import jax.numpy as jnp
        m = tiny_lm()
        eng = m.compile_serving(slots=2, max_len=32, prefill_len=8,
                                policy="bf16_mixed", registry=_reg())
        assert eng._cache[0]["k"].dtype == jnp.bfloat16
        fut = eng.submit([1, 2, 3], max_new_tokens=4, temperature=0.0)
        eng.run_until_idle()
        assert len(fut.result(timeout=5)["tokens"]) == 4
        info = eng.compiled_step_info()
        assert info["n_traces"] == 1
        assert info["policy"]["name"] == "bf16_mixed"


class TestRingCache:
    def test_wraparound_vs_reference(self):
        """Ring attend == reference softmax attention over the last
        ``min(pos+1, L)`` tokens — exercises BOTH regimes: full causal
        while the sequence fits the ring, sliding-window after it
        wraps."""
        rng = np.random.RandomState(0)
        W, H, L, D = 2, 2, 4, 3
        level = kv_cache.init_cache(W, H, L, D)
        ks = rng.randn(10, W, H, D).astype(np.float32)
        vs = rng.randn(10, W, H, D).astype(np.float32)
        scale = 1.0 / np.sqrt(D)
        for pos in range(10):
            p = np.full((W,), pos, np.int32)
            level = kv_cache.write_token(level, ks[pos], vs[pos], p)
            q = rng.randn(W, H, 1, D).astype(np.float32)
            got = np.asarray(kv_cache.attend(q, level, p, scale))
            lo = max(0, pos + 1 - L)
            win_k = ks[lo:pos + 1]          # (T, W, H, D)
            win_v = vs[lo:pos + 1]
            for w in range(W):
                for h in range(H):
                    s = (win_k[:, w, h] @ q[w, h, 0]) * scale
                    a = np.exp(s - s.max())
                    a = a / a.sum()
                    ref = a @ win_v[:, w, h]
                    np.testing.assert_allclose(got[w, h, 0], ref,
                                               rtol=1e-5, atol=1e-5)

    def test_ring_mask_window(self):
        import jax.numpy as jnp
        mask = np.asarray(kv_cache.ring_mask(
            jnp.asarray([0, 2, 5], jnp.int32), 4))
        assert mask[0].tolist() == [True, False, False, False]
        assert mask[1].tolist() == [True, True, True, False]
        assert mask[2].tolist() == [True, True, True, True]

    def test_prefill_write_respects_valid_mask(self):
        import jax.numpy as jnp
        level = kv_cache.init_cache(2, 1, 4, 2)
        rows = jnp.ones((1, 3, 2))
        upd = kv_cache.write_prompt(level, 1, rows, rows,
                                    jnp.asarray(False))
        assert float(np.abs(np.asarray(upd["k"])).sum()) == 0.0
        upd = kv_cache.write_prompt(level, 1, rows, rows,
                                    jnp.asarray(True))
        assert float(np.asarray(upd["k"])[1, 0, :3].sum()) == 6.0
        assert float(np.asarray(upd["k"])[0].sum()) == 0.0


class TestExactlyOnce:
    def test_injected_faults_retry_without_loss_or_dup(self):
        """A tick-level fault fires BEFORE state mutates, so the retry
        replays cleanly: nothing dropped, nothing delivered twice."""
        m = tiny_lm()
        reg = _reg()
        faults = FaultPlan()
        faults.fail_step(1, times=2)
        faults.fail_step(3, times=1)
        eng = m.compile_serving(slots=2, max_len=32, prefill_len=8,
                                registry=reg, faults=faults,
                                max_retries=3)
        futs = [eng.submit([1, 2, 3], max_new_tokens=4, seed=i)
                for i in range(5)]
        eng.run_until_idle()
        for f in futs:
            assert len(f.result(timeout=5)["tokens"]) == 4
            assert f.deliveries == 1
        assert reg.get("serve_retries_total").total() == 3

    def test_crash_fails_pending_once_and_dumps_blackbox(self, tmp_path):
        """Fault beyond the retry budget: the loop crashes, dumps the
        serve blackbox, and every pending future fails EXACTLY once."""
        m = tiny_lm()
        faults = FaultPlan()
        for s in range(6):
            faults.fail_step(s, times=10)
        eng = m.compile_serving(slots=2, max_len=32, prefill_len=8,
                                registry=_reg(), faults=faults,
                                max_retries=2,
                                telemetry_dir=str(tmp_path))
        futs = [eng.submit([1, 2], max_new_tokens=3) for _ in range(3)]
        eng.start()
        for f in futs:
            with pytest.raises(ServingError):
                f.result(timeout=30)
            assert f.deliveries == 1
        box = tmp_path / "blackbox-serve.jsonl"
        assert box.exists()
        header = json.loads(box.read_text().splitlines()[0])
        assert header["reason"] == "serve_loop_crash"
        # a crashed engine refuses new submits LOUDLY — a future that
        # could never resolve violates exactly-once ("never zero")
        with pytest.raises(ServingError, match="crashed"):
            eng.submit([1], max_new_tokens=1)
        eng.stop()

    def test_popped_batch_failure_delivers_error_once(self, tmp_path):
        """Requests already popped from the queue when the compiled
        prefill dies are in neither the queue nor the slot table — they
        must still fail exactly once, never hang."""
        m = tiny_lm()
        eng = m.compile_serving(slots=2, max_len=32, prefill_len=8,
                                registry=_reg(),
                                telemetry_dir=str(tmp_path))

        def boom(*a, **k):
            raise RuntimeError("prefill died")

        eng._prefill = boom
        futs = [eng.submit([1, 2], max_new_tokens=3) for _ in range(3)]
        eng.start()
        for f in futs:
            with pytest.raises(ServingError):
                f.result(timeout=30)
            assert f.deliveries == 1
        eng.stop()

    def test_inflight_deadline_raises_request_timeout(self):
        """A deadline that passes MID-generation raises the same typed
        error a queued expiry does."""
        m = tiny_lm()
        eng = m.compile_serving(slots=1, max_len=32, prefill_len=4,
                                registry=_reg())
        fut = eng.submit([1, 2], max_new_tokens=10_000, timeout=0.2)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 30 and not fut.done():
            eng.step()
        with pytest.raises(RequestTimeout):
            fut.result(timeout=5)
        assert fut.deliveries == 1
        assert eng.active_slots() == 0

    def test_queue_full_and_deadline(self):
        m = tiny_lm()
        reg = _reg()
        eng = m.compile_serving(slots=1, max_len=32, prefill_len=4,
                                registry=reg, queue_capacity=2)
        eng.submit([1], max_new_tokens=2)
        eng.submit([1], max_new_tokens=2)
        with pytest.raises(QueueFull):
            eng.submit([1], max_new_tokens=2)
        eng.run_until_idle()
        # a queued request whose deadline passes is timed out, not run
        late = eng.submit([1], max_new_tokens=2, timeout=0.001)
        time.sleep(0.05)
        eng.run_until_idle()
        with pytest.raises(RequestTimeout):
            late.result(timeout=5)
        assert late.deliveries == 1
        assert reg.get("serve_requests_total").value(
            status="timed_out") == 1


class TestDrainAndFleet:
    def test_drain_finishes_everything_then_refuses(self):
        m = tiny_lm()
        eng = m.compile_serving(slots=2, max_len=48, prefill_len=8,
                                registry=_reg())
        rep = ServingReplica(eng, name="t", registry=_reg()).start()
        futs = [eng.submit([1, 2], max_new_tokens=10, seed=i)
                for i in range(5)]
        code = rep.drain(timeout=60)
        assert code == EXIT_DRAINED
        for f in futs:
            assert len(f.result(timeout=5)["tokens"]) == 10
            assert f.deliveries == 1
        with pytest.raises(EngineDraining):
            eng.submit([1], max_new_tokens=1)

    def test_exactly_once_across_fault_plus_drain(self):
        """The acceptance combination: transient injected faults AND a
        mid-stream drain — every submitted request still gets exactly
        one complete response."""
        m = tiny_lm()
        faults = FaultPlan()
        faults.fail_step(2, times=2)
        eng = m.compile_serving(slots=2, max_len=48, prefill_len=8,
                                registry=_reg(), faults=faults,
                                max_retries=3)
        rep = ServingReplica(eng, name="fd", registry=_reg()).start()
        futs = [eng.submit([1, 2, 3], max_new_tokens=8, seed=i)
                for i in range(6)]
        assert rep.drain(timeout=60) == EXIT_DRAINED
        for f in futs:
            assert len(f.result(timeout=5)["tokens"]) == 8
            assert f.deliveries == 1
        assert eng.compiled_step_info()["n_traces"] == 1

    def test_fleet_failover_absorbs_drained_replica(self):
        """Router + two replicas: drain one mid-stream; the survivor
        absorbs every later request; nothing dropped, nothing doubled;
        neither engine ever retraced."""
        reg = _reg()
        reps, engines = [], []
        for i in range(2):
            m = tiny_lm(seed=i)
            eng = m.compile_serving(slots=2, max_len=48, prefill_len=8,
                                    registry=_reg())
            engines.append(eng)
            reps.append(ServingReplica(eng, name=f"r{i}",
                                       registry=_reg()).start())
        router = FleetRouter(reps, registry=reg)
        futs = [router.submit([1, 2, 3], max_new_tokens=6, seed=i)
                for i in range(6)]
        assert reps[0].drain(timeout=60) == EXIT_DRAINED
        pre0 = engines[0].queue._outcomes.value(status="completed")
        futs += [router.submit([2, 3], max_new_tokens=4, seed=i)
                 for i in range(4)]
        for eng in engines:
            if eng._thread is None:
                eng.run_until_idle()
        for f in futs:
            f.result(timeout=30)
            assert f.deliveries == 1
        # the drained replica took NOTHING after its drain
        assert engines[0].queue._outcomes.value(
            status="completed") == pre0
        for eng in engines:
            assert eng.compiled_step_info()["n_traces"] == 1
        for r in reps:
            r.drain(timeout=10)

    def test_replica_health_with_cluster_seat(self):
        from singa_tpu.resilience import SoloCluster
        m = tiny_lm()
        eng = m.compile_serving(slots=2, max_len=32, prefill_len=4,
                                registry=_reg())
        rep = ServingReplica(eng, cluster=SoloCluster(),
                             registry=_reg())
        h = rep.health()
        assert h["status"] == "serving"
        assert h["cluster"]["world"] == 1
        assert rep.drain(timeout=10) == EXIT_DRAINED
        assert rep.health()["status"] == "draining"


class TestBatchServing:
    def _mlp_onnx(self):
        np.random.seed(0)

        class MLPNet(model.Model):
            def __init__(self):
                super().__init__()
                self.fc1 = layer.Linear(8)
                self.relu = layer.ReLU()
                self.fc2 = layer.Linear(3)

            def forward(self, x):
                return self.fc2(self.relu(self.fc1(x)))

        m = MLPNet()
        x = Tensor(data=np.random.randn(2, 4).astype(np.float32),
                   device=DEV, requires_grad=False)
        m.forward(x)
        return sonnx.to_onnx(m, [x], "mlp"), m

    def test_onnx_import_serves_through_batch_engine(self):
        """Scenario diversity: an IMPORTED ONNX graph serves through
        the same engine stack via the inherited compile_serving."""
        onnx_model, ref = self._mlp_onnx()
        sm = sonnx.SONNXModel(onnx_model, device="CPU")
        eng = sm.compile_serving(input_shape=(4,), batch=3,
                                 registry=_reg())
        rows = np.random.randn(5, 4).astype(np.float32)
        futs = [eng.submit(r) for r in rows]
        eng.run_until_idle()
        want = np.asarray(ref.forward(Tensor(
            data=rows, device=DEV, requires_grad=False)).data)
        for i, f in enumerate(futs):
            np.testing.assert_allclose(np.asarray(f.result(timeout=5)),
                                       want[i], rtol=1e-4, atol=1e-5)
            assert f.deliveries == 1
        assert eng.compiled_step_info()["n_traces"] == 1

    def test_shape_mismatch_rejected(self):
        onnx_model, _ = self._mlp_onnx()
        sm = sonnx.SONNXModel(onnx_model, device="CPU")
        eng = sm.compile_serving(input_shape=(4,), batch=2,
                                 registry=_reg())
        with pytest.raises(ServingError):
            eng.submit(np.zeros((5,), np.float32))


class TestGateway:
    def _client(self, port):
        import http.client
        return http.client.HTTPConnection("127.0.0.1", port, timeout=60)

    def _post(self, port, path, doc):
        c = self._client(port)
        try:
            c.request("POST", path, json.dumps(doc))
            r = c.getresponse()
            return r.status, json.loads(r.read().decode() or "{}")
        finally:
            c.close()

    def _get(self, port, path):
        c = self._client(port)
        try:
            c.request("GET", path)
            r = c.getresponse()
            return r.status, r.read().decode()
        finally:
            c.close()

    def test_gateway_generate_health_metrics_drain(self):
        m = tiny_lm()
        eng = m.compile_serving(slots=2, max_len=32, prefill_len=8,
                                registry=_reg())
        rep = ServingReplica(eng, name="gw", registry=_reg()).start()
        server, port = serve_gateway(eng, replica=rep)
        try:
            st, doc = self._post(port, "/v1/generate",
                                 {"prompt": [1, 2, 3],
                                  "max_new_tokens": 4})
            assert st == 200 and len(doc["tokens"]) == 4
            st, doc = self._post(port, "/v1/generate", {"prompt": []})
            assert st == 400
            st, body = self._get(port, "/healthz")
            assert st == 200 and json.loads(body)["status"] == "serving"
            st, body = self._get(port, "/metrics")
            assert st == 200 and "serve_ttft_seconds" in body
            assert "serve_token_seconds_p99" in body
            st, _doc = self._post(port, "/drain", {})
            assert st == 202
            st, body = self._get(port, "/healthz")
            assert st == 503
            st, doc = self._post(port, "/v1/generate",
                                 {"prompt": [1], "max_new_tokens": 1})
            assert st == 503 and doc.get("retryable")
        finally:
            server.shutdown()
            server.server_close()
            rep.drain(timeout=10)


class TestSharedDecodeHelper:
    def test_greedy_host_and_jax_agree(self):
        import jax
        rng = np.random.RandomState(0)
        logits = rng.randn(33).astype(np.float32)
        host = decode_mod.sample_logits(logits, temperature=0.0)
        traced = int(decode_mod.sample_logits_jax(
            logits, 0, None, jax.random.PRNGKey(0)))
        assert host == traced == int(np.argmax(logits))

    def test_top_k_masks_below_kth(self):
        logits = np.asarray([0.1, 3.0, 2.0, -1.0, 2.5])
        masked = decode_mod.apply_top_k(logits, 2)
        assert np.isinf(masked[[0, 2, 3]]).all()
        assert masked[1] == 3.0 and masked[4] == 2.5
        # k >= vocab and k=0 are no-ops
        assert (decode_mod.apply_top_k(logits, 0) == logits).all()
        assert (decode_mod.apply_top_k(logits, 9) == logits).all()

    def test_temperature_sampling_deterministic_rng(self):
        rng1 = np.random.RandomState(7)
        rng2 = np.random.RandomState(7)
        logits = np.random.RandomState(0).randn(10)
        a = [decode_mod.sample_logits(logits, 0.8, 3, rng1)
             for _ in range(20)]
        b = [decode_mod.sample_logits(logits, 0.8, 3, rng2)
             for _ in range(20)]
        assert a == b
        top3 = set(np.argsort(logits)[-3:].tolist())
        assert set(a) <= top3


# ---------------------------------------------------------------------------
# the tick reads back tokens, not logits
# ---------------------------------------------------------------------------

LAYOUTS = {"ring": {}, "paged": dict(kv_layout="paged", kv_block_size=4)}


def _readbacks(reg):
    """``{(program, what): calls}`` of ``serve_readback_total``."""
    return {(s["labels"]["program"], s["labels"]["what"]): int(s["value"])
            for m in reg.snapshot()["metrics"]
            if m["name"] == "serve_readback_total" for s in m["series"]}


def _reference_tokens(m, prompt, n_new, temperature=0.0, top_k=None,
                      rng=None):
    """What the host sampler makes of the uncached eager forward's
    logits, one grown sequence at a time: the tokens a request is owed
    whatever the engine reads back."""
    seq = list(prompt)
    for _ in range(n_new):
        logits = m(Tensor(data=np.asarray(seq, np.float32)[None],
                          device=DEV, requires_grad=False))
        seq.append(decode_mod.sample_logits(
            np.asarray(logits.data)[0, -1], temperature=temperature,
            top_k=top_k, rng=rng))
    return seq[len(prompt):]


def _program_spans(rec):
    return [r for r in rec.records() if r.get("kind") == "span"
            and r.get("name") in ("serve.prefill", "serve.decode")]


@pytest.fixture
def recorder():
    """The process-wide flight recorder, emptied for one test."""
    from singa_tpu.observability import spans
    rec = spans.recorder()
    rec.clear()
    yield rec
    rec.clear()


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
class TestTokenReadback:
    def test_all_greedy_run_reads_no_logits(self, layout, recorder):
        """Greedy requests (``top_k`` set on one: with temperature 0 it
        changes nothing) are served the argmax of the reference's
        logits, and no program call brings the logits to the host."""
        m = tiny_lm(seed=2)
        reg = _reg()
        eng = m.compile_serving(slots=2, max_len=32, prefill_len=8,
                                registry=reg, **LAYOUTS[layout])
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, 19, (int(rng.randint(1, 8)),))
                   for _ in range(5)]
        futs = [eng.submit(p, max_new_tokens=5, temperature=0.0,
                           top_k=3 if i == 1 else None)
                for i, p in enumerate(prompts)]
        eng.run_until_idle()
        for p, f in zip(prompts, futs):
            assert f.result(timeout=5)["tokens"] == \
                _reference_tokens(m, p, 5)
        calls = _readbacks(reg)
        assert {what for _, what in calls} == {"tokens"}, calls
        assert calls["prefill", "tokens"] >= 3
        assert calls["decode", "tokens"] >= 4 * 3
        recs = _program_spans(recorder)
        assert len(recs) == sum(calls.values())
        assert {r["readback"] for r in recs} == {"tokens"}

    def test_a_tie_goes_to_the_lowest_id(self, layout):
        """The program's argmax breaks a tie as ``np.argmax`` on the
        host did: every logit row here IS the head's bias, with its
        largest value at ids 3, 7 and 12."""
        import jax.numpy as jnp
        m = tiny_lm(seed=2)
        bias = np.zeros(19, np.float32)
        bias[[12, 3, 7]] = 5.0
        m.head.W.data = jnp.zeros_like(m.head.W.data)
        m.head.b.data = jnp.asarray(bias)
        reg = _reg()
        eng = m.compile_serving(slots=2, max_len=32, prefill_len=8,
                                registry=reg, **LAYOUTS[layout])
        futs = [eng.submit(p, max_new_tokens=4) for p in ([1, 2], [5])]
        eng.run_until_idle()
        assert int(np.argmax(bias)) == 3
        for f in futs:
            assert f.result(timeout=5)["tokens"] == [3, 3, 3, 3]
        assert {what for _, what in _readbacks(reg)} == {"tokens"}

    def test_a_sampling_request_among_greedy_ones(self, layout, recorder):
        """One ``temperature=0.8`` request between greedy ones: every
        request gets the tokens the host sampler draws from the
        reference's logits (its own seeded ``rng``; the greedy rows of
        the same ticks take the program's token), the logits come to
        the host only in calls that serve the sampling request, and
        neither program traces again when a tick changes kind."""
        from singa_tpu.serving import scheduler
        m = tiny_lm(seed=5)
        reg = _reg()
        eng = m.compile_serving(slots=2, max_len=32, prefill_len=8,
                                prefill_batch=1, registry=reg,
                                **LAYOUTS[layout])
        first_id = next(scheduler.Request._ids) + 1
        # (prompt, new tokens, temperature, top_k): the sampler is
        # admitted second, lives 4 tokens, and greedy requests run
        # before, beside and after it
        plan = [([3, 1, 4], 9, 0.0, None), ([1, 5, 9, 2], 4, 0.8, 5),
                ([6, 5], 6, 0.0, None), ([3, 5, 8], 5, 0.0, None)]
        futs = [eng.submit(p, max_new_tokens=n, temperature=t, top_k=k,
                           seed=11)
                for p, n, t, k in plan]
        eng.run_until_idle()
        for i, ((p, n, t, k), f) in enumerate(zip(plan, futs)):
            want = _reference_tokens(
                m, p, n, temperature=t, top_k=k,
                rng=np.random.RandomState(11 + first_id + i))
            assert f.result(timeout=5)["tokens"] == want, i
        # the sampler's own calls: its prefill (batches of one) and the
        # decode ticks it was live in, one token each after the first
        calls = _readbacks(reg)
        assert calls["prefill", "logits"] == 1
        assert calls["prefill", "tokens"] == 3
        assert calls["decode", "logits"] == 3
        assert calls["decode", "tokens"] >= 5
        recs = _program_spans(recorder)
        assert sorted(r["readback"] for r in recs) == sorted(
            what for (_, what), n in calls.items() for _ in range(n))
        info = eng.compiled_step_info()
        assert info["n_traces"] == 1 and info["prefill_n_traces"] == 1
