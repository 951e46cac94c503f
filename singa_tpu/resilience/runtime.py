"""The fault-tolerant training driver (checkpoint-restart loop).

:class:`ResilientTrainer` owns the loop a production TPU job needs
around ``model(tx, ty)``:

- **Preemption**: SIGTERM/SIGINT set a flag; at the next step boundary
  the trainer checkpoints synchronously and exits with
  :data:`EXIT_PREEMPTED` (75, BSD's EX_TEMPFAIL: "transient — retry").
  The restart supervisor contract is: exit code 75 means *restart me*;
  the restarted trainer resumes from the preemption checkpoint with
  bit-identical state (params, optimizer aux, loss-scale, guard
  counters all ride the checkpoint).
- **Transient failures**: step exceptions and data-iterator exceptions
  retry with exponential backoff + deterministic jitter; an optional
  watchdog runs each step on a worker thread. A step that overruns the
  timeout gets one grace period: finishing late is used as-is, a step
  that raised late is retried, and a step STILL running after the grace
  raises a fatal :class:`StepTimeoutError` — a hung backend cannot be
  retried in-process (the zombie thread could land its update mid-retry),
  so the supervisor restart from checkpoint is the recovery.
- **Divergence**: when the model's optimizer is a
  :class:`~singa_tpu.resilience.guards.GuardedOptimizer`, the trainer
  polls its bad-streak counter (one scalar readback) and, after
  ``rollback_after`` consecutive bad steps, rolls state back to the
  last good checkpoint and continues (bounded by ``max_rollbacks``).
- **Restart**: every ``run`` begins with
  ``CheckpointManager.restore_latest``, which itself scans backward
  past corrupt/incomplete checkpoints (singa_tpu/checkpoint.py).

Usage::

    trainer = ResilientTrainer(model, "ckpts", save_interval_steps=50)
    summary = trainer.run(batches, num_steps=10_000)

where ``batches`` is any (re-)iterable yielding the positional args of
one training step (tuples of Tensors). Exhausted re-iterables
re-iterate (epoch wrap); endless generators work as-is; a FINITE
one-shot generator that runs dry mid-training raises a clear error
(it cannot be rewound).
"""

from __future__ import annotations

import os
import random
import signal
import threading
import time
import warnings

from .. import data as _data_mod
from ..checkpoint import CheckpointManager, DistributedCheckpointManager
from ..integrity import replica_buffer_mismatches, state_fingerprint
from ..observability import metrics as _metrics
from ..observability import perf as _perf
from ..observability import spans as _spans
from .cluster import BarrierTimeout, MembershipError
from .faults import NULL_PLAN
from .guards import GuardedOptimizer

# BSD EX_TEMPFAIL: the documented "preempted — checkpointed cleanly,
# restart me" exit code for the restart supervisor. Distinct from 0
# (done), 1 (crash), and 42-style user codes.
EXIT_PREEMPTED = 75

# Repeated cross-replica divergence (silent data corruption or a
# non-deterministic kernel) after quarantine-and-rollback already
# retried: DISTINCT from 75 because "relaunch the same command" is the
# wrong medicine — the fleet should cordon/replace the suspect host
# before restarting (resume still works: every committed checkpoint is
# cross-replica-agreed). 76 is BSD EX_PROTOCOL — "remote said the
# impossible" is close enough in spirit to a replica whose bytes
# disagree with its peers'.
EXIT_DIVERGED = 76


class DivergenceError(RuntimeError):
    """Replicas diverged again after quarantine-and-rollback — the
    supervisor contract is exit :data:`EXIT_DIVERGED` (76): investigate
    or cordon the divergent host, THEN relaunch (resume lands on the
    last cross-replica-agreed checkpoint)."""

    def __init__(self, step, divergent, rollbacks):
        self.step = int(step)
        self.divergent = list(divergent)
        super().__init__(
            f"cross-replica divergence at step {step} persisted after "
            f"{rollbacks} quarantine-rollback(s)"
            + (f" (divergent: {self.divergent})" if divergent else "")
            + f"; exiting {EXIT_DIVERGED} — cordon the suspect host "
            "before restarting")


class StepTimeoutError(RuntimeError):
    """A training step exceeded the watchdog timeout.

    Carries the worker thread plus its result/exception slots so the
    driver can decide safely: a LATE completion within the grace join
    is used as-is; a still-running worker makes the timeout fatal —
    retrying while a zombie step can still land its (state-mutating)
    update would race on the shared tensors."""

    def __init__(self, message, worker=None, result=None, raised=None):
        super().__init__(message)
        self.worker = worker
        self.result = result if result is not None else {}
        self.raised = raised if raised is not None else []


class _Preempted(Exception):
    """Internal control flow: a preemption checkpoint has committed."""


class ResilientTrainer:
    """Checkpoint-restart training loop (see module docstring).

    Parameters beyond the obvious:

    - ``step_retries`` / ``data_retries``: transient-failure retry
      budgets per step / per batch fetch.
    - ``backoff_base`` / ``backoff_cap`` / ``jitter``: retry delay is
      ``min(cap, base * 2**attempt) * (1 + jitter*u)`` with ``u`` drawn
      from a seeded RNG — exponential backoff, deterministic jitter.
    - ``step_timeout``: seconds before a step is declared overdue; one
      grace period follows (late success used, late failure retried,
      still-hung fatal). None disables the watchdog thread.
    - ``rollback_after``: consecutive guard-flagged bad steps before
      state rolls back to the last checkpoint (None disables; requires
      a GuardedOptimizer to ever trigger).
    - ``exit_on_preempt``: raise ``SystemExit(EXIT_PREEMPTED)`` after
      the preemption checkpoint (the supervisor contract); False makes
      ``run`` return its summary with ``preempted=True`` instead (for
      embedding in a larger host process).
    - ``faults``: a FaultPlan for chaos testing.
    - ``cluster``: a :mod:`~singa_tpu.resilience.cluster` member. When
      given, checkpoints go through the two-phase
      :class:`~singa_tpu.checkpoint.DistributedCheckpointManager`
      (commit marker only after every rank's ACK), cluster health is
      checked at every step boundary, and a lost peer (or a failed
      start rendezvous) exits :data:`EXIT_PREEMPTED` — membership loss
      is RECOVERABLE: the supervisor restarts at the smaller world size
      and ``run`` resumes from the last *committed* checkpoint,
      re-sharded onto the new mesh.
    - ``manifest_extra``: dict recorded in every commit marker (e.g.
      ``per_replica_batch`` — the elastic batch accounting reads it on
      resume, see ``parallel.communicator.rescale_batch``).
    - ``fingerprint_every``: every N steps, fingerprint the full model
      + optimizer state and check that replicas agree — bit-exactly:
      per-device buffer comparison locally
      (:func:`~singa_tpu.integrity.replica_buffer_mismatches`) and a
      digest exchange over the cluster for multi-rank runs
      (:meth:`~singa_tpu.resilience.cluster.ClusterBase.
      fingerprint_agree`). A disagreement means silent divergence (SDC,
      non-deterministic kernel): the step is QUARANTINED — never
      checkpointed — and state rolls back to the last *verified,
      cluster-agreed* checkpoint. 0 (the default) disables the check
      entirely: zero added work on the step path.
    - ``max_divergence_rollbacks``: quarantine-rollbacks allowed before
      the run exits :data:`EXIT_DIVERGED` (76) — repeated divergence
      means bad hardware, and "restart the same pod" is not a fix.
    - ``profile_every``: every N steps, run the step under a
      ``jax.profiler`` trace (``Model.profile_step``) and refresh the
      ``profile_fusion_*`` gauges — the continuous per-fusion view the
      MFU work reads. 0 (the default) disables sampling; non-sample
      steps pay one integer check, and the compiled step's
      ``n_traces`` pin is untouched (the profiler wraps the
      already-compiled dispatch).
    - ``anomaly_factor`` / ``anomaly_sustain`` / ``anomaly_warmup``:
      arm the step-time anomaly sentinel — ``anomaly_sustain``
      consecutive steps slower than ``anomaly_factor``× the rolling
      baseline fire an attributed ``step_anomaly`` event, a one-shot
      profile capture on the next step, and a blackbox dump. None
      (the default) disables the sentinel.
    - ``aot``: cold-start elimination (``singa_tpu.aot``). ``True``
      keeps an ``aot/`` sidecar beside the checkpoints (a path keeps
      it there instead): the persistent compilation cache is
      installed where ``aot.cache``'s rule puts it
      (``JAX_COMPILATION_CACHE_DIR``, else
      ``<checkout>/.jax_compile_cache``), the compiled train step is
      exported after the first step (single-device models; a
      mesh-sharded step rides the cache alone), and a restarted
      worker's restore path deserializes a MATCHING artifact instead
      of retracing — any mismatch (version, topology, avals, digest,
      policy) falls back to a loud fresh compile and quarantines the
      stale artifact. The run summary reports ``compile_sources``
      (observations per ``compile_seconds`` source label) and
      ``aot`` (per-program outcomes), the chaos ``warm-restart``
      gate's evidence. None (the default) changes nothing.
    """

    def __init__(self, model, ckpt_dir, *, max_to_keep=3,
                 save_interval_steps=1, step_retries=3, data_retries=3,
                 backoff_base=0.1, backoff_cap=5.0, jitter=0.25,
                 step_timeout=None, rollback_after=3, max_rollbacks=3,
                 exit_on_preempt=True, install_signal_handlers=True,
                 faults=None, seed=0, verbose=True, cluster=None,
                 commit_timeout=60.0, start_barrier_timeout=60.0,
                 preempt_commit_timeout=10.0, manifest_extra=None,
                 fingerprint_every=0, max_divergence_rollbacks=2,
                 telemetry_dir=None, profile_every=0,
                 anomaly_factor=None, anomaly_sustain=3,
                 anomaly_warmup=10, aot=None):
        self.model = model
        self.cluster = cluster
        self._rank = cluster.rank if cluster is not None else 0
        # flight-recorder blackbox home (``blackbox-<rank>.jsonl``):
        # beside the checkpoints unless the caller routes it elsewhere
        self.telemetry_dir = os.path.abspath(str(
            telemetry_dir if telemetry_dir is not None
            else os.path.join(str(ckpt_dir), "telemetry")))
        self.start_barrier_timeout = float(start_barrier_timeout)
        self.preempt_commit_timeout = float(preempt_commit_timeout)
        if cluster is not None:
            self.mgr = DistributedCheckpointManager(
                ckpt_dir, cluster, max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps,
                commit_timeout=commit_timeout,
                manifest_extra=manifest_extra)
        else:
            self.mgr = CheckpointManager(
                ckpt_dir, max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps)
        self.step_retries = int(step_retries)
        self.data_retries = int(data_retries)
        self.backoff_base = float(backoff_base)
        self.backoff_cap = float(backoff_cap)
        self.jitter = float(jitter)
        self.step_timeout = step_timeout
        self.rollback_after = rollback_after
        self.max_rollbacks = int(max_rollbacks)
        self.fingerprint_every = int(fingerprint_every)
        self.max_divergence_rollbacks = int(max_divergence_rollbacks)
        self.exit_on_preempt = bool(exit_on_preempt)
        self.install_signal_handlers = bool(install_signal_handlers)
        self.faults = faults if faults is not None else NULL_PLAN
        if cluster is not None and \
                getattr(cluster, "faults", NULL_PLAN) is NULL_PLAN:
            # one plan drives every hook point: a caller that armed
            # kill_before_ack on the trainer's plan gets it fired from
            # the cluster's ack path too
            cluster.faults = self.faults
        self.verbose = bool(verbose)
        self._rng = random.Random(seed)
        self._sleep = time.sleep          # injectable in tests
        self._preempt_signal = None
        self._data = None
        self._it = None
        # telemetry handles (get-or-create on the process registry):
        # every operation below is a host-side dict update — the
        # compiled step path (and its n_traces pin) is untouched
        reg = _metrics.default_registry()
        self._m_steps = reg.counter(
            "train_steps_total", "completed training steps")
        self._m_step_time = reg.histogram(
            "train_step_seconds", "wall-clock duration of one step")
        self._m_fetch = reg.histogram(
            "data_fetch_seconds", "wall-clock wait for the next batch")
        self._m_throughput = reg.gauge(
            "train_throughput_samples_per_sec",
            "samples/s of the newest step (batch dim0 / step seconds)")
        self._m_mfu = reg.gauge(
            "train_mfu", "achieved/peak FLOP fraction of the newest "
            "step (needs a cached XLA cost analysis and a known chip)")
        self._m_retries = reg.counter(
            "train_retries_total", "transient-failure retries",
            labels=("kind",))
        self._m_timeouts = reg.counter(
            "train_step_timeouts_total", "watchdog-overdue steps")
        self._m_rollbacks = reg.counter(
            "train_rollbacks_total",
            "state rollbacks to a checkpoint", labels=("kind",))
        self._m_bad_streak = reg.gauge(
            "guard_bad_streak", "consecutive guard-flagged bad steps")
        self._m_first_step = reg.gauge(
            "restart_to_first_step_seconds",
            "run() entry to first completed step — the cold-start "
            "regression gate (compile + restore + first batch)")
        self._step_flops = None       # resolved lazily after step 1
        self._last_blackbox = None
        self._cur_step = None
        # performance observability: the sampling profiler always
        # exists (the sentinel arms one-shot captures through it even
        # at profile_every=0); the sentinel only when asked for
        self._profiler = _perf.SamplingProfiler(profile_every)
        self._step_was_profiled = False
        self._sentinel = _perf.AnomalySentinel(
            factor=anomaly_factor, sustain=anomaly_sustain,
            warmup=anomaly_warmup) if anomaly_factor else None
        # cold-start elimination: persistent compile cache + AOT
        # train-step artifacts in an aot/ sidecar beside the
        # checkpoints (class docstring)
        self._aot_store = None
        if aot:
            from ..aot import cache as _aot_cache
            from ..aot import export as _aot_export
            aot_dir = os.path.join(str(ckpt_dir), "aot") \
                if aot is True else os.path.abspath(str(aot))
            _aot_cache.install()
            self._aot_store = _aot_export.AotStore(aot_dir)
            # Model._run_step consults the store before tracing a
            # fresh signature (the warm-restart load path)
            model._aot_store = self._aot_store

    # -- logging -----------------------------------------------------------
    def _log(self, msg):
        if self.verbose:
            print(f"[resilient] {msg}", flush=True)

    # -- signal handling ---------------------------------------------------
    def _handler(self, signum, frame):
        # only record: all real work (sync checkpoint, exit) happens at
        # the next step boundary, never inside the handler
        self._preempt_signal = signum

    def _install_handlers(self):
        if not self.install_signal_handlers:
            return None
        try:
            prev = {s: signal.signal(s, self._handler)
                    for s in (signal.SIGTERM, signal.SIGINT)}
        except ValueError:
            # signal.signal only works on the main thread; degrade to
            # no preemption handling rather than refusing to train
            warnings.warn(
                "ResilientTrainer: not on the main thread, preemption "
                "signal handlers NOT installed", stacklevel=3)
            return None
        return prev

    @staticmethod
    def _restore_handlers(prev):
        if prev:
            for s, h in prev.items():
                signal.signal(s, h)

    def _check_preempt(self, completed_step, start):
        """At a step boundary: if a preemption signal arrived, commit a
        synchronous checkpoint of the completed step and stop."""
        if self._preempt_signal is None:
            return
        signame = signal.Signals(self._preempt_signal).name
        if completed_step >= start:
            if self.mgr.latest_step() != completed_step:
                if isinstance(self.mgr, DistributedCheckpointManager):
                    # a forced off-schedule save only reaches quorum
                    # when EVERY rank was preempted at this boundary
                    # (whole-pod maintenance — the common TPU case); a
                    # per-node preemption cannot commit, so wait only
                    # briefly and leave resume to the last committed
                    # step rather than eating the kill grace
                    ok = self.mgr.save(
                        completed_step, self.model, force=True,
                        commit_timeout=self.preempt_commit_timeout,
                        data_state=self._data_state())
                    if not ok:
                        self._log(
                            f"{signame}: preemption checkpoint of step "
                            f"{completed_step} did not commit; resume "
                            "will use the last committed step")
                else:
                    self.mgr.save(completed_step, self.model,
                                  force=True,
                                  data_state=self._data_state())
            self.mgr.wait()     # synchronous: the bytes must be down
            self._log(f"{signame}: checkpointed step {completed_step}, "
                      f"exiting {EXIT_PREEMPTED} for the supervisor")
        else:
            self._log(f"{signame} before any step completed; "
                      f"exiting {EXIT_PREEMPTED} without a checkpoint")
        raise _Preempted()

    # -- retry plumbing ----------------------------------------------------
    def _backoff(self, attempt, what, summary, kind):
        from ..data import backoff_delay
        delay = backoff_delay(attempt, self.backoff_base,
                              self.backoff_cap, self.jitter, self._rng)
        summary[kind] += 1
        self._m_retries.inc(kind=kind)
        self._log(f"{what}: transient failure, retrying "
                  f"in {delay * 1e3:.0f} ms "
                  f"(attempt {attempt + 1})")
        self._sleep(delay)

    def _next_batch(self, step, summary):
        attempt = 0
        failed = None
        while True:
            try:
                self.faults.on_data(step)
                if self._it is None:
                    self._it = iter(self._data)
                try:
                    batch = next(self._it)
                except StopIteration:
                    self._it = iter(self._data)   # epoch wrap
                    batch = next(self._it)
                if not isinstance(batch, (tuple, list)):
                    batch = (batch,)
                self._yielded_any = True
                return self.faults.on_batch(step, tuple(batch))
            except StopIteration:
                # a generator that raised is CLOSED, not exhausted:
                # this StopIteration is the corpse of the retried
                # failure — the ONE shared rule
                # (data.raise_retried_failure, also the
                # RetryingIterator.__next__ rule) surfaces the real
                # error instead of truncating the stream
                _data_mod.raise_retried_failure(failed)
                if getattr(self, "_yielded_any", False):
                    raise RuntimeError(
                        "data source is exhausted and not re-iterable "
                        "(a one-shot generator?); pass a re-iterable "
                        "like NumpyBatchIter, or an endless generator"
                    ) from None
                raise RuntimeError(
                    "data source yielded no batches") from None
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                if attempt >= self.data_retries:
                    raise
                failed = e
                self._backoff(attempt, f"data fetch (step {step})",
                              summary, "data_retries")
                attempt += 1

    # a profiled step's wall-clock is dominated by the trace dump +
    # parse, not the step: its watchdog budget scales by this factor so
    # routine sampling can never trip a spurious (or fatal) timeout
    PROFILE_TIMEOUT_FACTOR = 4

    def _call_step(self, step, batch, attempt):
        """One step attempt: fault hooks + the model call, optionally
        under the watchdog thread."""
        # cleared per ATTEMPT, not per observe: a profiled attempt that
        # dies before _observe_step must not leak its flag onto the
        # next successful step (which would silently drop that step
        # from the step-time/MFU/sentinel series)
        self._step_was_profiled = False
        will_profile = self._profiler.should_sample(step) and \
            hasattr(self.model, "profile_step")

        def body():
            self.faults.on_step(step, attempt)
            if will_profile:
                # the sampled step runs THROUGH the already-compiled
                # dispatch under a profiler trace (measure_step_fusions)
                # — no retrace, one trace dump, gauges refreshed. The
                # flag keeps its inflated wall-clock (trace dump +
                # parse dominate) OUT of the step-time/MFU/throughput
                # series — its cost lands in profile_capture_seconds
                self._step_was_profiled = True
                t0 = time.perf_counter()
                events = []
                out, table = self.model.profile_step(
                    *batch, record=False, events_out=events)
                # the step-timeline decomposition (timeline_* gauges,
                # exposed-comm, MFU-loss waterfall) rides the same
                # capture; FLOP counts only when someone already paid
                # for a cost analysis (never forced on the step path)
                peak = _metrics.device_peak_flops(self._jax_device())
                self._profiler.record(
                    step, table, capture_s=time.perf_counter() - t0,
                    events=events, step_flops=self._step_flops,
                    peak_flops=peak)
                return out
            return self.model(*batch)

        if self.step_timeout is None:
            return body()
        timeout = self.step_timeout * \
            (self.PROFILE_TIMEOUT_FACTOR if will_profile else 1)
        result, raised = {}, []
        # carry the caller's contextvars into the worker: a use_layout()
        # scope (ops/layout.py ContextVar) entered around run() must be
        # visible to lazy conv/BN handle init inside the step
        import contextvars
        ctx = contextvars.copy_context()

        def work():
            try:
                result["out"] = ctx.run(body)
            except BaseException as e:     # noqa: BLE001 — re-raised below
                raised.append(e)

        worker = threading.Thread(target=work, daemon=True,
                                  name=f"resilient-step-{step}")
        worker.start()
        worker.join(timeout)
        if worker.is_alive():
            err = StepTimeoutError(
                f"step {step} exceeded the {timeout}s "
                "watchdog timeout"
                + (" (profiled-step budget)" if will_profile else ""),
                worker=worker, result=result, raised=raised)
            err.timeout = timeout   # the grace join reuses this budget
            raise err
        if raised:
            raise raised[0]
        return result.get("out")

    def _run_step(self, step, batch, summary):
        attempt = 0
        while True:
            try:
                return self._call_step(step, batch, attempt)
            except (KeyboardInterrupt, SystemExit):
                raise
            except StepTimeoutError as e:
                # grace-join the overdue worker one more timeout period:
                # a SLOW step that completes in the grace is simply used
                # (its update already landed); a step still running after
                # that is fatal — we cannot retry while a zombie thread
                # may yet land its state mutation concurrently
                summary["step_timeouts"] += 1
                self._m_timeouts.inc()
                grace = getattr(e, "timeout", self.step_timeout)
                e.worker.join(grace)
                if e.worker.is_alive():
                    raise StepTimeoutError(
                        f"step {step} still running after "
                        f"{2 * grace}s; a hung backend "
                        "cannot be retried in-process — exit and let "
                        "the supervisor restart from the checkpoint"
                    ) from None
                if not e.raised:
                    self._log(f"step {step} finished late "
                              "(within the watchdog grace); using it")
                    return e.result.get("out")
                if attempt >= self.step_retries:
                    raise e.raised[0]
                self._backoff(attempt, f"train step {step}",
                              summary, "step_retries")
                attempt += 1
            except Exception:
                if attempt >= self.step_retries:
                    raise
                self._backoff(attempt, f"train step {step}",
                              summary, "step_retries")
                attempt += 1

    # -- data-pipeline state -----------------------------------------------
    def _data_state(self):
        """The data source's ``state_dict()`` (None for a source that
        predates the protocol) — captured at EVERY save so a restored
        checkpoint rewinds the sample stream in lockstep with the
        tensors."""
        sd = getattr(self._data, "state_dict", None)
        return sd() if callable(sd) else None

    def _apply_data_state(self, resume_step):
        """Rewind the data pipeline in LOCKSTEP with a model-state
        restore (run start, guard rollback, divergence quarantine):
        load the restored checkpoint's data state and drop the live
        epoch iterator so the next fetch re-enters the source at the
        loaded offset — the consumed sample sequence stays bit-
        identical to a fault-free run's (exactly-once)."""
        state = getattr(self.mgr, "restored_data_state", None)
        # probe through delegating wrappers (a DevicePrefetcher around
        # a plain generator HAS load_state_dict but nothing to apply it
        # to): not-checkpointable must land on the warning below, not a
        # TypeError mid-restore
        loadable = _data_mod.can_load_state(self._data)
        ld = getattr(self._data, "load_state_dict", None) \
            if loadable else None
        if state is not None and callable(ld):
            self._data.load_state_dict(state)
            self._it = None
            self._data_resumed = True
            self._log(f"data stream rewound to the checkpointed "
                      f"offset (epoch {state.get('epoch')}, "
                      f"position {state.get('position')})")
        elif state is not None:
            warnings.warn(
                "the restored checkpoint carries data-iterator state "
                "but this data source is not checkpointable (no "
                "load_state_dict); the sample stream will NOT resume "
                "where the saved run left off", stacklevel=3)
        elif resume_step and callable(ld):
            warnings.warn(
                f"resumed at step {resume_step} from a checkpoint "
                "without data-iterator state (saved before data-state "
                "capture?); the sample stream restarts from the "
                "iterator's current position — exactly-once is NOT "
                "guaranteed for this resume", stacklevel=3)

    # -- cluster health ----------------------------------------------------
    def _check_cluster(self):
        """At a step boundary: raise MembershipError if a peer (or the
        coordinator) was lost — the run() handler turns it into the
        exit-75 supervisor contract."""
        if self.cluster is not None:
            self.cluster.check()

    # -- flight recorder ---------------------------------------------------
    def _jax_device(self):
        dev = getattr(self.model, "dev", None)
        return getattr(dev, "jax_device", None)

    def _blackbox_dump(self, reason, step=None, error=None):
        """Dump the in-memory flight recorder to
        ``<telemetry_dir>/blackbox-<rank>.jsonl`` — called on every
        ABNORMAL path (preemption, divergence, watchdog kill,
        membership loss, rollback, crash) so a post-mortem shows the
        last N seconds of spans and a final metrics snapshot, not just
        an exit code. A crash/watchdog dump additionally carries the
        HBM stats and a bounded ``jax.live_arrays()`` allocation
        breakdown — the OOM post-mortem. Never raises: losing the
        blackbox must not change how the run dies."""
        try:
            guard = self._guard()
            extra = {"guard": guard.stats()} if guard is not None else {}
            if error is not None:
                extra["error"] = \
                    f"{type(error).__name__}: {error}"[:500]
            if reason in ("crash", "watchdog_kill") or error is not None:
                hbm = _perf.hbm_stats(self._jax_device())
                if hbm:
                    extra["hbm"] = hbm
                live = _perf.live_array_report()
                if live:
                    extra["live_arrays"] = live
            path = os.path.join(self.telemetry_dir,
                                f"blackbox-{self._rank}.jsonl")
            self._last_blackbox = _spans.recorder().dump(
                path, reason, rank=self._rank,
                step=step if step is not None else self._cur_step,
                extra=extra)
            self._log(f"flight recorder dumped to "
                      f"{self._last_blackbox} ({reason})")
        except Exception as e:      # noqa: BLE001 — best-effort by design
            warnings.warn(f"flight-recorder dump failed "
                          f"({type(e).__name__}: {e})", stacklevel=2)

    def _finalize_summary(self, summary):
        """Observability that must survive EVERY exit path (success,
        preemption, membership loss): guard stats, data-pipeline
        flakiness counters, final cluster health."""
        guard = self._guard()
        if guard is not None:
            # one host readback of the guard scalars, recorded as
            # gauges too (loss scale, skipped total, grad norm)
            summary["skipped_steps"] = \
                guard.record_metrics()["skipped_total"]
        if self._last_blackbox is not None:
            summary["blackbox"] = self._last_blackbox
        from ..data import RetryingIterator
        summary["data_resumed"] = bool(getattr(self, "_data_resumed",
                                               False))
        # walk the wrapper chain — DevicePrefetcher (.iterator),
        # RetryingIterator (._src_obj), user staging adapters (.inner)
        # — so retry counters and per-sample quarantine attribution are
        # visible in the run summary no matter how the pipeline is
        # stacked, not just in warnings that scrolled away
        obj, seen = self._data, set()
        while obj is not None and id(obj) not in seen:
            seen.add(id(obj))
            if isinstance(obj, RetryingIterator) and \
                    "data_source" not in summary:
                summary["data_source"] = obj.counters()
            q = getattr(obj, "quarantined", None)
            if q and "data_quarantined" not in summary:
                summary["data_quarantined"] = [dict(r) for r in q]
                summary["data_skipped"] = int(
                    getattr(obj, "skip_count", len(q)))
            obj = next((w for w in (getattr(obj, "_src_obj", None),
                                    getattr(obj, "iterator", None),
                                    getattr(obj, "inner", None))
                        if w is not None), None)
        # cold-start evidence: where this run's executables came from
        # (the warm-restart chaos gate asserts zero "fresh" on a warm
        # path) and the compiled step's trace count — cheap host reads
        summary["compile_sources"] = _perf.compile_source_counts()
        rec = getattr(self.model, "_last_run_rec", None)
        if rec is not None:
            summary["n_traces"] = rec.get("n_traces")
        if self._aot_store is not None:
            summary["aot"] = dict(self._aot_store.outcomes)
        if self.cluster is not None:
            try:
                summary["cluster"] = self.cluster.health()
            except Exception:       # a torn-down cluster is not an error
                pass

    # -- divergence rollback ----------------------------------------------
    def _guard(self):
        opt = getattr(self.model, "optimizer", None)
        return opt if isinstance(opt, GuardedOptimizer) else None

    def _lockstep_restore(self, prefix, step, n):
        """The ONE rollback body both recovery paths (guard-streak
        rollback, fingerprint quarantine) share, so their ordering can
        never drift apart. Rollback must be LOCKSTEP: a rank rewinding
        alone would ack different step numbers forever and no
        checkpoint could ever commit again — a rank whose trigger is
        LOCAL (a hardware fault) strands its peers at the first
        barrier → BarrierTimeout → exit 75 → the supervisor restart is
        the consistent recovery. The resume barrier's name carries the
        resumed step (same agreement rule as the startup resume
        barrier): a rank whose shards fell back FURTHER than its peers
        strands them there instead of training at inconsistent
        parameter versions. Returns the step to resume from."""
        if self.cluster is not None and self.cluster.world > 1:
            with _spans.span("barrier", barrier=f"{prefix}-{step}-{n}"):
                self.cluster.barrier(f"{prefix}-{step}-{n}",
                                     timeout=self.start_barrier_timeout)
        self.mgr.wait()          # never restore under an in-flight save
        with _spans.span("restore", reason=prefix, step=step):
            resume = self.mgr.restore_latest(self.model)
        if self.cluster is not None and self.cluster.world > 1:
            with _spans.span("barrier",
                             barrier=f"{prefix}-resume-{resume}-{n}"):
                self.cluster.barrier(f"{prefix}-resume-{resume}-{n}",
                                     timeout=self.start_barrier_timeout)
        if isinstance(self.mgr, DistributedCheckpointManager):
            # agreement reached: markers at/after the resume point
            # vouch for a timeline about to be re-run
            self.mgr.invalidate_markers_from(resume)
        # the data stream rewinds WITH the tensors — on every rollback
        # and quarantine path, not just at run start: the re-run steps
        # must consume the exact batches the quarantined timeline did
        self._apply_data_state(resume)
        return resume

    def _maybe_rollback(self, step, bad_streak, summary):
        """Returns the step to continue from (rolled back), or None."""
        guard = self._guard()
        if guard is None or self.rollback_after is None:
            return None
        if bad_streak < self.rollback_after:
            return None
        if summary["rollbacks"] >= self.max_rollbacks:
            raise RuntimeError(
                f"training diverged: {self.rollback_after} consecutive "
                f"bad steps after {summary['rollbacks']} rollbacks")
        resume = self._lockstep_restore("rollback", step,
                                        summary["rollbacks"])
        guard.reset_streaks(extra_backoff=True)
        summary["rollbacks"] += 1
        self._m_rollbacks.inc(kind="guard")
        _spans.event("rollback", step=step, resume=resume, kind="guard")
        self._blackbox_dump("rollback", step=step)
        warnings.warn(
            f"{self.rollback_after} consecutive bad steps at step "
            f"{step}; rolled back to checkpoint, resuming at step "
            f"{resume} (rollback {summary['rollbacks']}/"
            f"{self.max_rollbacks})", stacklevel=2)
        return resume

    # -- cross-replica fingerprint: quarantine and rollback ----------------
    def _state_arrays(self):
        from ..checkpoint import _state_tensor_dict
        return {k: t.data
                for k, t in _state_tensor_dict(self.model).items()}

    def _fingerprint_check(self, step, summary):
        """Bit-exact cross-replica agreement on the FULL training state.
        Returns True when every replica agrees; False (with the
        divergents named) quarantines the step."""
        # chaos hook: diverge_at silently perturbs this rank's state —
        # the exact SDC shape the detector exists for
        self.faults.on_fingerprint(step, self.model)
        arrays = self._state_arrays()
        summary["fingerprints"] += 1
        # the agreement round is keyed by the CHECK count, not the step
        # number: in lockstep every rank counts the same rounds, and a
        # step re-run after a rollback opens a fresh round instead of
        # reusing its first run's stale verdict
        seq = summary["fingerprints"]
        divergent = []
        # local front: replicated per-device buffers must be identical
        local = replica_buffer_mismatches(arrays)
        if local:
            divergent += [f"{n}@{d}" for n, ds in local.items()
                          for d in ds]
        # cluster front: every rank's state digest must be identical
        if self.cluster is not None and self.cluster.world > 1:
            fp = state_fingerprint(arrays)
            ok, ranks = self.cluster.fingerprint_agree(
                seq, fp, timeout=self.start_barrier_timeout)
            if not ok:
                divergent += [f"rank{r}" for r in ranks] or ["unknown"]
        if divergent:
            warnings.warn(
                f"step {step}: cross-replica fingerprint mismatch "
                f"({divergent}) — quarantining the step and rolling "
                "back to the last verified checkpoint", stacklevel=2)
            summary["divergent"] = sorted(set(summary["divergent"])
                                          | set(divergent))
            return False
        return True

    def _quarantine_rollback(self, step, summary):
        """A diverged step is never checkpointed; roll every rank back
        (LOCKSTEP, like ``_maybe_rollback``) to the last verified —
        and, under a cluster, cross-replica-AGREED — checkpoint.
        Returns the step to resume from; raises
        :class:`DivergenceError` when the budget is spent."""
        summary["quarantined_steps"] += 1
        if summary["divergence_rollbacks"] >= \
                self.max_divergence_rollbacks:
            raise DivergenceError(step, summary["divergent"],
                                  summary["divergence_rollbacks"])
        # every rank saw the same fp-result broadcast, so all arrive at
        # the lockstep barriers together
        resume = self._lockstep_restore("quarantine", step,
                                        summary["divergence_rollbacks"])
        guard = self._guard()
        if guard is not None:
            guard.reset_streaks()
        summary["divergence_rollbacks"] += 1
        self._m_rollbacks.inc(kind="quarantine")
        _spans.event("quarantine", step=step, resume=resume,
                     divergent=summary["divergent"])
        self._blackbox_dump("quarantine", step=step)
        warnings.warn(
            f"quarantined diverged step {step}; rolled back to the "
            f"last verified checkpoint, resuming at step {resume} "
            f"(divergence rollback {summary['divergence_rollbacks']}/"
            f"{self.max_divergence_rollbacks})", stacklevel=2)
        return resume

    # -- per-step telemetry ------------------------------------------------
    def _observe_step(self, step, step_s, batch, summary, run_t0,
                      first):
        """Host-side step accounting: duration histogram, throughput,
        MFU when an XLA cost analysis is already cached (never forces a
        compile on the step path), HBM gauges (one ``memory_stats``
        read; a no-op off-accelerator after the first probe), the
        anomaly sentinel, and — once per run — the restart-to-
        first-step latency that gates cold-start regressions.

        A PROFILED step's wall-clock is dominated by the trace dump +
        parse, not the step: it still counts in train_steps_total, but
        its duration stays out of the step-time histogram, the
        throughput/MFU gauges, and the sentinel — operators must never
        read the sampling overhead as a performance regression (the
        real sampling cost is profile_capture_seconds)."""
        profiled = getattr(self, "_step_was_profiled", False)
        self._step_was_profiled = False
        self._m_steps.inc()
        if not profiled:
            self._m_step_time.observe(step_s)
        if first:
            lat = time.perf_counter() - run_t0
            summary["first_step_latency_s"] = round(lat, 6)
            self._m_first_step.set(lat)
            _spans.event("first_step", latency_s=lat,
                         resumed_at=summary["start"])
            # resolve the step's flop count ONCE, cheaply: only a cost
            # analysis someone already paid for (verbosity>=2, a prior
            # compiled_step_info/profile_step call) is consulted
            sf = getattr(self.model, "step_flops", None)
            if callable(sf):
                try:
                    self._step_flops = sf(compute=False)
                except Exception:       # audit is best-effort telemetry
                    self._step_flops = None
            if self._aot_store is not None:
                # the compiled step exists from THIS step on: persist
                # it so the next restart deserializes instead of
                # retracing. skip_if_current makes the warm steady
                # state free; failure degrades to cache-only warm
                # starts, loudly, never a dead trainer.
                from ..aot import export as _aot_export
                try:
                    with _spans.span("aot.export_train_step"):
                        _aot_export.export_train_step(
                            self.model, self._aot_store,
                            skip_if_current=True)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:      # noqa: BLE001 — degrade
                    warnings.warn(
                        f"AOT train-step export unavailable "
                        f"({type(e).__name__}: {e}); restarts warm "
                        "from the compile cache only", stacklevel=2)
        if step_s > 0 and not profiled:
            first_arr = next((b for b in batch
                              if hasattr(b, "shape") and
                              getattr(b, "shape", ())), None)
            if first_arr is not None and len(first_arr.shape) > 0:
                self._m_throughput.set(first_arr.shape[0] / step_s)
            if self._step_flops:
                peak = _metrics.device_peak_flops(self._jax_device())
                if peak:
                    self._m_mfu.set(self._step_flops / step_s / peak)
        # HBM at the step boundary (bytes_in_use / peak / limit gauges)
        _perf.record_hbm(self._jax_device(), site="train")
        # the first step carries the XLA compile: feeding it to the
        # sentinel would seed the baseline orders of magnitude high
        # and blind it for the whole EMA decay
        if self._sentinel is not None and not first and not profiled \
                and self._sentinel.observe(step, step_s):
            # sustained spike: the sentinel already left the attributed
            # step_anomaly event — capture a one-shot profile on the
            # next step and leave the blackbox behind now
            self._profiler.force_next()
            self._blackbox_dump("step_anomaly", step=step)

    # -- the loop ----------------------------------------------------------
    def run(self, data, num_steps, step_callback=None):
        """Train until global step ``num_steps``, surviving what the
        FaultPlan / real world throws. Returns a summary dict; raises
        ``SystemExit(EXIT_PREEMPTED)`` on preemption (see class doc)."""
        self._data = data
        self._it = None
        self._yielded_any = False
        self._data_resumed = False
        self._preempt_signal = None     # a reused trainer starts clean
        self._cur_step = None
        self._last_blackbox = None
        run_t0 = time.perf_counter()
        first_step_done = False
        summary = {"start": None, "steps_run": 0, "rollbacks": 0,
                   "step_retries": 0, "data_retries": 0,
                   "step_timeouts": 0, "skipped_steps": 0,
                   "preempted": False, "membership_lost": False,
                   "dead_ranks": [], "elastic": None,
                   "fingerprints": 0, "quarantined_steps": 0,
                   "divergence_rollbacks": 0, "divergent": [],
                   "diverged": False, "first_step_latency_s": None}
        prev_handlers = self._install_handlers()
        # ambient span attribution: every record made under this run —
        # trainer spans, checkpoint/cluster events, spans inside the
        # watchdog worker (it copies the context) — carries this rank
        span_ctx = _spans.context(rank=self._rank)
        span_ctx.__enter__()
        try:
            if self.cluster is not None and self.cluster.world > 1:
                # rendezvous BEFORE restore: a rank that never shows up
                # is named now, not discovered as a hung collective later
                with _spans.span("barrier", barrier="run-start"):
                    self.cluster.barrier(
                        "run-start", timeout=self.start_barrier_timeout)
            with _spans.span("restore", reason="run-start"):
                start = self.mgr.restore_latest(self.model)
            summary["start"] = start
            if self.cluster is not None and self.cluster.world > 1:
                # resume-step agreement: the barrier NAME carries the
                # resumed step, so a rank that fell back to an older
                # checkpoint (all same-step shard sources corrupt)
                # strands its peers here and everyone exits 75 LOUDLY
                # instead of training at inconsistent parameter
                # versions where no checkpoint could ever commit again
                with _spans.span("barrier", barrier=f"resume-{start}"):
                    self.cluster.barrier(
                        f"resume-{start}",
                        timeout=self.start_barrier_timeout)
            if isinstance(self.mgr, DistributedCheckpointManager):
                # agreement reached (barrier above, or a world of one):
                # markers at/after the resume point vouch for a
                # timeline about to be re-run — cleared now so a later
                # pre-ACK death cannot hide behind a stale marker
                self.mgr.invalidate_markers_from(start)
            self._apply_data_state(start)
            if start:
                self._log(f"resumed from checkpoint; continuing at "
                          f"step {start}")
            manifest = getattr(self.mgr, "restored_manifest", None)
            if manifest is not None and self.cluster is not None:
                saved_world = int(manifest.get("world",
                                               self.cluster.world))
                if saved_world != self.cluster.world:
                    from ..parallel.communicator import rescale_batch
                    per, gb = rescale_batch(manifest, self.cluster.world)
                    summary["elastic"] = {
                        "saved_world": saved_world,
                        "world": self.cluster.world,
                        "per_replica_batch": per, "global_batch": gb}
                    self._log(
                        f"elastic resume: world {saved_world} -> "
                        f"{self.cluster.world}" +
                        (f", global batch -> {gb} (per-replica {per} "
                         "kept)" if per is not None else ""))
            step = start
            self._check_preempt(step - 1, start)
            self._check_cluster()
            guard = self._guard()
            info = getattr(getattr(self.model, "optimizer", None),
                           "telemetry_info", None)
            if callable(info):
                try:        # one static run-config record, never per step
                    _spans.event("run_config", start=start,
                                 num_steps=num_steps, **info())
                except Exception:       # noqa: BLE001 — telemetry only
                    pass
            while step < num_steps:
                self._cur_step = step
                t_fetch = time.perf_counter()
                with _spans.span("data.next", step=step):
                    batch = self._next_batch(step, summary)
                self._m_fetch.observe(time.perf_counter() - t_fetch)
                t_step = time.perf_counter()
                with _spans.span("step", step=step):
                    out = self._run_step(step, batch, summary)
                step_s = time.perf_counter() - t_step
                summary["steps_run"] += 1
                self._observe_step(step, step_s, batch, summary,
                                   run_t0, first=not first_step_done)
                first_step_done = True
                # cross-replica fingerprint on its cadence, BEFORE the
                # save: a diverged step is quarantined — it must never
                # be checkpointed, and the rollback target is the last
                # verified (and cluster-agreed) step. Off by default:
                # fingerprint_every=0 adds zero work here.
                if self.fingerprint_every and \
                        (step + 1) % self.fingerprint_every == 0 and \
                        not self._fingerprint_check(step, summary):
                    step = self._quarantine_rollback(step, summary)
                    continue
                # ONE scalar readback per step; a guard-flagged bad step
                # is never checkpointed, so the newest checkpoint always
                # predates the bad streak and rollback actually rewinds
                bad = guard.bad_streak_value() if guard is not None else 0
                self._m_bad_streak.set(bad)  # value already read back
                if bad == 0:
                    # the data state rides every save: captured AFTER
                    # the step, so it counts this step's batch as
                    # consumed and a resume fetches the NEXT one
                    with _spans.span("checkpoint.save", step=step):
                        self.mgr.save(step, self.model,
                                      data_state=self._data_state())
                    self.faults.on_saved(step)
                if step_callback is not None:
                    step_callback(step, out)
                self._check_preempt(step, start)
                self._check_cluster()
                resumed = self._maybe_rollback(step, bad, summary)
                step = resumed if resumed is not None else step + 1
            self.mgr.wait()
            if self.cluster is not None and self.cluster.world > 1:
                # the run ends together: a coordinator that returned
                # (and closed the cluster) while a peer still had steps
                # to run would look to that peer like a dead
                # coordinator — membership lost on a healthy finish
                with _spans.span("barrier", barrier=f"end-{step}"):
                    self.cluster.barrier(
                        f"end-{step}", timeout=self.start_barrier_timeout)
            self._finalize_summary(summary)
            return summary
        except _Preempted:
            summary["preempted"] = True
            self._blackbox_dump("preempted")
            self._finalize_summary(summary)
            if self.exit_on_preempt:
                raise SystemExit(EXIT_PREEMPTED) from None
            return summary
        except DivergenceError as e:
            # NOT recoverable by a plain restart: replicas forked twice
            # despite rolling back to agreed state — suspect hardware.
            # Exit DISTINCT from 75 so the supervisor cordons/replaces
            # the divergent host first; resume still lands on the last
            # cross-replica-agreed checkpoint.
            summary["diverged"] = True
            self._blackbox_dump("diverged", step=e.step)
            self._finalize_summary(summary)
            self._log(f"{e}")
            if self.exit_on_preempt:
                raise SystemExit(EXIT_DIVERGED) from None
            return summary
        except StepTimeoutError:
            # fatal watchdog kill (the in-process grace already ran out
            # in _run_step): the supervisor restart is the recovery —
            # leave the last N seconds of evidence behind first
            self._blackbox_dump("watchdog_kill")
            raise
        except (MembershipError, BarrierTimeout) as e:
            # RECOVERABLE: the job is still viable at a smaller world.
            # Same supervisor contract as preemption — exit 75, restart
            # (now with fewer ranks), resume from the last COMMITTED
            # checkpoint re-sharded onto the new mesh. No checkpoint is
            # attempted here: a commit could never complete without the
            # dead rank's ACK, and the last committed step is consistent.
            summary["membership_lost"] = True
            summary["dead_ranks"] = list(getattr(e, "dead", [])) or \
                list(getattr(e, "missing", []))
            self._blackbox_dump("membership_lost")
            self._finalize_summary(summary)
            self._log(f"{e}; exiting {EXIT_PREEMPTED} for the "
                      "supervisor (restart at the surviving world size)")
            if self.exit_on_preempt:
                raise SystemExit(EXIT_PREEMPTED) from None
            return summary
        except Exception as e:      # noqa: BLE001 — re-raised below
            # any other crash (device OOM, an XLA failure past the
            # retry budget, a bug): leave the post-mortem behind — the
            # dump carries HBM stats and the live-array allocation
            # breakdown, so an OOM names where the memory went
            self._blackbox_dump("crash", error=e)
            raise
        finally:
            span_ctx.__exit__(None, None, None)
            self._restore_handlers(prev_handlers)

    def close(self):
        self.mgr.close()
