#!/usr/bin/env python
"""End-to-end cluster-chaos smoke under a hard wall-clock budget.

Runs the real-subprocess elastic scenarios (the same ones
``tests/test_multiprocess.py -m chaos`` asserts, without the pytest
harness) against ``examples/train_elastic.py``:

1. **dead-rank-elastic** — a 2-process run loses rank 1 to a hard kill;
   the survivor exits 75; a world-1 restart resumes from the last
   COMMITTED checkpoint with bit-identical optimizer state and rescaled
   batch accounting.
2. **commit-hole** — rank 1 dies after its shard is written but before
   its ACK; the step never gains a commit marker and the restart
   resumes from the previous committed step.
3. **barrier-missing** — a rank never shows up at the start rendezvous;
   the survivor names it and exits 75 instead of hanging.
4. **bitflip-restore** — bits flip in the newest committed checkpoint's
   tensor data (metadata intact — pure SDC); the restart detects it at
   restore and falls back to the previous VERIFIED step bit-identically,
   and the scrub CLI flags the damaged step.
5. **divergence-quarantine** — one rank's parameters silently fork
   (injected SDC); the cross-replica fingerprint catches it, every rank
   quarantines the step and rolls back to the last cluster-agreed
   checkpoint, and when the divergence repeats the run exits 76
   (``EXIT_DIVERGED`` — cordon the host, don't just relaunch).
6. **data-resume** — the exactly-once data invariant: a run killed
   mid-epoch and resumed consumes a per-step sample-id sequence
   BIT-IDENTICAL to a fault-free run's; the same invariant holds after
   a divergence-quarantine rewind (the data stream rolls back with the
   tensors) and across an elastic world-size change (the flattened
   consumed stream stays a clean prefix of the global permutation —
   nothing replayed, nothing skipped); and, in-process, a corrupt
   sample costs exactly one skipped-and-attributed sample while an
   exhausted skip budget fails loudly naming the bytes.
7. **serve-drain** — the serving fleet's drain contract: two gateway
   replicas (``examples/serve_transformer.py``) share a request
   stream; one is SIGTERMed mid-stream and must finish every admitted
   request (zero dropped in-flight responses), refuse new ones so the
   driver fails over, and exit 0 (``serving.EXIT_DRAINED``) while the
   survivor absorbs the queue without ever retracing its decode
   program.
8. **serve-crash** — fleet fault tolerance under a HARD kill: two
   gateway replicas behind a real ``FleetRouter``; one is SIGKILLed
   mid-stream (no drain handler runs). Zero failed client responses:
   every stranded request is re-dispatched to the survivor on its
   remaining deadline budget and the delivered tokens are bitwise
   identical to an uninterrupted greedy run; the circuit breaker
   ejects the corpse (gauge → open) and the redispatch/failover
   counters ride ``heartbeat_summary``. Banks the recovered-request
   count and the kill window's p99 time-to-response.
9. **serve-preempt** — preemption-deadline drain with live-KV
   handoff: a replica with ``--handoff-peers`` and a sub-second
   ``--drain-deadline`` takes a SIGTERM mid-stream; zero failed client
   responses, migrated continuations token-identical to uninterrupted
   runs, the drain honors the deadline, and the handoff leg recomputes
   STRICTLY fewer prefill tokens on the survivor than a forced
   re-dispatch baseline; plus the host-RAM spill tier (evicted cached
   prefixes spill under pool pressure and restore on a re-prompt).
10. **warm-restart** — cold-start elimination (``singa_tpu.aot``): a
    trainer and a serving replica restarted against a populated AOT
    cache reach the first step / first served token measurably faster
    than their cold baselines, with ZERO ``source="fresh"`` compiles
    and ``n_traces`` still 1 — every executable deserialized from an
    artifact or served from the persistent compile cache.
11. **serve-autoscale** — the SLO-driven warm autoscaler supervising
    real gateway subprocesses: a queue-depth breach scales up with a
    replica admitted through the warm gate (zero fresh compiles, an
    observed Retry-After while the spawn is in flight), a SIGKILLed
    replica is replaced with zero failed client responses, sustained
    calm retires the least-loaded replica through the drain path
    (every in-flight request delivered), and a flap-injected respawn
    loop is quarantined after the threshold instead of burning spawns
    forever. Banks spawn-to-ready p50/p99 and the recovered-request
    count.
12. **serve-disagg** — disaggregated prefill/decode pools across
    processes: a ``--pool-role prefill`` gateway transfers every
    sealed KV snapshot to one of two ``--pool-role decode`` gateways
    by prefix affinity; one decode peer is SIGKILLed holding injected
    work and one frame is corrupted on seal. Zero failed responses,
    every answer bitwise identical to colocated greedy, the transfer
    ladder's retry counters move, and the affinity leg's hit counter
    sits strictly above a round-robin baseline leg.

Every subprocess gets the REMAINING budget as its timeout, so the whole
smoke is bounded by ``--budget`` seconds end to end (default 600) —
exceeding it is itself a failure: a chaos path that hangs is exactly
the bug this suite exists to catch.

Usage::

    python tools/chaos_smoke.py [--budget 600] [--keep-dirs] \
        [--summary-json PATH]

Every kill/restart scenario also measures the restarted run's
``first_step_latency_s`` (run() entry to first completed step) and
banks it in the end-of-run measurement summary — the cold-start
regression series the persistent-compile-cache work gates on.
"""

import argparse
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ELASTIC = os.path.join(REPO, "examples", "train_elastic.py")
SCRUB = os.path.join(REPO, "tools", "scrub_checkpoints.py")
EXIT_PREEMPTED = 75
EXIT_DIVERGED = 76


class Budget:
    def __init__(self, seconds):
        self.deadline = time.monotonic() + seconds

    def remaining(self):
        left = self.deadline - time.monotonic()
        if left <= 0:
            raise TimeoutError("chaos smoke exceeded its wall-clock "
                               "budget")
        return left


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _cmd(rank, world, port, ckpt_dir, extra=(), steps=30):
    return [sys.executable, ELASTIC, "--cpu", "--rank", str(rank),
            "--world", str(world), "--coordinator", f"127.0.0.1:{port}",
            "--dir", str(ckpt_dir), "--steps", str(steps),
            "--save-every", "2", "--bs", "4", "--hb-interval", "0.2",
            "--dead-after", "1.5", "--commit-timeout", "5",
            "--start-timeout", "15"] + list(extra)


def _run(cmds, budget):
    procs = [subprocess.Popen(c, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for c in cmds]
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=budget.remaining())[0])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return [p.returncode for p in procs], outs


def _committed(ckpt_dir):
    cdir = os.path.join(str(ckpt_dir), "commits")
    if not os.path.isdir(cdir):
        return []
    # digits-only: a coordinator killed between tmp-write and rename
    # leaves .tmp-<step>.json, which must not crash the harness
    return sorted(int(f[:-5]) for f in os.listdir(cdir)
                  if f.endswith(".json") and f[:-5].isdigit())


def _check(ok, what, detail=""):
    if not ok:
        raise AssertionError(f"{what}\n{detail[-2000:]}")
    print(f"  ok: {what}")


# scenario name -> banked measurements (restart-to-first-step latency);
# printed as one JSON line at the end and written via --summary-json —
# the regression series the persistent-compile-cache work will gate on
BANK = {}


def _run_summary(out):
    """The trainer's end-of-run summary dict from a subprocess's
    output (the LAST ``summary {...}`` line — a restarted run prints
    exactly one)."""
    docs = [ln.split("summary ", 1)[1] for ln in out.splitlines()
            if ": summary {" in ln]
    return json.loads(docs[-1]) if docs else None


def _bank_restart_latency(scenario, out, leg="restart"):
    """Measure and ASSERT restart-to-first-step latency: every
    restarted run must report ``first_step_latency_s`` (run() entry to
    first completed step — compile + restore + first batch, the
    cold-start number the ROADMAP wants gated). Banked per scenario."""
    s = _run_summary(out)
    _check(s is not None, f"{scenario}/{leg}: run summary found")
    lat = s.get("first_step_latency_s")
    _check(isinstance(lat, (int, float)) and 0 < lat < 300,
           f"{scenario}/{leg}: restart-to-first-step latency measured "
           f"({lat if lat is None else round(lat, 3)}s)", out)
    BANK.setdefault(scenario, {})[f"{leg}_first_step_latency_s"] = \
        round(float(lat), 4)
    return lat


def scenario_dead_rank_elastic(root, budget):
    d = os.path.join(root, "ck")
    dumps = os.path.join(root, "dumps")
    os.makedirs(dumps)
    port = _free_port()
    rcs, outs = _run([
        _cmd(0, 2, port, d, ["--dump-on-save", dumps]),
        _cmd(1, 2, port, d, ["--die-at", "11", "--die-rank", "1"])],
        budget)
    _check(rcs == [EXIT_PREEMPTED, 1],
           f"survivor exits {EXIT_PREEMPTED}, victim hard-killed "
           f"(got {rcs})", outs[0])
    committed = _committed(d)
    # under load the survivor's commit wait for the last pre-death step
    # can time out (the ABORT semantics working as designed), so the
    # newest committed step is 10 or an earlier even step — the real
    # invariant is resume == newest committed + 1, bit-identical
    last = max(committed, default=-1)
    _check(bool(committed) and last >= 4,
           f"training committed real progress (markers: {committed})")
    restored = os.path.join(root, "restored.npz")
    rcs2, outs2 = _run([_cmd(0, 1, port, d,
                             ["--dump-restored", restored])], budget)
    _check(rcs2 == [0], f"world-1 restart completes (got {rcs2})",
           outs2[0])
    _bank_restart_latency("dead-rank-elastic", outs2[0])
    _check(f"continuing at step {last + 1}" in outs2[0],
           f"resumed at step {last + 1} from committed step {last}",
           outs2[0])
    _check("global batch 8 -> 4" in outs2[0],
           "batch accounting rescaled (per-replica kept)", outs2[0])
    a = np.load(restored)
    b = np.load(os.path.join(dumps, f"state_step{last}.npz"))
    assert set(a.files) == set(b.files)
    for k in a.files:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    _check(any(k.endswith(":momentum") for k in a.files),
           "bit-identical restore incl. optimizer momentum "
           f"({len(a.files)} state entries)")


def scenario_commit_hole(root, budget):
    d = os.path.join(root, "ck")
    port = _free_port()
    rcs, outs = _run([
        _cmd(0, 2, port, d),
        _cmd(1, 2, port, d, ["--kill-before-ack", "6",
                             "--die-rank", "1"])], budget)
    _check(rcs == [EXIT_PREEMPTED, 1],
           f"survivor exits {EXIT_PREEMPTED} after the commit-hole "
           f"death (got {rcs})", outs[0])
    committed = _committed(d)
    last = max(committed, default=-1)
    _check(6 not in committed and committed and last <= 4,
           f"step 6 never committed (markers: {committed})")
    _check(os.path.isdir(os.path.join(d, "rank1", "6")),
           "the victim's shard IS on disk — written, never acked")
    rcs2, outs2 = _run([_cmd(0, 1, port, d, ["--steps", "10"])], budget)
    _check(rcs2 == [0] and f"continuing at step {last + 1}" in outs2[0],
           "restart refuses the unmarked step, resumes after step "
           f"{last}", outs2[0])
    _bank_restart_latency("commit-hole", outs2[0])


def scenario_barrier_missing(root, budget):
    d = os.path.join(root, "ck")
    port = _free_port()
    rcs, outs = _run([_cmd(0, 2, port, d, ["--start-timeout", "3"])],
                     budget)
    _check(rcs == [EXIT_PREEMPTED],
           f"lone rank exits {EXIT_PREEMPTED} (got {rcs})", outs[0])
    _check("rank(s) [1]" in outs[0],
           "the missing rank is NAMED, not hung on", outs[0])


def scenario_bitflip_restore(root, budget):
    """Pure-SDC disk corruption: tensor bytes flip in the newest
    committed step, the restart refuses it (digest/chunk-CRC failure),
    falls back to the previous verified step BIT-IDENTICALLY, and the
    scrub CLI flags the damage."""
    d = os.path.join(root, "ck")
    dumps = os.path.join(root, "dumps")
    os.makedirs(dumps)
    port = _free_port()
    rcs, outs = _run([_cmd(0, 1, port, d,
                           ["--dump-on-save", dumps], steps=12)], budget)
    _check(rcs == [0], f"clean world-1 run completes (got {rcs})",
           outs[0])
    committed = _committed(d)
    last = max(committed)
    _check(last >= 4, f"real progress committed (markers: {committed})")

    sys.path.insert(0, REPO)
    from singa_tpu.resilience.faults import bitflip_checkpoint
    flipped = bitflip_checkpoint(os.path.join(d, "rank0"), last)
    _check(bool(flipped), f"bits flipped in step {last}'s tensor data "
           f"({len(flipped)} chunk files)")

    scrub = subprocess.run(
        [sys.executable, SCRUB, d], capture_output=True, text=True,
        timeout=budget.remaining())
    _check(scrub.returncode == 1 and f"rank0/{last}" in scrub.stdout,
           f"scrub CLI flags step {last} and exits nonzero",
           scrub.stdout + scrub.stderr)

    prev = max(s for s in committed if s != last)
    restored = os.path.join(root, "restored.npz")
    rcs2, outs2 = _run([_cmd(0, 1, port, d,
                             ["--dump-restored", restored],
                             steps=12)], budget)
    _check(rcs2 == [0], f"restart completes (got {rcs2})", outs2[0])
    _bank_restart_latency("bitflip-restore", outs2[0])
    _check(f"dumped restored state of step {prev}" in outs2[0],
           f"corrupt step {last} refused; restore fell back to "
           f"verified step {prev}", outs2[0])
    a = np.load(restored)
    b = np.load(os.path.join(dumps, f"state_step{prev}.npz"))
    assert set(a.files) == set(b.files)
    for k in a.files:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    _check(True, "recovery is bit-identical to the verified step "
           f"({len(a.files)} state entries)")


def scenario_divergence_quarantine(root, budget):
    """Injected single-replica SDC: the cross-replica fingerprint
    detects it, every rank quarantines + rolls back to the last
    cluster-agreed checkpoint, and repeated divergence exits 76."""
    d = os.path.join(root, "ck")
    port = _free_port()
    rcs, outs = _run([
        _cmd(0, 2, port, d, ["--fingerprint-every", "3",
                             "--max-divergence-rollbacks", "1"],
             steps=20),
        _cmd(1, 2, port, d, ["--fingerprint-every", "3",
                             "--max-divergence-rollbacks", "1",
                             "--diverge-at", "5", "--diverge-rank", "1",
                             "--diverge-times", "5"],
             steps=20)], budget)
    # the rank that loses the race to the verdict may instead see the
    # other's death as membership loss (75) — but at least the
    # coordinator always learns the verdict and exits 76
    _check(rcs[0] == EXIT_DIVERGED and
           rcs[1] in (EXIT_DIVERGED, EXIT_PREEMPTED),
           f"divergence exits {EXIT_DIVERGED} (got {rcs})",
           outs[0] + outs[1])
    _check("quarantined diverged step" in outs[0] + outs[1],
           "the diverged step was quarantined and rolled back",
           outs[0])
    _check("fingerprint" in outs[0] + outs[1],
           "the fingerprint detector is what fired", outs[0])
    committed = _committed(d)
    # save-every is 2, divergence at step 5: nothing at or after the
    # divergence point may commit (a vacuous `5 not in` would pass even
    # with quarantine broken, since odd steps never save)
    _check(bool(committed) and max(committed) < 5,
           f"nothing at/after the divergence committed "
           f"(markers: {committed})")


def _expected_stream(total, n=64, seed=0):
    """The analytic global sample stream ``train_elastic.py`` consumes:
    epoch after epoch of the stateless ``(seed, epoch)``-keyed
    permutation (``data.epoch_permutation``) over its ``n``-sample
    synthetic set — exactly what a fault-free run of ANY world size
    walks in order. ``tests/test_data_resume.py`` pins a live fault-free
    trainer to this stream, so asserting against it IS asserting
    bit-identity with a fault-free run."""
    sys.path.insert(0, REPO)
    from singa_tpu.data import epoch_permutation
    out = []
    epoch = 0
    while sum(len(p) for p in out) < total:
        out.append(epoch_permutation(seed, epoch, n))
        epoch += 1
    return np.concatenate(out)[:total]


def _final_ids(ids_dir):
    """{step: consumed sample ids} from the per-step npy dumps — the
    FINAL timeline (re-runs overwrite their step's file)."""
    out = {}
    for f in os.listdir(ids_dir):
        if f.startswith("ids_step") and f.endswith(".npy"):
            out[int(f[len("ids_step"):-4])] = np.load(
                os.path.join(ids_dir, f))
    return out


def scenario_data_resume(root, budget):
    """Exactly-once data pipeline: kill mid-epoch -> resume ->
    bit-identical per-step sample ids; same invariant through a
    quarantine rewind and an elastic world-size change; corrupt samples
    cost one attributed skip each, an exhausted budget fails loudly."""
    # -- 1. headline: world-1 kill mid-epoch, resume, bit-identical ----
    d = os.path.join(root, "ck")
    ids = os.path.join(root, "ids")
    port = _free_port()
    rcs, outs = _run([_cmd(0, 1, port, d,
                           ["--dump-sample-ids", ids, "--die-at", "9",
                            "--die-rank", "0"], steps=20)], budget)
    _check(rcs == [1], f"mid-epoch hard kill lands (got {rcs})", outs[0])
    rcs2, outs2 = _run([_cmd(0, 1, port, d,
                             ["--dump-sample-ids", ids], steps=20)],
                       budget)
    _check(rcs2 == [0], f"resumed run completes (got {rcs2})", outs2[0])
    _bank_restart_latency("data-resume", outs2[0])
    _check("data stream rewound" in outs2[0],
           "resume rewound the data stream to the checkpointed offset",
           outs2[0])
    got = _final_ids(ids)
    stream = _expected_stream(4 * 20)
    _check(sorted(got) == list(range(20)),
           f"every step's sample ids dumped (steps: {sorted(got)})")
    for k in range(20):
        np.testing.assert_array_equal(
            got[k], stream[4 * k:4 * (k + 1)], err_msg=f"step {k}")
    _check(True, "kill->resume: per-step sample ids BIT-IDENTICAL to a "
                 "fault-free run (all 20 steps)")

    # -- 2. quarantine rewind: the data stream rolls back too ----------
    d2 = os.path.join(root, "ck2")
    ids2 = os.path.join(root, "ids2")
    port = _free_port()
    fp = ["--fingerprint-every", "3", "--max-divergence-rollbacks", "2"]
    rcs, outs = _run([
        _cmd(0, 2, port, d2, fp + ["--dump-sample-ids", ids2], steps=12),
        _cmd(1, 2, port, d2, fp + ["--diverge-at", "5",
                                   "--diverge-rank", "1"], steps=12)],
        budget)
    _check(rcs == [0, 0],
           f"single-shot divergence recovers and completes (got {rcs})",
           outs[0] + outs[1])
    _check("quarantined diverged step" in outs[0] + outs[1],
           "the quarantine-rollback path is what ran", outs[1])
    got = _final_ids(ids2)
    stream = _expected_stream(8 * 12)
    for k in range(12):
        np.testing.assert_array_equal(
            got[k], stream[8 * k:8 * (k + 1)], err_msg=f"step {k}")
    _check(True, "quarantine rewind: re-run steps consumed the exact "
                 "batches of the quarantined timeline")

    # -- 3. elastic world change: the stream stays a clean prefix ------
    d3 = os.path.join(root, "ck3")
    ids3 = os.path.join(root, "ids3")
    port = _free_port()
    rcs, outs = _run([
        _cmd(0, 2, port, d3, ["--dump-sample-ids", ids3], steps=12),
        _cmd(1, 2, port, d3, ["--die-at", "7", "--die-rank", "1"],
             steps=12)], budget)
    _check(rcs == [EXIT_PREEMPTED, 1],
           f"world-2 loses rank 1, survivor exits 75 (got {rcs})",
           outs[0])
    rcs2, outs2 = _run([_cmd(0, 1, port, d3,
                             ["--dump-sample-ids", ids3], steps=12)],
                       budget)
    _check(rcs2 == [0] and "elastic restart" in outs2[0],
           f"world-1 elastic restart completes (got {rcs2})", outs2[0])
    _bank_restart_latency("data-resume", outs2[0], leg="elastic-restart")
    got = _final_ids(ids3)
    flat = np.concatenate([got[k] for k in sorted(got)])
    stream = _expected_stream(len(flat))
    np.testing.assert_array_equal(flat, stream)
    _check(len(flat) >= 64 and
           sorted(flat[:64].tolist()) == list(range(64)),
           "elastic resume: flattened stream is a clean prefix of the "
           f"global permutation ({len(flat)} samples, epoch 0 consumed "
           "exactly once)")

    # -- 4. corrupt samples: one attributed skip each, bounded ---------
    sys.path.insert(0, REPO)
    from singa_tpu.data import DataSampleError, ImageBatchIter
    from singa_tpu.resilience.faults import FaultPlan
    sdir = os.path.join(root, "samples")
    os.makedirs(sdir)
    for i in range(12):
        np.save(os.path.join(sdir, f"s{i}.npy"),
                np.full((2, 2), i, np.float32))
    lst = os.path.join(sdir, "list.txt")
    with open(lst, "w") as f:
        for i in range(12):
            f.write(f"s{i}.npy {i % 3}\n")

    def transform(path):
        return [np.load(path)]

    import warnings as _w
    it = ImageBatchIter(lst, 4, transform, shuffle=False,
                        image_folder=sdir, skip_budget=2,
                        faults=FaultPlan().corrupt_sample(1))
    it.start()
    with _w.catch_warnings():
        _w.simplefilter("ignore")
        batches = [next(it) for _ in range(3)]
    it.end()
    consumed = np.concatenate([b[1] for b in batches])
    _check(len(consumed) == 11 and it.skip_count == 1
           and it.quarantined[0]["index"] == 1
           and "s1.npy" in it.quarantined[0]["path"],
           "a corrupt sample costs exactly one skipped sample, "
           f"attributed ({it.quarantined[0]['path']})")

    it = ImageBatchIter(lst, 4, transform, shuffle=False,
                        image_folder=sdir, skip_budget=1,
                        faults=FaultPlan().corrupt_sample(0, times=3))
    it.start()
    try:
        with _w.catch_warnings():
            _w.simplefilter("ignore")
            while True:
                next(it)
    except DataSampleError as e:
        _check("skip budget exhausted" in str(e)
               and e.sample is not None,
               f"exhausted skip budget fails LOUDLY, naming the bytes "
               f"({e.sample['path']})")
    else:
        _check(False, "skip budget exhaustion did not raise")
    finally:
        it.end()


def scenario_serve_drain(root, budget):
    """Serving-fleet drain contract: two gateway replicas absorb one
    request stream; one replica is SIGTERMed mid-stream and must
    (a) finish every request it had admitted — zero dropped in-flight
    responses, (b) refuse new ones so the driver fails over to the
    survivor, (c) exit 0 (``serving.EXIT_DRAINED``). Every submitted
    request gets exactly one complete response."""
    import http.client
    import signal as _signal
    import threading

    serve = os.path.join(REPO, "examples", "serve_transformer.py")
    ports = [_free_port(), _free_port()]
    cmd = lambda p: [sys.executable, serve, "--cpu", "--port", str(p),  # noqa: E731
                     "--slots", "2", "--max-len", "48",
                     "--prefill-len", "8", "--vocab", "32",
                     "--d-model", "16", "--layers", "1"]
    procs = [subprocess.Popen(cmd(p), stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for p in ports]
    try:
        # wait for both gateways to answer /healthz
        deadline = time.monotonic() + min(120, budget.remaining())
        up = set()
        while len(up) < 2 and time.monotonic() < deadline:
            for p in ports:
                if p in up:
                    continue
                try:
                    c = http.client.HTTPConnection("127.0.0.1", p,
                                                   timeout=2)
                    c.request("GET", "/healthz")
                    if c.getresponse().status == 200:
                        up.add(p)
                    c.close()
                except OSError:
                    time.sleep(0.2)
        _check(len(up) == 2, "serve-drain: both replicas READY")

        N, new_tokens = 12, 8
        results = [None] * N
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 32, (int(rng.randint(1, 8)),)).tolist()
                   for _ in range(N)]
        started = threading.Semaphore(0)

        def one(i):
            body = json.dumps({"prompt": prompts[i],
                               "max_new_tokens": new_tokens,
                               "temperature": 0.0})
            # preferred replica first; fail over on refusal — the
            # router/LB behavior a drained replica's 503 exists for
            order = [ports[i % 2], ports[(i + 1) % 2]]
            started.release()
            last = None
            for attempt in range(10):
                # preferred first, then ALTERNATE: a transient failure
                # on the survivor must not strand retries on the
                # killed replica's port
                port = order[attempt % 2]
                try:
                    c = http.client.HTTPConnection("127.0.0.1", port,
                                                   timeout=120)
                    c.request("POST", "/v1/generate", body)
                    r = c.getresponse()
                    doc = json.loads(r.read().decode() or "{}")
                    c.close()
                except OSError as e:     # replica already gone
                    last = ("conn", str(e))
                    time.sleep(0.2)
                    continue
                if r.status == 200:
                    results[i] = doc
                    return
                last = (r.status, doc)   # 503 while draining: next
                time.sleep(0.2)
            results[i] = ("FAILED", last)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(N)]
        for t in threads[:6]:
            t.start()
        for _ in range(6):      # first wave is in flight NOW
            started.acquire()
        # kill replica 0 mid-stream: SIGTERM == graceful drain
        procs[0].send_signal(_signal.SIGTERM)
        for t in threads[6:]:
            t.start()
        for t in threads:
            t.join(timeout=budget.remaining())
        rc0 = procs[0].wait(timeout=budget.remaining())
        out0 = procs[0].communicate()[0]

        _check(rc0 == 0,
               f"serve-drain: drained replica exited 0 (got {rc0})",
               out0)
        _check("DRAINED exit=0" in out0,
               "serve-drain: replica reported a clean drain", out0)
        bad = [(i, r) for i, r in enumerate(results)
               if not isinstance(r, dict)
               or len(r.get("tokens", [])) != new_tokens]
        _check(not bad,
               f"serve-drain: all {N} requests answered exactly once, "
               f"complete ({len(bad)} bad)", repr(bad[:3]))
        # survivor still healthy and never retraced
        c = http.client.HTTPConnection("127.0.0.1", ports[1], timeout=5)
        c.request("GET", "/healthz")
        h = json.loads(c.getresponse().read())
        c.close()
        _check(h["status"] == "serving"
               and h["compiled"]["n_traces"] == 1,
               "serve-drain: survivor serving, decode traced once")
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()


def scenario_serve_crash(root, budget):
    """Fleet fault tolerance under a HARD kill: two gateway replicas
    (identical weights — both seed 0) absorb one request stream
    through a real in-driver ``FleetRouter``; replica 0 is SIGKILLed
    mid-stream (no drain, no goodbye). The contract: (a) ZERO failed
    client responses — every request stranded in the dead replica is
    re-dispatched to the survivor on its REMAINING deadline budget and
    delivered exactly once, (b) the re-dispatched tokens are bitwise
    identical to an uninterrupted greedy run on the survivor, (c) the
    breaker ejects the dead replica (gauge → open) and the
    redispatch/failover counters move, visible in
    ``heartbeat_summary``. Banks the recovered-request count and the
    p99 time-to-response across the kill window."""
    import http.client
    import signal as _signal
    import threading

    # the other scenarios are subprocess-only; this one drives a real
    # in-driver FleetRouter, so the repo root must be importable
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from singa_tpu import serving
    from singa_tpu.observability import metrics as obs_metrics

    serve = os.path.join(REPO, "examples", "serve_transformer.py")
    ports = [_free_port(), _free_port()]
    cmd = lambda p: [sys.executable, serve, "--cpu", "--port", str(p),  # noqa: E731
                     "--slots", "2", "--max-len", "48",
                     "--prefill-len", "8", "--vocab", "32",
                     "--d-model", "16", "--layers", "1"]
    procs = [subprocess.Popen(cmd(p), stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for p in ports]

    class HttpReplica:
        """The wire between the router and a gateway subprocess, with
        router-visible failure semantics: a dead socket at submit is a
        wire error (breaker fodder), a connection that dies mid-read
        is ``ReplicaCrashed`` (re-dispatch), a 503 is backpressure."""

        def __init__(self, name, port):
            self.name = name
            self.port = port
            self.draining = False
            self._lock = threading.Lock()
            self._outstanding = 0

        def queue_depth(self):
            with self._lock:
                return self._outstanding

        def health(self):
            c = http.client.HTTPConnection("127.0.0.1", self.port,
                                           timeout=2)
            try:
                c.request("GET", "/healthz")
                return json.loads(c.getresponse().read())
            finally:
                c.close()

        def submit(self, prompt, **kw):
            body = json.dumps(
                {"prompt": list(prompt),
                 **{k: kw[k] for k in ("max_new_tokens",
                                       "temperature", "timeout")
                    if kw.get(k) is not None}})
            conn = http.client.HTTPConnection(
                "127.0.0.1", self.port, timeout=120)
            try:
                conn.request("POST", "/v1/generate", body)
            except OSError as e:      # refused/reset at the door
                conn.close()
                raise ConnectionError(
                    f"{self.name}: submit wire error: {e}") from e
            fut = serving.ServeFuture()
            with self._lock:
                self._outstanding += 1

            def _read():
                try:
                    r = conn.getresponse()
                    doc = json.loads(r.read().decode() or "{}")
                    if r.status == 200:
                        fut.set_result(doc)
                    elif r.status == 503:
                        fut.set_error(serving.EngineDraining(
                            f"{self.name}: 503 {doc.get('error')}"))
                    else:
                        fut.set_error(serving.ServingError(
                            f"{self.name}: HTTP {r.status}: "
                            f"{doc.get('error')}"))
                except (OSError, http.client.HTTPException,
                        ValueError) as e:   # SIGKILL mid-response
                    fut.set_error(serving.ReplicaCrashed(
                        f"{self.name}: connection died "
                        f"mid-request: {e}"))
                finally:
                    conn.close()
                    with self._lock:
                        self._outstanding -= 1

            threading.Thread(target=_read, daemon=True).start()
            return fut

    try:
        deadline = time.monotonic() + min(120, budget.remaining())
        up = set()
        while len(up) < 2 and time.monotonic() < deadline:
            for p in ports:
                if p in up:
                    continue
                try:
                    c = http.client.HTTPConnection("127.0.0.1", p,
                                                   timeout=2)
                    c.request("GET", "/healthz")
                    if c.getresponse().status == 200:
                        up.add(p)
                    c.close()
                except OSError:
                    time.sleep(0.2)
        _check(len(up) == 2, "serve-crash: both replicas READY")

        r0 = HttpReplica("r0", ports[0])
        r1 = HttpReplica("r1", ports[1])
        reg = obs_metrics.MetricsRegistry()
        rt = serving.FleetRouter([r0, r1], registry=reg,
                                 breaker_threshold=2,
                                 breaker_backoff=2.0,
                                 max_redispatch=3)

        N, new_tokens = 12, 8
        rng = np.random.RandomState(1)
        prompts = [rng.randint(1, 32,
                               (int(rng.randint(1, 8)),)).tolist()
                   for _ in range(N)]
        results, lat = [None] * N, [None] * N
        errors = [None] * N

        def one(i):
            t0 = time.monotonic()
            try:
                f = rt.submit(prompts[i], max_new_tokens=new_tokens,
                              temperature=0.0, timeout=90.0)
                results[i] = (f.result(), f.redispatches)
            except Exception as e:  # noqa: BLE001
                errors[i] = f"{type(e).__name__}: {e}"
            lat[i] = time.monotonic() - t0

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(N)]
        for t in threads[:6]:
            t.start()
        # kill the moment replica 0 actually holds admitted work —
        # those requests are the stranded ones the re-dispatch exists
        # for (SIGKILL: no drain handler runs, sockets just die)
        kill_deadline = time.monotonic() + 30
        while (r0.queue_depth() < 2
               and time.monotonic() < kill_deadline):
            time.sleep(0.01)
        _check(r0.queue_depth() >= 1,
               "serve-crash: replica 0 holds in-flight work at kill")
        procs[0].send_signal(_signal.SIGKILL)
        for t in threads[6:]:
            t.start()
        for t in threads:
            t.join(timeout=budget.remaining())
        procs[0].wait(timeout=budget.remaining())

        _check(not any(errors),
               f"serve-crash: zero failed client responses "
               f"({sum(e is not None for e in errors)} failed)",
               repr([e for e in errors if e][:3]))
        bad = [i for i, (doc, _rd) in enumerate(results)
               if len(doc.get("tokens", [])) != new_tokens]
        _check(not bad,
               f"serve-crash: all {N} responses complete "
               f"({len(bad)} short)")
        recovered = sum(rd for _doc, rd in results)
        _check(recovered >= 1,
               f"serve-crash: stranded requests were re-dispatched "
               f"({recovered} recovered)")

        # bitwise token identity: every delivered answer must equal an
        # uninterrupted greedy run on the survivor (same seed-0
        # weights in both replicas, temperature 0)
        for i in range(N):
            c = http.client.HTTPConnection("127.0.0.1", ports[1],
                                           timeout=120)
            c.request("POST", "/v1/generate",
                      json.dumps({"prompt": prompts[i],
                                  "max_new_tokens": new_tokens,
                                  "temperature": 0.0}))
            ref = json.loads(c.getresponse().read())
            c.close()
            if results[i][0]["tokens"] != ref["tokens"]:
                raise AssertionError(
                    f"serve-crash: request {i} tokens diverged from "
                    f"the uninterrupted run: "
                    f"{results[i][0]['tokens']} != {ref['tokens']}")
        print(f"  ok: serve-crash: all {N} responses bitwise "
              f"identical to uninterrupted greedy runs")

        # breaker ejected the corpse; counters moved and ride the
        # heartbeat
        _check(rt.breaker_states()["r0"] == "open",
               "serve-crash: breaker OPEN on the killed replica")
        _check(reg.get("serve_fleet_redispatch_total").total()
               >= 1, "serve-crash: redispatch counter moved")
        hs = obs_metrics.heartbeat_summary(reg)["serving_fleet"]
        _check(hs["redispatches"] >= 1 and hs["breaker_opens"] >= 1
               and hs["breakers_open"] >= 1,
               f"serve-crash: heartbeat_summary carries the fleet "
               f"block {hs}")
        # survivor is intact: still serving, decode never retraced
        h = r1.health()
        _check(h["status"] == "serving"
               and h["compiled"]["n_traces"] == 1,
               "serve-crash: survivor serving, decode traced once")

        # the kill window's latency tail: requests that either had to
        # be re-dispatched off the corpse or were submitted after the
        # kill (they ate the breaker's discovery cost)
        kill_lat = [lat[i] for i in range(N)
                    if lat[i] is not None
                    and (results[i][1] > 0 or i >= 6)]
        p99 = float(np.percentile(kill_lat, 99)) if kill_lat else 0.0
        BANK["serve-crash"] = {
            "recovered_requests": int(recovered),
            "p99_ttr_kill_window_s": round(p99, 4),
        }
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()


def scenario_serve_preempt(root, budget):
    """Preemption-deadline drain with live-KV handoff: two gateway
    replicas; replica 0 runs with ``--handoff-peers <survivor>`` and a
    sub-second ``--drain-deadline``, takes a SIGTERM mid-stream, and
    must (a) migrate what cannot finish — zero failed client
    responses, every migrated continuation token-identical to an
    uninterrupted run on the survivor, (b) report ``DRAIN_DONE``
    within the deadline (plus process slack), never the full drain
    timeout, (c) move the handoff counters on the survivor. A second
    leg re-runs the SAME workload against a replica WITHOUT peers (the
    forced re-dispatch baseline) and asserts the handoff leg recomputed
    STRICTLY fewer prefill tokens on the survivor. A final sub-step
    exercises the host-RAM spill tier on the survivor (evict a cached
    prefix under pool pressure, re-prompt, assert spill+restore
    counters moved)."""
    import http.client
    import signal as _signal
    import threading

    serve = os.path.join(REPO, "examples", "serve_transformer.py")
    deadline_s = 0.2

    def _get_json(port, path, timeout=10):
        c = http.client.HTTPConnection("127.0.0.1", port,
                                       timeout=timeout)
        try:
            c.request("GET", path)
            r = c.getresponse()
            return r.status, json.loads(r.read().decode() or "{}")
        finally:
            c.close()

    def _counter_total(port, name):
        _st, doc = _get_json(port, "/metrics.json")
        for m in doc.get("metrics", []):
            if m.get("name") == name:
                return sum(s.get("value", 0)
                           for s in m.get("series", []))
        return 0

    def _wait_ready(ports_up):
        deadline = time.monotonic() + min(120, budget.remaining())
        up = set()
        while len(up) < len(ports_up) and time.monotonic() < deadline:
            for p in ports_up:
                if p in up:
                    continue
                try:
                    st, _ = _get_json(p, "/healthz", timeout=2)
                    if st == 200:
                        up.add(p)
                except OSError:
                    time.sleep(0.2)
        return len(up) == len(ports_up)

    # paged + small pool + spill tier on BOTH replicas: the survivor's
    # pool pressure drives the spill sub-step, and snapshots need the
    # same geometry on both ends
    base = ["--cpu", "--slots", "2", "--max-len", "96",
            "--prefill-len", "16", "--vocab", "32", "--d-model", "16",
            "--layers", "1", "--kv-layout", "paged",
            "--kv-block-size", "8", "--kv-blocks", "12",
            "--spill-bytes", str(4 << 20)]
    survivor_port = _free_port()
    surv = subprocess.Popen(
        [sys.executable, serve, "--port", str(survivor_port)] + base,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)

    N, new_tokens = 6, 64
    rng = np.random.RandomState(7)

    def _leg_prompts():
        # distinct 16-token prompts (2 full blocks each): measurable
        # prefill cost, no accidental shared prefixes — and a FRESH
        # set per leg, so the handoff leg cannot warm the survivor's
        # prefix/spill caches for the baseline leg's workload
        return [rng.randint(1, 32, (16,)).tolist() for _ in range(N)]

    def run_leg(name, with_peers, prompts):
        """One preemption leg against a fresh replica 0; returns the
        survivor's kill-window prefill-token delta for the leg."""
        port0 = _free_port()
        extra = ["--drain-deadline", str(deadline_s),
                 "--drain-timeout", "60"]
        if with_peers:
            extra += ["--handoff-peers", str(survivor_port)]
        p0 = subprocess.Popen(
            [sys.executable, serve, "--port", str(port0)]
            + base + extra,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        try:
            _check(_wait_ready([port0]),
                   f"serve-preempt/{name}: replica 0 READY")
            pf_before = _counter_total(survivor_port,
                                       "serve_prefill_tokens_total")
            results = [None] * N

            def one(i):
                body = json.dumps({"prompt": prompts[i],
                                   "max_new_tokens": new_tokens,
                                   "temperature": 0.0,
                                   "timeout": 120.0})
                order = [port0, survivor_port]
                last = None
                for attempt in range(12):
                    port = order[min(attempt, 1)] if attempt < 2 \
                        else order[attempt % 2]
                    try:
                        c = http.client.HTTPConnection(
                            "127.0.0.1", port, timeout=120)
                        c.request("POST", "/v1/generate", body)
                        r = c.getresponse()
                        doc = json.loads(r.read().decode() or "{}")
                        c.close()
                    except OSError as e:
                        last = ("conn", str(e))
                        time.sleep(0.2)
                        continue
                    if r.status == 200:
                        results[i] = doc
                        return
                    last = (r.status, doc)
                    time.sleep(0.2)
                results[i] = ("FAILED", last)

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(N)]
            for t in threads:
                t.start()
            # SIGTERM the moment replica 0 actually holds admitted
            # work — in-flight slots are what the snapshot handoff
            # migrates, queued work rides the recompute rung
            kill_by = time.monotonic() + 30
            while time.monotonic() < kill_by:
                try:
                    _st, h = _get_json(port0, "/healthz", timeout=2)
                except OSError:
                    break
                if (h.get("active_slots") or 0) >= 1 and \
                        h.get("queue_depth", 0) >= 1:
                    break
                time.sleep(0.01)
            p0.send_signal(_signal.SIGTERM)
            for t in threads:
                t.join(timeout=budget.remaining())
            rc0 = p0.wait(timeout=budget.remaining())
            out0 = p0.communicate()[0]

            bad = [(i, r) for i, r in enumerate(results)
                   if not isinstance(r, dict)
                   or len(r.get("tokens", [])) != new_tokens]
            _check(not bad,
                   f"serve-preempt/{name}: zero failed client "
                   f"responses ({len(bad)} bad)",
                   repr(bad[:3]) + "\n" + out0)
            # the deadline was honored: DRAIN_DONE well inside the
            # 60s drain timeout (generous slack covers handoff POSTs
            # + process teardown on a loaded CPU host)
            done = [ln for ln in out0.splitlines()
                    if ln.startswith("DRAIN_DONE in=")]
            _check(len(done) == 1,
                   f"serve-preempt/{name}: DRAIN_DONE printed", out0)
            took = float(done[0].split("=")[1].rstrip("s"))
            _check(took < deadline_s + 10.0,
                   f"serve-preempt/{name}: drain honored the "
                   f"{deadline_s}s deadline (took {took:.2f}s)", out0)
            if with_peers:
                _check(rc0 == 0,
                       f"serve-preempt/{name}: clean handoff drain "
                       f"exits 0 (got {rc0})", out0)
            # the kill-window recompute work, measured BEFORE the
            # reference re-runs below (those get prefix-cache hits
            # from the kill-window serves — their cost is not a
            # constant that can be subtracted back out)
            pf_after = _counter_total(survivor_port,
                                      "serve_prefill_tokens_total")
            # migrated continuations must be token-identical to an
            # uninterrupted greedy run (identical seed-0 weights)
            for i in range(N):
                c = http.client.HTTPConnection(
                    "127.0.0.1", survivor_port, timeout=120)
                c.request("POST", "/v1/generate",
                          json.dumps({"prompt": prompts[i],
                                      "max_new_tokens": new_tokens,
                                      "temperature": 0.0}))
                ref = json.loads(c.getresponse().read())
                c.close()
                if results[i]["tokens"] != ref["tokens"]:
                    raise AssertionError(
                        f"serve-preempt/{name}: request {i} diverged "
                        f"from the uninterrupted run: "
                        f"{results[i]['tokens']} != {ref['tokens']}")
            print(f"  ok: serve-preempt/{name}: all {N} responses "
                  f"token-identical to uninterrupted runs")
            return pf_after - pf_before, out0
        finally:
            if p0.poll() is None:
                p0.kill()
                p0.wait(timeout=20)

    try:
        _check(_wait_ready([survivor_port]),
               "serve-preempt: survivor READY")
        handoff_delta, out_h = run_leg("handoff", with_peers=True,
                                       prompts=_leg_prompts())
        h_in = _counter_total(survivor_port, "serve_handoff_in_total")
        _check(h_in >= 1,
               f"serve-preempt: survivor injected >=1 live-KV "
               f"snapshot (serve_handoff_in_total={h_in})", out_h)
        prompts = _leg_prompts()
        baseline_delta, _out_b = run_leg("baseline", with_peers=False,
                                         prompts=prompts)
        _check(handoff_delta < baseline_delta,
               f"serve-preempt: handoff leg recomputed strictly fewer "
               f"prefill tokens ({handoff_delta} < {baseline_delta})")

        # spill tier: the survivor's pool (12 blocks) cannot hold a
        # full request + the previous request's cached prefix, so each
        # admission evicts-and-spills the prior prefix; re-prompting
        # restores it from host RAM instead of re-prefilling
        sp_before = _counter_total(survivor_port, "serve_kv_spill_total")
        rs_before = _counter_total(survivor_port,
                                   "serve_kv_restore_total")
        for p in (prompts[0], prompts[1], prompts[2], prompts[0]):
            c = http.client.HTTPConnection("127.0.0.1", survivor_port,
                                           timeout=120)
            c.request("POST", "/v1/generate",
                      json.dumps({"prompt": p,
                                  "max_new_tokens": new_tokens,
                                  "temperature": 0.0}))
            r = c.getresponse()
            _check(r.status == 200,
                   f"serve-preempt/spill: request served "
                   f"({r.status})", r.read().decode())
            c.close()
        spills = _counter_total(survivor_port,
                                "serve_kv_spill_total") - sp_before
        restores = _counter_total(survivor_port,
                                  "serve_kv_restore_total") - rs_before
        _check(spills >= 1 and restores >= 1,
               f"serve-preempt/spill: spill+restore counters moved "
               f"(spills={spills} restores={restores})")

        # survivor never retraced through all of it
        _st, h = _get_json(survivor_port, "/healthz")
        _check(h["status"] == "serving"
               and h["compiled"]["n_traces"] == 1,
               "serve-preempt: survivor serving, decode traced once")
        BANK["serve-preempt"] = {
            "handoff_prefill_tokens": int(handoff_delta),
            "baseline_prefill_tokens": int(baseline_delta),
            "snapshot_injects": int(h_in),
            "spills": int(spills), "restores": int(restores),
        }
    finally:
        if surv.poll() is None:
            surv.terminate()
        try:
            surv.wait(timeout=20)
        except subprocess.TimeoutExpired:
            surv.kill()


def scenario_warm_restart(root, budget):
    """Cold-start elimination (``singa_tpu.aot``): kill a trainer and
    a serving replica, restart both against the populated AOT cache,
    and assert the warm restarts (a) reach the first step / first
    served token FASTER than the cold baseline, (b) log ZERO
    ``compile_seconds{source="fresh"}`` observations — every program
    deserialized from an artifact or served from the persistent cache
    — and (c) keep ``n_traces`` pinned at 1. Banked via
    ``--summary-json`` beside the other cold-start series."""
    import http.client
    import signal as _signal

    bank = BANK.setdefault("warm-restart", {})

    # ---- trainer half: cold run, SIGTERM mid-run, warm restart ------
    ck = os.path.join(root, "ck")
    aot_train = os.path.join(ck, "aot")
    cmd = _cmd(0, 1, _free_port(), ck,
               extra=["--aot-dir", aot_train], steps=6)
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    # let it compile + step a little, then preempt; a tiny run may
    # already have completed (exit 0) — either way the cache and
    # the aot/ sidecar are populated, which is what the warm half
    # needs
    time.sleep(8)
    p.send_signal(_signal.SIGTERM)
    out_cold = p.communicate(timeout=budget.remaining())[0]
    _check(p.returncode in (EXIT_PREEMPTED, 0),
           f"warm-restart: cold trainer exited cleanly "
           f"(got {p.returncode})", out_cold)
    s_cold = _run_summary(out_cold)
    _check(s_cold is not None and
           s_cold.get("aot", {}).get("train_step") in
           ("exported", "current"),
           "warm-restart: cold trainer exported its train step",
           out_cold)
    cold_first = s_cold["first_step_latency_s"]

    rcs, outs = _run([_cmd(0, 1, _free_port(), ck,
                           extra=["--aot-dir", aot_train], steps=10)],
                     budget)
    _check(rcs[0] == 0, "warm-restart: warm trainer completed",
           outs[0])
    s_warm = _run_summary(outs[0])
    _check(s_warm is not None and s_warm["start"] > 0,
           "warm-restart: trainer resumed from the checkpoint",
           outs[0])
    _check(s_warm.get("aot", {}).get("train_step") == "loaded",
           f"warm-restart: train step deserialized "
           f"({s_warm.get('aot')})", outs[0])
    srcs = s_warm.get("compile_sources") or {}
    _check(srcs.get("fresh", 0) == 0,
           f"warm-restart: zero fresh compiles on the warm trainer "
           f"({srcs})", outs[0])
    _check(s_warm.get("n_traces") == 1,
           f"warm-restart: warm trainer n_traces == 1 "
           f"({s_warm.get('n_traces')})", outs[0])
    warm_first = s_warm["first_step_latency_s"]
    _check(warm_first < cold_first,
           f"warm-restart: first step {warm_first:.3f}s beats the "
           f"cold {cold_first:.3f}s", outs[0])
    bank["train_cold_first_step_s"] = round(float(cold_first), 4)
    bank["train_warm_first_step_s"] = round(float(warm_first), 4)

    # ---- serving half: cold spin-up, kill, warm spin-up -------------
    serve = os.path.join(REPO, "examples", "serve_transformer.py")
    aot_serve = os.path.join(root, "aot-serve")
    scmd = lambda p: [sys.executable, serve, "--cpu",        # noqa: E731
                      "--port", str(p), "--slots", "2",
                      "--max-len", "48", "--prefill-len", "8",
                      "--vocab", "32", "--d-model", "16",
                      "--layers", "1", "--aot-dir", aot_serve]

    def first_token_latency(port):
        deadline = time.monotonic() + min(180, budget.remaining())
        ready = False
        while time.monotonic() < deadline and not ready:
            try:
                c = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=2)
                c.request("GET", "/healthz")
                ready = c.getresponse().status == 200
                c.close()
            except OSError:
                time.sleep(0.1)
        _check(ready, "warm-restart: gateway READY")
        t0 = time.monotonic()
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        c.request("POST", "/v1/generate",
                  json.dumps({"prompt": [1, 2, 3],
                              "max_new_tokens": 4}))
        r = c.getresponse()
        doc = json.loads(r.read().decode() or "{}")
        c.close()
        _check(r.status == 200 and len(doc.get("tokens", [])) == 4,
               "warm-restart: request served", repr(doc))
        return time.monotonic() - t0

    def healthz(port):
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        c.request("GET", "/healthz")
        doc = json.loads(c.getresponse().read())
        c.close()
        return doc

    def metrics_fresh_count(port):
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        c.request("GET", "/metrics.json")
        snap = json.loads(c.getresponse().read())
        c.close()
        n = 0
        for m in snap.get("metrics", []):
            if m.get("name") != "compile_seconds":
                continue
            for series in m.get("series", []):
                if series.get("labels", {}).get("source") == "fresh":
                    n += int(series.get("count", 0))
        return n

    port = _free_port()
    p = subprocess.Popen(scmd(port), stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    try:
        cold_tok = first_token_latency(port)
    finally:
        p.send_signal(_signal.SIGTERM)
    out0 = p.communicate(timeout=budget.remaining())[0]
    rc = p.returncode
    _check(rc == 0, f"warm-restart: cold replica drained 0 (got {rc})",
           out0)
    _check("AOT decode=exported prefill=exported" in out0,
           "warm-restart: cold replica exported its programs", out0)

    port = _free_port()
    p = subprocess.Popen(scmd(port), stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    try:
        warm_tok = first_token_latency(port)
        h = healthz(port)
        _check(h["compiled"]["aot"] ==
               {"serve_prefill": "loaded", "serve_decode": "loaded"},
               f"warm-restart: replica deserialized both programs "
               f"({h['compiled'].get('aot')})")
        _check(h["compiled"]["n_traces"] == 1,
               "warm-restart: warm replica decode n_traces == 1")
        fresh = metrics_fresh_count(port)
        _check(fresh == 0,
               f"warm-restart: zero fresh compiles on the warm "
               f"replica (got {fresh})")
        _check(warm_tok < cold_tok,
               f"warm-restart: first token {warm_tok:.3f}s beats the "
               f"cold {cold_tok:.3f}s")
    finally:
        p.send_signal(_signal.SIGTERM)
    # communicate (not bare wait): the drain logs share the stdout
    # pipe, and an undrained full pipe would block the child forever
    out1 = p.communicate(timeout=budget.remaining())[0]
    _check(p.returncode == 0,
           f"warm-restart: warm replica drained 0 "
           f"(got {p.returncode})", out1)
    bank["serve_cold_first_token_s"] = round(float(cold_tok), 4)
    bank["serve_warm_first_token_s"] = round(float(warm_tok), 4)


def scenario_serve_autoscale(root, budget):
    """SLO-driven warm autoscaler over real gateway subprocesses: an
    in-driver ``Autoscaler`` + ``FleetRouter`` supervise replicas that
    are each an ``examples/serve_transformer.py`` process spawned from
    prebuilt AOT artifacts. Four legs, one continuous request stream:

    (a) **warm scale-up** — a queue-depth burst breaches the SLO; the
        spawned replica passes the warm-admission gate with ZERO
        ``compile_seconds{source="fresh"}`` observations, and while the
        spawn is in flight :meth:`retry_after_hint` serves an observed
        (not constant) Retry-After;
    (b) **replacement** — a replica is SIGKILLed mid-stream; the
        supervisor respawns it and the router re-dispatches its
        stranded work — zero failed client responses;
    (c) **scale-down** — sustained calm retires the least-loaded
        replica through the drain path (exit 0, every in-flight
        request delivered);
    (d) **flap quarantine** — ``FaultPlan.flapping_replica`` dooms
        every respawn; after ``flap_threshold`` ready↔dead cycles the
        seat is quarantined and the respawn loop STOPS (the crash-loop
        money fire the damper exists for).

    Banks spawn-to-ready p50/p99 and the recovered-request count."""
    import http.client
    import signal as _signal
    import threading

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from singa_tpu import serving
    from singa_tpu.observability import metrics as obs_metrics
    from singa_tpu.resilience.faults import FaultPlan

    serve = os.path.join(REPO, "examples", "serve_transformer.py")
    aot_dir = os.path.join(root, "aot")
    geometry = ["--vocab", "32", "--d-model", "16", "--heads", "2",
                "--layers", "1", "--slots", "2", "--max-len", "48",
                "--prefill-len", "8"]
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "aot_cache.py"),
         "prebuild", "--aot-dir", aot_dir, "--cpu", "--spec", "lm"]
        + geometry,
        timeout=budget.remaining(), capture_output=True, text=True)
    _check(rc.returncode == 0, "serve-autoscale: AOT prebuild",
           rc.stdout + rc.stderr)

    class GwReplica:
        """Wire between the router/autoscaler and one gateway
        subprocess (the serve-crash idiom plus lifecycle verbs: the
        autoscaler drains, kills and autopsies through this)."""

        def __init__(self, name, port, proc):
            self.name = name
            self.port = port
            self.proc = proc
            self.draining = False
            self._lock = threading.Lock()
            self._outstanding = 0

        def queue_depth(self):
            with self._lock:
                return self._outstanding

        def _get_json(self, path, timeout=5):
            c = http.client.HTTPConnection("127.0.0.1", self.port,
                                           timeout=timeout)
            try:
                c.request("GET", path)
                return json.loads(c.getresponse().read() or b"{}")
            finally:
                c.close()

        def health(self):
            return self._get_json("/healthz")

        def fresh_compiles(self):
            n = 0
            for m in self._get_json("/metrics.json").get("metrics",
                                                         []):
                if m.get("name") != "compile_seconds":
                    continue
                for s in m.get("series", []):
                    if s.get("labels", {}).get("source") == "fresh":
                        n += int(s.get("count", 0))
            return n

        def submit(self, prompt, **kw):
            body = json.dumps(
                {"prompt": list(prompt),
                 **{k: kw[k] for k in ("max_new_tokens",
                                       "temperature", "timeout")
                    if kw.get(k) is not None}})
            conn = http.client.HTTPConnection(
                "127.0.0.1", self.port, timeout=120)
            try:
                conn.request("POST", "/v1/generate", body)
            except OSError as e:
                conn.close()
                raise ConnectionError(
                    f"{self.name}: submit wire error: {e}") from e
            fut = serving.ServeFuture()
            with self._lock:
                self._outstanding += 1

            def _read():
                try:
                    r = conn.getresponse()
                    doc = json.loads(r.read().decode() or "{}")
                    if r.status == 200:
                        fut.set_result(doc)
                    elif r.status == 503:
                        fut.set_error(serving.EngineDraining(
                            f"{self.name}: 503 {doc.get('error')}"))
                    else:
                        fut.set_error(serving.ServingError(
                            f"{self.name}: HTTP {r.status}: "
                            f"{doc.get('error')}"))
                except (OSError, http.client.HTTPException,
                        ValueError) as e:   # SIGKILL mid-response
                    fut.set_error(serving.ReplicaCrashed(
                        f"{self.name}: connection died "
                        f"mid-request: {e}"))
                finally:
                    conn.close()
                    with self._lock:
                        self._outstanding -= 1

            threading.Thread(target=_read, daemon=True).start()
            return fut

        def drain(self, timeout=60.0, handoff=None):
            """Scale-down retirement: the gateway's own drain finishes
            every admitted request before the process exits 0 (the
            router's handoff callable is for in-process engines; a
            subprocess drains itself)."""
            self.draining = True
            try:
                c = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=10)
                c.request("POST", "/drain", "{}")
                c.getresponse().read()
                c.close()
            except OSError:
                pass        # already dying: the wait below judges it
            try:
                code = self.proc.wait(timeout=timeout + 30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                return 1
            return serving.EXIT_DRAINED if code == 0 else 1

        def kill(self):
            if self.proc.poll() is None:
                self.proc.send_signal(_signal.SIGKILL)

        def destroy(self):
            self.kill()
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                pass

    spawned = []

    def spawn():
        port = _free_port()
        proc = subprocess.Popen(
            [sys.executable, serve, "--cpu", "--port", str(port),
             "--aot-dir", aot_dir] + geometry,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        r = GwReplica(f"g{len(spawned)}", port, proc)
        spawned.append(r)
        return r

    reg = obs_metrics.MetricsRegistry()
    errors, stop_trickle = [], threading.Event()

    def _await(cond, what, timeout=150.0):
        deadline = time.monotonic() + min(timeout, budget.remaining())
        while time.monotonic() < deadline:
            if cond():
                return
            time.sleep(0.1)
        raise AssertionError(f"serve-autoscale: timed out waiting "
                             f"for {what}")

    try:
        r0 = spawn()
        _await(lambda: r0.proc.poll() is None and _probe(r0),
               "base replica READY")
        rt = serving.FleetRouter([r0], registry=reg,
                                 breaker_threshold=2,
                                 breaker_backoff=0.5,
                                 max_redispatch=3)
        plan = FaultPlan()
        scaler = serving.Autoscaler(
            rt, spawn,
            targets=serving.AutoscaleTargets(
                min_replicas=1, max_replicas=2, queue_high=2.0,
                queue_low=1.0, up_window_s=0.6, down_window_s=1.5,
                up_cooldown_s=2.0, down_cooldown_s=2.0,
                replace_after_s=0.5, flap_threshold=3,
                flap_window_s=120.0, drain_deadline_s=60.0,
                spawn_timeout_s=120.0),
            registry=reg, interval=0.25, require_warm=True,
            fresh_compiles=lambda r: r.fresh_compiles(),
            destroy=lambda r: r.destroy(), probe_timeout=60.0,
            faults=plan)
        scaler.start()

        def trickle():
            # one request always in flight: scale-down retirement has
            # real in-flight work to deliver, and ANY dropped response
            # anywhere in the run is a scenario failure
            rng = np.random.RandomState(3)
            while not stop_trickle.is_set():
                p = rng.randint(1, 32, (4,)).tolist()
                try:
                    f = rt.submit(p, max_new_tokens=4,
                                  temperature=0.0, timeout=60.0)
                    doc = f.result(timeout=60.0)
                    if len(doc.get("tokens", [])) != 4:
                        errors.append(f"trickle: short {doc}")
                except serving.RequestShed:
                    time.sleep(0.2)     # the shed rung is working
                except Exception as e:  # noqa: BLE001
                    errors.append(f"trickle: {type(e).__name__}: {e}")

        tr = threading.Thread(target=trickle, daemon=True)
        tr.start()

        # ---- leg (a): sustained load -> breach -> warm scale-up -----
        # a one-shot burst drains before the hysteresis window
        # elapses (that is the POINT of hysteresis); breaching the SLO
        # takes load that STAYS: 8 closed-loop workers for ~12s keep
        # the per-replica queue depth pinned above queue_high
        burst, hints = [], []
        rng = np.random.RandomState(11)
        prompts = [rng.randint(1, 32, (8,)).tolist()
                   for _ in range(10)]
        load_until = time.monotonic() + 12.0

        def load_worker(w):
            while time.monotonic() < load_until:
                try:
                    f = rt.submit(prompts[w % len(prompts)],
                                  max_new_tokens=24,
                                  temperature=0.0, timeout=120.0)
                    doc = f.result(timeout=120.0)
                    if len(doc.get("tokens", [])) != 24:
                        errors.append(f"load {w}: short")
                except Exception as e:  # noqa: BLE001
                    errors.append(
                        f"load {w}: {type(e).__name__}: {e}")
                    return

        for w in range(8):
            t = threading.Thread(target=load_worker, args=(w,))
            t.start()
            burst.append(t)
        _await(lambda: (hints.append(scaler.retry_after_hint())
                        or rt.population() >= 2),
               "warm scale-up to 2 replicas")
        for t in burst:
            t.join(timeout=budget.remaining())
        _check(reg.get("autoscale_up_total").total() >= 1,
               "serve-autoscale: scale-up decision fired")
        _check(reg.get("autoscale_warm_refused_total").total() == 0
               and reg.get("autoscale_spawn_failed_total").total()
               == 0,
               "serve-autoscale: spawn admitted through the warm gate")
        fresh = {r.name: r.fresh_compiles()
                 for _i, r in rt.live_replicas()}
        _check(all(n == 0 for n in fresh.values()),
               f"serve-autoscale: zero fresh compiles fleet-wide "
               f"({fresh})")

        # ---- leg (b): SIGKILL -> replacement ------------------------
        victim = next(r for _i, r in rt.live_replicas())
        pop_before = rt.population()
        inflight = []

        def one_kill(i):
            try:
                f = rt.submit(prompts[i], max_new_tokens=24,
                              temperature=0.0, timeout=120.0)
                doc = f.result(timeout=120.0)
                if len(doc.get("tokens", [])) != 24:
                    errors.append(f"kill-leg {i}: short")
            except Exception as e:  # noqa: BLE001
                errors.append(f"kill-leg {i}: {type(e).__name__}: {e}")

        for i in range(6):
            t = threading.Thread(target=one_kill, args=(i,))
            t.start()
            inflight.append(t)
        _await(lambda: victim.queue_depth() >= 1,
               "victim holds in-flight work", timeout=30.0)
        victim.kill()
        _await(lambda: (hints.append(scaler.retry_after_hint())
                        or (reg.get("autoscale_replace_total").total()
                            >= 1 and rt.population() >= pop_before)),
               "replacement respawn")
        for t in inflight:
            t.join(timeout=budget.remaining())
        _check(any(h is not None and h >= 1.0 for h in hints),
               "serve-autoscale: retry_after_hint served an observed "
               "(>=1s) value while a spawn was in flight")

        # ---- leg (c): calm -> drain-based scale-down ----------------
        _await(lambda: (reg.get("autoscale_down_total").total() >= 1
                        and rt.population() == 1
                        and scaler.status()["retiring"] == 0),
               "calm scale-down to the 1-replica floor")
        stop_trickle.set()
        tr.join(timeout=60)
        _check(not errors,
               f"serve-autoscale: zero failed client responses "
               f"({len(errors)} failed)", repr(errors[:4]))
        recovered = int(
            reg.get("serve_fleet_redispatch_total").total())
        _check(recovered >= 1,
               f"serve-autoscale: stranded requests re-dispatched "
               f"({recovered} recovered)")

        # ---- leg (d): flap quarantine -------------------------------
        plan.flapping_replica(1, times=3)   # every respawn is doomed
        last = next(r for _i, r in rt.live_replicas())
        last.kill()
        _await(lambda: reg.get("autoscale_quarantine_total").total()
               >= 1, "flap quarantine")
        n_spawned = len(spawned)
        time.sleep(2.0)     # a quarantined seat must STAY parked
        _check(len(spawned) == n_spawned
               and scaler.status()["pending_spawns"] == 0,
               "serve-autoscale: quarantine stopped the respawn loop "
               f"(population {rt.population()})")
        _check(reg.get("autoscale_population").value() == 0,
               "serve-autoscale: population gauge tracks the "
               "quarantined fleet")
        hs = obs_metrics.heartbeat_summary(reg)["autoscale"]
        _check(hs["up"] >= 1 and hs["down"] >= 1
               and hs["replace"] >= 1 and hs["quarantine"] >= 1
               and hs["spawn_p50_s"] is not None,
               f"serve-autoscale: heartbeat_summary carries the "
               f"autoscale block {hs}")
        st = scaler.spawn_stats()
        BANK["serve-autoscale"] = {
            "spawn_to_ready_p50_s": round(st["p50_s"], 4),
            "spawn_to_ready_p99_s": round(st["p99_s"], 4),
            "spawns": int(st["count"]),
            "recovered_requests": recovered,
        }
        scaler.stop()
    finally:
        stop_trickle.set()
        for r in spawned:
            if r.proc.poll() is None:
                r.proc.kill()
        for r in spawned:
            try:
                r.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                pass


def _probe(r):
    try:
        return r.health().get("status") == "serving"
    except OSError:
        return False


def scenario_serve_disagg(root, budget):
    """Disaggregated prefill/decode pools across real gateway
    processes: one ``--pool-role prefill`` gateway fronts the clients
    and transfers every sealed KV snapshot to one of two
    ``--pool-role decode`` gateways, chosen by prefix affinity. Two
    legs, identical Poisson workload and fault schedule, differing
    ONLY in ``--no-affinity``:

    - **phase 1 (clean)** — K distinct prompts, each repeated, under
      Poisson arrivals: zero failed responses, every answer bitwise
      identical to an uninterrupted colocated greedy run, every
      continuation decoded by a pool peer (``serve_handoff_in_total``
      moves, the prefill side's decode stays home);
    - **phase 2 (faulted)** — ``--fault-corrupt-transfer`` flips a bit
      in one sealed frame (the receiving peer refuses it typed and
      the ladder's recompute rung serves it) while one decode peer is
      SIGKILLed holding injected work (dead-socket rung: the relay
      moves to the surviving peer). Still ZERO failed responses,
      still bitwise.

    Finally the affinity leg's phase-1 hit counter must sit STRICTLY
    above the no-affinity baseline's — the rendezvous hash is worth
    actual cache locality, not just plumbing. Banks hits, transfers,
    and retries."""
    import http.client
    import signal as _signal
    import threading

    serve = os.path.join(REPO, "examples", "serve_transformer.py")
    base = ["--cpu", "--slots", "2", "--max-len", "48",
            "--prefill-len", "8", "--vocab", "32", "--d-model", "16",
            "--layers", "1", "--kv-layout", "paged",
            "--kv-block-size", "4", "--kv-blocks", "24"]

    def _get_json(port, path, timeout=10):
        c = http.client.HTTPConnection("127.0.0.1", port,
                                       timeout=timeout)
        try:
            c.request("GET", path)
            r = c.getresponse()
            return r.status, json.loads(r.read().decode() or "{}")
        finally:
            c.close()

    def _counter_total(port, name):
        _st, doc = _get_json(port, "/metrics.json")
        for m in doc.get("metrics", []):
            if m.get("name") == name:
                return sum(s.get("value", 0)
                           for s in m.get("series", []))
        return 0

    def _wait_ready(ports_up):
        deadline = time.monotonic() + min(150, budget.remaining())
        up = set()
        while len(up) < len(ports_up) and time.monotonic() < deadline:
            for p in ports_up:
                if p in up:
                    continue
                try:
                    st, _ = _get_json(p, "/healthz", timeout=2)
                    if st == 200:
                        up.add(p)
                except OSError:
                    time.sleep(0.2)
        return len(up) == len(ports_up)

    def _gen(port, prompt, max_new, timeout=120):
        c = http.client.HTTPConnection("127.0.0.1", port,
                                       timeout=timeout)
        try:
            c.request("POST", "/v1/generate",
                      json.dumps({"prompt": prompt,
                                  "max_new_tokens": max_new,
                                  "temperature": 0.0,
                                  "timeout": float(timeout)}))
            r = c.getresponse()
            return r.status, json.loads(r.read().decode() or "{}")
        finally:
            c.close()

    rng = np.random.RandomState(23)
    # phase 1: 4 distinct block-aligned prompts x 4 repeats (the
    # affinity signal); phase 2: 8 distinct prompts with longer
    # decodes (in-flight work on the peer that dies)
    p1_prompts = [rng.randint(1, 32, (8,)).tolist() for _ in range(4)]
    p1_sched = [p1_prompts[i % 4] for i in range(16)]
    p2_prompts = [rng.randint(1, 32, (8,)).tolist() for _ in range(8)]
    P1_NEW, P2_NEW = 12, 24
    # phase 1 seals exactly one frame per request (16), so the 18th
    # seal is deterministically phase 2's second transfer
    corrupt_seq = len(p1_sched) + 2

    def _fire(port, sched, max_new, gaps):
        results = [None] * len(sched)

        def one(i):
            try:
                results[i] = _gen(port, sched[i], max_new)
            except OSError as e:
                results[i] = ("conn", str(e))

        threads = []
        for i in range(len(sched)):
            t = threading.Thread(target=one, args=(i,))
            t.start()
            threads.append(t)
            time.sleep(gaps[i])
        for t in threads:
            t.join(timeout=budget.remaining())
        return results

    def run_leg(name, affinity):
        dports = [_free_port(), _free_port()]
        pport = _free_port()
        procs = [subprocess.Popen(
            [sys.executable, serve, "--port", str(p), "--pool-role",
             "decode"] + base,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True) for p in dports]
        pf_extra = ["--pool-role", "prefill", "--decode-peers",
                    ",".join(str(p) for p in dports),
                    "--fault-corrupt-transfer", str(corrupt_seq)]
        if not affinity:
            pf_extra.append("--no-affinity")
        procs.append(subprocess.Popen(
            [sys.executable, serve, "--port", str(pport)] + base
            + pf_extra,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
        try:
            _check(_wait_ready(dports + [pport]),
                   f"serve-disagg/{name}: all three gateways READY")
            # ---- phase 1: clean Poisson load, 2 live decode peers --
            res1 = _fire(pport, p1_sched, P1_NEW,
                         rng.exponential(0.05, len(p1_sched)))
            bad = [(i, r) for i, r in enumerate(res1)
                   if not isinstance(r, tuple) or r[0] != 200
                   or len(r[1].get("tokens", [])) != P1_NEW]
            _check(not bad,
                   f"serve-disagg/{name}: phase 1 zero failed "
                   f"responses ({len(bad)} bad)", repr(bad[:3]))
            landed = sum(_counter_total(p, "serve_handoff_in_total")
                         for p in dports)
            _check(landed >= len(p1_sched),
                   f"serve-disagg/{name}: continuations decoded by "
                   f"the pool ({landed} injected)")
            hits1 = _counter_total(pport,
                                   "serve_pool_affinity_hit_total")
            # ---- phase 2: corrupt frame + SIGKILL a decode peer ----
            res2_box = {}
            ph2 = threading.Thread(
                target=lambda: res2_box.update(r=_fire(
                    pport, p2_prompts, P2_NEW,
                    rng.exponential(0.05, len(p2_prompts)))))
            ph2.start()
            victim = None
            kill_by = time.monotonic() + 20
            while victim is None and time.monotonic() < kill_by:
                for k, p in enumerate(dports):
                    try:
                        _st, h = _get_json(p, "/healthz", timeout=2)
                    except OSError:
                        continue
                    if (h.get("active_slots") or 0) >= 1:
                        victim = k
                        break
                time.sleep(0.01)
            _check(victim is not None,
                   f"serve-disagg/{name}: a decode peer holds "
                   f"injected work to kill")
            procs[victim].send_signal(_signal.SIGKILL)
            ph2.join(timeout=budget.remaining())
            procs[victim].wait(timeout=budget.remaining())
            res2 = res2_box.get("r") or []
            bad = [(i, r) for i, r in enumerate(res2)
                   if not isinstance(r, tuple) or r[0] != 200
                   or len(r[1].get("tokens", [])) != P2_NEW]
            _check(not bad,
                   f"serve-disagg/{name}: phase 2 zero failed "
                   f"responses through the fault ladder "
                   f"({len(bad)} bad)", repr(bad[:3]))
            retries = _counter_total(
                pport, "serve_pool_transfer_retry_total")
            _check(retries >= 1,
                   f"serve-disagg/{name}: the ladder retried "
                   f"(corrupt frame / dead peer, {retries} retries)")
            xfers = _counter_total(pport,
                                   "serve_pool_transfer_out_total")
            # ---- bitwise: every answer == an uninterrupted greedy
            # run on the surviving decode peer (same seed-0 weights)
            sport = dports[1 - victim]
            for sched, max_new, res in ((p1_sched, P1_NEW, res1),
                                        (p2_prompts, P2_NEW, res2)):
                for i, prompt in enumerate(sched):
                    st, ref = _gen(sport, prompt, max_new)
                    _check(st == 200,
                           f"serve-disagg/{name}: reference run "
                           f"served ({st})")
                    if res[i][1]["tokens"] != ref["tokens"]:
                        raise AssertionError(
                            f"serve-disagg/{name}: request {i} "
                            f"diverged from the colocated run: "
                            f"{res[i][1]['tokens']} != "
                            f"{ref['tokens']}")
            print(f"  ok: serve-disagg/{name}: all "
                  f"{len(res1) + len(res2)} responses bitwise "
                  f"identical to colocated greedy runs")
            # prefill-pool drain is still the clean exit path
            procs[-1].send_signal(_signal.SIGTERM)
            rc = procs[-1].wait(timeout=budget.remaining())
            _check(rc == 0,
                   f"serve-disagg/{name}: prefill gateway drained "
                   f"clean (exit {rc})")
            return hits1, xfers, retries
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            for p in procs:
                try:
                    p.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    pass

    hits_aff, xfers, retries = run_leg("affinity", affinity=True)
    hits_base, _x, _r = run_leg("baseline", affinity=False)
    _check(hits_aff > hits_base,
           f"serve-disagg: affinity hits strictly above the "
           f"no-affinity baseline ({hits_aff} > {hits_base})")
    BANK["serve-disagg"] = {
        "affinity_hits": int(hits_aff),
        "baseline_hits": int(hits_base),
        "transfers": int(xfers),
        "ladder_retries": int(retries),
    }


SCENARIOS = [("dead-rank-elastic", scenario_dead_rank_elastic),
             ("commit-hole", scenario_commit_hole),
             ("barrier-missing", scenario_barrier_missing),
             ("bitflip-restore", scenario_bitflip_restore),
             ("divergence-quarantine", scenario_divergence_quarantine),
             ("data-resume", scenario_data_resume),
             ("serve-drain", scenario_serve_drain),
             ("serve-crash", scenario_serve_crash),
             ("serve-preempt", scenario_serve_preempt),
             ("warm-restart", scenario_warm_restart),
             ("serve-autoscale", scenario_serve_autoscale),
             ("serve-disagg", scenario_serve_disagg)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=float, default=600.0,
                    help="hard wall-clock budget in seconds for the "
                         "WHOLE smoke")
    ap.add_argument("--keep-dirs", action="store_true")
    ap.add_argument("--only", default=None,
                    help="run a single scenario by name")
    ap.add_argument("--summary-json", default=None, metavar="PATH",
                    help="write the banked per-scenario measurements "
                         "(restart-to-first-step latencies) to PATH")
    args = ap.parse_args()

    budget = Budget(args.budget)
    root = tempfile.mkdtemp(prefix="chaos_smoke_")
    t0 = time.monotonic()
    failed = []
    try:
        for name, fn in SCENARIOS:
            if args.only and name != args.only:
                continue
            print(f"[chaos] {name} "
                  f"({budget.remaining():.0f}s budget left)")
            sdir = os.path.join(root, name)
            os.makedirs(sdir)
            # every child of the scenario keeps its compile cache here,
            # placed from outside (singa_tpu.aot.cache's rule): shared
            # between a run and its restart, and empty when the
            # scenario's "cold" start begins
            os.environ["JAX_COMPILATION_CACHE_DIR"] = \
                os.path.join(sdir, "xla-cache")
            try:
                fn(sdir, budget)
            except TimeoutError:
                raise
            except (AssertionError, Exception) as e:  # noqa: BLE001
                failed.append(name)
                print(f"  FAIL: {type(e).__name__}: {e}")
    except TimeoutError as e:
        print(f"[chaos] BUDGET EXCEEDED: {e}")
        failed.append("budget")
    finally:
        if not args.keep_dirs:
            shutil.rmtree(root, ignore_errors=True)
        else:
            print(f"[chaos] dirs kept under {root}")
    took = time.monotonic() - t0
    # the banked measurements (restart-to-first-step latency per
    # kill/restart scenario): the cold-start regression series
    if BANK:
        print(f"[chaos] measurements {json.dumps(BANK, sort_keys=True)}")
    if args.summary_json:
        with open(args.summary_json, "w") as f:
            json.dump({"took_s": round(took, 1), "failed": failed,
                       "scenarios": BANK}, f, indent=2, sort_keys=True)
        print(f"[chaos] measurements written to {args.summary_json}")
    if failed:
        print(f"[chaos] FAILED {failed} in {took:.0f}s")
        sys.exit(1)
    print(f"[chaos] all scenarios passed in {took:.0f}s "
          f"(budget {args.budget:.0f}s)")


if __name__ == "__main__":
    main()
