"""Cluster health: heartbeats, failing-fast barriers, two-phase commit.

The reference's multi-process story is fail-fast NCCL error prints
(include/singa/io/communicator.h:40-67): a dead or straggling host
silently hangs every collective, and a host that dies mid-save leaves a
checkpoint that only *looks* committed. This module is the control-plane
layer a pod-scale job needs on top of :mod:`singa_tpu.network`
(``NetworkThread``/``EndPoint`` — tensor traffic stays on XLA
collectives over ICI/DCN, never these sockets):

- **Membership**: every worker heartbeats the coordinator (rank 0); the
  coordinator tracks last-seen ages, flags *stragglers* (heartbeat gap
  over ``straggler_after``) and declares a rank *dead* after
  ``dead_after`` of silence. The digest rides back on every heartbeat
  ack, so workers learn of lost peers (and of a dead coordinator, by
  the ack going silent) without extra traffic. :meth:`ClusterBase.check`
  raises :class:`MembershipError` — a *recoverable* loss: the
  supervisor contract is exit :data:`~singa_tpu.resilience.runtime.
  EXIT_PREEMPTED` (75) and a restart at the smaller world size.
- **Barriers**: :meth:`ClusterBase.barrier` never hangs — at the
  timeout (or as soon as a participant is declared dead) it raises
  :class:`BarrierTimeout` *naming the missing ranks*.
- **Two-phase commit** (for distributed checkpoints,
  ``singa_tpu/checkpoint.py``): every rank writes its shard then
  :meth:`ClusterBase.ack_save`; the coordinator publishes the commit
  marker (the registered ``commit_hook``) only once ALL ranks acked,
  then broadcasts the decision; :meth:`ClusterBase.wait_commit` returns
  whether the step committed. A rank that dies between shard-write and
  ACK therefore leaves a step with NO marker — wreckage that
  ``restore_latest`` refuses.

Usage (one process per rank)::

    cluster = make_cluster(rank, world, "host0:19123")
    cluster.barrier("start", timeout=30)     # rendezvous, names absentees
    ...
    cluster.ack_save(step); cluster.wait_commit(step, timeout=30)
    cluster.check()                          # raises on membership loss
    cluster.close()

``world == 1`` returns a :class:`SoloCluster` that needs no sockets, so
elastic restarts down to a single host run the identical code path.
"""

from __future__ import annotations

import json
import os
import threading
import time
import warnings
from dataclasses import dataclass

from .. import network as net
from ..integrity import (MAX_MESSAGE_BYTES, IntegrityError, open_frame,
                         seal_frame)
from ..observability import metrics as _metrics
from .faults import NULL_PLAN, DropPeerSignal as _DropPeerSignal

# control-plane protocol version, negotiated in the hello handshake: a
# peer speaking a different framing/message dialect is REJECTED at join
# (named, loudly) instead of being mis-parsed for the whole run. Bump on
# any incompatible change to the message set or frame format.
PROTO_VERSION = 1


class ClusterError(RuntimeError):
    """Base class for cluster-health failures."""


class MembershipError(ClusterError):
    """A peer (or the coordinator) was lost — RECOVERABLE by a restart
    at the smaller world size: the supervisor contract is exit code 75
    (``resilience.EXIT_PREEMPTED``)."""

    def __init__(self, dead, world):
        self.dead = sorted(int(r) for r in dead)
        self.world = int(world)
        super().__init__(
            f"cluster membership lost: rank(s) {self.dead} of world "
            f"{self.world} are dead; restart at world "
            f"{self.world - len(self.dead)} to continue")


class BarrierTimeout(ClusterError):
    """A barrier did not complete — names who is missing instead of
    hanging the collective."""

    def __init__(self, name, missing, timeout):
        self.name = name
        self.missing = sorted(int(r) for r in missing)
        super().__init__(
            f"barrier {name!r} timed out after {timeout:.1f}s waiting "
            f"for rank(s) {self.missing}")


@dataclass
class ClusterConfig:
    """Timing knobs. Defaults suit tests/local chaos runs; production
    pods want heartbeat_interval ~1s and dead_after ~30s."""

    heartbeat_interval: float = 0.25   # worker -> coordinator beat period
    straggler_after: float = 0.75      # silence before a rank is "slow"
    dead_after: float = 2.5            # silence before a rank is dead
    connect_timeout: float = 15.0      # worker's coordinator-dial budget
    recv_slice: float = 0.25           # receiver-loop poll granularity
    stale_beats: float = 3.0           # heartbeats of silence before a
    #   rank's last-carried metric summary is STALE (dead data): the
    #   aggregate fleet view excludes it and surfaces the age instead
    #   of reporting frozen gauges as current

    @property
    def stale_after(self) -> float:
        """Seconds of silence before a rank's summary is stale
        (``stale_beats`` × ``heartbeat_interval``)."""
        return self.stale_beats * self.heartbeat_interval


def _addr(coordinator: str):
    host, port = coordinator.rsplit(":", 1)
    return host, int(port)


def _msg(kind: str, **payload) -> net.Message:
    """A SEALED control-plane message: the JSON payload rides behind the
    integrity frame header (magic + protocol version + CRCs), so a
    corrupted frame is detected before any parsing."""
    meta = kind.encode()
    raw = json.dumps(payload).encode()
    return net.Message(meta, seal_frame(meta, raw))


def _payload(msg: net.Message) -> dict:
    """Verify + parse a sealed message; raises
    :class:`~singa_tpu.integrity.IntegrityError` on a corrupt frame
    (receive loops drop-and-count those — see ``_open``)."""
    return json.loads(open_frame(msg.meta, msg.payload).decode() or "{}")


# decided commit steps kept in memory per rank — coordinator and worker
# MUST share this window: a worker pruning earlier than the coordinator
# could drop the Event for a step whose decision is still coming
COMMIT_WINDOW = 16


def _prune_window(decided, *others):
    """Bound per-step/per-round bookkeeping to the newest COMMIT_WINDOW
    decided keys — older entries can never be waited on again. One
    helper for the commit AND fingerprint slots on both coordinator and
    worker, so the four windows can never drift apart. ``others`` may
    be dicts or sets keyed like ``decided``."""
    for old in sorted(decided)[:-COMMIT_WINDOW]:
        decided.pop(old, None)
        for m in others:
            if isinstance(m, set):
                m.discard(old)
            else:
                m.pop(old, None)


class ClusterBase:
    """API shared by coordinator, worker, and the solo degenerate."""

    rank: int = 0
    world: int = 1
    _wire_seq = 0          # sent-frame counter (fault-injection keying)
    _wire_errors = 0       # corrupt frames dropped by this member
    _WIRE_WARN_LIMIT = 5   # warn the first few, count the rest silently
    # zero-arg callable returning this member's heartbeat metric
    # summary; None uses the process metrics registry
    # (observability.metrics.heartbeat_summary). Injectable so
    # in-process multi-rank tests give each member its own numbers.
    metrics_source = None

    def _metrics_summary(self):
        """This member's compact metric summary (rides heartbeats; the
        coordinator aggregates into one fleet view). Never raises —
        telemetry must not take the control plane down."""
        try:
            src = self.metrics_source
            return src() if callable(src) \
                else _metrics.heartbeat_summary()
        except Exception:       # noqa: BLE001 — best-effort by design
            return None

    # -- wire integrity ----------------------------------------------------
    def _send(self, ep, kind, **payload):
        """Seal and send one control-plane message. The fault hook runs
        on the SEALED bytes, so an injected bit-flip is exactly what a
        corrupted TCP frame looks like to the receiver's CRC."""
        msg = _msg(kind, **payload)
        self._wire_seq += 1
        msg.payload = self.faults.on_wire_send(self._wire_seq,
                                               msg.payload)
        ep.send(msg)

    def _open(self, msg):
        """Unseal + parse an inbound message; a frame failing any
        integrity check is dropped and counted (returns None) — the
        periodic/timeout nature of every protocol (heartbeats re-send,
        barriers and commits time out loudly) covers the loss, and
        garbage NEVER reaches protocol parsing."""
        try:
            return _payload(msg)
        except (IntegrityError, ValueError, UnicodeDecodeError) as e:
            self._note_wire_error(e)
            return None

    def _note_wire_error(self, exc):
        self._wire_errors += 1
        _metrics.default_registry().counter(
            "cluster_wire_errors_total",
            "corrupt control-plane frames dropped by this process").inc()
        if self._wire_errors <= self._WIRE_WARN_LIMIT:
            warnings.warn(
                f"cluster rank {self.rank}: dropped corrupt "
                f"control-plane frame #{self._wire_errors} "
                f"({exc}); protocol timeouts/retries absorb the loss",
                stacklevel=2)

    @property
    def wire_errors(self) -> int:
        """Corrupt control-plane frames this member has dropped."""
        return self._wire_errors

    # -- health ------------------------------------------------------------
    def health(self) -> dict:
        raise NotImplementedError

    def check(self):
        """Raise :class:`MembershipError` when membership was lost."""
        dead = self.health().get("dead", [])
        if dead:
            raise MembershipError(dead, self.world)

    # -- barrier -----------------------------------------------------------
    def barrier(self, name: str, timeout: float = 30.0):
        raise NotImplementedError

    # -- two-phase commit ---------------------------------------------------
    def set_commit_hook(self, hook):
        """Coordinator-side: ``hook(step) -> None`` runs exactly once per
        step, after every rank acked and before the commit broadcast —
        the checkpoint layer's marker write."""
        self._commit_hook = hook

    def ack_save(self, step: int, digest=None, data_digest=None):
        """ACK a durably-written shard. ``digest`` (optional) is the
        shard's manifest content digest: the coordinator compares the
        digests of ALL ranks before publishing — replicas that disagree
        mean divergence, and the step stays uncommitted rather than
        vouching for forked state. ``data_digest`` (optional) is the
        rank's data-iterator state digest; it is RECORDED per rank in
        the commit marker (not agreement-checked — the offsets are
        lockstep by construction, but the marker must vouch for
        whatever each rank wrote) so any restore can cross-check the
        data sidecar it lands on."""
        raise NotImplementedError

    def ack_data_digests(self, step: int) -> dict:
        """{rank: data-state digest} gathered from step N's ACKs (the
        commit hook records them in the marker). Empty off-coordinator
        and for steps outside the bounded commit window."""
        return {}

    def wait_commit(self, step: int, timeout: float = 30.0) -> bool:
        raise NotImplementedError

    # -- cross-replica fingerprint agreement --------------------------------
    def fingerprint_agree(self, seq: int, fp: str,
                          timeout: float = 30.0):
        """Exchange this rank's state fingerprint and wait for the
        cluster verdict. ``seq`` is a monotonically increasing check id
        identical across ranks — NOT the step number: a step re-run
        after a quarantine rollback must open a FRESH agreement round,
        never reuse the stale verdict of its first run. Returns
        ``(ok, divergent_ranks)`` — ``ok`` False when the fingerprints
        disagree (``divergent_ranks`` names the minority; attribution
        is majority-vote, so a 1-vs-1 tie names one side arbitrarily).
        A verdict that does not arrive within ``timeout`` returns
        ``(True, [])`` with a warning: a control-plane hiccup must not
        roll back healthy training, and a dead coordinator is caught by
        the membership check."""
        raise NotImplementedError

    def close(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class SoloCluster(ClusterBase):
    """World of one: every protocol completes instantly, no sockets —
    the elastic end state (a job restarted down to a single host) runs
    the same code path as the pod it shrank from."""

    def __init__(self, rank: int = 0, faults=None):
        self.rank = int(rank)
        self.world = 1
        self.faults = faults if faults is not None else NULL_PLAN
        self._commit_hook = None
        self._ack_data: dict = {}

    def health(self):
        return {"rank": self.rank, "world": 1, "alive": [self.rank],
                "dead": [], "stragglers": [], "heartbeat_age": {},
                "wire_errors": 0}

    def barrier(self, name, timeout=30.0):
        return

    def ack_save(self, step, digest=None, data_digest=None):
        self.faults.on_ack(int(step))
        # recorded BEFORE the hook runs: the commit marker reads it
        self._ack_data = {int(step): {0: data_digest}}
        if self._commit_hook is not None:
            self._commit_hook(int(step))

    def ack_data_digests(self, step):
        return dict(self._ack_data.get(int(step), {}))

    def wait_commit(self, step, timeout=30.0):
        return True

    def fingerprint_agree(self, seq, fp, timeout=30.0):
        # a world of one has no peer to disagree with; cross-DEVICE
        # divergence is covered by integrity.replica_buffer_mismatches
        return True, []


class Coordinator(ClusterBase):
    """Rank 0: owns the listener, the membership table, barrier and
    commit bookkeeping. Also a full participant (its own arrivals and
    ACKs count like any rank's)."""

    def __init__(self, world: int, coordinator: str,
                 config: ClusterConfig | None = None, faults=None):
        self.rank = 0
        self.world = int(world)
        self.cfg = config or ClusterConfig()
        self.faults = faults if faults is not None else NULL_PLAN
        host, port = _addr(coordinator)
        self._net = net.NetworkThread(port=port)
        self._lock = threading.Lock()
        self._running = True
        self._commit_hook = None
        self._peers: dict[int, net.EndPoint] = {}
        self._last_hb: dict[int, float] = {}
        self._hb_count: dict[int, int] = {}
        self._worker_metrics: dict[int, dict] = {}  # rank -> hb summary
        self._dead: set[int] = set()
        self._stragglers: set[int] = set()
        # barrier name -> {"arrived": set, "event": Event,
        #                  "missing": list|None}
        self._barriers: dict[str, dict] = {}
        # failed-barrier memory (bounded): a rank arriving AFTER the
        # failure gets told immediately instead of burning its own
        # timeout against a ghost slot that can never complete
        self._failed_barriers: dict[str, list] = {}
        self._acks: dict[int, set] = {}
        self._ack_digests: dict[int, dict] = {}  # step -> {rank: digest}
        self._ack_data: dict[int, dict] = {}     # step -> {rank: data dg}
        self._commit_done: dict[int, threading.Event] = {}
        self._commit_ok: dict[int, bool] = {}
        self._commit_claimed: set[int] = set()   # publish/abort decided
        # cross-replica fingerprint agreement (same bounded window)
        self._fp: dict[int, dict] = {}           # seq -> {rank: fp}
        self._fp_done: dict[int, threading.Event] = {}
        self._fp_result: dict[int, tuple] = {}   # seq -> (ok, divergent)
        self._fp_claimed: set[int] = set()       # verdict decided
        self._threads = []
        t = threading.Thread(target=self._accept_loop, daemon=True,
                             name="cluster-accept")
        t.start()
        self._threads.append(t)
        t = threading.Thread(target=self._monitor_loop, daemon=True,
                             name="cluster-monitor")
        t.start()
        self._threads.append(t)

    # -- wiring ------------------------------------------------------------
    def _accept_loop(self):
        while self._running:
            try:
                ep = self._net.accept(timeout=self.cfg.recv_slice)
            except ConnectionError:
                return                     # net closed
            if ep is None:
                continue
            # the hello handshake runs on the PEER's thread: one stalled
            # dialer (or a stray connection to the advertised port) must
            # not serialize every other rank's join behind its timeout.
            # Daemon + untracked: a long-lived coordinator accepting
            # dial-and-die churn must not accumulate dead Thread objects
            threading.Thread(target=self._join_then_serve, args=(ep,),
                             daemon=True, name="cluster-join").start()

    def _join_then_serve(self, ep):
        """The versioned hello handshake: verify the sealed hello and
        its protocol version, answer ``hello-ack`` (or ``hello-reject``
        naming both versions), THEN register the peer. A peer speaking
        an incompatible dialect is turned away at the door instead of
        being mis-parsed for the whole run."""
        try:
            hello = ep.recv(timeout=5.0, max_bytes=MAX_MESSAGE_BYTES)
        except (ConnectionError, IntegrityError):
            ep.close()       # dialer died mid-handshake: free the slot
            return
        if hello is None or hello.meta != b"hello":
            ep.close()
            return
        try:
            data = _payload(hello)
        except (IntegrityError, ValueError, UnicodeDecodeError) as e:
            # unsealed (pre-integrity peer) or corrupted hello
            self._note_wire_error(e)
            self._reject(ep, f"unreadable hello ({e})")
            return
        proto = int(data.get("proto", 0))
        if proto != PROTO_VERSION:
            warnings.warn(
                f"cluster: rejecting join from rank "
                f"{data.get('rank', '?')}: protocol version {proto} "
                f"(this coordinator speaks {PROTO_VERSION})",
                stacklevel=2)
            self._reject(ep, f"protocol version {proto} unsupported")
            return
        rank = int(data["rank"])
        try:
            self._send(ep, "hello-ack", proto=PROTO_VERSION,
                       world=self.world)
        except ConnectionError:
            ep.close()
            return
        with self._lock:
            self._peers[rank] = ep
            self._last_hb[rank] = time.monotonic()
            self._dead.discard(rank)
        self._peer_loop(rank, ep)

    def _reject(self, ep, reason):
        try:
            self._send(ep, "hello-reject", proto=PROTO_VERSION,
                       reason=reason)
            ep.drain(timeout=1.0)    # let the verdict reach the dialer
        except ConnectionError:
            pass
        ep.close()

    def _peer_loop(self, rank, ep):
        while self._running:
            try:
                msg = ep.recv(timeout=self.cfg.recv_slice,
                              max_bytes=MAX_MESSAGE_BYTES)
            except ConnectionError:
                return          # monitor will declare it dead by silence
            except IntegrityError as e:
                # oversized-frame guard: the frame was consumed by the
                # network layer — drop, count, keep serving the peer
                self._note_wire_error(e)
                continue
            if msg is None:
                continue
            data = self._open(msg)
            if data is None:
                continue        # corrupt frame: dropped and counted
            kind = msg.meta.decode()
            if kind == "hb":
                with self._lock:
                    self._last_hb[rank] = time.monotonic()
                    self._hb_count[rank] = self._hb_count.get(rank, 0) + 1
                    m = data.get("metrics")
                    if isinstance(m, dict):
                        # per-rank metric summary riding the beat: the
                        # digest below publishes the aggregated view
                        self._worker_metrics[rank] = m
                try:
                    self._send(ep, "hb-ack", **self._digest())
                except ConnectionError:
                    return
            elif kind == "barrier":
                self._barrier_arrive(data["name"], rank)
            elif kind == "ack":
                self._ack_arrive(int(data["step"]), rank,
                                 data.get("digest"),
                                 data.get("data_digest"))
            elif kind == "fp":
                self._fp_arrive(int(data["seq"]), rank, data.get("fp"))

    def _monitor_loop(self):
        while self._running:
            time.sleep(self.cfg.heartbeat_interval / 2)
            now = time.monotonic()
            newly_dead = []
            with self._lock:
                for rank, seen in self._last_hb.items():
                    age = now - seen
                    if age > self.cfg.dead_after and rank not in self._dead:
                        self._dead.add(rank)
                        newly_dead.append(rank)
                    if age > self.cfg.straggler_after:
                        self._stragglers.add(rank)
                    else:
                        self._stragglers.discard(rank)
            for rank in newly_dead:
                warnings.warn(
                    f"cluster: rank {rank} declared dead "
                    f"(no heartbeat for {self.cfg.dead_after:.1f}s)",
                    stacklevel=2)
                # a barrier waiting on a dead rank can never complete:
                # fail it NOW, naming the corpse, instead of hanging out
                # the caller's full timeout
                self._fail_barriers_missing(rank)

    def _digest(self) -> dict:
        # the coordinator is a full participant: its own summary joins
        # the fleet view (computed outside the lock — it only reads the
        # process metrics registry)
        own = self._metrics_summary()
        with self._lock:
            now = time.monotonic()
            expected = set(range(1, self.world))
            connected = set(self._last_hb)
            ages = {str(r): round(now - t, 3)
                    for r, t in self._last_hb.items()}
            summaries = dict(self._worker_metrics)
            stragglers = sorted(self._stragglers - self._dead)
            dead = sorted(self._dead)
            hb_counts = {str(r): c for r, c in self._hb_count.items()}
            wire_errors = self._wire_errors
        if own is not None:
            summaries[0] = own
        reg = _metrics.default_registry()
        reg.gauge("cluster_stragglers",
                  "ranks whose heartbeat is overdue").set(len(stragglers))
        reg.gauge("cluster_dead_ranks",
                  "ranks declared dead by silence").set(len(dead))
        return {
            "world": self.world,
            "alive": sorted({0} | (connected - set(dead))),
            "dead": dead,
            "never_joined": sorted(expected - connected),
            "stragglers": stragglers,
            "heartbeat_age": ages,
            "heartbeats": hb_counts,
            "wire_errors": wire_errors,
            # ONE fleet-wide metric view (min/max/mean step time, total
            # steps and wire errors), aggregated from the summaries each
            # rank attached to its heartbeats — small enough to ride
            # back on every hb-ack, so workers see it too. Ranks whose
            # last beat is older than cfg.stale_after carry DEAD data:
            # excluded from the aggregates, surfaced as {rank: age}
            "worker_metrics": dict(
                _metrics.aggregate_summaries(
                    summaries, ages=ages,
                    stale_after=self.cfg.stale_after),
                stragglers=len(stragglers)),
        }

    # -- health ------------------------------------------------------------
    def health(self):
        d = self._digest()
        d["rank"] = 0
        with self._lock:
            # the full per-rank breakdown only in the local health
            # report (the broadcast digest carries the aggregate);
            # each entry carries its staleness verdict so a reader
            # can tell a live gauge from a dead rank's last words
            by_rank = {str(r): dict(m)
                       for r, m in self._worker_metrics.items()}
        for r, m in by_rank.items():
            age = d.get("heartbeat_age", {}).get(r)
            m["hb_age_s"] = age
            m["stale"] = bool(age is not None
                              and age > self.cfg.stale_after)
        d["worker_metrics_by_rank"] = by_rank
        return d

    # -- barrier -----------------------------------------------------------
    def _barrier_slot(self, name):
        with self._lock:
            slot = self._barriers.get(name)
            if slot is None:
                slot = {"arrived": set(), "event": threading.Event(),
                        "missing": None}
                self._barriers[name] = slot
            return slot

    def _fail_barrier(self, name, slot, missing):
        """Record + announce a barrier failure: remember it (bounded)
        so late arrivals are told immediately, drop the live slot, wake
        local waiters, tell the arrived workers."""
        with self._lock:
            slot["missing"] = missing
            self._failed_barriers[name] = missing
            while len(self._failed_barriers) > 32:
                self._failed_barriers.pop(
                    next(iter(self._failed_barriers)))
            self._barriers.pop(name, None)
        self._broadcast("barrier-fail", ranks=slot["arrived"],
                        name=name, missing=missing)
        slot["event"].set()

    def _barrier_arrive(self, name, rank):
        with self._lock:
            failed = self._failed_barriers.get(name)
            ep = self._peers.get(rank)
        if failed is not None:
            # straggler arriving at an already-failed barrier: answer
            # NOW with the true missing set, instead of leaving it to
            # time out again and falsely blame the coordinator
            if rank != 0 and ep is not None:
                try:
                    self._send(ep, "barrier-fail", name=name,
                               missing=failed)
                except ConnectionError:
                    pass
            return
        slot = self._barrier_slot(name)
        with self._lock:
            slot["arrived"].add(rank)
            complete = len(slot["arrived"]) == self.world
            # a participant that is ALREADY dead will never arrive:
            # fail now, naming the corpse — live ranks merely being
            # slow still get the full timeout
            dead_missing = sorted(self._dead - slot["arrived"])
        if complete:
            self._broadcast("barrier-ok", ranks=slot["arrived"],
                            name=name)
            slot["event"].set()
        elif dead_missing:
            self._fail_barrier(name, slot, dead_missing)

    def _fail_barriers_missing(self, dead_rank):
        with self._lock:
            pending = [(n, s) for n, s in self._barriers.items()
                       if not s["event"].is_set()
                       and dead_rank not in s["arrived"]]
            missing = {n: sorted(self._dead - s["arrived"])
                       for n, s in pending}
        for name, slot in pending:
            self._fail_barrier(name, slot, missing[name])

    def _broadcast(self, kind, ranks=None, **payload):
        with self._lock:
            eps = [(r, ep) for r, ep in self._peers.items()
                   if (ranks is None or r in ranks) and r not in self._dead]
        for _r, ep in eps:
            try:
                self._send(ep, kind, **payload)
            except ConnectionError:
                pass

    def barrier(self, name, timeout=30.0):
        with self._lock:
            failed = self._failed_barriers.get(name)
        if failed is not None:
            raise BarrierTimeout(name, failed, 0.0)
        slot = self._barrier_slot(name)
        self._barrier_arrive(name, 0)
        if not slot["event"].wait(timeout):
            with self._lock:
                missing = sorted(
                    set(range(self.world)) - slot["arrived"])
            self._fail_barrier(name, slot, missing)
        with self._lock:
            self._barriers.pop(name, None)
            missing = slot["missing"]
        if missing:
            raise BarrierTimeout(name, missing, timeout)

    # -- two-phase commit ---------------------------------------------------
    def _commit_slot(self, step):
        with self._lock:
            ev = self._commit_done.get(step)
            if ev is None:
                ev = threading.Event()
                self._commit_done[step] = ev
                self._acks.setdefault(step, set())
            return ev

    def _ack_arrive(self, step, rank, digest=None, data_digest=None):
        ev = self._commit_slot(step)
        with self._lock:
            self._acks[step].add(rank)
            if digest is not None:
                self._ack_digests.setdefault(step, {})[rank] = digest
            if data_digest is not None:
                self._ack_data.setdefault(step, {})[rank] = data_digest
            complete = len(self._acks[step]) == self.world
            # claim the publish under the lock: a quorum completing
            # AFTER wait_commit's timeout aborted the step must not
            # publish a marker every save() caller was told to distrust
            claim = complete and step not in self._commit_claimed
            if claim:
                self._commit_claimed.add(step)
            digests = dict(self._ack_digests.get(step, {}))
        if claim:
            # full replicas must be bit-identical: ACK digests that
            # disagree mean a replica diverged, and a commit marker must
            # never vouch for forked state — the step stays uncommitted
            # (every checkpoint that DOES commit is therefore
            # cross-replica-agreed, which is what makes "roll back to
            # the last committed step" a divergence recovery)
            ok = len({d for d in digests.values()}) <= 1
            if not ok:
                groups: dict = {}
                for r, d in digests.items():
                    groups.setdefault(d, []).append(r)
                warnings.warn(
                    f"checkpoint step {step}: shard content digests "
                    f"disagree across ranks ({groups}) — replicas have "
                    "diverged; the step stays uncommitted", stacklevel=2)
            # publish the marker (the checkpoint layer's atomic write)
            # BEFORE telling anyone the step committed
            if ok and self._commit_hook is not None:
                try:
                    self._commit_hook(step)
                except Exception as e:      # marker write failed: abort
                    warnings.warn(f"commit hook for step {step} failed "
                                  f"({type(e).__name__}: {e}); step "
                                  "stays uncommitted", stacklevel=2)
                    ok = False
            with self._lock:
                self._commit_ok[step] = ok
                _prune_window(self._commit_ok, self._acks,
                              self._ack_digests, self._ack_data,
                              self._commit_done, self._commit_claimed)
            # peers hear the decision BEFORE the coordinator's own
            # waiter is released: released first, a coordinator at its
            # last step can finish and close the cluster while this
            # (receive) thread has yet to send, and its peers then wait
            # out the commit timeout on a step that committed
            self._broadcast("commit", step=step, ok=ok)
            ev.set()

    def ack_save(self, step, digest=None, data_digest=None):
        self.faults.on_ack(int(step))
        self._ack_arrive(int(step), 0, digest, data_digest)

    def ack_data_digests(self, step):
        with self._lock:
            return dict(self._ack_data.get(int(step), {}))

    def wait_commit(self, step, timeout=30.0):
        step = int(step)
        ev = self._commit_slot(step)
        if not ev.wait(timeout):
            with self._lock:
                aborted = step not in self._commit_claimed
                if aborted:
                    # no publish in flight: ABORT, so a straggler's late
                    # ACK cannot commit a step save() already reported
                    # uncommitted
                    self._commit_claimed.add(step)
                    self._commit_ok[step] = False
            if aborted:
                self._broadcast("commit", step=step, ok=False)
                ev.set()
            else:
                ev.wait(5.0)     # publish decision in flight; let it land
        with self._lock:
            return bool(self._commit_ok.get(step))

    # -- cross-replica fingerprint agreement --------------------------------
    def _fp_slot(self, seq):
        with self._lock:
            ev = self._fp_done.get(seq)
            if ev is None:
                ev = threading.Event()
                self._fp_done[seq] = ev
                self._fp.setdefault(seq, {})
            return ev

    def _fp_arrive(self, seq, rank, fp):
        ev = self._fp_slot(seq)
        with self._lock:
            self._fp[seq][rank] = fp
            complete = len(self._fp[seq]) == self.world
            # claim the verdict under the lock: a straggler's fp
            # landing AFTER fingerprint_agree's timeout already
            # recorded "agreed" must not broadcast a contradicting
            # late verdict (workers quarantining while rank 0 trains
            # on would strand the lockstep barriers) — same rule as
            # _commit_claimed on the commit path
            if not complete or seq in self._fp_claimed:
                return
            self._fp_claimed.add(seq)
            values = list(self._fp[seq].values())
            # deterministic tie-break (count, then the fp string): a
            # 1-vs-1 tie cannot attribute blame either way, but the
            # verdict must not depend on set-iteration hash order
            majority = max(sorted(set(values)), key=values.count)
            divergent = sorted(r for r, v in self._fp[seq].items()
                               if v != majority)
            ok = not divergent
            self._fp_result[seq] = (ok, divergent)
            _prune_window(self._fp_result, self._fp, self._fp_done,
                          self._fp_claimed)
        if not ok:
            warnings.warn(
                "cross-replica fingerprint DISAGREEMENT (check round "
                f"{seq}): rank(s) {divergent} hold a minority state "
                "(silent divergence — quarantine and roll back)",
                stacklevel=2)
        self._broadcast("fp-result", seq=seq, ok=ok,
                        divergent=divergent)
        ev.set()

    def fingerprint_agree(self, seq, fp, timeout=30.0):
        seq = int(seq)
        ev = self._fp_slot(seq)
        self._fp_arrive(seq, 0, fp)
        if not ev.wait(timeout):
            warnings.warn(
                f"fingerprint agreement round {seq} timed out after "
                f"{timeout:.0f}s (a rank stalled?); treating as agreed —"
                " membership checks cover a dead peer", stacklevel=2)
            with self._lock:
                # claim + record the non-verdict: the round is DECIDED
                # as "agreed" — a straggler's late fp can no longer
                # complete it into a contradicting broadcast, and the
                # window pruning reaches the slot (a lost 'fp' frame
                # must not leak its Event forever) — same rules as
                # wait_commit's timeout abort. If the round completed
                # and BROADCAST in the race window between our wait
                # expiring and this lock, that verdict was already
                # sent to every worker: return IT (not the literal
                # "agreed"), or rank 0 would train on while its
                # workers quarantine and the lockstep barriers strand
                if seq not in self._fp_claimed:
                    self._fp_claimed.add(seq)
                    self._fp_result[seq] = (True, [])
                result = self._fp_result.get(seq, (True, []))
                _prune_window(self._fp_result, self._fp, self._fp_done,
                              self._fp_claimed)
            return result
        with self._lock:
            return self._fp_result.get(seq, (True, []))

    # -- teardown ----------------------------------------------------------
    def close(self):
        self._running = False
        self._net.close()


class Worker(ClusterBase):
    """Rank > 0: dials the coordinator, heartbeats on a background
    thread, and learns cluster state from the heartbeat-ack digest."""

    def __init__(self, rank: int, world: int, coordinator: str,
                 config: ClusterConfig | None = None, faults=None):
        self.rank = int(rank)
        self.world = int(world)
        self.cfg = config or ClusterConfig()
        self.faults = faults if faults is not None else NULL_PLAN
        self._net = net.NetworkThread(port=-1)
        self._lock = threading.Lock()
        self._running = True
        self._commit_hook = None
        self._digest: dict = {}
        self._last_ack = time.monotonic()
        self._coordinator_dead = False
        self._dropped = False          # fault-injected silent death
        self._barriers: dict[str, dict] = {}
        self._commit_done: dict[int, threading.Event] = {}
        self._commit_ok: dict[int, bool] = {}
        self._fp_done: dict[int, threading.Event] = {}
        self._fp_result: dict[int, tuple] = {}
        host, port = _addr(coordinator)
        self._ep = self._dial(host, port)
        try:
            self._hello(host, port)
        except BaseException:
            self._net.close()
            raise
        self._threads = []
        for target, name in ((self._rx_loop, "rx"), (self._hb_loop, "hb")):
            t = threading.Thread(target=target, daemon=True,
                                 name=f"cluster-{name}-{rank}")
            t.start()
            self._threads.append(t)

    def _hello(self, host, port):
        """Versioned hello: announce our rank + protocol version and
        wait for the coordinator's verdict — ``hello-ack`` joins,
        ``hello-reject`` (or silence from a pre-integrity coordinator
        that cannot read the sealed hello) fails LOUDLY here, at join,
        instead of as mis-parsed messages mid-run."""
        self._send(self._ep, "hello", rank=self.rank,
                   proto=PROTO_VERSION)
        deadline = time.monotonic() + self.cfg.connect_timeout
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise ClusterError(
                    f"rank {self.rank}: no hello-ack from coordinator "
                    f"{host}:{port} within "
                    f"{self.cfg.connect_timeout:.0f}s (version-"
                    "mismatched or unreachable control plane?)")
            try:
                msg = self._ep.recv(timeout=min(1.0, remaining),
                                    max_bytes=MAX_MESSAGE_BYTES)
            except (ConnectionError, IntegrityError) as e:
                raise ClusterError(
                    f"rank {self.rank}: hello handshake with "
                    f"{host}:{port} failed ({e})") from None
            if msg is None:
                continue
            try:
                data = _payload(msg)
            except (IntegrityError, ValueError, UnicodeDecodeError) as e:
                raise ClusterError(
                    f"rank {self.rank}: corrupt hello reply from "
                    f"{host}:{port} ({e})") from None
            kind = msg.meta.decode()
            if kind == "hello-reject":
                raise ClusterError(
                    f"rank {self.rank}: coordinator rejected the join: "
                    f"{data.get('reason', 'no reason given')} "
                    f"(coordinator protocol {data.get('proto')}, ours "
                    f"{PROTO_VERSION})")
            if kind == "hello-ack":
                return
            # anything else this early is a protocol violation
            raise ClusterError(
                f"rank {self.rank}: unexpected {kind!r} during the "
                "hello handshake")

    def _dial(self, host, port):
        deadline = time.monotonic() + self.cfg.connect_timeout
        while True:
            try:
                return self._net.connect(host, port)
            except ConnectionError:
                if time.monotonic() >= deadline:
                    raise ClusterError(
                        f"rank {self.rank}: coordinator {host}:{port} "
                        f"unreachable for {self.cfg.connect_timeout:.0f}s"
                    ) from None
                time.sleep(0.1)

    # -- background loops --------------------------------------------------
    def _hb_loop(self):
        seq = 0
        while self._running:
            seq += 1
            try:
                self.faults.on_heartbeat(seq)
            except _DropPeerSignal:
                # simulate a silent network death: stop beating, leave
                # the socket up (the coordinator must detect by SILENCE)
                with self._lock:
                    self._dropped = True
                return
            if not self._running:
                return
            try:
                # the rank's metric summary rides every beat (a few
                # tens of bytes): the coordinator's health report
                # aggregates them into the fleet view
                self._hb_sent_at = time.monotonic()
                self._send(self._ep, "hb", rank=self.rank, seq=seq,
                           metrics=self._metrics_summary())
            except ConnectionError:
                if self._running:
                    self._mark_coordinator_dead()
                return
            time.sleep(self.cfg.heartbeat_interval)
            if time.monotonic() - self._last_ack > self.cfg.dead_after:
                self._mark_coordinator_dead()
                return

    def _rx_loop(self):
        while self._running:
            try:
                msg = self._ep.recv(timeout=self.cfg.recv_slice,
                                    max_bytes=MAX_MESSAGE_BYTES)
            except ConnectionError:
                if self._running:    # our own close() is not a death
                    self._mark_coordinator_dead()
                return
            except IntegrityError as e:
                self._note_wire_error(e)     # oversized-frame guard
                continue
            if msg is None:
                continue
            data = self._open(msg)
            if data is None:
                continue        # corrupt frame: dropped and counted
            kind = msg.meta.decode()
            if kind == "hb-ack":
                now = time.monotonic()
                with self._lock:
                    self._digest = data
                    self._last_ack = now
                    sent = getattr(self, "_hb_sent_at", None)
                    self._hb_sent_at = None
                if sent is not None:
                    # beat-to-ack round trip (control-plane latency; an
                    # ack matched against the NEWEST un-acked beat, so a
                    # coalesced/slow ack reads as the large RTT it is)
                    _metrics.default_registry().histogram(
                        "cluster_heartbeat_rtt_seconds",
                        "worker heartbeat send to coordinator ack"
                    ).observe(now - sent)
            elif kind in ("barrier-ok", "barrier-fail"):
                with self._lock:
                    slot = self._barriers.get(data["name"])
                if slot is not None:
                    slot["missing"] = data.get("missing") \
                        if kind == "barrier-fail" else None
                    slot["event"].set()
            elif kind == "commit":
                step = int(data["step"])
                with self._lock:
                    ev = self._commit_done.setdefault(
                        step, threading.Event())
                    self._commit_ok[step] = bool(data.get("ok"))
                    # same bounded window the coordinator keeps: a
                    # weeks-long run must not leak an Event per step
                    _prune_window(self._commit_ok, self._commit_done)
                ev.set()
            elif kind == "fp-result":
                seq = int(data["seq"])
                with self._lock:
                    ev = self._fp_done.setdefault(seq,
                                                  threading.Event())
                    self._fp_result[seq] = (
                        bool(data.get("ok")),
                        [int(r) for r in data.get("divergent", [])])
                    _prune_window(self._fp_result, self._fp_done)
                ev.set()

    def _mark_coordinator_dead(self):
        with self._lock:
            if self._dropped:       # fault-injected: we left, not them
                return
            self._coordinator_dead = True

    # -- health ------------------------------------------------------------
    def health(self):
        with self._lock:
            d = dict(self._digest) if self._digest else {
                "world": self.world, "alive": [], "dead": [],
                "stragglers": [], "heartbeat_age": {}}
            d["rank"] = self.rank
            d["coordinator_ack_age"] = round(
                time.monotonic() - self._last_ack, 3)
            # the digest's wire_errors is the COORDINATOR's count; ours
            # rides separately so a one-sided corrupt link is visible
            d["local_wire_errors"] = self._wire_errors
            if self._coordinator_dead:
                dead = set(d.get("dead", []))
                dead.add(0)
                d["dead"] = sorted(dead)
        return d

    # -- barrier -----------------------------------------------------------
    def barrier(self, name, timeout=30.0):
        slot = {"event": threading.Event(), "missing": None}
        with self._lock:
            self._barriers[name] = slot
        try:
            self._send(self._ep, "barrier", name=name, rank=self.rank)
        except ConnectionError:
            raise BarrierTimeout(name, [0], 0.0) from None
        # small slack over the caller's budget: the coordinator times
        # the barrier too and its fail message names the true missing
        # set — only a DEAD coordinator leaves us to our local timeout
        if not slot["event"].wait(timeout + 2 * self.cfg.recv_slice):
            with self._lock:
                self._barriers.pop(name, None)
            raise BarrierTimeout(name, [0], timeout)
        with self._lock:
            self._barriers.pop(name, None)
        if slot["missing"]:
            raise BarrierTimeout(name, slot["missing"], timeout)

    # -- two-phase commit ---------------------------------------------------
    def ack_save(self, step, digest=None, data_digest=None):
        self.faults.on_ack(int(step))
        with self._lock:
            self._commit_done.setdefault(int(step), threading.Event())
        try:
            self._send(self._ep, "ack", step=int(step), rank=self.rank,
                       digest=digest, data_digest=data_digest)
        except ConnectionError:
            self._mark_coordinator_dead()

    def wait_commit(self, step, timeout=30.0):
        with self._lock:
            ev = self._commit_done.setdefault(int(step),
                                              threading.Event())
        if not ev.wait(timeout):
            return False
        with self._lock:
            return bool(self._commit_ok.get(int(step)))

    # -- cross-replica fingerprint agreement --------------------------------
    def fingerprint_agree(self, seq, fp, timeout=30.0):
        seq = int(seq)
        with self._lock:
            ev = self._fp_done.setdefault(seq, threading.Event())
        try:
            self._send(self._ep, "fp", seq=seq, fp=fp,
                       rank=self.rank)
        except ConnectionError:
            self._mark_coordinator_dead()
            return True, []      # membership check reports the death
        if not ev.wait(timeout):
            with self._lock:
                # the verdict may have landed in the race window while
                # the wait expired — honor it if so
                late = self._fp_result.get(seq)
            if late is not None:
                return late
            warnings.warn(
                f"fingerprint agreement round {seq} timed out after "
                f"{timeout:.0f}s (coordinator stalled?); treating as "
                "agreed — membership checks cover a dead coordinator",
                stacklevel=2)
            return True, []
        with self._lock:
            return self._fp_result.get(seq, (True, []))

    # -- teardown ----------------------------------------------------------
    def close(self):
        self._running = False
        self._net.close()


def make_cluster(rank: int, world: int, coordinator: str | None = None,
                 config: ClusterConfig | None = None,
                 faults=None) -> ClusterBase:
    """Build this process's cluster member: :class:`SoloCluster` for a
    world of one, :class:`Coordinator` for rank 0, :class:`Worker`
    otherwise. ``coordinator`` is ``"host:port"`` (the same address the
    jax.distributed coordinator convention uses)."""
    if world <= 1:
        return SoloCluster(rank, faults)
    if coordinator is None:
        raise ValueError("multi-rank cluster needs coordinator='host:port'")
    if int(rank) == 0:
        return Coordinator(world, coordinator, config, faults)
    return Worker(rank, world, coordinator, config, faults)


__all__ = ["PROTO_VERSION", "ClusterConfig", "ClusterError",
           "MembershipError", "BarrierTimeout", "ClusterBase",
           "SoloCluster", "Coordinator", "Worker", "make_cluster"]
