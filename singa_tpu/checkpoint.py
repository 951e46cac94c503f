"""Async sharded checkpointing on Orbax.

The reference's persistence routes (Snapshot .bin/.desc, the
save_states zip — reference model.py:244-330, src/io/snapshot.cc:33-80)
both serialize through ONE host copy of every array. For models whose
state is tp/ep/pp-sharded across a mesh (or across hosts), this module
adds the TPU-idiomatic third route: state is read from the LIVE tensors
(no gather, no full-model host copy — each process contributes only its
addressable shards) and the write happens ASYNCHRONOUSLY, so training
steps continue while bytes land on disk.

    ck = AsyncModelCheckpointer()
    ck.save(path, model)          # returns immediately; shards stream out
    ...training continues...
    ck.wait()                     # barrier before e.g. rotating dirs
    ck.restore(path, model)       # shards land back WITH their shardings

Restore is driven by the CHECKPOINT's metadata (not the live state), so
a freshly constructed process — whose lazily-created optimizer aux does
not exist yet — restores momentum/moments too and replays the exact
trajectory. Every entry restores onto the CURRENT topology: live
counterparts keep their sharding, fresh optimizer aux adopts its owning
param's live sharding (never the layout persisted by a possibly
different mesh). ZeRO/FSDP needs no special handling here for the same
reason: the sharded-optimizer layout is recomputed from announced specs
when the GSPMD step compiles (``gspmd.fsdp_state_spec``), so a
ZeRO-sharded checkpoint restores bit-identical into the same mesh, a
different data degree, or an unsharded model — the live run owns the
layout, the checkpoint owns the bytes.
"""

from __future__ import annotations

import json
import os
import threading
import time
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from .integrity import (IntegrityError, data_state_digest, digest_tree,
                        manifest_digest, read_digest_sidecar,
                        verify_tree, write_digest_sidecar)
from .observability import metrics as _obs_metrics

# see CheckpointManager._orbax_save
_ORBAX_SAVE_LOCK = threading.Lock()


def _obs_restore_done(t0, fallback_depth):
    """Shared restore accounting for both manager flavours: duration,
    and how many newer steps had to be skipped to find a restorable one
    (``fallback_depth`` > 0 means work was lost — the gauge a dashboard
    alarms on)."""
    reg = _obs_metrics.default_registry()
    reg.histogram("checkpoint_restore_seconds",
                  "restore_latest wall-clock, fallbacks included"
                  ).observe(time.perf_counter() - t0)
    reg.gauge("checkpoint_restore_fallback_depth",
              "newer unrestorable steps skipped by the latest restore"
              ).set(fallback_depth)
    if fallback_depth:
        reg.counter("checkpoint_restore_fallbacks_total",
                    "corrupt/incomplete steps skipped across restores"
                    ).inc(fallback_depth)


def _state_tensor_dict(model):
    """name -> LIVE Tensor for every model state + optimizer aux (no
    gather, no host copy — unlike get_states()/save_states)."""
    out = {}
    for k, t in model.get_states().items():
        out[f"model/{k}"] = t
    opt = getattr(model, "optimizer", None)
    if opt is not None and hasattr(opt, "state_tensor_dict"):
        for k, t in opt.state_tensor_dict().items():
            out[f"optimizer/{k}"] = t
    return out


def _aux_param_base(name):
    """'<param>:<kind>' (optionally 'residual/<param>') -> param name."""
    return name.split("/", 1)[-1].rsplit(":", 1)[0]


def _adapt_float(arr, target_dt):
    """Adapt a restored array to a live/template dtype, float-to-float
    only: a checkpoint written under a different precision mode (pure
    bf16 params vs fp32 masters) lands in the LIVE dtype so the
    compiled step's avals — and state donation — survive the migration.
    bf16→f32 is lossless; the reverse is the destination policy's own
    quantisation. Same-dtype (and any non-float) input passes through
    untouched, bit-identical."""
    arr_dt = getattr(arr, "dtype", None)
    if (target_dt is not None and arr_dt is not None
            and target_dt != arr_dt
            and jnp.issubdtype(target_dt, jnp.floating)
            and jnp.issubdtype(arr_dt, jnp.floating)):
        return jnp.asarray(arr, dtype=target_dt)
    return arr


def _build_restore_template(live, meta_tree):
    """ShapeDtypeStruct tree for StandardRestore, keyed by the
    CHECKPOINT's metadata. Sharding targets come from the CURRENT
    process: a live counterpart's sharding when shapes agree, else —
    for fresh optimizer aux — the owning param's live sharding (the
    layout persisted in the checkpoint may belong to a different
    topology, which orbax itself flags as unsafe to reuse)."""
    template = {}
    for k, m in meta_tree.items():
        shape = tuple(m.shape)
        sharding = None
        lt = live.get(k)
        if lt is not None and tuple(np.shape(lt.data)) == shape:
            sharding = getattr(lt.data, "sharding", None)
        elif lt is None and k.startswith("optimizer/"):
            base = live.get(
                "model/" + _aux_param_base(k[len("optimizer/"):]))
            if base is not None and \
                    tuple(np.shape(base.data)) == shape:
                sharding = getattr(base.data, "sharding", None)
        template[k] = jax.ShapeDtypeStruct(shape, m.dtype,
                                           sharding=sharding)
    return template


def _apply_restored(model, live, restored):
    """Land restored arrays in the live tensors; create lazily-built
    optimizer aux that a fresh process has not materialised yet
    (announcing the owning param's spec so it keeps sharding like its
    param); skip — loudly — anything without a live home or with a
    mismatched shape (e.g. resuming into a re-architected model).

    A QUANTIZED checkpoint (``tools/quantize_checkpoint.py`` /
    ``quant.quantize_state_arrays``) carries int8 payloads plus
    ``quant-scale/<key>`` fp32 sidecars: restoring one into a model
    with floating masters dequantizes payload × scale here and then
    rides :func:`_adapt_float`'s normal rules into the live master
    dtype — so a 4x-smaller checkpoint restores into fp32 masters with
    no extra ceremony. Restoring one into a model quantized IN PLACE
    (``quant.quantize_params`` — live int8 payloads, scales under live
    ``<prefix>quant-scale/<name>`` tensors) lands the payload verbatim
    and the sidecar scale into its live scale tensor: an int8 payload
    without its matching scale is wrong weights, so a sidecar scale
    with no home is a LOUD skip like any other orphan entry."""
    from .quant.core import SCALE_PREFIX as _QSCALE
    from .quant.core import dequantize_entry
    q_scales = {k[len(_QSCALE):]: a for k, a in restored.items()
                if k.startswith(_QSCALE)}
    opt = getattr(model, "optimizer", None)
    for k, arr in restored.items():
        if k.startswith(_QSCALE):
            base = k[len(_QSCALE):]
            lt0 = live.get(base)
            tdt = getattr(getattr(lt0, "data", None), "dtype", None)
            if tdt is not None and jnp.issubdtype(tdt, jnp.floating):
                continue    # consumed by the payload's dequant below
            # int8-live payload: the scale's home is the live
            # quant-scale tensor ('model/<n>' -> 'model/quant-scale/<n>')
            head, _sep, tail = base.rpartition("/")
            home = live.get(f"{head}/{_QSCALE}{tail}" if head
                            else _QSCALE + tail)
            if home is not None and \
                    tuple(np.shape(home.data)) == tuple(np.shape(arr)):
                home.data = arr
                continue
            warnings.warn(
                f"checkpoint entry {k!r} (quantization scale) has no "
                "live scale tensor and its payload did not dequantize "
                "into floating masters; skipped — the restored int8 "
                "payload may be mis-scaled", stacklevel=3)
            continue
        if (k in q_scales
                and np.dtype(getattr(arr, "dtype", None)) == np.int8):
            lt0 = live.get(k)
            tdt = getattr(getattr(lt0, "data", None), "dtype", None)
            if tdt is not None and jnp.issubdtype(tdt, jnp.floating):
                arr = dequantize_entry(arr, q_scales[k])
        lt = live.get(k)
        if lt is not None:
            if tuple(np.shape(lt.data)) != tuple(np.shape(arr)):
                warnings.warn(
                    f"checkpoint entry {k!r} has shape "
                    f"{tuple(np.shape(arr))} but the live tensor is "
                    f"{tuple(np.shape(lt.data))}; skipped (did the "
                    "architecture change since the save?)", stacklevel=3)
                continue
            tdt = getattr(lt.data, "dtype", None)
            if (tdt is not None and jnp.dtype(tdt) == jnp.int8
                    and jnp.issubdtype(
                        np.dtype(getattr(arr, "dtype", np.int8)),
                        np.floating)):
                # an fp32 checkpoint restored into an in-place-
                # quantized model (warm restart after quantize_params):
                # landing the float bytes verbatim would make the
                # dequant scope multiply full-precision weights by the
                # stale scale (~100x shrink). Re-quantize fresh and
                # land the new scale beside the payload.
                from .quant.core import (SCALE_PREFIX, channel_axis,
                                         quantize_int8)
                head, _sep, tail = k.rpartition("/")
                home = live.get(f"{head}/{SCALE_PREFIX}{tail}" if head
                                else SCALE_PREFIX + tail)
                if home is None:
                    warnings.warn(
                        f"checkpoint entry {k!r} is float but the live "
                        "tensor is an int8 payload with no live scale "
                        "tensor; skipped", stacklevel=3)
                    continue
                q, s = quantize_int8(np.asarray(arr),
                                     channel_axis(np.shape(arr)))
                lt.data = q
                home.data = s
                continue
            lt.data = _adapt_float(arr, tdt)
        elif k.startswith("optimizer/") and opt is not None \
                and hasattr(opt, "restore_state_tensor"):
            nm = k[len("optimizer/"):]
            pt = live.get("model/" + _aux_param_base(nm))
            # lazily-built aux has no live tensor to adapt to yet — the
            # owning param's dtype is its template (momentum must match
            # its master, or the first step promotes and retraces)
            arr = _adapt_float(
                arr, getattr(getattr(pt, "data", None), "dtype", None))
            opt.restore_state_tensor(nm, arr, getattr(pt, "spec", None))
        else:
            warnings.warn(f"checkpoint entry {k!r} has no live "
                          "counterpart in this model; skipped",
                          stacklevel=3)
    # compiled steps close over state identity; force a rebind
    model._invalidate_compiled()


class AsyncModelCheckpointer:
    """Orbax ``AsyncCheckpointer`` over a Model's state pytree."""

    def __init__(self):
        import orbax.checkpoint as ocp
        self._ocp = ocp
        self._ckptr = ocp.AsyncCheckpointer(
            ocp.StandardCheckpointHandler())

    def save(self, path, model, force=True):
        """Start an async save of params + optimizer aux; returns
        immediately (the previous pending save is awaited first, as
        orbax allows a single outstanding write)."""
        arrays = {k: t.data for k, t in _state_tensor_dict(model).items()}
        self._ckptr.save(os.path.abspath(str(path)),
                         args=self._ocp.args.StandardSave(arrays),
                         force=force)

    def wait(self):
        """Block until the outstanding async save has fully committed."""
        self._ckptr.wait_until_finished()

    def restore(self, path, model):
        """Load shards back into the model's live tensors (see the
        module docstring for the template/topology rules)."""
        path = os.path.abspath(str(path))
        live = _state_tensor_dict(model)
        # orbax API drift: metadata() returns a plain dict tree on
        # newer versions, a CheckpointMetadata wrapper on older ones
        raw = self._ckptr.metadata(path)
        tree = getattr(getattr(raw, "item_metadata", None), "tree", None)
        meta = dict(tree if tree is not None else raw)
        restored = self._ckptr.restore(
            path, args=self._ocp.args.StandardRestore(
                _build_restore_template(live, meta)))
        _apply_restored(model, live, restored)

    def close(self):
        self._ckptr.close()


class CheckpointManager:
    """Rotated, step-numbered checkpoints over the async sharded route
    (orbax ``CheckpointManager``): save every ``save_interval_steps``,
    keep the newest ``max_to_keep``, resume from the latest — the
    checkpoint-restart loop the reference lacks entirely (its NCCL/MPI
    failures just exit, include/singa/io/communicator.h:40-67).

        mgr = CheckpointManager(dir, max_to_keep=3, save_interval_steps=50)
        start = mgr.restore_latest(model)        # 0 on a fresh run
        for step in range(start, total):
            model(tx, ty)
            mgr.save(step, model)                # no-op off-interval
        mgr.wait(); mgr.close()
    """

    def __init__(self, directory, max_to_keep=3, save_interval_steps=1,
                 sweep=True, digests=True):
        """``sweep=False`` skips the uncommitted-wreckage sweep at init —
        for READ-ONLY managers opened on a directory another rank owns
        (the elastic cross-rank restore path must never delete a live
        writer's in-flight step). ``digests=False`` disables the
        per-tensor content-digest sidecars (``<dir>/digests/<step>.json``)
        written with every save and re-verified before any restore
        hands state back — only for callers that measure the host-side
        CRC cost and prefer orbax's own parse errors as the sole
        corruption detector."""
        import orbax.checkpoint as ocp
        self._ocp = ocp
        self._dir = os.path.abspath(str(directory))
        self._max_to_keep = max_to_keep
        self._save_interval_steps = save_interval_steps
        self._digests_on = bool(digests)
        self._digest_dir = os.path.join(self._dir, "digests")
        self._data_dir = os.path.join(self._dir, "data_state")
        # digest tree of the newest save (the distributed manager acks
        # its manifest digest to the cluster); None when digests are off
        self.last_saved_digests = None
        # digest of the newest save's data-iterator state (rides the
        # two-phase ACK beside the tensor digest); None when the save
        # carried no data state
        self.last_saved_data_digest = None
        # data-iterator state of the newest successful restore_latest
        # (the trainer rewinds its iterator to it); None when the step
        # predates data-state capture
        self.restored_data_state = None
        self._restored_data_state = None
        self._mgr = self._make_mgr()
        if sweep:
            self._sweep_uncommitted()
        # steps THIS manager owns: present at init (post-sweep) or
        # saved by us. A step directory that appears outside this set
        # is a dead predecessor's late-finalized write — the save
        # retry below keys on membership here, never on orbax
        # internals (error text, all_steps caching)
        self._known_steps = {int(s) for s in self._mgr.all_steps()}

    def _make_mgr(self):
        ocp = self._ocp
        return ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=self._max_to_keep,
                save_interval_steps=self._save_interval_steps,
                enable_async_checkpointing=True),
            # a FRESH manager (resume path) must know the handler type
            # before any save, or item metadata cannot be read
            item_handlers=ocp.StandardCheckpointHandler())

    def _sweep_uncommitted(self):
        """Remove step directories a dead writer left without a commit
        marker. A process killed mid-async-save (the normal way a
        preempted job dies) leaves the step's directory on disk but
        absent from ``all_steps()``; the restarted job resumes from an
        earlier step, re-trains, and its ``save`` of that step number
        would then refuse — 'destination already exists' — stranding
        the run. Single-writer-per-directory is assumed (as it is for
        rotation)."""
        import shutil
        committed = {str(s) for s in self._mgr.all_steps()}
        try:
            entries = os.listdir(self._dir)
        except OSError:
            return
        for name in entries:
            # only orbax's own artifacts: an exact step-number dir with
            # no commit marker, or an orbax tmp dir. Anything else in
            # here (a user's "3.backup", notes, …) is not ours to delete
            wreck = (name.isdigit() and name not in committed) or \
                ".orbax-checkpoint-tmp" in name
            if wreck:
                path = os.path.join(self._dir, name)
                if os.path.isdir(path):
                    warnings.warn(
                        f"removing uncommitted checkpoint wreckage "
                        f"{path} (a previous writer died mid-save)",
                        stacklevel=3)
                    shutil.rmtree(path, ignore_errors=True)

    def _reopen(self):
        """Rebuild the orbax manager after step dirs were deleted out
        from under it (its should_save else refuses the re-run window),
        and prune digest sidecars down to the surviving steps."""
        self._mgr.close()
        self._mgr = self._make_mgr()
        self._prune_digests()

    # -- content digests ---------------------------------------------------
    def _digest_path(self, step):
        return os.path.join(self._digest_dir, f"{int(step)}.json")

    def _write_digests(self, step, tree):
        os.makedirs(self._digest_dir, exist_ok=True)
        write_digest_sidecar(self._digest_path(step), tree,
                             step=int(step))
        self._prune_digests()

    def read_digests(self, step):
        """The step's digest sidecar dict ({"algo","records","manifest"})
        or None when absent (a pre-integrity save) / unreadable."""
        return read_digest_sidecar(self._digest_path(step))

    def _prune_digests(self, keep=None):
        """Sidecars (tensor digests AND data states) follow the step
        rotation: one whose step orbax (or a wreckage sweep) already
        deleted is dead weight."""
        keep = {int(s) for s in (self._mgr.all_steps()
                                 if keep is None else keep)}
        for d in (self._digest_dir, self._data_dir):
            try:
                names = os.listdir(d)
            except OSError:
                continue
            for n in names:
                if n.endswith(".json") and n[:-5].isdigit() \
                        and int(n[:-5]) not in keep:
                    try:
                        os.remove(os.path.join(d, n))
                    except OSError:
                        pass

    # -- data-iterator state -----------------------------------------------
    def _data_state_path(self, step):
        return os.path.join(self._data_dir, f"{int(step)}.json")

    def _write_data_state(self, step, state):
        """Persist the data pipeline's ``state_dict()`` beside the step
        — synchronous (the state is a few counters) and atomic, with
        its own content digest: the sample-stream offset a resume
        rewinds to is vouched for exactly like the tensors."""
        os.makedirs(self._data_dir, exist_ok=True)
        digest = data_state_digest(state)
        doc = {"step": int(step), "state": state, "digest": digest}
        path = self._data_state_path(step)
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return digest

    def read_data_state(self, step):
        """The step's verified data-iterator state, or None when the
        step carries none (a pre-data-state save, or a run without a
        checkpointable iterator). Raises
        :class:`~singa_tpu.integrity.IntegrityError` when the sidecar
        exists but its content does not match its digest — a corrupt
        data offset must drive the same fallback chain as corrupt
        tensor bytes, never silently restart the stream."""
        try:
            with open(self._data_state_path(step)) as f:
                doc = json.load(f)
        except OSError:
            return None
        except ValueError as e:
            raise IntegrityError(
                f"checkpoint step {step}: data-state sidecar is "
                f"unparseable ({e})")
        state = doc.get("state")
        want = doc.get("digest")
        if state is None or want is None or \
                data_state_digest(state) != want:
            raise IntegrityError(
                f"checkpoint step {step}: data-state sidecar failed "
                f"its digest check — the resume offset is corrupt")
        return state

    def _verify_restored(self, step, restored, expect_manifest=None):
        """Verify restored arrays against the step's digest sidecar
        BEFORE they land in any live tensor — and, when the caller
        holds a cluster-committed manifest digest, against THAT too: a
        shard whose sidecar agrees with its own bytes but not with the
        commit marker is a stale/foreign shard wearing the right step
        number, and must be rejected before it touches training state.
        Raises :class:`~singa_tpu.integrity.IntegrityError` on any
        mismatch; returns the sidecar dict (None when the step predates
        the integrity layer — accepted, loudly)."""
        if not self._digests_on:
            return None
        expected = self.read_digests(step)
        if expected is None:
            if expect_manifest:
                # the commit marker carries the cluster-agreed digest,
                # so the shard can be verified DIRECTLY against it even
                # without its sidecar (lost, or this rank's sidecar
                # write failed at save time): recompute the manifest
                # digest from the restored bytes. A healthy shard
                # passes — no crash loop for a rank whose bookkeeping
                # failed — while a stale/corrupt shard still fails to
                # the next source, never reaching live tensors.
                tree = digest_tree(restored)
                got = manifest_digest(tree)
                if got != expect_manifest:
                    raise IntegrityError(
                        f"checkpoint step {step}: no digest sidecar, "
                        f"and the restored content ({got}) does not "
                        f"match the cluster-committed "
                        f"{expect_manifest} — stale or corrupt shard")
                warnings.warn(
                    f"checkpoint step {step}: digest sidecar missing; "
                    "shard re-verified directly against the cluster-"
                    "committed manifest digest", stacklevel=3)
                return {"algo": "crc32", "records": tree,
                        "manifest": got}
            warnings.warn(
                f"checkpoint step {step} has no digest sidecar (saved "
                "before the integrity layer?); restoring UNVERIFIED",
                stacklevel=3)
            return None
        bad = verify_tree(restored, expected["records"])
        if bad:
            raise IntegrityError(
                f"checkpoint step {step}: content digest mismatch for "
                f"{len(bad)} entr{'y' if len(bad) == 1 else 'ies'} "
                f"(first: {bad[:3]}) — the shard is corrupt on disk")
        got = expected.get("manifest")
        if expect_manifest and got and expect_manifest != got:
            raise IntegrityError(
                f"checkpoint step {step}: shard manifest digest {got} "
                f"does not match the cluster-committed "
                f"{expect_manifest}")
        return expected

    def save(self, step, model, force=False, data_state=None):
        t0 = time.perf_counter()
        # one outstanding digest worker, like orbax's one outstanding
        # write — and joined BEFORE the next orbax save so the worker's
        # all_steps()-based sidecar pruning never overlaps a write
        self._join_digest_thread()
        arrays = {k: t.data for k, t in _state_tensor_dict(model).items()}
        try:
            saved = self._orbax_save(step, arrays, force)
        except ValueError:
            # a crashed predecessor's zombie orbax machinery can still
            # mutate the directory after our init raced past it:
            # (a) its async writer FINALIZES its step dir (a rename)
            # after _sweep_uncommitted's rmtree — orbax then refuses
            # our re-save of the step a restore legitimately re-ran —
            # detected STRUCTURALLY (a step dir on disk this manager
            # never owned; not orbax's error text, which is unpinned);
            # (b) its ROTATION deletes an old step this manager had
            # already cached in its step list — orbax's next
            # should-remove scan then raises on the vanished dir.
            # Both recover the same way: reconcile with the on-disk
            # state (drop the foreign dir for (a), rebuild the manager
            # either way) and retry ONCE; an unrelated ValueError
            # recurs on the retry and propagates.
            path = os.path.join(self._dir, str(int(step)))
            if os.path.isdir(path) and \
                    int(step) not in self._known_steps:
                import shutil
                warnings.warn(
                    f"removing late-appearing uncommitted checkpoint "
                    f"wreckage {path} (a previous writer's async save "
                    "finalized after the init sweep)", stacklevel=2)
                shutil.rmtree(path, ignore_errors=True)
            else:
                warnings.warn(
                    f"checkpoint save of step {step} tripped on stale "
                    "step bookkeeping (a previous writer's rotation "
                    "deleted a step this manager had cached?); "
                    "rebuilding from on-disk state and retrying once",
                    stacklevel=2)
            self._reopen()
            self._known_steps &= {int(s)
                                  for s in self._mgr.all_steps()}
            saved = self._orbax_save(step, arrays, force)
        if saved:
            self._known_steps.add(int(step))
            reg = _obs_metrics.default_registry()
            reg.counter("checkpoint_saves_total",
                        "checkpoint saves actually started").inc()
            # host-side dispatch cost only — the write itself is async;
            # DistributedCheckpointManager.save adds the commit wait
            reg.histogram("checkpoint_save_seconds",
                          "host-side save dispatch (async write "
                          "excluded)").observe(time.perf_counter() - t0)
            # the data-iterator state rides every save (tiny JSON,
            # synchronous + atomic): on ANY restore of this step the
            # sample stream rewinds in lockstep with the tensors
            self.last_saved_data_digest = \
                self._write_data_state(step, data_state) \
                if data_state is not None else None
        if saved and self._digests_on:
            # digest the SAME immutable arrays handed to orbax (jax
            # arrays cannot change under the async write), so the
            # sidecar vouches for exactly the bytes being persisted —
            # but OFF the step path: the device→host transfer + CRC
            # runs on a worker thread overlapping training, exactly
            # like orbax's own async write, and wait() joins it. A
            # process that dies before the join leaves a step without
            # a sidecar, which restore treats as 'unverified' (warned)
            # — never as verified-and-wrong.
            import threading
            # cleared BEFORE the worker runs: a worker that fails must
            # leave None (ack'd as "no digest"), never the PREVIOUS
            # step's tree masquerading as this one's
            self.last_saved_digests = None

            def digest_work(arrays=arrays, step=int(step)):
                try:
                    tree = digest_tree(arrays)
                    self._write_digests(step, tree)
                    # published only once the sidecar is ON DISK: a
                    # digest ACKed into a commit marker must always
                    # have the sidecar restore will check against
                    self.last_saved_digests = tree
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    warnings.warn(
                        f"digest sidecar for step {step} failed "
                        f"({type(e).__name__}: {e}); the step will "
                        "restore unverified", stacklevel=2)

            self._digest_thread = threading.Thread(
                target=digest_work, daemon=True, name="ckpt-digest")
            self._digest_thread.start()
        return saved

    def _orbax_save(self, step, arrays, force):
        # orbax keys each save's directory-creation signals by a
        # process-global operation id, read back after it is bumped:
        # two managers of one process (ranks run as threads) that start
        # saves at once share an id, and each then creates and wipes
        # the other's temporary step directory. Starts are serialized
        # process-wide; the async write itself still overlaps.
        with _ORBAX_SAVE_LOCK:
            return self._mgr.save(
                int(step), args=self._ocp.args.StandardSave(arrays),
                force=force)

    def _join_digest_thread(self):
        t = getattr(self, "_digest_thread", None)
        if t is not None:
            t.join()
            self._digest_thread = None

    def latest_step(self):
        return self._mgr.latest_step()

    def all_steps(self):
        return sorted(self._mgr.all_steps())

    def _restore_step(self, step, model, expect_manifest=None):
        """Restore + VERIFY one step into ``model``. Digest verification
        (including the cluster-committed manifest digest, when the
        caller passes one) runs on the restored arrays BEFORE any of
        them lands in a live tensor, so corrupt or stale bytes never
        reach training state — the raised IntegrityError drives the
        caller's fallback chain (peer shards, then older steps) exactly
        like an unreadable file does. Returns the digest sidecar (or
        None, pre-integrity)."""
        live = _state_tensor_dict(model)
        meta = self._mgr.item_metadata(step)
        tree = dict(getattr(meta, "tree", None) or meta)
        restored = self._mgr.restore(
            step, args=self._ocp.args.StandardRestore(
                _build_restore_template(live, tree)))
        sidecar = self._verify_restored(step, restored, expect_manifest)
        # the data state is read AND verified before any restored array
        # lands in a live tensor: a corrupt resume offset falls back
        # exactly like corrupt tensor bytes, keeping data and model
        # state consistent at whatever step the chain settles on
        self._restored_data_state = self.read_data_state(step)
        _apply_restored(model, live, restored)
        return sidecar

    def restore_latest(self, model):
        """Restore the newest RESTORABLE checkpoint into ``model`` and
        return the NEXT step to run (0 when no checkpoint exists).

        A preempted or crashed writer can leave the newest step
        truncated or corrupt on disk even when its commit marker made
        it down; raising there would strand a job that has perfectly
        good earlier checkpoints. So restorability is verified by
        attempting the restore, scanning BACKWARD: a step that fails to
        load is warned about — loudly — and the scan falls back to the
        previous one. (A failed attempt may have partially landed
        arrays in the live tensors; the succeeding attempt overwrites
        every entry, so the model never trains on a half-restored mix.)
        """
        t0 = time.perf_counter()
        self._join_digest_thread()
        self.restored_data_state = None
        steps = sorted(self._mgr.all_steps(), reverse=True)
        for i, step in enumerate(steps):
            try:
                self._restore_step(step, model)
                self.restored_data_state = self._restored_data_state
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                warnings.warn(
                    f"checkpoint step {step} is not restorable "
                    f"({type(e).__name__}: {e}); falling back to the "
                    f"previous step", stacklevel=2)
                continue
            if i:
                warnings.warn(
                    f"resumed from step {step} after skipping {i} "
                    f"corrupt/incomplete newer checkpoint(s) — up to "
                    f"{steps[0] - step} step(s) of work were lost",
                    stacklevel=2)
                # delete the skipped wreckage and rebuild the manager:
                # while a corrupt step remains the directory's newest,
                # orbax's should_save refuses every interval save of the
                # re-run window (step <= latest), so a second crash
                # there would lose the same stretch of work again
                import shutil
                for bad_step in steps[:i]:
                    shutil.rmtree(os.path.join(self._dir, str(bad_step)),
                                  ignore_errors=True)
                self._reopen()
            _obs_restore_done(t0, i)
            return step + 1
        if steps:
            warnings.warn(
                f"none of the {len(steps)} checkpoints under this "
                "directory are restorable; starting from scratch",
                stacklevel=2)
            # same stranding as the partial-fallback case: while the
            # corrupt steps remain committed, orbax refuses every save
            # of the from-scratch re-run (step <= latest) — clear them
            import shutil
            for bad_step in steps:
                shutil.rmtree(os.path.join(self._dir, str(bad_step)),
                              ignore_errors=True)
            self._reopen()
        _obs_restore_done(t0, len(steps))
        return 0

    def scrub(self, delete=False):
        """Re-verify every at-rest checkpoint against its digest
        sidecar (no model needed: the restore template comes from the
        checkpoint's own metadata). Returns ``{step: status}`` with
        status one of ``"ok"``, ``"corrupt"``, ``"unreadable"``,
        ``"unverified"`` (no sidecar — a pre-integrity save), or
        ``"in-flight"`` (a live writer's not-yet-committed save —
        skipped, never demoted).

        With ``delete=True``, corrupt/unreadable steps are DEMOTED
        (step dir + sidecar removed) so the rotation window only ever
        counts — and therefore only ever deletes — *verified* steps:
        without the demotion, a corrupt newest step would let
        ``max_to_keep`` rotate away the last restorable one. Run it
        periodically (cron / a background thread between steps) or via
        ``tools/scrub_checkpoints.py``."""
        # a step whose async orbax write is still in flight appears in
        # all_steps() but cannot restore yet — wait() (digest join +
        # wait_until_finished) so a healthy in-flight step is never
        # reported, or demoted, as corrupt
        self.wait()
        scrub_t0 = time.perf_counter()
        report = {}
        for step in self.all_steps():
            if not os.path.isdir(os.path.join(self._dir, str(step))):
                # a LIVE WRITER's in-flight async save: listed in
                # all_steps() but its final-named dir only appears at
                # commit (until then only an orbax tmp dir exists). A
                # read-only scrubber (the CLI, the background daemon)
                # must neither flag it as corrupt nor — with delete —
                # demote it out from under the writer; our own wait()
                # above only covers the in-process pipeline.
                report[step] = "in-flight"
                continue
            try:
                # the data-state sidecar is part of the step: a corrupt
                # resume offset makes the checkpoint as unrestorable as
                # corrupt tensor bytes (restore would fall back past it)
                self.read_data_state(step)
            except IntegrityError as e:
                warnings.warn(
                    f"scrub: checkpoint step {step} data-state sidecar "
                    f"FAILED verification ({e})", stacklevel=2)
                report[step] = "corrupt"
                continue
            expected = self.read_digests(step) if self._digests_on \
                else None
            if expected is None:
                report[step] = "unverified"
                continue
            try:
                meta = self._mgr.item_metadata(step)
                tree = dict(getattr(meta, "tree", None) or meta)
                template = {k: jax.ShapeDtypeStruct(tuple(m.shape),
                                                    m.dtype)
                            for k, m in tree.items()}
                restored = self._mgr.restore(
                    step,
                    args=self._ocp.args.StandardRestore(template))
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                warnings.warn(
                    f"scrub: checkpoint step {step} is unreadable "
                    f"({type(e).__name__}: {e})", stacklevel=2)
                report[step] = "unreadable"
                continue
            bad = verify_tree(restored, expected["records"])
            if bad:
                warnings.warn(
                    f"scrub: checkpoint step {step} FAILED digest "
                    f"verification ({len(bad)} entries, first "
                    f"{bad[:3]})", stacklevel=2)
                report[step] = "corrupt"
            else:
                report[step] = "ok"
        # the aot/ sidecar (exported compiled executables,
        # singa_tpu.aot) is integrity-covered bytes like any other:
        # each artifact re-verifies against its manifest digest, and
        # delete=True QUARANTINES (not rmtree's) the bad ones — the
        # store's own demotion discipline
        aot_dir = os.path.join(self._dir, "aot")
        if os.path.isdir(aot_dir):
            from .aot.export import AotStore
            aot_report = AotStore(aot_dir).scrub(delete=delete)
            for prog, status in aot_report.items():
                report[f"aot/{prog}"] = status
        if delete:
            import shutil
            demoted = [s for s, st in report.items()
                       if st in ("corrupt", "unreadable")
                       and not isinstance(s, str)]   # aot: quarantined
            for s in demoted:
                shutil.rmtree(os.path.join(self._dir, str(s)),
                              ignore_errors=True)
            if demoted:
                warnings.warn(
                    f"scrub: demoted corrupt checkpoint step(s) "
                    f"{demoted} so rotation keeps only verified steps",
                    stacklevel=2)
                self._reopen()
        reg = _obs_metrics.default_registry()
        reg.histogram("checkpoint_scrub_seconds",
                      "one at-rest verification pass"
                      ).observe(time.perf_counter() - scrub_t0)
        reg.gauge("checkpoint_scrub_bad",
                  "corrupt/unreadable steps found by the newest scrub"
                  ).set(sum(1 for s in report.values()
                            if s in ("corrupt", "unreadable")))
        return report

    def start_scrubber(self, interval=3600.0):
        """Background at-rest verification: a daemon thread re-runs
        :meth:`scrub` every ``interval`` seconds through its OWN
        read-only manager (``sweep=False`` — the live writer's orbax
        bookkeeping is never touched), warns on anything corrupt, and
        publishes the newest result as ``self.scrub_report``.
        Report-only by design: demotion while a writer is live is an
        explicit decision (``scrub(delete=True)`` between runs, or the
        ``tools/scrub_checkpoints.py`` CLI). Returns the thread;
        ``stop_scrubber()`` (also called by ``close``) ends it."""
        import threading
        if getattr(self, "_scrub_stop", None) is not None:
            if self._scrubber.is_alive():
                raise RuntimeError("scrubber already running")
            # a prior stop_scrubber's timed join expired while a long
            # pass finished in the background; the thread is dead now —
            # disarm the stale guard and start fresh
            self._scrub_stop = None
        self._scrub_stop = threading.Event()
        self.scrub_report = {}

        def loop(stop=self._scrub_stop):
            # the Event is captured: stop_scrubber may null the
            # attribute after an expired join while a long scrub pass
            # is still mid-flight
            while not stop.wait(float(interval)):
                try:
                    ro = CheckpointManager(
                        self._dir, max_to_keep=self._max_to_keep,
                        sweep=False, digests=self._digests_on)
                    try:
                        self.scrub_report = ro.scrub()
                    finally:
                        ro.close()
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:   # keep scrubbing next round
                    warnings.warn(
                        f"background scrub pass failed "
                        f"({type(e).__name__}: {e})", stacklevel=2)

        t = threading.Thread(target=loop, daemon=True,
                             name="ckpt-scrubber")
        t.start()
        self._scrubber = t
        return t

    def stop_scrubber(self):
        stop = getattr(self, "_scrub_stop", None)
        if stop is not None:
            stop.set()
            self._scrubber.join(timeout=5.0)
            if not self._scrubber.is_alive():
                # a scrub pass longer than the join grace finishes in
                # the background and exits at its next wait(); until
                # then the already-running guard stays armed
                self._scrub_stop = None

    def wait(self):
        self._join_digest_thread()
        self._mgr.wait_until_finished()

    def close(self):
        self.stop_scrubber()
        self._join_digest_thread()
        self._mgr.close()


def latest_manifest(directory):
    """Peek the newest commit marker's manifest under a
    :class:`DistributedCheckpointManager` root WITHOUT constructing a
    manager — restarted launchers read this before building anything
    (the manifest's saved world size + batch extras decide the new
    run's batch shapes, which must exist before the model compiles).
    Returns None when no committed checkpoint exists."""
    cdir = os.path.join(os.path.abspath(str(directory)), "commits")
    try:
        names = os.listdir(cdir)
    except OSError:
        return None
    steps = sorted(int(n[:-5]) for n in names
                   if n.endswith(".json") and n[:-5].isdigit())
    for s in reversed(steps):
        try:
            with open(os.path.join(cdir, f"{s}.json")) as f:
                return json.load(f)
        except (OSError, ValueError):
            continue
    return None


class DistributedCheckpointManager(CheckpointManager):
    """Two-phase-commit checkpoints for a multi-host run.

    A host that dies mid-save must never leave a checkpoint that only
    *looks* committed. Layout under ``directory``::

        rank0/<step>/...      each rank's shard, via its own rotated
        rank1/<step>/...      orbax manager (single writer per dir)
        commits/<step>.json   the CLUSTER commit marker + manifest

    Phase 1: every rank writes its shard and waits the bytes down, then
    ACKs the step to the coordinator (``cluster.ack_save``). Phase 2:
    only after ALL ranks acked does the coordinator atomically publish
    ``commits/<step>.json`` (the registered commit hook) and broadcast
    the decision. A rank killed between shard-write and ACK leaves a
    step with no marker: ``restore_latest`` treats such step dirs as
    uncommitted wreckage — swept, never restored — reusing the
    backward-scan machinery of the base class.

    The marker doubles as the **elastic manifest**: it records the world
    size (and the caller's batch-accounting extras), so a run restarted
    at a *different* world size M deterministically re-shards: each new
    rank reads shard ``rank % N`` of the old world N (full-shape arrays
    re-land onto the CURRENT mesh via the live-sharding restore
    template), and the batch accounting rescales from the manifest
    (``parallel.communicator.rescale_batch``).

    This per-rank-directory scheme matches the control-plane-coordinated
    deployment (each process holds its full replica / addressable
    shards). Under ``jax.distributed`` with globally-addressed arrays,
    orbax's save is itself collective and all ranks share one directory
    — the two-phase marker protocol above still applies unchanged.
    """

    def __init__(self, directory, cluster, max_to_keep=3,
                 save_interval_steps=1, commit_timeout=60.0,
                 manifest_extra=None, digests=True):
        self.cluster = cluster
        self._root = os.path.abspath(str(directory))
        self._commit_dir = os.path.join(self._root, "commits")
        os.makedirs(self._commit_dir, exist_ok=True)
        self._commit_timeout = float(commit_timeout)
        self.manifest_extra = dict(manifest_extra or {})
        self.restored_manifest = None
        # step -> this rank's manifest digest, pending its commit marker
        # (rank 0's publish hook reads it; bounded by the save window)
        self._pending_digest = {}
        if cluster.rank == 0:
            cluster.set_commit_hook(self._publish_commit)
        super().__init__(os.path.join(self._root, f"rank{cluster.rank}"),
                         max_to_keep=max_to_keep,
                         save_interval_steps=save_interval_steps,
                         digests=digests)

    # -- commit markers ----------------------------------------------------
    def _marker(self, step):
        return os.path.join(self._commit_dir, f"{int(step)}.json")

    def committed_steps(self):
        """Steps with a published cluster commit marker."""
        try:
            names = os.listdir(self._commit_dir)
        except OSError:
            return []
        return sorted(int(n[:-5]) for n in names
                      if n.endswith(".json") and n[:-5].isdigit())

    def read_manifest(self, step):
        with open(self._marker(step)) as f:
            return json.load(f)

    def _publish_commit(self, step):
        """Coordinator-only (runs as the cluster's commit hook, after
        every rank's ACK): atomic tmp-write + rename, so a marker either
        fully exists or not at all — no torn marker can ever pass for a
        commit."""
        manifest = {"step": int(step), "world": int(self.cluster.world)}
        digest = self._pending_digest.pop(int(step), None)
        if digest is not None:
            # the manifest-level content digest: every rank ACKed this
            # exact digest (the cluster refuses to commit disagreeing
            # ones), so any rank's restore can cross-check its shard —
            # even a peer's — against the cluster-agreed content
            manifest["digest"] = digest
        data = {str(r): d for r, d in
                self.cluster.ack_data_digests(int(step)).items()
                if d is not None}
        if data:
            # each rank's data-iterator state digest rode its ACK: the
            # marker vouches for the sample-stream offset exactly like
            # it vouches for the tensors, and any restore cross-checks
            # whichever rank's data sidecar it lands on
            manifest["data_digests"] = data
        manifest.update(self.manifest_extra)
        tmp = os.path.join(self._commit_dir, f".tmp-{int(step)}.json")
        with open(tmp, "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._marker(step))
        # markers follow the shard rotation window: a marker whose
        # shards max_to_keep already rotated away is dead weight. Only
        # markers AT OR BELOW the step just published are candidates —
        # a stale higher-numbered marker (left by a resume that fell
        # back) must not make this fresh marker count as the oldest and
        # get pruned the moment it lands; stale-newer markers are
        # cleared by invalidate_markers_from once the cluster agrees on
        # a resume point
        committed = self.committed_steps()
        kept = [s for s in committed if s <= int(step)]
        kept = set(kept[-self._max_to_keep:])
        for old in committed:
            if old <= int(step) and old not in kept:
                try:
                    os.remove(self._marker(old))
                except OSError:
                    pass

    def invalidate_markers_from(self, step):
        """Remove commit markers at/after ``step`` — coordinator-only,
        and ONLY once the cluster has AGREED to resume at ``step`` (the
        trainer's resume barrier): agreement proves no rank restored
        past it, so those markers vouch for a timeline about to be
        re-run, where a rank killed pre-ACK would otherwise find a
        stale marker vouching for its never-acked shard. This is the
        cluster-consistent counterpart of what a lone rank must never
        do (its local restore failures say nothing about its peers'
        shards). Returns the number of markers removed."""
        if self.cluster.rank != 0:
            return 0
        removed = 0
        for s in self.committed_steps():
            if s >= int(step):
                try:
                    os.remove(self._marker(s))
                    removed += 1
                except OSError:
                    pass
        if removed:
            warnings.warn(
                f"invalidated {removed} stale commit marker(s) at/after "
                f"the agreed resume step {step} (their timeline is "
                "about to be re-run)", stacklevel=2)
        return removed

    # -- two-phase save ----------------------------------------------------
    def save(self, step, model, force=False, commit_timeout=None,
             data_state=None):
        """Write this rank's shard, ACK, and wait for the cluster commit.
        Returns True only when the step COMMITTED (marker published).
        The underlying write is awaited before the ACK — an ACK is a
        durability promise, not an intention. ``commit_timeout``
        overrides the manager default for THIS save (the preemption
        path uses a short one: a forced off-schedule save can only
        quorum when every rank was preempted at the same boundary, and
        a doomed wait must not eat the kill grace)."""
        saved = super().save(step, model, force=force,
                             data_state=data_state)
        if not saved:
            return False
        self.wait()     # bytes down AND digests computed BEFORE the ack
        digest = manifest_digest(self.last_saved_digests) \
            if self.last_saved_digests else None
        if digest is not None:
            self._pending_digest[int(step)] = digest
            # bound the bookkeeping to the rotation window
            for old in sorted(self._pending_digest)[:-self._max_to_keep]:
                self._pending_digest.pop(old, None)
        # the ACK carries this rank's manifest digest — the coordinator
        # commits only when EVERY rank acked the same content, so a
        # silently-diverged replica can never be vouched for by a
        # marker — and its data-state digest, recorded in the marker so
        # the committed checkpoint vouches for the sample-stream offset
        self.cluster.ack_save(  # fault: kill_before_ack
            step, digest=digest,
            data_digest=self.last_saved_data_digest)
        timeout = self._commit_timeout if commit_timeout is None \
            else float(commit_timeout)
        ok = self.cluster.wait_commit(step, timeout=timeout)
        if not ok:
            _obs_metrics.default_registry().counter(
                "checkpoint_commit_failures_total",
                "two-phase saves that never gained a commit marker"
            ).inc()
            warnings.warn(
                f"checkpoint step {step}: commit did not complete within "
                f"{timeout:.0f}s (a rank died before its ACK"
                "?); the step stays uncommitted and restore will refuse "
                "it", stacklevel=2)
        return ok

    # -- elastic restore ---------------------------------------------------
    def _source_ranks(self, manifest):
        """Deterministic shard-source order for this rank: our own (or
        wrapped, when the world grew) shard first, then every other
        rank of the SAVED world. In this per-rank-directory deployment
        each rank's shard is a full replica, so a rank whose own shard
        is corrupt restores a peer's copy of the SAME step instead of
        silently diverging to an older one."""
        saved_world = max(1, int(manifest.get("world",
                                              self.cluster.world)))
        primary = self.cluster.rank % saved_world
        return [primary] + [r for r in range(saved_world)
                            if r != primary]

    def _restore_foreign(self, src_rank, step, model,
                         expect_manifest=None):
        """Restore from another rank's shard directory (read-only: no
        wreckage sweep — that dir may belong to a live writer). The
        peer's digest sidecar is verified exactly like our own."""
        src = CheckpointManager(
            os.path.join(self._root, f"rank{src_rank}"),
            max_to_keep=self._max_to_keep,
            save_interval_steps=self._save_interval_steps, sweep=False,
            digests=self._digests_on)
        try:
            out = src._restore_step(step, model, expect_manifest)
            # the data state is GLOBAL-stream state (rank-agnostic by
            # construction — see data.NumpyBatchIter), so the peer's
            # offset resumes this rank's derived shard exactly
            self._restored_data_state = src._restored_data_state
            return out
        finally:
            src.close()

    def _check_restored_data(self, step, src_rank, manifest):
        """Cross-check the just-restored data state against the digest
        rank ``src_rank`` ACKed into the commit marker. Raises
        :class:`~singa_tpu.integrity.IntegrityError` (driving the
        caller's next-source fallback) when the marker vouches for a
        data state this shard cannot produce."""
        want = (manifest.get("data_digests") or {}).get(str(src_rank))
        if not want:
            return        # pre-data-state marker, or a stateless run
        state = self._restored_data_state
        if state is None:
            raise IntegrityError(
                f"checkpoint step {step}: rank {src_rank} ACKed a "
                f"data state into the commit marker but its sidecar "
                "is missing — the resume offset cannot be trusted")
        got = data_state_digest(state)
        if got != want:
            raise IntegrityError(
                f"checkpoint step {step}: data-state digest {got} "
                f"does not match the cluster-committed {want} for "
                f"rank {src_rank} — stale or corrupt resume offset")

    def restore_latest(self, model):
        """Restore the newest CLUSTER-COMMITTED checkpoint and return
        the next step to run (0 when none exists). Local step dirs
        without a commit marker are wreckage from a writer that died in
        the commit hole — swept, exactly like the base class sweeps
        orbax-uncommitted dirs. On success ``self.restored_manifest``
        carries the marker's manifest (saved world size + batch extras)
        for the elastic-resume accounting."""
        import shutil
        t0 = time.perf_counter()
        self._join_digest_thread()
        self.restored_manifest = None
        self.restored_data_state = None
        committed = self.committed_steps()
        committed_set = set(committed)
        local = set(self._mgr.all_steps())
        wreck = sorted(s for s in local if s not in committed_set)
        if wreck:
            warnings.warn(
                f"sweeping {len(wreck)} locally-saved but cluster-"
                f"uncommitted checkpoint step(s) {wreck} (a rank died "
                "between shard-write and ACK)", stacklevel=2)
            for s in wreck:
                shutil.rmtree(os.path.join(self._dir, str(s)),
                              ignore_errors=True)
            self._reopen()
            local -= set(wreck)
        for i, step in enumerate(reversed(committed)):
            restored = False
            try:
                manifest = self.read_manifest(step)
            except (OSError, ValueError):
                continue                       # torn marker: not ours
            # the commit marker carries the CLUSTER-AGREED manifest
            # digest: _verify_restored checks each candidate shard
            # against it BEFORE any array lands in a live tensor, so a
            # stale/foreign shard wearing the right step number is
            # rejected without ever touching training state
            want = manifest.get("digest")
            for src in self._source_ranks(manifest):
                try:
                    if src == self.cluster.rank and step in local:
                        self._restore_step(step, model, want)
                    else:
                        self._restore_foreign(src, step, model, want)
                    self._check_restored_data(step, src, manifest)
                    restored = True
                    break
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    warnings.warn(
                        f"committed checkpoint step {step}: rank "
                        f"{src}'s shard is not restorable on rank "
                        f"{self.cluster.rank} ({type(e).__name__}: {e})"
                        "; trying the next source", stacklevel=2)
            if not restored:
                warnings.warn(
                    f"committed checkpoint step {step} is not "
                    f"restorable from any rank's shard; falling back "
                    "to the previous step", stacklevel=2)
                continue
            if i:
                # clear OUR newer (locally corrupt) shards so orbax's
                # should_save does not refuse the re-run window; the
                # markers stay — other ranks' shards may be intact
                newer = [s for s in local if s > step]
                for s in newer:
                    shutil.rmtree(os.path.join(self._dir, str(s)),
                                  ignore_errors=True)
                if newer:
                    self._reopen()
            self.restored_manifest = manifest
            self.restored_data_state = self._restored_data_state
            _obs_restore_done(t0, i)
            if int(manifest.get("world", self.cluster.world)) != \
                    self.cluster.world:
                warnings.warn(
                    f"elastic resume: checkpoint step {step} was saved "
                    f"at world size {manifest.get('world')}, restoring "
                    f"into world size {self.cluster.world} (state "
                    "re-sharded onto the current mesh)", stacklevel=2)
            return step + 1
        if committed:
            warnings.warn(
                f"none of the {len(committed)} committed checkpoints "
                "are restorable on this rank; starting from scratch",
                stacklevel=2)
            for s in local:
                shutil.rmtree(os.path.join(self._dir, str(s)),
                              ignore_errors=True)
            # the shared commit markers are deliberately LEFT in place:
            # this branch only proves the steps unreadable on THIS rank
            # (possibly a transient IO error), and deleting markers
            # would destroy checkpoints peers can still restore. Ranks
            # that disagree about the resume step fail loudly at the
            # trainer's resume barrier; markers whose shards rotate
            # away are pruned by _publish_commit.
            self._reopen()
        _obs_restore_done(t0, len(committed))
        return 0
