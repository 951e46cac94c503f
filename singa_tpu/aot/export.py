"""AOT executable export/restore: the durability side of cold-start
elimination.

:class:`AotStore` is the ``aot/`` sidecar beside a checkpoint
directory: per program, a serialized lowered+compiled executable
(``<program>.bin`` — ``jax.experimental.serialize_executable`` payload
plus its arg/result treedefs) and a digest-bearing manifest
(``<program>.json``, :mod:`.manifest`). The store follows the same
sidecar discipline as ``data_state/``: atomic writes, content digests,
scrubbed by ``CheckpointManager.scrub`` and
``tools/scrub_checkpoints.py``.

The load contract is **honored-or-refused**: :meth:`AotStore.
load_program` verifies the manifest against the live world (versions,
backend, topology, avals, donation, policy, byte digest) BEFORE
deserializing; any mismatch raises a typed
:class:`~singa_tpu.aot.manifest.AotMismatch` and
:meth:`AotStore.try_load_program` turns that into a LOUD
warn-quarantine-return-None — the caller compiles fresh. A stale
artifact never executes and never blocks a restart.

**Trust boundary**: artifacts are pickled serialized executables —
loading one executes whatever the bytes deserialize to. The crc32
digest detects *rot* (a flipped bit, a truncated write), NOT an
adversary: anyone who can write the ``aot/`` directory can rewrite
the manifest digest to match malicious bytes. Load only from
directories with the same write-trust as the checkpoints themselves
(which have the identical property — restored tensors drive training
— so an ``aot/`` sidecar beside them adds no new exposure; shipping
``prebuild`` artifacts from a build box extends that trust to the
build box).

Program-level helpers:

- :func:`export_train_step` / :func:`load_train_step` — the compiled
  train step of a single-device :class:`~singa_tpu.model.Model`
  (mesh-sharded steps are refused at export; they ride the persistent
  compile cache instead). ``load_train_step`` rebuilds the step record
  ``Model._run_step`` dispatches through — the restarted worker's
  first step replays the deserialized executable with ``n_traces``
  reading 1 (the one trace happened in the exporting process) and a
  ``compile_seconds{source="aot"}`` observation instead of a fresh
  compile.
- :func:`export_serving` — a :class:`~singa_tpu.serving.ServingEngine`
  's prefill and decode programs (the engine loads them itself at
  construction via ``aot_store=``). The export lowers FRESH jits of
  the adapter's raw program bodies, so the engine's CI-pinned trace
  counters are untouched.
"""

from __future__ import annotations

import json
import os
import pickle
import time
import warnings

import numpy as np

from ..observability import metrics as _metrics
from ..observability import spans as _spans
from . import manifest as _manifest
from .manifest import AotMismatch

# programs the store knows how to rebuild call records for
TRAIN_STEP = "train_step"
SERVE_PREFILL = "serve_prefill"
SERVE_DECODE = "serve_decode"
SERVE_BATCH = "serve_batch"


class AotExportError(RuntimeError):
    """A program cannot be exported from this object (mesh-sharded
    step, no compiled step yet, non-serializable static args...).
    Typed so callers can degrade to cache-only warm starts loudly."""


def _sds(a):
    import jax
    return jax.ShapeDtypeStruct(
        tuple(int(d) for d in np.shape(a)),
        a.dtype if hasattr(a, "dtype") else np.asarray(a).dtype)


def _tree_sds(tree):
    import jax
    return jax.tree_util.tree_map(_sds, tree)


# -- out-tree / static-layout round-trip -------------------------------------
# Model's _flatten trees are nested tuples (("T", i) | ("L"/"U", kids)
# | ("D", {k: kid})); JSON turns tuples into lists, so the decode side
# restores the exact tuple shape _unflatten expects.

def encode_tree(tree):
    kind = tree[0]
    if kind == "T":
        return ["T", int(tree[1])]
    if kind in ("L", "U"):
        return [kind, [encode_tree(k) for k in tree[1]]]
    return ["D", {k: encode_tree(v) for k, v in tree[1].items()}]


def decode_tree(doc):
    kind = doc[0]
    if kind == "T":
        return ("T", int(doc[1]))
    if kind in ("L", "U"):
        return (kind, [decode_tree(k) for k in doc[1]])
    return ("D", {k: decode_tree(v) for k, v in doc[1].items()})


def encode_layout(layout):
    """Canonical JSON string of a step's static-arg layout (Model
    ``_split_step_args``): tensor slots as ``["T"]``, static values as
    ``["V", value]``. Raises :class:`AotExportError` when a static arg
    is not JSON-representable — such a step cannot be matched to an
    artifact and must not be exported."""
    from ..model import _TensorSlot
    enc = []
    for el in layout:
        if isinstance(el, _TensorSlot):
            enc.append(["T"])
        else:
            enc.append(["V", el])
    try:
        return json.dumps(enc, sort_keys=True)
    except TypeError as e:
        raise AotExportError(
            f"static step argument is not JSON-representable ({e}); "
            "this signature cannot be exported") from None


class AotStore:
    """One ``aot/`` sidecar directory of digest-verified executables
    (module docstring). ``outcomes`` records what happened to each
    program this process touched (``exported`` / ``loaded`` /
    ``refused:<reason>``) — surfaced in trainer summaries and engine
    health."""

    QUARANTINE_DIR = "quarantine"

    def __init__(self, directory, registry=None):
        self.directory = os.path.abspath(str(directory))
        self._reg = registry if registry is not None \
            else _metrics.default_registry()
        self.outcomes = {}

    # -- paths -------------------------------------------------------------
    def _bin_path(self, program):
        return os.path.join(self.directory, f"{program}.bin")

    def _manifest_path(self, program):
        return os.path.join(self.directory, f"{program}.json")

    def programs(self):
        """Program names with a manifest on disk."""
        try:
            names = os.listdir(self.directory)
        except OSError:
            return []
        return sorted(n[:-5] for n in names if n.endswith(".json"))

    def inspect(self):
        """{program: manifest} for every artifact (unreadable
        manifests report as ``{"error": ...}`` instead of raising —
        this is the CLI's read path)."""
        out = {}
        for p in self.programs():
            try:
                out[p] = _manifest.read(self._manifest_path(p))
            except AotMismatch as e:
                out[p] = {"error": str(e)}
        return out

    def read_manifest(self, program):
        return _manifest.read(self._manifest_path(program))

    # -- save --------------------------------------------------------------
    def save_program(self, program, compiled, *, avals,
                     donate_argnums=(), policy=None, jax_device=None,
                     extra=None):
        """Serialize one compiled executable + its manifest, atomically
        (payload first, manifest last: a crash between the two leaves a
        manifest-less blob that reads as ``missing``, never a manifest
        vouching for absent bytes). Returns the manifest."""
        from jax.experimental import serialize_executable
        t0 = time.perf_counter()
        payload, in_tree, out_tree = serialize_executable.serialize(
            compiled)
        blob = pickle.dumps(
            {"payload": payload, "in_tree": in_tree,
             "out_tree": out_tree}, protocol=pickle.HIGHEST_PROTOCOL)
        doc = _manifest.build(program, blob, avals=avals,
                              donate_argnums=donate_argnums,
                              policy=policy, jax_device=jax_device,
                              extra=extra)
        os.makedirs(self.directory, exist_ok=True)
        bin_path = self._bin_path(program)
        tmp = f"{bin_path}.tmp-{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, bin_path)
        _manifest.write(self._manifest_path(program), doc)
        secs = time.perf_counter() - t0
        self._reg.counter(
            "aot_exports_total", "AOT artifacts serialized to disk",
            labels=("program",)).inc(program=program)
        self._reg.histogram(
            "aot_export_seconds",
            "serialize + write wall-clock of one AOT artifact"
        ).observe(secs)
        _spans.event("aot.export", program=program,
                     bytes=len(blob), seconds=round(secs, 4))
        self.outcomes[program] = "exported"
        return doc

    # -- load --------------------------------------------------------------
    def load_program(self, program, *, avals, donate_argnums=(),
                     policy=None, jax_device=None, expect_extra=None):
        """Verify-then-deserialize one program. Returns
        ``(callable, manifest)``; raises :class:`AotMismatch` on ANY
        mismatch (manifest axes, byte digest, or a payload jax itself
        refuses to deserialize — reason ``format``)."""
        doc = self.read_manifest(program)
        bin_path = self._bin_path(program)
        try:
            with open(bin_path, "rb") as f:
                blob = f.read()
        except OSError:
            raise AotMismatch(
                "missing", f"manifest present but no payload at "
                f"{bin_path}") from None
        _manifest.verify(doc, payload=blob, avals=avals,
                         donate_argnums=donate_argnums, policy=policy,
                         jax_device=jax_device,
                         expect_extra=expect_extra)
        import jax
        from jax.experimental import serialize_executable
        # every exportable program is single-device (sharded ones are
        # refused at export): load it for the device it was compiled
        # for — left to its default, jax loads the executable over ALL
        # local devices and the first call fails on the shard count
        target = jax_device if jax_device is not None else jax.devices()[0]
        try:
            parts = pickle.loads(blob)
            fn = serialize_executable.deserialize_and_load(
                parts["payload"], parts["in_tree"], parts["out_tree"],
                backend=target.client, execution_devices=[target])
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:      # noqa: BLE001 — refused, typed
            raise AotMismatch(
                "format", f"payload failed to deserialize on this "
                f"runtime ({type(e).__name__}: {e})") from None
        return fn, doc

    def try_load_program(self, program, **kw):
        """:meth:`load_program` under the honored-or-refused contract:
        on ANY mismatch, warn LOUDLY naming the axis, quarantine the
        stale artifact (except a merely-missing one), count the
        outcome, and return ``(None, None)`` so the caller compiles
        fresh. Returns ``(callable, manifest)`` on success."""
        t0 = time.perf_counter()
        try:
            fn, doc = self.load_program(program, **kw)
        except AotMismatch as e:
            self._reg.counter(
                "aot_loads_total", "AOT artifact load attempts",
                labels=("program", "outcome")).inc(
                    program=program, outcome=f"refused:{e.reason}")
            self.outcomes[program] = f"refused:{e.reason}"
            if e.reason != "missing":
                warnings.warn(
                    f"AOT artifact {program!r} in {self.directory} "
                    f"REFUSED — {e}; falling back to a fresh compile "
                    "and quarantining the artifact", stacklevel=3)
                _spans.event("aot.refused", program=program,
                             reason=e.reason, detail=str(e)[:300])
                self.quarantine(program, e.reason)
            return None, None
        secs = time.perf_counter() - t0
        self._reg.counter(
            "aot_loads_total", "AOT artifact load attempts",
            labels=("program", "outcome")).inc(program=program,
                                               outcome="loaded")
        self._reg.histogram(
            "aot_load_seconds",
            "verify + deserialize wall-clock of one AOT artifact"
        ).observe(secs)
        _spans.event("aot.load", program=program,
                     seconds=round(secs, 4))
        self.outcomes[program] = "loaded"
        return fn, doc

    # -- quarantine / scrub -------------------------------------------------
    def quarantine(self, program, reason):
        """Move a refused artifact (payload + manifest) into
        ``quarantine/`` with the refusal reason in the name — evidence
        for the post-mortem, out of the load path so the next restart
        does not re-refuse it. Never raises."""
        qdir = os.path.join(self.directory, self.QUARANTINE_DIR)
        stamp = f"{program}.{reason}.{os.getpid()}-{int(time.time())}"
        moved = 0
        for src, ext in ((self._bin_path(program), "bin"),
                         (self._manifest_path(program), "json")):
            if not os.path.exists(src):
                continue
            try:
                os.makedirs(qdir, exist_ok=True)
                os.replace(src, os.path.join(qdir, f"{stamp}.{ext}"))
                moved += 1
            except OSError:
                try:        # quarantine must WIN: a stale artifact
                    os.remove(src)   # left in place would re-refuse
                    moved += 1       # (or worse, re-verify) forever
                except OSError:
                    pass
        if moved:
            self._reg.counter(
                "aot_artifacts_quarantined_total",
                "stale/corrupt AOT artifacts moved out of the load "
                "path", labels=("reason",)).inc(reason=reason)
        return moved

    def scrub(self, delete=False):
        """At-rest verification of every artifact's bytes against its
        manifest digest (the digest axis ONLY — version/backend/aval
        axes are load-time concerns relative to the loading process;
        bytes rotting on disk is the scrub concern, and a CPU-side
        scrubber must not demote a healthy TPU artifact). Returns
        {program: "ok"|"corrupt"|"unreadable"}; ``delete=True``
        quarantines the bad ones."""
        from ..integrity import bytes_digest
        report = {}
        for program in self.programs():
            try:
                doc = self.read_manifest(program)
                with open(self._bin_path(program), "rb") as f:
                    blob = f.read()
            except (AotMismatch, OSError) as e:
                warnings.warn(
                    f"aot scrub: artifact {program!r} is unreadable "
                    f"({e})", stacklevel=2)
                report[program] = "unreadable"
                continue
            if bytes_digest(blob) == doc.get("digest"):
                report[program] = "ok"
            else:
                warnings.warn(
                    f"aot scrub: artifact {program!r} FAILED its "
                    f"content-digest check (recorded "
                    f"{doc.get('digest')})", stacklevel=2)
                report[program] = "corrupt"
        if delete:
            for program, status in report.items():
                if status in ("corrupt", "unreadable"):
                    self.quarantine(program, status)
        return report


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def _current_step_rec(model):
    rec = getattr(model, "_last_run_rec", None)
    if rec is None or rec.get("jit") is None or "avals" not in rec:
        rec = next((r for r in model._steps.values()
                    if r.get("jit") is not None and "avals" in r), None)
    return rec


def _state_names(model):
    """Canonical name per threaded-state position (the checkpoint
    name space: ``model/...`` / ``optimizer/...``), or None when any
    entry is unnameable/ambiguous. Recorded in the train-step manifest
    because the threaded-state ORDER is a process accident: a fresh
    trace materialises optimizer aux in backward order while a
    restored process materialises it in checkpoint order — same
    tensors, different positions. The loader uses the names to restore
    the exporting process's order before binding the executable."""
    from ..checkpoint import _state_tensor_dict
    by_id = {id(t): name
             for name, t in _state_tensor_dict(model).items()}
    names = [by_id.get(id(t)) for t in model._state_list]
    if None in names or len(set(names)) != len(names):
        return None
    return names


def export_train_step(model, store, *, skip_if_current=False):
    """Serialize the model's compiled train step into ``store``.

    Refused typed (:class:`AotExportError`) for mesh-sharded models
    (``shard_map`` executables are topology-bound; the persistent
    compile cache is their warm-start path) and before any compiled
    step exists. ``skip_if_current=True`` skips the (re-lower +
    serialize) work when the on-disk artifact already matches the live
    program on every manifest axis except the byte digest — the
    restarted-then-re-exporting steady state."""
    if getattr(model, "_dist", None) is not None:
        raise AotExportError(
            "mesh-sharded train steps are not exportable (topology-"
            "bound shard_map executable); the persistent compile "
            "cache is the warm-start path for distributed models")
    rec = _current_step_rec(model)
    if rec is None:
        raise AotExportError(
            "no compiled train step to export: run one training batch "
            "in graph mode first")
    key = next((k for k, r in model._steps.items() if r is rec), None)
    if key is None or not isinstance(key, tuple):
        raise AotExportError(
            "the compiled step's static-arg layout is not hashable/"
            "encodable; cannot stamp a matching manifest")
    layout_doc = encode_layout(key)
    names = _state_names(model)
    if names is None:
        raise AotExportError(
            "threaded state is not uniquely nameable (anonymous or "
            "aliased state tensors); cannot stamp a manifest a "
            "restarted process could match")
    state_avals, rng_aval, in_avals = rec["avals"]
    avals = (list(state_avals), rng_aval, list(in_avals))
    policy = getattr(model, "_policy", None)
    jax_device = getattr(getattr(model, "dev", None), "jax_device",
                         None)
    extra = {"layout": layout_doc, "state_names": names,
             "out_tree": encode_tree(rec["out_tree"]["tree"])}
    if skip_if_current:
        try:
            _manifest.verify(store.read_manifest(TRAIN_STEP),
                             avals=avals, donate_argnums=(),
                             policy=policy, jax_device=jax_device,
                             expect_extra={"layout": layout_doc,
                                           "state_names": names})
            store.outcomes.setdefault(TRAIN_STEP, "current")
            return None          # artifact already matches this program
        except AotMismatch:
            pass
    # the EXPORTED twin is compiled WITHOUT state donation: a
    # deserialized executable's baked-in input/output aliasing frees
    # donated buffers underneath live python references (observed as
    # heap corruption on jaxlib's experimental serialize path), so the
    # artifact trades the in-place state update for memory safety —
    # the warm-restarted step briefly holds 2x state, which is the
    # price of skipping the whole trace+compile. One extra trace in
    # THIS process (n_traces legitimately +1); the loading process
    # never traces at all.
    import jax
    body = getattr(rec["jit"], "__wrapped__", None)
    if body is None:
        raise AotExportError(
            "the compiled step does not expose its traced body "
            "(non-jit executable?); cannot build the non-donating "
            "export twin")
    compiled = jax.jit(body).lower(state_avals, rng_aval,
                                   *in_avals).compile()
    return store.save_program(
        TRAIN_STEP, compiled, avals=avals, donate_argnums=(),
        policy=policy, jax_device=jax_device, extra=extra)


def load_train_step(model, store, layout, input_arrays):
    """Rebuild a dispatchable step record from the stored artifact, or
    return None (refusal already warned/quarantined/counted by the
    store). Called from ``Model._run_step`` at the point a fresh
    signature would otherwise trace+compile; the model's state is
    already materialised (the abstract first-step rehearsal ran)."""
    if getattr(model, "_dist", None) is not None:
        return None
    try:
        layout_doc = encode_layout(layout)
    except AotExportError:
        return None
    t0 = time.perf_counter()
    model._ensure_state()
    names = _state_names(model)
    if names is None:
        return None
    try:
        pre = store.read_manifest(TRAIN_STEP)
    except AotMismatch as e:
        if e.reason == "missing":
            store.outcomes[TRAIN_STEP] = "refused:missing"
            return None        # nothing to load: quiet, like try_load
        pre = None             # unreadable: try_load refuses loudly
    want = (pre or {}).get("state_names")
    if want and names != want:
        if sorted(names) != sorted(want) or model._steps:
            # different state SET (architecture/optimizer changed —
            # the aval/signature verify below refuses it loudly), or
            # other compiled signatures are already bound to the
            # current order and must not be re-ordered under them
            want = None
        else:
            # same tensors, different positions (fresh-trace backward
            # order vs restored checkpoint order): restore the
            # exporting process's order. A NEW list — never an
            # in-place sort — so nothing that captured the old list
            # object can see a reordering.
            by_name = dict(zip(names, model._state_list))
            model._state_list = [by_name[n] for n in want]
            names = want
    state_arrays = [t.data for t in model._state_list]
    rng = model.dev.current_key()
    avals = ([_sds(a) for a in state_arrays], _sds(rng),
             [_sds(a) for a in input_arrays])
    fn, doc = store.try_load_program(
        TRAIN_STEP, avals=avals, donate_argnums=(),
        policy=getattr(model, "_policy", None),
        jax_device=getattr(model.dev, "jax_device", None),
        expect_extra={"layout": layout_doc, "state_names": names})
    if fn is None:
        return None
    from ..observability import perf as _perf
    sig = _perf.step_signature(input_arrays)
    _perf.record_compile(TRAIN_STEP, time.perf_counter() - t0, sig,
                         source="aot")
    # the record Model._run_step dispatches through: the one trace
    # happened in the exporting process, so n_traces READS 1 here and
    # the steady-state pin (no further traces) still holds
    return {"jit": fn, "builder": None,
            "out_tree": {"tree": decode_tree(doc["out_tree"])},
            "leaf_specs": None, "input_specs": None,
            "n_traces": 1, "aot": True, "arg_sig": sig}


# ---------------------------------------------------------------------------
# serving programs
# ---------------------------------------------------------------------------

def serving_program_avals(engine):
    """The prefill/decode call avals of a ServingEngine, derived from
    its live params/cache and geometry — the ONE definition both
    export and engine-side load share, so they can never drift. Both
    KV layouts are described: the ring's slot-array programs and the
    paged block pool's chunked-prefill/verify programs (tables +
    absolute positions; the verify width K is ``speculative_k`` or
    1)."""
    Pa = _tree_sds(engine._P)
    Ca = _tree_sds(engine._cache)
    import jax
    B, S, W = engine.prefill_batch, engine.prefill_len, engine.slots
    i32 = np.dtype(np.int32)
    if getattr(engine, "kv_layout", "ring") == "paged":
        npages = engine._max_blocks
        K = engine._spec_width
        prefill = (Pa, Ca, jax.ShapeDtypeStruct((B, npages), i32),
                   jax.ShapeDtypeStruct((B, S), i32),
                   jax.ShapeDtypeStruct((B,), i32),
                   jax.ShapeDtypeStruct((B,), i32),
                   jax.ShapeDtypeStruct((B,), np.dtype(bool)))
        decode = (Pa, Ca, jax.ShapeDtypeStruct((W, npages), i32),
                  jax.ShapeDtypeStruct((W, K), i32),
                  jax.ShapeDtypeStruct((W,), i32),
                  jax.ShapeDtypeStruct((W,), i32))
        return prefill, decode
    prefill = (Pa, Ca, jax.ShapeDtypeStruct((B, S), i32),
               jax.ShapeDtypeStruct((B,), i32),
               jax.ShapeDtypeStruct((B,), i32),
               jax.ShapeDtypeStruct((B,), np.dtype(bool)))
    # (prev_tokens, tokens, fresh, positions, active): RingLayout.programs
    decode = (Pa, Ca, jax.ShapeDtypeStruct((W,), i32),
              jax.ShapeDtypeStruct((W,), i32),
              jax.ShapeDtypeStruct((W,), np.dtype(bool)),
              jax.ShapeDtypeStruct((W,), i32),
              jax.ShapeDtypeStruct((W,), np.dtype(bool)))
    return prefill, decode


def serving_geometry(engine):
    """The engine-geometry manifest stamp (``expect_extra``): an
    artifact exported at different slots/lengths — or a different KV
    LAYOUT (a ring executable honored by a paged engine would be a
    silently wrong program) — must refuse with reason ``signature``
    even before the aval diff names it, and so must one whose programs
    return another form of output (``outputs``: the avals are of the
    inputs and cannot tell). Paged manifests additionally carry the
    pool geometry (``kv_block_size``/``kv_blocks``) and the verify
    width."""
    geo = {"slots": engine.slots,
           "max_len": engine.max_len,
           "prefill_len": engine.prefill_len,
           "prefill_batch": engine.prefill_batch,
           "kv_layout": getattr(engine, "kv_layout", "ring"),
           # what the programs return beside the state: an artifact of
           # a build that returned the bare logits carries no such key
           # and is refused, never loaded and unpacked wrongly
           "outputs": engine._layout.outputs(engine.sharded)}
    if geo["kv_layout"] == "paged":
        geo.update(kv_block_size=engine.kv_block_size,
                   kv_blocks=engine.kv_blocks,
                   speculative_k=int(getattr(engine, "speculative_k",
                                             0)))
    return {"engine": geo}


def batch_program_avals(engine):
    """The fixed-width forward's call avals of a BatchServingEngine
    (threaded state + the padded input batch) — shared by export and
    engine-side load. State ORDER is stable here by construction:
    both processes materialise it through the same one eager forward
    at engine build, unlike the trainer's restore path."""
    import jax
    state_avals = [_sds(a) for a in engine._state_arrays]
    x_aval = jax.ShapeDtypeStruct(
        (engine.batch,) + engine.input_shape, engine.input_dtype)
    return (state_avals, x_aval)


def batch_geometry(engine):
    return {"engine": {"batch": engine.batch,
                       "input_shape": list(engine.input_shape),
                       "input_dtype": str(engine.input_dtype)}}


def export_serving(engine, store):
    """Serialize a serving engine's compiled programs: the
    autoregressive ServingEngine's prefill/decode split, or the
    stateless BatchServingEngine's one fixed-width forward.

    Lowers FRESH jits of the raw program bodies (not the engines'
    counting wrappers), so the CI-pinned ``n_traces`` counters are
    untouched by an export. Returns {program: manifest}."""
    import jax
    from ..serving.engine import BatchServingEngine, ServingEngine
    dev = getattr(engine, "_hbm_dev", None)
    if isinstance(engine, BatchServingEngine):
        body = getattr(engine._fwd, "__wrapped__", None)
        if body is None:
            raise AotExportError(
                "the batch forward does not expose its traced body; "
                "cannot export")
        avals = batch_program_avals(engine)
        compiled = jax.jit(body).lower(*avals).compile()
        return {SERVE_BATCH: store.save_program(
            SERVE_BATCH, compiled, avals=avals, donate_argnums=(),
            policy=engine.policy, jax_device=dev,
            extra=batch_geometry(engine))}
    if not isinstance(engine, ServingEngine):
        raise AotExportError(
            f"{type(engine).__name__} is not AOT-exportable")
    if getattr(engine, "sharded", False):
        d = engine._part.describe()
        raise AotExportError(
            f"sharded serving programs are not exportable: the "
            f"NamedSharding executables are bound to this mesh "
            f"(batch={d['batch']} × model={d['model']}); the "
            "persistent compile cache is their warm-start path")
    prefill_avals, decode_avals = serving_program_avals(engine)
    geometry = serving_geometry(engine)
    # the programs the engine runs, from the one place it picks them
    prefill_raw, decode_raw = engine._layout.programs(engine.sharded)
    out = {}
    for program, raw, avals in (
            (SERVE_PREFILL, prefill_raw, prefill_avals),
            (SERVE_DECODE, decode_raw, decode_avals)):
        compiled = jax.jit(raw, donate_argnums=(1,)).lower(
            *avals).compile()
        out[program] = store.save_program(
            program, compiled, avals=avals, donate_argnums=(1,),
            policy=engine.policy, jax_device=dev, extra=geometry)
    return out


__all__ = ["AotStore", "AotExportError", "TRAIN_STEP", "SERVE_PREFILL",
           "SERVE_DECODE", "SERVE_BATCH", "export_train_step",
           "load_train_step", "export_serving",
           "serving_program_avals", "serving_geometry",
           "batch_program_avals", "batch_geometry", "encode_tree",
           "decode_tree", "encode_layout"]
