"""Model API: trace-once-then-replay training steps on XLA.

Capability parity with the reference Model (python/singa/model.py): the user
subclasses :class:`Model`, defines ``forward`` and ``train_one_batch``, calls
``compile`` once, then ``model(tx, ty)`` per step. In the reference, graph
mode buffers ops into the C++ Graph on the first call and replays it after
(ModelMeta.buffer_operation, model.py:39-100); here graph mode *is*
``jax.jit``:

- call 1 runs eagerly, materialising deferred layer params and optimizer aux
  state (the reference's trace-with-graph-enabled pass);
- call 2 traces ``train_one_batch`` — forward, the autograd tape's backward,
  and the optimizer update — into ONE XLA computation with all mutable state
  (params, BN running stats, optimizer moments) threaded functionally and
  donated, so XLA buffer-assignment reproduces the Graph's memory recycling
  (scheduler.cc:671-688) and its topological scheduling for free;
- later calls replay the compiled executable.

Distributed, two generations:

- legacy (``DistOpt`` without ``compile(mesh=)``): the compiled step is
  ``shard_map``'d over the mesh 'data' axis — inputs batch-sharded, state
  replicated — and the per-gradient ``psum`` calls inside the tape become
  ICI all-reduces that XLA overlaps with remaining backward compute (the
  TPU form of the reference's stream-overlap design, opt.py:826-865);
- GSPMD (``compile(mesh=...)`` / ``fsdp_axis=`` / ``DistOpt(zero=True)``):
  the SAME step body jitted once with NamedSharding in/out annotations
  from ``parallel/gspmd.py`` — no shard_map, no hand-written psum (the
  communicator is identity outside its collective context); XLA's SPMD
  partitioner inserts the gradient all-reduces, and under FSDP shards
  optimizer state + masters over 'data' with just-in-time gathers
  (reduce-scatter grads → sharded update → all-gather params).
"""

from __future__ import annotations

import io
import json
import time
import zipfile

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from .tensor import Tensor
from .layer import Layer
from .autograd_base import CTX
from . import device as device_mod


def _aot_cache_snapshot():
    """Persistent-compile-cache hit/miss counters BEFORE a dispatch
    that may trace — two dict reads through a cached module ref, so the
    steady-state step path pays nothing measurable."""
    global _aot_cache_mod
    if _aot_cache_mod is None:
        from .aot import cache
        _aot_cache_mod = cache
    return _aot_cache_mod.snapshot()


_aot_cache_mod = None


class _TensorSlot:
    """Marker for a traced-tensor position in a step-arg layout (distinct
    from a static ``None`` arg such as the default ``spars``)."""

    def __repr__(self):
        return "<tensor>"


_TENSOR = _TensorSlot()


def _batch_dim_axes(input_specs, default_axis):
    """Mesh axes the batch (dim 0 of the first input) is sharded over —
    the correct default out-spec for batch-leading output leaves."""
    if input_specs:
        spec = input_specs[0]
        if len(spec) > 0 and spec[0] is not None:
            return spec[0]
    return default_axis


def _mesh_step_context(mesh, input_specs, axis):
    """Context both step bodies (train and eval) enter: register every
    mesh axis for collectives AND declare which axes shard the batch
    (read by cross-replica statistics like sync-BN). One shared helper so
    the two bodies can never derive different batch axes."""
    import contextlib

    from .parallel.communicator import batch_shard_axes, collective_context

    stack = contextlib.ExitStack()
    stack.enter_context(collective_context(*mesh.axis_names))
    stack.enter_context(batch_shard_axes(
        _batch_dim_axes(input_specs or [], axis)))
    return stack


def _resolve_leaf_specs(leaves, full_batch, input_specs, axis, user_out):
    """Default per-output-leaf layouts, shared by the train and eval
    builders: a user-supplied spec list wins; otherwise batch-leading
    leaves shard like the input batch dim (which may span several mesh
    axes, e.g. ('data','expert') for MoE — P('data') alone would
    mis-stitch those outputs) and everything else replicates.

    Leaves are already arrays (or array-shaped zeros from the abstract
    rehearsal); only their host metadata is read — no jnp.asarray, no
    device round-trip on the compile path."""
    if user_out is not None:
        return list(user_out)
    shapes = [x.shape if hasattr(x, "shape") else np.shape(x)
              for x in leaves]
    shard_mask = [len(s) >= 1 and s[0] == full_batch for s in shapes]
    batch_ax = _batch_dim_axes(input_specs, axis)
    return [P(batch_ax) if m else P() for m in shard_mask]


def _fit_state_spec(spec, shape, mesh):
    """Spec-to-mesh fitting now lives in the ONE sharding vocabulary
    (``parallel/gspmd.py`` — an indivisible dim falls back to
    replication and the layers' offset math detects the full-width
    tensor); this alias keeps the compiled-step and checkpoint
    live-sharding call sites unchanged. Lazy import: parallel pulls the
    layer stack in, and model.py is imported before it."""
    from .parallel.gspmd import fit_state_spec
    return fit_state_spec(spec, shape, mesh)


def _flatten(obj, leaves):
    """Flatten nested tuples/lists/dicts of Tensors into arrays + treedef."""
    if isinstance(obj, Tensor):
        leaves.append(obj.data)
        return ("T", len(leaves) - 1)
    if isinstance(obj, (list, tuple)):
        kids = [_flatten(o, leaves) for o in obj]
        return ("L" if isinstance(obj, list) else "U", kids)
    if isinstance(obj, dict):
        return ("D", {k: _flatten(v, leaves) for k, v in obj.items()})
    leaves.append(jnp.asarray(obj))
    return ("T", len(leaves) - 1)


def _unflatten(tree, leaves, device):
    kind, val = tree
    if kind == "T":
        return Tensor(data=leaves[val], device=device, requires_grad=False)
    if kind == "U":
        return tuple(_unflatten(k, leaves, device) for k in val)
    if kind == "L":
        return [_unflatten(k, leaves, device) for k in val]
    return {k: _unflatten(v, leaves, device) for k, v in val.items()}


class Model(Layer):
    """Base user model (reference python/singa/model.py Model).

    Mesh layout hooks (all optional class/instance attributes):

    - ``input_specs``: per-input PartitionSpec list for the compiled
      train step (default: batch dim over the DistOpt axis).
    - ``output_specs``: per-output-leaf specs for the train step.
    - ``eval_output_specs``: per-output-leaf specs for the SHARDED eval
      path. Without it, batch-leading leaves shard like the input batch
      and every other leaf is ``pmean``'d over the reduce axes — correct
      for mean-type outputs (losses, accuracies averaged in-model), but
      it would divide SUM-type outputs (per-batch counts, summed
      errors) by the world size relative to the gathered eager path.
    - ``eval_output_reduce``: per-leaf ``"mean"``/``"sum"`` list
      selecting how replicated (non-batch-leading) eval leaves combine
      across shards (default ``"mean"``). Models whose eval returns
      per-batch sums set ``"sum"`` for those leaves to keep sharded and
      eager eval numerically identical.
    """

    def __init__(self):
        super().__init__()
        self.graph_mode = True
        self.sequential = False
        self._train = False
        self.dev = None
        self._compiled = False
        self._step_ready = False   # first (eager) train call done
        self._steps = {}           # static-arg signature -> compiled step
        self._state_list = None
        self._dist = None
        self._gspmd_mesh = None    # compile(mesh=...) → GSPMD train step
        self._fsdp_axis = None     # ZeRO/FSDP shard axis (GSPMD only)
        self._policy = None        # mixed_precision.Policy (compile arg)
        self._step_count = 0
        self._eval_steps = {}      # input signature -> compiled eval step
        self.step_times = []

    # -- user hooks --------------------------------------------------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def train_one_batch(self, *args, **kwargs):
        raise NotImplementedError

    def _migrate_masters(self, new_policy):
        """Recompiling across a param-dtype change (pure-bf16 ->
        bf16_mixed, or back to an explicit 16-bit master policy): cast
        already-materialised trainable params — and the optimizer aux
        that mirrors them (momentum/moments/residuals) — to the new
        master dtype, so the live state matches what the new policy
        reports and checkpoints. Non-trainable state (BN running stats,
        guard counters/shadows) keeps its own dtype; 16->32 is
        lossless, 32->16 is the destination policy's own quantisation."""
        pd = new_policy.param_dtype if new_policy is not None else None
        if pd is None:
            return

        def _adapt(t):
            if not isinstance(t.data, jax.core.Tracer) and \
                    jnp.issubdtype(t.dtype, jnp.floating) and \
                    t.dtype != pd:
                t.data = t.data.astype(pd)

        for t in self.get_states().values():
            if t.requires_grad:
                _adapt(t)
        opt0 = getattr(self, "optimizer", None)
        if opt0 is not None and hasattr(opt0, "state_tensor_dict"):
            for k, t in opt0.state_tensor_dict().items():
                # per-param aux is named '<param>:<kind>' (residuals
                # 'residual/<param>'); scalars and guard shadows are not
                if ":" in k.rsplit("/", 1)[-1] or \
                        k.startswith("residual/"):
                    _adapt(t)

    def _policy_companion(self, optimizer):
        """Pair a 16-bit precision policy with dynamic loss scaling: the
        promised-automatic GuardedOptimizer wrap, applied wherever the
        optimizer meets the policy — compile(policy=...) over an
        existing optimizer OR set_optimizer called after compile. An
        optimizer already guarded (has dynamic_loss_scale) keeps its own
        configuration."""
        pol = getattr(self, "_policy", None)
        wants = pol is not None and pol.wants_loss_scaling
        mark = vars(optimizer).get("_policy_companion_wrap") \
            if optimizer is not None else None
        if mark is not None and (not wants or mark != pol):
            # undo OUR wrap (never a user's) when the policy stops
            # wanting scaling (loss_scaling=False recompile) or changed
            # contract (bf16_mixed -> float16_mixed must re-derive its
            # init scale, not inherit the old policy's); the same
            # policy keeps the wrap AND its adapted scale state
            optimizer = optimizer.inner
        if (wants and optimizer is not None
                and not hasattr(optimizer, "dynamic_loss_scale")):
            from .resilience import GuardedOptimizer
            optimizer = GuardedOptimizer.for_policy(optimizer, pol)
            optimizer._policy_companion_wrap = pol
        return optimizer

    def set_optimizer(self, optimizer):
        optimizer = self._policy_companion(optimizer)
        self.optimizer = optimizer
        if hasattr(optimizer, "bind_model"):
            # guards (resilience.GuardedOptimizer) shadow model state the
            # optimizer never sees (BN running stats) — hand them the model
            optimizer.bind_model(self)

    # -- modes -------------------------------------------------------------
    def train(self, mode=True):
        self._train = mode
        CTX.training = mode

    def eval(self):
        self.train(False)

    def graph(self, mode=True, sequential=False):
        """Enable/disable compiled-graph execution
        (reference model.py graph())."""
        self.graph_mode = mode
        self.sequential = sequential

    # -- compile -----------------------------------------------------------
    def compile(self, inputs, is_train=True, use_graph=False,
                sequential=False, policy=None, compile_cache=None,
                mesh=None, fsdp_axis=None):
        """Shape-infer via a dry forward run (reference model.py:156-184),
        decide graph (jit) mode, and detect a distributed optimizer.

        ``mesh``: a named :class:`jax.sharding.Mesh` (e.g.
        ``parallel.gspmd.train_mesh(data=8)``) switching the compiled
        train step onto the GSPMD path: ONE jitted program whose
        state/batch arguments carry explicit NamedShardings from the
        ``parallel/gspmd.py`` spec vocabulary — no shard_map wrapper,
        no hand-written psum; XLA's SPMD partitioner inserts the
        gradient all-reduces. Bitwise-parity-pinned against the legacy
        shard_map DP driver (the CI multichip leg).

        ``fsdp_axis``: ZeRO/FSDP memory layout on the GSPMD path —
        params, fp32 masters and optimizer aux sharded over this mesh
        axis (``True`` means ``'data'``) and gathered just-in-time
        inside the program (XLA emits reduce-scatter grads → sharded
        update → all-gather params), ~N× optimizer-state headroom per
        chip. Implied by a ``DistOpt(zero=True)`` optimizer; with no
        explicit ``mesh`` the default data mesh of the model's
        platform is used.

        ``policy``: a :class:`singa_tpu.mixed_precision.Policy` (or its
        name, e.g. ``"bf16_mixed"``) activating mixed-precision compile:
        parameters are created/updated as fp32 masters, matmul/conv/
        attention cast their operands to the compute dtype INSIDE the
        jitted step (one fused XLA program; donation of the fp32 state
        is unchanged), fragile ops (norm stats, softmax/loss reductions)
        stay fp32, and floating output leaves are cast back to the
        policy's output dtype at the step boundary. A 16-bit policy is
        paired with dynamic loss scaling by default: a plain optimizer
        is wrapped in ``resilience.GuardedOptimizer`` here (pass
        ``Policy(name, loss_scaling=False)`` or pre-wrap yourself to
        opt out).

        ``compile_cache``: a :class:`singa_tpu.aot.CachePolicy` (or a
        cache directory, or True for the rule's directory —
        ``JAX_COMPILATION_CACHE_DIR`` when set, which also overrides a
        directory named here, else ``<checkout>/.jax_compile_cache``)
        installing JAX's persistent compilation cache process-wide, so
        a restart of this same program deserializes its executables
        instead of recompiling — every traced dispatch then labels its
        ``compile_seconds`` observation ``source="cache"`` or
        ``"fresh"``. Process-global by nature (it is ONE jax config);
        routed through here so the policy travels with the compile
        call that benefits."""
        assert len(inputs) > 0
        from .observability import metrics as _obs_metrics
        from .observability import spans as _obs_spans
        if compile_cache is not None:
            from .aot import cache as _aot_cache
            _aot_cache.install(compile_cache)
        t0 = time.perf_counter()
        with _obs_spans.span("compile", policy=str(policy)):
            self._compile_body(inputs, is_train, use_graph, sequential,
                              policy, mesh=mesh, fsdp_axis=fsdp_axis)
        _obs_metrics.default_registry().histogram(
            "model_compile_seconds",
            "Model.compile wall-clock (dry run + shape inference; the "
            "XLA trace/compile itself lands on the first step)"
        ).observe(time.perf_counter() - t0)

    def compile_serving(self, policy=None, **kw):
        """Build this model's inference engine (``singa_tpu.serving``):
        the serving sibling of :meth:`compile`.

        Autoregressive models (anything exposing ``decode_adapter`` —
        the transformer and char-rnn zoo models) get a continuous-
        batching :class:`~singa_tpu.serving.ServingEngine`: two
        AOT-compiled fixed-shape programs (batched prefill writing a
        donated ring KV cache; a one-token O(1) decode step) over a
        ``slots``-wide in-flight slot array. Everything else — the
        classifier zoo, ONNX imports through ``sonnx.SONNXModel`` —
        serves through a fixed-width
        :class:`~singa_tpu.serving.BatchServingEngine` (pass
        ``input_shape=`` for the per-sample shape).

        ``policy``: a mixed-precision :class:`Policy` or name
        (``"bf16_mixed"`` serves in bf16 compute with an f32 head/
        logits). Defaults to the policy this model was last
        ``compile``d with, so a bf16-trained model serves bf16 out of
        the box. The engine is returned un-started; call ``.start()``
        for the background loop or drive ``step()`` synchronously.
        Other ``kw`` (``slots``, ``max_len``, ``prefill_len``,
        ``queue_capacity``, ``faults``, ``registry``, ...) pass through
        to the engine.

        Sharded serving (``singa_tpu.parallel.gspmd``):
        ``model_shards=N`` (or an explicit ``mesh=`` with named
        ``batch``/``model`` axes) runs the prefill/decode programs
        tensor/vocab-sharded over a (batch × model) device mesh as the
        SAME single jitted programs — params/KV annotated with
        NamedSharding, XLA inserts the collectives, greedy argmax
        computed in graph over the vocab shards. Configs the mesh
        cannot honor (indivisible heads/vocab/slots, too few devices)
        are typed declines at build.

        Cold-start knobs (``singa_tpu.aot``): ``compile_cache=``
        installs the persistent compilation cache exactly like
        :meth:`compile`'s; ``aot_store=`` (an
        :class:`~singa_tpu.aot.AotStore` or its directory) makes the
        engine deserialize previously exported prefill/decode
        executables instead of tracing — honored-or-refused against
        the artifact manifests — and is where
        ``engine.export_aot()`` writes."""
        from . import mixed_precision as mp
        from .serving import build_engine
        compile_cache = kw.pop("compile_cache", None)
        if compile_cache is not None:
            from .aot import cache as _aot_cache
            _aot_cache.install(compile_cache)
        pol = mp.resolve(policy) if policy is not None \
            else getattr(self, "_policy", None)
        return build_engine(self, policy=pol, **kw)

    def _compile_body(self, inputs, is_train, use_graph, sequential,
                      policy, mesh=None, fsdp_axis=None):
        from . import mixed_precision as mp
        new_policy = mp.resolve(policy)
        if new_policy != getattr(self, "_policy", None):
            # a RE-compile under a different policy must not replay
            # executables traced under the old one (they'd silently run
            # the old precision while every surface reports the new),
            # and params the old policy already materialised — the dry
            # run below creates them on the FIRST compile — move to the
            # new master dtype. Both are no-ops on a fresh model.
            self._invalidate_compiled()
            self._step_ready = False
            self._migrate_masters(new_policy)
        self._policy = new_policy
        opt0 = getattr(self, "optimizer", None)
        if opt0 is not None:
            # loss scaling is the default companion of a 16-bit policy:
            # re-route the existing optimizer through set_optimizer so
            # the _policy_companion wrap applies (set_optimizer called
            # AFTER compile hits the same wrap there)
            self.set_optimizer(opt0)
        self.dev = inputs[0].device
        self.graph_mode = use_graph
        self.sequential = sequential
        prev = CTX.training
        CTX.training = False
        try:
            # abstract dry run: layer.initialize still executes (params
            # materialise concretely — under a policy, as its master
            # dtype) but the inter-layer compute traces with zero device
            # work — an eager dry run costs one device dispatch PER OP
            self._abstract_call(inputs, lambda: self.forward(*inputs))
        except Exception as e:
            import warnings
            warnings.warn(
                f"abstract dry run failed ({type(e).__name__}: {e}); "
                "falling back to an eager forward — host-side effects in "
                "forward may have run twice", stacklevel=2)
            with self._policy_scope():
                self.forward(*inputs)
        finally:
            CTX.training = prev
        # name params/states now so optimizer aux keys are stable between
        # the eager first step and the traced step
        for name, t in self.get_states().items():
            t.name = t.name or name
        opt = getattr(self, "optimizer", None)
        from .opt import DistOpt
        if isinstance(opt, DistOpt):
            self._dist = opt
        elif isinstance(getattr(opt, "inner", None), DistOpt):
            # a wrapper (e.g. resilience.GuardedOptimizer) around a
            # DistOpt: the mesh/collective plumbing keys off the DistOpt
            self._dist = opt.inner
        if fsdp_axis is True:
            from .parallel.gspmd import DATA_AXIS
            fsdp_axis = DATA_AXIS
        if fsdp_axis is None and self._dist is not None and \
                getattr(self._dist, "zero", False):
            # DistOpt(zero=True) is the optimizer-side spelling of
            # compile(fsdp_axis=...): same GSPMD+FSDP program
            fsdp_axis = self._dist.axis_name
        if (mesh, fsdp_axis) != (self._gspmd_mesh, self._fsdp_axis) \
                and self._steps:
            # a re-compile that changes the partitioning mode must not
            # replay executables built for the old layout
            self._invalidate_compiled()
        self._gspmd_mesh = mesh
        self._fsdp_axis = fsdp_axis
        self._compiled = True
        self.train(is_train)

    def _policy_scope(self):
        """The model's precision-policy scope: entered inside every
        traced body (train step, eval step, abstract rehearsal) AND the
        eager fallbacks, so op-level compute casts and param creation
        see one consistent policy wherever the model's code runs —
        including a watchdog worker thread (the scope is entered inside
        the body, so no ContextVar propagation is needed). Nullcontext
        when the model was compiled without a policy.

        A weight-quantized model (``quant.quantize_params``) also
        enters its dequant scope here: int8 payloads are rebound to
        their in-graph dequantized values for the body's duration, so
        every path — eager, compiled, serving — consumes fp32 weights
        while the threaded/stored state stays int8."""
        import contextlib
        from . import mixed_precision as mp
        stack = contextlib.ExitStack()
        stack.enter_context(mp.policy_scope(getattr(self, "_policy",
                                                    None)))
        if getattr(self, "_quant_pairs", None):
            from .quant import core as _qcore
            stack.enter_context(_qcore.dequant_params_scope(self))
        return stack

    def get_states(self):
        """Layer state walk, plus the per-channel quantization scales a
        weight-quantized model carries (``quant-scale/<param>`` — see
        ``quant.quantize_params``): scales thread through compiled
        steps, checkpoints and digests exactly like any other state."""
        states = super().get_states()
        states.update(getattr(self, "_quant_scales", {}))
        return states

    # -- abstract (zero-compute) materialisation ---------------------------
    def _abstract_call(self, inputs, body):
        """Run ``body`` under ``jax.eval_shape`` with the input tensors'
        payloads abstracted, so python side effects (layer init, optimizer
        aux creation) happen while NO device computation is issued; any
        pre-existing state the body mutated is restored afterwards and
        tracer-valued leftovers are replaced with zeros.

        This is the reference's buffered-first-call semantics
        (model.py:56-91: the first call records, it does not execute) —
        O(ops) eager device dispatches become none. RNG keys consumed by the run (param inits, dropout)
        stay consumed, exactly as an eager first call would leave them.
        Returns the body result with concrete zero-filled leaves
        (shapes/dtypes preserved)."""
        from .device import get_default_device
        snapshot = [(t, t.data) for t in self._state_tensors()]
        datas = [t.data for t in inputs]
        devs = list({id(self.dev): self.dev,
                     id(get_default_device()): get_default_device()
                     }.values())
        prev_rngs = [d._get_rng_state() for d in devs]
        captured = {}

        def absfn(arrs):
            for t, a in zip(inputs, arrs):
                t.data = a
            res = body()
            leaves = []
            captured["tree"] = _flatten(res, leaves)
            return leaves

        try:
            with self._policy_scope():
                out_avals = jax.eval_shape(
                    absfn, [jax.ShapeDtypeStruct(np.shape(d), d.dtype)
                            for d in datas])
        finally:
            for t, d in zip(inputs, datas):
                t.data = d
            for t, d in snapshot:
                t.data = d
            # state born during the abstract run (optimizer aux, freshly
            # initialised layer stats) may hold dead tracers: zero it
            for t in self._state_tensors():
                if isinstance(t.data, jax.core.Tracer):
                    t.data = np.zeros(t.data.shape,
                                      t.data.dtype)
            # keys consumed concretely (param inits) stay consumed; if
            # TRACED draws (dropout) left a device rng holding a dead
            # tracer, hop each such device to its OWN fresh stream (a
            # rewind would replay init keys; sharing one repaired key
            # would correlate the devices' draws). Ops fall back to the
            # process-wide default device, so it is covered too.
            for i, (d, prev) in enumerate(zip(devs, prev_rngs)):
                if isinstance(d._get_rng_state(), jax.core.Tracer):
                    d._set_rng_state(jax.random.fold_in(prev, 0x5eed + i))
        leaves = [np.zeros(a.shape, a.dtype) for a in out_avals]
        return _unflatten(captured["tree"], list(leaves), self.dev)

    # -- state plumbing ----------------------------------------------------
    def _state_tensors(self):
        """Ordered mutable state: layer params+states, then optimizer aux."""
        seen = {}
        for name, t in self.get_states().items():
            if id(t) not in seen:
                t.name = t.name or name
                seen[id(t)] = t
        opt = getattr(self, "optimizer", None)
        if opt is not None and hasattr(opt, "state_tensors"):
            for t in opt.state_tensors():
                if id(t) not in seen:
                    seen[id(t)] = t
        return list(seen.values())

    # -- the compiled step -------------------------------------------------
    @staticmethod
    def _split_step_args(args):
        """Split positional args into traced tensor inputs and static
        config. Tensors/arrays are traced; strings, None and python
        scalars — the reference calling convention
        ``model(tx, ty, dist_option, spars)``
        (reference examples/cnn/train_cnn.py:219) — are closed over into
        the compiled step and key its cache, so each distinct dist option
        gets its own executable instead of crashing ``jnp.asarray``."""
        arrays, layout = [], []
        for a in args:
            if isinstance(a, Tensor):
                arrays.append(a.data)
                layout.append(_TENSOR)
            elif isinstance(a, (np.ndarray, jax.Array)):
                arrays.append(jnp.asarray(a))
                layout.append(_TENSOR)
            else:
                layout.append(a)
        return arrays, tuple(layout)

    def _ensure_state(self):
        """Collect mutable state once; move it to the model device
        (optimizer scalars are born on the host default device)."""
        if self._state_list is not None:
            return
        opt = getattr(self, "optimizer", None)
        if hasattr(opt, "materialize_shadows"):
            # create the guard's shadow tensors from the CURRENT concrete
            # values, so they join the threaded state collected below
            opt.materialize_shadows()
        state_list = self._state_tensors()
        for t in state_list:
            if not isinstance(t.data, jax.core.Tracer):
                t.data = self.dev.put(t.data)
                t.device = self.dev
        self._state_list = state_list
        opt = getattr(self, "optimizer", None)
        if opt is not None:
            (opt.opt if hasattr(opt, "opt") else opt)._frozen = True

    def _gspmd_active(self):
        """True when the train step compiles on the GSPMD path (one
        jitted program, NamedSharding in/out, XLA-inserted collectives)
        instead of the legacy shard_map + explicit-psum path."""
        return self._gspmd_mesh is not None or self._fsdp_axis is not None

    def _build_step(self, layout):
        self._ensure_state()
        state_list = self._state_list
        rec = {"jit": None, "builder": None, "out_tree": {},
               "leaf_specs": None, "input_specs": None}
        dist = self._dist
        gspmd = self._gspmd_active()
        n_inputs = sum(1 for s in layout if s is _TENSOR)

        def fn(state_arrays, rng_key, *input_arrays):
            # host-side trace counter: this python body runs ONCE per
            # jit trace (steady-state training must keep it at 1 — the
            # retrace-guard CI test pins that; cost-analysis/audit
            # re-lowers legitimately add to it)
            rec["n_traces"] = rec.get("n_traces", 0) + 1
            # advance the RNG stream inside the trace: one half drives this
            # step's random ops, the other is handed back as the next
            # step's key — no host-side eager split per step (it cost more
            # than the whole dispatch of a small compiled step)
            rng_key, next_key = jax.random.split(rng_key)
            if dist is not None and not gspmd:
                # distinct rng per batch-shard (data and, under sequence
                # parallelism, seq); model-parallel members share the key.
                # The GSPMD path traces OUTSIDE shard_map (axis names are
                # unbound — axis_index would not even trace) and draws
                # global-batch randomness from the one shared key, which
                # XLA partitions like any other value.
                for ax in dist.communicator.reduce_axes:
                    rng_key = jax.random.fold_in(
                        rng_key, jax.lax.axis_index(ax))
            for t, a in zip(state_list, state_arrays):
                t.data = a
            self.dev._set_rng_state(rng_key)
            it = iter(input_arrays)
            ins = [Tensor(data=next(it), device=self.dev,
                          requires_grad=False) if s is _TENSOR else s
                   for s in layout]
            from .ops import fused_optim as _fused
            fused_kinds = []
            with self._policy_scope(), _fused.trace_collector(fused_kinds):
                res = self.train_one_batch(*ins)
            if fused_kinds:
                # the program contains fused Pallas custom calls whose
                # FLOPs XLA's cost analysis cannot count — step_flops
                # must use the reference twin for MFU (see step_flops)
                rec["fused_kinds"] = sorted(set(fused_kinds))
            leaves = []
            rec["out_tree"]["tree"] = _flatten(res, leaves)
            pol = getattr(self, "_policy", None)
            if pol is not None:
                # step-boundary output cast: compute may run 16-bit but
                # what the host sees is the policy's output dtype
                leaves = [pol.cast_output(x) for x in leaves]
            if dist is not None and not gspmd:
                # output leaves that end up replicated (loss scalars,
                # metrics, param snapshots) are averaged across batch-like
                # shards so the replicated out-spec is sound. GSPMD leaves
                # are already GLOBAL values — XLA stitches them; a pmean
                # would both double-average and fail to trace (unbound
                # axis names outside shard_map).
                specs = rec["leaf_specs"]
                raxes = tuple(dist.communicator.reduce_axes)
                leaves = [x if specs[i] != P() else jax.lax.pmean(x, raxes)
                          for i, x in enumerate(leaves)]
            new_state = [t.data for t in state_list]
            return new_state, leaves, next_key

        if gspmd:
            from jax.sharding import NamedSharding
            from .parallel import gspmd as _gspmd
            from .parallel.communicator import get_mesh
            mesh = self._gspmd_mesh
            if mesh is None:
                # fsdp_axis-only compile: default data mesh over the
                # devices of the model's platform
                mesh = (dist.communicator.mesh
                        if dist is not None and
                        dist.communicator.mesh is not None
                        else get_mesh(devices=jax.devices(
                            self.dev.jax_device.platform)))
            fsdp = self._fsdp_axis
            axis = dist.axis_name if dist is not None else _gspmd.DATA_AXIS
            if axis not in mesh.shape:
                raise _gspmd.ShardingDecline(
                    f"train mesh {dict(mesh.shape)} has no batch axis "
                    f"{axis!r}: build it via parallel.gspmd.train_mesh "
                    "or parallel.mesh.MeshConfig")
            if fsdp is not None and fsdp not in mesh.shape:
                raise _gspmd.ShardingDecline(
                    f"fsdp_axis {fsdp!r} is not in the train mesh "
                    f"{dict(mesh.shape)}")
            if dist is not None:
                # keep the communicator's mesh pointer current so
                # checkpoint manifests / heartbeats describe the mesh
                # this model actually trains on (its collectives stay
                # identity — the GSPMD body never enters the context)
                dist.communicator.mesh = mesh

            def build(sample_inputs, rng):
                # output shapes are known from the first (abstract) full-
                # batch rehearsal; an output is batch-sharded iff its
                # leading dim is the global batch
                leaves = []
                _flatten(self._eager_out, leaves)
                full_batch = sample_inputs[0].shape[0]
                # per-state layouts from the ONE sharding vocabulary:
                # announced tensor/expert specs mesh-fitted; under FSDP
                # each state tensor additionally shards its first
                # divisible replicated dim over the fsdp axis
                if fsdp is not None:
                    state_specs = [_gspmd.fsdp_state_spec(
                        t.spec, t.shape, mesh, axis=fsdp)
                        for t in state_list]
                else:
                    state_specs = [_fit_state_spec(t.spec, t.shape, mesh)
                                   for t in state_list]
                self._state_specs = state_specs
                user_in = getattr(self, "input_specs", None)
                rec["input_specs"] = list(user_in) if user_in is not None \
                    else [P(axis)] * n_inputs
                rec["leaf_specs"] = _resolve_leaf_specs(
                    leaves, full_batch, rec["input_specs"], axis,
                    getattr(self, "output_specs", None))

                def ns(s):
                    return NamedSharding(mesh, s)

                in_sh = ([ns(s) for s in state_specs], ns(P()),
                         *[ns(s) for s in rec["input_specs"]])
                out_sh = ([ns(s) for s in state_specs],
                          [ns(s) for s in rec["leaf_specs"]], ns(P()))
                rec["raw_fn"] = fn   # step_flops' reference twin
                return jax.jit(fn, in_shardings=in_sh,
                               out_shardings=out_sh, donate_argnums=(0,))

            rec["builder"] = build
            self._mesh, self._axis = mesh, axis
        elif dist is not None:
            from .parallel.communicator import get_mesh
            mesh = dist.communicator.mesh
            if mesh is None:
                # mesh over the devices of the model's platform (virtual CPU
                # devices in tests, TPU chips in production)
                mesh = get_mesh(
                    devices=jax.devices(self.dev.jax_device.platform))
            dist.communicator.mesh = mesh
            axis = dist.axis_name

            def body(state_arrays, rng_key, *input_arrays):
                with _mesh_step_context(mesh, rec["input_specs"], axis):
                    return fn(state_arrays, rng_key, *input_arrays)

            def build(sample_inputs, rng):
                # output shapes are known from the first (eager) full-batch
                # call: an output is batch-sharded iff its leading dim is
                # the global batch; everything else is pmean'd + replicated
                leaves = []
                _flatten(self._eager_out, leaves)
                full_batch = sample_inputs[0].shape[0]
                # per-state sharding: tensor-parallel weights announce a
                # PartitionSpec via Tensor.spec; everything else replicates
                state_specs = [_fit_state_spec(t.spec, t.shape, mesh)
                               for t in state_list]
                self._state_specs = state_specs
                # per-input layouts: Model.input_specs overrides the default
                # batch-on-'data' sharding (sequence parallelism shards
                # dim 1 over 'seq': P('data', 'seq'))
                user_in = getattr(self, "input_specs", None)
                rec["input_specs"] = list(user_in) if user_in is not None \
                    else [P(axis)] * n_inputs
                in_specs = (state_specs, P(), *rec["input_specs"])
                rec["leaf_specs"] = _resolve_leaf_specs(
                    leaves, full_batch, rec["input_specs"], axis,
                    getattr(self, "output_specs", None))
                out_specs = (state_specs, rec["leaf_specs"], P())
                mapped = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                                   out_specs=tuple(out_specs),
                                   check_vma=False)
                rec["raw_fn"] = mapped   # step_flops' reference twin
                return jax.jit(mapped, donate_argnums=(0,))

            rec["builder"] = build
            self._mesh, self._axis = mesh, axis
        else:
            rec["jit"] = jax.jit(fn, donate_argnums=(0,))
            rec["raw_fn"] = fn
        return rec

    def _cast_output_tree(self, res):
        """Policy output contract for EAGER results (the compiled paths
        cast their flattened leaves instead): floating leaves — Tensor
        OR raw array, matching what _flatten treats as a leaf — go to
        output_dtype."""
        pol = getattr(self, "_policy", None)
        if pol is None:
            return res

        def _cast(t):
            if isinstance(t, Tensor):
                if jnp.issubdtype(t.dtype, jnp.floating) and \
                        t.dtype != pol.output_dtype:
                    t = Tensor(data=pol.cast_output(t.data),
                               device=t.device, requires_grad=False)
                return t
            return pol.cast_output(t)

        return jax.tree_util.tree_map(
            _cast, res, is_leaf=lambda x: isinstance(x, Tensor))

    def _run_step(self, *args):
        """Train-mode step dispatch (reference
        ModelMeta.buffer_operation wrapper, model.py:56-91)."""
        if not self.graph_mode:
            # the non-graph path honors the same policy contract as the
            # compiled one (compute casts + output dtype), just eagerly
            with self._policy_scope():
                res = self.train_one_batch(*args)
            return self._cast_output_tree(res)
        if not self._step_ready:
            # first call materialises params + optimizer aux states.
            # Preferred: abstractly (zero device compute — the reference's
            # buffered first call, model.py:56-91); then THIS call already
            # runs compiled. Fallback: the eager step (host-side ops or
            # data-dependent python in train_one_batch).
            import os
            # verbosity>=2 requests per-op wall times, which only the
            # eager dispatch can record (reference per-node timing)
            if self.dev.verbosity < 2 and \
                    os.environ.get("SINGA_EAGER_FIRST_STEP", "0") != "1":
                from .observability import spans as _obs_spans
                try:
                    tensor_args = [a for a in args if isinstance(a, Tensor)]
                    # the one part of set-up no other span covers (it
                    # runs once, before the first compiled step)
                    with _obs_spans.span("train_step.rehearse"):
                        self._eager_out = self._abstract_call(
                            tensor_args,
                            lambda: self.train_one_batch(*args))
                    self._step_ready = True
                except Exception as e:
                    import warnings
                    warnings.warn(
                        "abstract first-step rehearsal failed "
                        f"({type(e).__name__}: {e}); falling back to an "
                        "eager first step — note any host-side effects in "
                        "train_one_batch may have run twice", stacklevel=3)
            if not self._step_ready:
                with self._policy_scope():
                    res = self.train_one_batch(*args)
                self._step_ready = True
                self._eager_out = res
                return self._cast_output_tree(res)
        input_arrays, layout = self._split_step_args(args)
        try:
            hash(layout)
            key = layout
        except TypeError:
            key = repr(layout)
        rec = self._steps.get(key)
        if rec is None:
            # warm restart: an AOT store (ResilientTrainer(aot=...))
            # may hold this signature's exported executable — verify
            # its manifest and deserialize INSTEAD of tracing. Any
            # mismatch (version, topology, avals, digest, policy) was
            # already refused loudly inside the loader and falls
            # through to the normal fresh build below.
            store = getattr(self, "_aot_store", None)
            if store is not None and self._dist is None and \
                    not self._gspmd_active() and isinstance(key, tuple):
                try:
                    from .aot import export as _aot_export
                    rec = _aot_export.load_train_step(
                        self, store, key, input_arrays)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:    # noqa: BLE001 — never blocks
                    import warnings
                    warnings.warn(
                        f"AOT train-step load failed unexpectedly "
                        f"({type(e).__name__}: {e}); compiling fresh",
                        stacklevel=3)
                    rec = None
            if rec is None:
                rec = self._build_step(layout)
            self._steps[key] = rec
            if len(self._steps) == 9:
                import warnings
                warnings.warn(
                    "9th distinct static-arg signature compiled for this "
                    "model; each costs a full trace+compile and is cached. "
                    "Pass per-step-varying values as Tensors, not python "
                    "scalars.", stacklevel=3)
        rng = self.dev.current_key()  # advanced in-trace; next key returned
        if rec["jit"] is None:
            rec["jit"] = rec["builder"](input_arrays, rng)
        state_arrays = [t.data for t in self._state_list]
        if self._dist is not None or self._gspmd_active():
            from jax.sharding import NamedSharding
            rep = NamedSharding(self._mesh, P())
            place = self._place_mesh
            specs = getattr(self, "_state_specs", None) or \
                [P()] * len(state_arrays)
            state_arrays = [
                place(a, NamedSharding(self._mesh, s))
                for a, s in zip(state_arrays, specs)]
            in_specs = rec["input_specs"] or \
                [P(self._axis)] * len(input_arrays)
            # identity cache: benchmark/eval loops feed the same arrays
            # every step — skip re-sharding them (one previous batch is
            # kept alive per slot, the cost of a depth-1 prefetch).
            # Immutable jax.Arrays ONLY: a host numpy array mutated in
            # place between steps would hit on object identity and
            # silently train on the stale device shard.
            cache = rec.setdefault("in_cache", [None] * len(input_arrays))
            placed = []
            for i, (a, s) in enumerate(zip(input_arrays, in_specs)):
                c = cache[i] if i < len(cache) else None
                if c is not None and c[0] is a:
                    placed.append(c[1])
                    continue
                pa = place(a, NamedSharding(self._mesh, s))
                if i < len(cache) and isinstance(a, jax.Array):
                    cache[i] = (a, pa)
                placed.append(pa)
            input_arrays = placed
            rng = place(rng, rep)
        self._last_run_rec = rec       # compiled_step_info audits this
        shapes_key = tuple(np.shape(a) for a in input_arrays)
        if rec.get("avals_key") != shapes_key:
            # abstract signature of this step (shardings included) for
            # compiled_step_info()'s lower-without-rerun audit; refreshed
            # when input shapes change (jit retraces under the same rec,
            # and the audit must describe the executable that just ran)
            def _aval(a):
                return jax.ShapeDtypeStruct(
                    np.shape(a), np.asarray(a).dtype if not hasattr(
                        a, "dtype") else a.dtype,
                    sharding=getattr(a, "sharding", None))
            rec["avals"] = ([_aval(a) for a in state_arrays], _aval(rng),
                            [_aval(a) for a in input_arrays])
            rec["avals_key"] = shapes_key
            rec.pop("audit_compiled", None)
            # the cached cost analysis and FLOP count described the old
            # program — recompute against the new signature on next use
            rec.pop("step_flops", None)
            rec.pop("cost", None)
        # compile/retrace attribution: watch the host-side trace
        # counter across the dispatch — if THIS call traced (first
        # compile, a shape/dtype retrace, or the verbosity AOT
        # re-lower below), its wall-clock lands in compile_seconds and
        # a compile/retrace flight-recorder event names the signature
        # (and, on a retrace, the argument that changed). Steady-state
        # steps pay two dict reads.
        n_traces0 = rec.get("n_traces", 0)
        t_compile0 = time.perf_counter()
        cache_counts0 = _aot_cache_snapshot()
        if self.dev.verbosity >= 2 and "cost" not in rec:
            # one-time XLA cost analysis of this step signature (the
            # compiled-world per-op metric: flops / bytes, reference
            # per-node profiling scheduler.cc:240-298). The AOT-compiled
            # executable replaces the jit wrapper so the signature is
            # compiled exactly once.
            rec["cost"] = None
            try:
                compiled = rec["jit"].lower(
                    state_arrays, rng, *input_arrays).compile()
                rec["cost"] = compiled.cost_analysis()
                rec["jit"] = compiled
            except Exception:   # cost analysis is backend-best-effort
                pass
        t0 = time.perf_counter()
        if self.dev.verbosity >= 2 and not rec.get("fusions_measured"):
            # one-time MEASURED per-fusion table for this signature (the
            # compiled-world per-node timing, reference
            # scheduler.cc:240-298) — this very step runs under a
            # profiler trace, so no extra compute and no state copies
            from . import profiling as _prof
            rec["fusions_measured"] = True

            def run_once():
                # the trace must not stop before the device finishes
                return jax.block_until_ready(
                    rec["jit"](state_arrays, rng, *input_arrays))

            (new_state, leaves, next_key), fus = \
                _prof.measure_step_fusions(run_once)
            for name, (cnt, tot) in fus.items():
                c0, t0_ = self.dev.time_profiling.get(
                    f"fusion/{name}", (0, 0.0))
                self.dev.time_profiling[f"fusion/{name}"] = (c0 + cnt,
                                                             t0_ + tot)
        else:
            new_state, leaves, next_key = rec["jit"](state_arrays, rng,
                                                     *input_arrays)
        if rec.get("n_traces", 0) > n_traces0:
            from .aot import cache as _aot_cache
            from .observability import perf as _perf
            sig = _perf.step_signature(input_arrays)
            _perf.record_compile(
                "train_step", time.perf_counter() - t_compile0, sig,
                prev_signature=rec.get("arg_sig"),
                source=_aot_cache.classify(cache_counts0),
                step=self._step_count)
            rec["arg_sig"] = sig
        self.dev._set_rng_state(next_key)  # tracing clobbered dev rng
        if self._dist is not None or self._gspmd_active():
            # bound the async in-flight queue: a host loop can dispatch
            # compiled steps much faster than they run, and hundreds of
            # queued multi-device programs starve the collective
            # rendezvous (the CPU backend aborts after 40s; on TPU it
            # just bloats memory). Blocking on step N-2 keeps a depth-2
            # pipeline — overlap without unbounded growth. The fence
            # rides the returned rng key: an output (never donated, so
            # still alive two steps later) whose readiness implies the
            # whole step executed.
            fence = getattr(self, "_step_fence", None)
            if fence is None:
                from collections import deque
                fence = self._step_fence = deque()
            fence.append(next_key)
            if len(fence) > 2:
                jax.block_until_ready(fence.popleft())
        self._step_count += 1
        if self.dev.verbosity > 0 and \
                self._step_count > self.dev.skip_iteration:
            # reference semantics: timing starts after skip_iteration
            # steps (include/singa/core/device.h:115-129)
            jax.block_until_ready(new_state)
            self.dev._record_time("train_one_batch",
                                  time.perf_counter() - t0)
        for t, a in zip(self._state_list, new_state):
            t.data = a
        return _unflatten(rec["out_tree"]["tree"], list(leaves), self.dev)

    # -- profiling / debugging --------------------------------------------
    def cost_analysis(self):
        """XLA cost analysis (flops, bytes accessed, ...) per compiled
        step signature, captured at verbosity>=2. The compiled-world form
        of the reference's per-op profiling (scheduler.cc:240-298): XLA
        fuses ops, so per-fusion costs replace per-node times."""
        out = {}
        for key, rec in self._steps.items():
            c = rec.get("cost")
            if isinstance(c, (list, tuple)):
                c = c[0] if c else None
            out[key] = c
        return out

    def graph_debug(self, *args, print_out=True, max_rows=None):
        """Dump the traced training step as a jaxpr op table — the XLA-era
        ``Graph::Debug`` (reference src/core/scheduler/scheduler.cc:109-238
        dumps nodes/edges/blocks; here each jaxpr equation is a node and
        its avals are the blocks). Call with the same args as a step."""
        if not self._step_ready:
            raise ValueError(
                "graph_debug needs materialised state: run one training "
                "step first (the eager first call creates optimizer aux)")
        input_arrays, layout = self._split_step_args(args)
        self._ensure_state()
        state_arrays = [t.data for t in self._state_list]
        backup = list(state_arrays)
        host_key = self.dev._get_rng_state()

        def fn(state_arrays, *input_arrays):
            for t, a in zip(self._state_list, state_arrays):
                t.data = a
            it = iter(input_arrays)
            ins = [Tensor(data=next(it), device=self.dev,
                          requires_grad=False) if s is _TENSOR else s
                   for s in layout]
            # same policy scope as the real step, so the dumped jaxpr
            # shows the convert ops the compiled program actually runs
            with self._policy_scope():
                res = self.train_one_batch(*ins)
            leaves = []
            _flatten(res, leaves)
            return [t.data for t in self._state_list], leaves

        try:
            jaxpr = jax.make_jaxpr(fn)(state_arrays, *input_arrays)
        finally:
            for t, a in zip(self._state_list, backup):
                t.data = a
            self.dev._set_rng_state(host_key)
        eqns = jaxpr.jaxpr.eqns
        lines = [f"step graph: {len(eqns)} ops, "
                 f"{len(jaxpr.jaxpr.invars)} inputs, "
                 f"{len(jaxpr.jaxpr.outvars)} outputs"]
        shown = eqns if max_rows is None else eqns[:max_rows]
        for i, eqn in enumerate(shown):
            outs = ", ".join(str(v.aval) for v in eqn.outvars)
            lines.append(f"{i:4d}  {eqn.primitive.name:<28} -> {outs}")
        if max_rows is not None and len(eqns) > max_rows:
            lines.append(f"... {len(eqns) - max_rows} more ops")
        text = "\n".join(lines)
        if print_out:
            print(text)
        return text

    def _invalidate_compiled(self):
        """Drop every compiled step/eval specialization: the state
        tensors' identities changed (load_states / checkpoint restore)
        and the traced closures are bound to the old ones."""
        self._steps = {}
        self._eval_steps = {}
        self._state_list = None

    def _place_mesh(self, a, sharding):
        """Lay an array out on the mesh. On a multi-process mesh the
        sharding spans devices of other hosts, which device_put cannot
        reach — each process contributes its addressable shards from its
        (SPMD-identical) host copy instead."""
        if getattr(a, "sharding", None) == sharding:
            return a
        if sharding.is_fully_addressable:
            return jax.device_put(a, sharding)
        val = np.asarray(jax.device_get(a))
        return jax.make_array_from_callback(
            val.shape, sharding, lambda idx: val[idx])

    # -- sharded eval ------------------------------------------------------

    def _eval_input_specs(self, n_inputs):
        user_in = getattr(self, "input_specs", None)
        if user_in is not None:
            # eval usually takes fewer inputs than training (x, no y):
            # use the leading specs
            return list(user_in)[:n_inputs]
        return [P(self._axis)] * n_inputs

    def _eval_divisible(self, input_arrays, in_specs):
        for a, s in zip(input_arrays, in_specs):
            shape = np.shape(a)
            for d, names in enumerate(s):
                if names is None:
                    continue
                names = names if isinstance(names, tuple) else (names,)
                k = 1
                for nm in names:
                    k *= self._mesh.shape[nm]
                if d >= len(shape) or shape[d] % k:
                    return False
        return True

    def _build_eval(self, input_tensors):
        """Compile an eval forward under the SAME mesh and shardings as
        the training step, so tp/ep-sharded state is consumed where it
        lives instead of being gathered to one device — which OOMs for
        exactly the models model-parallelism exists for. (Reference
        inference runs on the same device graph, model.py:210-222.)"""
        self._ensure_state()
        state_list = self._state_list
        dist = self._dist
        mesh, axis = self._mesh, self._axis
        rec = {}

        # leaf shapes via an abstract rehearsal: zero device compute, and
        # collectives are identity outside the mesh so logical shapes match
        out = self._abstract_call(
            list(input_tensors), lambda: self.forward(*input_tensors))
        leaves0 = []
        _flatten(out, leaves0)
        rec["input_specs"] = self._eval_input_specs(len(input_tensors))
        rec["leaf_specs"] = _resolve_leaf_specs(
            leaves0, input_tensors[0].shape[0], rec["input_specs"], axis,
            getattr(self, "eval_output_specs", None))
        state_specs = getattr(self, "_state_specs", None) or \
            [_fit_state_spec(t.spec, t.shape, mesh) for t in state_list]
        rec["state_specs"] = state_specs

        def fn(state_arrays, *input_arrays):
            backup = [t.data for t in state_list]
            for t, a in zip(state_list, state_arrays):
                t.data = a
            prev = CTX.training
            CTX.training = False
            try:
                ins = [Tensor(data=a, device=self.dev,
                              requires_grad=False)
                       for a in input_arrays]
                with self._policy_scope():
                    res = self.forward(*ins)
            finally:
                CTX.training = prev
                # eval leaves state untouched: restore the concrete
                # arrays so no tracer outlives the trace
                for t, a in zip(state_list, backup):
                    t.data = a
            leaves = []
            rec["tree"] = _flatten(res, leaves)
            pol = getattr(self, "_policy", None)
            if pol is not None:
                leaves = [pol.cast_output(x) for x in leaves]
            specs = rec["leaf_specs"]
            raxes = tuple(dist.communicator.reduce_axes)
            kinds = getattr(self, "eval_output_reduce", None) or []

            def combine(i, x):
                if specs[i] != P():          # batch-sharded: stitched
                    return x
                kind = kinds[i] if i < len(kinds) else "mean"
                red = jax.lax.psum if kind == "sum" else jax.lax.pmean
                return red(x, raxes)

            leaves = [combine(i, x) for i, x in enumerate(leaves)]
            return leaves

        def body(state_arrays, *input_arrays):
            with _mesh_step_context(mesh, rec["input_specs"], axis):
                return fn(state_arrays, *input_arrays)

        mapped = shard_map(body, mesh=mesh,
                           in_specs=(state_specs, *rec["input_specs"]),
                           out_specs=rec["leaf_specs"],
                           check_vma=False)
        rec["jit"] = jax.jit(mapped)   # state NOT donated: eval reuses it
        return rec

    def _run_eval(self, *args):
        """Mesh-resident eval dispatch. Returns NotImplemented when the
        batch does not divide the mesh — the caller falls back to the
        gather-and-run-eager path."""
        input_arrays = [a.data for a in args]
        if not self._eval_divisible(input_arrays,
                                    self._eval_input_specs(len(args))):
            return NotImplemented
        # the key carries the resolved specs: changing input_specs /
        # eval_output_specs after a first eval must re-specialize, not
        # silently reuse the stale layout
        key = (tuple((tuple(np.shape(a)), str(getattr(a, "dtype", "?")))
                     for a in input_arrays),
               repr(self._eval_input_specs(len(args))),
               repr(getattr(self, "eval_output_specs", None)),
               repr(getattr(self, "eval_output_reduce", None)))
        rec = self._eval_steps.get(key)
        fresh = rec is None
        try:
            if fresh:
                rec = self._build_eval(args)
                self._eval_steps[key] = rec
            if rec is NotImplemented:
                return NotImplemented
            from jax.sharding import NamedSharding
            place = self._place_mesh
            state_arrays = [place(t.data, NamedSharding(self._mesh, s))
                            for t, s in zip(self._state_list,
                                            rec["state_specs"])]
            placed = [place(a, NamedSharding(self._mesh, s))
                      for a, s in zip(input_arrays, rec["input_specs"])]
            leaves = rec["jit"](state_arrays, *placed)
        except Exception as e:
            if not fresh:
                raise
            # per-shard constraints beyond input divisibility (e.g. a
            # pipeline's microbatch assert on the LOCAL batch) surface
            # when the shard_map first traces — fall back to the
            # gather+eager path, which sees the global batch. Only
            # STRUCTURAL errors pin the signature; a transient failure
            # (device OOM, interrupted backend: RuntimeError family)
            # falls back for THIS call and retries on the next, so one
            # bad moment cannot silently degrade every later eval of
            # this shape to the gather path.
            import warnings
            structural = isinstance(
                e, (TypeError, ValueError, AssertionError,
                    NotImplementedError, IndexError, KeyError))
            if not structural:
                # RuntimeError family (XlaRuntimeError covers both a
                # transient OOM and a permanent lowering failure): allow
                # a bounded number of retries, then pin — an unbounded
                # retry would pay a full retrace+compile attempt on
                # EVERY eval of a signature that can never build
                fails = getattr(self, "_eval_fail_counts", None)
                if fails is None:
                    fails = self._eval_fail_counts = {}
                fails[key] = fails.get(key, 0) + 1
                structural = fails[key] >= 3
            if structural:
                self._eval_steps[key] = NotImplemented
            else:
                self._eval_steps.pop(key, None)
            warnings.warn(
                f"sharded eval unavailable for this signature "
                f"({type(e).__name__}: {e}); falling back to gathered "
                f"eager eval ({'pinned' if structural else 'will retry'})",
                stacklevel=3)
            return NotImplemented
        return _unflatten(rec["tree"], list(leaves), self.dev)

    def _unshard_state(self):
        """After mesh-sharded training the live state arrays span the mesh;
        gather them to the model device so eager (eval) ops can mix them
        with single-device inputs."""
        if self._state_list is None:
            return
        gather = {}
        for t in self._state_list:
            arr = t.data
            if hasattr(arr, "devices") and not isinstance(
                    arr, jax.core.Tracer) and len(arr.devices()) > 1:
                gather[id(t)] = (t, arr)
        if gather:
            # one batched cross-process gather for everything host-sharded
            from .tensor import to_host_tree
            hosts = to_host_tree({k: a for k, (_t, a) in gather.items()})
            for k, (t, _a) in gather.items():
                t.data = self.dev.put(hosts[k])

    def __call__(self, *args, **kwargs):
        if self._train:
            if kwargs:
                raise TypeError(
                    "train-mode model calls take positional tensors only "
                    "(the compiled step is positional); got keyword "
                    f"arguments {sorted(kwargs)}")
            return self._run_step(*args)
        if self._dist is not None or self._gspmd_active():
            # the sharded (shard_map) eval path needs a communicator for
            # its cross-shard reductions and consumes state in the TRAIN
            # layout — under FSDP that layout splits whole weights, so
            # eval instead gathers below and runs the eager forward
            if (not kwargs and self.graph_mode and args
                    and self._dist is not None
                    and self._fsdp_axis is None
                    and getattr(self, "_mesh", None) is not None
                    and all(isinstance(a, Tensor) for a in args)):
                res = self._run_eval(*args)
                if res is not NotImplemented:
                    return res
            # fallback (no mesh yet / odd batch / kwargs / FSDP): gather
            # state to the model device and run the eager forward
            self._unshard_state()
        prev = CTX.training
        CTX.training = False
        try:
            with self._policy_scope():
                res = self.forward(*args, **kwargs)
            # the eager path honors the same output contract as the
            # compiled one (a bf16-computed eval still hands back
            # output_dtype leaves)
            return self._cast_output_tree(res)
        finally:
            CTX.training = prev

    # -- persistence (reference model.py:244-330) --------------------------
    TENSOR_DICT_FILENAME = "/tensor_dict.npz"
    STATES_ATTR_FILENAME = "/states_attr.json"

    def compiled_step_info(self):
        """Perf-readiness audit of the latest compiled train step:
        re-lowers the recorded abstract signature (no step re-runs, no
        state copies) and returns

        - ``memory_analysis``: XLA's executable memory breakdown
          (per-device under a mesh);
        - ``donated_bytes``: bytes the executable aliases input→output —
          donation actually holding for the threaded state is THE
          invariant that keeps big-model training at 1× weights instead
          of 2×;
        - ``state_bytes``: logical bytes of the threaded state, for
          comparison (divide by the device count under a mesh);
        - ``hlo``: the optimized HLO text, for structural regression
          checks (host round-trips show up as callback custom-calls,
          lost sharding as missing collectives).

        Requires one compiled step to have run. No reference
        counterpart (closest: Graph::Debug's node dump).
        """
        # audit the signature that actually RAN last (a one-off
        # odd-shaped batch must not hijack the audit away from the main
        # training signature); fall back to any compiled rec
        rec = getattr(self, "_last_run_rec", None)
        if rec is None or rec.get("jit") is None or "avals" not in rec:
            rec = None
            for r in self._steps.values():
                if r.get("jit") is not None and "avals" in r:
                    rec = r
        if rec is None:
            raise RuntimeError(
                "compiled_step_info() needs a compiled step: run one "
                "training batch in graph mode first")
        fn = rec["jit"]
        state_avals, rng_aval, in_avals = rec["avals"]
        compiled = rec.get("audit_compiled")
        if compiled is None:
            if hasattr(fn, "lower"):
                compiled = fn.lower(state_avals, rng_aval,
                                    *in_avals).compile()
            else:                  # verbosity path already AOT-compiled
                compiled = fn
            rec["audit_compiled"] = compiled   # repeat audits are free
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        state_bytes = sum(
            int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
            for a in state_avals)
        donated = getattr(ma, "alias_size_in_bytes", None)
        try:
            cost = compiled.cost_analysis()
        except Exception:       # cost analysis is backend-best-effort
            cost = None
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        return {"memory_analysis": ma, "donated_bytes": donated,
                "state_bytes": state_bytes, "hlo": hlo,
                "cost_analysis": cost,
                "n_traces": rec.get("n_traces"),
                "policy": self._policy.describe()
                if getattr(self, "_policy", None) is not None else None}

    def step_flops(self, compute=True):
        """FLOPs of one compiled training step, from XLA's cost
        analysis of the signature that last ran — the numerator of an
        honest MFU (``flops / step_seconds / chip_peak``), derived from
        the program actually executing rather than an analytic model.

        ``compute=False`` only consults an ALREADY-CACHED analysis
        (the verbosity>=2 path, a prior ``compiled_step_info()`` /
        ``step_flops()`` call) and returns None otherwise — the form
        the resilient trainer uses so MFU telemetry never pays a
        re-lower on the step path. Returns None when no step has
        compiled or the backend reports no flops."""
        rec = getattr(self, "_last_run_rec", None)
        if rec is None or rec.get("jit") is None or "avals" not in rec:
            rec = next((r for r in self._steps.values()
                        if r.get("jit") is not None and "avals" in r),
                       None)
        if rec is None:
            return None
        if "step_flops" in rec:
            return rec["step_flops"]
        if rec.get("fused_kinds"):
            # the executed program fuses optimizer updates into Pallas
            # custom calls, which XLA's cost analysis cannot see into
            # (on TPU they count ~0 flops; interpret mode counts the
            # emulation loop instead) — either way the analyzed number
            # would move vs the unfused program and MFU would lie. Lower
            # a REFERENCE twin of the same signature with every fused
            # kernel declined: fused and unfused programs then report
            # IDENTICAL FLOPs by construction. One extra trace+compile,
            # on the cost-analysis path only, never the step path
            # (compute=False still returns None until someone pays it).
            if not compute:
                return None
            raw = rec.get("raw_fn")
            if raw is None:
                return None
            state_avals, rng_aval, in_avals = rec["avals"]
            from .ops import fused_optim as _fused
            # a FRESH jit forces a fresh trace (the step's own jit would
            # serve its cached — fused — jaxpr from lower()); the traced
            # body mutates live state tensors and the device rng, so
            # snapshot and restore around it exactly like graph_debug
            backup = [(t, t.data) for t in (self._state_list or [])]
            rng_backup = self.dev._get_rng_state()
            # a fresh closure defeats jax's global trace cache (keyed on
            # the function object — reusing `raw` would serve the FUSED
            # jaxpr without ever re-running the body)
            def _twin_body(state_arrays, rng_key, *input_arrays):
                return raw(state_arrays, rng_key, *input_arrays)

            try:
                with _fused.force_reference():
                    twin = jax.jit(_twin_body, donate_argnums=(0,)).lower(
                        state_avals, rng_aval, *in_avals).compile()
                cost = twin.cost_analysis()
            except Exception:
                rec["step_flops"] = None
                return None
            finally:
                for t, d in backup:
                    t.data = d
                self.dev._set_rng_state(rng_backup)
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else None
            flops = None
            if isinstance(cost, dict):
                f = cost.get("flops")
                if f and f > 0:
                    flops = float(f)
            rec["step_flops"] = flops
            return flops
        cost = rec.get("cost")              # verbosity>=2 capture
        compiled = rec.get("audit_compiled")
        if cost is None:
            if compiled is None:
                if not compute:
                    return None             # nothing cached; stay cheap
                fn = rec["jit"]
                state_avals, rng_aval, in_avals = rec["avals"]
                try:
                    compiled = fn.lower(state_avals, rng_aval,
                                        *in_avals).compile() \
                        if hasattr(fn, "lower") else fn
                    rec["audit_compiled"] = compiled
                except Exception:
                    rec["step_flops"] = None
                    return None
            try:
                cost = compiled.cost_analysis()
            except Exception:
                cost = None
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        flops = None
        if isinstance(cost, dict):
            f = cost.get("flops")
            if f and f > 0:
                flops = float(f)
        rec["step_flops"] = flops
        return flops

    def profile_step(self, *args, record=True, events_out=None):
        """Run ONE training step under a ``jax.profiler`` trace and
        return ``(result, {fusion_name: (count, total_seconds)})`` —
        the measured per-fusion decomposition of the compiled step
        (reference per-node timing, scheduler.cc:240-298), on demand
        instead of only at device verbosity>=2. Rows are recorded into
        the metrics registry (``profile_fusion_seconds``/``_count``
        gauges) and folded into ``dev.time_profiling`` like the
        verbosity path's rows. Call with the same args as a training
        step; profiler failures degrade to an empty table
        (:func:`singa_tpu.profiling.measure_step_fusions`).

        ``record=False`` skips the registry publish (the device table
        still folds): the sampling profiler is then the ONE publisher,
        into ITS registry — without it every sampled step would set
        each gauge twice and a custom-registry profiler would leak the
        table into the default registry too.

        ``events_out``: a list that receives the capture's RAW
        timestamped trace events (``profiling.parse_trace_events``) —
        what ``observability.timeline.analyze`` buckets into the
        compute/collective/memcpy/host/idle step decomposition. Same
        single parse pass; an out-param so the 2-tuple return shape
        stays stable."""
        from . import profiling as _prof

        def run_once():
            res = self(*args)
            # the trace must outlive the device work (see the
            # verbosity>=2 path): block on the raw output arrays
            # (Tensors are not jax pytree leaves)
            leaves = []
            _flatten(res, leaves)
            jax.block_until_ready(leaves)
            return res

        result, table = _prof.measure_step_fusions(
            run_once, events_out=events_out)
        if record:
            _prof.record_fusion_metrics(table)
        for name, (cnt, tot) in table.items():
            c0, t0 = self.dev.time_profiling.get(
                f"fusion/{name}", (0, 0.0))
            self.dev.time_profiling[f"fusion/{name}"] = (c0 + cnt,
                                                         t0 + tot)
        return result, table

    def save_states(self, fpath, aux_states={}, quantize=None):  # noqa: B006 (parity)
        """Zip of params+states .npz and an attribute JSON, including
        optimizer aux states (reference model.py:244-295).

        ``quantize``: a quantized policy (or its name, e.g.
        ``"int8_weight_only"``) persists eligible weights as int8
        payloads plus per-channel ``quant-scale/`` fp32 sidecars (~4x
        smaller archive; lossy — fp32 masters stay untouched in
        memory). A model compiled under ``int8_weight_only`` quantizes
        its checkpoints by default; ``load_states`` dequantizes back
        into fp32 masters. A model already weight-quantized in place
        (``quant.quantize_params``) saves its int8 state as-is."""
        from . import mixed_precision as mp
        states = {k: v for k, v in self.get_states().items()}
        attr = {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                for k, v in states.items()}
        qpol = mp.resolve(quantize) if quantize is not None \
            else getattr(self, "_policy", None)
        if quantize is not None and (
                not isinstance(qpol, mp.QuantPolicy)
                or qpol.weight_quant is None):
            # an EXPLICIT quantize= that cannot be honored must fail,
            # not silently write a full-size fp32 archive the caller
            # believes is 4x smaller
            raise ValueError(
                f"save_states(quantize={quantize!r}): not a weight-"
                "quantizing policy (only 'int8_weight_only' persists "
                "int8 payloads; fp8/QAT presets quantize compute, not "
                "storage)")
        do_quant = (isinstance(qpol, mp.QuantPolicy)
                    and qpol.weight_quant is not None
                    and not getattr(self, "_quant_pairs", None)
                    and (quantize is not None
                         or getattr(qpol, "quantize_checkpoints",
                                    False)))
        if do_quant:
            # the archive self-describes as quantized: the preset
            # round-trips through meta/precision_policy
            attr["meta/precision_policy"] = qpol.describe()
        elif getattr(self, "_policy", None) is not None:
            # self-describing checkpoints: params in the archive are the
            # POLICY'S MASTERS (fp32 under bf16_mixed) — record the
            # policy so a reader can tell masters from a pure-16-bit run
            attr["meta/precision_policy"] = self._policy.describe()
        from .tensor import to_host_tree

        def _portable(a):
            # bf16 isn't a stock-numpy dtype: inside the .npz it would
            # round-trip as an uncastable raw-void array. Store it as
            # (lossless) f32 — attr records the true dtype, and
            # copy_from_numpy casts back to the param's dtype on load.
            a = np.asarray(a)
            return a.astype(np.float32) if str(a.dtype) == "bfloat16" \
                else a

        # one batched cross-process gather for every host-sharded param
        arrays = {k: _portable(v) for k, v in to_host_tree(
            {k: v.data for k, v in states.items()}).items()}
        if do_quant:
            from .quant import core as _qcore
            for k, t in states.items():
                if not _qcore.eligible(t):
                    continue
                q, s = _qcore.quantize_int8(
                    arrays[k], _qcore.channel_axis(np.shape(arrays[k])))
                arrays[k] = np.asarray(q)
                arrays[_qcore.SCALE_PREFIX + k] = np.asarray(s)
                attr[k] = {"shape": list(np.shape(arrays[k])),
                           "dtype": "int8",
                           "quant": {"kind": "int8",
                                     "orig_dtype": attr[k]["dtype"]}}
                attr[_qcore.SCALE_PREFIX + k] = {
                    "shape": list(np.shape(arrays[_qcore.SCALE_PREFIX
                                                  + k])),
                    "dtype": "float32", "quant_scale": True}
        opt = getattr(self, "optimizer", None)
        if opt is not None and hasattr(opt, "get_states"):
            for k, v in opt.get_states().items():
                arrays[f"optimizer/{k}"] = _portable(v)
                attr[f"optimizer/{k}"] = {
                    "shape": list(np.shape(v)),
                    "dtype": str(np.asarray(v).dtype),
                    "optimizer": True}
        for k, v in aux_states.items():
            raw = np.asarray(v.numpy() if isinstance(v, Tensor) else v)
            # attr records the TRUE dtype, taken before the portable-f32
            # conversion, so load_states can cast bf16 aux back
            attr[f"aux/{k}"] = {"shape": list(raw.shape),
                                "dtype": str(raw.dtype),
                                "aux": True}
            arrays[f"aux/{k}"] = _portable(raw)
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        buf.seek(0)
        with zipfile.ZipFile(fpath, "w") as zf:
            zf.writestr(self.TENSOR_DICT_FILENAME.strip("/"), buf.read())
            zf.writestr(self.STATES_ATTR_FILENAME.strip("/"),
                        json.dumps(attr))

    def load_states(self, fpath):
        """Restore params/states (+ optimizer aux) and return aux states
        (reference model.py:297-330)."""
        with zipfile.ZipFile(fpath, "r") as zf:
            attr = json.loads(zf.read(
                self.STATES_ATTR_FILENAME.strip("/")))
            with zf.open(self.TENSOR_DICT_FILENAME.strip("/")) as f:
                data = np.load(io.BytesIO(f.read()))
                arrays = {k: data[k] for k in data.files}

        def _true_dtype(k, a):
            # the archive stores bf16 as portable f32 (save_states
            # _portable); attr records the real dtype — cast back here
            # so every consumer (fresh optimizer aux included) sees the
            # dtype that was saved, not the transport representation
            want = attr.get(k, {}).get("dtype")
            if want and str(a.dtype) != want:
                if want == "bfloat16":
                    # numpy only knows bfloat16 once ml_dtypes (shipped
                    # with jax) has registered it — import explicitly so
                    # the cast can't silently hand consumers f32 arrays
                    try:
                        import ml_dtypes  # noqa: F401
                    except ImportError:
                        pass  # astype below fails loudly via the warning
                try:
                    return a.astype(np.dtype(want))
                except TypeError:
                    import warnings
                    warnings.warn(
                        f"load_states: recorded dtype {want!r} for {k!r} "
                        f"cannot be restored (keeping {a.dtype})",
                        stacklevel=2)
                    return a
            return a

        arrays = {k: _true_dtype(k, v) for k, v in arrays.items()}
        model_states = {k: v for k, v in arrays.items()
                        if not k.startswith(("optimizer/", "aux/"))}
        my_states = self.get_states()
        # quantized archive (save_states(quantize=...)): int8 payloads
        # carry a quant-scale/ sidecar — restoring into fp32 masters
        # dequantizes here; restoring into an equally-quantized model
        # copies payload and scale verbatim (its live tensors are int8,
        # so the dequant branch never fires for them)
        from .quant.core import SCALE_PREFIX as _QSCALE
        from .quant.core import dequantize_entry
        q_scales = {k[len(_QSCALE):]: v for k, v in arrays.items()
                    if k.startswith(_QSCALE)}
        for k, v in model_states.items():
            if k in my_states:
                lt = my_states[k]
                if (k in q_scales and np.dtype(v.dtype) == np.int8
                        and jnp.issubdtype(lt.dtype, jnp.floating)):
                    v = dequantize_entry(v, q_scales[k])
                lt.copy_from_numpy(v)
        opt = getattr(self, "optimizer", None)
        if opt is not None and hasattr(opt, "set_states"):
            opt_states = {k[len("optimizer/"):]: v
                          for k, v in arrays.items()
                          if k.startswith("optimizer/")}
            if opt_states:
                opt.set_states(opt_states)
                if hasattr(opt, "announce_aux_specs"):
                    # restored momentum/moments shard like their params
                    opt.announce_aux_specs(my_states)
        self._invalidate_compiled()
        return {k[len("aux/"):]: Tensor(data=v, requires_grad=False)
                for k, v in arrays.items() if k.startswith("aux/")}
