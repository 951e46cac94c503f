"""Pipeline parallelism: GPipe and 1F1B microbatch schedules over a mesh
axis.

No reference equivalent (the reference is data-parallel only, SURVEY.md
§2.4). TPU-native design: every pipeline stage is the same jitted program
(SPMD over the 'pipe' mesh axis inside ``shard_map``); activations hop to
the next stage with `lax.ppermute` over ICI each schedule tick, and the
whole schedule is a `lax.scan` — so XLA sees one static program.

Two schedules:
- GPipe (:func:`pipeline_spmd` / :class:`PipelineModule`): forward only;
  backward falls out of `jax.grad` of the scan (the transpose of
  `ppermute` is the reverse-direction `ppermute`) — simple, but autodiff
  stores every tick's activations, O(n_micro).
- 1F1B (:func:`pipeline_1f1b` / :class:`PipelineModule1F1B`): forward and
  backward micro-steps interleave in ONE scan with the per-microbatch
  loss inside the schedule; backward recomputes each stage from a saved
  input-activation ring of depth 2(S-1)+1, so activation memory is
  bounded by the pipe depth, not the microbatch count.

Prefer 1F1B for training: in the SPMD GPipe form every pipe member also
recomputes the downstream (post-pipeline) loss redundantly — inherent to
one-program-per-mesh SPMD, harmless for inference, but wasted compute
per training step that the in-schedule 1F1B loss avoids entirely.

Heterogeneous stages (different params AND different activation shapes
per stage — embedding -> blocks -> head) are first-class via
:class:`HeteroPipeline1F1B`.

Deprecation boundary: this module (like ``communicator.py``) is the
explicit-collective MECHANISM layer — it stays for the compiled train
step, but sharding LAYOUTS belong to :mod:`.gspmd` (the one
NamedSharding vocabulary training and serving share; see
``communicator.partitioner`` for the shim). New sharded code should
annotate arrays with NamedSharding and jit, not add ppermute schedules
here.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..autograd_base import Operator
from .communicator import axis_size as _axis_size
from ..layer import Layer
from ..tensor import Tensor


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _pipe_descale(x, axis_name):
    """Identity whose transpose divides the cotangent by the pipe degree.

    In the Model's shard_map (replication checks off) every pipe member
    computes the downstream loss redundantly and injects a full cotangent;
    the last-stage psum broadcast's transpose then sums them, inflating
    every in-pipeline gradient by the pipe degree. This normalises at the
    pipeline boundary so stage-param and upstream grads equal the
    single-program values."""
    return x


def _pipe_descale_fwd(x, axis_name):
    return x, None


def _pipe_descale_bwd(axis_name, _res, g):
    return (g / _axis_size(axis_name),)


_pipe_descale.defvjp(_pipe_descale_fwd, _pipe_descale_bwd)


def _mark_varying(v, axis_name):
    """Mark a value device-varying over ``axis_name`` for shard_map's
    vma typecheck."""
    return jax.lax.pcast(v, (axis_name,), to="varying")


def _pipeline_fwd_core(dispatch, stage_params, x_microbatches, wire_shape,
                       wire_dtype, axis_name):
    """Generic GPipe forward scan. ``dispatch(params, a_wire, mb) ->
    a_wire`` is this device's stage applied to the wire activation (or,
    on stage 0, to the injected microbatch ``mb``). Returns the last
    stage's wire outputs (n_micro, *wire_shape), broadcast to all
    stages."""
    n = _axis_size(axis_name)
    sid = lax.axis_index(axis_name)
    n_micro = x_microbatches.shape[0]
    steps = n_micro + n - 1
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        prev_y = carry
        # activation produced upstream last tick arrives over the ring
        recv = lax.ppermute(prev_y, axis_name, fwd_perm)
        mb = lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
        y = dispatch(stage_params, recv, mb)
        return y, y

    # the carry becomes device-varying (stage params differ per pipe
    # member); mark the init accordingly for shard_map's vma typecheck
    init = _mark_varying(jnp.zeros(wire_shape, wire_dtype), axis_name)
    _, ys = lax.scan(step, init, jnp.arange(steps))

    # last stage's outputs at ticks n-1 .. steps-1 are microbatches 0..M-1
    outs = lax.dynamic_slice_in_dim(ys, n - 1, n_micro, axis=0)
    # broadcast them from the last stage to everyone
    return lax.psum(jnp.where(sid == n - 1, outs, jnp.zeros_like(outs)),
                    axis_name)


def pipeline_spmd(stage_fn, stage_params, x_microbatches, axis_name="pipe"):
    """Run a GPipe forward inside ``shard_map`` over ``axis_name``.

    Args:
      stage_fn: ``(params, activation) -> activation`` — this device's
        pipeline stage (all stages must preserve the activation shape).
      stage_params: this device's stage parameters (pytree; under
        shard_map give the global stacked params a P(axis_name, ...) spec
        so each device holds its own stage's slice).
      x_microbatches: (n_micro, mb, ...) — the microbatched global input
        (replicated; only stage 0 reads it).

    Returns (n_micro, mb, ...) outputs of the LAST stage, broadcast to all
    stages (so a replicated loss can follow).

    Schedule: t = 0..n_micro+n_stages-2; stage 0 injects microbatch t,
    stage s>0 consumes the activation stage s-1 produced at t-1.
    """

    def dispatch(params, a_wire, mb):
        a = jnp.where(lax.axis_index(axis_name) == 0, mb, a_wire)
        return stage_fn(params, a)

    return _pipeline_fwd_core(dispatch, stage_params, x_microbatches,
                              x_microbatches.shape[1:],
                              x_microbatches.dtype, axis_name)


def pipeline_1f1b(stage_fn, loss_fn, stage_params, x_microbatches,
                  y_microbatches, axis_name="pipe"):
    """One-forward-one-backward schedule inside ``shard_map``: loss and
    gradients in ONE pass with activation memory bounded by the pipe
    depth, not the microbatch count (GPipe autodiff stores every tick).

    Each scan tick runs one forward micro-step and one backward
    micro-step per stage. Stage ``s`` forwards microbatch ``t - s`` and
    backwards microbatch ``t - 2(S-1) + s``; activations hop forward and
    cotangents hop backward over the ICI ring each tick, and the backward
    recomputes the stage forward from the saved *input* activation (vjp
    residuals are never carried across ticks) — so the live state per
    stage is a ring of at most ``2(S-1)+1`` input activations.

    Args:
      stage_fn: ``(params, a) -> a`` shape-preserving stage.
      loss_fn: ``(a, y_mb) -> scalar`` applied at the LAST stage per
        microbatch (mean-reduced over microbatches in the result).
      stage_params: this device's stage params (pytree).
      x_microbatches / y_microbatches: (M, mb, ...) replicated inputs.

    Returns ``(loss, param_grads, dx_microbatches)`` — loss is the mean
    over microbatches (broadcast to all stages), ``param_grads`` is the
    gradient of that mean loss wrt THIS stage's params, and
    ``dx_microbatches`` is the cotangent reaching the pipeline input
    (nonzero on every stage after the final psum) for upstream layers.
    """
    def dispatch(params, a_wire, mb, _y_mb, _m_idx):
        a = jnp.where(lax.axis_index(axis_name) == 0, mb, a_wire)
        return stage_fn(params, a)

    return _pipeline_1f1b_core(
        dispatch, loss_fn, stage_params, x_microbatches, y_microbatches,
        x_microbatches.shape[1:], x_microbatches.dtype, axis_name)


def _pipeline_1f1b_core(dispatch, loss_fn, stage_params, x_microbatches,
                        y_microbatches, wire_shape, wire_dtype, axis_name):
    """Generic 1F1B scan shared by the homogeneous and heterogeneous
    APIs.

    ``dispatch(params, a_wire, mb, y_mb, m_idx) -> a_wire`` applies this
    device's stage: stage 0 reads the injected microbatch ``mb``, later
    stages read the wire activation, and a heterogeneous last stage may
    fold the per-microbatch loss into its wire output (with ``loss_fn``
    then just extracting it). ``m_idx`` is the microbatch index — the
    SAME value reaches the forward tick and that microbatch's backward
    recompute, so RNG-consuming stages (dropout) can fold a key from it
    and see identical draws in both (a stateful trace-time key would
    bake a DIFFERENT mask into the recompute, silently corrupting
    gradients). The ring stores WIRE inputs only — stage 0's input is
    re-read from ``x_microbatches`` at backward time, so heterogeneous
    input shapes never touch the ring.
    """
    S = _axis_size(axis_name)
    sid = lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    R = 2 * (S - 1) + 1                       # max in-flight per stage
    steps = M + 2 * (S - 1)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [((i + 1) % S, i) for i in range(S)]

    x_shape = x_microbatches.shape[1:]
    is_last = sid == S - 1

    def step(carry, t):
        fwd_out, cot_out, ring, gacc, lacc, dxbuf = carry

        # ---- forward tick: mb (t - sid) -----------------------------
        recv_act = lax.ppermute(fwd_out, axis_name, fwd_perm)
        m_f = t - sid
        f_on = (m_f >= 0) & (m_f < M)
        mb = lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(m_f, 0, M - 1), 0, keepdims=False)
        y_f = lax.dynamic_index_in_dim(
            y_microbatches, jnp.clip(m_f, 0, M - 1), 0, keepdims=False)
        slot_f = jnp.clip(m_f, 0, M - 1) % R
        ring = jnp.where(
            f_on,
            lax.dynamic_update_index_in_dim(ring, recv_act, slot_f, 0),
            ring)
        y_new = dispatch(stage_params, recv_act, mb, y_f,
                         jnp.clip(m_f, 0, M - 1))
        fwd_out = jnp.where(f_on, y_new, fwd_out)

        # ---- backward tick: mb (t - 2(S-1) + sid) -------------------
        recv_cot = lax.ppermute(cot_out, axis_name, bwd_perm)
        m_b = t - 2 * (S - 1) + sid
        b_on = (m_b >= 0) & (m_b < M)
        slot_b = jnp.clip(m_b, 0, M - 1) % R
        a_saved = lax.dynamic_index_in_dim(ring, slot_b, 0, keepdims=False)
        mb_b = lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(m_b, 0, M - 1), 0, keepdims=False)
        y_mb = lax.dynamic_index_in_dim(
            y_microbatches, jnp.clip(m_b, 0, M - 1), 0, keepdims=False)

        mi_b = jnp.clip(m_b, 0, M - 1)
        out, vjp_fn = jax.vjp(
            lambda p, a, x: dispatch(p, a, x, y_mb, mi_b),
            stage_params, a_saved, mb_b)
        loss_mb, dout = jax.value_and_grad(loss_fn)(out, y_mb)
        cot_eff = jnp.where(is_last, dout, recv_cot)
        dp, da, dmb = vjp_fn(cot_eff)

        gacc = jax.tree_util.tree_map(
            lambda g, d: g + jnp.where(b_on, d, jnp.zeros_like(d)),
            gacc, dp)
        lacc = lacc + jnp.where(is_last & b_on, loss_mb, 0.0)
        dxbuf = jnp.where(
            (sid == 0) & b_on,
            lax.dynamic_update_index_in_dim(dxbuf, dmb, mi_b, 0), dxbuf)
        cot_out = jnp.where(b_on, da, jnp.zeros_like(da))

        return (fwd_out, cot_out, ring, gacc, lacc, dxbuf), None

    init = (
        _mark_varying(jnp.zeros(wire_shape, wire_dtype), axis_name),
        _mark_varying(jnp.zeros(wire_shape, wire_dtype), axis_name),
        _mark_varying(jnp.zeros((R,) + tuple(wire_shape), wire_dtype),
                      axis_name),
        jax.tree_util.tree_map(
            lambda p: _mark_varying(jnp.zeros_like(p), axis_name),
            stage_params),
        _mark_varying(jnp.asarray(0.0, jnp.float32), axis_name),
        _mark_varying(jnp.zeros((M,) + x_shape, x_microbatches.dtype),
                      axis_name),
    )
    (fwd_out, cot_out, ring, gacc, lacc, dxbuf), _ = \
        lax.scan(step, init, jnp.arange(steps))

    loss = lax.psum(jnp.where(is_last, lacc, 0.0), axis_name) / M
    grads = jax.tree_util.tree_map(lambda g: g / M, gacc)
    dx = lax.psum(jnp.where(sid == 0, dxbuf, jnp.zeros_like(dxbuf)),
                  axis_name) / M
    return loss, grads, dx


def stack_stage_params(per_stage_params):
    """[stage0_pytree, stage1_pytree, ...] -> stacked pytree with a leading
    stage axis, ready for a P('pipe', ...) sharding."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


def microbatch(x, n_micro):
    """(B, ...) -> (n_micro, B/n_micro, ...)"""
    B = x.shape[0]
    assert B % n_micro == 0, f"batch {B} not divisible by {n_micro}"
    return x.reshape((n_micro, B // n_micro) + x.shape[1:])


# ---------------------------------------------------------------------------
# Layer/Model API integration
# ---------------------------------------------------------------------------

class _Pipeline(Operator):
    """Tape op running the GPipe schedule. Inside the compiled shard_map'd
    step (mesh 'pipe' axis active) each pipe member holds its stage's
    (1, ...) slice of the stacked params and activations ride the ring;
    outside a mesh (the eager first step, eval, single-device) the stages
    run sequentially — identical math, so eager/compiled parity holds."""

    def __init__(self, stage_apply, n_stages, n_micro, axis="pipe"):
        super().__init__()
        self.stage_apply = stage_apply
        self.n_stages = n_stages
        self.n_micro = n_micro
        self.axis = axis
        self._mesh_branch = False

    def forward(self, x, *stacked):
        from .communicator import active_axis
        if active_axis(self.axis):
            self._mesh_branch = True
            assert stacked[0].shape[0] == 1, \
                f"mesh 'pipe' axis must have degree n_stages=" \
                f"{self.n_stages}; got param slice {stacked[0].shape}"
            local = [s[0] for s in stacked]
            x_mb = microbatch(x, self.n_micro)
            out = pipeline_spmd(
                lambda params, a: self.stage_apply(params, a),
                local, x_mb, self.axis)
            return _pipe_descale(out.reshape((-1,) + out.shape[2:]),
                                 self.axis)
        self._mesh_branch = False
        a = x
        for i in range(self.n_stages):
            a = self.stage_apply([s[i] for s in stacked], a)
        return a


def _make_1f1b_loss(stage_fn, loss_fn, axis_name):
    """Wrap the 1F1B schedule as a custom-vjp scalar-loss function, so
    differentiating it hands back the schedule's OWN gradients instead of
    autodiffing through the scan (which would re-materialise every tick's
    activations — the exact cost 1F1B exists to avoid)."""

    @jax.custom_vjp
    def f(params_local, x_mb, y_mb):
        loss, _, _ = pipeline_1f1b(stage_fn, loss_fn, params_local,
                                   x_mb, y_mb, axis_name)
        return loss

    def f_fwd(params_local, x_mb, y_mb):
        loss, grads, dx = pipeline_1f1b(stage_fn, loss_fn, params_local,
                                        x_mb, y_mb, axis_name)
        return loss, (grads, dx, y_mb)

    def f_bwd(res, ct):
        grads, dx, y_mb = res
        return (jax.tree_util.tree_map(lambda g: g * ct, grads),
                dx * ct, jnp.zeros_like(y_mb))

    f.defvjp(f_fwd, f_bwd)
    return f


class _Pipeline1F1B(Operator):
    """Tape op: (x, y, *stacked_params) -> scalar loss via the 1F1B
    schedule when the 'pipe' mesh axis is active; sequential identical
    math otherwise (eager first step / single device)."""

    def __init__(self, stage_apply, loss_fn, n_stages, n_micro,
                 axis="pipe"):
        super().__init__()
        self.stage_apply = stage_apply
        self.loss_fn = loss_fn
        self.n_stages = n_stages
        self.n_micro = n_micro
        self.axis = axis

    def forward(self, x, y, *stacked):
        from .communicator import active_axis
        x_mb = microbatch(x, self.n_micro)
        y_mb = microbatch(y, self.n_micro)
        if active_axis(self.axis):
            assert stacked[0].shape[0] == 1, \
                f"mesh 'pipe' axis must have degree n_stages=" \
                f"{self.n_stages}; got param slice {stacked[0].shape}"
            local = tuple(s[0] for s in stacked)
            f = _make_1f1b_loss(self.stage_apply, self.loss_fn, self.axis)
            return f(local, x_mb, y_mb)
        def one(xm, ym):
            a = xm
            for i in range(self.n_stages):
                a = self.stage_apply(tuple(s[i] for s in stacked), a)
            return self.loss_fn(a, ym)
        # vmap over microbatches: trace size stays O(n_stages)
        return jnp.mean(jax.vmap(one)(x_mb, y_mb))


class PipelineModule(Layer):
    """A pipeline-parallel stack of ``n_stages`` structurally identical
    stages, reachable from the Layer/Model API: drop it into a Model's
    forward and give the DistOpt mesh a 'pipe' axis of degree n_stages.

    ``stage_init(rng, x_shape) -> [arrays]`` builds one stage's params;
    ``stage_apply(params, a) -> a`` applies a stage (must preserve the
    activation shape — the GPipe ring rotates a fixed-shape buffer).
    Stage params are stacked on a leading axis and sharded P('pipe', ...),
    so each pipe member materialises only its own stage (optimizer
    moments inherit the spec and shard the same way).
    """

    def __init__(self, stage_apply, stage_init, n_stages, n_micro,
                 axis="pipe"):
        super().__init__()
        self.stage_apply = stage_apply
        self.stage_init = stage_init
        self.n_stages = n_stages
        self.n_micro = n_micro
        self.axis = axis

    def initialize(self, x):
        rng = np.random.RandomState(0)
        per_stage = [list(self.stage_init(rng, x.shape))
                     for _ in range(self.n_stages)]
        self._params = []
        for j in range(len(per_stage[0])):
            stacked = jnp.stack([jnp.asarray(per_stage[i][j])
                                 for i in range(self.n_stages)])
            t = Tensor(data=stacked, device=x.device, requires_grad=True)
            t.stores_grad = True
            t.spec = P(self.axis)
            self._params.append(t)

    def forward(self, x):
        return _Pipeline(self.stage_apply, self.n_stages, self.n_micro,
                         self.axis)(x, *self._params)

    def _own_params(self):
        return {f"stage_param{j}": t for j, t in enumerate(self._params)}


class PipelineModule1F1B(PipelineModule):
    """Pipeline stack trained with the 1F1B schedule: the per-microbatch
    loss lives INSIDE the schedule, so ``forward(x, y)`` returns the mean
    loss directly (activation memory bounded by pipe depth). ``forward(x)``
    without targets falls back to the GPipe forward for inference."""

    def __init__(self, stage_apply, stage_init, loss_fn, n_stages, n_micro,
                 axis="pipe"):
        super().__init__(stage_apply, stage_init, n_stages, n_micro, axis)
        self.loss_fn = loss_fn

    def initialize(self, x, y=None):
        super().initialize(x)

    def forward(self, x, y=None):
        if y is None:
            return super().forward(x)
        return _Pipeline1F1B(self.stage_apply, self.loss_fn,
                             self.n_stages, self.n_micro,
                             self.axis)(x, y, *self._params)


# ---------------------------------------------------------------------------
# heterogeneous stages: embedding -> blocks -> head
# ---------------------------------------------------------------------------

class _StagePack:
    """Flat-packing metadata for one stage's params. Each stage's Layer
    tensors are absorbed into one float32 row of a (S, Lmax) stack
    (sharded P('pipe'), so a pipe member materialises only its own
    stage), and unpacked back into the live tensors inside the traced
    stage apply — different stages may have entirely different param
    pytrees."""

    def __init__(self, tensors, row_dtype=jnp.float32):
        self.tensors = tensors
        self.row_dtype = jnp.dtype(row_dtype)
        self.shapes = [tuple(t.shape) for t in tensors]
        self.dtypes = [jnp.asarray(t.data).dtype for t in tensors]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.offsets = np.cumsum([0] + self.sizes[:-1]).tolist()
        self.size = int(sum(self.sizes))

    def pack(self):
        if not self.tensors:
            return jnp.zeros((0,), self.row_dtype)
        # via host: freshly-initialized params may sit on DIFFERENT
        # device sets (rng-derived ones inherit a mesh-replicated key's
        # devices, zeros-inits sit on the default device) and a device
        # concatenate across those sets is an error. One-time init cost.
        return jnp.asarray(np.concatenate([
            np.asarray(jax.device_get(t.data), np.float32).reshape(-1)
            for t in self.tensors])).astype(self.row_dtype)

    def unpack_into(self, flat):
        for t, shape, dtype, off, size in zip(
                self.tensors, self.shapes, self.dtypes, self.offsets,
                self.sizes):
            t.data = flat[off:off + size].reshape(shape).astype(dtype)


def _feat(shape):
    return int(np.prod(shape[1:])) if len(shape) > 1 else 1


class HeteroPipeline1F1B(Layer):
    """1F1B pipeline over HETEROGENEOUS stages: a list of per-stage Layer
    stacks with different parameters and different activation shapes at
    every boundary (embedding -> transformer blocks -> head, or a ResNet
    with downsampling at stage boundaries).

    TPU-native design: the program stays SPMD over the 'pipe' mesh axis —
    activations cross stage boundaries as flat padded (mb, wire) float32
    buffers riding `lax.ppermute` over ICI, and a `lax.switch` on the
    stage index applies this member's stage, unflattening its own static
    shapes. The last stage folds the per-microbatch loss into its wire
    output, so the schedule core never materialises logits on the wire.

    ``stages``: Layers (or Layer-like callables Tensor -> Tensor), one
    per pipe member, initialized lazily at microbatch shape.
    ``loss_fn(out_array, y_mb_array) -> scalar`` applies at the last
    stage. ``forward(x, y)`` returns the mean microbatch loss;
    ``forward(x)`` runs the GPipe forward for inference.

    The training input x must be float (LM token ids as float work; the
    embedding gather's index cast handles them) — integer inputs would
    need float0 cotangent plumbing.
    """

    def __init__(self, stages, loss_fn, n_micro, axis="pipe",
                 wire_dtype="float32", param_dtype="float32"):
        super().__init__()
        self._stages = list(stages)   # underscore: NOT sublayers — the
        self._loss_fn = loss_fn       # packed stack is the only state
        self.n_micro = n_micro
        self.axis = axis
        # "bfloat16" halves the ICI bytes of every activation AND
        # cotangent hop (the pipeline analogue of the 'half' dist
        # option); loss accumulation stays float32.
        # NOTE on the wire width: one max-over-boundaries width is a
        # DESIGN requirement, not laziness — the wire is a single SPMD
        # array ppermuted around the ring while different members sit at
        # different boundaries in the same tick, so per-boundary widths
        # cannot exist without per-member array shapes (not expressible
        # under shard_map). wire_dtype is the lever that actually
        # shrinks hop bytes.
        self._wire_dtype = jnp.dtype(wire_dtype)
        # "bfloat16" also halves the packed param stack's HBM (a
        # bf16-param model otherwise pays 2x for f32 rows). The rows ARE
        # the master copy, so optimizer updates quantize to bf16 — the
        # same trade as bf16 training anywhere else.
        self._param_dtype = jnp.dtype(param_dtype)

    def initialize(self, x, y=None):
        B = x.shape[0]
        assert B % self.n_micro == 0, \
            f"batch {B} not divisible by n_micro={self.n_micro}"
        mb = B // self.n_micro
        self._dev = x.device
        self._in_shapes, self._out_shapes, self._act_dtypes = [], [], []

        # thread a microbatch ABSTRACTLY through the stages to learn each
        # boundary's shape: stage param creation still executes concretely
        # (Layer.__call__ wraps initialize in ensure_compile_time_eval)
        # but the inter-stage forwards trace with zero device compute —
        # a concrete rehearsal would also mix devices when the rng key is
        # mesh-replicated from an earlier compiled step
        def thread(ab):
            a = Tensor(data=ab, device=x.device, requires_grad=False)
            for stage in self._stages:
                self._in_shapes.append(tuple(a.shape))
                a = stage(a)
                self._out_shapes.append(tuple(a.shape))
                self._act_dtypes.append(jnp.asarray(a.data).dtype)
            return a.data

        jax.eval_shape(thread, jax.ShapeDtypeStruct(
            (mb,) + tuple(x.shape[1:]), jnp.asarray(x.data).dtype))
        self._packs = [_StagePack(list(stage.get_params().values()),
                                  self._param_dtype)
                       if isinstance(stage, Layer)
                       else _StagePack([], self._param_dtype)
                       for stage in self._stages]
        lmax = max([p.size for p in self._packs] + [1])
        rows = [jnp.pad(p.pack(), (0, lmax - p.size))
                for p in self._packs]
        t = Tensor(data=jnp.stack(rows), device=x.device,
                   requires_grad=True)
        t.stores_grad = True
        t.spec = P(self.axis)
        self._stacked = t
        # wire width: largest INTER-stage boundary (the last stage's
        # output never rides the wire in 1F1B) + one slot for the
        # per-microbatch loss scalar
        self._wire_train = max(
            [_feat(s) for s in self._out_shapes[:-1]] + [1]) + 1
        # inference wire must carry the last stage's output too
        self._wire_fwd = max(_feat(s) for s in self._out_shapes)

    def _apply_stage(self, s, a_array):
        out = self._stages[s](Tensor(data=a_array, device=self._dev,
                                     requires_grad=False))
        return out.data

    def _stage_in(self, s, a_wire, mb_x):
        """This stage's input: the injected microbatch for stage 0, else
        the wire buffer unflattened to the boundary's shape. Only FEATURE
        dims are static — under dp the local microbatch is smaller than
        at init time."""
        if s == 0:
            return mb_x
        in_shape = self._in_shapes[s]
        return a_wire[:, :_feat(in_shape)] \
            .reshape((a_wire.shape[0],) + in_shape[1:]) \
            .astype(self._act_dtypes[s - 1])

    def _to_wire(self, o, n_rows, wire):
        of = o.reshape(o.shape[0], -1).astype(self._wire_dtype)
        return jnp.zeros((n_rows, wire), self._wire_dtype) \
            .at[:, :of.shape[1]].set(of)

    def _branch_train(self, s, n_stages):
        wire = self._wire_train

        def fn(flat, a_wire, mb_x, y_mb, key_m):
            # deterministic per-(microbatch, stage) stream: the SAME key
            # reaches this branch at the forward tick and at that
            # microbatch's backward recompute, so RNG layers (dropout)
            # draw identical masks in both — a stateful trace-time key
            # would bake a different mask into the recompute and
            # silently corrupt gradients
            self._dev._set_rng_state(jax.random.fold_in(key_m, s))
            self._packs[s].unpack_into(flat)
            o = self._apply_stage(s, self._stage_in(s, a_wire, mb_x))
            if s == n_stages - 1:
                loss = self._loss_fn(o, y_mb)
                return jnp.zeros((a_wire.shape[0], wire),
                                 self._wire_dtype) \
                    .at[0, -1].set(loss.astype(self._wire_dtype))
            return self._to_wire(o, a_wire.shape[0], wire)

        return fn

    def _branch_fwd(self, s, n_stages):
        wire = self._wire_fwd

        def fn(flat, a_wire, mb_x):
            self._packs[s].unpack_into(flat)
            o = self._apply_stage(s, self._stage_in(s, a_wire, mb_x))
            return self._to_wire(o, a_wire.shape[0], wire)

        return fn

    def _sequential(self, stacked, x_mb, y_mb=None, base_key=None):
        """Identical math without a mesh (eager first step, single
        device): unpack every stage once, then vmap over microbatches,
        folding the SAME per-(microbatch, stage) rng keys as the mesh
        schedule so dropout draws match across paths."""
        for row, pack in zip(stacked, self._packs):
            pack.unpack_into(row)
        if base_key is None:
            base_key = self._dev._get_rng_state()

        def stage_seq(xm, idx):
            a = xm
            for s in range(len(self._stages)):
                self._dev._set_rng_state(
                    jax.random.fold_in(jax.random.fold_in(base_key, idx),
                                       s))
                a = self._apply_stage(s, a)
            return a

        idxs = jnp.arange(x_mb.shape[0])
        if y_mb is None:
            return jax.vmap(stage_seq)(x_mb, idxs)

        def one(xm, ym, idx):
            return self._loss_fn(stage_seq(xm, idx), ym)

        return jnp.mean(jax.vmap(one)(x_mb, y_mb, idxs))

    def forward(self, x, y=None):
        if y is None:
            return _PipelineHetFwd(self)(x, self._stacked)
        return _PipelineHet1F1B(self)(x, y, self._stacked)

    def _own_params(self):
        return {"stages_packed": self._stacked}


def _make_het_1f1b_loss(make_dispatch, wire_shape, axis_name,
                        wire_dtype=jnp.float32):
    """custom-vjp wrapper: differentiating the scalar loss hands back the
    1F1B schedule's OWN gradients instead of autodiffing the scan. The
    rng base key is an explicit argument (custom_vjp forbids closing
    over tracers) with a float0 cotangent."""
    def extract(w, _y):
        return w[0, -1].astype(jnp.float32)

    def run(flat_local, x_mb, y_mb, base_key):
        return _pipeline_1f1b_core(
            make_dispatch(base_key), extract, flat_local, x_mb, y_mb,
            wire_shape, wire_dtype, axis_name)

    @jax.custom_vjp
    def f(flat_local, x_mb, y_mb, base_key):
        return run(flat_local, x_mb, y_mb, base_key)[0]

    def f_fwd(flat_local, x_mb, y_mb, base_key):
        loss, grads, dx = run(flat_local, x_mb, y_mb, base_key)
        return loss, (grads, dx, y_mb, base_key)

    def f_bwd(res, ct):
        grads, dx, y_mb, base_key = res
        return (jax.tree_util.tree_map(lambda g: g * ct, grads),
                dx * ct, jnp.zeros_like(y_mb),
                np.zeros(np.shape(base_key), jax.dtypes.float0))

    f.defvjp(f_fwd, f_bwd)
    return f


class _PipelineHet1F1B(Operator):
    """Tape op: (x, y, stacked_flat) -> scalar loss via the 1F1B schedule
    over heterogeneous stages when the 'pipe' axis is active; sequential
    identical math otherwise."""

    def __init__(self, module):
        super().__init__()
        self.m = module

    def forward(self, x, y, stacked):
        from .communicator import active_axis
        m = self.m
        if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            raise TypeError(
                "HeteroPipeline1F1B training input must be float "
                f"(got {jnp.asarray(x).dtype}); cast token ids to float")
        x_mb = microbatch(x, m.n_micro)
        y_mb = microbatch(y, m.n_micro)
        if active_axis(m.axis):
            S = len(m._stages)
            assert stacked.shape[0] == 1, \
                f"mesh '{m.axis}' axis degree must equal " \
                f"n_stages={S}; got param slice {stacked.shape}"
            branches = [m._branch_train(s, S) for s in range(S)]

            def make_dispatch(base_key):
                def dispatch(flat, a_wire, mb_x, y_m, m_idx):
                    key_m = jax.random.fold_in(base_key, m_idx)
                    return lax.switch(lax.axis_index(m.axis), branches,
                                      flat, a_wire, mb_x, y_m, key_m)
                return dispatch

            base_key = m._dev._get_rng_state()
            f = _make_het_1f1b_loss(
                make_dispatch, (x_mb.shape[1], m._wire_train), m.axis,
                m._wire_dtype)
            out = f(stacked[0], x_mb, y_mb, base_key)
            # branch traces left the device key holding inner tracers;
            # restore a deterministic continuation of the stream
            m._dev._set_rng_state(jax.random.fold_in(base_key, 0x8157))
            return out
        base_key = m._dev._get_rng_state()
        out = m._sequential(stacked, x_mb, y_mb, base_key)
        m._dev._set_rng_state(jax.random.fold_in(base_key, 0x8157))
        return out


class _PipelineHetFwd(Operator):
    """Tape op: (x, stacked_flat) -> last-stage output via the GPipe
    forward over heterogeneous stages (inference path)."""

    def __init__(self, module):
        super().__init__()
        self.m = module

    def forward(self, x, stacked):
        from .communicator import active_axis
        m = self.m
        x_mb = microbatch(x, m.n_micro)
        if active_axis(m.axis):
            S = len(m._stages)
            assert stacked.shape[0] == 1
            branches = [m._branch_fwd(s, S) for s in range(S)]

            def dispatch(flat, a_wire, mb_x):
                return lax.switch(lax.axis_index(m.axis), branches,
                                  flat, a_wire, mb_x)

            w = _pipeline_fwd_core(dispatch, stacked[0], x_mb,
                                   (x_mb.shape[1], m._wire_fwd),
                                   m._wire_dtype, m.axis)
            w = _pipe_descale(w, m.axis)
            out_shape = m._out_shapes[-1]
            o = w[:, :, :_feat(out_shape)].reshape(
                (m.n_micro, x_mb.shape[1]) + out_shape[1:]) \
                .astype(m._act_dtypes[-1])
            return o.reshape((-1,) + out_shape[1:])
        out = m._sequential(stacked, x_mb)
        return out.reshape((-1,) + out.shape[2:])
