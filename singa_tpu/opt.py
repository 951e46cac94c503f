"""Optimizers and the distributed optimizer driver.

Capability parity with reference python/singa/opt.py:
- tensor-resident scheduled hyperparameters (DecayScheduler, opt.py:28-68)
  so the learning rate is a traced value — schedules advance inside the
  compiled step with no recompilation;
- SGD/RMSProp/AdaGrad/Adam with the same update math (opt.py:174-660);
- DistOpt (opt.py:686-1094) whose all-reduce is `jax.lax.psum` over the mesh
  'data' axis instead of NCCL: the reference's fused-buffer trick
  (Communicator::fusedSynch) is unnecessary because XLA fuses and overlaps
  collectives; fp16 comm becomes bf16-cast-before-psum; topK/threshold
  sparsification is reproduced with mask + error-feedback residuals.

Because ``autograd.backward`` yields (param, grad) lazily, each all-reduce is
issued as soon as that gradient is complete — inside one jit trace XLA then
overlaps collectives with remaining backward compute, which is the TPU form
of the reference's stream-overlap design (opt.py:826-865).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd
from .tensor import Tensor


class DecayScheduler:
    """lr(step) as a traced function (reference opt.py:28-45)."""

    def __init__(self, init_value):
        self.init_value = init_value

    def __call__(self, step):
        raise NotImplementedError

    def get_states(self):
        return {"init_value": self.init_value}

    def set_states(self, states):
        if "init_value" in states:
            self.init_value = float(states["init_value"])


class Constant(DecayScheduler):
    def __call__(self, step):
        return jnp.asarray(self.init_value, dtype=jnp.float32)


class ExponentialDecay(DecayScheduler):
    def __init__(self, init_value, decay_steps, decay_rate, staircase=False):
        super().__init__(init_value)
        self.decay_steps = decay_steps
        self.decay_rate = decay_rate
        self.staircase = staircase

    def __call__(self, step):
        s = step.data if isinstance(step, Tensor) else step
        s = s.astype(jnp.float32)
        e = s / self.decay_steps
        if self.staircase:
            e = jnp.floor(e)
        return self.init_value * jnp.power(self.decay_rate, e)


class Regularizer:
    """Parameter-gradient regularizer (reference
    include/singa/model/optimizer.h:151-244, src/model/optimizer/
    optimizer.cc:92-99: L2 is ``grad += coefficient * value``).

    Functional: ``apply`` returns the new gradient array so it composes
    inside a jit-traced update."""

    def __init__(self, type="l2", coefficient=0.0):
        self.type = type.lower()
        if self.type not in ("l1", "l2", "notset"):
            raise ValueError(f"unknown regularizer type {type!r}")
        self.coefficient = coefficient

    def apply(self, value, grad):
        if self.type == "l2":
            return grad + self.coefficient * value
        if self.type == "l1":
            return grad + self.coefficient * jnp.sign(value)
        return grad


class Constraint:
    """Parameter-gradient constraint (reference optimizer.h:101-144: clip
    the gradient's L2 norm to a threshold; the reference declares the API
    and documents the semantics but stubs the math — here it is real)."""

    def __init__(self, type="l2", threshold=1.0):
        self.type = type.lower()
        if self.type not in ("l2", "value", "notset"):
            raise ValueError(f"unknown constraint type {type!r}")
        self.threshold = threshold

    def apply(self, value, grad):
        if self.type == "l2":
            norm = jnp.sqrt(jnp.sum(grad.astype(jnp.float32) ** 2))
            scale = jnp.minimum(1.0, self.threshold / (norm + 1e-12))
            return grad * scale.astype(grad.dtype)
        if self.type == "value":
            return jnp.clip(grad, -self.threshold, self.threshold)
        return grad


class Optimizer:
    """Base optimizer (reference opt.py:71-173). Aux states are Tensors so
    the whole update is jit-traceable and thread-able as donated state.

    Regularizer/Constraint/lr-multiplier registration mirrors reference
    Optimizer::Register + ApplyRegularizerConstraint (include/singa/model/
    optimizer.h:44-100, src/model/optimizer/optimizer.cc:36-77): per-param
    entries win over the global default."""

    def __init__(self, lr):
        self.lr = lr if isinstance(lr, DecayScheduler) else Constant(lr)
        self.step_counter = Tensor(shape=(), dtype=jnp.float32,
                                   requires_grad=False)
        self.step_counter.name = "step_counter"
        # dynamic-loss-scale state lives WITH the optimizer (not the
        # guard that drives it) so every checkpoint route — zip
        # save_states, Snapshot, the async sharded manager — carries it
        # and a resumed run continues with the backed-off scale instead
        # of re-diverging at the stale one. 1.0 = scaling inactive.
        self.loss_scale = Tensor(shape=(), dtype=jnp.float32,
                                 requires_grad=False)
        self.loss_scale.data = jnp.ones((), jnp.float32)
        self.loss_scale.name = "loss_scale"
        self._aux = {}  # name -> Tensor, created lazily per param
        self.regularizer = None       # global default
        self.constraint = None        # global default
        self._regularizers = {}       # per-param overrides
        self._constraints = {}
        self._lr_multipliers = {}

    def register(self, name, regularizer=None, constraint=None,
                 lr_multiplier=None):
        """Attach a per-param regularizer/constraint/lr multiplier
        (reference Optimizer::Register, optimizer.cc:36-56)."""
        if regularizer is not None:
            self._regularizers[name] = regularizer
        if constraint is not None:
            self._constraints[name] = constraint
        if lr_multiplier is not None:
            self._lr_multipliers[name] = float(lr_multiplier)

    def apply_regularizer_constraint(self, name, value, grad):
        """Regularizer first, then constraint (reference
        Optimizer::ApplyRegularizerConstraint, optimizer.cc:63-77)."""
        reg = self._regularizers.get(name, self.regularizer)
        if reg is not None:
            grad = reg.apply(value, grad)
        con = self._constraints.get(name, self.constraint)
        if con is not None:
            grad = con.apply(value, grad)
        return grad

    def _scaled_lr(self, name):
        mult = self._lr_multipliers.get(name)
        return self.lr_value * mult if mult is not None else self.lr_value

    def _fused_ok(self, name, p):
        """Whether THIS param's update may take the fused Pallas kernel:
        the optimizer was built with ``fused=True``, no regularizer or
        constraint applies to the param (their math is caller-composed
        and stays on the reference path — declining keeps them correct
        rather than silently dropped), the param is floating, and the
        backend-eligibility gate (``ops.fused_optim.available``) says a
        kernel launch pays for itself. Everything else falls through to
        the reference elementwise chain, per-param."""
        if not getattr(self, "fused", False):
            return False
        if self._regularizers.get(name, self.regularizer) is not None:
            return False
        if self._constraints.get(name, self.constraint) is not None:
            return False
        if not jnp.issubdtype(p.dtype, jnp.floating):
            return False
        from .ops import fused_optim
        return fused_optim.available(int(np.prod(p.shape)))

    # -- lr as a traced value --------------------------------------------
    @property
    def lr_value(self):
        return self.lr(self.step_counter)

    def should_apply_weight_decay(self, name):
        return True

    def telemetry_info(self):
        """Static facts for the telemetry layer (recorded once as
        labels/gauges at run start — NEVER per step: the schedule's
        current value lives in the traced ``lr_value``, and reading it
        back would add a device round trip). Wrappers (DistOpt,
        GuardedOptimizer) delegate through ``__getattr__``, so the run
        record names the innermost real optimizer."""
        return {"optimizer": type(self).__name__,
                "lr": float(self.lr.init_value)
                if hasattr(self.lr, "init_value") else None}

    # -- train driving -----------------------------------------------------
    def __call__(self, loss):
        self.backward_and_update(loss)

    def backward_and_update(self, loss):
        for p, g in autograd.backward(loss):
            self.apply(p.name or f"param/{id(p)}", p, g)
        self.step()

    def step(self):
        self.step_counter.data = self.step_counter.data + 1.0

    def apply(self, param_name, param_value, param_grad):
        raise NotImplementedError

    # -- state -------------------------------------------------------------
    def _get_aux(self, key, like):
        t = self._aux.get(key)
        if t is None:
            if getattr(self, "_frozen", False):
                raise RuntimeError(
                    f"optimizer aux state '{key}' created inside a compiled "
                    "step; it would silently reset every iteration. All aux "
                    "state must be materialised by the first (eager) step.")
            t = Tensor(shape=like.shape, device=like.device,
                       dtype=like.dtype, requires_grad=False)
            t.spec = like.spec  # momentum/moments shard like their param
            self._aux[key] = t
        return t

    def state_tensors(self):
        """All mutable optimizer state, for jit state-threading."""
        return [self.step_counter, self.loss_scale] + \
            list(self._aux.values())

    def state_tensor_dict(self):
        """name -> LIVE state Tensor — no gather, no host copy; the
        sharded-checkpointing counterpart of get_states (which pulls
        everything to host for the zip route)."""
        d = {"step_counter": self.step_counter,
             "loss_scale": self.loss_scale}
        d.update(self._aux)
        return d

    def restore_state_tensor(self, name, array, spec=None):
        """Set one live state entry from a restored (possibly sharded)
        array, creating lazily-built aux that does not exist yet (the
        fresh-process resume path). ``spec`` announces the mesh layout
        for a freshly created entry (momentum shards like its param)."""
        if name == "step_counter":
            self.step_counter.data = jnp.asarray(array)
            return
        if name == "loss_scale":
            self.loss_scale.data = jnp.asarray(array)
            return
        t = self._aux.get(name)
        if t is None:
            t = Tensor(data=array, requires_grad=False)
            t.spec = spec
            self._aux[name] = t
        else:
            t.data = array

    def get_states(self):
        from .tensor import to_host_tree
        states = {"step_counter": np.asarray(self.step_counter.data),
                  "loss_scale": np.asarray(self.loss_scale.data)}
        # batched gather: host-sharded aux (e.g. expert momentum) pays
        # one cross-process collective for the whole dict
        states.update(to_host_tree({k: v.data
                                    for k, v in self._aux.items()}))
        return states

    def set_states(self, states):
        if "step_counter" in states:
            self.step_counter.data = jnp.asarray(states["step_counter"])
        if "loss_scale" in states:
            self.loss_scale.data = jnp.asarray(
                states["loss_scale"], dtype=jnp.float32)
        for k, v in states.items():
            if k in ("step_counter", "loss_scale"):
                continue
            if k in self._aux:
                # keep the live buffer's dtype: checkpoints store bf16
                # aux as portable f32, and a dtype flip here would leak
                # f32 into the compiled bf16 update step
                self._aux[k].data = jnp.asarray(
                    v, dtype=self._aux[k].data.dtype)
            else:
                self._aux[k] = Tensor(data=np.asarray(v),
                                      requires_grad=False)

    def announce_aux_specs(self, params_by_name):
        """Re-attach mesh layouts to aux entries restored without one
        (``set_states`` on a fresh optimizer creates bare Tensors): an
        aux named ``<param>:<kind>`` shards like its param. Without this
        a restored momentum for a tensor-parallel weight would enter the
        compiled step replicated at full shape and collide with the
        local-shard gradient."""
        for k, t in self._aux.items():
            if getattr(t, "spec", None) is None:
                src = params_by_name.get(k.rsplit(":", 1)[0])
                if src is not None and getattr(src, "spec", None) is not None:
                    t.spec = src.spec


class SGD(Optimizer):
    """SGD with momentum / nesterov / weight decay (reference opt.py:174-334,
    update composed of the same axpy algebra, now one fused XLA kernel).

    ``fused=True`` routes eligible per-param updates through the
    one-HBM-pass Pallas kernel (``ops.fused_optim.sgd_momentum_update``;
    momentum runs only — a momentum-less SGD has no aux to fuse with).
    Ineligible params (regularizer/constraint attached, too small for a
    kernel launch, non-TPU backend without the interpret test hook)
    keep the reference path per-param. Parity is pinned in
    tests/test_fused_kernels.py; bench selects the mode by its
    ``BENCH_FUSED_OPTIM`` pin — never unconditionally."""

    def __init__(self, lr=0.1, momentum=0.0, dampening=0.0,
                 weight_decay=0.0, nesterov=False, fused=False):
        super().__init__(lr)
        self.momentum = momentum
        self.dampening = dampening
        self.weight_decay = weight_decay
        self.nesterov = nesterov
        self.fused = bool(fused)
        if nesterov and (momentum <= 0 or dampening != 0):
            raise ValueError(
                "Nesterov momentum requires momentum>0 and dampening=0")

    def apply(self, name, p: Tensor, g: Tensor):
        grad = g.data if isinstance(g, Tensor) else g
        grad = grad.astype(p.dtype)
        wd = self.weight_decay \
            if self.weight_decay != 0 and \
            self.should_apply_weight_decay(name) else 0.0
        if self.momentum != 0 and self._fused_ok(name, p):
            from .ops import fused_optim
            buf = self._get_aux(f"{name}:momentum", p)
            p.data, buf.data = fused_optim.sgd_momentum_update(
                p.data, grad, buf.data, self._scaled_lr(name),
                momentum=self.momentum, dampening=self.dampening,
                weight_decay=wd, nesterov=self.nesterov)
            return
        if wd:
            grad = grad + wd * p.data
        grad = self.apply_regularizer_constraint(name, p.data, grad)
        if self.momentum != 0:
            buf = self._get_aux(f"{name}:momentum", p)
            buf.data = (self.momentum * buf.data
                        + (1 - self.dampening) * grad).astype(buf.dtype)
            grad = grad + self.momentum * buf.data if self.nesterov \
                else buf.data
        # update math promotes to f32 for low-precision params (the traced
        # lr is f32); store back in the param's dtype so bf16/fp16 training
        # keeps its precision class instead of silently upcasting
        p.data = (p.data - self._scaled_lr(name) * grad).astype(p.dtype)


class RMSProp(Optimizer):
    """(reference opt.py:336-442)

    ``fused=True`` routes eligible per-param updates through the
    one-HBM-pass Pallas kernel (``ops.fused_optim.rmsprop_update``:
    grad + master + rms read once, master + rms written once, aliased
    in place). Same per-param decline rules as ``SGD(fused=True)`` —
    regularizer/constraint attached, non-floating param, or too small
    for a kernel launch keeps the reference elementwise chain — same
    interpret-mode parity pin in the ``pallas`` tier, same
    ``step_flops`` reference-twin registration (the kernel marks the
    trace collector, so fused and unfused programs report identical
    FLOPs)."""

    def __init__(self, lr=0.1, rho=0.9, epsilon=1e-8, weight_decay=0.0,
                 fused=False):
        super().__init__(lr)
        self.rho = rho
        self.epsilon = epsilon
        self.weight_decay = weight_decay
        self.fused = bool(fused)

    def apply(self, name, p: Tensor, g: Tensor):
        grad = (g.data if isinstance(g, Tensor) else g).astype(p.dtype)
        if self._fused_ok(name, p):
            from .ops import fused_optim
            rms = self._get_aux(f"{name}:rms", p)
            p.data, rms.data = fused_optim.rmsprop_update(
                p.data, grad, rms.data, self._scaled_lr(name),
                rho=self.rho, epsilon=self.epsilon,
                weight_decay=self.weight_decay)
            return
        if self.weight_decay != 0:
            grad = grad + self.weight_decay * p.data
        grad = self.apply_regularizer_constraint(name, p.data, grad)
        rms = self._get_aux(f"{name}:rms", p)
        rms.data = (self.rho * rms.data
                    + (1 - self.rho) * grad * grad).astype(rms.dtype)
        p.data = (p.data - self._scaled_lr(name) * grad
                  / jnp.sqrt(rms.data + self.epsilon)).astype(p.dtype)


class AdaGrad(Optimizer):
    """(reference opt.py:444-534)

    ``fused=True``: eligible params update through the one-HBM-pass
    Pallas kernel (``ops.fused_optim.adagrad_update``). Same
    gating/parity/FLOPs-twin story as ``RMSProp(fused=True)``."""

    def __init__(self, lr=0.1, epsilon=1e-8, weight_decay=0.0,
                 fused=False):
        super().__init__(lr)
        self.epsilon = epsilon
        self.weight_decay = weight_decay
        self.fused = bool(fused)

    def apply(self, name, p: Tensor, g: Tensor):
        grad = (g.data if isinstance(g, Tensor) else g).astype(p.dtype)
        if self._fused_ok(name, p):
            from .ops import fused_optim
            hist = self._get_aux(f"{name}:history", p)
            p.data, hist.data = fused_optim.adagrad_update(
                p.data, grad, hist.data, self._scaled_lr(name),
                epsilon=self.epsilon, weight_decay=self.weight_decay)
            return
        if self.weight_decay != 0:
            grad = grad + self.weight_decay * p.data
        grad = self.apply_regularizer_constraint(name, p.data, grad)
        hist = self._get_aux(f"{name}:history", p)
        hist.data = (hist.data + grad * grad).astype(hist.dtype)
        p.data = (p.data - self._scaled_lr(name) * grad
                  / jnp.sqrt(hist.data + self.epsilon)).astype(p.dtype)


class Adam(Optimizer):
    """(reference opt.py:536-660)

    ``fused=True``: eligible params update through the one-HBM-pass
    Pallas kernel (``ops.fused_optim.adam_update``; amsgrad keeps the
    reference path — its vmax compare-exchange is a fourth state tensor
    the fused contract doesn't cover). Same gating/parity story as
    ``SGD(fused=True)``."""

    def __init__(self, lr=0.001, beta_1=0.9, beta_2=0.999, epsilon=1e-8,
                 weight_decay=0.0, amsgrad=False, fused=False):
        super().__init__(lr)
        self.beta_1 = beta_1
        self.beta_2 = beta_2
        self.epsilon = epsilon
        self.weight_decay = weight_decay
        self.amsgrad = amsgrad
        self.fused = bool(fused)

    def apply(self, name, p: Tensor, g: Tensor):
        grad = (g.data if isinstance(g, Tensor) else g).astype(p.dtype)
        if not self.amsgrad and self._fused_ok(name, p):
            from .ops import fused_optim
            m = self._get_aux(f"{name}:m", p)
            v = self._get_aux(f"{name}:v", p)
            t = self.step_counter.data + 1.0
            p.data, m.data, v.data = fused_optim.adam_update(
                p.data, grad, m.data, v.data, self._scaled_lr(name),
                1 - jnp.power(self.beta_1, t),
                1 - jnp.power(self.beta_2, t),
                beta_1=self.beta_1, beta_2=self.beta_2,
                epsilon=self.epsilon, weight_decay=self.weight_decay)
            return
        if self.weight_decay != 0:
            grad = grad + self.weight_decay * p.data
        grad = self.apply_regularizer_constraint(name, p.data, grad)
        m = self._get_aux(f"{name}:m", p)
        v = self._get_aux(f"{name}:v", p)
        m.data = (self.beta_1 * m.data
                  + (1 - self.beta_1) * grad).astype(m.dtype)
        v.data = (self.beta_2 * v.data
                  + (1 - self.beta_2) * grad * grad).astype(v.dtype)
        t = self.step_counter.data + 1.0
        mhat = m.data / (1 - jnp.power(self.beta_1, t))
        if self.amsgrad:
            vmax = self._get_aux(f"{name}:vmax", p)
            vmax.data = jnp.maximum(vmax.data, v.data)
            vhat = vmax.data / (1 - jnp.power(self.beta_2, t))
        else:
            vhat = v.data / (1 - jnp.power(self.beta_2, t))
        p.data = (p.data - self._scaled_lr(name) * mhat
                  / (jnp.sqrt(vhat) + self.epsilon)).astype(p.dtype)


class DistOpt:
    """Distributed optimizer: data-parallel all-reduce over the mesh 'data'
    axis (reference DistOpt opt.py:686-1094 + Communicator
    src/io/communicator.cc, re-expressed as XLA collectives over ICI).

    Inside the compiled (shard_map'd) step, ``all_reduce`` is a
    ``lax.psum``; outside any mesh context it is the identity (world of 1),
    which keeps single-chip scripts unchanged.
    """

    def __init__(self, opt=None, nccl_id=None, local_rank=None,
                 world_size=None, buffSize=None, axis_name="data",
                 reduce_axes=None, bucket_mb=None, overlap=True,
                 zero=False):
        """``reduce_axes``: mesh axes gradients are summed over (default
        just the data axis; add 'seq' under sequence parallelism where the
        token batch is split over that axis too).

        ``zero=True``: ZeRO/FSDP — optimizer state and fp32 masters
        sharded over the data axis, gathered just-in-time inside the
        compiled step. Implies the GSPMD train path
        (``Model.compile`` picks it up as ``fsdp_axis=axis_name``); the
        specialized drivers (half/partialUpdate/sparse) keep replicated
        state and raise a typed :class:`ShardingDecline` instead of
        running a silently replicated "ZeRO" step.

        ``bucket_mb``: size target (MiB of wire bytes) for gradient-psum
        bucketing. ``None``/``0`` keeps the per-gradient streaming psum;
        a positive value makes :meth:`grad_reduce_stream` concatenate
        gradients — in the reverse-layer order backward produces them —
        into size-targeted buckets and issue ONE collective per bucket
        the moment it fills, so XLA can hide the fewer, larger
        all-reduces under the remaining backward compute (the
        ``timeline_exposed_collective_seconds`` target). A python attr
        read at trace time: changing it after ``compile`` needs a
        recompile, like every other static step config.

        ``overlap=False`` is the measured no-overlap BASELINE: every
        collective is pinned behind the full backward via
        ``lax.optimization_barrier``, so an A/B against it shows what
        the overlap actually buys on the step timeline."""
        from .parallel.communicator import Communicator
        self.opt = opt if opt is not None else SGD()
        self.communicator = Communicator(axis_name=axis_name,
                                         world_size=world_size,
                                         reduce_axes=reduce_axes)
        self.world_size = self.communicator.world_size
        self.local_rank = local_rank if local_rank is not None \
            else self.communicator.local_rank
        self.global_rank = self.communicator.global_rank
        self.axis_name = axis_name
        self.bucket_mb = float(bucket_mb) if bucket_mb else 0.0
        if self.bucket_mb < 0:
            raise ValueError(f"bucket_mb must be >= 0, got {bucket_mb!r}")
        self.overlap = bool(overlap)
        self.zero = bool(zero)
        # sparsification error-feedback residuals (reference sparse modes)
        self._residuals = {}

    # -- mirror underlying optimizer surface ------------------------------
    @property
    def step_counter(self):
        return self.opt.step_counter

    @property
    def loss_scale(self):
        return self.opt.loss_scale

    def state_tensors(self):
        return self.opt.state_tensors() + list(self._residuals.values())

    def state_tensor_dict(self):
        d = self.opt.state_tensor_dict()
        d.update({f"residual/{k}": v
                  for k, v in self._residuals.items()})
        return d

    def restore_state_tensor(self, name, array, spec=None):
        if name.startswith("residual/"):
            nm = name[len("residual/"):]
            t = self._residuals.get(nm)
            if t is None:
                t = Tensor(data=array, requires_grad=False)
                t.spec = spec
                self._residuals[nm] = t
            else:
                t.data = array
        else:
            self.opt.restore_state_tensor(name, array, spec)

    def get_states(self):
        from .tensor import to_host_tree
        states = self.opt.get_states()
        states.update(to_host_tree({f"residual/{k}": v.data
                                    for k, v in self._residuals.items()}))
        return states

    def set_states(self, states):
        self.opt.set_states({k: v for k, v in states.items()
                             if not k.startswith("residual/")})
        for k, v in states.items():
            if k.startswith("residual/"):
                name = k[len("residual/"):]
                if name in self._residuals:
                    self._residuals[name].data = jnp.asarray(v)
                else:
                    self._residuals[name] = Tensor(data=np.asarray(v),
                                                   requires_grad=False)

    def announce_aux_specs(self, params_by_name):
        self.opt.announce_aux_specs(params_by_name)
        # sparsification error-feedback residuals are keyed by the param
        # name itself and must shard like it too
        for k, t in self._residuals.items():
            if getattr(t, "spec", None) is None:
                src = params_by_name.get(k)
                if src is not None and getattr(src, "spec", None) is not None:
                    t.spec = src.spec

    def step(self):
        self.opt.step()

    def __call__(self, loss):
        self.backward_and_update(loss)

    # -- collectives -------------------------------------------------------
    @staticmethod
    def _shard_axes(p):
        """Mesh axes ``p`` is sharded over (its Tensor.spec): per-shard
        gradients on those axes are distinct values, not replicas, so they
        are excluded from the gradient all-reduce — expert weights on
        'expert', tensor-parallel weights on 'model'."""
        spec = getattr(p, "spec", None)
        if spec is None:
            return ()
        axes = set()
        for entry in spec:
            if entry is None:
                continue
            if isinstance(entry, (tuple, list)):
                axes.update(entry)
            else:
                axes.add(entry)
        return tuple(axes)

    def all_reduce(self, arr, exclude=()):
        return self.communicator.all_reduce(arr, exclude=exclude)

    def all_reduce_wire(self, arr, exclude=(), wire=None):
        """All-reduce with the policy's (or an explicit) 16-bit wire
        cast, returning f32 when a cast happened — the ONE place the
        comm-dtype discipline lives, shared by the plain and guarded
        drivers. ``wire=None`` resolves the active policy; no policy
        (or the grad already on the wire dtype) reduces as-is."""
        if wire is None:
            wire = self._policy_wire()
        if wire is not None and arr.dtype != wire:
            return self.all_reduce(arr.astype(wire),
                                   exclude=exclude).astype(jnp.float32)
        return self.all_reduce(arr, exclude=exclude)

    def update(self, p: Tensor, g: Tensor):
        """Average an already-summed gradient and apply
        (reference opt.py:738-746: grad /= world_size).

        The divisor is the FULL batch-shard count over every reduce axis,
        even for shard-excluded params: an expert-sharded weight's gradient
        already accumulates its expert-axis peers' token contributions
        through the all-to-all transpose, so only the psum skips the axis —
        the per-token averaging does not."""
        g.data = g.data / self.communicator.effective_world_size()
        self.opt.apply(p.name or f"param/{id(p)}", p, g)

    @staticmethod
    def _policy_wire():
        """Wire dtype for gradient collectives under the ACTIVE precision
        policy (None = reduce in the gradients' own dtype). The compiled
        step enters the model's policy scope, so a bf16_mixed model's
        psums automatically move 16-bit bytes — the policy-driven form of
        the explicit ``backward_and_update_half`` driver."""
        from .mixed_precision import active_policy
        pol = active_policy()
        return pol.comm_dtype if pol is not None else None

    # -- bucketed gradient reduction ----------------------------------------
    def _wire_cast_back(self, arr, orig_dtype, wire):
        """all_reduce_wire's post-reduce rule, factored for the bucketed
        path: a gradient that was CAST to a 16-bit wire comes back f32;
        one already on the wire dtype (or reduced with no wire policy)
        keeps its dtype."""
        if wire is not None and orig_dtype != wire:
            return arr.astype(jnp.float32)
        return arr

    def _flush_bucket(self, key, items, wire):
        """Reduce one bucket with a SINGLE collective: concatenate the
        members' (wire-cast) flattened gradients, all-reduce the buffer,
        split it back, and re-apply the per-gradient cast-back rule —
        numerically the same elements summed over the same replicas as
        per-gradient psums, just fewer/larger wire messages."""
        excl, eff = key
        casts = [g.data.astype(eff) if g.data.dtype != eff else g.data
                 for _p, g in items]
        if len(items) == 1:
            # a lone member (oversized grad, stream tail) skips the
            # concat/split round trip
            (p, g), red = items[0], self.all_reduce(casts[0], exclude=excl)
            g.data = self._wire_cast_back(red, g.data.dtype, wire)
            return [(p, g)]
        buf = jnp.concatenate([c.ravel() for c in casts])
        red = self.all_reduce(buf, exclude=excl)
        out, off = [], 0
        for (p, g), c in zip(items, casts):
            piece = red[off:off + c.size].reshape(c.shape)
            off += c.size
            g.data = self._wire_cast_back(piece, g.data.dtype, wire)
            out.append((p, g))
        return out

    def grad_reduce_stream(self, pairs, wire=None):
        """Generator transform over backward's ``(param, grad)`` stream:
        yields the same pairs with ``grad.data`` SUMMED over the reduce
        axes (averaging stays with the consumer, :meth:`update`). The
        ONE reduction chokepoint the plain and guarded drivers share, so
        bucketing/overlap config and the 16-bit wire-cast discipline
        (:meth:`all_reduce_wire` semantics, preserved per-gradient) can
        never diverge between them.

        - default (``overlap=True, bucket_mb=0``): per-gradient psum the
          moment backward yields it — the streaming path unchanged;
        - ``bucket_mb>0``: gradients accumulate into size-targeted
          buckets keyed by (shard-exclude axes, wire dtype) — members of
          different keys cannot share a collective — and each bucket
          reduces with ONE concatenated all-reduce as soon as it fills
          (backward yields reverse-layer order, so the bucket's grads
          are the newest ready and the collective overlaps the rest of
          backward);
        - ``overlap=False``: every gradient is first pinned behind the
          COMPLETE backward with ``lax.optimization_barrier`` — the
          honest no-overlap baseline an A/B measures against (without
          the barrier XLA's scheduler would overlap anyway, making the
          "off" leg a lie).
        """
        if wire is None:
            wire = self._policy_wire()
        if self.overlap and not self.bucket_mb:
            for p, g in pairs:
                g.data = self.all_reduce_wire(
                    g.data, exclude=self._shard_axes(p), wire=wire)
                yield p, g
            return
        if not self.overlap:
            # materialise the whole backward, then tie every grad to the
            # full set: no collective can issue before backward finishes
            pairs = list(pairs)
            barriered = jax.lax.optimization_barrier(
                tuple(g.data for _p, g in pairs))
            for (_p, g), arr in zip(pairs, barriered):
                g.data = arr
            pairs = iter(pairs)
        if not self.bucket_mb:
            for p, g in pairs:
                g.data = self.all_reduce_wire(
                    g.data, exclude=self._shard_axes(p), wire=wire)
                yield p, g
            return
        target = int(self.bucket_mb * (1 << 20))
        buckets = {}          # (excl, eff_dtype) -> [items, nbytes]
        order = []            # flush stale buckets in arrival order
        for p, g in pairs:
            excl = self._shard_axes(p)
            eff = np.dtype(wire) if wire is not None \
                else np.dtype(g.data.dtype)
            key = (excl, eff)
            if key not in buckets:
                buckets[key] = [[], 0]
                order.append(key)
            slot = buckets[key]
            slot[0].append((p, g))
            slot[1] += int(np.prod(np.shape(g.data))) * eff.itemsize
            if slot[1] >= target:
                items, _n = buckets.pop(key)
                order.remove(key)
                yield from self._flush_bucket(key, items, wire)
        for key in order:
            yield from self._flush_bucket(key, buckets[key][0], wire)

    def _decline_zero(self, driver):
        """``zero=True`` under a specialized driver is REFUSED, not
        warned: these drivers keep their own per-gradient reduction +
        replicated optimizer state, so a ZeRO request would silently
        train with full-size state on every chip while the run reports
        "ZeRO" — the exact lie the typed-decline discipline exists to
        prevent. Use the plain driver (``model(tx, ty)`` /
        ``backward_and_update``) on the GSPMD path, or drop zero."""
        if not getattr(self, "zero", False):
            return
        from .parallel.gspmd import ShardingDecline
        raise ShardingDecline(
            f"DistOpt(zero=True) cannot run the {driver} driver: it "
            "keeps replicated optimizer state and hand-rolled "
            "per-gradient collectives, so the requested ZeRO sharding "
            "would silently not happen. Use the plain driver "
            "(backward_and_update via the compiled GSPMD step) or "
            "construct the DistOpt without zero=True")

    def _warn_driver_skips_bucketing(self, driver):
        """The specialised drivers (half / partialUpdate / sparse) keep
        their own per-gradient reduction paths: a bucket_mb/overlap
        config would be silently dead there, and a user A/B'ing the
        overlap knobs under them would bank a comparison of two
        identical programs. Say so, once per driver."""
        if not self.bucket_mb and self.overlap:
            return
        warned = getattr(self, "_bucket_warned", None)
        if warned is None:
            warned = self._bucket_warned = set()
        if driver in warned:
            return
        warned.add(driver)
        import warnings
        warnings.warn(
            f"DistOpt(bucket_mb={self.bucket_mb}, overlap="
            f"{self.overlap}) has no effect on {driver}: only the "
            "plain and guarded drivers ride grad_reduce_stream; this "
            "driver streams per-gradient collectives", stacklevel=3)

    # -- training drivers ---------------------------------------------------
    def backward_and_update(self, loss, threshold=2097152):
        """All-reduce each gradient as soon as backward produces it
        (reference opt.py:826-865). ``threshold`` is accepted for parity;
        XLA handles small-tensor fusion so no manual fused buffer exists
        — but ``bucket_mb`` (see ``__init__``) additionally coalesces
        gradients into size-targeted single-collective buckets through
        :meth:`grad_reduce_stream`, the overlap knob the step timeline's
        exposed-communication gauge steers. Under an active 16-bit
        precision policy the reduce moves the policy's comm dtype on the
        wire; the update math that follows is back in the masters'
        precision."""
        wire = self._policy_wire()
        for p, g in self.grad_reduce_stream(autograd.backward(loss),
                                            wire=wire):
            self.update(p, g)
        self.opt.step()

    @classmethod
    def _half_wire_defaults(cls, dtype, clipping):
        """Resolve backward_and_update_half's (dtype, clipping)
        defaults: an explicit dtype keeps the caller's choices; a None
        dtype takes the active policy's comm dtype (else bfloat16), and
        a POLICY-selected fp16 wire forces clipping on — fp16 overflows
        above 65504 and this driver runs unguarded."""
        if dtype is not None:
            return dtype, clipping
        wire_pol = cls._policy_wire()
        if wire_pol == jnp.dtype(jnp.float16):
            return "float16", True
        return wire_pol or "bfloat16", clipping

    def backward_and_update_half(self, loss, threshold=2097152,
                                 clipping=False, clip_value=2.5,
                                 dtype=None):
        """Reduced-precision communication: cast to a 16-bit type before
        the all-reduce (reference synchHalf fp16 comm,
        src/io/communicator.cc:262-299). ``dtype`` selects the wire
        format: "bfloat16" (the TPU-native half type, same exponent
        range as fp32 so no clipping is required) or "float16" (the
        reference's IEEE wire format, e.g. for DCN cross-slice links
        where the fp16 convention is fixed; pair with ``clipping`` since
        fp16 overflows above 65504). Default (None): the active
        precision policy's comm dtype, else bfloat16 — and when the
        POLICY selects the fp16 wire, clipping turns on with it (this
        driver runs unguarded, so an unclipped policy-default fp16 wire
        would let one large gradient sum land inf in the params)."""
        self._decline_zero('backward_and_update_half')
        self._warn_driver_skips_bucketing('backward_and_update_half')
        dtype, clipping = self._half_wire_defaults(dtype, clipping)
        wire = {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
                jnp.bfloat16: jnp.bfloat16,
                jnp.float16: jnp.float16,
                jnp.dtype(jnp.bfloat16): jnp.bfloat16,
                jnp.dtype(jnp.float16): jnp.float16}.get(dtype)
        if wire is None:
            raise ValueError(
                f"dtype must be 'bfloat16' or 'float16', got {dtype!r}")
        for p, g in autograd.backward(loss):
            grad = g.data
            if clipping:
                grad = jnp.clip(grad, -clip_value, clip_value)
            half = grad.astype(wire)
            g.data = self.all_reduce(
                half, exclude=self._shard_axes(p)).astype(jnp.float32)
            self.update(p, g)
        self.opt.step()

    def backward_and_partial_update(self, loss, threshold=2097152,
                                    rotation=None):
        """Partial synchronisation: each step, only a rotating
        1/world_size partition of the parameters takes the globally
        averaged gradient; the rest update locally
        (reference opt.py:922-992).

        ``rotation`` — a STATIC python int (normally ``step %
        world_size``) — selects the partition at TRACE time, so the
        all-reduce is only emitted for the selected parameters: the
        reference's actual communication saving, at the cost of one
        compiled-step specialization per rotation value (the Model's
        static-arg cache holds all n).

        With ``rotation=None`` the selection rides the optimizer's traced
        step counter instead: a single compiled step that keeps rotating,
        but XLA cannot skip a collective on a traced predicate, so every
        gradient is still reduced and only the APPLICATION is masked.
        """
        self._decline_zero('backward_and_partial_update')
        self._warn_driver_skips_bucketing('backward_and_partial_update')
        n = max(1, self.communicator.effective_world_size())
        if rotation is not None:
            rot = int(rotation) % n
            for i, (p, g) in enumerate(autograd.backward(loss)):
                if i % n == rot:
                    g.data = self.all_reduce(
                        g.data, exclude=self._shard_axes(p)) / n
                self.opt.apply(p.name or f"param/{id(p)}", p, g)
            self.opt.step()
            return
        step = self.opt.step_counter.data
        for i, (p, g) in enumerate(autograd.backward(loss)):
            summed = self.all_reduce(g.data,
                                     exclude=self._shard_axes(p))
            sel = jnp.equal(jnp.mod(step + i, n), 0)
            g.data = jnp.where(sel, summed / n, g.data)
            self.opt.apply(p.name or f"param/{id(p)}", p, g)
        self.opt.step()

    def backward_and_sparse_update(self, loss, spars=0.05, topK=False,
                                   corr=True):
        """Gradient sparsification with error feedback (reference
        opt.py:994+ / Communicator::sparsification). On TPU the transport
        stays dense (masked values + psum ride the ICI all-reduce) while the
        semantics — threshold or top-K selection, residual accumulation —
        match the reference."""
        self._decline_zero('backward_and_sparse_update')
        self._warn_driver_skips_bucketing('backward_and_sparse_update')
        for p, g in autograd.backward(loss):
            name = p.name or f"param/{id(p)}"
            grad = g.data
            if corr:
                res = self._residuals.get(name)
                if res is None:
                    res = Tensor(shape=p.shape, device=p.device,
                                 requires_grad=False)
                    res.spec = p.spec   # error feedback shards like p
                    self._residuals[name] = res
                grad = grad + res.data
            absg = jnp.abs(grad)
            if topK:
                k = max(1, int(spars * grad.size))
                thresh = jax.lax.top_k(absg.ravel(), k)[0][-1]
                mask = absg >= thresh
            else:
                mask = absg >= spars
            sparse = jnp.where(mask, grad, 0.0)
            if corr:
                self._residuals[name].data = grad - sparse
            g.data = self.all_reduce(sparse,
                                     exclude=self._shard_axes(p))
            self.update(p, g)
        self.opt.step()
