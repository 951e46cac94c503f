"""Fused optimizer-update Pallas kernels: one HBM pass per parameter.

The reference optimizer updates are chains of elementwise ops (momentum
EWMA, bias correction, axpy) that XLA *may* fuse but, measured on the
bench ResNet step, often splits across several HBM round trips of the
full parameter + aux state — pure ``timeline_mfu_loss{compute_
inefficiency}`` budget. These kernels do the whole update in ONE pass
over flattened parameter blocks: read grad + master + aux once, write
master + aux once, with the aux/master outputs aliased onto their
inputs. Parameters whose size is not a (rows×128)-tile multiple pay a
pad/slice around the kernel (XLA fuses what it can, but the aliasing
then covers the padded buffers, not the live state) — whether the
fused form still wins for a given model is for a chip A/B to decide
(ROADMAP.md D3); it is never assumed.

House pattern (``ops/attention.py``): availability gate that DECLINES
to the reference path rather than erroring (``available``), interpreter
mode on CPU so tier-1 CI pins the exact kernel math the TPU executes
(``FORCE_PALLAS_INTERPRET`` — the ``pallas`` pytest marker selects
these suites), and the optimizers only take this path when constructed
with ``fused=True`` (bench: the ``BENCH_FUSED_OPTIM`` pin) — never
unconditionally.

FLOPs accounting: a Pallas kernel is a custom call XLA's cost analysis
cannot see into (on TPU it counts ~0 flops; in interpreter mode it
counts the lowered emulation loop instead). Either way the fused
program's analyzed FLOPs would differ from the reference program's and
MFU would move without the hardware doing anything different.
``trace_collector`` records which fused kernels a step trace took, and
``Model.step_flops`` re-lowers the step under :func:`force_reference`
when any did — so fused and unfused programs report IDENTICAL FLOPs by
construction (pinned in tests/test_fused_kernels.py).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Test hook, same contract as ops/attention.py: run the kernels under
# pl.pallas_call(interpret=True) on CPU so CI validates the exact math.
FORCE_PALLAS_INTERPRET = False

_LANES = 128
_SUBLANES = 8

# On silicon, one more kernel launch costs more than it saves for tiny
# parameters (a bias vector); the reference path keeps those. Interpret
# mode accepts ANY size so CPU CI exercises the padding/tiling logic.
MIN_FUSED_ELEMS = 4096

_FORCE_REFERENCE = contextvars.ContextVar("fused_force_reference",
                                          default=False)
_TRACE_SINK = contextvars.ContextVar("fused_trace_sink", default=None)


@contextlib.contextmanager
def force_reference():
    """Decline every fused kernel inside this scope — the reference
    elementwise math traces instead. ``Model.step_flops`` lowers its
    cost-analysis twin under this, so the FLOPs number always describes
    the reference program regardless of what the executed step fused."""
    tok = _FORCE_REFERENCE.set(True)
    try:
        yield
    finally:
        _FORCE_REFERENCE.reset(tok)


@contextlib.contextmanager
def trace_collector(sink):
    """Collect the kind tag of every fused kernel dispatched inside this
    scope into ``sink`` (a list). The Model step builder installs one
    per trace so the compiled-step record knows whether its program
    contains cost-analysis-invisible custom calls."""
    tok = _TRACE_SINK.set(sink)
    try:
        yield
    finally:
        _TRACE_SINK.reset(tok)


def _mark(kind):
    sink = _TRACE_SINK.get()
    if sink is not None:
        sink.append(kind)


def _interpret():
    return FORCE_PALLAS_INTERPRET or jax.default_backend() != "tpu"


def available(n_elems):
    """Kernel-eligibility gate: not inside :func:`force_reference`,
    and either a real TPU backend with a
    parameter big enough to amortise the launch, or the interpret-mode
    test hook (any size, so CI covers padding)."""
    if _FORCE_REFERENCE.get():
        return False
    if jax.default_backend() == "tpu":
        return int(n_elems) >= MIN_FUSED_ELEMS
    return FORCE_PALLAS_INTERPRET


# ---------------------------------------------------------------------------
# flattened-block layout: any parameter shape -> (rows, 128) f32-friendly
# tiles, rows padded to a sublane multiple; the tail pad is zeros, whose
# updates are computed and sliced away (cheaper than masking in-kernel)
# ---------------------------------------------------------------------------

def _pad_rows(n):
    rows = -(-n // _LANES)
    return -(-rows // _SUBLANES) * _SUBLANES


def _to_rows(arr, rows):
    flat = arr.ravel()
    pad = rows * _LANES - flat.size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, _LANES)


def _from_rows(arr, shape, n):
    return arr.ravel()[:n].reshape(shape)


def _block_rows(rows):
    """Largest row-block that tiles ``rows`` (rows is a sublane
    multiple, so 8 always divides)."""
    return next(b for b in (512, 256, 128, 64, 32, 16, 8)
                if rows % b == 0)


def _scalar(x):
    return jnp.asarray(x, jnp.float32).reshape(1, 1)


# ---------------------------------------------------------------------------
# SGD with momentum
# ---------------------------------------------------------------------------

def _sgd_kernel(lr_ref, p_ref, g_ref, m_ref, po_ref, mo_ref, *,
                momentum, dampening, weight_decay, nesterov):
    lr = lr_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * p
    m_new = momentum * m_ref[...].astype(jnp.float32) \
        + (1.0 - dampening) * g
    upd = g + momentum * m_new if nesterov else m_new
    po_ref[...] = (p - lr * upd).astype(po_ref.dtype)
    mo_ref[...] = m_new.astype(mo_ref.dtype)


def sgd_momentum_update(p, g, m, lr, *, momentum, dampening=0.0,
                        weight_decay=0.0, nesterov=False):
    """Fused ``opt.SGD`` momentum update: returns ``(p_new, m_new)``
    with the input shapes/dtypes preserved. Math identical to the
    reference ``SGD.apply`` chain (f32 accumulate, store back in the
    state dtype); parity is pinned bitwise in interpret mode."""
    _mark("sgd")
    shape, n = p.shape, p.size
    rows = _pad_rows(n)
    br = _block_rows(rows)
    blk = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    kernel = functools.partial(
        _sgd_kernel, momentum=float(momentum),
        dampening=float(dampening), weight_decay=float(weight_decay),
        nesterov=bool(nesterov))
    po, mo = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  blk, blk, blk],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), p.dtype),
                   jax.ShapeDtypeStruct((rows, _LANES), m.dtype)],
        # master/momentum update in place: input p (index 1 after the
        # scalar) aliases output 0, m (index 3) aliases output 1 — the
        # "one HBM pass" contract
        input_output_aliases={1: 0, 3: 1},
        interpret=_interpret(),
        name="fused_sgd",
    )(_scalar(lr), _to_rows(p, rows), _to_rows(g, rows),
      _to_rows(m, rows))
    return _from_rows(po, shape, n), _from_rows(mo, shape, n)


# ---------------------------------------------------------------------------
# Adam
# ---------------------------------------------------------------------------

def _adam_kernel(lr_ref, bc1_ref, bc2_ref, p_ref, g_ref, m_ref, v_ref,
                 po_ref, mo_ref, vo_ref, *, beta_1, beta_2, epsilon,
                 weight_decay):
    lr = lr_ref[0, 0]
    bc1 = bc1_ref[0, 0]
    bc2 = bc2_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * p
    m_new = beta_1 * m_ref[...].astype(jnp.float32) + (1.0 - beta_1) * g
    v_new = beta_2 * v_ref[...].astype(jnp.float32) \
        + (1.0 - beta_2) * g * g
    mhat = m_new / bc1
    vhat = v_new / bc2
    po_ref[...] = (p - lr * mhat
                   / (jnp.sqrt(vhat) + epsilon)).astype(po_ref.dtype)
    mo_ref[...] = m_new.astype(mo_ref.dtype)
    vo_ref[...] = v_new.astype(vo_ref.dtype)


def adam_update(p, g, m, v, lr, bias_corr1, bias_corr2, *, beta_1,
                beta_2, epsilon, weight_decay=0.0):
    """Fused ``opt.Adam`` update (no amsgrad): returns
    ``(p_new, m_new, v_new)``. ``bias_corr1/2`` are the traced
    ``1 - beta^t`` denominators (computed by the caller exactly as the
    reference does, so the step-counter semantics cannot drift)."""
    _mark("adam")
    shape, n = p.shape, p.size
    rows = _pad_rows(n)
    br = _block_rows(rows)
    blk = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    sca = pl.BlockSpec((1, 1), lambda i: (0, 0))
    kernel = functools.partial(
        _adam_kernel, beta_1=float(beta_1), beta_2=float(beta_2),
        epsilon=float(epsilon), weight_decay=float(weight_decay))
    po, mo, vo = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[sca, sca, sca, blk, blk, blk, blk],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), p.dtype),
                   jax.ShapeDtypeStruct((rows, _LANES), m.dtype),
                   jax.ShapeDtypeStruct((rows, _LANES), v.dtype)],
        input_output_aliases={3: 0, 5: 1, 6: 2},
        interpret=_interpret(),
        name="fused_adam",
    )(_scalar(lr), _scalar(bias_corr1), _scalar(bias_corr2),
      _to_rows(p, rows), _to_rows(g, rows), _to_rows(m, rows),
      _to_rows(v, rows))
    return (_from_rows(po, shape, n), _from_rows(mo, shape, n),
            _from_rows(vo, shape, n))


# ---------------------------------------------------------------------------
# RMSProp
# ---------------------------------------------------------------------------

def _rmsprop_kernel(lr_ref, p_ref, g_ref, r_ref, po_ref, ro_ref, *,
                    rho, epsilon, weight_decay):
    lr = lr_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * p
    # op order mirrors opt.RMSProp.apply exactly (rho*rms first, then
    # the (1-rho)*g*g term) so f32 params hold BITWISE parity
    r_new = rho * r_ref[...].astype(jnp.float32) \
        + (1.0 - rho) * g * g
    r_stored = r_new.astype(ro_ref.dtype)
    ro_ref[...] = r_stored
    po_ref[...] = (p - lr * g
                   / jnp.sqrt(r_stored.astype(jnp.float32)
                              + epsilon)).astype(po_ref.dtype)


def rmsprop_update(p, g, r, lr, *, rho, epsilon, weight_decay=0.0):
    """Fused ``opt.RMSProp`` update: returns ``(p_new, rms_new)`` with
    the input shapes/dtypes preserved, grad+master+rms read once and
    master+rms written once (aliased in place). Math identical to the
    reference chain — the rms store-back happens BEFORE the param
    update reads it, exactly like the reference's
    ``rms.data = ...; p.data = f(rms.data)`` sequence, so a non-f32
    rms state quantizes at the same point in both paths."""
    _mark("rmsprop")
    shape, n = p.shape, p.size
    rows = _pad_rows(n)
    br = _block_rows(rows)
    blk = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    kernel = functools.partial(
        _rmsprop_kernel, rho=float(rho), epsilon=float(epsilon),
        weight_decay=float(weight_decay))
    po, ro = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  blk, blk, blk],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), p.dtype),
                   jax.ShapeDtypeStruct((rows, _LANES), r.dtype)],
        input_output_aliases={1: 0, 3: 1},
        interpret=_interpret(),
        name="fused_rmsprop",
    )(_scalar(lr), _to_rows(p, rows), _to_rows(g, rows),
      _to_rows(r, rows))
    return _from_rows(po, shape, n), _from_rows(ro, shape, n)


# ---------------------------------------------------------------------------
# AdaGrad
# ---------------------------------------------------------------------------

def _adagrad_kernel(lr_ref, p_ref, g_ref, h_ref, po_ref, ho_ref, *,
                    epsilon, weight_decay):
    lr = lr_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * p
    h_new = h_ref[...].astype(jnp.float32) + g * g
    h_stored = h_new.astype(ho_ref.dtype)
    ho_ref[...] = h_stored
    po_ref[...] = (p - lr * g
                   / jnp.sqrt(h_stored.astype(jnp.float32)
                              + epsilon)).astype(po_ref.dtype)


def adagrad_update(p, g, h, lr, *, epsilon, weight_decay=0.0):
    """Fused ``opt.AdaGrad`` update: returns ``(p_new, history_new)``,
    same one-HBM-pass/aliasing contract as the other kernels. The
    accumulated-square history is unbounded by design (AdaGrad's
    semantics); f32 accumulation in-kernel matches the reference's
    f32 math on f32 state bitwise."""
    _mark("adagrad")
    shape, n = p.shape, p.size
    rows = _pad_rows(n)
    br = _block_rows(rows)
    blk = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    kernel = functools.partial(
        _adagrad_kernel, epsilon=float(epsilon),
        weight_decay=float(weight_decay))
    po, ho = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  blk, blk, blk],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), p.dtype),
                   jax.ShapeDtypeStruct((rows, _LANES), h.dtype)],
        input_output_aliases={1: 0, 3: 1},
        interpret=_interpret(),
        name="fused_adagrad",
    )(_scalar(lr), _to_rows(p, rows), _to_rows(g, rows),
      _to_rows(h, rows))
    return _from_rows(po, shape, n), _from_rows(ho, shape, n)
