"""Memory-lean loss kernels.

:func:`fused_ce_head` — the LM head matmul and softmax-cross-entropy
fused into one chunked computation: the (tokens, vocab) logits matrix —
the dominant HBM cost of large-vocab LM training (B·S·V floats, often
bigger than the whole model) — is NEVER materialised. The forward scans
vocab chunks with an online logsumexp; the backward (custom_vjp)
rescans, rebuilding each chunk's probabilities from the saved (O(tokens))
logsumexp, exactly the flash-attention residual trick applied to the
classifier head. No reference counterpart (the reference computes full
logits then CrossEntropyFwd, src/model/operation/../autograd).
:func:`fused_ce_rows` is the same head returning each token's
cross-entropy (the mean form is its mean), for losses that weight tokens
unequally — a looped model's exits, weighted by each token's exit
probability (models/ouro.py).

Vocab-parallel: pass ``axis_name`` when the head weight's columns are
sharded over a mesh axis (``ColumnParallelLinear``-style). Each rank
scans only its own V/tp vocab slice; the per-rank online logsumexp
states are merged with one pmax+psum pair and the target logit with one
psum, so no rank ever materialises — or even scans — another rank's
vocab columns. The backward psums the (D-wide) hidden-state cotangent
only; dW/db stay rank-local. Outside a mesh the collectives vanish and
the same code is the single-device kernel.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..autograd_base import Operator

_NEG = -1e30


def _chunks(W, b, chunk):
    """(D, V), (V,) -> per-chunk xs (n, D, c) / (n, c), -inf-padded bias
    so padded columns never contribute to the logsumexp."""
    D, V = W.shape
    n = (V + chunk - 1) // chunk
    pad = n * chunk - V
    if pad:
        W = jnp.pad(W, ((0, 0), (0, pad)))
        b = jnp.pad(b, (0, pad), constant_values=_NEG)
    return (W.reshape(D, n, chunk).transpose(1, 0, 2),
            b.reshape(n, chunk), n, pad)


def _shard_ctx(axis_name, W):
    """(live?, column offset of this rank's vocab slice). ``W`` is the
    rank-local slice inside shard_map, so the offset is index * local-V."""
    if not axis_name:
        return False, 0
    from ..parallel.communicator import active_axis
    if not active_axis(axis_name):
        return False, 0
    return True, lax.axis_index(axis_name) * W.shape[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_ce_head(h, W, b, ids, chunk=8192, axis_name=None):
    """Mean cross-entropy of ``softmax(h @ W + b)`` against ``ids``.

    h: (N, D) flattened tokens; W: (D, V); b: (V,); ids: (N,) integer
    (or float-encoded) target ids. Peak memory is O(N·chunk), not O(N·V).
    With ``axis_name`` and a live mesh axis, W/b hold this rank's vocab
    slice and ids stay global — see the module docstring.
    """
    return _fwd(h, W, b, ids, chunk, axis_name)[0]


def _zero_ct(x):
    """Cotangent of a non-differentiable input: float zeros for float
    encodings of ids, float0 for true integer ids."""
    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        return jnp.zeros_like(x)
    return np.zeros(np.shape(x), jax.dtypes.float0)


def _fwd(h, W, b, ids, chunk, axis_name=None):
    lse, tgt = _scan_lse(h, W, b, ids, chunk, axis_name)
    loss = jnp.mean(lse - tgt)
    return loss, (h, W, b, ids, lse)


def _scan_lse(h, W, b, ids, chunk, axis_name):
    """Per row: (logsumexp of the logits, the target's logit), by the
    chunked online logsumexp over the vocabulary."""
    sharded, offset = _shard_ctx(axis_name, W)
    hf = h.astype(jnp.float32)
    idi = ids.astype(jnp.int32) - offset        # local coords of targets
    Wc, bc, n, _pad = _chunks(W.astype(jnp.float32),
                              b.astype(jnp.float32), chunk)
    N = hf.shape[0]

    # a target this rank does not own may still land inside the last
    # chunk's -1e30-padded tail (local V < n*chunk): without the bound
    # below it would accumulate the pad bias into tgt and blow up the
    # loss by ~1e30 after the cross-rank psum
    owned = (idi >= 0) & (idi < W.shape[1])

    def step(carry, inputs):
        m, l, tgt = carry
        ci, Wk, bk = inputs
        logits = hf @ Wk + bk                        # (N, chunk)
        m_new = jnp.maximum(m, jnp.max(logits, -1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), -1)
        loc = idi - ci * chunk
        hit = (loc >= 0) & (loc < chunk) & owned
        got = jnp.take_along_axis(
            logits, jnp.clip(loc, 0, chunk - 1)[:, None], 1)[:, 0]
        tgt = tgt + jnp.where(hit, got, 0.0)
        return (m_new, l, tgt), None

    zero = jnp.zeros((N,), jnp.float32) + 0.0 * jnp.sum(hf, -1)
    init = (zero + _NEG, zero, zero)
    (m, l, tgt), _ = lax.scan(step, init,
                              (jnp.arange(n), Wc, bc))
    if sharded:
        # merge per-rank online-softmax states: one pmax + two psums
        # total, all O(N) — never O(V)
        m_all = lax.pmax(m, axis_name)
        l = lax.psum(l * jnp.exp(m - m_all), axis_name)
        tgt = lax.psum(tgt, axis_name)          # exactly one rank hit
        m = m_all
    return m + jnp.log(jnp.maximum(l, 1e-30)), tgt


def _bwd(chunk, axis_name, res, g):
    h, W, b, ids, lse = res
    gN = (g / h.shape[0]).astype(jnp.float32)
    return _scan_grads(h, W, b, ids, lse, gN, chunk, axis_name) \
        + (_zero_ct(ids),)


def _scan_grads(h, W, b, ids, lse, gN, chunk, axis_name):
    """(dh, dW, db) by a second scan over the vocabulary chunks, each
    chunk's softmax rebuilt from the saved ``lse``. ``gN`` is the
    cotangent of each row's cross-entropy: a scalar shared by every row
    (the mean form) or an (N, 1) column (the per-row form)."""
    sharded, offset = _shard_ctx(axis_name, W)
    idi = ids.astype(jnp.int32) - offset
    hf = h.astype(jnp.float32)
    Wc, bc, n, pad = _chunks(W.astype(jnp.float32),
                             b.astype(jnp.float32), chunk)

    owned = (idi >= 0) & (idi < W.shape[1])   # same bound as forward

    def step(dh, inputs):
        ci, Wk, bk = inputs
        logits = hf @ Wk + bk
        p = jnp.exp(logits - lse[:, None])          # chunk of softmax
        loc = idi - ci * chunk
        hit = (loc >= 0) & (loc < chunk) & owned
        onehot = jax.nn.one_hot(jnp.clip(loc, 0, chunk - 1), chunk,
                                dtype=jnp.float32) * hit[:, None]
        dlog = (p - onehot) * gN
        dh = dh + dlog @ Wk.T
        dWk = hf.T @ dlog
        dbk = jnp.sum(dlog, 0)
        return dh, (dWk, dbk)

    dh, (dWks, dbks) = lax.scan(step, hf * 0.0,
                                (jnp.arange(n), Wc, bc))
    if sharded:
        # h is replicated over the vocab axis; each rank produced only
        # its slice's contribution to dh. dW/db stay rank-local.
        dh = lax.psum(dh, axis_name)
    V = W.shape[1]
    dW = dWks.transpose(1, 0, 2).reshape(W.shape[0],
                                         n * chunk)[:, :V]
    db = dbks.reshape(n * chunk)[:V]
    return dh.astype(h.dtype), dW.astype(W.dtype), db.astype(b.dtype)


fused_ce_head.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_ce_rows(h, W, b, ids, chunk=8192):
    """Each row's cross-entropy of ``softmax(h @ W + b)`` against ``ids``:
    (N,) float32, the per-token form of :func:`fused_ce_head` (whose mean
    it is), for a loss that weights tokens unequally. Same scans, same
    memory; the backward takes a cotangent a row. ``b`` may be None (a
    head without a bias; no gradient is returned for it). The head is
    whole on this device (no vocab-parallel form)."""
    return _fwd_rows(h, W, b, ids, chunk)[0]


def _bias(b, W):
    return jnp.zeros((W.shape[1],), W.dtype) if b is None else b


def _fwd_rows(h, W, b, ids, chunk):
    lse, tgt = _scan_lse(h, W, _bias(b, W), ids, chunk, None)
    return lse - tgt, (h, W, b, ids, lse)


def _bwd_rows(chunk, res, g):
    h, W, b, ids, lse = res
    dh, dW, db = _scan_grads(h, W, _bias(b, W), ids, lse,
                             g.astype(jnp.float32)[:, None], chunk, None)
    return dh, dW, None if b is None else db, _zero_ct(ids)


fused_ce_rows.defvjp(_fwd_rows, _bwd_rows)


class _FusedCEHead(Operator):
    """Tape op: (hidden, W, b, ids) -> scalar mean CE, never
    materialising the logits. ``axis_name``: vocab-parallel mesh axis
    (W/b columns sharded over it) or None."""

    def __init__(self, chunk=8192, axis_name=None):
        super().__init__()
        self.chunk = chunk
        self.axis_name = axis_name

    def forward(self, h, W, b, ids):
        flat = h.reshape(-1, h.shape[-1])
        return fused_ce_head(flat, W, b, ids.reshape(-1), self.chunk,
                             self.axis_name)


def fused_softmax_cross_entropy(hidden, W, b, ids, chunk=8192,
                                axis_name=None):
    """Functional tape API over :class:`_FusedCEHead`; ``hidden`` may be
    (B, S, D) with (B, S) ids. ``axis_name`` turns on the vocab-parallel
    cross-shard reduction when W's columns live sharded over that mesh
    axis."""
    return _FusedCEHead(chunk, axis_name)(hidden, W, b, ids)


# the Layer-shaped fused heads live in singa_tpu.layer (FusedCEHead for
# Model code, FusedCEHeadStage for heterogeneous pipelines); this module
# stays layer-free so the kernel imports without the zoo
