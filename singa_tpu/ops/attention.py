"""Attention: fused flash kernel + ring (sequence-parallel) attention.

TPU-first components with no reference equivalent (the reference composes
attention from primitive autograd ops in examples and has no sequence
parallelism — SURVEY.md §5 'long-context: absent'); these are the
long-context machinery the TPU build makes first-class:

- :func:`flash_attention` — blocked online-softmax attention. On TPU the
  forward runs as a Pallas kernel (grid over (batch*heads, q-blocks),
  streaming k/v blocks through VMEM with running max/sum accumulators, so
  the S×S score matrix never hits HBM). Elsewhere (CPU mesh tests) an
  identical-math `lax.scan` implementation runs. Backward recomputes
  per-block scores (flash style) under `jax.custom_vjp`: two more Pallas
  kernels on TPU, the scan path elsewhere.
- :func:`ring_attention` — q/k/v sharded over a 'seq' mesh axis inside
  `shard_map`; k/v blocks rotate around the ICI ring via `lax.ppermute`
  while each device folds them into its online-softmax accumulator.
  Communication overlaps compute; memory per chip is O(S/n · S/n).
"""

from __future__ import annotations

import functools
import math
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..autograd_base import Operator
from ..mixed_precision import cast_compute as _cast_compute
from ..parallel.communicator import axis_size as _axis_size

_NEG_INF = -1e30


def _block_scan_attention(q, k, v, causal, scale, block_k,
                          q_offset=0, k_offset=0):
    """Online-softmax attention, scanning over key blocks.

    q: (B, H, Sq, D), k/v: (B, H, Sk, D). Returns (out, m, l) so partial
    results can be merged (ring attention needs the accumulators).
    ``q_offset``/``k_offset`` are global position offsets for causal
    masking of sharded sequences.
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_k = min(block_k, Sk)
    nblocks = (Sk + block_k - 1) // block_k
    pad = nblocks * block_k - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(B, H, nblocks, block_k, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, nblocks, block_k, D).transpose(2, 0, 1, 3, 4)

    q_pos = q_offset + jnp.arange(Sq)

    def step(carry, inputs):
        out, m, l = carry
        blk_idx, kblk, vblk = inputs
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kblk,
                       preferred_element_type=jnp.float32) * scale
        k_pos = k_offset + blk_idx * block_k + jnp.arange(block_k)
        mask = k_pos[None, :] < (Sk + k_offset)  # padding mask
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        out_new = out * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (out_new, m_new, l_new), None

    # derive accumulators from q so they carry its shard_map varying-axes
    # type (fresh zeros would be 'unvarying' and fail the scan typecheck)
    zero = q.astype(jnp.float32) * 0.0
    init = (zero,
            jnp.max(zero, axis=-1) + _NEG_INF,
            jnp.sum(zero, axis=-1))
    (out, m, l), _ = lax.scan(
        step, init, (jnp.arange(nblocks), kb, vb))
    return out, m, l


def _merge_partials(out, m, l):
    """Normalise a streamed accumulator into the final attention output."""
    return (out / jnp.maximum(l, 1e-30)[..., None])


def _scan_flash_fwd(q, k, v, causal, scale, block_k=512):
    """Scan-path forward returning (out, lse). lse = m + log(l) is the
    log-sum-exp of each query row — the O(S) residual the flash backward
    rebuilds probabilities from."""
    out, m, l = _block_scan_attention(q.astype(jnp.float32),
                                      k.astype(jnp.float32),
                                      v.astype(jnp.float32),
                                      causal, scale, block_k)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return _merge_partials(out, m, l).astype(q.dtype), lse


def _reference_attention(q, k, v, causal, scale, block_k=512):
    return _scan_flash_fwd(q, k, v, causal, scale, block_k)[0]


def _scan_flash_bwd(q, k, v, out, lse, g, causal, scale, block_k):
    """Blocked flash backward (everywhere-correct math; the Pallas TPU
    kernels below implement the same recurrence). Probabilities are
    recomputed per k-block from (q, k, lse) — never an S×S matrix — so
    residual memory stays O(S·D):

        delta = rowsum(dO * O)
        P     = exp(S - lse)           (block recompute)
        dV    = Pᵀ dO
        dS    = P * (dO Vᵀ - delta) * scale
        dQ    = dS K ;  dK = dSᵀ Q
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_k = min(block_k, Sk)
    nblocks = (Sk + block_k - 1) // block_k
    pad = nblocks * block_k - Sk
    kp, vp = k, v
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = kp.reshape(B, H, nblocks, block_k, D).transpose(2, 0, 1, 3, 4)
    vb = vp.reshape(B, H, nblocks, block_k, D).transpose(2, 0, 1, 3, 4)
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)   # (B, H, Sq)
    q_pos = jnp.arange(Sq)

    def step(dq, inputs):
        blk, kblk, vblk = inputs
        kblk = kblk.astype(jnp.float32)
        vblk = vblk.astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk,
                       preferred_element_type=jnp.float32) * scale
        k_pos = blk * block_k + jnp.arange(block_k)
        mask = k_pos[None, :] < Sk
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        s = jnp.where(mask[None, None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])           # masked entries -> 0
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vblk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kblk,
                             preferred_element_type=jnp.float32)
        dkb = jnp.einsum("bhqk,bhqd->bhkd", ds, qf,
                         preferred_element_type=jnp.float32)
        dvb = jnp.einsum("bhqk,bhqd->bhkd", p, gf,
                         preferred_element_type=jnp.float32)
        return dq, (dkb, dvb)

    # qf * 0.0 (not fresh zeros) so the carry inherits qf's shard_map
    # varying-axes type — same workaround as the forward scan init
    dq, (dkbs, dvbs) = lax.scan(
        step, qf * 0.0, (jnp.arange(nblocks), kb, vb))
    dk = dkbs.transpose(1, 2, 0, 3, 4).reshape(
        B, H, nblocks * block_k, D)[:, :, :Sk]
    dv = dvbs.transpose(1, 2, 0, 3, 4).reshape(
        B, H, nblocks * block_k, D)[:, :, :Sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernels (forward + backward)
#
# All kernels run a 3-D grid whose innermost dimension streams the far-side
# blocks through VMEM — K/V blocks for the forward/dQ kernels, Q blocks for
# the dK/dV kernel — so VMEM holds O(block · D) regardless of sequence
# length (the whole point of the long-context path). TPU grids iterate the
# trailing dimension sequentially, which is what makes the scratch-ref
# accumulator pattern below sound. The leading dimension steps over groups
# of (batch, head) slices (`_heads_per_step`); every block and scratch has
# that group as its first axis.
#
# Matrix products take their operands in the dtype the kernel was given
# and accumulate in float32; m, l, lse, delta, the exp and the mask are
# float32 always (the v5e's vector unit has no bf16 arithmetic). On the
# v5e a float32 product at Mosaic's default precision is one bf16 pass
# too (measured, PR 25: the same error against plain attention, 3e-3 of
# the largest value, and the same time either way), so float32 callers
# differ from bf16 ones in bytes, not in passes.
# ---------------------------------------------------------------------------

# Test hook: run kernels in interpreter mode so CPU CI validates the exact
# kernel math the TPU executes (tests/test_attention.py flips this).
FORCE_PALLAS_INTERPRET = False


_DECLINE_LOGGED = set()

# Lane width of a vector register. The kernels keep their running row
# statistics (m, l) in VMEM as (block_q, _LANES) with every lane of a row
# holding the same scalar; between HBM and the kernels lse and delta
# travel as (B*H, 1, S) with the sequence on the lanes — 4 bytes a row.
_LANES = 128


_ENV_BLOCK_CACHE = {}
_ENV_BLOCK_WARNED = set()


def _env_block(name):
    """Validated SINGA_FLASH_BLOCK_* override, or None. A value that is
    not a positive integer is warned about ONCE and ignored (the
    adaptive pick stands) instead of raising inside every attention
    dispatch; validation is memoized per raw value so the hot path pays
    one dict lookup."""
    v = os.environ.get(name)
    if not v:
        return None
    key = (name, v)
    if key not in _ENV_BLOCK_CACHE:
        val = None
        try:
            iv = int(v)
            if iv > 0:
                val = iv
        except ValueError:
            pass
        if val is None:
            import warnings
            warnings.warn(f"{name}={v!r} is not a positive integer; "
                          "ignoring the override", stacklevel=3)
        _ENV_BLOCK_CACHE[key] = val
    return _ENV_BLOCK_CACHE[key]


def _pick_blocks(Sq, Sk):
    """Largest Pallas block sizes, up to 512, that tile the sequence
    lengths.

    Measured on TPU v5e on 2026-10-01 (PR 25; forward + backward of the
    three kernels, causal, ms a call): at B4 H16 S1024 D64 bf16, the
    benchmark's train cell, (512, 512) 0.698, (1024, 1024) 0.720,
    (256, 256) 0.754, and the (512, 256) of the earlier pick 0.87;
    (512, 512) also leads at S2048 and ties at S512. Larger tiles share
    a row's statistics and a grid step over more keys; past 512 the
    tiles the diagonal crosses waste more than that saves there. At
    S1024 float32 and D128 ran another 8-18 % faster at (1024, 1024),
    which this rule does not pick: one shape each is no rule on head
    size or dtype (PERF.md section 6 has the table, section 7 the open
    question). :func:`_heads_per_step` fits the step to the VMEM.
    Falls back through 256 to the 128-lane minimum when the sequence
    length doesn't divide, so short or odd-length shapes still get the
    fused kernel whenever a legal tiling exists. Override for tuning
    with SINGA_FLASH_BLOCK_Q / SINGA_FLASH_BLOCK_K — an override that
    does not divide the sequence length is warned about (once per
    shape) and ignored, so a bad knob can never silently cost the
    fused kernel."""
    bq, bk = (min(next((b for b in (512, 256, 128) if S % b == 0), 128), S)
              for S in (Sq, Sk))
    # a partial override keeps the adaptive pick for the other axis
    out = []
    for name, env, adaptive, S in (("Q", _env_block("SINGA_FLASH_BLOCK_Q"),
                                    bq, Sq),
                                   ("K", _env_block("SINGA_FLASH_BLOCK_K"),
                                    bk, Sk)):
        if env is not None:
            # clamp to the sequence length FIRST: an oversized override
            # would otherwise reach the kernel unclamped and launch a
            # zero-size grid (output never written)
            env = min(env, S)
            if S % env:
                # a non-dividing override would silently cost the fused
                # kernel (_use_pallas declines): warn once per shape and
                # keep the adaptive pick instead
                key = (name, env, S)
                if key not in _ENV_BLOCK_WARNED:
                    _ENV_BLOCK_WARNED.add(key)
                    import warnings
                    warnings.warn(
                        f"SINGA_FLASH_BLOCK_{name}={env} does not divide "
                        f"sequence length {S}; using the adaptive "
                        f"{adaptive} instead", stacklevel=3)
                env = None
        out.append(env if env is not None else adaptive)
    return tuple(out)


def _pallas_blocks(q, k):
    """Adaptive block pick + kernel-eligibility check in one step:
    (block_q, block_k) when the Pallas kernels should run for these
    shapes, else None (scan-path fallback)."""
    bq, bk = _pick_blocks(q.shape[2], k.shape[2])
    return (bq, bk) if _use_pallas(q, k, bq, bk) else None


def _use_pallas(q, k, block_q, block_k):
    bq = min(block_q, q.shape[2])
    bk = min(block_k, k.shape[2])
    if q.shape[2] % bq or k.shape[2] % bk:
        if jax.default_backend() == "tpu":
            # on TPU this silently costs the fused kernel — say so once
            # per shape so an odd sequence length is a visible choice,
            # not a hidden perf cliff
            sig = (q.shape[2], k.shape[2], bq, bk)
            if sig not in _DECLINE_LOGGED:
                _DECLINE_LOGGED.add(sig)
                import warnings
                warnings.warn(
                    f"flash attention: sequence lengths q={q.shape[2]} "
                    f"k={k.shape[2]} not divisible by blocks "
                    f"({bq},{bk}); using the unfused scan path — pad "
                    "the sequence to a multiple of 128 to get the "
                    "Pallas kernel", stacklevel=3)
        return False
    return kernels_run()


def kernels_run():
    """Whether a Pallas kernel can run here: on the TPU, or anywhere
    under the interpret-mode test hook."""
    return jax.default_backend() == "tpu" or FORCE_PALLAS_INTERPRET


def _interpret():
    return FORCE_PALLAS_INTERPRET or jax.default_backend() != "tpu"


_NT = (((1,), (1,)), ((), ()))    # a @ b.T: contract the last axis of both
_NN = (((1,), (0,)), ((), ()))    # a @ b


def _mxu(a, b, dims=_NN):
    """Matrix product of two tiles in the dtype they arrive in (bf16 under
    bf16_mixed, float32 for float32 callers), accumulated in float32."""
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _scaled(q, scale):
    """q * scale in q's dtype, so the (block_q, block_k) score tile needs
    no multiply of its own. Exact for a power-of-two scale (head sizes 16,
    64, 256); otherwise one rounding of q, the same in all three kernels,
    so the backward's recomputed probabilities match the saved lse."""
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _rows_to_lanes(x):
    """(n, _LANES) lane-broadcast row statistic -> (1, n), rows on lanes."""
    return x.T[:1]


def _lanes_to_rows(x):
    """(1, n) statistic with rows on lanes -> (n, _LANES) lane-broadcast."""
    return jnp.broadcast_to(x, (_LANES, x.shape[1])).T


def _across(stat, n):
    """(rows, _LANES) lane-broadcast statistic -> (rows, n). Whole lane
    tiles repeat and a narrower tile is a slice, neither of which moves
    data between lanes (a ``stat[:, :1]`` broadcast does, once a row group
    a tile, and was most of the forward's time)."""
    if n % _LANES == 0:
        return pltpu.repeat(stat, n // _LANES, axis=1)
    if n < _LANES:
        return stat[:, :n]
    return jnp.broadcast_to(stat[:, :1], (stat.shape[0], n))


def _visible(shape, q_axis, bound):
    """Causal mask of a score tile whose first query lies ``bound``
    positions after its first key: key index - query index <= bound.
    ``q_axis`` is the tile axis the queries lie on (0 for s = q k^T, 1
    for the dK/dV kernel's s^T = k q^T)."""
    q_idx = lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_idx = lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return k_idx - q_idx <= bound


def _on_causal_tiles(causal, qi, kj, block_q, block_k, split, body):
    """Run ``body(parts)`` on the (qi, kj) tile unless the causal prune
    skips it. ``parts`` are the sub-tiles to compute, each (q0, nq, k0,
    nk, bound): rows q0..q0+nq of the q block against rows k0..k0+nk of
    the k block, masked by :func:`_visible` with ``bound`` unless it is
    None. A tile wholly above the diagonal does not run and one wholly
    below it runs whole and unmasked. A tile the diagonal crosses runs
    whole under a mask — or, where it is square and the diagonal runs
    from corner to corner, as two parts that leave the masked upper right
    quarter out: halves of the q rows (``split`` 'q': a row's statistics
    are visited once) or of the k rows ('k', for the dK/dV kernel)."""
    whole = (0, block_q, 0, block_k)
    if not causal:
        body([whole + (None,)])
        return
    first_k, first_q = kj * block_k, qi * block_q
    below = first_k + block_k - 1 <= first_q
    crosses = jnp.logical_and(first_k <= first_q + block_q - 1,
                              jnp.logical_not(below))
    half = block_q // 2
    if block_q == block_k and half % _LANES == 0:
        # square blocks: the tiles the diagonal crosses are qi == kj
        parts = ([(0, half, 0, half, 0), (half, half, 0, block_k, half)]
                 if split == "q" else
                 [(0, block_q, 0, half, 0), (half, half, half, half, 0)])
    else:
        parts = [whole + (first_q - first_k,)]
    pl.when(below)(lambda: body([whole + (None,)]))
    pl.when(crosses)(lambda: body(parts))


def _last_k_block(qi, block_q, block_k, nkb):
    """Last k block a causal q block attends to."""
    return jnp.minimum(nkb - 1, ((qi + 1) * block_q - 1) // block_k)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *,
                      causal, scale, block_q, block_k, nkb,
                      offset_ref=None):
    """``offset_ref`` (optional (1,1) i32 input placed before q_ref by the
    caller): global-position delta ``q_offset - k_offset`` for causal
    masking when q and k come from different sequence shards (ring
    attention). With a delta the k-grid is not pruned — masking handles
    everything — so the write happens at the final k block."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    pruned = causal and offset_ref is None
    heads, _, D = acc_ref.shape

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute(parts):
        for q0, nq, k0, nk, bound in parts:
            rows, keys = slice(q0, q0 + nq), slice(k0, k0 + nk)
            if bound is not None:
                visible = _visible((nq, nk), 0, bound)
            for h in range(heads):
                v = v_ref[h, keys]
                s = _mxu(_scaled(q_ref[h, rows], scale), k_ref[h, keys],
                         _NT)
                if bound is not None:
                    s = jnp.where(visible, s, _NEG_INF)
                # m/l live lane-broadcast as (block_q, _LANES)
                m = m_ref[h, rows]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - _across(m_new, nk))
                if bound is not None and offset_ref is not None:
                    # a FULLY-masked row has m_new == _NEG_INF (finite),
                    # making exp(s - m_new) == 1 on masked entries — zero
                    # them explicitly (offset grids are not pruned, so
                    # such blocks do occur)
                    p = jnp.where(visible, p, 0.0)
                alpha = jnp.exp(m - m_new)
                l_ref[h, rows] = l_ref[h, rows] * alpha + jnp.sum(
                    p, axis=-1, keepdims=True)
                acc_ref[h, rows] = acc_ref[h, rows] * _across(
                    alpha, D) + _mxu(p.astype(v.dtype), v)
                m_ref[h, rows] = m_new

    if causal and offset_ref is not None:
        _compute([(0, block_q, 0, block_k,
                   qi * block_q - kj * block_k + offset_ref[0, 0])])
    else:
        _on_causal_tiles(causal, qi, kj, block_q, block_k, "q", _compute)

    # the last k-block this q-block attends to writes the result
    last = _last_k_block(qi, block_q, block_k, nkb) if pruned else nkb - 1

    @pl.when(kj == last)
    def _write():
        for h in range(heads):
            l = jnp.maximum(l_ref[h], 1e-30)
            o_ref[h] = (acc_ref[h] / _across(l, D)).astype(o_ref.dtype)
            lse_ref[h] = _rows_to_lanes(m_ref[h] + jnp.log(l))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, lse_col, delta_col, *,
                         causal, scale, block_q, block_k, nkb):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    heads = acc_ref.shape[0]

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # the statistics arrive with the rows on lanes; this kernel's
        # tiles have the q rows on sublanes: turn them once a q block
        for h in range(heads):
            lse_col[h] = _lanes_to_rows(lse_ref[h])
            delta_col[h] = _lanes_to_rows(delta_ref[h])

    def _compute(parts):
        for q0, nq, k0, nk, bound in parts:
            rows, keys = slice(q0, q0 + nq), slice(k0, k0 + nk)
            if bound is not None:
                visible = _visible((nq, nk), 0, bound)
            for h in range(heads):
                k = k_ref[h, keys]
                s = _mxu(_scaled(q_ref[h, rows], scale), k, _NT)
                if bound is not None:
                    s = jnp.where(visible, s, _NEG_INF)
                p = jnp.exp(s - _across(lse_col[h, rows], nk))
                dp = _mxu(g_ref[h, rows], v_ref[h, keys], _NT)
                ds = p * (dp - _across(delta_col[h, rows], nk))
                acc_ref[h, rows] += _mxu(ds.astype(k.dtype), k)

    _on_causal_tiles(causal, qi, kj, block_q, block_k, "q", _compute)

    last = _last_k_block(qi, block_q, block_k, nkb) if causal else nkb - 1

    @pl.when(kj == last)
    def _write():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *,
                          causal, scale, block_q, block_k, nqb):
    """Works on the transposed tile s^T = k q^T (block_k, block_q): the q
    rows lie on lanes, as lse and delta arrive, so a row's statistic
    broadcasts down the sublanes and p^T g, ds^T q need no transpose."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    heads = dk_acc.shape[0]

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(parts):
        for q0, nq, k0, nk, bound in parts:
            rows, keys = slice(q0, q0 + nq), slice(k0, k0 + nk)
            if bound is not None:
                visible = _visible((nk, nq), 1, bound)
            for h in range(heads):
                q = q_ref[h, rows]
                g = g_ref[h, rows]
                st = _mxu(k_ref[h, keys], _scaled(q, scale), _NT)
                if bound is not None:
                    st = jnp.where(visible, st, _NEG_INF)
                pt = jnp.exp(st - lse_ref[h, :, rows])
                dpt = _mxu(v_ref[h, keys], g, _NT)
                dst = pt * (dpt - delta_ref[h, :, rows])
                dv_acc[h, keys] += _mxu(pt.astype(g.dtype), g)
                dk_acc[h, keys] += _mxu(dst.astype(q.dtype), q)

    _on_causal_tiles(causal, qi, kj, block_q, block_k, "k", _compute)

    @pl.when(qi == nqb - 1)
    def _write():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# Scoped VMEM a Mosaic call may use on the v5e unless told otherwise. Asking
# for more was measured to cost more than it buys (the ops around the call
# slow down), so the kernels size a grid step to fit it.
_VMEM_SCOPED = 16 * 2 ** 20


def _heads_per_step(n, block_q, block_k, D, itemsize):
    """How many of the ``n`` (batch, head) slices one grid step works on.

    The body is unrolled over them, so the scheduler overlaps one head's
    matrix products with another's exp and reductions, and the fixed cost
    of a grid step is shared. Forward + backward at (4, 16, 1024, 64)
    bf16 causal, v5e, 2026-10-01: (512, 512) tiles take 0.758 ms one head
    a step and 0.698 ms four; (256, 256) tiles 1.24 ms and, eight a
    step, 0.754 ms; a loop that is not unrolled gains nothing. The price
    is Mosaic's compile time, about 0.65 s a layer's three kernels at one
    head and 2.0 s at four, paid when a step compiles cold. As many as
    divide ``n`` and fit the scoped VMEM: the double-buffered blocks and
    the scratch of the largest kernel (head size padded to whole lane
    tiles, as VMEM holds it), with room for the float32 score tiles;
    8 at most, which bounds the unrolled code."""
    row = -(-D // _LANES) * _LANES
    per_head = max(
        # dQ: q, g, dq and k, v blocks twice; acc, lse, delta scratch
        2 * (3 * block_q + 2 * block_k) * row * itemsize
        + block_q * (row + 2 * _LANES) * 4,
        # dK/dV: q, g and k, v, dk, dv blocks twice; two accumulators
        2 * (2 * block_q + 4 * block_k) * row * itemsize
        + 2 * block_k * row * 4)
    # six live score-sized float32 tiles and 2 MiB of slack
    room = _VMEM_SCOPED - 2 ** 21 - 6 * block_q * block_k * 4
    return next(h for h in (8, 4, 2, 1)
                if h == 1 or (n % h == 0 and h * per_head <= room))


def _clamped_blocks(q, k, block_q, block_k):
    block_q = min(block_q, q.shape[2])
    block_k = min(block_k, k.shape[2])
    assert q.shape[2] % block_q == 0 and k.shape[2] % block_k == 0, \
        "flash kernel needs sequence divisible by block size"
    return block_q, block_k


def _kv_index_map(pruned, block_q, block_k, nkb):
    """Index map of the K/V blocks on a (b, q block, k block) grid. Steps
    the causal prune skips name the last block that ran, so they fetch
    nothing."""
    if pruned:
        return lambda b, i, j: (
            b, jnp.minimum(j, _last_k_block(i, block_q, block_k, nkb)), 0)
    return lambda b, i, j: (b, j, 0)


def _pallas_flash_fwd(q, k, v, causal, scale, block_q=128, block_k=128,
                      pos_delta=None):
    """(B, H, S, D) fused attention forward on the MXU -> (out, lse).

    ``pos_delta`` (traced i32 scalar, optional): global-position delta
    ``q_offset - k_offset`` when q and k come from different sequence
    shards (ring attention feeds the visiting k/v block's offset per ring
    step). With a delta, causal masking uses global positions and the
    k grid is not pruned."""
    block_q, block_k = _clamped_blocks(q, k, block_q, block_k)
    return _flash_fwd_call(q, k, v, pos_delta, causal=causal,
                           scale=float(scale), block_q=block_q,
                           block_k=block_k, interpret=_interpret())


# The calls are jitted so that a model of many layers traces and lowers a
# kernel once a shape, not once a layer (the unrolled bodies take a few
# tenths of a second to trace; 24 layers, three traces of a train step).
_KERNEL_STATICS = ("causal", "scale", "block_q", "block_k", "interpret")


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _flash_fwd_call(q, k, v, pos_delta, *, causal, scale, block_q, block_k,
                    interpret):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    nkb = Sk // block_k
    hb = _heads_per_step(B * H, block_q, block_k, D, q.dtype.itemsize)
    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, D)
    with_off = pos_delta is not None

    def kernel(*refs):
        if with_off:
            off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc, mr, lr = refs
        else:
            q_ref, k_ref, v_ref, o_ref, lse_ref, acc, mr, lr = refs
            off_ref = None
        _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                          acc, mr, lr, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, nkb=nkb,
                          offset_ref=off_ref)

    kv_map = _kv_index_map(causal and not with_off, block_q, block_k, nkb)
    in_specs = [
        pl.BlockSpec((hb, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((hb, block_k, D), kv_map),
        pl.BlockSpec((hb, block_k, D), kv_map),
    ]
    operands = [qr, kr, vr]
    if with_off:
        in_specs = [pl.BlockSpec((1, 1), lambda b, i, j: (0, 0))] + in_specs
        operands = [jnp.asarray(pos_delta, jnp.int32).reshape(1, 1)] + \
            operands
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H // hb, Sq // block_q, nkb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((hb, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((hb, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, block_q, D), jnp.float32),
            pltpu.VMEM((hb, block_q, _LANES), jnp.float32),
            pltpu.VMEM((hb, block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*operands)
    return out.reshape(B, H, Sq, D), lse.reshape(B, H, Sq)


def _pallas_flash_bwd(q, k, v, out, lse, g, causal, scale,
                      block_q=128, block_k=128):
    block_q, block_k = _clamped_blocks(q, k, block_q, block_k)
    return _flash_bwd_call(q, k, v, out, lse, g, causal=causal,
                           scale=float(scale), block_q=block_q,
                           block_k=block_k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _flash_bwd_call(q, k, v, out, lse, g, *, causal, scale, block_q, block_k,
                    interpret):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    nqb, nkb = Sq // block_q, Sk // block_k
    hb = _heads_per_step(B * H, block_q, block_k, D, q.dtype.itemsize)
    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, D)
    gr = g.reshape(B * H, Sq, D)
    # row statistics enter the kernels with the sequence on lanes
    lser = lse.reshape(B * H, 1, Sq)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(B * H, 1, Sq)

    kv_map = _kv_index_map(causal, block_q, block_k, nkb)

    def first_q(i, j):
        # the dK/dV grid's q side: steps before the first q block a k
        # block's prune lets run name that block, so they fetch nothing
        return jnp.maximum(i, (j * block_k) // block_q) if causal else i

    qspec = pl.BlockSpec((hb, block_q, D), lambda b, i, j: (b, i, 0))
    rowspec = pl.BlockSpec((hb, 1, block_q), lambda b, i, j: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, nkb=nkb),
        grid=(B * H // hb, nqb, nkb),
        in_specs=[
            qspec,
            pl.BlockSpec((hb, block_k, D), kv_map),
            pl.BlockSpec((hb, block_k, D), kv_map),
            qspec, rowspec, rowspec,
        ],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((hb, block_q, D), jnp.float32),
                        pltpu.VMEM((hb, block_q, _LANES), jnp.float32),
                        pltpu.VMEM((hb, block_q, _LANES), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qr, kr, vr, gr, lser, delta)

    kvspec = pl.BlockSpec((hb, block_k, D), lambda b, j, i: (b, j, 0))
    qside = pl.BlockSpec((hb, block_q, D),
                         lambda b, j, i: (b, first_q(i, j), 0))
    rowside = pl.BlockSpec((hb, 1, block_q),
                           lambda b, j, i: (b, 0, first_q(i, j)))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, nqb=nqb),
        grid=(B * H // hb, nkb, nqb),
        in_specs=[qside, kvspec, kvspec, qside, rowside, rowside],
        out_specs=[kvspec, kvspec],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Sk, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((hb, block_k, D), jnp.float32),
                        pltpu.VMEM((hb, block_k, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qr, kr, vr, gr, lser, delta)
    return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Sk, D),
            dv.reshape(B, H, Sk, D))


def _flash_fwd_impl(q, k, v, causal, scale, block_k):
    blocks = _pallas_blocks(q, k)
    if blocks:
        return _pallas_flash_fwd(q, k, v, causal, scale,
                                 block_q=blocks[0], block_k=blocks[1])
    return _scan_flash_fwd(q, k, v, causal, scale, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=False, scale=None, block_k=512):
    """Fused multi-head attention: softmax(q·kᵀ·scale [+ causal mask])·v.

    q/k/v: (batch, heads, seq, head_dim). The S×S score matrix is never
    materialised in either direction — forward keeps online-softmax
    accumulators, backward recomputes per-block probabilities from the
    saved lse — so train-mode memory is O(S·D). On TPU both directions run
    as Pallas kernels (primal path included, so inference uses the fused
    kernel too); elsewhere identical-math `lax.scan` implementations run.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_fwd_impl(q, k, v, causal, scale, block_k)[0]


def _flash_fwd(q, k, v, causal, scale, block_k):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    out, lse = _flash_fwd_impl(q, k, v, causal, scale, block_k)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_k, res, g):
    q, k, v, out, lse = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    blocks = _pallas_blocks(q, k)
    if blocks:
        return _pallas_flash_bwd(q, k, v, out, lse, g, causal, scale,
                                 block_q=blocks[0], block_k=blocks[1])
    return _scan_flash_bwd(q, k, v, out, lse, g, causal, scale, block_k)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# ring attention (sequence parallel over a mesh axis)
# ---------------------------------------------------------------------------

def _ring_partials_scan(qf, kr, vr, delta, causal, scale, block_k):
    """Normalized block-attention partials via the differentiable scan
    path: (out / l, m + log l). Only the position DELTA matters for
    causal masking, so (q_offset=delta, k_offset=0) is equivalent to any
    (q_off, k_off) with the same difference."""
    po, pm, pl = _block_scan_attention(qf, kr, vr, causal, scale, block_k,
                                       q_offset=delta, k_offset=0)
    lsafe = jnp.maximum(pl, 1e-30)
    return po / lsafe[..., None], pm + jnp.log(lsafe)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ring_partials(qf, kr, vr, delta, causal, scale, block_k):
    """One ring step's block attention -> normalized (out, lse) partials.

    Primal dispatches to the fused Pallas kernel when available (the MXU
    path; the per-step position delta rides in as a traced scalar);
    backward recomputes through the differentiable scan path — same
    O(S/n) activation footprint, exact same masking semantics."""
    blocks = _pallas_blocks(qf, kr)
    if blocks:
        return _pallas_flash_fwd(qf, kr, vr, causal, scale,
                                 block_q=blocks[0], block_k=blocks[1],
                                 pos_delta=delta)
    return _ring_partials_scan(qf, kr, vr, delta, causal, scale, block_k)


def _ring_partials_fwd(qf, kr, vr, delta, causal, scale, block_k):
    out = _ring_partials(qf, kr, vr, delta, causal, scale, block_k)
    return out, (qf, kr, vr, delta)


def _ring_partials_bwd(causal, scale, block_k, res, cots):
    qf, kr, vr, delta = res
    _, vjp_fn = jax.vjp(
        lambda q, kk, vv: _ring_partials_scan(q, kk, vv, delta, causal,
                                              scale, block_k),
        qf, kr, vr)
    dq, dk, dv = vjp_fn(cots)
    ddelta = np.zeros((), dtype=jax.dtypes.float0)
    return dq, dk, dv, ddelta


_ring_partials.defvjp(_ring_partials_fwd, _ring_partials_bwd)


def ring_attention(q, k, v, axis_name, causal=False, scale=None,
                   block_k=512):
    """Sequence-parallel attention inside ``shard_map``.

    Each device holds the (B, H, S/n, D) shard of q/k/v for its sequence
    slice. k/v rotate around the ring (`lax.ppermute` over ICI) for n
    steps; every step folds the visiting block into the local
    online-softmax accumulator, so activations stay O(S/n) per chip and
    the transfers overlap the einsums. Causal masking uses global
    positions, so results equal single-device causal attention.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    n = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, H, S_local, D = q.shape
    q_off = idx * S_local

    qf = q.astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, r):
        out, m, l, kr, vr = carry
        # the (idx - r)-th device's block is visiting us this round
        src = (idx - r) % n
        o_n, lse = _ring_partials(qf, kr, vr, q_off - src * S_local,
                                  causal, scale, block_k)
        # normalized partial + lse is merge-equivalent to
        # (unnormalized out, m, l) with m := lse, l := 1
        po, pm, plgt = o_n, lse, jnp.ones_like(lse)
        # merge the visiting block's partial into the accumulator
        m_new = jnp.maximum(m, pm)
        a1 = jnp.exp(m - m_new)
        a2 = jnp.exp(pm - m_new)
        out = out * a1[..., None] + po * a2[..., None]
        l = l * a1 + plgt * a2
        kr = lax.ppermute(kr, axis_name, perm)
        vr = lax.ppermute(vr, axis_name, perm)
        return (out, m_new, l, kr, vr), None

    zero = qf * 0.0  # inherits qf's varying-axes type (see above)
    init = (zero,
            jnp.max(zero, axis=-1) + _NEG_INF,
            jnp.sum(zero, axis=-1),
            k.astype(jnp.float32), v.astype(jnp.float32))
    (out, m, l, _, _), _ = lax.scan(step, init, jnp.arange(n))
    return _merge_partials(out, m, l).astype(q.dtype)


# ---------------------------------------------------------------------------
# tape ops
# ---------------------------------------------------------------------------

class _FlashAttention(Operator):
    """Tape op wrapping :func:`flash_attention`."""

    def __init__(self, causal=False, scale=None):
        super().__init__()
        self.causal = causal
        self.scale = scale

    def forward(self, q, k, v):
        # policy discipline: attention matmuls run in the compute dtype;
        # the kernel's own online-softmax statistics are f32 regardless
        q, k, v = _cast_compute(q, k, v)
        return flash_attention(q, k, v, self.causal, self.scale)


def ulysses_attention(q, k, v, axis_name, causal=False, scale=None,
                      block_k=512):
    """All-to-all sequence parallelism (Ulysses-style) inside
    ``shard_map``: each device holds the (B, H, S/n, D) shard of its
    sequence slice; ONE all_to_all re-shards HEADS over the axis while
    gathering the FULL sequence locally ((B, H/n, S, D)), the fused
    flash kernel then runs unchanged on the full sequence — plain causal
    masking, no position offsets — and a second all_to_all restores
    sequence sharding.

    Two collectives per attention call versus ring attention's n
    ppermute hops: the better trade when the axis is large and heads are
    plentiful; ring wins when H < n or the gathered (S, S)-block
    workspace per head would not fit. Requires H % n == 0 — the
    :func:`attention` dispatcher falls back to ring otherwise.
    """
    def a2a(x, split, concat):
        return lax.all_to_all(x, axis_name, split_axis=split,
                              concat_axis=concat, tiled=True)

    qh, kh, vh = (a2a(t, 1, 2) for t in (q, k, v))
    out = flash_attention(qh, kh, vh, causal=causal, scale=scale,
                          block_k=block_k)
    return a2a(out, 2, 1)


class _UlyssesAttention(Operator):
    """Tape op wrapping :func:`ulysses_attention` (inside shard_map)."""

    def __init__(self, axis_name, causal=False, scale=None):
        super().__init__()
        self.axis_name = axis_name
        self.causal = causal
        self.scale = scale

    def forward(self, q, k, v):
        q, k, v = _cast_compute(q, k, v)
        return ulysses_attention(q, k, v, self.axis_name, self.causal,
                                 self.scale)


class _RingAttention(Operator):
    """Tape op wrapping :func:`ring_attention` (inside shard_map)."""

    def __init__(self, axis_name, causal=False, scale=None):
        super().__init__()
        self.axis_name = axis_name
        self.causal = causal
        self.scale = scale

    def forward(self, q, k, v):
        q, k, v = _cast_compute(q, k, v)
        return ring_attention(q, k, v, self.axis_name, self.causal,
                              self.scale)


def attention(q, k, v, causal=False, scale=None, seq_axis=None,
              seq_mode="ring"):
    """Functional tape API. With ``seq_axis`` an active
    sequence-parallel mesh axis, ``seq_mode`` picks the long-context
    strategy: ``'ring'`` (k/v rotate over ICI, O(S/n) workspace) or
    ``'ulysses'`` (one all_to_all head re-shard, full local sequence).
    Ulysses needs the local head count divisible by the axis size and
    falls back to ring otherwise (one-time warning)."""
    from ..parallel.communicator import active_axis
    if seq_mode not in ("ring", "ulysses", "alltoall", "all_to_all"):
        raise ValueError(f"unknown seq_mode {seq_mode!r} "
                         "(expected 'ring' or 'ulysses')")
    if seq_axis is not None and active_axis(seq_axis):
        if seq_mode in ("ulysses", "alltoall", "all_to_all"):
            n = _axis_size(seq_axis)
            H = q.shape[1]
            if H % n == 0:
                return _UlyssesAttention(seq_axis, causal, scale)(q, k, v)
            sig = ("ulysses-fallback", H, n)
            if sig not in _DECLINE_LOGGED:
                _DECLINE_LOGGED.add(sig)
                import warnings
                warnings.warn(
                    f"ulysses attention needs heads ({H}) divisible by "
                    f"the '{seq_axis}' axis size ({n}); falling back to "
                    "ring attention", stacklevel=2)
        return _RingAttention(seq_axis, causal, scale)(q, k, v)
    return _FlashAttention(causal, scale)(q, k, v)
