"""One decode tick of a ring KV level as one Pallas pass.

``serving/kv_cache.decode_token`` sends a float ring level here on the
TPU: the new token's k/v row is written and its query attends in one
kernel that touches only what the tick needs of the ``(W, H_kv, L, D)``
level, where the XLA twins ``write_token`` + ``attend`` rewrite the level
and score all ``L`` positions of all ``W`` slots.

The ring is cut into blocks of ``B`` rows (:func:`block_rows`). A slot
whose new token sits at position ``p`` holds tokens in its first
``ceil(min(p + 1, L) / B)`` blocks, all of them once the ring has
wrapped; a dead slot holds none that matter. The wrapper lists those
(slot, block) pairs in order and the grid has one step a pair — its
length is read on the device — so the K/V blocks stream through VMEM by
the ordinary pipeline and nothing else is fetched. A step takes a group
of the level's KV heads (:func:`heads_per_step`: as many as their
double-buffered blocks fit the VMEM): on a row-major level each head of
the group is its own chain of two matrix products, batched over the
heads, so that a pair of ten heads of 128 is one step and not ten. Of
the step:

- scores and the value product take the block in the cache's dtype on
  the MXU with float32 accumulation; running max, sum and accumulator
  (online softmax across a slot's blocks) are float32 scratch, each
  head's in rows of its own; the ``G`` query heads that read one KV head
  ride the query axis, so a block is read once for all of them;
- the mask is ring arithmetic on ``p``: ring index ``j`` holds position
  ``p - ((p - j) % L)``, which is >= 0 exactly where ``j <= p`` (every
  ``j`` once ``p >= L``);
- the new row is merged in VMEM into the fetched block that holds ring
  index ``p % L``, is scored with the rest, and the one tile of each
  head's block that took it is copied back by a DMA into the level,
  which is aliased to the output — no other byte of the cache is
  written.

A layer that attends to ANOTHER layer's keys and values takes the same
walk read-only (:func:`ring_attend`, ``kv_cache.attend_token``): no new
row, nothing written, the level no output of the call, so every such
reader shares the owner's buffer.

A LATENT level (``kv_cache.LatentLevel``: one array a token, ``(W, 1, L,
P)``, scored over the row's whole width, the values its first columns)
takes the same walk in a latent form (:func:`latent_decode`): one streamed
operand instead of two, every query head a row of one matrix product
against it, the value product on a slice of the block already in VMEM,
one tile written back.

The kernel's text does not depend on ``L`` (blocks are a grid axis) nor
on ``G`` (one matrix product), and the call sits in a jitted wrapper with
static arguments, so a model lowers it once a distinct
``(W, H_kv, G, L, D, dtype)`` and not once a layer. A level of one KV
head, a latent level and one with the ring on the lanes take one pair of
two-dimensional products a step, with no axis of heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import (_LANES, _NEG_INF, _NN, _NT, _across, _interpret,
                        _mxu, kernels_run)

# Candidate block lengths, largest first: a ring takes the first that
# divides it and cuts it into MIN_BLOCKS at least, so that a short
# context reads an eighth of its ring and not half. On the v5e
# (2026-10-02, PR 28; the kernel alone, microseconds a call): the GPT-2
# level (64, 16, 1024, 64) bf16 with 36 live slots of median 224 tokens,
# blocks of 128 / 256 / 512: 100 / 107 / 154; Command A+'s window level
# (64, 1, 4096, 128) with 40 live slots of median 2.2 k tokens, 256 /
# 512: 164 / 109.
BLOCKS = (512, 256, 128)
MIN_BLOCKS = 8

# VMEM the double-buffered K and V blocks of a step may take; the scoped
# limit is 16 MiB and the score tiles and scratch need the rest.
_BLOCK_VMEM = 8 * 2 ** 20


def block_rows(length):
    """Rows of one block of a ring ``length`` long, or None where no
    block divides it (the level then keeps the XLA path)."""
    fits = [b for b in BLOCKS if length % b == 0]
    return next((b for b in fits if length // b >= MIN_BLOCKS),
                fits[-1] if fits else None)


def live_blocks(pos, active, length, block):
    """Blocks of each slot's ring that hold a token when the new token's
    position is ``pos``: ``(W,)`` int32, 0 for a dead slot."""
    rows = jnp.minimum(pos.astype(jnp.int32) + 1, length)
    return jnp.where(active, -(-rows // block), 0).astype(jnp.int32)


def heads_per_step(n_kv, block, D, itemsize):
    """KV heads one grid step works on, for a level of ``n_kv`` heads of
    ``D`` cut into blocks of ``block`` rows: as many as the double-buffered
    K and V blocks of a step fit in the VMEM. A level that arrives with
    the ring on the lanes (head size under a lane tile) gives them to one
    pair of matrix products, in whole lane tiles of ``heads x D``, halving
    from all of them; a row-major level takes the largest divisor of
    ``n_kv`` that fits, the heads the batch axis of its products, each
    head's query group filling its own rows."""
    fits = lambda hb: 4 * hb * D * block * itemsize <= _BLOCK_VMEM
    if D % _LANES == 0:
        return max(d for d in range(1, n_kv + 1)
                   if n_kv % d == 0 and (d == 1 or fits(d)))
    hb = n_kv
    while not fits(hb) and hb % 2 == 0 and (hb // 2 * D) % _LANES == 0:
        hb //= 2
    return hb


def kernel_block(n_kv, length, D):
    """Rows of a block for a level of these sizes, or None where the
    kernel has no form for it: no block divides the ring, or the head
    size is neither whole lane tiles (the level is row-major) nor a part
    of one that packs into sublane tiles (XLA keeps that level with the
    ring on the lanes) whose heads together fill whole lane tiles (Mosaic
    aborts on a narrower product: jax 0.9.0, checked by compiling for
    the v5e)."""
    if D % _LANES and (_LANES % D or D % 16 or (n_kv * D) % _LANES):
        return None
    return block_rows(length)


def _ring_decode_kernel(total_ref, slot_ref, blk_ref, pos_ref, walk_ref,
                        q_ref, *refs, scale, block, length, tile, cols,
                        write, vcols=None):
    """One (slot, block) pair of the walk, one group of KV heads.
    ``write`` false is the read-only pass (:func:`ring_attend`): no new
    row, no output but the attention's, the level is only read.

    Row-major level (``cols`` false): the block is ``(heads, B, D)`` and
    ``q`` ``(heads, M, D)``, each head's ``G`` queries in the first rows
    of its own ``M``; the heads are the batch axis of both matrix
    products and each keeps its own ``M`` rows of the scratch, so the
    heads of a step are independent chains of work for the MXUs (one
    head: plain two-dimensional products). ``cols``: the
    level is ``(W, H_kv * D, L)``, the block ``(heads * D, B)`` with
    every head of the group, and ``q`` is ``(heads * G, heads * D)``
    with a head's queries in that head's columns and zeros elsewhere, so
    that one product scores every head against its own keys; the value
    product then holds every head's values in every row, and the wrapper
    reads each row's own head out of it.

    ``vcols`` (the latent form, :func:`latent_decode`): the level is ONE
    row-major array a token; the scores take the row's whole width and
    the values are its first ``vcols`` columns, so the block is fetched
    once for both products and one tile is written back."""
    n = 2 if vcols is None else 1       # arrays a level holds
    new_refs, refs = (refs[:n], refs[n:]) if write else ((), refs)
    lvl_refs, o_ref, refs = refs[:n], refs[n], refs[n + 1:]
    out_refs, refs = (refs[:n], refs[n:]) if write else ((), refs)
    acc_ref, m_ref, l_ref, *sem = refs
    k_ref = lvl_refs[0]
    hg = pl.program_id(0)
    s = pl.program_id(1)
    w = slot_ref[s]
    j = blk_ref[s]
    p = pos_ref[w]
    r = p % length                      # ring index of the new row
    hb = 1 if cols else k_ref.shape[1]  # products a step: one a KV head
    M, A = acc_ref.shape                # A: columns of the value product
    M //= hb                            # scratch rows of one product
    K = k_ref.shape[-2] if cols else k_ref.shape[-1]
    writes = j == r // block            # this block takes the new row
    live = s < total_ref[0]
    # the tile of the block that takes the new row: `tile` rows of it,
    # or the 128 ring indices of one lane tile
    base = pl.multiple_of((r % block) // tile * tile, tile)
    at = r % block - base

    if cols:
        lead = (0,)                     # the block's unit axes
        here = (slice(None), pl.ds(base, tile))
        there = (w, pl.ds(hg * K, K), pl.ds(j * block + base, tile))
    else:
        # every head's tile in one copy
        lead = (0, 0) if hb == 1 else (0, slice(None))
        here = (pl.ds(base, tile), slice(None))
        there = (w, hg if hb == 1 else pl.ds(hg * hb, hb),
                 pl.ds(j * block + base, tile), slice(None))
    copies = [pltpu.make_async_copy(ref.at[lead + here], out.at[there],
                                    sem[0].at[i])
              for i, (ref, out) in enumerate(zip(lvl_refs, out_refs))]

    def _write():
        # merged into the fetched block, which the walk below then reads
        # like any other, and copied from there into the level
        if cols:
            # the row is a column here, and arrives 128 values a row:
            # a product with a one-hot matrix turns each of those rows
            # into the column of its 128 block rows (a broadcast from
            # one lane costs 41 cycles a row group: PR 25). Exact: the
            # values are the level's dtype already, and each sum has one
            # term
            eye = lax.broadcasted_iota(jnp.int32, (tile, tile), 0) == \
                lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
            mask = lax.broadcasted_iota(jnp.int32, (tile, tile), 1) == at
            hot = mask.astype(k_ref.dtype)
            exact = lax.Precision.HIGHEST \
                if k_ref.dtype == jnp.float32 else None
        else:
            mask = lax.broadcasted_iota(jnp.int32, (tile, K), 0) == at
        for ref, new_ref in zip(lvl_refs, new_refs):
            if not cols:
                for h in range(hb):
                    idx = (0, h) + here
                    ref[idx] = jnp.where(
                        mask, new_ref[0, h],
                        ref[idx].astype(jnp.float32)).astype(ref.dtype)
                continue
            for c in range(K // tile):
                new = lax.dot_general(
                    jnp.where(eye, new_ref[0, 0, c:c + 1], 0.0).astype(
                        ref.dtype),
                    hot, _NN, precision=exact,
                    preferred_element_type=jnp.float32)
                idx = (0, pl.ds(c * tile, tile), pl.ds(base, tile))
                ref[idx] = jnp.where(mask, new.astype(ref.dtype), ref[idx])
        for c in copies:
            c.start()

    if write:
        pl.when(jnp.logical_and(live, writes))(_write)

    @pl.when(jnp.logical_and(live, j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live)
    def _walk():
        col = j * block + lax.broadcasted_iota(jnp.int32, (M, block), 1)
        if hb == 1:
            v = lvl_refs[1][lead] if vcols is None \
                else k_ref[lead + (slice(None), slice(0, vcols))]
            q, k = q_ref[0, 0], k_ref[lead]
            dims = (_NN, _NT) if cols else (_NT, _NN)
        else:
            # the step's heads are the batch axis of both products: as
            # many independent chains of work as heads
            v, q, k = lvl_refs[1][0], q_ref[0], k_ref[0]
            dims = ((((2,), (2,)), ((0,), (0,))),
                    (((2,), (1,)), ((0,), (0,))))

        def stat(ref):
            """The scratch, a head's rows on an axis of their own."""
            return ref[...] if hb == 1 else \
                ref[...].reshape(hb, M, ref.shape[-1])

        def keep(ref, x):
            ref[...] = x if hb == 1 else x.reshape(ref.shape)

        sc = _mxu(q, k, dims[0])
        sc = jnp.where(col <= p, sc * scale, _NEG_INF)
        m = stat(m_ref)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        pr = jnp.exp(sc - _across(m_new, block))
        alpha = jnp.exp(m - m_new)
        keep(l_ref, stat(l_ref) * alpha + jnp.sum(pr, axis=-1,
                                                  keepdims=True))
        keep(acc_ref, stat(acc_ref) * _across(alpha, A) + _mxu(
            pr.astype(v.dtype), v, dims[1]))
        keep(m_ref, m_new)

    @pl.when(jnp.logical_and(live, j == walk_ref[w] - 1))
    def _out():
        for h in range(hb):
            rows = slice(h * M, (h + 1) * M)
            o_ref[0, h] = (acc_ref[rows] / _across(l_ref[rows], A)).astype(
                o_ref.dtype)

    if write:
        @pl.when(jnp.logical_and(live, writes))
        def _written():
            for c in copies:
                c.wait()


def _walk_list(pos, active, L, block):
    """The (slot, block) pairs a tick walks, in order: ``(total (1,),
    slot (steps,), blk (steps,), walk (W,))``, the grid ending with the
    last of the ``total`` pairs."""
    W, nb = pos.shape[0], L // block
    walk = live_blocks(pos, active, L, block)
    ends = jnp.cumsum(walk)
    step = jnp.arange(W * nb, dtype=jnp.int32)
    past = step[:, None] >= ends[None, :]       # (steps, W); no gather
    real = step < ends[-1]
    slot = jnp.where(real, jnp.sum(past, axis=1, dtype=jnp.int32), 0)
    blk = jnp.where(real, step - jnp.sum(jnp.where(past, walk, 0), axis=1),
                    0)
    return ends[-1:], slot, blk, walk


def _by_walk(shape, index):
    """A block chosen by the walk's step: ``index(slot, group, block)``."""
    return pl.BlockSpec(shape, lambda g, s, t, sl, bl, ps, wk:
                        index(sl[s], g, bl[s]))


@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def _ring_decode_call(q, k_new, v_new, k, v, pos, active, *, scale, block,
                      interpret):
    W, n_kv, L, D = k.shape
    G = q.shape[1] // n_kv
    dtype = k.dtype
    # XLA keeps a level whose head size is under a lane tile with the
    # ring on the lanes: (W, H_kv, D, L) is then a view of it
    cols = D % _LANES != 0
    rows = 32 // dtype.itemsize         # rows of one sublane tile
    hb = heads_per_step(n_kv, block, D, dtype.itemsize)
    groups = n_kv // hb

    pos = pos.astype(jnp.int32)
    total, slot, blk, walk = _walk_list(pos, active, L, block)
    write = k_new is not None
    if cols:
        M = -(-hb * G // rows) * rows
        K = hb * D
        # a group's queries, each head's in its own D columns of K
        qg = q.reshape(W, groups, hb, G, 1, D).astype(dtype)
        if hb > 1:
            qg = qg * jnp.eye(hb, dtype=dtype)[:, None, :, None]
        qg = jnp.pad(qg.reshape(W, groups, hb * G, K),
                     ((0, 0), (0, 0), (0, M - hb * G), (0, 0)))
        k, v = (a.swapaxes(2, 3).reshape(W, n_kv * D, L) for a in (k, v))
        new_shape = (W, groups, K // _LANES, _LANES)
        new_row = _by_walk((1, 1, K // _LANES, _LANES),
                           lambda w, g, b: (w, g, 0, 0))
        kv_block = _by_walk((1, K, block), lambda w, g, b: (w, g, b))
        by_slot = _by_walk((1, 1, M, K), lambda w, g, b: (w, g, 0, 0))
        out_shape, R = (W, groups, M, K), M
    else:
        # each head's queries in its own M rows
        M, K = -(-G // rows) * rows, D
        qg = jnp.pad(q.reshape(W, n_kv, G, D).astype(dtype),
                     ((0, 0), (0, 0), (0, M - G), (0, 0)))
        new_shape = (W, n_kv, 1, D)
        new_row = _by_walk((1, hb, 1, D), lambda w, g, b: (w, g, 0, 0))
        kv_block = _by_walk((1, hb, block, D),
                            lambda w, g, b: (w, g, b, 0))
        by_slot = _by_walk((1, hb, M, D), lambda w, g, b: (w, g, 0, 0))
        out_shape, R = (W, n_kv, M, D), hb * M
    kernel = functools.partial(
        _ring_decode_kernel, scale=scale, block=block, length=L,
        tile=_LANES if cols else rows, cols=cols, write=write)
    # the new rows as the cache will hold them, widened for the VPU (a
    # group's, 128 values to a row of the operand, where the ring lies
    # on the lanes); the read-only pass has none, writes nothing and
    # leaves the level to its other readers
    new_rows = [a.astype(dtype).astype(jnp.float32).reshape(new_shape)
                for a in (k_new, v_new)] if write else []
    level_out = [pl.BlockSpec(memory_space=pl.ANY)] * 2 if write else []
    out, *kv = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(groups, jnp.maximum(total[0], 1)),
            in_specs=[by_slot] + [new_row] * len(new_rows)
            + [kv_block, kv_block],
            out_specs=[by_slot] + level_out,
            scratch_shapes=[
                pltpu.VMEM((R, K), jnp.float32),
                pltpu.VMEM((R, _LANES), jnp.float32),
                pltpu.VMEM((R, _LANES), jnp.float32),
            ] + ([pltpu.SemaphoreType.DMA((2,))] if write else [])),
        out_shape=[jax.ShapeDtypeStruct(out_shape, q.dtype)]
        + ([jax.ShapeDtypeStruct(a.shape, dtype) for a in (k, v)]
           if write else []),
        # operands count the five prefetched arrays: k is 8, v is 9
        input_output_aliases={8: 1, 9: 2} if write else {},
        interpret=interpret,
        name="ring_decode" if write else "ring_attend",
    )(total, slot, blk, pos, walk, qg, *new_rows, k, v)
    # a dead slot's rows of the output were never written
    if cols:
        kv = [a.reshape(W, n_kv, D, L).swapaxes(2, 3) for a in kv]
        # each row's own head of the value product
        out = out[:, :, :hb * G]
        if hb > 1:
            out = jnp.einsum("wnhgkd,hk->wnhgd",
                             out.reshape(W, groups, hb, G, hb, D),
                             jnp.eye(hb, dtype=out.dtype))
    else:
        out = out[:, :, :G]
    out = jnp.where(active[:, None, None, None], out.reshape(q.shape), 0)
    return (out, *kv)


def ring_decode(q, k_new, v_new, k, v, pos, active, scale, block):
    """Write ``k_new`` / ``v_new`` ``(W, H_kv, D)`` at ring index
    ``pos % L`` of ``k`` / ``v`` ``(W, H_kv, L, D)`` and attend ``q``
    ``(W, H, 1, D)`` over each live slot's ring. Returns
    ``(out (W, H, 1, D), k, v)``; a dead slot's output is zero and its
    ring is left as it was."""
    return _ring_decode_call(q, k_new, v_new, k, v, pos, active,
                             scale=float(scale), block=int(block),
                             interpret=_interpret())


def ring_attend(q, k, v, pos, active, scale, block):
    """The read-only pass: attend ``q`` ``(W, H, 1, D)`` over each live
    slot's ring as it stands, ``pos`` the position of its newest row
    (written already, by the level's own :func:`ring_decode` of this
    tick). The same walk over the blocks that hold a token; no tile is
    written and the level is not an output, so as many layers as read one
    ring share one buffer. Returns ``out (W, H, 1, D)``, zero for a dead
    slot."""
    return _ring_decode_call(q, None, None, k, v, pos, active,
                             scale=float(scale), block=int(block),
                             interpret=_interpret())[0]


# ---------------------------------------------------------------------------
# the latent form: one array a token, values a prefix of the keys
# ---------------------------------------------------------------------------

def latent_block(length, padded):
    """Rows of a block for a latent level ``length`` rows of ``padded``
    columns, or None where the kernel has no form for it (no block
    divides the ring, or the row is not whole lane tiles)."""
    return None if padded % _LANES else block_rows(length)


@functools.partial(jax.jit, static_argnames=(
    "scale", "block", "value_width", "interpret"))
def _latent_decode_call(q, row_new, rows, pos, active, *, scale, block,
                        value_width, interpret):
    W, _, L, P = rows.shape
    H, width = q.shape[1], q.shape[-1]
    dtype = rows.dtype
    tile = 32 // dtype.itemsize         # rows of one sublane tile
    M = -(-H // tile) * tile
    # the value product takes the row's first `value_width` columns
    # where those are whole lane tiles, else the whole row (the wrapper
    # then reads the columns out)
    vcols = value_width if value_width % _LANES == 0 else P
    pos = pos.astype(jnp.int32)
    total, slot, blk, walk = _walk_list(pos, active, L, block)
    qg = jnp.pad(q.reshape(W, 1, H, width).astype(dtype),
                 ((0, 0), (0, 0), (0, M - H), (0, P - width)))
    new = jnp.pad(row_new.astype(dtype).astype(jnp.float32),
                  ((0, 0), (0, P - width))).reshape(W, 1, 1, P)
    kernel = functools.partial(
        _ring_decode_kernel, scale=scale, block=block, length=L, tile=tile,
        cols=False, write=True, vcols=vcols)
    out, rows = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1, jnp.maximum(total[0], 1)),
            in_specs=[
                _by_walk((1, 1, M, P), lambda w, g, b: (w, g, 0, 0)),
                _by_walk((1, 1, 1, P), lambda w, g, b: (w, g, 0, 0)),
                _by_walk((1, 1, block, P), lambda w, g, b: (w, g, b, 0))],
            out_specs=[
                _by_walk((1, 1, M, vcols), lambda w, g, b: (w, g, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.VMEM((M, vcols), jnp.float32),
                pltpu.VMEM((M, _LANES), jnp.float32),
                pltpu.VMEM((M, _LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((1,))]),
        out_shape=[jax.ShapeDtypeStruct((W, 1, M, vcols), q.dtype),
                   jax.ShapeDtypeStruct(rows.shape, dtype)],
        # operands count the five prefetched arrays: the level is 7
        input_output_aliases={7: 1},
        interpret=interpret,
        name="latent_decode",
    )(total, slot, blk, pos, walk, qg, new, rows)
    out = out[:, 0, :H, :value_width].reshape(W, H, 1, value_width)
    return jnp.where(active[:, None, None, None], out, 0), rows


def latent_decode(q, row_new, rows, pos, active, scale, block, value_width):
    """The latent form of :func:`ring_decode`: write ``row_new``
    ``(W, width)`` at ring index ``pos % L`` of ``rows`` ``(W, 1, L, P)``
    (``width`` columns and zeros up to ``P``) and attend ``q``
    ``(W, H, 1, width)`` over each live slot's ring — scores over the
    row's whole width, values its first ``value_width`` columns, a block
    fetched once for all ``H`` query rows and both products. Returns
    ``(out (W, H, 1, value_width), rows)``; a dead slot's output is zero
    and its ring is left as it was."""
    return _latent_decode_call(q, row_new, rows, pos, active,
                               scale=float(scale), block=int(block),
                               value_width=int(value_width),
                               interpret=_interpret())
