"""Fixed-shape KV caches: ring buffers and the paged block pool.

The serving engine's decode program must have ONE shape forever —
``compiled_step_info()["n_traces"] == 1`` is the serve-path invariant —
so the attention cache cannot grow with the sequence. Two layouts
satisfy that contract:

**Ring** (the original, still the default): each slot owns a RING of
``length`` key/value rows per layer: token ``t`` writes ring index
``t % length``, and the decode attention masks each index by the token
position it currently holds. Work and memory per emitted token are
constant (the compiler-first O(1)-cache design of PAPERS.md arxiv
2603.09555); semantically the ring IS sliding-window attention over the
last ``length`` tokens, and for sequences that fit (``pos < length``)
it is exactly full causal attention — the wraparound-vs-reference test
in ``tests/test_serving.py`` pins both. One ring level is
``(n_slots, n_heads, length, head_dim)`` — a W×L×H×D monolith whether
the slots are long, short, or empty. A decode tick goes through
:func:`decode_token`, one call a level: on the TPU a float level whose
ring a block divides takes the Pallas kernel of ``ops/ring_decode.py``
(only the blocks of each live slot's ring that hold a token are read,
one tile of the level is written, a dead slot is neither read nor
written); a quantized level, the GSPMD-sharded engine, an odd ring
length and every other backend keep the XLA twins :func:`write_token`
+ :func:`attend`, which write every slot and score the whole level. A
layer that reads a ring it does not own (cross attention to another
layer's keys and values) calls :func:`attend_token`, the read-only half
by the same rule. A ring level may be LATENT (:class:`LatentLevel`,
:func:`init_latent`): one array a token, scored over its whole width,
whose first columns are the values — what latent attention keeps once
for all its heads; the same functions take it by the same rule, the
kernel in its latent form. A cache may also hold STATE levels beside its rings —
what a recurrent layer keeps of a sequence, a fixed size a slot — which
the ring layout sizes, counts, snapshots and hands off with the rings
(:class:`RingLayout`).

**Paged** (``compile_serving(kv_layout="paged")``): one fixed POOL of
``(n_blocks, n_heads, block_size, head_dim)`` KV blocks per layer plus
a host-side per-slot block table mapping logical block index
``position // block_size`` to a pool block id. Memory scales with LIVE
tokens (each admitted request reserves exactly the blocks its
``prompt + max_new_tokens`` span needs) instead of slots × max_len, and
identical prompt prefixes SHARE refcounted blocks: a prefix-cache hit
skips prefill compute for the shared span entirely (the suffix is
prefilled chunked, attending to the cached prefix through the same
block table). Sharing granularity is whole blocks, capped one token
short of the full prompt (the last prompt token is always prefilled so
its logits exist); divergence is handled by construction — the
divergent tail block is never shared, the new request writes its own
copy (copy-on-write without a device copy). The device math is
position-exact: logical block ``b`` offset ``o`` holds position
``b*block_size + o``, attention masks ``position <= query position``,
so stale rows (freed sequences, rejected speculative drafts) are
unreachable until overwritten. The host-side :class:`BlockManager`
owns allocation, refcounts, and the prefix cache; exhaustion is a
typed :class:`~singa_tpu.serving.scheduler.BlockPoolExhausted`
admission refusal — a LIVE sequence's blocks are never evicted, only
unreferenced cached prefixes are reclaimed (LRU).

Everything device-side here is a pure function over arrays,
shape-stable by construction, ready to be closed over by a jitted
prefill/decode body. The HOST half of each format is a layout class
at the end of this file (:class:`RingLayout`, :class:`PagedLayout`,
picked by :func:`pick_layout`): what ``ServingEngine`` asks of a KV
format, so that its tick is written once. ``dtype=int8`` rides both layouts: per-row fp32
scales beside the ring, per-(block, offset) scale pools beside the
paged blocks.

Ring position bookkeeping (who holds ring index ``j`` when the newest
written token is at position ``p``)::

    t_j = p - ((p - j) % length)        # newest token position at j
    valid(j) = t_j >= 0                 # j was ever written

which masks exactly the last ``min(p+1, length)`` token positions —
no flags, no per-slot host state, just arithmetic on ``p``.
"""

from __future__ import annotations

import contextlib
import hashlib
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models import decode as _decode
from .scheduler import BlockPoolExhausted, HandoffRefused, ServingError


# ---------------------------------------------------------------------------
# chained prefix content keys (shared by the block manager's prefix
# cache and the fleet router's prefix-affinity placement)
# ---------------------------------------------------------------------------

def chain_keys(prompt, block_size):
    """Chained content keys for each FULL block of ``prompt``: key
    ``b`` covers block ``b``'s tokens AND everything before it, so a
    key match guarantees the whole preceding context matches. The ONE
    key construction — :class:`BlockManager`'s prefix cache and the
    fleet router's prefix-affinity hash both build keys here, so
    "lands on the replica holding the blocks" is true by construction,
    never by parallel reimplementation."""
    bs = int(block_size)
    keys, prev = [], ()
    for b in range(len(prompt) // bs):
        prev = (prev, tuple(int(t) for t in prompt[b*bs:(b+1)*bs]))
        keys.append(prev)
    return keys


def prefix_chain_key(prompt, block_size):
    """The chained content key of ``prompt``'s longest CACHEABLE
    full-block prefix — capped one token short of the whole prompt
    (``match_prefix``'s cap: the last token is always prefilled so its
    logits exist). ``None`` for a prompt too short to share even one
    block (a *cold* prefix — affinity routing falls back to
    least-loaded)."""
    cap = (len(prompt) - 1) // int(block_size)
    if cap <= 0:
        return None
    return chain_keys(prompt, block_size)[cap - 1]


def affinity_hash(key, salt=""):
    """Stable 64-bit digest of a chain key (optionally salted with a
    replica name for rendezvous/HRW scoring). Deliberately NOT python
    ``hash()``: that is randomized per process, and the affinity
    contract is *same prefix → same decode replica across router
    restarts*. sha1 over the key's canonical repr is stable across
    processes, platforms, and time."""
    h = hashlib.sha1(
        (repr(key) + "\x00" + str(salt)).encode()).digest()
    return int.from_bytes(h[:8], "big")


def init_cache(n_slots, n_heads, length, head_dim, dtype=jnp.float32):
    """One layer's ring cache: zeroed ``{"k","v"}`` of shape
    ``(n_slots, n_heads, length, head_dim)``. ``n_heads`` counts KV
    heads (fewer than the query heads under grouped attention) and
    ``length`` is this layer's own (levels of one cache may differ).

    ``dtype=int8`` builds the QUANTIZED ring (the
    ``singa_tpu.quant`` serving presets): int8 payloads plus one fp32
    scale per (slot, ring index) — ``{"k_scale","v_scale"}`` of shape
    ``(n_slots, length)`` — written alongside every token/prompt row
    and folded back in inside :func:`attend`'s f32 softmax. 4x less
    cache HBM per token; scales init to 1 (a zero payload dequantizes
    to zero either way)."""
    shape = (int(n_slots), int(n_heads), int(length), int(head_dim))
    level = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        # two DISTINCT buffers: the engine donates the whole cache
        # pytree, and donating one shared array twice is an XLA error
        level["k_scale"] = jnp.ones((int(n_slots), int(length)),
                                    jnp.float32)
        level["v_scale"] = jnp.ones((int(n_slots), int(length)),
                                    jnp.float32)
    return level


@jax.tree_util.register_pytree_with_keys_class
class LatentLevel(dict):
    """A ring level that holds ONE array a token: ``{"k": (n_slots, 1,
    length, padded)}``, rows of ``width`` numbers (zeros up to whole
    lane tiles) that are scored over their whole width and whose first
    ``value_width`` columns are the values — what latent attention in
    its absorbed form keeps, one "KV head" for every query head. The two
    widths are static (part of the tree's structure, not leaves)."""

    def __init__(self, rows, width, value_width):
        super().__init__(k=rows)
        self.width, self.value_width = int(width), int(value_width)

    def with_rows(self, rows):
        """The same level holding ``rows``."""
        return LatentLevel(rows, self.width, self.value_width)

    def copy(self):
        return self.with_rows(self["k"])

    def tree_flatten_with_keys(self):
        return ((jax.tree_util.DictKey("k"), self["k"]),), \
            (self.width, self.value_width)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def init_latent(n_slots, length, width, value_width, dtype=jnp.float32):
    """One latent ring level (:class:`LatentLevel`), zeroed: rows of
    ``width`` numbers padded to whole lane tiles of 128."""
    padded = -(-int(width) // 128) * 128
    return LatentLevel(
        jnp.zeros((int(n_slots), 1, int(length), padded), dtype),
        width, value_width)


def _latent_row(level, row):
    """``row`` (..., width) as the level stores it: (..., padded)."""
    pad = level["k"].shape[-1] - row.shape[-1]
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)])


def _quant_rows(x, axes):
    """Per-row cache quantization (one scale per written token row) —
    the ONE symmetric-int8 convention, shared with weight quantization
    so the two can never silently diverge."""
    from ..quant.core import quantize_int8_rows
    return quantize_int8_rows(x, axes)


def _dequant_level(level):
    """f32 views of a level's k/v — identity for float caches, payload
    × per-row scale for the quantized ring."""
    k, v = level["k"], level["v"]
    if "k_scale" in level:
        # (W, H, L, D) payload, (W, L) scale -> broadcast over H and D
        k = k.astype(jnp.float32) * level["k_scale"][:, None, :, None]
        v = v.astype(jnp.float32) * level["v_scale"][:, None, :, None]
    return k, v


def ring_positions(pos, length):
    """For newest-written position ``pos`` (vector over slots), the
    token position held at each ring index: ``(W, length)`` int32.
    Negative entries mean "never written"."""
    j = jnp.arange(length, dtype=jnp.int32)
    pos = pos.astype(jnp.int32)[:, None]
    return pos - ((pos - j[None, :]) % length)


def ring_mask(pos, length):
    """``(W, length)`` bool: ring entries holding a real token when the
    newest written position is ``pos`` per slot."""
    return ring_positions(pos, length) >= 0


def write_token(level, k_new, v_new, pos):
    """Write one new token per slot at its ring index.

    ``level``: ``{"k","v"}`` of ``(W, H, L, D)``;
    ``k_new``/``v_new``: ``(W, H, D)``; ``pos``: ``(W,)`` int — the new
    token's position. Returns the updated level. The XLA path of
    :func:`decode_token`: every slot is written here, dead ones too (the
    position mask admits a ring index only once its occupant has written
    it, so what a dead slot's rows hold is unreachable; the kernel path
    does not write them at all). A quantized level additionally writes
    each row's fp32 scale into its per-slot scale row."""
    L = level["k"].shape[2]
    pos = pos.astype(jnp.int32)

    def upd(c, row, p):
        return lax.dynamic_update_slice(
            c, row[:, None, :].astype(c.dtype), (0, p % L, 0))

    if isinstance(level, LatentLevel):
        # the one row a token (``k_new`` (W, width); no ``v_new``)
        return level.with_rows(jax.vmap(upd)(
            level["k"], _latent_row(level, k_new)[:, None], pos))
    if "k_scale" not in level:
        return {"k": jax.vmap(upd)(level["k"], k_new, pos),
                "v": jax.vmap(upd)(level["v"], v_new, pos)}
    # quantized ring: one scale per (slot, ring index), amax over (H,D)
    kq, ks = _quant_rows(k_new, (1, 2))           # (W,H,D) -> (W,)
    vq, vs = _quant_rows(v_new, (1, 2))

    def upd_s(srow, sval, p):
        return lax.dynamic_update_slice(srow, sval[None], (p % L,))

    return {"k": jax.vmap(upd)(level["k"], kq, pos),
            "v": jax.vmap(upd)(level["v"], vq, pos),
            "k_scale": jax.vmap(upd_s)(level["k_scale"], ks, pos),
            "v_scale": jax.vmap(upd_s)(level["v_scale"], vs, pos)}


_XLA_RINGS = threading.local()


@contextlib.contextmanager
def xla_rings():
    """While tracing inside this scope :func:`decode_token` keeps the
    XLA path: the GSPMD-sharded engine enters it, because a Mosaic call
    cannot be partitioned by a sharded jit."""
    prev = getattr(_XLA_RINGS, "on", False)
    _XLA_RINGS.on = True
    try:
        yield
    finally:
        _XLA_RINGS.on = prev


def ring_block(level):
    """Rows of a block of the ring decode kernel for this level, or None
    where the level keeps :func:`write_token` + :func:`attend`: a
    quantized level, a traced scope of :func:`xla_rings`, a backend
    other than the TPU (outside the interpret-mode test hook of
    ``ops/attention.py``), a ring length or head size the kernel has no
    form for (``ops/ring_decode.kernel_block``)."""
    from ..ops import ring_decode as _rd
    if "k_scale" in level or getattr(_XLA_RINGS, "on", False):
        return None
    k = level["k"]
    if k.dtype not in (jnp.bfloat16, jnp.float32) or not _rd.kernels_run():
        return None
    if isinstance(level, LatentLevel):
        return _rd.latent_block(*k.shape[2:])
    return _rd.kernel_block(*k.shape[1:])


def decode_token(level, q, k_new, v_new, pos, active, scale):
    """One decode tick of one ring level: write the new token's
    ``k_new`` / ``v_new`` ``(W, H_kv, D)`` at its ring index and attend
    ``q`` ``(W, H, 1, D)`` over the ring. Returns ``(out (W, H, 1, D),
    level)``. The one entry the models' decode programs call; it sends a
    level the kernel can take (:func:`ring_block`, a test on what it is
    given) through ``ops/ring_decode.py`` — one pass over the blocks
    that hold a token, one tile of the cache written, nothing for a slot
    that is not ``active`` — and any other through :func:`write_token`
    + :func:`attend`.

    A latent level (:class:`LatentLevel`) takes its one new row as
    ``k_new`` ``(W, width)`` with ``v_new`` None, and ``q`` ``(W, H, 1,
    width)``: every query head is scored against the one cached row over
    its whole width, the values are the row's first ``value_width``
    columns, ``out`` is ``(W, H, 1, value_width)`` — the same walk in
    its latent form (``ops/ring_decode.latent_decode``), or the same
    XLA twins."""
    block = ring_block(level)
    if block is None:
        level = write_token(level, k_new, v_new, pos)
        return attend(q, level, pos, scale), level
    from ..ops import ring_decode as _rd
    if isinstance(level, LatentLevel):
        out, rows = _rd.latent_decode(q, k_new, level["k"], pos, active,
                                      scale, block, level.value_width)
        return out, level.with_rows(rows)
    out, k, v = _rd.ring_decode(q, k_new, v_new, level["k"], level["v"],
                                pos, active, scale, block)
    return out, {"k": k, "v": v}


def attend_token(level, q, pos, active, scale):
    """The read-only half of a decode tick: attend ``q`` ``(W, H, 1, D)``
    over a ring level as it stands — ``pos`` the position of its newest
    row, which the level's own :func:`decode_token` has written in this
    tick — and write nothing. What a layer calls that reads another
    layer's keys and values. By the same rule as :func:`decode_token`: a
    level the kernel can take goes through its read-only pass over the
    blocks that hold a token (``ops/ring_decode.ring_attend``), any other
    through :func:`attend`, which scores the whole level. Returns
    ``(W, H, 1, D)``."""
    block = ring_block(level)
    if block is None:
        return attend(q, level, pos, scale)
    from ..ops import ring_decode as _rd
    return _rd.ring_attend(q, level["k"], level["v"], pos, active, scale,
                           block)


def write_prompt(level, slot, k_rows, v_rows, valid):
    """Write one prompt's rows into one slot, starting at ring index 0.

    ``k_rows``/``v_rows``: ``(H, S, D)`` with ``S <= L`` (the engine's
    ``prefill_len <= max_len`` contract); ``slot`` scalar int;
    ``valid`` scalar bool — False rows (prefill-batch padding) leave
    the cache untouched, which is what lets the prefill program keep a
    FIXED batch width over a variable number of admitted requests. A
    quantized level quantizes per token row (scale amax over heads ×
    head_dim) and writes the prompt's scale rows alongside."""
    rows = {"k": k_rows} if v_rows is None else {"k": k_rows, "v": v_rows}
    if "k_scale" in level:
        # (H, S, D): one scale per prompt position -> (S,)
        for name in ("k", "v"):
            rows[name], rows[name + "_scale"] = _quant_rows(rows[name],
                                                            (0, 2))
    out = level.copy()
    for name, new in rows.items():
        old = level[name]
        up = lax.dynamic_update_slice(
            old, new[None].astype(old.dtype), (slot,) + (0,) * new.ndim)
        out[name] = jnp.where(valid, up, old)
    return out


def write_prompts(level, slot_ids, k, v, lengths, valid):
    """A prefill batch's keys and values into their slots' rings.

    ``k``/``v``: ``(B, S, H, D)`` of whole (padded) prompts; ``slot_ids``,
    ``lengths``, ``valid``: ``(B,)``. A prompt no longer than the ring
    lies from index 0 (:func:`write_prompt`); of one that is longer
    (``S > L``: a window layer's ring under a longer prefill) ring index
    ``r`` gets the last prompt row ``t`` with ``t % L == r``, which is
    what token-by-token writing would have left. A latent level
    (:class:`LatentLevel`) takes its one row a token as ``k``
    ``(B, S, width)`` with ``v`` None."""
    L = level["k"].shape[2]
    B, S = k.shape[:2]
    if isinstance(level, LatentLevel):
        # the one row a token, its one "KV head": (B, S, 1, padded)
        k = _latent_row(level, k)[:, :, None]
    kh = k.swapaxes(1, 2)                                # B, H, S, D
    vh = None if v is None else v.swapaxes(1, 2)
    for b in range(B):
        kb, vb = kh[b], None if vh is None else vh[b]
        if S > L:
            r = jnp.arange(L, dtype=jnp.int32)
            last = lengths[b].astype(jnp.int32) - 1
            t = jnp.clip(last - ((last - r) % L), 0, S - 1)
            kb, vb = kb[:, t], None if vb is None else vb[:, t]
        level = write_prompt(level, slot_ids[b], kb, vb, valid[b])
    return level


def attend(q, level, pos, scale):
    """Ring attention for one decode tick.

    ``q``: ``(W, H, 1, D)`` (the new token's query, already written to
    the ring along with its k/v); ``pos``: ``(W,)`` — the new token's
    position. The level may hold fewer heads than ``q`` (grouped KV
    heads: query head ``i`` reads KV head ``i // (H / H_kv)``), and its
    own length (a window layer's ring is ``min(window, max_len)`` long
    beside a full layer's ``max_len``). Softmax in f32 regardless of
    cache dtype (bf16 AND int8 serving keep their numerics sane — a
    quantized ring dequantizes its rows here, payload × per-row scale,
    before the f32 scores), result cast back to ``q.dtype``. Returns
    ``(W, H, 1, D)``."""
    L = level["k"].shape[2]
    if isinstance(level, LatentLevel):
        # every head against the one row a token, over its whole width;
        # the values are the row's first columns
        rows = level["k"][:, 0].astype(jnp.float32)          # W, L, P
        qf = _latent_row(level, q[:, :, 0].astype(jnp.float32))
        s = jnp.einsum("whd,wld->whl", qf, rows) * scale
        s = jnp.where(ring_mask(pos, L)[:, None, :], s, -jnp.inf)
        out = jnp.einsum("whl,wlv->whv", jax.nn.softmax(s, axis=-1),
                         rows[..., :level.value_width])
        return out.astype(q.dtype)[:, :, None]
    kf, vf = _dequant_level(level)
    shape = q.shape
    if shape[1] != kf.shape[1]:
        # grouped heads: the G query heads that read one KV head ride
        # the query axis, so the ring is read once for all of them
        q = q.reshape(shape[0], kf.shape[1], -1, shape[3])
    s = jnp.einsum("whqd,whld->whql", q.astype(jnp.float32),
                   kf.astype(jnp.float32)) * scale
    mask = ring_mask(pos, L)[:, None, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("whql,whld->whqd", a, vf.astype(jnp.float32))
    return out.astype(q.dtype).reshape(shape)


# ---------------------------------------------------------------------------
# paged block pool: device math
# ---------------------------------------------------------------------------

def init_pool(n_blocks, n_heads, block_size, head_dim,
              dtype=jnp.float32):
    """One layer's block pool: zeroed ``{"k","v"}`` of shape
    ``(n_blocks, n_heads, block_size, head_dim)``.

    ``dtype=int8`` builds the QUANTIZED pool: int8 payloads plus one
    fp32 scale per (block, offset) row — ``{"k_scale","v_scale"}`` of
    shape ``(n_blocks, block_size)`` — written alongside every row and
    folded back in inside :func:`gather_pages`. Same per-row symmetric
    convention as the int8 ring (``quant.core.quantize_int8_rows``),
    so the two layouts cannot silently diverge numerically."""
    shape = (int(n_blocks), int(n_heads), int(block_size),
             int(head_dim))
    level = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        # distinct buffers (whole-pool donation, like the int8 ring)
        level["k_scale"] = jnp.ones((int(n_blocks), int(block_size)),
                                    jnp.float32)
        level["v_scale"] = jnp.ones((int(n_blocks), int(block_size)),
                                    jnp.float32)
    return level


def write_rows(level, tables, k_new, v_new, pos, wmask):
    """Write token rows into their block-table-mapped pool rows.

    ``tables``: ``(R, n_pages)`` int32 pool block ids per row (slot);
    ``k_new``/``v_new``: ``(R, H, Q, D)`` fresh rows; ``pos``:
    ``(R, Q)`` absolute token positions; ``wmask``: ``(R, Q)`` bool —
    False rows (batch padding, inactive slots, draft padding) are
    DROPPED via an out-of-bounds scatter index, never written. One
    scatter per tensor, fixed shape for any R/Q."""
    N = level["k"].shape[0]
    bs = level["k"].shape[2]
    pos = pos.astype(jnp.int32)
    page = jnp.take_along_axis(tables.astype(jnp.int32),
                               pos // bs, axis=1)        # (R, Q)
    off = pos % bs
    # masked rows scatter to block id N: out of bounds, mode="drop"
    page = jnp.where(wmask, page, N)
    R, H, Q, D = k_new.shape
    flat = lambda a: a.transpose(0, 2, 1, 3).reshape(R * Q, H, D)  # noqa: E731
    pf, of = page.reshape(-1), off.reshape(-1)
    if "k_scale" not in level:
        k_rows, v_rows = flat(k_new), flat(v_new)
        return dict(
            level,
            k=level["k"].at[pf, :, of, :].set(
                k_rows.astype(level["k"].dtype), mode="drop"),
            v=level["v"].at[pf, :, of, :].set(
                v_rows.astype(level["v"].dtype), mode="drop"))
    from ..quant.core import quantize_int8_rows
    # one scale per (row, token): amax over heads × head_dim
    kq, ks = quantize_int8_rows(k_new, (1, 3))           # scale (R, Q)
    vq, vs = quantize_int8_rows(v_new, (1, 3))
    return dict(
        level,
        k=level["k"].at[pf, :, of, :].set(flat(kq), mode="drop"),
        v=level["v"].at[pf, :, of, :].set(flat(vq), mode="drop"),
        k_scale=level["k_scale"].at[pf, of].set(
            ks.reshape(-1), mode="drop"),
        v_scale=level["v_scale"].at[pf, of].set(
            vs.reshape(-1), mode="drop"))


def gather_pages(level, tables):
    """Materialise each row's logical KV view from its block table:
    ``(R, n_pages)`` table -> f32 ``k, v`` of
    ``(R, H, n_pages*block_size, D)`` with logical index == token
    position. A quantized pool dequantizes here (payload × per-row
    scale) into the caller's f32 softmax. Unallocated table entries
    gather garbage by design — the caller's position mask never admits
    a position beyond the row's allocated span."""
    t = tables.astype(jnp.int32)
    k = jnp.take(level["k"], t, axis=0)     # (R, P, H, bs, D)
    v = jnp.take(level["v"], t, axis=0)
    if "k_scale" in level:
        ks = jnp.take(level["k_scale"], t, axis=0)       # (R, P, bs)
        vs = jnp.take(level["v_scale"], t, axis=0)
        k = k.astype(jnp.float32) * ks[:, :, None, :, None]
        v = v.astype(jnp.float32) * vs[:, :, None, :, None]
    R, P, H, bs, D = k.shape
    k = k.transpose(0, 2, 1, 3, 4).reshape(R, H, P * bs, D)
    v = v.transpose(0, 2, 1, 3, 4).reshape(R, H, P * bs, D)
    return k, v


def attend_pages(q, level, tables, q_pos, scale):
    """Paged causal attention: each query attends every cached position
    ``<= its own`` through the row's block table.

    ``q``: ``(R, H, Q, D)``; ``q_pos``: ``(R, Q)`` absolute query
    positions (the fresh rows are written BEFORE this runs, so a query
    sees itself and everything earlier — exactly full causal
    attention). Softmax in f32 regardless of pool dtype, result cast
    back to ``q.dtype``. Returns ``(R, H, Q, D)``."""
    kf, vf = gather_pages(level, tables)
    L = kf.shape[2]
    s = jnp.einsum("rhqd,rhld->rhql", q.astype(jnp.float32),
                   kf.astype(jnp.float32)) * scale
    mask = jnp.arange(L, dtype=jnp.int32)[None, None, None, :] \
        <= q_pos.astype(jnp.int32)[:, None, :, None]
    s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("rhql,rhld->rhqd", a, vf.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# host-RAM spill tier for evicted cached-prefix blocks
# ---------------------------------------------------------------------------

class HostSpillTier:
    """Byte-budgeted host-RAM tier for evicted cached-prefix KV blocks.

    When pool pressure evicts an unreferenced cached-prefix block
    (:meth:`BlockManager._evict_lru`), its rows are pulled to host and
    parked here as a CRC-sealed frame (:func:`integrity.seal_frame`)
    keyed by the same chained content key the device prefix cache uses.
    A later prefix hit RESTORES the rows into a fresh pool block instead
    of re-prefilling the span — graceful degradation under pressure, not
    recompute. LIVE blocks never reach this tier by construction:
    eviction only ever selects refcount-0 cached blocks.

    The budget is exact: an insert evicts LRU entries until the new
    entry fits, and an entry larger than the whole budget is refused
    outright. A frame that fails its CRC on the way back out is dropped
    (counted in ``drops``) and the caller re-prefills — corrupt rows are
    never restored into the pool."""

    def __init__(self, budget_bytes):
        from collections import OrderedDict
        self.budget_bytes = int(budget_bytes)
        self._entries = OrderedDict()   # key -> (meta, sealed_frame)
        self.bytes_used = 0
        self.drops = 0                  # CRC-failed frames discarded

    def __len__(self):
        return len(self._entries)

    @staticmethod
    def _size(meta, sealed):
        return len(meta) + len(sealed)

    def put(self, key, meta, payload):
        """Seal and store one evicted block's rows. Returns True when
        stored, False when the entry alone exceeds the byte budget."""
        from .. import integrity as _integrity
        meta = bytes(meta)
        sealed = _integrity.seal_frame(meta, payload)
        size = self._size(meta, sealed)
        if size > self.budget_bytes:
            return False
        old = self._entries.pop(key, None)
        if old is not None:
            self.bytes_used -= self._size(*old)
        while self._entries and self.bytes_used + size > self.budget_bytes:
            _k, (m, s) = self._entries.popitem(last=False)
            self.bytes_used -= self._size(m, s)
        self._entries[key] = (meta, sealed)
        self.bytes_used += size
        return True

    def get(self, key):
        """``(meta, payload)`` for a stored key after CRC verification,
        or None (absent, or corrupt — corrupt entries are dropped)."""
        from .. import integrity as _integrity
        entry = self._entries.get(key)
        if entry is None:
            return None
        meta, sealed = entry
        try:
            payload = _integrity.open_frame(meta, sealed)
        except _integrity.IntegrityError:
            self._entries.pop(key, None)
            self.bytes_used -= self._size(meta, sealed)
            self.drops += 1
            return None
        self._entries.move_to_end(key)          # LRU refresh
        return meta, payload


# ---------------------------------------------------------------------------
# paged block pool: host-side manager (allocation, refcounts, prefix cache)
# ---------------------------------------------------------------------------

class SlotAlloc:
    """One admitted sequence's block reservation: the pool block ids
    covering its full ``prompt + max_new_tokens`` span (shared prefix
    blocks first, then private blocks), plus how many prompt tokens the
    prefix-cache hit covers (``shared_tokens`` — prefill skips them)."""

    __slots__ = ("blocks", "shared_tokens", "prompt_blocks")

    def __init__(self, blocks, shared_tokens, prompt_blocks):
        self.blocks = list(blocks)
        self.shared_tokens = int(shared_tokens)
        # how many leading blocks hold FULL prompt content (cacheable
        # on release); the partial tail / generated blocks never cache
        self.prompt_blocks = int(prompt_blocks)


class BlockManager:
    """Host-side block accounting for one engine's pool (single loop
    thread; no locking needed — submit-path callers only read totals).

    Block states: **free** (on the free list), **live** (refcount > 0 —
    NEVER reclaimed), **cached** (refcount 0 but registered in the
    prefix cache — reclaimable, LRU). The prefix cache maps a CHAINED
    content key (this block's tokens + everything before it) to a block
    id, so a hit guarantees the whole preceding context matches — the
    only condition under which cached K/V rows are reusable."""

    def __init__(self, n_blocks, block_size):
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self._ref = [0] * self.n_blocks
        self._key = [None] * self.n_blocks      # prefix-cache key or None
        self._free = list(range(self.n_blocks - 1, -1, -1))
        self._cache = {}                        # chained key -> block id
        self._lru = {}                          # block id -> stamp
        self._tick = 0
        # host-RAM spill tier (attach_spill): evicted cached prefixes
        # park here instead of vanishing
        self._spill = None
        self._spill_read = None
        self._spill_write = None
        self._on_spill = None
        self._on_restore = None
        self.spilled_total = 0
        self.restored_total = 0

    def attach_spill(self, tier, reader, writer,
                     on_spill=None, on_restore=None):
        """Arm the host-RAM spill tier. The manager has no device
        access, so the engine supplies ``reader(bid) -> (meta, bytes)``
        (pull one pool block's rows to host) and
        ``writer(bid, meta, payload)`` (push them back). ``on_spill`` /
        ``on_restore`` are metric hooks called once per block moved."""
        self._spill = tier
        self._spill_read = reader
        self._spill_write = writer
        self._on_spill = on_spill
        self._on_restore = on_restore

    # -- introspection (gauges, tests) -------------------------------------
    def blocks_live(self):
        return sum(1 for r in self._ref if r > 0)

    def blocks_cached(self):
        return sum(1 for i, r in enumerate(self._ref)
                   if r == 0 and self._key[i] is not None)

    def blocks_free(self):
        return len(self._free)

    def n_for(self, n_tokens):
        """Blocks covering ``n_tokens`` positions."""
        return -(-int(n_tokens) // self.block_size)

    # -- prefix cache -------------------------------------------------------
    def _chain_keys(self, prompt):
        """Chained content keys for each FULL block of ``prompt``."""
        return chain_keys(prompt, self.block_size)

    def match_prefix(self, prompt):
        """Longest cached full-block prefix of ``prompt``, capped one
        token short of the whole prompt (the last token must be
        prefilled so its logits exist). Returns
        ``(block_ids, n_tokens)`` WITHOUT taking references —
        :meth:`admit` re-matches and takes them atomically."""
        cap = (len(prompt) - 1) // self.block_size
        ids = []
        for key in self._chain_keys(prompt)[:cap]:
            bid = self._cache.get(key)
            if bid is None:
                break
            ids.append(bid)
        return ids, len(ids) * self.block_size

    # -- allocation ---------------------------------------------------------
    def _reclaimable(self, shared):
        """Free + cached blocks available to a request whose prefix hit
        covers ``shared`` (those are about to become live — they must
        not be counted as evictable fuel for the same admission)."""
        keep = set(shared)
        cached = sum(1 for i, r in enumerate(self._ref)
                     if r == 0 and self._key[i] is not None
                     and i not in keep)
        return len(self._free) + cached

    def admit(self, prompt, total_tokens):
        """Reserve every block the sequence can ever touch (positions
        ``[0, total_tokens)`` — decode can then never stall or corrupt
        a neighbour mid-flight). Shared prefix blocks are re-referenced
        FIRST (so LRU reclaim can never eat the prefix being shared);
        the rest come from the free list, reclaiming LRU cached blocks
        when it runs dry. Raises
        :class:`~singa_tpu.serving.scheduler.BlockPoolExhausted` when
        the pool cannot cover it without touching a live block."""
        shared, shared_tokens = self.match_prefix(prompt)
        need = self.n_for(total_tokens) - len(shared)
        if need > self._reclaimable(shared):
            live = self.blocks_live()
            raise BlockPoolExhausted(
                f"block pool exhausted: need {need} free blocks for a "
                f"{total_tokens}-token reservation ({len(shared)} "
                f"shared), have {len(self._free)} free + "
                f"{self.blocks_cached()} reclaimable cached "
                f"({live} live blocks are never evicted; pool is "
                f"{self.n_blocks} × {self.block_size} tokens)")
        self._tick += 1
        for bid in shared:
            self._ref[bid] += 1
            self._lru[bid] = self._tick
        fresh = [self._take_free() for _ in range(need)]
        shared_tokens += self._restore_spilled(prompt, shared, fresh)
        return SlotAlloc(shared + fresh, shared_tokens,
                         len(prompt) // self.block_size)

    def _restore_spilled(self, prompt, shared, fresh):
        """Continue the prefix chain past the device-cache hit against
        the spill tier: each consecutive hit restores its rows into the
        next fresh block (which then re-enters the prefix cache under
        its chained key) and extends the shared span — the tokens it
        covers skip prefill. Returns extra shared tokens. Restored
        blocks come out of the SAME ``fresh`` reservation, so admission
        accounting (``_reclaimable``) is unchanged."""
        if self._spill is None or self._spill_write is None or not fresh:
            return 0
        keys = self._chain_keys(prompt)
        cap = (len(prompt) - 1) // self.block_size   # match_prefix cap
        restored = 0
        for j in range(len(shared), cap):
            if restored >= len(fresh):
                break
            hit = self._spill.get(keys[j])
            if hit is None:
                break
            meta, payload = hit
            bid = fresh[restored]
            try:
                self._spill_write(bid, meta, payload)
            except Exception:
                break       # degrade to re-prefilling the span
            if keys[j] not in self._cache:
                self._key[bid] = keys[j]
                self._cache[keys[j]] = bid
            self._lru[bid] = self._tick
            restored += 1
            self.restored_total += 1
            if self._on_restore is not None:
                self._on_restore()
        return restored * self.block_size

    def _take_free(self):
        if not self._free:
            self._evict_lru()
        bid = self._free.pop()
        self._ref[bid] = 1
        return bid

    def _evict_lru(self):
        """Reclaim the least-recently-used CACHED block (refcount 0).
        Callers guarantee one exists (``admit`` checked). With a
        spill tier attached the victim's rows move to host RAM first —
        only cached-prefix blocks ever reach this point, so a LIVE
        block can never be spilled."""
        victim = min(
            (i for i in range(self.n_blocks)
             if self._ref[i] == 0 and self._key[i] is not None),
            key=lambda i: self._lru.get(i, 0))
        if self._spill is not None and self._spill_read is not None:
            try:
                meta, payload = self._spill_read(victim)
                if self._spill.put(self._key[victim], meta, payload):
                    self.spilled_total += 1
                    if self._on_spill is not None:
                        self._on_spill()
            except Exception:
                pass        # spilling is best-effort; eviction is not
        del self._cache[self._key[victim]]
        self._key[victim] = None
        self._lru.pop(victim, None)
        self._free.append(victim)

    def release(self, alloc, prompt):
        """Drop a finished/failed sequence's references. Its FULL
        prompt blocks enter the prefix cache (refcount 0, reclaimable)
        so the next identical prompt skips their prefill; partial-tail
        and generated-token blocks free immediately."""
        keys = self._chain_keys(prompt)
        self._tick += 1
        for i, bid in enumerate(alloc.blocks):
            self._ref[bid] -= 1
            if i < alloc.prompt_blocks and self._key[bid] is None \
                    and keys[i] not in self._cache:
                self._key[bid] = keys[i]
                self._cache[keys[i]] = bid
                self._lru[bid] = self._tick
            if self._ref[bid] == 0 and self._key[bid] is None:
                self._free.append(bid)


# ---------------------------------------------------------------------------
# the host half of each layout: everything ServingEngine asks of a KV format
# ---------------------------------------------------------------------------
#
# The engine owns the slot table, the queue, sampling, spans and the device
# state itself (``engine._cache``); a layout owns how that state is built,
# which of the adapter's programs run on it, what a request reserves, how a
# tick's host arrays are packed, and how one slot's rows leave and enter it.
# Both classes below answer the same calls; :func:`pick_layout` is the one
# place that reads the ``kv_layout`` string.

# KV level arrays in their ONE canonical serialization order: every
# snapshot/spill frame packs present keys in this order, so the bytes
# on both sides of a handoff agree by construction.
LEVEL_KEYS = ("k", "v", "k_scale", "v_scale")


def _level_names(level):
    """A level's arrays in serialization order: the ``LEVEL_KEYS`` it
    has, then whatever else it holds (a state level's arrays, whose
    names are the adapter's) by name."""
    return [n for n in LEVEL_KEYS if n in level] \
        + sorted(n for n in level if n not in LEVEL_KEYS)


def _rows_to_host(state, index):
    """Every level's arrays at ``index`` of their first axis (a ring's
    or a state's slot, a pool's block or blocks), on the host, in
    :func:`_level_names` order."""
    return [np.asarray(level[name][index]) for level in state
            for name in _level_names(level)]


def _rows_from_host(state, arrays, index, what, lead=None, skip=0):
    """The inverse: a new state with ``arrays`` written at ``index``
    (host-side ``.at[].set`` OUTSIDE the two compiled programs: no
    retrace, and the fresh buffers are donated on the next tick like
    any other). ``lead=None``: one array a level key, shaped like one
    entry of the level (a slot, a block). ``lead=n``: each array holds
    ``n`` such entries (a slot's blocks), the first ``skip`` of which
    stay as they are and the rest go to ``index``. A shape that is not
    the level's is refused, never written."""
    it = iter(arrays)
    new_state = []
    for level in state:
        upd = level.copy()
        for name in _level_names(level):
            arr = next(it)
            want = tuple(level[name].shape[1:])
            if lead is not None:
                want = (lead,) + want
            if tuple(arr.shape) != want:
                raise HandoffRefused(
                    f"{what} array {name} shape {tuple(arr.shape)} does "
                    f"not match this engine's {want}")
            if lead is not None:
                arr = arr[skip:]
                if not len(arr):
                    continue
            upd[name] = level[name].at[index].set(jnp.asarray(arr))
        new_state.append(upd)
    return new_state


def with_tokens(program, keep_logits=True):
    """``program`` with the token chosen where the logits are. An
    adapter's serve program returns ``(state, logits)``, or ``(state,
    (logits, stats))`` where it counts (``stats_recorder``); this
    returns ``(state, (tokens, logits[, stats]))`` with ``tokens =
    argmax(logits, -1)`` as int32 — ``(rows,)``, or ``(W, K)`` for the
    paged verify program. Ties go to the lowest id, as ``np.argmax``
    on the host sends them, so a greedy row needs nothing but its
    token and the ``(rows, V)`` logits stay on the device unless a
    request samples. ``keep_logits=False`` drops them from the
    outputs (the sharded engine: XLA combines the shards' partial
    argmaxes and the full-vocab array is never gathered)."""

    def fn(*args):
        state, out = program(*args)
        logits, *stats = out if isinstance(out, tuple) else (out,)
        tokens = jnp.argmax(logits, -1).astype(jnp.int32)
        kept = (logits,) if keep_logits else ()
        return state, (tokens, *kept, *stats)

    return fn


class KVLayout:
    """What both layouts share, and what a format that reserves nothing
    answers: no pool, no reservation, nothing that can fail to fit. A
    layout fills in ``name``, the programs' argument names (for the
    compile/retrace events), ``init_state``, ``pack_prefill``,
    ``pack_decode``, ``geometry``, ``info``, ``read_slot`` and
    ``write_slot``; one whose decode takes an input from the last call
    keeps it through ``bind_device`` and ``decoded``."""

    # the attributes ServingEngine republishes (None: no pool here)
    mgr = block_size = n_blocks = max_blocks = spill_tier = None
    spec_width = 1
    admit = None            # pop_batch predicate: nothing to reserve
    candidate_axis = False  # decode returns (W, ...), one row a slot

    def __init__(self, adapter, registry, *, slots, max_len, prefill_len,
                 prefill_batch):
        self.adapter = adapter
        self._reg = registry
        self.slots, self.max_len = slots, max_len
        self.prefill_len, self.prefill_batch = prefill_len, prefill_batch

    def programs(self, sharded):
        """``(prefill, decode)``: the adapter's two programs, each
        returning its rows' tokens beside its logits
        (:func:`with_tokens`). Sharded, the logits are dropped: they
        are vocab-sharded, and an output would gather them."""
        return tuple(with_tokens(getattr(self.adapter, fn)(), not sharded)
                     for fn in self._programs)

    def outputs(self, sharded):
        """The form of the programs' ``out``, as AOT export stamps it
        on an artifact: ``tokens[+logits][+stats]``."""
        parts = ["tokens"]
        if not sharded:
            parts.append("logits")
        if hasattr(self.adapter, "stats_recorder"):
            parts.append("stats")
        return "+".join(parts)

    def bind_device(self, put):
        """``put(array)``: how the engine places an array on the device
        beside its programs' arguments, once at build (nothing kept
        here)."""

    def decoded(self, out):
        """A decode call's ``out`` as dispatched (nothing kept here)."""

    def never_fits(self, n_prompt, max_new):
        """The typed error for a request no state of the layout could
        take, or None."""
        return None

    def reserve(self, prompt, max_new):
        """What a request holds beside its slot while it runs (the
        ``alloc`` of its slot; a layout that hands one out takes it
        back through ``release(alloc, prompt, cache=True)``)."""
        return None


class RingLayout(KVLayout):
    """One slot's share of every level (``adapter.init_cache``): a free
    slot is a free row of each and generation past ``max_len`` slides a
    ring's window, so the defaults above hold; a decode tick carries one
    token a slot. A cache is a list of levels, each a ring ``{"k","v"}``,
    a latent ring (:class:`LatentLevel`: one array a token under ``"k"``,
    one KV head, counted, snapshot and handed off as a ring) or a state —
    any other dict of arrays with the slot first: what a recurrent layer
    keeps of a sequence, of a fixed size whatever its length. Everything here derives from that list, not from the model:
    levels may be fewer than layers (layers that read another layer's
    ring own none), and the adapter may say what each level is
    (``cache_kinds()``: ``"window"`` | ``"full"`` | ``"latent"`` |
    ``"state"``) and how
    many layers read it each tick (``cache_readers()``). A recurrent
    adapter whose state is not such a list rides the layout opaquely
    (``lengths`` stays None, no gauges, no snapshots of it)."""

    name = "ring"
    prefill_names = ("tokens", "lengths", "slot_ids", "valid")
    decode_names = ("prev_tokens", "tokens", "fresh", "positions", "active")
    _programs = ("prefill_fn", "decode_fn")
    lengths = None
    n_state = 0
    _latent = ()
    _prefill_rows = None

    def programs(self, sharded):
        """The adapter's two programs (:meth:`KVLayout.programs`), decode
        taking its input tokens from two places: ``tokens`` from the host
        where ``fresh`` is set, else ``prev_tokens``, the tokens the last
        decode call put first, which stay on the device. So a tick can be
        dispatched before the one it follows has been read, through the
        one executable."""
        prefill, decode = super().programs(sharded)

        def decode_chained(P, state, prev_tokens, tokens, fresh, *rest):
            return decode(P, state, jnp.where(fresh, tokens, prev_tokens),
                          *rest)

        return prefill, decode_chained

    def bind_device(self, put):
        """``prev_tokens`` before the first decode call: zeros, placed
        as the calls place their tokens, so every call passes one kind
        of array there."""
        self._prev_tokens = put(np.zeros((self.slots,), np.int32))

    def decoded(self, out):
        """Keep the call's tokens, on the device, for the next call's
        ``prev_tokens``."""
        self._prev_tokens = out[0]

    def init_state(self):
        state = self.adapter.init_cache(self.slots, self.max_len)
        if hasattr(self.adapter, "prefill_rows"):
            self._prefill_rows = self._reg.counter(
                "serve_prefill_rows_total", "rows of prefill batches "
                "each of the adapter's decoders ran (an adapter whose "
                "later layers run a prompt's last token alone)",
                labels=("decoder",))
        if not (isinstance(state, list) and all(
                isinstance(lv, dict) for lv in state) and any(
                "k" in lv for lv in state)):
            return state
        rings = [lv for lv in state if "k" in lv]
        # what the levels hold, by kind: an adapter whose layers keep
        # rings of different lengths, or states beside them, names each
        # level's kind (``cache_kinds``); one geometry reads as "full"
        kinds = getattr(self.adapter, "cache_kinds", None)
        kinds = kinds() if kinds is not None else \
            ["latent" if isinstance(lv, LatentLevel) else
             "full" if "k" in lv else "state" for lv in state]
        kv_bytes = self._reg.gauge(
            "serve_kv_bytes", "bytes of per-slot serving state, by kind "
            "of level (window: rings of min(window, max_len) positions "
            "a slot; full: of max_len; latent: rings of one array a "
            "token; state: what recurrent layers keep, of a fixed size "
            "a slot)", labels=("kind",))
        for kind in sorted(set(kinds)):
            kv_bytes.set(sum(
                int(a.size) * a.dtype.itemsize
                for k, level in zip(kinds, state) if k == kind
                for a in level.values()), kind=kind)
        self.lengths = np.asarray([int(lv["k"].shape[2]) for lv in rings])
        # a ring that no block divides is walked whole
        from ..ops.ring_decode import block_rows
        self._blocks = np.asarray(
            [block_rows(n) or n for n in self.lengths])
        # layers that read each ring a tick (a layer that attends to
        # another layer's keys and values reads that layer's ring again)
        readers = getattr(self.adapter, "cache_readers", None)
        readers = readers() if readers is not None else [1] * len(state)
        self._readers = np.asarray(
            [int(n) for n, lv in zip(readers, state) if "k" in lv])
        self._state_shapes = [
            [name, [int(d) for d in lv[name].shape[1:]],
             str(lv[name].dtype)]
            for lv in state if "k" not in lv for name in _level_names(lv)]
        self.n_state = len(state) - len(rings)
        # a latent level's two widths (its shape says only the padding)
        self._latent = [[i, lv.width, lv.value_width]
                        for i, lv in enumerate(state)
                        if isinstance(lv, LatentLevel)]
        self._kv_rows = self._reg.counter(
            "serve_kv_rows_attended_total", "ring rows holding a token "
            "that decode ticks attended to, summed over the layers that "
            "read them and active slots (what a tick has to read of "
            "the rings)")
        self._kv_blocks = self._reg.counter(
            "serve_kv_blocks_walked_total", "blocks of the rings "
            "holding a token, as the ring decode kernel cuts them, "
            "summed over the layers that read them and active slots "
            "(against slots x blocks a ring: the share of the whole "
            "walk)")
        if self.n_state:
            self._state_steps = self._reg.counter(
                "serve_state_steps_total", "recurrent states a decode "
                "tick stepped: active slots x state levels")
        return state

    def pack_prefill(self, batch, free, attrs):
        """``(program arrays, [(request, slot, alloc)], prompt tokens
        the program runs)`` for one admitted batch. An adapter whose
        layers do not all run every row of a prompt says what each of
        its decoders runs (``prefill_rows(lengths) -> {decoder:
        rows}``): that goes on the span (``attrs``, as
        ``<decoder>_rows``) and into ``serve_prefill_rows_total``."""
        B, S = self.prefill_batch, self.prefill_len
        tokens = np.zeros((B, S), np.int32)
        lengths = np.zeros((B,), np.int32)
        slot_ids = np.zeros((B,), np.int32)
        valid = np.zeros((B,), bool)
        placed = []
        for b, req in enumerate(batch):
            n = req.prompt.size
            tokens[b, :n] = req.prompt
            lengths[b] = n
            slot_ids[b] = free[b]
            valid[b] = True
            placed.append((req, free[b], None))
        # what causal attention's work goes with: the sum of the
        # prompts' squared lengths
        attrs["tokens_sq"] = int(np.sum(lengths.astype(np.int64) ** 2))
        if self._prefill_rows is not None:
            for decoder, n in self.adapter.prefill_rows(
                    lengths[valid]).items():
                attrs[f"{decoder}_rows"] = n
                self._prefill_rows.inc(n, decoder=decoder)
        return (tokens, lengths, slot_ids, valid), placed, \
            int(lengths.sum())

    def pack_decode(self, slots, attrs, draft, chained=None):
        """``(program arrays, None)``: one pending token a live slot, no
        candidate rows. A slot whose request ``chained``
        (slot -> request) names had a row in the call still in flight
        takes that call's token, one position on; every other row is
        ``fresh``. What the tick has to read of the rings goes on the
        span (``attrs``) and the two counters."""
        W = self.slots
        tokens = np.zeros((W,), np.int32)
        fresh = np.ones((W,), bool)
        positions = np.zeros((W,), np.int32)
        active = np.zeros((W,), bool)
        for i, slot in enumerate(slots):
            if slot is None:
                continue
            active[i] = True
            if chained and chained.get(i) is slot["req"]:
                fresh[i] = False
                positions[i] = slot["pos"] + 1
            else:
                tokens[i] = slot["tok"]
                positions[i] = slot["pos"]
        if self.lengths is not None:
            rows = np.minimum(positions[active, None] + 1, self.lengths)
            attrs["kv_rows"] = int((rows * self._readers).sum())
            attrs["kv_blocks"] = int(
                (-(-rows // self._blocks) * self._readers).sum())
            self._kv_rows.inc(attrs["kv_rows"])
            self._kv_blocks.inc(attrs["kv_blocks"])
            if self.n_state:
                attrs["state_slots"] = int(active.sum()) * self.n_state
                self._state_steps.inc(attrs["state_slots"])
        return (self._prev_tokens, tokens, fresh, positions, active), None

    def geometry(self):
        g = {"layout": self.name}
        if self.lengths is not None and \
                any(n != self.max_len for n in self.lengths):
            # layers with rings of their own length (window layers)
            g["ring_lengths"] = [int(n) for n in self.lengths]
        if self.n_state:
            # what each state level keeps of a slot: name, shape, dtype
            g["state"] = self._state_shapes
        if self.lengths is not None and self._latent:
            # which levels are latent: index, row width, value width
            g["latent"] = self._latent
        return g

    def info(self, part):
        return {} if part is None else \
            {"slots_per_device": self.slots // part.batch_shards}

    def read_slot(self, state, slot_idx, alloc):
        return _rows_to_host(state, slot_idx)

    def write_slot(self, state, arrays, slot_idx, alloc):
        return _rows_from_host(state, arrays, slot_idx, "snapshot")


class PagedLayout(KVLayout):
    """One block pool a level (``adapter.init_pool``) under a
    :class:`BlockManager`: a request reserves the blocks of its whole
    ``prompt + max_new_tokens`` span before it is popped, a prefix-cache
    hit enters prefill with ``start > 0`` and only its SUFFIX tokens,
    and a decode tick carries a row of up to ``spec_width`` candidates a
    slot (the pending token plus n-gram drafts) for the verify program.
    Rejected drafts leave stale rows past the new position; the
    position-exact mask keeps them unreachable until overwritten."""

    name = "paged"
    candidate_axis = True   # decode returns (W, K, ...)
    prefill_names = ("tables", "tokens", "starts", "lengths", "valid")
    decode_names = ("tables", "tokens", "positions", "counts")
    _programs = ("paged_prefill_fn", "paged_decode_fn")

    def __init__(self, adapter, registry, *, block_size, n_blocks,
                 spec_width, spill_bytes, **size):
        super().__init__(adapter, registry, **size)
        self.block_size = int(block_size)
        if self.block_size < 1:
            raise ValueError(
                f"kv_block_size must be >= 1, got {block_size}")
        self.max_blocks = -(-self.max_len // self.block_size)
        # default pool covers slots × max_len (no saving, full safety);
        # a smaller kv_blocks is where paged memory elasticity lives —
        # admission backpressure keeps it safe
        self.n_blocks = int(n_blocks) if n_blocks \
            else self.slots * self.max_blocks
        if self.n_blocks < 1:
            raise ValueError(f"kv_blocks must be >= 1, got {n_blocks}")
        self.spec_width = spec_width
        self.mgr = BlockManager(self.n_blocks, self.block_size)
        # pool-pressure gauges: what /metrics.json and the heartbeat
        # fleet view read to see a replica running out of KV blocks
        # before requests start backing up
        reg = self._reg
        reg.gauge("kv_blocks_total",
                  "paged KV pool size in blocks").set(self.n_blocks)
        self._in_use = reg.gauge(
            "kv_blocks_in_use",
            "pool blocks referenced by live sequences (never evicted)")
        self._cached = reg.gauge(
            "kv_blocks_cached",
            "unreferenced blocks held by the prefix cache "
            "(reclaimable, LRU)")
        self._prefix_hits = reg.counter(
            "prefix_cache_hits_total",
            "admitted prompts whose prefix matched cached blocks "
            "(prefill skipped for the shared span)")
        self._prefix_tokens = reg.counter(
            "prefix_cache_tokens_total",
            "prompt tokens served from cached prefix blocks "
            "instead of prefill compute")
        self._proposed = reg.counter(
            "speculative_proposed_total",
            "draft tokens proposed to the verify program")
        self._accepted = reg.counter(
            "speculative_accepted_total",
            "draft tokens accepted by the greedy verify rule")
        self._ratio = reg.gauge(
            "speculative_accepted_ratio",
            "cumulative accepted/proposed draft-token ratio (the "
            "speculative speedup is roughly 1 + ratio × (k-1))")
        self.spill_tier = HostSpillTier(spill_bytes) \
            if spill_bytes else None

    def init_state(self):
        return self.adapter.init_pool(self.n_blocks, self.block_size)

    def attach_spill(self, reader, writer):
        """Arm the spill tier with the engine's block reader and writer
        (the manager and this layout hold no device state)."""
        tier, reg = self.spill_tier, self._reg
        spilled = reg.counter(
            "serve_kv_spill_total",
            "cached-prefix blocks spilled to the host-RAM "
            "tier on pool eviction")
        restored = reg.counter(
            "serve_kv_restore_total",
            "prefix blocks restored from the host-RAM tier "
            "instead of being re-prefilled")
        held = reg.gauge(
            "serve_kv_spill_bytes",
            "bytes the host-RAM spill tier currently holds "
            f"(budget {tier.budget_bytes})")

        def moved(counter):
            counter.inc()
            held.set(tier.bytes_used)

        self.mgr.attach_spill(
            tier, reader, writer, on_spill=lambda: moved(spilled),
            on_restore=lambda: moved(restored))

    # -- what a request needs ----------------------------------------------
    def never_fits(self, n_prompt, max_new):
        total = n_prompt + max_new
        if total > self.max_len:
            return ServingError(
                f"prompt ({n_prompt}) + max_new_tokens ({max_new}) = "
                f"{total} exceeds max_len {self.max_len}: the paged "
                "layout is exact full attention within max_len (no "
                "logical slot exists past it) — raise max_len, or use "
                "the ring layout for sliding-window generation")
        if self.mgr.n_for(total) > self.n_blocks:
            return BlockPoolExhausted(
                f"request needs {self.mgr.n_for(total)} KV blocks but "
                f"the whole pool is {self.n_blocks} (× {self.block_size} "
                "tokens): it can NEVER be admitted — raise kv_blocks or "
                "lower max_new_tokens")
        return None

    def reserve(self, prompt, max_new):
        """The request's :class:`SlotAlloc`, or ``BlockPoolExhausted``
        (backpressure: the caller tries again next tick)."""
        alloc = self.mgr.admit(prompt, int(prompt.size) + max_new)
        self._update_gauges()
        return alloc

    def admit(self, req):
        """The ``pop_batch`` predicate: RESERVES the request's blocks
        (prefix-shared ones re-referenced) so a batch can never
        over-commit the pool; a request that does not fit right now
        stays at the head of the queue (FIFO-fair — live sequences are
        never evicted to make room)."""
        try:
            req._alloc = self.mgr.admit(
                req.prompt, int(req.prompt.size) + req.max_new_tokens)
            return True
        except BlockPoolExhausted:
            return False

    def release(self, alloc, prompt, cache=True):
        """Return a sequence's block references (its full prompt blocks
        enter the prefix cache unless ``cache`` is False: blocks a
        failed write left half-filled must never be shared)."""
        if not cache:
            alloc = SlotAlloc(alloc.blocks, alloc.shared_tokens, 0)
        self.mgr.release(alloc, prompt)
        self._update_gauges()

    def _update_gauges(self):
        self._in_use.set(self.mgr.blocks_live())
        self._cached.set(self.mgr.blocks_cached())

    # -- packing -------------------------------------------------------------
    def pack_prefill(self, batch, free, attrs):
        """As :meth:`RingLayout.pack_prefill`; each popped request
        arrives with its reservation taken (``req._alloc``), and the
        pool gauges follow the batch's, once."""
        B, S = self.prefill_batch, self.prefill_len
        tokens = np.zeros((B, S), np.int32)
        starts = np.zeros((B,), np.int32)
        lengths = np.zeros((B,), np.int32)
        tables = np.zeros((B, self.max_blocks), np.int32)
        valid = np.zeros((B,), bool)
        placed = []
        for b, req in enumerate(batch):
            alloc = req._alloc
            suffix = req.prompt[alloc.shared_tokens:]
            tokens[b, :suffix.size] = suffix
            starts[b] = alloc.shared_tokens
            lengths[b] = suffix.size
            tables[b, :len(alloc.blocks)] = alloc.blocks
            valid[b] = True
            placed.append((req, free[b], alloc))
            if alloc.shared_tokens:
                self._prefix_hits.inc()
                self._prefix_tokens.inc(alloc.shared_tokens)
        self._update_gauges()
        return (tables, tokens, starts, lengths, valid), placed, \
            int(lengths.sum())

    def pack_decode(self, slots, attrs, draft, chained=None):
        """``(program arrays, rows)``: ``rows`` is None at width 1;
        under speculation it maps a slot to its candidate row, the
        pending token plus up to ``spec_width - 1`` n-gram drafts
        (none while ``draft`` is off). A verify tick is never dispatched
        before the one it follows is read, so ``chained`` is empty."""
        W, K = self.slots, self.spec_width
        tokens = np.zeros((W, K), np.int32)
        positions = np.zeros((W,), np.int32)
        counts = np.zeros((W,), np.int32)
        tables = np.zeros((W, self.max_blocks), np.int32)
        rows = {} if K > 1 and draft else None
        for i, slot in enumerate(slots):
            if slot is None:
                continue
            tokens[i, 0] = slot["tok"]
            positions[i] = slot["pos"]
            counts[i] = 1
            blocks = slot["alloc"].blocks
            tables[i, :len(blocks)] = blocks
            req = slot["req"]
            if rows is None or req.temperature != 0:
                # greedy-only: the accept rule is exact for argmax; a
                # sampled request decodes one token per tick (its
                # per-request rng draw order must not change)
                continue
            n = min(K, req.max_new_tokens - len(req.tokens),
                    self.max_len - slot["pos"])
            if n > 1:
                row = [slot["tok"]] + _decode.ngram_propose(
                    list(req.prompt) + req.tokens, n - 1)
                self._proposed.inc(n - 1)
                tokens[i, :len(row)] = row
                counts[i] = len(row)
                rows[i] = row
        return (tables, tokens, positions, counts), rows

    def note_accepted(self, n):
        """``n`` drafts of one slot's row passed the verify rule."""
        self._accepted.inc(n)
        proposed = self._proposed.total()
        if proposed:
            self._ratio.set(self._accepted.total() / proposed)

    # -- hand-off ------------------------------------------------------------
    def geometry(self):
        return {"layout": self.name, "block_size": self.block_size}

    def info(self, part):
        info = {"kv_block_size": self.block_size,
                "kv_blocks": self.n_blocks,
                "kv_blocks_in_use": self.mgr.blocks_live(),
                "kv_blocks_cached": self.mgr.blocks_cached(),
                "prefix_cache_entries": len(self.mgr._cache)}
        if self.spill_tier is not None:
            info["spill"] = {
                "budget_bytes": self.spill_tier.budget_bytes,
                "bytes_used": self.spill_tier.bytes_used,
                "entries": len(self.spill_tier),
                "spilled_total": self.mgr.spilled_total,
                "restored_total": self.mgr.restored_total}
        return info

    def read_slot(self, state, slot_idx, alloc):
        """The slot's blocks, in block-table order."""
        return _rows_to_host(state, np.asarray(alloc.blocks, np.int32))

    def write_slot(self, state, arrays, slot_idx, alloc):
        """A snapshot's blocks into ``alloc``'s; the leading blocks a
        prefix-cache hit or a spill restore already covers are skipped
        (same positions, bitwise-identical content under greedy
        determinism)."""
        skip = alloc.shared_tokens // self.block_size
        return _rows_from_host(
            state, arrays, jnp.asarray(alloc.blocks[skip:], jnp.int32),
            "snapshot", lead=len(alloc.blocks), skip=skip)

    def read_block(self, state, bid):
        return _rows_to_host(state, int(bid))

    def write_block(self, state, bid, arrays):
        return _rows_from_host(state, arrays, int(bid), "spilled block")


def pick_layout(kv_layout, adapter, registry, *, slots, max_len,
                prefill_len, prefill_batch, kv_block_size, kv_blocks,
                speculative_k, spill_bytes, sharded):
    """The layout an engine runs on, from what was asked for and what
    the adapter and the mesh can honour. What cannot be honoured
    declines LOUDLY (a warning, and the reason under ``declined`` for
    ``compiled_step_info``), never silently."""
    kv_layout = str(kv_layout)
    if kv_layout not in ("ring", "paged"):
        raise ValueError(
            f"kv_layout must be 'ring' or 'paged', got {kv_layout!r}")
    declined = {}
    paged = kv_layout == "paged"
    # a level that is no ring (``cache_kinds``: "state") is what a
    # recurrent layer keeps of a sequence: no row a position for a pool
    # to page or share, and nothing a rejected draft could be rolled
    # back to
    kinds = getattr(adapter, "cache_kinds", None)
    kinds = kinds() if kinds is not None else ()
    recurrent = "state" in kinds
    # a latent level is one array a token: the pool's pages are
    # ``{"k","v"}`` pairs of one head size, which it is not
    latent = "latent" in kinds
    if paged and (recurrent or latent
                  or not getattr(adapter, "supports_paged", False)):
        warnings.warn(
            f"kv_layout='paged' declined: {type(adapter).__name__} has "
            "no paged block-pool programs (its decode state is not "
            "per-position KV rows); serving on the ring layout instead",
            stacklevel=4)
        declined["kv_layout_declined"] = "recurrent_state" if recurrent \
            else "latent_level" if latent else "adapter_unsupported"
        paged = False
    # speculative_k = verify-program width: up to speculative_k tokens
    # emitted per tick (speculative_k - 1 of them drafted). It needs the
    # paged mask's position-exactness — a wrapped ring re-attributes a
    # rejected draft's stale row INTO the sliding window (pos+1 wraps to
    # pos-L+1), so the ring declines rather than risking silent
    # corruption.
    spec = int(speculative_k or 0)
    if spec > 1 and not paged:
        warnings.warn(
            "speculative_k declined: speculative decoding needs "
            "kv_layout='paged' (the ring's wraparound would "
            "re-attribute rejected-draft rows into the attention "
            "window" + ("; a recurrent state stepped over a rejected "
                        "draft cannot be rolled back" if recurrent else "")
            + "); decoding one token per tick", stacklevel=4)
        declined["speculative_declined"] = "recurrent_state" if recurrent \
            else "latent_level" if latent else "requires_paged_layout"
    spill = int(spill_bytes or 0)
    if spill > 0 and not paged:
        warnings.warn(
            "spill_bytes declined: the host-RAM spill tier parks "
            "evicted cached-prefix BLOCKS, which only the paged layout "
            "has", stacklevel=4)
        declined["spill_declined"] = "requires_paged_layout"
    elif spill > 0 and sharded:
        warnings.warn(
            "spill_bytes declined: a sharded pool's blocks are sliced "
            "over the mesh ('model' axis) — a host spill/restore would "
            "need per-device gathers; serve single-device to spill",
            stacklevel=4)
        declined["spill_declined"] = "sharded"
        spill = 0
    size = dict(slots=slots, max_len=max_len, prefill_len=prefill_len,
                prefill_batch=prefill_batch)
    if paged:
        layout = PagedLayout(
            adapter, registry, block_size=kv_block_size,
            n_blocks=kv_blocks, spec_width=max(1, spec),
            spill_bytes=spill, **size)
    else:
        layout = RingLayout(adapter, registry, **size)
    layout.declined = declined
    return layout


__all__ = ["init_cache", "init_latent", "LatentLevel", "ring_positions", "ring_mask", "write_token",
           "write_prompt", "write_prompts", "attend", "decode_token",
           "attend_token",
           "ring_block",
           "xla_rings", "init_pool", "write_rows",
           "gather_pages", "attend_pages", "SlotAlloc", "BlockManager",
           "HostSpillTier", "chain_keys", "prefix_chain_key",
           "affinity_hash", "LEVEL_KEYS", "KVLayout", "RingLayout",
           "PagedLayout", "pick_layout", "with_tokens"]
