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
+ :func:`attend`, which write every slot and score the whole level.

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
prefill/decode body. ``dtype=int8`` rides both layouts: per-row fp32
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

import jax
import jax.numpy as jnp
from jax import lax


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
    + :func:`attend`."""
    block = ring_block(level)
    if block is None:
        level = write_token(level, k_new, v_new, pos)
        return attend(q, level, pos, scale), level
    from ..ops import ring_decode as _rd
    out, k, v = _rd.ring_decode(q, k_new, v_new, level["k"], level["v"],
                                pos, active, scale, block)
    return out, {"k": k, "v": v}


def write_prompt(level, slot, k_rows, v_rows, valid):
    """Write one prompt's rows into one slot, starting at ring index 0.

    ``k_rows``/``v_rows``: ``(H, S, D)`` with ``S <= L`` (the engine's
    ``prefill_len <= max_len`` contract); ``slot`` scalar int;
    ``valid`` scalar bool — False rows (prefill-batch padding) leave
    the cache untouched, which is what lets the prefill program keep a
    FIXED batch width over a variable number of admitted requests. A
    quantized level quantizes per token row (scale amax over heads ×
    head_dim) and writes the prompt's scale rows alongside."""
    if "k_scale" in level:
        # (H, S, D): one scale per prompt position -> (S,)
        k_rows, ks = _quant_rows(k_rows, (0, 2))
        v_rows, vs = _quant_rows(v_rows, (0, 2))
    k_up = lax.dynamic_update_slice(
        level["k"], k_rows[None].astype(level["k"].dtype),
        (slot, 0, 0, 0))
    v_up = lax.dynamic_update_slice(
        level["v"], v_rows[None].astype(level["v"].dtype),
        (slot, 0, 0, 0))
    out = {"k": jnp.where(valid, k_up, level["k"]),
           "v": jnp.where(valid, v_up, level["v"])}
    if "k_scale" in level:
        ks_up = lax.dynamic_update_slice(level["k_scale"], ks[None],
                                         (slot, 0))
        vs_up = lax.dynamic_update_slice(level["v_scale"], vs[None],
                                         (slot, 0))
        out["k_scale"] = jnp.where(valid, ks_up, level["k_scale"])
        out["v_scale"] = jnp.where(valid, vs_up, level["v_scale"])
    return out


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

    def can_admit(self, prompt, total_tokens):
        """Whether :meth:`admit` would succeed right now (the queue's
        backpressure gate — a request that cannot be placed THIS tick
        stays queued, it is not failed)."""
        shared, _ = self.match_prefix(prompt)
        need = self.n_for(total_tokens) - len(shared)
        return need <= self._reclaimable(shared)

    def admit(self, prompt, total_tokens):
        """Reserve every block the sequence can ever touch (positions
        ``[0, total_tokens)`` — decode can then never stall or corrupt
        a neighbour mid-flight). Shared prefix blocks are re-referenced
        FIRST (so LRU reclaim can never eat the prefix being shared);
        the rest come from the free list, reclaiming LRU cached blocks
        when it runs dry. Raises
        :class:`~singa_tpu.serving.scheduler.BlockPoolExhausted` when
        the pool cannot cover it without touching a live block."""
        from .scheduler import BlockPoolExhausted
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
        accounting (``can_admit``/``_reclaimable``) is unchanged."""
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
        Callers guarantee one exists (can_admit/admit checked). With a
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


__all__ = ["init_cache", "ring_positions", "ring_mask", "write_token",
           "write_prompt", "attend", "decode_token", "ring_block",
           "xla_rings", "init_pool", "write_rows",
           "gather_pages", "attend_pages", "SlotAlloc", "BlockManager",
           "HostSpillTier", "chain_keys", "prefix_chain_key",
           "affinity_hash"]
