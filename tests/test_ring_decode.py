"""The ring decode kernel (``ops/ring_decode.py``) against the XLA twins
it replaces, on the CPU through the interpret-mode hook of
``ops/attention.py``: one parametrised test a property, so each case
counts.

- ``kv_cache.decode_token`` == ``write_token`` + ``attend``, and
  ``kv_cache.attend_token`` (the read-only pass) == ``attend``, for
  lengths short of a block, on a block edge and mixed across slots; for
  a ring that has wrapped; for one query head a KV head and sixteen, head
  sizes 64 and 128 (the level with the ring on the lanes, and the
  row-major one); several row-major KV heads a grid step (ten of 128 read
  by four queries each, rings of 512 and 3,072, and a level whose blocks
  fit only half its heads in a step); bf16 and float32 levels; dead
  slots beside live ones;
- the heads a grid step takes, level by level, and the kernel timing
  tool's sweep of them (``tools/ring_kernel_time.py``) at a toy size;
- the written row is bitwise ``write_token``'s and no other row of a
  live slot, nor any row of a dead one, is touched;
- a cache whose levels have two lengths;
- what the kernel cannot take keeps the XLA path: an int8 level, the
  sharded engine, a ring that no block divides, a backend that is not
  the TPU outside the hook;
- through the engine: the served tokens, ``n_traces == 1``, and the
  ``kv_blocks`` and ``walk_steps`` span attrs and counters.
"""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from singa_tpu import device
from singa_tpu.models import transformer
from singa_tpu.observability import metrics as obs_metrics
from singa_tpu.observability import spans as obs_spans
from singa_tpu.ops import attention_mod, ring_decode
from singa_tpu.serving import kv_cache
from singa_tpu.tensor import Tensor

pytestmark = pytest.mark.serving

DEV = device.create_cpu_device()


@pytest.fixture
def interpreted():
    prev = attention_mod.FORCE_PALLAS_INTERPRET
    attention_mod.FORCE_PALLAS_INTERPRET = True
    yield
    attention_mod.FORCE_PALLAS_INTERPRET = prev


def _level_and_token(W, n_kv, G, L, D, dtype, seed=0):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    level = {"k": draw(W, n_kv, L, D), "v": draw(W, n_kv, L, D)}
    return level, draw(W, n_kv * G, 1, D), draw(W, n_kv, D), draw(W, n_kv, D)


def _bits(a):
    return np.ascontiguousarray(np.asarray(a)).view(np.uint8)


def _check_against_twins(level, q, k_new, v_new, pos, active, tol):
    """decode_token against write_token + attend on one level, and the
    read-only pass over the written level against attend."""
    pos = jnp.asarray(pos, jnp.int32)
    active = np.asarray(active, bool)
    scale = q.shape[-1] ** -0.5
    assert kv_cache.ring_block(level) is not None
    want_level = kv_cache.write_token(level, k_new, v_new, pos)
    want = kv_cache.attend(q, want_level, pos, scale)
    got, got_level = kv_cache.decode_token(
        level, q, k_new, v_new, pos, jnp.asarray(active), scale)
    assert got.shape == q.shape and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got[active], want[active], atol=tol, rtol=0)
    assert not got[~active].any()
    read = np.asarray(kv_cache.attend_token(
        want_level, q, pos, jnp.asarray(active), scale), np.float32)
    np.testing.assert_allclose(read[active], want[active], atol=tol, rtol=0)
    assert not read[~active].any()
    for name in ("k", "v"):
        # a live slot: the new row as write_token wrote it, bit for bit,
        # and every other row as it was; a dead slot: untouched
        assert np.array_equal(_bits(got_level[name])[active],
                              _bits(want_level[name])[active]), name
        assert np.array_equal(_bits(got_level[name])[~active],
                              _bits(level[name])[~active]), name


# ring of three blocks of 128 rows; positions are the new token's
LENGTHS = {
    "short_of_a_block": [3, 60, 126],
    "on_a_block_edge": [127, 128, 255, 256],
    "mixed_across_slots": [0, 130, 383, 17, 300],
    "last_index_before_the_wrap": [383, 382],
    "wrapped": [384, 500, 1000],
    "wrapped_at_a_multiple_of_the_ring": [768, 1152, 384],
}


# ten KV heads of 128 read by four queries each, bf16 (phi4flash's
# levels): all ten heads a grid step, on a window ring of 512 (blocks of
# 128) and a full ring of 3,072 (blocks of 256), slots partial and wrapped
HEADS_LENGTHS = {
    "ten_heads_ring_512": (512, [3, 127, 128, 511, 512, 700, 1023, 1536]),
    "ten_heads_ring_3072": (3072, [0, 255, 256, 3071, 3072, 5000]),
}


@pytest.mark.parametrize("case", sorted(LENGTHS) + sorted(HEADS_LENGTHS))
def test_lengths(interpreted, case):
    if case in LENGTHS:
        pos, tol = LENGTHS[case], 2e-6
        level, q, k_new, v_new = _level_and_token(len(pos), 2, 1, 384, 64,
                                                  jnp.float32)
    else:
        # the output is rounded to the level's dtype: one bf16 step
        (L, pos), tol = HEADS_LENGTHS[case], 2 ** -6
        level, q, k_new, v_new = _level_and_token(len(pos), 10, 4, L, 128,
                                                  jnp.bfloat16)
        assert _heads_per_step(level) == 10
    _check_against_twins(level, q, k_new, v_new, pos, [True] * len(pos),
                         tol=tol)


def _heads_per_step(level):
    W, n_kv, L, D = level["k"].shape
    return ring_decode.heads_per_step(n_kv, ring_decode.block_rows(L), D,
                                      level["k"].dtype.itemsize)


# (head size, query heads a KV head, KV heads, ring, KV heads a step)
GROUPS = {
    "64-1": (64, 1, 2, 256, 2),
    "64-16": (64, 16, 2, 256, 2),
    "128-1": (128, 1, 1, 256, 1),
    "128-16": (128, 16, 1, 256, 1),
    "128-1-kv2": (128, 1, 2, 256, 2),
    "128-4-kv2": (128, 4, 2, 256, 2),
    "128-1-kv10": (128, 1, 10, 512, 10),
    "128-4-kv10": (128, 4, 10, 512, 10),
    # blocks of 512 float32 rows: K and V of five heads, double-buffered,
    # fill the VMEM the rule allows, so the ten take two steps a block
    "128-4-kv10-half-a-step": (128, 4, 10, 4096, 5),
}


@pytest.mark.parametrize("case", list(GROUPS))
def test_grouped_heads_and_head_sizes(interpreted, case):
    """KV heads read by G query heads each; head size 64 is the level
    XLA keeps with the ring on the lanes (two heads fill its lane tile),
    128 the row-major one, one KV head or several, each step taking as
    many KV heads as the rule gives."""
    D, G, n_kv, L, hb = GROUPS[case]
    level, q, k_new, v_new = _level_and_token(3, n_kv, G, L, D,
                                              jnp.float32)
    assert _heads_per_step(level) == hb
    wrapped = 600 if L < 600 else L + 600
    _check_against_twins(level, q, k_new, v_new, [5, 255, wrapped],
                         [True] * 3, tol=2e-6)


@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_level_dtypes(interpreted, dtype, D):
    dtype = jnp.dtype(dtype)
    level, q, k_new, v_new = _level_and_token(4, 2, 2, 256, D, dtype, seed=1)
    # the output is rounded to the level's dtype: one bf16 step at most
    tol = 2e-6 if dtype == jnp.float32 else 2 ** -6
    _check_against_twins(level, q, k_new, v_new, [0, 129, 255, 700],
                         [True] * 4, tol=tol)


DEAD = {
    "dead_beside_live": ([5, 200, 7, 300], [True, False, True, False]),
    "first_slot_dead": ([9, 130, 2], [False, True, True]),
    "position_nought": ([0, 0, 0], [True, False, True]),
    "all_dead": ([4, 5], [False, False]),
}


# head size, or head size x KV heads where there are more than two
@pytest.mark.parametrize("heads", ["64", "128", "128x10"])
@pytest.mark.parametrize("case", sorted(DEAD))
def test_dead_slots(interpreted, case, heads):
    pos, active = DEAD[case]
    D, n_kv = (int(n) for n in (heads + "x2").split("x")[:2])
    level, q, k_new, v_new = _level_and_token(len(pos), n_kv, 1, 384, D,
                                              jnp.float32, seed=2)
    _check_against_twins(level, q, k_new, v_new, pos, active, tol=2e-6)


def test_levels_of_two_lengths(interpreted):
    """Four window rings and a full one, at toy size, walked tick after
    tick by one jitted program until the window rings have wrapped."""
    W, n_kv, G, D = 3, 1, 4, 128
    lengths = [128] * 4 + [384]
    rng = np.random.default_rng(3)
    cache = [kv_cache.init_cache(W, n_kv, L, D) for L in lengths]
    twin = [dict(level) for level in cache]
    active = jnp.asarray([True, True, False])

    @functools.partial(jax.jit, static_argnames="xla")
    def tick(cache, q, k_new, v_new, pos, xla):
        outs, new = [], []
        for level in cache:
            if xla:
                level = kv_cache.write_token(level, k_new, v_new, pos)
                o = kv_cache.attend(q, level, pos, D ** -0.5)
            else:
                o, level = kv_cache.decode_token(level, q, k_new, v_new,
                                                 pos, active, D ** -0.5)
            outs.append(o)
            new.append(level)
        return jnp.stack(outs), new

    for t in (0, 1, 126, 127, 128, 129, 140):
        q = jnp.asarray(rng.normal(size=(W, n_kv * G, 1, D)), jnp.float32)
        k_new, v_new = (jnp.asarray(rng.normal(size=(W, n_kv, D)),
                                    jnp.float32) for _ in range(2))
        pos = jnp.asarray([t, t + 100, 0], jnp.int32)
        got, cache = tick(cache, q, k_new, v_new, pos, xla=False)
        want, twin = tick(twin, q, k_new, v_new, pos, xla=True)
        np.testing.assert_allclose(np.asarray(got)[:, :2],
                                   np.asarray(want)[:, :2], atol=2e-6)
    for level, want in zip(cache, twin):
        assert np.array_equal(_bits(level["k"])[:2], _bits(want["k"])[:2])


def _never(*a, **kw):
    raise AssertionError("the ring decode kernel was called")


FALLBACKS = ["int8_level", "xla_rings_scope", "no_block_divides",
             "head_size_no_form", "heads_fill_no_lane_tile", "hook_off"]


@pytest.mark.parametrize("case", FALLBACKS)
def test_what_the_kernel_cannot_take_keeps_the_xla_path(
        monkeypatch, case):
    attention_mod_hook = case != "hook_off"
    monkeypatch.setattr(attention_mod, "FORCE_PALLAS_INTERPRET",
                        attention_mod_hook)
    monkeypatch.setattr(ring_decode, "ring_decode", _never)
    W, n_kv, L, D = 2, 2, 256, 64
    dtype = jnp.float32
    if case == "int8_level":
        dtype = jnp.int8
    elif case == "no_block_divides":
        L = 192
    elif case == "head_size_no_form":
        D = 48
    elif case == "heads_fill_no_lane_tile":
        n_kv = 1
    level = kv_cache.init_cache(W, n_kv, L, D, dtype)
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(W, n_kv, 1, D)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(W, n_kv, D)), jnp.float32)
                    for _ in range(2))
    pos = jnp.asarray([3, 9], jnp.int32)
    active = jnp.asarray([True, True])

    def run():
        assert kv_cache.ring_block(level) is None
        return kv_cache.decode_token(level, q, k_new, v_new, pos, active,
                                     D ** -0.5)

    if case == "xla_rings_scope":
        with kv_cache.xla_rings():
            got, got_level = run()
        # and the scope ends with its block
        assert kv_cache.ring_block(level) is not None
    else:
        got, got_level = run()
    want_level = kv_cache.write_token(level, k_new, v_new, pos)
    want = kv_cache.attend(q, want_level, pos, D ** -0.5)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert sorted(got_level) == sorted(want_level)


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------

def _lm(seed=0, max_len=128, d_model=128, n_heads=4):
    np.random.seed(seed)
    DEV.SetRandSeed(seed)
    m = transformer.TransformerLM(64, d_model=d_model, n_heads=n_heads,
                                  n_layers=2, max_len=max_len, tp=False)
    m.eval()
    m(Tensor(data=np.zeros((1, 8), np.float32), device=DEV,
             requires_grad=False))
    return m


def _prompts():
    rng = np.random.RandomState(5)
    return [rng.randint(1, 64, (n,)) for n in (3, 16, 9, 1, 12, 7)]


def _serve(m, **kw):
    reg = obs_metrics.MetricsRegistry()
    eng = m.compile_serving(slots=2, max_len=128, prefill_len=16,
                            registry=reg, **kw)
    futs = [eng.submit(p, max_new_tokens=6) for p in _prompts()]
    eng.run_until_idle()
    tokens = [f.result(timeout=5)["tokens"] for f in futs]
    info = eng.compiled_step_info()
    eng.stop()
    return tokens, info, reg


def test_engine_serves_the_same_tokens_traced_once(interpreted,
                                                   monkeypatch):
    m = _lm()
    calls = []
    real = ring_decode.ring_decode
    monkeypatch.setattr(
        ring_decode, "ring_decode",
        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    got, info, _ = _serve(m)
    assert len(calls) == 2          # once a level, in the one trace
    assert info["n_traces"] == 1 and info["kv_layout"] == "ring", info
    monkeypatch.setattr(attention_mod, "FORCE_PALLAS_INTERPRET", False)
    want, _, _ = _serve(m)
    assert got == want


def test_sharded_engine_keeps_the_xla_path(interpreted, monkeypatch):
    monkeypatch.setattr(ring_decode, "ring_decode", _never)
    m = _lm(seed=1)
    got, info, _ = _serve(m, model_shards=2)
    assert info["n_traces"] == 1 and info["model_shards"] == 2, info
    monkeypatch.setattr(attention_mod, "FORCE_PALLAS_INTERPRET", False)
    want, _, _ = _serve(m)
    assert got == want


def test_kv_blocks_on_the_span_and_the_counter():
    """``kv_blocks`` is the count of live blocks from the positions of
    the tick, a block being what the kernel would cut (the whole ring
    where none divides it), on every engine whatever path it takes."""
    m = _lm(seed=2)
    obs_spans.recorder().clear()
    _, info, reg = _serve(m)
    ticks = [r for r in obs_spans.recorder().records()
             if r.get("name") == "serve.decode"]
    assert ticks and info["n_traces"] == 1
    for r in ticks:
        rows, blocks = r["kv_rows"], r["kv_blocks"]
        # two levels 128 long, cut in one block of 128: a live slot
        # holds one block a level, and 1..128 rows
        assert blocks % 2 == 0 and 2 <= blocks <= 4
        assert blocks // 2 <= rows // 2 <= 128 * blocks // 2
    assert reg.get("serve_kv_blocks_walked_total").value() == \
        sum(r["kv_blocks"] for r in ticks)
    assert reg.get("serve_kv_rows_attended_total").value() == \
        sum(r["kv_rows"] for r in ticks)


# (KV heads x head size, VMEM the rule may give a step's K and V blocks,
# grid steps a block takes): four heads of 32 in one product on the
# lanes; two row-major heads of 128 in one step, or, where the VMEM
# holds one head's blocks only, in two
WALKS = {
    "lanes-4x32": (4, 32, None, 1),
    "rows-2x128": (2, 128, None, 1),
    "rows-2x128-one-head-a-step": (2, 128, 2 ** 17, 2),
}


@pytest.mark.parametrize("case", list(WALKS))
def test_walk_steps_on_the_span_and_the_counter(monkeypatch, case):
    """``walk_steps`` is the kernel's grid steps a tick: ``kv_blocks`` x
    the groups of KV heads a step takes (``ring_decode.heads_per_step``
    of each level), so ``kv_blocks`` x KV heads ÷ ``walk_steps`` reads
    the heads a step carries; summed over the spans it is
    ``serve_kv_walk_steps_total``."""
    n_kv, D, vmem, groups = WALKS[case]
    if vmem is not None:
        monkeypatch.setattr(ring_decode, "_BLOCK_VMEM", vmem)
    m = _lm(seed=2, d_model=n_kv * D, n_heads=n_kv)
    obs_spans.recorder().clear()
    _, info, reg = _serve(m)
    ticks = [r for r in obs_spans.recorder().records()
             if r.get("name") == "serve.decode"]
    assert ticks and info["n_traces"] == 1
    for r in ticks:
        assert r["kv_blocks"] > 0
        assert r["walk_steps"] == r["kv_blocks"] * groups
        assert r["kv_blocks"] * n_kv // r["walk_steps"] == n_kv // groups
    assert reg.get("serve_kv_walk_steps_total").value() == \
        sum(r["walk_steps"] for r in ticks)


HEADS = {
    # phi4flash's window and full rings: all ten heads a step
    "phi4flash-window": ((10, 128, 128, 2), 10),
    "phi4flash-full": ((10, 256, 128, 2), 10),
    # one KV head (Command A+'s rings; a latent level's one row a token)
    "one-kv-head": ((1, 512, 128, 2), 1),
    "latent-row": ((1, 512, 640, 2), 1),
    # the ring on the lanes: all of GPT-2's sixteen heads of 64 in one
    # product, halved while a step's blocks outgrow the VMEM
    "lanes-gpt2": ((16, 128, 64, 2), 16),
    "lanes-halved": ((32, 512, 64, 4), 16),
    # row-major blocks that hold half the heads: the largest divisor
    "rows-halved": ((10, 512, 128, 4), 5),
    "rows-odd": ((3, 2048, 128, 4), 1),
}


@pytest.mark.parametrize("case", list(HEADS))
def test_heads_per_step(case):
    """The KV heads a grid step takes, from ``(n_kv, block, D, itemsize)``
    alone: one for a level of one KV head, all that fit for a row-major
    level, and for a level with the ring on the lanes all of them halved
    while their blocks outgrow the VMEM."""
    args, want = HEADS[case]
    assert ring_decode.heads_per_step(*args) == want


@pytest.mark.parametrize("length,block,pos,want", [
    (1024, 128, [0, 127, 128, 1023, 5000], [1, 1, 2, 8, 8]),
    (5120, 512, [4095, 4096, 9999], [8, 9, 10]),
    (384, 128, [127, 128, 383], [1, 2, 3]),
])
def test_live_blocks_from_positions(length, block, pos, want):
    assert ring_decode.block_rows(length) == block
    got = ring_decode.live_blocks(jnp.asarray(pos), jnp.ones(len(pos), bool),
                                  length, block)
    assert list(np.asarray(got)) == want
    dead = ring_decode.live_blocks(jnp.asarray(pos),
                                   jnp.zeros(len(pos), bool), length, block)
    assert not np.asarray(dead).any()


def test_kernel_time_tool_forces_the_heads_a_step(interpreted, capsys):
    """``tools/ring_kernel_time.py`` at a toy size: a line a (pass, heads
    a step), each with the grid steps of that count, and the rule's own
    count restored after the sweep."""
    import json
    import os
    import runpy
    tool = runpy.run_path(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "ring_kernel_time.py"))
    rule = ring_decode.heads_per_step
    tool["main"](["--slots", "4", "--kv-heads", "2", "--rings", "256",
                  "--live", "3", "--tokens", "100", "--calls", "1",
                  "--heads-a-step", "1,2"])
    assert ring_decode.heads_per_step is rule
    lines = [json.loads(line)
             for line in capsys.readouterr().out.splitlines()]
    assert [(d["op"], d["heads_a_step"]) for d in lines] == [
        ("ring_decode", 1), ("ring_attend", 1),
        ("ring_decode", 2), ("ring_attend", 2)]
    for d in lines:
        assert d["rule"] == 2 and d["block"] == 128
        assert d["grid_steps"] == d["blocks_walked"] * 2 // d["heads_a_step"]
