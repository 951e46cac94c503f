"""The flash kernels compile for the TPU v5e at real widths — without the
chip. The TPU's compiler is installed here and compiles for a chip that is
described and not attached, so what Mosaic would refuse on the chip (a tile
it cannot lay out, a grid step that does not fit the scoped VMEM) fails
here first; interpret mode shows neither. Nothing runs: these cases say
nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports every
test file.
"""

import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from singa_tpu.ops import attention_mod as ATTN
from singa_tpu.parallel import moe


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_the_chip(monkeypatch):
    """Lower the Pallas calls for Mosaic (not interpreted), and keep the
    persistent compile cache out of it: an entry written for a described
    chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ATTN, "_interpret", lambda: False)
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _fwd_bwd(causal, blocks):
    def f(q, k, v, g):
        scale = q.shape[-1] ** -0.5
        out, lse = ATTN._pallas_flash_fwd(q, k, v, causal, scale, *blocks)
        return (out,) + ATTN._pallas_flash_bwd(q, k, v, out, lse, g,
                                               causal, scale, *blocks)
    return f


# (B, H, S, D), dtype: the benchmark's train cell first, then the shapes
# the block and heads-per-step rules have to hold for — float32 callers,
# other head sizes, a sequence shorter than a block, a B*H no power of two
SHAPES = [
    ((4, 16, 1024, 64), jnp.bfloat16),
    ((4, 16, 1024, 64), jnp.float32),
    ((2, 8, 2048, 128), jnp.bfloat16),
    ((2, 8, 1024, 256), jnp.float32),
    ((3, 5, 384, 64), jnp.bfloat16),
    ((2, 4, 64, 64), jnp.bfloat16),
]


@pytest.mark.pallas
@pytest.mark.parametrize("causal", (True, False), ids=("causal", "full"))
@pytest.mark.parametrize(
    "shape,dtype", SHAPES,
    ids=[f"{'x'.join(map(str, s))}-{jnp.dtype(d).name}" for s, d in SHAPES])
def test_fwd_and_bwd_compile_at_the_picked_blocks(one_chip, for_the_chip,
                                                  shape, dtype, causal):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    blocks = ATTN._pick_blocks(shape[2], shape[2])
    hlo = jax.jit(_fwd_bwd(causal, blocks)).lower(x, x, x, x).compile() \
        .as_text()
    assert hlo.count("tpu_custom_call") == 3
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in hlo


# (B, H, S, key head, value head): latent attention's 192-wide keys and
# 128-wide values (the Kanana-2 train cell), and a float32 pair
NARROW_V = [((2, 32, 4096, 192, 128), jnp.bfloat16),
            ((1, 4, 512, 192, 128), jnp.float32)]


@pytest.mark.pallas
@pytest.mark.parametrize(
    "shape,dtype", NARROW_V,
    ids=[f"{'x'.join(map(str, s))}-{jnp.dtype(d).name}" for s, d in NARROW_V])
def test_values_narrower_than_keys_compile(one_chip, for_the_chip, shape,
                                           dtype):
    """v, the output, g and dv as wide as the values; q, k, dq, dk as the
    keys: no padding of V to the key width."""
    B, H, S, D, Dv = shape
    qk = jax.ShapeDtypeStruct((B, H, S, D), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((B, H, S, Dv), dtype, sharding=one_chip)
    blocks = ATTN._pick_blocks(S, S)
    hlo = jax.jit(_fwd_bwd(True, blocks)).lower(qk, qk, v, v).compile() \
        .as_text()
    assert hlo.count("tpu_custom_call") == 3
    out = jax.eval_shape(_fwd_bwd(True, blocks), qk, qk, v, v)
    assert [o.shape[-1] for o in out] == [Dv, D, D, Dv]


# (pairs' rows, hidden, expert width, held experts): the Kanana-2 train
# cell's expert layer (8,192 tokens x 6 picks, 16 of its 128 experts held
# here), both of its products; then a toy
GROUPED = [(49152, 2048, 768, 16), (49152, 768, 2048, 16),
           (256, 64, 96, 4)]


@pytest.mark.pallas
@pytest.mark.parametrize("M,K,N,G", GROUPED,
                         ids=["x".join(map(str, g)) for g in GROUPED])
def test_grouped_product_and_its_reverse_compile(one_chip, for_the_chip, M,
                                                 K, N, G):
    """megablox's `gmm` forward, and `gmm` (the rows' gradient) and `tgmm`
    (the banks') backward at the tiles `moe._tiling` gives: their blocks
    fit the scoped VMEM and Mosaic lays them out."""
    lhs = jax.ShapeDtypeStruct((M, K), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((G, K, N), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((G + 1,), jnp.int32, sharding=one_chip)
    dy = jax.ShapeDtypeStruct((M, N), jnp.float32, sharding=one_chip)

    def f(lhs, rhs, sizes, dy):
        out, vjp = jax.vjp(lambda a, b: moe.grouped_matmul(a, b, sizes),
                           lhs, rhs)
        return (out,) + vjp(dy)
    hlo = jax.jit(f).lower(lhs, rhs, sizes, dy).compile().as_text()
    assert hlo.count("tpu_custom_call") == 3
    calls = [line.split("=")[0] for line in hlo.splitlines()
             if "tpu_custom_call" in line]
    assert sum("tgmm" in c for c in calls) == 1
    assert sum("gmm" in c for c in calls) == 3


@pytest.mark.pallas
def test_ring_form_compiles(one_chip, for_the_chip):
    """The forward with a traced position delta, float32, as
    `_ring_partials` calls it."""
    x = jax.ShapeDtypeStruct((1, 4, 512, 64), jnp.float32,
                             sharding=one_chip)
    d = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    blocks = ATTN._pick_blocks(512, 512)

    def f(q, k, v, delta):
        return ATTN._pallas_flash_fwd(q, k, v, True, 0.125, *blocks,
                                      pos_delta=delta)
    hlo = jax.jit(f).lower(x, x, x, d).compile().as_text()
    assert hlo.count("tpu_custom_call") == 1 and "flash_fwd" in hlo


# ---------------------------------------------------------------------------
# the ring decode kernel (ops/ring_decode.py), in this file because only one
# test file of a run may describe the topology (module docstring)
# ---------------------------------------------------------------------------

# (slots, KV heads, query heads a KV head, ring, head size), dtype: the two
# serve cells' levels first (GPT-2's lies with the ring on the lanes,
# Command A+'s two are row-major, as are the two of the differential
# attention of phi4flash: 10 paired KV heads of 128 read by 4 queries each),
# then float32 levels, grouped KV heads at head size 128, and chip_smoke's
# toy rings
RING_LEVELS = [
    ((64, 16, 1, 1024, 64), jnp.bfloat16),
    ((64, 1, 16, 4096, 128), jnp.bfloat16),
    ((64, 1, 16, 5120, 128), jnp.bfloat16),
    ((64, 10, 4, 512, 128), jnp.bfloat16),
    ((64, 10, 4, 3072, 128), jnp.bfloat16),
    ((8, 16, 1, 1024, 64), jnp.float32),
    ((8, 2, 1, 1024, 128), jnp.float32),
    ((8, 8, 4, 2048, 128), jnp.bfloat16),
    ((4, 8, 1, 256, 64), jnp.bfloat16),
    ((4, 4, 2, 128, 32), jnp.bfloat16),
]


def _all_heads_a_step_where_they_fit(ring_decode, n_kv, block, D, dtype):
    """Every level here takes all its KV heads in one grid step where
    their double-buffered K and V blocks fit the rule's VMEM (phi4flash's
    ten on both its rings)."""
    itemsize = jnp.dtype(dtype).itemsize
    hb = ring_decode.heads_per_step(n_kv, block, D, itemsize)
    assert hb == n_kv or \
        4 * n_kv * block * D * itemsize > ring_decode._BLOCK_VMEM


@pytest.mark.pallas
@pytest.mark.parametrize(
    "shape,dtype", RING_LEVELS,
    ids=[f"{'x'.join(map(str, s))}-{jnp.dtype(d).name}"
         for s, d in RING_LEVELS])
def test_ring_decode_compiles_and_writes_the_level_in_place(
        one_chip, for_the_chip, monkeypatch, shape, dtype):
    """The kernel compiles at the level's own block and the heads a step
    its rule gives, once, and the compiled program aliases both halves
    of the level to its outputs and holds no temporary of a level's
    size: the donated cache is written where it lies, not copied."""
    from singa_tpu.ops import ring_decode
    monkeypatch.setattr(ring_decode, "_interpret", lambda: False)
    W, n_kv, G, L, D = shape
    block = ring_decode.kernel_block(n_kv, L, D)
    assert block is not None
    _all_heads_a_step_where_they_fit(ring_decode, n_kv, block, D, dtype)

    def sds(*s, dt=dtype):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    def f(q, k_new, v_new, k, v, pos, active):
        return ring_decode.ring_decode(q, k_new, v_new, k, v, pos, active,
                                       D ** -0.5, block)

    compiled = jax.jit(f, donate_argnums=(3, 4)).lower(
        sds(W, n_kv * G, 1, D), sds(W, n_kv, D), sds(W, n_kv, D),
        sds(W, n_kv, L, D), sds(W, n_kv, L, D), sds(W, dt=jnp.int32),
        sds(W, dt=jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1 and "ring_decode" in hlo
    level = W * n_kv * L * D * jnp.dtype(dtype).itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * level
    assert mem.temp_size_in_bytes < level // 4


@pytest.mark.pallas
@pytest.mark.parametrize(
    "shape,dtype",
    [RING_LEVELS[4], RING_LEVELS[3], RING_LEVELS[0], RING_LEVELS[-1]],
    ids=["full-ring-10x4x128", "window-ring-10x4x128",
         "ring-on-the-lanes-16x64", "toy-4x2x32"])
def test_ring_attend_compiles_and_leaves_the_level_alone(
        one_chip, for_the_chip, monkeypatch, shape, dtype):
    """The read-only pass (a layer that reads another layer's ring): one
    Mosaic call named apart from the writing one, the level neither an
    output nor aliased, and no temporary of a level's size."""
    from singa_tpu.ops import ring_decode
    monkeypatch.setattr(ring_decode, "_interpret", lambda: False)
    W, n_kv, G, L, D = shape
    block = ring_decode.kernel_block(n_kv, L, D)
    _all_heads_a_step_where_they_fit(ring_decode, n_kv, block, D, dtype)

    def sds(*s, dt=dtype):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    def f(q, k, v, pos, active):
        return ring_decode.ring_attend(q, k, v, pos, active, D ** -0.5,
                                       block)

    compiled = jax.jit(f).lower(
        sds(W, n_kv * G, 1, D), sds(W, n_kv, L, D), sds(W, n_kv, L, D),
        sds(W, dt=jnp.int32), sds(W, dt=jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1 and "ring_attend" in hlo
    level = W * n_kv * L * D * jnp.dtype(dtype).itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 0
    assert mem.temp_size_in_bytes < level // 4
    assert mem.output_size_in_bytes < level // 4


# (slots, query heads, ring, row width, value width), dtype: the latent
# cell's level (64 heads on one cached row of 512 + 64 numbers, stored 640
# wide), a float32 level, chip_smoke's toy level (4 heads on the same row),
# and a row of 32 + 16 whose values are no whole lane tile
LATENT_LEVELS = [
    ((64, 64, 4096, 576, 512), jnp.bfloat16),
    ((8, 16, 1024, 576, 512), jnp.float32),
    ((4, 4, 256, 576, 512), jnp.bfloat16),
    ((4, 4, 128, 48, 32), jnp.bfloat16),
]


@pytest.mark.pallas
@pytest.mark.parametrize(
    "shape,dtype", LATENT_LEVELS,
    ids=[f"{'x'.join(map(str, s))}-{jnp.dtype(d).name}"
         for s, d in LATENT_LEVELS])
def test_latent_decode_compiles_and_writes_the_level_in_place(
        one_chip, for_the_chip, monkeypatch, shape, dtype):
    """The latent form of the walk compiles at the level's own block,
    once; the one array of the level is aliased to its output and no
    temporary of a level's size exists."""
    from singa_tpu.ops import ring_decode
    monkeypatch.setattr(ring_decode, "_interpret", lambda: False)
    W, H, L, width, vw = shape
    P = -(-width // 128) * 128
    block = ring_decode.latent_block(L, P)
    assert block is not None

    def sds(*s, dt=dtype):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    def f(q, row, rows, pos, active):
        return ring_decode.latent_decode(q, row, rows, pos, active,
                                         width ** -0.5, block, vw)

    compiled = jax.jit(f, donate_argnums=(2,)).lower(
        sds(W, H, 1, width), sds(W, width), sds(W, 1, L, P),
        sds(W, dt=jnp.int32), sds(W, dt=jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1 and "latent_decode" in hlo
    level = W * L * P * jnp.dtype(dtype).itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == level
    assert mem.temp_size_in_bytes < level // 4
