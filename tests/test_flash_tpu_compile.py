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
