"""The control's arithmetic: operands of every matrix product and
convolution rounded to fp8 (e4m3, one scale per tensor), the nearest
precision below the bf16 compute the configurations state. Written with
plain float ops so that it runs wherever float32 does."""

import jax.numpy as jnp


def fp8_e4m3(x):
    """x rounded to the e4m3 grid after scaling its largest magnitude to
    448 (the format's maximum): 4 significant bits, exponents down to 2**-6,
    subnormals below."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    y = x * scale
    _, e = jnp.frexp(y)
    quantum = jnp.exp2((jnp.maximum(e, -5) - 4).astype(jnp.float32))
    return jnp.round(y / quantum) * quantum / scale


CASTS = {"float32": None, "fp8_e4m3": fp8_e4m3}
