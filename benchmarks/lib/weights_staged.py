"""Weights from the seed, one leaf at a time: a leaf's values depend on the
seed and the leaf's name and on nothing else, so the program can load leaf
by leaf in the leaf's own dtype and the reference can draw one layer, use
it and free it. `lib/weights.make` draws every leaf of a model in float32
in one call; for a configuration whose expert matrices alone are 12.9 GB in
float32 that cannot be held.

Every value is drawn in float32 and rounded to `store` (the dtype the
configuration's `precision` keeps its weights in) before either side sees
it: the weights ARE the stored values, as a checkpoint's are, and the
reference computes on them in float32.
"""

import functools
import zlib

import jax
import jax.numpy as jnp

from . import weights


def leaf_key(seed, name):
    """The leaf's own PRNG key: the seed's, folded with a digest of the
    leaf's name."""
    return jax.random.fold_in(weights.key_for(seed),
                              zlib.crc32(name.encode()) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, store, out, mean, std):
    x = mean + std * jax.random.normal(key, shape, jnp.float32)
    return x.astype(store).astype(out)


def make_leaf(spec, seed, store, out=None):
    """One leaf of spec (name, shape, mean, std): drawn in float32, rounded
    to `store`, handed back as `out` (default `store`). A bank of matrices
    (three axes: the experts of a layer) is drawn a matrix at a time, each
    from the leaf's key folded with its index, so that the float32 draw of
    a 0.5 GB bank is never alive at once."""
    name, shape, mean, std = spec
    store, out = jnp.dtype(store), jnp.dtype(out or store)
    key, shape = leaf_key(seed, name), tuple(shape)
    mean, std = jnp.float32(mean), jnp.float32(std)
    if len(shape) != 3:
        return _draw(key, shape, store, out, mean, std)
    return jnp.stack([_draw(jax.random.fold_in(key, i), shape[1:], store,
                            out, mean, std) for i in range(shape[0])])


def make(specs, seed, store, out=None):
    """{name: array} of `specs`: as many leaves alive as the caller asks
    for at once (a layer's, for the reference)."""
    return {spec[0]: make_leaf(spec, seed, store, out) for spec in specs}
