"""Weights from the seed, made on the device in one jitted call, and the
per-leaf norms the training comparison reads. Both sides of a comparison
(the program's state and the reference's) go through the same functions.

Leaves of one shape are drawn together, as one array with a leading axis:
a program with one random draw a leaf (292 for GPT-2-medium) takes two
minutes to compile for the chip, one with a draw a shape (8) seconds.
"""

import functools

import jax
import jax.numpy as jnp


def key_for(seed):
    """A PRNG key from any whole number up to 62 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _freeze(specs):
    return tuple((n, tuple(s), float(m), float(sd)) for n, s, m, sd in specs)


def _groups(specs):
    """[(shape, [indices of the leaves of that shape])], in first-seen
    order: the order is part of what a seed means."""
    groups = {}
    for i, (_, shape, _, _) in enumerate(specs):
        groups.setdefault(shape, []).append(i)
    return list(groups.items())


def _draw(specs, key):
    """Per group: (indices, array (n_leaves, *shape)) of initial weights."""
    out = []
    for g, (shape, idx) in enumerate(_groups(specs)):
        ones = (1,) * len(shape)
        mean = jnp.asarray([specs[i][2] for i in idx],
                           jnp.float32).reshape(-1, *ones)
        std = jnp.asarray([specs[i][3] for i in idx],
                          jnp.float32).reshape(-1, *ones)
        noise = jax.random.normal(jax.random.fold_in(key, g),
                                  (len(idx), *shape), jnp.float32)
        out.append((idx, mean + std * noise))
    return out


def make(specs, seed):
    """{name: float32 array} for specs [(name, shape, mean, std)]."""
    return _make(_freeze(specs), key_for(seed))


@functools.partial(jax.jit, static_argnums=0)
def _make(specs, key):
    out = {}
    for idx, stacked in _draw(specs, key):
        for j, i in enumerate(idx):
            out[specs[i][0]] = stacked[j]
    return out


@functools.partial(jax.jit, static_argnums=0)
def _norms(specs, key, leaves, scale_p0):
    """[|| leaf + scale_p0 * p0 ||] per spec, p0 drawn again from the key,
    so the initial weights need not be kept beside the state."""
    out = [None] * len(specs)
    for idx, stacked in _draw(specs, key):
        mine = jnp.stack([leaves[i].astype(jnp.float32) for i in idx])
        sq = jnp.square(mine + scale_p0 * stacked)
        norms = jnp.sqrt(jnp.sum(sq, axis=tuple(range(1, sq.ndim))))
        for j, i in enumerate(idx):
            out[i] = norms[j]
    return jnp.stack(out)


def norms_against_init(specs, seed, leaves, scale_p0):
    return _norms(_freeze(specs), key_for(seed), tuple(leaves),
                  jnp.float32(scale_p0))
