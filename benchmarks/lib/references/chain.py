"""A model as a chain of stages, differentiated stage by stage.

A reference that is one program of a 50-layer net in float32 at the highest
matmul precision takes minutes to compile for the chip and is too large for
its compile cache. Stage by stage, every stage of one shape shares one small
program, and only the activations at stage boundaries are kept: the float32
reference then fits beside nothing else at the timed batch.

A stage is (fn, names, statics): `fn(local_params, x, **statics)` gives the
next activation; `names` maps the stage's local parameter names to the
model's. The last stage's `fn(local_params, x, labels, **statics)` gives
the scalar loss. Same maths as `jax.value_and_grad` of the composition.
"""

import functools

import jax


@functools.lru_cache(maxsize=None)
def _forward(fn, statics):
    return jax.jit(lambda p, x: fn(p, x, **dict(statics)))


@functools.lru_cache(maxsize=None)
def _backward(fn, statics, with_input):
    def bwd(p, x, g):
        if with_input:
            _, vjp = jax.vjp(lambda p, x: fn(p, x, **dict(statics)), p, x)
            return vjp(g)
        _, vjp = jax.vjp(lambda p: fn(p, x, **dict(statics)), p)
        return vjp(g)[0], None
    return jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _loss_and_grad(fn, statics):
    return jax.jit(jax.value_and_grad(
        lambda p, x, y: fn(p, x, y, **dict(statics)), argnums=(0, 1)))


def _local(params, names):
    return {k: params[v] for k, v in names.items()}


def forward(stages, params, x):
    """The activation entering the last stage."""
    for fn, names, statics in stages[:-1]:
        x = _forward(fn, statics)(_local(params, names), x)
    return x


def value_and_grad(stages, params, x, labels):
    """(loss, {model name: gradient}) of the chain on one batch."""
    xs = [x]
    for fn, names, statics in stages[:-1]:
        xs.append(_forward(fn, statics)(_local(params, names), xs[-1]))
    fn, names, statics = stages[-1]
    loss, (gp, g) = _loss_and_grad(fn, statics)(
        _local(params, names), xs.pop(), labels)
    grads = {names[k]: v for k, v in gp.items()}
    for i in range(len(stages) - 2, -1, -1):
        fn, names, statics = stages[i]
        gp, g = _backward(fn, statics, i > 0)(
            _local(params, names), xs.pop(), g)
        grads.update({names[k]: v for k, v in gp.items()})
    return loss, grads
