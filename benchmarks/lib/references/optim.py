"""The optimizers' published update rules, on dicts of float32 leaves."""

import jax.numpy as jnp


def sgd_init(params):
    return {k: jnp.zeros_like(v) for k, v in params.items()}


def sgd_step(params, grads, state, step, *, lr, momentum=0.0,
             weight_decay=0.0):
    """Momentum SGD with L2 weight decay on every leaf (Sutskever form, no
    dampening): g += wd*p; buf = momentum*buf + g; p -= lr*buf."""
    new_p, new_s = {}, {}
    for k, p in params.items():
        g = grads[k] + weight_decay * p
        buf = momentum * state[k] + g
        new_s[k] = buf
        new_p[k] = p - lr * buf
    return new_p, new_s


def adam_init(params):
    return {k: (jnp.zeros_like(v), jnp.zeros_like(v))
            for k, v in params.items()}


def adam_step(params, grads, state, step, *, lr, beta_1=0.9, beta_2=0.999,
              epsilon=1e-8, weight_decay=0.0):
    """Adam (Kingma & Ba 2015, algorithm 1) with bias correction; `step`
    counts from 1."""
    new_p, new_s = {}, {}
    c1 = 1.0 - beta_1 ** step
    c2 = 1.0 - beta_2 ** step
    for k, p in params.items():
        g = grads[k] + weight_decay * p
        m, v = state[k]
        m = beta_1 * m + (1.0 - beta_1) * g
        v = beta_2 * v + (1.0 - beta_2) * g * g
        new_s[k] = (m, v)
        new_p[k] = p - lr * (m / c1) / (jnp.sqrt(v / c2) + epsilon)
    return new_p, new_s


OPTIMIZERS = {"sgd": (sgd_init, sgd_step), "adam": (adam_init, adam_step)}
