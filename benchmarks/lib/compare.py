"""The comparison that decides `correct`: the reference's side of it, and the
numbers held against their limits.

Training: each of the first steps' losses, the per-leaf norm of the first
gradient, and the per-leaf norm of the parameters' change after the steps.
The per-leaf numbers are the gap between the program's norm and the
reference's (not the norm of a difference), against the reference's norm of
that leaf or of the median leaf, whichever is larger; the worst leaf counts.
Leaves whose gradient is nought to rounding in the reference (under a
thousandth of the median leaf's: a key's bias under softmax) move under Adam
by round-off alone and are left out of the change.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from . import weights
from .references import chain, optim


def reference_train(config, opt_spec, seed, batch, steps, cast=None):
    """Drive the plain reference from the seed through `steps` steps on
    `batch`. Returns {"losses", "first_grad", "change"} like the driver's
    readings. `cast` names the control's arithmetic (lowprec.CASTS)."""
    ref = importlib.import_module(
        f"{__package__}.references.{config['reference']}")
    specs = ref.param_specs(config)
    names = [n for n, *_ in specs]
    stages = ref.stages(config, cast or "float32")
    hyper = dict(opt_spec)
    init, update = optim.OPTIMIZERS[hyper.pop("kind")]
    apply = jax.jit(lambda p, g, s, step: update(p, g, s, step, **hyper),
                    donate_argnums=(0, 2))
    norms = jax.jit(lambda g: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(g[n]))) for n in names]))
    x, labels = batch
    with jax.default_matmul_precision("highest"):
        params = weights.make(specs, seed)
        state = init(params)
        losses, first_grad = [], None
        for i in range(steps):
            loss, grads = chain.value_and_grad(stages, params, x, labels)
            losses.append(float(np.asarray(loss)))
            if i == 0:
                first_grad = np.asarray(norms(grads), np.float64)
            params, state = apply(params, grads, state, jnp.float32(i + 1))
            del grads
        change = weights.norms_against_init(
            specs, seed, [params[n] for n in names], -1.0)
    return {"losses": losses, "first_grad": first_grad,
            "change": np.asarray(change, np.float64)}


def _worst_leaf(prog, ref, counted=None):
    floor = float(np.median(ref))
    gap = np.abs(prog - ref) / np.maximum(ref, floor)
    if counted is not None:
        gap = np.where(counted, gap, 0.0)
    if not np.all(np.isfinite(gap)):
        return float("nan")
    return float(np.max(gap))


def train_numbers(prog, ref, limits):
    """{name: (value, limit)} for the numbers the cell's file gives limits
    for. A reading that is not finite compares as not-a-number, which is
    over any limit."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    counted = ref["first_grad"] >= 1e-3 * np.median(ref["first_grad"])
    values = {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr)))
        if np.all(np.isfinite(lp)) else float("nan"),
        "first_grad_norm_gap": _worst_leaf(prog["first_grad"],
                                           ref["first_grad"]),
        "change_norm_gap": _worst_leaf(prog["change"], ref["change"],
                                       counted),
    }
    return {k: (values[k], float(lim)) for k, lim in limits.items()}


def served_numbers(gaps, limits):
    """{name: (value, limit)}: `logit_gap` is the widest gap by which a
    served token's logit lies below the reference's best; `unanswered` the
    count of sampled requests with no or a malformed answer."""
    values = {"logit_gap": float(np.max(gaps["gaps"]))
              if len(gaps["gaps"]) else float("nan"),
              "unanswered": float(gaps["unanswered"])}
    return {k: (values[k], float(lim)) for k, lim in limits.items()}
