"""Driver for the compiled train step: `Model.compile(is_train=True,
use_graph=True, policy=...)` then `model(tx, ty)` on a fixed batch made from
the seed and held on the device, steps dispatched back to back. With
`"parallel": "distopt"` in the traffic file the optimizer is wrapped in
`opt.DistOpt` over a data mesh of the cell's chips.

Set-up builds ONE object (the compiled step with its state), loads weights
made from the seed, drives it through its first three steps by the window's
own call and feed, and hands the same object to the window. The readings of
those steps (each loss, per-leaf norm of the first gradient as the optimizer
got it, per-leaf norm of the parameters' change after three steps) are what
`check` holds against the plain reference once the program's state is freed.
"""

import collections
import gc
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from lib import compare, weights
from lib.program import Handle, build_model, load_weights, singa_device

CHECK_STEPS = 3
DISPATCH_AHEAD = 2


def _make_feed(config, job, batch, seed):
    """(program inputs as jax arrays, the same batch as the reference takes
    it), all rows different, made on the device from the seed."""
    key = jax.random.fold_in(weights.key_for(seed), 0x0FEED)
    k1, k2 = jax.random.split(key)
    if config["feed"] == "images":
        hw = int(config["image_size"])
        x = jax.random.normal(k1, (batch, int(config["num_channels"]), hw,
                                   hw), jnp.float32)
        labels = jax.random.randint(k2, (batch,), 0,
                                    int(config["num_classes"]))
        onehot = jax.nn.one_hot(labels, int(config["num_classes"]),
                                dtype=jnp.float32)
        return (x, onehot), (x, labels)
    if config["feed"] == "tokens":
        ids = jax.random.randint(k1, (batch, int(job["seq_len"])), 1,
                                 int(config["vocab_size"]))
        targets = jnp.roll(ids, -1, axis=1)
        return (ids.astype(jnp.float32), targets.astype(jnp.float32)), \
            (ids, targets)
    raise ValueError(f"unknown feed {config['feed']!r}")


def _make_optimizer(job):
    from singa_tpu import opt
    spec = dict(job["optimizer"])
    kind = spec.pop("kind")
    return {"sgd": opt.SGD, "adam": opt.Adam}[kind](**spec)


def _call_step(h):
    """The one call the window makes: a train step on the held batch.
    Returns the loss as a device array."""
    return h.model(h.tx, h.ty)[1].data


def setup(run):
    from singa_tpu import opt, tensor
    h = Handle()
    config, job = run.config, run.traffic
    h.batch = int(job["batch_per_chip"]) * run.chips
    h.dev = singa_device(run.devices[0].platform)
    h.dev.SetRandSeed(run.seed & 0x7FFFFFFF)
    reference = importlib.import_module(f"lib.references.{config['reference']}")
    h.specs = reference.param_specs(config)
    (x, y), _ = _make_feed(config, job, h.batch, run.seed)
    h.tx = tensor.Tensor(data=x, device=h.dev, requires_grad=False)
    h.ty = tensor.Tensor(data=y, device=h.dev, requires_grad=False)

    optimizer = _make_optimizer(job)
    h.base_opt = optimizer
    h.mesh_set = False
    if job.get("parallel") == "distopt":
        from singa_tpu.parallel import mesh as mesh_mod
        from singa_tpu.parallel.communicator import set_mesh
        msh = mesh_mod.make_mesh(run.devices, mesh_mod.MeshConfig())
        set_mesh(msh)
        h.mesh_set = True
        optimizer = opt.DistOpt(optimizer, world_size=run.chips)
        optimizer.communicator.mesh = msh
    run.phase("feed_made")
    h.model = build_model(config, job)
    h.model.set_optimizer(optimizer)
    h.model.compile([h.tx], is_train=True, use_graph=True,
                    policy=config["precision"])
    run.phase("model_compiled")

    h.names, h.param_tensors = load_weights(h.model, config, h.specs,
                                            run.seed)
    run.phase("weights_loaded")

    # the first steps, through the window's own call and feed
    losses = []
    first_grad = None
    for step in range(CHECK_STEPS):
        losses.append(_call_step(h))
        if step == 0:
            jax.block_until_ready(losses[0])
            run.phase("first_step")
            first_grad = _first_grad_norms(h, run.seed)
    change = weights.norms_against_init(
        h.specs, run.seed, [t.data for t in h.param_tensors], -1.0)
    h.readings = {"losses": [float(np.asarray(v)) for v in losses],
                  "first_grad": np.asarray(first_grad, np.float64),
                  "change": np.asarray(change, np.float64)}
    return h


def _first_grad_norms(h, seed):
    """Per-leaf norm of the first gradient as the optimizer got it, worked
    out from the optimizer's state after one step: momentum SGD holds
    buf = g + wd * p0; Adam holds m = (1 - beta_1) * g."""
    aux = h.base_opt._aux
    if hasattr(h.base_opt, "momentum"):
        bufs = [aux[f"{n}:momentum"].data for n in h.names]
        return weights.norms_against_init(
            h.specs, seed, bufs, -float(h.base_opt.weight_decay))
    ms = [aux[f"{n}:m"].data for n in h.names]
    return weights.norms_against_init(h.specs, seed, ms, 0.0) \
        / (1.0 - float(h.base_opt.beta_1))


def _timed_steps(h, seconds, annotate):
    """Dispatch steps back to back for `seconds`, at most DISPATCH_AHEAD in
    flight; the clock stops when the last step's loss is ready."""
    pending = collections.deque()
    steps = 0
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while time.perf_counter() < t_end:
        with annotate("bench.train_step"):
            pending.append(_call_step(h))
        steps += 1
        if len(pending) > DISPATCH_AHEAD:
            with annotate("bench.block_until_ready"):
                jax.block_until_ready(pending.popleft())
    with annotate("bench.block_until_ready"):
        last = jax.block_until_ready(pending[-1])
    return steps, time.perf_counter() - t0, last


def window(run, h):
    from jax.profiler import TraceAnnotation
    job = run.traffic
    jax.block_until_ready(_call_step(h))        # the queue starts empty
    run.mark_setup_done()
    slice_s = float(job.get("trace_seconds", 1.0)) if run.trace_dir else 0.0
    steps, elapsed, last = _timed_steps(h, max(run.seconds - slice_s, 0.1),
                                        TraceAnnotation)
    traced_steps = 0
    if run.trace_dir:
        run.start_trace()
        try:
            with TraceAnnotation("bench.window"):
                traced_steps, _, last = _timed_steps(h, slice_s,
                                                     TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
    last = float(np.asarray(last))
    finite = bool(np.isfinite(last))
    return {"steps": steps, "window_s": elapsed, "batch": h.batch,
            "chips": run.chips, "attempted": steps + traced_steps,
            "failed": 0 if finite else steps + traced_steps,
            "notes": {"last_loss": last, "steps": steps,
                      "traced_steps": traced_steps,
                      "first_losses": h.readings["losses"]}}


def end_to_end(run, m):
    per_chip = m["steps"] * m["batch"] / m["window_s"] / m["chips"]
    if run.config["feed"] == "tokens":
        return {"train_tokens_per_s_per_chip":
                (per_chip * int(run.traffic["seq_len"]), "tokens/s/chip")}
    return {"train_images_per_s_per_chip": (per_chip, "img/s/chip")}


def release(run, h):
    """Free the program's state; keep only the readings (host numbers)."""
    evidence = h.readings
    if h.mesh_set:
        from singa_tpu.parallel.communicator import set_mesh
        set_mesh(None)
    for t in h.model._state_list or []:
        t.data = None
    h.__dict__.clear()
    gc.collect()
    return evidence


def check(run, readings, cast=None):
    """Follow the first three steps with the plain reference, at the timed
    batch, and compare. `cast` puts the control in the reference's place
    (tests and the limit-setting runs only)."""
    config, job = run.config, run.traffic
    batch = int(job["batch_per_chip"]) * run.chips
    _, ref_batch = _make_feed(config, job, batch, run.seed)
    ref = compare.reference_train(config, job["optimizer"], run.seed,
                                  ref_batch, CHECK_STEPS, cast=cast)
    return compare.train_numbers(readings, ref, run.cell["limits"])
