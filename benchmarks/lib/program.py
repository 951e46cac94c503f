"""What both drivers need of the program under test: build the model the
configuration's file names, put weights made from the seed into it, and the
bag a driver keeps its state in between set-up, window and release.
"""

import importlib

from . import weights


def _factory(path):
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)


def build_model(config, job):
    prog = config["program"]
    args = [config[k] for k in prog.get("args", [])]
    kwargs = {k: config[v] for k, v in prog.get("kwargs", {}).items()}
    kwargs.update(job.get("model_kwargs", {}))
    return _factory(prog["factory"])(*args, **kwargs)


class Handle:
    pass


def singa_device(platform):
    from singa_tpu import device
    return device.create_cpu_device() if platform == "cpu" \
        else device.create_tpu_device()


def load_weights(model, config, specs, seed):
    """Put weights made from the seed (lib/weights.py) into the program's
    parameters, leaf for leaf by name. Returns (the program's names, its
    tensors) in the order of `specs`."""
    prefix = config["program"]["prefix"]
    names = [f"{prefix}.{n}" for n, *_ in specs]
    states = model.get_states()
    missing = [n for n in names if n not in states]
    extra = [n for n, t in states.items()
             if t.requires_grad and n not in set(names)]
    if missing or extra:
        raise SystemExit(f"the reference's leaves and the program's differ: "
                         f"missing {missing[:4]}, unknown {extra[:4]}")
    made = weights.make(specs, seed)
    for (n, shape, *_), full in zip(specs, names):
        if tuple(states[full].shape) != tuple(shape):
            raise SystemExit(f"{full}: program {states[full].shape}, "
                             f"reference {shape}")
        states[full].data = made[n]
    return names, [states[n] for n in names]
