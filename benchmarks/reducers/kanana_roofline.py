"""A kernel family's share of its roofline in a latent-attention expert LM's
train step, from the device trace: the least time the chip could take for
the work the traced steps need (a need function of lib/flops_kanana.py: the
larger of FLOPs / peak and bytes / bandwidth) over the device time of the
ops whose names match. The time includes the forward kernels that
rematerialisation runs again, which the need does not count.
args: {"patterns": [regex, ...], "need": "<function of lib.flops_kanana>",
"pairs": "<gauge>"}; with "pairs", the need takes the pairs a step that the
program counts: the named gauge of the process's registry summed over its
series (models/kanana.py sets `moe_pairs_here{layer}`, the newest step's,
the traced steps being the window's last). None where the program sets no
such gauge."""

import math

from lib import flops, flops_kanana, xplane


def gauge_sum(name):
    """The sum of `name`'s series in the process's registry, or None."""
    from singa_tpu.observability.metrics import default_registry
    metric = default_registry().get(name)
    if metric is None:
        return None
    values = [s["value"] for s in metric.to_doc()["series"]]
    if not values or any(not math.isfinite(v) for v in values):
        return None
    return sum(values)


def compute(args, run, measured, trace):
    steps = measured.get("notes", {}).get("traced_steps")
    if trace is None or not steps:
        return None
    chip = min(trace["events"])
    seconds, n = xplane.matching_seconds(trace["events"][chip],
                                         args["patterns"])
    if n == 0 or seconds <= 0:
        return None
    extra = ()
    if "pairs" in args:
        pairs = gauge_sum(args["pairs"])
        if not pairs:
            return None
        extra = (pairs,)
    need_flops, need_bytes = getattr(flops_kanana, args["need"])(
        run.config, measured["batch"] // measured["chips"],
        int(run.traffic["seq_len"]), *extra)
    least, bound = flops.roofline_seconds(need_flops * steps,
                                          need_bytes * steps, run.peaks)
    measured.setdefault("notes", {})[f"roofline_bound.{args['need']}"] = \
        bound
    return 100.0 * least / seconds
