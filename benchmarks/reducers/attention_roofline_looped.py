"""The flash kernels' share of their roofline in a looped LM's train step,
from the device trace: the least time the chip could take for what causal
attention needs in the traced steps over every layer application
(lib/flops_ouro.attention_train_need: the larger of FLOPs / peak and bytes /
bandwidth) over the device time of the ops whose names match. The time
includes the forward kernels that rematerialisation runs again, which the
need does not count. args: {"patterns": [regex, ...]}."""

from lib import flops, flops_ouro, xplane


def compute(args, run, measured, trace):
    steps = measured.get("notes", {}).get("traced_steps")
    if trace is None or not steps:
        return None
    chip = min(trace["events"])
    seconds, n = xplane.matching_seconds(trace["events"][chip],
                                         args["patterns"])
    if n == 0 or seconds <= 0:
        return None
    need_flops, need_bytes = flops_ouro.attention_train_need(
        run.config, measured["batch"] // measured["chips"],
        int(run.traffic["seq_len"]))
    least, bound = flops.roofline_seconds(need_flops * steps,
                                          need_bytes * steps, run.peaks)
    measured.setdefault("notes", {})["roofline_bound.looped_attention"] = \
        bound
    return 100.0 * least / seconds
