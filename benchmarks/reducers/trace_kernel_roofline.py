"""A kernel family's share of its roofline, from the device trace: the least
time the chip could take for the work the algorithm needs in the traced
steps (the larger of FLOPs / peak and bytes / bandwidth, lib/flops.py) over
the device time of the trace's ops whose names match.
args: {"patterns": [regex, ...], "need": "<function of lib.flops>"}."""

from lib import flops, xplane


def compute(args, run, measured, trace):
    steps = measured.get("notes", {}).get("traced_steps")
    if trace is None or not steps:
        return None
    chip = min(trace["events"])
    seconds, n = xplane.matching_seconds(trace["events"][chip],
                                         args["patterns"])
    if n == 0 or seconds <= 0:
        return None
    need_flops, need_bytes = getattr(flops, args["need"])(
        run.config, measured["batch"] // measured["chips"],
        int(run.traffic["seq_len"]))
    least, bound = flops.roofline_seconds(need_flops * steps,
                                          need_bytes * steps, run.peaks)
    measured.setdefault("notes", {})[f"roofline_bound.{args['need']}"] = bound
    return 100.0 * least / seconds
