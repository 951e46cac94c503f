"""The whole step's share of the chip's peak: analytic model FLOPs a unit of
work (lib/flops.py, from the configuration's shapes; 3x forward, recompute
not counted) x units a second a chip / the chip's bf16 peak, in percent.
Read from the clock over the untraced part of the window.
args: {"flops": "<function of lib.flops>", "unit": "images" | "tokens"}."""

from lib import flops


def compute(args, run, measured, trace):
    if not measured.get("steps"):
        return None
    units = measured["steps"] * measured["batch"] / measured["chips"]
    if args["unit"] == "tokens":
        seq = int(run.traffic["seq_len"])
        units *= seq
        per_unit = getattr(flops, args["flops"])(run.config, seq)
    else:
        per_unit = getattr(flops, args["flops"])(run.config)
    return 100.0 * per_unit * units / measured["window_s"] \
        / run.peaks["bf16_flops_per_s"]
