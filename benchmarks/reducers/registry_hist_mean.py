"""Mean of an engine-registry histogram over the untraced part of the
window: delta sum / delta count. args: {"histogram": key of the driver's
snapshots, "scale": multiplier}."""


def compute(args, run, measured, trace):
    a = measured["snap_start"][args["histogram"]]
    b = measured["snap_end"][args["histogram"]]
    n = b["count"] - a["count"]
    if n <= 0:
        return None
    return (b["sum"] - a["sum"]) / n * float(args.get("scale", 1.0))
