"""Mean duration of the program's spans of one name inside the untraced part
of the window (flight recorder). args: {"spans": key of the driver's
measurements, "scale": multiplier}."""


def compute(args, run, measured, trace):
    durations = measured.get(args["spans"]) or []
    if not durations:
        return None
    return sum(durations) / len(durations) * float(args.get("scale", 1.0))
