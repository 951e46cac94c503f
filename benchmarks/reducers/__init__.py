"""One module per reducer kind: `compute(args, run, measured, trace)` gives
the metric's value, or None where it finds nothing to read."""
