"""The benchmark's yardstick: peaks, operation counts, the trace reduction,
the load generator and the plain references. Nothing here imports the
program (`singa_tpu`)."""
