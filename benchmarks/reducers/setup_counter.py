"""A compile-cache counter as set-up left it. args: {"counter": "misses"}."""


def compute(args, run, measured, trace):
    return run.cache_at_setup[args["counter"]]
