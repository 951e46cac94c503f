"""The whole step's share of the chip's peak for a looped LM: analytic model
FLOPs a token (lib/flops_ouro.py: 3x forward, recompute not counted) x tokens
a second a chip / the chip's bf16 peak, in percent, over the untraced part of
the window. How many layer applications and exit heads a token's forward
runs is what the program says it runs: the gauges
`model_layer_applications{model}` and `model_exit_heads{model}` it sets at
build (`model` = the configuration's program prefix); the widths come from
the configuration. None where the program sets no such gauge."""

from lib import flops_ouro


def gauge(name, model):
    """The value of `name{model=...}` in the process's registry, or None."""
    from singa_tpu.observability.metrics import default_registry
    metric = default_registry().get(name)
    if metric is None:
        return None
    for series in metric.to_doc()["series"]:
        if series["labels"].get("model") == model:
            return series["value"]
    return None


def compute(args, run, measured, trace):
    model = run.config["program"]["prefix"]
    apps = gauge("model_layer_applications", model)
    heads = gauge("model_exit_heads", model)
    if not measured.get("steps") or not apps or not heads:
        return None
    seq = int(run.traffic["seq_len"])
    tokens = measured["steps"] * measured["batch"] * seq / measured["chips"]
    per_token = flops_ouro.train_flops_per_token(run.config, seq, apps, heads)
    return 100.0 * per_token * tokens / measured["window_s"] \
        / run.peaks["bf16_flops_per_s"]
