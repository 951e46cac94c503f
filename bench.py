"""Benchmark harness: ResNet-50 synthetic-data training throughput.

The reference's headline harness (examples/cnn/benchmark.py:85-87) measures
`throughput = niters * batch * world / (end - start)` on ResNet-50 with
synthetic data. The reference publishes no numbers, so ``vs_baseline``
reports against our own recorded TPU run when one is given
(BENCH_BASELINE env), else 1.0.

One process that needs a chip: ``main()`` fails at once when
``jax.devices()[0]`` is not a TPU, then runs every leg in this process.
A leg that raises ends the run with its traceback and a non-zero exit;
no metric line is printed for a run that did not finish. On success it
prints ONE JSON line
{"metric", "value", "unit", "vs_baseline", "platform", "device_kind",
"n_devices", ...legs}.

Timing is a host clock around back-to-back steps that end in
``jax.block_until_ready`` (``_step_seconds``), after a warm-up that
carries the compile.
"""

import json
import os
import subprocess
import sys
import time

import jax

# ResNet-50 @224x224: ~4.09 GMACs forward per image; 2 flops/MAC; a training
# step (fwd + bwd wrt activations + bwd wrt weights) is ~3x forward.
RESNET50_TRAIN_FLOPS_PER_IMAGE = 4.09e9 * 2 * 3


def _lm_train_flops_per_token(d, n_layers, seq, vocab, ff_mult=4,
                              causal=True):
    """Matmul training FLOPs per token for the bench transformer.

    Per layer: qkv+o projections 4d² params, MLP 2·d·(ff_mult·d);
    plus the d·V head (the fused CE still does the full matmul, just
    chunked). Forward = 2 FLOPs per param per token; training ≈ 3×
    forward (bwd wrt activations + weights). Attention scores/values
    add 4·S·d per layer, halved when causal. The flash backward's
    score recompute is NOT counted, so the reported MFU slightly
    understates actual hardware utilisation."""
    proj = 4 * d * d + 2 * d * (ff_mult * d)
    attn_flops = 4 * seq * d * (0.5 if causal else 1.0)  # already FLOPs
    per_token_fwd = 2 * (n_layers * proj + d * vocab) + \
        n_layers * attn_flops
    return 3 * per_token_fwd


# the bench LM's shape — single source for _measure_lm and the MFU math
LM_SHAPE = {"d_model": 512, "n_layers": 6, "seq": 1024, "vocab": 32000}

_GIT_REV_CACHE = []


def _git_rev():
    """Short commit hash stamped into the result (None outside a work
    tree — the chip tool's copy of the repo is not one). Cached:
    constant for the process lifetime."""
    if _GIT_REV_CACHE:
        return _GIT_REV_CACHE[0]
    try:
        out = subprocess.run(
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10)
        rev = out.stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        rev = None
    _GIT_REV_CACHE.append(rev)
    return rev


def _knob(env_var, choices, default, canon=str):
    """A tuning knob is its env pin or its default, nothing else:
    returns (value, "env" | "default"). A pin that is not one of
    ``choices`` is an error, not a silent demotion to the default."""
    raw = os.environ.get(env_var)
    if raw is None:
        return default, "default"
    if raw.lower() not in choices:
        raise ValueError(
            f"{env_var}={raw!r} is not {'|'.join(choices)}")
    return canon(raw.lower()), "env"


def _conv_layout():
    """Activation layout for the ResNet legs: BENCH_CONV_LAYOUT pin,
    else NCHW."""
    return _knob("BENCH_CONV_LAYOUT", ("nchw", "nhwc"), "NCHW",
                 canon=str.upper)


def _resnet_stem():
    """Stem for the ResNet legs: BENCH_RESNET_STEM pin (the
    space-to-depth variant is exact — tests pin parity), else conv7."""
    return _knob("BENCH_RESNET_STEM", ("conv7", "space_to_depth"),
                 "conv7")


def _fused_optim():
    """Fused-vs-reference optimizer update for the train legs:
    BENCH_FUSED_OPTIM pin, else reference."""
    return _knob("BENCH_FUSED_OPTIM", ("fused", "reference"),
                 "reference")


def _conv_epilogue():
    """Inference conv-epilogue fusion (BN scale/shift + ReLU in one
    Pallas pass, ops/fused_epilogue.py) for the inference/serving
    legs: BENCH_CONV_EPILOGUE pin, else reference."""
    return _knob("BENCH_CONV_EPILOGUE", ("fused", "reference"),
                 "reference")


def _compile_stats():
    """Process-wide compile telemetry snapshot: persistent-cache
    hits/misses plus the ``compile_seconds`` histogram's count/sum —
    diffed around each leg so its record shows what the leg paid in
    compiles and whether the cache served them."""
    from singa_tpu.aot import cache as aot_cache
    from singa_tpu.observability import metrics as _obs
    snap = aot_cache.snapshot()
    out = {"cache_hits": snap["hits"], "cache_misses": snap["misses"],
           "compiles": 0, "compile_seconds": 0.0}
    h = _obs.default_registry().get("compile_seconds")
    if h is not None:
        for series in h.to_doc()["series"]:
            out["compiles"] += int(series.get("count", 0))
            out["compile_seconds"] += float(series.get("sum", 0.0))
    return out


def _compile_delta(before):
    after = _compile_stats()
    return {k: round(after[k] - before[k], 3) if isinstance(after[k],
                                                            float)
            else after[k] - before[k] for k in before}


def _step_seconds(step_fn, out_of, niters):
    """Per-step seconds: a host clock around ``niters`` back-to-back
    (asynchronously dispatched) steps that ends when the last step's
    output is ready. The caller has warmed the step up, so no compile
    falls in the window."""
    t0 = time.perf_counter()
    r = None
    for _ in range(niters):
        r = step_fn()
    jax.block_until_ready(out_of(r))
    return (time.perf_counter() - t0) / niters



def _bf16_leg_dtype():
    """The dtype_name every bf16 ResNet measurement uses — the bench
    timing leg AND the probe legs that must decompose/steer the SAME
    compiled program (fusion profile, layout/stem A/B, b128, HBM).
    Default "bf16_mixed" (the policy program production training runs);
    BENCH_BF16_MODE=cast restores the legacy params-follow-bf16-input
    program for comparison. Returns (dtype_name, mode_label)."""
    mode = os.environ.get("BENCH_BF16_MODE", "bf16_mixed")
    if mode not in ("bf16_mixed", "cast"):
        print(f"bench: BENCH_BF16_MODE={mode!r} is not "
              "bf16_mixed|cast; using bf16_mixed", file=sys.stderr)
        mode = "bf16_mixed"
    return ("bfloat16" if mode == "cast" else "bf16_mixed"), mode


def _setup_resnet_step(dev, batch, image_size, depth, dtype_name,
                       layout="NCHW", stem=None, fused_optim=None):
    """Build + compile THE canonical benchmark ResNet train step (SGD
    momentum 0.9, weight_decay 1e-5, synthetic data) and return its
    step() closure.

    ``dtype_name``: "float32" | "bfloat16" (legacy ad-hoc input cast:
    params follow the bf16 input) | "bf16_mixed" (the framework's
    precision policy: fp32 masters + loss scaling, bf16 compute — what
    production training actually runs).

    ``fused_optim``: True/False pins the Pallas fused optimizer-update
    path; None resolves ``_fused_optim()`` (BENCH_FUSED_OPTIM pin,
    else reference)."""
    from singa_tpu import tensor, opt
    from singa_tpu.models import resnet
    import jax.numpy as jnp
    import numpy as np

    stem = stem or _resnet_stem()[0]
    if fused_optim is None:
        fused_optim = _fused_optim()[0] == "fused"
    model = resnet.create_model(depth=depth, num_classes=10, num_channels=3,
                                layout=layout, stem=stem)
    model.set_optimizer(opt.SGD(lr=0.1, momentum=0.9, weight_decay=1e-5,
                                fused=bool(fused_optim)))

    x = np.random.randn(batch, 3, image_size, image_size).astype(np.float32)
    y = np.eye(10)[np.random.randint(0, 10, batch)].astype(np.float32)
    tx = tensor.Tensor(data=x, device=dev, dtype=tensor.float32,
                       requires_grad=False)
    if dtype_name == "bfloat16":
        tx = tx.as_type(jnp.bfloat16)
    ty = tensor.Tensor(data=y, device=dev, dtype=tensor.float32,
                       requires_grad=False)

    model.compile([tx], is_train=True, use_graph=True,
                  policy="bf16_mixed" if dtype_name == "bf16_mixed"
                  else None)

    def step():
        out, loss = model(tx, ty)
        return loss

    step.model = model   # cost analysis is read off the same program
    return step


def _xla_step_flops(model):
    """Per-step FLOPs from XLA's cost analysis of the JUST-MEASURED
    compiled program (``Model.step_flops``) — the numerator of the
    measured-not-modeled MFU every leg reports alongside the analytic
    one. Costs one AOT re-lower of the already-compiled signature
    (cheap with the persistent compile cache warm); disable with
    BENCH_XLA_MFU=0."""
    if os.environ.get("BENCH_XLA_MFU", "1") == "0":
        return None
    return model.step_flops(compute=True)


def _timeline_capture(step_fn, force):
    """One profiled step's compute/collective/memcpy/host/idle
    decomposition (``observability.timeline`` over the SAME compiled
    program the leg just timed) — reported per leg so the MFU
    trajectory names WHAT to fix (exposed collectives vs input stalls
    vs HBM-bound fusions), not just that it moved. ``force`` blocks on
    the step output (the trace must outlive the device work). Disable
    with BENCH_TIMELINE=0."""
    if os.environ.get("BENCH_TIMELINE", "1") == "0":
        return None
    from singa_tpu import profiling as _prof
    from singa_tpu.observability import timeline as _tl
    events = []
    _prof.measure_step_fusions(lambda: force(step_fn()),
                               events_out=events)
    return _tl.compact(_tl.analyze(events))


def _peak_hbm(dev):
    """Peak-HBM high-water (bytes) via the shared observability helper
    (``observability.perf.hbm_stats``). NOTE: the peak is a
    process-lifetime high-water mark, so within one bench process
    later legs see earlier legs' peak too — the number is each leg's
    upper bound; a precise per-model peak needs a process of its
    own."""
    from singa_tpu.observability import perf as _obs_perf
    stats = _obs_perf.hbm_stats(dev.jax_device)
    return stats.get("peak_bytes_in_use") if stats else None


def _measure(dev, batch, niters, warmup, image_size, depth, dtype_name,
             layout="NCHW", stem=None, extras=None, fused_optim=None):
    """Returns (images/sec, step_ms); when the caller passes an
    ``extras`` dict, ``xla_flops_per_step``, ``peak_hbm_bytes``, the
    compile delta and the step timeline are recorded into it."""
    cc0 = _compile_stats()
    step = _setup_resnet_step(dev, batch, image_size, depth, dtype_name,
                              layout=layout, stem=stem,
                              fused_optim=fused_optim)
    loss = None
    for _ in range(warmup):
        loss = step()
    jax.block_until_ready(loss.data)

    dt = _step_seconds(step, lambda l: l.data, niters)
    if extras is not None:
        extras["xla_flops_per_step"] = _xla_step_flops(step.model)
        extras["peak_hbm_bytes"] = _peak_hbm(dev)
        extras["compile"] = _compile_delta(cc0)
        extras["timeline"] = _timeline_capture(
            step, lambda loss: jax.block_until_ready(loss.data))
    return batch / dt, dt * 1e3


def _ride_alongs(res, prefix, extras):
    """Fold a leg's extras into the result under its prefix: peak HBM
    (see _peak_hbm's monotonicity caveat), what the leg paid in compiles
    and whether the persistent cache served them, and the step-timeline
    decomposition."""
    for key, name in (("peak_hbm_bytes", "hbm_peak_bytes"),
                      ("compile", "compile"), ("timeline", "timeline")):
        if extras.get(key):
            res[prefix + name] = extras[key]


def run_bench(batch=32, niters=50, warmup=8, image_size=224, depth=50):
    """Every leg, in this process, on the chip. A leg that raises ends
    the run: nothing here catches it."""
    from singa_tpu import device
    from singa_tpu.aot import cache as aot_cache
    from singa_tpu.observability.metrics import device_peak_flops

    dev = device.create_tpu_device()
    jd = dev.jax_device
    aot_cache.install()
    # TPUs publish one dense matmul peak per generation — the bf16 MXU
    # figure. At XLA's default precision fp32 matmul/conv inputs run as
    # bf16 MXU passes with fp32 accumulation, so the bf16 figure is the
    # hardware ceiling for the fp32 leg too: its MFU is labeled
    # ``mfu_denominator: bf16_peak``.
    peak = device_peak_flops(jd)
    layout, layout_src = _conv_layout()
    stem, stem_src = _resnet_stem()
    fused_mode, fused_src = _fused_optim()

    def _mfu_xla(flops_per_step, rate, units_per_step):
        """achieved/peak from XLA-counted per-step flops + the measured
        rate (units/s ÷ units/step = steps/s) — the measured-not-modeled
        MFU each leg reports beside its analytic estimate."""
        if not (flops_per_step and units_per_step):
            return None
        return flops_per_step * rate / units_per_step / peak

    fp32_extras = {}
    throughput, step_ms = _measure(
        dev, batch, niters, warmup, image_size, depth, "float32",
        layout=layout, stem=stem, extras=fp32_extras)
    res = {
        "throughput": throughput,
        "step_ms": step_ms,
        "mfu": throughput * RESNET50_TRAIN_FLOPS_PER_IMAGE / peak,
        "mfu_xla": _mfu_xla(fp32_extras.get("xla_flops_per_step"),
                            throughput, batch),
        "mfu_denominator": "bf16_peak",
        "conv_layout": layout,
        "conv_layout_src": layout_src,
        "resnet_stem": stem,
        "resnet_stem_src": stem_src,
        "fused_optim": fused_mode,
        "fused_optim_src": fused_src,
        "platform": jd.platform,
        "device_kind": jd.device_kind,
        "n_devices": len(jax.devices()),
        "git": _git_rev(),
    }
    _ride_alongs(res, "", fp32_extras)
    # bf16 variant — POLICY-DRIVEN by default: Model.compile(
    # policy="bf16_mixed") keeps fp32 masters + dynamic loss scaling and
    # runs conv/matmul compute in the MXU's native precision. This is
    # what production mixed-precision training actually executes.
    # BENCH_BF16_MODE=cast restores the old ad-hoc leg (params follow a
    # bf16 input) for comparison.
    if os.environ.get("BENCH_BF16", "1") != "0":
        leg_dtype, bf16_mode = _bf16_leg_dtype()
        res["bf16_mode"] = bf16_mode
        bf16_extras = {}
        bt, bs = _measure(dev, batch, niters, warmup, image_size, depth,
                          leg_dtype, layout=layout, stem=stem,
                          extras=bf16_extras)
        res["bf16_throughput"] = bt
        res["bf16_step_ms"] = bs
        res["bf16_mfu"] = bt * RESNET50_TRAIN_FLOPS_PER_IMAGE / peak
        res["bf16_mfu_xla"] = _mfu_xla(
            bf16_extras.get("xla_flops_per_step"), bt, batch)
        _ride_alongs(res, "bf16_", bf16_extras)
    # transformer-LM leg (secondary metric exercising the Pallas
    # flash-attention path; the headline stays ResNet-50)
    if os.environ.get("BENCH_LM", "1") != "0":
        lm_flops = _lm_train_flops_per_token(
            LM_SHAPE["d_model"], LM_SHAPE["n_layers"], LM_SHAPE["seq"],
            LM_SHAPE["vocab"])
        lm_extras = {}
        res["lm_tokens_per_sec"] = _measure_lm(dev, extras=lm_extras)
        res["lm_mfu"] = res["lm_tokens_per_sec"] * lm_flops / peak
        res["lm_mfu_xla"] = _mfu_xla(
            lm_extras.get("xla_flops_per_step"),
            res["lm_tokens_per_sec"], lm_extras.get("tokens_per_step"))
        _ride_alongs(res, "lm_", lm_extras)
        # what the LM leg measured: fused-CE-head or full-logits path
        res["lm_fused_head"] = \
            os.environ.get("BENCH_LM_FUSED", "1") != "0"
        # bf16 LM: compute_dtype=bfloat16 puts the whole transformer
        # stack (params + attention matmuls) in MXU-native precision —
        # the LM counterpart of the CNN bf16 leg
        if os.environ.get("BENCH_LM_BF16", "1") != "0":
            lmb_extras = {}
            res["lm_bf16_tokens_per_sec"] = _measure_lm(
                dev, compute_dtype="bfloat16", extras=lmb_extras)
            res["lm_bf16_mfu"] = \
                res["lm_bf16_tokens_per_sec"] * lm_flops / peak
            res["lm_bf16_mfu_xla"] = _mfu_xla(
                lmb_extras.get("xla_flops_per_step"),
                res["lm_bf16_tokens_per_sec"],
                lmb_extras.get("tokens_per_step"))
            _ride_alongs(res, "lm_bf16_", lmb_extras)
    # serving leg: decode tok/s + p99 per-token latency of the
    # continuous-batching engine
    if os.environ.get("BENCH_SERVE", "1") != "0":
        res["serving"] = _measure_serving(dev)
    # sharded serving leg (BENCH_SERVING_SHARDED=1 opt-in: it needs a
    # ≥4-device mesh): the GSPMD (batch × model) engine's decode tok/s
    # + per-device KV/HBM bytes beside the unsharded serving record
    if os.environ.get("BENCH_SERVING_SHARDED", "0") == "1":
        res["serving_sharded"] = _measure_serving_sharded(dev)
    # serving load-sweep leg: the PAGED/speculative engine driven with
    # synthetic Poisson load across slots × prefill_len × speculative_k
    # configs; tok/s + p99 curves per config
    # (tools/bench_report.py renders the curves + winner per SLO target)
    if os.environ.get("BENCH_SERVING_SWEEP", "1") != "0":
        res["serving_sweep"] = _measure_serving_sweep(dev)
    # disaggregated-pool serving leg (BENCH_SERVING_DISAGG=1 opt-in:
    # it compiles three engines): one prefill + two decode replicas
    # behind the FleetRouter's prefix-affinity transfer path under
    # Poisson load — TTFT p99 on the prefill pool and per-token
    # p50/p99 on the decode pool, the SLO split disaggregation buys
    if os.environ.get("BENCH_SERVING_DISAGG", "0") == "1":
        res["serving_disagg"] = _measure_serving_disagg(dev)
    # quant leg (singa_tpu.quant): int8 weight-only inference — ResNet
    # img/s + LM tok/s + serving decode tok/s + quantized-checkpoint
    # bytes on disk, each with its MFU where one is defined
    if os.environ.get("BENCH_QUANT", "1") != "0":
        res["quant"] = _measure_quant(dev, batch=batch,
                                      image_size=image_size,
                                      depth=depth, peak=peak)
    return res


def _measure_quant(dev, batch=32, image_size=224, depth=50, niters=20,
                   warmup=3, peak=None, lm_batch=8, lm_seq=256):
    """The quant leg: int8 weight-only INFERENCE throughput
    (``quant.quantize_params`` + in-graph dequant — the 4x-less-HBM
    deployment form) plus the quantized serving engine and the
    bytes-on-disk shrink of a quantized checkpoint.

    MFU is reported per sub-leg against the same peak the training legs
    use (inference = 2 FLOPs/param/unit, no backward)."""
    import tempfile

    import numpy as np

    from singa_tpu import quant, tensor
    from singa_tpu.models import resnet, transformer

    out = {"batch": batch, "depth": depth, "image_size": image_size}
    cc0 = _compile_stats()

    # -- int8 ResNet inference img/s ------------------------------------
    model = resnet.create_model(depth=depth, num_classes=10,
                                num_channels=3,
                                layout=_conv_layout()[0],
                                stem=_resnet_stem()[0])
    x = np.random.RandomState(0).randn(
        batch, 3, image_size, image_size).astype(np.float32)
    tx = tensor.Tensor(data=x, device=dev, requires_grad=False)
    model.compile([tx], is_train=False, use_graph=True)
    with tempfile.TemporaryDirectory() as td:
        # fp32 twin FIRST (quantize_params is one-way), then the int8
        # archive the same save route writes once the model is quantized
        fp32_zip = os.path.join(td, "fp32.zip")
        model.save_states(fp32_zip)
        q_report = quant.quantize_params(model)
        int8_zip = os.path.join(td, "int8.zip")
        model.save_states(int8_zip)
        out["ckpt_fp32_bytes"] = os.path.getsize(fp32_zip)
        out["ckpt_int8_bytes"] = os.path.getsize(int8_zip)
        out["ckpt_ratio"] = round(
            out["ckpt_fp32_bytes"] / out["ckpt_int8_bytes"], 2)
    out["quantized_tensors"] = len(q_report)
    model.eval()
    o = None
    for _ in range(warmup):
        o = model(tx)
    jax.block_until_ready(o.data)
    dt = _step_seconds(lambda: model(tx), lambda t: t.data, niters)
    out["resnet_img_s"] = batch / dt
    # inference: fwd only (no 3x training multiplier)
    if peak:
        out["resnet_mfu"] = out["resnet_img_s"] * \
            (RESNET50_TRAIN_FLOPS_PER_IMAGE / 3) / peak
    # conv-epilogue choice (ops/fused_epilogue.py — BN scale/shift +
    # ReLU in one pass): the kernel only fires inside a traced
    # forward, so the fused sub-leg times a JITTED inference and
    # reports it as its own metric — the eager resnet_img_s above
    # stays comparable across runs. The choice + source always report.
    ep_mode, ep_src = _conv_epilogue()
    out["conv_epilogue"], out["conv_epilogue_src"] = ep_mode, ep_src
    if ep_mode == "fused":
        from singa_tpu.ops import fused_epilogue as _fe

        def _fwd(arr):
            t = tensor.Tensor(data=arr, device=dev,
                              requires_grad=False)
            with model._policy_scope():
                return model.forward(t).data

        with _fe.enabled_scope(True):
            jf = jax.jit(_fwd)
            o = None
            for _ in range(warmup):
                o = jf(tx.data)
            jax.block_until_ready(o)
            dt2 = _step_seconds(lambda: jf(tx.data), lambda t: t,
                                niters)
        out["resnet_img_s_fused_epilogue"] = batch / dt2
    del model, tx

    # -- int8 LM inference tok/s ----------------------------------------
    lm = transformer.TransformerLM(
        LM_SHAPE["vocab"], d_model=LM_SHAPE["d_model"], n_heads=8,
        n_layers=LM_SHAPE["n_layers"], max_len=lm_seq, tp=False)
    ids = np.random.RandomState(0).randint(
        0, LM_SHAPE["vocab"], (lm_batch, lm_seq)).astype(np.float32)
    ti = tensor.Tensor(data=ids, device=dev, requires_grad=False)
    lm.compile([ti], is_train=False, use_graph=True)
    quant.quantize_params(lm)
    lm.eval()
    o = None
    for _ in range(warmup):
        o = lm(ti)
    jax.block_until_ready(o.data)
    dt = _step_seconds(lambda: lm(ti), lambda t: t.data, niters)
    out["lm_tok_s"] = lm_batch * lm_seq / dt
    if peak:
        lm_fwd_flops = _lm_train_flops_per_token(
            LM_SHAPE["d_model"], LM_SHAPE["n_layers"], lm_seq,
            LM_SHAPE["vocab"]) / 3
        out["lm_mfu"] = out["lm_tok_s"] * lm_fwd_flops / peak
    del lm, ti

    # -- quantized serving decode tok/s ----------------------------------
    serve = _measure_serving(dev, policy="int8_weight_only")
    out["serving_decode_tok_s"] = serve["decode_tok_s"]
    out["serving_p99_token_s"] = serve["p99_token_s"]
    out["hbm_peak_bytes"] = _peak_hbm(dev)
    out["compile"] = _compile_delta(cc0)
    return out


def _measure_serving(dev, slots=4, max_len=96, prefill_len=16,
                     n_requests=16, new_tokens=32, policy=None):
    """The serving leg: decode throughput and tail token latency
    of the continuous-batching engine over a small TransformerLM.

    A private metrics registry keeps bench runs out of the process
    SLO series; the numbers come from the engine's own histograms —
    ``decode_tok_s`` is generated tokens over summed decode-tick time,
    ``p99_token_s`` the p99 of ``serve_token_seconds`` (the quantile
    summaries the snapshot now carries). The leg also asserts the
    serve-path invariant: the decode program traced exactly once."""
    import numpy as np

    from singa_tpu import tensor
    from singa_tpu.models import transformer
    from singa_tpu.observability import metrics as obs_metrics

    cc0 = _compile_stats()
    vocab = 512
    model = transformer.TransformerLM(vocab, d_model=128, n_heads=4,
                                      n_layers=2, max_len=max_len,
                                      tp=False)
    model.eval()
    model(tensor.Tensor(data=np.zeros((1, prefill_len), np.float32),
                        device=dev, requires_grad=False))
    reg = obs_metrics.MetricsRegistry()
    eng = model.compile_serving(slots=slots, max_len=max_len,
                                prefill_len=prefill_len, policy=policy,
                                registry=reg)
    rng = np.random.RandomState(0)
    futs = [eng.submit(rng.randint(1, vocab,
                                   (int(rng.randint(1, prefill_len)),)),
                       max_new_tokens=new_tokens)
            for _ in range(n_requests)]
    # warmup: compile both programs off the clock
    eng.run_until_idle()
    for f in futs:
        f.result(timeout=1)

    wave = _measure_decode_wave(
        eng, reg,
        lambda: [eng.submit(
            rng.randint(1, vocab, (int(rng.randint(1, prefill_len)),)),
            max_new_tokens=new_tokens) for _ in range(n_requests)])
    # step-timeline probe AFTER the measured wave (a profiled tick
    # inside it would decouple the token count from the observed
    # decode time): a tiny all-ticks-profiled wave records the serving
    # decode's bucket decomposition beside the SLO numbers
    timeline = None
    if os.environ.get("BENCH_TIMELINE", "1") != "0":
        from singa_tpu.observability import timeline as _tl
        eng._profile_every = 1
        probe = [eng.submit(rng.randint(1, vocab, (4,)),
                            max_new_tokens=4) for _ in range(2)]
        eng.run_until_idle()
        for f in probe:
            f.result(timeout=1)
        timeline = _tl.compact(eng.last_timeline)
    eng.stop()
    return {
        **wave,
        **({"timeline": timeline} if timeline else {}),
        "slots": slots, "new_tokens": new_tokens,
        "n_requests": n_requests,
        "policy": str(policy) if policy is not None else None,
        "hbm_peak_bytes": _peak_hbm(dev),
        "compile": _compile_delta(cc0),
    }


def _measure_decode_wave(eng, reg, submit):
    """One steady-state serving wave against an already-WARM engine:
    ``submit()`` enqueues the wave and returns its futures. The
    decode-token accounting (each prefill samples one token OUTSIDE
    any decode tick, so the throughput numerator is decode-produced
    tokens only) and the histogram-delta p50/p99 math live HERE so
    the serving and serving_sharded legs measure the same thing by
    construction. Asserts the no-retrace pin; returns the SLO dict."""
    from singa_tpu.observability.export import series_quantiles

    def _series():
        return reg.get("serve_token_seconds").to_doc()["series"][0]

    tok0 = reg.get("serve_tokens_total").total()
    pre0 = reg.get("serve_prefill_total").total()
    before = _series()
    futs = submit()
    t0 = time.perf_counter()
    eng.run_until_idle()
    wall = time.perf_counter() - t0
    for f in futs:
        f.result(timeout=1)
    info = eng.compiled_step_info()
    assert info["n_traces"] == 1, f"decode retraced: {info}"
    tok = reg.get("serve_tokens_total").total() - tok0
    tok -= reg.get("serve_prefill_total").total() - pre0
    after = _series()
    # warmup ticks carry the XLA compile: the reported numbers are the
    # STEADY-state wave, so subtract the pre-wave series
    delta = {
        "count": after["count"] - before["count"],
        "sum": after["sum"] - before["sum"],
        "buckets": [[le, ca - cb] for (le, ca), (_le, cb)
                    in zip(after["buckets"], before["buckets"])],
    }
    q = series_quantiles(delta)
    return {
        "decode_tok_s": (tok / delta["sum"]) if delta["sum"] else None,
        "p99_token_s": q.get("p99"),
        "p50_token_s": q.get("p50"),
        "wall_tok_s": tok / wall if wall > 0 else None,
    }


def _measure_serving_sharded(dev, slots=4, max_len=96, prefill_len=16,
                             n_requests=16, new_tokens=32,
                             model_shards=2):
    """The ``serving_sharded`` leg: the SAME small TransformerLM
    as the serving leg, compiled with ``model_shards=2`` over a
    (batch × model) GSPMD mesh — decode tok/s, per-device KV/HBM
    bytes, and a greedy token-parity spot-check against a
    single-device engine (a sharded leg that silently diverged must
    never report a throughput number). Needs ≥ 2·model_shards devices
    and raises otherwise."""
    import numpy as np

    from singa_tpu import tensor
    from singa_tpu.models import transformer
    from singa_tpu.observability import metrics as obs_metrics

    n_dev = len(jax.devices())
    if n_dev < 2 * model_shards:
        raise RuntimeError(
            f"serving_sharded needs a ≥{2 * model_shards}-device mesh "
            f"(have {n_dev})")
    cc0 = _compile_stats()
    vocab = 512
    model = transformer.TransformerLM(vocab, d_model=128, n_heads=4,
                                      n_layers=2, max_len=max_len,
                                      tp=False)
    model.eval()
    model(tensor.Tensor(data=np.zeros((1, prefill_len), np.float32),
                        device=dev, requires_grad=False))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, vocab, (int(rng.randint(1, prefill_len)),))
               for _ in range(n_requests)]

    # parity spot-check (greedy, short) before the measured wave
    ref_eng = model.compile_serving(
        slots=slots, max_len=max_len, prefill_len=prefill_len,
        registry=obs_metrics.MetricsRegistry())
    ref_futs = [ref_eng.submit(p, max_new_tokens=4) for p in prompts[:4]]
    ref_eng.run_until_idle()
    ref_toks = [f.result(timeout=1)["tokens"] for f in ref_futs]
    ref_eng.stop()

    reg = obs_metrics.MetricsRegistry()
    eng = model.compile_serving(
        slots=slots, max_len=max_len, prefill_len=prefill_len,
        model_shards=model_shards, registry=reg)
    futs = [eng.submit(p, max_new_tokens=4) for p in prompts[:4]]
    eng.run_until_idle()           # warmup: compiles off the clock
    toks = [f.result(timeout=1)["tokens"] for f in futs]
    assert toks == ref_toks, "sharded greedy tokens diverged"

    wave = _measure_decode_wave(
        eng, reg,
        lambda: [eng.submit(p, max_new_tokens=new_tokens)
                 for p in prompts])
    info = eng.compiled_step_info()
    eng.stop()
    return {
        **wave,
        "slots": slots, "new_tokens": new_tokens,
        "n_requests": n_requests,
        "mesh": info["mesh"],
        "model_shards": info["model_shards"],
        "kv_per_device_bytes": info["kv_per_device_bytes"],
        "kv_global_bytes": info["kv_global_bytes"],
        "token_parity": True,
        "hbm_peak_bytes": _peak_hbm(dev),
        "compile": _compile_delta(cc0),
    }


def _measure_serving_disagg(dev, slots=4, max_len=96, prefill_len=16,
                            n_requests=24, new_tokens=32, rps=8.0,
                            seed=0):
    """The ``serving_disagg`` leg: the SAME small TransformerLM
    split into disaggregated pools — one prefill replica transferring
    every sealed KV snapshot to one of two decode replicas through a
    ``FleetRouter``'s prefix-affinity routing — under seeded Poisson
    load. Banks the SLO split the architecture exists for: TTFT p99
    measured on the PREFILL pool (admission + chunked prefill, no
    decode ticks competing) and per-token p50/p99 measured on the
    DECODE pool (steady decode, no prefill bubbles), plus decode
    tok/s, transfer count, and the affinity hit ratio. Half the
    prompts share a prefix so affinity has something to hit."""
    import numpy as np

    from singa_tpu import tensor
    from singa_tpu.models import transformer
    from singa_tpu.observability import metrics as obs_metrics
    from singa_tpu.observability.export import series_quantiles
    from singa_tpu.serving import FleetRouter, ServingReplica

    cc0 = _compile_stats()
    vocab = 512
    model = transformer.TransformerLM(vocab, d_model=128, n_heads=4,
                                      n_layers=2, max_len=max_len,
                                      tp=False)
    model.eval()
    model(tensor.Tensor(data=np.zeros((1, prefill_len), np.float32),
                        device=dev, requires_grad=False))
    kw = dict(slots=slots, max_len=max_len, prefill_len=prefill_len,
              kv_layout="paged", kv_block_size=4)
    preg = obs_metrics.MetricsRegistry()
    dregs = [obs_metrics.MetricsRegistry() for _ in range(2)]
    pe = model.compile_serving(pool_role="prefill", registry=preg,
                               **kw)
    des = [model.compile_serving(pool_role="decode", registry=r, **kw)
           for r in dregs]
    rreg = obs_metrics.MetricsRegistry()
    reps = [ServingReplica(pe, name="p0", registry=preg).start()]
    reps += [ServingReplica(d, name=f"d{i}",
                            registry=dregs[i]).start()
             for i, d in enumerate(des)]
    rt = FleetRouter(reps, registry=rreg)

    rng = np.random.RandomState(seed)
    shared = rng.randint(1, vocab, (max(2, prefill_len // 2),))

    def mk_prompt():
        if rng.rand() < 0.5:
            tail = rng.randint(
                1, vocab,
                (int(rng.randint(1, max(2, prefill_len
                                        - shared.size + 1))),))
            return np.concatenate([shared, tail])[:prefill_len]
        return rng.randint(1, vocab,
                           (int(rng.randint(1, prefill_len + 1)),))

    try:
        # warmup: both pools compile off the clock
        futs = [rt.submit(mk_prompt(), max_new_tokens=new_tokens,
                          timeout=120) for _ in range(2)]
        for f in futs:
            f.result(timeout=120)

        def _series(reg, name):
            # a pool replica the affinity hash hasn't routed to yet
            # has an empty histogram — treat it as all-zero
            m = reg.get(name)
            series = m.to_doc()["series"] if m is not None else []
            return series[0] if series else None

        def _delta(a, b):
            if a is None:
                return None
            if b is None:
                return dict(a, buckets=[list(x) for x in a["buckets"]])
            return {"count": a["count"] - b["count"],
                    "sum": a["sum"] - b["sum"],
                    "buckets": [[le, ca - cb] for (le, ca), (_le, cb)
                                in zip(a["buckets"], b["buckets"])]}

        def _merge(ds):
            ds = [d for d in ds if d is not None]
            return {"count": sum(d["count"] for d in ds),
                    "sum": sum(d["sum"] for d in ds),
                    "buckets": [[row[0][0],
                                 sum(r[1] for r in row)] for row
                                in zip(*(d["buckets"] for d in ds))]}

        ttft0 = _series(preg, "serve_ttft_seconds")
        tpot0 = [_series(r, "serve_token_seconds") for r in dregs]
        tok0 = sum(r.get("serve_tokens_total").total() for r in dregs)
        t0 = time.perf_counter()
        futs = []
        for _ in range(n_requests):
            futs.append(rt.submit(mk_prompt(),
                                  max_new_tokens=new_tokens,
                                  timeout=120))
            time.sleep(float(rng.exponential(1.0 / rps)))
        for f in futs:
            f.result(timeout=120)
        wall = time.perf_counter() - t0
        # the no-retrace pin, per role: decode replicas trace their
        # decode program exactly once; the prefill replica decodes
        # only on colocate fallback (0 traces when the pool is clean)
        for e in des:
            info = e.compiled_step_info()
            assert info["n_traces"] == 1, f"decode retraced: {info}"
        assert pe.compiled_step_info()["n_traces"] <= 1, \
            f"prefill-side decode retraced: {pe.compiled_step_info()}"
        tok = sum(r.get("serve_tokens_total").total()
                  for r in dregs) - tok0
        ttft_q = series_quantiles(_delta(
            _series(preg, "serve_ttft_seconds"), ttft0))
        d = _merge([_delta(_series(r, "serve_token_seconds"), t)
                    for r, t in zip(dregs, tpot0)])
        q = series_quantiles(d)
        pools = rt.pools_summary()
        return {
            "prefill_ttft_p99_s": ttft_q.get("p99"),
            "decode_p99_token_s": q.get("p99"),
            "decode_p50_token_s": q.get("p50"),
            "decode_tok_s": (tok / d["sum"]) if d["sum"] else None,
            "wall_tok_s": tok / wall if wall > 0 else None,
            "transferred": pools["transfers"]["transferred"],
            "colocate_fallback":
                pools["transfers"]["colocate_fallback"],
            "affinity_hit_ratio": pools["affinity"]["hit_ratio"],
            "slots": slots, "new_tokens": new_tokens,
            "n_requests": n_requests, "offered_rps": rps,
            "decode_replicas": len(des),
            "hbm_peak_bytes": _peak_hbm(dev),
            "compile": _compile_delta(cc0),
        }
    finally:
        for r in reps:
            r.drain(timeout=60)


# default serving_sweep grid: (kv_layout, slots, prefill_len,
# speculative_k). The ring 4×16 row is the PR-7 baseline the paged
# rows are judged against; the k>0 rows measure what speculation buys
# under the same load. BENCH_SWEEP_CONFIGS trims/extends it as
# "layout:slots:prefill:k" comma-separated triples.
SWEEP_GRID = (
    ("ring", 4, 16, 0),
    ("paged", 4, 16, 0),
    ("paged", 4, 16, 4),
    ("paged", 2, 8, 0),
    ("paged", 2, 8, 4),
)


def _parse_sweep_grid():
    env = os.environ.get("BENCH_SWEEP_CONFIGS")
    if not env:
        return SWEEP_GRID
    grid = []
    for part in env.split(","):
        try:
            lay, slots, pf, k = part.strip().split(":")
            if lay not in ("ring", "paged"):
                raise ValueError(lay)
            grid.append((lay, int(slots), int(pf), int(k)))
        except ValueError:
            print(f"bench: ignoring malformed BENCH_SWEEP_CONFIGS "
                  f"entry {part!r} (want ring|paged:slots:prefill:k)",
                  file=sys.stderr)
    return tuple(grid) or SWEEP_GRID


def _measure_serving_sweep(dev, grid=None, n_requests=12,
                           new_tokens=24, rps=None, seed=0):
    """The ``serving_sweep`` leg: one small TransformerLM served
    under synthetic POISSON load (seeded exponential inter-arrivals,
    open loop on the background serve thread) across a grid of
    (kv_layout, slots, prefill_len, speculative_k) configs. Each
    config reports steady-state ``decode_tok_s`` (decode tokens over
    summed tick time), ``wall_tok_s`` (tokens over the whole loaded
    window — queueing included, what the fleet actually delivers),
    tick-latency p50/p99, TTFT p99, and — for paged rows — the prefix
    cache hit count (half the generated prompts share a prefix) and
    the speculative accepted ratio. Warmup/compile happens off the
    clock (closed-loop wave before the Poisson window); the no-retrace
    pin is asserted per config like the plain serving leg."""
    import numpy as np

    from singa_tpu import tensor
    from singa_tpu.models import transformer
    from singa_tpu.observability import metrics as obs_metrics
    from singa_tpu.observability.export import series_quantiles

    grid = grid if grid is not None else _parse_sweep_grid()
    rps = float(rps if rps is not None
                else os.environ.get("BENCH_SWEEP_RPS", "8"))
    vocab = 512
    max_pf = max(cfg[2] for cfg in grid)
    model = transformer.TransformerLM(vocab, d_model=128, n_heads=4,
                                      n_layers=2,
                                      max_len=max_pf + new_tokens + 8,
                                      tp=False)
    model.eval()
    model(tensor.Tensor(data=np.zeros((1, max_pf), np.float32),
                        device=dev, requires_grad=False))
    out = {"n_requests": n_requests, "new_tokens": new_tokens,
           "offered_rps": rps, "poisson_seed": seed, "configs": []}
    for lay, slots, pf, spec_k in grid:
        rng = np.random.RandomState(seed)
        reg = obs_metrics.MetricsRegistry()
        kw = dict(slots=slots, max_len=pf + new_tokens,
                  prefill_len=pf, registry=reg)
        if lay == "paged":
            # block_size 4 so the generated prompts actually span
            # full blocks and the shared prefix is shareable
            kw.update(kv_layout="paged", kv_block_size=4,
                      speculative_k=spec_k)
        eng = model.compile_serving(**kw)
        shared = rng.randint(1, vocab, (max(2, pf // 2),))

        def mk_prompt():
            if rng.rand() < 0.5:
                tail = rng.randint(
                    1, vocab,
                    (int(rng.randint(1, max(2, pf - shared.size + 1))),))
                return np.concatenate([shared, tail])[:pf]
            return rng.randint(1, vocab,
                               (int(rng.randint(1, pf + 1)),))

        # warmup: compile both programs off the clock (synchronous)
        futs = [eng.submit(mk_prompt(), max_new_tokens=new_tokens)
                for _ in range(2)]
        eng.run_until_idle()
        for f in futs:
            f.result(timeout=5)

        def _series(name):
            return reg.get(name).to_doc()["series"][0]

        tok0 = reg.get("serve_tokens_total").total()
        pre0 = reg.get("serve_prefill_total").total()
        before = _series("serve_token_seconds")
        ttft_before = _series("serve_ttft_seconds")
        eng.start()
        t0 = time.perf_counter()
        futs = []
        for _ in range(n_requests):
            futs.append(eng.submit(mk_prompt(),
                                   max_new_tokens=new_tokens))
            time.sleep(float(rng.exponential(1.0 / rps)))
        for f in futs:
            f.result(timeout=120)
        wall = time.perf_counter() - t0
        info = eng.compiled_step_info()
        assert info["n_traces"] == 1, \
            f"decode retraced in sweep config {lay}:{slots}:{pf}:" \
            f"{spec_k}: {info}"
        tok = reg.get("serve_tokens_total").total() - tok0
        tok -= reg.get("serve_prefill_total").total() - pre0
        after = _series("serve_token_seconds")

        def _delta(a, b):
            return {"count": a["count"] - b["count"],
                    "sum": a["sum"] - b["sum"],
                    "buckets": [[le, ca - cb] for (le, ca), (_le, cb)
                                in zip(a["buckets"], b["buckets"])]}

        d = _delta(after, before)
        q = series_quantiles(d)
        ttft_q = series_quantiles(_delta(_series("serve_ttft_seconds"),
                                         ttft_before))
        # report what actually RAN, not what was requested: a declined
        # layout/speculation must not label its row with the claimed
        # config (the report's winner table steers deployments on it)
        rec = {"kv_layout": info["kv_layout"], "slots": slots,
               "prefill_len": pf,
               "speculative_k": info["speculative_k"],
               "decode_tok_s": (tok / d["sum"]) if d["sum"] else None,
               "wall_tok_s": tok / wall if wall > 0 else None,
               "p99_token_s": q.get("p99"), "p50_token_s": q.get("p50"),
               "ttft_p99_s": ttft_q.get("p99")}
        if info["kv_layout"] == "paged":
            rec["prefix_cache_hits"] = \
                int(reg.get("prefix_cache_hits_total").total())
            ratio = reg.get("speculative_accepted_ratio")
            rec["speculative_accepted_ratio"] = \
                ratio.value() if ratio is not None \
                and info["speculative_k"] else None
        eng.drain(timeout=30)
        eng.stop()
        out["configs"].append(rec)
    return out


def _setup_lm_step(dev, batch=8, seq=None, compute_dtype=None):
    """Build + compile THE canonical benchmark transformer-LM train step
    and return its step() closure (single source for the timing leg and
    the HBM-footprint probe)."""
    seq = seq or LM_SHAPE["seq"]
    from singa_tpu import tensor, opt
    from singa_tpu.models import transformer
    import jax.numpy as jnp
    import numpy as np

    # fused CE head: the (B,S,32000) logits never materialise in the
    # train step (1 GiB fp32 at these shapes) — disable via
    # BENCH_LM_FUSED=0 to measure the full-logits path
    fused = os.environ.get("BENCH_LM_FUSED", "1") != "0"
    m = transformer.TransformerLM(LM_SHAPE["vocab"],
                                  d_model=LM_SHAPE["d_model"], n_heads=8,
                                  n_layers=LM_SHAPE["n_layers"],
                                  max_len=seq, tp=False,
                                  remat=False,
                                  fused_head_chunk=8192 if fused
                                  else None,
                                  compute_dtype=jnp.bfloat16
                                  if compute_dtype == "bfloat16" else None)
    m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, LM_SHAPE["vocab"], (batch, seq)) \
        .astype(np.float32)
    tgt = np.roll(ids, -1, 1)
    ti = tensor.Tensor(data=ids, device=dev, requires_grad=False)
    tt = tensor.Tensor(data=tgt, device=dev, requires_grad=False)
    m.compile([ti], is_train=True, use_graph=True)

    def step():
        _, loss = m(ti, tt)
        return loss

    step.model = m       # probes read cost analysis off the same program
    return step


def _measure_lm(dev, batch=8, seq=None, niters=20, warmup=3,
                compute_dtype=None, extras=None):
    seq = seq or LM_SHAPE["seq"]
    cc0 = _compile_stats()
    step = _setup_lm_step(dev, batch=batch, seq=seq,
                          compute_dtype=compute_dtype)
    loss = None
    for _ in range(warmup):
        loss = step()
    jax.block_until_ready(loss.data)

    dt = _step_seconds(step, lambda l: l.data, niters)
    if extras is not None:
        extras["xla_flops_per_step"] = _xla_step_flops(step.model)
        extras["tokens_per_step"] = batch * seq
        extras["peak_hbm_bytes"] = _peak_hbm(dev)
        extras["compile"] = _compile_delta(cc0)
        extras["timeline"] = _timeline_capture(
            step, lambda loss: jax.block_until_ready(loss.data))
    return batch * seq / dt



def main():
    d = jax.devices()[0]
    if d.platform != "tpu":
        print(f"bench: needs a TPU; jax.devices()[0] is {d.platform!r} "
              f"({d.device_kind!r}). No number is reported from another "
              "backend.", file=sys.stderr)
        return 1
    res = run_bench(batch=int(os.environ.get("BENCH_BATCH", "32")),
                    niters=int(os.environ.get("BENCH_ITERS", "50")))
    baseline = float(os.environ.get("BENCH_BASELINE", "0") or 0)
    throughput = res.pop("throughput")
    out = {
        "metric": "resnet50_synthetic_images_per_sec_per_chip",
        "value": round(throughput, 2),
        "unit": "images/sec",
        "vs_baseline": round(throughput / baseline, 3)
        if baseline > 0 else 1.0,
    }
    # every leg rides along in the one line (MFU, bf16 leg, LM
    # tokens/s, serving and quant blocks, timelines, compile deltas);
    # tools/bench_report.py reads them by these names
    out.update((k, round(v, 4) if isinstance(v, float) else v)
               for k, v in res.items() if v is not None)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
