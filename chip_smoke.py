#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user
calls, at the full width of the models the repo benchmarks (depth cut,
random weights from a seed), and checks what comes out by the repo's own
means. It exits 0 only if every phase passed ON A TPU; with no chip it
fails at once and never carries on on the CPU. Nothing here catches a
phase's failure: the first one that raises ends the run with its
traceback and a non-zero exit.

    python chip_smoke.py             # on the chip (what the driver runs)
    python chip_smoke.py --dry-run   # sandbox debugging only: the same
                                     # control flow at tiny sizes, Pallas
                                     # in interpret mode, no TPU needed;
                                     # prints "DRY RUN platform=..." and
                                     # never the final result line

Phases (each prints one JSON line with platform, device_kind, n_devices):

- device          jax.devices(); the kind is in the peak table; the
                  compile-cache directory in effect.
- resnet50_train  ResNet-50, SGD momentum, bf16_mixed, b32 at 224 px
                  through Model.compile: finite loss that moves, one
                  trace, donated state, state on the chip; the step time
                  by two completion barriers (information only).
- lm_train        TransformerLM d512/8 heads/6 layers/S1024/V32000, fused
                  CE head, bf16_mixed: the step's HLO holds the Mosaic
                  flash forward and both backward kernels; the kernel and
                  its three gradients against a plain softmax attention.
- kernels         every other Pallas kernel, compiled (not interpreted)
                  against its reference: the four fused optimizer
                  updates, the conv epilogue in both layouts with and
                  without the residual, the flash forward with a
                  position delta (the ring-attention form), the ring
                  decode kernel in both its forms against
                  kv_cache.write_token + attend.
- serve           the same LM through Model.compile_serving, ring and
                  paged KV, mixed prompt lengths through submit(): every
                  future resolves, decode traced once, chosen tokens
                  agree with the eager forward's logits; the ring
                  engine's compiled decode program holds the ring_decode
                  Mosaic call once a level and rewrites no level; a
                  call of either program passes at most 100 buffers
                  (serve_program_arg_buffers, printed for both).
- moe_serve       at a toy size, in bf16: the share-aware expert layer
                  (parallel/moe.py; few rows, every held expert on every
                  row; many rows, the pairs sorted and worked off in
                  tiles) against its plain form, and the parallel-block
                  LM (models/cohere_moe.py) served with prompts longer
                  than the window until the window rings have wrapped,
                  against its own eval forward (plain masked attention);
                  its rings of two lengths go through ring_decode too.
- hybrid_serve    at a toy size, in bf16: the decoder-hybrid-decoder LM
                  (models/phi4flash.py: state-space layers, window rings
                  and one full ring that the cross layer reads, a Gated
                  Memory Unit) served with greedy requests longer than
                  its window, against its own eval forward (one chunked
                  scan and plain masked attention over whole sequences);
                  its decode program holds ring_decode once a ring and
                  the read-only ring_attend once a cross layer.
- latent_serve    at toy widths but the real latent row (512 + 64 numbers
                  a token a block, kept once for all heads), in bf16: the
                  shortcut-connected LM (models/longcat_flash.py: two
                  latent-attention blocks, two dense FFNs and one expert
                  layer with identity experts a layer) served with greedy
                  requests, prefill in the attention's expanded form and
                  decode in its absorbed form, against its own eval
                  forward (expanded throughout); its decode program holds
                  the latent_decode Mosaic call once a level.
- multichip       with >= 4 devices: ResNet-50 through DistOpt (the
                  shard_map driver) and through the GSPMD step with FSDP,
                  the LM at dp2 x tp2; state on four devices, FSDP bytes
                  about a quarter, collectives in the HLO, first-step
                  loss against the one-chip phases. Otherwise says
                  "multichip: skipped (n_devices=N)".

The compile cache follows singa_tpu.aot.cache's rule
(JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_compile_cache);
the last phase line reports its hits and misses, so a second run in the
same place shows hits and no misses. The last line of standard output is
{"ok": true, "device": {"platform", "kind", "count"}}.
"""

import argparse
import gc
import json
import re
import sys
import time
import warnings

import numpy as np

SEED = 0
# ResNet-50 at random init on a random batch is sharp: at lr 1e-2 the
# loss climbs for the first steps (on the CPU in float32 as on the chip),
# at 2e-4 it falls from the first step
RESNET_LR = 2e-4
LM_LR = 0.05

# the expert share and the mixed rings are checked at one toy size on the
# chip and in the dry run alike (the benchmark measures the real one)
# (rings of 128 and 256 rows and four KV heads of 32: the least the ring
# decode kernel takes)
MOE_TOY = {"hidden": 128, "heads": 8, "kv_heads": 4, "head_dim": 32,
           "ff": 256, "experts": 16, "held": 4, "held_from": 4, "top_k": 4,
           "shared": 2, "window": 128, "layers": 4, "vocab": 512,
           "rows": (32, 300), "slots": 4, "max_len": 256,
           "prefill_len": 160, "new_tokens": 24}

# state-space layers, window rings of 128 and a full ring of 256 rows that
# the cross layer reads, two paired KV heads of 128: likewise one toy size
HYBRID_TOY = {"hidden": 256, "heads": 4, "kv_heads": 4, "ff": 512,
              "window": 128, "layers": 8, "vocab": 512, "slots": 4,
              "max_len": 256, "prefill_len": 160, "new_tokens": 24}

# two double layers over the REAL latent row (kv_lora_rank 512 + 64 rotary
# columns: the kernel's latent form as the benchmark's cell runs it), every
# other width small; 4 of 16 routed experts held, 8 identity experts
LATENT_TOY = {"hidden": 128, "heads": 4, "q_rank": 64, "kv_rank": 512,
              "nope": 32, "rope": 64, "v": 32, "ff": 256, "expert_ff": 128,
              "held": 4, "held_from": 4, "router": 24, "zero": 8,
              "top_k": 4, "layers": 2, "vocab": 512, "slots": 4,
              "max_len": 256, "prefill_len": 160, "new_tokens": 24}

FULL = {
    "resnet": {"depth": 50, "batch": 32, "image": 224, "steps": 5,
               "timed_steps": 20},
    "lm": {"d_model": 512, "n_heads": 8, "n_layers": 6, "seq": 1024,
           "vocab": 32000, "head_chunk": 8192, "batch": 8, "steps": 3},
    "optim_mlp": (2048, 1000, 77),
    "epilogue": [(32, 64, 112, 112), (32, 256, 56, 56)],
    "ring": {"batch": 2, "heads": 8, "seq": 512, "hd": 64},
    # (slots, KV heads, query heads a KV head, ring, head size, dtype)
    "ring_decode": {"d64": (8, 16, 1, 1024, 64, "bfloat16"),
                    "d128": (8, 1, 16, 2048, 128, "bfloat16")},
    "serve": {"slots": 4, "max_len": 256, "prefill_len": 64,
              "new_tokens": 12, "ref_len": 128},
    "moe": MOE_TOY,
    "hybrid": HYBRID_TOY,
    "latent": LATENT_TOY,
}
# --dry-run: the same control flow where a CPU can finish it. 224 px stays
# because the ResNet's 7x7 average pool needs the 7x7 final feature map.
DRY = {
    "resnet": {"depth": 18, "batch": 4, "image": 224, "steps": 3,
               "timed_steps": 2},
    "lm": {"d_model": 128, "n_heads": 4, "n_layers": 1, "seq": 128,
           "vocab": 512, "head_chunk": 256, "batch": 4, "steps": 3},
    "optim_mlp": (64, 72, 7),
    "epilogue": [(2, 8, 12, 12)],
    "ring": {"batch": 1, "heads": 2, "seq": 128, "hd": 64},
    "ring_decode": {"d64": (6, 2, 1, 256, 64, "float32"),
                    "d128": (6, 1, 4, 384, 128, "bfloat16")},
    "serve": {"slots": 4, "max_len": 128, "prefill_len": 16,
              "new_tokens": 4, "ref_len": 128},
    "moe": MOE_TOY,
    "hybrid": HYBRID_TOY,
    "latent": LATENT_TOY,
}


class Ctx:
    """What the phases share: the sizes, the singa device, and the
    first-step losses the multichip phase compares against."""

    def __init__(self, dry_run):
        self.dry = dry_run
        self.sizes = DRY if dry_run else FULL
        self.dev = None
        self.first_loss = {}
        self.lm = None


def _stamp():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "n_devices": len(jax.devices())}


def run_phase(name, fn, ctx):
    t0 = time.perf_counter()
    info = fn(ctx) or {}
    print(json.dumps({"phase": name, "ok": True, **_stamp(),
                      "seconds": round(time.perf_counter() - t0, 1),
                      **info}), flush=True)
    gc.collect()


def _put(ctx, array):
    from singa_tpu import tensor
    return tensor.Tensor(data=array, device=ctx.dev, requires_grad=False)


def _rel_err(got, want):
    """max|got - want| over max|want|, in float32."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) /
                 max(float(np.max(np.abs(want))), 1e-30))


def _compiled_for_chip(ctx, jitted, args, what, n=1):
    """Compile ``jitted`` for ``args`` and return the executable, having
    checked that it holds >= n Mosaic custom calls — i.e. that the
    kernel was compiled for the chip, not interpreted or declined.
    (Under --dry-run the kernels are interpreted and nothing is
    checked.)"""
    compiled = jitted.lower(*args).compile()
    if not ctx.dry:
        got = compiled.as_text().count("tpu_custom_call")
        assert got >= n, f"{what}: {got} Mosaic custom calls in the " \
            f"HLO, expected >= {n} — the kernel did not reach the chip"
    return compiled


def _ring_kernel_in_decode(ctx, eng, attends=0, kernel="ring_decode"):
    """The compiled decode program of a ring engine holds the
    ``ring_decode`` Mosaic call (``kernel``: ``latent_decode`` for
    latent levels) once a ring level, the read-only ``ring_attend`` once
    a layer that reads another's ring (``attends``) and no
    ``dynamic-update-slice`` of a level's size: the level is walked and
    written by the kernel, in place. Returns the count of the kernel's
    calls. (The dry run interprets the kernel; nothing is compiled for a
    chip.)"""
    if ctx.dry:
        return None
    from singa_tpu.aot import export as aot_export
    avals = aot_export.serving_program_avals(eng)[1]
    hlo = eng._decode.lower(*avals).compile().as_text()
    rings = [lv for lv in eng._cache if "k" in lv]
    count = lambda name: len(re.findall(                       # noqa: E731
        rf"^\s*%?{name}[.\d]* = .*custom-call\(", hlo, re.M))
    calls = count(kernel)
    assert calls == len(rings), \
        f"{calls} {kernel} calls in the decode program for " \
        f"{len(rings)} ring levels"
    assert count("ring_attend") == attends, \
        f"{count('ring_attend')} ring_attend calls for {attends} layers " \
        "that read another layer's ring"
    least = min(lv["k"].size for lv in rings)
    for dims in re.findall(r"= \w+\[([\d,]+)\]\S* dynamic-update-slice\(",
                           hlo):
        assert np.prod([int(d) for d in dims.split(",")]) < least, \
            f"the decode program rewrites [{dims}], a level's size"
    return calls


def _logits_readbacks(reg):
    """Program calls of an engine that brought their ``(rows, V)`` logits
    to the host: none for greedy requests, whose tokens the programs
    choose, and every call counted (``serve_readback_total``)."""
    counter = reg.get("serve_readback_total")
    n = sum(int(counter.value(program=p, what="logits"))
            for p in ("prefill", "decode"))
    assert n == 0 and counter.total() > 0, \
        f"{n} of {counter.total()} greedy program calls read their logits"
    return n


def _arg_buffers(reg):
    """Buffers a call of each serve program passes
    (``serve_program_arg_buffers``). The host pays for each one every
    tick: this LM's engine passes 6 matrices and 2 cache arrays a layer
    and 19 leaves besides (its vector roles are one stacked leaf each),
    67 at six layers, where a leaf a layer a role was 117."""
    gauge = reg.get("serve_program_arg_buffers")
    n = {p: int(gauge.value(program=p)) for p in ("prefill", "decode")}
    assert all(0 < v <= 100 for v in n.values()), \
        f"a TransformerLM engine passes {n} buffers a call: over 100"
    return n


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device(ctx):
    import jax
    from singa_tpu import device
    from singa_tpu.aot import cache as aot_cache
    from singa_tpu.observability import metrics

    d = jax.devices()[0]
    # None on the CPU (a dry run); raises for a TPU kind the table lacks
    peaks = metrics.device_peaks(d)
    assert ctx.dry or (d.platform == "tpu" and peaks), d
    ctx.dev = device.create_cpu_device() if d.platform == "cpu" \
        else device.create_tpu_device()
    assert ctx.dev.jax_device == d
    ctx.dev.SetRandSeed(SEED)
    pol = aot_cache.active()
    return {"cache_dir": pol.directory,
            "cache_entries_at_start": aot_cache.stats()["entries"],
            "peak_bf16_tflops": peaks and peaks["bf16_flops"] / 1e12,
            "peak_hbm_gb_s": peaks and peaks["hbm_bytes_per_s"] / 1e9}


# ---------------------------------------------------------------------------
# resnet50_train
# ---------------------------------------------------------------------------

def _build_resnet(ctx, optimizer, **compile_kw):
    """The flagship train step through the user's entry points, with
    its fixed batch. Every build reseeds the device, so each phase
    starts from the same weights."""
    from singa_tpu.models import resnet
    cfg = ctx.sizes["resnet"]
    ctx.dev.SetRandSeed(SEED)
    rng = np.random.RandomState(SEED)
    tx = _put(ctx, rng.randn(cfg["batch"], 3, cfg["image"],
                             cfg["image"]).astype(np.float32))
    ty = _put(ctx, np.eye(10, dtype=np.float32)[
        rng.randint(0, 10, cfg["batch"])])
    m = resnet.create_model(depth=ctx.sizes["resnet"]["depth"])
    m.set_optimizer(optimizer)
    m.compile([tx], is_train=True, use_graph=True, policy="bf16_mixed",
              **compile_kw)
    return m, tx, ty


def _two_barrier_step_times(step, n):
    """Seconds per step by (a) a host clock around n steps that ends in
    jax.block_until_ready, and (b) the slope between a short and a long
    run that each end in a scalar read back to the host. They agree
    where block_until_ready waits for the device."""
    import jax
    import jax.numpy as jnp

    def readback(loss):
        return float(np.asarray(jnp.sum(jnp.ravel(loss)[:1])))

    def seg(k, barrier):
        t0 = time.perf_counter()
        loss = None
        for _ in range(k):
            loss = step()
        barrier(loss)
        return time.perf_counter() - t0

    readback(step())                    # compile the readback reduction
    blocked = seg(n, jax.block_until_ready) / n
    n_small = max(1, n // 4)
    t_small, t_big = seg(n_small, readback), seg(n, readback)
    slope = (t_big - t_small) / (n - n_small)
    return blocked, slope


def phase_resnet50_train(ctx):
    from singa_tpu import opt
    cfg = ctx.sizes["resnet"]
    m, tx, ty = _build_resnet(ctx, opt.SGD(lr=RESNET_LR, momentum=0.9))
    t0 = time.perf_counter()
    first = float(m(tx, ty)[1].data)            # carries the compile
    compile_s = time.perf_counter() - t0
    losses = [first] + [float(m(tx, ty)[1].data)
                        for _ in range(cfg["steps"])]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], \
        f"loss does not fall on a fixed batch: {losses}"
    info = m.compiled_step_info()
    assert info["n_traces"] == 1, info["n_traces"]
    assert (info["donated_bytes"] or 0) > 0, info["donated_bytes"]
    want = {ctx.dev.jax_device}
    off = [t.name for t in m._state_list if t.data.devices() != want]
    assert not off, f"state not on {want}: {off[:5]}"
    ctx.first_loss["resnet"] = first

    blocked, slope = _two_barrier_step_times(
        lambda: m(tx, ty)[1].data, cfg["timed_steps"])
    return {"losses": [round(v, 4) for v in losses],
            "first_step_seconds": round(compile_s, 1),
            "n_traces": info["n_traces"],
            "donated_bytes": info["donated_bytes"],
            "state_bytes": info["state_bytes"],
            "step_ms_block_until_ready": round(blocked * 1e3, 3),
            "step_ms_readback_slope": round(slope * 1e3, 3),
            "barrier_ratio": round(blocked / slope, 3)}


# ---------------------------------------------------------------------------
# lm_train
# ---------------------------------------------------------------------------

def _build_lm(ctx, optimizer, tp=False, **compile_kw):
    """The LM train step and its fixed batch of token ids (inputs and
    next-token targets), reseeded like the ResNet."""
    from singa_tpu.models import transformer
    cfg = ctx.sizes["lm"]
    ctx.dev.SetRandSeed(SEED)
    ids = np.random.RandomState(SEED).randint(
        0, cfg["vocab"], (cfg["batch"], cfg["seq"])).astype(np.float32)
    ti, tt = _put(ctx, ids), _put(ctx, np.roll(ids, -1, 1))
    m = transformer.TransformerLM(
        cfg["vocab"], d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_layers=cfg["n_layers"], max_len=cfg["seq"], tp=tp,
        fused_head_chunk=cfg["head_chunk"])
    m.set_optimizer(optimizer)
    m.compile([ti], is_train=True, use_graph=True, policy="bf16_mixed",
              **compile_kw)
    return m, ti, tt


def _plain_attention(q, k, v, causal):
    """softmax(q k^T / sqrt(d)) v in float32 with nothing fused: the
    reference the flash kernels are judged against."""
    import jax
    import jax.numpy as jnp
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        mask = jnp.tril(jnp.ones(s.shape[-2:], bool))
        s = jnp.where(mask, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def _flash_vs_plain(ctx):
    """The flash kernel's output and its three gradients, at the shape
    and dtype the LM step feeds it, against plain attention computed at
    the highest matmul precision."""
    import jax
    import jax.numpy as jnp
    from singa_tpu.ops.attention import flash_attention
    cfg = ctx.sizes["lm"]
    shape = (cfg["batch"], cfg["n_heads"], cfg["seq"],
             cfg["d_model"] // cfg["n_heads"])
    rng = np.random.RandomState(SEED + 1)
    q, k, v, g = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                  for _ in range(4))

    def flash(q, k, v):
        out, vjp = jax.vjp(lambda a, b, c: flash_attention(
            a, b, c, True), q, k, v)
        return (out,) + vjp(g)

    def plain(q, k, v):
        out, vjp = jax.vjp(lambda a, b, c: _plain_attention(
            a, b, c, True), q, k, v)
        return (out,) + vjp(g.astype(jnp.float32))

    got = _compiled_for_chip(ctx, jax.jit(flash), (q, k, v),
                             "flash fwd+bwd", n=3)(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(plain)(q, k, v)
    # Tolerance 3e-2 of the largest reference value. The inputs are bf16
    # and exact in both; the kernels then round the probabilities and dS
    # to bf16 for their MXU passes (2^-9 relative each) and sum them over
    # up to 1024 keys, and the outputs round to bf16 once more (2^-9). A
    # wrong mask, scale or block index is off by the size of the values.
    errs = {n: _rel_err(a, b)
            for n, a, b in zip(("out", "dq", "dk", "dv"), got, want)}
    assert all(np.isfinite(np.asarray(a, np.float32)).all() for a in got)
    assert max(errs.values()) < 3e-2, errs
    return {n: round(e, 5) for n, e in errs.items()}


def phase_lm_train(ctx):
    from singa_tpu import opt
    from singa_tpu.ops import attention_mod as attention
    cfg = ctx.sizes["lm"]
    if not ctx.dry:
        assert not attention._interpret()
    m, ti, tt = _build_lm(ctx, opt.SGD(lr=LM_LR, momentum=0.9))
    t0 = time.perf_counter()
    first = float(m(ti, tt)[1].data)
    compile_s = time.perf_counter() - t0
    losses = [first] + [float(m(ti, tt)[1].data)
                        for _ in range(cfg["steps"])]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], \
        f"loss does not fall on a fixed batch: {losses}"
    info = m.compiled_step_info()
    assert info["n_traces"] == 1, info["n_traces"]
    assert (info["donated_bytes"] or 0) > 0
    out = {"losses": [round(v, 4) for v in losses],
           "first_step_seconds": round(compile_s, 1)}
    if not ctx.dry:
        # one forward and two backward kernels per layer, by the names
        # ops/attention.py gives its pallas_calls
        hlo = info["hlo"]
        calls = hlo.count("tpu_custom_call")
        assert calls >= 3 * cfg["n_layers"], \
            f"{calls} Mosaic custom calls in the LM step, expected " \
            f">= {3 * cfg['n_layers']}: flash attention declined"
        for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            assert name in hlo, f"no {name} kernel in the LM step's HLO"
        out["mosaic_custom_calls"] = calls
    ctx.first_loss["lm"] = first
    ctx.lm = m
    out["flash_vs_plain_rel_err"] = _flash_vs_plain(ctx)
    return out


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _optimizer_twins(ctx):
    """Each fused optimizer update against its reference twin, through
    Model.compile: the zoo's two-layer MLP, whose first weight tiles
    exactly into (rows, 128) blocks and whose second needs the pad path.
    Both twins start from the same seed and take two steps on one
    batch."""
    import jax
    from singa_tpu import opt
    from singa_tpu.models import mlp
    d_in, d_hid, d_out = ctx.sizes["optim_mlp"]
    rng = np.random.RandomState(SEED)
    x = rng.randn(64, d_in).astype(np.float32)
    y = np.eye(d_out, dtype=np.float32)[rng.randint(0, d_out, 64)]

    def train(optimizer):
        ctx.dev.SetRandSeed(SEED)
        tx, ty = _put(ctx, x), _put(ctx, y)
        m = mlp.create_model(perceptron_size=d_hid, num_classes=d_out)
        m.set_optimizer(optimizer)
        m.compile([tx], is_train=True, use_graph=True)
        for _ in range(2):
            m(tx, ty)
        rec = m._last_run_rec
        state = {t.name: np.asarray(jax.device_get(t.data))
                 for t in m._state_list}
        return m, rec, state

    makers = {
        "sgd": lambda f: opt.SGD(lr=0.05, momentum=0.9,
                                 weight_decay=1e-4, fused=f),
        "adam": lambda f: opt.Adam(lr=1e-3, fused=f),
        "rmsprop": lambda f: opt.RMSProp(lr=1e-3, fused=f),
        "adagrad": lambda f: opt.AdaGrad(lr=1e-2, fused=f),
    }
    errs = {}
    for kind, make in makers.items():
        mf, rec, fused = train(make(True))
        assert rec.get("fused_kinds") == [kind], \
            f"{kind}: the step fused {rec.get('fused_kinds')}"
        if not ctx.dry:
            # both weights (the bias vectors are under the size gate)
            calls = mf.compiled_step_info()["hlo"].count("tpu_custom_call")
            assert calls >= 2, f"fused {kind}: {calls} Mosaic calls"
        _, _, ref = train(make(False))
        assert fused.keys() == ref.keys()
        # float32 elementwise math on both sides; Mosaic and XLA may
        # contract multiply-adds and expand sqrt and divide differently,
        # which is a few units in the last place: 2e-5 relative, 1e-6
        # absolute for values near zero.
        for name in ref:
            np.testing.assert_allclose(
                fused[name], ref[name], rtol=2e-5, atol=1e-6,
                err_msg=f"fused {kind} vs reference: {name}")
        errs[kind] = max(_rel_err(fused[n], ref[n]) for n in ref)
    return {k: float(f"{v:.2e}") for k, v in errs.items()}


def _epilogue_vs_reference(ctx):
    """The conv epilogue kernel in both layouts, with and without the
    residual, at the two largest activations of the ResNet-50 trunk."""
    import jax
    from singa_tpu.ops import fused_epilogue, fused_optim
    worst = 0.0
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 64))
    for n, c, h, w in ctx.sizes["epilogue"]:
        scale = jax.random.uniform(next(keys), (c,)) + 0.5
        shift = jax.random.normal(next(keys), (c,))
        for layout, shape in (("NCHW", (n, c, h, w)),
                              ("NHWC", (n, h, w, c))):
            x = jax.random.normal(next(keys), shape)
            res = jax.random.normal(next(keys), shape)
            for residual in (None, res):
                def fused(x, residual=residual, layout=layout):
                    if residual is None:
                        return fused_epilogue.scale_shift_relu(
                            x, scale, shift, layout=layout)
                    return fused_epilogue.scale_shift_add_relu(
                        x, scale, shift, residual, layout=layout)

                what = f"epilogue {layout} {shape} " \
                    f"residual={residual is not None}"
                marks = []
                with fused_optim.trace_collector(marks):
                    got = _compiled_for_chip(ctx, jax.jit(fused), (x,),
                                             what)(x)
                # the kernel ran, not its in-module reference twin
                assert marks and set(marks) == {"epilogue"}, \
                    f"{what}: declined to the reference ({marks})"
                want = fused_epilogue._reference(x, scale, shift, layout,
                                                 residual)
                # one float32 multiply-add (plus one add) per element on
                # both sides; only the contraction can differ
                err = _rel_err(got, want)
                assert err < 1e-6, f"{what}: rel err {err}"
                worst = max(worst, err)
    return float(f"{worst:.2e}")


def _ring_form_vs_scan(ctx):
    """The flash forward with a position delta — what ring attention
    feeds it per ring step — against the scan path, for the two steps
    of a 2-shard causal ring seen from the second shard: its own block
    (delta 0) and the block before it (delta = the shard length)."""
    import jax
    import jax.numpy as jnp
    from singa_tpu.ops import attention_mod as attention
    cfg = ctx.sizes["ring"]
    shape = (cfg["batch"], cfg["heads"], cfg["seq"], cfg["hd"])
    rng = np.random.RandomState(SEED + 2)
    q, k, v = (jnp.asarray(rng.randn(*shape), jnp.float32)
               for _ in range(3))
    scale = 1.0 / np.sqrt(cfg["hd"])
    assert attention._pallas_blocks(q, k), "ring form declined"

    def kernel(q, k, v, delta):
        return attention._ring_partials(q, k, v, delta, True, scale, 512)

    def scan(q, k, v, delta):
        return attention._ring_partials_scan(q, k, v, delta, True, scale,
                                             512)

    errs = {}
    for delta in (0, cfg["seq"]):
        d = jnp.asarray(delta, jnp.int32)
        out, lse = _compiled_for_chip(
            ctx, jax.jit(kernel), (q, k, v, d),
            "flash fwd + pos_delta")(q, k, v, d)
        with jax.default_matmul_precision("highest"):
            ref_out, ref_lse = jax.jit(scan)(q, k, v, d)
        # float32 inputs that the kernel's MXU passes round to bf16
        # (2^-9 relative) against a float32 scan at the highest
        # precision: 2e-2 of the largest value, as for flash above
        errs[f"delta{delta}"] = max(_rel_err(out, ref_out),
                                    _rel_err(lse, ref_lse))
    assert max(errs.values()) < 2e-2, errs
    return {k: round(v, 5) for k, v in errs.items()}


def _ring_decode_vs_twins(ctx):
    """One decode tick of a ring level through the kernel
    (ops/ring_decode.py) against kv_cache.write_token + attend, in both
    forms of the kernel: head size 64 (XLA keeps that level with the
    ring on the lanes) with one query head a KV head, and head size 128
    (row-major) with a group of query heads on one KV head. Slots short
    of a block, on a block's edge, wrapped, and dead; the level after
    the tick is write_token's bit for bit where the slot is live and
    untouched where it is not."""
    import jax
    import jax.numpy as jnp
    from singa_tpu.serving import kv_cache
    rng = np.random.RandomState(SEED + 4)
    errs = {}
    for name, (W, n_kv, G, L, D, dtype) in ctx.sizes["ring_decode"].items():
        dtype = jnp.dtype(dtype)
        draw = lambda *shape: jnp.asarray(rng.randn(*shape), dtype)  # noqa: E731
        level = {"k": draw(W, n_kv, L, D), "v": draw(W, n_kv, L, D)}
        q, k_new, v_new = draw(W, n_kv * G, 1, D), draw(W, n_kv, D), \
            draw(W, n_kv, D)
        pos = np.resize([0, 130, L - 1, L, 3 * L + 7, L // 2, 5, 255], W)
        live = np.resize([True, True, True, True, True, False], W)
        scale = 1.0 / np.sqrt(D)
        assert kv_cache.ring_block(level), f"{name}: kernel declined"

        def kernel(level, q, k_new, v_new, pos, live):
            return kv_cache.decode_token(level, q, k_new, v_new, pos, live,
                                         scale)

        def twins(level, q, k_new, v_new, pos):
            level = kv_cache.write_token(level, k_new, v_new, pos)
            return kv_cache.attend(q, level, pos, scale), level

        args = (level, q, k_new, v_new, jnp.asarray(pos, jnp.int32))
        out, got = _compiled_for_chip(
            ctx, jax.jit(kernel), args + (jnp.asarray(live),),
            f"ring_decode {name}")(*args, jnp.asarray(live))
        ref, want = jax.jit(twins)(*args)
        bits = lambda a: np.ascontiguousarray(np.asarray(a)).view(np.uint8)  # noqa: E731
        for n in ("k", "v"):
            assert np.array_equal(bits(got[n])[live], bits(want[n])[live]), \
                f"{name}: {n} of a live slot differs from write_token's"
            assert np.array_equal(bits(got[n])[~live],
                                  bits(level[n])[~live]), \
                f"{name}: {n} of a dead slot was written"
        errs[name] = _rel_err(np.asarray(out, np.float32)[live],
                              np.asarray(ref, np.float32)[live])
    # probabilities rounded to the level's dtype for the value product,
    # and the output to it: 2^-8 relative each
    assert max(errs.values()) < 2e-2, errs
    return {k: round(v, 5) for k, v in errs.items()}


def phase_kernels(ctx):
    return {"fused_optim_rel_err": _optimizer_twins(ctx),
            "conv_epilogue_rel_err": _epilogue_vs_reference(ctx),
            "flash_pos_delta_rel_err": _ring_form_vs_scan(ctx),
            "ring_decode_rel_err": _ring_decode_vs_twins(ctx)}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def phase_serve(ctx):
    """The LM the lm_train phase trained, served twice: ring KV and
    paged KV. The sampled tokens are judged by logits, not by equality:
    with random weights the argmax turns on rounding."""
    from singa_tpu.observability import metrics as obs_metrics
    cfg, lm_cfg = ctx.sizes["serve"], ctx.sizes["lm"]
    m = ctx.lm
    m.eval()
    rng = np.random.RandomState(SEED + 3)
    lengths = [3, cfg["prefill_len"], cfg["prefill_len"] // 2, 1,
               cfg["prefill_len"] - 1, 7]
    prompts = [rng.randint(1, lm_cfg["vocab"], (n,)) for n in lengths]
    out = {}
    served = {}
    logits_readbacks = 0
    for kv_layout in ("ring", "paged"):
        kw = {"kv_layout": "paged"} if kv_layout == "paged" else {}
        reg = obs_metrics.MetricsRegistry()
        eng = m.compile_serving(
            slots=cfg["slots"], max_len=cfg["max_len"],
            prefill_len=cfg["prefill_len"], registry=reg, **kw)
        futs = [eng.submit(p, max_new_tokens=cfg["new_tokens"])
                for p in prompts]
        eng.run_until_idle()
        results = [f.result(timeout=5) for f in futs]
        info = eng.compiled_step_info()
        logits_readbacks += _logits_readbacks(reg)
        if kv_layout == "ring":
            out["ring_decode_calls"] = _ring_kernel_in_decode(ctx, eng)
        eng.stop()
        assert info["kv_layout"] == kv_layout and \
            "kv_layout_declined" not in info, info
        assert info["n_traces"] == 1, info
        for p, r in zip(prompts, results):
            assert r["prompt_len"] == len(p), r
            assert len(r["tokens"]) == cfg["new_tokens"], r
            assert all(0 <= t < lm_cfg["vocab"] for t in r["tokens"]), r
        served[kv_layout] = [r["tokens"] for r in results]
        out[kv_layout] = {"requests": len(results),
                          "decode_n_traces": info["n_traces"],
                          "prefill_n_traces": info["prefill_n_traces"],
                          "arg_buffers": _arg_buffers(reg)}

    # teacher-forced reference: the eager forward over prompt + generated
    # tokens, padded to a multiple of the flash block (causal attention:
    # the padding cannot reach back)
    L = cfg["ref_len"]
    worst = 0.0
    for kv_layout, tokens in served.items():
        ids = np.zeros((len(prompts), L), np.float32)
        for r, (p, toks) in enumerate(zip(prompts, tokens)):
            seq = np.concatenate([p, toks])
            ids[r, :len(seq)] = seq
        logits = np.asarray(m(_put(ctx, ids)).data, np.float32)
        assert np.isfinite(logits).all(), "non-finite logits"
        for r, (p, toks) in enumerate(zip(prompts, tokens)):
            for j, tok in enumerate(toks):
                row = logits[r, len(p) - 1 + j]
                worst = max(worst, float(row.max() - row[tok]))
    # Both sides compute in bf16 (2^-8 relative on activations and
    # logits of order 1) by different routes — cached decode against one
    # flash pass — so the token the engine chose may trail the eager
    # argmax by rounding, a few 1e-2. A wrong token would trail it by
    # the spread of 32000 random logits, which is several units.
    assert worst < 0.25, f"engine tokens trail the eager argmax by " \
        f"{worst} in logit"
    out["max_logit_gap_vs_eager"] = round(worst, 4)
    out["logits_readbacks"] = logits_readbacks
    # information: requests whose tokens are the same on both layouts
    # (the two sum attention in another order; see the gap above)
    out["ring_paged_equal_requests"] = sum(
        a == b for a, b in zip(served["ring"], served["paged"]))
    return out


# ---------------------------------------------------------------------------
# moe_serve
# ---------------------------------------------------------------------------

def _expert_share_vs_plain(ctx):
    """`expert_share_ffn` in bf16 on both of its paths against every
    held expert on every row in float32 at the highest precision."""
    import jax
    import jax.numpy as jnp
    from singa_tpu.parallel import moe
    c = ctx.sizes["moe"]
    D, F, G, S = c["hidden"], c["ff"], c["held"], c["shared"]
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED + 5), 16))
    draw = lambda *shape: (0.08 * jax.random.normal(           # noqa: E731
        next(keys), shape)).astype(jnp.bfloat16)
    p = {"router": draw(D, c["experts"]), "w_gate": draw(G, D, F),
         "w_up": draw(G, D, F), "w_down": draw(G, F, D),
         "s_gate": draw(S, D, F), "s_up": draw(S, D, F),
         "s_down": draw(S, F, D)}

    def plain(p, h):
        f = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
        idx, w = moe.route_sigmoid_topk(h, p["router"], c["top_k"])
        gated = lambda x, a, b, d: (jax.nn.silu(x @ a) * (x @ b)) @ d  # noqa: E731
        y = sum(gated(h, f["s_gate"][j], f["s_up"][j], f["s_down"][j])
                for j in range(S)) / S
        for g in range(G):
            weight = jnp.sum(jnp.where(idx == c["held_from"] + g, w, 0.0),
                             axis=-1)
            y = y + weight[:, None] * gated(h, f["w_gate"][g], f["w_up"][g],
                                            f["w_down"][g])
        return y

    out = {}
    for rows in c["rows"]:
        h = jax.random.normal(next(keys), (rows, D)).astype(jnp.bfloat16)
        y, stats = jax.jit(lambda p, h: moe.expert_share_ffn(
            p, h, top_k=c["top_k"], held_from=c["held_from"],
            h_route=h.astype(jnp.float32)))(p, h)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(plain)(p, h.astype(jnp.float32))
        err = _rel_err(y, want)
        # bf16 operands, float32 sums: 2^-8 a product
        assert err < 3e-2, f"expert share at {rows} rows: {err}"
        assert int(stats["pairs_here"] + stats["pairs_absent"]) == \
            rows * c["top_k"], stats
        out[f"rows_{rows}_rel_err"] = round(err, 5)
    return out


def phase_moe_serve(ctx):
    """What PR 26 added to the serving path, at a toy size in bf16: the
    expert share against its plain form, then a parallel-block LM served
    with prompts longer than its window until every window ring has
    wrapped, each served token read in the model's own eval forward
    (plain masked attention, no cache)."""
    import jax.numpy as jnp
    from singa_tpu.models.cohere_moe import CohereMoELM
    from singa_tpu.observability import metrics as obs_metrics
    c = ctx.sizes["moe"]
    out = {"expert_share": _expert_share_vs_plain(ctx)}
    m = CohereMoELM(
        c["vocab"], hidden_size=c["hidden"], num_layers=c["layers"],
        num_heads=c["heads"], num_kv_heads=c["kv_heads"],
        head_dim=c["head_dim"], intermediate_size=c["ff"],
        num_experts=c["held"], router_width=c["experts"], top_k=c["top_k"],
        num_shared_experts=c["shared"], experts_held_from=c["held_from"],
        sliding_window=c["window"], init_std=0.08, out_std=0.03)
    ids = _put(ctx, jnp.zeros((1, c["max_len"]), jnp.float32))
    m.compile([ids], is_train=False, use_graph=True, policy="bfloat16")
    m.eval()
    reg = obs_metrics.MetricsRegistry()
    eng = m.compile_serving(slots=c["slots"], max_len=c["max_len"],
                            prefill_len=c["prefill_len"], prefill_batch=1,
                            policy="bfloat16", registry=reg)
    lengths = [[lv[n].shape[2] for n in ("k", "v")] for lv in eng._cache]
    assert lengths == [[c["window"]] * 2] * 3 + [[c["max_len"]] * 2], lengths
    rng = np.random.RandomState(SEED + 7)
    prompts = [rng.randint(1, c["vocab"], (n,))
               for n in (c["prefill_len"], 5, c["window"] + 3, 11)]
    futs = [eng.submit(p, max_new_tokens=c["new_tokens"]) for p in prompts]
    eng.run_until_idle()
    served = [f.result(timeout=5)["tokens"] for f in futs]
    info = eng.compiled_step_info()
    out["ring_decode_calls"] = _ring_kernel_in_decode(ctx, eng)
    out["logits_readbacks"] = _logits_readbacks(reg)
    eng.stop()
    assert info["n_traces"] == 1 and info["kv_layout"] == "ring", info
    seqs = np.zeros((len(prompts), c["max_len"]), np.float32)
    for r, (p, toks) in enumerate(zip(prompts, served)):
        assert len(toks) == c["new_tokens"], toks
        seqs[r, :len(p) + len(toks)] = np.concatenate([p, toks])
    logits = np.asarray(m(_put(ctx, seqs)).data, np.float32)
    assert np.isfinite(logits).all(), "non-finite logits"
    gaps = np.asarray([logits[r, len(p) - 1 + j].max()
                       - logits[r, len(p) - 1 + j, tok]
                       for r, (p, toks) in enumerate(zip(prompts, served))
                       for j, tok in enumerate(toks)])
    # both sides in bf16 by different routes (rings against one masked
    # pass): a chosen token may trail the eager best by rounding, and a
    # rounding that swaps a token's last pick of experts moves that one
    # token by about the spread of the logits, so the MEAN gap decides
    # (as in the benchmark's cell). Read in the dry run: 0 as it stands;
    # 0.048 of the spread with the window rings' mask left open or their
    # writes one row off
    spread = float(logits.std())
    worst, mean = float(gaps.max()), float(gaps.mean())
    assert mean < 0.03 * spread, \
        f"served tokens trail the eval forward by {mean} on average " \
        f"(at most {worst}) of {spread}"
    out.update(
        max_logit_gap_vs_eval=round(worst, 4),
        mean_logit_gap_vs_eval=round(mean, 5), logit_std=round(spread, 2),
        longest_context=max(len(p) for p in prompts) + c["new_tokens"],
        pairs_here=reg.get("moe_pairs_total").value(held="here"),
        pairs_absent=reg.get("moe_pairs_total").value(held="absent"))
    return out


# ---------------------------------------------------------------------------
# hybrid_serve
# ---------------------------------------------------------------------------

def phase_hybrid_serve(ctx):
    """What PR 31 added to the serving path, at a toy size in bf16: a
    decoder-hybrid-decoder LM (three state-space layers, two window rings,
    one full ring that the cross layer reads beside its owner, one Gated
    Memory Unit) served greedily with prompts longer than its window
    until the window rings have wrapped, each served token read in the
    model's own eval forward (no cache, no state handed over)."""
    import jax.numpy as jnp
    from singa_tpu.models.phi4flash import Phi4FlashLM
    from singa_tpu.observability import metrics as obs_metrics
    from singa_tpu.observability import spans
    c = ctx.sizes["hybrid"]
    m = Phi4FlashLM(
        c["vocab"], hidden_size=c["hidden"], num_layers=c["layers"],
        num_heads=c["heads"], num_kv_heads=c["kv_heads"],
        intermediate_size=c["ff"], sliding_window=c["window"],
        init={"matrix": (0.0, 0.08), "dt_bias": (-2.0, 1.0)})
    ids = _put(ctx, jnp.zeros((1, c["max_len"]), jnp.float32))
    m.compile([ids], is_train=False, use_graph=True, policy="bfloat16")
    m.eval()
    reg = obs_metrics.MetricsRegistry()
    eng = m.compile_serving(slots=c["slots"], max_len=c["max_len"],
                            prefill_len=c["prefill_len"], prefill_batch=2,
                            policy="bfloat16", registry=reg)
    kinds = ["state", "window"] * 2 + ["state", "full"]
    assert eng._layout.adapter.cache_kinds() == kinds
    lengths = [lv["k"].shape[2] for lv in eng._cache if "k" in lv]
    assert lengths == [c["window"]] * 2 + [c["max_len"]], lengths
    assert all(lv["ssm"].dtype == jnp.float32 for lv in eng._cache
               if "ssm" in lv)
    rng = np.random.RandomState(SEED + 11)
    prompts = [rng.randint(1, c["vocab"], (n,))
               for n in (c["prefill_len"], 5, c["window"] + 3, 11, 40)]
    futs = [eng.submit(p, max_new_tokens=c["new_tokens"]) for p in prompts]
    eng.run_until_idle()
    served = [f.result(timeout=5)["tokens"] for f in futs]
    info = eng.compiled_step_info()
    out = {"ring_decode_calls": _ring_kernel_in_decode(ctx, eng, attends=1),
           "logits_readbacks": _logits_readbacks(reg)}
    eng.stop()
    assert info["n_traces"] == 1 and info["kv_layout"] == "ring", info
    steps = reg.get("serve_state_steps_total").value()
    ticks = [r for r in spans.recorder().records()
             if r.get("name") == "serve.decode" and "state_slots" in r]
    assert steps > 0 and sum(r["state_slots"] for r in ticks) == steps, \
        f"{steps} state steps counted, the spans carry " \
        f"{sum(r['state_slots'] for r in ticks)}"
    rows = reg.get("serve_prefill_rows_total")
    assert rows.value(decoder="self") == sum(len(p) for p in prompts) \
        and rows.value(decoder="cross") == len(prompts)
    seqs = np.zeros((len(prompts), c["max_len"]), np.float32)
    for r, (p, toks) in enumerate(zip(prompts, served)):
        assert len(toks) == c["new_tokens"], toks
        seqs[r, :len(p) + len(toks)] = np.concatenate([p, toks])
    logits = np.asarray(m(_put(ctx, seqs)).data, np.float32)
    assert np.isfinite(logits).all(), "non-finite logits"
    gaps = np.asarray([logits[r, len(p) - 1 + j].max()
                       - logits[r, len(p) - 1 + j, tok]
                       for r, (p, toks) in enumerate(zip(prompts, served))
                       for j, tok in enumerate(toks)])
    # both sides in bf16 by different routes (rings, state steps and a
    # last-token cross-decoder against one chunked scan and one masked
    # pass): a chosen token may trail the eval forward's best by
    # rounding. No router here, so the widest gap decides. Read in the
    # dry run: 0.036 of the spread as it stands (0.047 of 1.28); 5.3 with
    # the state left behind at the hand-over from prefill, 0.27 with the
    # cross layer reading a window ring
    spread = float(logits.std())
    worst = float(gaps.max())
    assert worst < 0.08 * spread, \
        f"served tokens trail the eval forward by up to {worst} of {spread}"
    out.update(max_logit_gap_vs_eval=round(worst, 4),
               logit_std=round(spread, 2), state_slots=int(steps),
               longest_context=max(len(p) for p in prompts)
               + c["new_tokens"])
    return out


# ---------------------------------------------------------------------------
# latent_serve
# ---------------------------------------------------------------------------

def phase_latent_serve(ctx):
    """What PR 33 added to the serving path, at toy widths around the
    real latent row, in bf16: a shortcut-connected LM with latent
    attention served greedily — prefill in the expanded form, decode in
    the absorbed form over latent ring levels — each served token read
    in the model's own eval forward (expanded attention, no cache)."""
    import jax.numpy as jnp
    from singa_tpu.models.longcat_flash import LongCatFlashLM
    from singa_tpu.observability import metrics as obs_metrics
    from singa_tpu.observability import spans
    from singa_tpu.serving import kv_cache
    c = ctx.sizes["latent"]
    m = LongCatFlashLM(
        c["vocab"], hidden_size=c["hidden"], num_layers=c["layers"],
        num_heads=c["heads"], q_lora_rank=c["q_rank"],
        kv_lora_rank=c["kv_rank"], qk_nope_head_dim=c["nope"],
        qk_rope_head_dim=c["rope"], v_head_dim=c["v"],
        ffn_hidden_size=c["ff"], expert_ffn_hidden_size=c["expert_ff"],
        num_experts=c["held"], router_width=c["router"],
        zero_expert_num=c["zero"], top_k=c["top_k"],
        experts_held_from=c["held_from"], init_std=0.08,
        router_bias_std=0.01)
    ids = _put(ctx, jnp.zeros((1, c["max_len"]), jnp.float32))
    m.compile([ids], is_train=False, use_graph=True, policy="bfloat16")
    m.eval()
    reg = obs_metrics.MetricsRegistry()
    eng = m.compile_serving(slots=c["slots"], max_len=c["max_len"],
                            prefill_len=c["prefill_len"], prefill_batch=1,
                            policy="bfloat16", registry=reg)
    levels = 2 * c["layers"]
    assert eng._layout.adapter.cache_kinds() == ["latent"] * levels
    assert all(isinstance(lv, kv_cache.LatentLevel)
               and lv["k"].dtype == jnp.bfloat16 for lv in eng._cache)
    # one row a token a block, for every head: 576 numbers of 2 bytes
    # (stored in whole lane tiles: 640)
    row_bytes = sum(lv.width * lv["k"].dtype.itemsize for lv in eng._cache)
    assert row_bytes == 1152 * levels, row_bytes
    stored = reg.get("serve_kv_bytes").value(kind="latent")
    assert stored == c["slots"] * c["max_len"] * 1280 * levels, stored
    rng = np.random.RandomState(SEED + 13)
    prompts = [rng.randint(1, c["vocab"], (n,))
               for n in (c["prefill_len"], 5, 131, 11, 40)]
    futs = [eng.submit(p, max_new_tokens=c["new_tokens"]) for p in prompts]
    eng.run_until_idle()
    served = [f.result(timeout=5)["tokens"] for f in futs]
    info = eng.compiled_step_info()
    out = {"latent_decode_calls": _ring_kernel_in_decode(
               ctx, eng, kernel="latent_decode"),
           "logits_readbacks": _logits_readbacks(reg)}
    eng.stop()
    assert info["n_traces"] == 1 and info["kv_layout"] == "ring", info
    pairs = {h: int(reg.get("moe_pairs_total").value(held=h))
             for h in ("here", "absent", "zero")}
    rows = sum(len(p) + c["new_tokens"] - 1 for p in prompts)
    assert sum(pairs.values()) == rows * c["top_k"] * c["layers"], pairs
    assert pairs["zero"] > 0, pairs
    ticks = [r for r in spans.recorder().records()
             if r.get("name") in ("serve.decode", "serve.prefill")
             and "pairs_zero" in r]
    assert sum(r["pairs_zero"] for r in ticks) >= pairs["zero"]
    seqs = np.zeros((len(prompts), c["max_len"]), np.float32)
    for r, (p, toks) in enumerate(zip(prompts, served)):
        assert len(toks) == c["new_tokens"], toks
        seqs[r, :len(p) + len(toks)] = np.concatenate([p, toks])
    logits = np.asarray(m(_put(ctx, seqs)).data, np.float32)
    assert np.isfinite(logits).all(), "non-finite logits"
    gaps = np.asarray([logits[r, len(p) - 1 + j].max()
                       - logits[r, len(p) - 1 + j, tok]
                       for r, (p, toks) in enumerate(zip(prompts, served))
                       for j, tok in enumerate(toks)])
    # both sides in bf16 by different routes (absorbed attention over the
    # cached rows, with the query's up-projection rounded to bf16, against
    # expanded attention over whole sequences), and a rounding that swaps
    # a token's last pick of experts moves that one token by about the
    # spread of the logits, so the MEAN gap decides (as in moe_serve and
    # in the benchmark's cell)
    spread = float(logits.std())
    worst, mean = float(gaps.max()), float(gaps.mean())
    assert mean < 0.03 * spread, \
        f"served tokens trail the eval forward by {mean} on average " \
        f"(at most {worst}) of {spread}"
    out.update(
        max_logit_gap_vs_eval=round(worst, 4),
        mean_logit_gap_vs_eval=round(mean, 5), logit_std=round(spread, 2),
        longest_context=max(len(p) for p in prompts) + c["new_tokens"],
        latent_bytes_a_token=row_bytes, pairs_here=pairs["here"],
        pairs_absent=pairs["absent"], pairs_zero=pairs["zero"])
    return out


# ---------------------------------------------------------------------------
# multichip
# ---------------------------------------------------------------------------

def _placement(arrays, devices):
    """Every array is laid out over exactly these devices."""
    want = set(devices)
    bad = [i for i, a in enumerate(arrays)
           if set(a.sharding.device_set) != want]
    assert not bad, f"{len(bad)} of {len(arrays)} state arrays are not " \
        f"on the {len(want)} mesh devices (first: {bad[:3]})"


def _has_collectives(hlo, *names):
    for n in names:
        assert n in hlo, f"no {n} in the step's optimized HLO"


# The first step of every multichip variant starts from the weights and
# batch of its one-chip phase (same seed). What differs is only where the
# bf16 partial sums are added up — per shard, then across shards — so
# the losses agree to bf16 accumulation noise: 2e-2 relative.
LOSS_TOL = 2e-2


def _first_loss_agrees(ctx, name, first, ref_key):
    ref = ctx.first_loss[ref_key]
    rel = abs(first - ref) / abs(ref)
    assert np.isfinite(first) and rel < LOSS_TOL, \
        f"{name}: first-step loss {first} vs one-chip {ref} " \
        f"(rel {rel:.4f} > {LOSS_TOL})"
    return {"first_loss": round(first, 4), "one_chip": round(ref, 4),
            "rel_diff": round(rel, 5)}


def _two_steps(m, *batch):
    """(first-step loss, the step's audit, the state arrays) after two
    steps, the second of which must still be finite and untraced."""
    first = float(m(*batch)[1].data)
    assert np.isfinite(float(m(*batch)[1].data))
    info = m.compiled_step_info()
    assert info["n_traces"] == 1, info["n_traces"]
    return first, info, [t.data for t in m._state_list]


def _mc_resnet_distopt(ctx, devs):
    """ResNet-50 through opt.DistOpt: the shard_map driver."""
    from singa_tpu import opt
    from singa_tpu.parallel import mesh as mesh_mod
    from singa_tpu.parallel.communicator import set_mesh
    dp4 = mesh_mod.make_mesh(devs, mesh_mod.MeshConfig())
    set_mesh(dp4)
    try:
        dist = opt.DistOpt(opt.SGD(lr=RESNET_LR, momentum=0.9),
                           world_size=len(devs))
        dist.communicator.mesh = dp4
        m, tx, ty = _build_resnet(ctx, dist)
        first, info, state = _two_steps(m, tx, ty)
    finally:
        set_mesh(None)
    _placement(state, devs)
    _has_collectives(info["hlo"], "all-reduce")
    return _first_loss_agrees(ctx, "resnet DistOpt", first, "resnet")


def _mc_resnet_gspmd_fsdp(ctx, devs):
    """ResNet-50 through the single-jit GSPMD step with FSDP."""
    from singa_tpu import opt
    from singa_tpu.parallel import gspmd
    m, tx, ty = _build_resnet(
        ctx, opt.SGD(lr=RESNET_LR, momentum=0.9),
        mesh=gspmd.train_mesh(devs, data=len(devs)), fsdp_axis="data")
    first, info, state = _two_steps(m, tx, ty)
    _placement(state, devs)
    per_dev = gspmd.Partitioner.per_device_bytes(state)
    glob = gspmd.Partitioner.global_bytes(state)
    # about a quarter: only a tensor with no dimension divisible by 4
    # (the scalars, the 10-wide head bias) stays whole on every chip
    assert glob / per_dev > 0.8 * len(devs), (glob, per_dev)
    _has_collectives(info["hlo"], "all-gather")
    assert "reduce-scatter" in info["hlo"] or \
        "all-reduce" in info["hlo"], "no gradient reduction in the HLO"
    return {**_first_loss_agrees(ctx, "resnet GSPMD+FSDP", first,
                                 "resnet"),
            "state_bytes_global": glob,
            "state_bytes_per_device": per_dev,
            "ratio": round(glob / per_dev, 2),
            "reduce_scatter_in_hlo": "reduce-scatter" in info["hlo"]}


def _mc_lm_dp2_tp2(ctx, devs):
    """The LM at dp2 x tp2 through DistOpt over a (data, model) mesh."""
    from singa_tpu import opt
    from singa_tpu.parallel import gspmd, mesh as mesh_mod
    from singa_tpu.parallel.communicator import set_mesh
    msh = mesh_mod.make_mesh(devs, mesh_mod.MeshConfig(model=2))
    set_mesh(msh)
    try:
        dist = opt.DistOpt(opt.SGD(lr=LM_LR, momentum=0.9))
        dist.communicator.mesh = msh
        m, ti, tt = _build_lm(ctx, dist, tp=True)
        first, info, state = _two_steps(m, ti, tt)
    finally:
        set_mesh(None)
    _placement(state, devs)
    per_dev = gspmd.Partitioner.per_device_bytes(state)
    glob = gspmd.Partitioner.global_bytes(state)
    # tensor parallelism halves the attention, MLP, embedding and head
    # weights; norms, row-parallel biases and positions stay whole
    assert glob / per_dev > 1.5, (glob, per_dev)
    _has_collectives(info["hlo"], "all-reduce")
    if not ctx.dry:
        calls = info["hlo"].count("tpu_custom_call")
        assert calls >= 3 * ctx.sizes["lm"]["n_layers"], \
            f"flash attention declined under tp ({calls} Mosaic calls)"
    return {**_first_loss_agrees(ctx, "LM dp2xtp2", first, "lm"),
            "state_bytes_global": glob,
            "state_bytes_per_device": per_dev}


def phase_multichip(ctx):
    import jax
    n = jax.device_count()
    if n < 4:
        print(f"multichip: skipped (n_devices={n})", flush=True)
        return {"skipped": True}
    devs = jax.devices()[:4]
    out = {}
    for name, leg in (("resnet_distopt_shard_map", _mc_resnet_distopt),
                      ("resnet_gspmd_fsdp", _mc_resnet_gspmd_fsdp),
                      ("lm_dp2_tp2", _mc_lm_dp2_tp2)):
        out[name] = leg(ctx, devs)
        gc.collect()
    return out


# ---------------------------------------------------------------------------

def phase_cache(ctx):
    """What the persistent compile cache did for this run: a second run
    in the same place reports hits and no misses."""
    from singa_tpu.aot import cache as aot_cache
    snap = aot_cache.snapshot()
    native = sys.modules.get("singa_tpu.native")
    return {"cache_dir": aot_cache.active().directory,
            "compile_cache_hits_total": snap["hits"],
            "compile_cache_misses_total": snap["misses"],
            "cache_entries": aot_cache.stats()["entries"],
            "io": "not loaded (no phase reads record files)"
            if native is None else
            ("native" if native.AVAILABLE else "pure-python")}


PHASES = (("device", phase_device),
          ("resnet50_train", phase_resnet50_train),
          ("lm_train", phase_lm_train),
          ("kernels", phase_kernels),
          ("serve", phase_serve),
          ("moe_serve", phase_moe_serve),
          ("hybrid_serve", phase_hybrid_serve),
          ("latent_serve", phase_latent_serve),
          ("multichip", phase_multichip),
          ("cache", phase_cache))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="sandbox debugging: tiny sizes, Pallas "
                         "interpreted, no TPU needed; not a result")
    ap.add_argument("--only", default="",
                    help="comma-separated phases to run after `device` "
                         "(debugging one phase; the check is the whole run)")
    args = ap.parse_args(argv)
    only = set(filter(None, args.only.split(","))) | {"device"}
    unknown = only - {name for name, _ in PHASES}
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")
    t0 = time.perf_counter()

    import jax
    d = jax.devices()[0]
    if args.dry_run:
        print(f"DRY RUN platform={d.platform}", flush=True)
    elif d.platform != "tpu":
        print(f"chip_smoke: no TPU — jax.devices()[0] is "
              f"{d.platform!r}. This check runs on the chip or fails.",
              file=sys.stderr)
        return 1

    # A warning is how this code base declines to a slower path (the
    # eager fallbacks of Model.compile and the first step, a kernel that
    # gives way to the scan path, a declined KV layout). Here every one
    # is an error, whichever module it is attributed to: stacklevel makes
    # most of them point at their caller.
    warnings.simplefilter("error")
    for quiet in (DeprecationWarning, PendingDeprecationWarning,
                  ImportWarning, ResourceWarning):
        warnings.simplefilter("default", quiet)
    # JAX's note that an executable is larger than the compile cache may
    # hold (the chip tool caps its cache at 192 MiB; the conv epilogue
    # check's program is 285 MB) declines nothing: the program still runs.
    warnings.filterwarnings(
        "default", message="Error writing persistent compilation cache")

    from singa_tpu.aot import cache as aot_cache
    aot_cache.install()
    if args.dry_run:
        from singa_tpu.ops import attention_mod as attention, fused_optim
        attention.FORCE_PALLAS_INTERPRET = True
        fused_optim.FORCE_PALLAS_INTERPRET = True

    ctx = Ctx(args.dry_run)
    for name, fn in PHASES:
        if not args.only or name in only:
            run_phase(name, fn, ctx)

    wall = round(time.perf_counter() - t0, 1)
    if args.dry_run:
        print(f"DRY RUN platform={d.platform} passed in {wall}s "
              "(not a result)", flush=True)
        return 0
    print(json.dumps({"phase": "done", "wall_seconds": wall}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
