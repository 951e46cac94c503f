"""Driver for the serving engine with a model too large to hold twice: the
same entry points as `drivers/serve_engine.py` (`Model.compile` ->
`compile_serving` -> `submit`), whose window, end-to-end metric and release
it uses as they are, with two differences the size forces.

Set-up loads the weights leaf by leaf in the leaf's own dtype
(`lib/weights_staged.py`: a leaf's values hang on the seed and its name
alone), so no float32 copy of the model ever exists. The comparison runs the
reference layer by layer (`lib/references/<family>.served_gaps` draws a
layer, uses it on every sampled sequence, frees it). The per-layer metrics
of the expert layer read the counts the program puts on its `serve.prefill`
and `serve.decode` spans, so the window needs nothing added.

`correct` holds the served tokens to the plain reference as the other serve
driver does (`logit_gap`, the widest gap of a served token's logit below the
reference's best; `unanswered`, no sampled request unanswered), and by four
numbers more, each under the limit the cell's file gives it, if any. With
sparse experts a rounding can swap a token's 8th and 9th expert, which moves
that token's logits as far as a lower precision moves every token's, so the
widest gap cannot tell the two apart; what can is how many tokens have a gap
and how large the gaps are short of the very widest: `logit_gap_mean`, the
mean of the gaps over every compared token; `logit_gap_p99`, their 99th
percentile; `logit_gap_over_0`, the share of compared tokens whose logit lies
below the reference's best at all. `contexts_within_window` is 1 where the
longest checked context does not exceed `sliding_window` (a run that never
wrapped a window ring has not shown the rings right) and 0 otherwise, under
a limit of 0.
"""

import importlib

import jax.numpy as jnp
import numpy as np

from drivers import serve_engine
from drivers.serve_engine import end_to_end, release as _release  # noqa: F401
from lib import compare, loadgen, weights_staged
from lib.program import Handle, build_model, singa_device


def _reference(config):
    return importlib.import_module(f"lib.references.{config['reference']}")


def load_weights_staged(model, config, seed):
    """Each leaf drawn from (seed, name), rounded to the dtype the program
    holds it in, and assigned; the leaf it replaces is freed first."""
    ref = _reference(config)
    prefix = config["program"]["prefix"]
    states = model.get_states()
    specs = ref.param_specs(config)
    names = {f"{prefix}.{n}" for n, *_ in specs}
    if names != set(states):
        raise SystemExit(
            f"the reference's leaves and the program's differ: "
            f"{sorted(names ^ set(states))[:6]}")
    for spec in specs:
        t = states[f"{prefix}.{spec[0]}"]
        if tuple(t.shape) != tuple(spec[1]):
            raise SystemExit(f"{spec[0]}: program {t.shape}, "
                             f"reference {spec[1]}")
        dtype, t.data = t.data.dtype, None      # freed before the draw
        t.data = weights_staged.make_leaf(spec, seed, dtype)


def setup(run):
    from singa_tpu import tensor
    from singa_tpu.observability import metrics as obs_metrics
    from singa_tpu.observability import spans
    h = Handle()
    config, job = run.config, run.traffic
    eng_kw = dict(job["engine"])
    h.dev = singa_device(run.devices[0].platform)
    h.dev.SetRandSeed(run.seed & 0x7FFFFFFF)
    model = build_model(config, job)
    ids = tensor.Tensor(data=jnp.zeros((1, int(eng_kw["prefill_len"])),
                                       jnp.float32),
                        device=h.dev, requires_grad=False)
    # shape inference only: the dry run makes the parameters, no forward
    model.compile([ids], is_train=False, use_graph=True,
                  policy=config["precision"])
    model.eval()
    run.phase("model_compiled")
    load_weights_staged(model, config, run.seed)
    run.phase("weights_loaded")
    spans.configure(capacity=int(job.get("recorder_capacity", 400000)))
    h.registry = obs_metrics.MetricsRegistry()
    h.engine = model.compile_serving(policy=config["precision"],
                                     registry=h.registry, **eng_kw)
    h.model = model
    run.phase("engine_built")
    h.engine.start()
    # warm up the two programs this traffic uses (prefill, decode)
    rng = np.random.default_rng(run.seed)
    warm = [h.engine.submit(rng.integers(1, int(config["vocab_size"]), n,
                                         dtype=np.int32),
                            max_new_tokens=4, temperature=0.0)
            for n in (int(eng_kw["prefill_len"]), 16, 16)]
    for f in warm:
        f.result(timeout=1100)
    run.phase("warmed_up")
    h.schedule = loadgen.make_schedule(job, int(config["vocab_size"]),
                                       run.seed, run.seconds)
    return h


def window(run, h):
    """`serve_engine.window` as it is; the notes it made are kept for
    `release` to add to."""
    measured = serve_engine.window(run, h)
    h.notes = measured["notes"]
    return measured


def release(run, h):
    """`serve_engine.release`, and what the sample reached: the longest
    checked context goes under `notes` and with the evidence."""
    notes = h.notes
    evidence = _release(run, h)
    reach = max((len(p) + len(t) for p, t in evidence["samples"]), default=0)
    notes["check_max_context"] = evidence["max_context"] = int(reach)
    notes["check_requests"] = len(evidence["samples"])
    return evidence


def reference_gaps(run, samples, cast=None, picks_out=None):
    """The plain reference, layer by layer, over each sampled prompt with
    its served tokens. Returns (gaps of the served tokens, gaps of the
    tokens the lower-precision pass `cast` puts first, or None)."""
    config = run.config
    length = int(run.traffic["engine"]["max_len"])
    ids = np.zeros((len(samples), length), np.int32)
    spans_ = []
    for r, (prompt, tokens) in enumerate(samples):
        seq = np.concatenate([prompt, tokens])[:length]
        ids[r, :len(seq)] = seq
        spans_.append((len(prompt) - 1, len(seq) - 1))
    if not samples:
        return np.zeros((0,)), None
    served, low = _reference(config).served_gaps(
        config, run.seed, ids, cast, picks_out)
    if picks_out is not None:
        picks_out["spans"] = spans_
    cut = (lambda g: np.concatenate([g[r, s:e]
                                     for r, (s, e) in enumerate(spans_)]))
    return cut(served), (None if low is None else cut(low))


GAP_STATS = {
    "logit_gap_mean": np.mean,
    "logit_gap_p99": lambda gaps: np.percentile(gaps, 99),
    "logit_gap_over_0": lambda gaps: np.mean(gaps > 0),
}


def numbers(run, gaps, evidence):
    """{name: (value, limit)} for the limits the cell's file gives."""
    limits = dict(run.cell["limits"])
    mine = {k: limits.pop(k) for k in (*GAP_STATS, "contexts_within_window")
            if k in limits}
    out = compare.served_numbers(
        {"gaps": gaps, "unanswered": evidence["unanswered"]}, limits)
    for name, limit in mine.items():
        if name in GAP_STATS:
            value = GAP_STATS[name](gaps) if len(gaps) else float("nan")
        else:
            value = evidence["max_context"] \
                <= int(run.config["sliding_window"])
        out[name] = (float(value), float(limit))
    return out


def check(run, evidence):
    gaps, _ = reference_gaps(run, evidence["samples"])
    return numbers(run, gaps, evidence)
