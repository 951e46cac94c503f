#!/usr/bin/env python
"""Render a directory of BENCH_r*.json records as a table (or JSON).

Each record is one ``bench.py`` result line (bare, or wrapped as
``{"n": round, "parsed": {...}}``). This CLI folds the records into one
per-round table: per-leg throughput (img/s, tok/s), MFU, peak HBM,
compile cost, serving SLOs, and the step-timeline decomposition
(compute/exposed-comm/idle fractions) the MFU push steers by — each
with its delta vs the previous record, and loud ``REGRESSION`` flags
when a throughput metric drops more than the threshold::

    python tools/bench_report.py --dir runs/      # records under runs/
    python tools/bench_report.py --dir runs/ --json
    python tools/bench_report.py --threshold 0.10
    python tools/bench_report.py --selftest       # CI gate

``--selftest`` (wired into tests/test_examples.py like the other tool
selftests) synthesizes a three-round trajectory with a known bf16
regression and asserts the extraction, the deltas, and the flag.
"""

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# (column label, extractor) — every metric the trajectory tracks. An
# extractor returns None when the leg didn't run that round; deltas
# skip None-to-None and None-to-value transitions.
METRICS = [
    ("img_s", lambda p: p.get("value") or p.get("throughput")),
    ("mfu", lambda p: p.get("mfu")),
    ("bf16_img_s", lambda p: p.get("bf16_throughput")),
    ("bf16_mfu", lambda p: p.get("bf16_mfu")),
    ("lm_tok_s", lambda p: p.get("lm_tokens_per_sec")),
    ("lm_mfu", lambda p: p.get("lm_mfu")),
    ("lm_bf16_tok_s", lambda p: p.get("lm_bf16_tokens_per_sec")),
    ("lm_bf16_mfu", lambda p: p.get("lm_bf16_mfu")),
    ("serve_tok_s", lambda p: (p.get("serving") or {}).get(
        "decode_tok_s")),
    ("serve_p99_ms", lambda p: _scale((p.get("serving") or {}).get(
        "p99_token_s"), 1e3)),
    ("quant_img_s", lambda p: (p.get("quant") or {}).get(
        "resnet_img_s")),
    ("sweep_best_tok_s", lambda p: _sweep_best(p.get("serving_sweep"))),
    ("serve_sh_tok_s", lambda p: (p.get("serving_sharded") or {}).get(
        "decode_tok_s")),
    ("serve_sh_kv_dev_mib", lambda p: _scale(
        (p.get("serving_sharded") or {}).get("kv_per_device_bytes"),
        1 / 2**20)),
    ("serve_sh_hbm_gib", lambda p: _scale(
        (p.get("serving_sharded") or {}).get("hbm_peak_bytes"),
        1 / 2**30)),
    ("hbm_peak_gib", lambda p: _scale(p.get("hbm_peak_bytes"),
                                      1 / 2**30)),
    ("bf16_hbm_gib", lambda p: _scale(p.get("bf16_hbm_peak_bytes"),
                                      1 / 2**30)),
    ("compile_s", lambda p: (p.get("compile") or {}).get("seconds")),
]

# higher-is-better metrics get the regression gate; latency/memory
# metrics are reported with deltas but a rise there is not flagged
# (the p99 of a 2-request CPU smoke is far too noisy to gate on)
GATED = {"img_s", "bf16_img_s", "lm_tok_s", "lm_bf16_tok_s",
         "serve_tok_s", "quant_img_s", "sweep_best_tok_s",
         "serve_sh_tok_s"}

# SLO latency targets (ms) the serving_sweep winner table is computed
# against: for each, the highest-throughput config whose p99 per-tick
# latency fits under it ("None" = unconstrained best throughput)
SWEEP_SLO_TARGETS_MS = (1.0, 5.0, 25.0, None)

# per-leg MFU columns the --mfu-floor gate guards (the MFU-push PRs'
# cron tripwire: a win banked by one round must not silently erode)
MFU_GATED = {"mfu", "bf16_mfu", "lm_mfu", "lm_bf16_mfu"}

# exposed-comm rises smaller than this (seconds) are timing noise, not
# an overlap regression — CPU/TPU profiler jitter sits well under it
EXPOSED_COMM_EPS_S = 1e-4

# per-leg timeline columns (bucket fractions + exposed comm) — the
# "what to fix" companion of each MFU number
TIMELINE_LEGS = [("timeline", "fp32"), ("bf16_timeline", "bf16"),
                 ("lm_timeline", "lm"),
                 ("lm_bf16_timeline", "lm_bf16"),
                 ("serving.timeline", "serving")]


def _scale(v, k):
    return v * k if isinstance(v, (int, float)) else None


def _sweep_configs(sweep):
    return [c for c in (sweep or {}).get("configs") or []
            if isinstance(c, dict)
            and isinstance(c.get("decode_tok_s"), (int, float))]


def _sweep_best(sweep):
    """Best decode tok/s across the round's serving_sweep configs —
    the one scalar the trajectory/regression gate tracks (per-config
    curves render separately)."""
    configs = _sweep_configs(sweep)
    return max((c["decode_tok_s"] for c in configs), default=None)


def _cfg_name(c):
    return (f"{c.get('kv_layout', '?')} s{c.get('slots', '?')}"
            f" pf{c.get('prefill_len', '?')}"
            f" k{c.get('speculative_k', 0)}")


def sweep_winners(sweep):
    """Winner per SLO target: for each p99 tick-latency budget, the
    highest-throughput config that fits under it. The load-sweep's
    whole point — "which engine config should this fleet run at THIS
    latency target" answered from banked curves, not guesses."""
    configs = _sweep_configs(sweep)
    winners = []
    for t in SWEEP_SLO_TARGETS_MS:
        elig = [c for c in configs
                if t is None
                or (isinstance(c.get("p99_token_s"), (int, float))
                    and c["p99_token_s"] * 1e3 <= t)]
        if not elig:
            winners.append({"slo_ms": t, "config": None})
            continue
        best = max(elig, key=lambda c: c["decode_tok_s"])
        winners.append({"slo_ms": t, "config": _cfg_name(best),
                        "decode_tok_s": best["decode_tok_s"],
                        "p99_ms": _scale(best.get("p99_token_s"), 1e3)})
    return winners


def _round_no(path):
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    return int(m.group(1)) if m else -1


def load_records(directory):
    """[(round_no, parsed-record dict)] sorted by round, skipping
    files without a parsed benchmark payload."""
    out = []
    for path in sorted(glob.glob(os.path.join(directory,
                                              "BENCH_r*.json")),
                       key=_round_no):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"bench_report: skipping {path} ({e})",
                  file=sys.stderr)
            continue
        parsed = doc.get("parsed") if isinstance(doc, dict) else None
        if isinstance(doc, dict) and parsed is None and \
                ("value" in doc or "throughput" in doc):
            parsed = doc          # a bare bench.py record, unwrapped
        if not isinstance(parsed, dict):
            print(f"bench_report: {path} has no parsed record",
                  file=sys.stderr)
            continue
        out.append((doc.get("n", _round_no(path)), parsed))
    return out


def _timeline_doc(parsed, key):
    node = parsed
    for part in key.split("."):
        node = (node or {}).get(part) if isinstance(node, dict) else None
    return node if isinstance(node, dict) else None


def build_report(records, threshold=0.05, mfu_floor=None):
    """The JSON-able report doc: one row per round with extracted
    metrics, deltas vs the previous record (fractional), per-leg
    timeline decompositions, and the regression list.

    ``mfu_floor`` arms the MFU-push cron gate: a leg whose MFU falls
    BELOW the floor after the previous same-platform record held it
    (or keeps dropping past ``threshold`` while already under it) is a
    regression, and so is a per-leg ``exposed_collective_s`` that
    rises more than ``threshold`` (plus a noise epsilon) vs the
    previous same-platform record — the two numbers this PR's overlap
    and fused-kernel wins are banked in, guarded round over round."""
    rows = []
    # deltas compare a round against the previous record on the SAME
    # platform: a tpu round after a cpu-fallback round is not a
    # 100000% speedup, and the cpu round after it is not a regression
    prev_by_platform = {}
    for n, parsed in records:
        vals = {name: fn(parsed) for name, fn in METRICS}
        row = {"round": n,
               "measured_at": parsed.get("measured_at"),
               "git": parsed.get("git"),
               "platform": parsed.get("platform"),
               "device_kind": parsed.get("device_kind"),
               "metrics": vals, "deltas": {}, "regressions": []}
        prev = prev_by_platform.get(row["platform"])
        timelines = {}
        for key, leg in TIMELINE_LEGS:
            tl = _timeline_doc(parsed, key)
            if tl:
                timelines[leg] = {
                    "fractions": tl.get("fractions"),
                    "exposed_collective_s":
                        tl.get("exposed_collective_s")}
        if timelines:
            row["timeline"] = timelines
        sweep = parsed.get("serving_sweep")
        sweep_cfgs = _sweep_configs(sweep)
        if sweep_cfgs:
            row["serving_sweep"] = {
                "configs": [
                    {"name": _cfg_name(c),
                     "decode_tok_s": c["decode_tok_s"],
                     "p99_ms": _scale(c.get("p99_token_s"), 1e3),
                     "prefix_cache_hits": c.get("prefix_cache_hits"),
                     "speculative_accepted_ratio":
                         c.get("speculative_accepted_ratio")}
                    for c in sweep_cfgs],
                "winners": sweep_winners(sweep)}
            # per-config same-platform deltas, matched by config name
            # (a grid change between rounds simply yields no delta)
            prev_cfgs = {c["name"]: c for c in
                         ((prev or {}).get("serving_sweep") or {})
                         .get("configs", [])}
            for c in row["serving_sweep"]["configs"]:
                pc = prev_cfgs.get(c["name"])
                if pc and isinstance(pc.get("decode_tok_s"),
                                     (int, float)) \
                        and pc["decode_tok_s"]:
                    c["delta"] = (c["decode_tok_s"]
                                  - pc["decode_tok_s"]) \
                        / pc["decode_tok_s"]
        sh = parsed.get("serving_sharded")
        if isinstance(sh, dict) and \
                isinstance(sh.get("decode_tok_s"), (int, float)):
            mesh = sh.get("mesh") or {}
            blk = {"decode_tok_s": sh["decode_tok_s"],
                   "mesh": f"{mesh.get('batch', '?')}x"
                           f"{mesh.get('model', '?')}",
                   "kv_per_device_mib": _scale(
                       sh.get("kv_per_device_bytes"), 1 / 2**20),
                   "hbm_peak_gib": _scale(sh.get("hbm_peak_bytes"),
                                          1 / 2**30)}
            # vs the SAME round's unsharded serving record: what
            # sharding costs (CPU: unoverlapped collectives) or buys
            # (per-chip memory) this round — never across platforms
            unsh = (parsed.get("serving") or {}).get("decode_tok_s")
            if isinstance(unsh, (int, float)) and unsh:
                blk["vs_unsharded"] = sh["decode_tok_s"] / unsh
            row["serving_sharded"] = blk
        if prev is not None:
            for name, v in vals.items():
                pv = prev["metrics"].get(name)
                if isinstance(v, (int, float)) and \
                        isinstance(pv, (int, float)) and pv:
                    d = (v - pv) / pv
                    row["deltas"][name] = d
                    if name in GATED and d < -threshold:
                        row["regressions"].append(
                            {"metric": name, "delta": d,
                             "prev": pv, "now": v,
                             "vs_round": prev["round"]})
                    if mfu_floor is not None and name in MFU_GATED \
                            and v < mfu_floor \
                            and (pv >= mfu_floor or d < -threshold):
                        # lost the floor the previous round held, or
                        # still sliding while already under it
                        row["regressions"].append(
                            {"metric": name, "kind": "mfu_floor",
                             "floor": mfu_floor, "delta": d,
                             "prev": pv, "now": v,
                             "vs_round": prev["round"]})
            if mfu_floor is not None:
                for leg, tl in timelines.items():
                    cur = tl.get("exposed_collective_s")
                    ptl = (prev.get("timeline") or {}).get(leg) or {}
                    pv = ptl.get("exposed_collective_s")
                    if not (isinstance(cur, (int, float))
                            and isinstance(pv, (int, float))):
                        continue
                    if cur > pv * (1 + threshold) + EXPOSED_COMM_EPS_S:
                        row["regressions"].append(
                            {"metric": f"{leg}_exposed_comm",
                             "kind": "exposed_comm",
                             "delta": (cur - pv) / pv if pv else None,
                             "prev": pv, "now": cur,
                             "vs_round": prev["round"]})
        rows.append(row)
        prev_by_platform[row["platform"]] = row
    return {"schema": "singa-tpu-bench-report/1", "rounds": rows,
            "threshold": threshold, "mfu_floor": mfu_floor,
            "regressions": [r for row in rows
                            for r in row["regressions"]]}


def _fmt(v):
    if v is None:
        return "-"
    if isinstance(v, float):
        if abs(v) >= 1000:
            return f"{v:,.0f}"
        return f"{v:.4g}"
    return str(v)


def _fmt_delta(d):
    return "" if d is None else f" ({d:+.1%})"


def render_table(report):
    """Plain-text trajectory table: one block per round (records carry
    different leg sets per round, so a fixed-width grid would be
    mostly holes)."""
    lines = []
    for row in report["rounds"]:
        head = f"round r{row['round']:02d}"
        if row.get("measured_at"):
            head += f"  {row['measured_at']}"
        if row.get("git"):
            head += f"  git {row['git']}"
        if row.get("device_kind"):
            head += f"  [{row['device_kind']}]"
        lines.append(head)
        for name, _fn in METRICS:
            v = row["metrics"].get(name)
            if v is None:
                continue
            flag = next((r for r in row["regressions"]
                         if r["metric"] == name), None)
            lines.append(
                f"  {name:<14} {_fmt(v):>12}"
                f"{_fmt_delta(row['deltas'].get(name))}"
                + ("   << REGRESSION" if flag else ""))
        for leg, tl in (row.get("timeline") or {}).items():
            fr = tl.get("fractions") or {}
            parts = " ".join(f"{b}={fr[b]:.0%}" for b in
                             ("compute", "collective", "memcpy",
                              "host", "idle") if b in fr)
            exp = tl.get("exposed_collective_s")
            lines.append(f"  {leg + '_timeline':<14} {parts}"
                         + (f"  exposed_comm={exp * 1e3:.3g}ms"
                            if exp is not None else ""))
        sw = row.get("serving_sweep")
        if sw:
            for c in sw["configs"]:
                extras = []
                if c.get("p99_ms") is not None:
                    extras.append(f"p99={c['p99_ms']:.3g}ms")
                if c.get("prefix_cache_hits"):
                    extras.append(f"prefix_hits={c['prefix_cache_hits']}")
                if isinstance(c.get("speculative_accepted_ratio"),
                              (int, float)):
                    extras.append(
                        f"spec_accept="
                        f"{c['speculative_accepted_ratio']:.0%}")
                lines.append(
                    f"  sweep {c['name']:<22}"
                    f" {_fmt(c['decode_tok_s']):>10} tok/s"
                    f"{_fmt_delta(c.get('delta'))}  "
                    + " ".join(extras))
            for w in sw["winners"]:
                target = "unconstrained" if w["slo_ms"] is None \
                    else f"p99<={w['slo_ms']:g}ms"
                if w.get("config"):
                    lines.append(
                        f"  sweep winner [{target}] {w['config']}"
                        f" ({_fmt(w['decode_tok_s'])} tok/s)")
                else:
                    lines.append(
                        f"  sweep winner [{target}] none fits")
        sh = row.get("serving_sharded")
        if sh:
            parts = [f"mesh {sh['mesh']}",
                     f"{_fmt(sh['decode_tok_s'])} tok/s"
                     f"{_fmt_delta(row['deltas'].get('serve_sh_tok_s'))}"]
            if sh.get("vs_unsharded") is not None:
                parts.append(f"{sh['vs_unsharded']:.2f}x unsharded")
            if sh.get("kv_per_device_mib") is not None:
                parts.append(
                    f"kv/dev={sh['kv_per_device_mib']:.3g}MiB")
            if sh.get("hbm_peak_gib") is not None:
                parts.append(f"hbm/dev={sh['hbm_peak_gib']:.3g}GiB")
            lines.append("  sharded " + "  ".join(parts))
        lines.append("")
    regs = report["regressions"]
    lines.append(f"{len(report['rounds'])} round(s), "
                 f"{len(regs)} regression(s) at "
                 f"threshold {report['threshold']:.0%}"
                 + (f", mfu floor {report['mfu_floor']}"
                    if report.get("mfu_floor") is not None else ""))
    for r in regs:
        kind = f" [{r['kind']}]" if r.get("kind") else ""
        delta = f" ({r['delta']:+.1%})" if isinstance(
            r.get("delta"), (int, float)) else ""
        lines.append(f"  REGRESSION{kind} {r['metric']}: "
                     f"{_fmt(r['prev'])} -> {_fmt(r['now'])}{delta}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# selftest
# ---------------------------------------------------------------------------

def selftest():
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        recs = [
            # r1: fp32 only, no timeline yet
            {"n": 1, "parsed": {
                "value": 1000.0, "mfu": 0.12, "platform": "tpu",
                "device_kind": "TPU v5 lite", "git": "aaa111",
                "measured_at": "2026-01-01T00:00:00"}},
            # r2: bf16 + lm appear, timeline + serving_sweep banked
            {"n": 2, "parsed": {
                "value": 1100.0, "mfu": 0.14, "platform": "tpu",
                "bf16_throughput": 2400.0, "bf16_mfu": 0.30,
                "lm_tokens_per_sec": 140000.0,
                "hbm_peak_bytes": 6 * 2**30, "git": "bbb222",
                "timeline": {"fractions": {
                    "compute": 0.5, "collective": 0.1, "memcpy": 0.05,
                    "host": 0.15, "idle": 0.2},
                    "exposed_collective_s": 4e-5, "window_s": 4e-4},
                "serving": {"decode_tok_s": 500.0,
                            "p99_token_s": 0.002},
                "serving_sharded": {
                    "decode_tok_s": 400.0,
                    "mesh": {"batch": 2, "model": 2, "devices": 4},
                    "kv_per_device_bytes": 8 * 2**20,
                    "hbm_peak_bytes": 2 * 2**30},
                "serving_sweep": {"configs": [
                    {"kv_layout": "ring", "slots": 4,
                     "prefill_len": 16, "speculative_k": 0,
                     "decode_tok_s": 500.0, "p99_token_s": 0.0008},
                    {"kv_layout": "paged", "slots": 4,
                     "prefill_len": 16, "speculative_k": 4,
                     "decode_tok_s": 900.0, "p99_token_s": 0.004,
                     "prefix_cache_hits": 5,
                     "speculative_accepted_ratio": 0.4}]}}},
            # r3: bf16 REGRESSES 20%, lm improves; a cpu-fallback round
            # in between must NOT become anyone's comparison baseline
            {"n": 3, "parsed": {
                "value": 9.0, "platform": "cpu", "git": "ccc333"}},
            {"n": 4, "parsed": {
                "value": 1105.0, "platform": "tpu",
                "bf16_throughput": 1920.0, "bf16_mfu": 0.30,
                "lm_tokens_per_sec": 150000.0, "git": "ddd444",
                "timeline": {"fractions": {"compute": 0.55},
                             "exposed_collective_s": 4e-5,
                             "window_s": 4e-4},
                "serving_sharded": {
                    "decode_tok_s": 440.0,
                    "mesh": {"batch": 2, "model": 2, "devices": 4},
                    "kv_per_device_bytes": 8 * 2**20},
                "serving_sweep": {"configs": [
                    {"kv_layout": "paged", "slots": 4,
                     "prefill_len": 16, "speculative_k": 4,
                     "decode_tok_s": 990.0, "p99_token_s": 0.004}]}}},
        ]
        for r in recs:
            with open(os.path.join(td, f"BENCH_r{r['n']:02d}.json"),
                      "w") as f:
                json.dump(r, f)
        # a torn file must be skipped, not fatal
        with open(os.path.join(td, "BENCH_r99.json"), "w") as f:
            f.write("{torn")

        records = load_records(td)
        assert [n for n, _p in records] == [1, 2, 3, 4], records
        report = build_report(records, threshold=0.05)
        rows = {r["round"]: r for r in report["rounds"]}

        assert rows[1]["metrics"]["img_s"] == 1000.0
        assert rows[1]["deltas"] == {}           # nothing to diff yet
        # r2 deltas against r1; legs appearing for the first time have
        # no delta
        assert abs(rows[2]["deltas"]["img_s"] - 0.10) < 1e-9
        assert "bf16_img_s" not in rows[2]["deltas"]
        assert rows[2]["timeline"]["fp32"]["fractions"]["idle"] == 0.2
        assert "serving" not in rows[2]["timeline"]  # no timeline there
        assert rows[2]["metrics"]["serve_tok_s"] == 500.0
        assert rows[2]["metrics"]["serve_p99_ms"] == 2.0
        assert rows[2]["metrics"]["hbm_peak_gib"] == 6.0
        # serving_sweep: best-config scalar extracted, per-config
        # curves + winner-per-SLO table built
        assert rows[2]["metrics"]["sweep_best_tok_s"] == 900.0
        # serving_sharded: decode tok/s + per-device bytes extracted,
        # the vs-unsharded ratio computed from the SAME round's
        # serving record, and the r4 repeat carries a same-platform
        # delta across the cpu round
        shb = rows[2]["serving_sharded"]
        assert shb["mesh"] == "2x2" and shb["decode_tok_s"] == 400.0
        assert abs(shb["vs_unsharded"] - 0.8) < 1e-9, shb
        assert shb["kv_per_device_mib"] == 8.0
        assert rows[2]["metrics"]["serve_sh_kv_dev_mib"] == 8.0
        assert abs(rows[4]["deltas"]["serve_sh_tok_s"] - 0.10) < 1e-9
        assert "vs_unsharded" not in rows[4]["serving_sharded"]
        sw = rows[2]["serving_sweep"]
        assert [c["name"] for c in sw["configs"]] == \
            ["ring s4 pf16 k0", "paged s4 pf16 k4"]
        by_slo = {w["slo_ms"]: w for w in sw["winners"]}
        # under a 1ms p99 budget only the ring config fits; the paged
        # speculative config wins once the budget allows it
        assert by_slo[1.0]["config"] == "ring s4 pf16 k0", by_slo
        assert by_slo[5.0]["config"] == "paged s4 pf16 k4"
        assert by_slo[None]["config"] == "paged s4 pf16 k4"
        # r4's repeated paged config carries a same-platform delta
        # (matched by name, across the cpu round); the vanished ring
        # config simply has none
        sw4 = rows[4]["serving_sweep"]["configs"]
        assert abs(sw4[0]["delta"] - 0.10) < 1e-9, sw4
        # the cpu-fallback round has no tpu baseline: no delta, no flag
        assert rows[3]["deltas"] == {} and not rows[3]["regressions"]
        # r4 compares against r2 (the previous TPU round, ACROSS the
        # cpu round): the 20% bf16 drop is flagged; the small fp32
        # wiggle and the lm IMPROVEMENT are not
        (reg,) = report["regressions"]
        assert reg["metric"] == "bf16_img_s" and \
            abs(reg["delta"] + 0.20) < 1e-9 and \
            reg["vs_round"] == 2, reg
        assert rows[4]["deltas"]["lm_tok_s"] > 0
        assert not [r for r in rows[4]["regressions"]
                    if r["metric"] != "bf16_img_s"]

        text = render_table(report)
        assert "REGRESSION" in text and "bf16_img_s" in text
        assert "compute=50%" in text and "exposed_comm" in text
        assert "sweep paged s4 pf16 k4" in text and \
            "sweep winner [p99<=1ms] ring s4 pf16 k0" in text and \
            "spec_accept=40%" in text, text
        assert "sharded mesh 2x2" in text and \
            "0.80x unsharded" in text and "kv/dev=8MiB" in text, text
        json.dumps(report)                       # JSON-able end to end

        # --mfu-floor gate: r5 drops bf16 MFU below the floor r2 held
        # AND exposes more collective time than r2's timeline banked —
        # both flag (and only with the floor armed)
        with open(os.path.join(td, "BENCH_r05.json"), "w") as f:
            json.dump({"n": 5, "parsed": {
                "value": 1100.0, "platform": "tpu", "git": "eee555",
                "bf16_throughput": 2400.0, "bf16_mfu": 0.22,
                "timeline": {"fractions": {"compute": 0.6},
                             "exposed_collective_s": 9e-4,
                             "window_s": 4e-4}}}, f)
        records5 = load_records(td)
        plain = build_report(records5, threshold=0.05)
        assert not [r for r in plain["regressions"]
                    if r.get("kind")], plain["regressions"]
        armed = build_report(records5, threshold=0.05, mfu_floor=0.30)
        kinds = {r["metric"]: r for r in armed["regressions"]
                 if r.get("kind")}
        floor = kinds["bf16_mfu"]
        assert floor["kind"] == "mfu_floor" and floor["prev"] == 0.30 \
            and floor["now"] == 0.22, floor
        ec = kinds["fp32_exposed_comm"]
        assert ec["kind"] == "exposed_comm" and ec["prev"] == 4e-5 \
            and ec["now"] == 9e-4, ec
        # an MFU already under the floor but HOLDING (tiny wiggle) does
        # not flag: r6 repeats r5's bf16_mfu
        with open(os.path.join(td, "BENCH_r06.json"), "w") as f:
            json.dump({"n": 6, "parsed": {
                "value": 1100.0, "platform": "tpu",
                "bf16_throughput": 2400.0, "bf16_mfu": 0.219}}, f)
        armed6 = build_report(load_records(td), threshold=0.05,
                              mfu_floor=0.30)
        assert not [r for r in armed6["regressions"]
                    if r.get("kind") and r.get("vs_round") == 5], \
            armed6["regressions"]
        text5 = render_table(armed)
        assert "mfu_floor" in text5 and "exposed_comm" in text5
    print("selftest: OK — 4-round trajectory extracted, same-platform "
          "deltas and timeline columns rendered, the 20% bf16 drop "
          "flagged across the cpu round, torn record skipped, the "
          "serving_sweep curves + winner-per-SLO table built (with "
          "per-config deltas), the serving_sharded leg rendered with "
          "its vs-unsharded ratio + per-device bytes, and the "
          "--mfu-floor gate flags the lost floor + exposed-comm rise "
          "only when armed")


def main():
    ap = argparse.ArgumentParser(
        description="render the banked BENCH_r*.json benchmark "
                    "trajectory (per-leg throughput/MFU/HBM/timeline "
                    "with regression deltas)")
    ap.add_argument("--dir", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))),
        help="directory holding BENCH_r*.json (default: repo root)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as JSON instead of the "
                         "table")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="fractional drop that flags a regression "
                         "(default 0.05)")
    ap.add_argument("--mfu-floor", type=float, default=None,
                    metavar="X",
                    help="arm the MFU gate: exit 3 when any leg's MFU "
                         "falls below X after the previous same-"
                         "platform record held it (or keeps dropping "
                         "past --threshold under it), or when a leg's "
                         "timeline exposed_collective_s rises more "
                         "than --threshold vs the previous record — "
                         "the cron guard for the overlap/fused-kernel "
                         "wins")
    ap.add_argument("--selftest", action="store_true",
                    help="run the built-in synthetic-trajectory check "
                         "(the tier-1 CI gate)")
    args = ap.parse_args()
    if args.selftest:
        selftest()
        return
    records = load_records(args.dir)
    if not records:
        print(f"no BENCH_r*.json records under {args.dir}",
              file=sys.stderr)
        raise SystemExit(2)
    report = build_report(records, threshold=args.threshold,
                          mfu_floor=args.mfu_floor)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(render_table(report))
    # regressions exit nonzero so a cron wrapper can alarm on it
    if report["regressions"]:
        raise SystemExit(3)


if __name__ == "__main__":
    main()
