"""What must hold where there is no chip: the chip check and the
benchmark fail at once and report nothing, ``create_tpu_device`` raises,
and ``chip_smoke.py --dry-run`` walks every phase's control flow at tiny
sizes on the virtual CPU mesh (Pallas interpreted)."""

import json
import os
import subprocess
import sys
import time

import pytest

from singa_tpu import device

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    t0 = time.time()
    proc = subprocess.run([sys.executable] + args, cwd=ROOT, env=full,
                          capture_output=True, text=True, timeout=timeout)
    return proc, time.time() - t0


def _json_lines(text):
    out = []
    for line in text.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict):
            out.append(doc)
    return out


def test_create_tpu_device_raises_without_accelerator():
    """The reference's create_cuda_gpu fails without a GPU; so do the
    TPU factory and its CUDA-named aliases — no quiet CPU stand-in."""
    for make in (device.create_tpu_device, device.create_cuda_gpu,
                 lambda: device.create_tpu_devices(2),
                 lambda: device.create_cuda_gpu_on(0)):
        with pytest.raises(RuntimeError, match="no accelerator"):
            make()
    assert device.create_cpu_device().jax_device.platform == "cpu"


def test_chip_smoke_fails_at_once_without_a_chip():
    proc, secs = _run(["chip_smoke.py"], timeout=120)
    assert proc.returncode != 0
    assert secs < 60, secs
    assert "no TPU" in proc.stderr
    assert not _json_lines(proc.stdout), proc.stdout[-500:]


def test_bench_fails_without_a_chip_and_prints_no_metric():
    proc, _ = _run(["bench.py"], timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "metric" not in proc.stdout and \
        not _json_lines(proc.stdout), proc.stdout[-500:]


@pytest.mark.slow       # a minute of cold XLA:CPU compiles
def test_chip_smoke_dry_run_walks_every_phase(tmp_path):
    """Every phase, multichip included (four of the eight virtual
    devices), passes at tiny sizes — and a dry run never prints the
    result line the driver reads. The compile cache is placed from
    outside, in a directory of the test's own."""
    cache = tmp_path / "cache"
    proc, _ = _run(["chip_smoke.py", "--dry-run"], timeout=600,
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   JAX_COMPILATION_CACHE_DIR=str(cache))
    assert proc.returncode == 0, \
        f"{proc.stdout[-1500:]}\n{proc.stderr[-3000:]}"
    lines = proc.stdout.strip().splitlines()
    assert lines[0] == "DRY RUN platform=cpu"
    assert lines[-1].startswith("DRY RUN platform=cpu passed")
    phases = {d["phase"]: d for d in _json_lines(proc.stdout)
              if "phase" in d}
    assert list(phases) == ["device", "resnet50_train", "lm_train",
                            "kernels", "serve", "moe_serve", "multichip",
                            "cache"]
    assert all(d["ok"] and d["platform"] == "cpu"
               for d in phases.values())
    assert not phases["multichip"].get("skipped")
    assert phases["multichip"]["resnet_gspmd_fsdp"]["ratio"] > 3.2
    assert phases["serve"]["paged"]["decode_n_traces"] == 1
    assert phases["moe_serve"]["longest_context"] > 128     # the window
    assert set(phases["kernels"]["ring_decode_rel_err"]) == {"d64", "d128"}
    assert phases["cache"]["cache_dir"] == str(cache)
    assert phases["cache"]["compile_cache_misses_total"] > 0
    assert any(n.endswith("-cache") for n in os.listdir(cache))
    assert not any("ok" in d and "device" in d
                   for d in _json_lines(proc.stdout))


def test_bench_knobs_are_an_env_pin_or_the_default(monkeypatch):
    """No third source: a knob is its validated env pin or its default
    — nothing read from a file git would not commit."""
    import bench
    monkeypatch.delenv("BENCH_CONV_LAYOUT", raising=False)
    assert bench._conv_layout() == ("NCHW", "default")
    monkeypatch.setenv("BENCH_CONV_LAYOUT", "nhwc")
    assert bench._conv_layout() == ("NHWC", "env")
    monkeypatch.setenv("BENCH_CONV_LAYOUT", "auto")
    with pytest.raises(ValueError, match="BENCH_CONV_LAYOUT"):
        bench._conv_layout()
    monkeypatch.delenv("BENCH_FUSED_OPTIM", raising=False)
    assert bench._fused_optim() == ("reference", "default")


@pytest.mark.parametrize("args, needle", [
    (["examples/train_multiprocess.py", "--procs", "2",
      "--platform", "tpu"], "one rank per host"),
    (["examples/train_elastic.py", "--world", "2", "--dir", "unused"],
     "one rank per host"),
])
def test_single_host_launchers_refuse_accelerator_ranks(args, needle):
    """N ranks on one accelerator host would each claim every chip (a
    chip belongs to one process): the launchers say so instead of
    hanging."""
    proc, secs = _run(args, timeout=120)
    assert proc.returncode != 0 and secs < 60
    assert needle in proc.stderr and "every" in proc.stderr, \
        proc.stderr[-800:]
