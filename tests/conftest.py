"""Test harness: hermetic multi-device CPU mesh.

The reference cannot test distributed paths without a GPU cluster
(SURVEY.md §4); we can — 8 virtual XLA host devices stand in for an 8-chip
slice, so DP/collective tests run on any machine.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)


@pytest.fixture
def training_mode():
    """Shared tape-mode toggle for tests that record backward: request
    (or alias with an autouse shim) instead of hand-rolling the
    save/set/restore dance per module."""
    from singa_tpu.autograd_base import CTX
    prev = CTX.training
    CTX.training = True
    yield
    CTX.training = prev


@pytest.fixture(autouse=True)
def _fresh_mode():
    """Tape mode is process-global; a test that trains and never calls
    eval() would leak training=True into later tests and silently flip
    BatchNorm/Dropout semantics there (seen as order-dependent ONNX
    backend-suite failures). Every test starts in inference mode; tests
    that train set it themselves (Model.train / the gradcheck
    fixture)."""
    from singa_tpu.autograd_base import CTX
    CTX.training = False
    yield
    CTX.training = False


# ---------------------------------------------------------------------------
# Two-tier suite: the default run skips tests marked `slow` so the
# everyday loop stays fast; `--full` (CI / pre-release) runs everything.
#   python -m pytest tests/ -q          # fast tier (default)
#   python -m pytest tests/ -q --full   # entire suite
# ---------------------------------------------------------------------------

def pytest_addoption(parser):
    parser.addoption(
        "--full", action="store_true", default=False,
        help="run the slow tier too (long meshes, example smoke runs, "
             "multi-process bootstraps)")


# (the `slow` and `chaos` markers are registered in pyproject.toml's
# [tool.pytest.ini_options] — one source of truth)


def _selects_slow_tier(markexpr):
    """True when -m POSITIVELY selects a slow-tier marker (``slow``,
    ``chaos``, …) — i.e. the marker appears and is not negated."""
    import re
    return any(
        re.search(rf"\b{m}\b", markexpr)
        and not re.search(rf"\bnot\s+{m}\b", markexpr)
        for m in ("slow", "chaos"))


def pytest_collection_modifyitems(config, items):
    if config.getoption("--full"):
        return
    markexpr = config.getoption("-m") or ""
    if _selects_slow_tier(markexpr):
        # `pytest -m slow` without --full used to report a green
        # "63 skipped" NO-OP — the worst kind of pass. Selecting the
        # slow tier by marker IS the opt-in, so imply --full instead
        # of silently skipping everything that was asked for.
        tr = config.pluginmanager.getplugin("terminalreporter")
        if tr is not None:
            tr.write_line(
                f"[conftest] -m {markexpr!r} selects the slow tier: "
                "implying --full so the selection actually runs")
        return
    skip = pytest.mark.skip(
        reason="slow tier (run with --full)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
