"""Activation-layout selection for the 2-D CNN stack (NCHW vs NHWC).

The reference API is NCHW end-to-end (cuDNN's native layout,
src/model/operation/convolution.h:43-90). On TPU the MXU wants the
channel dimension in the 128-lane minor position, so NHWC activations
avoid the relayout copies XLA otherwise inserts around every conv/BN
fusion. This module provides the one switch the conv/pool/BN handles
consult at construction time:

- the *public* tensor API stays NCHW (reference parity);
- a model that opts in (e.g. ``models.resnet.create_model(layout="NHWC")``)
  transposes its input once at the stem and runs its whole conv trunk
  channels-last, with weights still stored OIHW so checkpoints are
  layout-independent.

Which layout is faster is a hardware question that no chip run has
answered yet (ROADMAP.md D3): NCHW is the default and NHWC an explicit
choice (``create_model(layout=)``, ``BENCH_CONV_LAYOUT``).
"""

from __future__ import annotations

import contextlib
import os
from contextvars import ContextVar

_VALID = ("NCHW", "NHWC")


def _env_default() -> str:
    v = os.environ.get("SINGA_CONV_LAYOUT", "NCHW").upper()
    return v if v in _VALID else "NCHW"


# Per-context (thread/task) scope stack: a ContextVar instead of a
# process-global list, so an NHWC scope entered while one model builds
# (e.g. training) can never leak into handle construction on another
# thread (e.g. a concurrent serving model) — each thread/asyncio task
# sees only its own scopes, falling back to the env default.
_stack: ContextVar[tuple] = ContextVar("singa_tpu_conv_layout",
                                       default=(_env_default(),))


def current_layout() -> str:
    """Layout new conv/pool/BN handles capture (handles read this once
    at construction; op forward paths use the captured value)."""
    return _stack.get()[-1]


def channel_axis(ndim: int = 4) -> int:
    """Channel axis of an activation under the current layout."""
    return 1 if current_layout() == "NCHW" or ndim == 2 else ndim - 1


def resolve(layout) -> str:
    """Normalise a handle's layout argument: explicit value (validated)
    or the ambient default. The one place every handle resolves through,
    so a typo'd layout= fails loudly instead of silently meaning NCHW."""
    v = (str(layout).upper() if layout else current_layout())
    if v not in _VALID:
        raise ValueError(f"layout must be one of {_VALID}, got {layout!r}")
    return v


@contextlib.contextmanager
def use_layout(layout: str):
    """Scope a layout for handle construction and deferred layer init —
    a model's forward wraps its conv trunk in this so its layers
    initialize channels-last without any global state leaking out."""
    layout = str(layout).upper()
    if layout not in _VALID:
        raise ValueError(f"layout must be one of {_VALID}, got {layout!r}")
    token = _stack.set(_stack.get() + (layout,))
    try:
        yield
    finally:
        _stack.reset(token)
