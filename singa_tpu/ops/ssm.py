"""The selective state-space recurrence (Mamba-1) for serving.

For a sequence of inputs ``x_t`` (C channels), step sizes ``dt_t`` (C),
and per-token ``B_t`` / ``C_t`` (N each), with ``A`` (C, N) negative::

    s_t = exp(dt_t A) * s_{t-1} + (dt_t x_t) (x) B_t        (C, N)
    y_t = s_t . C_t + D * x_t                               (C,)

in front of it a depthwise causal convolution over the last ``K`` inputs.
What a sequence keeps between calls has a fixed size whatever its length:
the state ``s`` and the convolution's last ``K - 1`` inputs.

:func:`selective_scan` runs whole (padded) prompts, :func:`selective_step`
one token a row; both go through :func:`_advance`, the recurrence written
once. The scan is chunked: the state is carried from chunk to chunk by one
loop whose length is read on the device (``ceil(max(lengths) / chunk)``
— padding behind the longest prompt costs nothing), a chunk's inputs are
sliced and its outputs written in bulk, and the steps inside a chunk are
unrolled, so that XLA sees ``chunk`` dependent elementwise updates of the
state and not ``S`` trips of a loop. (An associative scan inside the chunk
was weighed and left: it moves the ``(chunk, B, N, C)`` decay and input
terms through memory a dozen times where the unrolled recurrence keeps one
state, and the recurrence is a few hundred vector ops wide at every step,
so the dependency costs nothing.)

The state is float32 and lies ``(B, N, C)``: the channels on the lanes,
the N = 16 state columns on the sublanes (``(B, C, 16)`` would pad each
row of 16 to a lane tile of 128). Sums, ``exp`` and the state are float32
whatever the operands. Positions at or past a row's ``length`` leave its
state and its convolution tail untouched (``dt`` is 0 there).

Inference only: no reverse is defined here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# tokens a chunk of the scan: the loop's trips are S / CHUNK, the unrolled
# body CHUNK updates of the state
CHUNK = 16


def causal_conv(x, w, b, tail, lengths):
    """Depthwise causal convolution over the last ``K`` inputs.

    ``x``: (B, S, C); ``w``: (C, K), ``w[:, K - 1]`` multiplying the
    current input; ``b``: (C,); ``tail``: (B, K - 1, C), the inputs
    before ``x``; ``lengths``: (B,) true lengths. Returns ``(y (B, S, C)
    float32, tail')`` with ``tail'`` the last ``K - 1`` inputs before
    ``length`` (``tail`` itself for a row of length 0), in ``tail``'s
    dtype."""
    S, K = x.shape[1], w.shape[1]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(jnp.float32)
    y = b.astype(jnp.float32) + sum(
        xp[:, k:k + S].astype(jnp.float32) * wf[:, k] for k in range(K))
    at = lengths.astype(jnp.int32)[:, None] + jnp.arange(K - 1)
    new_tail = jnp.take_along_axis(xp, at[:, :, None], axis=1)
    return y, new_tail.astype(tail.dtype)


def _advance(s, a_t, dt, dtx, b, c):
    """One token: ``s`` (B, N, C) float32; ``a_t`` (N, C); ``dt``,
    ``dtx`` (B, C); ``b``, ``c`` (B, N). Returns ``(s', s' . c (B, C))``."""
    s = jnp.exp(dt[:, None, :] * a_t) * s + dtx[:, None, :] * b[:, :, None]
    return s, jnp.sum(s * c[:, :, None], axis=1)


def selective_step(x, dt, A, B, C, D, s, active):
    """One token a row: ``x``, ``dt`` (B, C); ``A`` (C, N); ``B``, ``C``
    (B, N); ``D`` (C,); ``s`` (B, N, C) float32; ``active`` (B,) bool —
    a row that is not keeps its state. Returns ``(y (B, C) float32,
    s')``."""
    f32 = jnp.float32
    with jax.named_scope("ssm_step"):
        xf = x.astype(f32)
        dt = jnp.where(active[:, None], dt.astype(f32), 0.0)
        s, y = _advance(s, A.astype(f32).T, dt, dt * xf, B.astype(f32),
                        C.astype(f32))
        return y + D.astype(f32) * xf, s


def selective_scan(x, dt, A, B, C, D, s0, lengths, chunk=CHUNK):
    """Whole sequences: ``x``, ``dt`` (B, S, C); ``A`` (C, N); ``B``,
    ``C`` (B, S, N); ``D`` (C,); ``s0`` (B, N, C) float32; ``lengths``
    (B,). Returns ``(y (B, S, C) float32, s_last)`` — the state after each
    row's last true token; ``y`` is nought in the chunks past the longest
    row."""
    f32 = jnp.float32
    Bn, S, Cn = x.shape
    T = chunk if S % chunk == 0 else 1
    with jax.named_scope("ssm_scan"):
        lengths = lengths.astype(jnp.int32)
        a_t = A.astype(f32).T
        live = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]

        def one(i, carry):
            s, y = carry
            t0 = i * T
            cut = lambda a: lax.dynamic_slice_in_dim(  # noqa: E731
                a, t0, T, axis=1)
            xf = cut(x).astype(f32)
            dtc = jnp.where(cut(live)[:, :, None], cut(dt).astype(f32), 0.0)
            dtx = dtc * xf
            bc, cc = cut(B).astype(f32), cut(C).astype(f32)
            ys = []
            for t in range(T):
                s, yt = _advance(s, a_t, dtc[:, t], dtx[:, t], bc[:, t],
                                 cc[:, t])
                ys.append(yt)
            yc = jnp.stack(ys, axis=1) + D.astype(f32) * xf
            return s, lax.dynamic_update_slice_in_dim(y, yc, t0, axis=1)

        n_live = -(-jnp.max(lengths) // T)
        s, y = lax.fori_loop(0, n_live, one,
                             (s0.astype(f32), jnp.zeros((Bn, S, Cn), f32)))
        return y, s


__all__ = ["causal_conv", "selective_scan", "selective_step", "CHUNK"]
