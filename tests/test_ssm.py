"""The selective state-space ops (``ops/ssm.py``) on the CPU: one
parametrised test a property, so each case counts.

- the chunked scan equals the recurrence taken a token at a time
  (``selective_step`` in a python loop) for ragged ``lengths``, a non-zero
  initial state, chunks that divide the sequence and a sequence no chunk
  divides, and equals the textbook recurrence written with numpy;
- positions at or past a row's ``length`` leave its state untouched and
  the state after the scan is the state at the row's last true token;
- the causal convolution equals the sum it is defined as, taken whole or
  a token at a time through its tail, and its new tail is the last
  ``K - 1`` inputs before ``length``;
- state and sums are float32 whatever the operands.

Tolerances: float32 throughout, so the two orders of the same few
multiply-adds differ by rounding alone (1e-5 on values of magnitude ~1).
"""

import numpy as np
import pytest
import jax.numpy as jnp

from singa_tpu.ops import ssm

ATOL = 1e-5


def _inputs(B, S, C, N, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.normal(size=s).astype(np.float32)   # noqa: E731
    return dict(
        x=jnp.asarray(f(B, S, C), dtype),
        dt=jnp.asarray(np.log1p(np.exp(f(B, S, C) - 2.0)), dtype),
        A=-jnp.exp(jnp.asarray(f(C, N))),
        B=jnp.asarray(f(B, S, N), dtype), C=jnp.asarray(f(B, S, N), dtype),
        D=jnp.asarray(1.0 + 0.1 * f(C)),
        s0=jnp.asarray(0.5 * f(B, N, C)))


def _token_by_token(a, lengths):
    s, ys = a["s0"], []
    for t in range(a["x"].shape[1]):
        y, s = ssm.selective_step(
            a["x"][:, t], a["dt"][:, t], a["A"], a["B"][:, t], a["C"][:, t],
            a["D"], s, jnp.asarray(t < np.asarray(lengths)))
        ys.append(y)
    return jnp.stack(ys, axis=1), s


@pytest.mark.parametrize("S,chunk,lengths", [
    (32, 16, [32, 32, 32]),       # whole, two chunks
    (32, 8, [5, 32, 17]),         # ragged, inside and on chunk edges
    (32, 16, [16, 1, 0]),         # a chunk edge, one token, a padding row
    (30, 16, [30, 7, 12]),        # no chunk divides: a token a trip
    (48, 16, [9, 3, 14]),         # the chunks past the longest row are left
])
def test_chunked_scan_equals_the_recurrence_a_token_at_a_time(S, chunk,
                                                              lengths):
    a = _inputs(3, S, 12, 4, seed=S + chunk)
    y, s = ssm.selective_scan(a["x"], a["dt"], a["A"], a["B"], a["C"],
                              a["D"], a["s0"], jnp.asarray(lengths),
                              chunk=chunk)
    want_y, want_s = _token_by_token(a, lengths)
    assert y.dtype == s.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=ATOL)
    for b, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(y)[b, :n],
                                   np.asarray(want_y)[b, :n], atol=ATOL)


def test_scan_equals_the_recurrence_as_published():
    """``s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) (x) B_t; y_t = s_t C_t + D
    x_t`` with the state (C, N), in numpy."""
    a = {k: np.asarray(v, np.float64) for k, v in
         _inputs(2, 20, 6, 3, seed=1).items()}
    want = np.zeros((2, 20, 6))
    for b in range(2):
        s = a["s0"][b].T.copy()                              # (C, N)
        for t in range(20):
            s = np.exp(a["dt"][b, t][:, None] * a["A"]) * s \
                + (a["dt"][b, t] * a["x"][b, t])[:, None] * a["B"][b, t]
            want[b, t] = s @ a["C"][b, t] + a["D"] * a["x"][b, t]
    f = _inputs(2, 20, 6, 3, seed=1)
    y, _ = ssm.selective_scan(f["x"], f["dt"], f["A"], f["B"], f["C"],
                              f["D"], f["s0"], jnp.asarray([20, 20]),
                              chunk=4)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4)


@pytest.mark.parametrize("lengths", [[0, 0], [4, 0], [11, 16]])
def test_positions_past_a_rows_length_leave_its_state(lengths):
    a = _inputs(2, 16, 8, 4, seed=3)
    _, s = ssm.selective_scan(a["x"], a["dt"], a["A"], a["B"], a["C"],
                              a["D"], a["s0"], jnp.asarray(lengths), chunk=8)
    for b, n in enumerate(lengths):
        cut = {k: (v[b:b + 1, :n] if k in ("x", "dt", "B", "C") else v)
               for k, v in a.items()}
        if n == 0:
            want = a["s0"][b]
        else:
            _, want = ssm.selective_scan(
                cut["x"], cut["dt"], a["A"], cut["B"], cut["C"], a["D"],
                a["s0"][b:b + 1], jnp.asarray([n]), chunk=1)
            want = want[0]
        np.testing.assert_allclose(np.asarray(s)[b], np.asarray(want),
                                   atol=ATOL)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_state_and_sums_are_float32_whatever_the_operands(dtype):
    a = _inputs(2, 16, 8, 4, seed=5, dtype=dtype)
    y, s = ssm.selective_scan(a["x"], a["dt"], a["A"], a["B"], a["C"],
                              a["D"], a["s0"], jnp.asarray([16, 9]))
    y1, s1 = ssm.selective_step(a["x"][:, 0], a["dt"][:, 0], a["A"],
                                a["B"][:, 0], a["C"][:, 0], a["D"], a["s0"],
                                jnp.asarray([True, False]))
    assert {y.dtype, s.dtype, y1.dtype, s1.dtype} == {jnp.dtype("float32")}
    np.testing.assert_array_equal(np.asarray(s1)[1], np.asarray(a["s0"])[1])


def _conv_inputs(B, S, C, K, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return f(B, S, C), f(C, K), f(C), f(B, K - 1, C)


@pytest.mark.parametrize("lengths", [[12, 12], [5, 12], [1, 0], [2, 3]])
def test_causal_conv_is_the_sum_over_the_last_inputs(lengths):
    x, w, b, tail = _conv_inputs(2, 12, 6, 4, seed=sum(lengths))
    y, new_tail = ssm.causal_conv(x, w, b, tail, jnp.asarray(lengths))
    hist = np.concatenate([np.asarray(tail), np.asarray(x)], axis=1)
    for r, n in enumerate(lengths):
        for t in range(n):
            want = np.asarray(b) + sum(
                np.asarray(w)[:, k] * hist[r, t + k] for k in range(4))
            np.testing.assert_allclose(np.asarray(y)[r, t], want, atol=ATOL)
        # the last three inputs before `length` (the old tail's where the
        # row is shorter than that)
        np.testing.assert_array_equal(np.asarray(new_tail)[r],
                                      hist[r, n:n + 3])


def test_causal_conv_a_token_at_a_time_through_its_tail():
    x, w, b, tail = _conv_inputs(2, 10, 6, 4, seed=9)
    whole, whole_tail = ssm.causal_conv(x, w, b, tail, jnp.asarray([10, 10]))
    t_, ys = tail, []
    for t in range(10):
        # row 1 is dead at t = 4: its tail stands still for that call
        live = jnp.asarray([1, 0 if t == 4 else 1])
        y, nxt = ssm.causal_conv(x[:, t:t + 1], w, b, t_, live)
        if t == 4:
            np.testing.assert_array_equal(np.asarray(nxt)[1],
                                          np.asarray(t_)[1])
            y, nxt = ssm.causal_conv(x[:, t:t + 1], w, b, t_,
                                     jnp.asarray([1, 1]))
        t_ = nxt
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, axis=1)),
                               np.asarray(whole), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(t_), np.asarray(whole_tail))


def test_conv_tail_keeps_its_own_dtype():
    x, w, b, tail = _conv_inputs(1, 4, 6, 4)
    y, new_tail = ssm.causal_conv(x.astype(jnp.bfloat16), w, b,
                                  tail.astype(jnp.bfloat16),
                                  jnp.asarray([4]))
    assert y.dtype == jnp.float32 and new_tail.dtype == jnp.bfloat16
