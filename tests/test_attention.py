"""Flash + ring attention: numerics vs naive softmax oracle, causal
masking, gradients, and ring==single-device parity on the 8-dev mesh."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
try:
    from jax import shard_map
except ImportError:
    from jax.experimental.shard_map import shard_map

from singa_tpu.ops import attention_mod as ATTN
from singa_tpu.ops.attention import (flash_attention, ring_attention,
                                     attention)
from singa_tpu import autograd
from singa_tpu.tensor import Tensor


def naive_attention(q, k, v, causal=False):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        Sq, Sk = s.shape[-2:]
        mask = jnp.tril(jnp.ones((Sq, Sk), bool))
        s = jnp.where(mask, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def qkv(B=2, H=3, S=32, D=16, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
                 for _ in range(3))


class TestFlashAttention:
    def test_matches_naive(self):
        q, k, v = qkv()
        out = flash_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(naive_attention(q, k, v)),
                                   rtol=2e-5, atol=2e-5)

    def test_causal(self):
        q, k, v = qkv(S=16)
        out = flash_attention(q, k, v, True)
        ref = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_blocking_invariance(self):
        q, k, v = qkv(S=48)
        a = flash_attention(q, k, v, False, None, 16)
        b = flash_attention(q, k, v, False, None, 48)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    def test_gradients_match_naive(self):
        q, k, v = qkv(S=16)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True) ** 2)

        def loss_naive(q, k, v):
            return jnp.sum(naive_attention(q, k, v, True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gn):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    def test_jit(self):
        q, k, v = qkv()
        out = jax.jit(lambda *a: flash_attention(*a))(q, k, v)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(naive_attention(q, k, v)),
                                   rtol=2e-5, atol=2e-5)

    def test_tape_op(self):
        autograd.training = True
        try:
            q, k, v = qkv(S=8)
            tq = Tensor(data=np.asarray(q), requires_grad=True,
                        stores_grad=True)
            tk = Tensor(data=np.asarray(k), requires_grad=True,
                        stores_grad=True)
            tv = Tensor(data=np.asarray(v), requires_grad=True,
                        stores_grad=True)
            y = attention(tq, tk, tv, causal=True)
            grads = {id(p): g for p, g in autograd.backward(y)}
            assert len(grads) == 3
            assert grads[id(tq)].shape == tq.shape
        finally:
            autograd.training = False


class TestRingAttention:
    def _ring(self, causal, n=4, S=32):
        devs = jax.devices("cpu")[:n]
        mesh = Mesh(np.array(devs), ("seq",))
        q, k, v = qkv(S=S)

        def f(q, k, v):
            return ring_attention(q, k, v, "seq", causal=causal)

        mapped = shard_map(f, mesh=mesh,
                           in_specs=(P(None, None, "seq"),) * 3,
                           out_specs=P(None, None, "seq"))
        return mapped(q, k, v), naive_attention(q, k, v, causal)

    def test_full_matches(self):
        out, ref = self._ring(causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_causal_matches(self):
        out, ref = self._ring(causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_eight_way(self):
        out, ref = self._ring(causal=True, n=8, S=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_gradients_flow(self):
        devs = jax.devices("cpu")[:4]
        mesh = Mesh(np.array(devs), ("seq",))
        q, k, v = qkv(S=32)

        def loss(q, k, v):
            out = ring_attention(q, k, v, "seq", causal=True)
            return jax.lax.psum(jnp.sum(out ** 2), "seq")

        mapped = shard_map(loss, mesh=mesh,
                           in_specs=(P(None, None, "seq"),) * 3,
                           out_specs=P())
        g = jax.grad(lambda *a: jax.jit(mapped)(*a))(q, k, v)

        def ref_loss(q):
            return jnp.sum(naive_attention(q, k, v, True) ** 2)

        gref = jax.grad(ref_loss)(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.pallas
class TestPallasKernels:
    """Validate the exact Pallas kernel math on CPU via interpreter mode
    (the TPU executes the same kernels compiled). Small block sizes force
    multi-block streaming through the grid's innermost dimension."""

    def _naive(self, q, k, v, causal):
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if causal:
            S = q.shape[2]
            mask = np.tril(np.ones((S, S), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    def _rand(self, B=2, H=2, S=64, D=16, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
        return mk(), mk(), mk()

    def test_fwd_kernel_multiblock(self):
        A = ATTN
        q, k, v = self._rand()
        scale = 1.0 / np.sqrt(q.shape[-1])
        prev = A.FORCE_PALLAS_INTERPRET
        A.FORCE_PALLAS_INTERPRET = True
        try:
            out, lse = A._pallas_flash_fwd(q, k, v, False, scale,
                                           block_q=16, block_k=16)
        finally:
            A.FORCE_PALLAS_INTERPRET = prev
        ref = self._naive(q, k, v, False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        # lse must be the true row log-sum-exp
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        ref_lse = jax.scipy.special.logsumexp(s, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=1e-5, atol=1e-5)

    def test_fwd_kernel_causal(self):
        A = ATTN
        q, k, v = self._rand(S=48)
        scale = 1.0 / np.sqrt(q.shape[-1])
        prev = A.FORCE_PALLAS_INTERPRET
        A.FORCE_PALLAS_INTERPRET = True
        try:
            out, _ = A._pallas_flash_fwd(q, k, v, True, scale,
                                         block_q=16, block_k=16)
        finally:
            A.FORCE_PALLAS_INTERPRET = prev
        ref = self._naive(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bwd_kernels_match_autodiff(self):
        A = ATTN
        for causal in (False, True):
            q, k, v = self._rand(S=32, seed=3)
            scale = 1.0 / np.sqrt(q.shape[-1])
            g = jnp.asarray(np.random.RandomState(9).randn(
                *q.shape).astype(np.float32))
            ref_out, ref_vjp = jax.vjp(
                lambda a, b, c: self._naive(a, b, c, causal), q, k, v)
            dq_r, dk_r, dv_r = ref_vjp(g)
            prev = A.FORCE_PALLAS_INTERPRET
            A.FORCE_PALLAS_INTERPRET = True
            try:
                out, lse = A._pallas_flash_fwd(q, k, v, causal, scale,
                                               block_q=16, block_k=16)
                dq, dk, dv = A._pallas_flash_bwd(q, k, v, out, lse, g,
                                                 causal, scale,
                                                 block_q=16, block_k=16)
            finally:
                A.FORCE_PALLAS_INTERPRET = prev
            for got, want, name in [(dq, dq_r, "dq"), (dk, dk_r, "dk"),
                                    (dv, dv_r, "dv")]:
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want),
                    rtol=2e-4, atol=2e-4, err_msg=f"causal={causal} {name}")

    def test_dispatch_uses_kernel_in_primal(self):
        # with interpret forced, flash_attention's primal path must run the
        # pallas kernel (ADVICE: forward-only calls use the fused kernel)
        A = ATTN
        q, k, v = self._rand(S=32)
        prev = A.FORCE_PALLAS_INTERPRET
        A.FORCE_PALLAS_INTERPRET = True
        try:
            out = A.flash_attention(q, k, v)
        finally:
            A.FORCE_PALLAS_INTERPRET = prev
        ref = self._naive(q, k, v, False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grad_through_custom_vjp_interpret(self):
        A = ATTN
        q, k, v = self._rand(S=32, seed=5)

        def loss(q, k, v):
            return jnp.sum(A.flash_attention(q, k, v, True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(self._naive(q, k, v, True) ** 2)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        prev = A.FORCE_PALLAS_INTERPRET
        A.FORCE_PALLAS_INTERPRET = True
        try:
            gp = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        finally:
            A.FORCE_PALLAS_INTERPRET = prev
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_bwd_residuals_are_linear_in_seq(self):
        # the custom_vjp must save only (q, k, v, out, lse) — no S x S
        A = ATTN
        q, k, v = self._rand(B=1, H=1, S=64, D=8)
        _, vjp = jax.vjp(lambda a, b, c: A.flash_attention(a, b, c, True),
                        q, k, v)
        import jax.tree_util as jtu
        sizes = [x.size for x in jtu.tree_leaves(vjp)
                 if hasattr(x, "size")]
        S, D = 64, 8
        assert max(sizes) <= S * D, sizes  # biggest residual is S x D


@pytest.mark.pallas
class TestRingPallasPath:
    """Ring attention's per-step block computation through the Pallas
    kernel (interpret mode = the exact TPU kernel math): offsets ride in
    as a traced position delta, fully-masked visiting blocks contribute
    zero weight."""

    def _ring_pallas(self, causal, n=4, S=32):
        A = ATTN
        devs = jax.devices("cpu")[:n]
        mesh = Mesh(np.array(devs), ("seq",))
        q, k, v = qkv(S=S)

        def f(q, k, v):
            return ring_attention(q, k, v, "seq", causal=causal)

        import inspect
        kw = {}
        sig = inspect.signature(shard_map).parameters
        if "check_vma" in sig:
            kw["check_vma"] = False
        elif "check_rep" in sig:
            kw["check_rep"] = False
        mapped = shard_map(f, mesh=mesh,
                           in_specs=(P(None, None, "seq"),) * 3,
                           out_specs=P(None, None, "seq"), **kw)
        prev = A.FORCE_PALLAS_INTERPRET
        A.FORCE_PALLAS_INTERPRET = True
        try:
            out = mapped(q, k, v)
        finally:
            A.FORCE_PALLAS_INTERPRET = prev
        return out, naive_attention(q, k, v, causal)

    def test_causal_matches_reference(self):
        out, ref = self._ring_pallas(causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_full_matches_reference(self):
        out, ref = self._ring_pallas(causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_offset_kernel_directly(self):
        """_pallas_flash_fwd with a position delta == masked reference
        for every relative shard alignment (incl. fully-masked)."""
        A = ATTN
        rng = np.random.RandomState(3)
        B, H, S, D = 1, 2, 16, 8
        q = rng.randn(B, H, S, D).astype(np.float32)
        k = rng.randn(B, H, S, D).astype(np.float32)
        v = rng.randn(B, H, S, D).astype(np.float32)
        prev = A.FORCE_PALLAS_INTERPRET
        A.FORCE_PALLAS_INTERPRET = True
        try:
            for delta in (-16, 0, 16):
                out, lse = A._pallas_flash_fwd(
                    jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                    True, 1.0 / np.sqrt(D), pos_delta=delta)
                qpos = np.arange(S)[:, None] + delta
                kpos = np.arange(S)[None, :]
                mask = kpos <= qpos
                s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
                s = np.where(mask, s, -np.inf)
                with np.errstate(over="ignore", invalid="ignore"):
                    p = np.exp(s - np.nanmax(
                        np.where(np.isfinite(s), s, np.nan), -1,
                        keepdims=True))
                    p = np.where(np.isfinite(s), p, 0.0)
                    denom = p.sum(-1, keepdims=True)
                    ref = np.where(denom > 0,
                                   np.einsum("bhqk,bhkd->bhqd",
                                             p / np.maximum(denom, 1e-30),
                                             v),
                                   0.0)
                np.testing.assert_allclose(np.asarray(out), ref,
                                           rtol=2e-5, atol=2e-5,
                                           err_msg=f"delta={delta}")
        finally:
            A.FORCE_PALLAS_INTERPRET = prev


class TestUlyssesAttention:
    """All-to-all sequence parallelism: one head re-shard gathers the
    full sequence locally, the fused kernel runs unchanged, and a second
    all_to_all restores sequence sharding. Must match single-device
    attention exactly."""

    def _ulysses(self, causal, n=4, S=32, H=4):
        from singa_tpu.ops.attention import ulysses_attention
        devs = jax.devices("cpu")[:n]
        mesh = Mesh(np.array(devs), ("seq",))
        q, k, v = qkv(S=S, H=H)

        def f(q, k, v):
            return ulysses_attention(q, k, v, "seq", causal=causal)

        mapped = shard_map(f, mesh=mesh,
                          in_specs=(P(None, None, "seq"),) * 3,
                          out_specs=P(None, None, "seq"))
        return mapped(q, k, v), naive_attention(q, k, v, causal)

    def test_causal_matches(self):
        out, ref = self._ulysses(causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_full_matches(self):
        out, ref = self._ulysses(causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_eight_way(self):
        out, ref = self._ulysses(causal=True, n=8, S=64, H=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_gradients_match_dense(self):
        from singa_tpu.ops.attention import (flash_attention,
                                             ulysses_attention)
        devs = jax.devices("cpu")[:4]
        mesh = Mesh(np.array(devs), ("seq",))
        q, k, v = qkv(S=32, H=4)

        def loss_sp(q, k, v):
            out = ulysses_attention(q, k, v, "seq", causal=True)
            return jax.lax.psum(jnp.sum(out ** 2), "seq")

        mapped = shard_map(loss_sp, mesh=mesh,
                          in_specs=(P(None, None, "seq"),) * 3,
                          out_specs=P())
        gs = jax.grad(lambda q: mapped(q, k, v))(q)

        def loss_dense(q):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        gd = jax.grad(loss_dense)(q)
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gd),
                                   rtol=1e-4, atol=1e-5)

    def test_dispatcher_falls_back_when_heads_indivisible(self):
        """H=3 on a 4-way axis: attention() must warn once and use
        ring, still matching the dense result."""
        import warnings as w
        att = ATTN   # the module (singa_tpu.ops re-exports the function)
        from singa_tpu.parallel.communicator import collective_context
        devs = jax.devices("cpu")[:4]
        mesh = Mesh(np.array(devs), ("seq",))
        q, k, v = qkv(S=32, H=3)
        from singa_tpu.tensor import Tensor
        # materialise the default device OUTSIDE shard_map: its lazy
        # creation does an explicit device_put, forbidden inside
        from singa_tpu import device as _dev_mod
        _dev_mod.get_default_device()

        def f(qa, ka, va):
            with collective_context("seq"):
                out = att.attention(
                    Tensor(data=qa, requires_grad=False),
                    Tensor(data=ka, requires_grad=False),
                    Tensor(data=va, requires_grad=False),
                    causal=True, seq_axis="seq", seq_mode="ulysses")
            return out.data

        mapped = shard_map(f, mesh=mesh,
                          in_specs=(P(None, None, "seq"),) * 3,
                          out_specs=P(None, None, "seq"))
        att._DECLINE_LOGGED.clear()     # module-level once-dedup
        with pytest.warns(UserWarning,
                          match="ulysses attention needs heads"):
            out = mapped(q, k, v)
        ref = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestPickBlocks:
    """Block-size selection for the Pallas kernels (tuned on v5e:
    (512,512) measured fastest at S=1024, PR 25)."""

    def test_large_sequences_get_big_tiles(self):
        from singa_tpu.ops.attention import _pick_blocks
        assert _pick_blocks(1024, 1024) == (512, 512)
        assert _pick_blocks(512, 512) == (512, 512)

    def test_fallback_chain_to_lane_minimum(self):
        from singa_tpu.ops.attention import _pick_blocks
        assert _pick_blocks(384, 384) == (128, 128)
        assert _pick_blocks(768, 768) == (256, 256)

    def test_short_sequences_clamp(self):
        from singa_tpu.ops.attention import _pick_blocks
        assert _pick_blocks(64, 64) == (64, 64)

    def test_env_override(self, monkeypatch):
        from singa_tpu.ops.attention import _pick_blocks
        monkeypatch.setenv("SINGA_FLASH_BLOCK_Q", "256")
        monkeypatch.setenv("SINGA_FLASH_BLOCK_K", "128")
        assert _pick_blocks(1024, 1024) == (256, 128)

    def test_partial_env_override_keeps_adaptive_other_axis(
            self, monkeypatch):
        from singa_tpu.ops.attention import _pick_blocks
        monkeypatch.setenv("SINGA_FLASH_BLOCK_Q", "512")
        assert _pick_blocks(1024, 1024) == (512, 512)
        monkeypatch.delenv("SINGA_FLASH_BLOCK_Q")
        monkeypatch.setenv("SINGA_FLASH_BLOCK_K", "128")
        assert _pick_blocks(1024, 1024) == (512, 128)

    def test_bad_env_value_warned_and_ignored(self, monkeypatch):
        """A non-integer knob must not raise inside attention dispatch,
        and must not silently disable the kernel — the adaptive pick
        stands (round-4 advisor finding)."""
        import warnings
        from singa_tpu.ops.attention import _pick_blocks
        monkeypatch.setenv("SINGA_FLASH_BLOCK_Q", "huge")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert _pick_blocks(1024, 1024) == (512, 512)
        assert any("not a positive integer" in str(x.message) for x in w)
        monkeypatch.setenv("SINGA_FLASH_BLOCK_Q", "-64")
        assert _pick_blocks(1024, 1024) == (512, 512)

    def test_oversized_env_value_clamps_to_sequence(self, monkeypatch):
        """env block > S must clamp, not reach the kernel raw (an
        unclamped oversize launches a zero-size Pallas grid whose
        output is never written)."""
        from singa_tpu.ops.attention import _pick_blocks
        monkeypatch.setenv("SINGA_FLASH_BLOCK_Q", "2048")
        assert _pick_blocks(1024, 1024) == (1024, 512)
        monkeypatch.setenv("SINGA_FLASH_BLOCK_K", "4096")
        assert _pick_blocks(1024, 1024) == (1024, 1024)

    def test_nondividing_env_value_falls_back_to_adaptive(
            self, monkeypatch):
        import warnings
        from singa_tpu.ops import attention_mod as attention
        monkeypatch.setenv("SINGA_FLASH_BLOCK_Q", "384")
        attention._ENV_BLOCK_WARNED.clear()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert attention._pick_blocks(1024, 1024) == (512, 512)
            # warned exactly once per (axis, value, length), even
            # across repeated dispatches of the same shape
            assert attention._pick_blocks(1024, 1024) == (512, 512)
        hits = [x for x in w if "does not divide" in str(x.message)]
        assert len(hits) == 1, [str(x.message) for x in w]

    def test_dispatch_asymmetric_blocks_match(self, monkeypatch):
        """Dispatch path with bq != bk and multi-block grids both ways
        (the measured-best v5e configs are asymmetric)."""
        import jax
        A = ATTN
        rng = np.random.RandomState(11)
        q, k, v = (jnp.asarray(rng.randn(1, 2, 256, 16)
                               .astype(np.float32)) for _ in range(3))

        def naive(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(16.0)
            mask = np.tril(np.ones((256, 256), bool))
            p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), -1)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v)

        monkeypatch.setenv("SINGA_FLASH_BLOCK_Q", "128")
        monkeypatch.setenv("SINGA_FLASH_BLOCK_K", "64")
        prev = A.FORCE_PALLAS_INTERPRET
        A.FORCE_PALLAS_INTERPRET = True
        try:
            out = A.flash_attention(q, k, v, True)
            g = jax.grad(lambda a, b, c: jnp.sum(
                A.flash_attention(a, b, c, True) ** 2),
                argnums=(0, 1, 2))(q, k, v)
        finally:
            A.FORCE_PALLAS_INTERPRET = prev
        gr = jax.grad(lambda a, b, c: jnp.sum(naive(a, b, c) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(naive(q, k, v)),
                                   rtol=2e-4, atol=2e-4)
        for got, want in zip(g, gr):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-3, atol=2e-3)


def _eqns_named(jaxpr, primitive):
    """Every equation of that primitive in a jaxpr, nested jaxprs
    included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_eqns_named(sub, primitive))
    return found


def _flash_fwd_bwd(q, k, v, g, causal=True):
    out, vjp = jax.vjp(lambda a, b, c: ATTN.flash_attention(a, b, c, causal),
                       q, k, v)
    return (out,) + vjp(g)


@pytest.fixture
def interpret_kernels():
    prev = ATTN.FORCE_PALLAS_INTERPRET
    ATTN.FORCE_PALLAS_INTERPRET = True
    yield
    ATTN.FORCE_PALLAS_INTERPRET = prev


_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


@pytest.mark.pallas
class TestPallasComputeDtype:
    """The kernels feed the MXU in the dtype they were given and carry
    lse/delta at 4 bytes a row (PR 25). Interpret mode runs the exact
    kernel bodies; the jaxpr cases read what the chip would be handed."""

    B, H, S, D = 1, 2, 256, 64
    _cache = {}

    def _inputs(self, dtype, S=S, seed=21):
        rng = np.random.RandomState(seed)
        return tuple(jnp.asarray(rng.randn(self.B, self.H, S, self.D),
                                 dtype) for _ in range(4))

    def _calls(self, dtype):
        """name -> the pallas_call equation, of forward + backward."""
        if dtype not in self._cache:
            jaxpr = jax.make_jaxpr(_flash_fwd_bwd)(*self._inputs(dtype))
            self._cache[dtype] = _eqns_named(jaxpr.jaxpr, "pallas_call")
        return self._cache[dtype]

    @pytest.mark.parametrize("name", ("out", "dq", "dk", "dv"))
    @pytest.mark.parametrize("causal", (True, False))
    @pytest.mark.parametrize("blocks", ((128, 64), (64, 128), (256, 256)))
    def test_bf16_matches_float32_naive(self, blocks, causal, name,
                                        monkeypatch, interpret_kernels):
        """(a) bf16 q, k, v, g over a multi-block grid at D 64 (tiles
        below, on and above the diagonal; square blocks take the
        diagonal tiles in two parts) against float32 naive attention of
        the same bf16 values."""
        key = ("values", blocks, causal)
        if key not in self._cache:
            monkeypatch.setenv("SINGA_FLASH_BLOCK_Q", str(blocks[0]))
            monkeypatch.setenv("SINGA_FLASH_BLOCK_K", str(blocks[1]))
            q, k, v, g = self._inputs(jnp.bfloat16, 2 * max(blocks))
            got = _flash_fwd_bwd(q, k, v, g, causal)
            q32, k32, v32, g32 = (t.astype(jnp.float32)
                                  for t in (q, k, v, g))
            out, vjp = jax.vjp(
                lambda a, b, c: naive_attention(a, b, c, causal),
                q32, k32, v32)
            self._cache[key] = dict(zip(
                ("out", "dq", "dk", "dv"),
                zip(got, (out,) + vjp(g32))))
        got, want = self._cache[key][name]
        assert got.dtype == jnp.bfloat16
        got, want = np.asarray(got, np.float32), np.asarray(want)
        assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()

    @pytest.mark.parametrize("kernel", _KERNELS)
    @pytest.mark.parametrize("dtype", (jnp.bfloat16, jnp.float32),
                             ids=("bf16", "f32"))
    def test_products_take_the_input_dtype(self, dtype, kernel,
                                           interpret_kernels):
        """(b) every matrix product inside a kernel has operands of the
        dtype the kernel was given and a float32 result: bf16 callers get
        one-pass bf16 products, float32 callers (the ring path, the 2e-5
        cases above) float32 products."""
        (call,) = [c for c in self._calls(dtype)
                   if c.params["name"] == kernel]
        dots = _eqns_named(call.params["jaxpr"], "dot_general")
        assert len(dots) >= {"flash_fwd": 2, "flash_bwd_dq": 3,
                             "flash_bwd_dkv": 4}[kernel]
        for dot in dots:
            assert [v.aval.dtype for v in dot.invars] == [dtype, dtype], dot
            assert dot.outvars[0].aval.dtype == jnp.float32, dot

    @pytest.mark.parametrize("kernel", _KERNELS)
    def test_row_statistics_cross_hbm_narrow(self, kernel,
                                             interpret_kernels):
        """(c) no operand or result of a kernel is a float32 array of
        shape (..., S, 128): with bf16 q, k, v, g the only float32 arrays
        are lse and delta, at no more than 8 lanes a row."""
        (call,) = [c for c in self._calls(jnp.bfloat16)
                   if c.params["name"] == kernel]
        stats = [v.aval for v in list(call.invars) + list(call.outvars)
                 if v.aval.dtype == jnp.float32]
        assert len(stats) == (1 if kernel == "flash_fwd" else 2)
        for aval in stats:
            assert tuple(aval.shape[-2:]) != (self.S, 128), aval
            assert aval.size * 4 <= self.B * self.H * self.S * 4 * 8, aval

    def test_kernel_names_are_what_the_benchmark_reads(
            self, interpret_kernels):
        """(d) forward + backward is three pallas_calls, each under a name
        that `attention_roofline` (and chip_smoke's HLO check) finds."""
        import json
        import os
        import re
        metric = os.path.join(os.path.dirname(__file__), os.pardir,
                              "benchmarks", "layer_metrics",
                              "attention_roofline.json")
        with open(metric) as f:
            patterns = json.load(f)["args"]["patterns"]
        assert sorted(patterns) == sorted(_KERNELS)
        names = [c.params["name"] for c in self._calls(jnp.bfloat16)]
        assert sorted(names) == sorted(_KERNELS)
        for name in names:
            assert any(re.search(p, name) for p in patterns), name

    def test_lse_keeps_its_shape_and_meaning(self, interpret_kernels):
        """The forward still returns (out, lse[B, H, S]) — ring attention
        merges partials on it."""
        q, k, v, _ = self._inputs(jnp.float32)
        scale = 1.0 / np.sqrt(self.D)
        out, lse = ATTN._pallas_flash_fwd(q, k, v, True, scale, 128, 64)
        assert lse.shape == (self.B, self.H, self.S)
        assert lse.dtype == jnp.float32
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((self.S, self.S), bool)), s, -1e30)
        np.testing.assert_allclose(
            np.asarray(lse),
            np.asarray(jax.scipy.special.logsumexp(s, axis=-1)),
            rtol=1e-5, atol=1e-5)
