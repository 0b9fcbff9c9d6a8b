"""Pallas flash attention vs reference math (backend-vs-backend pattern,
the ValidateCudnnLSTM.java role for the attention hot op).

Runs the kernel in interpreter mode on CPU: same kernel code path the TPU
compiles, exactness asserted against reference_attention and jax.grad
through it. Real-chip perf lives in PERF.md.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers.pallas_attention import (
    flash_attention, flash_attention_supported,
)
from deeplearning4j_tpu.parallel.sequence import reference_attention


def _qkv(B=2, H=2, T=256, D=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((B, H, T, D)) * 0.5, dtype)
    return mk(), mk(), mk()


class TestForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128, interpret=True)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_unequal_blocks(self):
        q, k, v = _qkv(T=512)
        out = flash_attention(q, k, v, causal=True, block_q=256,
                              block_k=128, interpret=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_ragged_t_multi_block_padding_noncausal(self):
        # unequal blocks pad T to lcm(bq,bk)=256, so padded keys span TWO
        # KV blocks (300->512, blocks j=2,3 at bk=128); every padded block
        # must take the masked path, not just the last one
        q, k, v = _qkv(T=300)
        out = flash_attention(q, k, v, causal=False, block_q=256,
                              block_k=128, interpret=True)
        ref = reference_attention(q, k, v, causal=False)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_ragged_t_padding(self):
        # T not a multiple of the block: padded internally, sliced back
        q, k, v = _qkv(T=200)
        out = flash_attention(q, k, v, causal=True, block_q=128,
                              block_k=128, interpret=True)
        ref = reference_attention(q, k, v, causal=True)
        assert out.shape == q.shape
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_key_mask(self):
        B, T = 2, 256
        q, k, v = _qkv(B=B, T=T)
        rng = np.random.default_rng(3)
        lengths = rng.integers(T // 4, T, B)
        km = jnp.asarray(np.arange(T)[None, :] < lengths[:, None],
                         jnp.float32)
        out = flash_attention(q, k, v, key_mask=km, block_q=128,
                              block_k=128, interpret=True)
        # reference: NEG_INF-mask the padded keys
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        s = jnp.where(km[:, None, None, :] > 0, s, -1e30)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_causal_with_key_mask(self):
        # both mask sources at once: causal triangle AND variable-length
        # keys (the user_mask path folds the causal test into _block_mask)
        B, T = 2, 256
        q, k, v = _qkv(B=B, T=T, seed=21)
        lengths = np.array([200, 120])
        km = jnp.asarray(np.arange(T)[None, :] < lengths[:, None],
                         jnp.float32)
        out = flash_attention(q, k, v, causal=True, key_mask=km,
                              block_q=128, block_k=128, interpret=True)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        tri = jnp.tril(jnp.ones((T, T), bool))
        valid = tri[None, None] & (km[:, None, None, :] > 0)
        s = jnp.where(valid, s, -1e30)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        # rows with zero valid keys (q_pos >= length under causal can't
        # happen: position i always sees key i... unless i >= length):
        # those rows are undefined in the naive ref too — compare only
        # rows with at least one valid key
        H = q.shape[1]
        row_ok = np.broadcast_to(np.asarray(valid.any(axis=-1)),
                                 (B, H, T))
        got, want = np.asarray(out), np.asarray(ref)
        np.testing.assert_allclose(got[row_ok], want[row_ok],
                                   atol=2e-5, rtol=2e-5)

    def test_bf16_inputs(self):
        q, k, v = _qkv(dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True, block_q=128,
                              block_k=128, interpret=True)
        ref = reference_attention(q.astype(jnp.float32),
                                  k.astype(jnp.float32),
                                  v.astype(jnp.float32), causal=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(out.astype(jnp.float32), ref,
                                   atol=3e-2, rtol=3e-2)

    def test_supported_gate(self):
        assert flash_attention_supported((2, 4, 1024, 128))
        assert flash_attention_supported((2, 4, 1024, 64))
        assert not flash_attention_supported((2, 4, 1024, 80))
        assert not flash_attention_supported((2, 4, 32, 64))
        assert not flash_attention_supported((4, 1024, 128))


class TestBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal):
        q, k, v = _qkv(B=1, H=2, T=256, D=64, seed=7)
        tgt = jnp.asarray(
            np.random.default_rng(9).standard_normal(q.shape), jnp.float32)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, block_q=128,
                                block_k=128, interpret=True)
            return jnp.sum((o - tgt) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum((reference_attention(q, k, v, causal=causal)
                            - tgt) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{name}")

    def test_grads_with_ragged_t(self):
        q, k, v = _qkv(B=1, H=1, T=200, D=64, seed=11)

        def loss_flash(q):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           block_q=128, block_k=128,
                                           interpret=True) ** 2)

        def loss_ref(q):
            return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

        np.testing.assert_allclose(jax.grad(loss_flash)(q),
                                   jax.grad(loss_ref)(q),
                                   atol=5e-4, rtol=5e-4)

    def test_zero_length_row_grads_finite(self):
        # a batch row whose key_mask is all zeros must not NaN the grads
        # (masked raw scores above the row lse would overflow exp if the
        # backward kernels exponentiated unmasked scores)
        B, T = 2, 128
        q, k, v = _qkv(B=B, T=T, seed=17)
        km = jnp.stack([jnp.ones((T,)), jnp.zeros((T,))]).astype(jnp.float32)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, key_mask=km,
                                           block_q=128, block_k=128,
                                           interpret=True) ** 2)

        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for g in grads:
            assert np.all(np.isfinite(np.asarray(g)))

    def test_grads_with_key_mask(self):
        B, T = 2, 128
        q, k, v = _qkv(B=B, T=T, seed=13)
        km = jnp.asarray(np.arange(T)[None, :] < np.array([100, 64])[:, None],
                         jnp.float32)

        def loss_flash(k, v):
            return jnp.sum(flash_attention(q, k, v, key_mask=km,
                                           block_q=128, block_k=128,
                                           interpret=True) ** 2)

        def loss_ref(k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
            s = jnp.where(km[:, None, None, :] > 0, s, -1e30)
            o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
            return jnp.sum(o ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1))(k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1))(k, v)
        for a, b, name in zip(gf, gr, ("k", "v")):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{name}")


class TestWindowKernel:
    """Sliding-window block skipping in the flash kernel."""

    def _masked_ref(self, q, k, v, W):
        T = q.shape[2]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        idx = jnp.arange(T)
        valid = (idx[:, None] >= idx[None, :]) & \
                (idx[:, None] - idx[None, :] < W)
        s = jnp.where(valid[None, None], s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    @pytest.mark.parametrize("W", [64, 128, 200])
    def test_matches_masked_reference(self, W):
        q, k, v = _qkv(T=512, seed=41)
        out = flash_attention(q, k, v, causal=True, window=W,
                              block_q=128, block_k=128, interpret=True)
        np.testing.assert_allclose(out, self._masked_ref(q, k, v, W),
                                   atol=2e-5, rtol=2e-5)

    def test_grads_match_masked_reference(self):
        q, k, v = _qkv(B=1, H=1, T=256, D=64, seed=43)
        W = 96

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True, window=W,
                                           block_q=128, block_k=128,
                                           interpret=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(self._masked_ref(q, k, v, W) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{name}")

    def test_window_with_key_mask(self):
        B, T, W = 2, 256, 64
        q, k, v = _qkv(B=B, T=T, seed=45)
        km = jnp.asarray(np.arange(T)[None, :] <
                         np.array([220, 130])[:, None], jnp.float32)
        out = flash_attention(q, k, v, causal=True, window=W, key_mask=km,
                              block_q=128, block_k=128, interpret=True)
        idx = jnp.arange(T)
        valid = (idx[:, None] >= idx[None, :]) & \
                (idx[:, None] - idx[None, :] < W)
        valid = valid[None, None] & (km[:, None, None, :] > 0)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        s = jnp.where(valid, s, -1e30)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        row_ok = np.broadcast_to(np.asarray(valid.any(-1)),
                                 (B, q.shape[1], T))
        np.testing.assert_allclose(np.asarray(out)[row_ok],
                                   np.asarray(ref)[row_ok],
                                   atol=2e-5, rtol=2e-5)

    def test_scan_and_kernel_agree(self):
        from deeplearning4j_tpu.parallel.sequence import blockwise_attention
        q, k, v = _qkv(T=256, seed=47)
        a = blockwise_attention(q, k, v, causal=True, window=80,
                                use_pallas=False)
        b = flash_attention(q, k, v, causal=True, window=80,
                            block_q=128, block_k=128, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)

    def test_noncausal_window_rejected(self):
        q, k, v = _qkv(T=128)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, window=32,
                            interpret=True)


class TestDecodeShapes:
    """Decode-shaped queries (PR 10, the serving fast path): a width-1
    or width-1+gamma query block attending a long KV prefix as banded
    attention with q_offset = Tk - W — the flash-kernel shape the
    engine's dispatch family maps onto (the paged pool variant lives in
    serving/paged_kernel.py, pinned by its own suite; this pins the
    dense-KV kernel at the same query widths)."""

    @staticmethod
    def _decode_ref(q, k, v, W):
        # query w sits at absolute position Tk - W + w
        Tk = k.shape[2]
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(q.shape[-1])
        qpos = Tk - W + jnp.arange(W)
        valid = jnp.arange(Tk)[None, :] <= qpos[:, None]
        s = jnp.where(valid[None, None], s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                          v.astype(jnp.float32))

    @pytest.mark.parametrize("W", [1, 5])
    def test_decode_width_matches_reference(self, W):
        from deeplearning4j_tpu.nn.layers.pallas_attention import (
            flash_attention_lse)
        rng = np.random.default_rng(11)
        B, H, Tk, D = 2, 2, 384, 64
        q = jnp.asarray(rng.standard_normal((B, H, W, D)) * 0.5,
                        jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, H, Tk, D)) * 0.5,
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, H, Tk, D)) * 0.5,
                        jnp.float32)
        out, lse = flash_attention_lse(q, k, v, causal=True,
                                       q_offset=Tk - W,
                                       block_q=128, block_k=128,
                                       interpret=True)
        ref = self._decode_ref(q, k, v, W)
        assert out.shape == (B, H, W, D)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
        # the lse is finite and real for every decode row (the ring /
        # cross-chunk combine contract holds at decode widths too)
        assert np.isfinite(np.asarray(lse)).all()

    def test_decode_width_sees_only_past(self):
        """Poison the keys strictly after the LAST query's position
        with large FINITE garbage (the kernel's masking contract — the
        dense arena's idle-slot argument: masked scores go to -1e30
        before the softmax, and zero probabilities annihilate finite
        values exactly): a decode-shaped block must not read them."""
        from deeplearning4j_tpu.nn.layers.pallas_attention import (
            flash_attention_lse)
        rng = np.random.default_rng(13)
        B, H, Tk, D, W = 1, 2, 256, 64, 3
        q = jnp.asarray(rng.standard_normal((B, H, W, D)), jnp.float32)
        k = np.asarray(rng.standard_normal((B, H, Tk, D)), np.float32)
        v = np.asarray(rng.standard_normal((B, H, Tk, D)), np.float32)
        # run the appended chunk mid-sequence: keys past off+W are
        # visible to NO real query row
        off = 100
        kp, vp = k.copy(), v.copy()
        kp[:, :, off + W:] = 1e6
        vp[:, :, off + W:] = 1e6
        a, _ = flash_attention_lse(jnp.asarray(q),
                                   jnp.asarray(k[:, :, :off + W]),
                                   jnp.asarray(v[:, :, :off + W]),
                                   causal=True, q_offset=off,
                                   block_q=128, block_k=128,
                                   interpret=True)
        b, _ = flash_attention_lse(jnp.asarray(q), jnp.asarray(kp),
                                   jnp.asarray(vp), causal=True,
                                   q_offset=off, block_q=128,
                                   block_k=128, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)
