"""ops/ kernel tests — lax reference vs Pallas (interpret mode on CPU).

Plays the role of the reference's Torch7 oracle specs (SURVEY.md §4.3):
the lax implementation is the oracle; the Pallas kernel must match it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.ops import dot_product_attention, int8_matmul, quantize_per_channel
from bigdl_tpu.ops.attention import _reference_attention, flash_attention


def _qkv(b=2, h=2, t=64, d=16, seed=0):
    r = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(r.randn(b, h, t, d).astype(np.float32))
    return mk(), mk(), mk()


class TestAttention:
    def test_reference_matches_naive_softmax(self):
        q, k, v = _qkv()
        out = _reference_attention(q, k, v, causal=False, scale=0.25)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.25
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
        np.testing.assert_allclose(out, want, atol=1e-5)

    def test_causal_masks_future(self):
        q, k, v = _qkv(t=16)
        out = _reference_attention(q, k, v, causal=True, scale=0.25)
        # position 0 attends only to key 0
        want0 = v[:, :, 0, :]
        np.testing.assert_allclose(out[:, :, 0, :], want0, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_matches_reference(self, causal):
        q, k, v = _qkv(t=64, d=16)
        scale = 1.0 / np.sqrt(16)
        ref = _reference_attention(q, k, v, causal=causal, scale=scale)
        got = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_flash_grad_matches_reference(self):
        q, k, v = _qkv(t=32, d=8)

        def loss_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=True, interpret=True) ** 2
            )

        def loss_ref(q, k, v):
            return jnp.sum(
                _reference_attention(
                    q, k, v, causal=True, scale=8 ** -0.5
                ) ** 2
            )

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_blockwise_backward_multiblock(self, causal):
        # T=256 -> two 128-blocks: exercises the blockwise dq and dk/dv
        # kernels' inner loops, the causal block-skip bounds, and the
        # (bh, T//bq, bq) logsumexp layout across block boundaries.
        # distinct q/k/v gradients (not the q=k=v fold) via argnums.
        q, k, v = _qkv(t=256, d=16)
        rs = np.random.RandomState(7)
        g = jnp.asarray(rs.randn(*q.shape).astype(np.float32))

        def loss_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=causal, interpret=True) * g
            )

        def loss_ref(q, k, v):
            return jnp.sum(
                _reference_attention(
                    q, k, v, causal=causal, scale=16 ** -0.5
                ) * g
            )

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
                err_msg=f"d{name}")

    def test_seq_offset_matches_full_causal(self):
        # ring-attention building block: computing the second half of the
        # queries with seq_offset must equal the full causal slice
        q, k, v = _qkv(t=32, d=8)
        full = _reference_attention(q, k, v, causal=True, scale=0.5)
        half = _reference_attention(
            q[:, :, 16:], k, v, causal=True, scale=0.5, seq_offset=16
        )
        np.testing.assert_allclose(np.asarray(half),
                                   np.asarray(full[:, :, 16:]), atol=1e-5)

    def test_dispatcher_lax_path(self):
        q, k, v = _qkv(t=24, d=8)  # 24 not a multiple of 128 -> lax
        out = dot_product_attention(q, k, v, causal=False)
        assert out.shape == q.shape


class TestInt8Matmul:
    def test_quantize_roundtrip(self):
        w = jnp.asarray(np.random.RandomState(0).randn(16, 32).astype(np.float32))
        q, scale = quantize_per_channel(w, axis=0)
        assert q.dtype == jnp.int8
        np.testing.assert_allclose(np.asarray(q * scale), np.asarray(w),
                                   atol=np.abs(w).max() / 100)

    def test_matmul_close_to_fp32(self):
        r = np.random.RandomState(1)
        x = jnp.asarray(r.randn(4, 32).astype(np.float32))
        w = jnp.asarray(r.randn(8, 32).astype(np.float32))
        wq, ws = quantize_per_channel(w, axis=0)
        got = int8_matmul(x, wq, ws)
        want = x @ w.T
        err = np.abs(np.asarray(got - want)).max()
        assert err < 0.05 * np.abs(np.asarray(want)).max() + 0.05


def test_flash_untileable_t_raises_on_explicit_request():
    # T=27 tiles to nothing.  An explicit request for the kernel must
    # not be answered by the lax reference under the kernel's name;
    # impl="auto" never reaches the kernel for such a shape.
    rs = np.random.RandomState(11)
    q = jnp.asarray(rs.randn(1, 2, 27, 8).astype(np.float32))
    with pytest.raises(ValueError, match="cannot tile"):
        flash_attention(q, q, q, causal=True, interpret=True)
    with pytest.raises(ValueError, match="cannot tile"):
        dot_product_attention(q, q, q, causal=True,
                              impl="pallas_interpret")
    out = dot_product_attention(q, q, q, causal=True, impl="auto")
    want = _reference_attention(q, q, q, causal=True, scale=8 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-6)


def test_flash_forward_lse_matches_reference_logsumexp():
    # the blockwise backward trusts the forward's saved logsumexp; pin
    # it against a direct computation (causal, multi-block)
    from bigdl_tpu.ops.attention import _flash_forward

    rs = np.random.RandomState(5)
    b, h, t, d = 1, 2, 256, 16
    q = jnp.asarray(rs.randn(b, h, t, d).astype(np.float32) * 0.5)
    k = jnp.asarray(rs.randn(b, h, t, d).astype(np.float32) * 0.5)
    v = jnp.asarray(rs.randn(b, h, t, d).astype(np.float32))
    scale = d ** -0.5
    out, lse = _flash_forward(q, k, v, True, scale, True, with_lse=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    qpos = jnp.arange(t)[:, None]
    kpos = jnp.arange(t)[None, :]
    s = jnp.where(qpos >= kpos, s, -jnp.inf)
    want = jax.scipy.special.logsumexp(s, axis=-1).reshape(b * h, -1)
    got = np.asarray(lse).reshape(b * h, -1)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-4, rtol=1e-4)


def test_flash_chunked_seq_offset_matches_full():
    # chunked causal attention: two query chunks at static seq_offsets
    # against the full kv must reproduce the full causal pass, forward
    # and per-argument gradients (the long-context chunked-training
    # surface of the flash kernels)
    rs = np.random.RandomState(21)
    B, H, Tk, D = 1, 2, 256, 16
    k = jnp.asarray(rs.randn(B, H, Tk, D).astype(np.float32) * 0.5)
    v = jnp.asarray(rs.randn(B, H, Tk, D).astype(np.float32))
    q = jnp.asarray(rs.randn(B, H, Tk, D).astype(np.float32) * 0.5)
    g = jnp.asarray(rs.randn(B, H, Tk, D).astype(np.float32))

    full = flash_attention(q, k, v, causal=True, interpret=True)
    chunks = [
        flash_attention(q[:, :, i:i + 128], k, v, causal=True,
                        interpret=True, seq_offset=i)
        for i in (0, 128)
    ]
    np.testing.assert_allclose(np.asarray(jnp.concatenate(chunks, axis=2)),
                               np.asarray(full), atol=1e-5)

    q1, g1 = q[:, :, 128:], g[:, :, 128:]

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True, seq_offset=128) * g1)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(
            q, k, v, causal=True, scale=16 ** -0.5, seq_offset=128) * g1)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q1, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q1, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name}")


def test_flash_cross_length_non_causal():
    # Tq != Tk (cross-attention shape) on the kernel path
    rs = np.random.RandomState(22)
    q = jnp.asarray(rs.randn(1, 2, 64, 16).astype(np.float32))
    k = jnp.asarray(rs.randn(1, 2, 256, 16).astype(np.float32))
    v = jnp.asarray(rs.randn(1, 2, 256, 16).astype(np.float32))
    out = flash_attention(q, k, v, causal=False, interpret=True)
    ref = _reference_attention(q, k, v, causal=False, scale=16 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
