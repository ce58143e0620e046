"""Pallas flash-attention kernel vs the XLA reference (interpret mode on
CPU — same kernels that compile via Mosaic on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.ops.attention import _sdpa_reference
from singa_tpu.ops.flash_attention import flash_attention


def _mk(B, T, H, D, K=None, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    K = K or H
    q = jnp.asarray(rng.randn(B, T, H, D), dtype) * 0.3
    k = jnp.asarray(rng.randn(B, T, K, D), dtype) * 0.3
    v = jnp.asarray(rng.randn(B, T, K, D), dtype) * 0.3
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(causal):
    q, k, v = _mk(2, 256, 2, 64)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _sdpa_reference(q, k, v, causal, None, 1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_gqa_forward():
    q, k, v = _mk(1, 256, 4, 64, K=2)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _sdpa_reference(q, k, v, True, None, 1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_reference(causal):
    q, k, v = _mk(1, 128, 2, 32, seed=3)
    s = 1.0 / np.sqrt(32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=True)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = _sdpa_reference(q, k, v, causal, None, s)
        return jnp.sum(o * jnp.cos(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_flash_gqa_backward():
    q, k, v = _mk(1, 128, 4, 32, K=2, seed=5)
    s = 1.0 / np.sqrt(32)

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True) ** 2)

    def lr(q, k, v):
        return jnp.sum(_sdpa_reference(q, k, v, True, None, s) ** 2)

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_flash_untileable_falls_back():
    # T=100 not a multiple of 128 -> reference path, still correct
    q, k, v = _mk(1, 100, 2, 16)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _sdpa_reference(q, k, v, True, None, 1.0 / np.sqrt(16))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_flash_under_jit_and_grad_composes():
    q, k, v = _mk(1, 256, 2, 64, seed=7)

    @jax.jit
    def step(q, k, v):
        def loss(q, k, v):
            return jnp.mean(flash_attention(q, k, v, causal=True,
                                            interpret=True))
        return jax.grad(loss)(q, k, v)

    g = step(q, k, v)
    assert np.isfinite(np.asarray(g)).all()


def _mk_qkv(B, Tq, Tk, H, K, D, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, Tq, H, D), dtype) * 0.3
    k = jnp.asarray(rng.randn(B, Tk, K, D), dtype) * 0.3
    v = jnp.asarray(rng.randn(B, Tk, K, D), dtype) * 0.3
    return q, k, v


@pytest.mark.parametrize("H,K", [(2, 2), (4, 2)], ids=["mha", "gqa"])
def test_flash_tq_ne_tk_causal_forward(H, K):
    """Tq=128 against Tk=256 (KV-decode alignment): bottom-right-aligned
    causal mask must match the XLA reference (VERDICT r2 item 4)."""
    q, k, v = _mk_qkv(1, 128, 256, H, K, 64, seed=11)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _sdpa_reference(q, k, v, True, None, 1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_tq_ne_tk_causal_backward():
    q, k, v = _mk_qkv(1, 128, 256, 4, 2, 32, seed=13)
    s = 1.0 / np.sqrt(32)

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True) ** 2)

    def lr(q, k, v):
        return jnp.sum(_sdpa_reference(q, k, v, True, None, s) ** 2)

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name} mismatch (Tq!=Tk)")


def test_flash_tq_ne_tk_noncausal():
    q, k, v = _mk_qkv(1, 128, 384, 2, 2, 64, seed=17)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    ref = _sdpa_reference(q, k, v, False, None, 1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_block_size_override(monkeypatch):
    """SINGA_FLASH_BLOCK tunes the kernel tiles; an override that does
    not tile raises; numerics unchanged (interpret mode)."""
    import jax.numpy as jnp

    from singa_tpu.ops.attention import _sdpa_reference
    from singa_tpu.ops.flash_attention import _block_sizes, flash_attention

    monkeypatch.delenv("SINGA_FLASH_BLOCK", raising=False)
    assert _block_sizes(256, 256) == (256, 256)
    monkeypatch.setenv("SINGA_FLASH_BLOCK", "128,128")
    assert _block_sizes(256, 256) == (128, 128)
    for bad in ("384,128", "garbage"):                   # 384 ∤ 256
        monkeypatch.setenv("SINGA_FLASH_BLOCK", bad)
        with pytest.raises(ValueError, match="SINGA_FLASH_BLOCK"):
            _block_sizes(256, 256)

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 256, 2, 32).astype(np.float32))
    ref = _sdpa_reference(q, q, q, True, None, 1.0 / np.sqrt(32))
    monkeypatch.setenv("SINGA_FLASH_BLOCK", "128,128")
    out = flash_attention(q, q, q, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_with_lse_dlse_cotangent():
    """flash_attention_with_lse: gradients through BOTH outputs (o and
    lse) must match autodiff of the reference (the dlse term folds into
    the backward as delta - dlse)."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.ops.flash_attention import flash_attention_with_lse

    rng = np.random.RandomState(3)
    B, H, T, D = 1, 2, 128, 32
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    scale = 1.0 / np.sqrt(D)

    def ref_loss(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        # depends on BOTH o and lse, with different weights
        return jnp.sum(o ** 2) + 0.5 * jnp.sum(lse ** 2)

    def flash_loss(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=False,
                                          scale=scale, interpret=True)
        return jnp.sum(o.astype(jnp.float32) ** 2) \
            + 0.5 * jnp.sum(lse[..., 0] ** 2)

    g_ref = jax.grad(ref_loss, (0, 1, 2))(q, k, v)
    g_fl = jax.grad(flash_loss, (0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3, err_msg=nm)


class TestChunkedBandedSDPA:
    """ops.attention.banded_sdpa: O(T*W) chunked sliding-window
    attention must equal the full-mask oracle (fwd + grad, GQA incl.)."""

    @pytest.mark.parametrize("T,H,K,W,C", [
        (64, 4, 2, 8, 16), (48, 2, 2, 12, 16),
        # largest shape repeats the GQA mode of the first param —
        # slow lane (6 s)
        (64, 4, 4, 16, 16),
        pytest.param(96, 4, 2, 32, 32, marks=pytest.mark.slow)])
    def test_matches_full_mask_oracle(self, T, H, K, W, C):
        import jax

        from singa_tpu.ops.attention import (_banded_reference,
                                             banded_sdpa)
        rng = np.random.RandomState(0)
        D = 16
        q = jnp.asarray(rng.randn(2, T, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(2, T, K, D).astype(np.float32))
        v = jnp.asarray(rng.randn(2, T, K, D).astype(np.float32))
        scale = 1.0 / np.sqrt(D)
        ref = _banded_reference(q, k, v, W, scale)
        out = banded_sdpa(q, k, v, W, chunk=C)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        g1 = jax.grad(lambda q: (banded_sdpa(q, k, v, W,
                                             chunk=C) ** 2).sum())(q)
        g2 = jax.grad(lambda q: (_banded_reference(
            q, k, v, W, scale) ** 2).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-5)

    def test_rejects_indivisible_chunk(self):
        from singa_tpu.ops.attention import banded_sdpa
        q = jnp.zeros((1, 50, 2, 8), jnp.float32)
        with pytest.raises(ValueError, match="divide"):
            banded_sdpa(q, q[:, :, :2], q[:, :, :2], 8, chunk=16)


class TestBandedFlashKernel:
    """The Pallas kernel's sliding-window mode: below-band kv tiles are
    skipped entirely (same pl.when discipline as causal) and the banded
    fwd/dq/dk/dv match the full-mask oracle in interpret mode —
    including GQA, non-block-aligned windows, and window > T."""

    @pytest.mark.parametrize("T,H,K,W", [
        (256, 4, 2, 64), (256, 2, 2, 100),
        # largest shape repeats the aligned-window mode the first
        # param covers — slow lane (8 s of interpret-mode compile)
        pytest.param(384, 4, 4, 256, marks=pytest.mark.slow),
        (256, 4, 2, 300)])
    def test_banded_kernel_matches_oracle(self, T, H, K, W):
        import jax

        from singa_tpu.ops.attention import _banded_reference
        from singa_tpu.ops.flash_attention import flash_attention
        rng = np.random.RandomState(0)
        D = 32
        q = jnp.asarray(rng.randn(1, T, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(1, T, K, D).astype(np.float32))
        v = jnp.asarray(rng.randn(1, T, K, D).astype(np.float32))
        sc = 1.0 / np.sqrt(D)
        ref = _banded_reference(q, k, v, W, sc)
        out = flash_attention(q, k, v, causal=True, window=W,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        for wrt, arg in (("q", q), ("k", k), ("v", v)):
            def f_fn(a, wrt=wrt):
                args = {"q": q, "k": k, "v": v}
                args[wrt] = a
                return (flash_attention(args["q"], args["k"], args["v"],
                                        causal=True, window=W,
                                        interpret=True) ** 2).sum()

            def r_fn(a, wrt=wrt):
                args = {"q": q, "k": k, "v": v}
                args[wrt] = a
                return (_banded_reference(args["q"], args["k"],
                                          args["v"], W, sc) ** 2).sum()

            g1 = jax.grad(f_fn)(arg)
            g2 = jax.grad(r_fn)(arg)
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{wrt}")

    def test_window_requires_causal(self):
        from singa_tpu.ops.flash_attention import flash_attention
        q = jnp.zeros((1, 256, 2, 32), jnp.float32)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, q, q, causal=False, window=8)

    def test_untileable_window_falls_back_banded(self):
        """Non-tiling shapes still honor the band (reference path)."""
        from singa_tpu.ops.attention import _banded_reference
        from singa_tpu.ops.flash_attention import flash_attention
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(1, 100, 2, 32).astype(np.float32))
        ref = _banded_reference(q, q, q, 16, 1.0 / np.sqrt(32))
        out = flash_attention(q, q, q, causal=True, window=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
