"""Compressed Convolutional Attention (CCA; Zyphra, arXiv:2510.04476, as
the ZAYA1 report arXiv:2511.17127 uses it): attention whose queries,
keys and values live in a latent narrower than the residual stream,
mixed along time by two small causal convolutions before the heads
attend.

For a normed input ``h`` (B, T, D), ``Hq`` query and ``Hkv`` KV heads of
width ``d`` (``G = Hq / Hkv``):

* latents ``q~ = h Wq`` (Hq d wide), ``k~ = h Wk`` (Hkv d wide), packed
  ``L = [q~ ; k~]`` (C = (Hq + Hkv) d channels);
* the value is shifted in time for half its heads: ``v_t = [h_t Wva ;
  h_{t-1} Wvb]``, ``h_{-1} = 0``;
* q-k means, taken before the convolutions: ``mq = (q~ + rep_G(k~)) / 2``
  (each KV head repeated to its G query heads), ``mk = (mean_G(q~) +
  k~) / 2``;
* ``c = conv1(conv0(L))`` causal in time: L is padded on the left with
  ``(t0 - 1) + (t1 - 1)`` zero rows once, then a depthwise convolution
  of ``t0`` taps and one of ``t1`` taps grouped by head (d -> d within
  each of the Hq + Hkv heads), both with a bias and neither padding
  again;
* ``q = c_q + mq``, ``k = c_k + mk``, each head scaled to norm sqrt(d),
  ``k`` times a learned scalar per KV head.

Rotary embedding, the cache and the softmax are the caller's
(``models/zaya.py``): K and the shifted V are what a KV cache holds.

**The side state.**  Position t reads, beside the cache, the latents of
the ``pad = (t0 - 1) + (t1 - 1)`` positions before it and ``h_{t-1}
Wvb``.  They cannot be recomputed from K and V, so a cached forward
carries them: ``state`` (B, pad C + Hkv d / 2), the last ``pad`` rows of
L then the last row of ``h Wvb``, zeros before position 0.  ``cca_qkv``
takes the state as it stood before its first row and returns it as it
stands after its last, or, for ``state_rows`` (R,), after each of those
rows: (B, R, S), which is what a paged engine keeps per KV block and
per slot (serve/slots.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["state_size", "cca_qkv"]


def state_size(num_heads: int, num_kv_heads: int, head_dim: int,
               t0: int, t1: int) -> int:
    """Values of side state a batch row holds (see the module's text)."""
    pad = (t0 - 1) + (t1 - 1)
    return pad * (num_heads + num_kv_heads) * head_dim \
        + (num_kv_heads // 2) * head_dim


def cca_qkv(h, state, wq, wk, wva, wvb, conv0_w, conv0_b, conv1_w, conv1_b,
            k_scale, *, num_heads: int, num_kv_heads: int, head_dim: int,
            state_rows=None):
    """``h`` (B, T, D), ``state`` (B, S) -> (q (B, T, Hq, d), k and v
    (B, T, Hkv, d) before any rotary embedding, the new state).

    ``conv0_w`` (t0, C) and ``conv1_w`` (t1, Hq + Hkv, d, d) hold their
    taps oldest first, the last tap on the current position.  The
    mixing runs in f32 whatever ``h`` is; q, k, v and the state come
    back in ``h``'s and ``state``'s dtypes."""
    B, T, _ = h.shape
    Hq, Hkv, d = num_heads, num_kv_heads, head_dim
    G, C = Hq // Hkv, (Hq + Hkv) * head_dim
    t0, t1 = conv0_w.shape[0], conv1_w.shape[0]
    pad = (t0 - 1) + (t1 - 1)
    cast = lambda w: w.astype(h.dtype)
    lat = jnp.concatenate([h @ cast(wq), h @ cast(wk)], axis=-1)  # (B, T, C)
    va, vb = h @ cast(wva), h @ cast(wvb)

    with jax.named_scope("attn.cca.state"):
        prev = state[:, :pad * C].reshape(B, pad, C).astype(lat.dtype)
        ext = jnp.concatenate([prev, lat], axis=1)         # (B, pad + T, C)
        vb_ext = jnp.concatenate(
            [state[:, None, pad * C:].astype(vb.dtype), vb], axis=1)
        v = jnp.concatenate([va, vb_ext[:, :-1]], axis=-1) \
            .reshape(B, T, Hkv, d)
        # the state after chunk row j: latents j + 1 .. j + pad of `ext`
        # (its own and the pad - 1 before it) and row j of `vb`
        if state_rows is None:
            new_state = jnp.concatenate(
                [ext[:, T:].reshape(B, pad * C), vb[:, -1]], axis=-1)
        else:
            idx = state_rows[:, None] + 1 + jnp.arange(pad)[None, :]
            new_state = jnp.concatenate(
                [jnp.take(ext, idx, axis=1).reshape(B, -1, pad * C),
                 jnp.take(vb, state_rows, axis=1)], axis=-1)
        new_state = new_state.astype(state.dtype)

    with jax.named_scope("attn.cca.mix"):
        f32 = lambda a: a.astype(jnp.float32)
        x = f32(ext)
        qt = x[:, pad:, :Hq * d].reshape(B, T, Hkv, G, d)
        kt = x[:, pad:, Hq * d:].reshape(B, T, Hkv, d)
        mq = 0.5 * (qt + kt[:, :, :, None, :])
        mk = 0.5 * (jnp.mean(qt, axis=3) + kt)
        n0 = T + t1 - 1
        c0 = sum(f32(conv0_w[j]) * x[:, j:j + n0] for j in range(t0)) \
            + f32(conv0_b)
        c0 = c0.reshape(B, n0, Hq + Hkv, d)
        c1 = sum(jnp.einsum("bthc,hcd->bthd", c0[:, j:j + T],
                            f32(conv1_w[j])) for j in range(t1)) \
            + f32(conv1_b).reshape(Hq + Hkv, d)
        q = c1[:, :, :Hq] + mq.reshape(B, T, Hq, d)
        k = c1[:, :, Hq:] + mk

        def unit(a):        # each head to norm sqrt(d)
            n = jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True))
            return a * (math.sqrt(d) / jnp.maximum(n, 1e-6))

        q, k = unit(q), unit(k) * f32(k_scale)[:, None]
    return q.astype(h.dtype), k.astype(h.dtype), v, new_state
