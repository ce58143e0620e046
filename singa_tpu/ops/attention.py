"""Scaled-dot-product attention as an autograd Operator.

Two lowerings behind one API:
  * `_sdpa_reference` — plain jnp einsum/softmax; XLA fuses this well for
    short sequences, and it is the correctness oracle on CPU.
  * the Pallas flash-attention kernel (singa_tpu.ops.flash_attention) —
    blockwise O(T) memory for long sequences on TPU.
Selection is by sequence length + platform; both are jit-traceable so the
choice is static at capture time.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .. import autograd
from ..device import on_tpu
from ..tensor import Tensor

__all__ = ["attention", "sdpa", "banded_attention", "banded_sdpa"]

# sequences at least this long route to the flash kernel on TPU
_FLASH_MIN_LEN = 512


def _sdpa_reference(q, k, v, causal: bool, mask, scale: float):
    # q: (B, T, H, D); k/v: (B, T, K, D) with K | H (grouped-query attention
    # when K < H — Llama-3 style).  Head dim kept last for MXU-friendly
    # einsums; the group axis stays folded into one batched matmul.
    H, K = q.shape[2], k.shape[2]
    if K != H:
        G = H // K
        q = q.reshape(q.shape[:2] + (K, G, q.shape[-1]))
        logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k) * scale
    else:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    extra = logits.ndim - 2  # leading axes before (Tq, Tk)
    if causal:
        Tq, Tk = q.shape[1], k.shape[1]
        cm = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        cm = cm[(None,) * extra]
        logits = jnp.where(cm, logits, jnp.finfo(logits.dtype).min)
    if mask is not None:
        m = jnp.asarray(mask)
        if K != H and m.ndim == 4:
            # user masks address (B, H|1, Tq|1, Ts); grouped logits are
            # (B, K, G, Tq, Ts) — split the head axis so broadcasting can't
            # silently land the batch dim on the kv-head axis
            if m.shape[1] == H:
                m = m.reshape(m.shape[0], K, G, *m.shape[2:])
            else:
                m = m[:, :, None]
        logits = jnp.where(m, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if K != H:
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
        return out.reshape(out.shape[:2] + (H, out.shape[-1]))
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _use_flash(q, k=None) -> bool:
    import os
    if os.environ.get("SINGA_DISABLE_FLASH"):
        return False
    if q.shape[1] < _FLASH_MIN_LEN:
        return False
    if k is not None and q.shape[2] % k.shape[2] != 0:
        return False  # non-grouping head ratio: einsum reference path
    return on_tpu()


class SDPA(autograd.Operator):
    def __init__(self, causal: bool, mask, scale: Optional[float]):
        super().__init__()
        self.causal = causal
        self.mask = mask
        self.scale = scale

    def fwd(self, q, k, v):
        scale = self.scale or (1.0 / math.sqrt(q.shape[-1]))
        if self.mask is None and _use_flash(q, k):
            from .flash_attention import flash_attention
            return flash_attention(q, k, v, causal=self.causal, scale=scale)
        return _sdpa_reference(q, k, v, self.causal, self.mask, scale)


def attention(q: Tensor, k: Tensor, v: Tensor, causal: bool = False,
              mask: Optional[Tensor] = None,
              scale: Optional[float] = None) -> Tensor:
    """(B, T, H, D) attention with optional causal/explicit mask."""
    m = mask.data if isinstance(mask, Tensor) else mask
    return SDPA(causal, m, scale)(q, k, v)


def sdpa(q, k, v, causal=False, mask=None, scale=None):
    """Raw-array entry point used by models bypassing the tape."""
    scale = scale or (1.0 / math.sqrt(q.shape[-1]))
    if mask is None and _use_flash(q, k):
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale)
    return _sdpa_reference(q, k, v, causal, mask, scale)


# ---------------------------------------------------------------------------
# chunked banded (sliding-window) attention — O(T * W) memory
# ---------------------------------------------------------------------------

def _banded_reference(q, k, v, window: int, scale: float):
    """Oracle: full (Tq, Tk) band mask through _sdpa_reference
    (bottom-right aligned when Tk > Tq, matching the causal
    convention)."""
    Tq, Tk = q.shape[1], k.shape[1]
    qpos = jnp.arange(Tq)[:, None] + (Tk - Tq)
    kpos = jnp.arange(Tk)[None, :]
    band = (kpos <= qpos) & (kpos > qpos - window)
    return _sdpa_reference(q, k, v, False, band[None, None], scale)


def pick_band_chunk(T: int, window: int) -> Optional[int]:
    """Largest divisor of T up to ~the window (capped at 512) — the
    chunk size that keeps (C, C+W) score tiles small.  None when only a
    degenerate chunk (< 8) divides T: the k/v duplication of tiny
    chunks would cost more than the full masked path."""
    cap = max(16, min(window, 512))
    c = next(c for c in range(min(cap, T), 0, -1) if T % c == 0)
    return c if c >= 8 else None


def banded_sdpa(q, k, v, window: int, scale: Optional[float] = None,
                chunk: Optional[int] = None):
    """Sliding-window attention (query t attends keys in (t-W, t])
    computed in query chunks so only (chunk, chunk+W) score tiles ever
    materialize — O(T*W) memory instead of the O(T^2) masked path, on
    any backend, in pure jnp (so jax.vjp differentiates it).

    The relative band is identical for every interior chunk: chunk i's
    queries [iC, iC+C) need keys [iC-W+1, iC+C), a width-(C+W-1) slice
    of k/v left-padded by W so edge chunks clamp cleanly; padded keys
    fall outside the band mask.  vmap over chunks keeps everything one
    fused program."""
    T = q.shape[1]
    scale = scale or (1.0 / math.sqrt(q.shape[-1]))
    W = int(window)
    if chunk is None:
        chunk = pick_band_chunk(T, W)
        if chunk is None:
            raise ValueError(
                f"no usable chunk divides T={T} (all divisors < 8); "
                "use the masked path instead")
    C = int(chunk)
    if T % C:
        raise ValueError(f"seq len {T} must divide by chunk {C}")
    n = T // C
    span = C + W                                    # keys per chunk
    # left-pad keys/values by W (zeros; masked out below)
    kp = jnp.pad(k, ((0, 0), (W, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (W, 0), (0, 0), (0, 0)))
    qc = q.reshape(q.shape[0], n, C, *q.shape[2:])  # (B, n, C, H, D)
    starts = jnp.arange(n) * C                      # chunk i keys start
    kc = jax.vmap(lambda s: jax.lax.dynamic_slice_in_dim(kp, s, span, 1),
                  out_axes=1)(starts)               # (B, n, span, K, D)
    vc = jax.vmap(lambda s: jax.lax.dynamic_slice_in_dim(vp, s, span, 1),
                  out_axes=1)(starts)
    # relative positions are chunk-invariant: query c (0..C-1) sits at
    # absolute offset c; key j (0..span-1) at absolute offset j - W.
    # band: 0 <= (c + W - j) < W  i.e.  c < j <= c + W ... in padded
    # coords: key abs = j - W, query abs = c; causal j - W <= c and
    # within-window j - W > c - W  =>  c < j <= c + W
    cpos = jnp.arange(C)[:, None]
    jpos = jnp.arange(span)[None, :]
    band = (jpos <= cpos + W) & (jpos > cpos)       # (C, span)
    # first chunk's left-pad keys are already outside the band only
    # when j > c holds... padded keys have j < W and represent
    # negative absolute positions; for chunk 0 they must be masked:
    # absolute key pos = starts[i] + j - W >= 0  =>  j >= W - starts[i]
    valid0 = jpos[None] >= (W - starts)[:, None, None]  # (n, 1, span)
    mask = band[None] & valid0                      # (n, C, span)

    def one_chunk(qi, ki, vi, mi):
        return _sdpa_reference(qi, ki, vi, False, mi[None, None], scale)

    out = jax.vmap(one_chunk, in_axes=(1, 1, 1, 0), out_axes=1)(
        qc, kc, vc, mask)                           # (B, n, C, H, D)
    return out.reshape(q.shape)


class BandedSDPA(autograd.Operator):
    """Backend selection mirrors SDPA: the Pallas banded kernel on TPU
    (below-band tiles skipped entirely), the chunked jnp path
    elsewhere, the full-mask reference for degenerate chunkings."""

    def __init__(self, window: int, scale: Optional[float],
                 chunk: Optional[int]):
        super().__init__()
        self.window = window
        self.scale = scale
        self.chunk = chunk

    def fwd(self, q, k, v):
        scale = self.scale or (1.0 / math.sqrt(q.shape[-1]))
        W = self.window
        if self.chunk is None and _use_flash(q, k):
            from .flash_attention import flash_attention
            # falls back to the banded reference internally when the
            # shape doesn't tile
            return flash_attention(q, k, v, causal=True, scale=scale,
                                   window=W)
        if self.chunk is None and pick_band_chunk(q.shape[1], W) is None:
            return _banded_reference(q, k, v, W, scale)
        return banded_sdpa(q, k, v, W, scale, self.chunk)


def banded_attention(q: Tensor, k: Tensor, v: Tensor, window: int,
                     scale: Optional[float] = None,
                     chunk: Optional[int] = None) -> Tensor:
    """Tape entry point for chunked sliding-window attention."""
    return BandedSDPA(window, scale, chunk)(q, k, v)
