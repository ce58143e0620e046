"""Rotary position embeddings (RoPE) — needed for GPT-NeoX/Llama families
(BASELINE.json:11 stretch config)."""

from __future__ import annotations

import functools
import math

import jax.numpy as jnp

from .. import autograd
from ..tensor import Tensor

__all__ = ["rope_frequencies", "yarn_frequencies", "apply_rope",
           "llama31_rope_scaling"]


def llama31_rope_scaling(inv_freq, scale_factor: float = 8.0,
                         low_freq_factor: float = 1.0,
                         high_freq_factor: float = 4.0,
                         original_max_position: int = 8192):
    """Llama-3.1-style frequency-dependent NTK interpolation: long
    wavelengths (beyond the original context) are divided by
    `scale_factor`, short wavelengths pass through, and the band in
    between blends linearly — extends the usable context by
    ~scale_factor without retraining the short-range behavior."""
    wavelen = 2.0 * jnp.pi / inv_freq
    low_bound = original_max_position / low_freq_factor    # long waves
    high_bound = original_max_position / high_freq_factor  # short waves
    # smooth in (0,1): 0 at the long-wave bound, 1 at the short-wave one
    smooth = (original_max_position / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor)
    scaled = jnp.where(
        wavelen > low_bound, inv_freq / scale_factor,
        jnp.where(wavelen < high_bound, inv_freq,
                  (1 - smooth) * inv_freq / scale_factor + smooth * inv_freq))
    return scaled


@functools.lru_cache(maxsize=32)
def rope_frequencies(head_dim: int, max_len: int, theta: float = 10000.0,
                     rope_scaling: float = 0.0,
                     rope_original_max_position: int = 8192):
    """Precompute (cos, sin) tables of shape (max_len, head_dim//2).

    Cached so every attention layer of a model shares one table pair
    instead of baking per-layer copies into the compiled module.

    rope_scaling > 0 applies Llama-3.1-style frequency-dependent
    interpolation with that scale factor (context extension);
    `rope_original_max_position` is the PRETRAINED context window the
    interpolation bands are anchored to."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if rope_scaling and rope_scaling > 0.0:
        inv = llama31_rope_scaling(
            inv, scale_factor=float(rope_scaling),
            original_max_position=int(rope_original_max_position))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs), jnp.sin(freqs)


@functools.lru_cache(maxsize=32)
def yarn_frequencies(head_dim: int, max_len: int, theta: float, factor: float,
                     original_max_position: int):
    """(cos, sin) tables of shape (max_len, head_dim//2) under YaRN
    (Peng et al. 2023, arXiv:2309.00071), as transformers'
    `_compute_yarn_parameters` has it at its defaults: pair j keeps its
    frequency when it turns more than 32 times (beta_fast) inside the
    pretrained context `original_max_position`, is divided by `factor`
    when it turns less than once (beta_slow), and blends linearly over
    the pairs between.  Both tables are multiplied by the paper's
    attention factor 0.1 ln(factor) + 1, which scales q.k by its
    square."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))

    def pair_turning(rotations):
        return head_dim * math.log(
            original_max_position / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair_turning(32.0)), 0)
    hi = min(math.ceil(pair_turning(1.0)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    scale = 0.1 * math.log(factor) + 1.0
    freqs = jnp.outer(jnp.arange(max_len, dtype=jnp.float32), inv)
    return jnp.cos(freqs) * scale, jnp.sin(freqs) * scale


def _rope_fn(x, cos, sin, offset=0):
    # x: (B, T, H, D); tables sliced to [offset, offset+T).  `offset` may
    # be a traced scalar (KV-cached decoding) — dynamic_slice keeps the
    # compiled decode step position-independent — or a traced (B,)
    # vector (continuous-batching decode, serve.engine): row b reads
    # table rows [offset[b], offset[b]+T), so every slot rotates at its
    # own position inside ONE compiled step.
    import jax
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        # partial rotary: tables built for the first `rot` dims of each
        # head rotate those, the rest pass through unrotated
        return jnp.concatenate(
            [_rope_fn(x[..., :rot], cos, sin, offset), x[..., rot:]],
            axis=-1)
    T = x.shape[1]
    if getattr(offset, "ndim", 0):
        idx = offset[:, None] + jnp.arange(T)[None, :]       # (B, T)
        c = jnp.take(cos, idx, axis=0)[:, :, None, :]        # (B, T, 1, D/2)
        s = jnp.take(sin, idx, axis=0)[:, :, None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
        return out.astype(x.dtype)
    if isinstance(offset, int) and offset == 0:
        c, s = cos[:T], sin[:T]
    else:
        c = jax.lax.dynamic_slice_in_dim(cos, offset, T, axis=0)
        s = jax.lax.dynamic_slice_in_dim(sin, offset, T, axis=0)
    c = c[None, :, None, :]
    s = s[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)


class Rope(autograd.Operator):
    def __init__(self, cos, sin, offset=0):
        super().__init__()
        self.cos, self.sin, self.offset = cos, sin, offset

    def fwd(self, x):
        return _rope_fn(x, self.cos, self.sin, self.offset)


def apply_rope(x, cos, sin, offset=0):
    """Rotate `x` (B, T, H, D) by the tables' rows [offset, offset + T).
    Tables narrower than D / 2 (`rope_frequencies(rot_dim, ...)` with
    rot_dim = partial_rotary_factor x D) rotate the first rot_dim dims
    of each head, half-split among themselves, and pass the rest."""
    if isinstance(x, Tensor):
        return Rope(cos, sin, offset)(x)
    return _rope_fn(x, cos, sin, offset)
