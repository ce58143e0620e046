"""Flash attention — blockwise Pallas TPU kernel (forward + backward).

Online-softmax attention with O(block) VMEM: K/V stream through the
innermost grid dimension one (BLOCK_K, D) tile at a time while the
(running max, denominator, f32 accumulator) persist in VMEM scratch, so
sequence length is bounded by HBM, not VMEM — the long-context half of
the single-chip design (cross-chip sequence scaling is
ops.ring_attention).  Structure follows FlashAttention-2; backward
recomputes score tiles from the saved logsumexp with separate dQ and
dK/dV kernels.

TPU mapping (pallas_guide.md): QK^T and PV tiles ride the MXU via
jnp.dot(..., preferred_element_type=f32); tiles live in VMEM; causal
skips fully-masked tiles with pl.when; GQA maps G query heads onto one
kv head in the BlockSpec index map so grouped (Llama-3) attention needs
no head replication in HBM.  Causal masking is bottom-right aligned
(qpos + Tk - Tq >= kpos), matching the XLA reference for Tq != Tk
(KV-cached decoding).

Shapes the kernel does not tile (T not a multiple of 128, tiny head
dims) take the XLA-fused reference.  Interpret mode runs the same
kernels on CPU for tests; it is on only when the caller asks for it or
the platform is not a TPU (device.on_tpu) — a TPU backend that fails is
an error, never a quiet switch to the interpreter.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import on_tpu

__all__ = ["flash_attention", "flash_attention_with_lse"]

_NEG_INF = -1e30


def _causal_ids(qi, kj, block_q, block_k, off):
    qpos = qi * block_q + off + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return qpos, kpos


# ---------------------------------------------------------------------------
# forward: grid (B, H, nq, nkv); kv streams innermost; acc/m/l in scratch
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k, off, window=None):
    qi, kj = pl.program_id(2), pl.program_id(3)
    nkv = pl.num_programs(3)

    @pl.when(kj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: skip tiles where even the last q row precedes the first
    # key; window: also skip tiles entirely below the band (every key
    # older than first-query-pos - W)
    live = True
    if causal:
        live = (qi * block_q + block_q - 1 + off) >= kj * block_k
    if window is not None:
        live = live & (kj * block_k + block_k - 1
                       > qi * block_q + off - window)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal or window is not None:
            qpos, kpos = _causal_ids(qi, kj, block_q, block_k, off)
            if causal:
                s = jnp.where(qpos >= kpos, s, _NEG_INF)
            if window is not None:
                s = jnp.where(kpos > qpos - window, s, _NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new

    @pl.when(kj == nkv - 1)
    def _():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # lse carried as (bq, 1): Mosaic requires the trailing two block
        # dims be (mult-of-8, mult-of-128 | full-dim), which (bq, 1) over
        # a (B, H, Tq, 1) array satisfies and (1, bq) over (B, H, Tq)
        # does not.
        lse_ref[0, 0] = m_ref[:] + jnp.log(l)


# ---------------------------------------------------------------------------
# backward: dQ streams kv innermost; dK/dV streams q innermost
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, block_q, block_k, off,
                   window=None):
    qi, kj = pl.program_id(2), pl.program_id(3)
    nkv = pl.num_programs(3)

    @pl.when(kj == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = True
    if causal:
        live = (qi * block_q + block_q - 1 + off) >= kj * block_k
    if window is not None:
        live = live & (kj * block_k + block_k - 1
                       > qi * block_q + off - window)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        if causal or window is not None:
            qpos, kpos = _causal_ids(qi, kj, block_q, block_k, off)
            if causal:
                p = jnp.where(qpos >= kpos, p, 0.0)
            if window is not None:
                p = jnp.where(kpos > qpos - window, p, 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[:] = dq_acc[:] + jnp.dot(ds, k,
                                        preferred_element_type=jnp.float32)

    @pl.when(kj == nkv - 1)
    def _():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, off, window=None):
    kj, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = True
    if causal:
        live = (qi * block_q + block_q - 1 + off) >= kj * block_k
    if window is not None:
        live = live & (kj * block_k + block_k - 1
                       > qi * block_q + off - window)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)                              # (BQ, BK)
        if causal or window is not None:
            qpos, kpos = _causal_ids(qi, kj, block_q, block_k, off)
            if causal:
                p = jnp.where(qpos >= kpos, p, 0.0)
            if window is not None:
                p = jnp.where(kpos > qpos - window, p, 0.0)
        dv_acc[:] = dv_acc[:] + jnp.dot(p.T, do,
                                        preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[:] = dk_acc[:] + jnp.dot(ds.T, q,
                                        preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call drivers over (B, H, T, D) layout
# ---------------------------------------------------------------------------

def _block_sizes(seq_q, seq_k):
    """Tile sizes for the kernel grid.  SINGA_FLASH_BLOCK="bq,bk"
    overrides for tuning; each must divide its sequence length and be a
    positive multiple of 128, anything else raises."""
    import os
    override = os.environ.get("SINGA_FLASH_BLOCK")
    if override:
        try:
            bq, bk = (int(v) for v in override.split(","))
        except ValueError:
            raise ValueError(
                f"SINGA_FLASH_BLOCK={override!r}: expected 'bq,bk'") from None
        if not (bq > 0 and bk > 0 and bq % 128 == 0 and bk % 128 == 0
                and seq_q % bq == 0 and seq_k % bk == 0):
            raise ValueError(
                f"SINGA_FLASH_BLOCK={override!r} does not tile "
                f"Tq={seq_q}, Tk={seq_k}: each block must be a positive "
                f"multiple of 128 that divides its sequence length")
        return bq, bk
    # largest tile that divides the sequence: fewer grid steps amortize
    # the per-step grid overhead
    def best(seq):
        for b in (512, 256, 128):
            if seq % b == 0:
                return b
        return 128
    return best(seq_q), best(seq_k)


def _fwd(q, k, v, causal, scale, interpret, window=None):
    B, H, Tq, D = q.shape
    K, Tk = k.shape[1], k.shape[2]
    G = H // K
    bq, bk = _block_sizes(Tq, Tk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, off=Tk - Tq,
                               window=window)
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, Tq // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _bwd(q, k, v, o, lse, do, causal, scale, interpret, dlse=None,
         window=None):
    B, H, Tq, D = q.shape
    K, Tk = k.shape[1], k.shape[2]
    G = H // K
    bq, bk = _block_sizes(Tq, Tk)
    off = Tk - Tq
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                        # (B, H, Tq, 1)
    if dlse is not None:
        # lse cotangent folds into delta: ds = p * (dp - delta + dlse)
        # (∂lse_i/∂s_ij = p_ij), so delta_eff = delta - dlse
        delta = delta - dlse.reshape(B, H, Tq, 1).astype(jnp.float32)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, off=off, window=window),
        grid=(B, H, Tq // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv per *query* head (grid over H), reduced over each GQA group
    # outside the kernel — avoids cross-program accumulation
    dk_p, dv_p = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, off=off, window=window),
        grid=(B, H, Tk // bk, Tq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, j, i, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, j, i, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tk, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tk, D), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    if G > 1:
        dk = dk_p.reshape(B, K, G, Tk, D).sum(axis=2).astype(k.dtype)
        dv = dv_p.reshape(B, K, G, Tk, D).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_p, dv_p
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp core in (B, H, T, D) layout
# ---------------------------------------------------------------------------

def _flash_core(q, k, v, causal, scale, interpret, window=None):
    """o-only view over the (o, lse) core; the lse cotangent is zeros,
    which _bwd folds in for free (delta - 0)."""
    return _flash_core_lse(q, k, v, causal, scale, interpret, window)[0]


# -- (o, lse) core: also the building block for cross-chip ring attention --

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core_lse(q, k, v, causal, scale, interpret, window=None):
    return _fwd(q, k, v, causal, scale, interpret, window)


def _flash_core_lse_fwd(q, k, v, causal, scale, interpret, window=None):
    o, lse = _fwd(q, k, v, causal, scale, interpret, window)
    return (o, lse), (q, k, v, o, lse)


def _flash_core_lse_bwd(causal, scale, interpret, window, res, cots):
    q, k, v, o, lse = res
    do, dlse = cots
    return _bwd(q, k, v, o, lse, do, causal, scale, interpret, dlse=dlse,
                window=window)


_flash_core_lse.defvjp(_flash_core_lse_fwd, _flash_core_lse_bwd)


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: float = None, interpret: bool = None):
    """(B, H, T, D)-layout flash attention returning (o, lse) with lse
    differentiable — the per-block primitive ring attention combines
    across chips (lse (B, H, Tq, 1) f32).  No XLA fallback: shapes that
    don't tile raise (a silent fallback here would skip tail rows)."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    if not _tileable(Tq, Tk, D) or H % k.shape[1] != 0:
        raise ValueError(
            f"flash_attention_with_lse needs tiling shapes "
            f"(T % 128 == 0, D >= 32, D % 8 == 0); got Tq={Tq}, Tk={Tk}, "
            f"D={D}, H={H}, K={k.shape[1]}")
    scale = scale or (1.0 / math.sqrt(q.shape[-1]))
    if interpret is None:
        interpret = not on_tpu()
    return _flash_core_lse(q, k, v, bool(causal), float(scale),
                           bool(interpret))


def _tileable(Tq, Tk, D) -> bool:
    return Tq % 128 == 0 and Tk % 128 == 0 and D >= 32 and D % 8 == 0


def flash_attention(q, k, v, causal: bool = False, scale: float = None,
                    interpret: bool = None, window: int = None):
    """(B, T, H, D) attention; k/v may have fewer heads (GQA, H % K == 0)
    or a longer sequence (KV cache; causal is bottom-right aligned).
    `window`: Mistral-style sliding window — banded tiles below the
    band are skipped entirely (requires causal=True).

    Uses the Pallas kernel when shapes tile onto the hardware, else the
    XLA-fused reference (same math, O(T^2) logits)."""
    from .attention import _sdpa_reference

    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (the band is "
                             "causal by definition)")
        if window < 1:
            raise ValueError(
                f"window must be >= 1, got {window} (0 would mask every "
                "key; use window=None for full causal attention)")
    scale = scale or (1.0 / math.sqrt(q.shape[-1]))
    B, Tq, H, D = q.shape
    Tk, K = k.shape[1], k.shape[2]
    if not _tileable(Tq, Tk, D) or H % K != 0:
        if window is not None:
            from .attention import _banded_reference
            return _banded_reference(q, k, v, window, scale)
        return _sdpa_reference(q, k, v, causal, None, scale)
    if interpret is None:
        interpret = not on_tpu()
    # (B, T, H, D) -> (B, H, T, D) for contiguous per-head tiles
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    o = _flash_core(qh, kh, vh, causal, float(scale), bool(interpret),
                    None if window is None else int(window))
    return jnp.swapaxes(o, 1, 2)
