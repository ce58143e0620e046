"""KV-cache primitives for autoregressive decoding (VERDICT r2 item 4;
SURVEY.md §7.3.5 — GPT-2 generation with dynamic shapes is hostile to
XLA, so the TPU-native formulation is a *static* cache: preallocated
(B, S_max, K, D) buffers updated in place with dynamic_update_slice and
an explicit validity mask, so every decode step reuses ONE compiled
module regardless of how many tokens have been generated).

Prefill attends within the prompt via the regular attention stack (the
Pallas flash kernel when the shape tiles); decode steps (Tq=1) are
bandwidth-bound matvecs where flash has nothing to win, so they run the
masked-reference path against the full cache.

**Int8 KV blocks** (ISSUE 17): a paged arena may store its blocks as
:class:`QuantKV` — int8 codes plus a per-position f32 scale (one scale
per (K, D) slab, i.e. a ``(block_size,)`` scale vector per block).
Every gather/scatter primitive below branches on ``isinstance(ck,
QuantKV)`` at TRACE time: quantize-on-scatter / dequantize-on-gather
are fixed-shape elementwise ops folded into the same programs, so an
int8 arena compiles the same fixed program set as a full-precision one
(one jit entry per program, asserted in tests) while its decode
dispatch streams ~4x fewer KV bytes through HBM (the hlocost
``decode_int8`` flagship baseline is the committed evidence).  The
scale granularity is per POSITION, not per block, because
``scatter_token_kv``/``scatter_tokens_kv`` write partial blocks — a
single per-block scalar would force requantizing the block's existing
content whenever a new token's amax grew past it.

**Decode without a view** (ISSUE 33): a decode tick may hand the model
:class:`PagedKV` caches — a pool as the arena holds it, the block table
and where this tick's token goes.  ``update_cache`` then writes the
token into the pool in place and ``cached_sdpa`` reads the slot's
blocks through the table (``ops.paged_attention``), so the model's two
calls stay what they are and no ``max_len``-sized view exists.
:func:`reads_blocks` says when: a plain bf16/f32 pool the kernel tiles,
on a TPU.  Everything else gathers a view as before.
"""

from __future__ import annotations

import math
from typing import List, Tuple

import jax
import jax.numpy as jnp

from ..device import on_tpu

__all__ = ["init_cache", "update_cache", "cached_sdpa",
           "gather_block_kv", "scatter_block_kv", "scatter_token_kv",
           "scatter_tokens_kv", "QuantKV", "quantize_kv",
           "dequantize_kv", "PagedKV", "reads_blocks"]

#: int8 code range: symmetric, -127..127 (the -128 code is unused so
#: quantization commutes with negation and the scale maps amax -> 127)
_QMAX = 127.0
#: scale floor so an all-zero (K, D) slab quantizes to exact zeros
#: instead of dividing by zero (dequantized value stays exactly 0.0)
_SCALE_FLOOR = 1e-30


@jax.tree_util.register_pytree_node_class
class QuantKV:
    """One int8-quantized KV pool: ``q`` int8 codes with the pool's
    layout (``(num_blocks, block_size, K, D)``) and ``scale`` f32 of
    shape ``(num_blocks, block_size, 1, 1)`` — dequantized value is
    ``q * scale``.  A registered pytree, so it flows through jit
    arguments, donation and ``jax.tree`` utilities exactly like the
    plain arrays it replaces; ``.shape``/``.dtype`` mirror ``q`` so
    shape-reading call sites (``ck.shape[1]``) need no branch."""

    __slots__ = ("q", "scale")

    def __init__(self, q, scale):
        self.q = q
        self.scale = scale

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return f"QuantKV(q={self.q.shape}, scale={self.scale.shape})"


@jax.tree_util.register_pytree_node_class
class PagedKV:
    """One pool of a paged arena as a decode tick's cache: ``pool``
    (num_blocks, block_size, K, D) as the arena holds it, ``tables``
    (S, max_blocks) int32 block-table rows, and ``block`` / ``offset``
    (S,) int32, where this tick's one token a slot is written.
    :func:`update_cache` and :func:`cached_sdpa` take it where a dense
    (S, max_len, K, D) cache would go, so a model's cached attention
    serves from the arena without learning what an arena is.  ``plan``:
    for a latent pool, which slots read their shared leading blocks once
    for a group (``ops.mla_attention.group_plan``); None elsewhere."""

    __slots__ = ("pool", "tables", "block", "offset", "plan")

    def __init__(self, pool, tables, block, offset, plan=None):
        self.pool = pool
        self.tables = tables
        self.block = block
        self.offset = offset
        self.plan = plan

    def tree_flatten(self):
        return (self.pool, self.tables, self.block, self.offset,
                self.plan), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return f"PagedKV(pool={self.pool.shape}, tables={self.tables.shape})"


def reads_blocks(pool) -> bool:
    """Whether one-row decode attention reads this pool's blocks through
    the block table (``ops.paged_attention``) instead of a gathered
    view: a plain bf16 or f32 pool of a shape the kernel tiles, on a
    TPU.  Decided by what the pool is, like ``attention._use_flash``;
    there is no switch.  A pool without a head axis, ``(num_blocks,
    block_size, width)``, is a latent pool (``models/glm_mla.py``): its
    kernel is ``ops.mla_attention``'s."""
    if isinstance(pool, QuantKV) \
            or pool.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if len(pool.shape) == 3:
        from .mla_attention import tiles as latent_tiles
        return latent_tiles(pool.shape, pool.dtype) and on_tpu()
    from .paged_attention import tiles
    return tiles(pool.shape, pool.dtype) and on_tpu()


def attends_view(view) -> bool:
    """Whether a latent model's cached attention over this dense view,
    ``(B, max_len, kv_lora_rank)``, runs as ``ops.mla_attention``'s
    prefill kernel instead of masked softmax over the whole view: a bf16
    or f32 view of a shape the kernel tiles, on a TPU.  Decided by what
    the view is, like :func:`reads_blocks`; there is no switch."""
    from .mla_attention import prefill_tiles
    return prefill_tiles(view.shape, view.dtype) and on_tpu()


def quantize_kv(x):
    """Quantize ``x`` (..., K, D) to (int8 codes, f32 scales): one
    symmetric absmax scale per leading index (per position), shape
    (..., 1, 1).  Fixed-shape elementwise math — folds into whatever
    program performs the scatter."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1), keepdims=True)
    scale = jnp.maximum(amax / _QMAX, _SCALE_FLOOR)
    q = jnp.clip(jnp.round(xf / scale), -_QMAX, _QMAX).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale):
    """Inverse of :func:`quantize_kv` (f32 out)."""
    return q.astype(jnp.float32) * scale


def init_cache(num_layers: int, batch: int, max_len: int, num_kv_heads: int,
               head_dim: int, dtype=jnp.float32) -> List[Tuple]:
    """Per-layer (k, v) buffers of shape (B, S_max, K, D)."""
    shape = (batch, max_len, num_kv_heads, head_dim)
    return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
            for _ in range(num_layers)]


def update_cache(ck, cv, k_new, v_new, pos):
    """Write k/v for positions [pos, pos+T) into the cache (functional).

    `pos` may be a traced scalar — decode steps compile once and slide —
    or a traced (B,) vector (continuous batching, serve.engine): row b's
    new keys land at its own positions [pos[b], pos[b]+T), so slots at
    different generation depths share ONE compiled decode step.

    :class:`PagedKV` caches take row b's one token (T == 1) into the
    pool at ``[block[b], offset[b]]``, in place."""
    if isinstance(ck, PagedKV):
        pk, pv = scatter_token_kv(ck.pool, cv.pool, ck.block, ck.offset,
                                  k_new[:, 0], v_new[:, 0])
        return (PagedKV(pk, ck.tables, ck.block, ck.offset, ck.plan),
                PagedKV(pv, cv.tables, cv.block, cv.offset, cv.plan))
    if getattr(pos, "ndim", 0):
        def row(c, n, p):
            return jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0)
        return (jax.vmap(row)(ck, k_new.astype(ck.dtype), pos),
                jax.vmap(row)(cv, v_new.astype(cv.dtype), pos))
    ck = jax.lax.dynamic_update_slice_in_dim(ck, k_new.astype(ck.dtype),
                                             pos, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cv, v_new.astype(cv.dtype),
                                             pos, axis=1)
    return ck, cv


def gather_block_kv(ck, cv, table):
    """Gather a contiguous per-request view out of a paged block arena.

    ``ck``/``cv``: (num_blocks, block_size, K, D) block pools.
    ``table``: (B, max_blocks) int32 block table — row b's logical block
    i lives in physical block ``table[b, i]``.  Returns dense
    (B, max_blocks * block_size, K, D) views.  The gather is a
    fixed-shape ``jnp.take`` on the leading axis, so the paged arena
    rides ONE compiled program no matter which physical blocks a
    request holds (stale/unallocated table entries read garbage that
    the attention ``limit`` mask makes unreachable).  A :class:`QuantKV`
    arena gathers codes AND scales through the same take and
    dequantizes in-program — the dense view is f32 either way the
    attention math sees it.

    Every table entry must be a valid block id in ``[0, num_blocks)``.
    ``serve.slots.BlockPool`` guarantees it: its tables start at zeros
    (the null block) and rows are only ever set from ids the pool
    handed out.  So the take asks for no out-of-bounds fill
    (``mode="clip"``): ``jnp.take``'s default ``mode="fill"`` lowers
    to the gather PLUS a select that reads and rewrites the whole
    dense view to put NaN where an index was out of range — a second
    pass over a ``max_len``-sized view in every serve program, for
    nothing.  A NaN would not guard anything either: the attention mask
    zeroes the probability, and ``0 x NaN`` in ``P @ V`` is NaN.  For
    in-range ids fill and clip return the same bytes.  Reading a
    trace: before the reshape the view is ``(B * max_blocks,
    block_size, K, D)``, which is the arena's own shape when
    ``num_blocks == B * max_blocks`` and one block short of it at
    ``BlockPool``'s default ``num_blocks`` — an op of that shape is
    not necessarily a write to the arena."""
    B, M = table.shape
    bs = ck.shape[1]

    def dense(c):
        g = jnp.take(c, table.reshape(-1), axis=0,
                     mode="clip")                         # (B*M, bs, K, D)
        return g.reshape((B, M * bs) + c.shape[2:])

    if isinstance(ck, QuantKV):
        return (dense(ck.q).astype(jnp.float32) * dense(ck.scale),
                dense(cv.q).astype(jnp.float32) * dense(cv.scale))
    return dense(ck), dense(cv)


def scatter_block_kv(ck, cv, block, k_blk, v_blk):
    """Write one block's worth of k/v back into the paged arena.

    ``block`` is a traced int32 scalar physical block id; ``k_blk`` /
    ``v_blk`` are (block_size, K, D).  The chunked-prefill counterpart
    of :func:`gather_block_kv` — a fixed-shape scatter at a dynamic
    leading index, one compiled shape for every block."""
    if isinstance(ck, QuantKV):
        kq, ks = quantize_kv(k_blk)
        vq, vs = quantize_kv(v_blk)
        return (QuantKV(ck.q.at[block].set(kq),
                        ck.scale.at[block].set(ks)),
                QuantKV(cv.q.at[block].set(vq),
                        cv.scale.at[block].set(vs)))
    return (ck.at[block].set(k_blk.astype(ck.dtype)),
            cv.at[block].set(v_blk.astype(cv.dtype)))


def scatter_token_kv(ck, cv, block, offset, k_tok, v_tok):
    """Write ONE position's k/v per batch row into the paged arena.

    ``block``/``offset``: (B,) int32 vectors — row b's token lands at
    ``[block[b], offset[b]]``.  ``k_tok``/``v_tok``: (B, K, D).  The
    decode-over-block-tables counterpart of :func:`update_cache`'s
    per-row vector path; rows sharing a target (inactive slots
    redirected to the null block) resolve arbitrarily, which is safe
    because the null block is never inside any row's validity window."""
    if isinstance(ck, QuantKV):
        kq, ks = quantize_kv(k_tok)
        vq, vs = quantize_kv(v_tok)
        return (QuantKV(ck.q.at[block, offset].set(kq),
                        ck.scale.at[block, offset].set(ks)),
                QuantKV(cv.q.at[block, offset].set(vq),
                        cv.scale.at[block, offset].set(vs)))
    return (ck.at[block, offset].set(k_tok.astype(ck.dtype)),
            cv.at[block, offset].set(v_tok.astype(cv.dtype)))


def scatter_tokens_kv(ck, cv, blocks, offsets, k_toks, v_toks):
    """Write a per-row WINDOW of positions into the paged arena.

    ``blocks``/``offsets``: (B, T) int32 — row b's window token t lands
    at ``[blocks[b, t], offsets[b, t]]``.  ``k_toks``/``v_toks``:
    (B, T, K, D).  The speculative verify-k counterpart of
    :func:`scatter_token_kv`: one verify dispatch writes k+1 positions
    per slot (the pending token plus the k proposals), and rejected
    positions are rolled back by TRUNCATING the slot's ``pos``/attention
    ``limit`` — the stale entries past the new limit are unreachable,
    exactly like any stale block content.  Rows sharing a target
    (inactive slots redirected to the null block for every window
    position) resolve arbitrarily, which is safe for the same reason."""
    if isinstance(ck, QuantKV):
        kq, ks = quantize_kv(k_toks)
        vq, vs = quantize_kv(v_toks)
        return (QuantKV(ck.q.at[blocks, offsets].set(kq),
                        ck.scale.at[blocks, offsets].set(ks)),
                QuantKV(cv.q.at[blocks, offsets].set(vq),
                        cv.scale.at[blocks, offsets].set(vs)))
    return (ck.at[blocks, offsets].set(k_toks.astype(ck.dtype)),
            cv.at[blocks, offsets].set(v_toks.astype(cv.dtype)))


def cached_sdpa(q, ck, cv, limit, scale: float = None, mask=None,
                window: int = None):
    """Attention of q (B, T, H, D) against the full cache (B, S, K, D),
    masked to cache positions < `limit` plus bottom-right-aligned
    causality inside the query block (query t attends cache positions
    <= limit - T + t).  `limit` may be a scalar or a (B,) vector of
    per-row limits (continuous batching: every slot attends its own
    prefix inside one compiled step).  GQA (H % K == 0) and the grouped
    einsums are delegated to attention._sdpa_reference — one attention
    math, two entry points.  `mask`: optional (B, 1|H, 1|T, S) boolean
    padding mask ANDed with the validity window.  `window`:
    Mistral-style sliding window — each query also ignores cache
    positions more than `window - 1` behind it.

    :class:`PagedKV` caches (one query row a slot, a `limit` a slot, no
    `mask`): the slot's blocks are read through its table row, only
    those that hold a position below `limit` and inside `window`."""
    if isinstance(ck, PagedKV):
        from .paged_attention import paged_attention
        if q.shape[1] != 1 or mask is not None:
            raise ValueError(
                "PagedKV caches attend one query row a slot under a limit "
                f"and a window only (got {q.shape[1]} rows, mask "
                f"{'given' if mask is not None else 'None'})")
        return paged_attention(q[:, 0], ck.pool, cv.pool, ck.tables, limit,
                               window=window or 0, scale=scale)[:, None]
    from .attention import _sdpa_reference
    T = q.shape[1]
    S = ck.shape[1]
    scale = scale or (1.0 / math.sqrt(q.shape[-1]))
    kpos = jnp.arange(S)[None, None, None, :]           # (1, 1, 1, S)
    lim = jnp.asarray(limit)
    lim = lim.reshape((-1, 1, 1, 1)) if lim.ndim else lim
    qpos = lim - T + jnp.arange(T)[None, None, :, None]  # (B|1, 1, T, 1)
    valid = kpos <= qpos                                 # (B|1, 1, T, S)
    if window is not None:
        valid = jnp.logical_and(valid, kpos > qpos - window)
    if mask is not None:
        valid = jnp.logical_and(valid, mask)
    return _sdpa_reference(q, ck, cv, False, valid, scale)
