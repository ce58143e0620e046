"""Paged decode attention — Pallas TPU kernel over a block arena.

One query token a slot attends the KV blocks that slot holds, read
straight out of the paged arena through its block-table row and up to
its own length: no dense ``max_len``-sized view of the arena is built,
written or walked (``ops.kv_cache.gather_block_kv`` is the other way,
still taken by prefill, the speculative verify and every backend that
is not a TPU).

TPU mapping (pallas_guide.md).  The two pools stay in HBM
(``memory_space=pl.ANY``); the block table and the lengths are scalar
prefetched into SMEM, so block ids are known before the body runs.
Grid ``(S,)``, one step a slot.  A step walks the slot's live blocks
``max(0, len - window) // bs … (len - 1) // bs`` in chunks of ``C``
blocks: one ``make_async_copy`` a block and a pool (a block is
contiguous for all its KV heads), two chunk buffers, the next chunk —
or the next slot's first — in flight while this one is computed, and
one wait a pool for a whole chunk.  A chunk always copies ``C`` blocks,
the ids past the slot's last clamped to it (their positions are masked
like the last block's tail), so the copies are straight-line code.

A block ``(bs, K, D)`` is the matrix ``(bs·K, D)`` already: row
``p·K + h`` is position p of KV head h.  The pools are handed over as
``(num_blocks, bs·K, D)`` — a bitcast under the TPU's tiled layouts
when K is a power of two (the tests compile the cells' shapes and look
for a copy of an arena) — and a chunk lies in VMEM as ``(C·bs·K, D)``
in the native tiling.  ``q (H, D) @ chunk^T`` gives every query head's
score against every (position, KV head) row; the rows of the other KV
heads are masked off with the tail past ``len`` and the head before the
window, and ``p @ chunk_v`` contracts over the same rows.  So GQA
shares one fetched block among the ``H / K`` query heads of a KV head
with no strided read of one head out of a sublane-packed block; the
masked rows cost MXU passes that a bandwidth-bound kernel has to spare
(K = 2 read as ``(bs, 2, D)`` tiles took 2.2x as long: PERF.md §6,
PR 33).  Running max, sum and accumulator are f32 (online softmax);
probabilities are cast to the pool's dtype for ``p @ v`` as the
reference does.

The table has to fit SMEM beside the lengths (1 MiB on a v5e:
``S x max_blocks`` rounded up to 128, x 4 B).

Interpret mode runs the same kernel on the CPU for tests; it is on only
when the caller asks for it or the platform is not a TPU — a TPU
backend that fails to lower is an error, never a quiet switch to the
gathered view.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import on_tpu

__all__ = ["paged_attention", "tiles"]

_NEG_INF = -1e30
#: rows (positions x KV heads) of one chunk
_CHUNK_ROWS = 2048


def tiles(pool_shape, dtype) -> bool:
    """Whether the kernel takes a pool of this shape on a TPU: heads of
    whole 128-lane rows; a power of two of KV heads that fills a 32-bit
    sublane, so that the pool's (K, D) tiles hold no padding and
    ``(N, bs, K, D)`` -> ``(N, bs·K, D)`` is a bitcast, not a copy of
    the arena; blocks of whole (16, 128) tiles."""
    _, bs, K, D = pool_shape
    return (D % 128 == 0 and K & (K - 1) == 0
            and K * jnp.dtype(dtype).itemsize >= 4 and (bs * K) % 16 == 0)


def _chunk_blocks(bs: int, K: int, max_blocks: int) -> int:
    return max(1, min(_CHUNK_ROWS // (bs * K), max_blocks))


def _kernel(tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, buf_ref, *, scale, window, C, bs, K, G):
    s = pl.program_id(0)
    S = pl.num_programs(0)
    H, D = q_ref.shape
    R = C * bs * K

    def span(slot):
        """(first, last) live block of `slot`'s table row."""
        n = lengths_ref[slot]
        first = jnp.maximum(n - window, 0) // bs if window else 0
        return first, (n - 1) // bs

    def start(slot, chunk, buf):
        first, last = span(slot)
        for c in range(C):
            blk = tables_ref[slot, jnp.minimum(first + chunk * C + c, last)]
            rows = pl.ds(c * bs * K, bs * K)
            pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[buf, rows],
                                  sems.at[0, buf]).start()
            pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[buf, rows],
                                  sems.at[1, buf]).start()

    def wait(buf):
        # a DMA semaphore counts bytes: one wait of a whole buffer's
        # size takes the chunk's C copies (the source only gives a size)
        pltpu.make_async_copy(kbuf.at[1 - buf], kbuf.at[buf],
                              sems.at[0, buf]).wait()
        pltpu.make_async_copy(vbuf.at[1 - buf], vbuf.at[buf],
                              sems.at[1, buf]).wait()

    @pl.when(s == 0)
    def _():
        buf_ref[0] = 0
        start(0, 0, 0)

    n = lengths_ref[s]
    first, last = span(s)
    nchunks = (last - first) // C + 1
    q = q_ref[...]                                        # (H, D)

    # bf16 operands are one MXU pass whatever precision the caller's
    # config asks of its f32 matmuls; f32 pools follow that config
    precision = (jax.lax.Precision.DEFAULT
                 if k_hbm.dtype == jnp.bfloat16 else None)

    # row r of a chunk is (position r // K of the chunk, KV head r % K)
    row = jax.lax.broadcasted_iota(jnp.int32, (H, R), 1)
    qhead = jax.lax.broadcasted_iota(jnp.int32, (H, R), 0)
    own = (row % K) == (qhead // G)
    rpos = row // K

    def body(j, carry):
        m, l, acc, buf = carry
        nxt = 1 - buf

        @pl.when(j + 1 < nchunks)
        def _():
            start(s, j + 1, nxt)

        @pl.when(jnp.logical_and(j + 1 == nchunks, s + 1 < S))
        def _():
            start(s + 1, 0, nxt)

        wait(buf)
        k = kbuf[buf]                                     # (R, D)
        v = vbuf[buf]
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale    # (H, R)
        pos = (first + j * C) * bs + rpos
        valid = jnp.logical_and(own, pos < n)
        if window:
            valid = jnp.logical_and(valid, pos >= n - window)
        sc = jnp.where(valid, sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                    precision=precision,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc, nxt

    _, l, acc, buf = jax.lax.fori_loop(
        0, nchunks, body,
        (jnp.full((H, 1), _NEG_INF, jnp.float32),
         jnp.zeros((H, 1), jnp.float32),
         jnp.zeros((H, D), jnp.float32), buf_ref[0]))
    buf_ref[0] = buf
    o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def paged_attention(q, k_pool, v_pool, tables, lengths, *, window: int = 0,
                    scale: float = None, interpret: bool = None):
    """Attention of one query token a slot over that slot's KV blocks.

    ``q``: (S, H, D).  ``k_pool`` / ``v_pool``: (num_blocks, block_size,
    K, D), the arena's two pools of one layer as they are, H % K == 0.
    ``tables``: (S, max_blocks) int32, slot s's logical block i in
    physical block ``tables[s, i]``.  ``lengths``: (S,) int32 >= 1, the
    cached positions slot s attends (the query is the last of them).
    ``window``: a static sliding window, 0 for none: positions more than
    ``window - 1`` behind the query are left out, and their blocks are
    not read.  Returns (S, H, D) in ``q``'s dtype.

    Only table entries of blocks that hold a position in
    ``[max(0, len - window), len)`` are looked at; each must be a valid
    block id."""
    S, H, D = q.shape
    _, bs, K, _ = k_pool.shape
    if H % K:
        raise ValueError(f"{H} query heads do not group over {K} KV heads")
    if interpret is None:
        interpret = not on_tpu()
    scale = scale or (1.0 / math.sqrt(D))
    C = _chunk_blocks(bs, K, tables.shape[1])
    buffers = (2, C * bs * K, D)
    k_pool = k_pool.reshape(k_pool.shape[0], bs * K, D)
    v_pool = v_pool.reshape(v_pool.shape[0], bs * K, D)
    kernel = functools.partial(_kernel, scale=scale, window=int(window or 0),
                               C=C, bs=bs, K=K, G=H // K)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((None, H, D), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, H, D), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM(buffers, k_pool.dtype),
                pltpu.VMEM(buffers, v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q, k_pool, v_pool)
