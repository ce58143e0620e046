"""Latent attention — Pallas TPU kernels over a paged latent arena (one
decode token a slot) and over a slot's dense latent view (a prefill
chunk's rows).

Multi-head latent attention (MLA, DeepSeek-V2 arXiv:2405.04434) caches
per token one compressed row shared by every query head: ``c_kv``
(``kv_lora_rank`` values, normed) and the rotated ``k_rope``
(``qk_rope_head_dim`` values).  A decode step absorbs the key and value
up-projections into the query and the output, so each head's score is
``q_lat · c_kv + q_rope · k_rope`` and its output, still in the latent,
``Σ p c_kv``: every query head reads the same latent rows, as in
multi-query attention with one head of ``kv_lora_rank + rope`` wide keys
whose values are their first ``kv_lora_rank`` columns.

The arena keeps the two parts as two pools of one layer,
``(num_blocks, block_size, kv_lora_rank)`` and ``(num_blocks,
block_size, rope)``, the rope part zero-padded to whole 128-lane rows
(a 64-wide row takes 128 lanes in HBM all the same, and a DMA cannot
slice it narrower) (``serve.slots.BlockPool`` holds them where a
``(K, D)`` layer holds its keys and values).  One query token a slot
attends the blocks that slot holds, read through its block-table row up
to its own length, as ``ops.paged_attention`` does for keys and values:
the pools stay in HBM, the table and the lengths are scalar prefetched,
grid ``(S,)``, a step walks the slot's live blocks in chunks of ``C``,
one ``make_async_copy`` a block and a pool, two chunk buffers with the
next chunk (or the next slot's first) in flight.  A fetched chunk is
scored by all ``H`` heads at once, ``(H, r) @ (C·bs, r)^T + (H, rope) @
(C·bs, rope)^T``, and summed back with ``p @ c_kv``: the heads share
each fetched block.  Running max, sum and accumulator are f32 (online
softmax); probabilities are cast to the pool's dtype for ``p @ c_kv``.

A prefill chunk of ``T`` rows (``mla_prefill_attention``) attends the
slot's dense view, ``(B, max_len, r)`` and ``(B, max_len, rope)``, as
the engine gathers it, the chunk's own rows written in.  The heads fold
into the MXU's row axis: the queries are ``(T·H, r)`` and ``(T·H,
rope)``, row ``t·H + h``, against keys every head shares.  Grid ``(B,
query tiles, KV tiles)``: up to ``_Q_ROWS`` folded rows a query tile,
``_KV_ROWS`` view rows a KV tile, running max, sum and accumulator in
f32 across the KV tiles.  The limit (``pos + T``, one a batch row) is
scalar prefetched: folded row ``q`` sees view row ``s`` where ``s <=
limit - T + q // H`` (bottom-right-aligned causality); a KV tile past
the limit is skipped and its index clamped to the last live tile, so
no DMA fetches it, and only the tiles the diagonal crosses are masked.
A view whose length is not whole KV tiles is padded with zero rows to
the next tile's edge; they lie past every limit, so the mask drops them.
The f32 scores of a tile live in VMEM and are never written to HBM.
bf16 views take single-pass dots, f32 views ``Precision.HIGHEST``.
``ops.kv_cache.attends_view`` chooses it: a bf16 or f32 view of whole
128-lane latent rows (``prefill_tiles``), on a TPU.

Interpret mode runs the same kernels on the CPU for tests; it is on only
when the caller asks for it or the platform is not a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import on_tpu

__all__ = ["mla_decode_attention", "mla_prefill_attention", "prefill_tiles",
           "tiles"]

_NEG_INF = -1e30
#: latent rows (positions) of one chunk
_CHUNK_ROWS = 1024
#: the prefill kernel's tiles: folded query rows (rows x heads) at most,
#: and view rows; and the scoped VMEM it may take for them, their f32
#: scores and accumulator
_Q_ROWS = 1024
_KV_ROWS = 512
_PREFILL_VMEM = 64 << 20


def tiles(latent_shape, dtype) -> bool:
    """Whether the kernel takes a latent pool of this shape on a TPU: a
    latent of whole 128-lane rows in blocks of whole (16, 128) tiles
    (the rope part beside it is whole lanes by construction: a DMA
    cannot take a narrower slice of a block, so the model pads it)."""
    _, bs, r = latent_shape
    return r % 128 == 0 and bs % (32 // jnp.dtype(dtype).itemsize) == 0


def prefill_tiles(view_shape, dtype) -> bool:
    """Whether the prefill kernel takes a dense latent view of this
    shape, ``(B, max_len, r)``: bf16 or f32, a latent of whole 128-lane
    rows (any length: the kernel pads the last KV tile)."""
    return (len(view_shape) == 3
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
            and view_shape[2] % 128 == 0)


def _chunk_blocks(bs: int, max_blocks: int) -> int:
    return max(1, min(_CHUNK_ROWS // bs, max_blocks))


def _kernel(tables_ref, lengths_ref, ql_ref, qr_ref, c_hbm, r_hbm, o_ref,
            cbuf, rbuf, sems, buf_ref, *, scale, C, bs):
    s = pl.program_id(0)
    S = pl.num_programs(0)
    H = ql_ref.shape[0]
    R = C * bs

    def last_block(slot):
        return (lengths_ref[slot] - 1) // bs

    def start(slot, chunk, buf):
        last = last_block(slot)
        for c in range(C):
            blk = tables_ref[slot, jnp.minimum(chunk * C + c, last)]
            rows = pl.ds(c * bs, bs)
            pltpu.make_async_copy(c_hbm.at[blk], cbuf.at[buf, rows],
                                  sems.at[0, buf]).start()
            pltpu.make_async_copy(r_hbm.at[blk], rbuf.at[buf, rows],
                                  sems.at[1, buf]).start()

    def wait(buf):
        # a DMA semaphore counts bytes: one wait of a whole buffer's
        # size takes the chunk's C copies
        pltpu.make_async_copy(cbuf.at[1 - buf], cbuf.at[buf],
                              sems.at[0, buf]).wait()
        pltpu.make_async_copy(rbuf.at[1 - buf], rbuf.at[buf],
                              sems.at[1, buf]).wait()

    @pl.when(s == 0)
    def _():
        buf_ref[0] = 0
        start(0, 0, 0)

    n = lengths_ref[s]
    nchunks = last_block(s) // C + 1
    ql, qr = ql_ref[...], qr_ref[...]                     # (H, r), (H, dr)
    precision = (jax.lax.Precision.DEFAULT
                 if c_hbm.dtype == jnp.bfloat16 else None)
    rpos = jax.lax.broadcasted_iota(jnp.int32, (H, R), 1)
    nt = (((1,), (1,)), ((), ()))

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
        lat = cbuf[buf]                                   # (R, r)
        sc = (jax.lax.dot_general(ql, lat, nt, precision=precision,
                                  preferred_element_type=jnp.float32)
              + jax.lax.dot_general(qr, rbuf[buf], nt, precision=precision,
                                    preferred_element_type=jnp.float32)
              ) * scale                                   # (H, R)
        sc = jnp.where(j * R + rpos < n, sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(lat.dtype), lat,
                                    precision=precision,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc, nxt

    _, l, acc, buf = jax.lax.fori_loop(
        0, nchunks, body,
        (jnp.full((H, 1), _NEG_INF, jnp.float32),
         jnp.zeros((H, 1), jnp.float32),
         jnp.zeros(ql.shape, jnp.float32), buf_ref[0]))
    buf_ref[0] = buf
    o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_decode_attention(q_lat, q_rope, latent_pool, rope_pool, tables,
                         lengths, *, scale: float, interpret: bool = None):
    """Attention of one query token a slot over that slot's latent blocks.

    ``q_lat``: (S, H, r), the heads' queries absorbed into the latent;
    ``q_rope``: (S, H, dr), their rotated rope part.  ``latent_pool``:
    (num_blocks, block_size, r), ``rope_pool``: (num_blocks, block_size,
    dr), one layer's two pools as the arena holds them.  ``tables``:
    (S, max_blocks) int32; ``lengths``: (S,) int32 >= 1, the cached
    positions slot s attends (the query is the last of them).  Returns
    (S, H, r) in ``q_lat``'s dtype: ``softmax(scale (q_lat c_kv^T +
    q_rope k_rope^T)) c_kv`` per head, still in the latent.

    Only table entries of blocks below ``lengths`` are looked at; each
    must be a valid block id."""
    S, H, r = q_lat.shape
    _, bs, _ = latent_pool.shape
    if interpret is None:
        interpret = not on_tpu()
    C = _chunk_blocks(bs, tables.shape[1])
    kernel = functools.partial(_kernel, scale=scale, C=C, bs=bs)
    dr = q_rope.shape[-1]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((None, H, r), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec((None, H, dr), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, H, r), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, C * bs, r), latent_pool.dtype),
                pltpu.VMEM((2, C * bs, dr), rope_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct(q_lat.shape, q_lat.dtype),
        interpret=interpret,
        name="mla_decode_attention",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q_lat,
      q_rope.astype(q_lat.dtype), latent_pool, rope_pool)


def _prefill_kernel(lim_ref, ql_ref, qr_ref, c_ref, r_ref, o_ref, acc_ref,
                    m_ref, l_ref, *, scale, T, H, TQ, TK, precision):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    limit = lim_ref[b]
    # folded row q is the chunk's row t = q // H, at position start + t:
    # it sees view row s where (s - start) * H <= q
    start = limit - T
    q0, k0 = i * TQ, j * TK

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def attend(masked):
        lat = c_ref[...]                                  # (TK, r)
        nt = (((1,), (1,)), ((), ()))
        sc = (jax.lax.dot_general(ql_ref[...], lat, nt, precision=precision,
                                  preferred_element_type=jnp.float32)
              + jax.lax.dot_general(qr_ref[...], r_ref[...], nt,
                                    precision=precision,
                                    preferred_element_type=jnp.float32)
              ) * scale                                   # (TQ, TK)
        if masked:
            rows = q0 + jax.lax.broadcasted_iota(jnp.int32, (TQ, TK), 0)
            cols = k0 + jax.lax.broadcasted_iota(jnp.int32, (TQ, TK), 1)
            sc = jnp.where(((cols - start) * H <= rows) & (cols < limit),
                           sc, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(lat.dtype), lat, precision=precision,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # some row of the query tile sees some row of the KV tile, and every
    # row of the query tile sees every row of the KV tile
    live = (k0 < limit) & ((k0 - start) * H <= q0 + TQ - 1)
    whole = (k0 + TK - 1 - start) * H <= q0
    pl.when(live & whole)(lambda: attend(False))
    pl.when(live & jnp.logical_not(whole))(lambda: attend(True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_prefill_attention(q_lat, q_rope, view, rope_view, limit, *,
                          scale: float, interpret: bool = None):
    """Attention of a chunk's rows over a slot's dense latent view.

    ``q_lat``: (B, T, H, r), the heads' queries absorbed into the
    latent; ``q_rope``: (B, T, H, dr), their rotated rope part (zero
    lanes are added up to the rope view's).  ``view``: (B, S, r),
    ``rope_view``: (B, S, rope), the slot's cached rows with the
    chunk's own written in, as the engine gathers them.  ``limit``: a
    scalar or (B,) int32, ``pos + T``: row t of batch row b attends
    view rows ``s <= limit[b] - T + t``.  Returns (B, T, H, r) in the
    view's dtype: ``softmax(scale (q_lat c_kv^T + q_rope k_rope^T))
    c_kv`` per head, still in the latent.  ``prefill_tiles`` must hold
    for the view; the queries are cast to its dtype."""
    B, T, H, r = q_lat.shape
    S, dr = view.shape[1], rope_view.shape[-1]
    dt = view.dtype
    TK = _KV_ROWS
    if S % TK:
        # zero rows up to the last tile's edge, past every limit
        pad = ((0, 0), (0, -S % TK), (0, 0))
        view, rope_view = jnp.pad(view, pad), jnp.pad(rope_view, pad)
        S = view.shape[1]
    if interpret is None:
        interpret = not on_tpu()
    n = T * H
    TQ = min(_Q_ROWS, -(-n // 16) * 16)
    N = -(-n // TQ) * TQ

    def fold(q, lanes):
        q = q.reshape(B, n, q.shape[-1]).astype(dt)
        return jnp.pad(q, ((0, 0), (0, N - n), (0, lanes - q.shape[-1])))

    lim = jnp.broadcast_to(jnp.asarray(limit, jnp.int32), (B,))

    def kv(b, i, j, lim_ref):
        return b, jnp.minimum(j, (lim_ref[b] - 1) // TK), 0

    precision = (jax.lax.Precision.DEFAULT if dt == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    kernel = functools.partial(_prefill_kernel, scale=scale, T=T, H=H, TQ=TQ,
                               TK=TK, precision=precision)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, N // TQ, S // TK),
            in_specs=[
                pl.BlockSpec((None, TQ, r), lambda b, i, j, _: (b, i, 0)),
                pl.BlockSpec((None, TQ, dr), lambda b, i, j, _: (b, i, 0)),
                pl.BlockSpec((None, TK, r), kv),
                pl.BlockSpec((None, TK, dr), kv),
            ],
            out_specs=pl.BlockSpec((None, TQ, r),
                                   lambda b, i, j, _: (b, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((TQ, r), jnp.float32),
                pltpu.VMEM((TQ, 1), jnp.float32),
                pltpu.VMEM((TQ, 1), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM),
        out_shape=jax.ShapeDtypeStruct((B, N, r), dt),
        interpret=interpret,
        name="mla_prefill_attention",
    )(lim, fold(q_lat, r), fold(q_rope, dr), view, rope_view)
    return out[:, :n].reshape(B, T, H, r)
