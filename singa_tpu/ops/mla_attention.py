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
one ``make_async_copy`` a block and a pool (none past the walk's end),
two chunk buffers with the next chunk (or the next slot's first) in
flight.  A fetched chunk is scored by all ``H`` heads at once, ``(H, r)
@ (C·bs, r)^T + (H, rope) @ (C·bs, rope)^T``, and summed back with ``p
@ c_kv``: the heads share each fetched block.  Running max, sum and
accumulator are f32 (online softmax); probabilities are cast to the
pool's dtype for ``p @ c_kv``.

Slots whose rows hold the same leading blocks (a document behind a
prefix hit) read them once for their group (Hydragen, arXiv:2402.05099):
:func:`group_plan` says which, and a first pass walks each group's
shared run once with its members' queries stacked into ``(G·H, r)``
rows, as many members as fill the MXU's 128 rows, each member's rows
masked past its own run.  It leaves each member's partial as (normalised
sum, log-sum-exp) in f32; the per-slot walk above then starts where the
slot's run ends and resumes its online softmax from that partial, which
merges the two by their log-sum-exp.  A run shorter than one chunk reads
alone (:func:`group_limits`).

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import on_tpu

__all__ = ["mla_decode_attention", "mla_prefill_attention", "prefill_tiles",
           "tiles", "group_limits", "group_plan", "blocks_fetched"]

_NEG_INF = -1e30
#: latent rows (positions) of one chunk
_CHUNK_ROWS = 1024
#: rows of one pass through a v5e's 128 x 128 MXU: a decode group
#: stacks its members' heads into at most this many query rows
_MXU_ROWS = 128
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


def group_limits(num_heads: int, block_size: int, max_blocks: int):
    """``(max_group, min_blocks)`` of :func:`group_plan` for a latent
    layer of ``num_heads`` query heads over tables of ``max_blocks``
    blocks of ``block_size`` rows: as many members as stack their heads'
    queries into one pass of the MXU's rows, and a shared run of at
    least one of the kernel's chunks, below which the group's extra pass
    costs more than the reads it saves."""
    return (max(1, _MXU_ROWS // num_heads),
            _chunk_blocks(block_size, max_blocks))


def group_plan(tables, full, *, max_group: int, min_blocks: int, xp=np):
    """Which slots read which of their leading blocks once for a group,
    as one ``(2, S)`` int32 array: row 0 each slot's group leader, row 1
    how many leading blocks the slot reads through the group's shared
    pass (0: it reads all its blocks alone, and leads itself).

    ``tables``: (S, max_blocks) block-table rows; ``full``: (S,) the
    whole blocks below each slot's write position, 0 for a slot that
    does not run.  Slots whose rows hold the same first ``min_blocks``
    blocks, each whole below both positions, form a class; a class
    splits in slot order into groups of at most ``max_group``, whose
    lowest slot leads, and a lone remainder reads alone.  A member reads
    through its group the run of leading blocks its row shares with the
    leader's, whole below both positions; the leader the longest of its
    members' runs.  Nothing writes a block below a write position again
    (a decode writes at its position, a prefill only past a prefix
    hit), so those blocks are the same rows for every member, tick
    after tick.  ``xp``: numpy on the host, ``jax.numpy`` in a program."""
    S, mb = tables.shape
    slots = xp.arange(S)
    ok = full >= min_blocks
    head = tables[:, :min_blocks]
    same = (xp.all(head[:, None, :] == head[None, :, :], axis=-1)
            & ok[:, None] & ok[None, :])
    rank = xp.sum(same & (slots[None, :] < slots[:, None]), axis=1)
    first = rank // max_group * max_group
    leader = xp.argmax(same & (rank[None, :] == first[:, None]), axis=1)
    grouped = ok & (xp.minimum(xp.sum(same, axis=1) - first, max_group) >= 2)
    agree = ((tables == tables[leader])
             & (xp.arange(mb)[None, :]
                < xp.minimum(full, full[leader])[:, None]))
    run = xp.sum(xp.cumprod(agree.astype(xp.int32), axis=1), axis=1)
    leads = grouped & (leader == slots)
    count = xp.where(grouped & ~leads, run, 0)
    longest = xp.max(xp.where(leader[None, :] == slots[:, None],
                              count[None, :], 0), axis=1)
    count = xp.where(leads, longest, count)
    return xp.stack([xp.where(grouped, leader, slots),
                     count]).astype(xp.int32)


def blocks_fetched(plan, live) -> int:
    """Blocks the decode kernel fetches under ``plan`` for slots that
    attend ``live`` blocks each (to the one holding the position they
    write; 0 for a slot that does not run): each group's shared run
    once, then each slot's own blocks."""
    leader, count = plan
    leads = leader == np.arange(leader.size)
    return int(live.sum() - count.sum() + count[leads].sum())


def _fetch(tables_ref, row, first, n, c_hbm, r_hbm, cbuf, rbuf, sems, buf,
           *, bs):
    """Start the copies of blocks ``first .. first + n - 1`` (``n <=
    C``) of table row ``row`` into chunk buffer ``buf``: a block past
    the walk's end is not fetched, and its rows of the buffer keep what
    they held (finite: zeros at first, else rows of earlier chunks)."""
    def body(c, carry):
        blk = tables_ref[row, first + c]
        rows = pl.ds(pl.multiple_of(c * bs, bs), bs)
        pltpu.make_async_copy(c_hbm.at[blk], cbuf.at[buf, rows],
                              sems.at[0, buf]).start()
        pltpu.make_async_copy(r_hbm.at[blk], rbuf.at[buf, rows],
                              sems.at[1, buf]).start()
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _land(n, cbuf, rbuf, sems, buf, *, C, bs):
    """Wait for the ``n`` copies :func:`_fetch` started into ``buf``.
    A DMA semaphore counts bytes: one wait of the whole buffer's size
    takes a full chunk, one of a block's size each block of a part."""
    @pl.when(n == C)
    def _():
        pltpu.make_async_copy(cbuf.at[1 - buf], cbuf.at[buf],
                              sems.at[0, buf]).wait()
        pltpu.make_async_copy(rbuf.at[1 - buf], rbuf.at[buf],
                              sems.at[1, buf]).wait()

    @pl.when(n < C)
    def _():
        one = pl.ds(0, bs)

        def body(c, carry):
            pltpu.make_async_copy(cbuf.at[1 - buf, one], cbuf.at[buf, one],
                                  sems.at[0, buf]).wait()
            pltpu.make_async_copy(rbuf.at[1 - buf, one], rbuf.at[buf, one],
                                  sems.at[1, buf]).wait()
            return carry

        jax.lax.fori_loop(0, n, body, 0)


def _attend(q, qr, lat, rop, valid, m, l, acc, *, scale, precision):
    """One chunk of online softmax: rows ``q`` / ``qr`` against the
    chunk's latent and rope rows where ``valid``."""
    nt = (((1,), (1,)), ((), ()))
    sc = (jax.lax.dot_general(q, lat, nt, precision=precision,
                              preferred_element_type=jnp.float32)
          + jax.lax.dot_general(qr, rop, nt, precision=precision,
                                preferred_element_type=jnp.float32)
          ) * scale
    sc = jnp.where(valid, sc, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
    p = jnp.exp(sc - m_new)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jnp.dot(p.astype(lat.dtype), lat,
                                precision=precision,
                                preferred_element_type=jnp.float32)
    return m_new, l, acc


def _precision(dtype):
    return jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


def _shared_kernel(tables_ref, slot_ref, blocks_ref, counts_ref, n_ref,
                   q_ref, qr_ref, c_hbm, r_hbm, o_ref, lse_ref, cbuf, rbuf,
                   sems, buf_ref, *, scale, C, bs, G, H):
    g = pl.program_id(0)
    n = n_ref[0]
    R = C * bs
    io = dict(C=C, bs=bs)

    def span(grp, chunk):
        return chunk * C, jnp.clip(blocks_ref[grp] - chunk * C, 0, C)

    def start(grp, chunk, buf):
        first, k = span(grp, chunk)
        _fetch(tables_ref, slot_ref[grp], first, k, c_hbm, r_hbm, cbuf, rbuf,
               sems, buf, bs=bs)

    @pl.when(jnp.logical_and(g == 0, n > 0))
    def _():
        cbuf[...] = jnp.zeros(cbuf.shape, cbuf.dtype)
        rbuf[...] = jnp.zeros(rbuf.shape, rbuf.dtype)
        buf_ref[0] = 0
        start(0, 0, 0)

    @pl.when(g < n)
    def _():
        nchunks = (blocks_ref[g] + C - 1) // C
        # member k's rows k*H .. (k+1)*H - 1 see its own shared run
        row = jax.lax.broadcasted_iota(jnp.int32, (G * H, 1), 0)
        lim = jnp.zeros((G * H, 1), jnp.int32)
        for k in range(G):
            lim = jnp.where((row >= k * H) & (row < (k + 1) * H),
                            counts_ref[g * G + k] * bs, lim)
        col = jax.lax.broadcasted_iota(jnp.int32, (G * H, R), 1)
        q, qr = q_ref[...], qr_ref[...]                  # (G·H, r), (G·H, dr)
        precision = _precision(c_hbm.dtype)

        def body(j, carry):
            m, l, acc, buf = carry
            nxt = 1 - buf
            pl.when(j + 1 < nchunks)(lambda: start(g, j + 1, nxt))
            pl.when(jnp.logical_and(j + 1 == nchunks, g + 1 < n))(
                lambda: start(g + 1, 0, nxt))
            _land(span(g, j)[1], cbuf, rbuf, sems, buf, **io)
            m, l, acc = _attend(q, qr, cbuf[buf], rbuf[buf], j * R + col < lim,
                                m, l, acc, scale=scale, precision=precision)
            return m, l, acc, nxt

        m, l, acc, buf = jax.lax.fori_loop(
            0, nchunks, body,
            (jnp.full((G * H, 1), _NEG_INF, jnp.float32),
             jnp.zeros((G * H, 1), jnp.float32),
             jnp.zeros(q.shape, jnp.float32), buf_ref[0]))
        buf_ref[0] = buf
        o_ref[...] = acc / l
        lse_ref[...] = m + jnp.log(l)


def _own_kernel(tables_ref, lengths_ref, start_ref, ql_ref, qr_ref, *refs,
                scale, C, bs, partials):
    if partials:
        po_ref, plse_ref, *refs = refs
    c_hbm, r_hbm, o_ref, cbuf, rbuf, sems, buf_ref = refs
    s = pl.program_id(0)
    S = pl.num_programs(0)
    H, r = ql_ref.shape
    R = C * bs
    io = dict(C=C, bs=bs)

    def span(slot, chunk):
        first = start_ref[slot] + chunk * C
        return first, jnp.clip((lengths_ref[slot] - 1) // bs - first + 1,
                               0, C)

    def start(slot, chunk, buf):
        first, k = span(slot, chunk)
        _fetch(tables_ref, slot, first, k, c_hbm, r_hbm, cbuf, rbuf, sems,
               buf, bs=bs)

    @pl.when(s == 0)
    def _():
        cbuf[...] = jnp.zeros(cbuf.shape, cbuf.dtype)
        rbuf[...] = jnp.zeros(rbuf.shape, rbuf.dtype)
        buf_ref[0] = 0
        start(0, 0, 0)

    n = lengths_ref[s]
    b0 = start_ref[s]
    # at least one chunk, fetched or not, so that the walk to the next
    # slot's first chunk goes on
    nchunks = jnp.maximum((n - 1) // bs - b0 + C, C) // C
    ql, qr = ql_ref[...], qr_ref[...]                     # (H, r), (H, dr)
    precision = _precision(c_hbm.dtype)
    rpos = b0 * bs + jax.lax.broadcasted_iota(jnp.int32, (H, R), 1)

    def body(j, carry):
        m, l, acc, buf = carry
        nxt = 1 - buf
        pl.when(j + 1 < nchunks)(lambda: start(s, j + 1, nxt))
        pl.when(jnp.logical_and(j + 1 == nchunks, s + 1 < S))(
            lambda: start(s + 1, 0, nxt))
        _land(span(s, j)[1], cbuf, rbuf, sems, buf, **io)
        m, l, acc = _attend(ql, qr, cbuf[buf], rbuf[buf], j * R + rpos < n,
                            m, l, acc, scale=scale, precision=precision)
        return m, l, acc, nxt

    m0 = jnp.full((H, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, r), jnp.float32)
    if partials:
        # the group's partial over the shared run, as (normalised sum,
        # log-sum-exp): the online softmax resumes from it
        shared = jnp.full((H, 1), b0, jnp.int32) > 0
        m0 = jnp.where(shared, plse_ref[...], m0)
        l0 = jnp.where(shared, 1.0, l0)
        acc0 = jnp.where(jnp.full((H, r), b0, jnp.int32) > 0, po_ref[...],
                         acc0)
    _, l, acc, buf = jax.lax.fori_loop(0, nchunks, body,
                                       (m0, l0, acc0, buf_ref[0]))
    buf_ref[0] = buf
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def _groups(plan, S: int, G: int):
    """The kernel's view of ``plan``, groups (at most ``S // 2``) in the
    order of their leaders: their members and the members' runs, ``(S //
    2, G)`` in slot order; their leaders; their number; and per slot its
    group (``S // 2`` for none) and place in it."""
    NG = S // 2
    leader, start = plan[0], plan[1]
    slots = jnp.arange(S, dtype=jnp.int32)
    grouped = start > 0
    leads = grouped & (leader == slots)
    gid = jnp.where(grouped, (jnp.cumsum(leads) - 1)[leader], NG)
    rank = jnp.sum(grouped[None, :] & (leader[None, :] == leader[:, None])
                   & (slots[None, :] < slots[:, None]), axis=1)
    members = jnp.zeros((NG, G), jnp.int32).at[gid, rank].set(
        slots, mode="drop")
    counts = jnp.zeros((NG, G), jnp.int32).at[gid, rank].set(
        start, mode="drop")
    g_slot = jnp.zeros((NG,), jnp.int32).at[
        jnp.where(leads, gid, NG)].set(slots, mode="drop")
    n = jnp.sum(leads, dtype=jnp.int32).reshape(1)
    return members, counts, g_slot, n, gid, rank


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_decode_attention(q_lat, q_rope, latent_pool, rope_pool, tables,
                         lengths, plan=None, *, scale: float,
                         interpret: bool = None):
    """Attention of one query token a slot over that slot's latent blocks.

    ``q_lat``: (S, H, r), the heads' queries absorbed into the latent;
    ``q_rope``: (S, H, dr), their rotated rope part.  ``latent_pool``:
    (num_blocks, block_size, r), ``rope_pool``: (num_blocks, block_size,
    dr), one layer's two pools as the arena holds them.  ``tables``:
    (S, max_blocks) int32; ``lengths``: (S,) int32 >= 1, the cached
    positions slot s attends (the query is the last of them).  ``plan``:
    :func:`group_plan`'s (2, S) for these tables under
    :func:`group_limits`, made where it is made once for many ticks;
    None: made here from the tables and lengths.  Returns (S, H, r) in
    ``q_lat``'s dtype: ``softmax(scale (q_lat c_kv^T + q_rope k_rope^T))
    c_kv`` per head, still in the latent.

    Two Pallas calls: ``mla_decode_attention_shared`` walks each
    group's shared run once, its members' queries stacked into ``(G·H,
    r)`` rows, each member's rows masked past its own run, and leaves
    each member's f32 partial as (normalised sum, log-sum-exp);
    ``mla_decode_attention`` walks each slot's own blocks from where its
    run ends (a slot alone from block 0) with its online softmax resumed
    from that partial.

    Only table entries of blocks below ``lengths`` are looked at; each
    must be a valid block id, and a slot's shared run lies below its
    length."""
    S, H, r = q_lat.shape
    _, bs, _ = latent_pool.shape
    dr = q_rope.shape[-1]
    if interpret is None:
        interpret = not on_tpu()
    tables = tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    q_rope = q_rope.astype(q_lat.dtype)
    C = _chunk_blocks(bs, tables.shape[1])
    G, min_blocks = group_limits(H, bs, tables.shape[1])
    NG = S // 2

    def call(kernel, grid, prefetch, in_specs, out_specs, out_shape, name):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch), grid=grid,
                in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
                out_specs=out_specs,
                scratch_shapes=[
                    pltpu.VMEM((2, C * bs, r), latent_pool.dtype),
                    pltpu.VMEM((2, C * bs, dr), rope_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.SMEM((1,), jnp.int32),
                ]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            out_shape=out_shape, interpret=interpret, name=name)

    partials = []
    start = jnp.zeros((S,), jnp.int32)
    if G > 1 and NG:
        if plan is None:
            plan = group_plan(tables, (lengths - 1) // bs, max_group=G,
                              min_blocks=min_blocks, xp=jnp)
        start = plan[1]
        members, counts, g_slot, n, gid, rank = _groups(plan, S, G)

        def block(g, *refs):
            # a program past the last group keeps the last group's
            # blocks: nothing is fetched or written back for it
            return jnp.minimum(g, jnp.maximum(refs[4][0] - 1, 0)), 0, 0

        o_sh, lse_sh = call(
            functools.partial(_shared_kernel, scale=scale, C=C, bs=bs, G=G,
                              H=H),
            (NG,), (tables, g_slot, counts.max(axis=1), counts.reshape(-1),
                    n),
            [pl.BlockSpec((None, G * H, r), block),
             pl.BlockSpec((None, G * H, dr), block)],
            [pl.BlockSpec((None, G * H, r), block),
             pl.BlockSpec((None, G * H, 1), block)],
            [jax.ShapeDtypeStruct((NG, G * H, r), jnp.float32),
             jax.ShapeDtypeStruct((NG, G * H, 1), jnp.float32)],
            "mla_decode_attention_shared",
        )(tables, g_slot, counts.max(axis=1), counts.reshape(-1), n,
          q_lat[members].reshape(NG, G * H, r),
          q_rope[members].reshape(NG, G * H, dr), latent_pool, rope_pool)
        # each slot's rows of its group's partial (a slot alone reads
        # rows it ignores)
        at = (jnp.minimum(gid, NG - 1)[:, None],
              (rank * H)[:, None] + jnp.arange(H)[None, :])
        partials = [o_sh[at], lse_sh[at]]

    slot = lambda s, *_: (s, 0, 0)
    return call(
        functools.partial(_own_kernel, scale=scale, C=C, bs=bs,
                          partials=bool(partials)),
        (S,), (tables, lengths, start),
        [pl.BlockSpec((None, H, r), slot), pl.BlockSpec((None, H, dr), slot)]
        + [pl.BlockSpec((None, H, r), slot),
           pl.BlockSpec((None, H, 1), slot)][:len(partials)],
        pl.BlockSpec((None, H, r), slot),
        jax.ShapeDtypeStruct(q_lat.shape, q_lat.dtype),
        "mla_decode_attention",
    )(tables, lengths, start, q_lat, q_rope, *partials, latent_pool,
      rope_pool)


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
