"""Speculative decoding over the paged KV arena (ISSUE 13).

Decode is MEMORY-bound: every
decode dispatch streams the whole weight + KV working set through HBM
to emit one token per slot.  Speculative decoding raises
tokens-per-dispatch instead of trying to make the dispatch cheaper: a
small DRAFT model proposes ``k`` tokens per slot, and the target model
scores all ``k + 1`` window positions in ONE compute-denser **verify**
dispatch — the third gated program, next to prefill and decode.

How one verify round works (all of it inside the single compiled
``verify`` program; ``k`` is a trace-time constant):

1. **propose** — the draft runs ``k + 1`` single-token steps over its
   own dense cache view (gathered through the SAME block tables as the
   target's: the draft arena is a parallel per-layer block pool in
   :class:`~singa_tpu.serve.slots.BlockPool`), greedily picking
   ``d1..dk`` from the pending token ``t0``.  The extra (k+1)-th step
   exists only to write ``dk``'s draft KV, so a fully-accepted round
   leaves no gap in the draft cache.
2. **verify** — the target scores the window ``[t0, d1..dk]`` at
   per-slot positions in one ``(num_slots, k+1)`` forward
   (``cached_sdpa``'s per-row ``limit`` and the per-row RoPE offset
   vector already support multi-token windows), writing the window's
   KV for BOTH arenas via the fixed-shape multi-token scatter
   (``ops.kv_cache.scatter_tokens_kv``).
3. **accept + commit/rollback** — the accepted run is the longest
   prefix of proposals matching the target's own greedy picks; the
   delivered tokens are literally the TARGET's argmaxes
   (``cand[:, :a+1]``), which is why speculative greedy streams are
   bitwise identical to ``generate()`` *by construction* — the draft
   can only change HOW MANY target picks one dispatch yields, never
   their values.  Rejected positions are rolled back by TRUNCATING the
   slot's position/attention limit (the host advances its ``pos`` by
   ``a + 1``, not ``k + 1``): the
   stale KV past the new limit is unreachable (masked by every
   reader's validity window) and is overwritten by the next round —
   no arena reshape, no scrubbing, no per-``k`` program.

Fault containment (site ``serve.verify``, registered in
``faults/sites.py``): an injected/transient verify failure past the
retry budget falls back to a PLAIN decode tick for that round instead
of wedging the slot or rebuilding the arena — the accepted stream is
unaffected (plain decode is the same target argmax), at the cost of a
gap in the draft cache at the fallback position, which can only lower
the accept rate of later rounds, never change accepted tokens.

Draft quality is strictly a PERFORMANCE knob: a perfect draft
(self-speculation, ``draft_model is model``) accepts everything and
delivers ``k + 1`` tokens per dispatch; an adversarial draft accepts
nothing and the engine still makes one target-correct token of
progress per round (tests/test_spec.py proves both ends bitwise equal
to ``generate()``).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..models._generate import decode_step, resume_step
from ..obs import events
from ..obs import trace as obs_trace
from ..ops import kv_cache as kv_ops

__all__ = ["make_spec_prefill", "make_verify", "verify_round",
           "VerifyDispatchFailed", "resume_on_row", "scatter_chunk",
           "chunk_blocks", "gather_view"]


class VerifyDispatchFailed(RuntimeError):
    """The verify DISPATCH died past its retry budget (injection site
    ``serve.verify`` or a real pre-launch transient).  The only
    exception :meth:`ServeEngine._spec_tick` converts into a
    plain-decode fallback: at that point nothing was committed, so a
    plain tick on the untouched arena is safe.  Any failure AFTER the
    dispatch (result fetch, delivery) propagates unchanged instead —
    the round is half-committed and only the step-level arena recovery
    may touch it (falling back there would decode the new pending
    token at a stale position and silently diverge the stream)."""


def gather_view(ck, cv, table):
    """``ops.kv_cache.gather_block_kv`` for one layer of an arena; a
    layer that keeps no keys (its pools are None: serve/slots.py) has no
    view either."""
    if ck is None:
        return None, None
    return kv_ops.gather_block_kv(ck, cv, table)


def resume_on_row(resume, params, buffers, ids, pos, row, caches,
                  entry=None, state_rows=None):
    """Gather ``row``'s dense per-layer view and run ``resume`` (a
    ``models._generate.resume_step`` closure) on it at traced offset
    ``pos`` — the shared first half of every prefill-chunk program
    (plain AND speculative), so the two engines' prefill semantics can
    never drift apart.

    ``entry``, for a model with side state beside its KV cache: per
    layer the tuple of state arrays (1, ...) as they stood before row
    ``pos``; each layer's cache is then ``(k, v, *state)`` and comes
    back with the state after each of ``state_rows``."""
    dense = [gather_view(ck, cv, row) for ck, cv in caches]
    if entry is None:
        return resume(params, buffers, ids, pos, dense)
    dense = [kv + tuple(st) for kv, st in zip(dense, entry)]
    return resume(params, buffers, ids, pos, dense, state_rows)


def chunk_blocks(row, pos, fresh, block_size, chunk):
    """Physical ids of the ``chunk // block_size`` blocks a prefill
    chunk at ``pos`` writes through table row ``row``: the null block 0
    for those below ``fresh``, which the chunk only recomputed."""
    idx = pos // block_size + jnp.arange(chunk // block_size)
    return jnp.where(idx * block_size >= fresh,
                     jnp.take(row[0], idx, mode="clip"), 0)


def scatter_chunk(row, pos, fresh, caches, dense, block_size, chunk):
    """Scatter the ``chunk // block_size`` physical blocks a prefill
    chunk covers back into the paged arena — the shared second half of
    every prefill-chunk program (see :func:`resume_on_row`).

    The chunk's view rows ``[pos, pos + chunk)`` go out through the
    slot's table row, except the blocks below ``fresh`` (a traced
    block-aligned position), which go to the null block 0: they hold
    tokens the chunk only RECOMPUTED — a shared, refcounted prefix
    block, or one an earlier chunk of this prompt already wrote — and
    what is resident is never rewritten.  Rows past the prompt's end
    are written too, whatever ``last_idx`` says: into the slot's own
    later blocks, where decode overwrites each position before any mask
    admits it, or, past the mapped part of the row, into the null block
    (``BlockPool`` keeps the rest of a row zero).  So only scatter
    through a row that ``map_slot`` has just installed.  The
    caller keeps ``[pos, pos + chunk)`` inside the view:
    ``dynamic_slice`` would clamp a crossing chunk silently and send
    K/V to the wrong positions."""
    bs, n = block_size, chunk // block_size
    wb = chunk_blocks(row, pos, fresh, bs, chunk)
    new = []
    for (ck, cv), (dk, dv) in zip(caches, dense):
        if ck is None:                  # a layer without keys
            new.append((ck, cv))
            continue
        kb = jax.lax.dynamic_slice_in_dim(dk[0], pos, chunk, axis=0)
        vb = jax.lax.dynamic_slice_in_dim(dv[0], pos, chunk, axis=0)
        # one in-place block write each, not one scatter at the vector
        # of ids: for an arena of 4 KV heads the TPU compiler gives that
        # scatter a layout of its own and copies the whole arena there
        # and back, 2.4 ms of an 8.8 ms chunk (PERF.md, PR 29)
        for i in range(n):
            ck, cv = kv_ops.scatter_block_kv(
                ck, cv, wb[i], kb[i * bs:(i + 1) * bs],
                vb[i * bs:(i + 1) * bs])
        new.append((ck, cv))
    return new


def make_spec_prefill(model, draft, block_size: int, chunk: int):
    """The spec engine's prefill-chunk closure: identical to the plain
    engine's (gather the slot's dense view, run the cached forward at
    the traced offset, pick the chunk's last token in-program, scatter
    the chunk's blocks back) — plus the same chunk through the DRAFT
    model into the draft arena, so a prefilled slot always has both
    caches warm.  The draft's chunk logits are unused (the TARGET picks
    the first token) and XLA dead-code-eliminates its lm_head."""
    bs = block_size
    resume = resume_step(model)
    dresume = resume_step(draft)

    def prefill_chunk_spec(params, buffers, dparams, dbuffers, ids, pos,
                           last_idx, slot, fresh, tables, toks, caches,
                           dcaches):
        row = jax.lax.dynamic_index_in_dim(tables, slot, axis=0,
                                           keepdims=True)       # (1, MB)
        logits, dense = resume_on_row(resume, params, buffers, ids,
                                      pos, row, caches)
        last = jax.lax.dynamic_slice_in_dim(
            logits, last_idx, 1, axis=1)[:, 0, :]
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)[0]
        toks = toks.at[slot].set(tok)
        new = scatter_chunk(row, pos, fresh, caches, dense, bs, chunk)
        _, ddense = resume_on_row(dresume, dparams, dbuffers, ids, pos,
                                  row, dcaches)
        dnew = scatter_chunk(row, pos, fresh, dcaches, ddense, bs, chunk)
        return toks, new, dnew

    return prefill_chunk_spec


def make_verify(model, draft, spec_k: int, block_size: int):
    """Build the verify program's closure (see the module docstring for
    the three phases).  Returns
    ``(accepted, cand, new_toks, caches, dcaches)`` where
    ``accepted`` is the per-slot count of accepted PROPOSALS (0..k) and
    ``cand`` is the (num_slots, k+1) matrix of the target's greedy
    picks — the host delivers ``cand[slot, :accepted+1]`` and advances
    the slot's ``pos`` by ``accepted + 1``.  Inactive
    slots are masked exactly like plain decode: positions clamped to 0,
    every window write redirected to the null block, token entries
    frozen."""
    k, bs = spec_k, block_size
    dec_d = decode_step(draft)
    res_t = resume_step(model)

    def verify(params, buffers, dparams, dbuffers, toks, pos, active,
               tables, caches, dcaches):
        posc = jnp.where(active, pos, 0)

        # -- 1. draft propose: k+1 single-token greedy steps ------------
        ddense = [kv_ops.gather_block_kv(ck, cv, tables)
                  for ck, cv in dcaches]
        cur, dp = toks, posc
        props = []
        for j in range(k + 1):
            dlogits, ddense = dec_d(dparams, dbuffers, cur[:, None], dp,
                                    ddense)
            cur = jnp.argmax(dlogits.astype(jnp.float32),
                             axis=-1).astype(jnp.int32)
            if j < k:
                props.append(cur)
            dp = dp + 1
        props = jnp.stack(props, axis=1)                       # (S, k)

        # window scatter targets, shared by both arenas: position
        # pos+j lands at [table[slot, (pos+j)//bs], (pos+j)%bs]
        wpos = posc[:, None] + jnp.arange(k + 1)[None, :]      # (S, k+1)
        wblk = jnp.take_along_axis(tables, wpos // bs, axis=1)
        wblk = jnp.where(active[:, None], wblk, 0)
        woff = jnp.where(active[:, None], wpos % bs, 0)

        def window(c, p):
            return jax.lax.dynamic_slice_in_dim(c, p, k + 1, axis=0)

        def scatter_window(cs, dense):
            new = []
            for (ck, cv), (dk, dv) in zip(cs, dense):
                kw = jax.vmap(window)(dk, posc)        # (S, k+1, K, D)
                vw = jax.vmap(window)(dv, posc)
                new.append(kv_ops.scatter_tokens_kv(ck, cv, wblk, woff,
                                                    kw, vw))
            return new

        new_d = scatter_window(dcaches, ddense)

        # -- 2. target verify: one (S, k+1) forward ---------------------
        win_ids = jnp.concatenate([toks[:, None], props], axis=1)
        dense = [kv_ops.gather_block_kv(ck, cv, tables)
                 for ck, cv in caches]
        logits, dense = res_t(params, buffers, win_ids, posc, dense)
        cand = jnp.argmax(logits.astype(jnp.float32),
                          axis=-1).astype(jnp.int32)           # (S, k+1)
        new_t = scatter_window(caches, dense)

        # -- 3. accept the longest matching greedy prefix ---------------
        match = (props == cand[:, :k]).astype(jnp.int32)
        acc = jnp.cumprod(match, axis=1).sum(axis=1)           # (S,) 0..k
        new_tok = jnp.take_along_axis(cand, acc[:, None], axis=1)[:, 0]
        new_toks = jnp.where(active, new_tok, toks)
        acc = jnp.where(active, acc, 0)
        return acc, cand, new_toks, new_t, new_d

    return verify


def verify_round(engine) -> int:
    """One speculative tick over the whole arena: dispatch the verify
    program, then commit each slot's accepted run host-side — deliver
    ``accepted + 1`` tokens (the target's own picks) in stream order,
    stopping early at EOS/budget like any other delivery path.  Same
    subsystem-package access pattern as ``disagg/handoff.py``: this is
    the implementation behind ``ServeEngine._spec_tick``."""
    from ..utils import failure
    k = engine.spec_k
    t0 = time.perf_counter()
    with events.span("serve.verify", active=len(engine._running), k=k):
        try:
            out = engine._dispatch(
                "serve.verify", engine._verify,
                (engine._params, engine._buffers, engine._dparams,
                 engine._dbuffers, engine._toks, *engine.pool.snapshot(),
                 engine.pool.caches, engine.pool.draft_caches),
                active=len(engine._running))
        except (RuntimeError, OSError) as e:
            if isinstance(e, failure.FailureDetected):
                raise
            # ONLY the un-committed dispatch failure is fallback-safe;
            # everything past this point is half-committed state whose
            # failures must escalate (see VerifyDispatchFailed)
            raise VerifyDispatchFailed(
                f"{type(e).__name__}: {e}") from e
        (acc_v, cand_v, engine._toks, engine.pool.caches,
         engine.pool.draft_caches) = out
        # a verify round does not run ahead: its fetch leaves the
        # device with nothing to do (metrics.HostAccount)
        engine.metrics.host.call_in("verify.fetch")
        try:
            acc = np.asarray(acc_v)    # singalint: disable=SGL008 the designed per-tick sync: one (S,) + one (S, k+1) int fetch commits a whole verify round
            cand = np.asarray(cand_v)
        finally:
            engine.metrics.host.call_out("verify.fetch", "other")
    # rollback IS this truncation: the window's k+1 positions are all
    # written, the slot's limit moves past the accepted ones only, and
    # the rest sit beyond it, unreachable and overwritten next round
    engine.pool.advance(acc + 1)
    dt = time.perf_counter() - t0
    delivered = 0
    for slot in list(engine._running):
        req = engine._running[slot]
        a = int(acc[slot])
        run = [int(t) for t in cand[slot, :a + 1]]
        done = False
        n = 0
        with obs_trace.activate(req.trace_id):
            engine.metrics.on_spec_round(k, a)
            for tok in run:
                done = req.deliver(tok)
                n += 1
                engine.metrics.on_deliver(req.rid, len(req.tokens))
                if done:
                    # budget/EOS mid-run: the leftover accepted tokens
                    # are DISCARDED (generate() would never have
                    # produced them either) and the slot is released
                    break
            # per-token cost = this dispatch's latency amortized over
            # the tokens it yielded for this slot (a plain decode tick
            # is the n == 1 case of the same definition)
            for _ in range(n):
                engine.metrics.on_token(dt / n)
            engine.metrics.on_slot_dispatch(n)
        if req.on_token is not None:
            for tok in run[:n]:
                req.on_token(tok, req.handle)
        delivered += n
        if done:
            engine._finalize(slot)
    return delivered
