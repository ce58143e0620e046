"""KV block handoff between serving engines (ISSUE 12).

The paged arena makes a finished prefill cheap to move by
construction: it is just KV blocks plus a block-table row.  A handoff
therefore never reshapes a tensor —

* the SOURCE (a prefill worker) gathers the slot's dense per-layer
  view through its block-table row with ``ops.kv_cache.
  gather_block_kv`` — the engine's optional THIRD compiled program
  (``handoff_gather``, fixed shapes, lazily compiled on the first
  handoff, audited by the hloaudit/hlocost gates next to prefill and
  decode), then releases the slot;
* the DESTINATION (a decode worker) maps the same logical block
  sequence onto its own physical blocks: blocks whose prefix chain
  keys are already resident are matched COPY-FREE (``match_prefix`` —
  refcounts and prefix-cache keys transfer with the blocks, so a
  tenant's shared system prompt crosses the wire once per decode
  worker, not once per request), the rest are written with
  ``scatter_block_kv``, one fixed-shape block write per remaining
  logical block.

The request object itself (prompt, tokens-so-far, deadline, handle,
trace id) is pure host state and travels inside the
:class:`HandoffPackage`.  After injection the destination's decode
program continues the stream mid-flight: its per-slot position is the
replay length minus one and its last-token entry is the prefill's
first token, exactly the state a local prefill would have left —
which is why disaggregated greedy streams are bitwise identical to a
single engine's (asserted in tests/test_faults.py).

Correctness of copy-free matching rests on the same invariant the
prefix cache already stands on: a chain key commits to every token of
the whole prefix, and a full prompt block's KV content is a
deterministic function of those tokens under the shared weights, so a
key match means bitwise-equal block content no matter which worker
prefilled it.

These functions are the implementation behind
``ServeEngine.extract_handoff`` / ``inject_handoff`` /
``can_accept_handoff``; they reach into engine/pool internals by
design (same subsystem package).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from ...ops import kv_cache as kv_ops
from ..scheduler import RUNNING, Request


__all__ = ["HandoffPackage", "extract", "inject", "can_accept"]


@dataclass
class HandoffPackage:
    """One prefilled request in flight between workers: the host-side
    request state plus its gathered KV and the prefix keys that let the
    destination map shared blocks copy-free."""

    req: Request
    #: per layer (dense_k, dense_v) device views, shape
    #: (1, max_blocks * block_size, K, D) — the handoff_gather output
    kv: list
    #: valid cache positions (== replay length - 1 == the per-slot
    #: ``pos`` the destination activates with)
    pos: int
    #: logical blocks the destination must map (shared + copied)
    n_blocks: int
    #: chain keys of the request's FULL prompt blocks ([] when the
    #: source pool has prefix sharing disabled) — what transfers the
    #: prefix-cache identity along with the block contents
    prompt_keys: List[bytes] = field(default_factory=list)
    #: source worker name (events/debugging only)
    src: str = ""
    #: speculative tier (ISSUE 13): the DRAFT model's dense per-layer
    #: views for the same blocks (None when the source engine carries
    #: no draft) — both arenas ride the same block tables, so the
    #: handoff moves both or the destination's verify rounds would
    #: start from a cold draft cache and accept nothing
    draft_kv: Optional[list] = None


def extract(engine, slot: int) -> HandoffPackage:
    """Pull the request in ``slot`` out of ``engine`` (see module
    docstring).  The gather runs BEFORE any bookkeeping mutation and
    the gather program does not donate, so a failure at any point
    leaves the source arena AND the engine's request map consistent —
    the request is still withdrawable for a re-route."""
    req = engine._running[slot]
    pool = engine.pool
    n_blocks = pool.mapped_count(slot)
    # device pos == replay length - 1 by construction (prefill
    # activates at the replay length then delivers one token; every
    # decode tick advances both) — no device fetch needed
    pos = req.replay_ids().size - 1
    if pool.draft_caches is not None:
        # speculative engine: ONE gather call over the combined
        # per-layer list (target caches + draft caches — a pytree, so
        # the handoff program still has exactly one jit-cache entry),
        # split back host-side
        both = engine._handoff(pool.tables_snapshot(), np.int32(slot),
                               pool.caches + pool.draft_caches)
        dense, draft_kv = both[:len(pool.caches)], both[len(pool.caches):]
    else:
        dense = engine._handoff(pool.tables_snapshot(), np.int32(slot),
                                pool.caches)
        draft_kv = None
    keys = engine._req_keys(req)[:req.prompt.size // pool.block_size]
    # point of no return: only after the gather succeeded
    engine._running.pop(slot)
    pool.release(slot)
    req.slot = None
    engine.flight.note("counter", "serve.handoff_out", rid=req.rid,
                       blocks=n_blocks)
    return HandoffPackage(req=req, kv=dense, pos=pos, n_blocks=n_blocks,
                          prompt_keys=keys, draft_kv=draft_kv)


def _probe(engine, pkg: HandoffPackage):
    """(n_shared, n_lru) of the destination's resident-prefix coverage
    for this package (side-effect free)."""
    if not engine.share_prefix or not pkg.prompt_keys:
        return 0, 0
    return engine.pool.probe_prefix(
        pkg.req.prompt, len(pkg.prompt_keys), keys=pkg.prompt_keys)


def can_accept(engine, pkg: HandoffPackage) -> bool:
    """Free slot + coverable blocks on ``engine`` for ``pkg``, counting
    resident shared-prefix blocks (claiming LRU-parked ones consumes
    availability, same accounting as admission)."""
    if engine.pool.free_count < 1:
        return False
    n_shared, n_lru = _probe(engine, pkg)
    return (engine.pool.available_blocks - n_lru
            >= pkg.n_blocks - n_shared)


def inject(engine, pkg: HandoffPackage) -> bool:
    """Install ``pkg`` into ``engine`` mid-stream (see module
    docstring).  Returns False when capacity is lacking — the caller
    parks the package; the destination is untouched."""
    if not can_accept(engine, pkg):
        return False
    req = pkg.req
    assert req.tokens, "handoff of a request with no prefill token"
    pool = engine.pool
    bs = pool.block_size
    n_shared = 0
    shared_ids: List[int] = []
    if engine.share_prefix and pkg.prompt_keys:
        n_shared, shared_ids = pool.match_prefix(
            req.prompt, len(pkg.prompt_keys), keys=pkg.prompt_keys)
    slot = pool.alloc_slot()
    owned = pool.alloc_blocks(pkg.n_blocks - n_shared) or []
    assert slot is not None and len(owned) == pkg.n_blocks - n_shared, \
        "capacity vanished between can_accept and inject"
    pool.map_slot(slot, shared_ids + owned)
    try:
        # copy only the unshared logical blocks out of the dense view —
        # one fixed-shape block scatter per (block, layer).  These are
        # EAGER ops: each write materializes a fresh arena buffer (no
        # donation outside jit) — the sanctioned cost of "no new jit
        # programs beyond the handoff gather" (ISSUE 12); on-chip, a
        # donating multi-block scatter program is the known upgrade
        # (ROADMAP item 3 note) if handoff copies ever show up in a
        # profile.
        caches = list(pool.caches)
        dcaches = (list(pool.draft_caches)
                   if pool.draft_caches is not None
                   and pkg.draft_kv is not None else None)
        for i, wb in enumerate(owned):
            lo = (n_shared + i) * bs
            for li, (dk, dv) in enumerate(pkg.kv):
                ck, cv = caches[li]
                caches[li] = kv_ops.scatter_block_kv(
                    ck, cv, jnp.asarray(wb, jnp.int32),
                    dk[0, lo:lo + bs], dv[0, lo:lo + bs])
            if dcaches is not None:
                # the draft arena maps the SAME physical block ids —
                # one more fixed-shape write per (block, draft layer)
                for li, (dk, dv) in enumerate(pkg.draft_kv):
                    ck, cv = dcaches[li]
                    dcaches[li] = kv_ops.scatter_block_kv(
                        ck, cv, jnp.asarray(wb, jnp.int32),
                        dk[0, lo:lo + bs], dv[0, lo:lo + bs])
        pool.caches = caches
        if dcaches is not None:
            pool.draft_caches = dcaches
        if engine.share_prefix and pkg.prompt_keys:
            pool.register_prefix(req.prompt, slot, len(pkg.prompt_keys),
                                 keys=pkg.prompt_keys)
        pool.activate(slot, pkg.pos)
        # decode reads the slot's LAST token as its next input
        engine._toks = engine._toks.at[slot].set(int(req.tokens[-1]))
    except BaseException:
        # unwind the claim so a mid-scatter failure cannot leak the
        # destination slot/blocks: release() drops the mapping (shared
        # keyed blocks park back in the LRU, owned unkeyed ones are
        # freed; partially-written content is unreachable garbage, the
        # same contract as any stale block).  The caller re-routes.
        pool.release(slot)
        raise
    req.slot = slot
    req.state = RUNNING
    engine._running[slot] = req
    engine.flight.note("counter", "serve.handoff_in", rid=req.rid,
                       blocks=pkg.n_blocks, shared=n_shared)
    return True
