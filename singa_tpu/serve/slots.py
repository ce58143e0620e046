"""Paged KV-cache arena for continuous-batching inference.

PR 2's ``SlotPool`` gave every request a fixed ``max_len`` cache row, so
a 10-token request paid the same HBM as a 500-token one and a shared
system prompt was re-prefilled from scratch for every tenant.  The
arena is now PAGED (the vLLM design, expressed as fixed-shape XLA
gathers): the per-layer cache is a pool of ``num_blocks`` fixed-size
blocks of ``block_size`` tokens — ``(num_blocks, block_size, K, D)``
buffers — and each request maps the blocks its length actually needs
through a ``(num_slots, max_blocks)`` int32 **block table**.  The
engine's two compiled programs never see physical block identities as
shapes: prefill/decode gather a request's dense view with
``ops.kv_cache.gather_block_kv`` (a ``jnp.take`` over the table row)
and scatter written positions back with ``scatter_block_kv`` /
``scatter_token_kv``, so admitting, growing, evicting and re-mapping
requests are pure index updates — the same single-compiled-module
discipline the fixed arena had, with memory proportional to live
tokens instead of live slots.

**Who owns the slot state.**  Only the block pools live on the device.
The block tables and the per-slot ``pos`` / ``active`` vectors are HOST
``numpy`` arrays that this class edits in place: every value in them is something the host decided —
a row is ``_mapped[slot]`` zero-padded, ``pos[slot]`` the slot's valid
cache positions, ``active[slot]`` whether a request runs there — so an
update is a store, never a device program.  The compiled programs take
them as arguments of a fixed shape and dtype, so a step's device work
is its dispatches and one token fetch.  A dispatch is asynchronous and
may read a host argument after the call returns (the CPU backend
aliases an aligned ``numpy`` buffer instead of copying it), so a
program is handed :meth:`snapshot` / :meth:`tables_snapshot` — copies
nobody edits — and never these arrays themselves.

**Prefix-cache sharing** rides the block pool: every FULL prompt block
gets a chain hash key (blake2b over the block's tokens and its
ancestor's key, so a key identifies the whole prefix up to and
including the block).  A new request whose leading prompt blocks are
already resident maps them copy-free (refcount bump, no prefill) and
prefills only the unshared suffix.  Refcounts govern the lifecycle:

* a mapped block has ``ref >= 1`` (one per slot mapping it);
* when the last mapping is released, a KEYED block parks in an LRU
  pool of evictable blocks (content intact — the next request with the
  same prefix reuses it) while an unkeyed block returns to the free
  list immediately;
* allocation takes from the free list first, then evicts the LRU
  evictable block — eviction *asserts* ``ref == 0``, so evicting a
  block while any request references it is impossible by construction.

Physical block 0 is the reserved **null block**: never allocated, it
is the redirect target for unmapped table entries and masked decode
writes.  Its contents are garbage by design — every reader masks cache
positions past its own validity window (``cached_sdpa`` per-row
``limit``), so the null block (like any stale table entry) is
unreachable.

**Side state beside the KV blocks.**  A model whose layers read more
than their KV cache (``models/zaya.py``: a CCA layer's output at
position t needs the latents of the two positions before it and the
previous token's part of the value, which no cache entry holds and
nothing can recompute) says so through ``init_caches``: its per-layer
cache is ``(k, v, *state)``, each state array with the batch axis
leading.  The pool then keeps that state twice, both on the device:
:attr:`slot_state`, per slot, what the running request's next token
reads (``decode_paged`` carries it, ``prefill_chunk`` leaves it as it
stood after the prompt's last valid row); and :attr:`tails`, per
physical block, the state as it stood after the block's last token,
written by the prefill chunk that filled the block.  Every prefill
chunk starts at a block boundary and takes its entry state from the
tail of the block before it in the slot's row (zeros at position 0),
so a prefix hit, a prompt's second chunk and the re-prefill after
``resubmit`` or preemption all resume exactly; shared tails are never
rewritten, as shared blocks are not (:func:`serve.spec.chunk_blocks`).
Decode never writes a tail: the only blocks a chunk can start after
are full blocks of a prompt, and a prefill filled those.  Spill, the
int8 arena and a draft model's arena know nothing of this state and
are refused for such a model.

**A layer has the cache it needs.**  ``init_caches`` may yield ``(None,
None, *state)`` for a layer that keeps state and no keys
(``models/granite_hybrid.py``: a state-space layer) beside ``(k, v)``
layers: the pools then hold ``(None, None)`` there, no block of such a
layer exists, and blocks, tables and the paged kernel serve the layers
that have keys.  Each leaf keeps the dtype the model gives it under
the served weights (an f32 recurrent state beside a bf16 arena).

**State too heavy for a tail a block.**  Where one block's tail would
outweigh the block's keys and values (a state-space layer's state is
heads x head_dim x d_state values whatever the sequence's length: at
the published sizes 38 MB a slot against 4 KB of keys and values a
token), a tail a block is out of the question.  The pool then keeps
the state per slot, as above, and in :attr:`snapshots`: a few entries
on the device, each the state as it stood after one full block's last
row, found through a host map from that block's chain key, least
recently used out first, an entry never evicted while the admission
that reads it runs.  An admission whose prompt matches resident keyed
blocks up to block ``n`` maps them as ever; its prefill starts after
the deepest block ``m <= n`` on that chain that has a snapshot (at
position 0 from zeros), recomputes the rows of blocks ``m .. n``
without rewriting them, and leaves the state after block ``n`` as a
new snapshot: where two prompts part is where the next one will want
to resume, so a tenant's system prompt is a full hit from its third
request on, and nothing has to guess which blocks are worth 38 MB.  A
prompt's later chunks enter from the slot's own state.

**Memory hierarchy** (ISSUE 17, :mod:`singa_tpu.serve.mem`):
``kv_dtype="int8"`` stores either arena as int8 codes + per-position
f32 scales (:class:`~singa_tpu.ops.kv_cache.QuantKV` — the gather/
scatter primitives quantize/dequantize in-program, so the compiled
program set is unchanged), and a :class:`~singa_tpu.serve.mem.
SpillStore` (``spill=``) turns LRU eviction of a keyed prefix block
into a spill to host RAM: :meth:`_evict_lru` copies the block's exact
device bytes out before reclaiming it, and :meth:`match_prefix`
restores spilled blocks into free physical blocks on the next prefix
hit (both seams fire the ``serve.spill`` injection site; an injected
fault degrades to the pre-spill behavior — the block dies or the
prefix re-prefills — never to a changed stream).
"""

from __future__ import annotations

import hashlib
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import mla_attention
from . import mem

__all__ = ["BlockPool", "has_side_state"]

#: chain-hash seed of the empty prefix
_ROOT = b"singa-kv-prefix-root"


def _chain_keys(tokens: np.ndarray, n_blocks: int, block_size: int
                ) -> List[bytes]:
    """Keys of the first ``n_blocks`` FULL blocks of ``tokens``; key i
    commits to every token in blocks 0..i, so equal keys mean equal
    whole prefixes (not just equal block contents)."""
    keys, prev = [], _ROOT
    for i in range(n_blocks):
        h = hashlib.blake2b(prev, digest_size=16)
        h.update(tokens[i * block_size:(i + 1) * block_size]
                 .astype("<i4").tobytes())
        prev = h.digest()
        keys.append(prev)
    return keys


def _leaf_dtypes(spec, dtype):
    """The dtype of every leaf of ``spec`` (``init_caches``' result,
    abstract) under the ``dtype=`` a pool was given: None, each leaf's
    own; one dtype, that for every leaf; a pytree of dtypes shaped like
    ``spec`` (what the model yields under the served weights, where
    its leaves differ: an f32 state beside bf16 keys), itself."""
    if isinstance(dtype, (list, tuple)):
        return dtype
    return jax.tree.map(lambda a: a.dtype if dtype is None else dtype, spec)


def _zeros(spec, types):
    return jax.tree.map(lambda a, t: jnp.zeros(a.shape, t), spec, types)


def _nbytes(tree, types=None) -> int:
    """Bytes of the arrays (or shapes) of ``tree``, in ``types`` if
    given."""
    leaves = jax.tree.leaves(tree)
    kinds = [a.dtype for a in leaves] if types is None \
        else jax.tree.leaves(types)
    return sum(a.size * jnp.dtype(t).itemsize for a, t in zip(leaves, kinds))


def has_side_state(model) -> bool:
    """Whether ``model``'s layers keep state beside their KV cache: its
    ``init_caches`` then yields ``(k, v, *state)`` per layer."""
    spec = jax.eval_shape(lambda: model.init_caches(1, 1))
    return any(len(layer) > 2 for layer in spec)


class BlockPool:
    """Paged arena of ``num_blocks`` KV blocks behind ``num_slots``
    block-table rows.

    Host side: slot free list, block free list, per-block refcounts,
    the prefix cache (chain key -> block), the evictable LRU, and the
    slot state the programs take as arguments — the ``(num_slots,
    max_blocks)`` block tables and the per-slot ``pos``/``active``
    vectors (``numpy``; see the module docstring).  Device side: the
    per-layer block pools.
    """

    def __init__(self, model, num_slots: int, max_len: int, *,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 dtype=None, draft_model=None, kv_dtype=None,
                 draft_kv_dtype=None,
                 spill: Optional[mem.SpillStore] = None):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_slots = num_slots
        self.max_len = max_len
        self.block_size = block_size
        self.max_blocks = -(-max_len // block_size)
        if num_blocks is None:
            # capacity parity with the old fixed arena (+ null block):
            # every slot can hold a full-length request at once
            num_blocks = num_slots * self.max_blocks + 1
        if num_blocks < self.max_blocks + 1:
            raise ValueError(
                f"num_blocks ({num_blocks}) must cover the largest "
                f"request plus the null block (>= {self.max_blocks + 1} "
                f"for max_len {max_len} at block_size {block_size})")
        self.num_blocks = num_blocks
        # the memory-hierarchy knobs (serve/mem.py): per-arena storage
        # format (None = full precision, "int8" = QuantKV codes +
        # scales) — the draft arena inherits the target's format unless
        # overridden, so a quantized engine quantizes both by default
        # while the referee configuration (int8 proposer, f32 target)
        # stays expressible via draft_kv_dtype="int8" alone
        self.kv_dtype = mem.normalize_kv_dtype(kv_dtype)
        self.draft_kv_dtype = (self.kv_dtype if draft_kv_dtype is None
                               else mem.normalize_kv_dtype(draft_kv_dtype))
        # what the model's layers cache, abstractly (eval_shape: nothing
        # is allocated, so construction never holds two copies)
        spec = jax.eval_shape(
            lambda: model.init_caches(num_blocks, block_size))
        side = any(len(layer) > 2 for layer in spec)
        for what, on in (("a draft model's arena (speculative decoding)",
                          draft_model is not None),
                         ("the int8 arena", self.kv_dtype == "int8"),
                         ("the spill tier", spill is not None)):
            if side and on:
                raise NotImplementedError(
                    f"{type(model).__name__} keeps state beside its KV "
                    f"blocks, which {what} does not carry: a request "
                    f"resumed from such a block would read the wrong "
                    f"state")
        # a latent pool (no head axis: models/glm_mla.py) has no
        # (K, D) slab for the int8 arena's per-position scale
        if self.kv_dtype == "int8" and any(
                c[0] is not None and len(c[0].shape) == 3 for c in spec):
            raise NotImplementedError(
                f"{type(model).__name__} caches latent rows, which the "
                f"int8 arena does not quantize: its scale is one a (K, D) "
                f"slab of a position")
        # the dtype each leaf is served in
        types = _leaf_dtypes(spec, dtype)
        if self.kv_dtype == "int8":
            # int8 arena: codes + scales replace the float pool (the
            # dtype= serving-precision override is moot — scales are
            # f32 by contract, codes are int8)
            self.caches = mem.quant_arena(model, num_blocks, block_size)
        elif dtype is None and not side:
            self.caches = model.init_caches(num_blocks, block_size)
        else:
            # straight in the serving dtype (e.g. bf16 under a
            # param_dtype cast); the pools keep (k, v) alone, as every
            # program and the memory tiers know them
            self.caches = _zeros([c[:2] for c in spec],
                                 [t[:2] for t in types])
        # side state beside the KV blocks (module docstring): what a
        # layer's cache holds beyond (k, v) is kept per slot and either
        # per block (its leading axis is num_blocks in `spec`) or, where
        # that would outweigh the blocks themselves, in a few snapshots
        self.tails = self.slot_state = self.snapshots = None
        if side:
            state, stypes = [c[2:] for c in spec], [t[2:] for t in types]
            rows = lambda n: jax.tree.map(
                lambda a: jax.ShapeDtypeStruct((n,) + a.shape[1:], a.dtype),
                state)
            self.slot_state = _zeros(rows(num_slots), stypes)
            if _nbytes(state, stypes) > _nbytes(self.caches):
                # a quarter as many entries as slots: the pool holds a
                # slot's state for every slot already, and what is worth
                # keeping beside them is what several slots share
                self.snapshots = _zeros(rows(max(2, num_slots // 4)), stypes)
            else:
                self.tails = _zeros(state, stypes)
        #: bytes one cached position takes across the layers' pools
        #: where they are latent pools (no head axis: models/glm_mla.py),
        #: else 0
        self.latent_row_bytes = sum(
            mem.arena_block_bytes([c]) // block_size for c in self.caches
            if c[0] is not None and len(c[0].shape) == 3)
        #: the latent decode kernel's group limits where the pools hold
        #: latent rows (:meth:`group_plan`), else None
        self._group_limits = mla_attention.group_limits(
            model.latent_heads, block_size, self.max_blocks) \
            if self.latent_row_bytes else None
        self._plan = None
        #: bytes of side state ONE slot holds across the layers
        self.slot_state_bytes = _nbytes(self.slot_state) // num_slots \
            if side else 0
        # snapshots, host side: chain key -> entry, least recently used
        # first; free entries; entries the running admission reads or
        # writes
        self._snap_of: "OrderedDict[bytes, int]" = OrderedDict()
        self._snap_free: List[int] = list(range(self.snapshot_entries))
        self._snap_busy: set = set()
        # speculative decoding (serve/spec.py): the DRAFT model's KV
        # blocks ride the SAME block tables — draft caches are a second
        # per-layer pool with identical (num_blocks, block_size) leading
        # dims (draft layer/head/dim shapes differ freely), so every
        # host-side mapping decision (admit, grow, evict, prefix share,
        # preempt, handoff) covers both arenas with one index update.
        # A shared full prompt block therefore shares its draft KV too:
        # the spec prefill writes both, and block content is a
        # deterministic function of the chain-keyed prefix either way.
        self.draft_model = draft_model
        if draft_model is None:
            self.draft_caches = None
        elif self.draft_kv_dtype == "int8":
            self.draft_caches = mem.quant_arena(draft_model, num_blocks,
                                                block_size)
        elif dtype is None:
            self.draft_caches = draft_model.init_caches(num_blocks,
                                                        block_size)
        else:
            # the serving-dtype override applies to BOTH arenas: decode
            # and verify are weight/KV-read bound, and a full-precision
            # draft arena would double the draft's KV traffic (and,
            # under self-speculation, let draft and target argmaxes
            # diverge by reading different-precision KV)
            dspec = jax.eval_shape(
                lambda: draft_model.init_caches(num_blocks, block_size))
            self.draft_caches = jax.tree.map(
                lambda s: jnp.zeros(s.shape, jax.tree.leaves(types)[0]),
                dspec)
        # an unmapped slot's row is all null block, its pos 0
        self.tables = np.zeros((num_slots, self.max_blocks), np.int32)
        self.pos = np.zeros((num_slots,), np.int32)
        self.active = np.zeros((num_slots,), bool)
        # LIFO reuse: the most recently freed slot/block is re-used
        # first (hottest in the HBM/cache hierarchy)
        self._free_slots: List[int] = list(range(num_slots - 1, -1, -1))
        self._free_blocks: List[int] = list(range(num_blocks - 1, 0, -1))
        self._mapped: List[List[int]] = [[] for _ in range(num_slots)]
        self.ref = np.zeros((num_blocks,), np.int64)
        self._key_of: Dict[int, bytes] = {}     # block -> chain key
        self._block_of: Dict[bytes, int] = {}   # chain key -> block
        # refcount-0 keyed blocks, oldest first (eviction order)
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        # spill tier (serve/mem.py): evicted keyed blocks land here
        # instead of dying; the engine wires the three callbacks after
        # construction (metrics for spill/prefetch accounting, incident
        # plumbing for injected serve.spill faults)
        self.spill = spill
        self.on_spill = None        # callable(n_blocks)
        self.on_prefetch = None     # callable(n_blocks, wait_ms)
        self.on_spill_fault = None  # callable(op, exc)
        #: bytes ONE physical block occupies across every arena leaf
        #: (target + draft, codes + scales) — the honest per-block HBM
        #: footprint behind blocks_in_use_bytes
        self.block_bytes = mem.arena_block_bytes(self.caches,
                                                 self.draft_caches) \
            + (mem.arena_block_bytes(self.tails) if self.tails else 0)

    # -- slot bookkeeping -------------------------------------------------
    @property
    def free_count(self) -> int:
        return len(self._free_slots)

    @property
    def active_count(self) -> int:
        return self.num_slots - len(self._free_slots)

    def alloc_slot(self) -> Optional[int]:
        """Claim a free block-table row, or None when every row is live
        (the scheduler's signal to keep the request queued)."""
        return self._free_slots.pop() if self._free_slots else None

    # -- block accounting -------------------------------------------------
    @property
    def available_blocks(self) -> int:
        """Blocks an allocation could obtain right now: the free list
        plus the evictable (refcount-0) prefix blocks."""
        return len(self._free_blocks) + len(self._lru)

    @property
    def blocks_in_use(self) -> int:
        """Blocks currently referenced by at least one mapped slot."""
        return int((self.ref > 0).sum())

    @property
    def blocks_in_use_bytes(self) -> int:
        """HBM bytes those blocks pin across BOTH arenas (target +
        draft, int8 codes AND f32 scale tensors) — blocks alone
        under-report a quantized or speculative arena's footprint."""
        return self.blocks_in_use * self.block_bytes

    @property
    def snapshot_entries(self) -> int:
        """Entries of the snapshot pool (0 where the model's state is
        kept a block, or there is none)."""
        return jax.tree.leaves(self.snapshots)[0].shape[0] \
            if self.snapshots is not None else 0

    @property
    def tail_blocks(self) -> int:
        """Keyed blocks, each holding a tail a request can resume from
        (0 for a model without side state)."""
        return len(self._key_of) if self.tails is not None else 0

    def mapped_count(self, slot: int) -> int:
        return len(self._mapped[slot])

    def mapped_blocks(self, slot: int) -> List[int]:
        """The slot's physical block ids in logical order (a copy) —
        what a disaggregated KV handoff transfers: the source engine
        gathers these blocks' contents and the destination pool maps
        the same logical sequence onto its own physical blocks."""
        return list(self._mapped[slot])

    def _evict_lru(self) -> int:
        block, _ = self._lru.popitem(last=False)
        # the invariant the prefix cache stands on: only a block no
        # request references may ever be reclaimed
        assert self.ref[block] == 0, \
            f"evicting block {block} with refcount {self.ref[block]}"
        key = self._key_of.pop(block, None)
        if key is not None and self._block_of.get(key) == block:
            del self._block_of[key]
            if self.spill is not None:
                self._spill_block(key, block)
        return block

    # -- spill tier (serve/mem.py) ----------------------------------------
    def _spill_block(self, key: bytes, block: int) -> None:
        """Spill-write seam: copy the evicted keyed block's exact
        device bytes into the host store BEFORE the arena reclaims the
        physical block.  An injected ``serve.spill`` fault here skips
        the spill — the block dies exactly as it did before the spill
        tier existed (a prefix-cache miss later, never a changed
        stream)."""
        from .. import faults
        try:
            faults.fire("serve.spill", op="spill", block=block)
        except (RuntimeError, OSError) as e:
            if self.on_spill_fault is not None:
                self.on_spill_fault("spill", e)
            return
        self.spill.put(key, mem.read_block(self.caches,
                                           self.draft_caches, block))
        if self.on_spill is not None:
            self.on_spill(1)

    def _stage_restore(self, key: bytes) -> Optional[Tuple[int, dict]]:
        """Prefetch-read seam: claim an available physical block — a
        free one, else by evicting the coldest refcount-0 LRU block
        (which itself spills: a SWAP of a cold prefix for the hot one
        being requested, never touching a referenced block) — and pop
        the spilled payload for it.  Returns ``(block, payload)``, or
        None on a store miss / no claimable block / injected fault
        (all of which degrade to a plain prefix miss: the suffix
        prefills normally).  Consuming free-or-LRU is exactly the
        budget :meth:`probe_prefix`'s conservative feasibility math
        (spilled = miss) already charged for this block's fresh
        allocation, so admission accounting is unchanged.  The device
        write is deferred to :meth:`_commit_restores` so an admission
        restoring several blocks pays ONE batched write."""
        if self.spill is None or key not in self.spill \
                or not (self._free_blocks or self._lru):
            return None
        from .. import faults
        try:
            faults.fire("serve.spill", op="prefetch")
        except (RuntimeError, OSError) as e:
            if self.on_spill_fault is not None:
                self.on_spill_fault("prefetch", e)
            return None
        payload = self.spill.get(key)
        if (payload["draft"] is None) != (self.draft_caches is None):
            return None  # arena shape changed under the store
        self.spill.pop(key)
        block = (self._free_blocks.pop() if self._free_blocks
                 else self._evict_lru())
        return block, payload

    def _commit_restores(self, restores: List[Tuple[bytes, int, dict]]
                         ) -> None:
        """Land an admission's staged restores: one fancy-indexed
        device write per arena leaf (see :func:`mem.write_blocks`),
        then key the blocks resident.  The writes ride JAX's async
        dispatch — the host enqueues the copies and returns;
        ``wait_ms`` measures the host-side restore orchestration the
        admission actually waited."""
        t0 = time.perf_counter()
        self.caches, self.draft_caches = mem.write_blocks(
            self.caches, self.draft_caches,
            [b for _, b, _ in restores], [p for _, _, p in restores])
        for key, block, _ in restores:
            self._key_of[block] = key
            self._block_of[key] = block
        if self.on_prefetch is not None:
            self.on_prefetch(len(restores),
                             (time.perf_counter() - t0) * 1e3)

    def alloc_blocks(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` physical blocks (all-or-nothing), evicting LRU
        prefix blocks as needed.  None when fewer than ``n`` are
        obtainable — the caller's cue to defer admission or preempt."""
        if self.available_blocks < n:
            return None
        out = []
        for _ in range(n):
            out.append(self._free_blocks.pop() if self._free_blocks
                       else self._evict_lru())
        return out

    def free_blocks(self, blocks: List[int]) -> None:
        """Return unmapped, unkeyed blocks straight to the free list
        (the cleanup path of an admission that failed between
        allocation and mapping)."""
        for b in blocks:
            assert self.ref[b] == 0 and b not in self._key_of
            self._free_blocks.append(b)

    def unref_shared(self, blocks: List[int]) -> None:
        """Drop the references :meth:`match_prefix` took, without a
        slot mapping to release through (the cleanup path of an
        admission that failed before :meth:`map_slot`)."""
        for b in blocks:
            assert self.ref[b] > 0
            self.ref[b] -= 1
            if self.ref[b] == 0:
                self._lru[b] = None
                self._lru.move_to_end(b)

    def release_slot_row(self, slot: int) -> None:
        """Hand back an UNMAPPED slot row (failed admission) — the
        block-side cleanup happened through :meth:`unref_shared` /
        :meth:`free_blocks`."""
        assert not self._mapped[slot]
        if slot in self._free_slots:
            raise ValueError(f"slot {slot} double-freed")
        self._free_slots.append(slot)

    # -- prefix cache -----------------------------------------------------
    def prefix_keys(self, prompt: np.ndarray, n_blocks: int
                    ) -> List[bytes]:
        """Chain keys of ``prompt``'s first ``n_blocks`` full blocks —
        exposed so the engine can memoize them per request (they depend
        only on the immutable prompt) and pass them back via ``keys=``
        instead of re-hashing on every admission probe."""
        return _chain_keys(prompt, n_blocks, self.block_size)

    def probe_prefix(self, prompt: np.ndarray, limit_blocks: int,
                     keys: Optional[List[bytes]] = None
                     ) -> Tuple[int, int]:
        """How many leading full blocks of ``prompt`` are resident, and
        how many of those currently sit in the evictable LRU
        (side-effect free — the admission-feasibility check).  The LRU
        count matters because claiming those shared blocks REMOVES them
        from :attr:`available_blocks`: an admission is feasible only
        when ``available_blocks - n_in_lru`` covers the fresh blocks it
        must still allocate."""
        if keys is None:
            keys = _chain_keys(prompt, limit_blocks, self.block_size)
        n = n_lru = 0
        for key in keys[:limit_blocks]:
            block = self._block_of.get(key)
            if block is None:
                break
            n += 1
            if self.ref[block] == 0:
                n_lru += 1
        return n, n_lru

    def match_prefix(self, prompt: np.ndarray, limit_blocks: int,
                     keys: Optional[List[bytes]] = None
                     ) -> Tuple[int, List[int]]:
        """Claim the longest resident chain of leading full prompt
        blocks: each matched block's refcount is bumped (reactivating
        it out of the evictable LRU).  A key that misses residency but
        hits the spill tier is PREFETCHED into a free physical block
        and the chain continues — the restored block consumes exactly
        the one free block the conservative :meth:`probe_prefix`
        feasibility math already budgeted for its fresh allocation, so
        admission accounting is unchanged.  Returns (n_shared, block
        ids)."""
        if keys is None:
            keys = _chain_keys(prompt, limit_blocks, self.block_size)
        ids: List[int] = []
        restores: List[Tuple[bytes, int, dict]] = []
        for key in keys[:limit_blocks]:
            block = self._block_of.get(key)
            if block is None:
                staged = self._stage_restore(key)
                if staged is None:
                    break
                block, payload = staged
                restores.append((key, block, payload))
            if self.ref[block] == 0:
                self._lru.pop(block, None)
            self.ref[block] += 1
            ids.append(block)
        if restores:
            self._commit_restores(restores)
        return len(ids), ids

    def register_prefix(self, prompt: np.ndarray, slot: int,
                        n_blocks: int,
                        keys: Optional[List[bytes]] = None) -> None:
        """Key the first ``n_blocks`` (full, just-prefilled prompt)
        blocks of ``slot`` so later requests with the same prefix can
        map them.  A key already mapping another resident block is
        re-pointed here (the old holder keeps serving its refs but
        loses shareability — content is identical either way)."""
        if keys is None:
            keys = _chain_keys(prompt, n_blocks, self.block_size)
        row = self._mapped[slot]
        for i, key in enumerate(keys[:n_blocks]):
            block = row[i]
            if self._key_of.get(block) == key:
                continue                     # matched share, already keyed
            old = self._block_of.get(key)
            if old is not None and old != block:
                del self._key_of[old]
                if old in self._lru:         # keyless + unreferenced:
                    self._lru.pop(old)       # nothing can find it again
                    self._free_blocks.append(old)
            self._block_of[key] = block
            self._key_of[block] = key

    # -- state snapshots (module docstring) --------------------------------
    def match_snapshot(self, keys: List[bytes], n_blocks: int
                       ) -> Tuple[int, Optional[int]]:
        """The deepest of the chain's first ``n_blocks`` blocks that has
        a snapshot: ``(m, entry)``, the state after block ``m``'s last
        row standing in ``entry``; ``(0, None)`` when none has.  The
        entry is in use until :meth:`settle_snapshots`."""
        for m in range(min(n_blocks, len(keys)), 0, -1):
            entry = self._snap_of.get(keys[m - 1])
            if entry is not None:
                self._snap_of.move_to_end(keys[m - 1])
                self._snap_busy.add(entry)
                return m, entry
        return 0, None

    def claim_snapshot(self, key: bytes) -> Tuple[Optional[int], bool]:
        """An entry to write the state after the block ``key`` names
        into: a free one, else the least recently used that no running
        admission reads or writes.  ``(entry, whether one was evicted
        for it)``; ``(None, False)`` when the key has a snapshot already
        or every entry is in use.  The key maps to the entry only once
        :meth:`settle_snapshots` keeps it."""
        if key in self._snap_of or not self.snapshot_entries:
            return None, False
        evicted = not self._snap_free
        if evicted:
            old = next((k for k, e in self._snap_of.items()
                        if e not in self._snap_busy), None)
            if old is None:
                return None, False
            entry = self._snap_of.pop(old)
        else:
            entry = self._snap_free.pop()
        self._snap_busy.add(entry)
        return entry, evicted

    def settle_snapshots(self, key: Optional[bytes] = None,
                         entry: Optional[int] = None,
                         written: bool = False) -> None:
        """The admission is over: nothing is in use any more, and the
        claimed ``entry`` holds ``key``'s state if its prefill ``written``
        it, else it is free again."""
        if entry is not None:
            if written:
                self._snap_of[key] = entry
            else:
                self._snap_free.append(entry)
        self._snap_busy.clear()

    # -- slot mapping ------------------------------------------------------
    def map_slot(self, slot: int, blocks: List[int]) -> None:
        """Install ``blocks`` (shared prefix + freshly allocated, in
        logical order) as the slot's block table.  Shared blocks arrive
        with their refcount already bumped by :meth:`match_prefix`;
        fresh ones are claimed here."""
        assert not self._mapped[slot], f"slot {slot} already mapped"
        if len(blocks) > self.max_blocks:
            raise ValueError(
                f"{len(blocks)} blocks exceed max_blocks "
                f"({self.max_blocks})")
        self._mapped[slot] = list(blocks)
        for b in blocks:
            if self.ref[b] == 0:
                self.ref[b] = 1
        # the rest of the row is already null: release() left it so
        self.tables[slot, :len(blocks)] = blocks
        self._plan = None

    def append_block(self, slot: int, block: int) -> None:
        """Decode-time growth: one more block for a slot whose next
        token crosses a block boundary."""
        n = len(self._mapped[slot])
        if n >= self.max_blocks:
            raise ValueError(f"slot {slot} already at max_blocks")
        self._mapped[slot].append(block)
        self.ref[block] = 1
        self.tables[slot, n] = block

    def release(self, slot: int) -> None:
        """Return the slot row to the free list and drop one reference
        from every block it mapped: keyed blocks park in the evictable
        LRU (content intact for the next prefix hit), unkeyed ones are
        freed.  The row goes back to all null block, so an inactive
        slot's gather reads block 0 and nothing another request owns.
        Device-side cache rows are never scrubbed — stale blocks are
        unreachable past every reader's validity window."""
        if slot in self._free_slots:
            raise ValueError(f"slot {slot} double-freed")
        for b in self._mapped[slot]:
            assert self.ref[b] > 0
            self.ref[b] -= 1
            if self.ref[b] == 0:
                if b in self._key_of:
                    self._lru[b] = None
                    self._lru.move_to_end(b)
                else:
                    self._free_blocks.append(b)
        self._mapped[slot] = []
        self.tables[slot] = 0
        self.active[slot] = False
        self.pos[slot] = 0
        self._free_slots.append(slot)
        self._plan = None

    def activate(self, slot: int, length: int) -> None:
        """Mark ``slot`` live with ``length`` valid cache positions
        (called after its prompt chunks were prefilled into its
        blocks)."""
        self.pos[slot] = length
        self.active[slot] = True
        self._plan = None

    def advance(self, written) -> None:
        """The tick just dispatched wrote ``written`` more positions
        (1 for a decode tick; a per-slot vector, ``accepted + 1``, for
        a verify round) into every ACTIVE slot's cache."""
        self.pos += np.where(self.active, written, 0)

    # -- what a dispatch is handed ----------------------------------------
    def tables_snapshot(self) -> np.ndarray:
        """The block tables as ONE dispatch reads them: a copy nobody
        edits, because the program may read its host arguments after
        the call returns (module docstring) while the next admission,
        growth or release already stores into :attr:`tables`."""
        return self.tables.copy()

    def group_plan(self) -> Optional[np.ndarray]:
        """Which running slots read which of their leading blocks once
        for a group in the latent decode kernel
        (``ops.mla_attention.group_plan``, ``(2, num_slots)`` int32),
        for pools that hold latent rows; None for any other model.
        Made again only after an admission or a release changed a row
        or the running set, from the tables and the positions as they
        then stand: a run shared then stays shared, since positions
        only grow while a slot runs.  A new array each time, never
        edited, so a dispatch may hold it."""
        if self._group_limits is None:
            return None
        if self._plan is None:
            max_group, min_blocks = self._group_limits
            self._plan = mla_attention.group_plan(
                self.tables, np.where(self.active,
                                      self.pos // self.block_size, 0),
                max_group=max_group, min_blocks=min_blocks)
        return self._plan

    def snapshot(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``(pos, active, tables)`` for one decode or verify dispatch,
        in the programs' argument order — copies, for the reason
        :meth:`tables_snapshot` gives."""
        return self.pos.copy(), self.active.copy(), self.tables_snapshot()
