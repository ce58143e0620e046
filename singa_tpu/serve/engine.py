"""ServeEngine — continuous-batching inference over a paged KV arena.

The engine turns the one-session decode loop of
``models/_generate.py`` into a multi-request server while keeping the
training stack's single-compiled-module discipline: for a given
(model, num_slots, max_len, block_size) it compiles exactly TWO XLA
programs —

* **prefill-chunk** — ``C`` tokens of one request's prompt at a traced
  block-aligned offset, ``C`` being several whole blocks (256 rows'
  worth, see ``_PREFILL_ROWS``: a chunk streams every weight once, so
  the more rows share that pass the cheaper a prompt token): the
  slot's block-table row is gathered into a dense cache view, the
  chunk's k/v are written at [pos, pos+C) and the ``C / block_size``
  physical blocks it covers are scattered back
  (``ops.kv_cache.scatter_block_kv``).  A prompt prefills as
  ``ceil(len / C)`` dispatches of this one program, its tail padded
  inside the fixed ``(1, C)`` shape — and a request whose leading
  prompt blocks are already resident (prefix cache) SKIPS them:
  prefill cost scales with the unshared suffix, which is the TTFT win
  paging buys.
* **decode-over-block-tables** — ONE token for every slot per
  dispatch: the (num_slots, max_blocks) block tables gather every
  slot's dense view, per-slot positions drive RoPE offsets and
  attention limits as (num_slots,) vectors, and each slot's new k/v is
  scattered to ``[table[slot, pos // bs], pos % bs]``
  (``scatter_token_kv``).  Inactive slots are masked — position
  clamped to 0, writes redirected to the null block, token entries
  frozen — so a half-empty arena still runs the same program.

Both programs thread params/buffers as jit arguments through the same
``_bound`` rebinding as generation, so weights are never baked into the
executables, and both donate the arena, so cache memory is updated in
place.  Submitting, admitting, growing and evicting requests are
host-side index updates — no recompilation ever happens after warmup
(asserted in tests/test_serve.py via the jit cache size).

Greedy decode through the engine is token-identical to
``GenerateMixin.generate`` (same cached forward, same argmax), which
anchors the whole subsystem's correctness to existing behavior.

One tick in flight (docs/serving.md): a step DISPATCHES decode tick N
and only then LANDS tick N-1 — fetches its tokens and delivers them —
so the launch, the wake-up after the fetch, delivery, the caller's work
between steps and the next step's admission all run beside a busy
chip.  A tick's input tokens never leave the device (``decode_paged``
takes ``toks`` and returns them, not donated), positions are the
host's, and whether a request ends BY LENGTH is known before its token
is: such a tick lands in the step that dispatched it, so a finished
request's successor finds an empty device queue.  Landing skips a
participant whose slot no longer runs that request (evicted,
pre-empted, withdrawn, finished by an EOS one landing ago): the token
is dropped, and a replay regenerates it bit for bit.  Whatever reads a
request's tokens or moves a slot from outside a step lands first.  The
speculative engine does not run ahead: its accepted count decides the
positions.

An admission's first token lands behind the tick: ``prefill_chunk``
writes the picked token into ``toks[slot]`` on the device, where the
tick reads it, so the host needs it only to DELIVER it.  A step that
admits dispatches the chunks, ends the admission's bookkeeping,
dispatches its decode tick over the new slot too, and only then fetches
the chunk's token, while the chip holds the tick.  The token still
lands at once where the step dispatches no plain tick
(``step(decode=False)``, the speculative engine) and where it is known
to end the request by length.  A pending first token is dropped like a
tick's: by a pre-emption, a rebuild, whatever took the request out of
its slot before the landing.

Admission counts FREE BLOCKS, not slots: a request needs a table row
AND enough blocks for its prompt (minus the shared prefix), and decode
grows a slot by one block when its position crosses a block boundary.
When growth finds no free or evictable block, the youngest running
request is PREEMPTED — its blocks are released and it re-queues at the
head, to be re-prefilled later from prompt + tokens-so-far (greedy
decode makes the replay idempotent, so preemption never changes a
stream).

Resilience (ISSUE 4, extended to the paged arena) — every path below
is exercised by deterministic chaos tests (``singa_tpu.faults``,
tests/test_faults.py):

* **retry** — transient dispatch failures (RuntimeError/OSError before
  the program launches) are retried with bounded exponential backoff;
  the ``serve.prefill``/``serve.decode`` injection sites fire *before*
  the jitted call, so an injected fault leaves the donated arena intact
  and the retry re-dispatches the same tick.
* **quarantine** — a request whose prefill (or admission-time block
  allocation, site ``serve.block_alloc``) keeps failing is marked
  ``failed`` on its handle instead of crashing the engine.
* **shedding** — deadline-aware overload control: queued requests whose
  deadline will expire before they could plausibly reach a slot are
  shed at the step boundary (reason ``shed``) instead of wasting a
  prefill.
* **recovery** — when decode or a decode-time block allocation dies
  past retries, or a Heartbeat detects a hang (``recover_on_hang``),
  the arena is rebuilt — fresh block pool, fresh tables, fresh
  refcounts, empty prefix cache — and every in-flight request is
  re-prefilled from prompt + tokens-so-far.  Greedy decode makes the
  replay idempotent: recovered streams are bit-identical to an
  uninterrupted run.
* **drain/close** — ``drain()`` refuses new submissions while
  completing everything in the system; ``close()`` drains and releases
  the arena.

With ``heartbeat_timeout_s`` set and ``recover_on_hang`` unset, a hung
dispatch still surfaces as a clean abort instead of wedging the server.
Quarantines and recoveries land as durable ``incident`` records
(``record_store``), linted by ``tools/record_check.py``.

Observability (ISSUE 11): every request gets a trace id
(``handle.trace_id``) activated around its admission, prefill chunks,
token deliveries and eviction, so the whole request reconstructs as one
trace in the obs event stream (``tools/obsq.py trace``) — TTFT and
tokens/s are derivable from it and asserted equal to the histogram
metrics.  The engine also keeps a :class:`~singa_tpu.obs.flight.
FlightRecorder` ring of its recent events (in-memory, sink or no sink);
each quarantine/recovery dumps the ring to
``<record dir>/incidents/<ts>-<site>.jsonl`` and the incident record's
``flight_ref`` points at it.  With no ``record_store`` and no sink the
engine performs zero file writes.

Speculative decoding (ISSUE 13): ``ServeEngine(draft_model=, spec_k=)``
replaces the per-tick decode with a **verify-k round** — the THIRD
gated program (serve/spec.py): the draft proposes k tokens per slot
(its KV blocks ride the same block tables, a parallel pool in
``BlockPool``), the target scores all k+1 window positions in one
dispatch, the longest matching greedy prefix commits and rejected
positions roll back by truncating the slot's position/limit.  The
delivered tokens are the target's own picks, so speculative greedy
streams are bitwise identical to ``generate()`` by construction; an
injected/transient verify failure past retries falls back to a plain
decode tick (site ``serve.verify``).  The fixed compiled set becomes
(prefill, decode, verify, handoff), asserted via
:meth:`spec_compiled_counts`.

Disaggregated serving (ISSUE 12): the engine is also the worker unit
of :mod:`singa_tpu.serve.disagg` — a prefill pool ticks with
``step(decode=False)`` and hands finished prefills to a decode pool
through :meth:`extract_handoff`/:meth:`inject_handoff` (KV blocks move
via the optional third compiled program, a fixed-shape
``handoff_gather``; refcounts and prefix-cache keys transfer with the
blocks).  Same-config workers share one set of executables via
``programs=`` (:class:`SharedPrograms`), so a whole tier costs one
engine's compiles.
"""

from __future__ import annotations

import itertools
import threading
import time
import warnings
from contextlib import nullcontext
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults
from ..models._generate import _bound, decode_step, resume_step
from ..obs import events
from ..obs import flight as obs_flight
from ..obs import record as obs_record
from ..obs import trace as obs_trace
from ..ops import kv_cache as kv_ops
from ..utils import failure
from ..utils.failure import Heartbeat
from . import mem as serve_mem
from .metrics import ServeMetrics
from .scheduler import (EVICTED, FAILED, FINISHED, QUEUED, RUNNING,
                        QueueFull, Request, RequestHandle, Scheduler,
                        eta_first_token)
from .slots import BlockPool

__all__ = ["ServeEngine", "QueueFull", "EngineClosed", "SharedPrograms"]

#: distinguishes engines built in the same second+pid (run_id suffix)
_ENGINE_SEQ = itertools.count()

#: rows one prefill dispatch feeds the MXU.  A chunk streams every
#: weight once whatever its length, so a prompt token gets cheaper with
#: every row that shares the pass, until the matmuls leave the
#: bandwidth roof: in bf16 on a TPU v5e at 197e12 / 819e9 = 240 rows
#: (dropless experts multiply every expert by every row, so they share
#: that ridge).  256 is the power of two at the ridge; past it a chunk
#: costs its rows in full, pad rows included, and stalls the running
#: streams that much longer.  Timed on the chip at 64 / 128 / 256 / 512
#: in both serve cells of the benchmark (PERF.md, PR 29).
_PREFILL_ROWS = 256


#: where a prefill chunk of a model with state snapshots enters from,
#: beside a snapshot's entry (>= 0): the slot's own state, or zeros
_OWN, _ZEROS = -1, -2


def _chunk_tokens(block_size: int, max_blocks: int) -> int:
    """Tokens a prefill dispatch covers: ``_PREFILL_ROWS`` rounded down
    to whole blocks, at least one block, at most the slot's view."""
    return block_size * max(1, min(_PREFILL_ROWS // block_size, max_blocks))


class EngineClosed(RuntimeError):
    """submit()/step() refused: the engine is draining or closed."""


class SharedPrograms(NamedTuple):
    """The compiled-program bundle one engine can lend to another
    (``ServeEngine(..., programs=template.programs())``) — how a
    disaggregated worker pool keeps the whole tier on ONE set of
    executables: every same-config worker dispatches through the same
    jitted callables, so N prefill + M decode workers cost exactly the
    template's compiles (the per-worker jit-cache assertions then count
    the shared caches).  Sharing requires the SAME model object, block
    size and prefill chunk (the closures capture all three); arena
    shapes (num_slots/max_len/num_blocks) may differ, but each distinct
    shape adds a cache entry to the shared programs, so homogeneous
    pools are what keeps the per-worker (1, 1) invariant literal."""

    model_ref: object
    block_size: int
    prefill: object
    decode: object
    handoff: object
    #: speculative decoding (serve/spec.py): the draft model the verify
    #: program's closures capture (None for a plain engine), the
    #: trace-time k baked into that program, and the verify executable
    #: itself.  Sharing requires the SAME draft object and equal k —
    #: a tier mixes spec and plain engines only by NOT sharing programs.
    draft_ref: object = None
    spec_k: int = 0
    verify: object = None
    #: KV memory hierarchy (ISSUE 17, serve/mem.py): the arena storage
    #: formats the closures were TRACED against (None = full precision,
    #: "int8" = QuantKV codes + scales).  A format mismatch would not
    #: error — it would silently add a second jit-cache entry per
    #: program and break the (1, 1) invariant — so sharing validates
    #: equality up front.
    kv_dtype: object = None
    draft_kv_dtype: object = None
    #: tokens a prefill dispatch covers (``_chunk_tokens``): baked into
    #: the prefill closure's scatter, and the shape of its ``ids``
    chunk: int = 0


class ServeEngine:
    """Continuous-batching engine over one decoder model.

        eng = ServeEngine(model, num_slots=8, max_len=256, block_size=32)
        h = eng.submit(prompt_ids, max_new_tokens=64, deadline_s=30.0)
        eng.run_until_idle()
        full = h.result()              # prompt + generated tokens

    ``step()`` advances the whole arena by one decode tick (evict →
    admit/prefill → decode), delivering one token to every live request
    and invoking their streaming ``on_token`` callbacks: the token of
    the tick the step BEFORE dispatched, while the chip runs this
    step's (module docstring, "One tick in flight").

    ``num_blocks`` sizes the physical block pool (default: capacity
    parity with a fixed ``(num_slots, max_len)`` arena); a SMALLER pool
    with MORE slots is how paging admits more concurrent requests in
    the same memory.  ``share_prefix=False`` disables prefix-cache
    sharing (every prompt block is private).

    Decoding is greedy — the serving counterpart of
    ``generate(temperature=0)`` and token-identical to it.
    """

    def __init__(self, model, num_slots: int, max_len: int, *,
                 block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 share_prefix: bool = True,
                 max_queue: Optional[int] = None,
                 param_dtype=None,
                 heartbeat_timeout_s: Optional[float] = None,
                 on_failure=None,
                 max_dispatch_retries: int = 2,
                 backoff_base: float = 0.05,
                 backoff_max: float = 1.0,
                 recover_on_hang: bool = False,
                 max_recoveries: int = 2,
                 record_store: Optional[str] = None,
                 run_id: Optional[str] = None,
                 programs: Optional[SharedPrograms] = None,
                 draft_model=None, spec_k: Optional[int] = None,
                 kv_dtype=None, draft_kv_dtype=None,
                 spill_blocks: Optional[int] = None,
                 _sleep: Callable[[float], None] = time.sleep):
        self.model = model
        # speculative decoding (serve/spec.py): a draft model turns the
        # per-tick decode into a verify-k round — k proposals + the
        # pending token scored by ONE target dispatch.  spec_k=None
        # with a draft resolves the window depth from the committed
        # best-config table (ISSUE 14 / ROADMAP item 2b: the table's k
        # comes from measured accept_rate / tokens_per_dispatch
        # records); an explicit integer always wins
        if draft_model is not None and spec_k is None:
            from ..autotune import table as autotune_table
            spec_k = autotune_table.resolve_spec_k(model)
        if spec_k is None:
            spec_k = 0
        if (draft_model is None) != (spec_k == 0):
            raise ValueError(
                "speculative decoding needs BOTH draft_model and "
                f"spec_k >= 1 (got draft_model="
                f"{'set' if draft_model is not None else 'None'}, "
                f"spec_k={spec_k})")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.draft_model = draft_model
        self.spec_k = int(spec_k)
        max_pos = getattr(getattr(model, "cfg", None), "max_position", None)
        if max_pos is not None and max_len > max_pos:
            raise ValueError(
                f"max_len ({max_len}) exceeds the model's max_position "
                f"({max_pos})")
        # experts a token is routed to (0: a dense model), for the
        # host-side count of what each dispatch routes
        cfg = getattr(model, "cfg", None)
        self._moe_top_k = int(getattr(cfg, "moe_top_k", 0)) \
            if getattr(cfg, "num_experts", 0) else 0
        self.share_prefix = bool(share_prefix)
        self.sched = Scheduler(
            max_queue=2 * num_slots if max_queue is None else max_queue)
        # the incident flight ring (ISSUE 11): always recording (bounded
        # in-memory, zero file I/O), registered for fault-fire
        # broadcasts; dumps happen only when record_store names a place
        # for the incident evidence to live
        self.flight = obs_flight.register(obs_flight.FlightRecorder())
        self.metrics = ServeMetrics(flight=self.flight)
        # the collector's pauses are charged to the turn they fall in
        events.watch_gc()
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self._on_failure = on_failure
        self.max_dispatch_retries = int(max_dispatch_retries)
        if self.max_dispatch_retries < 0:
            raise ValueError(f"max_dispatch_retries must be >= 0, got "
                             f"{max_dispatch_retries}")
        self.backoff_base = float(backoff_base)
        self.backoff_max = float(backoff_max)
        self.recover_on_hang = bool(recover_on_hang)
        self.max_recoveries = int(max_recoveries)
        self.record_store = record_store
        self.run_id = run_id or \
            f"{obs_record.new_run_id('serve')}-e{next(_ENGINE_SEQ)}"
        self._sleep = _sleep
        self._draining = False
        self._closed = False
        # set by the Heartbeat monitor thread, consumed at the next
        # step boundary by the step thread (which owns the arena)
        self._recover_flag = threading.Event()
        self._recoveries = 0
        self._incident_seq = itertools.count()
        self._tick_ewma: Optional[float] = None   # measured step() wall s
        # measured accepted-tokens-per-tick PER SLOT (EWMA): 1.0 for a
        # plain engine by construction, up to spec_k + 1 under
        # speculation — the shed eta divides by it so a spec engine
        # (whose queued requests reach their first token sooner because
        # slots drain faster) does not over-shed against a 1-token/tick
        # assumption (scheduler.eta_first_token)
        self._tpt_ewma: Optional[float] = None
        # admission-cadence hint from an external driver (the
        # disaggregated Router pushes its measured round time here):
        # the shed eta uses the slower of this and the engine's own
        # tick EWMA, so a worker stepped once per router round does not
        # under-estimate queue wait by (round / own-tick)
        self.tick_hint_s: Optional[float] = None

        # weights snapshotted once (same pattern as _gen_setup); decode
        # is weight-read bound, so an optional one-time bf16 cast halves
        # per-token HBM traffic on TPU
        params = {n: t.data for n, t in model.get_params().items()}
        if not params:
            raise ValueError(
                "model has no initialized params — call model.compile() "
                "(or run one forward) before building a ServeEngine")
        buffers = {n: t.data for n, t in model._get_buffers().items()}
        arena_dtype = None
        if param_dtype is not None:
            params = {n: (a.astype(param_dtype)
                          if jnp.issubdtype(a.dtype, jnp.floating) else a)
                      for n, a in params.items()}
            # the arena must match the dtype init_caches picks under the
            # CAST params inside the prefill trace (models size their
            # caches off the bound weights' dtype) — otherwise the
            # block scatter type-mismatches at trace time.  eval_shape
            # under the cast binding reads that dtype without allocating.
            # Leaf by leaf: a model may pin one (an f32 recurrent state
            # beside bf16 keys, models/granite_hybrid.py).
            with _bound(model, params, buffers):
                spec = jax.eval_shape(lambda: model.init_caches(1, 2))
            arena_dtype = jax.tree.map(lambda a: a.dtype, spec)
        self._params, self._buffers = params, buffers
        # draft weights snapshotted the same way (param_dtype applies to
        # the draft too — decode AND verify are weight-read bound)
        if draft_model is not None:
            dparams = {n: t.data for n, t in draft_model.get_params().items()}
            if not dparams:
                raise ValueError(
                    "draft model has no initialized params — call "
                    "draft.compile() (or run one forward) before "
                    "building a speculative ServeEngine")
            dbuffers = {n: t.data
                        for n, t in draft_model._get_buffers().items()}
            if param_dtype is not None:
                dparams = {n: (a.astype(param_dtype)
                               if jnp.issubdtype(a.dtype, jnp.floating)
                               else a)
                           for n, a in dparams.items()}
            self._dparams, self._dbuffers = dparams, dbuffers
        else:
            self._dparams = self._dbuffers = None
        # arena construction args kept for recovery rebuilds
        self._num_slots, self._max_len = num_slots, max_len
        self._block_size, self._num_blocks = block_size, num_blocks
        self._arena_dtype = arena_dtype
        # KV memory hierarchy (ISSUE 17, serve/mem.py): arena storage
        # formats + the host-RAM spill tier for evicted prefix blocks.
        # The SpillStore is content-addressed (chain keys), so it
        # SURVIVES arena recovery — _recover hands the same store to
        # the fresh pool and a tenant's spilled system prompt outlives
        # even a rebuild.
        self._kv_dtype = serve_mem.normalize_kv_dtype(kv_dtype)
        self._draft_kv_dtype = (self._kv_dtype if draft_kv_dtype is None
                                else serve_mem.normalize_kv_dtype(
                                    draft_kv_dtype))
        if spill_blocks is not None and spill_blocks < 1:
            raise ValueError(
                f"spill_blocks must be >= 1 (or None to disable the "
                f"spill tier), got {spill_blocks}")
        self._spill = (serve_mem.SpillStore(spill_blocks)
                       if spill_blocks is not None else None)
        self.pool = BlockPool(model, num_slots, max_len,
                              block_size=block_size, num_blocks=num_blocks,
                              dtype=arena_dtype, draft_model=draft_model,
                              kv_dtype=self._kv_dtype,
                              draft_kv_dtype=self._draft_kv_dtype,
                              spill=self._spill)
        self._wire_spill()

        # tokens one prefill dispatch covers: derived from the arena,
        # the same for every prompt, so prefill stays ONE program
        self._chunk = _chunk_tokens(self.pool.block_size,
                                    self.pool.max_blocks)

        self._running: Dict[int, Request] = {}      # slot -> request
        # device-resident per-slot last tokens (the one piece of slot
        # state that is: the programs chain it): written by prefill (the
        # request's first token) and decode (each next token); the host
        # only ever FETCHES this small int vector — tokens are never
        # uploaded, so the decode hot loop is one dispatch + one tiny
        # fetch per tick
        self._toks = jnp.zeros((num_slots,), jnp.int32)
        # decode ticks dispatched and not landed yet, oldest first:
        # (the tick's token array, its (slot, request) pairs, when it
        # was dispatched).  One between steps at most; two only inside
        # _decode_tick, between dispatching tick N and landing N-1
        self._flying: List[Tuple[object, List[Tuple[int, Request]],
                                 float]] = []
        self._landed_at = 0.0       # perf_counter of the last landing
        # admissions of this step whose chunks are dispatched and whose
        # first token is not fetched yet, oldest first: (the token array
        # its last chunk returned, slot, request, ``decode_ticks`` then).
        # One array an admission, so that an earlier one's token does
        # not wait for a later one's chunks.  Empty between steps
        self._first: List[Tuple[object, int, Request, int]] = []

        # ---- the exactly-two compiled programs --------------------------
        # (plus the optional third: the fixed-shape handoff gather a
        # disaggregated tier uses to move a finished prefill's blocks —
        # compiled lazily, only on the first handoff)
        if programs is not None:
            if programs.model_ref is not model:
                raise ValueError(
                    "programs= sharing requires the SAME model object "
                    "(the jitted closures capture its cached forward)")
            if programs.block_size != self.pool.block_size:
                raise ValueError(
                    f"programs= sharing requires matching block_size "
                    f"(template {programs.block_size}, this engine "
                    f"{self.pool.block_size})")
            if programs.chunk != self._chunk:
                raise ValueError(
                    f"programs= sharing requires the same prefill chunk "
                    f"(template {programs.chunk} tokens, this engine "
                    f"{self._chunk}: a max_len under {_PREFILL_ROWS} "
                    f"tokens caps it)")
            if programs.draft_ref is not draft_model or \
                    programs.spec_k != self.spec_k:
                raise ValueError(
                    "programs= sharing requires the SAME draft model "
                    "object and spec_k (the verify program's closures "
                    f"capture both; template spec_k={programs.spec_k}, "
                    f"this engine spec_k={self.spec_k})")
            if programs.kv_dtype != self._kv_dtype or \
                    programs.draft_kv_dtype != self._draft_kv_dtype:
                raise ValueError(
                    "programs= sharing requires matching arena storage "
                    "formats (template kv_dtype="
                    f"{programs.kv_dtype!r}/draft "
                    f"{programs.draft_kv_dtype!r}, this engine "
                    f"{self._kv_dtype!r}/{self._draft_kv_dtype!r}) — a "
                    "mismatch would silently retrace every program "
                    "against the other arena layout instead of sharing")
            self._prefill = programs.prefill
            self._decode = programs.decode
            self._handoff = programs.handoff
            self._verify = programs.verify
            return
        bs, chunk = self.pool.block_size, self._chunk
        resume = resume_step(model)

        from . import spec as spec_mod

        def prefill_chunk(params, buffers, ids, pos, last_idx, slot,
                          fresh, tables, toks, caches, slot_state, tails,
                          snaps, plan):
            # one block-aligned chunk of one request's prompt: gather
            # the slot's dense view, run the cached forward at the
            # traced offset, pick the chunk's last valid token
            # in-program (only the final chunk's pick survives), and
            # scatter the blocks this chunk filled, those from position
            # ``fresh`` on, back to the arena
            # (the gather/forward/scatter halves are the SAME helpers
            # the speculative prefill composes — serve/spec.py — so
            # the two prefill programs' semantics cannot drift apart)
            row = jax.lax.dynamic_index_in_dim(tables, slot, axis=0,
                                               keepdims=True)   # (1, MB)
            entry = state_rows = None
            if tails is not None:
                # side state beside the KV blocks (serve/slots.py): the
                # chunk starts from the tail of the block before it in
                # the row, zeros at position 0, and reports the state
                # after each of its blocks' last rows and after its
                # last valid row, which pad rows must not move
                before = jnp.take(row[0], jnp.maximum(pos // bs - 1, 0))
                entry = [tuple(jnp.where(pos > 0, t[before], 0)[None]
                               for t in tail) for tail in tails]
                state_rows = jnp.append(
                    jnp.arange(chunk // bs) * bs + bs - 1, last_idx)
            if snaps is not None:
                # state too heavy for a tail a block (serve/slots.py):
                # ``plan`` = (where the chunk enters from: a snapshot's
                # entry, _OWN the slot's own state as the prompt's
                # chunk before left it, _ZEROS at position 0; the entry
                # to leave a snapshot in, or -1; the chunk's row it
                # stands after).  The scan reports those two rows'
                # states and no others.
                src, dst, snap_row = plan[0], plan[1], plan[2]
                entry = [tuple(
                    jnp.where(src == _ZEROS, 0, jnp.where(
                        src >= 0, sn[jnp.maximum(src, 0)], own[slot]))[None]
                    for own, sn in zip(state, snap))
                    for state, snap in zip(slot_state, snaps)]
                state_rows = jnp.stack([snap_row, last_idx])
                # no tail lets such a chunk start early, so it may cross
                # the view's end: the row grows by a chunk of null
                # blocks, which take the rows past it
                row = jnp.concatenate(
                    [row, jnp.zeros((1, chunk // bs), row.dtype)], axis=1)
            logits, dense = spec_mod.resume_on_row(
                resume, params, buffers, ids, pos, row, caches, entry,
                state_rows)
            last = jax.lax.dynamic_slice_in_dim(
                logits, last_idx, 1, axis=1)[:, 0, :]
            # greedy pick in-program (jnp.argmax — bit-identical to
            # _pick_impl's temperature-0 branch in generate())
            tok = jnp.argmax(last, axis=-1).astype(jnp.int32)[0]
            toks = toks.at[slot].set(tok)
            new = spec_mod.scatter_chunk(row, pos, fresh, caches,
                                         [d[:2] for d in dense], bs, chunk)
            if snaps is not None:
                slot_state = [tuple(s.at[slot].set(st[0, 1])
                                    for s, st in zip(state, d[2:]))
                              for state, d in zip(slot_state, dense)]
                at = jnp.maximum(dst, 0)
                snaps = [tuple(
                    sn.at[at].set(jnp.where(dst >= 0, st[0, 0], sn[at]))
                    for sn, st in zip(snap, d[2:]))
                    for snap, d in zip(snaps, dense)]
                return toks, new, slot_state, None, snaps
            if tails is None:
                return toks, new, None, None, None
            wb = spec_mod.chunk_blocks(row, pos, fresh, bs, chunk)
            n = chunk // bs
            for i in range(n):      # in-place writes, as the KV blocks'
                tails = [tuple(t.at[wb[i]].set(st[0, i])
                               for t, st in zip(tail, d[2:]))
                         for tail, d in zip(tails, dense)]
            slot_state = [tuple(s.at[slot].set(st[0, n])
                                for s, st in zip(state, d[2:]))
                          for state, d in zip(slot_state, dense)]
            return toks, new, slot_state, tails, None

        dec = decode_step(model)

        def decode_paged(params, buffers, toks, pos, active, tables,
                         caches, slot_state):
            # inactive slots are masked: position clamped to 0 and the
            # write redirected to the null block (their table row may
            # point at blocks now owned by OTHER requests, so —
            # unlike the fixed arena — scribbling through it is not
            # harmless), and their token entry frozen so nothing
            # downstream reads a garbage argmax
            posc = jnp.where(active, pos, 0)
            wb = jnp.take_along_axis(tables, (posc // bs)[:, None],
                                     axis=1)[:, 0]
            wb = jnp.where(active, wb, 0)
            off = jnp.where(active, posc % bs, 0)
            # what the pools are decides how attention reads them
            # (ops.kv_cache.reads_blocks): through the block table, the
            # token written into the arena first and no view of it made
            # — or, for an int8 arena and off the TPU, a gathered dense
            # view that the model writes the token into
            # (a layer without keys has no pool: its (None, None) passes
            # through every branch)
            paged = kv_ops.reads_blocks(
                next(ck for ck, _ in caches if ck is not None))
            if paged:
                # an inactive slot reads the null block it writes
                rows = jnp.where(active[:, None], tables, 0)
                dense = [(kv_ops.PagedKV(ck, rows, wb, off),
                          kv_ops.PagedKV(cv, rows, wb, off))
                         if ck is not None else (None, None)
                         for ck, cv in caches]
            else:
                dense = [spec_mod.gather_view(ck, cv, tables)
                         for ck, cv in caches]
            if slot_state is not None:
                # an inactive slot reads zeros and keeps what it holds
                live = active[:, None]
                rows_of = lambda a: live.reshape(
                    live.shape + (1,) * (a.ndim - 2))
                dense = [kv + tuple(jnp.where(rows_of(s), s, 0)
                                    for s in state)
                         for kv, state in zip(dense, slot_state)]
            logits, dense = dec(params, buffers, toks[:, None], posc,
                                dense)
            if slot_state is not None:
                slot_state = [
                    tuple(jnp.where(rows_of(s), n, s)
                          for n, s in zip(d[2:], state))
                    for d, state in zip(dense, slot_state)]
            picked = jnp.argmax(logits.astype(jnp.float32),
                                axis=-1).astype(jnp.int32)
            new_toks = jnp.where(active, picked, toks)
            # ``pos`` is the host's, which adds the 1 itself
            # (BlockPool.advance)
            if paged:
                return (new_toks,
                        [(dk.pool, dv.pool) if dk is not None
                         else (None, None) for dk, dv, *_ in dense],
                        slot_state)

            def row_at(c, p):
                return jax.lax.dynamic_slice_in_dim(c, p, 1, axis=0)[0]

            new = []
            for (ck, cv), (dk, dv, *_) in zip(caches, dense):
                if ck is None:
                    new.append((None, None))
                    continue
                k_tok = jax.vmap(row_at)(dk, posc)       # (S, K, D)
                v_tok = jax.vmap(row_at)(dv, posc)
                new.append(kv_ops.scatter_token_kv(ck, cv, wb, off,
                                                   k_tok, v_tok))
            return new_toks, new, slot_state

        def handoff_gather(tables, slot, caches):
            # the disaggregated tier's KV handoff source: ONE slot's
            # dense per-layer view gathered through its block-table row
            # (ops.kv_cache.gather_block_kv — no tensor reshaping).
            # The arena is NOT donated: a failed handoff must leave the
            # source caches valid so the router can re-route.
            row = jax.lax.dynamic_index_in_dim(tables, slot, axis=0,
                                               keepdims=True)   # (1, MB)
            return [spec_mod.gather_view(ck, cv, row) for ck, cv in caches]

        if draft_model is not None:
            # speculative engine: the prefill program also writes the
            # draft arena (both caches donated), and the VERIFY program
            # (serve/spec.py) replaces the per-tick decode — the plain
            # decode program stays as the serve.verify fault-fallback,
            # so the fixed compiled set is (prefill, decode, verify,
            # handoff), asserted via spec_compiled_counts()
            self._prefill = jax.jit(
                spec_mod.make_spec_prefill(model, draft_model, bs, chunk),
                donate_argnums=(11, 12))
            self._verify = jax.jit(
                spec_mod.make_verify(model, draft_model, self.spec_k, bs),
                donate_argnums=(8, 9))
        else:
            self._prefill = jax.jit(prefill_chunk,
                                    donate_argnums=(9, 10, 11, 12))
            self._verify = None
        self._decode = jax.jit(decode_paged, donate_argnums=(6, 7))
        self._handoff = jax.jit(handoff_gather)

    # -- introspection ----------------------------------------------------
    def weights(self):
        """``(params, buffers)`` as every compiled program reads them:
        the model's, snapshotted at construction, after the one-time
        ``param_dtype`` cast.  For a check that must score what the
        engine serves with and not the model's masters (a teacher-forced
        pass through ``models._generate.resume_step`` under them)."""
        return self._params, self._buffers

    def slot_cache(self, slot: int):
        """What the arena holds for the request in ``slot``, per layer
        ``(k, v, *state)`` as host arrays: its ``pos`` cached positions
        (n, K, D) gathered through its block-table row, and the slot's
        side state where the model keeps one (``k`` and ``v`` None for a
        layer that keeps state alone).  For a check of what the
        programs wrote against a reference; it fetches from the device
        and belongs in no step.  Lands the tick in flight first, so the
        rows are those of the tokens the request has been handed."""
        self._land()
        n = int(self.pool.pos[slot])
        row = self.pool.tables[slot:slot + 1]
        out = []
        for i, (ck, cv) in enumerate(self.pool.caches):
            state = () if self.pool.slot_state is None else tuple(
                np.asarray(s[slot]) for s in self.pool.slot_state[i])
            if ck is None:              # a layer with state and no keys
                out.append((None, None, *state))
                continue
            k, v = kv_ops.gather_block_kv(ck, cv, row)
            out.append((np.asarray(k[0, :n]), np.asarray(v[0, :n]), *state))
        return out

    def compiled_counts(self):
        """(prefill, decode) jit-cache entry counts — the no-recompile
        invariant says both stay at 1 after warmup (tested via
        tools.lint.hlo.assert_program_count, shared with the HLO gate).
        When programs are shared across a worker pool these are the
        SHARED caches, so the invariant covers the whole tier at once."""
        return (self._prefill._cache_size(), self._decode._cache_size())

    def handoff_compiled_count(self) -> int:
        """Jit-cache entry count of the optional third program (the
        disaggregated handoff gather): 0 until the first handoff, 1
        after — never more (same fixed shapes as decode's inputs)."""
        return self._handoff._cache_size()

    def spec_compiled_counts(self):
        """(prefill, decode, verify, handoff) jit-cache entry counts —
        the FIXED PROGRAM SET invariant of ISSUE 13: a speculative
        engine's whole serving lifetime compiles exactly the asserted
        set and nothing else.  ``decode`` is 0 until a ``serve.verify``
        fault forces a plain-decode fallback tick, ``handoff`` is 0
        outside a disaggregated tier; no entry ever exceeds 1.  Read
        every step by the host's account (metrics.HostAccount), which
        tells by it a turn in which a program compiled: so a lent or
        substituted program that keeps no count is not asked for one
        and reads 0, as unchanged."""
        return tuple(
            getattr(p, "_cache_size", int)() if p is not None else 0
            for p in (self._prefill, self._decode, self._verify,
                      self._handoff))

    def programs(self) -> SharedPrograms:
        """The engine's compiled-program bundle, lendable to another
        same-model/same-block-size engine via ``programs=`` — see
        :class:`SharedPrograms`."""
        return SharedPrograms(self.model, self.pool.block_size,
                              self._prefill, self._decode, self._handoff,
                              self.draft_model, self.spec_k, self._verify,
                              self._kv_dtype, self._draft_kv_dtype,
                              self._chunk)

    def lower_programs(self, names=None):
        """jax ``Lowered`` handles of the exactly-two programs (keyed
        ``prefill_chunk`` / ``decode``) plus the optional third
        (``handoff_gather``, the disaggregated tier's KV handoff
        source) and — on a speculative engine — ``verify``; the hook
        ``tools/lint/hlo.py`` compiles to optimized HLO and audits
        (fusions, donation of the KV arena, op histogram).  ``names``
        restricts the set (the gate lowers only ``verify`` from its
        spec engine — tracing the others there would be pure waste).
        Lowering is abstract: nothing executes, nothing is donated,
        and the jit caches (:meth:`compiled_counts`) are untouched.
        The traced shapes are exactly the runtime dispatch shapes, so
        the audited modules ARE the serving modules."""
        zero = np.int32(0)

        def lower_prefill():
            staged = (np.zeros((1, self._chunk), np.int32), zero,
                      np.int32(self._chunk - 1), zero, zero)
            if self._verify is not None:
                return self._prefill.lower(
                    self._params, self._buffers, self._dparams,
                    self._dbuffers, *staged,
                    self.pool.tables, self._toks, self.pool.caches,
                    self.pool.draft_caches)
            return self._prefill.lower(
                self._params, self._buffers, *staged,
                self.pool.tables, self._toks, self.pool.caches,
                self.pool.slot_state, self.pool.tails, self.pool.snapshots,
                None if self.pool.snapshots is None
                else np.zeros((3,), np.int32))

        def lower_handoff():
            caches = (self.pool.caches + self.pool.draft_caches
                      if self._verify is not None else self.pool.caches)
            return self._handoff.lower(self.pool.tables, zero, caches)

        def lower_decode():
            return self._decode.lower(
                self._params, self._buffers, self._toks, self.pool.pos,
                self.pool.active, self.pool.tables, self.pool.caches,
                self.pool.slot_state)

        def lower_verify():
            return self._verify.lower(
                self._params, self._buffers, self._dparams,
                self._dbuffers, self._toks, self.pool.pos,
                self.pool.active, self.pool.tables, self.pool.caches,
                self.pool.draft_caches)

        thunks = {"prefill_chunk": lower_prefill, "decode": lower_decode,
                  "handoff_gather": lower_handoff}
        if self._verify is not None:
            thunks["verify"] = lower_verify
        wanted = thunks if names is None else {
            n: thunks[n] for n in names}
        return {name: thunk() for name, thunk in wanted.items()}

    @property
    def pending(self) -> int:
        """Requests still in flight (queued + running)."""
        return self.sched.depth + len(self._running)

    # -- disaggregated-tier hooks (serve/disagg) ---------------------------
    def running_items(self) -> List[Tuple[int, Request]]:
        """(slot, request) pairs currently occupying slots, slot order —
        the router's per-tick view of what a prefill worker has ready to
        hand off (a snapshot: handing off mutates ``_running``).  Lands
        the tick in flight first: the caller reads the requests' tokens
        and positions."""
        self._land()
        return sorted(self._running.items())

    def withdraw(self, slot: int) -> Request:
        """Remove a RUNNING request from this engine without finishing
        it: the slot and its blocks are released, the request keeps its
        prompt + tokens-so-far and goes back to QUEUED — the router's
        re-route primitive (greedy decode makes the replay elsewhere
        reproduce the exact stream, same argument as preemption).
        Lands the tick in flight first (a request that landing ends is
        gone from its slot: ``KeyError``, as for any empty slot)."""
        self._land()
        req = self._running.pop(slot)
        self.pool.release(slot)
        req.slot = None
        req.state = QUEUED
        return req

    def can_accept_handoff(self, pkg) -> bool:
        """Whether this engine could :meth:`inject_handoff` ``pkg``
        right now (free slot + coverable blocks, prefix sharing
        counted) — side-effect free; see serve/disagg/handoff.py."""
        from .disagg import handoff as _handoff_mod
        return _handoff_mod.can_accept(self, pkg)

    def extract_handoff(self, slot: int):
        """Pull a finished prefill out of this engine as a
        :class:`~singa_tpu.serve.disagg.handoff.HandoffPackage`:
        the slot's blocks are gathered through the fixed-shape
        ``handoff_gather`` program (the optional third compiled
        program), then slot and blocks are released here — the
        request now lives in the package until injected elsewhere."""
        from .disagg import handoff as _handoff_mod
        self._refuse_handoff()
        self._land()        # the package carries the request's tokens
        return _handoff_mod.extract(self, slot)

    def inject_handoff(self, pkg) -> bool:
        """Admit a prefilled request arriving from another engine:
        blocks whose prefix chain keys are already resident map
        copy-free (refcounts and keys transfer with the blocks), the
        rest are scattered into freshly allocated blocks, and the
        request continues decoding here mid-stream.  False when
        capacity is lacking (the router parks the handoff)."""
        from .disagg import handoff as _handoff_mod
        self._refuse_handoff()
        return _handoff_mod.inject(self, pkg)

    def _refuse_handoff(self) -> None:
        """A handoff package carries KV blocks and nothing else: for a
        model with side state beside them (serve/slots.py) the
        receiving engine would decode from the wrong state.
        ``disagg.build_pools`` refuses such a model when a tier is
        built."""
        if self.pool.slot_state is not None:
            raise NotImplementedError(
                f"{type(self.model).__name__} keeps state beside its KV "
                f"blocks, which the disaggregated handoff does not carry")

    # -- submission --------------------------------------------------------
    def submit(self, prompt_ids, *, max_new_tokens: int,
               deadline_s: Optional[float] = None,
               eos_id: Optional[int] = None,
               on_token=None,
               trace_id: Optional[str] = None) -> RequestHandle:
        """Queue one generation request; returns its handle.

        Raises :class:`QueueFull` when admission control refuses the
        request — the wait queue is at capacity.  Admission out of the
        queue into slots happens only at ``step()`` boundaries, so a
        burst of more than ``max_queue`` un-stepped submissions is
        rejected even while slots are free (size ``max_queue`` for the
        largest burst to absorb; default ``2 * num_slots``).  Raises
        ``ValueError`` when the request cannot ever fit the arena
        (prompt + budget past ``max_len`` — the guarantee that decode
        never writes past a request's block budget is enforced here, at
        the door; chunked prefill itself has no separate prompt cap).
        Raises :class:`EngineClosed` while draining or after
        ``close()``."""
        if self._closed:
            raise EngineClosed("submit() on a closed engine")
        if self._draining:
            raise EngineClosed(
                "engine is draining — new submissions are refused while "
                "in-flight requests complete")
        req = Request(prompt_ids, max_new_tokens, deadline_s, eos_id,
                      on_token)
        # one trace per request (ISSUE 11): every event the engine emits
        # about this request — admission, prefix hit, prefill chunks,
        # first token, decode deliveries, preemption, quarantine,
        # finish/shed/evict — carries this id, so the whole request is
        # reconstructable as a single trace (handle.trace_id).  A
        # caller-supplied ``trace_id`` (the disaggregated Router) keeps
        # ONE id alive across every worker the request touches, which
        # is what makes the cross-worker timeline a single trace.
        req.trace_id = trace_id or f"{self.run_id}/r{req.rid}"
        p = req.prompt.size
        # a speculative engine needs spec_k tokens of arena headroom:
        # the request's LAST verify round may still write a full
        # k+1-position window past its final accepted token, and those
        # writes must stay inside the slot's dense view
        if p + req.max_new_tokens + self.spec_k > self.pool.max_len:
            k_note = (f" + spec_k ({self.spec_k})" if self.spec_k
                      else "")
            raise ValueError(
                f"prompt ({p}) + max_new_tokens ({req.max_new_tokens})"
                f"{k_note} = {p + req.max_new_tokens + self.spec_k} "
                f"exceeds max_len ({self.pool.max_len})")
        with obs_trace.activate(req.trace_id):
            try:
                self.sched.offer(req)
            except QueueFull:
                self.metrics.on_reject()
                raise
            self.metrics.on_submit()
        return req.handle

    def resubmit(self, prompt_ids, tokens, *, max_new_tokens: int,
                 deadline_s: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 trace_id: Optional[str] = None,
                 ttft_s: Optional[float] = None) -> RequestHandle:
        """Re-admit a request that was already in flight SOMEWHERE ELSE
        (a drained or dead worker in the multi-process tier): the
        replay re-route primitive across process boundaries.  The
        request re-enters at the HEAD of the queue with its generated
        ``tokens`` pre-installed, so the next admission re-prefills
        prompt + tokens and greedy replay idempotence continues the
        stream bit-identically — the same machinery ``withdraw()`` +
        ``requeue_front()`` provide in-process, reconstructed here from
        the supervisor's host mirror of the request.  ``ttft_s`` (the
        original first-token latency, when one was already delivered)
        is preserved so a re-route never *improves* a reported TTFT.
        Not counted as a new submission in the run ledger — the request
        was submitted once, on the worker that lost it."""
        if self._closed:
            raise EngineClosed("resubmit() on a closed engine")
        if self._draining:
            raise EngineClosed(
                "engine is draining — new submissions are refused while "
                "in-flight requests complete")
        req = Request(prompt_ids, max_new_tokens, deadline_s, eos_id,
                      None)
        req.tokens = [int(t) for t in tokens]
        req.trace_id = trace_id or f"{self.run_id}/r{req.rid}"
        req.ttft_s = ttft_s
        p = req.prompt.size
        if p + req.max_new_tokens + self.spec_k > self.pool.max_len:
            raise ValueError(
                f"prompt ({p}) + max_new_tokens ({req.max_new_tokens}) "
                f"exceeds max_len ({self.pool.max_len})")
        with obs_trace.activate(req.trace_id):
            self.sched.requeue_front([req])
            self.flight.note("counter", "serve.resubmit", rid=req.rid,
                             replayed=len(req.tokens))
        return req.handle

    # -- the engine loop ---------------------------------------------------
    def step(self, *, decode: bool = True) -> int:
        """One continuous-batching tick: recovery (if requested by the
        hang watchdog) → deadline eviction → overload shedding →
        admission (prefill queued requests into free slots while free
        blocks cover them; the chunks are dispatched, their token is
        not fetched yet) → block-table growth → the dispatch of one
        decode over all active slots, the admitted ones included → the
        LANDING of the admissions' first tokens and of the decode the
        step before dispatched (fetched and delivered:
        :meth:`_decode_tick`).  A tick that ends a request by length
        lands in its own step.  Returns the number of tokens delivered,
        first tokens of admissions included.

        ``decode=False`` lands what is in flight and stops after
        admission, each admission's first token landed at once — the
        disaggregated
        tier's PREFILL-WORKER tick: freshly prefilled requests stay in
        their slots (blocks intact) for the router to hand off to a
        decode worker instead of decoding here.  Deadline eviction
        still applies to parked requests, so a handoff the decode pool
        cannot absorb in time is shed by the same machinery as any
        other overload."""
        if self._closed:
            raise EngineClosed("step() on a closed engine")
        host = self.metrics.host
        # an idle engine that its loop polls opens no turn, and reads
        # neither a clock nor a count for the account
        counted = self.pending or not host.resting
        if counted:
            host.step_in(self.spec_compiled_counts())
        with events.span("serve.step"):
            now = time.monotonic()
            delivered = 0

            with events.span("serve.expire"):
                # 0. hang recovery — the Heartbeat monitor thread can
                #    only REQUEST it; the rebuild must run here, on the
                #    step thread, which owns the arena
                if self._recover_flag.is_set():
                    self._recover_flag.clear()
                    self._recover("heartbeat")

                # 1. deadline eviction — queued requests that died
                #    waiting and running requests past their deadline
                #    vacate first, so their slots/blocks are admittable
                #    this same tick
                for req in self.sched.expire_queued(now):
                    with obs_trace.activate(req.trace_id):
                        self.metrics.on_evict("deadline")
                for slot in [s for s, r in self._running.items()
                             if r.expired(now)]:
                    req = self._running[slot]
                    req.finish_reason = "deadline"
                    self._finalize(slot, evicted=True)

                # 1b. deadline-aware overload shedding — queued requests
                #     that cannot plausibly deliver a first token before
                #     their deadline are shed before burning a prefill
                for req in self.sched.shed_overload(now,
                                                    self._eta_first_token):
                    with obs_trace.activate(req.trace_id):
                        self.metrics.on_evict("shed")

            # 2. admission — prefill into free slots between decode
            #    steps.  A slot row is not enough: the head-of-queue
            #    request must also be coverable by free + evictable
            #    blocks (FIFO: a too-big head blocks the line rather
            #    than being overtaken).  The first token lands behind
            #    the plain tick this step dispatches, if it does
            behind = decode and self._verify is None
            while self.pool.free_count:
                with events.span("serve.admit.probe"):
                    req = self.sched.peek()
                    if req is None or not self._admittable(req):
                        break
                    self.sched.pop_for_admission()
                delivered += self._admit(req, behind)

            # 3. block-table growth + one decode tick over the whole
            #    arena, dispatched BEFORE the admissions' first tokens
            #    and the tick in flight land; a decode (or a decode-time
            #    block allocation, or a landing's fetch: a chunk or a
            #    tick that died on the device surfaces there) that died
            #    past its retry budget escalates to an arena rebuild +
            #    re-prefill instead of crashing the engine
            try:
                if self._running and decode:
                    with events.span("serve.grow"):
                        self._ensure_blocks()
                if self._running and decode:
                    delivered += (self._spec_tick()
                                  if self._verify is not None
                                  else self._decode_tick())
                else:
                    # nothing to dispatch behind it
                    delivered += self._land()
            except (RuntimeError, OSError) as e:
                if isinstance(e, failure.FailureDetected):
                    raise
                self._recover(f"decode: {type(e).__name__}: {e}")

            with events.span("serve.step.tail"):
                # settle spill payloads onto host numpy AFTER the
                # landing's token-extraction sync: the copies this step
                # queued stand before the tick it dispatched, so this
                # collects without waiting out that tick, and
                # device-side spill buffers live at most one tick
                if self._spill is not None:
                    self._spill.settle()

                self.metrics.on_step(self.sched.depth,
                                     self.pool.active_count,
                                     self.pool.blocks_in_use,
                                     self.pool.blocks_in_use_bytes)
                dt = time.monotonic() - now
                self._tick_ewma = dt if self._tick_ewma is None else \
                    0.8 * self._tick_ewma + 0.2 * dt
        if counted:
            host.step_out(None if self.pending
                          else self.spec_compiled_counts())
        return delivered

    def _eta_first_token(self, position: int) -> float:
        """Seconds until the queued request at ``position`` could
        plausibly deliver its first token — delegates to the shared
        :func:`scheduler.eta_first_token` model with this engine's
        admission period: the slower of the measured tick EWMA and the
        external ``tick_hint_s`` a multi-pool driver (the disaggregated
        Router) pushes, so a worker that only gets one admission
        opportunity per router round sheds against the ROUND cadence,
        not its own optimistic step time.  0.0 before any timing
        evidence exists — shedding never fires blind."""
        tick = self._tick_ewma
        if self.tick_hint_s:
            tick = (self.tick_hint_s if tick is None
                    else max(tick, self.tick_hint_s))
        if tick is None:
            return 0.0
        return eta_first_token(position, free_slots=self.pool.free_count,
                               wave_size=self.pool.num_slots, tick_s=tick,
                               tokens_per_tick=self._tpt_ewma or 1.0)

    def run_until_idle(self, max_steps: Optional[int] = None) -> None:
        """Drive ``step()`` until no request is queued or running.  With
        ``heartbeat_timeout_s`` set, a Heartbeat watchdog guards every
        tick — a hung decode (dead device, hung collective) aborts cleanly
        instead of wedging the server, or, with ``recover_on_hang``,
        requests an arena rebuild + re-prefill at the next step
        boundary.  Returns with no tick in flight."""
        hb = Heartbeat(timeout=self.heartbeat_timeout_s,
                       on_failure=(self._hb_failure if self.recover_on_hang
                                   else self._on_failure)) \
            if self.heartbeat_timeout_s else None
        n = 0
        # a loop of its own: what the caller did since the last step is
        # no turn of it (metrics.HostAccount)
        self.metrics.host.rest()
        with hb if hb is not None else nullcontext():
            while self.pending:
                self.step()
                n += 1
                if hb is not None:
                    hb.beat(n)
                    if hb.fired and self.recover_on_hang:
                        # the monitor thread exits after firing once;
                        # re-arm it so a later hang in this same drive
                        # is also caught
                        hb.stop()
                        hb.start()
                if max_steps is not None and n >= max_steps:
                    break
            # the last tick: nothing is dispatched behind it
            self._land()
        if not self.pending:
            # a fully drained system is proof the last recovery took —
            # give future incidents a fresh rebuild budget, and drop any
            # rebuild REQUEST a hang on the final tick left behind (the
            # late decode still delivered everything; rebuilding a
            # healthy idle arena at the next drive's first step would
            # burn recovery budget and record a bogus incident)
            self._recoveries = 0
            self._recover_flag.clear()

    def drain(self, max_steps: Optional[int] = None) -> None:
        """Stop accepting new requests and complete everything already
        in the system: queued requests still get admitted, in-flight
        slots decode to completion (or eviction).  ``submit()`` raises
        :class:`EngineClosed` from the moment drain begins — draining is
        one-way, the step before :meth:`close`.  Safe to call
        repeatedly."""
        self._draining = True
        self.run_until_idle(max_steps=max_steps)

    def close(self) -> None:
        """``drain()`` to idle, then release the engine: the arena and
        token buffer are dropped (freeing device memory) and every
        subsequent ``submit()``/``step()`` raises :class:`EngineClosed`.
        Idempotent."""
        if self._closed:
            return
        self.drain()
        self._closed = True
        self.pool = None
        self._toks = None

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # -- internals ---------------------------------------------------------
    def _wire_spill(self) -> None:
        """Point the pool's spill-tier callbacks at this engine: spill/
        prefetch accounting lands in the metrics, and an injected
        ``serve.spill`` fault produces a flight dump + incident record
        (the fault itself only DEGRADES — the block dies or the prefix
        re-prefills, streams are unchanged — but the evidence trail
        must still exist)."""
        if self.pool.spill is None:
            return
        self.pool.on_spill = self.metrics.on_spill
        self.pool.on_prefetch = self.metrics.on_prefetch
        self.pool.on_spill_fault = self._spill_fault

    def _spill_fault(self, op: str, exc: Exception) -> None:
        ref = self._flight_dump("serve.spill",
                                f"{op} fault: {type(exc).__name__}")
        self._incident("serve.spill", type(exc).__name__, f"op:{op}",
                       "degraded", 0, flight_ref=ref)

    def _dispatch(self, site: str, fn, args, **attrs):
        """One guarded jitted dispatch: the injection site fires first
        (host-side, BEFORE the call — the donated arena is still
        intact), and transient RuntimeError/OSError is retried with
        bounded exponential backoff.  Retry scope mirrors
        ``train.loop``: sound for dispatch-level transients (a failure
        before launch, injected faults); a REAL mid-execution
        failure invalidates the donated arena, so retries fail too and
        the error escalates to the caller — quarantine for prefill,
        arena recovery for decode."""
        attempt = 0
        # one of the engine's two kinds of runtime call, retries and
        # their backoff included (metrics.HostAccount): the site
        # "serve.decode" is the call "decode.dispatch"
        call = site.removeprefix("serve.") + ".dispatch"
        host = self.metrics.host
        host.call_in(call)
        try:
            while True:
                try:
                    faults.fire(site, attempt=attempt, **attrs)
                    return fn(*args)
                except (RuntimeError, OSError) as e:
                    if isinstance(e, failure.FailureDetected):
                        raise
                    if attempt >= self.max_dispatch_retries:
                        raise
                    delay = min(self.backoff_max,
                                self.backoff_base * (2 ** attempt))
                    attempt += 1
                    self.metrics.on_retry(site)
                    self._sleep(delay)
        finally:
            host.call_out(call)

    # -- paged-arena bookkeeping -------------------------------------------
    def _share_limit(self, req: Request) -> int:
        """How many leading blocks of this request's replay are
        ELIGIBLE for prefix sharing: full blocks wholly inside the
        ORIGINAL prompt (generated tokens are private), and never the
        whole replay — at least one suffix token must run prefill so
        the request has last-position logits to pick its first token
        from."""
        if not self.share_prefix:
            return 0
        bs = self.pool.block_size
        return min(req.prompt.size // bs,
                   (req.replay_ids().size - 1) // bs)

    def _blocks_needed(self, req: Request, n_shared: int) -> int:
        """Fresh blocks an admission must allocate: coverage for the
        replay plus the first decode position, minus the shared
        prefix."""
        replay = req.replay_ids().size
        return replay // self.pool.block_size + 1 - n_shared

    def _req_keys(self, req: Request) -> list:
        """The request's prefix chain keys, computed once (they depend
        only on the immutable prompt) — a head-of-queue request waiting
        on free blocks is probed every step and must not re-hash its
        whole prefix each time."""
        if not self.share_prefix:
            return []
        if req.prefix_keys is None:
            req.prefix_keys = self.pool.prefix_keys(
                req.prompt, req.prompt.size // self.pool.block_size)
        return req.prefix_keys

    def _admittable(self, req: Request) -> bool:
        n_shared, n_lru = self.pool.probe_prefix(
            req.prompt, self._share_limit(req), keys=self._req_keys(req))
        # claiming shared blocks out of the evictable LRU consumes
        # available_blocks too — only what remains can cover the fresh
        # allocation
        return (self.pool.available_blocks - n_lru
                >= self._blocks_needed(req, n_shared))

    def _alloc_blocks(self, n: int, rid: int) -> Optional[List[int]]:
        """Claim ``n`` blocks through the ``serve.block_alloc``
        injection site (fires BEFORE the host-side allocation, so an
        injected error leaves refcounts untouched).  Returns None when
        the pool genuinely cannot cover ``n`` — the preemption cue."""
        faults.fire("serve.block_alloc", n=n, rid=rid)
        return self.pool.alloc_blocks(n)

    def _admit(self, req: Request, behind: bool) -> int:
        # the whole admission — block claim, prefix hit, prefill chunks,
        # quarantine on failure, the first token where it lands at once
        # — runs under the request's trace, so each of those events
        # carries its id
        with obs_trace.activate(req.trace_id), events.span("serve.admit"):
            return self._admit_traced(req, behind)

    def _admit_traced(self, req: Request, behind: bool) -> int:
        """Claim, prefill and activate ``req``.  ``behind``: this step
        dispatches a plain decode tick after its admissions, and the
        first token is fetched behind it (:meth:`_land_first`) unless
        it is known to end the request by length: such a request takes
        part in no tick and its slot is free for the next admission of
        this step.  Returns the tokens delivered: 1 at once, 0 pending
        or quarantined."""
        slot = self.pool.alloc_slot()
        assert slot is not None, "admission with no free slot"
        # replay_ids == prompt for a fresh request; for a request
        # re-admitted by preemption or arena recovery it is prompt +
        # tokens-so-far, whose greedy prefill pick IS the next decode
        # token — the re-prefill is idempotent
        replay = req.replay_ids()
        P = replay.size
        bs = self.pool.block_size
        at_once = not behind or req.max_new_tokens - len(req.tokens) <= 1
        tok = None
        owned: List[int] = []
        shared_ids: List[int] = []
        mapped = False
        by_snapshot = self.pool.snapshots is not None
        src = dst = snap_key = plan = None
        # the allocation site fires once (no retry loop); only the
        # prefill dispatches below go through _dispatch's backoff —
        # quarantine must attribute the failure to the seam that died
        fail_site, fail_attempts = "serve.block_alloc", 1
        try:
            with events.span("serve.admit.claim"):
                n_shared, shared_ids = self.pool.match_prefix(
                    req.prompt, self._share_limit(req),
                    keys=self._req_keys(req))
                owned = self._alloc_blocks(
                    self._blocks_needed(req, n_shared), req.rid) or []
                if len(owned) < self._blocks_needed(req, n_shared):
                    # _admittable() held when we were popped and nothing
                    # ran since — an all-or-nothing alloc can only come
                    # up short through a bug; fail THIS request loudly
                    raise RuntimeError("block allocation came up short")
                fail_site = "serve.prefill"
                fail_attempts = self.max_dispatch_retries + 1
                self.pool.map_slot(slot, shared_ids + owned)
                mapped = True
            # blocks below ``keep`` are mapped and stay as they are; the
            # prefill starts there, or, for a model whose state lives in
            # snapshots (serve/slots.py), after the deepest block that
            # has one, and recomputes the rows between
            keep = start0 = n_shared * bs
            if by_snapshot:
                keys = self._req_keys(req)
                m, src = self.pool.match_snapshot(keys, n_shared)
                start0 = m * bs
                if n_shared > m:
                    snap_key = keys[n_shared - 1]
                    dst, evicted = self.pool.claim_snapshot(snap_key)
                    self.metrics.on_snapshot_miss(keep - start0, evicted)
                if src is not None:
                    self.metrics.on_snapshot_hit()
            if start0:
                self.metrics.on_prefix_hit(start0)
                if self.pool.tails is not None:
                    self.metrics.on_state_resume(self.pool.tail_blocks)
            C = self._chunk
            view = self.pool.max_blocks * bs
            # no row changes between this admission's chunks
            tables = self.pool.tables_snapshot()
            with events.span("serve.prefill", slot=slot, prompt=P,
                             shared=start0, chunks=-(-(P - start0) // C)):
                for first_row in range(start0, P, C):
                    with events.span("serve.prefill.stage"):
                        # the program writes all C rows at [start,
                        # start + C) whatever is valid, and
                        # dynamic_update_slice clamps silently: a chunk
                        # that would cross the view's end starts early
                        # instead and recomputes the tokens below
                        # ``fresh``, whose blocks the scatter leaves be
                        # (a chunk entering from a carried state cannot
                        # start early: its program widens the view)
                        fresh = max(first_row, keep)
                        start = first_row if by_snapshot \
                            else min(first_row, view - C)
                        if by_snapshot:
                            here = dst is not None \
                                and start < keep <= start + C
                            plan = np.array(
                                [_OWN if first_row > start0 else
                                 _ZEROS if src is None else src,
                                 dst if here else -1,
                                 keep - 1 - start if here else 0], np.int32)
                        ids = np.zeros((1, C), np.int32)
                        chunk = replay[start:start + C]
                        ids[0, :chunk.size] = chunk
                        # host values of the programs' dtypes: the
                        # dispatch itself transfers them, no eager
                        # program stages anything
                        staged = (ids, np.int32(start),
                                  np.int32(chunk.size - 1),
                                  np.int32(slot), np.int32(fresh))
                    with events.span("serve.prefill.dispatch"):
                        if self._verify is not None:
                            # spec engine: the ONE prefill program
                            # writes the chunk into BOTH arenas (target
                            # + draft)
                            (self._toks, self.pool.caches,
                             self.pool.draft_caches) = self._dispatch(
                                "serve.prefill", self._prefill,
                                (self._params, self._buffers,
                                 self._dparams, self._dbuffers, *staged,
                                 tables, self._toks,
                                 self.pool.caches, self.pool.draft_caches),
                                rid=req.rid)
                        else:
                            (self._toks, self.pool.caches,
                             self.pool.slot_state, self.pool.tails,
                             self.pool.snapshots) = self._dispatch(
                                "serve.prefill", self._prefill,
                                (self._params, self._buffers, *staged,
                                 tables, self._toks, self.pool.caches,
                                 self.pool.slot_state, self.pool.tails,
                                 self.pool.snapshots, plan),
                                rid=req.rid)
                        self.metrics.on_prefill_chunk(
                            max(0, start + chunk.size - fresh))
                        if self._moe_top_k:
                            self.metrics.on_moe_dispatch(
                                chunk.size * self._moe_top_k)
                if at_once:
                    tok = self._fetch_first(self._toks, slot, behind=False)
        except (RuntimeError, OSError) as e:
            if isinstance(e, failure.FailureDetected):
                raise
            # the injected/transient failure fired before a dispatch
            # touched anything irreversible: unwind this request's
            # claims (refcounts included) and fail only THIS request,
            # not the engine
            if mapped:
                self.pool.release(slot)
            else:
                self.pool.unref_shared(shared_ids)
                self.pool.free_blocks(owned)
                self.pool.release_slot_row(slot)
            self.pool.settle_snapshots(snap_key, dst, written=False)
            self._quarantine(req, e, fail_site, fail_attempts)
            return 0
        with events.span("serve.admit.finish"):
            self.pool.settle_snapshots(snap_key, dst, written=True)
            if dst is not None:
                self.metrics.on_snapshot_write()
            if self.share_prefix:
                self.pool.register_prefix(req.prompt, slot,
                                          req.prompt.size // bs,
                                          keys=self._req_keys(req))
            self.pool.activate(slot, P)
            req.slot = slot
            req.state = RUNNING
            self._running[slot] = req
            if at_once:
                self._deliver_first(slot, req, tok)
                return 1
        # the tick reads the token where the chunk wrote it, on the
        # device: the host fetches this array once the tick is dispatched
        self._first.append((self._toks, slot, req,
                            self.metrics.decode_ticks))
        return 0

    def _fetch_first(self, arr, slot: int, behind: bool) -> int:
        """The blocking fetch of the token an admission's last chunk
        picked, out of the token array that chunk returned.  ``behind``:
        a decode tick was dispatched after the chunk and is not fetched
        yet, so the chip has work while the host waits and after;
        otherwise the chunk was the newest program and the device holds
        nothing once its token is here, a tick dispatched before it
        included (metrics.HostAccount)."""
        host = self.metrics.host
        with events.span("serve.prefill.fetch"):
            host.call_in("prefill.fetch")
            try:
                return int(np.asarray(arr)[slot])  # singalint: disable=SGL008 the designed per-admission sync: one num_slots-int fetch delivers the prefill token, behind the step's tick where there is one
            finally:
                host.call_out("prefill.fetch", None if behind else "admit")

    def _deliver_first(self, slot: int, req: Request, tok: int) -> None:
        """Hand a request the token its prefill picked: its first, or
        for a request re-admitted by pre-emption or recovery the next of
        its stream."""
        first = not req.tokens
        if first:
            # preemption/recovery re-prefills count under their own
            # counters, not here — ``admitted`` stays comparable to
            # ``submitted``, also for a request pre-empted or rebuilt
            # before its first token landed, which is why it is counted
            # with the token and not with the chunks
            self.metrics.on_admit()
        done = req.deliver(tok)
        self.metrics.on_deliver(req.rid, len(req.tokens))
        if first:
            self.metrics.on_first_token(req.ttft_s)
        if req.on_token is not None:
            req.on_token(tok, req.handle)
        if done:
            self._finalize(slot)

    def _land_first(self) -> int:
        """Fetch and deliver the first tokens that this step's
        admissions left pending, oldest admission first.  An admission
        whose slot no longer runs that request gets nothing (as a tick's
        participant, :meth:`_land`): ``_ensure_blocks`` pre-empted it,
        the youngest, or it was withdrawn or rebuilt; it replays from
        its prompt.  An EOS here behaves as one from a run-ahead tick:
        the tick already dispatched wrote one row into the request's own
        block and picked a token nobody gets."""
        delivered = 0
        while self._first:
            arr, slot, req, ticks = self._first.pop(0)
            if self._running.get(slot) is not req:
                continue
            behind = self.metrics.decode_ticks > ticks
            with obs_trace.activate(req.trace_id):
                tok = self._fetch_first(arr, slot, behind)
                if behind:
                    self.metrics.on_first_token_behind_tick()
                with events.span("serve.deliver"):
                    self._deliver_first(slot, req, tok)
            delivered += 1
        return delivered

    def _quarantine(self, req: Request, err: Exception,
                    site: str = "serve.prefill",
                    attempts: Optional[int] = None) -> None:
        """Repeatedly-poisoned prefill/admission: surface a per-request
        failure status (handle.failed / handle.error), never an engine
        crash.  ``site``/``attempts`` name the seam that actually died
        (block allocation fires once; prefill retries with backoff) so
        the incident record stays honest evidence."""
        if attempts is None:
            attempts = self.max_dispatch_retries + 1
        req.state = FAILED
        req.finish_reason = "quarantined"
        req.error = (f"{site} failed after {attempts} attempt(s): "
                     f"{type(err).__name__}: {err}")
        self.metrics.on_quarantine()
        ref = self._flight_dump(site, f"quarantine req:{req.rid}")
        self._incident(site, type(err).__name__,
                       f"req:{req.rid}", "quarantined", attempts,
                       flight_ref=ref)
        warnings.warn(f"serve: request {req.rid} quarantined: "
                      f"{req.error}", stacklevel=2)

    def _ensure_blocks(self) -> None:
        """Decode-time growth: before the tick, every running slot
        whose next write position crosses into an unmapped block gets
        one more block — preempting the youngest running request when
        the pool is exhausted (its blocks are released, it re-queues at
        the head and replays later; greedy decode keeps its stream
        bit-identical)."""
        for slot in sorted(self._running):
            req = self._running.get(slot)
            if req is None:
                continue
            bs = self.pool.block_size
            # the slot's position is prompt + tokens delivered - 1,
            # plus 1 with a tick in flight, whose token is not
            # delivered yet: the tick about to be dispatched writes
            # there, and the row after it is kept mapped too.  A verify
            # round writes up to position pos + spec_k (the full k+1
            # window), so a speculative slot needs its blocks mapped
            # spec_k positions ahead of a plain one
            need = (int(self.pool.pos[slot]) + 1 + self.spec_k) // bs + 1
            while slot in self._running and \
                    self.pool.mapped_count(slot) < need:
                got = self._alloc_blocks(1, req.rid)
                if got:
                    self.pool.append_block(slot, got[0])
                else:
                    self._preempt_youngest()

    def _preempt_youngest(self) -> None:
        victim_slot = max(self._running,
                          key=lambda s: self._running[s].rid)
        req = self._running.pop(victim_slot)
        self.pool.release(victim_slot)
        req.state = QUEUED
        req.slot = None
        self.sched.requeue_front([req])
        with obs_trace.activate(req.trace_id):
            self.metrics.on_preempt()

    def _decode_tick(self) -> int:
        """Dispatch decode tick N, then land the first tokens of this
        step's admissions and tick N-1 (:meth:`_land`): the chip holds N
        while the host fetches and delivers them, ends the step, does
        the caller's work and starts the next step.  A tick the host
        already knows to end a request BY LENGTH (the first token just
        landed counted) lands
        here too, and the step returns with nothing in flight: the
        successor the caller then submits finds an empty device queue
        for its prefill, as it did before ticks ran ahead.  So does the
        plain tick a speculative engine falls back to, whose next
        verify round reads the positions."""
        pool = self.pool
        # what this tick's attention has to read, from the host's own
        # slot state (no device read): each active slot's blocks up to
        # the position it writes, against every slot's whole table row
        self.metrics.on_decode_kv(
            int((pool.pos[pool.active] // pool.block_size + 1).sum()),
            pool.num_slots * pool.max_blocks)
        if pool.snapshots is not None:
            # what the tick's state updates read and write: each
            # running slot's state once each way
            self.metrics.on_ssm_state(
                2 * len(self._running) * pool.slot_state_bytes)
        with events.span("serve.decode", active=len(self._running)):
            with events.span("serve.decode.dispatch"):
                t0 = time.perf_counter()
                (self._toks, self.pool.caches,
                 self.pool.slot_state) = self._dispatch(
                    "serve.decode", self._decode,
                    (self._params, self._buffers, self._toks,
                     *self.pool.snapshot(), self.pool.caches,
                     self.pool.slot_state),
                    active=len(self._running))
                self.pool.advance(1)
                if self._moe_top_k:
                    self.metrics.on_moe_dispatch(
                        len(self._running) * self._moe_top_k)
                self.metrics.on_decode_tick(ahead=bool(self._flying))
                # ``toks`` is not donated: this tick's array stays
                # readable after the next tick was dispatched on it
                pairs = list(self._running.items())
                self._flying.append((self._toks, pairs, t0))
        delivered = self._land(keep=1)
        if self._verify is not None:
            delivered += self._land()
        elif any(self._running.get(slot) is req
                 and len(req.tokens) + 1 >= req.max_new_tokens
                 for slot, req in pairs):
            delivered += self._land(cause="finish")
        return delivered

    def _land(self, keep: int = 0, cause: str = "other") -> int:
        """Fetch and deliver the first tokens pending
        (:meth:`_land_first`: they are older than the tick dispatched
        behind them, and a tick's test of a finish by length counts
        them), then the ticks in flight, oldest first, but for
        the newest ``keep``; ``cause``: what the host's account
        (metrics.HostAccount) is to call the empty device queue that the
        last of them leaves.  A participant whose slot no longer runs
        that same request gets nothing: it was evicted by deadline,
        pre-empted, withdrawn or rebuilt since the dispatch (it replays
        from prompt + tokens delivered, and greedy decode picks the
        dropped token again), or the landing before this one handed it
        its EOS (the run-ahead tick wrote one row past it into the
        request's own block, and nothing is delivered after an EOS).
        A tick none of whose participants is left is dropped unfetched.
        Returns the number of tokens delivered."""
        delivered = self._land_first()
        host = self.metrics.host
        while len(self._flying) > keep:
            arr, pairs, t0 = self._flying.pop(0)
            pairs = [(slot, req) for slot, req in pairs
                     if self._running.get(slot) is req]
            if not pairs:
                continue
            with events.span("serve.decode.fetch"):
                host.call_in("decode.fetch")
                try:
                    toks = np.asarray(arr)    # singalint: disable=SGL008 the designed per-tick sync: ONE num_slots-int fetch per decode dispatch is the engine's hot-loop host traffic, and the chip holds the next tick while a run-ahead tick's is made
                finally:
                    host.call_out("decode.fetch",
                                  None if self._flying else cause)
            # the time between two landings; from its own dispatch for a
            # tick dispatched with nothing in flight
            now = time.perf_counter()
            dt = now - max(self._landed_at, t0)
            self._landed_at = now
            with events.span("serve.deliver"):
                for slot, req in pairs:
                    tok = int(toks[slot])
                    # one batched decode dispatch delivers to many
                    # requests; the per-request section runs under each
                    # request's trace so its token events attribute
                    # correctly
                    with obs_trace.activate(req.trace_id):
                        done = req.deliver(tok)
                        self.metrics.on_token(dt)
                        self.metrics.on_deliver(req.rid, len(req.tokens))
                        self.metrics.on_slot_dispatch(1)
                    if req.on_token is not None:
                        req.on_token(tok, req.handle)
                    if done:
                        self._finalize(slot)
                self._note_tpt(len(pairs), len(pairs))
            delivered += len(pairs)
        return delivered

    def _spec_tick(self) -> int:
        """One speculative verify round (serve/spec.py) — with a
        PLAIN-DECODE fallback when the verify DISPATCH dies past its
        retry budget (injected ``serve.verify`` faults included): one
        target-correct token per slot still lands this tick, the
        accepted stream is unchanged (plain decode is the same target
        argmax), and only the draft cache takes a gap at the fallback
        position — a later accept-rate cost, never a correctness one.
        Only :class:`~singa_tpu.serve.spec.VerifyDispatchFailed` takes
        this path — nothing was committed yet, so a plain tick on the
        untouched arena is safe.  A failure AFTER the dispatch (result
        fetch, delivery) is half-committed and propagates to step()'s
        arena-recovery handler instead, as does a fallback tick that
        ALSO fails."""
        from . import spec as spec_mod
        participants = len(self._running)
        try:
            delivered = spec_mod.verify_round(self)
        except spec_mod.VerifyDispatchFailed as e:
            self.metrics.on_spec_fallback()
            warnings.warn(
                f"serve: verify round failed past retries "
                f"({type(e).__name__}: {e}); falling back to plain "
                f"decode for this tick", stacklevel=2)
            return self._decode_tick()
        self._note_tpt(delivered, participants)
        return delivered

    def _note_tpt(self, delivered: int, participants: int) -> None:
        """Fold one tick's accepted-tokens-per-slot into the EWMA the
        shed eta consumes (scheduler.eta_first_token tokens_per_tick)."""
        if not participants:
            return
        tpt = delivered / participants
        self._tpt_ewma = tpt if self._tpt_ewma is None else \
            0.8 * self._tpt_ewma + 0.2 * tpt

    def _finalize(self, slot: int, evicted: bool = False) -> None:
        req = self._running.pop(slot)
        self.pool.release(slot)
        req.state = EVICTED if evicted else FINISHED
        with obs_trace.activate(req.trace_id):
            self.metrics.on_evict(req.finish_reason or "unknown")

    # -- recovery ----------------------------------------------------------
    def recover(self, reason: str = "requested") -> None:
        """Rebuild the arena — fresh block pool, block tables,
        refcounts, empty prefix cache — and re-prefill every in-flight
        request; the path behind Heartbeat hang detection, also
        callable directly after an external device event.  Each running
        request is requeued at the HEAD of the queue and re-prefilled
        from ``prompt + tokens-so-far``; greedy decode makes that
        replay idempotent, so however many times recovery runs, the
        final streams are bit-identical to an uninterrupted run.
        (Chunked prefill has no prompt-length cap below ``max_len``, so
        — unlike the PR 2 fixed arena — every in-flight replay is
        recoverable.)  Lands the tick in flight first, if the device
        still yields it; the rebuild drops what it does not."""
        try:
            self._land()
        except (RuntimeError, OSError) as e:
            if isinstance(e, failure.FailureDetected):
                raise
        self._recover(reason)

    def _recover(self, reason: str) -> None:
        self._recoveries += 1
        if self._recoveries > self.max_recoveries:
            raise RuntimeError(
                f"serve engine exceeded max_recoveries="
                f"{self.max_recoveries} (last reason: {reason}) — the "
                f"fault is not transient; surfacing it instead of "
                f"rebuilding forever")
        with events.span("serve.recover", reason=reason):
            inflight = sorted(self._running.values(), key=lambda r: r.rid)
            self._running.clear()
            # a tick in flight read the old arena, a pending first
            # token was written into it: they are dropped, and the
            # replays pick them again
            self._flying.clear()
            self._first.clear()
            # the device holds nothing the engine will fetch
            self.metrics.host.call_out("recover", "other")
            # fresh arena + tables + token buffer: same shapes/dtypes,
            # so the two compiled programs are reused — recovery never
            # recompiles.  The prefix cache dies with the old pool
            # (its blocks' contents are gone); re-prefills rebuild
            # tables and refcounts from scratch.
            # ... except what already SPILLED: the store is content-
            # addressed (chain keys), so its host-side payloads stay
            # valid for the fresh arena and survive the rebuild
            self.pool = BlockPool(self.model, self._num_slots,
                                  self._max_len,
                                  block_size=self._block_size,
                                  num_blocks=self._num_blocks,
                                  dtype=self._arena_dtype,
                                  draft_model=self.draft_model,
                                  kv_dtype=self._kv_dtype,
                                  draft_kv_dtype=self._draft_kv_dtype,
                                  spill=self._spill)
            self._wire_spill()
            self._toks = jnp.zeros((self._num_slots,), jnp.int32)
            requeue = []
            for req in inflight:
                if req.replay_ids().size >= self.pool.max_len:
                    # defensive: unreachable while submit() enforces
                    # prompt + budget <= max_len, but a replay that
                    # could never decode again must fail loudly, not
                    # silently truncate
                    req.state = FAILED
                    req.finish_reason = "unrecoverable"
                    req.error = (
                        f"cannot re-prefill after arena rebuild: prompt "
                        f"+ generated = {req.replay_ids().size} tokens "
                        f"leaves no room to decode under max_len "
                        f"({self.pool.max_len})")
                    # the request's terminal event must carry its trace
                    # like every other evict site — THIS request is the
                    # one the incident postmortem is about
                    with obs_trace.activate(req.trace_id):
                        self.metrics.on_evict("unrecoverable")
                        self._incident(
                            "serve.arena", reason, f"req:{req.rid}",
                            "unrecoverable", 0,
                            flight_ref=self._flight_dump(
                                "serve.arena",
                                f"unrecoverable req:{req.rid}"))
                else:
                    requeue.append(req)
            self.sched.requeue_front(requeue)
            self.metrics.on_recover(len(requeue))
            self._incident("serve.arena", reason,
                           f"inflight:{len(requeue)}", "recovered",
                           self._recoveries,
                           flight_ref=self._flight_dump(
                               "serve.arena", f"recovery: {reason}"))

    def _hb_failure(self, age: float, last_beat: int) -> None:
        """Heartbeat monitor-thread path (``recover_on_hang``): only
        REQUEST recovery — the step thread owns the arena and performs
        the rebuild at its next step boundary (a hung dispatch cannot be
        preempted from here anyway; an injected hang simply returns
        late).  A user ``on_failure`` still gets the observation."""
        events.counter("serve.hangs", 1, age_s=round(age, 3))
        # monitor thread: deliberately trace-less (the hang is an
        # engine-level observation, not any one request's)
        self.flight.note("counter", "serve.hangs", age_s=round(age, 3))
        self._recover_flag.set()
        if self._on_failure is not None:
            self._on_failure(age, last_beat)

    # -- durable incident records + flight dumps --------------------------
    def _flight_dump(self, site: str, reason: str) -> Optional[str]:
        """Dump the flight ring next to the record store and return the
        ``flight_ref`` (or None without a store) — the shared
        :func:`obs.flight.dump_for_store` contract; this thin wrapper
        exists so literal sites at call sites stay SGL009-checkable."""
        return obs_flight.dump_for_store(self.flight, site,
                                         self.record_store, reason)

    def _incident(self, site: str, fault: str, ref, outcome: str,
                  retries: int, flight_ref: Optional[str] = None) -> None:
        """Append one ``incident`` entry to the run-record store (when
        ``record_store`` is set).  Best-effort: the record is evidence,
        not a dependency — a full disk must not turn a survived fault
        into a crash."""
        events.counter("serve.incident", 1, site=site, outcome=outcome)
        self.flight.note("counter", "serve.incident", site=site,
                         outcome=outcome)
        if not self.record_store:
            return
        try:
            platform = jax.default_backend()
            dev = jax.devices()[0]
            payload = {"site": site, "fault": fault, "ref": ref,
                       "outcome": outcome, "retries": int(retries),
                       "engine_run": self.run_id}
            if flight_ref:
                payload["flight_ref"] = flight_ref
            entry = obs_record.new_entry(
                "incident", platform, platform != "tpu",
                getattr(dev, "device_kind", "") or platform,
                run_id=f"{self.run_id}-inc{next(self._incident_seq)}",
                payload=payload)
            obs_record.RunRecord(self.record_store).append(entry)
        except Exception as e:
            warnings.warn(f"could not append incident record: "
                          f"{type(e).__name__}: {e}", stacklevel=2)
