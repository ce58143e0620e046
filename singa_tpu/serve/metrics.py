"""Serving telemetry: queue/slot gauges, admission counters, latency
histograms — all through the shared ``obs.events`` layer, so a single
``SINGA_OBS=/path.jsonl`` env var captures training AND serving events
in one stream.

Metric names (documented in docs/serving.md):

==========================  =========  ==================================
name                        kind       meaning
==========================  =========  ==================================
``serve.submitted``         counter    requests accepted by submit()
``serve.admitted``          counter    first prefill into a slot (recovery
                                       re-prefills count under
                                       ``serve.recoveries``)
``serve.rejected``          counter    refused at submit (queue full)
``serve.evicted``           counter    left the system — a slot vacated
                                       (``eos``/``length``/``deadline``)
                                       or a queued request dropped at
                                       its deadline or shed under
                                       overload (``reason`` attr)
``serve.retries``           counter    one transient dispatch failure
                                       retried with backoff (``site``)
``serve.quarantined``       counter    a request the engine gave up on
                                       (failed handle status)
``serve.recoveries``        counter    arena rebuild + re-prefill of
                                       in-flight requests
``serve.preempted``         counter    a running request released its
                                       blocks to an exhausted pool and
                                       re-queued (replayed later,
                                       stream unchanged)
``serve.prefix_hits``       counter    an admission mapped >= 1 resident
                                       shared-prefix block copy-free
``serve.prefix_hit_tokens`` counter    prompt tokens whose prefill was
                                       SKIPPED via the prefix cache
``serve.queue_depth``       gauge      waiting requests, after each step
``serve.active_slots``      gauge      live slots, after each step
``serve.blocks_in_use``     gauge      referenced KV blocks, after each
                                       step (the paged-arena footprint)
``serve.blocks_in_use_bytes``  gauge   HBM bytes those blocks pin —
                                       target + draft arenas, int8
                                       codes AND f32 scale tensors
                                       (block counts alone under-report
                                       a quantized/speculative arena)
``serve.spilled_blocks``    counter    evicted prefix blocks whose
                                       bytes landed in the host-RAM
                                       spill tier instead of dying
``serve.prefetch_hits``     counter    spilled blocks restored into the
                                       arena on a prefix hit (one per
                                       restored block)
``serve.prefetch_wait_ms``  histogram  host-side restore orchestration
                                       per prefetched block (the copy
                                       itself rides JAX async dispatch)
``serve.step``              span       one engine step (host wall clock);
                                       the phase spans below tile it
``serve.expire``            span       recovery flag, deadline eviction,
                                       overload shedding
``serve.admit.probe``       span       head-of-queue peek + block
                                       feasibility probe, once per turn
                                       of the admission loop
``serve.admit``             span       one admission, whole but for the
                                       landing of a first token that
                                       comes behind the step's tick
                                       (under the request's trace id)
``serve.admit.claim``       span       prefix match, block allocation,
                                       slot table mapping
``serve.prefill``           span       all chunks of one admission, and
                                       the token fetch where it is made
                                       at once (``slot``, ``prompt``,
                                       ``shared``, ``chunks`` attrs)
``serve.prefill.stage``     span       per chunk: host staging of the
                                       ids and scalar arguments
``serve.prefill.dispatch``  span       per chunk: the guarded dispatch
                                       of ``prefill_chunk``
``serve.prefill.fetch``     span       the blocking fetch of the token
                                       the last chunk picked: after the
                                       step's ``serve.decode.dispatch``
                                       and outside ``serve.admit``
                                       (under the request's trace id),
                                       or at once inside
                                       ``serve.prefill`` where the step
                                       dispatches no plain tick or the
                                       token ends the request by length
``serve.admit.finish``      span       prefix registration, slot
                                       activation; delivery and
                                       callback of a first token that
                                       landed at once
``serve.first_tokens_behind_tick``  counter  one prefill's token fetched
                                       with the decode tick that reads
                                       it already dispatched.
                                       ``snapshot()`` keeps the total,
                                       beside ``admitted`` and the
                                       re-prefills' counters
``serve.grow``              span       decode-time block-table growth
                                       (and any preemption it triggers)
``serve.decode``            span       the dispatch of one decode tick
``serve.decode.dispatch``   span       the guarded dispatch of
                                       ``decode_paged``
``serve.decode.fetch``      span       a landing's blocking token fetch:
                                       of the tick dispatched a step
                                       EARLIER where ticks run ahead, so
                                       it opens after the next tick's
                                       ``serve.decode.dispatch`` closed
``serve.deliver``           span       the per-slot loop after a tick's
                                       fetch: delivery, metrics, flight
                                       notes, ``on_token`` callbacks,
                                       finalize; the same for one first
                                       token landed behind the tick
``serve.decode_ticks``      counter    one dispatch of ``decode_paged``
                                       (``ahead`` attr: another tick was
                                       in flight, so this one's launch
                                       and the other's landing ran
                                       beside a busy chip).
                                       ``snapshot()`` keeps
                                       ``decode_ticks`` and
                                       ``decode_ticks_ahead``; host-known
``serve.step.tail``         span       spill settle, per-step gauges,
                                       the tick EWMA
``serve.verify``            span       one speculative verify round
                                       (draft propose-k + target
                                       verify in ONE dispatch + fetch;
                                       ``k`` attr)
``serve.spec_proposed``     counter    draft tokens proposed this round
                                       (k per active slot)
``serve.spec_accepted``     counter    proposals the target's own
                                       greedy picks confirmed
``serve.spec_fallbacks``    counter    verify rounds that fell back to
                                       plain decode (``serve.verify``
                                       fault past retries)
``serve.accept_rate``       histogram  per-(slot, round) accepted / k
``serve.prefill_chunk_rows``  counter  prompt tokens one dispatch of
                                       ``prefill_chunk`` prefilled: the
                                       filled rows of its fixed (1, C)
                                       shape, not counting pad rows or
                                       tokens it only recomputed.
                                       ``snapshot()`` keeps the total
                                       and ``prefill_chunks``, the
                                       number of dispatches: rows per
                                       chunk is how full a chunk runs
``serve.decode_kv_blocks_live``  counter  KV blocks one decode tick's
                                       attention has to read: over the
                                       active slots, ``pos //
                                       block_size + 1`` (host-known)
``serve.decode_kv_blocks_view``  counter  blocks a dense view of the
                                       arena spans that tick:
                                       ``num_slots x max_blocks``.
                                       live / view is the share of the
                                       view a tick reads where decode
                                       reads blocks through the table
                                       (``ops/paged_attention.py``);
                                       ``snapshot()`` keeps both totals
``serve.moe_assignments``   counter    (token, expert) pairs one prefill
                                       chunk or decode tick of a
                                       mixture-of-experts model routes:
                                       its valid tokens x top-k, known
                                       on the host (no device read);
                                       never emitted for a dense model.
                                       ``snapshot()`` keeps the total
                                       and ``moe_dispatches``, the
                                       number of such dispatches
``serve.cca_state_resumes`` counter    one admission of a model with side
                                       state beside its KV blocks
                                       (serve/slots.py) that started
                                       from a shared block's tail;
                                       ``snapshot()`` keeps the total
``serve.cca_tail_blocks``   gauge      keyed blocks resident then, each
                                       holding a tail to resume from
``serve.state_snapshot_hits`` counter  one admission of a model whose
                                       state lives in snapshots
                                       (serve/slots.py) that entered
                                       from one
``serve.state_snapshot_writes`` counter  one admission that left the
                                       state after its last shared
                                       block as a new snapshot
``serve.state_snapshot_evictions`` counter  a snapshot dropped, least
                                       recently used first, for one
                                       being written
``serve.prefix_tokens_recomputed`` counter  rows between the snapshot
                                       an admission entered from and
                                       the end of its shared blocks:
                                       prefilled again, their blocks
                                       not rewritten
``serve.ssm_state_bytes``   counter    bytes of per-slot recurrent
                                       state one decode tick's updates
                                       read and write (each running
                                       slot's, once each way); host-
                                       known.  ``snapshot()`` keeps the
                                       totals of all five
``serve.token``             counter    one token delivered to a request
                                       (prefill first token, decode
                                       tick, recovery/preemption replay
                                       — tokens/s is derivable from the
                                       trace by counting these)
``serve.exposed_ms.finish``  histogram  one Python stretch between two
``serve.exposed_ms.admit``             of the engine's runtime calls
``serve.exposed_ms.other``             under an EMPTY device queue, by
                                       what emptied it
                                       (:class:`HostAccount`; ``cause``,
                                       ``chunks``, ``by`` attrs)
``serve.covered_ms``        histogram  per turn whose tick ran ahead:
                                       its Python time beside a busy
                                       chip
``serve.dispatch_ms``       histogram  per turn whose tick ran ahead:
                                       its time inside dispatch calls,
                                       which is the runtime's and no
                                       Python of the engine's
``serve.turn_ms``           histogram  wall of one turn of the step loop
                                       (``step()``'s entry to its next);
                                       ``serve.turn_ms.admitting`` of
                                       one that admitted a request
``serve.tick_ahead``        histogram  1.0 / 0.0 a decode tick: ahead of
                                       the tick before or not
``serve.turn_gc_ms``        histogram  per turn with one: collector
                                       pauses that fell in it, ms
``serve.slow_turn_ms``      histogram  a turn whose Python time passed
                                       50 ms: the excess.  Its story is
                                       the ``serve.slow_turn`` gauge,
                                       a flight-ring note and
                                       ``snapshot()["host"]
                                       ["slow_turns"]``
``serve.ttft_ms``           histogram  submit → first token
``serve.token_ms``          histogram  per generated token, decode path:
                                       the time between two landings
                                       (``on_token``)
==========================  =========  ==================================

Counters/gauges cost one attribute check when no sink is configured;
a span costs about a microsecond (it is always a
``jax.profiler.TraceAnnotation``, inert outside a profiler session —
inside one the spans above land on the stepping thread's line of the
trace, on the device ops' clock, which is how the benchmark's
``idle_unattributed.serve`` / ``idle_engine_python.serve`` name the
phase the chip was waiting on).  Code added to ``step()`` goes inside
one of the phase spans, or under a new ``serve.*`` one; its time is
accounted either way, by :class:`HostAccount`, which knows only the
runtime calls around it.
Latency aggregation is PER ENGINE: each ServeMetrics owns its own
histogram state (``snapshot()`` reads it), so two engines in one
process never reset or pollute each other's percentiles; the emitted
``serve.ttft_ms``/``serve.token_ms`` sink lines keep the documented
names (the global ``events.histogram_summary`` view then spans every
engine — by design for a whole-process dashboard).

Trace attribution (ISSUE 11): the engine activates the request's
``obs.trace`` context around each per-request section, so every line
above that is about ONE request carries its trace id — and the same
events are noted into the engine's :class:`~singa_tpu.obs.flight.
FlightRecorder` ring (pass ``flight=``), which is what an incident
dump's timeline is made of.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ..obs import events
from ..obs import flight as obs_flight
# per-engine aggregation state reuses the events-layer histogram
# implementation (exact totals + bounded deterministic sample ring)
from ..obs.events import _Hist

__all__ = ["ServeMetrics", "HostAccount"]

#: a turn of the step loop whose Python time (its wall outside runtime
#: calls) passes this is a slow turn, and its story is kept.  Python
#: stretches are 1-5 ms in every cell of the benchmark (PERF.md section
#: 5) and a turn is 9-14 ms in all, so ten times the longest stretch is
#: far from any turn that went as usual and well under the 110-145 ms
#: stalls the list exists to explain.  A constant, not a knob.
_SLOW_TURN_MS = 50.0
#: slow turns kept (the flight ring is washed out by per-token notes in
#: a few ticks, hence a list of its own)
_SLOW_TURNS_KEPT = 32

_CAUSES = ("finish", "admit", "other")


class _Turn:
    """One turn of the step loop: ``step()``'s entry to its next."""

    __slots__ = ("t0", "cpu0", "gc0", "sizes", "calls", "in_dispatch",
                 "in_fetch", "covered", "chunks", "admitting", "ahead",
                 "longest", "phase", "stretches")

    def __init__(self, t0: float, cpu0: float, gc0: float, sizes):
        self.t0, self.cpu0, self.gc0, self.sizes = t0, cpu0, gc0, sizes
        self.calls = 0              # runtime calls entered
        self.in_dispatch = self.in_fetch = 0.0  # seconds inside them
        self.covered = 0.0          # Python seconds beside a busy chip
        self.chunks = 0
        self.admitting = False
        self.ahead: Optional[bool] = None   # its decode tick's, if any
        self.longest = 0.0          # the longest Python segment, and the
        self.phase = ("", "")       # boundaries it lay between
        # exposed stretches that a runtime call of this turn closed:
        # (cause, seconds, the caller's seconds of them, closed by)
        self.stretches: List[Tuple[str, float, float, str]] = []


class HostAccount:
    """What the host did between the engine's runtime calls.

    ``ServeEngine`` makes two kinds of runtime call, a dispatch of a
    compiled program and a blocking fetch of one's result, and tells
    this account of the entry (:meth:`call_in`) and the return
    (:meth:`call_out`) of each, and of the entry and return of
    ``step()``.  Everything between a return and the next entry is a
    Python STRETCH, whatever code ran in it and across the end of a
    step and the caller's code into the next: **exposed** where the
    call that returned left nothing on the device (its ``cause`` says
    what emptied the queue), **covered** otherwise.  A TURN is one
    entry of ``step()`` to the next.  So code added to the engine
    between two runtime calls is accounted with nothing added to it.

    An engine with nothing queued or running when a step returns is
    idle: the turn ends there and the clock stops, so what an arrival
    waited for is nobody's Python; a driver that takes the engine over
    starts the clock anew (:meth:`rest`).  A turn that made no runtime call,
    or in which a program compiled, is in no total and no histogram,
    nor is a stretch that reaches into one.  Observations are made
    once a turn, at the next step's entry.

    One stepping thread: the account takes no lock."""

    def __init__(self, flight: Optional[obs_flight.FlightRecorder] = None):
        self.flight = flight
        self.exposed_s = dict.fromkeys(_CAUSES, 0.0)
        self.exposed_n = dict.fromkeys(_CAUSES, 0)
        self.exposed_caller_s = 0.0
        self.exposed_by_decode = 0
        self.covered_s = 0.0
        self.dispatch_s = 0.0       # inside dispatch calls: the runtime's
        self.turn_s = 0.0
        self.turns = 0
        self.turns_admitting = 0
        self.gc_ms = 0.0
        self.slow_turns_total = 0
        self.slow_turn_ms = 0.0
        self.slow_turn_cpu_ms = 0.0
        self.slow_turns: deque = deque(maxlen=_SLOW_TURNS_KEPT)
        self._turn: Optional[_Turn] = None
        self._t: Optional[float] = None     # the last boundary, and
        self._at = ""                       # its name
        self._busy = False                  # inside a runtime call,
        self._fetching = False              # a blocking fetch
        # the Python stretch now running: since when (None: the clock
        # is stopped), what emptied the queue before it (None: covered),
        # the caller's seconds of it, when step() last returned in it,
        # and whether it reaches into a turn that is left out
        self._s0: Optional[float] = None
        self._cause: Optional[str] = None
        self._caller = 0.0
        self._ret: Optional[float] = None
        self._tainted = False

    def _mark(self, name: str, now: float) -> None:
        """A boundary: the segment since the last one goes to the open
        turn, as time in a call or as Python of the running stretch."""
        turn = self._turn
        if turn is not None and self._t is not None:
            seg = now - self._t
            if self._busy:
                if self._fetching:
                    turn.in_fetch += seg
                else:
                    turn.in_dispatch += seg
            else:
                if self._s0 is not None and self._cause is None:
                    turn.covered += seg
                if seg > turn.longest:
                    turn.longest, turn.phase = seg, (self._at, name)
        self._t, self._at = now, name

    def _leave_caller(self, now: float) -> None:
        if self._ret is not None:
            if self._cause is not None:
                self._caller += now - self._ret
            self._ret = None

    def call_in(self, name: str) -> None:
        """The engine is about to make the runtime call ``name``
        (``prefill.dispatch``, ``decode.fetch``, ...)."""
        now = time.perf_counter()
        self._mark(name, now)
        self._leave_caller(now)
        turn = self._turn
        if turn is not None:
            turn.calls += 1
            if name == "prefill.dispatch":
                turn.chunks += 1
            if self._s0 is not None and self._cause is not None \
                    and not self._tainted:
                turn.stretches.append(
                    (self._cause, now - self._s0, self._caller, name))
        self._s0, self._busy = None, True
        self._fetching = name.endswith("fetch")

    def call_out(self, name: str, cause: Optional[str] = None) -> None:
        """The runtime call ``name`` returned (or raised).  ``cause``:
        why nothing is left on the device now (``finish``: the landing
        of a tick that ended a request by length, ``admit``: an
        admission's token fetch with no tick dispatched behind the
        chunk, ``other``), or None while something the engine
        dispatched after it is still to be fetched."""
        now = time.perf_counter()
        self._mark(name, now)
        self._busy = False
        self._s0, self._cause = now, cause
        self._caller, self._ret, self._tainted = 0.0, None, False
        if name == "prefill.fetch" and self._turn is not None:
            self._turn.admitting = True

    def tick(self, ahead: bool) -> None:
        """This turn dispatched its decode tick, ``ahead`` of the one
        before or not."""
        if self._turn is not None:
            self._turn.ahead = ahead

    def step_in(self, sizes) -> None:
        """``step()`` was entered: the turn before ends, one begins.
        ``sizes``: the jit caches' entry counts, by which a turn that
        compiled is told."""
        now = time.perf_counter()
        self._mark("step", now)
        self._leave_caller(now)
        cpu, gc_ms = time.thread_time(), events.gc_pause_ms()
        self._end_turn(now, cpu, gc_ms, sizes)
        self._turn = _Turn(now, cpu, gc_ms, sizes)

    def step_out(self, idle_sizes=None) -> None:
        """``step()`` returns.  ``idle_sizes``: the jit caches' entry
        counts where nothing is queued or running any more, and the
        turn ends here and the clock stops; None otherwise."""
        now = time.perf_counter()
        self._mark("return", now)
        self._ret = now
        if idle_sizes is not None:
            self._end_turn(now, time.thread_time(), events.gc_pause_ms(),
                           idle_sizes)
            self.rest()

    @property
    def resting(self) -> bool:
        """The clock is stopped: no turn is open and no stretch runs."""
        return self._t is None

    def rest(self) -> None:
        """Stop the clock: the open turn and the running stretch are
        dropped.  For an engine gone idle, and for a driver that takes
        the engine over (``run_until_idle``, hence ``drain`` and
        ``close``): whatever its caller did since the last step, taking
        a profile apart or doing sums over a window, belongs to no loop
        and would read as one turn of seconds."""
        self._turn = self._t = self._s0 = self._ret = None

    def _end_turn(self, now: float, cpu: float, gc_now: float,
                  sizes) -> None:
        turn = self._turn
        if turn is None:
            return
        if not turn.calls or sizes != turn.sizes:
            self._tainted = True
            return
        wall = now - turn.t0
        gc_ms = gc_now - turn.gc0
        self.turns += 1
        self.turn_s += wall
        self.covered_s += turn.covered
        self.dispatch_s += turn.in_dispatch
        self.gc_ms += gc_ms
        for cause, sec, caller, by in turn.stretches:
            self.exposed_s[cause] += sec
            self.exposed_n[cause] += 1
            self.exposed_caller_s += caller
            self.exposed_by_decode += by == "decode.dispatch"
            events.histogram("serve.exposed_ms." + cause, sec * 1e3,
                             cause=cause, chunks=turn.chunks, by=by)
        events.histogram("serve.turn_ms", wall * 1e3, chunks=turn.chunks)
        if turn.admitting:
            self.turns_admitting += 1
            events.histogram("serve.turn_ms.admitting", wall * 1e3,
                             chunks=turn.chunks)
        if turn.ahead is not None:
            events.histogram("serve.tick_ahead", float(turn.ahead))
            if turn.ahead:
                events.histogram("serve.covered_ms", turn.covered * 1e3,
                                 chunks=turn.chunks)
                events.histogram("serve.dispatch_ms",
                                 turn.in_dispatch * 1e3, chunks=turn.chunks)
        if gc_ms:
            events.histogram("serve.turn_gc_ms", gc_ms)
        python_ms = (wall - turn.in_dispatch - turn.in_fetch) * 1e3
        if python_ms > _SLOW_TURN_MS:
            self._slow_turn(turn, wall * 1e3, python_ms,
                            (cpu - turn.cpu0) * 1e3, gc_ms)

    def _slow_turn(self, turn: _Turn, wall_ms: float, python_ms: float,
                   cpu_ms: float, gc_ms: float) -> None:
        story = {"t": time.time(),  # singalint: disable=SGL005 read beside the sink's event timestamps, which are wall-clock; every duration here is from the monotonic clocks
                 "wall_ms": wall_ms, "python_ms": python_ms,
                 "cpu_ms": cpu_ms, "gc_ms": gc_ms, "chunks": turn.chunks,
                 "phase": ">".join(turn.phase)}
        self.slow_turns.append(story)
        self.slow_turns_total += 1
        self.slow_turn_ms += python_ms - _SLOW_TURN_MS
        self.slow_turn_cpu_ms += cpu_ms
        events.histogram("serve.slow_turn_ms", python_ms - _SLOW_TURN_MS)
        attrs = {k: round(v, 3) if isinstance(v, float) else v
                 for k, v in story.items() if k != "t"}
        events.gauge("serve.slow_turn", attrs["python_ms"], **attrs)
        if self.flight is not None:
            self.flight.note("gauge", "serve.slow_turn", **attrs)

    def snapshot(self) -> Dict[str, Any]:
        return {"exposed_s": dict(self.exposed_s),
                "exposed_n": dict(self.exposed_n),
                "exposed_caller_s": self.exposed_caller_s,
                "exposed_by_decode": self.exposed_by_decode,
                "covered_s": self.covered_s,
                "dispatch_s": self.dispatch_s,
                "turn_s": self.turn_s, "turns": self.turns,
                "turns_admitting": self.turns_admitting,
                "gc_ms": self.gc_ms,
                "slow_turns": list(self.slow_turns),
                "slow_turns_total": self.slow_turns_total,
                "slow_turn_ms": self.slow_turn_ms,
                "slow_turn_cpu_ms": self.slow_turn_cpu_ms}


class ServeMetrics:
    """Thin per-engine facade: exact local totals (for snapshots/tests)
    plus pass-through emission to the shared obs sink and (when given)
    the engine's flight-recorder ring."""

    def __init__(self, flight: Optional[obs_flight.FlightRecorder] = None):
        self.flight = flight
        # the host's own time between runtime calls (HostAccount)
        self.host = HostAccount(flight)
        self.submitted = 0
        self.admitted = 0
        self.rejected = 0
        self.evicted: Dict[str, int] = {}
        self.retries: Dict[str, int] = {}
        self.quarantined = 0
        self.recoveries = 0
        self.preempted = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.steps = 0
        # KV memory hierarchy (ISSUE 17): spill-tier pressure counters
        self.spilled_blocks = 0
        self.prefetch_hits = 0
        self.prefetch_wait_ms = 0.0
        # speculative decoding (ISSUE 13): per-(slot, round) accounting
        # for the accept rate and the tokens-per-dispatch headline —
        # slot_dispatches counts per-slot participations in a decode OR
        # verify dispatch (a plain tick is the 1-token case), so
        # tokens_per_dispatch = slot_dispatch_tokens / slot_dispatches
        # is comparable across spec and plain engines
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_fallbacks = 0
        self.slot_dispatches = 0
        self.slot_dispatch_tokens = 0
        # prefill dispatches, and the prompt tokens they prefilled
        self.prefill_chunks = 0
        self.prefill_chunk_rows = 0
        # decode ticks: the KV blocks their attention had to read, and
        # the blocks a dense view of every slot's table row spans
        self.decode_kv_blocks_live = 0
        self.decode_kv_blocks_view = 0
        # dispatches of the decode program, and those made while the
        # tick before was still in flight (serve/engine.py)
        self.decode_ticks = 0
        self.decode_ticks_ahead = 0
        # tokens of a prefill (an admission's first, a re-prefill's
        # next) fetched after the step's decode tick was dispatched
        self.first_tokens_behind_tick = 0
        # mixture-of-experts models: dispatches that ran the router and
        # the (token, expert) pairs they routed; both 0 for a dense model
        self.moe_dispatches = 0
        self.moe_assignments = 0
        # models with side state beside the KV blocks (CCA): admissions
        # that started from a shared block's tail, and the blocks whose
        # tail can be resumed from (a level, not a total); 0 otherwise
        self.cca_state_resumes = 0
        self.cca_tail_blocks = 0
        # models whose state lives per slot and in snapshots (a
        # state-space layer's): serve/slots.py; 0 otherwise
        self.state_snapshot_hits = 0
        self.state_snapshot_writes = 0
        self.state_snapshot_evictions = 0
        self.prefix_tokens_recomputed = 0
        self.ssm_state_bytes = 0
        self._ttft = _Hist()
        self._token = _Hist()

    def _note(self, kind: str, name: str, **attrs) -> None:
        """Mirror one emission into the engine's flight ring (in-memory
        only; the active trace id is stamped by the recorder)."""
        if self.flight is not None:
            self.flight.note(kind, name, **attrs)

    # -- request lifecycle ------------------------------------------------
    def on_submit(self) -> None:
        self.submitted += 1
        events.counter("serve.submitted", 1)
        self._note("counter", "serve.submitted")

    def on_reject(self) -> None:
        self.rejected += 1
        events.counter("serve.rejected", 1)
        self._note("counter", "serve.rejected")

    def on_admit(self) -> None:
        self.admitted += 1
        events.counter("serve.admitted", 1)
        self._note("counter", "serve.admitted")

    def on_evict(self, reason: str) -> None:
        self.evicted[reason] = self.evicted.get(reason, 0) + 1
        events.counter("serve.evicted", 1, reason=reason)
        self._note("counter", "serve.evicted", reason=reason)

    # -- resilience (ISSUE 4) ---------------------------------------------
    def on_retry(self, site: str) -> None:
        self.retries[site] = self.retries.get(site, 0) + 1
        events.counter("serve.retries", 1, site=site)
        self._note("counter", "serve.retries", site=site)

    def on_quarantine(self) -> None:
        self.quarantined += 1
        events.counter("serve.quarantined", 1)
        self._note("counter", "serve.quarantined")

    def on_recover(self, inflight: int) -> None:
        self.recoveries += 1
        events.counter("serve.recoveries", 1, inflight=inflight)
        self._note("counter", "serve.recoveries", inflight=inflight)

    def on_preempt(self) -> None:
        self.preempted += 1
        events.counter("serve.preempted", 1)
        self._note("counter", "serve.preempted")

    # -- paged arena / prefix cache (ISSUE 6) ------------------------------
    def on_prefix_hit(self, tokens: int) -> None:
        self.prefix_hits += 1
        self.prefix_hit_tokens += tokens
        events.counter("serve.prefix_hits", 1)
        events.counter("serve.prefix_hit_tokens", tokens)
        self._note("counter", "serve.prefix_hits", tokens=tokens)

    # -- KV memory hierarchy / spill tier (ISSUE 17) -----------------------
    def on_spill(self, blocks: int) -> None:
        """``blocks`` evicted prefix blocks spilled to host RAM instead
        of dying (their next prefix hit restores them copy-wise)."""
        self.spilled_blocks += blocks
        events.counter("serve.spilled_blocks", blocks)
        self._note("counter", "serve.spilled_blocks", blocks=blocks)

    def on_prefetch(self, blocks: int, wait_ms: float) -> None:
        """``blocks`` spilled block(s) restored on one prefix hit (the
        pool fires this once per restored block); ``wait_ms`` is the
        host-side restore orchestration time (the device copy itself
        is async-dispatched)."""
        self.prefetch_hits += blocks
        self.prefetch_wait_ms += wait_ms
        events.counter("serve.prefetch_hits", 1, blocks=blocks)
        events.histogram("serve.prefetch_wait_ms", wait_ms)
        self._note("counter", "serve.prefetch_hits", blocks=blocks,
                   wait_ms=round(wait_ms, 3))

    # -- speculative decoding (ISSUE 13) -----------------------------------
    def on_spec_round(self, proposed: int, accepted: int) -> None:
        """One (slot, verify round): ``proposed`` = k draft tokens,
        ``accepted`` = how many of them the target's own greedy picks
        confirmed (the round still delivers accepted + 1 tokens — the
        correction/bonus pick is the target's, not the draft's)."""
        self.spec_rounds += 1
        self.spec_proposed += proposed
        self.spec_accepted += accepted
        rate = accepted / proposed if proposed else 0.0
        events.counter("serve.spec_proposed", proposed)
        events.counter("serve.spec_accepted", accepted)
        events.histogram("serve.accept_rate", rate)
        self._note("counter", "serve.spec_accepted", accepted=accepted,
                   proposed=proposed)

    def on_spec_fallback(self) -> None:
        """A verify round died past retries and this tick ran plain
        decode instead — stream unchanged, accept rate pays later."""
        self.spec_fallbacks += 1
        events.counter("serve.spec_fallbacks", 1)
        self._note("counter", "serve.spec_fallbacks")

    def on_slot_dispatch(self, tokens: int) -> None:
        """One slot's share of one decode/verify dispatch, yielding
        ``tokens`` delivered tokens — the denominator/numerator pair of
        the ``tokens_per_dispatch`` headline."""
        self.slot_dispatches += 1
        self.slot_dispatch_tokens += tokens

    def on_prefill_chunk(self, rows: int) -> None:
        """One dispatch of ``prefill_chunk`` that prefilled ``rows``
        prompt tokens."""
        self.prefill_chunks += 1
        self.prefill_chunk_rows += rows
        events.counter("serve.prefill_chunk_rows", rows)

    def on_decode_kv(self, live: int, view: int) -> None:
        """One decode tick whose active slots hold ``live`` KV blocks
        up to the positions they write, of the ``view`` blocks that
        every slot's whole table row spans."""
        self.decode_kv_blocks_live += live
        self.decode_kv_blocks_view += view
        events.counter("serve.decode_kv_blocks_live", live)
        events.counter("serve.decode_kv_blocks_view", view)

    def on_decode_tick(self, ahead: bool) -> None:
        """One dispatch of the decode program; ``ahead``: the tick
        before it had not landed yet."""
        self.decode_ticks += 1
        self.decode_ticks_ahead += ahead
        self.host.tick(ahead)
        events.counter("serve.decode_ticks", 1, ahead=ahead)

    def on_first_token_behind_tick(self) -> None:
        """One prefill's token fetched with the decode tick that reads
        it already dispatched: the chip had work under the fetch."""
        self.first_tokens_behind_tick += 1
        events.counter("serve.first_tokens_behind_tick", 1)

    def on_moe_dispatch(self, assignments: int) -> None:
        """One prefill chunk or decode tick of a mixture-of-experts
        model: ``assignments`` = its valid tokens x top-k."""
        self.moe_dispatches += 1
        self.moe_assignments += assignments
        events.counter("serve.moe_assignments", assignments)

    def on_state_resume(self, tail_blocks: int) -> None:
        """One admission of a model with side state beside its KV
        blocks that started from the tail kept with its last shared
        block; `tail_blocks` keyed blocks now hold a tail a later
        request can start from."""
        self.cca_state_resumes += 1
        self.cca_tail_blocks = tail_blocks
        events.counter("serve.cca_state_resumes", 1)
        events.gauge("serve.cca_tail_blocks", tail_blocks)

    def on_snapshot_hit(self) -> None:
        """One admission that entered from a state snapshot."""
        self.state_snapshot_hits += 1
        events.counter("serve.state_snapshot_hits", 1)

    def on_snapshot_miss(self, recomputed: int, evicted: bool) -> None:
        """One admission whose shared blocks run ``recomputed`` rows past
        the snapshot it entered from (or past position 0): it prefills
        them again; ``evicted``: a snapshot was dropped for the one it
        will leave there."""
        self.prefix_tokens_recomputed += recomputed
        events.counter("serve.prefix_tokens_recomputed", recomputed)
        if evicted:
            self.state_snapshot_evictions += 1
            events.counter("serve.state_snapshot_evictions", 1)

    def on_snapshot_write(self) -> None:
        """One admission that left a new state snapshot."""
        self.state_snapshot_writes += 1
        events.counter("serve.state_snapshot_writes", 1)

    def on_ssm_state(self, nbytes: int) -> None:
        """One decode tick whose state updates read and write ``nbytes``
        of per-slot recurrent state."""
        self.ssm_state_bytes += nbytes
        events.counter("serve.ssm_state_bytes", nbytes)

    @property
    def accept_rate(self) -> Optional[float]:
        """Overall accepted / proposed (None before any verify round)."""
        if not self.spec_proposed:
            return None
        return self.spec_accepted / self.spec_proposed

    @property
    def tokens_per_dispatch(self) -> Optional[float]:
        """Delivered tokens per per-slot dispatch participation (None
        before any decode/verify tick; exactly 1.0 for a plain
        engine)."""
        if not self.slot_dispatches:
            return None
        return self.slot_dispatch_tokens / self.slot_dispatches

    # -- latency / delivery ------------------------------------------------
    def on_first_token(self, ttft_s: float) -> None:
        self._ttft.observe(ttft_s * 1e3)
        events.histogram("serve.ttft_ms", ttft_s * 1e3)
        self._note("hist", "serve.ttft_ms", value=ttft_s * 1e3)

    def on_token(self, latency_s: float) -> None:
        """One token of a decode tick, ``latency_s`` after the landing
        before its own (after its dispatch, for a tick dispatched with
        nothing in flight): with ticks running ahead that is the pace
        tokens reach a request at, not a dispatch's round trip.  A
        verify round's: its dispatch to its fetch, over the tokens it
        yielded the slot."""
        self._token.observe(latency_s * 1e3)
        events.histogram("serve.token_ms", latency_s * 1e3)

    def on_deliver(self, rid: int, n: int) -> None:
        """One token handed to a request (any path: prefill first
        token, decode tick, recovery/preemption replay) — the
        trace-countable delivery event tokens/s derives from."""
        events.counter("serve.token", 1, rid=rid, n=n)
        self._note("counter", "serve.token", rid=rid, n=n)

    # -- per-step levels ---------------------------------------------------
    def on_step(self, queue_depth: int, active_slots: int,
                blocks_in_use: int = 0,
                blocks_in_use_bytes: int = 0) -> None:
        self.steps += 1
        events.gauge("serve.queue_depth", queue_depth)
        events.gauge("serve.active_slots", active_slots)
        events.gauge("serve.blocks_in_use", blocks_in_use)
        events.gauge("serve.blocks_in_use_bytes", blocks_in_use_bytes)
        self._note("gauge", "serve.step", queue_depth=queue_depth,
                   active_slots=active_slots,
                   blocks_in_use=blocks_in_use,
                   blocks_in_use_bytes=blocks_in_use_bytes)

    def snapshot(self) -> Dict[str, Any]:
        """Exact totals + THIS engine's latency summaries (None until
        observed)."""
        return {
            "submitted": self.submitted, "admitted": self.admitted,
            "rejected": self.rejected, "evicted": dict(self.evicted),
            "retries": dict(self.retries),
            "quarantined": self.quarantined,
            "recoveries": self.recoveries,
            "preempted": self.preempted,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "steps": self.steps,
            "spilled_blocks": self.spilled_blocks,
            "prefetch_hits": self.prefetch_hits,
            "prefetch_wait_ms": self.prefetch_wait_ms,
            "spec_rounds": self.spec_rounds,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "spec_fallbacks": self.spec_fallbacks,
            "slot_dispatches": self.slot_dispatches,
            "slot_dispatch_tokens": self.slot_dispatch_tokens,
            "prefill_chunks": self.prefill_chunks,
            "prefill_chunk_rows": self.prefill_chunk_rows,
            "decode_kv_blocks_live": self.decode_kv_blocks_live,
            "decode_kv_blocks_view": self.decode_kv_blocks_view,
            "decode_ticks": self.decode_ticks,
            "decode_ticks_ahead": self.decode_ticks_ahead,
            "first_tokens_behind_tick": self.first_tokens_behind_tick,
            "moe_dispatches": self.moe_dispatches,
            "moe_assignments": self.moe_assignments,
            "cca_state_resumes": self.cca_state_resumes,
            "cca_tail_blocks": self.cca_tail_blocks,
            "state_snapshot_hits": self.state_snapshot_hits,
            "state_snapshot_writes": self.state_snapshot_writes,
            "state_snapshot_evictions": self.state_snapshot_evictions,
            "prefix_tokens_recomputed": self.prefix_tokens_recomputed,
            "ssm_state_bytes": self.ssm_state_bytes,
            "accept_rate": self.accept_rate,
            "tokens_per_dispatch": self.tokens_per_dispatch,
            "ttft_ms": self._ttft.summary(),
            "token_ms": self._token.summary(),
            "host": self.host.snapshot(),
        }
